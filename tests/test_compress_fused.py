"""Fused recompression pipeline invariants (DESIGN.md §5.5).

- the tol path runs the truncation upsweep's batched SVDs exactly once
- its rank picks coincide with the two-sweep reference implementation
- the fixed-rank path is one jitted program: no retrace on repeat calls,
  no host callbacks anywhere in its jaxpr
- orthogonalize handles structures with empty coupling levels
"""
import numpy as np
import pytest
import jax
import jax.numpy as jnp

import repro.core.compression as compression
from repro.core.clustering import regular_grid_points
from repro.core.construction import construct_h2
from repro.core.kernels_fn import exponential_kernel
from repro.core.matvec import h2_matvec
from repro.obs import counter
from repro.core.orthogonalize import orthogonalize
from repro.core.reconstruct import reconstruct_dense
from repro.core.structure import shape_of


def _setup(side=16, leaf=8, p=5, eta=0.9):
    pts = regular_grid_points(side, 2)
    kern = exponential_kernel(0.1)
    shape, data, tree, bs = construct_h2(pts, kern, leaf_size=leaf,
                                         cheb_p=p, eta=eta,
                                         dtype=jnp.float32)
    return shape, data


class TestSingleSweepTol:
    def test_upsweep_svds_run_exactly_once(self, monkeypatch):
        shape, data = _setup()
        calls = []
        orig = compression._batched_svd

        def counting(a, backend):
            calls.append(a.shape)
            return orig(a, backend)

        # route the per-level jitted steps through their eager bodies so
        # every SVD is a counted call regardless of jit-cache warmth
        monkeypatch.setattr(compression, "_leaf_factors_jit",
                            compression.truncation_leaf_factors)
        monkeypatch.setattr(compression, "_inner_factors_jit",
                            compression.truncation_inner_factors)
        monkeypatch.setattr(compression, "_batched_svd", counting)
        compression.compress(shape, data, tol=1e-3)
        # symmetric aliased operator: one leaf SVD + one per inner level
        assert len(calls) == shape.depth + 1, calls
        calls.clear()
        compression.compress(shape, data, tol=1e-3, legacy_two_sweep=True)
        legacy_calls = len(calls)
        assert legacy_calls > shape.depth + 1, legacy_calls

    @pytest.mark.parametrize("tol", [1e-1, 1e-2, 1e-3, 1e-4])
    def test_same_ranks_as_two_sweep(self, tol):
        shape, data = _setup(p=6)
        cs_new, cd_new = compression.compress(shape, data, tol=tol)
        cs_old, cd_old = compression.compress(shape, data, tol=tol,
                                              legacy_two_sweep=True)
        assert cs_new.ranks == cs_old.ranks, (cs_new.ranks, cs_old.ranks)
        a_new = np.asarray(reconstruct_dense(cs_new, cd_new))
        a_old = np.asarray(reconstruct_dense(cs_old, cd_old))
        scale = np.abs(a_old).max()
        np.testing.assert_allclose(a_new, a_old, atol=50 * tol * scale)

    @pytest.mark.parametrize("eta,leaf", [(0.7, 8), (1.2, 4)])
    def test_same_ranks_other_structures(self, eta, leaf):
        shape, data = _setup(side=16, leaf=leaf, p=4, eta=eta)
        for tol in (1e-2, 1e-3):
            cs_new, _ = compression.compress(shape, data, tol=tol)
            cs_old, _ = compression.compress(shape, data, tol=tol,
                                             legacy_two_sweep=True)
            assert cs_new.ranks == cs_old.ranks

    def test_aliased_weights_equivalent(self):
        """rv := ru for symmetric operators: same Gram, so the downstream
        SVDs see the same spectra (R is unique up to row signs)."""
        shape, data = _setup(p=4)
        s2, od = compression._orthogonalized(shape, data, "jnp",
                                             aliased=True)
        ru, rv_alias = compression.compression_weights(s2, od, "jnp",
                                                       aliased=True)
        _, rv_full = compression.compression_weights(s2, od, "jnp",
                                                     aliased=False)
        assert rv_alias[shape.depth] is ru[shape.depth]
        for l in range(shape.depth + 1):
            ga = np.einsum("nij,nik->njk", np.asarray(rv_alias[l]),
                           np.asarray(rv_alias[l]))
            gf = np.einsum("nij,nik->njk", np.asarray(rv_full[l]),
                           np.asarray(rv_full[l]))
            scale = max(np.abs(gf).max(), 1e-30)
            np.testing.assert_allclose(ga, gf, atol=1e-4 * scale)


from jaxpr_utils import walk_primitives as _walk_primitives  # noqa: E402


class TestFixedRankSingleDispatch:
    def test_no_retrace_on_repeat_calls(self):
        shape, data = _setup(p=4)
        tgt = tuple(min(6, k) for k in shape.ranks)
        base = counter("retrace/compress_fixed")
        cs1, cd1 = compression.compress(shape, data, target_ranks=tgt)
        cs2, cd2 = compression.compress(shape, data, target_ranks=tgt)
        assert counter("retrace/compress_fixed") == base + 1
        assert cs1.ranks == cs2.ranks
        np.testing.assert_array_equal(np.asarray(cd1.u_leaf),
                                      np.asarray(cd2.u_leaf))

    def test_pipeline_is_one_program_without_callbacks(self):
        """The whole orthogonalize->weights->truncate->project pipeline
        traces to a single closed jaxpr with no host round-trips."""
        shape, data = _setup(p=4)
        tgt = tuple(min(6, k) for k in shape.ranks)
        jaxpr = jax.make_jaxpr(
            lambda d: compression._compress_fixed(shape, d, tgt, "jnp",
                                                  False, True))(data)
        prims = _walk_primitives(jaxpr.jaxpr, [])
        assert not any("callback" in p for p in prims), set(prims)

    def test_assume_orthogonal_aliased_factors_one_tree(self):
        """Inside the jit the trees are distinct tracers; the static
        aliased flag must still dedupe the symmetric upsweep (regression:
        assume_orthogonal=True used to trace both sweeps — 2x the SVDs)."""
        shape, data = _setup(p=4)
        s2, od = compression._orthogonalized(shape, data, "jnp",
                                             aliased=True)
        tgt = tuple(min(6, k) for k in s2.ranks)
        jaxpr = jax.make_jaxpr(
            lambda d: compression._compress_fixed(s2, d, tgt, "jnp",
                                                  True, True))(od)
        n_svd = sum(1 for p in _walk_primitives(jaxpr.jaxpr, [])
                    if p == "svd")
        assert n_svd == shape.depth + 1, n_svd

    def test_matches_tol_path_at_picked_ranks(self):
        shape, data = _setup(p=5)
        cs_tol, cd_tol = compression.compress(shape, data, tol=1e-3)
        cs_fix, cd_fix = compression.compress(shape, data,
                                              target_ranks=cs_tol.ranks)
        assert cs_fix.ranks == cs_tol.ranks
        a_t = np.asarray(reconstruct_dense(cs_tol, cd_tol))
        a_f = np.asarray(reconstruct_dense(cs_fix, cd_fix))
        scale = np.abs(a_t).max()
        np.testing.assert_allclose(a_f, a_t, atol=1e-3 * scale)


class TestOrthogonalizeEmptyCouplingLevel:
    def test_empty_level_regression(self):
        """Structures always have coupling-free top levels; orthogonalize
        must pass them through (regression for the dead-branch cleanup)."""
        shape, data = _setup(p=4)
        assert 0 in shape.coupling_counts, shape.coupling_counts
        od = orthogonalize(shape, data)
        for l in range(shape.depth + 1):
            if shape.coupling_counts[l] == 0:
                assert od.s[l].shape[0] == 0
        s2 = shape_of(od, shape.leaf_size)
        x = np.random.default_rng(3).standard_normal(
            (shape.n, 2)).astype(np.float32)
        y0 = np.asarray(h2_matvec(shape, data, jnp.asarray(x)))
        y1 = np.asarray(h2_matvec(s2, od, jnp.asarray(x)))
        np.testing.assert_allclose(y0, y1, rtol=2e-3, atol=2e-3)
