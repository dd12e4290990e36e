"""Ahead-of-time compiles of the Pallas kernels for a described TPU v5e.

Nothing runs: each test lowers one kernel at the main path's shapes (leaf
64, Chebyshev rank 36, nv in {1, 16}) for a v5e chip that is described,
not attached, and asserts that Mosaic accepted it (a ``tpu_custom_call``
in the compiled program).  Interpret-mode tests cannot see what the chip's
compiler refuses: unaligned blocks, contractions Mosaic cannot lower.
Two more compile the one-chip V-cycle and the fractional operator's
grid<->tree permutations and assert that the TPU program holds no gather.
"""
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels import ops

LEAF, RANK = 64, 36


@pytest.fixture(scope="module")
def one_chip():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler here: nothing to compile for
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # compiles for a described chip cannot be read back from the
    # persistent cache, so keep them out of it
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


def _compile(fn, *shapes, sharding):
    args = [jax.ShapeDtypeStruct(s, d, sharding=sharding) for s, d in shapes]
    text = jax.jit(fn).lower(*args).compile().as_text()
    assert "tpu_custom_call" in text
    return text


F32, I32 = jnp.float32, jnp.int32


@pytest.mark.parametrize("m", [LEAF, RANK])
@pytest.mark.parametrize("nv", [1, 16])
def test_batched_gemm_compiles(one_chip, m, nv):
    _compile(ops.batched_gemm, ((512, m, RANK), F32), ((512, RANK, nv), F32),
             sharding=one_chip)


@pytest.mark.parametrize("nv", [1, 16])
def test_coupling_mv_compiles(one_chip, nv):
    rows, maxb = 256, 27
    nb = rows * 20

    def fn(s, x, blk, col, cnt):
        return ops.coupling_mv(s, x, blk, col, cnt, maxb=maxb)

    _compile(fn, ((nb, RANK, RANK), F32), ((rows, RANK, nv), F32),
             ((rows * maxb,), I32), ((rows * maxb,), I32), ((rows,), I32),
             sharding=one_chip)


def test_halo_pack_compiles(one_chip):
    _compile(ops.halo_pack, ((256, RANK, 1), F32), ((48,), I32),
             sharding=one_chip)


@pytest.mark.parametrize("n", [LEAF, 2 * RANK])
def test_batched_qr_compiles(one_chip, n):
    _compile(ops.batched_qr, ((256, n, RANK), F32), sharding=one_chip)


@pytest.mark.parametrize("n", [2 * RANK, RANK])
def test_batched_svd_compiles(one_chip, n):
    text = _compile(ops.batched_svd, ((128, n, RANK), F32),
                    sharding=one_chip)
    # the Jacobi kernel and its QR polish both stay compiled
    assert text.count("tpu_custom_call") >= 2


def test_vcycle_compiles_without_gather(one_chip):
    # the one-chip V-cycle on the n = 256 grid: its restriction must not
    # lower to element-by-element gathers (no Pallas kernel here)
    from repro.solvers import build_grid_mg, mg_precond_local
    n = 256
    rng = np.random.default_rng(0)
    mg, arrs = build_grid_mg(1.0 + rng.random((n, n), np.float32),
                             rng.random((n, n), np.float32), 0.5, 2.0 / n, n)
    spec = lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=one_chip)
    text = jax.jit(lambda a, r: mg_precond_local(mg, a, r)).lower(
        jax.tree.map(spec, arrs),
        jax.ShapeDtypeStruct((n * n,), F32, sharding=one_chip)
    ).compile().as_text()
    assert "gather(" not in text


@pytest.mark.parametrize("direction", [0, 1])
def test_grid_tree_maps_compile_without_gather(one_chip, direction):
    # the n = 256 operator's grid -> tree (0) and tree -> grid (1) maps:
    # one transpose each, not an element-by-element gather
    from repro.apps.fractional import grid_tree_maps, interior_grid
    from repro.core.clustering import build_cluster_tree
    n = 256
    perm = build_cluster_tree(interior_grid(n), 64).perm
    fn = grid_tree_maps(perm, np.argsort(perm))[direction]
    text = jax.jit(fn).lower(
        jax.ShapeDtypeStruct((n * n,), F32, sharding=one_chip)
    ).compile().as_text()
    assert "gather(" not in text and "copy(" in text
