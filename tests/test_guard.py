"""Numerical guard rails (repro/guard/, DESIGN.md §11) — tests.

Three pillars: operator certification (``validate_h2`` structural
invariants + ``certify_matvec`` stochastic error estimates), solver
breakdown detection (jit-compatible status codes in the Krylov carries),
and precision-escalation recovery (``run_with_guards`` ladders).  The
deterministic fault drills (``guard.drills``) run under the ``guard``
marker so CI gives them their own leg; everything else is fast-tier.

Guard-off compilation is held to a hard bar: ``guard=False`` (or the
global kill-switch) must produce a byte-identical jaxpr to the
pre-guard solver — the rails are free when disabled.
"""
import numpy as np
import pytest
import jax
import jax.numpy as jnp

from test_solvers import hyp, random_spd

from repro.guard import (Certificate, STATUS_BREAKDOWN,
                         STATUS_INDEFINITE, STATUS_NAN, STATUS_OK,
                         STATUS_STAGNATION, certify_h2, certify_matvec,
                         check_orthogonal, construct_h2_certified,
                         drill_corrupt_operator, drill_near_singular,
                         drill_rank_starved, fp64_scalars,
                         kernel_reference_apply, probe_block,
                         reset_guard_counters, run_with_guards,
                         status_name, validate_h2, worst_status)
from repro.obs import counter
from repro.solvers import block_cg, gmres, pcg, set_guards_enabled


@pytest.fixture(autouse=True)
def _clean_counters():
    reset_guard_counters()
    yield
    reset_guard_counters()


def _cheb_operator(side=16, leaf=16, p=4, eta=0.9):
    from repro.core.clustering import regular_grid_points
    from repro.core.construction import construct_h2
    from repro.core.kernels_fn import exponential_kernel
    pts = regular_grid_points(side, 2)
    kern = exponential_kernel(0.1)
    shape, data, tree, bs = construct_h2(pts, kern, leaf_size=leaf,
                                         cheb_p=p, eta=eta,
                                         dtype=jnp.float32)
    return pts, kern, shape, data, tree


# ---------------------------------------------------------------------------
# pillar 2: solver breakdown detection


class TestSolverStatus:
    def test_healthy_spd_is_ok(self):
        a = random_spd(24, 0)
        b = jnp.ones(24, jnp.float32)
        res = pcg(lambda x: a @ x, b, tol=1e-6, maxiter=100)
        assert bool(res.converged)
        assert worst_status(res.status) == STATUS_OK
        assert status_name(res.status) == "ok"

    def test_pcg_indefinite_trips(self):
        a, b = drill_near_singular(lam_min=-0.1, seed=0)
        res = pcg(lambda x: a @ x, b, tol=1e-6, maxiter=200)
        assert worst_status(res.status) == STATUS_INDEFINITE
        assert not bool(res.converged)

    def test_pcg_nan_trips(self):
        a, b = drill_near_singular(lam_min=-0.1, seed=0)
        a = a.at[0, 0].set(jnp.nan)
        res = pcg(lambda x: a @ x, b, tol=1e-6, maxiter=50)
        assert worst_status(res.status) == STATUS_NAN
        # the guard ends the loop early instead of burning maxiter
        assert int(res.iters) < 50

    def test_pcg_stagnation_trips(self):
        """Tiny positive extreme eigenvalue: fp32 PCG hits its rounding
        floor far above tol; the stagnation window ends the solve."""
        a, b = drill_near_singular(lam_min=1e-7, seed=1)
        res = pcg(lambda x: a @ x, b, tol=1e-10, maxiter=500)
        assert worst_status(res.status) == STATUS_STAGNATION
        assert int(res.iters) < 500

    def test_gmres_nan_is_breakdown(self):
        a, b = drill_near_singular(lam_min=-0.1, seed=0)
        a = a.at[0, 0].set(jnp.nan)
        res = gmres(lambda x: a @ x, b, m=8, tol=1e-5)
        assert worst_status(res.status) in (STATUS_BREAKDOWN, STATUS_NAN)
        assert not bool(res.converged)

    def test_gmres_handles_indefinite(self):
        """The escalation target: GMRES converges where PCG tripped."""
        a, b = drill_near_singular(lam_min=-0.1, seed=0)
        res = gmres(lambda x: a @ x, b, m=32, tol=1e-5, maxiter=128)
        assert bool(res.converged)
        assert worst_status(res.status) == STATUS_OK

    def test_block_cg_status_per_column(self):
        """One poisoned column trips NAN for that column only."""
        a = random_spd(24, 3)
        B = np.asarray(
            np.random.default_rng(0).standard_normal((24, 3)), np.float32)
        B[:, 1] = np.nan
        res = block_cg(lambda x: a @ x, jnp.asarray(B), tol=1e-6,
                       maxiter=100)
        st = np.asarray(res.status)
        assert st.shape == (3,)
        assert st[1] == STATUS_NAN
        assert st[0] == STATUS_OK and st[2] == STATUS_OK
        assert worst_status(res.status) == STATUS_NAN

    def test_guard_off_bitwise_parity(self):
        a = random_spd(24, 5)
        b = jnp.ones(24, jnp.float32)
        on = pcg(lambda x: a @ x, b, tol=1e-6, maxiter=100, guard=True)
        off = pcg(lambda x: a @ x, b, tol=1e-6, maxiter=100, guard=False)
        assert np.array_equal(np.asarray(on.x), np.asarray(off.x))
        assert int(on.iters) == int(off.iters)
        assert worst_status(off.status) == STATUS_OK   # synthesized OK

    def test_worst_status_none_is_ok(self):
        assert worst_status(None) == STATUS_OK
        assert status_name(None) == "ok"


class TestGuardCompilation:
    """Acceptance bar: guards compile out to a byte-identical jaxpr."""

    def _jaxpr(self, **kw):
        a = random_spd(16, 7)

        def f(b):
            return pcg(lambda x: a @ x, b, tol=1e-6, maxiter=50, **kw).x
        return str(jax.make_jaxpr(f)(jnp.ones(16, jnp.float32)))

    def test_kill_switch_matches_guard_false(self):
        j_off = self._jaxpr(guard=False)
        set_guards_enabled(False)
        try:
            j_kill = self._jaxpr(guard=True)
        finally:
            set_guards_enabled(True)
        assert j_off == j_kill

    def test_guard_off_has_no_guard_ops(self):
        j_off = self._jaxpr(guard=False)
        assert "is_finite" not in j_off

    def test_guard_on_differs(self):
        assert self._jaxpr(guard=True) != self._jaxpr(guard=False)

    def test_kill_switch_roundtrip(self):
        from repro.solvers import guards_enabled
        assert guards_enabled()
        set_guards_enabled(False)
        try:
            assert not guards_enabled()
        finally:
            set_guards_enabled(True)


# ---------------------------------------------------------------------------
# pillar 1a: structural validation (+ promoted check_orthogonal)


class TestCheckOrthogonal:
    def test_shim_and_guard_agree(self):
        """core.reconstruct.check_orthogonal is now a re-export shim."""
        from repro.core.reconstruct import check_orthogonal as shim
        _, _, shape, data, _ = _cheb_operator(side=8, leaf=8, p=3)
        assert shim(shape, data) == check_orthogonal(shape, data)

    def test_orthogonalized_bases_pass(self):
        from repro.core.orthogonalize import orthogonalize
        from repro.core.structure import shape_of
        _, _, shape, data, _ = _cheb_operator(side=8, leaf=8, p=3)
        od = orthogonalize(shape, data)
        assert check_orthogonal(shape_of(od, shape.leaf_size), od) < 1e-4

    def test_chebyshev_bases_deviate(self):
        """Interpolation bases are legitimately non-orthonormal — the
        reason validate_h2 warns instead of erroring by default."""
        _, _, shape, data, _ = _cheb_operator(side=8, leaf=8, p=3)
        assert check_orthogonal(shape, data) > 1.0


class TestValidateH2:
    def test_healthy_operator_validates(self):
        _, _, shape, data, _ = _cheb_operator()
        rep = validate_h2(shape, data)
        assert rep.ok and bool(rep)
        assert not rep.errors
        # Chebyshev bases: orthogonality surfaces as a warning
        assert any("orthogonality" in w for w in rep.warnings)
        assert rep.orthogonality is not None

    def test_require_orthogonal_promotes_to_error(self):
        _, _, shape, data, _ = _cheb_operator(side=8, leaf=8, p=3)
        rep = validate_h2(shape, data, require_orthogonal=True)
        assert not rep.ok
        assert any("orthogonality" in e for e in rep.errors)

    def test_scale_corruption_breaks_twin_coherence(self):
        """The silent-corruption case: the matvec reads only s_mar, so a
        corrupted marshaled twin must be caught structurally."""
        _, _, shape, data, _ = _cheb_operator()
        desc = drill_corrupt_operator(data, mode="scale")
        assert "s_mar" in desc
        rep = validate_h2(shape, data)
        assert not rep.ok
        assert any("s_mar" in e and "incoherent" in e for e in rep.errors)

    def test_nan_corruption_breaks_finiteness(self):
        _, _, shape, data, _ = _cheb_operator()
        drill_corrupt_operator(data, mode="nan")
        rep = validate_h2(shape, data)
        assert not rep.ok
        assert any("non-finite" in e for e in rep.errors)

    def test_stale_s_without_remarshal_is_caught(self):
        """Rewriting s in place without remarshal desynchronizes the
        twins in the opposite direction — also caught."""
        _, _, shape, data, _ = _cheb_operator()
        lvl = max(range(len(data.s)), key=lambda l: data.s[l].size)
        data.s[lvl] = data.s[lvl] * 2.0
        rep = validate_h2(shape, data)
        assert not rep.ok
        assert any("incoherent" in e for e in rep.errors)

    def test_unsorted_rows_rejected(self):
        _, _, shape, data, _ = _cheb_operator()
        dr = np.asarray(data.d_rows).copy()
        if dr.size >= 2:
            dr[[0, -1]] = dr[[-1, 0]]
            data.d_rows = jnp.asarray(dr)
            rep = validate_h2(shape, data, check_marshal=False,
                              check_orth=False)
            assert not rep.ok

    def test_summary_strings(self):
        _, _, shape, data, _ = _cheb_operator(side=8, leaf=8, p=3)
        rep = validate_h2(shape, data)
        assert "warning" in rep.summary()
        drill_corrupt_operator(data, mode="nan")
        assert "error" in validate_h2(shape, data).summary()


# ---------------------------------------------------------------------------
# pillar 1b: stochastic certification


class TestCertify:
    def test_probe_block_deterministic(self):
        om1 = probe_block(64, 4, seed=3)
        om2 = probe_block(64, 4, seed=3)
        assert np.array_equal(np.asarray(om1), np.asarray(om2))
        assert not np.array_equal(np.asarray(om1),
                                  np.asarray(probe_block(64, 4, seed=4)))

    def test_identical_applies_certify(self):
        a = random_spd(32, 0)
        cert = certify_matvec(lambda x: a @ x, lambda x: a @ x, 32,
                              probes=4, tol=1e-6)
        assert cert.ok and bool(cert)
        assert cert.rel_err < 1e-6

    def test_relative_error_estimated(self):
        """The probe estimate concentrates near the true relative
        operator error (Frobenius test, arXiv 2506.16759)."""
        a = random_spd(48, 1)
        e = 1e-3 * random_spd(48, 2)
        true = float(jnp.linalg.norm(e) / jnp.linalg.norm(a))
        cert = certify_matvec(lambda x: (a + e) @ x, lambda x: a @ x, 48,
                              probes=16, tol=1.0)
        assert 0.1 * true < cert.rel_err < 10 * true

    def test_nan_poisoned_operator_cannot_certify(self):
        a = random_spd(32, 0)
        bad = a.at[0, 0].set(jnp.nan)
        cert = certify_matvec(lambda x: bad @ x, lambda x: a @ x, 32,
                              probes=4, tol=1e3)
        assert not cert.ok
        assert not np.isfinite(cert.rel_err)

    def test_h2_operator_certifies_against_kernel(self):
        pts, kern, shape, data, tree = _cheb_operator()
        ref = kernel_reference_apply(pts, kern, tree.perm, chunk=128)
        cert = certify_h2(shape, data, ref, probes=6, tol=1e-2)
        assert cert.ok, cert.rel_err

    def test_corrupted_operator_rejected_before_serving(self):
        """ISSUE acceptance: a corrupted operator is rejected by
        certification before any serving dispatch touches it."""
        pts, kern, shape, data, tree = _cheb_operator()
        ref = kernel_reference_apply(pts, kern, tree.perm, chunk=128)
        drill_corrupt_operator(data, mode="scale")
        cert = certify_h2(shape, data, ref, probes=6, tol=1e-2)
        assert not cert.ok
        assert cert.rel_err > 1.0
        # and the structural check independently refuses it
        assert not validate_h2(shape, data).ok


# ---------------------------------------------------------------------------
# satellite: structure fuzzing through validate_h2


class TestFuzzValidate:
    @hyp(lv=(2, 4), depth=(2, 4), seed=(0, 10**6))
    def test_random_geometry_validates(self, lv, depth, seed):
        """Random point clouds, leaf sizes, and tree depths all produce
        operators whose invariants hold (N = leaf * 2**depth is the
        clustering contract)."""
        from repro.core.construction import construct_h2
        from repro.core.kernels_fn import exponential_kernel
        leaf = 2 ** lv
        rng = np.random.default_rng(seed)
        pts = np.asarray(rng.uniform(0, 1, (leaf * 2 ** depth, 2)),
                         np.float32)
        shape, data, _, _ = construct_h2(
            pts, exponential_kernel(0.2), leaf_size=leaf, cheb_p=3,
            eta=0.9, dtype=jnp.float32)
        rep = validate_h2(shape, data, check_orth=False)
        assert rep.ok, rep.summary()

    @hyp(depth=(3, 5), p=(3, 5), seed=(0, 10**6))
    def test_certify_compress_certify_roundtrip(self, depth, p, seed):
        """Compression must preserve certification: the recompressed
        operator's stochastic error stays within the compression tol."""
        from repro.core.compression import compress
        from repro.core.construction import construct_h2
        from repro.core.kernels_fn import exponential_kernel
        rng = np.random.default_rng(seed)
        pts = np.asarray(rng.uniform(0, 1, (8 * 2 ** depth, 2)),
                         np.float32)
        kern = exponential_kernel(0.2)
        shape, data, tree, _ = construct_h2(
            pts, kern, leaf_size=8, cheb_p=p, eta=0.9, dtype=jnp.float32)
        ref = kernel_reference_apply(pts, kern, tree.perm, chunk=128)
        cert0 = certify_h2(shape, data, ref, probes=4, tol=5e-2,
                           seed=seed % 97)
        assert cert0.ok, cert0.rel_err
        cshape, cdata = compress(shape, data, tol=1e-3)
        assert validate_h2(cshape, cdata, check_orth=False).ok
        cert1 = certify_h2(cshape, cdata, ref, probes=4, tol=5e-2,
                           seed=seed % 97)
        assert cert1.ok, cert1.rel_err
        # compression at 1e-3 cannot move the estimate by more than the
        # compression error itself (plus probe noise headroom)
        assert cert1.rel_err <= cert0.rel_err + 1e-2


# ---------------------------------------------------------------------------
# pillar 3: escalation ladders


class TestRunWithGuards:
    def test_primary_accepted_first(self):
        a = random_spd(24, 0)
        b = jnp.ones(24, jnp.float32)
        out = run_with_guards([
            ("primary", lambda: pcg(lambda x: a @ x, b, tol=1e-6,
                                    maxiter=100)),
            ("never", lambda: (_ for _ in ()).throw(AssertionError())),
        ])
        assert out.ok and out.rung == "primary"
        assert not out.recovered
        assert counter("guard/accept/primary") == 1
        assert counter("guard/escalations") == 0

    def test_ladder_recovers_indefinite_via_gmres(self):
        """The acceptance drill: a near-indefinite system trips PCG, the
        GMRES rung recovers, the outcome records the escalation."""
        a, b = drill_near_singular(lam_min=-0.1, seed=0)
        out = run_with_guards([
            ("pcg", lambda: pcg(lambda x: a @ x, b, tol=1e-5,
                                maxiter=200)),
            ("gmres", lambda: gmres(lambda x: a @ x, b, m=32, tol=1e-5,
                                    maxiter=128)),
        ])
        assert out.ok and out.recovered
        assert out.rung == "gmres"
        assert out.attempts[0] == ("pcg", "indefinite")
        assert out.attempts[1] == ("gmres", "ok")
        assert counter("guard/reject/pcg") == 1
        assert counter("guard/accept/gmres") == 1
        assert counter("guard/status/indefinite") == 1

    def test_raising_rung_continues_ladder(self):
        def boom():
            raise RuntimeError("rung failure")
        a = random_spd(16, 0)
        b = jnp.ones(16, jnp.float32)
        out = run_with_guards([
            ("bad", boom),
            ("good", lambda: pcg(lambda x: a @ x, b, tol=1e-6,
                                 maxiter=100)),
        ])
        assert out.ok and out.rung == "good"
        assert out.attempts[0][1].startswith("raised:")
        assert counter("guard/raise/bad") == 1

    def test_exhausted_ladder_reports_not_ok(self):
        a, b = drill_near_singular(lam_min=-0.1, seed=0)
        out = run_with_guards([
            ("pcg", lambda: pcg(lambda x: a @ x, b, tol=1e-6,
                                maxiter=50)),
        ])
        assert not out.ok and not out.recovered
        assert counter("guard/exhausted") == 1

    def test_all_raising_reraises(self):
        def boom():
            raise RuntimeError("rung failure")
        with pytest.raises(RuntimeError, match="rung failure"):
            run_with_guards([("a", boom), ("b", boom)])

    def test_fp64_scalars_rung_traces(self):
        """The fp64-scalars rung: re-trace with double accumulation
        under enable_x64; iterates stay fp32."""
        a = random_spd(24, 0)
        b = jnp.ones(24, jnp.float32)
        with fp64_scalars() as sdt:
            assert sdt == jnp.float64
            res = pcg(lambda x: a @ x, b, tol=1e-6, maxiter=100,
                      scalar_dtype=sdt)
        assert bool(res.converged)
        assert res.x.dtype == jnp.float32


@pytest.mark.guard
class TestGuardDrills:
    """Deterministic numerical-fault drills (the chaos harness's third
    leg) — own CI marker so the fast tier stays fast."""

    def test_rank_starved_construction_recovers(self):
        from repro.core.clustering import regular_grid_points
        from repro.core.kernels_fn import exponential_kernel
        pts = regular_grid_points(16, 2)
        kern = exponential_kernel(0.1, xp=jnp)
        shape, data, tree, bs, cert, rounds = construct_h2_certified(
            pts, kern, 16, 0.9, cert_tol=1e-2, probes=6, max_rounds=4,
            sketch_opts=drill_rank_starved())
        assert cert.ok, cert.rel_err
        assert rounds > 1          # escalation had real work
        assert counter("guard/construct/recovered") == 1
        assert counter("guard/construct/cert-failed") == rounds - 1
        # the recovered operator also passes structural validation
        assert validate_h2(shape, data, check_orth=False).ok

    def test_fractional_solve_reports_status(self):
        from repro.apps.fractional import solve
        out = solve(16, tol=1e-8, h2_tol=1e-6)
        assert out["converged"] and out["status"] == STATUS_OK

    def test_fractional_guard_ladder_healthy(self):
        from repro.apps.fractional import solve_with_guards
        out = solve_with_guards(16, tol=1e-8, h2_tol=1e-6)
        assert out["guard_ok"] and out["converged"]
        assert out["rung"] == "primary" and not out["recovered"]
        assert out["status"] == STATUS_OK

    def test_near_singular_returns_status_not_ok(self):
        """ISSUE acceptance: a solver fed a nearly-indefinite system
        returns status != OK instead of silently burning maxiter."""
        a, b = drill_near_singular(lam_min=-0.1, seed=0)
        res = pcg(lambda x: a @ x, b, tol=1e-6, maxiter=400)
        assert worst_status(res.status) != STATUS_OK
        assert int(res.iters) < 400
