"""Multi-device distributed-H2 checks; run in a subprocess with 8 fake devices.

Prints one "OK <name>" line per passing check; the pytest wrapper asserts on
them.  (Device count must be set before jax initializes, hence the
subprocess.)

Covers the three communication modes (halo-plan / ppermute / allgather) plus
their bf16-payload variants, the compressed-plan comm model, a clustered 1D
geometry that forces a halo radius >= 2 below the C-level, and the
distributed compression path (whose R-factor / projection-map exchanges ride
the same HaloPlan).

Solver subsystem (repro/solvers/): distributed PCG / GMRES parity vs the
single-device solvers at p in {2, 8} on uniform-2D and graded-1D
geometries — same iteration count, matching solutions, no retrace on
repeat calls, callback-free jaxpr — plus the end-to-end distributed
fractional-diffusion solve against the single-device and dense-direct
references.

Fused iteration schedule (ISSUE 10, DESIGN.md §12): fused-vs-two-step
parity across comms/schedules at p in {2, 8}, bf16 fused payloads with
bounded iteration delta, jaxpr collective-count budgets (fused emits
strictly fewer ppermute/all_gather, three all_to_all rounds), and
solver-embedded Krylov (``hide_flops``) parity on both geometries.

Observability layer (repro/obs): the *measured* collective bytes of the
partitioned HLO (perf.hlo_cost, wire-normalized by obs.metrics) must
agree with the analytic comm models for every comm mode, and the
always-on phase annotations must leave the distributed matvec and the
fused solve jaxprs byte-identical when disabled.

Elasticity (repro/core/repartition + repro/serving over distributed
operators): shrink-remesh p=8 -> p' in {4, 2} bitwise-reproduces a fresh
partition at p', and comm-mode-keyed cache entries serve identical
solutions through real shard_map matvecs.

Run with ``--chaos`` for the deterministic chaos drills instead
(device-loss / NaN / straggler against the elastic fractional solve);
the pytest wrapper for that mode is ``tests/test_chaos.py``.
"""
import os

os.environ["XLA_FLAGS"] = (
    "--xla_force_host_platform_device_count=8 "
    + os.environ.get("XLA_FLAGS", ""))

import numpy as np          # noqa: E402
import jax                  # noqa: E402
import jax.numpy as jnp     # noqa: E402
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P  # noqa: E402

from repro.core.clustering import regular_grid_points      # noqa: E402
from repro.core.construction import construct_h2            # noqa: E402
from repro.core.kernels_fn import exponential_kernel        # noqa: E402
from repro.core.matvec import h2_matvec                     # noqa: E402
from repro.core.compression import compress                 # noqa: E402
from repro.core.dist import (partition_h2, make_dist_matvec,  # noqa: E402
                             make_dist_compress, matvec_comm_bytes,
                             dist_specs)


def place(mesh, dshape, ddata):
    specs = dist_specs(dshape, "blk")
    dd = jax.tree.map(
        lambda a, s: jax.device_put(a, NamedSharding(mesh, s)),
        ddata, specs)
    return dd


def main():
    assert len(jax.devices()) == 8, jax.devices()
    mesh = jax.make_mesh((8,), ("blk",))

    pts = regular_grid_points(32, 2)      # N = 1024
    shape, data, tree, bs = construct_h2(pts, exponential_kernel(0.1),
                                         leaf_size=16, cheb_p=4, eta=0.9)
    dshape, ddata = partition_h2(shape, data, 8)
    print("OK partition", dshape.br_radius, dshape.dense_radius,
          dshape.br_caps)

    rng = np.random.default_rng(0)
    x = jnp.asarray(rng.standard_normal((shape.n, 4)), jnp.float32)
    y_ref = np.asarray(h2_matvec(shape, data, x))

    ddata_dev = place(mesh, dshape, ddata)
    x_dev = jax.device_put(x, NamedSharding(mesh, P("blk", None)))

    for comm in ("allgather", "ppermute", "halo-plan"):
        mv = make_dist_matvec(dshape, mesh, "blk", comm=comm)
        y = np.asarray(mv(ddata_dev, x_dev))
        err = np.linalg.norm(y - y_ref) / np.linalg.norm(y_ref)
        assert err < 1e-5, (comm, err)
        print(f"OK matvec_{comm}", err)

    # both halo-plan GEMM schedules: the §4.2 diag/off split twins and the
    # fused combined-GEMM form must agree with the reference
    for sched in ("overlap", "fused"):
        mv = make_dist_matvec(dshape, mesh, "blk", comm="halo-plan",
                              schedule=sched)
        y = np.asarray(mv(ddata_dev, x_dev))
        err = np.linalg.norm(y - y_ref) / np.linalg.norm(y_ref)
        assert err < 1e-5, (sched, err)
        print(f"OK matvec_halo-plan_{sched}", err)

    # pallas send packing (kernels/halo_pack.py scalar-prefetch gather,
    # interpret mode) composed with shard_map
    mv = make_dist_matvec(dshape, mesh, "blk", comm="halo-plan",
                          backend="pallas")
    y = np.asarray(mv(ddata_dev, x_dev))
    err = np.linalg.norm(y - y_ref) / np.linalg.norm(y_ref)
    assert err < 1e-5, err
    print("OK matvec_halo-plan_pallas", err)

    # bf16-payload halos: compute stays f32, so only the exchanged values
    # round — parity within bf16's ~3 decimal digits
    for comm in ("ppermute-bf16", "halo-plan-bf16"):
        mv = make_dist_matvec(dshape, mesh, "blk", comm=comm)
        y = np.asarray(mv(ddata_dev, x_dev))
        err = np.linalg.norm(y - y_ref) / np.linalg.norm(y_ref)
        assert err < 2e-2, (comm, err)
        print(f"OK matvec_{comm}", err)

    # comm model: compressed plan strictly below broadcast, broadcast below
    # allgather (paper §4.1 volume ordering)
    b_hp = matvec_comm_bytes(dshape, 4, "halo-plan")
    b_pp = matvec_comm_bytes(dshape, 4, "ppermute")
    b_ag = matvec_comm_bytes(dshape, 4, "allgather")
    assert b_hp < b_pp < b_ag, (b_hp, b_pp, b_ag)
    print("OK comm_model", b_hp, b_pp, b_ag)

    # ---- clustered 1D geometry: grading piles leaves up near 0, so wide
    # blocks reach >= 2 devices away below the C-level (rad >= 2 halos) ----
    n1 = 1024
    pts1 = (((np.arange(n1) + 0.5) / n1) ** 8)[:, None]
    shape1, data1, tree1, bs1 = construct_h2(pts1, exponential_kernel(0.2),
                                             leaf_size=8, cheb_p=6, eta=0.9)
    dshape1, ddata1 = partition_h2(shape1, data1, 8)
    deep_rads = [dshape1.br_radius[i]
                 for i, l in enumerate(range(dshape1.lc, dshape1.depth + 1))
                 if dshape1.nodes_local(l) >= 2]
    assert max(deep_rads) >= 2, (dshape1.br_radius, deep_rads)
    x1 = jnp.asarray(rng.standard_normal((shape1.n, 4)), jnp.float32)
    y1_ref = np.asarray(h2_matvec(shape1, data1, x1))
    dd1 = place(mesh, dshape1, ddata1)
    x1_dev = jax.device_put(x1, NamedSharding(mesh, P("blk", None)))
    for comm in ("ppermute", "halo-plan"):
        mv = make_dist_matvec(dshape1, mesh, "blk", comm=comm)
        y1 = np.asarray(mv(dd1, x1_dev))
        err = np.linalg.norm(y1 - y1_ref) / np.linalg.norm(y1_ref)
        assert err < 1e-5, (comm, err)
    b1_hp = matvec_comm_bytes(dshape1, 4, "halo-plan")
    b1_pp = matvec_comm_bytes(dshape1, 4, "ppermute")
    assert b1_hp < b1_pp, (b1_hp, b1_pp)
    print("OK matvec_rad2", max(deep_rads), err, b1_hp, b1_pp)

    # distributed compression vs single-device compression
    tgt = tuple(min(10, k) for k in shape.ranks)
    cs, cd = compress(shape, data, target_ranks=tgt)
    y_c_ref = np.asarray(h2_matvec(cs, cd, x))

    comp = make_dist_compress(dshape, mesh, "blk", tgt)
    cdd = comp(ddata_dev)
    # the compressed distributed matrix has the new ranks
    import dataclasses
    dshape_c = dataclasses.replace(dshape, ranks=tgt)
    mv_c = make_dist_matvec(dshape_c, mesh, "blk", comm="halo-plan")
    y_c = np.asarray(mv_c(cdd, x_dev))
    err_vs_ref = (np.linalg.norm(y_c - y_c_ref) /
                  np.linalg.norm(y_c_ref))
    err_vs_full = (np.linalg.norm(y_c - y_ref) /
                   np.linalg.norm(y_ref))
    # both single and distributed compression approximate the full matvec;
    # they need not be bitwise equal (different QR/SVD sign choices), so we
    # compare approximation quality.
    assert err_vs_full < 5e-2, err_vs_full
    print("OK dist_compress", err_vs_ref, err_vs_full)

    # multi-vector sharding over a second mesh axis
    mesh2 = jax.make_mesh((4, 2), ("blk", "nv"))
    dshape2, ddata2 = partition_h2(shape, data, 4)
    specs2 = dist_specs(dshape2, "blk")
    dd2 = jax.tree.map(
        lambda a, s: jax.device_put(a, NamedSharding(mesh2, s)),
        ddata2, specs2)
    x2 = jax.device_put(x, NamedSharding(mesh2, P("blk", "nv")))
    mv2 = make_dist_matvec(dshape2, mesh2, "blk", comm="halo-plan",
                           nv_axis="nv")
    y2 = np.asarray(mv2(dd2, x2))
    err2 = np.linalg.norm(y2 - y_ref) / np.linalg.norm(y_ref)
    assert err2 < 1e-5, err2
    print("OK matvec_2d_mesh", err2)

    repartition_checks(rng, {"uniform2d": (shape, data),
                             "graded1d": (shape1, data1)})
    serving_dist_checks(mesh, shape, data, pts)
    solver_checks(rng, {"uniform2d": (shape, data),
                        "graded1d": (shape1, data1)})
    mg_gathered_check(rng)
    fractional_checks()
    fused_solver_checks(rng, {"uniform2d": (shape, data),
                              "graded1d": (shape1, data1)})
    obs_checks(mesh, dshape, ddata_dev, x_dev)   # LAST: clears jit caches

    print("ALL_OK")


from jaxpr_utils import assert_callback_free as _assert_callback_free  # noqa: E402


def repartition_checks(rng, geometries):
    """Shrink-remesh (core/repartition.py): re-sharding a p=8 operator
    onto p' in {4, 2} must reproduce a fresh ``partition_h2`` at p'
    exactly — same shape, bitwise-equal arrays — so the elastic solve's
    device-loss recovery computes with the identical operator it would
    have built from scratch.  The comm model is then recomputed for p'
    (fewer, fatter slabs move fewer total halo bytes)."""
    from repro.core.repartition import repartition_h2, unpartition_h2

    for tag, (shp, dat) in geometries.items():
        dsp8, ddp8 = partition_h2(shp, dat, 8)
        x = jnp.asarray(rng.standard_normal((shp.n, 4)), jnp.float32)
        y_ref = np.asarray(h2_matvec(shp, dat, x))

        # round trip: unpartition reproduces the single-device operator
        shp_u, dat_u = unpartition_h2(dsp8, ddp8)
        y_u = np.asarray(h2_matvec(shp_u, dat_u, x))
        assert np.array_equal(y_u, y_ref)
        print(f"OK unpartition_{tag}")

        b8 = matvec_comm_bytes(dsp8, 4, "halo-plan")
        for p_new in (4, 2):
            dsp_n, ddp_n = repartition_h2(dsp8, ddp8, p_new)
            dsp_f, ddp_f = partition_h2(shp, dat, p_new)
            assert dsp_n == dsp_f, (tag, p_new)
            jax.tree.map(
                lambda a, b: np.testing.assert_array_equal(
                    np.asarray(a), np.asarray(b)), ddp_n, ddp_f)

            mesh_n = jax.make_mesh((p_new,), ("blk",))
            dd_n = place(mesh_n, dsp_n, ddp_n)
            x_n = jax.device_put(x, NamedSharding(mesh_n, P("blk", None)))
            mv = make_dist_matvec(dsp_n, mesh_n, "blk", comm="halo-plan")
            y_n = np.asarray(mv(dd_n, x_n))
            err = np.linalg.norm(y_n - y_ref) / np.linalg.norm(y_ref)
            assert err < 1e-5, (tag, p_new, err)

            # comm model recomputed for the shrunk mesh: volume ordering
            # holds at p', and the p' plan moves no more bytes than
            # p=8's (equality is possible on the graded geometry, whose
            # halo traffic concentrates in the near-origin slabs)
            bn_hp = matvec_comm_bytes(dsp_n, 4, "halo-plan")
            bn_ag = matvec_comm_bytes(dsp_n, 4, "allgather")
            assert 0 < bn_hp < bn_ag, (tag, p_new, bn_hp, bn_ag)
            assert bn_hp <= b8, (tag, p_new, bn_hp, b8)
            print(f"OK repartition_{tag}_p8to{p_new}", err, bn_hp, bn_ag)


def serving_dist_checks(mesh, shape, data, pts):
    """Serving over *distributed* operators: the ``comm`` field of
    ``OperatorKey`` keys distinct residents (a halo-plan operator and an
    allgather one are different cache entries), each served through the
    real jitted shard_map matvec at p=8, and all comm modes must return
    the same solutions as the single-device ("local") operator."""
    from repro.serving import (OperatorCache, OperatorKey, PoissonLoad,
                               ServiceFaultPlan, SolverService,
                               geometry_digest)

    geom = geometry_digest(pts)
    cache = OperatorCache()
    n_req = 6

    def load():
        return PoissonLoad(n=shape.n, rate=200.0, n_requests=n_req,
                           tol=1e-6, seed=11).requests()

    def svc(make_apply, fault_plan=None):
        return SolverService(cache, panel_width=4, restart_every=25,
                             max_segments=20, tol=1e-6,
                             dispatch_cost=0.02, seed=0,
                             fault_plan=fault_plan,
                             make_apply=make_apply)

    sols = {}
    for comm in ("local", "halo-plan", "allgather"):
        key = OperatorKey(geometry=geom, kernel=("exponential", 0.1),
                          tol=None, comm=comm)
        if comm == "local":
            def build():
                return shape, data, {}

            def make_apply(shp):
                return lambda d, x: x + h2_matvec(shp, d, x)
        else:
            dsp, ddp = partition_h2(shape, data, 8)
            mv = make_dist_matvec(dsp, mesh, "blk", comm=comm)

            def build(dsp=dsp, ddp=ddp):
                return shape, place(mesh, dsp, ddp), {"dshape": dsp}

            def make_apply(shp, mv=mv):
                return lambda d, x: x + mv(d, x)
        rep = svc(make_apply).serve(load(), key, build)
        assert rep.metrics["completed"] == n_req, (comm, rep.metrics)
        assert all(c.status == "ok" for c in rep.completions.values())
        sols[comm] = {rid: np.asarray(c.x)
                      for rid, c in rep.completions.items()}

    # distinct residents per comm mode...
    assert len(cache) == 3 and cache.stats()["misses"] == 3, cache.stats()
    # ...but identical answers (same system, different exchange plans)
    for comm in ("halo-plan", "allgather"):
        for rid, x_loc in sols["local"].items():
            d = (np.linalg.norm(sols[comm][rid] - x_loc)
                 / np.linalg.norm(x_loc))
            assert d < 1e-4, (comm, rid, d)
    print("OK serving_dist_cache", cache.stats()["misses"], len(cache))

    # a served request list replayed against the cached halo-plan
    # resident is a pure cache hit (no rebuild) AND survives an injected
    # nan fault through the distributed operator's retry path
    key_hp = OperatorKey(geometry=geom, kernel=("exponential", 0.1),
                         tol=None, comm="halo-plan")
    dsp, _ = partition_h2(shape, data, 8)
    mv = make_dist_matvec(dsp, mesh, "blk", comm="halo-plan")

    def must_not_build():
        raise AssertionError("halo-plan operator rebuilt on a hit")

    rep = svc(lambda shp: (lambda d, x: x + mv(d, x)),
              fault_plan=ServiceFaultPlan(nan_at={1})).serve(
        load(), key_hp, must_not_build)
    m = rep.metrics
    assert m["completed"] == n_req and m["dispatch_failures"] >= 1
    assert m["retries"] >= 1
    assert all(c.status == "ok" and np.isfinite(c.x).all()
               for c in rep.completions.values())
    for rid, c in rep.completions.items():
        d = (np.linalg.norm(np.asarray(c.x) - sols["local"][rid])
             / np.linalg.norm(sols["local"][rid]))
        assert d < 1e-4, (rid, d)
    print("OK serving_dist_fault", m["dispatch_failures"], m["retries"])


def solver_checks(rng, geometries):
    """Distributed PCG/GMRES on (I + A) vs the single-device solvers.

    Uniform geometry: exact iteration-count parity (the residual crosses
    tol decisively).  Graded geometry: the ill-conditioned system's
    residual HOVERS at the crossing for a few iterations, so psum
    reassociation can legitimately shift the count by an iteration or
    two — parity there is |delta| <= 2 with a looser solution check.
    """
    from repro.solvers import TRACE_COUNTS, gmres, make_dist_krylov, pcg

    cfg = {"uniform2d": dict(tol=1e-6, slack=0, xerr=1e-4),
           "graded1d": dict(tol=1e-4, slack=2, xerr=5e-3)}
    for tag, (shp, dat) in geometries.items():
        tol, slack, xerr = (cfg[tag][k] for k in ("tol", "slack", "xerr"))
        b = jnp.asarray(rng.standard_normal(shp.n), jnp.float32)
        apply_ref = lambda x: x + h2_matvec(shp, dat, x[:, None])[:, 0]  # noqa: E731
        ref_p = jax.jit(lambda rhs: pcg(apply_ref, rhs, tol=tol,
                                        maxiter=250))(b)
        ref_g = jax.jit(lambda rhs: gmres(apply_ref, rhs, m=20, tol=tol,
                                          maxiter=100))(b)
        assert bool(ref_p.converged) and bool(ref_g.converged)
        for p in (2, 8):
            mesh_p = jax.make_mesh((p,), ("blk",))
            dsp, ddp = partition_h2(shp, dat, p)
            ddev = place(mesh_p, dsp, ddp)
            bdev = jax.device_put(b, NamedSharding(mesh_p, P("blk")))

            base = TRACE_COUNTS["dist_pcg"]
            sv = make_dist_krylov(dsp, mesh_p, "blk", method="pcg",
                                  shift=1.0, tol=tol, maxiter=250)
            rp = sv(ddev, bdev)
            err = (np.linalg.norm(np.asarray(rp.x) - np.asarray(ref_p.x))
                   / np.linalg.norm(np.asarray(ref_p.x)))
            assert bool(rp.converged)
            assert abs(int(rp.iters) - int(ref_p.iters)) <= slack, \
                (tag, p, int(rp.iters), int(ref_p.iters))
            assert err < xerr, (tag, p, err)
            sv(ddev, 2.0 * bdev)                 # cached: no retrace
            assert TRACE_COUNTS["dist_pcg"] == base + 1
            print(f"OK solver_pcg_{tag}_p{p}", int(rp.iters), err)

            sg = make_dist_krylov(dsp, mesh_p, "blk", method="gmres",
                                  shift=1.0, tol=tol, maxiter=100,
                                  restart=20)
            rg = sg(ddev, bdev)
            errg = (np.linalg.norm(np.asarray(rg.x) - np.asarray(ref_g.x))
                    / np.linalg.norm(np.asarray(ref_g.x)))
            assert bool(rg.converged)
            assert int(rg.iters) == int(ref_g.iters), \
                (tag, p, int(rg.iters), int(ref_g.iters))
            assert errg < xerr, (tag, p, errg)
            print(f"OK solver_gmres_{tag}_p{p}", int(rg.iters), errg)

            if tag == "uniform2d" and p == 8:
                _assert_callback_free(sv, ddev, bdev)
                print("OK solver_jaxpr_callback_free")


def mg_gathered_check(rng):
    """solvers/mg.py gathered fallback (p > 1 but the grid is too coarse
    to strip-shard, n_sharded == 0): the strips are all_gather'ed, the
    whole V-cycle runs replicated, and the own strip is sliced back —
    must equal the p=1 preconditioner exactly."""
    from repro.solvers.mg import (build_grid_mg, mg_halo_bytes,
                                  mg_precond_local, mg_specs)

    n, p = 8, 8
    kappa = 1.0 + 0.5 * rng.random((n, n))
    dd = 1.0 + rng.random((n, n))
    mg1, a1 = build_grid_mg(kappa, dd, gamma=2.0, h0=0.25, n=n, p=1)
    mg8, a8 = build_grid_mg(kappa, dd, gamma=2.0, h0=0.25, n=n, p=p)
    assert mg8.n_sharded == 0, mg8
    assert mg_halo_bytes(mg8) > 0
    r = jnp.asarray(rng.standard_normal(n * n), jnp.float32)
    ref = np.asarray(mg_precond_local(mg1, a1, r))

    mesh_p = jax.make_mesh((p,), ("blk",))
    fn = jax.shard_map(
        lambda aa, rr: mg_precond_local(mg8, aa, rr, "blk"),
        mesh=mesh_p, in_specs=(mg_specs(mg8, "blk"), P("blk")),
        out_specs=P("blk"), check_vma=False)
    a8_dev = jax.tree.map(
        lambda a, s: jax.device_put(a, NamedSharding(mesh_p, s)),
        a8, mg_specs(mg8, "blk"))
    r_dev = jax.device_put(r, NamedSharding(mesh_p, P("blk")))
    out = np.asarray(jax.jit(fn)(a8_dev, r_dev))
    err = np.linalg.norm(out - ref) / np.linalg.norm(ref)
    assert err < 1e-6, err
    print("OK mg_gathered", err)


def fractional_checks():
    """End-to-end distributed fractional solve (paper §6.4) at p in
    {2, 8}: one shard_map program, same iterations as single-device,
    matches the dense direct solve."""
    from repro.apps.fractional import (dense_reference_solution, solve,
                                       solve_distributed)
    from repro.solvers import TRACE_COUNTS

    ref = solve(16, h2_tol=1e-7, tol=1e-10)
    u_dense = dense_reference_solution(16)
    for p in (2, 8):
        mesh_p = jax.make_mesh((p,), ("blk",))
        res = solve_distributed(16, mesh_p, h2_tol=1e-7, tol=1e-10)
        assert res["converged"]
        assert res["iters"] == ref["iters"], (p, res["iters"], ref["iters"])
        du = np.linalg.norm(res["u"] - ref["u"]) / np.linalg.norm(ref["u"])
        dd = (np.linalg.norm(res["u"] - u_dense)
              / np.linalg.norm(u_dense))
        assert du < 1e-5, (p, du)
        assert dd < 2e-2, (p, dd)
        base = TRACE_COUNTS["dist_fractional"]
        res["parts"]["fn"](*res["placed_args"], res["b"])
        assert TRACE_COUNTS["dist_fractional"] == base
        if p == 8:
            _assert_callback_free(res["parts"]["fn"], *res["placed_args"],
                                  res["b"])
            print("OK frac_dist_jaxpr_callback_free")
        print(f"OK frac_dist_p{p}", res["iters"], du, dd)


def fused_solver_checks(rng, geometries):
    """ISSUE 10 fused iteration schedule (DESIGN.md §12).

    Parity matrix: the fused distributed fractional solve (grid<->tree
    transpositions as plan-compressed all_to_alls with the C-stencil halo
    riding the inbound lanes, ONE merged residue-class H^2 exchange,
    deep-halo V-cycle smoothing) must match the two-step schedule
    EXACTLY — same iteration count as the single-device reference and the
    same solution — at p in {2, 8} for both fp32 comms and both GEMM
    schedules.  bf16 fused payloads keep a bounded iteration delta.

    Collective budget: the fused program's jaxpr must emit strictly fewer
    ``ppermute`` AND ``all_gather`` than the two-step one (the whole point
    of the restructuring), carry the three ``all_to_all`` rounds, and stay
    callback-free.

    Graded geometry rides through ``make_dist_krylov(hide_flops=...)``:
    a solver-embedded H^2 matvec (merged exchange + compute-hidden
    association) on the clustered 1D operator must agree with the
    per-level exchange build within the same slack ``solver_checks``
    grants psum reassociation.
    """
    from jaxpr_utils import collective_counts
    from repro.apps.fractional import (FractionalProblem, make_dist_solve,
                                       solve)
    from repro.solvers import make_dist_krylov, solver_hide_flops

    n = 16
    ref = solve(n, h2_tol=1e-7, tol=1e-10)
    prob = FractionalProblem(n).build()
    b = jnp.ones((n * n,), jnp.float32) * prob["h"] ** 2
    for p in (2, 8):
        mesh_p = jax.make_mesh((p,), ("blk",))
        b_dev = jax.device_put(b, NamedSharding(mesh_p, P("blk")))
        fns = {}
        for comm in ("halo-plan", "allgather"):
            scheds = {(False, "auto"), (True, "auto"), (True, "overlap")}
            for fused, sched in sorted(scheds):
                parts = make_dist_solve(prob, mesh_p, comm=comm,
                                        tol=1e-10, schedule=sched,
                                        fused=fused)
                assert parts["fused"] == fused
                pargs = parts["place"](parts["args"])
                res = jax.block_until_ready(parts["fn"](*pargs, b_dev))
                du = (np.linalg.norm(
                    np.asarray(res.x).reshape(n, n) - ref["u"])
                    / np.linalg.norm(ref["u"]))
                assert bool(res.converged), (p, comm, fused, sched)
                assert int(res.iters) == ref["iters"], \
                    (p, comm, fused, sched, int(res.iters), ref["iters"])
                assert du < 1e-5, (p, comm, fused, sched, du)
                if sched == "auto":
                    fns[(comm, fused)] = (parts["fn"], pargs)
            print(f"OK fused_parity_{comm}_p{p}", ref["iters"])

        parts = make_dist_solve(prob, mesh_p, comm="halo-plan-bf16",
                                tol=1e-10)
        assert parts["fused"]          # halo-plan comms fuse by default
        pargs = parts["place"](parts["args"])
        res = jax.block_until_ready(parts["fn"](*pargs, b_dev))
        du = (np.linalg.norm(np.asarray(res.x).reshape(n, n) - ref["u"])
              / np.linalg.norm(ref["u"]))
        assert bool(res.converged), (p, int(res.iters))
        assert abs(int(res.iters) - ref["iters"]) <= 5, \
            (p, int(res.iters), ref["iters"])
        assert du < 1e-3, (p, du)
        print(f"OK fused_bf16_solve_p{p}", int(res.iters), du)

        if p == 8:
            fn_f, a_f = fns[("halo-plan", True)]
            fn_u, a_u = fns[("halo-plan", False)]
            k_f = collective_counts(fn_f, *a_f, b_dev)
            k_u = collective_counts(fn_u, *a_u, b_dev)
            assert k_f["ppermute"] < k_u["ppermute"], (k_f, k_u)
            assert k_f["all_gather"] < k_u["all_gather"], (k_f, k_u)
            # T-in, merged H^2 exchange, T-out
            assert k_f["all_to_all"] >= 3, k_f
            assert k_u["all_to_all"] == 0, k_u
            _assert_callback_free(fn_f, *a_f, b_dev)
            _assert_callback_free(fn_u, *a_u, b_dev)
            print("OK fused_collective_counts",
                  dict(k_f), dict(k_u))

    cfg = {"uniform2d": dict(tol=1e-6, slack=0, xerr=1e-4),
           "graded1d": dict(tol=1e-4, slack=2, xerr=5e-3)}
    assert solver_hide_flops(None) == 0    # no V-cycle -> nothing to hide
    hide = 1 << 40                         # force compute-hidden association
    for tag, (shp, dat) in geometries.items():
        tol, slack, xerr = (cfg[tag][k] for k in ("tol", "slack", "xerr"))
        b2 = jnp.asarray(rng.standard_normal(shp.n), jnp.float32)
        for p in (2, 8):
            mesh_p = jax.make_mesh((p,), ("blk",))
            dsp, ddp = partition_h2(shp, dat, p)
            ddev = place(mesh_p, dsp, ddp)
            bdev = jax.device_put(b2, NamedSharding(mesh_p, P("blk")))
            r0 = make_dist_krylov(dsp, mesh_p, "blk", method="pcg",
                                  shift=1.0, tol=tol,
                                  maxiter=250)(ddev, bdev)
            r1 = make_dist_krylov(dsp, mesh_p, "blk", method="pcg",
                                  shift=1.0, tol=tol, maxiter=250,
                                  hide_flops=hide)(ddev, bdev)
            assert bool(r0.converged) and bool(r1.converged), (tag, p)
            assert abs(int(r1.iters) - int(r0.iters)) <= slack, \
                (tag, p, int(r1.iters), int(r0.iters))
            err = (np.linalg.norm(np.asarray(r1.x) - np.asarray(r0.x))
                   / np.linalg.norm(np.asarray(r0.x)))
            assert err < xerr, (tag, p, err)
            print(f"OK fused_krylov_{tag}_p{p}", int(r1.iters), err)


def obs_checks(mesh, dshape, dd, x_dev):
    """Measured-vs-modeled collective bytes + trace neutrality at p=8.

    Matvec: ``perf.hlo_cost`` collective bytes of the partitioned HLO,
    wire-normalized (``obs.metrics.wire_bytes``), must match
    ``matvec_comm_bytes`` within 10% for all three comm modes — the
    models the roofline/profiling layers report are thereby *measured*,
    not just asserted.  Solve: XLA lowers the PCG while-loop so the body's
    collectives appear once (plus the prologue's), so the measurement
    lands between 1x and 2.5x one iteration's model; the halo-plan-vs-
    allgather byte DELTA, however, is exchange-volume only and must match
    the model delta almost exactly.  Trace neutrality: the jaxprs of the
    distributed matvec and the fused solve are byte-identical with phase
    annotations on (default) and off — run LAST because forcing fresh
    traces clears the jit caches.
    """
    from repro.apps.fractional import (FractionalProblem,
                                       dist_solve_comm_bytes,
                                       make_dist_solve)
    from repro.obs import metrics, trace

    for comm in ("halo-plan", "ppermute", "allgather"):
        mv = make_dist_matvec(dshape, mesh, "blk", comm=comm)
        by_kind = metrics.measured_collective_bytes(mv, dd, x_dev)
        meas = metrics.wire_bytes(by_kind, dshape.p)
        model = matvec_comm_bytes(dshape, 4, comm)
        ratio = meas / model
        assert 0.9 <= ratio <= 1.1, (comm, meas, model, by_kind)
        print(f"OK obs_comm_bytes_{comm}", meas, model, round(ratio, 3))

    n = 16
    prob = FractionalProblem(n).build()
    b = jnp.ones((n * n,), jnp.float32) * prob["h"] ** 2
    b_dev = jax.device_put(b, NamedSharding(mesh, P("blk")))
    solve_meas, solve_model = {}, {}
    for comm in ("halo-plan", "allgather"):
        # two-step schedule pinned explicitly: the delta check below
        # relies on the transposition/precond bytes being identical
        # across comm modes so only the exchange volume survives
        parts = make_dist_solve(prob, mesh, comm=comm, tol=1e-8,
                                maxiter=200, fused=False)
        pargs = parts["place"](parts["args"])
        by_kind = metrics.measured_collective_bytes(parts["fn"],
                                                    *pargs, b_dev)
        meas = metrics.wire_bytes(by_kind, dshape.p)
        model = dist_solve_comm_bytes(parts["dshape"], parts["mg"], comm,
                                      fused=False)
        ratio = meas / model
        assert 1.0 <= ratio <= 2.5, (comm, meas, model, by_kind)
        solve_meas[comm], solve_model[comm] = meas, model
        print(f"OK obs_solve_bytes_{comm}", meas, model, round(ratio, 3))
    d_meas = solve_meas["halo-plan"] - solve_meas["allgather"]
    d_model = solve_model["halo-plan"] - solve_model["allgather"]
    assert abs(d_meas - d_model) <= 0.02 * solve_model["allgather"] + 64, \
        (d_meas, d_model)
    print("OK obs_comm_delta", d_meas, d_model)

    # the fused schedule (halo-plan default) against ITS model — merged
    # exchange + plan-compressed transposition all_to_alls + fused
    # V-cycle halos (dist_solve_comm_bytes with tcaps/fused)
    parts_f = make_dist_solve(prob, mesh, comm="halo-plan", tol=1e-8,
                              maxiter=200)
    assert parts_f["fused"]
    pargs_f = parts_f["place"](parts_f["args"])
    by_kind = metrics.measured_collective_bytes(parts_f["fn"],
                                                *pargs_f, b_dev)
    meas_f = metrics.wire_bytes(by_kind, dshape.p)
    model_f = dist_solve_comm_bytes(parts_f["dshape"], parts_f["mg"],
                                    "halo-plan", tcaps=parts_f["tcaps"],
                                    fused=True)
    ratio_f = meas_f / model_f
    assert 1.0 <= ratio_f <= 2.5, (meas_f, model_f, by_kind)
    print("OK obs_solve_bytes_fused", meas_f, model_f, round(ratio_f, 3))

    def fresh_jaxpr(fn, *fargs):
        jax.clear_caches()
        return str(jax.make_jaxpr(fn)(*fargs))

    mv = make_dist_matvec(dshape, mesh, "blk", comm="halo-plan")
    parts, pargs = parts_f, pargs_f      # neutrality on the fused program
    assert trace.enabled()
    mv_on = fresh_jaxpr(mv, dd, x_dev)
    sv_on = fresh_jaxpr(parts["fn"], *pargs, b_dev)
    trace.set_enabled(False)
    try:
        mv_off = fresh_jaxpr(mv, dd, x_dev)
        sv_off = fresh_jaxpr(parts["fn"], *pargs, b_dev)
    finally:
        trace.set_enabled(True)
    assert mv_on == mv_off
    print("OK obs_trace_neutral_matvec", len(mv_on))
    assert sv_on == sv_off
    print("OK obs_trace_neutral_solve", len(sv_on))


def chaos_main():
    """Deterministic chaos drills (ISSUE 8): the elastic distributed
    fractional solve at p=8 under scheduled device-loss / NaN-corruption /
    straggler faults must converge to the SAME tolerance as the fault-free
    single-device reference with bounded extra iterations (at most one
    checkpoint interval per fault), shrink-remesh to the scheduled
    surviving device count, roll corrupted state back to the last valid
    checkpoint, and flag stragglers without losing iterations."""
    import tempfile

    from repro.apps.fractional import solve, solve_distributed_elastic
    from repro.runtime.chaos import ChaosPlan
    from repro.runtime.fault import StragglerMonitor

    assert len(jax.devices()) == 8, jax.devices()
    mesh = jax.make_mesh((8,), ("blk",))
    n, tol = 16, 1e-10

    ref = solve(n, h2_tol=1e-7, tol=tol)
    assert ref["converged"]
    it_ref = ref["iters"]
    print("OK chaos_ref", it_ref)

    def du(res):
        return (np.linalg.norm(res["u"] - ref["u"])
                / np.linalg.norm(ref["u"]))

    def run(ckpt_every, chaos=None, monitor=None):
        with tempfile.TemporaryDirectory() as d:
            return solve_distributed_elastic(
                n, mesh, h2_tol=1e-7, tol=tol, ckpt_dir=d,
                ckpt_every=ckpt_every, chaos=chaos, monitor=monitor)

    # fault-free elastic path: exact iteration parity with the
    # single-device reference (segmented while_loop == monolithic one)
    res = run(ckpt_every=10)
    assert res["converged"] and res["restarts"] == 0
    assert res["iters"] == it_ref, (res["iters"], it_ref)
    assert res["p_final"] == 8
    assert du(res) < 1e-5, du(res)
    assert res["report"].ckpt_save_s      # checkpoints actually written
    print("OK chaos_clean", res["iters"], du(res))

    # device loss at segment 2 -> shrink-remesh to p'=4, restore the
    # segment-boundary checkpoint: zero iterations lost
    res = run(ckpt_every=4, chaos=ChaosPlan(device_loss_at={2: 4}))
    assert res["converged"] and res["restarts"] == 1
    assert res["p_final"] == 4
    assert res["iters"] == it_ref, (res["iters"], it_ref)
    assert du(res) < 1e-5, du(res)
    ev = [e for e in res["report"].events if e.kind == "device-loss"]
    assert len(ev) == 1 and ev[0].p_from == 8 and ev[0].p_to == 4
    assert res["report"].iters_lost("device-loss") == 0
    print("OK chaos_device_loss", res["iters"], du(res),
          res["report"].summary()["faults"]["device-loss"])

    # NaN poisoning of segment 1's fresh iterate: the recurrence residual
    # stays finite but the recomputed-residual tripwire fires; rollback
    # re-runs exactly one checkpoint interval
    res = run(ckpt_every=4, chaos=ChaosPlan(nan_at={1}))
    assert res["converged"] and res["restarts"] == 1
    assert res["p_final"] == 8
    assert res["iters"] == it_ref, (res["iters"], it_ref)
    assert du(res) < 1e-5, du(res)
    assert res["report"].iters_lost("corruption") == 4   # == ckpt_every
    assert np.isfinite(res["u"]).all()
    print("OK chaos_nan_rollback", res["iters"],
          res["report"].iters_lost("corruption"))

    # straggler at segment 4: flagged by the monitor, costs (virtual)
    # wall time but zero iterations and zero restarts; the inflation is
    # far above threshold x EMA even though the EMA seeds on the first
    # segment's compile-inclusive wall time
    res = run(ckpt_every=2, chaos=ChaosPlan(straggle_at={4: 1000.0}),
              monitor=StragglerMonitor(threshold=3.0, warmup=3))
    assert res["converged"] and res["restarts"] == 0
    assert res["iters"] == it_ref, (res["iters"], it_ref)
    assert 4 in res["report"].straggler_flags, \
        res["report"].straggler_flags
    assert res["report"].iters_lost() == 0
    print("OK chaos_straggler", res["report"].straggler_flags)

    # guard-rail escalation drill (DESIGN.md §11): NaN corruption during
    # a bf16-payload run triggers the precision-escalation rung — the
    # restart rebuilds the segment with full fp32 halo payloads and the
    # solve converges with a clean final status
    from repro.guard import GUARD_COUNTERS, reset_guard_counters
    reset_guard_counters()
    with tempfile.TemporaryDirectory() as d:
        res = solve_distributed_elastic(
            n, mesh, h2_tol=1e-7, tol=tol, ckpt_dir=d, ckpt_every=4,
            comm="halo-plan-bf16", chaos=ChaosPlan(nan_at={1}))
    assert res["converged"] and res["restarts"] == 1
    assert res["comm_final"] == "halo-plan", res["comm_final"]
    assert res["status"] == 0
    assert GUARD_COUNTERS["elastic/fp32-comm"] == 1
    assert du(res) < 1e-5, du(res)
    print("OK chaos_guard_fp32comm", res["iters"], res["comm_final"])

    print("CHAOS_ALL_OK")


if __name__ == "__main__":
    import sys
    if "--chaos" in sys.argv[1:]:
        chaos_main()
    else:
        main()
