"""Per-phase profile of the distributed fractional solve (DESIGN.md §8).

``python -m repro.obs.profile_solve`` runs the end-to-end distributed PCG
solve (``apps.fractional.make_dist_solve``) at p=8 for the ``halo-plan``
and ``allgather`` comm modes and attributes the measured wall time to the
named phases of one Krylov iteration via **segmented replay**
(``obs.timers``): the one fused solve program is cut at the phase
boundaries

    solve/transpose-in  -> hgemv/upsweep -> hgemv/exchange
    -> hgemv/coupling-gemm -> hgemv/downsweep -> solve/transpose-out
    -> solve/stencil    -> precond/vcycle -> krylov/scalars

and timed by **truncated-loop differencing**: for every cut k one jitted
shard_map program runs an m-iteration fori_loop of stages 1..k, and
phase k's per-iteration time is the per-round difference
``(T(loop_k) - T(loop_{k-1})) / m`` (median over interleaved rounds,
fixed inputs).  Differencing cancels the fixed per-dispatch replay cost
— python flattening, executable launch, the device-thread rendezvous of
the fake-device mesh — and measuring *inside* a loop captures the
marginal in-loop iteration cost the fused while-loop actually pays
(warm caches, loop-carried scheduling), which a single dispatched
iteration overstates 1.5-2x on the CPU mesh.  The per-phase sum
telescopes to the full-loop-body time, so it tracks the fused
per-iteration time by construction instead of bounding it loosely from
above.  Separately-jitted single-stage programs are still built — they
feed the per-stage *measured collective bytes* (``perf.hlo_cost``) and
``benchmarks/solver_bench.py``'s per-phase breakdown.  Every per-phase
row joins the measured time with the modeled flops
(``perf.jaxpr_cost``), the analytic comm-byte model (the per-phase
decomposition of ``dist_solve_comm_bytes``) and the *measured* collective
bytes of the stage's partitioned HLO (``perf.hlo_cost``, wire-normalized).

Output: ``BENCH_solver_phases.json`` (per-phase records + per-comm summary
+ the halo-plan-vs-allgather per-phase gap table that localizes the
solver-side regression BENCH_solver.json reports) and a Chrome-trace /
perfetto timeline (one lane per comm mode) for chrome://tracing.

Device count must be fixed before jax initializes, so the measurement runs
in a subprocess (``--worker``) — the same harness as ``benchmarks``.
"""
from __future__ import annotations

import argparse
import json
import os
import subprocess
import sys
from typing import Dict, List, Optional, Sequence

MARKER = "PROFILE_SOLVE_JSON:"

#: replay-stage order; Stage.phase of the pipeline built below
PHASE_ORDER = (
    "solve/transpose-in", "hgemv/upsweep", "hgemv/exchange",
    "hgemv/coupling-gemm", "hgemv/downsweep", "solve/transpose-out",
    "solve/stencil", "precond/vcycle", "krylov/scalars",
)

#: the pipeline's external inputs — argument order of the prefix programs
EXT_INPUTS = ("d", "aux", "mga", "xvec", "r", "pvec", "rz")


def phase_comm_model(dshape, mg, comm: str, bytes_per_el: int = 4,
                     tcaps=None, fused=None) -> Dict[str, int]:
    """Per-phase decomposition of ``dist_solve_comm_bytes`` — modeled
    per-device collective bytes of ONE PCG iteration, keyed by phase.
    The terms sum exactly to ``dist_solve_comm_bytes(dshape, mg, comm,
    tcaps=tcaps, fused=fused)`` for the matching schedule: pass
    ``tcaps``/``fused`` from ``make_dist_solve``'s parts for the fused
    iteration (all_to_all transpositions carrying the stencil halo,
    merged H^2 exchange, deep-halo V-cycle)."""
    from repro.apps.fractional import _fused_default
    from repro.core.dist import (matvec_comm_bytes, merged_exchange_bytes,
                                 root_gather_bytes)
    from repro.solvers.mg import mg_halo_bytes

    p = dshape.p
    if p <= 1:
        return {ph: 0 for ph in PHASE_ORDER}
    root = root_gather_bytes(dshape, 1, comm, bytes_per_el)
    if _fused_default(fused, comm) and tcaps is not None:
        cap_in, cap_out = tcaps
        exch = merged_exchange_bytes(dshape, 1, comm, bytes_per_el) \
            if comm.startswith("halo-plan") \
            else matvec_comm_bytes(dshape, 1, comm, bytes_per_el) - root
        return {
            "solve/transpose-in": (p - 1) * (cap_in + mg.levels[0])
            * bytes_per_el,                    # + stencil-halo lanes
            "hgemv/upsweep": root,             # branch-root all_gather
            "hgemv/exchange": exch,
            "hgemv/coupling-gemm": 0,
            "hgemv/downsweep": 0,
            "solve/transpose-out": (p - 1) * cap_out * bytes_per_el,
            "solve/stencil": 0,                # rode the transpose-in a2a
            "precond/vcycle": mg_halo_bytes(
                mg, bytes_per_el, fused=True,
                bf16=comm.endswith("-bf16")),
            "krylov/scalars": 3 * (p - 1) * bytes_per_el,
        }
    mv = matvec_comm_bytes(dshape, 1, comm, bytes_per_el)
    tr = (p - 1) * (dshape.n // p) * bytes_per_el
    return {
        "solve/transpose-in": tr,
        "hgemv/upsweep": root,                 # branch-root all_gather
        "hgemv/exchange": mv - root,
        "hgemv/coupling-gemm": 0,
        "hgemv/downsweep": 0,
        "solve/transpose-out": tr,
        "solve/stencil": 2 * mg.levels[0] * bytes_per_el,
        "precond/vcycle": mg_halo_bytes(mg, bytes_per_el),
        "krylov/scalars": 3 * (p - 1) * bytes_per_el,
    }


def build_solve_stages(parts: Dict, mesh, comm: str, loop_m: int = 12):
    """Cut the fused distributed solve into the nine replay stages.

    ``parts`` is ``make_dist_solve``'s return value.  Each stage is one
    ``jit(shard_map(...))`` program calling the SAME per-device bodies as
    the fused solve (``core.dist`` / ``solvers.mg`` / the PCG scalar
    block), so per-stage times attribute the fused program's phases; the
    stage boundaries are exactly the ``obs.trace.phase`` boundaries.
    Returns ``(stages, loops)``: ``timers.Stage`` objects (feed them an
    env holding ``d`` (placed DistH2Data), ``aux``, ``mga``,
    ``xvec``/``r``/``pvec`` (grid vectors, ``P(axis)``) and ``rz``
    (replicated scalar)) used for per-stage collective-byte measurement,
    and the truncated-loop timing programs ``loops[k]`` = ``loop_m``
    fori_loop iterations of stages 1..k (args = ``EXT_INPUTS``;
    ``loops[0]`` is the loop-scaffolding baseline).
    """
    import jax
    import jax.numpy as jnp
    from jax.sharding import PartitionSpec as P

    from repro.core.dist import (_coupling_phase, _coupling_phase_overlap,
                                 _dense_phase, _hp_pack_exchange,
                                 _hp_payload_layout, _local_downsweep,
                                 _local_upsweep)
    from repro.core.halo import transpose_a2a
    from repro.obs.timers import Stage
    from repro.solvers.krylov import _dot, _norm
    from repro.solvers.mg import _apply_op as _mg_apply_op
    from repro.solvers.mg import mg_precond_local, solver_hide_flops

    dshape, mg, axis = parts["dshape"], parts["mg"], parts["axis"]
    dspec, aux_spec, mg_spec = parts["specs"]
    n, h = mg.n, mg.hs[0]
    p, lc, depth = dshape.p, dshape.lc, dshape.depth
    nl, m = dshape.leaves_per_dev, dshape.leaf_size
    sh, rep, shv = P(axis), P(), P(axis, None)
    br_levels = tuple(range(lc, depth + 1))
    top_levels = tuple(range(lc + 1))
    fused = bool(parts.get("fused")) and p > 1
    bf16 = comm.endswith("-bf16")
    hide = solver_hide_flops(mg) if fused else 0

    def shmap(fn, in_specs, out_specs):
        return jax.jit(jax.shard_map(fn, mesh=mesh, in_specs=in_specs,
                                     out_specs=out_specs, check_vma=False))

    def to_dicts(sweep):
        xh = dict(zip(br_levels, sweep[0]))
        xtop = dict(zip(top_levels, sweep[1]))
        return xh, xtop

    if fused:
        # fused transposition: one plan-compressed all_to_all whose extra
        # lanes carry the stencil row halo (same bodies as
        # apps.fractional._dist_apply_a's fused branch)
        def s_transpose_in(aux, x):
            rows = n // p
            x2d = x.reshape(rows, n)
            me = jax.lax.axis_index(axis)
            dump = jnp.zeros((p + 1, n), x.dtype)
            dump = jax.lax.dynamic_update_slice(dump, x2d[-1:],
                                                (me + 1, 0))
            dump = jax.lax.dynamic_update_slice(
                dump, x2d[:1], (jnp.where(me >= 1, me - 1, p), 0))
            xt, ex = transpose_a2a(x, aux["tin_send"], aux["tin_take"],
                                   axis, extra=dump[:p])
            top = jax.lax.dynamic_slice(ex, (jnp.maximum(me - 1, 0), 0),
                                        (1, n))
            top = jnp.where(me >= 1, top, 0.0)
            bot = jax.lax.dynamic_slice(
                ex, (jnp.minimum(me + 1, p - 1), 0), (1, n))
            bot = jnp.where(me <= p - 2, bot, 0.0)
            return xt[:, None], top, bot
        tin_out, tin_outputs = (shv, sh, sh), ("xt", "top", "bot")
    else:
        def s_transpose_in(aux, x):
            xf = jax.lax.all_gather(x, axis, axis=0, tiled=True) if p > 1 \
                else x
            return jnp.take(xf, aux["perm"], axis=0)[:, None]
        tin_out, tin_outputs = shv, ("xt",)

    def s_upsweep(d, xt):
        xh, xtop = _local_upsweep(dshape, d, xt.reshape(nl, m, -1), axis)
        return (tuple(xh[l] for l in br_levels),
                tuple(xtop[l] for l in top_levels))

    sweep_spec = (tuple(sh for _ in br_levels),
                  tuple(rep for _ in top_levels))

    if comm.startswith("halo-plan"):
        _, tot = _hp_payload_layout(dshape, 1)
        deltas = tuple(sorted(tot))

        def s_exchange(d, xt, sweep):
            xh, _ = to_dicts(sweep)
            chunks = _hp_pack_exchange(dshape, d, xh,
                                       xt.reshape(nl, m, -1), axis, comm,
                                       merged=fused)
            return tuple(chunks[dl] for dl in deltas)

        payload_spec = tuple(sh for _ in deltas)

        def s_coupling(d, xt, sweep, payload):
            xh, xtop = to_dicts(sweep)
            yh, ytop, yde = _coupling_phase_overlap(
                dshape, d, xh, xtop, xt.reshape(nl, m, -1), axis, comm,
                chunks=dict(zip(deltas, payload)), hide_flops=hide)
            return (tuple(yh[l] for l in br_levels),
                    tuple(ytop[l] for l in range(lc)), yde)
    else:
        ag_levels = tuple(l for l in br_levels if dshape.ranks[l] > 0)

        def s_exchange(d, xt, sweep):
            xh, _ = to_dicts(sweep)
            gl = tuple(jax.lax.all_gather(xh[l], axis, tiled=True)
                       for l in ag_levels)
            gde = jax.lax.all_gather(xt.reshape(nl, m, -1), axis,
                                     tiled=True)
            return gl, gde

        payload_spec = (tuple(rep for _ in ag_levels), rep)

        def s_coupling(d, xt, sweep, payload):
            xh, xtop = to_dicts(sweep)
            gl, gde = payload
            yh, ytop = _coupling_phase(dshape, d, xh, xtop, axis, comm,
                                       gathered=dict(zip(ag_levels, gl)))
            yde = _dense_phase(dshape, d, xt.reshape(nl, m, -1), axis,
                               comm, gathered=gde)
            return (tuple(yh[l] for l in br_levels),
                    tuple(ytop[l] for l in range(lc)), yde)

    coupled_spec = (tuple(sh for _ in br_levels),
                    tuple(rep for _ in range(lc)), sh)

    def s_downsweep(d, coupled):
        yh_t, ytop_t, yde = coupled
        y_lr = _local_downsweep(dshape, d, dict(zip(br_levels, yh_t)),
                                dict(zip(range(lc), ytop_t)), axis)
        return (y_lr + yde).reshape(dshape.n_local(), -1)[:, 0]

    if fused:
        def s_transpose_out(aux, kut):
            ku, _ = transpose_a2a(kut, aux["tout_send"],
                                  aux["tout_take"], axis)
            return ku

        def s_stencil(mga, x, ku, top, bot):
            u = x.reshape(n // p, n)
            local = _mg_apply_op(mg, mga, 0, u, axis,
                                 halo=(top, bot)).reshape(x.shape)
            return (h * h) * (ku + local)

        sten_in, sten_inputs = (mg_spec, sh, sh, sh, sh), \
            ("mga", "xvec", "ku", "top", "bot")
    else:
        def s_transpose_out(aux, kut):
            kf = jax.lax.all_gather(kut, axis, axis=0, tiled=True) \
                if p > 1 else kut
            return jnp.take(kf, aux["unperm"], axis=0)

        def s_stencil(mga, x, ku):
            u = x.reshape(n // p if p > 1 else n, n)
            local = _mg_apply_op(mg, mga, 0, u, axis).reshape(x.shape)
            return (h * h) * (ku + local)

        sten_in, sten_inputs = (mg_spec, sh, sh), ("mga", "xvec", "ku")

    def s_precond(mga, r):
        return mg_precond_local(mg, mga, r, axis, fused=fused, bf16=bf16)

    def s_scalars(x, r, pv, z, ap, rz):
        # the PCG body minus apply_a/precond: psum'd dots + axpys
        pap = _dot(pv, ap, axis)
        alpha = rz / jnp.where(pap != 0, pap, 1.0)
        x2 = x + alpha * pv
        r2 = r - alpha * ap
        res = _norm(r2, axis)
        rz2 = _dot(r2, z, axis)
        beta = rz2 / jnp.where(rz != 0, rz, 1.0)
        p2 = z + beta * pv
        return x2, r2, p2, rz2, res

    defs = [
        ("solve/transpose-in", s_transpose_in, (aux_spec, sh), tin_out,
         ("aux", "xvec"), tin_outputs),
        ("hgemv/upsweep", s_upsweep, (dspec, shv), sweep_spec,
         ("d", "xt"), ("sweep",)),
        ("hgemv/exchange", s_exchange, (dspec, shv, sweep_spec),
         payload_spec, ("d", "xt", "sweep"), ("payload",)),
        ("hgemv/coupling-gemm", s_coupling,
         (dspec, shv, sweep_spec, payload_spec), coupled_spec,
         ("d", "xt", "sweep", "payload"), ("coupled",)),
        ("hgemv/downsweep", s_downsweep, (dspec, coupled_spec), sh,
         ("d", "coupled"), ("kut",)),
        ("solve/transpose-out", s_transpose_out, (aux_spec, sh), sh,
         ("aux", "kut"), ("ku",)),
        ("solve/stencil", s_stencil, sten_in, sh,
         sten_inputs, ("ap",)),
        ("precond/vcycle", s_precond, (mg_spec, sh), sh,
         ("mga", "r"), ("z",)),
        ("krylov/scalars", s_scalars, (sh, sh, sh, sh, sh, rep),
         (sh, sh, sh, rep, rep),
         ("xvec", "r", "pvec", "z", "ap", "rz"),
         ("x2", "r2", "p2", "rz2", "res")),
    ]

    stages = [Stage(name, shmap(body, in_specs, out_specs),
                    inputs, outputs)
              for name, body, in_specs, out_specs, inputs, outputs in defs]

    # truncated-loop programs for differential timing: loop_k runs
    # ``loop_m`` fori_loop iterations of stages 1..k inside ONE shard_map,
    # so ``(T(loop_k) - T(loop_{k-1})) / loop_m`` is phase k's *marginal
    # in-loop* cost — the same thing one extra phase costs the fused
    # while-loop (warm caches, loop-carried scheduling), with the
    # per-dispatch replay overhead amortized away.  A single dispatched
    # iteration measures 1.5-2x the marginal one on the fake-device CPU
    # mesh, so stage-at-a-time replay can never sum to the fused time;
    # this construction telescopes to it by design.  Each iteration folds
    # a ~1e-30-scaled sum of every truncated-frontier output back into the
    # carried vectors: numerically nothing, but a real data dependence, so
    # no stage is loop-invariant and nothing gets hoisted out of the loop.
    ext_specs = (dspec, aux_spec, mg_spec, sh, sh, sh, rep)
    last_use: Dict[str, int] = {}
    for i, (_, _, _, _, inputs, _) in enumerate(defs):
        for nm in inputs:
            last_use[nm] = i

    def make_loop(k):
        # outputs no later truncated stage consumes (or nothing consumes):
        # these must feed the carry or dead-code elimination drops their
        # producing stage from loop_k entirely
        kept = [i for i in range(k)
                if any(nm not in last_use or last_use[nm] >= k
                       for nm in defs[i][5])]

        def prog(d, aux, mga, xvec, r, pvec, rz):
            def it(_, carry):
                xv, rr, pv, zz = carry
                local = {"d": d, "aux": aux, "mga": mga, "xvec": xv,
                         "r": rr, "pvec": pv, "rz": zz}
                s = jnp.sum(xv) * 1e-30
                for i, (_, fn, _, _, inputs, outputs) in \
                        enumerate(defs[:k]):
                    res = fn(*(local[nm] for nm in inputs))
                    if len(outputs) == 1:
                        local[outputs[0]] = res
                    else:
                        local.update(zip(outputs, res))
                    if i in kept:
                        s = s + sum(
                            jnp.sum(leaf).astype(jnp.float32) * 1e-30
                            for leaf in jax.tree_util.tree_leaves(res))
                return (xv + s, rr + s, pv + s, zz + s)
            return jax.lax.fori_loop(0, loop_m, it,
                                     (xvec, r, pvec, rz))
        return shmap(prog, ext_specs, (sh, sh, sh, rep))

    # loop_0 is the baseline: dispatch + loop scaffolding + the carry
    # injection, so the differences charge none of that to any phase
    loops = [make_loop(k) for k in range(len(defs) + 1)]
    return stages, loops


def stage_env(parts: Dict, mesh, b) -> Dict:
    """Initial replay environment: placed operator args + b-seeded solver
    vectors (values only set operand magnitudes, not stage cost)."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import NamedSharding, PartitionSpec as P

    d, aux, mga = parts["place"](parts["args"])
    axis = parts["axis"]
    vec = jax.device_put(b, NamedSharding(mesh, P(axis)))
    rz = jax.device_put(jnp.float32(1.0), NamedSharding(mesh, P()))
    return {"d": d, "aux": aux, "mga": mga, "xvec": vec, "r": vec,
            "pvec": vec, "rz": rz}


def profile_stages(parts: Dict, mesh, b, comm: str, reps: int = 8,
                   loop_m: int = 12):
    """Build + warm + time the replay pipeline by truncated-loop
    differencing.

    The loop programs (``loop_m`` iterations of stages 1..k each) are
    timed in interleaved rounds with fixed inputs; phase k's per-iteration
    time is the median over rounds of ``(T(loop_k) - T(loop_{k-1})) /
    loop_m`` (clamped at 0 — the difference of two noisy measurements),
    which both cancels the fixed per-dispatch replay cost and measures the
    *marginal in-loop* phase cost the fused while-loop actually pays.
    Returns ``(stages, env, phase_secs, cum_secs)``: the single-stage
    programs (for per-stage collective-byte measurement), the populated
    replay env, {phase: seconds per iteration}, and the cumulative loop
    medians (whole-program seconds) keyed by phase.
    """
    import numpy as np

    from repro.obs.timers import interleaved_times, run_stages

    stages, loops = build_solve_stages(parts, mesh, comm, loop_m=loop_m)
    env = stage_env(parts, mesh, b)
    run_stages(stages, env)                    # compile + populate env
    ext = tuple(env[k] for k in EXT_INPUTS)
    fns = {f"p{k}": (lambda lp=lp: lp(*ext))
           for k, lp in enumerate(loops)}
    acc = interleaved_times(fns, reps=reps, warmup=1)
    phase_secs, cum_secs = {}, {}
    for k, ph in enumerate(PHASE_ORDER, start=1):
        diffs = [a - b_ for a, b_ in zip(acc[f"p{k}"], acc[f"p{k - 1}"])]
        phase_secs[ph] = max(float(np.median(diffs)), 0.0) / loop_m
        cum_secs[ph] = float(np.median(acc[f"p{k}"]))
    return stages, env, phase_secs, cum_secs


def _worker(args: argparse.Namespace) -> None:
    os.environ["XLA_FLAGS"] = (
        f"--xla_force_host_platform_device_count={args.p} "
        + os.environ.get("XLA_FLAGS", ""))
    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.sharding import NamedSharding, PartitionSpec as P

    from repro.apps.fractional import (FractionalProblem,
                                       dist_solve_comm_bytes,
                                       make_dist_solve)
    from repro.obs import metrics
    from repro.obs.timers import interleaved_times, run_stages

    n = args.n or (16 if args.quick else 32)
    mesh = jax.make_mesh((args.p,), ("blk",))
    prob = FractionalProblem(n).build()
    b = jnp.ones((n * n,), jnp.float32) * prob["h"] ** 2
    b_dev = jax.device_put(b, NamedSharding(mesh, P("blk")))
    comms = tuple(args.comms.split(","))

    solves: Dict[str, tuple] = {}
    for comm in comms:
        parts = make_dist_solve(prob, mesh, comm=comm, tol=args.tol,
                                maxiter=args.maxiter)
        pargs = parts["place"](parts["args"])
        res = jax.block_until_ready(parts["fn"](*pargs, b_dev))
        assert bool(res.converged), (n, comm, float(res.relres))
        solves[comm] = (parts, pargs, int(res.iters))

    # ONE interleaved timing set over every comm mode's fused solve and
    # truncated-loop programs: within a round all of them see the same
    # machine state, so the coverage ratios and the cross-mode gap table
    # are insensitive to the shared host's throughput drift
    loop_m = 12
    built: Dict[str, tuple] = {}
    fns: Dict[str, object] = {}
    for comm in comms:
        parts, pargs, iters = solves[comm]
        stages, loops = build_solve_stages(parts, mesh, comm,
                                           loop_m=loop_m)
        env = stage_env(parts, mesh, b)
        run_stages(stages, env)                # compile + populate env
        ext = tuple(env[k] for k in EXT_INPUTS)
        built[comm] = (stages, env)
        fns[f"{comm}|solve"] = (
            lambda parts=parts, pargs=pargs: parts["fn"](*pargs, b_dev))
        for k, lp in enumerate(loops):
            fns[f"{comm}|p{k}"] = (lambda lp=lp, ext=ext: lp(*ext))
    acc = interleaved_times(fns, reps=10 if args.quick else 16, warmup=1)

    doc: Dict = {"bench": "solver_phases", "n": n, "N": n * n,
                 "p": args.p, "tol": args.tol, "maxiter": args.maxiter,
                 "phase_order": list(PHASE_ORDER), "summary": {},
                 "phases": []}
    phase_us_by_comm: Dict[str, Dict[str, float]] = {}
    for comm in comms:
        parts, pargs, iters = solves[comm]
        stages, env = built[comm]
        phase_us, cum_us = {}, {}
        for k, ph in enumerate(PHASE_ORDER, start=1):
            diffs = [a - b_ for a, b_ in zip(acc[f"{comm}|p{k}"],
                                             acc[f"{comm}|p{k - 1}"])]
            phase_us[ph] = max(float(np.median(diffs)), 0.0) / loop_m * 1e6
            cum_us[ph] = float(np.median(acc[f"{comm}|p{k}"])) * 1e6
        phase_us_by_comm[comm] = phase_us
        model = phase_comm_model(parts["dshape"], parts["mg"], comm,
                                 tcaps=parts.get("tcaps"),
                                 fused=parts.get("fused"))
        records = []
        for s in stages:
            sargs = tuple(env[k] for k in s.inputs)
            records.append(metrics.phase_record(
                s.phase, us=round(phase_us[s.phase], 1), fn=s.fn,
                args=sargs, model_comm_bytes=model[s.phase], p=args.p,
                comm=comm, us_loop_cum=round(cum_us[s.phase], 1)))
        doc["phases"] += [r.to_dict() for r in records]

        whole_us = float(np.median(acc[f"{comm}|solve"])) * 1e6
        # the per-phase sum, telescoped: sum_k (T_k - T_{k-1}) == T_9 - T_0
        # identically, so the per-round (T_9 - T_0)/m median IS the
        # per-phase sum without the upward bias the per-phase clamping
        # (max(diff, 0)) adds to the displayed table rows
        kmax = len(PHASE_ORDER)
        per_iter = float(np.median(
            [(a - b_) / loop_m for a, b_ in
             zip(acc[f"{comm}|p{kmax}"], acc[f"{comm}|p0"])])) * 1e6
        # the solve = iters full iterations + the PCG prologue (initial
        # precond + the first dots/norms)
        attributed = per_iter * iters \
            + phase_us["precond/vcycle"] + phase_us["krylov/scalars"]
        doc["summary"][comm] = {
            "iters": iters,
            "whole_solve_us": round(whole_us, 1),
            "whole_us_per_iter": round(whole_us / max(iters, 1), 1),
            "stage_sum_us_per_iter": round(per_iter, 1),
            "clamped_sum_us_per_iter": round(sum(phase_us.values()), 1),
            "loop_m": loop_m,
            "full_loop_us": round(cum_us["krylov/scalars"], 1),
            "loop_baseline_us": round(
                float(np.median(acc[f"{comm}|p0"])) * 1e6, 1),
            "attributed_us": round(attributed, 1),
            "coverage": round(attributed / whole_us, 3),
            "fused": bool(parts.get("fused")),
            "model_comm_bytes_per_iter": dist_solve_comm_bytes(
                parts["dshape"], parts["mg"], comm,
                tcaps=parts.get("tcaps"), fused=parts.get("fused")),
        }

    if "halo-plan" in phase_us_by_comm and "allgather" in phase_us_by_comm:
        hp, ag = (phase_us_by_comm["halo-plan"],
                  phase_us_by_comm["allgather"])
        gap = [{"phase": ph, "halo_plan_us": round(hp[ph], 1),
                "allgather_us": round(ag[ph], 1),
                "delta_us": round(hp[ph] - ag[ph], 1)}
               for ph in PHASE_ORDER]
        gap.sort(key=lambda g: -g["delta_us"])
        doc["gap"] = gap
        doc["gap_phases"] = [g["phase"] for g in gap if g["delta_us"] > 0]
    doc["platform"] = jax.default_backend()
    print(MARKER + json.dumps(doc))


def run_profile(argv: Optional[Sequence[str]] = None) -> Dict:
    """Fork the device-forcing worker, collect the report document."""
    args = _parse(argv)
    root = os.path.abspath(os.path.join(os.path.dirname(__file__),
                                        "..", "..", ".."))
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.join(root, "src") + (
        os.pathsep + env["PYTHONPATH"] if env.get("PYTHONPATH") else "")
    # fake host devices by design: the child never reaches for a chip
    # (which its parent may hold)
    env["JAX_PLATFORMS"] = "cpu"
    cmd = [sys.executable, "-m", "repro.obs.profile_solve", "--worker",
           "--p", str(args.p), "--maxiter", str(args.maxiter),
           "--tol", str(args.tol), "--comms", args.comms]
    if args.quick:
        cmd.append("--quick")
    if args.n:
        cmd += ["--n", str(args.n)]
    proc = subprocess.run(cmd, capture_output=True, text=True,
                          timeout=2400, env=env, cwd=root)
    if proc.returncode != 0:
        raise RuntimeError(f"profile_solve worker failed:\n{proc.stdout}"
                           f"\n{proc.stderr}")
    doc = None
    for line in proc.stdout.splitlines():
        if line.startswith(MARKER):
            doc = json.loads(line[len(MARKER):])
    assert doc is not None, proc.stdout
    return doc


def write_outputs(doc: Dict, json_path: str, trace_path: str) -> None:
    from repro.obs.export import write_chrome_trace

    with open(json_path, "w") as f:
        json.dump(doc, f, indent=1)
    lanes = []
    for comm, summ in doc["summary"].items():
        phase_us = {r["phase"]: r["us"] for r in doc["phases"]
                    if r.get("comm") == comm}
        lanes.append({"lane": comm, "phase_us": phase_us,
                      "iters": summ["iters"]})
    write_chrome_trace(trace_path, lanes)


def _parse(argv: Optional[Sequence[str]] = None) -> argparse.Namespace:
    ap = argparse.ArgumentParser(
        description="per-phase profile of the distributed fractional "
                    "solve (segmented replay at p=8)")
    ap.add_argument("--worker", action="store_true")
    ap.add_argument("--quick", action="store_true",
                    help="CI smoke tier (n=16, fewer rounds)")
    ap.add_argument("--n", type=int, default=0,
                    help="grid side (default 32; 16 with --quick)")
    ap.add_argument("--p", type=int, default=8)
    ap.add_argument("--maxiter", type=int, default=200)
    ap.add_argument("--tol", type=float, default=1e-8)
    ap.add_argument("--comms", default="halo-plan,allgather")
    ap.add_argument("--json", default="BENCH_solver_phases.json")
    ap.add_argument("--trace", default="BENCH_solver_phases_trace.json")
    return ap.parse_args(argv)


def main(argv: Optional[Sequence[str]] = None) -> None:
    args = _parse(argv)
    if args.worker:
        _worker(args)
        return
    doc = run_profile(argv)
    write_outputs(doc, args.json, args.trace)
    for comm, summ in doc["summary"].items():
        print(f"# {comm}: {summ['iters']} iters, "
              f"{summ['whole_us_per_iter']} us/iter fused, "
              f"{summ['stage_sum_us_per_iter']} us/iter replayed, "
              f"coverage {summ['coverage']}")
    for g in doc.get("gap", [])[:3]:
        print(f"# gap {g['phase']}: {g['delta_us']:+.1f} us/iter "
              f"(halo-plan {g['halo_plan_us']} vs allgather "
              f"{g['allgather_us']})")
    print(f"# wrote {args.json} + {args.trace}")


if __name__ == "__main__":
    main()
