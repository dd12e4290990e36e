"""Distributed fractional-diffusion solver benchmark (paper §6.4 workload).

Times the end-to-end distributed PCG solve — the whole Krylov iteration
(halo-plan H^2 matvec, sharded stencil V-cycle preconditioner, psum dot
products) inside ONE jitted shard_map program (`repro/solvers/`,
`apps/fractional.py::make_dist_solve`) — on 8 fake host devices, for the
``halo-plan`` compressed-exchange matvec vs the ``allgather`` baseline, at
two problem sizes per tier.  Reported per record: iterations to tolerance,
wall time per solve and per iteration, and the modeled per-device
collective bytes per iteration (`dist_solve_comm_bytes`).

Methodology matches `benchmarks/dist_bench.py` and routes through
`repro.obs.timers`: the comm modes are timed in interleaved rounds and the
speedup row is the **median of per-round ratios**, which cancels the
shared host's throughput drift.  Each record additionally carries a
``phases`` dict — the dispatch-corrected per-phase µs of one Krylov
iteration from the segmented replay (`repro.obs.profile_solve`), so the
whole-solve regression this benchmark reports is localized in the same
JSON that reports it.  Device count must be fixed before jax initializes,
so the measurement runs in a subprocess (`--worker`).

Since ISSUE 10 the halo-plan records run the FUSED iteration schedule by
default (``make_dist_solve``'s ``fused`` default; DESIGN.md §12) while
allgather stays two-step, so ``frac_solve_speedup_*``'s
``halo_plan_vs_allgather`` is the headline end-to-end ratio the fused
restructuring must keep >= 1 — ``benchmarks.run``'s baseline check
treats any value below 1.0 as an (absolute, non-fatal) tripwire hit.

Set ``REPRO_BENCH_QUICK=1`` (or ``benchmarks.run --quick``) for the CI
smoke tier (n in {16, 32}; the full tier runs n in {32, 64}).
"""
from __future__ import annotations

import json
import os
import subprocess
import sys
from typing import Dict, List, Optional

MARKER = "SOLVER_BENCH_JSON:"


def _worker(quick: bool) -> None:
    os.environ["XLA_FLAGS"] = (
        "--xla_force_host_platform_device_count=8 "
        + os.environ.get("XLA_FLAGS", ""))
    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.sharding import NamedSharding, PartitionSpec as P

    from repro.apps.fractional import (FractionalProblem,
                                       dist_solve_comm_bytes,
                                       make_dist_solve)
    from repro.obs.profile_solve import profile_stages
    from repro.obs.timers import interleaved_times, median_ratio

    p = 8
    mesh = jax.make_mesh((p,), ("blk",))
    records: List[Dict] = []
    ns = (16, 32) if quick else (32, 64)
    comms = ("halo-plan", "allgather")
    for n in ns:
        prob = FractionalProblem(n).build()
        b = jnp.ones((n * n,), jnp.float32) * prob["h"] ** 2
        b_dev = jax.device_put(b, NamedSharding(mesh, P("blk")))
        solvers: Dict[str, tuple] = {}
        for comm in comms:
            parts = make_dist_solve(prob, mesh, comm=comm, tol=1e-8,
                                    maxiter=200)
            args = parts["place"](parts["args"])
            res = jax.block_until_ready(parts["fn"](*args, b_dev))
            assert bool(res.converged), (n, comm, float(res.relres))
            solvers[comm] = (parts, args, int(res.iters),
                             float(res.relres))
        it0 = {c: solvers[c][2] for c in comms}
        # the comm modes reassociate the same sums — and fused halo-plan
        # additionally pins the combined-GEMM association where auto used
        # to split — so a residual hovering at the tol crossing may
        # legitimately shift the count by a few steps (exact fused-vs-
        # two-step parity per comm is pinned in tests/dist_worker.py)
        assert abs(it0["halo-plan"] - it0["allgather"]) <= 5, it0

        acc = interleaved_times(
            {comm: (lambda comm=comm: solvers[comm][0]["fn"](
                *solvers[comm][1], b_dev)) for comm in comms},
            reps=6 if quick else 10, warmup=0)  # parity gate warmed up
        for comm in comms:
            parts, _, iters, relres = solvers[comm]
            us = float(np.median(acc[comm])) * 1e6
            _, _, corrected, _ = profile_stages(
                parts, mesh, b, comm, reps=4 if quick else 6)
            records.append({
                "name": f"frac_solve_n{n}_{comm}",
                "n": n, "N": n * n, "p": p, "comm": comm,
                "fused": bool(parts["fused"]),
                "iters": iters, "relres": relres,
                "us_per_solve": round(us, 1),
                "us_per_iter": round(us / max(iters, 1), 1),
                "model_bytes_per_iter": dist_solve_comm_bytes(
                    parts["dshape"], parts["mg"], comm,
                    tcaps=parts["tcaps"], fused=parts["fused"]),
                "phases": {ph: round(sec * 1e6, 1)
                           for ph, sec in corrected.items()},
            })
        records.append({
            "name": f"frac_solve_speedup_n{n}",
            "n": n, "N": n * n, "p": p, "iters": it0["halo-plan"],
            "halo_plan_vs_allgather": round(
                median_ratio(acc["allgather"], acc["halo-plan"]), 2),
        })
    for r in records:
        r["platform"] = jax.default_backend()
    print(MARKER + json.dumps(records))


def run(out_rows: List[str], records: Optional[List[Dict]] = None) -> None:
    quick = os.environ.get("REPRO_BENCH_QUICK", "0") == "1"
    root = os.path.abspath(os.path.join(os.path.dirname(__file__), ".."))
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.join(root, "src") + (
        os.pathsep + env["PYTHONPATH"] if env.get("PYTHONPATH") else "")
    # fake host devices by design: the child never reaches for a chip
    # (which its parent may hold)
    env["JAX_PLATFORMS"] = "cpu"
    cmd = [sys.executable, "-m", "benchmarks.solver_bench", "--worker"]
    if quick:
        cmd.append("--quick")
    # below the CI bench-smoke job's 45-min cap so a hung worker surfaces
    # THIS diagnostic path, not an opaque job-level timeout
    proc = subprocess.run(cmd, capture_output=True, text=True, timeout=1800,
                          env=env, cwd=root)
    if proc.returncode != 0:
        raise RuntimeError(f"solver_bench worker failed:\n{proc.stdout}"
                           f"\n{proc.stderr}")
    payload = None
    for line in proc.stdout.splitlines():
        if line.startswith(MARKER):
            payload = json.loads(line[len(MARKER):])
    assert payload is not None, proc.stdout
    for r in payload:
        if "us_per_iter" in r:
            out_rows.append(
                f"{r['name']},{r['us_per_solve']:.1f},"
                f"us_per_iter={r['us_per_iter']};iters={r['iters']};"
                f"bytes_per_iter={r['model_bytes_per_iter']}")
        else:
            out_rows.append(
                f"{r['name']},0.0,"
                f"vs_allgather={r['halo_plan_vs_allgather']}")
        if records is not None:
            records.append(r)


def main() -> None:
    if "--worker" in sys.argv:
        _worker(quick="--quick" in sys.argv
                or os.environ.get("REPRO_BENCH_QUICK", "0") == "1")
        return
    rows: List[str] = []
    records: List[Dict] = []
    run(rows, records)
    for r in rows:
        print(r)
    with open("BENCH_solver.json", "w") as f:
        json.dump(records, f, indent=1)
    print("# wrote BENCH_solver.json")


if __name__ == "__main__":
    main()
