"""Fault-tolerance benchmark: recovery cost of the elastic distributed
solve (DESIGN.md §10) on 8 fake host devices.

Measures, per fault class of the deterministic chaos harness
(``runtime/chaos.py``), against ``apps.fractional.solve_distributed_elastic``
at n=32 (N=1024 unknowns), K=10 iterations per checkpoint segment:

  - **checkpoint overhead**: steady-state cost of the async
    (``block=False``) per-segment ``CheckpointManager.save`` as % of
    median segment wall time — the ISSUE 8 acceptance criterion is
    <= 5% at K=10;
  - **time-to-recover** per fault class (device loss -> shrink-remesh +
    restore; NaN corruption -> rollback): detection to first state ready
    to resume, in ms;
  - **iterations lost** per fault class: re-run work after the restore
    (device loss at a segment boundary loses 0; a corrupted segment
    rolls back exactly K).

Device count must be fixed before jax initializes, so the measurement
runs in a subprocess (``--worker``) — the same pattern as
``benchmarks/dist_bench.py``.  All faults are scheduled (virtual), so the
records are deterministic up to wall-clock noise in the timing fields.
"""
from __future__ import annotations

import json
import os
import subprocess
import sys
import tempfile
from typing import Dict, List, Optional

MARKER = "FAULT_BENCH_JSON:"


def _worker(quick: bool) -> None:
    os.environ["XLA_FLAGS"] = (
        "--xla_force_host_platform_device_count=8 "
        + os.environ.get("XLA_FLAGS", ""))
    import jax
    import numpy as np

    from repro.apps.fractional import solve_distributed_elastic
    from repro.runtime.chaos import ChaosPlan
    from repro.runtime.fault import StragglerMonitor

    p, n, K = 8, 32, 10
    mesh = jax.make_mesh((p,), ("blk",))
    records: List[Dict] = []

    def run(chaos=None, monitor=None, ckpt=True):
        with tempfile.TemporaryDirectory() as d:
            return solve_distributed_elastic(
                n, mesh, h2_tol=1e-6, tol=1e-8,
                ckpt_dir=d if ckpt else None, ckpt_every=K,
                chaos=chaos, monitor=monitor, ckpt_block=False)

    # -- steady state: checkpoint overhead at K=10 (async saves) --------
    run(ckpt=True)                       # warm the jit caches
    res = run(ckpt=True)
    rep = res["report"]
    assert res["converged"] and rep.restarts == 0
    seg_med = sorted(rep.seg_wall_s)[len(rep.seg_wall_s) // 2]
    records.append({
        "name": "fault_ckpt_overhead",
        "us_per_iter": round(seg_med / K * 1e6, 1),
        "ckpt_overhead_pct": round(rep.checkpoint_overhead_pct(), 3),
        "segments": rep.segments_run, "iters": res["iters"],
        "K": K, "n": n, "p": p,
    })

    # -- fault classes: time-to-recover + iterations lost ---------------
    seg_fault = 2                        # fault mid-solve, past warmup
    drills = {
        "device-loss": dict(chaos=ChaosPlan(
            device_loss_at={seg_fault: p // 2})),
        "corruption": dict(chaos=ChaosPlan(nan_at={seg_fault})),
    }
    for kind, kw in drills.items():
        res = run(**kw)
        rep = res["report"]
        assert res["converged"] and rep.restarts == 1, (kind, rep)
        ev = [e for e in rep.events if e.kind == kind]
        assert len(ev) == 1, (kind, rep.events)
        records.append({
            "name": f"fault_recover_{kind}",
            "recover_ms": round(ev[0].recover_s * 1e3, 1),
            "iters_lost": rep.iters_lost(kind),
            "p_from": ev[0].p_from, "p_to": ev[0].p_to,
            "iters": res["iters"], "K": K, "n": n,
        })

    # -- straggler: flagged, zero iterations lost ------------------------
    res = run(chaos=ChaosPlan(straggle_at={seg_fault: 1000.0}),
              monitor=StragglerMonitor(threshold=3.0, warmup=1))
    rep = res["report"]
    assert res["converged"] and rep.restarts == 0
    records.append({
        "name": "fault_straggler",
        "flags": list(rep.straggler_flags),
        "iters_lost": rep.iters_lost("straggler"),
        "iters": res["iters"], "K": K, "n": n,
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
    cmd = [sys.executable, "-m", "benchmarks.fault_bench", "--worker"]
    if quick:
        cmd.append("--quick")
    proc = subprocess.run(cmd, capture_output=True, text=True, timeout=3000,
                          env=env, cwd=root)
    if proc.returncode != 0:
        raise RuntimeError(f"fault_bench worker failed:\n{proc.stdout}"
                           f"\n{proc.stderr}")
    payload = None
    for line in proc.stdout.splitlines():
        if line.startswith(MARKER):
            payload = json.loads(line[len(MARKER):])
    assert payload is not None, proc.stdout
    for r in payload:
        if r["name"] == "fault_ckpt_overhead":
            out_rows.append(
                f"{r['name']},{r['us_per_iter']:.1f},"
                f"overhead_pct={r['ckpt_overhead_pct']};K={r['K']}")
        elif "recover_ms" in r:
            out_rows.append(
                f"{r['name']},0.0,recover_ms={r['recover_ms']};"
                f"iters_lost={r['iters_lost']};"
                f"p={r['p_from']}to{r['p_to']}")
        else:
            out_rows.append(
                f"{r['name']},0.0,flags={r['flags']};"
                f"iters_lost={r['iters_lost']}")
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
    with open("BENCH_fault.json", "w") as f:
        json.dump(records, f, indent=1)
    print("# wrote BENCH_fault.json")


if __name__ == "__main__":
    main()
