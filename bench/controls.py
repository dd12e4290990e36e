"""Readings that set a cell's limits: the program over many seeds, the
precision control, and the planted faults.

    python3 bench/controls.py --workload <cell> --seeds 12 \\
        --control-seeds 3 --units 6 --out <file.json>

One process builds the cell's system once, then for each seed runs the
cell's driver (set-up from that seed, ``--units`` units, the comparison).
For each control seed it reads, from that seed's run, the driver's
``control``: the plain reference in the program's place with its products
at the ``high`` matmul precision (three bf16 passes, the step below the
configurations' ``highest``).  For each of the driver's ``FAULTS`` it
plants the fault (``planted``) and reads the numbers again.  The limits in
``bench/limits/<cell>.json`` are set from these readings.  Runs on
whatever device JAX has (the chip for real readings).
"""
from __future__ import annotations

import argparse
import collections
import json
import sys
import time
import types
from pathlib import Path

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent


def no_limits() -> dict:
    return collections.defaultdict(lambda: float("inf"))


def readings(op, system, cfg, traffic, seed, units):
    """Set-up from ``seed``, ``units`` units, the comparison: ``(state,
    outputs, numbers)``."""
    state = op.setup(system, cfg, traffic, seed)
    outputs = [op.unit(state, i) for i in range(units)]
    if hasattr(op, "drain"):
        op.drain(state)
    op.summarize(state, outputs, 1.0)
    op.release(state)
    return state, outputs, op.check(state, outputs, cfg, traffic, seed,
                                    no_limits())


def planted(op, fault: str, frac: float = 1e-3):
    """A stand-in for the driver ``op`` whose set-up plants ``fault``
    under the entry the window calls (``op.plant``)."""
    def setup(*a, **k):
        state = op.setup(*a, **k)
        op.plant(state, fault, frac)
        return state

    return types.SimpleNamespace(**dict(vars(op), setup=setup))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, default=12)
    ap.add_argument("--control-seeds", type=int, default=3)
    ap.add_argument("--units", type=int, default=6)
    ap.add_argument("--first-seed", type=int, default=2**31 + 11)
    ap.add_argument("--precision", default="high")
    ap.add_argument("--skip", default="", help="comma list of: program,"
                    "control,faults")
    ap.add_argument("--out", required=True)
    args = ap.parse_args(argv)
    sys.path[:0] = [str(ROOT), str(ROOT / "src")]
    import jax
    from bench.run import Cell, read_json
    from repro.launch.compile_cache import enable_compile_cache

    enable_compile_cache(ROOT)
    cell = Cell(args.workload, read_json(ROOT / "BENCHMARK.json"))
    op = cell.op
    skip = set(args.skip.split(","))
    out = {"workload": args.workload, "device": jax.devices()[0].device_kind,
           "precision": args.precision, "program": {}, "control": {},
           "faults": {}}

    def save():
        Path(args.out).parent.mkdir(parents=True, exist_ok=True)
        Path(args.out).write_text(json.dumps(out, indent=1))

    def values(r):
        return {k: v["value"] for k, v in r.items()}

    t0 = time.perf_counter()
    system = cell.system.build(cell.config)
    out["build_s"] = time.perf_counter() - t0
    seeds = [args.first_seed + 7919 * k for k in range(args.seeds)]
    for k, s in enumerate(seeds):
        if "program" in skip and k >= args.control_seeds:
            break
        state, outputs, r = readings(op, system, cell.config, cell.traffic,
                                     s, args.units)
        if "program" not in skip:
            out["program"][str(s)] = values(r)
            print("program", s, out["program"][str(s)], flush=True)
        if "control" not in skip and k < args.control_seeds:
            c = op.control(state, len(outputs), cell.config, cell.traffic,
                           s, args.precision, no_limits())
            out["control"][str(s)] = values(c)
            print("control", s, out["control"][str(s)], flush=True)
        save()
    if "faults" not in skip:
        for fault in op.FAULTS:
            broken = planted(op, fault)
            for s in seeds[:args.control_seeds]:
                _, _, r = readings(broken, system, cell.config, cell.traffic,
                                   s, args.units)
                out["faults"].setdefault(fault, {})[str(s)] = values(r)
                print("fault", fault, s, out["faults"][fault][str(s)],
                      flush=True)
                save()
    out["total_s"] = time.perf_counter() - t0
    save()
    return 0


if __name__ == "__main__":
    sys.exit(main())
