"""Benchmark harness: one module per paper table/figure.

    PYTHONPATH=src python -m benchmarks.run [--only hgemv,compression_bench]
                                            [--quick] [--json-dir DIR]
                                            [--baseline BENCH.json]

Prints ``name,us_per_call,derived`` CSV rows.  Modules whose ``run``
accepts a second argument also emit machine-readable records, written as
``BENCH_<module>.json`` (a list of dicts; for hgemv: µs, model GFLOP/s, N,
nv, backend) — the perf trajectory consumed by CI and future PRs.  The
roofline table (dry-run derived, 256/512-device) is produced separately by
``benchmarks/roofline.py`` from ``dryrun_results.json``.

``--baseline`` loads a previous run's BENCH json (any of the emitted
files, or a ``repro.obs.profile_solve`` document) and prints non-fatal
``# WARN`` rows for records whose timing keys regressed by more than 20%
vs the record of the same name — a shared-CI-runner-tolerant tripwire,
not a gate.
"""
from __future__ import annotations

import argparse
import inspect
import json
import os
import sys
import traceback
from pathlib import Path
from typing import Dict, List, Optional

MODULES = ["accuracy", "hgemv", "compression_bench", "construction_bench",
           "dist_bench", "solver_bench", "serve_bench", "fault_bench",
           "guard_bench", "fractional", "lm_step"]

#: per-record wall-time keys compared by ``compare_to_baseline``
#: (p50/p99 are the serving-latency tripwires from BENCH_serve.json)
TIMING_KEYS = ("us", "us_per_solve", "us_per_iter", "p50_ms", "p99_ms")


def _record_key(r: Dict):
    return r.get("name") or (r.get("phase"), r.get("comm"))


def load_baseline(path: str) -> Optional[List[Dict]]:
    """Load a baseline record list from a BENCH json — either a plain
    record list (``benchmarks.run`` output) or a ``profile_solve``
    document (its ``phases`` records are compared by (phase, comm)).

    A missing file returns ``None`` with a loud warning instead of
    raising: a newly-registered module (e.g. serve) has no committed
    baseline on its first run, and that must not abort — or silently
    skip — the tripwire for every other module."""
    if not os.path.exists(path):
        print(f"# WARN baseline file {path!r} not found — baseline "
              "comparison skipped (expected on a module's first run; "
              "commit the fresh BENCH json to arm the tripwire)",
              flush=True)
        return None
    with open(path) as f:
        doc = json.load(f)
    if isinstance(doc, dict):
        doc = doc.get("phases", [])
    return [r for r in doc if isinstance(r, dict)]


def compare_to_baseline(records: List[Dict], baseline: List[Dict],
                        threshold: float = 0.2) -> List[str]:
    """Non-fatal regression check: ``# WARN`` line per timing key (and
    per phase of a ``phases`` breakdown) that exceeds the baseline by
    more than ``threshold`` (relative).  Unknown names are skipped.

    One ABSOLUTE floor rides along (ISSUE 10): any record carrying a
    ``halo_plan_vs_allgather`` end-to-end solver ratio below 1.0 warns
    even without a matching baseline entry — the fused iteration
    schedule exists to keep the compressed exchange ahead of the
    allgather baseline inside the solve, so a sub-1.0 ratio is a
    regression regardless of what the previous run measured."""
    base = {_record_key(b): b for b in baseline}
    warns: List[str] = []
    for r in records:
        ratio = r.get("halo_plan_vs_allgather")
        if isinstance(ratio, (int, float)) and ratio < 1.0:
            warns.append(
                f"# WARN {_record_key(r)} halo_plan_vs_allgather="
                f"{ratio:.2f} < 1.0 — compressed-exchange solve slower "
                "than the allgather baseline (fused-schedule tripwire)")
        b = base.get(_record_key(r))
        if b is None:
            continue
        for k in TIMING_KEYS:
            cur, ref = r.get(k), b.get(k)
            if isinstance(cur, (int, float)) and \
                    isinstance(ref, (int, float)) and ref > 0 \
                    and cur / ref > 1.0 + threshold:
                warns.append(
                    f"# WARN {_record_key(r)} {k}: {cur:.1f} vs baseline "
                    f"{ref:.1f} ({cur / ref:.2f}x)")
        cur_ph, ref_ph = r.get("phases"), b.get("phases")
        if isinstance(cur_ph, dict) and isinstance(ref_ph, dict):
            for ph, cur in cur_ph.items():
                ref = ref_ph.get(ph)
                if isinstance(cur, (int, float)) and \
                        isinstance(ref, (int, float)) and ref > 0 \
                        and cur / ref > 1.0 + threshold:
                    warns.append(
                        f"# WARN {_record_key(r)} phase {ph}: {cur:.1f} vs "
                        f"baseline {ref:.1f} ({cur / ref:.2f}x)")
    return warns


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--only", default=None,
                    help="comma-separated module subset")
    ap.add_argument("--quick", action="store_true",
                    help="smoke configuration (sets REPRO_BENCH_QUICK=1)")
    ap.add_argument("--json-dir", default=".",
                    help="directory for BENCH_<module>.json files")
    ap.add_argument("--baseline", default=None, metavar="BENCH.json",
                    help="previous-run records to diff against; >20%% "
                         "per-key regressions print non-fatal # WARN rows")
    args, _ = ap.parse_known_args()
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache(Path(__file__).resolve().parents[1])
    if args.quick:
        os.environ["REPRO_BENCH_QUICK"] = "1"
    mods = args.only.split(",") if args.only else MODULES
    baseline = load_baseline(args.baseline) if args.baseline else None

    rows: List[str] = []
    all_records: List[Dict] = []
    module_records: Dict[str, List[Dict]] = {}
    print("name,us_per_call,derived")
    failed = []
    for name in mods:
        try:
            mod = __import__(f"benchmarks.{name}", fromlist=["run"])
            before = len(rows)
            records: List[Dict] = []
            if len(inspect.signature(mod.run).parameters) >= 2:
                mod.run(rows, records)
            else:
                mod.run(rows)
            for r in rows[before:]:
                print(r, flush=True)
            if records:
                all_records += records
                module_records[name] = records
                stem = name[:-len("_bench")] if name.endswith("_bench") \
                    else name
                os.makedirs(args.json_dir, exist_ok=True)
                path = os.path.join(args.json_dir, f"BENCH_{stem}.json")
                with open(path, "w") as f:
                    json.dump(records, f, indent=1)
                print(f"# wrote {path}", flush=True)
        except Exception:
            failed.append(name)
            traceback.print_exc()
    if baseline is not None:
        # a module none of whose fresh records match any baseline record
        # has no tripwire coverage — say so loudly instead of silently
        # reporting "no regressions" for it (new modules start this way)
        base_keys = {_record_key(b) for b in baseline}
        for name, recs in module_records.items():
            if not any(_record_key(r) in base_keys for r in recs):
                print(f"# WARN module {name!r}: none of its "
                      f"{len(recs)} records have a baseline entry — "
                      "regressions not checked (new module? commit its "
                      "BENCH json to arm the tripwire)", flush=True)
        warns = compare_to_baseline(all_records, baseline)
        for w in warns:
            print(w, flush=True)
        if not warns:
            print("# baseline check: no >20% regressions", flush=True)
    if failed:
        print(f"FAILED modules: {failed}", file=sys.stderr)
        sys.exit(1)


if __name__ == "__main__":
    main()
