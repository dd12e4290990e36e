"""One run of one benchmark cell on the accelerator.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

The cell's entry in ``BENCHMARK.json`` names a configuration and a traffic
mix.  The harness finds everything by those names, so a new cell needs
data files only:

  bench/configs/<config>.json   the deployment; its ``system`` names the
                                builder in bench/systems/<system>.py
  bench/traffic/<traffic>.json  the mix; its ``op`` names the driver in
                                bench/ops/<op>.py (set-up, one unit of
                                work, the comparison that decides correct,
                                its control and its planted faults)
  bench/limits/<cell>.json      the limit of each compared number
  bench/metrics/<metric>.py     one reader per per-layer metric (falls back
                                to the part of the name before the first
                                ``.``), given the reduced device trace

A run: set-up (build, warm every shape of the cell: ``setup_s``), then
units of work back to back for ``--seconds``, then the comparison with the
plain reference.  With ``--trace 1`` the window is profiled, and lasts the
traffic's ``trace_seconds`` where that is shorter (a trace of a long window
of small operations is too large to read back within a run's time).  The
last line of standard output is one JSON object; the compared numbers and
their limits are the last lines of standard error and the last key of that
object.  Exits 2 without a result where JAX finds no TPU or fewer chips
than the cell asks.
"""
from __future__ import annotations

import argparse
import gc
import importlib.util
import json
import shutil
import sys
import time
from pathlib import Path

T_START = time.perf_counter()
BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent
TRACE_DIR = ROOT / ".bench_trace"
GIB = float(1 << 30)


def load_module(path: Path):
    """Import a harness file by path (names may hold ``-`` and ``.``)."""
    spec = importlib.util.spec_from_file_location(
        "bench_" + path.stem.replace("-", "_").replace(".", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def read_json(path: Path):
    with open(path) as fh:
        return json.load(fh)


class CompileStats:
    """Backend compiles, from JAX's monitoring events (so a compile inside
    the window shows)."""

    def __init__(self, jax):
        self.compiles = 0
        jax.monitoring.register_event_duration_secs_listener(self._duration)

    def _duration(self, event, secs, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            self.compiles += 1


class Cell:
    """Everything one run needs, found by name from ``BENCHMARK.json``."""

    def __init__(self, name: str, spec: dict, bench: Path = BENCH,
                 data: Path = BENCH):
        cells = {w["name"]: w for w in spec["workloads"]}
        if name not in cells:
            raise SystemExit(f"unknown workload {name!r}; known: "
                             f"{sorted(cells)}")
        self.name = name
        self.entry = cells[name]
        self.spec = spec
        self.bench = bench
        self.data = data
        self.config = read_json(data / "configs" /
                                f"{self.entry['config']}.json")
        self.traffic = read_json(data / "traffic" /
                                 f"{self.entry['traffic']}.json")
        self.limits = read_json(data / "limits" / f"{name}.json")
        self.system = load_module(bench / "systems" /
                                  f"{self.config['system']}.py")
        self.op = load_module(bench / "ops" / f"{self.traffic['op']}.py")

    def _for_me(self, metric: dict) -> bool:
        return "workloads" not in metric or self.name in metric["workloads"]

    def end_to_end(self):
        return [m for m in self.spec["end_to_end"] if self._for_me(m)]

    def per_layer(self):
        return [m for m in self.spec["per_layer"] if self._for_me(m)]

    def reader(self, metric_name: str):
        for stem in (metric_name, metric_name.split(".")[0]):
            for where in (self.data, self.bench):
                path = where / "metrics" / f"{stem}.py"
                if path.exists():
                    return load_module(path).read
        raise FileNotFoundError(f"no reader for metric {metric_name!r}")


def device_info(devices):
    d0 = devices[0]
    peak = max(int(d.memory_stats().get("peak_bytes_in_use", 0))
               if d.memory_stats() else 0 for d in devices)
    return {"platform": d0.platform, "kind": d0.device_kind,
            "count": len(devices), "memory_peak_bytes": peak}


def window(op, state, seconds: float, jax):
    """Units of work back to back until ``seconds`` have passed.  A driver
    that dispatches units ahead of the one it waits for has a ``drain``:
    once the time is up nothing more is sent, and the window closes when
    all that was sent has completed.  Returns (outputs, elapsed seconds
    from the first dispatch to the last completion)."""
    outputs = []
    t0 = time.perf_counter()
    i = 0
    while time.perf_counter() - t0 < seconds or not outputs:
        with jax.profiler.TraceAnnotation("bench/unit"):
            outputs.append(op.unit(state, i))
        i += 1
    drain = getattr(op, "drain", None)
    if drain is not None:
        with jax.profiler.TraceAnnotation("bench/unit"):
            drain(state)
    return outputs, time.perf_counter() - t0


def run(cell: Cell, seed: int, seconds: float, trace: bool, jax,
        devices, peaks: dict, log=sys.stderr) -> dict:
    """Set-up, window, comparison and metrics of one run; returns the
    result object (without printing it)."""
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache(ROOT)
    stats = CompileStats(jax)
    system = cell.system.build(cell.config)
    state = cell.op.setup(system, cell.config, cell.traffic, seed)
    del system
    setup_s = time.perf_counter() - T_START
    compiles_before = stats.compiles

    reduced = None
    if trace:
        shutil.rmtree(TRACE_DIR, ignore_errors=True)
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        jax.profiler.start_trace(str(TRACE_DIR), profiler_options=opts)
        try:
            outputs, elapsed = window(
                cell.op, state,
                min(seconds, cell.traffic.get("trace_seconds", seconds)), jax)
        finally:
            jax.profiler.stop_trace()
    else:
        outputs, elapsed = window(cell.op, state, seconds, jax)
    compiles_in_window = stats.compiles - compiles_before
    device = device_info(devices)
    summary = cell.op.summarize(state, outputs, elapsed)
    cell.op.release(state)
    gc.collect()

    if trace:
        tr = load_module(cell.bench / "trace_reduce.py")
        reduced = tr.reduce_dir(TRACE_DIR, n_devices=len(devices))
        shutil.rmtree(TRACE_DIR, ignore_errors=True)

    t_check = time.perf_counter()
    checks = cell.op.check(state, outputs, cell.config, cell.traffic, seed,
                           cell.limits)
    print(f"setup {setup_s:.1f} s, window {elapsed:.1f} s, "
          f"{summary['attempted']} units, comparison "
          f"{time.perf_counter() - t_check:.1f} s", file=log)
    correct = all(c["value"] <= c["limit"] for c in checks.values()) and \
        summary["failed"] == 0 and compiles_in_window == 0

    metrics = {}
    if trace:
        ctx = dict(summary, reduced=reduced, peaks=peaks, cell=cell.name,
                   config=cell.config, traffic=cell.traffic)
        for m in cell.per_layer():
            value = cell.reader(m["name"])(ctx)
            if value is not None:
                metrics[m["name"]] = {"value": value, "unit": m["unit"]}
        device["busy_s"] = reduced.busy_s
        device["window_s"] = reduced.window_s
    else:
        e2e = dict(summary["end_to_end"], setup_s=setup_s,
                   peak_hbm_gib=device["memory_peak_bytes"] / GIB)
        for m in cell.end_to_end():
            metrics[m["name"]] = {"value": e2e[m["name"]], "unit": m["unit"]}

    result = {"correct": bool(correct), "attempted": summary["attempted"],
              "failed": summary["failed"], "metrics": metrics,
              "device": device}
    if trace:
        result["breakdown"] = reduced.breakdown()
    if compiles_in_window:
        print(f"compiles inside the window: {compiles_in_window}", file=log)
    result["compared"] = checks
    for name, c in checks.items():
        print(f"compared {name} = {c['value']!r} limit {c['limit']!r}",
              file=log)
    return result


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    cell = Cell(args.workload, read_json(ROOT / "BENCHMARK.json"))
    sys.path[:0] = [str(ROOT), str(ROOT / "src")]
    import repro  # noqa: F401  the system under test: absent, no run
    import jax
    platform = jax.default_backend()
    if platform != "tpu":
        print(f"bench: needs a TPU; JAX found platform {platform!r}",
              file=sys.stderr)
        return 2
    devices = jax.devices()[:cell.entry["chips"]]
    if len(devices) < cell.entry["chips"]:
        print(f"bench: {args.workload} needs {cell.entry['chips']} chips; "
              f"JAX found {len(jax.devices())}", file=sys.stderr)
        return 2
    peaks = read_json(BENCH / "peaks.json")
    kind = devices[0].device_kind
    if kind not in peaks:
        print(f"bench: no peaks for device kind {kind!r} in peaks.json",
              file=sys.stderr)
        return 2
    result = run(cell, args.seed, args.seconds, bool(args.trace), jax,
                 devices, peaks[kind])
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
