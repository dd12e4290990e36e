"""CPU rehearsal of each traffic mix at a tiny size, through the pieces the
harness calls (builder, driver set-up, window, comparison, metrics)."""
import json
import sys
from pathlib import Path

import pytest

BENCH = Path(__file__).resolve().parents[1]
ROOT = BENCH.parent
TINY = BENCH / "tests" / "data" / "tiny"
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

from bench import run as harness  # noqa: E402

SPEC = {
    "workloads": [
        {"name": "frac2d-n16.solve", "config": "frac2d-n16",
         "traffic": "solve", "chips": 1},
        {"name": "cov2d-n32.apply8", "config": "cov2d-n32",
         "traffic": "apply8", "chips": 1},
        {"name": "cov2d-n32.compress", "config": "cov2d-n32",
         "traffic": "compress", "chips": 1},
    ],
    "end_to_end": [
        {"name": "solve_s", "unit": "s", "workloads": ["frac2d-n16.solve"]},
        {"name": "apply_ms", "unit": "ms",
         "workloads": ["cov2d-n32.apply8"]},
        {"name": "compress_s", "unit": "s",
         "workloads": ["cov2d-n32.compress"]},
        {"name": "setup_s", "unit": "s"},
        {"name": "peak_hbm_gib", "unit": "GiB"},
    ],
    "per_layer": [
        {"name": "pcg_iters.solve", "unit": "iters",
         "workloads": ["frac2d-n16.solve"]},
        {"name": "idle_pct.solve", "unit": "%",
         "workloads": ["frac2d-n16.solve"]},
        {"name": "hgemv_ms.apply", "unit": "ms",
         "workloads": ["cov2d-n32.apply8"]},
        {"name": "idle_pct.apply", "unit": "%",
         "workloads": ["cov2d-n32.apply8"]},
        {"name": "idle_pct.compress", "unit": "%",
         "workloads": ["cov2d-n32.compress"]},
    ],
}


def run_tiny(name, seed=2**31 + 7, seconds=1.0, trace=False):
    import jax
    cell = harness.Cell(name, SPEC, data=TINY)
    return harness.run(cell, seed, seconds, trace, jax, jax.devices()[:1],
                       {}, log=sys.stderr)


@pytest.mark.parametrize("name, metric", [
    ("frac2d-n16.solve", "solve_s"), ("cov2d-n32.apply8", "apply_ms"),
    ("cov2d-n32.compress", "compress_s")])
def test_tiny_cell_runs_and_is_correct(name, metric):
    res = run_tiny(name)
    json.dumps(res)
    assert res["correct"], res["compared"]
    assert res["attempted"] >= 1 and res["failed"] == 0
    assert set(res["metrics"]) == {metric, "setup_s", "peak_hbm_gib"}
    assert list(res)[-1] == "compared"


@pytest.mark.parametrize("name, idle", [
    ("frac2d-n16.solve", "idle_pct.solve"),
    ("cov2d-n32.apply8", "idle_pct.apply"),
    ("cov2d-n32.compress", "idle_pct.compress")])
def test_tiny_cell_traced_run_reports_its_layers(name, idle):
    """The CPU trace has no device plane and no scope paths: the readers
    that need a scope leave their metric out; the others report."""
    res = run_tiny(name, seconds=0.5, trace=True)
    json.dumps(res)
    assert res["correct"], res["compared"]
    assert 0.0 <= res["metrics"][idle]["value"] <= 100.0
    assert 0.0 < res["device"]["busy_s"] <= res["device"]["window_s"]
    assert len(res["breakdown"]["device_ops"]) <= 10
    assert len(res["breakdown"]["idle_gaps"]) <= 10
