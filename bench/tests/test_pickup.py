"""A new configuration, traffic mix, limits file and metric reader are
found by name, with no edit to any file that is there."""
import json
import shutil
import sys
from pathlib import Path

BENCH = Path(__file__).resolve().parents[1]
ROOT = BENCH.parent
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

from bench import run as harness  # noqa: E402
from test_rehearsal import TINY  # noqa: E402


def test_new_files_are_picked_up_by_name(tmp_path):
    import jax
    cfg = json.loads((TINY / "configs" / "cov2d-n32.json").read_text())
    cfg.update(name="cov2d-n16", leaf=16)
    cfg["grid"]["side"] = 16
    for sub in ("configs", "traffic", "limits", "metrics"):
        (tmp_path / sub).mkdir()
    (tmp_path / "configs" / "cov2d-n16.json").write_text(json.dumps(cfg))
    (tmp_path / "traffic" / "apply2.json").write_text(json.dumps(
        {"op": "apply", "nv": 2, "pool": 2, "probes": 1, "compare": 1,
         "keep_within": 2, "leaves": 4, "blocks": 8, "ahead": 0}))
    shutil.copy(TINY / "limits" / "cov2d-n32.apply8.json",
                tmp_path / "limits" / "cov2d-n16.apply2.json")
    (tmp_path / "metrics" / "units_done.py").write_text(
        "def read(ctx):\n    return float(ctx['units'])\n")
    spec = {"workloads": [{"name": "cov2d-n16.apply2", "config": "cov2d-n16",
                           "traffic": "apply2", "chips": 1}],
            "end_to_end": [{"name": "apply_ms", "unit": "ms"},
                           {"name": "setup_s", "unit": "s"}],
            "per_layer": [{"name": "units_done.apply", "unit": "units"}]}
    cell = harness.Cell("cov2d-n16.apply2", spec, data=tmp_path)
    res = harness.run(cell, 5, 0.3, True, jax, jax.devices()[:1], {})
    assert res["correct"], res["compared"]
    assert res["metrics"]["units_done.apply"]["value"] == res["attempted"]
