"""The readers of the program's host spans and counters in the traced tiny
runs of ``test_rehearsal``: on the CPU they report, as on the chip."""
import copy
import json

import pytest

from test_rehearsal import SPEC, TINY, harness

CELLS = ("frac2d-n16.solve", "cov2d-n32.apply8", "cov2d-n32.compress")
SPANS = copy.deepcopy(SPEC)
SPANS["per_layer"] += [
    {"name": "construct_s", "unit": "s", "workloads": list(CELLS)},
    {"name": "compile_s", "unit": "s", "workloads": list(CELLS)},
    {"name": "d_assembly_s.solve", "unit": "s",
     "workloads": ["frac2d-n16.solve"]},
    {"name": "host_syncs.compress", "unit": "syncs/call",
     "workloads": ["cov2d-n32.compress"]},
    {"name": "rank_pick_idle_ms.compress", "unit": "ms",
     "workloads": ["cov2d-n32.compress"]},
]


def run_traced(name, seed=2**31 + 11):
    """One traced tiny run, with the compress counters of earlier runs in
    this process forgotten (the bench runs one cell per process)."""
    import jax
    from repro.obs import REGISTRY
    REGISTRY.clear("compress/")
    cell = harness.Cell(name, SPANS, data=TINY)
    return harness.run(cell, seed, 0.5, True, jax, jax.devices()[:1], {})


@pytest.mark.parametrize("name", CELLS)
def test_traced_run_reports_the_host_span_metrics(name):
    res = run_traced(name)
    json.dumps(res)
    assert res["correct"], res["compared"]
    got = res["metrics"]
    assert got["construct_s"]["value"] > 0.0
    assert got["compile_s"]["value"] > 0.0
    if name.endswith(".solve"):
        assert got["d_assembly_s.solve"]["value"] > 0.0
    if name.endswith(".compress"):
        cfg = json.loads((TINY / "configs" / "cov2d-n32.json").read_text())
        depth = (cfg["grid"]["side"] ** 2 // cfg["leaf"]).bit_length() - 1
        assert got["host_syncs.compress"]["value"] == depth + 2
        assert got["rank_pick_idle_ms.compress"]["value"] >= 0.0


def test_setup_cut_uses_the_spans_both_timelines_hold():
    from bench.metrics import program_spans as ps

    ms, off = 10**6, 10**18           # registry time = trace time + off
    spans = [("compile/backend", 0, 50 * ms), ("construct/tree", 60 * ms,
                                                90 * ms),
             ("compress/rank-pick", off + 200 * ms, off + 210 * ms),
             ("compress/rank-pick", off + 300 * ms, off + 320 * ms),
             ("compile/backend", off + 900 * ms, off + 950 * ms)]
    host = [(200 * ms + 3000, 210 * ms, "compress/rank-pick"),
            (300 * ms - 2000, 320 * ms, "compress/rank-pick"),
            (150 * ms, 400 * ms, "bench/unit")]
    assert abs(ps.offset_ns(spans, host) - off) <= 3000
    assert ps.offset_ns(spans, host[2:]) is None
    assert ps.first_quiet(spans[:2] + spans[4:], 500 * ms) == 90 * ms
    assert ps.first_quiet(spans[:2], 500 * ms) == 90 * ms
    assert ps.total_s(spans, "compile/") == pytest.approx(0.1)
    nested = [("build/d-assembly", 0, 100), ("construct/tree", 10, 20),
              ("construct/tree", 200, 230)]
    assert ps.total_s(nested, "construct/", outside="build/d-assembly") == \
        pytest.approx(30e-9)
