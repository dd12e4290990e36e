"""The new scope and span readers on a trace recorded on a TPU v5e
(``data/scopes.xplane.pb`` and ``data/scopes.json``, written by
``record_scopes_trace.py``: one fractional solve at n = 32 and one
``compress(tol)`` of a 32 x 32 covariance operator, each in a
``bench/unit`` span)."""
import json
import sys
from pathlib import Path

import pytest

BENCH = Path(__file__).resolve().parents[1]
ROOT = BENCH.parent
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

from bench import run as harness  # noqa: E402
from bench import trace_reduce as tr  # noqa: E402
from bench.metrics import program_spans  # noqa: E402

DATA = BENCH / "tests" / "data"


@pytest.fixture(scope="module")
def recorded():
    side = json.loads((DATA / "scopes.json").read_text())
    return tr.reduce_file(DATA / "scopes.xplane.pb"), side


def read(metric, ctx):
    cell = harness.Cell.__new__(harness.Cell)
    cell.bench = cell.data = BENCH
    return cell.reader(metric)(ctx)


def test_transpose_and_truncate_scopes_are_on_the_chip_trace(recorded):
    red, side = recorded
    busy_ms = 1e3 * red.busy_s
    t = read("transpose_ms.solve", {"reduced": red,
                                    "iterations": side["iterations"]})
    assert 0.0 < t * side["iterations"] < busy_ms
    assert red.has_scope("solve/stencil") and red.has_scope("matvec/layout")
    assert not any(tr.matches(o.scope, "hgemv/matvec") for o in red.devices[0])
    k = read("truncate_ms.compress", {"reduced": red,
                                      "units": side["compressions"]})
    assert 0.0 < k < busy_ms
    assert red.scope_s("compress/truncate") > red.scope_s("compress/weights")


def test_unscoped_share_on_the_chip_trace(recorded, monkeypatch):
    from repro.obs import trace

    red, side = recorded
    monkeypatch.setattr(trace, "PHASES_SEEN", set(side["phases"]))
    share = read("unscoped_pct.solve", {"reduced": red})
    assert 0.0 <= share < 10.0
    monkeypatch.setattr(trace, "PHASES_SEEN", set())
    assert read("unscoped_pct.solve", {"reduced": red}) == \
        pytest.approx(100.0)


def test_rank_pick_spans_and_the_registry_clock(recorded):
    red, side = recorded
    idle = read("rank_pick_idle_ms.compress", {"reduced": red,
                                               "units": side["compressions"]})
    assert idle is not None and idle >= 0.0
    picks = [s for s in red.host if s[2] == "compress/rank-pick"]
    assert len(picks) == len(side["spans"]) > 0
    off = program_spans.offset_ns([tuple(s) for s in side["spans"]],
                                  red.host)
    assert abs(off - side["profile_start_time"]) < 1e6
