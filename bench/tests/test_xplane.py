"""The trace reader: an event carries its metadata's statistics, updated by
its own, on a trace written with the reader's own schema."""
import sys
from pathlib import Path

import pytest

BENCH = Path(__file__).resolve().parents[1]
sys.path[:0] = [str(BENCH.parent)]

from bench import trace_reduce as tr  # noqa: E402
from bench import xplane  # noqa: E402


def space_bytes() -> bytes:
    space = xplane.space_class()()
    dev = space.planes.add(id=1, name="/device:TPU:0")
    for k, name in ((1, "tf_op"), (2, "long_name"), (3, "hlo_category"),
                    (4, "group_id")):
        dev.stat_metadata[k].id, dev.stat_metadata[k].name = k, name
    dev.stat_metadata[9].id, dev.stat_metadata[9].name = 9, "convolution"
    meta = dev.event_metadata[7]
    meta.id, meta.name, meta.display_name = 7, "%fusion.1 = f32[8] fusion()", \
        "fusion.1"
    meta.stats.add(metadata_id=1, str_value="jit(f)/hgemv/dense/dot")
    meta.stats.add(metadata_id=2, str_value="%fusion.1 = f32[8] fusion()")
    meta.stats.add(metadata_id=3, ref_value=9)
    line = dev.lines.add(id=1, name="XLA Ops", timestamp_ns=1000)
    line.events.add(metadata_id=7, offset_ps=5000, duration_ps=9000)
    ev = line.events.add(metadata_id=7, offset_ps=20000, duration_ps=1000)
    ev.stats.add(metadata_id=4, int64_value=3)
    host = space.planes.add(id=2, name="/host:CPU")
    host.event_metadata[1].id, host.event_metadata[1].name = 1, "bench/unit"
    hl = host.lines.add(id=1, name="python3", timestamp_ns=1000)
    hl.events.add(metadata_id=1, offset_ps=0, duration_ps=30000)
    return space.SerializeToString()


def test_events_carry_their_metadata_statistics():
    prof = xplane.Profile(space_bytes())
    dev, host = prof.planes
    assert dev.name == "/device:TPU:0" and host.name == "/host:CPU"
    (line,) = dev.lines
    first, second = line.events
    assert first.name == "%fusion.1 = f32[8] fusion()"
    assert (first.start_ns, first.duration_ns, first.end_ns) == \
        pytest.approx((1005.0, 9.0, 1014.0))
    st = dict(first.stats)
    assert st["tf_op"] == "jit(f)/hgemv/dense/dot"
    assert st["hlo_category"] == "convolution"
    assert "group_id" not in st
    assert dict(second.stats)["group_id"] == 3
    assert dict(second.stats)["tf_op"] == "jit(f)/hgemv/dense/dot"


def test_reduction_reads_scopes_from_metadata():
    r = tr.reduce_profile(xplane.Profile(space_bytes()))
    assert (r.t0, r.t1) == (1000.0, 1030.0)
    assert r.scope_s("hgemv") == pytest.approx(10e-9)
    assert r.scope_s("hgemv/dense") == pytest.approx(10e-9)
    assert r.busy_s == pytest.approx(10e-9)
