"""The trace reduction on synthetic intervals and on a small trace recorded
on a TPU v5e (``data/small.xplane.pb``, written by ``record_small_trace.py``:
three 64-column H^2 applications at N = 4096, each in a ``bench/unit`` span
with a ``bench/wait`` inside)."""
import sys
from pathlib import Path
from types import SimpleNamespace

import pytest

BENCH = Path(__file__).resolve().parents[1]
ROOT = BENCH.parent
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

from bench import trace_reduce as tr  # noqa: E402

SMALL = BENCH / "tests" / "data" / "small.xplane.pb"


def test_union_and_clip():
    assert tr.union([(5, 7), (0, 2), (1, 3), (7, 8)]) == [(0, 3), (5, 8)]
    assert tr.clip([(0, 3), (5, 8)], 2, 6) == [(2, 3), (5, 6)]


def test_scope_matching_is_by_whole_segments():
    s = "jit(solve)/while/body/krylov/precond/precond/vcycle/mg/level0/add"
    assert tr.matches(s, "precond/vcycle") and tr.matches(s, "mg/")
    assert not tr.matches(s, "vcyc") and not tr.matches(s, "hgemv")


def synthetic():
    ops = [tr.Op(10, 20, "a", "jit(f)/hgemv/upsweep/dot"),
           tr.Op(15, 25, "b", "jit(f)/hgemv/dense/dot"),
           tr.Op(40, 50, "c", "jit(f)/other/add")]
    host = [(5, 60, "bench/unit"), (30, 45, "bench/wait")]
    return tr.Reduced([ops], host, (5, 60))


def test_busy_gaps_and_scopes_on_synthetic_ops():
    r = synthetic()
    assert r.busy[0] == [(10, 25), (40, 50)]
    assert r.busy_s == pytest.approx(25e-9)
    assert r.window_s == pytest.approx(55e-9)
    assert r.gaps() == [(5, 10), (25, 40), (50, 60)]
    assert r.scope_s("hgemv") == pytest.approx(20e-9)
    assert r.scope_s("other") == pytest.approx(10e-9)
    b = r.breakdown()
    assert dict(b["idle_gaps"]) == pytest.approx(
        {"bench/unit": 15e-9, "bench/wait": 15e-9})
    assert b["device_ops"][0][0] == "jit(f)/hgemv/upsweep/dot"


def fake_profile(device_lines, host_ops):
    """A stand-in for ``ProfileData``: one device plane whose lines are
    named ``device_lines`` (or none), and one host plane whose events carry
    an ``hlo_op`` statistic, as a CPU backend's XLA ops do."""
    ev = lambda s, e, name, **st: SimpleNamespace(  # noqa: E731
        start_ns=s, end_ns=e, duration_ns=e - s, name=name,
        stats=list(st.items()))
    line = lambda name, evs: SimpleNamespace(name=name, events=evs)  # noqa
    host = SimpleNamespace(name="/host:CPU", lines=[line("ops", [
        ev(0, 10, "bench/unit"),
        ev(2, 4, "dot", hlo_op="dot", tf_op="jit(f)/hgemv/dot")])] if
        host_ops else [line("ops", [ev(0, 10, "bench/unit")])])
    planes = [host]
    if device_lines is not None:
        planes.append(SimpleNamespace(name="/device:TPU:0", lines=[
            line(n, [ev(3, 5, "fusion", tf_op="jit(f)/hgemv/fusion")])
            for n in device_lines]))
    return SimpleNamespace(planes=planes)


def test_cpu_ops_stand_in_only_without_a_device_plane():
    r = tr.reduce_profile(fake_profile(None, True))
    assert r.scope_s("hgemv") == pytest.approx(2e-9)
    r = tr.reduce_profile(fake_profile(["XLA Ops"], True))
    assert r.busy[0] == [(3, 5)]
    with pytest.raises(ValueError, match="holds no line"):
        tr.reduce_profile(fake_profile(["XLA Modules"], True))


@pytest.mark.skipif(not SMALL.exists(), reason="no recorded chip trace")
def test_recorded_chip_trace():
    from bench.xplane import Profile
    r = tr.reduce_file(SMALL)
    assert 0.0 < r.busy_s < r.window_s
    gaps = sum(e - s for s, e in r.gaps()) * 1e-9
    assert gaps + r.busy_s == pytest.approx(r.window_s, rel=1e-9)
    # per-scope sum against a plain pass over the raw events
    pd = Profile.from_file(SMALL)
    total = hgemv = 0
    for plane in pd.planes:
        if plane.name.startswith("/device:"):
            for line in plane.lines:
                if line.name != "XLA Ops":
                    continue
                for ev in line.events:
                    if ev.end_ns <= r.t0 or ev.start_ns >= r.t1:
                        continue
                    total += ev.duration_ns
                    if "/hgemv/" in "/" + tr._scope_of(dict(ev.stats)):
                        hgemv += ev.duration_ns
            break
    assert hgemv > 0
    assert r.scope_s("hgemv") == pytest.approx(hgemv * 1e-9)
    assert r.busy_s <= total * 1e-9 + 1e-12
    names = {n for n, _ in r.breakdown()["idle_gaps"]}
    assert names <= {"bench/unit", "bench/wait", "no host span"} | {
        s[2] for s in r.host}
    assert "bench/wait" in names


def test_nested_ops_count_their_own_time():
    """A loop's event spans its body's ops: each op counts its own time."""
    ops = [tr.Op(0, 100, "%while.1", ""),
           tr.Op(10, 40, "a", "jit(f)/while/body/precond/vcycle/dot"),
           tr.Op(50, 90, "b", "jit(f)/while/body/hgemv/dense/dot"),
           tr.Op(55, 60, "c", "jit(f)/while/body/hgemv/dense/add"),
           tr.Op(120, 130, "d", "jit(f)/other")]
    r = tr.Reduced([ops], [(0, 130, "bench/unit")], (0, 130))
    assert [o.own for o in ops] == [30, 30, 35, 5, 10]
    assert r.scope_s("precond/vcycle") == pytest.approx(30e-9)
    assert r.scope_s("hgemv") == pytest.approx(40e-9)
    assert sum(o.own for o in ops) * 1e-9 == pytest.approx(r.busy_s)
    assert dict(r.breakdown()["device_ops"])["%while.1"] == \
        pytest.approx(30e-9)
