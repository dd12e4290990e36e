"""Observability layer (repro/obs) — trace neutrality + timer/metric units.

The load-bearing guarantee: ``obs.trace.phase`` annotations are enabled by
default on every hot path (matvec, halo, compression, solvers, fractional),
so they MUST add zero operations to the traced programs — the jaxpr of an
annotated function is byte-identical with tracing enabled and disabled,
and stays callback-free.  (``IterationTimer`` is the sanctioned exception:
it DOES add a callback and is therefore opt-in only — asserted here too.)

The host spans and the one counter registry: totals, the disable switch,
a span's neutrality inside a trace, its place on a profiler trace's
timeline, the compress tolerance path's host syncs, and the scopes the
lowered programs carry.

Also covered: the replay timers' env threading, the wire-byte
normalization factors, PhaseRecord's model join, the Chrome-trace export,
and the per-phase comm-model decomposition summing exactly to
``dist_solve_comm_bytes`` (the invariant ``profile_solve`` reports rely
on).  Multi-device behavior (measured-vs-modeled collective bytes,
dist-solve neutrality at p=8) lives in ``tests/dist_worker.py``.
"""
import json

import numpy as np
import pytest
import jax
import jax.numpy as jnp

from jaxpr_utils import walk_primitives

from repro.obs import trace
from repro.obs.timers import (IterationTimer, Stage, interleaved_times,
                              median_ratio, run_stages, time_fn,
                              time_stages)


@pytest.fixture(scope="module")
def small_h2():
    from repro.core.clustering import regular_grid_points
    from repro.core.construction import construct_h2
    from repro.core.kernels_fn import exponential_kernel

    pts = regular_grid_points(16, 2)          # N = 256
    return construct_h2(pts, exponential_kernel(0.1),
                        leaf_size=16, cheb_p=4, eta=0.9)


@pytest.fixture(autouse=True)
def _tracing_restored():
    yield
    trace.set_enabled(True)


def _jaxpr_str(fn, *args):
    """Fresh jaxpr text: caches cleared so the trace actually re-runs
    under the current enable flag instead of replaying a memoized trace."""
    jax.clear_caches()
    return str(jax.make_jaxpr(fn)(*args))


# ---------------------------------------------------------------------------
# trace neutrality: annotations on by default, zero ops in the jaxpr
# ---------------------------------------------------------------------------

def test_phase_annotations_are_jaxpr_neutral_matvec(small_h2):
    from repro.core.matvec import h2_matvec

    shape, data, _, _ = small_h2
    x = jnp.ones((shape.n, 2), jnp.float32)
    fn = lambda d, xx: h2_matvec(shape, d, xx)       # noqa: E731

    assert trace.enabled()                # default ON — that's the point
    j_on = _jaxpr_str(fn, data, x)
    trace.set_enabled(False)
    j_off = _jaxpr_str(fn, data, x)
    assert j_on == j_off                  # byte-identical program

    prims = walk_primitives(jax.make_jaxpr(fn)(data, x).jaxpr, [])
    assert not any("callback" in p for p in prims), set(prims)


def test_phase_annotations_are_jaxpr_neutral_pcg():
    from repro.solvers import pcg

    op = lambda x: 3.0 * x               # noqa: E731
    b = jnp.ones((64,), jnp.float32)
    fn = lambda bb: pcg(op, bb, tol=1e-6, maxiter=50)    # noqa: E731

    j_on = _jaxpr_str(fn, b)
    trace.set_enabled(False)
    j_off = _jaxpr_str(fn, b)
    assert j_on == j_off

    prims = walk_primitives(jax.make_jaxpr(fn)(b).jaxpr, [])
    assert any(p == "while" for p in prims)
    assert not any("callback" in p for p in prims), set(prims)


def test_phase_annotations_are_jaxpr_neutral_compression(small_h2):
    from repro.core.compression import compression_weights

    shape, data, _, _ = small_h2
    fn = lambda d: compression_weights(shape, d)         # noqa: E731
    j_on = _jaxpr_str(fn, data)
    trace.set_enabled(False)
    j_off = _jaxpr_str(fn, data)
    assert j_on == j_off


def test_phases_registered(small_h2):
    from repro.core.matvec import h2_matvec

    shape, data, _, _ = small_h2
    x = jnp.ones((shape.n, 1), jnp.float32)
    jax.clear_caches()
    jax.make_jaxpr(lambda d, xx: h2_matvec(shape, d, xx))(data, x)
    assert {"hgemv/upsweep", "hgemv/coupling-gemm", "hgemv/downsweep",
            "hgemv/dense"} <= trace.PHASES_SEEN


def test_disabled_phase_registers_nothing():
    trace.set_enabled(False)
    before = set(trace.PHASES_SEEN)
    with trace.phase("obs-test/never-on"):
        pass
    assert "obs-test/never-on" not in trace.PHASES_SEEN
    assert trace.PHASES_SEEN == before


def test_iteration_timer_is_not_neutral():
    """The coarse in-graph mode DOES add a callback — which is exactly why
    it is opt-in and banned from the default path."""
    timer = IterationTimer()
    fn = timer.wrap(lambda x: x * 2.0)
    prims = walk_primitives(jax.make_jaxpr(fn)(jnp.ones(4)).jaxpr, [])
    assert any("callback" in p for p in prims), set(prims)


# ---------------------------------------------------------------------------
# host spans and counters
# ---------------------------------------------------------------------------

def _span_total(name):
    return trace.REGISTRY.totals().get(name, (0, 0))


def test_span_and_count_totals():
    trace.REGISTRY.clear("obs-test/")
    for _ in range(2):
        with trace.span("obs-test/host"):
            sum(range(1000))
    trace.count("obs-test/n")
    trace.count("obs-test/n", 2)
    n, ns = _span_total("obs-test/host")
    assert n == 2 and ns > 0
    assert trace.counter("obs-test/n") == 3
    mine = [sp for sp in trace.REGISTRY.recent() if sp[0] == "obs-test/host"]
    assert len(mine) == 2 and all(s < e for _, s, e in mine)
    assert sum(e - s for _, s, e in mine) == ns
    trace.REGISTRY.clear("obs-test/")
    assert trace.counter("obs-test/n") == 0
    assert _span_total("obs-test/host") == (0, 0)


def test_span_and_count_do_nothing_when_disabled():
    trace.REGISTRY.clear("obs-test/")
    trace.set_enabled(False)
    with trace.span("obs-test/off"):
        pass
    trace.count("obs-test/off")
    assert _span_total("obs-test/off") == (0, 0)
    assert trace.counter("obs-test/off") == 0


def test_registry_memory_is_bounded():
    reg = trace.Registry(recent=4)
    for i in range(10):
        reg.add_span("obs-test/x", i, i + 1)
    assert reg.totals()["obs-test/x"] == (10, 10)
    assert [s for _, s, _ in reg.recent()] == [6, 7, 8, 9]
    assert reg.recorded - len(reg.recent()) == 6


def test_span_inside_jit_is_neutral_and_records_nothing():
    def plain(x):
        return jnp.sin(x) * 2.0

    def spanned(x):
        with trace.span("obs-test/traced"):
            return jnp.sin(x) * 2.0

    x = jnp.ones((8,), jnp.float32)
    before = _span_total("obs-test/traced")
    assert _jaxpr_str(spanned, x) == _jaxpr_str(plain, x)
    jax.jit(spanned)(x).block_until_ready()
    assert _span_total("obs-test/traced") == before


def test_compile_spans_do_not_overlap():
    inner = jax.jit(lambda x: jnp.cos(x) + 1.0)
    outer = jax.jit(lambda x: inner(x) * inner(2.0 * x))
    jax.clear_caches()
    outer(jnp.ones((5,), jnp.float32)).block_until_ready()
    comp = sorted((s, e) for n, s, e in trace.REGISTRY.recent()
                  if n.startswith("compile/"))
    assert {n for n, _, _ in trace.REGISTRY.recent()} >= {
        "compile/trace", "compile/lower", "compile/backend"}
    assert all(b[0] >= a[1] for a, b in zip(comp, comp[1:]))


def test_span_lands_on_its_annotation_in_a_profiler_trace(tmp_path):
    """Registry time = trace time + the session's profile_start_time."""
    import glob

    from jax.profiler import ProfileData

    x = jnp.ones((64,), jnp.float32)
    with jax.profiler.trace(str(tmp_path)):
        with trace.span("obs-test/profiled"):
            jnp.sin(x).block_until_ready()
    name, start, end = [sp for sp in trace.REGISTRY.recent()
                        if sp[0] == "obs-test/profiled"][-1]
    path = glob.glob(str(tmp_path / "**" / "*.xplane.pb"), recursive=True)[0]
    pd = ProfileData.from_file(path)
    zero = [v for pl in pd.planes for k, v in pl.stats
            if k == "profile_start_time"]
    events = [ev for pl in pd.planes if pl.name.startswith("/host:")
              for line in pl.lines for ev in line.events if ev.name == name]
    assert len(zero) == 1 and len(events) == 1
    ev = events[0]
    assert abs(zero[0] + ev.start_ns - start) < 1e6
    assert abs(zero[0] + ev.start_ns + ev.duration_ns - end) < 1e6


def test_tol_compress_counts_its_host_syncs(small_h2):
    from repro.core.compression import compress

    shape, data, _, _ = small_h2
    syncs, calls = (trace.counter("compress/host-syncs"),
                    trace.counter("compress/calls"))
    picks = _span_total("compress/rank-pick")[0]
    compress(shape, data, tol=1e-3)
    # the scale, the leaf rank and one rank per inner level
    assert trace.counter("compress/host-syncs") - syncs == shape.depth + 2
    assert trace.counter("compress/calls") - calls == 1
    assert _span_total("compress/rank-pick")[0] - picks == shape.depth + 2
    compress(shape, data, target_ranks=tuple(min(4, k)
                                             for k in shape.ranks))
    assert trace.counter("compress/host-syncs") - syncs == shape.depth + 2


def _lowered_text(fn, *args):
    jax.clear_caches()
    return fn.lower(*args).as_text(debug_info=True)


def test_lowered_programs_carry_the_new_scopes(small_h2):
    from repro.apps.fractional import FractionalProblem, make_operator
    from repro.core import compression as c
    from repro.core.matvec import h2_matvec

    prob = FractionalProblem(8).build()
    u = jnp.ones((64,), jnp.float32)
    text = _lowered_text(jax.jit(make_operator(prob)), u)
    for scope in ("solve/transpose-in", "solve/transpose-out",
                  "solve/stencil", "matvec/layout", "hgemv/dense"):
        assert scope in text, scope
    assert "hgemv/matvec" not in text and "hgemv/solve" not in text

    shape, data, _, _ = small_h2
    x = jnp.ones((shape.n, 1), jnp.float32)
    text = _lowered_text(h2_matvec, shape, data, x)
    assert "matvec/layout" in text and "hgemv/matvec/layout" not in text

    data, ru, rv = c._orthogonalize_weights(shape, data, "jnp", True)
    d = shape.depth
    w, s = c._leaf_factors_jit(ru[d], "jnp")
    k = shape.ranks[d - 1]
    steps = [(c._leaf_factors_jit, (ru[d], "jnp")),
             (c._leaf_apply_jit, (data.u_leaf, w, 3)),
             (c._inner_factors_jit, (jnp.swapaxes(w, -1, -2),
                                     data.e[d], ru[d - 1], "jnp")),
             (c._inner_apply_jit, (jnp.ones((shape.nodes(d) // 2, 2 * k, k)),
                                   jnp.ones((shape.nodes(d) // 2, 2 * k, k)),
                                   3, shape.nodes(d)))]
    for fn, args in steps:
        assert "compress/truncate" in _lowered_text(fn, *args), fn
    tgt = tuple(min(4, r) for r in shape.ranks)
    text = _lowered_text(c._compress_fixed, shape, data, tgt, "jnp", True,
                         True)
    assert "compress/truncate" in text and "compress/project-s" in text
    assert "compress/truncate/compress/truncate" not in text


# ---------------------------------------------------------------------------
# timers
# ---------------------------------------------------------------------------

def test_time_fn_and_interleaved():
    x = jnp.ones((128,), jnp.float32)
    sec = time_fn(jnp.sin, x, reps=3)
    assert sec > 0
    acc = interleaved_times({"a": lambda: jnp.sin(x),
                             "b": lambda: jnp.cos(x)}, reps=4)
    assert sorted(acc) == ["a", "b"]
    assert all(len(v) == 4 and min(v) > 0 for v in acc.values())
    assert median_ratio([2.0, 4.0, 8.0], [1.0, 2.0, 4.0]) == 2.0


def test_stage_pipeline_env_threading():
    stages = [
        Stage("double", jax.jit(lambda x: 2.0 * x), ("x",), ("y",)),
        Stage("split", jax.jit(lambda y: (y + 1.0, y - 1.0)),
              ("y",), ("hi", "lo"), phase="split-phase"),
        Stage("sum", jax.jit(lambda a, b: a + b), ("hi", "lo"), ("z",)),
    ]
    env = run_stages(stages, {"x": jnp.full((8,), 3.0)})
    np.testing.assert_allclose(np.asarray(env["z"]), 12.0)
    assert set(env) == {"x", "y", "hi", "lo", "z"}

    secs = time_stages(stages, env, reps=3)
    assert sorted(secs) == ["double", "split", "sum"]
    assert all(v > 0 for v in secs.values())
    assert stages[1].phase == "split-phase"


# ---------------------------------------------------------------------------
# metrics + export
# ---------------------------------------------------------------------------

def test_wire_bytes_factors():
    from repro.obs.metrics import wire_bytes

    assert wire_bytes({"all-gather": 800.0}, 8) == 700.0
    assert wire_bytes({"reduce-scatter": 800.0}, 8) == 700.0
    assert wire_bytes({"all-reduce": 10.0}, 8) == 70.0
    assert wire_bytes({"collective-permute": 64.0}, 8) == 64.0
    assert wire_bytes({"all-gather": 800.0,
                       "collective-permute": 100.0}, 8) == 800.0


def test_phase_record_joins_models(tmp_path):
    from repro.obs.metrics import phase_record, records_to_json

    a = jnp.ones((16, 32), jnp.float32)
    bmat = jnp.ones((32, 8), jnp.float32)
    rec = phase_record("test/gemm", us=12.5,
                       fn=jax.jit(lambda x, y: x @ y), args=(a, bmat),
                       model_comm_bytes=0, p=1, comm="none")
    assert rec.model_flops == 2 * 16 * 32 * 8
    d = rec.to_dict()
    assert d["comm"] == "none" and "extra" not in d
    assert d["us"] == 12.5

    path = tmp_path / "phases.json"
    records_to_json([rec], str(path), bench="unit")
    doc = json.loads(path.read_text())
    assert doc["bench"] == "unit"
    assert doc["phases"][0]["phase"] == "test/gemm"


def test_chrome_trace_export(tmp_path):
    from repro.obs.export import write_chrome_trace

    path = tmp_path / "trace.json"
    lanes = [{"lane": "halo-plan", "iters": 2,
              "phase_us": {"a": 10.0, "b": 5.0}},
             {"lane": "allgather", "iters": 1,
              "phase_us": {"a": 12.0}}]
    write_chrome_trace(str(path), lanes)
    doc = json.loads(path.read_text())
    ev = doc["traceEvents"]
    names = [e["name"] for e in ev if e.get("ph") == "X"]
    assert names.count("a") == 3 and names.count("b") == 2
    assert all(e["dur"] > 0 for e in ev if e.get("ph") == "X")
    tids = {e["tid"] for e in ev if e.get("ph") == "X"}
    assert len(tids) == 2                 # one thread row per comm mode


def test_phase_comm_model_sums_to_solve_model():
    """The per-phase byte decomposition must sum EXACTLY to the whole-
    iteration model — profile_solve's records are a partition of
    ``dist_solve_comm_bytes``, not an independent estimate."""
    from repro.apps.fractional import (FractionalProblem,
                                       build_dist_problem,
                                       dist_solve_comm_bytes)
    from repro.obs.profile_solve import PHASE_ORDER, phase_comm_model

    prob = FractionalProblem(16).build()
    dshape, mg, _, _ = build_dist_problem(prob, p=8)
    for comm in ("halo-plan", "ppermute", "allgather"):
        model = phase_comm_model(dshape, mg, comm)
        assert set(model) == set(PHASE_ORDER)
        assert sum(model.values()) == dist_solve_comm_bytes(
            dshape, mg, comm), comm
        assert model["hgemv/exchange"] > 0


def test_baseline_compare_warns_on_regression():
    import sys
    import os
    sys.path.insert(0, os.path.abspath(
        os.path.join(os.path.dirname(__file__), "..")))
    from benchmarks.run import compare_to_baseline

    base = [{"name": "x", "us": 100.0, "phases": {"a": 50.0, "b": 50.0}}]
    ok = [{"name": "x", "us": 110.0, "phases": {"a": 55.0, "b": 55.0}}]
    bad = [{"name": "x", "us": 130.0, "phases": {"a": 40.0, "b": 90.0}}]
    unknown = [{"name": "y", "us": 9000.0}]
    assert compare_to_baseline(ok, base) == []
    warns = compare_to_baseline(bad, base)
    assert len(warns) == 2                # us + phase b, not phase a
    assert any("phase b" in w for w in warns)
    assert compare_to_baseline(unknown, base) == []
