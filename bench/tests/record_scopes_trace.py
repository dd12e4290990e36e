"""Record ``bench/tests/data/scopes.xplane.pb`` on a TPU for
``test_scopes_trace.py``.

    python3 bench/tests/record_scopes_trace.py

Two units of work, each in a ``bench/unit`` span, traced with the
harness's profiler options: one GMG-preconditioned PCG solve of the
fractional problem at n = 32 (``make_operator``'s scopes
``solve/transpose-*``, ``solve/stencil``, the V-cycle, ``matvec/layout``)
and one ``compress(tol=1e-3)`` of the 32 x 32 exponential covariance
Chebyshev H^2 operator (leaf 16, cheb_p 6, eta 0.9: the ``compress/*``
scopes and the host spans ``compress/rank-pick``).  Beside the trace,
``scopes.json`` keeps what the readers take from the run's process: the
solve's iterations, the phases seen, the program's spans recorded during
the trace, and the session's ``profile_start_time``.  Exits 2 without a
TPU.
"""
from __future__ import annotations

import glob
import json
import os
import shutil
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]
DATA = ROOT / "bench" / "tests" / "data"
TMP = ROOT / ".bench_trace_small"


def main() -> int:
    sys.path[:0] = [str(ROOT), str(ROOT / "src")]
    import jax
    import jax.numpy as jnp
    if jax.default_backend() != "tpu":
        print("record_scopes_trace: needs a TPU", file=sys.stderr)
        return 2
    from bench.reference.grids import regular_grid
    from bench.xplane import space_class
    from repro.apps.fractional import (FractionalProblem, make_operator,
                                       make_preconditioner)
    from repro.core import kernels_fn
    from repro.core.compression import compress
    from repro.core.construction import construct_h2
    from repro.obs import trace
    from repro.solvers import pcg

    prob = FractionalProblem(32).build()
    apply_a, pre = make_operator(prob), make_preconditioner(prob)
    b = jnp.ones((32 * 32,), jnp.float32) * prob["h"] ** 2
    solve = jax.jit(lambda rhs: pcg(apply_a, rhs, pre, tol=1e-8,
                                    maxiter=200))
    pts = regular_grid({"side": 32, "dim": 2, "lo": 0.0, "hi": 1.0})
    shape, data, _, _ = construct_h2(pts, kernels_fn.exponential_kernel(0.1),
                                     leaf_size=16, cheb_p=6, eta=0.9)
    solve(b).x.block_until_ready()
    jax.block_until_ready(compress(shape, data, tol=1e-3)[1])

    shutil.rmtree(TMP, ignore_errors=True)
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    first = trace.REGISTRY.recorded
    jax.profiler.start_trace(str(TMP), profiler_options=opts)
    try:
        with jax.profiler.TraceAnnotation("bench/unit"):
            res = solve(b)
            res.x.block_until_ready()
        with jax.profiler.TraceAnnotation("bench/unit"):
            jax.block_until_ready(compress(shape, data, tol=1e-3)[1])
    finally:
        jax.profiler.stop_trace()
    spans = trace.REGISTRY.recent()
    spans = spans[len(spans) - (trace.REGISTRY.recorded - first):]
    path = max(glob.glob(str(TMP / "**" / "*.xplane.pb"), recursive=True),
               key=os.path.getmtime)
    space = space_class()()
    with open(path, "rb") as fh:
        space.ParseFromString(fh.read())
    start = [s.uint64_value for p in space.planes for s in p.stats
             if p.stat_metadata[s.metadata_id].name == "profile_start_time"]
    shutil.copyfile(path, DATA / "scopes.xplane.pb")
    shutil.rmtree(TMP, ignore_errors=True)
    side = {"iterations": int(res.iters), "compressions": 1,
            "phases": sorted(trace.PHASES_SEEN), "spans": spans,
            "profile_start_time": start[0]}
    (DATA / "scopes.json").write_text(json.dumps(side, indent=1) + "\n")
    print(f"scopes.xplane.pb: {(DATA / 'scopes.xplane.pb').stat().st_size} "
          f"bytes, {side['iterations']} iterations, {len(spans)} spans")
    return 0


if __name__ == "__main__":
    sys.exit(main())
