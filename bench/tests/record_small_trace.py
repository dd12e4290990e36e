"""Record ``bench/tests/data/small.xplane.pb`` on a TPU for
``test_trace_reduce.test_recorded_chip_trace``.

    python3 bench/tests/record_small_trace.py

Three 64-column H^2 applications at N = 4096 (the 64 x 64 exponential
covariance grid, Chebyshev H^2 with cheb_p 6, eta 0.9, leaf 64), each in a
``bench/unit`` span with a ``bench/wait`` span around its wait, traced with
the harness's profiler options.  Exits 2 without a TPU.
"""
from __future__ import annotations

import glob
import os
import shutil
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]
OUT = ROOT / "bench" / "tests" / "data" / "small.xplane.pb"
TMP = ROOT / ".bench_trace_small"


def main() -> int:
    sys.path[:0] = [str(ROOT), str(ROOT / "src")]
    import jax
    if jax.default_backend() != "tpu":
        print("record_small_trace: needs a TPU", file=sys.stderr)
        return 2
    from bench.reference.grids import regular_grid
    from repro.core import kernels_fn
    from repro.core.construction import construct_h2
    from repro.core.matvec import h2_matvec

    pts = regular_grid({"side": 64, "dim": 2, "lo": 0.0, "hi": 1.0})
    shape, data, _, _ = construct_h2(pts, kernels_fn.exponential_kernel(0.1),
                                     leaf_size=64, cheb_p=6, eta=0.9)
    x = jax.random.normal(jax.random.key(0), (pts.shape[0], 64))
    h2_matvec(shape, data, x).block_until_ready()
    shutil.rmtree(TMP, ignore_errors=True)
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(str(TMP), profiler_options=opts)
    try:
        for _ in range(3):
            with jax.profiler.TraceAnnotation("bench/unit"):
                y = h2_matvec(shape, data, x)
                with jax.profiler.TraceAnnotation("bench/wait"):
                    y.block_until_ready()
    finally:
        jax.profiler.stop_trace()
    path = max(glob.glob(str(TMP / "**" / "*.xplane.pb"), recursive=True),
               key=os.path.getmtime)
    OUT.parent.mkdir(parents=True, exist_ok=True)
    shutil.copyfile(path, OUT)
    shutil.rmtree(TMP, ignore_errors=True)
    print(f"{OUT.relative_to(ROOT)}: {OUT.stat().st_size} bytes")
    return 0


if __name__ == "__main__":
    sys.exit(main())
