"""Example: serve H^2 covariance solves through the ``repro.serving``
subsystem (DESIGN.md §9) — a thin CLI over the real service stack.

One expensively-constructed H^2 operator amortizes over many O(N) applies
(the paper's §5 use case); here that economics is operational: operators
are built through the **operator cache** (keyed by geometry digest +
kernel params + tol; repeat requests are cache hits that also reuse the
compiled solver), single right-hand sides and whole Poisson request
streams go through the **continuous-batching serve loop** (multi-RHS
``block_cg`` panel, late arrivals join at restart boundaries), and the
fault layer (retry/hedging/circuit-breaker) is armed but idle without an
injection plan.

    PYTHONPATH=src python examples/serve_h2_solver.py [--side 64]
        [--leaf-size 64] [--tol 1e-6] [--rate 50] [--requests 8]
"""
import argparse
import time
from pathlib import Path

import numpy as np

from repro.launch.compile_cache import enable_compile_cache
from repro.core.clustering import regular_grid_points
from repro.core.compression import compress
from repro.core.construction import construct_h2
from repro.core.kernels_fn import exponential_kernel
from repro.serving import (OperatorCache, OperatorKey, PoissonLoad,
                           SolveRequest, SolverService, geometry_digest)

CORR = 0.1   # exponential-kernel correlation length served by this demo


def make_builder(pts, leaf_size: int, tol):
    """Cache-aside builder: construct (and optionally recompress) the
    operator for one ``OperatorKey``.  Runs only on cache misses."""
    def build():
        shape, data, _, _ = construct_h2(pts, exponential_kernel(CORR),
                                         leaf_size=leaf_size, cheb_p=6,
                                         eta=0.9)
        if tol is not None:
            shape, data = compress(shape, data, tol=tol)
        return shape, data, {}
    return build


def main(side: int = 64, leaf_size: int = 64, tol: float = 1e-6,
         rate: float = 50.0, n_requests: int = 8):
    pts = regular_grid_points(side, 2)
    n = side * side
    geom = geometry_digest(pts)
    key_full = OperatorKey(geometry=geom, kernel=("exponential", CORR),
                           tol=None)
    key_comp = key_full.loosened(1e-5)

    cache = OperatorCache()
    svc = SolverService(cache, panel_width=8, restart_every=25, tol=tol)
    b = np.random.default_rng(0).standard_normal(n).astype(np.float32)

    # single RHS against the uncompressed operator (cache miss -> build)
    t0 = time.perf_counter()
    rep1 = svc.serve([SolveRequest(rid=0, b=b, arrival=0.0, tol=tol)],
                     key_full, make_builder(pts, leaf_size, None))
    t1 = time.perf_counter() - t0
    r1 = rep1.completions[0]
    print(f"uncompressed (rank {cache.peek(key_full).shape.ranks[-1]}): "
          f"{r1.iters} iters, relres {r1.relres:.1e}, {t1:.2f}s "
          f"incl. construction")

    # same RHS against the recompressed operator (second cache entry)
    rep2 = svc.serve([SolveRequest(rid=0, b=b, arrival=0.0, tol=tol)],
                     key_comp, make_builder(pts, leaf_size, 1e-5))
    r2 = rep2.completions[0]
    ratio = cache.peek(key_full).shape.memory_lowrank() \
        / cache.peek(key_comp).shape.memory_lowrank()
    drift = float(np.linalg.norm(r1.x - r2.x) / np.linalg.norm(r1.x))
    print(f"recompressed ({ratio:.1f}x smaller): {r2.iters} iters, "
          f"solution drift {drift:.1e}")

    # a Poisson stream served by the continuous-batching panel; the
    # operator AND its jitted panel solver come straight from the cache
    load = PoissonLoad(n=n, rate=rate, n_requests=n_requests, tol=tol,
                       seed=1)
    t0 = time.perf_counter()
    rb = svc.serve(load.requests(), key_comp,
                   make_builder(pts, leaf_size, 1e-5))
    tb = time.perf_counter() - t0
    iters = [rb.completions[i].iters for i in range(n_requests)]
    print(f"continuous batching, {n_requests} Poisson RHS: iters/req "
          f"{iters}, occupancy {rb.metrics['mean_occupancy']:.1f}/"
          f"{rb.metrics['panel_width']}, p50 {rb.percentile(50) * 1e3:.1f}ms "
          f"p99 {rb.percentile(99) * 1e3:.1f}ms (virtual), {tb:.2f}s wall")
    st = cache.stats()
    print(f"operator cache: {st['hits']} hits / {st['misses']} misses, "
          f"{st['bytes'] / 1e6:.1f} MB resident, "
          f"construction {st['build_seconds']:.2f}s amortized over "
          f"{2 + n_requests} requests")
    return r1, r2, rb


if __name__ == "__main__":
    enable_compile_cache(Path(__file__).resolve().parents[1])
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--side", type=int, default=64)
    ap.add_argument("--leaf-size", type=int, default=64)
    ap.add_argument("--tol", type=float, default=1e-6)
    ap.add_argument("--rate", type=float, default=50.0)
    ap.add_argument("--requests", type=int, default=8)
    a = ap.parse_args()
    main(side=a.side, leaf_size=a.leaf_size, tol=a.tol, rate=a.rate,
         n_requests=a.requests)
