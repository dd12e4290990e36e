"""Guard-rail cost benchmark (DESIGN.md §11): what do the rails cost
when nothing is wrong?

Two numbers, both on 8 fake host devices:

  - **status-carry overhead**: the breakdown guards ride the PCG
    while_loop carry as one traced int32 (NaN / indefiniteness /
    stagnation checks, zero host syncs).  Measured as us_per_iter of the
    p=8 fused distributed fractional solve with guards on vs the global
    kill-switch (``set_guards_enabled(False)``, which compiles every
    guard op out — the jaxprs are byte-identical to pre-guard solvers,
    asserted in tests/test_guard.py).  Acceptance: <= 3% per iteration.
  - **certification cost**: wall time of ``validate_h2`` (structural
    invariants) and ``certify_h2`` (stochastic probes) on a constructed
    operator, reported in units of one matvec — the "cheap enough to run
    after construct/compress/update" claim, quantified.

Device count must be fixed before jax initializes, so the measurement
runs in a subprocess (``--worker``) — the ``fault_bench`` pattern.
"""
from __future__ import annotations

import json
import os
import subprocess
import sys
import time
from typing import Dict, List, Optional

MARKER = "GUARD_BENCH_JSON:"


def _worker(quick: bool) -> None:
    os.environ["XLA_FLAGS"] = (
        "--xla_force_host_platform_device_count=8 "
        + os.environ.get("XLA_FLAGS", ""))
    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.sharding import NamedSharding, PartitionSpec as P

    from repro.apps.fractional import FractionalProblem, make_dist_solve
    from repro.obs.timers import interleaved_times
    from repro.solvers import set_guards_enabled

    p, n = 8, 16 if quick else 32
    mesh = jax.make_mesh((p,), ("blk",))
    records: List[Dict] = []

    # -- status-carry overhead on the fused distributed solve ------------
    prob = FractionalProblem(n).build()
    b = jnp.ones((n * n,), jnp.float32) * prob["h"] ** 2
    b_dev = jax.device_put(b, NamedSharding(mesh, P("blk")))
    solvers: Dict[str, tuple] = {}
    for tag, enabled in (("guard_on", True), ("guard_off", False)):
        set_guards_enabled(enabled)
        try:
            parts = make_dist_solve(prob, mesh, comm="halo-plan",
                                    tol=1e-8, maxiter=200)
            args = parts["place"](parts["args"])
            res = jax.block_until_ready(parts["fn"](*args, b_dev))
        finally:
            set_guards_enabled(True)
        assert bool(res.converged), (tag, float(res.relres))
        solvers[tag] = (parts["fn"], args, int(res.iters))
    assert solvers["guard_on"][2] == solvers["guard_off"][2], \
        {t: s[2] for t, s in solvers.items()}   # guards change no iterate

    acc = interleaved_times(
        {tag: (lambda tag=tag: solvers[tag][0](*solvers[tag][1], b_dev))
         for tag in solvers},
        reps=8 if quick else 16, warmup=1)
    iters = solvers["guard_on"][2]
    us = {tag: float(np.median(acc[tag])) * 1e6 for tag in solvers}
    overhead_pct = (us["guard_on"] / us["guard_off"] - 1.0) * 100.0
    records.append({
        "name": "guard_status_carry",
        "n": n, "N": n * n, "p": p, "iters": iters,
        "us_per_iter": round(us["guard_on"] / max(iters, 1), 2),
        "us_per_iter_off": round(us["guard_off"] / max(iters, 1), 2),
        "overhead_pct": round(overhead_pct, 2),
    })

    # -- certification cost in matvec units ------------------------------
    from repro.core.clustering import regular_grid_points
    from repro.core.construction import construct_h2
    from repro.core.kernels_fn import exponential_kernel
    from repro.core.matvec import h2_matvec
    from repro.guard import certify_h2, kernel_reference_apply, validate_h2

    side = 16 if quick else 32
    pts = regular_grid_points(side, 2)
    kern = exponential_kernel(0.1)
    shape, data, tree, _ = construct_h2(pts, kern, leaf_size=16, cheb_p=4,
                                        eta=0.9, dtype=jnp.float32)
    x = jnp.ones((shape.n, 1), jnp.float32)
    jax.block_until_ready(h2_matvec(shape, data, x))     # warm
    t0 = time.perf_counter()
    for _ in range(8):
        jax.block_until_ready(h2_matvec(shape, data, x))
    mv_s = (time.perf_counter() - t0) / 8

    t0 = time.perf_counter()
    rep = validate_h2(shape, data)
    val_s = time.perf_counter() - t0
    assert rep.ok, rep.summary()

    probes = 8
    ref = kernel_reference_apply(pts, kern, tree.perm, chunk=1024)
    certify_h2(shape, data, ref, probes=probes, tol=1e-2)   # warm
    t0 = time.perf_counter()
    cert = certify_h2(shape, data, ref, probes=probes, tol=1e-2)
    cert_s = time.perf_counter() - t0
    assert cert.ok, cert.rel_err
    records.append({
        "name": "guard_certification",
        "N": shape.n, "probes": probes,
        "rel_err": float(cert.rel_err),
        "matvec_us": round(mv_s * 1e6, 1),
        "validate_us": round(val_s * 1e6, 1),
        "certify_us": round(cert_s * 1e6, 1),
        "validate_matvecs": round(val_s / mv_s, 1),
        "certify_matvecs": round(cert_s / mv_s, 1),
    })
    for r in records:
        r["platform"] = jax.default_backend()
    print(MARKER + json.dumps(records))


def run(out_rows: List[str], records: Optional[List[Dict]] = None) -> None:
    quick = os.environ.get("REPRO_BENCH_QUICK", "0") == "1"
    root = os.path.abspath(os.path.join(os.path.dirname(__file__), ".."))
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.join(root, "src") + (
        os.pathsep + env["PYTHONPATH"] if env.get("PYTHONPATH") else "")
    # fake host devices by design: the child never reaches for a chip
    # (which its parent may hold)
    env["JAX_PLATFORMS"] = "cpu"
    cmd = [sys.executable, "-m", "benchmarks.guard_bench", "--worker"]
    if quick:
        cmd.append("--quick")
    proc = subprocess.run(cmd, capture_output=True, text=True, timeout=3000,
                          env=env, cwd=root)
    if proc.returncode != 0:
        raise RuntimeError(f"guard_bench worker failed:\n{proc.stdout}"
                           f"\n{proc.stderr}")
    payload = None
    for line in proc.stdout.splitlines():
        if line.startswith(MARKER):
            payload = json.loads(line[len(MARKER):])
    assert payload is not None, proc.stdout
    for r in payload:
        if r["name"] == "guard_status_carry":
            out_rows.append(
                f"{r['name']},{r['us_per_iter']:.2f},"
                f"overhead_pct={r['overhead_pct']};"
                f"off={r['us_per_iter_off']};iters={r['iters']}")
        else:
            out_rows.append(
                f"{r['name']},{r['certify_us']:.1f},"
                f"certify_matvecs={r['certify_matvecs']};"
                f"validate_matvecs={r['validate_matvecs']};"
                f"rel_err={r['rel_err']:.2e}")
        if records is not None:
            records.append(r)


def main() -> None:
    if "--worker" in sys.argv:
        _worker(quick="--quick" in sys.argv
                or os.environ.get("REPRO_BENCH_QUICK", "0") == "1")
        return
    rows: List[str] = []
    records: List[Dict] = []
    run(rows, records)
    for r in rows:
        print(r)
    with open("BENCH_guard.json", "w") as f:
        json.dump(records, f, indent=1)
    print("# wrote BENCH_guard.json")


if __name__ == "__main__":
    main()
