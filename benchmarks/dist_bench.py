"""Distributed HGEMV benchmark: compressed-halo plan vs broadcast halos.

Times the three communication modes of the `shard_map` distributed matvec
(`core/dist.py`) on 8 fake host devices at N in {16384, 65536}, nv=16:

  - ``halo-plan``  compressed send/recv plans (core/halo.py): packed
                   gathers, one fused ppermute round per neighbor distance
  - ``ppermute``   broadcast halo (whole level x 2*rad per level)
  - ``allgather``  whole-level gather baseline ((P-1)x volume)
  - ``halo-plan-merged``  the solver lowering (``hide_flops > 0``): every
                   per-offset round collapsed into ONE residue-layout
                   ``all_to_all`` (DESIGN.md §12) — round-count-minimal

Structure: 1D interval, exponential kernel, leaf 32, Chebyshev p=8,
eta = 0.9 — a C_sp ~ 3 operator (the boundary-integral-type geometry of
the H^2 literature) whose distributed matvec is communication-bound: the
per-device GEMM work shrinks with C_sp while the broadcast/allgather
volumes are structure-independent (they ship whole levels regardless),
and the halo structure is real (radius 1-3 per level, dense radius 1),
so the compressed send lists cut modeled volume by ~60x vs the broadcast
halo and ~200x vs allgather.  On a strong-admissibility 2D grid
(C_sp ~ 17) the CPU matvec is compute-bound and the modes converge in
wall time — the comm model rows (`matvec_comm_bytes`, also emitted by
``benchmarks/hgemv.py``) quantify the volume gap there.

Device count must be fixed before jax initializes, so the measurement runs
in a subprocess (`--worker`); `run()` forks it and forwards the records —
the same pattern as `tests/test_dist.py`.  Timing methodology
(`repro.obs.timers`): the modes are timed in interleaved rounds and the
speedups are **medians of per-round ratios** — the host's throughput
drifts on multi-second scales
(shared machine), but within one round (~100 ms) all modes see the same
machine state, so the ratio estimator cancels the drift that would poison
independent means.

Set ``REPRO_BENCH_QUICK=1`` (or ``benchmarks.run --quick``) for the
N=16384-only smoke configuration (CI).
"""
from __future__ import annotations

import json
import os
import subprocess
import sys
from typing import Dict, List, Optional

MARKER = "DIST_BENCH_JSON:"


def _worker(quick: bool) -> None:
    os.environ["XLA_FLAGS"] = (
        "--xla_force_host_platform_device_count=8 "
        + os.environ.get("XLA_FLAGS", ""))
    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.sharding import NamedSharding, PartitionSpec as P

    from repro.core.construction import construct_h2
    from repro.core.dist import (dist_specs, make_dist_matvec,
                                 matvec_comm_bytes, merged_exchange_bytes,
                                 partition_h2)
    from repro.core.kernels_fn import exponential_kernel
    from repro.core.matvec import h2_matvec
    from repro.obs.timers import interleaved_times, median_ratio

    p, nv = 8, 16
    mesh = jax.make_mesh((p,), ("blk",))
    records: List[Dict] = []
    ns = (16384,) if quick else (16384, 65536)
    for n in ns:
        pts = np.linspace(0.0, 1.0, n)[:, None]
        shape, data, tree, bs = construct_h2(
            pts, exponential_kernel(0.05),
            leaf_size=32, cheb_p=8, eta=0.9)
        dshape, ddata = partition_h2(shape, data, p)
        specs = dist_specs(dshape, "blk")
        dd = jax.tree.map(
            lambda a, s: jax.device_put(a, NamedSharding(mesh, s)),
            ddata, specs)
        rng = np.random.default_rng(0)
        xh = jnp.asarray(rng.standard_normal((shape.n, nv)), jnp.float32)
        x = jax.device_put(xh, NamedSharding(mesh, P("blk", None)))
        y_ref = np.asarray(h2_matvec(shape, data, xh))

        mvs = {comm: make_dist_matvec(dshape, mesh, "blk", comm=comm)
               for comm in ("halo-plan", "ppermute", "allgather")}
        # the solver lowering (ISSUE 10): hide_flops > 0 collapses every
        # per-offset ppermute into ONE residue-layout all_to_all — the
        # round-count-minimal form the fused fractional iteration embeds
        mvs["halo-plan-merged"] = make_dist_matvec(
            dshape, mesh, "blk", comm="halo-plan", hide_flops=1)
        for comm, mv in mvs.items():          # warmup + parity gate
            y = np.asarray(mv(dd, x))
            err = np.linalg.norm(y - y_ref) / np.linalg.norm(y_ref)
            assert err < 1e-5, (comm, err)
        acc = interleaved_times(
            {comm: (lambda mv=mv: mv(dd, x)) for comm, mv in mvs.items()},
            reps=12 if quick else 24, warmup=0)   # parity gate warmed up
        root_b = (p - 1) * dshape.ranks[dshape.lc] * nv * 4
        for comm, ts in acc.items():
            model = (root_b + merged_exchange_bytes(dshape, nv)
                     if comm == "halo-plan-merged"
                     else matvec_comm_bytes(dshape, nv, comm))
            records.append({
                "name": f"dist_mv_N{shape.n}_{comm}",
                "us": round(float(np.median(ts)) * 1e6, 1),
                "model_bytes_per_dev": model,
                "N": shape.n, "nv": nv, "p": p, "comm": comm,
                "Csp": bs.sparsity_constant(),
            })
        records.append({
            "name": f"dist_speedup_N{shape.n}",
            "N": shape.n, "nv": nv, "p": p,
            "halo_plan_vs_allgather": round(
                median_ratio(acc["allgather"], acc["halo-plan"]), 2),
            "halo_plan_vs_ppermute": round(
                median_ratio(acc["ppermute"], acc["halo-plan"]), 2),
            "merged_vs_halo_plan": round(
                median_ratio(acc["halo-plan"], acc["halo-plan-merged"]), 2),
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
    cmd = [sys.executable, "-m", "benchmarks.dist_bench", "--worker"]
    if quick:
        cmd.append("--quick")
    proc = subprocess.run(cmd, capture_output=True, text=True, timeout=3000,
                          env=env, cwd=root)
    if proc.returncode != 0:
        raise RuntimeError(f"dist_bench worker failed:\n{proc.stdout}"
                           f"\n{proc.stderr}")
    payload = None
    for line in proc.stdout.splitlines():
        if line.startswith(MARKER):
            payload = json.loads(line[len(MARKER):])
    assert payload is not None, proc.stdout
    for r in payload:
        if "us" in r:
            out_rows.append(
                f"{r['name']},{r['us']:.1f},bytes={r['model_bytes_per_dev']}"
                f";p={r['p']};nv={r['nv']}")
        else:
            out_rows.append(
                f"{r['name']},0.0,vs_allgather={r['halo_plan_vs_allgather']}"
                f";vs_ppermute={r['halo_plan_vs_ppermute']}")
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
    with open("BENCH_dist.json", "w") as f:
        json.dump(records, f, indent=1)
    print("# wrote BENCH_dist.json")


if __name__ == "__main__":
    main()
