"""Smoke run of the paper's §6.4 fractional-diffusion solve on a TPU.

    python chip_smoke.py              # one chip
    python chip_smoke.py --chips 4    # the distributed solve on four chips

On one chip it drives the main path through its entry points at n = 256
(65,536 unknowns: the largest grid whose set-up fits a 16 GB v5e, because
the diagonal D is assembled from an H^2 operator on the 3n x 3n extended
grid):

  build    ``FractionalProblem(256).build()``: Chebyshev construction,
           ``compress(tol)`` on the device, then D;
  certify  the compressed K against the chunked exact kernel apply;
  solve    the jitted GMG-preconditioned PCG solve, checked by its own
           residual and by the true residual recomputed outside the loop;
  pallas   the compiled Pallas ``h2_matvec`` (nv 1 and 16) and
           ``compress(tol)`` against the ``jnp`` backend.

``--chips 4`` builds the same problem and runs only the distributed solve
(``make_dist_solve`` on a 4-device mesh with its defaults: halo-plan
exchange, fused schedule) and the one-device solve it is compared with.

Each phase prints one JSON line.  The last line of standard output is
``{"ok": true, "device": {"platform": ..., "kind": ..., "count": ...}}``.
A failed check raises, so the script exits non-zero; so does a run where
JAX finds no TPU.  The persistent compilation cache goes where
``JAX_COMPILATION_CACHE_DIR`` says, else to ``.jax_cache`` in this
checkout.
"""
from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parent
N = 256                 # grid side: N*N unknowns
# K against the exact kernel: the Chebyshev construction (cheb_p=6,
# eta=0.9) itself is 4.5e-3 off this kernel at n = 32..128, so 1e-2 is the
# tightest decade it meets; compress(1e-6) adds nothing measurable
CERT_TOL = 1e-2
SOLVE_TOL = 1e-8        # PCG tolerance on ||r|| / ||b||
MAXITER = 500
# GMG iterations grow with n (29, 54, 88, 146 at n=32..256): more than
# ITERS_MAX means the preconditioner stopped working
ITERS_MAX = 200
# ||b - Au|| / ||b|| recomputed outside the loop: the f32 matvec's floor
# grows with the conditioning (1.8e-5 at n=64, 6.1e-5 at n=128)
TRUE_RES_TOL = 1e-3
PALLAS_TOL = 1e-5       # compiled Pallas vs jnp matvec, relative
COMPRESS_TOL = 1e-4     # Pallas- vs jnp-compressed K (both at h2_tol=1e-6)


class SmokeFailure(AssertionError):
    """A phase's result is wrong."""


def check(ok: bool, what: str) -> None:
    if not ok:
        raise SmokeFailure(what)


class CompileStats:
    """Backend compile seconds and persistent-cache hits, from JAX's own
    monitoring events."""

    def __init__(self, jax):
        self.compile_s = 0.0
        self.hits = 0
        self.misses = 0
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)

    def _duration(self, event, secs, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            self.compile_s += secs

    def _event(self, event, **_):
        if event == "/jax/compilation_cache/cache_hits":
            self.hits += 1
        elif event == "/jax/compilation_cache/cache_misses":
            self.misses += 1

    def snapshot(self):
        return self.compile_s, self.hits, self.misses

    def since(self, snap):
        return {"compile_s": self.compile_s - snap[0],
                "cache_hits": self.hits - snap[1],
                "cache_misses": self.misses - snap[2]}


def report(phase: str, **fields) -> None:
    print(json.dumps({"phase": phase, **fields}), flush=True)


def peak_bytes(devices):
    return [d.memory_stats()["peak_bytes_in_use"] for d in devices]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: run only the distributed solve on a 4-chip "
                         "mesh against the one-device solve")
    args = ap.parse_args(argv)

    import jax
    backend = jax.default_backend()
    if backend != "tpu":
        print(f"chip_smoke: needs a TPU; JAX found platform {backend!r}",
              file=sys.stderr)
        return 2
    devices = jax.devices()
    if len(devices) < args.chips:
        print(f"chip_smoke: --chips {args.chips} needs {args.chips} "
              f"devices; JAX found {len(devices)}", file=sys.stderr)
        return 2

    sys.path.insert(0, str(ROOT / "src"))
    from repro.launch.compile_cache import enable_compile_cache
    cache_dir = enable_compile_cache(ROOT)
    stats = CompileStats(jax)
    d0 = devices[0]
    report("device", platform=d0.platform, kind=d0.device_kind,
           count=len(devices), cache_dir=cache_dir)

    prob, fp = build(jax, stats)
    res = solve_one_device(jax, stats, prob)
    if args.chips == 4:
        solve_distributed(jax, stats, prob, res, devices[:4])
    else:
        certify(jax, prob, fp)
        pallas(jax, stats, prob, fp)
    report("cache", **stats.since((0.0, 0, 0)))
    print(json.dumps({"ok": True, "device": {
        "platform": d0.platform, "kind": d0.device_kind,
        "count": len(devices)}}))
    return 0


def build(jax, stats):
    from repro.apps.fractional import FractionalProblem

    fp = FractionalProblem(N)
    snap = stats.snapshot()
    t0 = time.perf_counter()
    prob = fp.build()
    jax.block_until_ready((prob["data"], prob["d_diag"]))
    setup_s = time.perf_counter() - t0
    report("build", n=N * N, setup_s=setup_s, **stats.since(snap),
           ranks=list(prob["shape"].ranks),
           peak_bytes_in_use=peak_bytes(jax.devices()[:1]))
    return prob, fp


def _reference(jnp, prob, fp):
    from repro.apps.fractional import interior_grid
    from repro.core.kernels_fn import fractional_kernel_2d
    from repro.guard import kernel_reference_apply

    pts = jnp.asarray(interior_grid(N), jnp.float32)
    return kernel_reference_apply(
        pts, fractional_kernel_2d(fp.beta, xp=jnp), prob["perm"])


def certify(jax, prob, fp):
    import jax.numpy as jnp
    from repro.guard import certify_h2

    t0 = time.perf_counter()
    cert = certify_h2(prob["shape"], prob["data"], _reference(jnp, prob, fp),
                      tol=CERT_TOL)
    report("certify", rel_err=cert.rel_err, tol=CERT_TOL, ok=cert.ok,
           probes=cert.probes, s=time.perf_counter() - t0)
    check(cert.ok, f"compressed K fails certification: rel_err "
                   f"{cert.rel_err} > {CERT_TOL}")


def solve_one_device(jax, stats, prob):
    """``solve``'s body on the built problem: jitted PCG + GMG V-cycles."""
    import jax.numpy as jnp
    from repro.apps.fractional import make_operator, make_preconditioner
    from repro.guard.status import worst_status
    from repro.solvers import pcg

    apply_a = make_operator(prob)
    pre = make_preconditioner(prob)
    b = jnp.ones((N * N,), jnp.float32) * prob["h"] ** 2
    snap = stats.snapshot()
    t0 = time.perf_counter()
    solver = jax.jit(lambda rhs: pcg(apply_a, rhs, pre, tol=SOLVE_TOL,
                                     maxiter=MAXITER)).lower(b).compile()
    compile_s = time.perf_counter() - t0
    cstats = stats.since(snap)
    t0 = time.perf_counter()
    res = solver(b)
    jax.block_until_ready(res.x)
    solve_s = time.perf_counter() - t0
    true_res = float(jnp.linalg.norm(b - jax.jit(apply_a)(res.x))
                     / jnp.linalg.norm(b))
    iters, relres = int(res.iters), float(res.relres)
    status = worst_status(res.status)
    report("solve", iters=iters, relres=relres, true_relres=true_res,
           status=status, converged=bool(res.converged),
           compile_wall_s=compile_s, solve_s=solve_s, **cstats,
           peak_bytes_in_use=peak_bytes(jax.devices()[:1]))
    check(bool(res.converged) and status == 0,
          f"solve did not converge cleanly (status {status})")
    check(relres <= SOLVE_TOL, f"relres {relres} > {SOLVE_TOL}")
    check(iters <= ITERS_MAX, f"{iters} iterations > {ITERS_MAX}")
    check(true_res <= TRUE_RES_TOL,
          f"true relative residual {true_res} > {TRUE_RES_TOL}")
    return res


def pallas(jax, stats, prob, fp):
    """Compiled Pallas kernels against the jnp backend."""
    import jax.numpy as jnp
    from repro.core.compression import (_leaf_factors_jit,
                                        _orthogonalize_weights, compress)
    from repro.core.construction import construct_h2
    from repro.core.kernels_fn import fractional_kernel_2d
    from repro.core.matvec import h2_matvec
    from repro.apps.fractional import interior_grid
    from repro.guard import certify_h2, certify_matvec

    shape, data = prob["shape"], prob["data"]
    for nv in (1, 16):
        x = jax.random.normal(jax.random.key(nv), (shape.n, nv), jnp.float32)
        text = h2_matvec.lower(shape, data, x, "pallas").compile().as_text()
        check("tpu_custom_call" in text,
              f"pallas h2_matvec (nv={nv}) holds no compiled kernel")
        y_j = h2_matvec(shape, data, x, "jnp")
        y_p = h2_matvec(shape, data, x, "pallas")
        rel = float(jnp.linalg.norm(y_p - y_j) / jnp.linalg.norm(y_j))
        report("pallas_matvec", nv=nv, rel_diff=rel, tol=PALLAS_TOL)
        check(rel <= PALLAS_TOL, f"pallas matvec nv={nv}: rel diff {rel}")

    # compress(tol) from the same Chebyshev operator build() compressed
    shape0, data0, _, _ = construct_h2(
        interior_grid(N), fractional_kernel_2d(fp.beta),
        leaf_size=shape.leaf_size, cheb_p=fp.cheb_p, eta=fp.eta)
    aliased = bool(shape0.symmetric and data0.v_leaf is data0.u_leaf)
    text = _orthogonalize_weights.lower(shape0, data0, "pallas",
                                        aliased).compile().as_text()
    check("tpu_custom_call" in text, "pallas QR stage holds no kernel")
    _, ru, _ = _orthogonalize_weights(shape0, data0, "pallas", aliased)
    text = _leaf_factors_jit.lower(ru[shape0.depth],
                                   "pallas").compile().as_text()
    check("tpu_custom_call" in text, "pallas SVD stage holds no kernel")
    snap = stats.snapshot()
    t0 = time.perf_counter()
    shape_p, data_p = compress(shape0, data0, tol=fp.h2_tol,
                               backend="pallas")
    jax.block_until_ready(data_p)
    compress_s = time.perf_counter() - t0
    cert = certify_h2(shape_p, data_p, _reference(jnp, prob, fp),
                      tol=CERT_TOL)
    same = certify_matvec(lambda x: h2_matvec(shape_p, data_p, x),
                          lambda x: h2_matvec(shape, data, x), shape.n,
                          tol=COMPRESS_TOL)
    report("pallas_compress", ranks=list(shape_p.ranks),
           jnp_ranks=list(shape.ranks), rel_err=cert.rel_err,
           rel_diff_vs_jnp=same.rel_err, s=compress_s, **stats.since(snap))
    # h2_tol = 1e-6 cuts at sigma > 1e-6 sigma_max, ~8 f32 ulps of
    # sigma_max: XLA's SVD and the Jacobi kernel put a sigma that close to
    # the cut on either side of it, so a level's rank may differ by one
    check(all(abs(a - b) <= 1 for a, b in zip(shape_p.ranks, shape.ranks)),
          f"pallas ranks {shape_p.ranks} vs jnp ranks {shape.ranks}")
    check(same.ok, f"pallas- vs jnp-compressed K differ by {same.rel_err}")
    check(cert.ok, f"pallas-compressed K fails certification: rel_err "
                   f"{cert.rel_err} > {CERT_TOL}")


def solve_distributed(jax, stats, prob, res1, devices):
    """``make_dist_solve`` on a 4-device mesh against the one-device
    solve of the same problem."""
    import jax.numpy as jnp
    import numpy as np
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
    from repro.apps.fractional import make_dist_solve
    from repro.guard.status import worst_status

    mesh = Mesh(np.asarray(devices), ("blk",))
    parts = make_dist_solve(prob, mesh, tol=SOLVE_TOL, maxiter=MAXITER)
    args = parts["place"](parts["args"])
    b = jnp.ones((N * N,), jnp.float32) * prob["h"] ** 2
    b_dev = jax.device_put(b, NamedSharding(mesh, P("blk")))
    snap = stats.snapshot()
    t0 = time.perf_counter()
    fn = parts["fn"].lower(*args, b_dev).compile()
    compile_s = time.perf_counter() - t0
    cstats = stats.since(snap)
    t0 = time.perf_counter()
    res = fn(*args, b_dev)
    jax.block_until_ready(res.x)
    solve_s = time.perf_counter() - t0
    x1, x4 = np.asarray(res1.x), np.asarray(res.x)
    rel = float(np.linalg.norm(x4 - x1) / np.linalg.norm(x1))
    iters, iters1 = int(res.iters), int(res1.iters)
    status = worst_status(res.status)
    report("dist_solve", p=len(devices), comm="halo-plan",
           fused=bool(parts["fused"]), iters=iters, iters_one_device=iters1,
           relres=float(res.relres), status=status,
           converged=bool(res.converged), rel_diff_vs_one_device=rel,
           compile_wall_s=compile_s, solve_s=solve_s, **cstats,
           peak_bytes_in_use=peak_bytes(devices))
    check(bool(res.converged) and status == 0,
          f"distributed solve did not converge cleanly (status {status})")
    check(abs(iters - iters1) <= 2,
          f"distributed {iters} vs one-device {iters1} iterations")
    check(rel <= 1e-5, f"distributed solution differs by {rel}")


if __name__ == "__main__":
    sys.exit(main())
