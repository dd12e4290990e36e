"""Driver of closed-loop single-RHS solves (paper §6.4).

Set-up jits the program's PCG with the operator, D, kappa and the V-cycle
arrays passed as arguments (``make_operator`` / ``mg_precond_local`` built
inside the trace), so the executable holds no operator constants and fits
the persistent compilation cache.  The traffic draws a pool of right-hand
sides b = h^2 (mean + noise * xi), xi iid N(0, 1), on the device from its
own fixed ``rhs_key``, the same for every seed: the iteration count differs
from one right-hand side to the next, so a pool drawn from the seed would
change the work with the seed.  The seed puts the pool in its order; unit i
solves with the i-th b of that order (cyclically) and waits for it.

The comparison: every solve must report convergence to the configuration's
tolerance with a clean status, and

  residual    for a sample of solves drawn from the seed (the last one
              always in it), ``||b - A x|| / ||b||`` against the plain
              float64 reference operator (``reference.fractional``);
  k_proj_err  the compressed K that the window's solves applied, against
              the reference's Chebyshev K: its couplings are the
              projections of the reference's on its own orthonormal bases
              (``blocks`` per level).

The control (``control``) solves the same right-hand sides with the
reference operator on the device and puts the reference's projected
couplings in K, every product at a lower precision; the faults
(``FAULTS``, ``plant``) break the jitted solve where it answers.
"""
from __future__ import annotations

import dataclasses
import functools
import json
import types

import numpy as np


def setup(prob: dict, cfg: dict, traffic: dict, seed: int) -> dict:
    import jax
    import jax.numpy as jnp
    from bench.seeds import jax_key
    from repro.apps.fractional import make_operator
    from repro.solvers import build_grid_mg, mg_precond_local, pcg

    n, h = prob["n"], prob["h"]
    mgc = cfg["solver"]["mg"]
    mg, mga = build_grid_mg(prob["kappa"], prob["d_diag"].reshape(n, n),
                            prob["gamma"], h, n, p=1, nu=mgc["nu"],
                            omega=mgc["omega"], n_cycles=mgc["n_cycles"])
    static = {k: prob[k] for k in ("shape", "perm", "unperm", "gamma", "h",
                                   "n")}
    tol, maxiter = cfg["solver"]["tol"], cfg["solver"]["maxiter"]

    def solve(data, d_diag, kappa, mg_arrays, b):
        apply_a = make_operator(dict(static, data=data, d_diag=d_diag,
                                     kappa=kappa))
        return pcg(apply_a, b,
                   lambda r: mg_precond_local(mg, mg_arrays, r),
                   tol=tol, maxiter=maxiter)

    args = (prob["data"], prob["d_diag"], prob["kappa"], mga)
    pool = traffic["pool"]

    def draw(key, order):
        f = traffic["mean"] + traffic["noise"] * jax.random.normal(
            key, (pool, n * n), jnp.float32)
        f = f[order]
        return tuple((h * h) * f[i] for i in range(pool))

    # one right-hand side per unit, split in set-up: the window indexes
    # nothing
    order = np.random.default_rng([seed, 5]).permutation(pool)
    rhs = jax.jit(draw)(jax_key(jax, traffic["rhs_key"]), jnp.asarray(order))
    solver = jax.jit(solve).lower(*args, rhs[0]).compile()
    jax.block_until_ready(solver(*args, rhs[0]).x)
    return {"solver": solver, "args": args, "rhs": rhs, "pool": pool,
            "cfg": cfg, "shape": prob["shape"]}


def unit(state: dict, i: int):
    import jax
    res = state["solver"](*state["args"], state["rhs"][i % state["pool"]])
    with jax.profiler.TraceAnnotation("bench/wait"):
        res.x.block_until_ready()
    return res


def summarize(state: dict, outputs: list, elapsed: float) -> dict:
    from bench.reference.h2_answer import to_host
    state["host_k"] = to_host(state["args"][0])
    tol = state["cfg"]["solver"]["tol"]
    iters = [int(r.iters) for r in outputs]
    bad = sum(1 for r in outputs if not (bool(r.converged) and
                                         int(r.status) == 0 and
                                         float(r.relres) <= tol))
    return {"attempted": len(outputs), "failed": bad,
            "end_to_end": {"solve_s": elapsed / len(outputs)},
            "units": len(outputs), "iterations": sum(iters),
            "matvecs": sum(iters),
            "matvec_shape": state["shape"], "nv": 1}


def release(state: dict) -> None:
    state.pop("solver")
    state.pop("args")


def sample(seed: int, count: int, k: int) -> list:
    """Indices of the answers compared: k drawn from the seed, and the last."""
    rng = np.random.default_rng(seed)
    pick = rng.choice(count, size=min(k, count), replace=False)
    return sorted(set(int(i) for i in pick) | {count - 1})


@functools.lru_cache(maxsize=1)
def reference(cfg_json: str):
    """The plain reference of a configuration (built once per process)."""
    from bench.reference.fractional import FractionalReference
    return FractionalReference(json.loads(cfg_json))


def check(state: dict, outputs: list, cfg: dict, traffic: dict, seed: int,
          limits: dict) -> dict:
    from bench.reference.h2_answer import explicit_bases, projection_gap

    idx = sample(seed, len(outputs), traffic["compare"])
    pool = state["pool"]
    b = np.stack([np.asarray(state["rhs"][i % pool], np.float64)
                  for i in idx], 1)
    x = np.stack([np.asarray(outputs[i].x, np.float64) for i in idx], 1)
    ref = reference(json.dumps(cfg, sort_keys=True))
    res = ref.residual(b, x)
    if not state["shape"].symmetric:
        raise NotImplementedError("the fractional K is symmetric")
    h = state["host_k"]
    gap = projection_gap(h, explicit_bases(h["u_leaf"], h["e"]), ref.K,
                         ref.K.kernel, np.random.default_rng([seed, 4]),
                         traffic["blocks"])
    return {"residual": {"value": float(res.max()),
                         "limit": limits["residual"]},
            "k_proj_err": {"value": gap, "limit": limits["k_proj_err"]}}


FAULTS = ("unchanged", "altered")


def plant(state: dict, fault: str, frac: float) -> None:
    """Break the solve where it answers: ``unchanged`` returns the start
    x = 0, ``altered`` scales x by (1 + frac)."""
    solver = state["solver"]

    def broken(*args):
        res = solver(*args)
        x = res.x * 0.0 if fault == "unchanged" else res.x * (1.0 + frac)
        return dataclasses.replace(res, x=x)
    state["solver"] = broken


def control(state: dict, units: int, cfg: dict, traffic: dict, seed: int,
            precision: str, limits: dict) -> dict:
    """The readings of CG (Jacobi) on the reference operator applied at
    ``precision``, on the right-hand sides the program's run solved, and
    of K with the reference's projected couplings at ``precision``."""
    import jax
    import jax.numpy as jnp
    from bench.reference.h2_answer import explicit_bases, projected_couplings
    from bench.reference.precision import einsum

    ref = reference(json.dumps(cfg, sort_keys=True))
    apply = _device_apply(json.dumps(cfg, sort_keys=True), precision)
    h = ref.h
    kp = np.pad(ref.kappa, 1, mode="edge")
    c = kp[1:-1, 1:-1]
    faces = 2 * c + 0.5 * (kp[2:, 1:-1] + kp[:-2, 1:-1] + kp[1:-1, 2:]
                           + kp[1:-1, :-2])
    diag = jnp.asarray((h * h) * (ref.d + ref.gamma * faces.ravel() /
                                  (h * h)), jnp.float32)
    tol = cfg["solver"]["tol"]

    dot = functools.partial(jnp.dot, precision="highest")

    @jax.jit
    def cg(b):
        def body(s):
            k, x, r, p, rz = s
            ap = apply(p[:, None])[:, 0]
            alpha = rz / dot(p, ap)
            x, r = x + alpha * p, r - alpha * ap
            z = r / diag
            rz_new = dot(r, z)
            return k + 1, x, r, z + (rz_new / rz) * p, rz_new

        def cond(s):
            return (s[0] < 20000) & (jnp.linalg.norm(s[2]) >
                                     tol * jnp.linalg.norm(b))
        z = b / diag
        return jax.lax.while_loop(cond, body, (0, jnp.zeros_like(b), b, z,
                                               dot(b, z)))[1]

    idx = set(sample(seed, units, traffic["compare"]))
    outs = [types.SimpleNamespace(
        x=cg(state["rhs"][i % state["pool"]]) if i in idx else None)
        for i in range(units)]
    hk = state["host_k"]
    state = dict(state, host_k=dict(hk, s=projected_couplings(
        hk, explicit_bases(hk["u_leaf"], hk["e"]), ref.K,
        einsum(precision))))
    return check(state, outs, cfg, traffic, seed, limits)


@functools.lru_cache(maxsize=2)
def _device_apply(cfg_json: str, precision: str):
    return reference(cfg_json).device_apply(precision)
