"""Driver of closed-loop H^2 operator applications (paper Fig. 9 HGEMV).

Set-up compresses the configuration's Chebyshev operator to its ``tol``
once and draws a pool of ``pool`` blocks X ``[N, nv]`` on the device from
the seed: iid N(0, 1) entries, except ``probes`` columns per block, spread
over the block, that are unit vectors e_j at grid points j drawn from the
seed.  Unit i applies the operator to X[i mod pool] with ``h2_matvec``
(default backend); it waits for the application ``ahead`` units before it
(for its own where ``ahead`` is 0), so that the chip is kept fed while the
host stands still, and ``drain`` waits for the rest once the window's time
is up.

Answers compared, after the window, with the applied operator read back
to the host: a sample of units drawn from the seed, and the last one.

  apply_err   Y at the rows of ``leaves`` leaves drawn from the seed
              against the operator's own factors applied by plain float64
              code (``reference.h2_answer``), relative Frobenius gap: the
              window applied the operator it holds;
  probe_err   at each probe column, the entries of Y at the grid
              neighbours of j, whose leaf pairs are always dense blocks:
              there the operator is the kernel itself, so Y must equal
              K(x_i, x_j) (float64) to float32 rounding;
  proj_err    the operator the window applied, against the plain Chebyshev
              reference (``reference.cheb_h2``): its couplings are the
              projections ``U'_t^T U_t S_ts V_s^T V'_s`` of the reference's
              on its own orthonormal bases (``blocks`` per level; the
              compress cell's ``proj_err``).

The control (``control``) puts the operator's factors, applied by the
plain code at a lower precision, in the program's place; the faults
(``FAULTS``, ``plant``) break ``h2_matvec`` where it answers.
"""
from __future__ import annotations

import collections
import json

import numpy as np


def probe_columns(nv: int, probes: int) -> np.ndarray:
    """Probe columns spread evenly over the block (both halves)."""
    return np.linspace(0, nv - 1, probes).round().astype(int)


def probe_points(cfg: dict, seed: int, count: int) -> np.ndarray:
    """Grid indices of the probe columns: interior points (all four grid
    neighbours exist), drawn from the seed."""
    side = cfg["grid"]["side"]
    rng = np.random.default_rng([seed, 1])
    ij = rng.integers(1, side - 1, size=(count, 2))
    return ij[:, 0] * side + ij[:, 1]


def setup(system: dict, cfg: dict, traffic: dict, seed: int) -> dict:
    import jax
    import jax.numpy as jnp
    from bench.seeds import jax_key
    from repro.core.compression import compress
    from repro.core.matvec import h2_matvec

    shape, data = compress(system["shape0"], system["data0"],
                           tol=cfg["compress_tol"])
    pool, nv, probes = traffic["pool"], traffic["nv"], traffic["probes"]
    n = shape.n
    perm = np.asarray(system["perm"])
    inv = np.argsort(perm)
    js = probe_points(cfg, seed, pool * probes).reshape(pool, probes)
    cols = probe_columns(nv, probes)

    def draw(key, rows_j):
        x = jax.random.normal(key, (pool, n, nv), jnp.float32)
        # the probe columns of each block: e_j in tree order
        x = x.at[:, :, cols].set(0.0)
        b = jnp.arange(pool)[:, None]
        x = x.at[b, rows_j, jnp.asarray(cols)[None, :]].set(1.0)
        return tuple(x[i] for i in range(pool))

    # one block per unit, split in set-up: the window indexes nothing
    xs = jax.jit(draw)(jax_key(jax, seed), jnp.asarray(inv[js]))
    jax.block_until_ready(h2_matvec(shape, data, xs[0]))
    keep = set(int(i) for i in np.random.default_rng([seed, 2]).choice(
        traffic["keep_within"], size=traffic["compare"], replace=False))
    return {"shape": shape, "data": data, "xs": xs, "pool": pool,
            "perm": perm, "probes": js, "keep": keep, "kept": {},
            "matvec": h2_matvec, "ahead": traffic["ahead"],
            "sent": collections.deque()}


def unit(state: dict, i: int):
    y = state["matvec"](state["shape"], state["data"],
                        state["xs"][i % state["pool"]])
    state["sent"].append(y)
    while len(state["sent"]) > state["ahead"]:
        wait(state["sent"].popleft())
    if i in state["keep"]:
        state["kept"][i] = y
    state["last"] = (i, y)
    return None


def wait(y) -> None:
    import jax
    with jax.profiler.TraceAnnotation("bench/wait"):
        y.block_until_ready()


def drain(state: dict) -> None:
    while state["sent"]:
        wait(state["sent"].popleft())


def summarize(state: dict, outputs: list, elapsed: float) -> dict:
    from bench.reference.h2_answer import to_host
    units = len(outputs)
    i, y = state.pop("last")
    state["kept"][i] = y
    state["host_op"] = to_host(state["data"])
    return {"attempted": units, "failed": 0,
            "end_to_end": {"apply_ms": 1e3 * elapsed / units},
            "units": units, "matvecs": units,
            "matvec_shape": state["shape"],
            "nv": state["xs"][0].shape[-1]}


def release(state: dict) -> None:
    state.pop("data")
    state.pop("matvec")


def compare(answers: dict, state: dict, cfg: dict, traffic: dict, seed: int,
            limits: dict) -> dict:
    """The three numbers for answers ``{unit index: Y (tree order)}``."""
    from bench.reference.cheb_h2 import for_config
    from bench.reference.grids import grid_neighbours
    from bench.reference.h2_answer import (apply_rows, explicit_bases,
                                           projection_gap)

    h = state["host_op"]
    bases = explicit_bases(h["u_leaf"], h["e"])
    ref = for_config(json.dumps(cfg, sort_keys=True))
    pts = ref.points
    inv = np.argsort(state["perm"])
    m = h["dense"].shape[1]
    leaves = np.sort(np.random.default_rng([seed, 3]).choice(
        h["u_leaf"].shape[0], size=traffic["leaves"], replace=False))
    cols = probe_columns(state["xs"][0].shape[-1], traffic["probes"])
    num = den = probe = 0.0
    for i, y in sorted(answers.items()):
        b = i % state["pool"]
        y = np.asarray(y, np.float64)
        want = apply_rows(h, bases, np.asarray(state["xs"][b], np.float64),
                          leaves)
        got = y.reshape(-1, m, y.shape[1])[leaves].reshape(want.shape)
        num += float(((got - want) ** 2).sum())
        den += float((want ** 2).sum())
        for c, j in zip(cols, state["probes"][b]):
            nb = grid_neighbours(cfg["grid"], int(j))
            k = ref.kernel(pts[nb], pts[j])
            probe = max(probe, float(np.max(np.abs(y[inv[nb], c] - k) /
                                            np.abs(k))))
    proj = projection_gap(h, bases, ref, ref.kernel,
                          np.random.default_rng([seed, 4]),
                          traffic["blocks"])
    return {"apply_err": {"value": (num / den) ** 0.5,
                          "limit": limits["apply_err"]},
            "probe_err": {"value": probe, "limit": limits["probe_err"]},
            "proj_err": {"value": proj, "limit": limits["proj_err"]}}


def check(state: dict, outputs: list, cfg: dict, traffic: dict, seed: int,
          limits: dict) -> dict:
    return compare(state["kept"], state, cfg, traffic, seed, limits)


FAULTS = ("unchanged", "half", "altered")


def plant(state: dict, fault: str, frac: float) -> None:
    """Break ``h2_matvec`` where it answers: ``unchanged`` returns X,
    ``half`` leaves the last nv/2 columns out (zero), ``altered`` scales Y
    by (1 + frac)."""
    matvec = state["matvec"]

    def broken(shape, data, x):
        if fault == "unchanged":
            return x
        y = matvec(shape, data, x)
        if fault == "half":
            return y.at[:, y.shape[1] // 2:].set(0.0)
        return y * (1.0 + frac)
    state["matvec"] = broken


def control(state: dict, units: int, cfg: dict, traffic: dict, seed: int,
            precision: str, limits: dict) -> dict:
    """The readings of answers whose compared rows are the operator's
    factors applied by the plain code on the device at ``precision``."""
    import jax.numpy as jnp
    from bench.reference.grids import grid_neighbours
    from bench.reference.h2_answer import apply_rows, explicit_bases
    from bench.reference.precision import einsum

    h = state["host_op"]
    bases = explicit_bases(h["u_leaf"], h["e"])
    m = h["dense"].shape[1]
    n = h["u_leaf"].shape[0] * m
    inv = np.argsort(state["perm"])
    leaves = np.random.default_rng([seed, 3]).choice(
        n // m, size=traffic["leaves"], replace=False)
    answers = {}
    for i in state["kept"]:
        b = i % state["pool"]
        nbs = np.concatenate([grid_neighbours(cfg["grid"], int(j))
                              for j in state["probes"][b]])
        need = np.unique(np.concatenate([leaves, inv[nbs] // m]))
        y = np.zeros((n // m, m, traffic["nv"]))
        y[need] = np.asarray(apply_rows(
            h, bases, state["xs"][b], need, ein=einsum(precision), xp=jnp),
            np.float64).reshape(need.size, m, -1)
        answers[i] = y.reshape(n, -1)
    return compare(answers, state, cfg, traffic, seed, limits)
