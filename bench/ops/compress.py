"""Driver of closed-loop algebraic recompressions (paper §5, §6.2).

Set-up keeps the configuration's uncompressed Chebyshev operator on the
device and compresses it once (compiling every stage).  Unit i runs
``compress(shape0, data0, tol)`` on it and waits for the result.

Answers compared: the last one and one drawn from the seed.  An answer is
an operator (nested orthonormal bases U', couplings S', the dense leaves);
it is read back to the host and checked by plain code, never by the
program's matvec:

  kernel_err  the answer applied to a seeded block P (explicit bases,
              float64) against the exact kernel rows times P, on sampled
              rows: the truncation and interpolation error, so it catches
              ranks cut too far (the ``loose`` fault), which the projection
              identity below cannot see;
  proj_err    a compression projects: S'_ts = U'_t^T U_t S_ts V_s^T V'_s
              with U_t, S_ts the Chebyshev basis and coupling of the plain
              reference (``reference.cheb_h2``) and U'_t the answer's own
              orthonormal basis.  The largest relative gap over sampled
              blocks; a compression computed below the configuration's
              precision, bases that are not orthonormal, or none at all,
              fail here.

The control (``control``) puts the reference's projection, computed at a
lower precision, in place of the answer's couplings; the faults
(``FAULTS``, ``plant``) break ``compress`` where it answers.
"""
from __future__ import annotations

import dataclasses
import json

import numpy as np


def setup(system: dict, cfg: dict, traffic: dict, seed: int) -> dict:
    import jax
    from repro.core.compression import compress

    shape0, data0 = system["shape0"], system["data0"]
    tol = cfg["compress_tol"]
    jax.block_until_ready(compress(shape0, data0, tol=tol)[1])
    keep = int(np.random.default_rng([seed, 2]).integers(
        traffic["keep_within"]))
    return {"shape0": shape0, "data0": data0, "tol": tol,
            "compress": compress, "perm": np.asarray(system["perm"]),
            "keep": keep, "kept": {}}


def unit(state: dict, i: int):
    import jax
    shape, data = state["compress"](state["shape0"], state["data0"],
                                    tol=state["tol"])
    with jax.profiler.TraceAnnotation("bench/wait"):
        jax.block_until_ready(data)
    if i == state["keep"]:
        state["kept"][i] = (shape, data)
    state["last"] = (i, (shape, data))
    return None


def summarize(state: dict, outputs: list, elapsed: float) -> dict:
    units = len(outputs)
    i, ans = state.pop("last")
    state["kept"][i] = ans
    return {"attempted": units, "failed": 0,
            "end_to_end": {"compress_s": elapsed / units},
            "units": units, "matvecs": 0}


def release(state: dict) -> None:
    state.pop("data0")
    state.pop("compress")


def compare(answers: dict, state: dict, cfg: dict, traffic: dict,
            seed: int, limits: dict) -> dict:
    import jax.numpy as jnp
    from bench.reference.cheb_h2 import for_config
    from bench.reference.dense_rows import rows_apply
    from bench.reference.h2_answer import (apply_rows, explicit_bases,
                                           projection_gap, to_host)
    from bench.reference.kernels import by_name

    ref = for_config(json.dumps(cfg, sort_keys=True))
    pts = ref.points
    perm = state["perm"]
    rng = np.random.default_rng([seed, 3])
    m = cfg["leaf"]
    leaves = np.sort(rng.choice(pts.shape[0] // m, size=traffic["leaves"],
                                replace=False))
    rows = (leaves[:, None] * m + np.arange(m)).ravel()   # tree order
    p = rng.standard_normal((pts.shape[0], traffic["probes"]))
    want = rows_apply(by_name(cfg["kernel"], jnp), pts[perm[rows]],
                      pts[perm], jnp.asarray(p, jnp.float32))

    kernel_err = proj_err = 0.0
    for _, (shape, data) in sorted(answers.items()):
        if not shape.symmetric:
            raise NotImplementedError("the cells hold symmetric kernels")
        h = data if isinstance(data, dict) else to_host(data)
        bases = explicit_bases(h["u_leaf"], h["e"])
        got = apply_rows(h, bases, p, leaves)
        kernel_err = max(kernel_err, float(
            np.linalg.norm(got - want) / np.linalg.norm(want)))
        proj_err = max(proj_err, projection_gap(
            h, bases, ref, ref.kernel, rng, traffic["blocks"]))
    return {"kernel_err": {"value": kernel_err,
                           "limit": limits["kernel_err"]},
            "proj_err": {"value": proj_err, "limit": limits["proj_err"]}}


def check(state: dict, outputs: list, cfg: dict, traffic: dict, seed: int,
          limits: dict) -> dict:
    return compare(state["kept"], state, cfg, traffic, seed, limits)


FAULTS = ("unchanged", "altered", "loose")


def plant(state: dict, fault: str, frac: float) -> None:
    """Break ``compress`` where it answers: ``unchanged`` returns the
    uncompressed operator, ``altered`` scales every coupling by
    (1 + frac), ``loose`` truncates at ten times the tolerance (ranks cut
    too far)."""
    compress = state["compress"]

    def broken(shape0, data0, tol):
        if fault == "unchanged":
            return shape0, data0
        if fault == "loose":
            return compress(shape0, data0, tol=10.0 * tol)
        shape, data = compress(shape0, data0, tol=tol)
        return shape, dataclasses.replace(
            data, s=[s * (1.0 + frac) for s in data.s])
    state["compress"] = broken


def control(state: dict, units: int, cfg: dict, traffic: dict, seed: int,
            precision: str, limits: dict) -> dict:
    """The readings of the program's answers with every coupling block
    replaced by the reference's projection ``M_t S_ts M_s^T`` computed at
    ``precision``."""
    from bench.reference.cheb_h2 import for_config
    from bench.reference.h2_answer import (explicit_bases,
                                           projected_couplings, to_host)
    from bench.reference.precision import einsum

    ref = for_config(json.dumps(cfg, sort_keys=True))
    answers = {}
    for i, (shape, data) in state["kept"].items():
        h = to_host(data)
        s = projected_couplings(h, explicit_bases(h["u_leaf"], h["e"]), ref,
                                einsum(precision))
        answers[i] = (shape, dict(h, s=s))
    return compare(answers, state, cfg, traffic, seed, limits)
