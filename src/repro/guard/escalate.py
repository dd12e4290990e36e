"""Escalation policies: status -> recovery ladder (guard pillar 3).

``run_with_guards`` is the generic orchestrator: it walks a ladder of
named *rungs* (thunks producing a solve-like result), accepts the first
result that passes (converged, every status OK), and counts every
attempt / acceptance / rejection as an obs counter ``guard/...``
(``repro.obs.count``) so the obs layer and the serving metrics can
surface trip rates.  The rung vocabulary the
apps wire in (DESIGN.md §11):

- ``fp64-scalars`` — re-trace the solve under :func:`fp64_scalars` with
  ``scalar_dtype=float64``: the Krylov *reductions* accumulate in double
  while the vectors (and the operator) stay in working precision.  This
  is the cheapest rung — it recovers stagnation caused by dot-product
  rounding, the dominant fp32 failure mode.
- ``fp32-comm`` — drop ``halo-plan-bf16`` exchange payloads to fp32
  (distributed solves; the elastic restart ladder applies it).
- oversampling escalation — :func:`construct_h2_certified` doubles the
  rangefinder budget until the operator certifies.
- ``loose`` — a looser-tolerance solve as the last resort (serving keeps
  a looser-tol cached operator for the same purpose).

Counters are process-global and monotone, like the ``retrace/...`` ones;
``reset_guard_counters`` is for tests.
"""
from __future__ import annotations

import contextlib
import dataclasses
from typing import Any, Callable, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.obs.trace import REGISTRY, count

from .certify import Certificate, certify_h2, kernel_reference_apply
from .status import STATUS_OK, status_name, worst_status


def reset_guard_counters() -> None:
    """Zero the ``guard/...`` counters (tests)."""
    REGISTRY.clear("guard/")


@contextlib.contextmanager
def fp64_scalars():
    """Enable-x64 scope for the ``fp64-scalars`` rung: inside it, pass
    ``scalar_dtype=jnp.float64`` to a solver and its reductions accumulate
    in double (the re-trace under x64 is what makes float64 real)."""
    with jax.enable_x64(True):
        yield jnp.float64


@dataclasses.dataclass
class GuardOutcome:
    """What the ladder did: the final result, which rung produced it, and
    the per-rung status trail."""
    result: Any
    rung: str
    attempts: List[Tuple[str, str]]      # (rung name, status/verdict name)
    ok: bool                             # some rung was accepted

    @property
    def recovered(self) -> bool:
        """True when a rung past the first was needed and succeeded."""
        return self.ok and len(self.attempts) > 1


def default_accept(result: Any) -> bool:
    """A solve-like result is acceptable when it converged and no guard
    tripped (objects without those fields pass vacuously)."""
    ok = True
    conv = getattr(result, "converged", None)
    if conv is not None:
        ok = ok and bool(np.all(np.asarray(conv)))
    st = getattr(result, "status", None)
    if st is not None:
        ok = ok and worst_status(st) == STATUS_OK
    return ok


def run_with_guards(rungs: Sequence[Tuple[str, Callable[[], Any]]],
                    accept: Callable[[Any], bool] = default_accept
                    ) -> GuardOutcome:
    """Walk the recovery ladder; return the first accepted result.

    ``rungs``: ordered ``(name, thunk)`` pairs — rung 0 is the primary
    attempt.  A thunk that raises counts as a rejected rung (the ladder
    continues) unless it is the last one.  When no rung is accepted the
    last result (or exception) is returned with ``ok=False``.
    """
    attempts: List[Tuple[str, str]] = []
    last: Any = None
    last_name = ""
    last_exc: Optional[BaseException] = None
    for i, (name, thunk) in enumerate(rungs):
        count(f"guard/attempt/{name}")
        if i > 0:
            count("guard/escalations")
        try:
            result = thunk()
        except Exception as e:            # noqa: BLE001 — rung failure is data
            count(f"guard/raise/{name}")
            attempts.append((name, f"raised:{type(e).__name__}"))
            last_exc, last, last_name = e, None, name
            continue
        last, last_name, last_exc = result, name, None
        verdict = status_name(getattr(result, "status", None))
        attempts.append((name, verdict))
        if verdict != "ok":
            count(f"guard/status/{verdict}")
        if accept(result):
            count(f"guard/accept/{name}")
            return GuardOutcome(result=result, rung=name, attempts=attempts,
                                ok=True)
        count(f"guard/reject/{name}")
    count("guard/exhausted")
    if last is None and last_exc is not None:
        raise last_exc
    return GuardOutcome(result=last, rung=last_name, attempts=attempts,
                        ok=False)


def construct_h2_certified(points: np.ndarray, kernel: Callable,
                           leaf_size: int, eta: float, *,
                           cert_tol: float = 1e-2, probes: int = 8,
                           max_rounds: int = 3, min_level: int = 1,
                           dtype=jnp.float32, chunk: int = 1024,
                           sketch_opts: Optional[dict] = None):
    """Sketch-construct an H^2 operator, certify it, and escalate the
    rangefinder budget (oversampling, initial samples, rank cap doubled
    each round) until the stochastic error estimate passes ``cert_tol``.

    Returns ``(shape, data, tree, bs, cert, rounds)``; the last round's
    result is returned even when it fails certification (``cert.ok``
    tells).  Every escalation round is counted (``guard/construct/...``).
    """
    from repro.core.construction import construct_h2

    opts = dict(sketch_opts or {})
    ref = None
    cert: Optional[Certificate] = None
    out = None
    for rnd in range(max_rounds):
        out = construct_h2(points, kernel, leaf_size, cheb_p=0, eta=eta,
                           dtype=dtype, min_level=min_level,
                           method="sketch", sketch_opts=opts)
        shape, data, tree, _ = out
        if ref is None:
            ref = kernel_reference_apply(points, kernel, tree.perm, chunk)
        cert = certify_h2(shape, data, ref, probes=probes,
                          seed=int(opts.get("seed", 0)), tol=cert_tol)
        if cert.ok:
            if rnd > 0:
                count("guard/construct/recovered")
            return (*out, cert, rnd + 1)
        count("guard/construct/cert-failed")
        # double the rangefinder budget: more oversampling columns, more
        # initial samples, a higher rank cap (a starved cap can never
        # certify no matter how many probes confirm it)
        opts["oversample"] = 2 * int(opts.get("oversample", 10))
        opts["max_rank"] = 2 * int(opts.get("max_rank", 64))
        if opts.get("n_samples0"):
            opts["n_samples0"] = 2 * int(opts["n_samples0"])
    count("guard/construct/exhausted")
    return (*out, cert, max_rounds)
