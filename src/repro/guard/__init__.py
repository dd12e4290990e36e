"""Numerical guard rails (DESIGN.md §11).

Three pillars, one subsystem:

- **Operator certification** (``validate``, ``certify``): structural
  invariant checking of an H^2 operator (index bounds, marshaled-twin
  coherence, symmetry aliasing, basis orthogonality) plus a stochastic
  a-posteriori relative-error estimate of the operator against a reference
  apply — cheap enough to run after construct / compress / update /
  repartition, strong enough to reject a silently corrupted operator
  before it serves traffic.
- **Solver breakdown guards** (``status``): the jit-compatible status
  codes carried through the Krylov while_loops
  (``repro.solvers.krylov``), re-exported here with names.
- **Escalation policies** (``escalate``): ``run_with_guards`` maps a
  failed/suspect solve onto a recovery ladder (fp64 scalar accumulation,
  fp32 halo payloads, oversampling escalation, looser tolerance), with
  every trip counted in the obs counters ``guard/...``.

Deterministic numerical-fault drills live in ``drills`` and are exercised
by the chaos harness and ``tests/test_guard.py``.
"""
from .status import (STATUS_BREAKDOWN, STATUS_INDEFINITE, STATUS_NAN,
                     STATUS_NAMES, STATUS_OK, STATUS_STAGNATION,
                     guards_enabled, set_guards_enabled, status_name,
                     worst_status)
from .validate import ValidationReport, check_orthogonal, validate_dist_h2, \
    validate_h2
from .certify import (CERT_STREAM, Certificate, certify_h2, certify_matvec,
                      kernel_reference_apply, probe_block)
from .escalate import (GuardOutcome, construct_h2_certified,
                       default_accept, fp64_scalars, reset_guard_counters,
                       run_with_guards)
from .drills import drill_corrupt_operator, drill_near_singular, \
    drill_rank_starved

__all__ = [
    "STATUS_OK", "STATUS_NAN", "STATUS_INDEFINITE", "STATUS_STAGNATION",
    "STATUS_BREAKDOWN", "STATUS_NAMES", "status_name", "worst_status",
    "guards_enabled", "set_guards_enabled",
    "ValidationReport", "validate_h2", "validate_dist_h2",
    "check_orthogonal",
    "Certificate", "certify_matvec", "certify_h2",
    "kernel_reference_apply", "probe_block", "CERT_STREAM",
    "GuardOutcome", "run_with_guards", "default_accept",
    "fp64_scalars", "construct_h2_certified", "reset_guard_counters",
    "drill_corrupt_operator", "drill_rank_starved", "drill_near_singular",
]
