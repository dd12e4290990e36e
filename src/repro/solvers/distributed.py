"""Whole-solve ``shard_map`` Krylov programs over the distributed H^2 stack.

The builders here wrap the axis-aware solver bodies of ``solvers.krylov``
around ``core.dist.dist_h2_matvec_local`` so the ENTIRE iteration — matvec
(compressed-halo exchange, ``comm="halo-plan"`` by default), dot products
(``psum``), preconditioner, convergence test — is one jitted ``shard_map``
program: zero per-iteration host round-trips, one dispatch per solve.

``make_dist_krylov`` solves ``(shift*I + A) x = b`` for the plain H^2
operator ``A`` (``shift > 0`` gives the SPD covariance-solve form
``I + A``).  The end-to-end fractional-diffusion solve, whose operator
composes the H^2 kernel with a sharded stencil and grid<->tree
transpositions, lives in ``apps.fractional`` and reuses the same solver
bodies.
"""
from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P

from repro.core.dist import (DistH2Data, DistH2Shape, dist_h2_matvec_local,
                             dist_specs, matvec_comm_bytes)
from repro.obs.trace import count

from .krylov import (PCGState, SolveResult, block_cg, gmres,
                     pcg, pcg_init, pcg_segment, _norm)


def result_specs(x_spec) -> SolveResult:
    """PartitionSpec pytree for a SolveResult: the solution is sharded like
    ``b``; every psum-reduced scalar/history is replicated."""
    return SolveResult(x=x_spec, iters=P(), relres=P(), converged=P(),
                       res_history=P(), status=P())


def pcg_state_specs(x_spec) -> PCGState:
    """PartitionSpec pytree for a PCGState: the vector carries (x, r, p)
    are sharded like ``b``; the psum-reduced scalars are replicated."""
    return PCGState(k=P(), x=x_spec, r=x_spec, p=x_spec, rz=P(), res=P(),
                    status=P())


def make_dist_krylov_segment(dshape: DistH2Shape, mesh: Mesh, axis,
                             comm: str = "halo-plan", shift: float = 0.0,
                             tol: float = 1e-8, steps: int = 10,
                             maxiter: int = 200, schedule: str = "auto",
                             backend: str = "jnp", hide_flops: int = 0):
    """Segmented (checkpointable) distributed PCG on ``(shift*I + A)``.

    Returns the three jitted ``shard_map`` programs of the elastic solve
    (DESIGN.md §10), each taking operator/vectors placed with
    ``dist_specs(dshape, axis)`` / ``P(axis)`` shardings:

      - ``init(d, b) -> PCGState``
      - ``segment(d, b, state) -> PCGState`` — at most ``steps``
        iterations, exiting early on convergence; drives the exact
        :func:`repro.solvers.krylov.pcg` recurrence, so iteration counts
        match the monolithic solve
      - ``residual(d, b, state) -> (true_relres, rec_relres)`` — the
        recomputed ``||b - (shift*I + A) x|| / ||b||`` next to the
        recurrence residual, the silent-corruption tripwire

    plus ``state_specs`` for placing a restored checkpoint.
    """
    specs = dist_specs(dshape, axis)
    bspec = P(axis)
    sspecs = pcg_state_specs(bspec)

    def apply_a(d, x):
        y = dist_h2_matvec_local(dshape, d, x[:, None], axis, comm,
                                 backend, schedule, hide_flops)[:, 0]
        return shift * x + y if shift else y

    def init_local(d, b):
        return pcg_init(lambda v: apply_a(d, v), b, axis=axis)

    def seg_local(d, b, state):
        return pcg_segment(lambda v: apply_a(d, v), b, state, tol=tol,
                           steps=steps, maxiter=maxiter, axis=axis)

    def res_local(d, b, state):
        bn = _norm(b, axis)
        bn_safe = jnp.where(bn > 0, bn, 1.0)
        true = _norm(b - apply_a(d, state.x), axis)
        return true / bn_safe, state.res / bn_safe

    return {
        "init": jax.jit(jax.shard_map(init_local, mesh=mesh,
                                      in_specs=(specs, bspec),
                                      out_specs=sspecs, check_vma=False)),
        "segment": jax.jit(jax.shard_map(seg_local, mesh=mesh,
                                         in_specs=(specs, bspec, sspecs),
                                         out_specs=sspecs, check_vma=False)),
        "residual": jax.jit(jax.shard_map(res_local, mesh=mesh,
                                          in_specs=(specs, bspec, sspecs),
                                          out_specs=(P(), P()),
                                          check_vma=False)),
        "state_specs": sspecs,
    }


def make_dist_krylov(dshape: DistH2Shape, mesh: Mesh, axis,
                     method: str = "pcg", comm: str = "halo-plan",
                     shift: float = 0.0, tol: float = 1e-8,
                     maxiter: int = 200, restart: int = 30,
                     schedule: str = "auto", backend: str = "jnp",
                     hide_flops: int = 0):
    """Jitted ``(d, b) -> SolveResult`` solving ``(shift*I + A) x = b``.

    ``method``: ``"pcg"`` | ``"gmres"`` (b: [n]) or ``"block_cg"``
    (b: [n, nv], every RHS in one program).  ``d`` and ``b`` must be placed
    with ``dist_specs(dshape, axis)`` / ``P(axis)`` shardings.
    ``hide_flops`` requests the solver-embedded matvec lowering (merged
    single-round exchange, hide-aware auto schedule — ``core.dist``).
    """
    if method not in ("pcg", "gmres", "block_cg"):
        raise ValueError(f"unknown method {method!r}")
    specs = dist_specs(dshape, axis)
    multi = method == "block_cg"
    bspec = P(axis, None) if multi else P(axis)

    def local(d: DistH2Data, b: jax.Array) -> SolveResult:
        count(f"retrace/dist_{method}")

        def apply_a(x):
            xm = x if multi else x[:, None]
            y = dist_h2_matvec_local(dshape, d, xm, axis, comm, backend,
                                     schedule, hide_flops)
            y = y if multi else y[:, 0]
            return shift * x + y if shift else y

        if method == "pcg":
            return pcg(apply_a, b, tol=tol, maxiter=maxiter, axis=axis)
        if method == "block_cg":
            return block_cg(apply_a, b, tol=tol, maxiter=maxiter, axis=axis)
        return gmres(apply_a, b, m=restart, tol=tol, maxiter=maxiter,
                     axis=axis)

    shmapped = jax.shard_map(local, mesh=mesh, in_specs=(specs, bspec),
                             out_specs=result_specs(bspec), check_vma=False)
    return jax.jit(shmapped)


def krylov_comm_bytes(dshape: DistH2Shape, nv: int = 1,
                      comm: str = "halo-plan",
                      bytes_per_el: int = 4) -> int:
    """Per-device collective bytes of ONE Krylov iteration on the plain H^2
    operator: the matvec exchange plus the psum'd scalar reductions (CG:
    three scalars per iteration, each an all-reduce)."""
    psums = 3 * nv * bytes_per_el * max(dshape.p - 1, 0)
    return matvec_comm_bytes(dshape, nv, comm, bytes_per_el) + psums
