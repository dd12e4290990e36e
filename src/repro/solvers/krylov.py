"""Fully-jitted Krylov solvers (DESIGN.md §7).

Every solver here is a pure traceable function built on ``lax.while_loop``
— no Python-level convergence loop, no host round-trips — so a whole solve
lowers to ONE XLA program.  The same bodies run single-device and
distributed: every reduction goes through ``_dot``/``_norm`` which take an
optional mesh ``axis``; with ``axis=None`` they are plain sums, inside
``shard_map`` they are ``psum`` reductions over the block-row axis.  The
distributed variants in ``solvers/distributed.py`` are therefore the same
algorithms, word for word, wrapped in one ``shard_map`` program.

Tolerance semantics (uniform across all solvers, and the fix for the old
``apps.fractional.pcg`` which mixed absolute and relative checks): ``tol``
is always **relative to ||b||** — convergence is ``||r|| <= tol * ||b||``,
``relres`` and every entry of ``res_history`` are ``||r|| / ||b||``.  For
``b = 0`` the exact solution ``x = 0`` is returned immediately with
``iters = 0``, ``relres = 0`` and ``converged = True``.

``res_history`` is a fixed-length ``[maxiter + 1]`` array (jit needs static
shapes): entry ``i`` is the relative residual after ``i`` iterations;
entries past the solve's end are NaN.  For ``block_cg`` the history is
``[maxiter + 1, nv]`` and a column converged at iteration ``k`` carries its
final value forward while other columns still run (rows past the LAST
column's finish are NaN; per-column counts live in ``iters``).  For GMRES
the history is per *restart* (entry ``i`` = relative true residual after
``i`` restart cycles).
"""
from __future__ import annotations

import dataclasses
import os
from typing import Callable, Optional

import jax
import jax.numpy as jnp

from repro.obs.trace import phase

# retrace counters, keyed by program name (test hook — mirrors
# core/compression.TRACE_COUNTS)
TRACE_COUNTS = {"pcg": 0, "block_cg": 0, "gmres": 0,
                "dist_pcg": 0, "dist_block_cg": 0, "dist_gmres": 0,
                "dist_fractional": 0, "pcg_segment": 0}

# ----------------------------------------------------------------------
# breakdown-guard status codes (DESIGN.md §11).  The codes ride the
# while_loop carry as one int32 (per-column [nv] for block_cg) — pure
# traced ops, zero extra host syncs — and surface in ``SolveResult.status``.
# ``repro.guard.status`` re-exports them with names; they live here so the
# solver bodies need no import from the guard package (no cycle).
# ----------------------------------------------------------------------
STATUS_OK = 0            # clean (possibly unconverged-at-maxiter) solve
STATUS_NAN = 1           # non-finite residual / <r,z> in the carry
STATUS_INDEFINITE = 2    # p^T A p <= 0: operator not SPD on this Krylov space
STATUS_STAGNATION = 3    # no residual progress over the stagnation window
STATUS_BREAKDOWN = 4     # GMRES least-squares breakdown (non-finite update)

_GUARD_ENABLED = os.environ.get("REPRO_GUARD_DISABLE", "0") != "1"


def guards_enabled() -> bool:
    return _GUARD_ENABLED


def set_guards_enabled(flag: bool) -> None:
    """Global kill-switch for the breakdown guards (mirrors
    ``obs.trace.set_enabled``): with guards disabled, subsequently *traced*
    solver programs carry no status machinery at all — the jaxpr is
    byte-identical to a per-call ``guard=False`` solve (asserted in
    tests/test_guard.py)."""
    global _GUARD_ENABLED
    _GUARD_ENABLED = bool(flag)


@jax.tree_util.register_pytree_node_class
@dataclasses.dataclass
class SolveResult:
    """Solution + convergence record of one Krylov solve.

    ``x``: the solution (same shape as ``b``); ``iters``: iterations taken
    (int32 scalar; for ``block_cg`` an ``[nv]`` vector, for ``gmres`` the
    number of restart cycles x m); ``relres``: final ``||r|| / ||b||``;
    ``converged``: ``||r|| <= tol * ||b||``; ``res_history``: see module
    docstring; ``status``: breakdown-guard code (``STATUS_OK`` etc.; int32
    scalar, per-column ``[nv]`` for ``block_cg`` — a constant
    ``STATUS_OK`` when guards are compiled out).
    """
    x: jax.Array
    iters: jax.Array
    relres: jax.Array
    converged: jax.Array
    res_history: jax.Array
    status: Optional[jax.Array] = None

    def tree_flatten(self):
        return ((self.x, self.iters, self.relres, self.converged,
                 self.res_history, self.status), None)

    @classmethod
    def tree_unflatten(cls, aux, ch):
        return cls(*ch)


def _psum(v, axis):
    return jax.lax.psum(v, axis) if axis is not None else v


def _dot(u: jax.Array, v: jax.Array, axis=None, dt=None) -> jax.Array:
    """Global <u, v> over all elements; psum over ``axis`` when sharded.

    ``dt`` (the fp64 escalation hook): accumulate the products in that
    dtype — meaningful under ``jax.enable_x64(True)``; without x64
    it canonicalizes back to f32 and is a no-op.
    """
    if dt is not None:
        u = u.astype(dt)
        v = v.astype(dt)
    return _psum(jnp.sum(u * v), axis)


def _norm(u: jax.Array, axis=None, dt=None) -> jax.Array:
    return jnp.sqrt(_dot(u, u, axis, dt))


def _cdot(u: jax.Array, v: jax.Array, axis=None, dt=None) -> jax.Array:
    """Per-column <u_j, v_j> for [n, nv] blocks -> [nv]."""
    if dt is not None:
        u = u.astype(dt)
        v = v.astype(dt)
    return _psum(jnp.sum(u * v, axis=0), axis)


def _identity(r):
    return r


@jax.tree_util.register_pytree_node_class
@dataclasses.dataclass
class PCGState:
    """Resumable PCG carry at an iteration boundary (DESIGN.md §10).

    Exactly the ``lax.while_loop`` carry of :func:`pcg` minus the residual
    history: ``k`` iterations completed (int32), the iterate ``x``, residual
    ``r``, search direction ``p``, the ``<r, z>`` scalar ``rz`` and the
    absolute residual norm ``res``.  A solve driven as
    ``pcg_init`` + repeated ``pcg_segment`` calls reproduces ``pcg``'s
    iterates bit for bit — segmentation only moves the loop-exit test to a
    periodic boundary, it does not change the recurrence — which is what
    makes the state a valid checkpoint: persist it every segment, restore
    it after a failure (possibly re-sharded onto a different mesh), and the
    solve continues as if uninterrupted.
    """
    k: jax.Array
    x: jax.Array
    r: jax.Array
    p: jax.Array
    rz: jax.Array
    res: jax.Array
    status: Optional[jax.Array] = None

    def tree_flatten(self):
        return ((self.k, self.x, self.r, self.p, self.rz, self.res,
                 self.status), None)

    @classmethod
    def tree_unflatten(cls, aux, ch):
        return cls(*ch)


def _pcg_step(apply_a, m, axis, x, r, p, rz, sdt=None):
    """One PCG iteration — the shared body of ``pcg`` and
    ``pcg_segment`` (identical op order keeps the two bitwise-equal).
    Also returns ``pap`` for the indefiniteness guard.  ``sdt``:
    scalar-accumulation dtype (fp64 escalation); scalars are cast back to
    the vector dtype before touching the iterates, so the carry dtypes of
    ``x``/``r``/``p`` never change."""
    with phase("krylov/apply-A"):
        ap = apply_a(p)
    with phase("krylov/scalars"):
        pap = _dot(p, ap, axis, sdt)
        alpha = rz / jnp.where(pap != 0, pap, 1.0)
        if sdt is not None:
            alpha = alpha.astype(x.dtype)
        x = x + alpha * p
        r = r - alpha * ap
        res = _norm(r, axis, sdt)
    with phase("krylov/precond"):
        z = m(r)
    with phase("krylov/scalars"):
        rz_new = _dot(r, z, axis, sdt)
        beta = rz_new / jnp.where(rz != 0, rz, 1.0)
        if sdt is not None:
            beta = beta.astype(x.dtype)
        p = z + beta * p
    return x, r, p, rz_new, res, pap


def pcg_init(apply_a: Callable, b: jax.Array,
             precond: Optional[Callable] = None,
             x0: Optional[jax.Array] = None, axis=None,
             guard: bool = True) -> PCGState:
    """Initial :class:`PCGState` for a segmented solve — the same prologue
    as :func:`pcg` (``x0=None`` starts from ``r = b`` without an operator
    application)."""
    g = bool(guard) and _GUARD_ENABLED
    m = precond if precond is not None else _identity
    x = jnp.zeros_like(b) if x0 is None else x0
    r = b - apply_a(x) if x0 is not None else b
    z = m(r)
    rz = _dot(r, z, axis)
    res = _norm(r, axis)
    if g:
        status = jnp.where(jnp.isfinite(res) & jnp.isfinite(rz),
                           jnp.int32(STATUS_OK), jnp.int32(STATUS_NAN))
    else:
        status = jnp.int32(STATUS_OK)
    return PCGState(k=jnp.int32(0), x=x, r=r, p=z, rz=rz, res=res,
                    status=status)


def pcg_segment(apply_a: Callable, b: jax.Array, state: PCGState,
                precond: Optional[Callable] = None, tol: float = 1e-8,
                steps: int = 10, maxiter: int = 200,
                axis=None, guard: bool = True) -> PCGState:
    """Advance a PCG solve by at most ``steps`` iterations.

    The periodic-exit restart boundary of the checkpointing scheme: the
    ``while_loop`` runs the exact :func:`pcg` recurrence but additionally
    exits after ``steps`` iterations, handing the carry back to the host
    so the driver can snapshot it, probe the TRUE residual
    ``||b - A x|| / ||b||`` against the recurrence residual (the
    silent-corruption tripwire), or re-shard it onto a new mesh.  The
    convergence test is unchanged (``res <= tol * ||b||`` ends the solve
    regardless of segment position), so total iteration counts match the
    monolithic ``pcg`` exactly.

    ``guard``: carry the breakdown-status code (NaN/Inf, indefiniteness —
    no stagnation window here: the segment carries no residual history;
    the elastic driver's recomputed-residual tripwire covers slow-drift
    cases at segment boundaries).
    """
    TRACE_COUNTS["pcg_segment"] += 1
    g = bool(guard) and _GUARD_ENABLED
    m = precond if precond is not None else _identity
    b_norm = _norm(b, axis)
    k_stop = jnp.minimum(state.k + jnp.int32(steps), jnp.int32(maxiter))

    def cond(s):
        keep = (s.k < k_stop) & (s.res > tol * b_norm)
        return keep & (s.status == STATUS_OK) if g else keep

    def body(s):
        x, r, p, rz_new, res, pap = _pcg_step(apply_a, m, axis,
                                              s.x, s.r, s.p, s.rz)
        if g:
            with phase("krylov/guard"):
                finite = jnp.isfinite(res) & jnp.isfinite(rz_new)
                new = jnp.where(~finite, jnp.int32(STATUS_NAN),
                                jnp.where(pap <= 0,
                                          jnp.int32(STATUS_INDEFINITE),
                                          jnp.int32(STATUS_OK)))
                status = jnp.where(s.status == STATUS_OK, new, s.status)
        else:
            status = s.status
        return PCGState(k=s.k + 1, x=x, r=r, p=p, rz=rz_new, res=res,
                        status=status)

    return jax.lax.while_loop(cond, body, state)


def pcg(apply_a: Callable, b: jax.Array,
        precond: Optional[Callable] = None, tol: float = 1e-8,
        maxiter: int = 200, x0: Optional[jax.Array] = None,
        axis=None, guard: bool = True, stag_window: int = 30,
        scalar_dtype=None) -> SolveResult:
    """Preconditioned conjugate gradients as one ``lax.while_loop``.

    ``apply_a``/``precond`` map arrays of ``b``'s shape to the same shape;
    ``precond`` must apply a fixed SPD ``M^{-1}``.  Inside ``shard_map``
    pass the mesh ``axis`` and per-device shards of ``b``.

    ``guard`` (DESIGN.md §11): carry a breakdown-status int32 and end the
    loop on NaN/Inf in the carry, ``p^T A p <= 0`` (indefiniteness) or no
    residual progress over ``stag_window`` iterations — all traced ops,
    zero extra host syncs.  ``guard=False`` (or the global
    ``set_guards_enabled(False)``) compiles every guard op out.
    ``scalar_dtype``: accumulate the dot-product scalars in this dtype
    (the fp64 escalation rung; vector iterates keep ``b``'s dtype).
    """
    TRACE_COUNTS["pcg"] += 1
    g = bool(guard) and _GUARD_ENABLED
    sdt = scalar_dtype
    cast = (lambda v: v.astype(b.dtype)) if sdt is not None else \
        (lambda v: v)
    m = precond if precond is not None else _identity
    b_norm = _norm(b, axis, sdt)
    bn_safe = jnp.where(b_norm > 0, b_norm, 1.0)
    x = jnp.zeros_like(b) if x0 is None else x0
    r = b - apply_a(x) if x0 is not None else b
    z = m(r)
    p = z
    rz = _dot(r, z, axis, sdt)
    res = _norm(r, axis, sdt)
    hist = jnp.full((maxiter + 1,), jnp.nan, b.dtype)
    hist = hist.at[0].set(cast(res / bn_safe))
    W = max(1, min(int(stag_window), int(maxiter)))

    def cond(state):
        if g:
            k, _, _, _, _, res_k, _, status = state
            return (k < maxiter) & (res_k > tol * b_norm) & \
                (status == STATUS_OK)
        k, _, _, _, _, res_k, _ = state
        return (k < maxiter) & (res_k > tol * b_norm)

    def body(state):
        if g:
            k, x, r, p, rz, _, hist, status = state
        else:
            k, x, r, p, rz, _, hist = state
        x, r, p, rz_new, res, pap = _pcg_step(apply_a, m, axis, x, r, p,
                                              rz, sdt)
        with phase("krylov/scalars"):
            hist = hist.at[k + 1].set(cast(res / bn_safe))
        if not g:
            return k + 1, x, r, p, rz_new, res, hist
        with phase("krylov/guard"):
            finite = jnp.isfinite(res) & jnp.isfinite(rz_new)
            stalled = (k + 1 >= W) & \
                (hist[k + 1] >= hist[jnp.maximum(k + 1 - W, 0)])
            new = jnp.where(~finite, jnp.int32(STATUS_NAN),
                            jnp.where(pap <= 0,
                                      jnp.int32(STATUS_INDEFINITE),
                                      jnp.where(stalled,
                                                jnp.int32(STATUS_STAGNATION),
                                                jnp.int32(STATUS_OK))))
            status = jnp.where(status == STATUS_OK, new, status)
        return k + 1, x, r, p, rz_new, res, hist, status

    if g:
        status0 = jnp.where(jnp.isfinite(res) & jnp.isfinite(rz),
                            jnp.int32(STATUS_OK), jnp.int32(STATUS_NAN))
        state = (jnp.int32(0), x, r, p, rz, res, hist, status0)
        k, x, r, _, _, res, hist, status = \
            jax.lax.while_loop(cond, body, state)
        conv = res <= tol * b_norm
        # a solve that stalls exactly on the tolerance boundary converged;
        # don't report the final-iteration stagnation flag
        status = jnp.where((status == STATUS_STAGNATION) & conv,
                           jnp.int32(STATUS_OK), status)
    else:
        state = (jnp.int32(0), x, r, p, rz, res, hist)
        k, x, r, _, _, res, hist = jax.lax.while_loop(cond, body, state)
        conv = res <= tol * b_norm
        status = jnp.int32(STATUS_OK)
    relres = cast(res / bn_safe)
    return SolveResult(x=x, iters=k, relres=relres, converged=conv,
                       res_history=hist, status=status)


def block_cg(apply_a: Callable, b: jax.Array,
             precond: Optional[Callable] = None, tol: float = 1e-8,
             maxiter: int = 200, x0: Optional[jax.Array] = None,
             axis=None, guard: bool = True, stag_window: int = 30,
             scalar_dtype=None) -> SolveResult:
    """Batched multi-RHS CG: ``b`` is ``[n, nv]``, ``apply_a`` maps
    ``[n, nv] -> [n, nv]`` (the H^2 matvec's native multi-vector form).

    Each column runs an independent CG recurrence (per-column alpha/beta),
    all fused into one program so the nv matvecs share every dispatch.
    Converged columns are frozen via masking; ``iters`` is per-column.

    ``x0`` warm-starts every column (zero-initialized columns behave
    exactly as before); already-converged columns take zero iterations —
    this is the restart-boundary hook the serving layer's continuous
    batching uses to let late-arriving RHS join a panel mid-flight
    (DESIGN.md §9).  ``tol`` may be a traced scalar so one jitted segment
    program serves requests at different tolerances without retracing.

    ``guard``: per-column breakdown status (``SolveResult.status`` is
    ``[nv]``); a broken column freezes (its iterate stops updating) while
    healthy columns keep running — the serving layer retires it through
    the degraded path.  ``scalar_dtype``: see :func:`pcg`.
    """
    TRACE_COUNTS["block_cg"] += 1
    g = bool(guard) and _GUARD_ENABLED
    sdt = scalar_dtype
    cast = (lambda v: v.astype(b.dtype)) if sdt is not None else \
        (lambda v: v)
    m = precond if precond is not None else _identity
    b_norm = jnp.sqrt(_cdot(b, b, axis, sdt))              # [nv]
    bn_safe = jnp.where(b_norm > 0, b_norm, 1.0)
    x = jnp.zeros_like(b) if x0 is None else x0
    r = b - apply_a(x) if x0 is not None else b
    z = m(r)
    p = z
    rz = _cdot(r, z, axis, sdt)
    res = jnp.sqrt(_cdot(r, r, axis, sdt))
    nv = b.shape[1]
    maxit = int(maxiter)
    hist = jnp.full((maxit + 1, nv), jnp.nan, b.dtype)
    hist = hist.at[0].set(cast(res / bn_safe))
    iters0 = jnp.zeros((nv,), jnp.int32)
    W = max(1, min(int(stag_window), maxit))

    def cond(state):
        if g:
            k, _, _, _, _, res_k, _, _, status = state
            return (k < maxit) & jnp.any((res_k > tol * b_norm)
                                         & (status == STATUS_OK))
        k, _, _, _, _, res_k, _, _ = state
        return (k < maxit) & jnp.any(res_k > tol * b_norm)

    def body(state):
        if g:
            k, x, r, p, rz, res, hist, iters, status = state
            active = (res > tol * b_norm) & (status == STATUS_OK)  # [nv]
        else:
            k, x, r, p, rz, res, hist, iters = state
            active = res > tol * b_norm                    # [nv]
        with phase("krylov/apply-A"):
            ap = apply_a(p)
        pap = _cdot(p, ap, axis, sdt)
        alpha = jnp.where(active,
                          cast(rz / jnp.where(pap != 0, pap, 1.0)), 0.0)
        x = x + alpha[None, :] * p
        r = jnp.where(active[None, :], r - alpha[None, :] * ap, r)
        res = jnp.sqrt(_cdot(r, r, axis, sdt))
        with phase("krylov/precond"):
            z = m(r)
        rz_new = jnp.where(active, _cdot(r, z, axis, sdt), rz)
        beta = jnp.where(active,
                         cast(rz_new / jnp.where(rz != 0, rz, 1.0)), 0.0)
        p = jnp.where(active[None, :], z + beta[None, :] * p, p)
        hist = hist.at[k + 1].set(jnp.where(active, cast(res / bn_safe),
                                            hist[k]))
        if not g:
            return (k + 1, x, r, p, rz_new, res, hist,
                    iters + active.astype(jnp.int32))
        with phase("krylov/guard"):
            finite = jnp.isfinite(res) & jnp.isfinite(rz_new)   # [nv]
            stalled = (k + 1 >= W) & \
                (hist[k + 1] >= hist[jnp.maximum(k + 1 - W, 0)])
            new = jnp.where(~finite, jnp.int32(STATUS_NAN),
                            jnp.where(pap <= 0,
                                      jnp.int32(STATUS_INDEFINITE),
                                      jnp.where(stalled,
                                                jnp.int32(STATUS_STAGNATION),
                                                jnp.int32(STATUS_OK))))
            status = jnp.where(active & (status == STATUS_OK), new,
                               status)
        return (k + 1, x, r, p, rz_new, res, hist,
                iters + active.astype(jnp.int32), status)

    if g:
        status0 = jnp.where(jnp.isfinite(res) & jnp.isfinite(rz),
                            jnp.int32(STATUS_OK), jnp.int32(STATUS_NAN))
        status0 = jnp.broadcast_to(status0, (nv,))
        state = (jnp.int32(0), x, r, p, rz, res, hist, iters0, status0)
        _, x, r, _, _, res, hist, iters, status = \
            jax.lax.while_loop(cond, body, state)
        status = jnp.where((status == STATUS_STAGNATION)
                           & (res <= tol * b_norm),
                           jnp.int32(STATUS_OK), status)
    else:
        state = (jnp.int32(0), x, r, p, rz, res, hist, iters0)
        _, x, r, _, _, res, hist, iters = \
            jax.lax.while_loop(cond, body, state)
        status = jnp.zeros((nv,), jnp.int32)
    relres = cast(res / bn_safe)
    return SolveResult(x=x, iters=iters, relres=relres,
                       converged=jnp.all(res <= tol * b_norm),
                       res_history=hist, status=status)


def _arnoldi(op: Callable, v0: jax.Array, m: int, axis=None):
    """m steps of Arnoldi with two-pass classical Gram-Schmidt.

    Returns (V [m+1, n...], H [m+1, m]).  The CGS projections are
    vectorized over the whole basis with an ``i <= j`` mask so the inner
    loop is a fixed-shape ``fori_loop`` (jit/shard_map friendly); the
    second pass restores the orthogonality one-pass CGS loses in f32.
    Happy breakdown (``h_{j+1,j} ~ 0``) zeroes the next basis vector, which
    leaves the least-squares solve of H well-posed via lstsq.
    """
    n_shape = v0.shape
    V = jnp.zeros((m + 1,) + n_shape, v0.dtype).at[0].set(v0)
    H = jnp.zeros((m + 1, m), v0.dtype)

    def vdot_all(V, w):
        # <V_i, w> for all i, psum'd when sharded: [m+1]
        d = jnp.sum(V * w[None], axis=tuple(range(1, w.ndim + 1)))
        return _psum(d, axis)

    def step(j, carry):
        V, H = carry
        with phase("krylov/apply-A"):
            w = op(V[j])
        mask = (jnp.arange(m + 1) <= j).astype(w.dtype)
        h1 = vdot_all(V, w) * mask
        w = w - jnp.tensordot(h1, V, axes=1, precision="highest")
        h2 = vdot_all(V, w) * mask                 # CGS second pass
        w = w - jnp.tensordot(h2, V, axes=1, precision="highest")
        h = h1 + h2
        hn = _norm(w, axis)
        v_next = jnp.where(hn > 0, w / jnp.where(hn > 0, hn, 1.0), 0.0)
        V = V.at[j + 1].set(v_next)
        H = H.at[:, j].set(h.at[j + 1].set(hn))
        return V, H

    return jax.lax.fori_loop(0, m, step, (V, H))


def gmres(apply_a: Callable, b: jax.Array,
          precond: Optional[Callable] = None, m: int = 30,
          tol: float = 1e-8, maxiter: int = 200,
          x0: Optional[jax.Array] = None, axis=None,
          guard: bool = True) -> SolveResult:
    """Restarted GMRES(m), left-preconditioned, as one jitted program.

    Each restart runs exactly ``m`` Arnoldi steps on ``M^{-1} A`` (a fixed
    trip count keeps the loop a static-shape ``fori_loop``), solves the
    ``(m+1) x m`` least-squares problem by ridge-regularized normal
    equations (breakdown-safe), and updates ``x``.  The outer
    ``while_loop`` restarts until the TRUE residual ``||b - A x||`` meets
    ``tol * ||b||`` or ``ceil(maxiter / m)`` cycles have run.
    ``res_history`` is per restart; ``iters = cycles * m``.

    ``guard``: surface breakdown as ``SolveResult.status`` —
    ``STATUS_BREAKDOWN`` when a restart's least-squares update turned
    non-finite, ``STATUS_NAN`` for a non-finite initial residual, and
    ``STATUS_STAGNATION`` when the accept-only-improving restart logic
    ended the solve without convergence.
    """
    TRACE_COUNTS["gmres"] += 1
    g_on = bool(guard) and _GUARD_ENABLED
    mp = precond if precond is not None else _identity
    n_restarts = max(1, -(-int(maxiter) // int(m)))
    b_norm = _norm(b, axis)
    bn_safe = jnp.where(b_norm > 0, b_norm, 1.0)
    x = jnp.zeros_like(b) if x0 is None else x0
    r = b - apply_a(x) if x0 is not None else b
    res = _norm(r, axis)
    hist = jnp.full((n_restarts + 1,), jnp.nan, b.dtype)
    hist = hist.at[0].set(res / bn_safe)

    def op(v):
        return mp(apply_a(v))

    def cond(state):
        if g_on:
            k, _, _, res_k, _, progress, _ = state
        else:
            k, _, _, res_k, _, progress = state
        # a rejected restart leaves the state bitwise unchanged — further
        # cycles would deterministically recompute the same rejected
        # correction, so stagnation ends the solve
        return (k < n_restarts) & (res_k > tol * b_norm) & progress

    def body(state):
        # the true residual of the accepted iterate rides the loop state,
        # so each restart costs m+1 operator applications, not m+2
        if g_on:
            k, x, r, res_old, hist, _, status = state
        else:
            k, x, r, res_old, hist, _ = state
        with phase("krylov/precond"):
            z = mp(r)
        beta = _norm(z, axis)
        beta_safe = jnp.where(beta > 0, beta, 1.0)
        with phase("krylov/arnoldi"):
            V, H = _arnoldi(op, z / beta_safe, m, axis)
        # min_y ||beta e1 - H y||: ridge-regularized normal equations keep
        # the solve well-posed through happy breakdown (zero H columns)
        e1 = jnp.zeros((m + 1,), b.dtype).at[0].set(beta)
        g = jnp.matmul(H.T, H, precision="highest")
        ridge = 1e-7 * (jnp.trace(g) / m + 1e-30)
        y = jnp.linalg.solve(g + ridge * jnp.eye(m, dtype=b.dtype),
                             jnp.matmul(H.T, e1, precision="highest"))
        x_new = x + jnp.tensordot(y, V[:m], axes=1, precision="highest")
        r_new = b - apply_a(x_new)
        res_new = _norm(r_new, axis)
        # accept only improving restarts: at the dtype's stagnation floor
        # the correction is pure rounding noise and must not grow ||r||
        better = res_new < res_old
        x = jnp.where(better, x_new, x)
        r = jnp.where(better, r_new, r)
        res = jnp.where(better, res_new, res_old)
        hist = hist.at[k + 1].set(res / bn_safe)
        if not g_on:
            return k + 1, x, r, res, hist, better
        with phase("krylov/guard"):
            # a non-finite LS update is a breakdown, not mere stagnation
            # (the rejected carry hides it from the residual record)
            brk = ~jnp.isfinite(res_new)
            status = jnp.where((status == STATUS_OK) & brk,
                               jnp.int32(STATUS_BREAKDOWN), status)
        return k + 1, x, r, res, hist, better, status

    if g_on:
        status0 = jnp.where(jnp.isfinite(res), jnp.int32(STATUS_OK),
                            jnp.int32(STATUS_NAN))
        state = (jnp.int32(0), x, r, res, hist, jnp.bool_(True), status0)
        k, x, _, res, hist, progress, status = \
            jax.lax.while_loop(cond, body, state)
        conv = res <= tol * b_norm
        status = jnp.where(~conv & ~progress & (status == STATUS_OK),
                           jnp.int32(STATUS_STAGNATION), status)
        status = jnp.where(conv, jnp.int32(STATUS_OK), status)
    else:
        state = (jnp.int32(0), x, r, res, hist, jnp.bool_(True))
        k, x, _, res, hist, progress = \
            jax.lax.while_loop(cond, body, state)
        conv = res <= tol * b_norm
        status = jnp.int32(STATUS_OK)
    return SolveResult(x=x, iters=k * m, relres=res / bn_safe,
                       converged=conv, res_history=hist, status=status)
