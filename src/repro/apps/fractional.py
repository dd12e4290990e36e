"""2D variable-diffusivity integral fractional diffusion solver (paper §6.4).

    L[u](x) = -2 int_{Omega u Omega_0} (u(y)-u(x)) a(x,y) / |y-x|^(2+2b) dy

discretized on a regular grid (paper Eq. 9):  h^2 (D + K + C) u = b, with
  K  — the dense kernel matrix (zero diagonal), compressed as an H^2 matrix
       built by Chebyshev interpolation + algebraic recompression;
  D  — diagonal, D_ii = (Khat @ 1)_i where Khat is the same (positive) kernel
       on the extended grid Omega u Omega_0 (paper Eq. 10) — assembled with a
       second H^2 operator and one distributed matvec, then discarded;
  C  — the sparse regularization term; per the paper it has the footprint of
       a kappa-weighted 5-point Laplacian.  Deviation (DESIGN.md): we use the
       leading-order term gamma * (-div kappa grad)_h with gamma = h^(-2*beta)
       instead of the full locally-corrected quadrature constants of [8].

Solver: the Krylov subsystem (``repro.solvers``, DESIGN.md §7) — a fully
jitted ``lax.while_loop`` PCG (or GMRES) preconditioned by geometric-
multigrid V-cycles on ``gamma*C + diag(D)`` (weighted-Jacobi smoothing,
full-weighting restriction, bilinear prolongation) — the GMG stand-in for
the paper's AMG.  ``make_dist_solve``/``solve_distributed`` run the WHOLE
iteration (halo-plan H^2 matvec, sharded stencil V-cycle, psum dot
products) inside one ``shard_map`` program over the block-row mesh — the
paper's §6.4 end-to-end workload with zero per-iteration host sync.
"""
from __future__ import annotations

import dataclasses
import time
import warnings
from typing import Callable, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.core.clustering import build_cluster_tree
from repro.core.construction import construct_h2
from repro.core.compression import compress
from repro.core.dist import (DistH2Data, DistH2Shape, dist_h2_matvec_local,
                             dist_specs, matvec_comm_bytes,
                             merged_exchange_bytes, partition_h2)
from repro.core.halo import build_transpose_plan, transpose_a2a
from repro.core.kernels_fn import (diffusivity_2d, fractional_kernel_2d,
                                   fractional_kernel_2d_positive)
from repro.core.matvec import h2_matvec
from repro.core.repartition import repartition_h2
from repro.core.structure import H2Data, H2Shape
from repro.checkpoint.manager import CheckpointManager
from repro.guard.escalate import fp64_scalars, run_with_guards
from repro.guard.status import worst_status
from repro.obs.trace import count, phase, span
from repro.runtime.chaos import ChaosPlan, ChaosReport, FaultEvent
from repro.runtime.fault import (StepFailure, StragglerMonitor,
                                 run_with_restarts)
from repro.solvers import (build_grid_mg, mg_halo_bytes,
                           mg_precond_local, mg_specs, pcg_init, pcg_segment,
                           pcg_state_specs, result_specs, solver_hide_flops)
from repro.solvers import gmres as _gmres
from repro.solvers import pcg as _pcg
from repro.solvers.krylov import _norm as _vec_norm
from repro.solvers.mg import _apply_op as _mg_apply_op


def interior_grid(n: int) -> np.ndarray:
    """n x n cell-centered grid on Omega = [-1, 1]^2."""
    h = 2.0 / n
    ax = -1.0 + h * (np.arange(n) + 0.5)
    xx, yy = np.meshgrid(ax, ax, indexing="ij")
    return np.stack([xx.ravel(), yy.ravel()], -1)


def extended_grid(n: int) -> Tuple[np.ndarray, np.ndarray]:
    """3n x 3n grid on [-3, 3]^2 (same h); returns (points, interior mask)."""
    h = 2.0 / n
    ax = -3.0 + h * (np.arange(3 * n) + 0.5)
    xx, yy = np.meshgrid(ax, ax, indexing="ij")
    pts = np.stack([xx.ravel(), yy.ravel()], -1)
    inside = (np.abs(pts[:, 0]) < 1.0) & (np.abs(pts[:, 1]) < 1.0)
    return pts, inside


@dataclasses.dataclass
class FractionalProblem:
    n: int                       # grid side (interior)
    beta: float = 0.75
    h2_tol: float = 1e-6         # compression tolerance for K
    cheb_p: int = 6
    eta: float = 0.9
    construction: str = "cheb"   # "cheb" (host) | "sketch" (device fast path)

    def _construct(self, pts, kern_np, kern_jnp, m):
        """One kernel-matrix construction, host-Chebyshev or device-sketch.

        The sketch path is already rank-adaptive (its rangefinder truncates
        to tolerance), so it needs no separate recompression pass; f32
        sketching floors the tolerance at 1e-4 (DESIGN.md §5).
        """
        if self.construction == "sketch":
            tol = max(self.h2_tol, 1e-4)
            return construct_h2(
                pts, kern_jnp, leaf_size=m, cheb_p=self.cheb_p, eta=self.eta,
                method="sketch", sketch_opts={"tol": tol}), False
        if self.construction != "cheb":
            raise ValueError(f"unknown construction {self.construction!r}")
        return construct_h2(
            pts, kern_np, leaf_size=m, cheb_p=self.cheb_p,
            eta=self.eta), True

    def build(self, compress_k: bool = True) -> Dict:
        """K, D, kappa and the grid<->tree maps.  Host spans:
        ``build/compress-k`` (K's recompression) and ``build/d-assembly``
        (everything that exists only to get D, K-hat's ``construct/*``
        spans inside it)."""
        n = self.n
        h = 2.0 / n
        pts = interior_grid(n)
        m = 16 if n <= 32 else 64
        (shape, data, tree, bs), needs_compress = self._construct(
            pts, fractional_kernel_2d(self.beta),
            fractional_kernel_2d(self.beta, xp=jnp), m)
        if compress_k and needs_compress:
            with span("build/compress-k"):
                shape, data = compress(shape, data, tol=self.h2_tol)
                jax.block_until_ready(data)     # K's device work in here

        # --- D via Khat @ 1 on the extended grid (Eq. 10) ---
        with span("build/d-assembly"):
            d_diag = self._assemble_d()

        # --- C: kappa-weighted 5-point Laplacian, gamma = h^(-2 beta) ---
        kappa = diffusivity_2d(pts).reshape(n, n)
        gamma = h ** (-2.0 * self.beta)

        # tree-order <-> grid-order maps for K
        perm = tree.perm
        unperm_k = np.empty(shape.n, np.int64)
        unperm_k[perm] = np.arange(shape.n)

        return {
            "shape": shape, "data": data, "perm": perm,
            "unperm": unperm_k, "d_diag": jnp.asarray(d_diag, jnp.float32),
            "kappa": jnp.asarray(kappa, jnp.float32),
            "gamma": gamma, "h": h, "n": n,
        }

    def _assemble_d(self) -> np.ndarray:
        """D_ii = (Khat @ 1)_i restricted to Omega (Eq. 10), grid order."""
        n = self.n
        pts_ext, inside = extended_grid(n)
        m_ext = 36 if (9 * n * n) % 36 == 0 else 16
        n_ext = pts_ext.shape[0]
        while n_ext % m_ext or ((n_ext // m_ext) & (n_ext // m_ext - 1)):
            m_ext *= 2
            if m_ext > n_ext:
                m_ext = n_ext
                break
        (eshape, edata, etree, _), _ = self._construct(
            pts_ext, fractional_kernel_2d_positive(self.beta),
            fractional_kernel_2d_positive(self.beta, xp=jnp), m_ext)
        ones = jnp.ones((eshape.n, 1), jnp.float32)
        row_sums = np.asarray(h2_matvec(eshape, edata, ones))[:, 0]
        # undo the tree permutation, restrict to Omega
        unperm = np.empty(eshape.n, np.int64)
        unperm[etree.perm] = np.arange(eshape.n)
        return row_sums[unperm][inside]


def apply_c(u: jax.Array, kappa: jax.Array, h: float) -> jax.Array:
    """(-div kappa grad)_h u with zero Dirichlet (volume constraint) halo.
    u: [n, n]."""
    n = u.shape[0]
    up = jnp.pad(u, 1)                     # u = 0 outside Omega
    kp = jnp.pad(kappa, 1, mode="edge")
    ke = 0.5 * (kp[1:-1, 1:-1] + kp[2:, 1:-1])      # south face
    kw = 0.5 * (kp[1:-1, 1:-1] + kp[:-2, 1:-1])
    kn = 0.5 * (kp[1:-1, 1:-1] + kp[1:-1, 2:])
    ks = 0.5 * (kp[1:-1, 1:-1] + kp[1:-1, :-2])
    lap = (ke * (up[2:, 1:-1] - up[1:-1, 1:-1]) +
           kw * (up[:-2, 1:-1] - up[1:-1, 1:-1]) +
           kn * (up[1:-1, 2:] - up[1:-1, 1:-1]) +
           ks * (up[1:-1, :-2] - up[1:-1, 1:-1]))
    return -lap / (h * h)


def make_operator(prob: Dict) -> Callable[[jax.Array], jax.Array]:
    """A u = h^2 (D + K + C) u; u in grid order [N]."""
    shape, data = prob["shape"], prob["data"]
    perm, unperm = prob["perm"], prob["unperm"]
    d_diag, kappa = prob["d_diag"], prob["kappa"]
    gamma, h, n = prob["gamma"], prob["h"], prob["n"]
    perm_j = jnp.asarray(perm)
    unperm_j = jnp.asarray(unperm)

    def apply_a(u: jax.Array) -> jax.Array:
        # the phases _dist_apply_a gives the same work
        with phase("solve/transpose-in"):
            ut = u[perm_j][:, None]
        kut = h2_matvec(shape, data, ut)
        with phase("solve/transpose-out"):
            ku = kut[:, 0][unperm_j]
        with phase("solve/stencil"):
            cu = apply_c(u.reshape(n, n), kappa, h).ravel()
            return (h * h) * (d_diag * u + ku + gamma * cu)

    return apply_a


# ----------------------------------------------------------------------
# geometric multigrid V-cycle on C (the preconditioner) — built on the
# solver subsystem's sharded stencil V-cycle (solvers/mg.py) at p=1
# ----------------------------------------------------------------------

def make_preconditioner(prob: Dict, n_cycles: int = 2, nu: int = 3,
                        omega: float = 0.7):
    """V-cycles on gamma*C + diag(D) (the local part of the operator)."""
    n = prob["n"]
    mg, arrs = build_grid_mg(prob["kappa"], prob["d_diag"].reshape(n, n),
                             prob["gamma"], prob["h"], n, p=1,
                             nu=nu, omega=omega, n_cycles=n_cycles)

    def precond(r: jax.Array) -> jax.Array:
        return mg_precond_local(mg, arrs, r)

    return precond


def pcg(apply_a, b, precond=None, tol=1e-8, maxiter=200):
    """Deprecated shim over ``repro.solvers.pcg`` — returns the legacy
    ``(x, iters, relres)`` tuple.  ``tol`` is relative to ``||b||`` (the
    historical implementation already converged on the relative residual
    but host-looped every iteration)."""
    warnings.warn("apps.fractional.pcg is deprecated; use repro.solvers.pcg",
                  DeprecationWarning, stacklevel=2)
    res = jax.jit(lambda rhs: _pcg(apply_a, rhs, precond, tol=tol,
                                   maxiter=maxiter))(b)
    return res.x, int(res.iters), float(res.relres)


def solve(n: int, beta: float = 0.75, tol: float = 1e-8,
          h2_tol: float = 1e-6, use_precond: bool = True,
          construction: str = "cheb", method: str = "pcg",
          maxiter: int = 200) -> Dict:
    prob = FractionalProblem(n, beta=beta, h2_tol=h2_tol,
                             construction=construction).build()
    apply_a = make_operator(prob)
    b = jnp.ones((n * n,), jnp.float32) * (2.0 / n) ** 2   # h^2 * 1
    pre = make_preconditioner(prob) if use_precond else None
    if method == "pcg":
        solver = lambda rhs: _pcg(apply_a, rhs, pre, tol=tol,        # noqa: E731
                                  maxiter=maxiter)
    elif method == "gmres":
        solver = lambda rhs: _gmres(apply_a, rhs, pre, m=30, tol=tol,  # noqa: E731
                                    maxiter=maxiter)
    else:
        raise ValueError(f"unknown method {method!r}")
    res = jax.jit(solver)(b)
    return {"u": np.asarray(res.x).reshape(n, n), "iters": int(res.iters),
            "relres": float(res.relres), "converged": bool(res.converged),
            "status": worst_status(res.status),
            "history": np.asarray(res.res_history), "prob": prob}


def solve_with_guards(n: int, beta: float = 0.75, tol: float = 1e-8,
                      h2_tol: float = 1e-6, use_precond: bool = True,
                      construction: str = "cheb", maxiter: int = 200,
                      loose_tol: Optional[float] = None) -> Dict:
    """``solve`` through the guard escalation ladder (DESIGN.md §11).

    Rungs: (1) the primary jitted PCG; (2) the same solve re-traced with
    fp64 scalar accumulation (recovers dot-product-rounding stagnation);
    (3) looser-tolerance GMRES as the last resort (handles indefinite
    drift the CG recurrence cannot).  The returned dict matches ``solve``
    plus the ladder outcome (``rung``, ``attempts``, ``recovered``,
    ``guard_ok``).
    """
    prob = FractionalProblem(n, beta=beta, h2_tol=h2_tol,
                             construction=construction).build()
    apply_a = make_operator(prob)
    b = jnp.ones((n * n,), jnp.float32) * (2.0 / n) ** 2
    pre = make_preconditioner(prob) if use_precond else None

    def primary():
        return jax.jit(lambda rhs: _pcg(apply_a, rhs, pre, tol=tol,
                                        maxiter=maxiter))(b)

    def fp64_rung():
        with fp64_scalars() as sdt:
            return jax.jit(lambda rhs: _pcg(apply_a, rhs, pre, tol=tol,
                                            maxiter=maxiter,
                                            scalar_dtype=sdt))(b)

    def loose_rung():
        lt = loose_tol if loose_tol is not None else 100.0 * tol
        return jax.jit(lambda rhs: _gmres(apply_a, rhs, pre, m=30, tol=lt,
                                          maxiter=maxiter))(b)

    out = run_with_guards([("primary", primary),
                           ("fp64-scalars", fp64_rung),
                           ("gmres-loose", loose_rung)])
    res = out.result
    return {"u": np.asarray(res.x).reshape(n, n), "iters": int(res.iters),
            "relres": float(res.relres), "converged": bool(res.converged),
            "status": worst_status(res.status),
            "history": np.asarray(res.res_history), "prob": prob,
            "rung": out.rung, "attempts": out.attempts,
            "recovered": out.recovered, "guard_ok": out.ok}


# ----------------------------------------------------------------------
# distributed end-to-end solve (paper §6.4): one shard_map program per
# solve — halo-plan H^2 matvec + sharded stencil + sharded V-cycle
# ----------------------------------------------------------------------

def build_dist_problem(prob: Dict, p: int, n_cycles: int = 2, nu: int = 3,
                       omega: float = 0.7, dist_source=None):
    """Partition the fractional operator for ``p`` block rows.

    Returns ``(dshape, mg, args, specs)`` where ``args = (ddata, aux,
    mg_arrays)`` and ``specs`` the matching PartitionSpec pytree (pass
    axis to ``spec_tree(axis)``).  ``aux`` carries the grid<->tree
    transposition maps (sharded in row strips like the solver state); the
    operator's local part ``D + gamma*C`` reuses the V-cycle's level-0
    stencil arrays (``mg._apply_op``) instead of shipping a second copy.

    ``dist_source``: optional ``(dshape_old, ddata_old)`` of an existing
    partition — the elastic remesh path re-shards it via
    ``core.repartition.repartition_h2`` instead of partitioning the
    single-device operator afresh (DESIGN.md §10).
    """
    n = prob["n"]
    if dist_source is not None:
        dshape, ddata = repartition_h2(dist_source[0], dist_source[1], p)
    else:
        dshape, ddata = partition_h2(prob["shape"], prob["data"], p)
    mg, mga = build_grid_mg(prob["kappa"], prob["d_diag"].reshape(n, n),
                            prob["gamma"], prob["h"], n, p=p,
                            nu=nu, omega=omega, n_cycles=n_cycles)
    if p > 1 and not mg.sharded(0):
        # power-of-two N = leaf*2^depth and p | n already imply
        # n % 2p == 0 for every partitionable configuration
        raise ValueError(f"grid side {n} too small to strip-shard over "
                         f"p={p} devices (n % 2p != 0)")
    aux = {
        "perm": jnp.asarray(prob["perm"], jnp.int32),
        "unperm": jnp.asarray(prob["unperm"], jnp.int32),
    }
    if p > 1:
        # all_to_all transposition plans for the fused iteration: each
        # device ships only the rows its peers actually need (vs the
        # (p-1)*nloc rows of the all_gather two-step path), and the
        # C-stencil row halo rides the same round as extra lanes
        _, tin_send, tin_take = build_transpose_plan(prob["perm"], p)
        _, tout_send, tout_take = build_transpose_plan(prob["unperm"], p)
        aux.update(tin_send=jnp.asarray(tin_send),
                   tin_take=jnp.asarray(tin_take),
                   tout_send=jnp.asarray(tout_send),
                   tout_take=jnp.asarray(tout_take))

    def spec_tree(axis):
        return (dist_specs(dshape, axis),
                {k: P(axis) for k in aux},
                mg_specs(mg, axis))

    return dshape, mg, (ddata, aux, mga), spec_tree


def _dist_apply_a(dshape: DistH2Shape, d: DistH2Data, aux: Dict, mg,
                  mga, x: jax.Array, axis, comm: str, n: int, h: float,
                  schedule: str = "auto", backend: str = "jnp",
                  fused: bool = False, hide: int = 0) -> jax.Array:
    """Per-device A u = h^2 (D + K + C) u; ``x``: grid-order row strip.

    The H^2 kernel works in tree order — the grid<->tree transpositions
    are device-boundary-crossing permutations.  Two-step (``fused=False``):
    one tiled ``all_gather`` + local take each way (the top-tree
    replication deviation already ships comparable volume; DESIGN.md §7).
    Fused (DESIGN.md §12): each transposition is ONE ``all_to_all`` on
    its precomputed plan (``core.halo.build_transpose_plan``) shipping
    only the rows peers actually reference, and the C-stencil's row halo
    rides the inbound round as extra lanes — the local term then needs NO
    collective of its own.  ``hide > 0`` additionally lowers the H^2
    exchange to its merged single-round form (``core.dist``).
    """
    p = dshape.p
    if fused and p > 1:
        rows = n // p
        x2d = x.reshape(rows, n)
        me = jax.lax.axis_index(axis)
        with phase("solve/transpose-in"):
            # dump-row trick: sender lane r = what lands at receiver r;
            # our LAST row feeds receiver me+1's top halo, our FIRST row
            # receiver me-1's bottom halo; edge devices dump to row p
            dump = jnp.zeros((p + 1, n), x.dtype)
            dump = jax.lax.dynamic_update_slice(dump, x2d[-1:],
                                                (me + 1, 0))
            dump = jax.lax.dynamic_update_slice(
                dump, x2d[:1], (jnp.where(me >= 1, me - 1, p), 0))
            xt, ex = transpose_a2a(x, aux["tin_send"], aux["tin_take"],
                                   axis, extra=dump[:p])
        ku_t = dist_h2_matvec_local(dshape, d, xt[:, None], axis, comm,
                                    backend, schedule, hide)[:, 0]
        with phase("solve/transpose-out"):
            ku, _ = transpose_a2a(ku_t, aux["tout_send"],
                                  aux["tout_take"], axis)
        with phase("solve/stencil"):
            top = jax.lax.dynamic_slice(ex, (jnp.maximum(me - 1, 0), 0),
                                        (1, n))
            top = jnp.where(me >= 1, top, 0.0)
            bot = jax.lax.dynamic_slice(ex, (jnp.minimum(me + 1, p - 1), 0),
                                        (1, n))
            bot = jnp.where(me <= p - 2, bot, 0.0)
            local = _mg_apply_op(mg, mga, 0, x2d, axis,
                                 halo=(top, bot)).reshape(x.shape)
            return (h * h) * (ku + local)
    with phase("solve/transpose-in"):
        xf = jax.lax.all_gather(x, axis, axis=0, tiled=True) if p > 1 \
            else x
        xt = jnp.take(xf, aux["perm"], axis=0)[:, None]
    ku_t = dist_h2_matvec_local(dshape, d, xt, axis, comm, backend,
                                schedule)[:, 0]
    with phase("solve/transpose-out"):
        kf = jax.lax.all_gather(ku_t, axis, axis=0, tiled=True) if p > 1 \
            else ku_t
        ku = jnp.take(kf, aux["unperm"], axis=0)
    with phase("solve/stencil"):
        u = x.reshape(n // p if p > 1 else n, n)
        local = _mg_apply_op(mg, mga, 0, u, axis).reshape(x.shape)
        return (h * h) * (ku + local)


def _fused_default(fused: Optional[bool], comm: str) -> bool:
    """Fused iteration default: on for the halo-plan comm modes (whose
    merged lowering it completes), off for the allgather/ppermute
    baselines — forceable either way."""
    return comm.startswith("halo-plan") if fused is None else bool(fused)


def make_dist_solve(prob: Dict, mesh: Mesh, axis="blk",
                    method: str = "pcg", comm: str = "halo-plan",
                    tol: float = 1e-8, maxiter: int = 200,
                    use_precond: bool = True, restart: int = 30,
                    n_cycles: int = 2, nu: int = 3, omega: float = 0.7,
                    schedule: str = "auto", backend: str = "jnp",
                    fused: Optional[bool] = None) -> Dict:
    """One jitted shard_map program running the whole fractional solve.

    Returns ``{"fn", "args", "specs", "dshape", "mg", "place"}``:
    ``fn(ddata, aux, mg_arrays, b) -> SolveResult`` with every input
    placed by ``place(args)`` / ``b`` sharded ``P(axis)`` in grid order.

    ``fused`` selects the DESIGN.md §12 iteration schedule (all_to_all
    transpositions carrying the stencil halo, merged single-round H^2
    exchange, deep-halo V-cycle smoothing); default: on for halo-plan
    comm modes.  ``schedule``/``backend`` thread through to the H^2
    matvec (``core.dist``).
    """
    p = mesh.shape[axis]
    n, h = prob["n"], prob["h"]
    dshape, mg, args, spec_tree = build_dist_problem(
        prob, p, n_cycles=n_cycles, nu=nu, omega=omega)
    specs = spec_tree(axis)
    fused = _fused_default(fused, comm)
    hide = solver_hide_flops(mg) if fused else 0
    bf16 = comm.endswith("-bf16")

    def local(d, aux, mga, b):
        count("retrace/dist_fractional")

        def apply_a(x):
            return _dist_apply_a(dshape, d, aux, mg, mga, x, axis, comm,
                                 n, h, schedule, backend, fused, hide)

        pre = (lambda r: mg_precond_local(mg, mga, r, axis, fused=fused,
                                          bf16=bf16)) \
            if use_precond else None
        if method == "pcg":
            return _pcg(apply_a, b, pre, tol=tol, maxiter=maxiter,
                        axis=axis)
        if method == "gmres":
            return _gmres(apply_a, b, pre, m=restart, tol=tol,
                          maxiter=maxiter, axis=axis)
        raise ValueError(f"unknown method {method!r}")

    fn = jax.jit(jax.shard_map(local, mesh=mesh,
                               in_specs=(*specs, P(axis)),
                               out_specs=result_specs(P(axis)),
                               check_vma=False))

    def place(tree, tree_specs=specs):
        return jax.tree.map(
            lambda a, s: jax.device_put(a, NamedSharding(mesh, s)),
            tree, tree_specs)

    tcaps = (args[1]["tin_send"].shape[1], args[1]["tout_send"].shape[1]) \
        if p > 1 else (0, 0)
    return {"fn": fn, "args": args, "specs": specs, "dshape": dshape,
            "mg": mg, "place": place, "axis": axis, "fused": fused,
            "tcaps": tcaps, "schedule": schedule}


def solve_distributed(n: int, mesh: Mesh, axis="blk", beta: float = 0.75,
                      tol: float = 1e-8, h2_tol: float = 1e-6,
                      maxiter: int = 200, comm: str = "halo-plan",
                      method: str = "pcg", use_precond: bool = True,
                      construction: str = "cheb", schedule: str = "auto",
                      fused: Optional[bool] = None) -> Dict:
    """End-to-end distributed fractional-diffusion solve on a mesh."""
    prob = FractionalProblem(n, beta=beta, h2_tol=h2_tol,
                             construction=construction).build()
    parts = make_dist_solve(prob, mesh, axis, method=method, comm=comm,
                            tol=tol, maxiter=maxiter,
                            use_precond=use_precond, schedule=schedule,
                            fused=fused)
    b = jnp.ones((n * n,), jnp.float32) * prob["h"] ** 2
    args = parts["place"](parts["args"])
    b_dev = jax.device_put(b, NamedSharding(mesh, P(axis)))
    res = parts["fn"](*args, b_dev)
    return {"u": np.asarray(res.x).reshape(n, n), "iters": int(res.iters),
            "relres": float(res.relres), "converged": bool(res.converged),
            "status": worst_status(res.status),
            "history": np.asarray(res.res_history), "prob": prob,
            "parts": parts, "placed_args": args, "b": b_dev}


# ----------------------------------------------------------------------
# elastic fault-tolerant solve (DESIGN.md §10): segmented PCG with
# checkpointed state, shrink-remesh recovery, and a residual tripwire
# ----------------------------------------------------------------------

def make_dist_solve_segment(prob: Dict, mesh: Mesh, axis="blk",
                            comm: str = "halo-plan", tol: float = 1e-8,
                            steps: int = 10, maxiter: int = 200,
                            use_precond: bool = True, n_cycles: int = 2,
                            nu: int = 3, omega: float = 0.7,
                            dist_source=None, schedule: str = "auto",
                            backend: str = "jnp",
                            fused: Optional[bool] = None) -> Dict:
    """Segmented (checkpointable) variant of ``make_dist_solve``.

    Instead of one monolithic solve program this returns the three jitted
    ``shard_map`` programs of the elastic solve — ``init(args, b) ->
    PCGState``, ``segment(args, b, state) -> PCGState`` (at most ``steps``
    iterations, the periodic-exit checkpoint boundary) and
    ``residual(args, b, state) -> (true_relres, rec_relres)`` (the
    recomputed ``||b - A x|| / ||b||`` silent-corruption tripwire) — all
    driving the exact ``solvers.pcg`` recurrence, so total iteration
    counts match the monolithic solve.  ``dist_source`` re-shards an
    existing partition via ``repartition_h2`` (the post-device-loss
    path).
    """
    p = mesh.shape[axis]
    n, h = prob["n"], prob["h"]
    dshape, mg, args, spec_tree = build_dist_problem(
        prob, p, n_cycles=n_cycles, nu=nu, omega=omega,
        dist_source=dist_source)
    specs = spec_tree(axis)
    sspecs = pcg_state_specs(P(axis))
    fused = _fused_default(fused, comm)
    hide = solver_hide_flops(mg) if fused else 0
    bf16 = comm.endswith("-bf16")

    def _ops(d, aux, mga):
        def apply_a(x):
            return _dist_apply_a(dshape, d, aux, mg, mga, x, axis, comm,
                                 n, h, schedule, backend, fused, hide)

        pre = (lambda r: mg_precond_local(mg, mga, r, axis, fused=fused,
                                          bf16=bf16)) \
            if use_precond else None
        return apply_a, pre

    def init_local(d, aux, mga, b):
        apply_a, pre = _ops(d, aux, mga)
        return pcg_init(apply_a, b, pre, axis=axis)

    def seg_local(d, aux, mga, b, state):
        apply_a, pre = _ops(d, aux, mga)
        return pcg_segment(apply_a, b, state, pre, tol=tol, steps=steps,
                           maxiter=maxiter, axis=axis)

    def res_local(d, aux, mga, b, state):
        apply_a, _ = _ops(d, aux, mga)
        bn = _vec_norm(b, axis)
        bn_safe = jnp.where(bn > 0, bn, 1.0)
        true = _vec_norm(b - apply_a(state.x), axis)
        return true / bn_safe, state.res / bn_safe

    def rebase_local(d, aux, mga, b, state):
        # re-anchor the recurrence on the (possibly rebuilt) operator:
        # fresh r = b - A x from the checkpointed iterate, keeping the
        # iteration count.  Needed after a precision escalation — the
        # carried r/p/rz of a bf16-payload segment are inconsistent with
        # the fp32 rebuild at the old payload's accuracy level, which
        # would re-fire the corruption tripwire forever.
        apply_a, pre = _ops(d, aux, mga)
        st = pcg_init(apply_a, b, pre, x0=state.x, axis=axis)
        return dataclasses.replace(st, k=state.k)

    init = jax.jit(jax.shard_map(init_local, mesh=mesh,
                                 in_specs=(*specs, P(axis)),
                                 out_specs=sspecs, check_vma=False))
    segment = jax.jit(jax.shard_map(seg_local, mesh=mesh,
                                    in_specs=(*specs, P(axis), sspecs),
                                    out_specs=sspecs, check_vma=False))
    residual = jax.jit(jax.shard_map(res_local, mesh=mesh,
                                     in_specs=(*specs, P(axis), sspecs),
                                     out_specs=(P(), P()), check_vma=False))
    rebaseline = jax.jit(jax.shard_map(rebase_local, mesh=mesh,
                                       in_specs=(*specs, P(axis), sspecs),
                                       out_specs=sspecs, check_vma=False))

    def place(tree, tree_specs=specs):
        return jax.tree.map(
            lambda a, s: jax.device_put(a, NamedSharding(mesh, s)),
            tree, tree_specs)

    def place_state(state):
        return jax.tree.map(
            lambda a, s: jax.device_put(a, NamedSharding(mesh, s)),
            state, sspecs)

    return {"init": init, "segment": segment, "residual": residual,
            "rebaseline": rebaseline,
            "args": args, "specs": specs, "state_specs": sspecs,
            "dshape": dshape, "mg": mg, "place": place,
            "place_state": place_state, "axis": axis, "fused": fused}


def solve_distributed_elastic(n: int, mesh: Mesh, axis="blk",
                              beta: float = 0.75, tol: float = 1e-8,
                              h2_tol: float = 1e-6, maxiter: int = 200,
                              comm: str = "halo-plan",
                              use_precond: bool = True,
                              construction: str = "cheb",
                              ckpt_dir: Optional[str] = None,
                              ckpt_every: int = 10, max_restarts: int = 5,
                              chaos: Optional[ChaosPlan] = None,
                              monitor: Optional[StragglerMonitor] = None,
                              ckpt_block: bool = True) -> Dict:
    """Fault-tolerant distributed fractional solve (DESIGN.md §10).

    The solve runs as segments of ``ckpt_every`` PCG iterations.  After
    each segment the host snapshots the :class:`PCGState` through
    ``CheckpointManager`` (when ``ckpt_dir`` is given) and probes the
    recomputed true residual against the recurrence residual — a
    divergence or non-finite value means silent state corruption, raised
    as ``StepFailure`` *without* committing the poisoned state.  Recovery
    is orchestrated by ``runtime.fault.run_with_restarts``: on a device
    loss the operator is re-sharded onto the scheduled surviving mesh via
    ``repartition_h2`` (fresh ``HaloPlan``s from ``partition_h2``'s own
    plan construction), the latest *valid* checkpoint is restored and
    re-placed under the new sharding, and the solve resumes from that
    segment; corrupted state rolls back the same way on the unchanged
    mesh.  Stragglers (injected via ``chaos`` or real) are flagged by the
    ``StragglerMonitor`` but cost no iterations.

    ``chaos`` takes a deterministic :class:`runtime.chaos.ChaosPlan`; the
    returned dict carries the resulting :class:`ChaosReport` under
    ``"report"`` (fault events, recovery cost, checkpoint overhead).
    """
    prob = FractionalProblem(n, beta=beta, h2_tol=h2_tol,
                             construction=construction).build()
    b_host = jnp.ones((n * n,), jnp.float32) * prob["h"] ** 2
    b_norm = float(jnp.linalg.norm(b_host))
    bn_safe = b_norm if b_norm > 0 else 1.0
    plan = chaos if chaos is not None else ChaosPlan.empty()
    report = ChaosReport()
    mon = monitor if monitor is not None else StragglerMonitor()
    mgr = CheckpointManager(ckpt_dir, keep=3) if ckpt_dir else None

    ctx: Dict = {"comm": comm}

    def build_ctx(mesh_cur, dist_source=None):
        parts = make_dist_solve_segment(
            prob, mesh_cur, axis, comm=ctx["comm"], tol=tol,
            steps=ckpt_every, maxiter=maxiter, use_precond=use_precond,
            dist_source=dist_source)
        ctx["parts"] = parts
        ctx["mesh"] = mesh_cur
        ctx["p"] = int(mesh_cur.shape[axis])
        ctx["args"] = parts["place"](parts["args"])
        ctx["b"] = jax.device_put(b_host,
                                  NamedSharding(mesh_cur, P(axis)))

    build_ctx(mesh)
    state = ctx["parts"]["init"](*ctx["args"], ctx["b"])
    total_segments = -(-int(maxiter) // int(ckpt_every))
    flags = {"converged": False}
    pending: Dict = {}
    history: List[float] = []

    def step_fn(seg):
        nonlocal state
        if flags["converged"]:
            return
        p_lost = plan.device_loss(seg)
        if p_lost is not None:
            pending.update(kind="device-loss", segment=seg, p_to=p_lost,
                           k_done=int(jax.device_get(state.k)),
                           t0=time.perf_counter())
            raise StepFailure(f"device lost at segment {seg} "
                              f"(p {ctx['p']} -> {p_lost})")
        t0 = time.perf_counter()
        new_state = ctx["parts"]["segment"](*ctx["args"], ctx["b"], state)
        jax.block_until_ready(new_state.x)
        wall = time.perf_counter() - t0
        if plan.corrupts(seg):
            # in-flight memory corruption: poison the fresh iterate
            # AFTER the recurrence computed it — invisible to the
            # recurrence residual, visible to the recomputed one
            new_state = dataclasses.replace(
                new_state, x=new_state.x * jnp.float32(jnp.nan))
        true_rr, rec_rr = ctx["parts"]["residual"](*ctx["args"], ctx["b"],
                                                   new_state)
        true_rr, rec_rr = float(true_rr), float(rec_rr)
        wall += plan.straggle(seg)
        report.seg_wall_s.append(wall)
        report.segments_run += 1
        if mon.record(seg, wall):
            report.straggler_flags.append(seg)
            report.events.append(FaultEvent(
                kind="straggler", segment=seg, p_from=ctx["p"],
                p_to=ctx["p"], iters_lost=0, recover_s=0.0))
        st = worst_status(getattr(new_state, "status", None))
        if st != 0:
            # the solver's own in-loop breakdown guard (NaN / indefinite
            # carry) — trips without waiting for the recomputed residual
            pending.update(kind="breakdown", segment=seg, p_to=ctx["p"],
                           k_done=int(jax.device_get(new_state.k)),
                           t0=time.perf_counter())
            raise StepFailure(
                f"solver guard tripped at segment {seg} (status {st})")
        if not np.isfinite(true_rr) or true_rr > 10.0 * rec_rr + 1e-5:
            pending.update(kind="corruption", segment=seg, p_to=ctx["p"],
                           k_done=int(jax.device_get(new_state.k)),
                           t0=time.perf_counter())
            raise StepFailure(
                f"residual tripwire at segment {seg}: true relres "
                f"{true_rr:.3e} vs recurrence {rec_rr:.3e}")
        state = new_state
        history.append(rec_rr)
        if mgr is not None:
            t0 = time.perf_counter()
            mgr.save(seg + 1, state,
                     extra={"p": ctx["p"], "tol": tol, "comm": comm,
                            "n": n, "iters": int(jax.device_get(state.k))},
                     block=ckpt_block)
            report.ckpt_save_s.append(time.perf_counter() - t0)
        if float(jax.device_get(state.res)) <= tol * b_norm:
            flags["converged"] = True

    def on_restart(at):
        nonlocal state
        kind = pending.get("kind", "unknown")
        p_from = ctx["p"]
        escalated = False
        if kind == "device-loss":
            p_new = pending["p_to"]
            devs = np.asarray(ctx["mesh"].devices).ravel()[:p_new]
            # the block-row partition is pure reorganization, so the
            # surviving operator re-shards losslessly onto the shrunk
            # mesh — fresh HaloPlans via partition_h2's plan construction
            src = (ctx["parts"]["dshape"], ctx["args"][0])
            build_ctx(Mesh(devs, (axis,)), dist_source=src)
        elif kind in ("corruption", "breakdown") and \
                ctx["comm"].endswith("-bf16"):
            # precision-escalation rung: a numerically-suspect restart on
            # a bf16-payload exchange drops to full fp32 payloads before
            # resuming from the checkpoint
            ctx["comm"] = ctx["comm"][:-len("-bf16")]
            count("guard/elastic/fp32-comm")
            build_ctx(ctx["mesh"])
            escalated = True
        if mgr is not None:
            mgr.wait()
        restored = mgr.latest_step() if mgr is not None else None
        if restored is not None:
            shardings = jax.tree.map(
                lambda s: NamedSharding(ctx["mesh"], s),
                ctx["parts"]["state_specs"])
            state, man = mgr.restore(state, shardings=shardings)
            resume = int(man["step"])
        else:
            state = ctx["parts"]["init"](*ctx["args"], ctx["b"])
            resume = 0
        if escalated and restored is not None:
            # the checkpointed recurrence was produced by the bf16
            # exchange; re-anchor r/p/rz on the fp32 rebuild so the
            # tripwire compares like against like from here on
            state = ctx["parts"]["rebaseline"](*ctx["args"], ctx["b"],
                                               state)
        k_res = int(jax.device_get(state.k))
        report.events.append(FaultEvent(
            kind=kind, segment=pending.get("segment", at), p_from=p_from,
            p_to=ctx["p"], iters_lost=max(0, pending.get("k_done", 0) - k_res),
            recover_s=time.perf_counter() - pending.get("t0",
                                                        time.perf_counter())))
        pending.clear()
        return resume

    _, restarts = run_with_restarts(
        step_fn, start_step=0, total_steps=total_segments,
        max_restarts=max_restarts, on_restart=on_restart)
    if mgr is not None:
        mgr.wait()
    report.restarts = restarts
    res = float(jax.device_get(state.res))
    return {"u": np.asarray(jax.device_get(state.x)).reshape(n, n),
            "iters": int(jax.device_get(state.k)),
            "relres": res / bn_safe,
            "converged": res <= tol * b_norm,
            "status": worst_status(getattr(state, "status", None)),
            "history": history, "prob": prob, "p_final": ctx["p"],
            "comm_final": ctx["comm"],
            "report": report, "parts": ctx["parts"], "restarts": restarts}


def dist_solve_comm_bytes(dshape: DistH2Shape, mg, comm: str = "halo-plan",
                          bytes_per_el: int = 4,
                          tcaps: Optional[Tuple[int, int]] = None,
                          fused: Optional[bool] = None) -> int:
    """Modeled per-device collective bytes of ONE distributed PCG iteration
    on the fractional operator.

    Two-step (``fused=False``): H^2 matvec exchange + the two grid<->tree
    transposition all_gathers + the C-stencil row halo + the V-cycle
    halos (``mg_halo_bytes``) + the three psum'd CG scalars.  Fused
    (DESIGN.md §12): the branch-root gather + ONE merged H^2 all_to_all
    (``merged_exchange_bytes``), the two plan-compressed transposition
    all_to_alls (``tcaps`` = their per-peer row caps, from
    ``make_dist_solve(...)["tcaps"]``; the inbound one carries the
    stencil halo lanes for free), the fused V-cycle halos, and the
    psums."""
    p = dshape.p
    if p <= 1:
        return 0
    fused = _fused_default(fused, comm)
    psums = 3 * (p - 1) * bytes_per_el
    if fused and tcaps is not None:
        if comm.startswith("halo-plan"):
            # merged single-round H^2 exchange
            k_lc = dshape.ranks[dshape.lc]
            mv = (p - 1) * k_lc * bytes_per_el \
                + merged_exchange_bytes(dshape, 1, comm, bytes_per_el)
        else:
            # allgather/ppermute keep their per-level exchange even when
            # the transpositions and V-cycle are fused
            mv = matvec_comm_bytes(dshape, 1, comm, bytes_per_el)
        cap_in, cap_out = tcaps
        # inbound lanes + the [p, n]-wide stencil-halo extra lanes
        transpose = (p - 1) * (cap_in + mg.levels[0] + cap_out) \
            * bytes_per_el
        return mv + transpose + psums + mg_halo_bytes(
            mg, bytes_per_el, fused=True, bf16=comm.endswith("-bf16"))
    mv = matvec_comm_bytes(dshape, 1, comm, bytes_per_el)
    transpose = 2 * (p - 1) * (dshape.n // p) * bytes_per_el
    stencil = 2 * mg.levels[0] * bytes_per_el
    return mv + transpose + stencil + mg_halo_bytes(mg, bytes_per_el) \
        + psums


def dense_reference_solution(n: int, beta: float = 0.75) -> np.ndarray:
    """O(N^2) exact assembly + direct solve, for validation at small n."""
    pts = interior_grid(n)
    h = 2.0 / n
    kern = fractional_kernel_2d(beta)
    k_mat = kern(pts[:, None, :], pts[None, :, :])
    pts_ext, inside = extended_grid(n)
    kpos = fractional_kernel_2d_positive(beta)
    khat = kpos(pts_ext[:, None, :], pts_ext[None, :, :])
    d_ext = khat.sum(axis=1)
    d = d_ext[inside]
    kappa = diffusivity_2d(pts).reshape(n, n)
    gamma = h ** (-2.0 * beta)

    # dense C via applying apply_c to unit vectors
    nn = n * n
    c_mat = np.zeros((nn, nn))
    eye = np.eye(nn, dtype=np.float32)
    for i in range(nn):
        c_mat[:, i] = np.asarray(apply_c(
            jnp.asarray(eye[:, i].reshape(n, n)), jnp.asarray(kappa), h)
        ).ravel()
    a = (h * h) * (np.diag(d) + k_mat + gamma * c_mat)
    b = np.full(nn, h * h)
    return np.linalg.solve(a, b).reshape(n, n)
