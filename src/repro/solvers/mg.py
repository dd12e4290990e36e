"""Sharded geometric-multigrid V-cycle preconditioner (DESIGN.md §7).

Re-derivation of the GMG stand-in for the paper's AMG (previously a
host-looped, single-device closure in ``apps/fractional.py``) as a
stencil V-cycle on ``gamma*C + diag(D)`` that runs entirely inside one
``shard_map`` program:

  - the grid is sharded in contiguous **row strips** ([n, n] -> [n/p, n]
    per device), matching the flat-vector ``P(axis)`` sharding of the
    Krylov state;
  - the 5-point kappa-weighted stencil's face coefficients are precomputed
    globally per level on the host and sharded with the grid, so smoothing
    needs only a one-row halo of ``u`` — two ``ppermute`` shifts per
    stencil application (zero rows at the domain boundary = the volume
    constraint's Dirichlet condition);
  - restriction / prolongation are local while the strip keeps an even
    number of rows (level ``l`` stays sharded iff ``n_l % 2p == 0``);
  - below that, the coarse grid is **gathered to every device**
    (``all_gather``, the psum-style coarsening of the tiny top levels) and
    the remaining V-cycle tail runs replicated — the same
    replicate-the-top-tree deviation as the distributed H^2 sweeps
    (DESIGN.md §2), removing any root-device serialization.

``p = 1`` builds the identical numerics with no communication primitives,
so the single-device ``apps.fractional.make_preconditioner`` is now a thin
wrapper over this module.
"""
from __future__ import annotations

import dataclasses
from typing import List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.obs.trace import phase


@dataclasses.dataclass(frozen=True)
class GridMG:
    """Static V-cycle description (shapes, schedule, scalars)."""
    n: int
    p: int
    levels: Tuple[int, ...]          # grid side per level (n, n/2, ..., 4)
    hs: Tuple[float, ...]
    n_sharded: int                   # leading levels kept in strip layout
    gamma: float
    nu: int = 3
    omega: float = 0.7
    n_cycles: int = 2

    def sharded(self, l: int) -> bool:
        return self.p > 1 and l < self.n_sharded


@jax.tree_util.register_pytree_node_class
@dataclasses.dataclass
class MGArrays:
    """Per-level stencil data.  Levels ``< n_sharded`` are sharded over the
    mesh axis (leading/row dim), the tail is replicated — ``mg_specs``
    builds the matching PartitionSpec pytree."""
    ke: List[jax.Array]              # face coefficients [n_l, n_l]
    kw: List[jax.Array]
    kn: List[jax.Array]
    ks: List[jax.Array]
    dd: List[jax.Array]              # restricted diag(D) [n_l, n_l]
    jd: List[jax.Array]              # Jacobi diagonal gamma*ksum/h^2 + dd
    #: per SHARDED level, the nu-row-extended coefficient strips feeding
    #: the fused deep-halo smoother (``_smooth_deep``): global
    #: [p*(n_l/p + 2*nu), 6, n_l] with field order (ke, kw, kn, ks, dd,
    #: jd); out-of-domain ghost coefficients are 0 (jd ghost 1) so ghost
    #: updates stay exactly +0.0.  Empty at p == 1.
    hc: List[jax.Array] = dataclasses.field(default_factory=list)

    def tree_flatten(self):
        return ((tuple(self.ke), tuple(self.kw), tuple(self.kn),
                 tuple(self.ks), tuple(self.dd), tuple(self.jd),
                 tuple(self.hc)), None)

    @classmethod
    def tree_unflatten(cls, aux, ch):
        return cls(*(list(c) for c in ch))


def _restrict_np(r: np.ndarray) -> np.ndarray:
    return 0.25 * (r[0::2, 0::2] + r[1::2, 0::2] + r[0::2, 1::2]
                   + r[1::2, 1::2])


def stencil_faces(k: np.ndarray):
    """Edge-padded face-averaged diffusivity coefficients of the 5-point
    ``-div kappa grad`` stencil (neighbor order: row+1, row-1, col+1,
    col-1)."""
    kp = np.pad(k, 1, mode="edge")
    ke = 0.5 * (kp[1:-1, 1:-1] + kp[2:, 1:-1])
    kw = 0.5 * (kp[1:-1, 1:-1] + kp[:-2, 1:-1])
    kn = 0.5 * (kp[1:-1, 1:-1] + kp[1:-1, 2:])
    ks = 0.5 * (kp[1:-1, 1:-1] + kp[1:-1, :-2])
    return ke, kw, kn, ks


def build_grid_mg(kappa, d_diag, gamma: float, h0: float, n: int, p: int = 1,
                  nu: int = 3, omega: float = 0.7, n_cycles: int = 2
                  ) -> Tuple[GridMG, MGArrays]:
    """Host-side pyramid build: restrict kappa/diag(D), precompute faces.

    ``kappa``/``d_diag``: [n, n] grid-order arrays.  ``p > 1`` requires
    ``n % p == 0`` (row-strip layout).
    """
    if p > 1 and n % p != 0:
        raise ValueError(f"grid side {n} not divisible by p={p}")
    k = np.asarray(kappa, np.float32)
    d = np.asarray(d_diag, np.float32)
    levels, hs = [], []
    fields_np = []                   # per level (ke, kw, kn, ks, dd, jd)
    arrs = MGArrays([], [], [], [], [], [])
    nn, hh = n, h0
    while nn >= 4:
        ke, kw, kn, ks = stencil_faces(k)
        jd = gamma * (ke + kw + kn + ks) / (hh * hh) + d
        for lst, a in zip((arrs.ke, arrs.kw, arrs.kn, arrs.ks, arrs.dd,
                           arrs.jd), (ke, kw, kn, ks, d, jd)):
            lst.append(jnp.asarray(a))
        fields_np.append((ke, kw, kn, ks, d, jd))
        levels.append(nn)
        hs.append(hh)
        k = _restrict_np(k)
        d = _restrict_np(d)
        nn //= 2
        hh *= 2
    n_sharded = 0
    if p > 1:
        for n_l in levels:
            if n_l % (2 * p) != 0:
                break
            n_sharded += 1
    if p > 1:
        # nu-row-extended coefficient strips for the fused deep-halo
        # smoother: out-of-domain ghosts get zero face/diag coefficients
        # and a unit Jacobi diagonal, so a ghost row's update is exactly
        # ``u + omega*(b_ext - 0)/1`` — +0.0 whenever its b/u ghosts are
        # zero, reproducing the Dirichlet zero-fill of ``_halo_rows``
        kh = nu
        for l in range(n_sharded):
            n_l, rows = levels[l], levels[l] // p
            padded = [np.pad(f, ((kh, kh), (0, 0)),
                             constant_values=1.0 if i == 5 else 0.0)
                      for i, f in enumerate(fields_np[l])]
            stacked = np.stack(padded, axis=1)   # [n_l + 2kh, 6, n_l]
            arrs.hc.append(jnp.asarray(np.concatenate(
                [stacked[q * rows:q * rows + rows + 2 * kh]
                 for q in range(p)], axis=0)))
    mg = GridMG(n=n, p=p, levels=tuple(levels), hs=tuple(hs),
                n_sharded=n_sharded, gamma=gamma, nu=nu, omega=omega,
                n_cycles=n_cycles)
    return mg, arrs


def mg_specs(mg: GridMG, axis) -> MGArrays:
    """PartitionSpec pytree matching ``MGArrays`` for ``shard_map``."""
    from jax.sharding import PartitionSpec as P
    specs = [P(axis) if mg.sharded(l) else P()
             for l in range(len(mg.levels))]
    n_hc = mg.n_sharded if mg.p > 1 else 0
    return MGArrays(ke=list(specs), kw=list(specs), kn=list(specs),
                    ks=list(specs), dd=list(specs), jd=list(specs),
                    hc=[P(axis)] * n_hc)


# ---------------------------------------------------------------------------
# device-side V-cycle
# ---------------------------------------------------------------------------

def _halo_rows(u: jax.Array, axis, p: int):
    """One-row halo from the row-strip neighbors (zeros at the boundary)."""
    top = jax.lax.ppermute(u[-1:], axis,
                           [(s, s + 1) for s in range(p - 1)])
    bot = jax.lax.ppermute(u[:1], axis,
                           [(s, s - 1) for s in range(1, p)])
    return top, bot


def _apply_op(mg: GridMG, a: MGArrays, l: int, u: jax.Array, axis,
              halo=None) -> jax.Array:
    """(gamma*C + diag(D)) u on level ``l`` (strip or replicated layout).

    ``halo`` optionally supplies already-landed ``(top, bot)`` neighbor
    rows (each ``[1, n_l]``) — the fused solver iteration rides them on
    the grid->tree transposition ``all_to_all`` instead of a dedicated
    ``ppermute`` pair.
    """
    if halo is not None:
        top, bot = halo
    elif mg.sharded(l):
        top, bot = _halo_rows(u, axis, mg.p)
    else:
        top = jnp.zeros_like(u[:1])
        bot = jnp.zeros_like(u[:1])
    ue = jnp.concatenate([top, u, bot], axis=0)       # rows halo
    uc = jnp.pad(u, ((0, 0), (1, 1)))                 # cols: Dirichlet
    h = mg.hs[l]
    lap = (a.ke[l] * (ue[2:] - u) + a.kw[l] * (ue[:-2] - u)
           + a.kn[l] * (uc[:, 2:] - u) + a.ks[l] * (uc[:, :-2] - u))
    return mg.gamma * (-lap / (h * h)) + a.dd[l] * u


def _smooth(mg: GridMG, a: MGArrays, l: int, u, b, axis):
    for _ in range(mg.nu):
        r = b - _apply_op(mg, a, l, u, axis)
        u = u + mg.omega * r / a.jd[l]
    return u


def _halo_rows_k(u: jax.Array, axis, p: int, k: int):
    """``k``-row halo from the strip neighbors (zeros at the boundary).

    ``k`` may exceed the strip height: hop ``j`` fetches from the
    neighbor ``j`` strips away with one ``ppermute`` (2*ceil(k/rows)
    permutes total, never per-sweep).  Row order is global top-to-bottom.
    """
    rows = u.shape[0]
    tops, bots = [], []
    j = -(-k // rows)                       # farthest hop first (top halo)
    while j > 0:
        t = min(k - (j - 1) * rows, rows)   # rows owed by hop j
        if j >= p:                          # beyond the domain: Dirichlet
            z = jnp.zeros((t,) + u.shape[1:], u.dtype)
            tops.append(z)
            bots.append(z)
        else:
            tops.append(jax.lax.ppermute(
                u[rows - t:], axis, [(s, s + j) for s in range(p - j)]))
            bots.append(jax.lax.ppermute(
                u[:t], axis, [(s, s - j) for s in range(j, p)]))
        j -= 1
    top = jnp.concatenate(tops, axis=0) if len(tops) > 1 else tops[0]
    bot = jnp.concatenate(bots[::-1], axis=0) if len(bots) > 1 else bots[0]
    return top, bot


def _extend(x: jax.Array, axis, p: int, kh: int, k: int, bf16: bool):
    """Strip -> ``kh``-row-extended strip with ``k`` real halo rows per
    side (zero-padded to ``kh``).  ``bf16`` rounds the shipped halo rows
    only — own rows stay exact."""
    if k <= 0:
        z = jnp.zeros((kh,) + x.shape[1:], x.dtype)
        return jnp.concatenate([z, x, z], axis=0)
    src = x
    if bf16:
        src = jax.lax.optimization_barrier(x.astype(jnp.bfloat16))
    top, bot = _halo_rows_k(src, axis, p, k)
    top, bot = top.astype(x.dtype), bot.astype(x.dtype)
    parts = [top, x, bot]
    if k < kh:
        z = jnp.zeros((kh - k,) + x.shape[1:], x.dtype)
        parts = [z] + parts + [z]
    return jnp.concatenate(parts, axis=0)


def _smooth_deep(mg: GridMG, a: MGArrays, l: int, u_ext, b_ext, axis):
    """``nu`` weighted-Jacobi sweeps on the ``nu``-row-extended strip with
    ZERO per-sweep communication (the fused schedule, DESIGN.md §12).

    Bitwise-identical to ``_smooth`` on the own rows: each sweep
    recomputes the ghost rows from the neighbor's exact operands (the
    extended coefficient strips ``a.hc[l]``), so a ghost row holds the
    same bits the neighbor computes for it; validity shrinks one row per
    sweep and the ``b`` halo needs only depth ``nu - 1``.  The caller
    slices ``[nu:-nu]``."""
    hc = a.hc[l]                            # [rows + 2nu, 6, n_l]
    ke, kw, kn, ks, dd, jd = (hc[:, i] for i in range(6))
    h = mg.hs[l]
    u = u_ext
    for _ in range(mg.nu):
        ue = jnp.concatenate([jnp.zeros_like(u[:1]), u,
                              jnp.zeros_like(u[:1])], axis=0)
        uc = jnp.pad(u, ((0, 0), (1, 1)))
        lap = (ke * (ue[2:] - u) + kw * (ue[:-2] - u)
               + kn * (uc[:, 2:] - u) + ks * (uc[:, :-2] - u))
        au = mg.gamma * (-lap / (h * h)) + dd * u
        u = u + mg.omega * (b_ext - au) / jd
    return u


def _restrict(r):
    # full weighting over each 2x2 block, the additions in
    # ``_restrict_np``'s order.  Strided ``lax.slice``, not
    # ``r[0::2, 0::2]``: JAX lowers numpy-style strided indexing to an
    # element-by-element gather, which took most of the V-cycle's device
    # time on the TPU
    s = lambda i, j: jax.lax.slice(r, (i, j), r.shape, (2, 2))
    return 0.25 * (s(0, 0) + s(1, 0) + s(0, 1) + s(1, 1))


def _prolong(e):
    # piecewise-constant: each coarse value fills its 2x2 fine block.  No
    # scatter: the TPU compiler splits strided scatters into fusions that
    # carry no scope, so their time showed under no phase
    return jnp.repeat(jnp.repeat(e, 2, axis=0), 2, axis=1)


def _vcycle(mg: GridMG, a: MGArrays, l: int, b, axis, fused: bool = False,
            bf16: bool = False):
    # python recursion over static levels: each level's ops get their own
    # named scope ("mg/level0", "mg/level1", ...) in profiles
    #
    # fused (DESIGN.md §12): sharded levels smooth on the nu-row-extended
    # strip — ONE (nu-1)-row exchange of b before the pre-smooth and ONE
    # nu-row exchange of u before the post-smooth replace the 2*nu
    # per-sweep one-row halos, bitwise-identically (``_smooth_deep``).
    # The restriction residual keeps its exact one-row ``_apply_op``
    # exchange.  ``bf16`` (halo-plan-bf16 payloads) rounds only the
    # smoothing-halo rows; residual exchanges stay fp32.
    deep = fused and mg.sharded(l) and l < len(a.hc)
    kh = mg.nu
    b_ext = None
    with phase(f"mg/level{l}"):
        if deep:
            b_ext = _extend(b, axis, mg.p, kh, mg.nu - 1, bf16)
            u = _smooth_deep(mg, a, l, jnp.zeros_like(b_ext), b_ext,
                             axis)[kh:-kh]
        else:
            u = _smooth(mg, a, l, jnp.zeros_like(b), b, axis)
        if l + 1 < len(mg.levels):
            r = b - _apply_op(mg, a, l, u, axis)
            rc = _restrict(r)
        else:
            return u
    if mg.sharded(l) and not mg.sharded(l + 1):
        # sharded -> replicated switch: gather the coarse strips so the
        # tiny tail levels run redundantly on every device
        with phase("mg/coarse-gather"):
            rlc = rc.shape[0]
            rc_full = jax.lax.all_gather(rc, axis, axis=0, tiled=True)
        e = _vcycle(mg, a, l + 1, rc_full, axis, fused, bf16)
        me = jax.lax.axis_index(axis)
        e = jax.lax.dynamic_slice_in_dim(e, me * rlc, rlc, axis=0)
    else:
        e = _vcycle(mg, a, l + 1, rc, axis, fused, bf16)
    with phase(f"mg/level{l}"):
        u = u + _prolong(e)
        if deep:
            u_ext = _extend(u, axis, mg.p, kh, kh, bf16)
            u = _smooth_deep(mg, a, l, u_ext, b_ext, axis)[kh:-kh]
        else:
            u = _smooth(mg, a, l, u, b, axis)
    return u


def mg_precond_local(mg: GridMG, a: MGArrays, r: jax.Array, axis=None,
                     fused: bool = False, bf16: bool = False) -> jax.Array:
    """Apply ``n_cycles`` V-cycles to the flat residual ``r``.

    Single-device: ``r`` is the full [n*n] grid-order vector.  Inside
    ``shard_map`` (``p > 1``): ``r`` is the device's [n*n/p] row strip.
    The incoming residual is scaled by ``1/h^2`` — the preconditioner
    inverts the UNSCALED local operator ``gamma*C + diag(D)`` while the
    fractional system carries the paper's ``h^2`` prefactor.

    ``fused``: comm-avoiding deep-halo smoothing on sharded levels (3
    exchanges per level per cycle instead of ``2*nu + 1``, bitwise-equal
    results); ``bf16`` additionally rounds the smoothing-halo payloads
    (halo-plan-bf16 comm modes).
    """
    with phase("precond/vcycle"):
        h0 = mg.hs[0]
        strip = mg.p > 1
        rows = (mg.n // mg.p) if strip else mg.n
        b = r.reshape(rows, mg.n) / (h0 * h0)
        gathered = strip and mg.n_sharded == 0
        if gathered:  # too coarse to shard even level 0: replicate fully
            b = jax.lax.all_gather(b, axis, axis=0, tiled=True)
        u = jnp.zeros_like(b)
        for _ in range(mg.n_cycles):
            u = u + _vcycle(mg, a, 0, b - _apply_op(mg, a, 0, u, axis),
                            axis, fused, bf16)
        if gathered:
            me = jax.lax.axis_index(axis)
            u = jax.lax.dynamic_slice_in_dim(u, me * rows, rows, axis=0)
        return u.reshape(r.shape)


def mg_halo_bytes(mg: GridMG, bytes_per_el: int = 4, fused: bool = False,
                  bf16: bool = False) -> int:
    """Per-device collective bytes of ONE preconditioner application.

    Unfused: each stencil application on a sharded level ships two halo
    rows; one V-cycle does ``2*nu + 2`` stencil applications per
    non-coarsest level (two smooths + the restriction residual + the
    cycle-entry residual is counted once at level 0 by the caller loop)
    and ``nu`` on the coarsest.  Fused (deep-halo smoothing, DESIGN.md
    §12): the pre-smooth ships one ``(nu-1)``-row b halo, the post-smooth
    one ``nu``-row u halo (both at ``bf16`` width when the comm mode
    rounds payloads), and only the residual exchanges remain one-row
    fp32.  The sharded->replicated switch adds one coarse-grid
    all_gather either way.
    """
    if mg.p <= 1:
        return 0
    if mg.n_sharded == 0:
        # gathered path: one full-grid all_gather per application (the
        # replicated V-cycle itself is then communication-free)
        return (mg.p - 1) * (mg.n // mg.p) * mg.n * bytes_per_el
    total = 0
    nlev = len(mg.levels)
    bpe_h = 2 if (fused and bf16) else bytes_per_el
    for l in range(min(mg.n_sharded, nlev)):
        n_l = mg.levels[l]
        if fused:
            rows_h = mg.nu - 1                    # pre-smooth b halo
            if l < nlev - 1:
                rows_h += mg.nu                   # post-smooth u halo
            total += 2 * rows_h * n_l * bpe_h
            resid = 1 if l < nlev - 1 else 0      # restriction residual
            if l == 0:
                resid += 1                        # cycle-entry residual
            total += resid * 2 * n_l * bytes_per_el
        else:
            apps = mg.nu if l == nlev - 1 else 2 * mg.nu + 1
            if l == 0:
                apps += 1                         # cycle-entry residual
            total += apps * 2 * n_l * bytes_per_el
    if 0 < mg.n_sharded < nlev:
        n_sw = mg.levels[mg.n_sharded]      # replicated coarse side
        total += (mg.p - 1) * (n_sw * n_sw // mg.p) * bytes_per_el
    return total * mg.n_cycles


def solver_hide_flops(mg: Optional[GridMG], nv: int = 1) -> int:
    """Static per-iteration estimate of the solver compute OUTSIDE the
    H^2 matvec — the C-stencil application plus the V-cycle smoothing —
    available to hide H^2 halo transfers under.  Feeds the solver-aware
    ``schedule="auto"`` policy (``core.dist._use_split``): when this
    dwarfs a level's coupling-GEMM flops the split schedule's padded
    off-diagonal GEMM buys nothing, so auto keeps the combined form and
    the merged single-round exchange simply lands before phase C.
    """
    if mg is None:
        return 0
    pdiv = mg.p if mg.p > 1 else 1
    # ~11 flops/point per 5-point stencil application, +4 for the Jacobi
    # update riding each smoothing sweep
    total = 11 * (mg.levels[0] ** 2 // pdiv)      # A's stencil term
    vcyc = 0
    nlev = len(mg.levels)
    for l, n_l in enumerate(mg.levels):
        pts = n_l * n_l // (pdiv if mg.sharded(l) else 1)
        apps = mg.nu if l == nlev - 1 else 2 * mg.nu + 1
        if l == 0:
            apps += 1
        vcyc += apps * 15 * pts
    return (total + vcyc * mg.n_cycles) * nv
