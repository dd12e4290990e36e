"""Algebraic H^2 recompression (paper §5) as a single-sweep pipeline.

Three passes, all batched per level (the paper's downsweep/upsweep
structure):

1. ``compression_weights`` — downsweep computing the re-weighting factors
   ``R_t`` per basis node from QR of the stacked ``[R_parent E^T; S^T ...]``
   blocks (paper Eq. 2–4).  Requires orthogonal bases (run ``orthogonalize``
   first).
2. Truncation upsweep of batched SVDs.  Because the bases are orthonormal,
   the SVD of the re-weighted basis ``U R^T`` ([m, k]) reduces to the SVD of
   the small ``R^T`` ([k, k]) at the leaves, and of the stacked projected
   transfers at inner nodes.  Produces the truncated basis (new leaf bases +
   transfer matrices) and the old->new projection maps ``P = U'^T U``.
3. Coupling projection ``S' = P_row S P_col^T`` (batched GEMM, paper §5.2
   end).

Rank selection (DESIGN.md §5.5):

- ``target_ranks`` (static per level): the **entire** pipeline
  ``orthogonalize -> weights -> truncate -> project`` is one jitted program
  (``_compress_fixed``) — a single dispatch from Python, which is what the
  multi-pod dry-run lowers.
- ``tol`` (singular-value threshold): a **single sweep**.  Each upsweep SVD
  is computed exactly once; only its singular values travel to the host,
  where the per-level rank is picked, and the already-computed factors are
  sliced to the picked rank and reused — no re-factorization.  The
  two-sweep implementation this replaces (probe the upsweep for ranks, then
  redo it to truncate) is retained as ``pick_ranks_by_tol`` + ``truncate``
  behind ``compress(..., legacy_two_sweep=True)``: it is the reference the
  rank-pick property test compares against and the baseline the compression
  benchmark measures the fused path's speedup from.

The upsweep step functions (``truncation_leaf_factors`` /
``truncation_inner_factors`` / ``truncation_project``) are shared with the
distributed compression in ``core/dist.py``, which runs the same schedule
per branch inside ``shard_map``.
"""
from __future__ import annotations

import collections
import dataclasses
import functools
from typing import List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp

from repro.obs.trace import phase

from .structure import H2Data, H2Shape, remarshal, shape_of, \
    stack_blocks_by_plan

# incremented when the fused fixed-rank pipeline is (re)traced — the
# single-dispatch regression test asserts repeat calls do not retrace
TRACE_COUNTS = collections.Counter()


def _batched_qr_r(a: jax.Array, backend: str) -> jax.Array:
    from repro.kernels.ops import backend_qr_r
    return backend_qr_r(a, backend)


def _batched_svd(a: jax.Array, backend: str):
    from repro.kernels.ops import backend_svd
    return backend_svd(a, backend)


def _slot_positions(idx: jax.Array, n_nodes: int) -> jax.Array:
    """Position of each (sorted) block within its row/column group."""
    start = jnp.searchsorted(idx, jnp.arange(n_nodes, dtype=idx.dtype))
    return jnp.arange(idx.shape[0], dtype=idx.dtype) - start[idx]


def _stack_blocks(blocks: jax.Array, idx: jax.Array, n_nodes: int,
                  maxb: int) -> jax.Array:
    """Scatter [nb,k,k] blocks into [n_nodes, maxb*k, k] stacks by group."""
    k1, k2 = blocks.shape[-2], blocks.shape[-1]
    pos = _slot_positions(idx, n_nodes)
    flat = jnp.zeros((n_nodes * maxb, k1, k2), blocks.dtype)
    flat = flat.at[idx * maxb + pos].set(blocks)
    return flat.reshape(n_nodes, maxb * k1, k2)


def compression_weights(shape: H2Shape, data: H2Data, backend: str = "jnp",
                        aliased: bool = False
                        ) -> Tuple[List[jax.Array], List[jax.Array]]:
    """Downsweep computing R_t per node for the row (U) and column (V) trees.

    ``aliased=True`` (fused pipelines, symmetric operators with one shared
    basis tree) skips the column sweep entirely: for a symmetric operator
    ``S_ts = S_st^T`` block-for-block, so node t's column-grouped stack of
    ``S`` is float-identical to its row-grouped stack of ``S^T`` and the
    two QR sweeps produce the same R factors.
    """
    depth = shape.depth
    ranks = shape.ranks

    def sweep(transfers, stacked_fn, maxb_tuple):
        r: List[jax.Array] = [None] * (depth + 1)
        r[0] = jnp.zeros((1, ranks[0], ranks[0]), data.u_leaf.dtype)
        for l in range(1, depth + 1):
            nn = shape.nodes(l)
            kl, kp = ranks[l], ranks[l - 1]
            # parent part: R_parent @ E_c^T -> [2**l, k_{l-1}, k_l]
            rpar = jnp.repeat(r[l - 1], 2, axis=0)
            par = jnp.einsum("cij,ckj->cik", rpar, transfers[l],
                             precision="highest")
            pieces = [par]
            if shape.coupling_counts[l] > 0 and maxb_tuple[l] > 0:
                pieces.append(stacked_fn(l))        # [nn, maxb*k_l, k_l]
            stack = jnp.concatenate(pieces, axis=1)
            if stack.shape[1] < kl:                        # ensure R is [k_l, k_l]
                pad = jnp.zeros((nn, kl - stack.shape[1], kl), stack.dtype)
                stack = jnp.concatenate([stack, pad], axis=1)
            r[l] = _batched_qr_r(stack, backend)[..., :kl, :]
        return r

    # Row tree: blocks grouped by row, entries S^T (paper Eq. 4).  The
    # row-marshaled buffer [nn, k, maxb*k] transposes into exactly the
    # stacked layout the sweep wants — the plan replaces the scatter in
    # ``_stack_blocks``.
    def stacked_row(l):
        if data.s_mar is not None:
            return jnp.swapaxes(data.s_mar[l], -1, -2)
        return _stack_blocks(jnp.swapaxes(data.s[l], -1, -2),
                             data.s_rows[l], shape.nodes(l),
                             shape.row_maxb[l])

    # Column tree: blocks grouped by column, entries S (un-transposed).
    def stacked_col(l):
        if data.plan is not None:
            return stack_blocks_by_plan(data.s[l], data.plan.cblk[l],
                                        shape.nodes(l))
        order = jnp.argsort(data.s_cols[l], stable=True)
        return _stack_blocks(jnp.take(data.s[l], order, axis=0),
                             jnp.sort(data.s_cols[l]), shape.nodes(l),
                             shape.col_maxb[l])

    with phase("compress/weights"):
        ru = sweep(data.e, stacked_row, shape.row_maxb)
        if aliased and shape.symmetric:
            return ru, ru
        rv = sweep(data.f, stacked_col, shape.col_maxb)
        return ru, rv


# ---------------------------------------------------------------------------
# truncation upsweep steps (shared with the distributed path in core/dist.py)
# ---------------------------------------------------------------------------

def truncation_leaf_factors(r_leaf: jax.Array, backend: str = "jnp"
                            ) -> Tuple[jax.Array, jax.Array]:
    """Leaf upsweep step: SVD of ``R^T`` (U orthonormal) -> (basis, svals)."""
    w, s, _ = _batched_svd(jnp.swapaxes(r_leaf, -1, -2), backend)
    return w, s


def truncation_inner_factors(p: jax.Array, transfer: jax.Array,
                             r_parent: jax.Array, backend: str = "jnp"
                             ) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """Inner upsweep step at level ``l``: children candidate ``P_c E_c``
    stacked per parent and re-weighted by ``R_{l-1}``; one batched SVD.

    Returns (stack [nn/2, 2r_l, k_{l-1}], basis g, svals).
    """
    pe = jnp.einsum("crk,ckp->crp", p, transfer, precision="highest")
    rl = pe.shape[1]
    stack = pe.reshape(pe.shape[0] // 2, 2 * rl, -1)
    m = jnp.einsum("nik,njk->nij", stack, r_parent, precision="highest")
    g, s, _ = _batched_svd(m, backend)
    return stack, g, s


def truncation_project(gk: jax.Array, stack: jax.Array) -> jax.Array:
    """Next level's projection map ``P_{l-1} = G_k^T stack``."""
    return jnp.einsum("nir,nik->nrk", gk, stack, precision="highest")


def _project_couplings(shape: H2Shape, data: H2Data, pu: List[jax.Array],
                       pv: List[jax.Array], dtype) -> List[jax.Array]:
    """Coupling projection ``S' = P_row S P_col^T`` (batched GEMM)."""
    s_new = []
    with phase("compress/project-s"):
        for l in range(shape.depth + 1):
            if shape.coupling_counts[l] == 0:
                s_new.append(jnp.zeros((0, pu[l].shape[1], pv[l].shape[1]),
                                       dtype))
                continue
            pl = jnp.take(pu[l], data.s_rows[l], axis=0)  # [nb, r, k]
            pr = jnp.take(pv[l], data.s_cols[l], axis=0)
            s_new.append(jnp.einsum("brk,bkj,bsj->brs", pl, data.s[l], pr,
                                    precision="highest"))
    return s_new


def _pack_truncated(shape: H2Shape, data: H2Data, u_leaf, v_leaf, e_new,
                    f_new, pu, pv) -> Tuple[H2Shape, H2Data]:
    """Assemble the truncated operator + refreshed marshaled buffers."""
    depth = shape.depth
    s_new = _project_couplings(shape, data, pu, pv, u_leaf.dtype)
    new_ranks = tuple(int(pu[l].shape[1]) for l in range(depth + 1))
    new_shape = H2Shape(n=shape.n, leaf_size=shape.leaf_size, depth=depth,
                        ranks=new_ranks,
                        coupling_counts=shape.coupling_counts,
                        dense_count=shape.dense_count,
                        symmetric=shape.symmetric,
                        row_maxb=shape.row_maxb, col_maxb=shape.col_maxb,
                        dense_maxb=shape.dense_maxb)
    new_data = remarshal(H2Data(
        u_leaf=u_leaf, v_leaf=v_leaf, e=e_new, f=f_new,
        s=s_new, s_rows=list(data.s_rows),
        s_cols=list(data.s_cols), dense=data.dense,
        d_rows=data.d_rows, d_cols=data.d_cols,
        plan=data.plan, dense_mar=data.dense_mar), dense=False)
    return new_shape, new_data


def truncate(shape: H2Shape, data: H2Data, ru: List[jax.Array],
             rv: List[jax.Array], target_ranks: Sequence[int],
             backend: str = "jnp") -> Tuple[H2Shape, H2Data]:
    """Upsweep truncation + coupling projection with static target ranks.

    Fully jittable; ``_compress_fixed`` fuses it with the orthogonalization
    and weights passes into one program.
    """
    depth = shape.depth
    tr = list(target_ranks)

    def sweep(leaf, transfers, r):
        """Returns (new_leaf, new_transfers, p[l] projections)."""
        p: List[jax.Array] = [None] * (depth + 1)
        new_t: List[jax.Array] = [transfers[0]] + [None] * depth
        w, _ = truncation_leaf_factors(r[depth], backend)
        rq = min(tr[depth], w.shape[-1])
        wk = w[..., :rq]                                  # [nl, k, r]
        new_leaf = jnp.einsum("nmk,nkr->nmr", leaf, wk, precision="highest")
        p[depth] = jnp.swapaxes(wk, -1, -2)               # [nl, r, k]
        for l in range(depth, 0, -1):
            nn = shape.nodes(l)
            stack, g, _ = truncation_inner_factors(p[l], transfers[l],
                                                   r[l - 1], backend)
            rl = stack.shape[1] // 2
            rp = min(tr[l - 1], g.shape[-1], 2 * rl)
            gk = g[..., :rp]                              # [.., 2r_l, rp]
            new_t[l] = gk.reshape(nn, rl, rp)             # split children rows
            p[l - 1] = truncation_project(gk, stack)
        return new_leaf, new_t, p

    with phase("compress/truncate"):
        u_leaf, e_new, pu = sweep(data.u_leaf, data.e, ru)
        if shape.symmetric and data.v_leaf is data.u_leaf:
            v_leaf, f_new, pv = u_leaf, e_new, pu
        else:
            v_leaf, f_new, pv = sweep(data.v_leaf, data.f, rv)
    return _pack_truncated(shape, data, u_leaf, v_leaf, e_new, f_new, pu, pv)


# jitted single-sweep steps (cached per level shape; the tol path stays
# host-in-the-loop only for the integer rank picks)
_leaf_factors_jit = jax.jit(truncation_leaf_factors,
                            static_argnames=("backend",))
_inner_factors_jit = jax.jit(truncation_inner_factors,
                             static_argnames=("backend",))


@functools.partial(jax.jit, static_argnames=("rq",))
def _leaf_apply_jit(leaf: jax.Array, w: jax.Array, rq: int):
    wk = w[..., :rq]
    new_leaf = jnp.einsum("nmk,nkr->nmr", leaf, wk, precision="highest")
    return new_leaf, jnp.swapaxes(wk, -1, -2)


@functools.partial(jax.jit, static_argnames=("rp", "nn"))
def _inner_apply_jit(g: jax.Array, stack: jax.Array, rp: int, nn: int):
    gk = g[..., :rp]
    return gk.reshape(nn, stack.shape[1] // 2, rp), \
        truncation_project(gk, stack)


@functools.partial(jax.jit, static_argnames=("shape",))
def _pack_data_jit(shape: H2Shape, data: H2Data, u_leaf, v_leaf,
                   e_new, f_new, pu, pv) -> H2Data:
    return _pack_truncated(shape, data, u_leaf, v_leaf, list(e_new),
                           list(f_new), list(pu), list(pv))[1]


def truncate_by_tol(shape: H2Shape, data: H2Data, ru: List[jax.Array],
                    rv: List[jax.Array], tol: float, backend: str = "jnp"
                    ) -> Tuple[H2Shape, H2Data]:
    """Single-sweep tolerance truncation (the fused tol path).

    Each upsweep SVD runs exactly once: its singular values are pulled to
    the host to pick the level's rank (``rank = max #{sigma > tol*scale}``
    over both trees, the same pick the two-sweep reference makes), then the
    already-computed factors are sliced to that rank and the sweep
    continues — no second factorization pass.
    """
    depth = shape.depth

    wu, su = _leaf_factors_jit(ru[depth], backend)
    sym = shape.symmetric and data.v_leaf is data.u_leaf
    wv, sv = (wu, su) if sym else _leaf_factors_jit(rv[depth], backend)
    scale = float(jnp.maximum(su.max(), sv.max()))
    thresh = tol * scale

    def count2(s_a, s_b) -> int:
        c = jnp.maximum((s_a > thresh).sum(axis=-1).max(),
                        (s_b > thresh).sum(axis=-1).max())
        return int(jnp.maximum(c, 1))

    rq = min(count2(su, sv), shape.ranks[depth])

    u_leaf, p_u = _leaf_apply_jit(data.u_leaf, wu, rq)
    v_leaf, p_v = (u_leaf, p_u) if sym else \
        _leaf_apply_jit(data.v_leaf, wv, rq)
    pu: List[jax.Array] = [None] * (depth + 1)
    pv: List[jax.Array] = [None] * (depth + 1)
    pu[depth], pv[depth] = p_u, p_v
    e_new: List[jax.Array] = [data.e[0]] + [None] * depth
    f_new: List[jax.Array] = [data.f[0]] + [None] * depth

    for l in range(depth, 0, -1):
        nn = shape.nodes(l)
        stack_u, g_u, s_u = _inner_factors_jit(pu[l], data.e[l],
                                               ru[l - 1], backend)
        stack_v, g_v, s_v = (stack_u, g_u, s_u) if sym else \
            _inner_factors_jit(pv[l], data.f[l], rv[l - 1], backend)
        rl = stack_u.shape[1] // 2
        rp = min(count2(s_u, s_v), shape.ranks[l - 1],
                 g_u.shape[-1], 2 * rl)
        e_new[l], pu[l - 1] = _inner_apply_jit(g_u, stack_u, rp, nn)
        if sym:
            f_new[l], pv[l - 1] = e_new[l], pu[l - 1]
        else:
            f_new[l], pv[l - 1] = _inner_apply_jit(g_v, stack_v, rp, nn)

    new_data = _pack_data_jit(shape, data, u_leaf, v_leaf, tuple(e_new),
                              tuple(f_new), tuple(pu), tuple(pv))
    new_ranks = tuple(int(p.shape[1]) for p in pu)
    new_shape = dataclasses.replace(shape, ranks=new_ranks)
    return new_shape, new_data


def pick_ranks_by_tol(shape: H2Shape, data: H2Data, ru: List[jax.Array],
                      rv: List[jax.Array], tol: float,
                      backend: str = "jnp") -> Tuple[int, ...]:
    """Two-sweep reference: probe the truncation upsweep for ranks only.

    Retained as the baseline the fused single-sweep path is validated
    against (the rank-pick property test) and benchmarked from — it re-runs
    every upsweep SVD that ``truncate`` then repeats, which is exactly the
    duplicated work ``truncate_by_tol`` eliminates.

    The scale is the largest singular value seen at the leaf level (a proxy
    for the norm of the low-rank part, making ``tol`` a relative threshold).
    """
    depth = shape.depth
    # leaf sigmas from both trees
    _, s_u = truncation_leaf_factors(ru[depth], backend)
    _, s_v = truncation_leaf_factors(rv[depth], backend)
    scale = float(jnp.maximum(s_u.max(), s_v.max()))
    thresh = tol * scale

    ranks = [0] * (depth + 1)

    def count(s):
        return int(jnp.maximum((s > thresh).sum(axis=-1).max(), 1))

    ranks[depth] = max(count(s_u), count(s_v))

    # probe the upsweep eagerly with per-level picked ranks
    def sweep_probe(leaf, transfers, r):
        picked = [0] * (depth + 1)
        w, s = truncation_leaf_factors(r[depth], backend)
        picked[depth] = count(s)
        rq = ranks[depth]
        p = jnp.swapaxes(w[..., :rq], -1, -2)
        for l in range(depth, 0, -1):
            stack, g, s = truncation_inner_factors(p, transfers[l],
                                                   r[l - 1], backend)
            rl = stack.shape[1] // 2
            picked[l - 1] = min(count(s), 2 * rl)
            gk = g[..., :picked[l - 1]]
            p = truncation_project(gk, stack)
        return picked

    pu = sweep_probe(data.u_leaf, data.e, ru)
    pv = pu if (shape.symmetric and data.v_leaf is data.u_leaf) else \
        sweep_probe(data.v_leaf, data.f, rv)
    out = [max(a, b) for a, b in zip(pu, pv)]
    out[depth] = ranks[depth]
    # never exceed current ranks
    return tuple(min(o, k) for o, k in zip(out, shape.ranks))


# ---------------------------------------------------------------------------
# fused pipelines
# ---------------------------------------------------------------------------

def _restore_maxb(new: H2Shape, old: H2Shape) -> H2Shape:
    """Carry the marshaling statics through when data has no plan."""
    if new.row_maxb is None:
        new = dataclasses.replace(new, row_maxb=old.row_maxb,
                                  col_maxb=old.col_maxb,
                                  dense_maxb=old.dense_maxb)
    return new


def _orthogonalized(shape: H2Shape, data: H2Data, backend: str,
                    aliased: bool) -> Tuple[H2Shape, H2Data]:
    """Orthogonalize and carry the refreshed static shape.

    ``aliased`` is the pre-trace symmetry decision (see
    ``orthogonalize._orthogonalize_impl``); when set, the post-jit alias is
    restored so downstream ``is`` checks keep seeing one tree.
    """
    from .orthogonalize import _orthogonalize_impl, _orthogonalize_jit
    inside_trace = isinstance(data.u_leaf, jax.core.Tracer)
    if inside_trace:
        data = _orthogonalize_impl(shape, data, backend, aliased)
    else:
        data = _orthogonalize_jit(shape, data, backend, aliased)
    if aliased:
        # jit boundaries return distinct (equal-valued) buffers for the
        # two trees; restore the alias so the upsweep factors V only once
        data = dataclasses.replace(data, v_leaf=data.u_leaf, f=data.e)
    shape = _restore_maxb(
        shape_of(data, shape.leaf_size, shape.symmetric), shape)
    return shape, data


@functools.partial(jax.jit, static_argnames=("shape", "backend", "aliased"))
def _orthogonalize_weights(shape: H2Shape, data: H2Data, backend: str,
                           aliased: bool):
    """Stage A of the fused tol path: orthogonalize + weights, one program."""
    TRACE_COUNTS["orthogonalize_weights"] += 1
    shape, data = _orthogonalized(shape, data, backend, aliased)
    ru, rv = compression_weights(shape, data, backend, aliased=aliased)
    return data, ru, rv


@functools.partial(jax.jit, static_argnames=("shape", "target_ranks",
                                             "backend", "assume_orthogonal",
                                             "aliased"))
def _compress_fixed(shape: H2Shape, data: H2Data,
                    target_ranks: Tuple[int, ...], backend: str,
                    assume_orthogonal: bool, aliased: bool) -> H2Data:
    """The whole fixed-rank recompression as ONE jitted program.

    ``orthogonalize -> compression_weights -> truncate -> project`` all
    trace into a single jaxpr — one dispatch from Python per (structure,
    target_ranks) pair, no host round-trips in between.
    """
    TRACE_COUNTS["compress_fixed"] += 1
    if not assume_orthogonal:
        shape, data = _orthogonalized(shape, data, backend, aliased)
    elif aliased:
        # pytree flattening handed the two trees distinct tracers; re-alias
        # so truncate's `is` fast path factors the symmetric tree once
        data = dataclasses.replace(data, v_leaf=data.u_leaf, f=data.e)
    ru, rv = compression_weights(shape, data, backend, aliased=aliased)
    _, new_data = truncate(shape, data, ru, rv, target_ranks, backend)
    return new_data


def compress(shape: H2Shape, data: H2Data, tol: Optional[float] = None,
             target_ranks: Optional[Sequence[int]] = None,
             backend: str = "jnp", assume_orthogonal: bool = False,
             legacy_two_sweep: bool = False) -> Tuple[H2Shape, H2Data]:
    """Full recompression: orthogonalize -> weights -> truncate -> project.

    ``target_ranks`` dispatches the single jitted program;
    ``tol`` runs the single-sweep host-in-the-loop rank picking (SVDs once).
    ``legacy_two_sweep=True`` forces the retired probe-then-truncate tol
    path, kept byte-for-byte on the pre-fusion schedule (separately
    dispatched orthogonalize, eager weights/probe/truncate, no symmetry
    aliasing) — it is the reference of the rank-pick property test and the
    baseline of the compression benchmark.
    """
    aliased = bool(shape.symmetric and data.v_leaf is data.u_leaf)
    if target_ranks is not None:
        new_data = _compress_fixed(shape, data, tuple(int(t) for t in
                                                      target_ranks),
                                   backend, assume_orthogonal, aliased)
        new_shape = _restore_maxb(
            shape_of(new_data, shape.leaf_size, shape.symmetric), shape)
        return new_shape, new_data
    if tol is None:
        raise ValueError("need tol or target_ranks")
    if legacy_two_sweep:
        if not assume_orthogonal:
            shape, data = _orthogonalized(shape, data, backend,
                                          aliased=False)
        ru, rv = compression_weights(shape, data, backend)
        picked = pick_ranks_by_tol(shape, data, ru, rv, tol, backend)
        return truncate(shape, data, ru, rv, picked, backend)
    if not assume_orthogonal:
        data, ru, rv = _orthogonalize_weights(shape, data, backend, aliased)
        if aliased:
            data = dataclasses.replace(data, v_leaf=data.u_leaf, f=data.e)
        shape = _restore_maxb(
            shape_of(data, shape.leaf_size, shape.symmetric), shape)
    else:
        ru, rv = compression_weights(shape, data, backend, aliased=aliased)
    return truncate_by_tol(shape, data, ru, rv, tol, backend)
