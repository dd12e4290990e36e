"""HaloPlan: compressed, overlap-schedulable halo exchange (paper §4.1/§4.2).

The broadcast halo (``dist._halo_exchange``) ships every device's *entire*
level ``2*rad`` times per level.  The paper instead exchanges **compressed
send/recv node lists**: each device ships only the nodes that remote
coupling rows actually reference.  This module is the plan-driven analogue
for the ``shard_map`` SPMD setting, built entirely on the host at
``partition_h2`` time:

- **Send lists** — for every nonzero device offset ``delta`` appearing in a
  level's block list, sender ``q`` owes device ``q - delta`` exactly the
  nodes of ``q`` that show up as block *columns* on ``q - delta``.  SPMD
  needs uniform shapes, so the per-device lists are padded to the global
  per-offset cap and stored as one block-row-sharded int32 array per
  offset: inside ``shard_map`` each device gathers its own ``[cap]`` slice,
  packs ``x[send]`` and ships it with ONE ``lax.ppermute`` per offset.
- **Landed-buffer layout** — a device's halo buffer is
  ``concat([own x (nloc), recv(delta_0) (cap_0), recv(delta_1), ...])``
  with static per-offset bases, so every remote column has a host-computable
  position in it.  Three gather maps are precomputed against this layout:
  ``diag_*`` (own-column slots -> local node), ``off_*`` (remote-column
  slots -> buffer position) over the padded ``nloc x maxb`` slot layouts,
  and ``blk_idx`` (block-slab order -> buffer position) for passes that
  walk the raw block list (the orthogonalization R exchange and the
  compression projection-map exchange reuse the SAME plan: the node set a
  remote device references is identical for xhat rows, R factors, and
  projection maps).
- **Diag/off split** — the marshaled value buffers are split into an
  own-column twin and a remote-column twin so the diagonal GEMMs depend
  only on local data: the matvec issues every packed exchange first,
  computes all diagonal (and dense-diagonal) GEMMs while the permutes are
  in flight, and only then touches the landed buffers — the paper's §4.2
  communication/computation overlap, expressed so XLA's async collectives
  can hide the transfer.  The diagonal twin keeps the padded ``nloc x
  maxb_d`` slot layout (interior rows are the bulk — one gather + one
  batched GEMM, same shape family as the combined buffer).  The
  off-diagonal twin is **row-compressed**: off-diagonal blocks only exist
  in boundary rows, so its ``maxb_o`` slot layout spans just the
  ``n_bnd_cap`` boundary rows of each device (``bnd_rows``), and the
  correction folds back scatter-free through a precomputed output
  permutation (``rowpos``): ``yhat = take(concat([diag, diag[bnd] +
  off]), rowpos)``.

- **Fused transport** — all levels' payloads for a given offset are
  flattened and concatenated, so the whole matvec ships ONE ``ppermute``
  round-trip per neighbor distance regardless of tree depth.  For the
  *bare* matvec this per-offset form is volume-optimal and kept.  Inside
  the solver iteration, where stencil + V-cycle compute hides transfer
  latency, collective COUNT dominates wall-clock: measured inside a
  ``fori_loop`` on the 8-fake-device CPU mesh one ``ppermute`` costs
  ~35-40 µs nearly independent of payload size while one ``all_to_all``
  replacing ANY number of per-offset rounds costs ~the same as a single
  ``all_gather`` (~56 µs).  The solve therefore lowers the same
  per-offset payloads into ONE ``all_to_all`` round via a residue-class
  row layout (``dist._hp_pack_exchange(merged=True)``), and the
  grid<->tree transpositions ride the same transport through
  :func:`build_transpose_plan` (DESIGN.md §12).  (An earlier note here
  claimed the ``all_to_all`` variant strictly slower — that was measured
  per-dispatch, outside the solver loop, where the ~300 µs dispatch
  overhead swamps the collective count.)

Volume per level drops from ``2*rad*nloc`` rows to ``sum(caps)`` rows
(``caps[delta] <= nloc`` always; far less once devices own many nodes).
"""
from __future__ import annotations

import dataclasses
from typing import List, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.obs.trace import phase


@jax.tree_util.register_pytree_node_class
@dataclasses.dataclass
class HaloPlan:
    """Runtime gather maps of one level's compressed exchange (int32).

    Shapes below are per device; the stored arrays carry a ``P*`` leading
    factor and are sharded over block rows (see ``dist.dist_specs``).

    send[j]:  [cap_j]           local rows to pack for offset ``offsets[j]``
    comb_idx: [nloc*maxb]       combined slot -> landed-halo-buffer position
                                (the ``fused`` schedule's plan column)
    diag_blk: [nloc*maxb_d]     slot -> local slab block (sentinel = nbmax)
    diag_col: [nloc*maxb_d]     slot -> local source node
    bnd_rows: [n_bnd_cap]       boundary rows (rows owning off blocks;
                                padding repeats 0 — harmless, never merged)
    rowpos:   [nloc]            output merge map: interior row r -> r,
                                boundary row r -> nloc + its bnd rank
    off_blk:  [n_bnd_cap*maxb_o] slot -> local slab block (sentinel = nbmax)
    off_idx:  [n_bnd_cap*maxb_o] slot -> landed-halo-buffer position
    blk_idx:  [nbmax]           slab block -> buffer position of its column
    """

    send: List[jax.Array]
    comb_idx: jax.Array
    diag_blk: jax.Array
    diag_col: jax.Array
    bnd_rows: jax.Array
    rowpos: jax.Array
    off_blk: jax.Array
    off_idx: jax.Array
    blk_idx: jax.Array

    def tree_flatten(self):
        return ((tuple(self.send), self.comb_idx, self.diag_blk,
                 self.diag_col, self.bnd_rows, self.rowpos, self.off_blk,
                 self.off_idx, self.blk_idx), None)

    @classmethod
    def tree_unflatten(cls, aux, ch):
        send, ci, db, dc, br, rp, ob, oi, bi = ch
        return cls(list(send), ci, db, dc, br, rp, ob, oi, bi)


@dataclasses.dataclass(frozen=True)
class LevelPartition:
    """Host-side result of partitioning one level's block list over P
    devices: the conflict-free slab, the combined marshaled layout (legacy
    broadcast/allgather modes), and the compressed halo plan with its
    diag/off marshaled twins."""

    # slab layout (block-list order per device, padded to nbmax)
    sv: np.ndarray          # [p*nbmax, k1, k2]
    sr: np.ndarray          # [p*nbmax] local row
    sc: np.ndarray          # [p*nbmax] GLOBAL col
    nbmax: int
    rad: int                # broadcast halo radius (legacy modes)
    # combined marshaled layout (allgather / broadcast-ppermute modes)
    pb: np.ndarray          # [p*nloc*maxb] slot -> slab block (sentinel nbmax)
    pc: np.ndarray          # [p*nloc*maxb] slot -> GLOBAL col
    sv_mar: np.ndarray      # [p*nloc, k1, maxb*k2]
    # compressed halo plan
    offsets: Tuple[int, ...]
    caps: Tuple[int, ...]
    send: List[np.ndarray]  # per offset: [p*cap] local rows to pack
    comb_idx: np.ndarray
    diag_blk: np.ndarray
    diag_col: np.ndarray
    bnd_rows: np.ndarray
    rowpos: np.ndarray
    off_blk: np.ndarray
    off_idx: np.ndarray
    blk_idx: np.ndarray
    sv_mar_diag: np.ndarray  # [p*nloc, k1, maxb_d*k2]
    sv_mar_off: np.ndarray   # [p*n_bnd_cap, k1, maxb_o*k2]

    def plan(self) -> HaloPlan:
        """The plan's gather maps, host arrays (placed with the rest of
        the partitioned operator)."""
        return HaloPlan(send=list(self.send), comb_idx=self.comb_idx,
                        diag_blk=self.diag_blk, diag_col=self.diag_col,
                        bnd_rows=self.bnd_rows, rowpos=self.rowpos,
                        off_blk=self.off_blk, off_idx=self.off_idx,
                        blk_idx=self.blk_idx)


def build_send_lists(rows: np.ndarray, cols: np.ndarray, p: int, shift: int
                     ) -> Tuple[Tuple[int, ...], Tuple[int, ...],
                                List[np.ndarray], dict]:
    """Compressed send lists of one level.

    Returns ``(offsets, caps, send, colpos)``: the sorted nonzero device
    offsets present in the block list, the per-offset packed-row caps
    (global max over senders), the padded per-device send arrays
    ``[p*cap]`` (local rows sender ``q`` packs for receiver ``q - delta``),
    and ``colpos`` mapping block index -> position of its column in the
    receiver's landed buffer ``[own (nloc) | recv(offsets[0]) | ...]``.
    """
    nloc = 1 << shift
    rows = np.asarray(rows, np.int64)
    cols = np.asarray(cols, np.int64)
    owner = rows >> shift
    col_owner = cols >> shift
    dvec = col_owner - owner
    offsets = tuple(int(d) for d in np.unique(dvec) if d != 0)
    send: List[np.ndarray] = []
    caps: List[int] = []
    # per (offset, sender) sorted unique local node lists
    lists = {}
    for d in offsets:
        cap = 1
        for q in range(p):
            loc = np.unique(cols[(col_owner == q) & (dvec == d)]) - q * nloc
            lists[(d, q)] = loc
            cap = max(cap, loc.shape[0])
        caps.append(cap)
        arr = np.zeros(p * cap, np.int32)
        for q in range(p):
            loc = lists[(d, q)]
            arr[q * cap:q * cap + loc.shape[0]] = loc
        send.append(arr)
    base = {}
    off = nloc
    for d, cap in zip(offsets, caps):
        base[d] = off
        off += cap
    colpos = np.empty(rows.shape[0], np.int64)
    for b in range(rows.shape[0]):
        d = int(dvec[b])
        if d == 0:
            colpos[b] = int(cols[b]) - int(owner[b]) * nloc
        else:
            q = int(col_owner[b])
            loc = lists[(d, q)]
            colpos[b] = base[d] + int(
                np.searchsorted(loc, int(cols[b]) - q * nloc))
    return tuple(offsets), tuple(caps), send, colpos


def partition_level(rows: np.ndarray, cols: np.ndarray, vals: np.ndarray,
                    p: int, shift: int) -> LevelPartition:
    """Partition one level's (row-sorted) block list into the per-device
    slab + combined marshaled layout + compressed halo plan (host/numpy)."""
    rows = np.asarray(rows, np.int64)
    cols = np.asarray(cols, np.int64)
    vals = np.asarray(vals)
    nloc = 1 << shift
    n_rows_g = p * nloc
    owner = rows >> shift
    col_owner = cols >> shift
    dvec = col_owner - owner
    k1 = vals.shape[-2] if vals.ndim == 3 else 1
    k2 = vals.shape[-1] if vals.ndim == 3 else 1
    dt = vals.dtype if vals.size else np.float32

    counts = np.bincount(owner, minlength=p) if rows.size else \
        np.zeros(p, np.int64)
    nbmax = max(int(counts.max()) if counts.size else 0, 1)
    nrow = np.bincount(rows, minlength=n_rows_g) if rows.size else \
        np.zeros(n_rows_g, np.int64)
    maxb = max(int(nrow.max()) if rows.size else 0, 1)
    is_off = dvec != 0
    nrow_d = np.bincount(rows[~is_off], minlength=n_rows_g) if rows.size \
        else np.zeros(n_rows_g, np.int64)
    nrow_o = np.bincount(rows[is_off], minlength=n_rows_g) if rows.size \
        else np.zeros(n_rows_g, np.int64)
    maxb_d = max(int(nrow_d.max()) if rows.size else 0, 1)
    maxb_o = int(nrow_o.max()) if rows.size else 0
    # boundary rows (rows owning >= 1 off block), padded to the global cap
    bnd_mask = (nrow_o > 0).reshape(p, nloc)
    n_bnd_cap = int(bnd_mask.sum(axis=1).max()) if rows.size else 0

    offsets, caps, send, colpos = build_send_lists(rows, cols, p, shift)

    sv = np.zeros((p * nbmax, k1, k2), dt)
    sr = np.zeros(p * nbmax, np.int32)
    sc = np.zeros(p * nbmax, np.int32)
    pb = np.full(n_rows_g * maxb, nbmax, np.int32)      # nbmax = pad sentinel
    pc = np.zeros(n_rows_g * maxb, np.int32)
    comb_idx = np.zeros(n_rows_g * maxb, np.int32)
    sv_mar = np.zeros((n_rows_g, maxb, k1, k2), dt)
    diag_blk = np.full(n_rows_g * maxb_d, nbmax, np.int32)
    diag_col = np.zeros(n_rows_g * maxb_d, np.int32)
    bnd_rows = np.zeros(p * n_bnd_cap, np.int32)
    rowpos = np.tile(np.arange(nloc, dtype=np.int32), p)
    off_blk = np.full(p * n_bnd_cap * maxb_o, nbmax, np.int32)
    off_idx = np.zeros(p * n_bnd_cap * maxb_o, np.int32)
    blk_idx = np.zeros(p * nbmax, np.int32)
    sv_mar_diag = np.zeros((n_rows_g, maxb_d, k1, k2), dt)
    sv_mar_off = np.zeros((p * n_bnd_cap, maxb_o, k1, k2), dt)
    # per-row boundary rank (within its device); interior rows get -1
    bnd_rank = np.full(n_rows_g, -1, np.int64)
    for d in range(p):
        loc = np.nonzero(bnd_mask[d])[0]
        bnd_rows[d * n_bnd_cap:d * n_bnd_cap + loc.shape[0]] = loc
        bnd_rank[d * nloc + loc] = np.arange(loc.shape[0])
        rowpos[d * nloc + loc] = nloc + np.arange(loc.shape[0])
    # default cols to the owner's first node (no spurious halo traffic)
    for d in range(p):
        sc[d * nbmax:(d + 1) * nbmax] = d * nloc
        pc[d * nloc * maxb:(d + 1) * nloc * maxb] = d * nloc

    fill = np.zeros(p, np.int64)
    rowfill = np.zeros(n_rows_g, np.int64)
    rowfill_d = np.zeros(n_rows_g, np.int64)
    rowfill_o = np.zeros(n_rows_g, np.int64)
    for b in range(rows.shape[0]):
        d = int(owner[b])
        slot = d * nbmax + int(fill[d])
        sv[slot] = vals[b]
        sr[slot] = int(rows[b]) - d * nloc
        sc[slot] = int(cols[b])
        blk_idx[slot] = int(colpos[b])
        r_g = int(rows[b])
        j = int(rowfill[r_g])
        pb[r_g * maxb + j] = int(fill[d])
        pc[r_g * maxb + j] = int(cols[b])
        comb_idx[r_g * maxb + j] = int(colpos[b])
        sv_mar[r_g, j] = vals[b]
        rowfill[r_g] += 1
        if is_off[b]:
            rb = d * n_bnd_cap + int(bnd_rank[r_g])
            j = int(rowfill_o[r_g])
            off_blk[rb * maxb_o + j] = int(fill[d])
            off_idx[rb * maxb_o + j] = int(colpos[b])
            sv_mar_off[rb, j] = vals[b]
            rowfill_o[r_g] += 1
        else:
            j = int(rowfill_d[r_g])
            diag_blk[r_g * maxb_d + j] = int(fill[d])
            diag_col[r_g * maxb_d + j] = int(colpos[b])
            sv_mar_diag[r_g, j] = vals[b]
            rowfill_d[r_g] += 1
        fill[d] += 1

    rad = int(np.abs(dvec).max()) if rows.size else 0
    return LevelPartition(
        sv=sv, sr=sr, sc=sc, nbmax=nbmax, rad=rad,
        pb=pb, pc=pc,
        sv_mar=np.moveaxis(sv_mar, 1, 2).reshape(n_rows_g, k1, maxb * k2),
        offsets=offsets, caps=caps, send=send, comb_idx=comb_idx,
        diag_blk=diag_blk, diag_col=diag_col,
        bnd_rows=bnd_rows, rowpos=rowpos,
        off_blk=off_blk, off_idx=off_idx, blk_idx=blk_idx,
        sv_mar_diag=np.moveaxis(sv_mar_diag, 1, 2
                                ).reshape(n_rows_g, k1, maxb_d * k2),
        sv_mar_off=np.moveaxis(sv_mar_off, 1, 2
                               ).reshape(p * n_bnd_cap, k1, maxb_o * k2))


# ---------------------------------------------------------------------------
# device-side exchange (inside shard_map)
# ---------------------------------------------------------------------------

def start_halo(x: jax.Array, plan: HaloPlan, offsets: Sequence[int], axis,
               p: int, bf16: bool = False) -> List[jax.Array]:
    """Issue one level's packed exchanges; returns the in-flight chunks.

    One gather + one ``ppermute`` per neighbor offset, shipping only the
    ``cap`` planned rows.  ``bf16`` halves the payload (serving-accuracy
    mode); the barrier stops XLA from hoisting the convert past the
    permute.  The matvec's exchange (``dist._coupling_phase_overlap``)
    speaks the same wire protocol but fuses all levels' payloads per
    offset before the permute — keep the two in sync.
    """
    chunks = []
    for delta, idx in zip(offsets, plan.send):
        with phase("halo/pack"):
            packed = jnp.take(x, idx, axis=0)
            if bf16:
                packed = jax.lax.optimization_barrier(
                    packed.astype(jnp.bfloat16))
        perm = [(src, (src - delta) % p) for src in range(p)]
        with phase("halo/round"):
            chunks.append(jax.lax.ppermute(packed, axis, perm))
    return chunks


def land_halo(x: jax.Array, chunks: Sequence[jax.Array]) -> jax.Array:
    """Concatenate own rows + landed chunks into the plan's buffer layout."""
    if not chunks:
        return x
    with phase("halo/land"):
        return jnp.concatenate([x] + [c.astype(x.dtype) for c in chunks],
                               axis=0)


def exchange(x: jax.Array, plan: HaloPlan, offsets: Sequence[int], axis,
             p: int, bf16: bool = False) -> jax.Array:
    """start + land in one go (no compute to overlap: R-factor /
    projection-map exchanges in the compression sweeps)."""
    return land_halo(x, start_halo(x, plan, offsets, axis, p, bf16))


# ---------------------------------------------------------------------------
# generic cross-device permutation as ONE all_to_all (the solver's fused
# grid<->tree transposition rounds; DESIGN.md §12)
# ---------------------------------------------------------------------------

def build_transpose_plan(g: np.ndarray, p: int):
    """Host-side send/recv plan realizing the sharded gather
    ``y[i] = x[g[i]]`` (both ``x`` and ``y`` in contiguous ``n/p`` row
    strips) as ONE ``all_to_all`` instead of ``all_gather`` + take.

    Same compression idea as :func:`build_send_lists`: sender ``s`` owes
    receiver ``r`` only the *unique* local rows of ``s`` that ``r``'s
    ``g``-slice references, padded to the global per-pair cap so SPMD
    shapes stay uniform.  Returns ``(cap, send_idx, take_idx)``:

    ``cap``       static per-(sender, receiver) row cap (>= 1)
    ``send_idx``  [p*p, cap] int32, sharded over senders: device ``s``'s
                  local ``[p, cap]`` slice holds, per receiver ``r``, the
                  sorted local rows to pack into its lane (padding
                  repeats row 0 — harmless, never landed-read)
    ``take_idx``  [p * (n//p)] int32, sharded over receivers: positions
                  into the landed ``[p, cap]`` buffer (flattened) whose
                  row ``s`` is the lane received from sender ``s``.
    """
    g = np.asarray(g, np.int64)
    n = g.shape[0]
    if n % p:
        raise ValueError(f"transpose plan needs p | n ({n} % {p})")
    nloc = n // p
    send: dict = {}
    cap = 1
    for r in range(p):
        need = g[r * nloc:(r + 1) * nloc]
        for s in range(p):
            rows = np.unique(need[(need // nloc) == s]) - s * nloc
            send[(s, r)] = rows
            cap = max(cap, len(rows))
    send_idx = np.zeros((p * p, cap), np.int32)
    for (s, r), rows in send.items():
        send_idx[s * p + r, :len(rows)] = rows
    take_idx = np.empty(n, np.int32)
    for r in range(p):
        need = g[r * nloc:(r + 1) * nloc]
        for i, gi in enumerate(need):
            s = int(gi) // nloc
            pos = int(np.searchsorted(send[(s, r)], int(gi) - s * nloc))
            take_idx[r * nloc + i] = s * cap + pos
    return cap, send_idx, take_idx


def transpose_a2a(x: jax.Array, send_idx: jax.Array, take_idx: jax.Array,
                  axis, extra=None):
    """Apply a :func:`build_transpose_plan` permutation inside shard_map.

    ``x``: the device's [nloc] strip; ``send_idx``/``take_idx``: the
    device's local plan slices ([p, cap] / [nloc]).  ``extra`` optionally
    appends per-receiver side-channel rows ``[p, e]`` onto the payload
    lanes (the C-stencil row halo rides the solve's transpose-in round);
    returns ``(y, extra_landed)`` where ``extra_landed[s]`` is the extra
    row sender ``s`` addressed to this device (``None`` without
    ``extra``).
    """
    p, cap = send_idx.shape
    with phase("halo/pack"):
        buf = jnp.take(x, send_idx.reshape(-1), axis=0).reshape(p, cap)
        if extra is not None:
            buf = jnp.concatenate([buf, extra.astype(buf.dtype)], axis=1)
    with phase("halo/round"):
        land = jax.lax.all_to_all(buf, axis, split_axis=0, concat_axis=0,
                                  tiled=False)
        land = land.reshape(p, buf.shape[1])
    with phase("halo/land"):
        y = jnp.take(land[:, :cap].reshape(p * cap), take_idx, axis=0)
    ex = land[:, cap:] if extra is not None else None
    return y, ex
