"""Distributed H^2 operations via shard_map (paper §2.2–§5).

Decomposition (paper Fig. 4): every tree level is a block-sparse matrix,
decomposed into **block rows**; device ``p`` owns a contiguous branch of the
cluster tree below the C-level ``lc = log2(P)``.  Deviation from the paper
(documented in DESIGN.md): instead of a *master GPU* owning the top levels we
**replicate** the (tiny) top tree on all devices — branch roots are
``all_gather``-ed at the C-level and every device redundantly computes the top
sweeps.  This removes the root-GPU serialization the paper identifies as its
1024-GPU bottleneck.

Communication modes for the off-diagonal coupling phase (paper §4.1):
  - ``allgather``: gather the whole level (baseline, maximal volume)
  - ``ppermute``: broadcast halo exchange via ``lax.ppermute`` — ships each
    device's *entire* level ``2*rad`` times (rad is the static device-distance
    radius derived from the block structure).  Kept as the mid baseline.
  - ``halo-plan`` (default): the compressed-plan exchange (``core/halo.py``,
    DESIGN.md §3) — per-level send-row gather lists + recv-slot maps built at
    ``partition_h2`` time ship only the nodes remote coupling rows actually
    reference, one packed ``ppermute`` per neighbor offset.  The marshaled
    coupling buffers are split into diagonal / off-diagonal twins so the
    matvec issues every packed exchange up front, computes all diagonal GEMMs
    plus the dense diagonal block while the halos are in flight, and finishes
    the off-diagonal GEMMs from the landed buffers — the paper's §4.2
    communication/computation overlap.  ``-bf16`` suffixes halve the payload.

The same plans drive the R-factor exchange in ``dist_orthogonalize_local``
and the projection-map exchange in ``dist_compress_local`` (the node set a
remote device references is identical for xhat rows, R factors, and
projection maps).
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.obs.trace import phase

from . import halo as _halo
from .halo import HaloPlan, partition_level
from .structure import H2Data, H2Shape, build_slot_plan, marshal_blocks


# ---------------------------------------------------------------------------
# static distributed shape
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class DistH2Shape:
    """Static description of a block-row-partitioned H^2 matrix."""
    n: int
    leaf_size: int
    depth: int
    ranks: Tuple[int, ...]
    p: int                                # number of block rows (devices)
    lc: int                               # C-level = log2(p)
    # branch levels lc..depth: per-device padded block count and halo radius
    br_counts: Tuple[int, ...]            # indexed l-lc
    br_radius: Tuple[int, ...]            # device-distance halo radius
    # top levels 0..lc-1: replicated global block counts
    top_counts: Tuple[int, ...]
    dense_count: int                      # per-device padded dense blocks
    dense_radius: int
    row_maxb: Tuple[int, ...]             # max blocks/row (global levels 0..depth)
    symmetric: bool = True
    dense_maxb: int = 1                   # max dense blocks per leaf row
    # compressed halo plan statics (core/halo.py): per branch level, the
    # sorted nonzero device offsets present in the block list and the packed
    # send-row caps per offset (global max over senders) — these size the
    # one-ppermute-per-offset exchange and the comm model
    br_offsets: Tuple[Tuple[int, ...], ...] = ()
    br_caps: Tuple[Tuple[int, ...], ...] = ()
    dense_offsets: Tuple[int, ...] = ()
    dense_caps: Tuple[int, ...] = ()

    @property
    def leaves_per_dev(self) -> int:
        return (1 << self.depth) // self.p

    def nodes_local(self, l: int) -> int:
        return (1 << l) // self.p if l >= self.lc else (1 << l)

    def n_local(self) -> int:
        return self.n // self.p


@jax.tree_util.register_pytree_node_class
@dataclasses.dataclass
class DistH2Data:
    """Runtime arrays; leading axis of *_br arrays is sharded over block rows.

    Branch lists are indexed ``l - lc``; top lists are indexed ``l``.

    The per-device marshaling plan (DESIGN.md §3.5) mirrors the
    single-device one: ``pb_blk``/``pb_col`` are the branch levels'
    ``slot -> local slab block`` / ``slot -> GLOBAL source node`` arrays
    over the local ``nloc x maxb`` slot layout, ``s_br_mar`` the
    row-marshaled block values ``[P*nloc, k, maxb*k]`` (zero padding), so
    every device's coupling phase is one gather + one batched GEMM —
    no segment-sum inside ``shard_map``.  Top levels and dense leaves get
    the same treatment (replicated / sharded respectively).

    The compressed halo plan (``hp_br``/``hp_dense``, core/halo.py) splits
    each level's marshaled buffer into a diagonal (own-column) twin
    ``s_br_mar_diag`` and an off-diagonal twin ``s_br_mar_off`` whose slot
    columns index the landed packed-exchange buffer — the layout behind the
    ``halo-plan`` overlap schedule.  Only the branch levels and the dense
    leaves carry plans; top levels are replicated and never communicate.
    """
    u_leaf: jax.Array                     # [P*nl_loc, m, k]
    v_leaf: jax.Array
    e_br: List[jax.Array]                 # l=lc..depth; e_br[0] is empty
    f_br: List[jax.Array]
    s_br: List[jax.Array]                 # [P*nbmax_l, k, k]
    s_br_rows: List[jax.Array]            # local row node index  [P*nbmax_l]
    s_br_cols: List[jax.Array]            # GLOBAL col node index [P*nbmax_l]
    e_top: List[jax.Array]                # l=0..lc (replicated); e_top[0] empty
    f_top: List[jax.Array]
    s_top: List[jax.Array]                # l=0..lc-1 (replicated)
    s_top_rows: List[jax.Array]
    s_top_cols: List[jax.Array]
    dense: jax.Array                      # [P*nbd_max, m, m]
    d_rows: jax.Array
    d_cols: jax.Array
    # marshaling plan + marshaled value buffers
    pb_blk: List[jax.Array]               # [P*nloc_l*maxb_l] int32 (nbmax = pad)
    pb_col: List[jax.Array]               # [P*nloc_l*maxb_l] int32 global col
    s_br_mar: List[jax.Array]             # [P*nloc_l, k, maxb_l*k]
    pt_blk: List[jax.Array]               # l=0..lc-1 (replicated)
    pt_col: List[jax.Array]
    s_top_mar: List[jax.Array]            # [2**l, k, maxb_l*k]
    pd_col: jax.Array                     # [P*nl_loc*dmaxb] int32 global col
    dense_mar: jax.Array                  # [P*nl_loc, m, dmaxb*m]
    # compressed halo plans + diag/off marshaled twins (core/halo.py)
    hp_br: List[HaloPlan]                 # l=lc..depth
    hp_dense: HaloPlan
    s_br_mar_diag: List[jax.Array]        # [P*nloc_l, k, maxb_d_l*k]
    s_br_mar_off: List[jax.Array]         # [P*off_cap_l, k, k] (slab form)
    dense_mar_diag: jax.Array             # [P*nl_loc, m, dmaxb_d*m]
    dense_mar_off: jax.Array              # [P*doff_cap, m, m] (slab form)

    def tree_flatten(self):
        return ((self.u_leaf, self.v_leaf, tuple(self.e_br), tuple(self.f_br),
                 tuple(self.s_br), tuple(self.s_br_rows), tuple(self.s_br_cols),
                 tuple(self.e_top), tuple(self.f_top), tuple(self.s_top),
                 tuple(self.s_top_rows), tuple(self.s_top_cols),
                 self.dense, self.d_rows, self.d_cols,
                 tuple(self.pb_blk), tuple(self.pb_col), tuple(self.s_br_mar),
                 tuple(self.pt_blk), tuple(self.pt_col), tuple(self.s_top_mar),
                 self.pd_col, self.dense_mar,
                 tuple(self.hp_br), self.hp_dense,
                 tuple(self.s_br_mar_diag), tuple(self.s_br_mar_off),
                 self.dense_mar_diag, self.dense_mar_off), None)

    @classmethod
    def tree_unflatten(cls, aux, ch):
        (u, v, eb, fb, sb, sbr, sbc, et, ft, st, str_, stc, de, dr, dc,
         pbb, pbc, sbm, ptb, ptc, stm, pdc, dm,
         hpb, hpd, smd, smo, dmd, dmo) = ch
        return cls(u, v, list(eb), list(fb), list(sb), list(sbr), list(sbc),
                   list(et), list(ft), list(st), list(str_), list(stc),
                   de, dr, dc, list(pbb), list(pbc), list(sbm),
                   list(ptb), list(ptc), list(stm), pdc, dm,
                   list(hpb), hpd, list(smd), list(smo), dmd, dmo)


def dist_specs(dshape: DistH2Shape, axis) -> DistH2Data:
    """PartitionSpec pytree matching DistH2Data (axis: mesh axis name/tuple)."""
    sh = P(axis)          # sharded on leading dim
    rep = P()
    lc, depth = dshape.lc, dshape.depth
    nbr = depth - lc + 1

    def plan_spec(n_offsets: int) -> HaloPlan:
        return HaloPlan(send=[sh] * n_offsets, comb_idx=sh, diag_blk=sh,
                        diag_col=sh, bnd_rows=sh, rowpos=sh, off_blk=sh,
                        off_idx=sh, blk_idx=sh)

    return DistH2Data(
        u_leaf=sh, v_leaf=sh,
        e_br=[sh] * nbr, f_br=[sh] * nbr,
        s_br=[sh] * nbr, s_br_rows=[sh] * nbr, s_br_cols=[sh] * nbr,
        e_top=[rep] * (lc + 1), f_top=[rep] * (lc + 1),
        s_top=[rep] * lc, s_top_rows=[rep] * lc, s_top_cols=[rep] * lc,
        dense=sh, d_rows=sh, d_cols=sh,
        pb_blk=[sh] * nbr, pb_col=[sh] * nbr, s_br_mar=[sh] * nbr,
        pt_blk=[rep] * lc, pt_col=[rep] * lc, s_top_mar=[rep] * lc,
        pd_col=sh, dense_mar=sh,
        hp_br=[plan_spec(len(dshape.br_offsets[i])) for i in range(nbr)],
        hp_dense=plan_spec(len(dshape.dense_offsets)),
        s_br_mar_diag=[sh] * nbr, s_br_mar_off=[sh] * nbr,
        dense_mar_diag=sh, dense_mar_off=sh)


# ---------------------------------------------------------------------------
# host-side partitioning
# ---------------------------------------------------------------------------

def partition_h2(shape: H2Shape, data: H2Data, p: int
                 ) -> Tuple[DistH2Shape, DistH2Data]:
    """Reorganize a single-device H2Data into the block-row layout."""
    lc = int(np.log2(p))
    if (1 << lc) != p:
        raise ValueError("device count must be a power of two")
    if shape.depth < lc:
        raise ValueError(f"tree depth {shape.depth} < log2(P)={lc}")
    depth, m = shape.depth, shape.leaf_size

    e_br = [np.zeros((p, 0, 0), np.float32)]
    f_br = [np.zeros((p, 0, 0), np.float32)]
    for l in range(lc + 1, depth + 1):
        e_br.append(np.asarray(data.e[l]))
        f_br.append(np.asarray(data.f[l]))

    s_br, s_br_r, s_br_c, br_counts, br_rad = [], [], [], [], []
    pb_blk, pb_col, s_br_mar = [], [], []
    hp_br, s_br_mar_diag, s_br_mar_off = [], [], []
    br_offsets, br_caps = [], []
    for l in range(lc, depth + 1):
        lp = partition_level(np.asarray(data.s_rows[l]),
                             np.asarray(data.s_cols[l]),
                             np.asarray(data.s[l]), p, l - lc)
        s_br.append(lp.sv)
        s_br_r.append(lp.sr)
        s_br_c.append(lp.sc)
        br_counts.append(lp.nbmax)
        br_rad.append(lp.rad)
        pb_blk.append(lp.pb)
        pb_col.append(lp.pc)
        s_br_mar.append(lp.sv_mar)
        hp_br.append(lp.plan())
        s_br_mar_diag.append(lp.sv_mar_diag)
        s_br_mar_off.append(lp.sv_mar_off)
        br_offsets.append(lp.offsets)
        br_caps.append(lp.caps)

    # dense leaves: same treatment at the leaf level
    ld = partition_level(np.asarray(data.d_rows), np.asarray(data.d_cols),
                         np.asarray(data.dense), p, depth - lc)
    nbd, d_rad, dmaxb = ld.nbmax, ld.rad, ld.pc.shape[0] // (1 << depth)

    # replicated top levels: the global slot plan + marshaled blocks
    pt_blk, pt_col, s_top_mar = [], [], []
    for l in range(lc):
        b_, c_, _, _ = build_slot_plan(np.asarray(data.s_rows[l]),
                                       np.asarray(data.s_cols[l]), 1 << l)
        pt_blk.append(jnp.asarray(b_))
        pt_col.append(jnp.asarray(c_))
        s_top_mar.append(marshal_blocks(jnp.asarray(np.asarray(data.s[l])),
                                        jnp.asarray(b_), 1 << l))

    dshape = DistH2Shape(
        n=shape.n, leaf_size=m, depth=depth, ranks=shape.ranks, p=p, lc=lc,
        br_counts=tuple(br_counts), br_radius=tuple(br_rad),
        top_counts=tuple(shape.coupling_counts[:lc]),
        dense_count=nbd, dense_radius=d_rad,
        row_maxb=shape.row_maxb or tuple([0] * (depth + 1)),
        symmetric=shape.symmetric, dense_maxb=dmaxb,
        br_offsets=tuple(br_offsets), br_caps=tuple(br_caps),
        dense_offsets=ld.offsets, dense_caps=ld.caps)

    ddata = DistH2Data(
        u_leaf=jnp.asarray(np.asarray(data.u_leaf)),
        v_leaf=jnp.asarray(np.asarray(data.v_leaf)),
        e_br=[jnp.asarray(x) for x in e_br],
        f_br=[jnp.asarray(x) for x in f_br],
        s_br=[jnp.asarray(x) for x in s_br],
        s_br_rows=[jnp.asarray(x) for x in s_br_r],
        s_br_cols=[jnp.asarray(x) for x in s_br_c],
        e_top=[jnp.asarray(np.asarray(data.e[l])) if l > 0 else
               jnp.zeros((0, 0, 0)) for l in range(lc + 1)],
        f_top=[jnp.asarray(np.asarray(data.f[l])) if l > 0 else
               jnp.zeros((0, 0, 0)) for l in range(lc + 1)],
        s_top=[jnp.asarray(np.asarray(data.s[l])) for l in range(lc)],
        s_top_rows=[jnp.asarray(np.asarray(data.s_rows[l])) for l in range(lc)],
        s_top_cols=[jnp.asarray(np.asarray(data.s_cols[l])) for l in range(lc)],
        dense=jnp.asarray(ld.sv), d_rows=jnp.asarray(ld.sr),
        d_cols=jnp.asarray(ld.sc),
        pb_blk=[jnp.asarray(x) for x in pb_blk],
        pb_col=[jnp.asarray(x) for x in pb_col],
        s_br_mar=[jnp.asarray(x) for x in s_br_mar],
        pt_blk=pt_blk, pt_col=pt_col, s_top_mar=s_top_mar,
        pd_col=jnp.asarray(ld.pc),
        dense_mar=jnp.asarray(ld.sv_mar),
        hp_br=hp_br, hp_dense=ld.plan(),
        s_br_mar_diag=[jnp.asarray(x) for x in s_br_mar_diag],
        s_br_mar_off=[jnp.asarray(x) for x in s_br_mar_off],
        dense_mar_diag=jnp.asarray(ld.sv_mar_diag),
        dense_mar_off=jnp.asarray(ld.sv_mar_off))
    return dshape, ddata


# ---------------------------------------------------------------------------
# collectives
# ---------------------------------------------------------------------------

def _halo_exchange(x: jax.Array, axis, rad: int, p: int) -> jax.Array:
    """Return [(2*rad+1) * n_loc, ...]: neighbors' blocks, own block centered.

    chunk i (i = 0..2rad) holds the block of device ``p - rad + i``; realized
    with 2*rad ``ppermute`` shifts (the paper's neighbor-only exchange).
    """
    if rad == 0:
        return x
    chunks = []
    for i in range(2 * rad + 1):
        delta = i - rad                       # data of device p + delta
        if delta == 0:
            chunks.append(x)
        else:
            perm = [(src, (src - delta) % p) for src in range(p)]
            chunks.append(jax.lax.ppermute(x, axis, perm))
    return jnp.concatenate(chunks, axis=0)


# ---------------------------------------------------------------------------
# distributed matvec (inside shard_map)
# ---------------------------------------------------------------------------

def _local_upsweep(dshape: DistH2Shape, d: DistH2Data, x_leaves, axis):
    """Branch upsweep -> xhat dict for levels lc..depth, then replicated top."""
    depth, lc = dshape.depth, dshape.lc
    with phase("hgemv/upsweep"):
        xhat: Dict[int, jax.Array] = {}
        xhat[depth] = jnp.einsum("bmk,bmv->bkv", d.v_leaf, x_leaves,
                                 precision="highest")
        for l in range(depth, lc, -1):
            f = d.f_br[l - lc]
            contrib = jnp.einsum("ckp,ckv->cpv", f, xhat[l],
                                 precision="highest")
            nn = contrib.shape[0]
            xhat[l - 1] = contrib.reshape(nn // 2, 2,
                                          *contrib.shape[1:]).sum(1)
        # gather branch roots -> replicated level-lc vector tree
        root = xhat[lc]                          # [1, k, nv]
        with phase("hgemv/root-gather"):
            gathered = jax.lax.all_gather(root, axis, tiled=True)
        xhat_top: Dict[int, jax.Array] = {lc: gathered}  # [2**lc, k, nv]
        for l in range(lc, 0, -1):
            f = d.f_top[l]
            contrib = jnp.einsum("ckp,ckv->cpv", f, xhat_top[l],
                                 precision="highest")
            nn = contrib.shape[0]
            xhat_top[l - 1] = contrib.reshape(nn // 2, 2,
                                              *contrib.shape[1:]).sum(1)
    return xhat, xhat_top


def _coupling_phase(dshape: DistH2Shape, d: DistH2Data, xhat, xhat_top,
                    axis, comm: str, gathered: Optional[Dict] = None):
    """yhat at branch levels (local) + top levels (replicated).

    Single dispatch per level (DESIGN.md §3.5): the halo/allgather sources
    are gathered by the per-device slot plan into ``[nloc, maxb*k, nv]``
    and contracted against the row-marshaled blocks in one batched GEMM —
    the slot reduction rides the contraction, no scatter inside shard_map.

    ``gathered`` (allgather mode only) optionally supplies the already
    all_gather'ed full levels ``{l: [2**l, k, nv]}`` so the exchange can be
    cut into its own stage program (obs segmented replay).
    """
    depth, lc, p = dshape.depth, dshape.lc, dshape.p
    nv = xhat[depth].shape[-1]
    yhat: Dict[int, jax.Array] = {}
    yhat_top: Dict[int, jax.Array] = {}
    me = jax.lax.axis_index(axis)

    for l in range(lc, depth + 1):
        i = l - lc
        nloc = dshape.nodes_local(l)
        k = dshape.ranks[l]
        if k == 0:
            yhat[l] = jnp.zeros((nloc, k, nv), xhat[depth].dtype)
            continue
        s_mar = d.s_br_mar[i]                 # [nloc, k, maxb*k] per device
        maxb = s_mar.shape[-1] // k
        cols = d.pb_col[i]                    # [nloc*maxb] global col plan
        own_start = me * nloc
        if comm == "allgather" and p > 1:
            with phase("hgemv/exchange"):
                xg_full = gathered[l] if gathered is not None else \
                    jax.lax.all_gather(xhat[l], axis, tiled=True)
            xg = jnp.take(xg_full, cols, axis=0)
        else:
            rad = dshape.br_radius[i] if p > 1 else 0
            src = xhat[l]
            if comm == "ppermute-bf16":
                # beyond-paper: halo payloads in bf16 (2x less ICI traffic;
                # compute stays f32) — serving-accuracy mode.  The barrier
                # stops XLA from hoisting the convert past the permute
                # (which would send f32 and round afterwards).
                src = jax.lax.optimization_barrier(
                    src.astype(jnp.bfloat16))
            with phase("hgemv/exchange"):
                halo = _halo_exchange(src, axis, rad, p)
            idx = cols - own_start + rad * nloc
            xg = jnp.take(halo, idx, axis=0).astype(xhat[l].dtype)
        with phase("hgemv/coupling-gemm"):
            yhat[l] = jnp.einsum("nkj,njv->nkv", s_mar,
                                 xg.reshape(nloc, maxb * k, nv),
                                 precision="highest")

    with phase("hgemv/coupling-gemm"):
        _top_coupling(dshape, d, xhat_top, yhat_top, nv)
    return yhat, yhat_top


def _top_coupling(dshape: DistH2Shape, d: DistH2Data, xhat_top, yhat_top,
                  nv: int) -> None:
    """Replicated top-level coupling GEMMs (no communication)."""
    for l in range(dshape.lc):
        nn = 1 << l
        k = dshape.ranks[l]
        if dshape.top_counts[l] == 0 or k == 0:
            yhat_top[l] = jnp.zeros((nn, k, nv), xhat_top[dshape.lc].dtype)
            continue
        s_mar = d.s_top_mar[l]
        maxb = s_mar.shape[-1] // k
        xg = jnp.take(xhat_top[l], d.pt_col[l], axis=0)
        yhat_top[l] = jnp.einsum("nkj,njv->nkv", s_mar,
                                 xg.reshape(nn, maxb * k, nv),
                                 precision="highest")


def _use_split(schedule: str, nloc: int, maxb: int, maxb_d: int,
               n_bnd: int, maxb_o: int, hide_flops: int = 0,
               level_flops: int = 0) -> bool:
    """Static per-level schedule policy.

    ``overlap`` always splits (the §4.2 diag/off twins — on hardware with
    async collectives the off padding rides otherwise-idle time).
    ``fused`` never splits (one combined GEMM per level from the landed
    buffer — zero extra flops; each level's transfer still hides under the
    other levels' GEMMs because every exchange is issued up front).
    ``auto`` splits only where the split's padded volume is not larger —
    on balanced grids interior rows keep ``maxb_d == maxb``, so the fused
    form usually wins wherever overlap cannot be realized.

    ``hide_flops`` makes auto solver-aware: it is the caller's static
    estimate of NON-matvec compute per iteration (C-stencil + V-cycle
    smoothing) scheduled after the exchange is issued.  When that alone
    dwarfs this level's coupling GEMM (``level_flops``), the halo already
    hides under solver compute and the split's padded off-diagonal GEMM
    buys nothing — auto keeps the combined form.
    """
    if schedule == "overlap":
        return True
    if schedule == "fused":
        return False
    if hide_flops and hide_flops >= level_flops:
        return False
    return nloc * maxb_d + n_bnd * maxb_o < nloc * maxb


def _hp_payload_layout(dshape: DistH2Shape, nv: int):
    """Host-static layout of the fused per-offset halo payloads.

    Mirrors EXACTLY the pack order of ``_hp_pack_exchange`` (branch levels
    ``lc+1..depth`` ascending, then the dense leaves): ``seg[(key, delta)]
    = (lo, sz)`` is level ``key``'s flat slice inside offset ``delta``'s
    fused payload (element counts — dtype-independent) and ``tot[delta]``
    the payload's total length.  The dense key is ``depth + 1``.  Shared
    by the matvec and the obs profiler's stage cut, so the landed-buffer
    slicing cannot drift from the pack order.
    """
    depth, lc = dshape.depth, dshape.lc
    seg: Dict[Tuple[int, int], Tuple[int, int]] = {}
    tot: Dict[int, int] = {}

    def add(key, offsets, caps, width):
        for delta, cap in zip(offsets, caps):
            sz = cap * width * nv
            seg[(key, delta)] = (tot.get(delta, 0), sz)
            tot[delta] = tot.get(delta, 0) + sz

    if dshape.p > 1:
        for l in range(lc + 1, depth + 1):
            i = l - lc
            if dshape.ranks[l] == 0 or not dshape.br_offsets[i]:
                continue
            add(l, dshape.br_offsets[i], dshape.br_caps[i], dshape.ranks[l])
        add(depth + 1, dshape.dense_offsets, dshape.dense_caps,
            dshape.leaf_size)
    return seg, tot


def _hp_merged_layout(tot: Dict[int, int], p: int):
    """Residue-class layout merging EVERY per-offset payload into one
    ``all_to_all`` row buffer ``[p, capmax]``.

    The a2a semantics (``split_axis=0, concat_axis=0, tiled=False``) give
    receiver ``q`` row ``s`` = sender ``s``'s row ``q``.  Chunk ``delta``
    therefore travels sender row ``(me - delta) % p`` -> receiver row
    ``(me + delta) % p``; two offsets share a row exactly when their
    residues ``delta % p`` collide (p=2: +1/-1), resolved by cumulative
    column offsets within the residue class.  Returns ``(capmax, pos)``
    with ``pos[delta] = (residue, col_lo)`` and ``capmax`` = the widest
    residue class (min 1 so the buffer is never zero-width).
    """
    by_res: Dict[int, int] = {}
    pos: Dict[int, Tuple[int, int]] = {}
    for delta in sorted(tot):
        res = delta % p
        pos[delta] = (res, by_res.get(res, 0))
        by_res[res] = by_res.get(res, 0) + tot[delta]
    capmax = max(by_res.values()) if by_res else 1
    return max(capmax, 1), pos


def _hp_pack_exchange(dshape: DistH2Shape, d: DistH2Data, xhat, x_leaves,
                      axis, comm: str, backend: str = "jnp",
                      merged: bool = False) -> Dict[int, jax.Array]:
    """Phase A of the §4.2 overlap schedule: gather every level's planned
    send rows (branch levels AND dense leaves), flatten and fuse them per
    neighbor offset, and issue one packed ``ppermute`` per offset — the
    whole matvec's exchange up front.  Returns the landed flat payloads
    ``chunks[delta]``, laid out per ``_hp_payload_layout``.  Factored out
    of ``_coupling_phase_overlap`` so the obs profiler can cut the matvec
    at the pack/exchange boundary.

    ``merged=True`` (the solver lowering) further collapses all offsets
    into ONE ``all_to_all`` round on the ``_hp_merged_layout`` residue
    layout; the landed ``chunks`` dict is identical either way.

    Level ``lc`` never exchanges: the C-level branch-root gather that
    feeds the replicated top sweep already delivered every device's
    ``xhat[lc]``, so its coupling sources from that replica for free.
    """
    depth, lc, p = dshape.depth, dshape.lc, dshape.p
    bf16 = comm.endswith("-bf16")
    parts: Dict[int, List[jax.Array]] = {}     # offset -> flat payloads

    def _pack(src, plan: HaloPlan, offsets):
        for delta, idx in zip(offsets, plan.send):
            with phase("halo/pack"):
                if backend == "pallas":
                    from repro.kernels import ops as kops
                    packed = kops.halo_pack(src, idx)
                else:
                    packed = jnp.take(src, idx, axis=0)
                if bf16:
                    packed = packed.astype(jnp.bfloat16)
                parts.setdefault(delta, []).append(packed.reshape(-1))

    if p > 1:
        for l in range(lc + 1, depth + 1):
            i = l - lc
            if dshape.ranks[l] == 0 or not dshape.br_offsets[i]:
                continue
            _pack(xhat[l], d.hp_br[i], dshape.br_offsets[i])
        _pack(x_leaves, d.hp_dense, dshape.dense_offsets)
    chunks: Dict[int, jax.Array] = {}
    if merged and parts:
        # Solver lowering: in-loop collective COUNT dominates latency, so
        # every offset rides ONE all_to_all on the residue-class layout
        # (``_hp_merged_layout``).  Cross-residue slots ship zeros — the
        # padding is bounded by the widest residue class, and at solver
        # scale one a2a beats len(offsets) ppermutes decisively.
        payloads = {delta: (jnp.concatenate(lst) if len(lst) > 1 else lst[0])
                    for delta, lst in parts.items()}
        tot = {delta: int(pay.shape[0]) for delta, pay in payloads.items()}
        capmax, pos = _hp_merged_layout(tot, p)
        me = jax.lax.axis_index(axis)
        dtype = next(iter(payloads.values())).dtype
        with phase("halo/pack"):
            buf = jnp.zeros((p, capmax), dtype)
            for delta, pay in payloads.items():
                res, lo = pos[delta]
                row = jnp.mod(me - res, p)
                buf = jax.lax.dynamic_update_slice(
                    buf, pay.reshape(1, -1), (row, lo))
        if bf16:
            buf = jax.lax.optimization_barrier(buf)
        with phase("halo/round"):
            land = jax.lax.all_to_all(buf, axis, split_axis=0,
                                      concat_axis=0, tiled=False)
            land = land.reshape(p, capmax)
        with phase("halo/land"):
            for delta in payloads:
                res, lo = pos[delta]
                row = jnp.mod(me + res, p)
                chunks[delta] = jax.lax.dynamic_slice(
                    land, (row, lo), (1, tot[delta]))[0]
        return chunks
    for delta, lst in parts.items():
        payload = jnp.concatenate(lst) if len(lst) > 1 else lst[0]
        if bf16:
            # stop XLA hoisting the converts past the permute (which
            # would ship f32 and round afterwards)
            payload = jax.lax.optimization_barrier(payload)
        perm = [(src, (src - delta) % p) for src in range(p)]
        with phase("halo/round"):
            chunks[delta] = jax.lax.ppermute(payload, axis, perm)
    return chunks


def _coupling_phase_overlap(dshape: DistH2Shape, d: DistH2Data, xhat,
                            xhat_top, x_leaves, axis, comm: str,
                            backend: str = "jnp", schedule: str = "auto",
                            chunks: Optional[Dict[int, jax.Array]] = None,
                            hide_flops: int = 0):
    """Compressed-halo coupling + dense phases on the §4.2 overlap schedule.

    Program order (= XLA scheduling opportunity): (A) the fused packed
    exchange (``_hp_pack_exchange``) for the whole matvec up front — one
    ``ppermute`` round-trip per neighbor distance; (B) compute every
    diagonal (own-column) GEMM, the dense diagonal block, and the
    replicated top levels while the permutes are in flight (level ``lc``
    sources from the C-level branch-root gather and never exchanges);
    (C) slice the landed fused buffers back into per-level halos and
    finish the off-diagonal GEMMs (or, for levels the static policy left
    fused, the whole level's combined GEMM).  Returns
    ``(yhat, yhat_top, y_dense)``.

    ``chunks`` optionally supplies already-landed payloads (phase A run
    separately — the obs profiler's stage cut); they must follow
    ``_hp_payload_layout``.

    ``hide_flops > 0`` marks a solver-embedded matvec: phase A lowers to
    the merged single-``all_to_all`` exchange and the auto schedule gets
    the solver's hideable compute (see ``_use_split``).
    """
    depth, lc, p = dshape.depth, dshape.lc, dshape.p
    m = dshape.leaf_size
    nl = dshape.leaves_per_dev
    nv = xhat[depth].shape[-1]
    DENSE = depth + 1                          # key for the dense payload
    seg, _ = _hp_payload_layout(dshape, nv)

    # --- phase A: pack + fuse payloads per offset, one ppermute each
    # (or, solver-embedded, ONE merged all_to_all for every offset)
    if chunks is None:
        with phase("hgemv/exchange"):
            chunks = _hp_pack_exchange(dshape, d, xhat, x_leaves, axis,
                                       comm, backend,
                                       merged=hide_flops > 0)

    def _landed(src, key, offsets, caps, width):
        """[nloc + sum(caps), width-per-row ...] buffer in plan layout."""
        with phase("halo/land"):
            pieces = [src]
            for delta, cap in zip(offsets, caps):
                lo, sz = seg[(key, delta)]
                pieces.append(chunks[delta][lo:lo + sz]
                              .reshape(cap, width, nv).astype(src.dtype))
            return jnp.concatenate(pieces, axis=0)

    def _split(i, k):
        nloc_g = d.s_br_mar[i].shape[0]
        maxb = d.s_br_mar[i].shape[-1] // k
        return _use_split(schedule, nloc_g, maxb,
                          d.s_br_mar_diag[i].shape[-1] // k,
                          d.s_br_mar_off[i].shape[0],
                          d.s_br_mar_off[i].shape[-1] // k,
                          hide_flops, 2 * nloc_g * k * maxb * k * nv)

    dmaxb_full = d.dense_mar.shape[-1] // m
    d_split = _use_split(schedule, d.dense_mar.shape[0], dmaxb_full,
                         d.dense_mar_diag.shape[-1] // m,
                         d.dense_mar_off.shape[0],
                         d.dense_mar_off.shape[-1] // m,
                         hide_flops, 2 * nl * m * dmaxb_full * m * nv)

    # --- phase B: diagonal GEMMs + dense diagonal + replicated top
    # (fused-schedule levels wait for their halo in phase C instead)
    yhat: Dict[int, jax.Array] = {}
    yhat_top: Dict[int, jax.Array] = {}
    with phase("hgemv/diag-gemm"):
        for l in range(lc, depth + 1):
            i = l - lc
            nloc = dshape.nodes_local(l)
            k = dshape.ranks[l]
            if k == 0:
                yhat[l] = jnp.zeros((nloc, k, nv), xhat[depth].dtype)
                continue
            if l == lc and p > 1:
                # sourced from the replicated C-level gather — local
                # compute, one combined GEMM with the GLOBAL column plan
                s_mar = d.s_br_mar[i]
                maxb = s_mar.shape[-1] // k
                xg = jnp.take(xhat_top[lc], d.pb_col[i], axis=0)
                yhat[l] = jnp.einsum("nkj,njv->nkv", s_mar,
                                     xg.reshape(nloc, maxb * k, nv),
                                     precision="highest")
                continue
            if not _split(i, k):
                yhat[l] = None
                continue
            s_diag = d.s_br_mar_diag[i]        # [nloc, k, maxb_d*k]
            maxb_d = s_diag.shape[-1] // k
            xg = jnp.take(xhat[l], d.hp_br[i].diag_col, axis=0)
            yhat[l] = jnp.einsum("nkj,njv->nkv", s_diag,
                                 xg.reshape(nloc, maxb_d * k, nv),
                                 precision="highest")
        y_de = None
        if d_split:
            d_diag = d.dense_mar_diag          # [nl, m, dmaxb_d*m]
            dmaxb_d = d_diag.shape[-1] // m
            xg = jnp.take(x_leaves, d.hp_dense.diag_col, axis=0)
            y_de = jnp.einsum("nkj,njv->nkv", d_diag,
                              xg.reshape(nl, dmaxb_d * m, nv),
                              precision="highest")
        _top_coupling(dshape, d, xhat_top, yhat_top, nv)

    # --- phase C: finish from the landed buffers.  Split levels add the
    # off-diagonal correction: the off twin is row-compressed over the
    # boundary rows and merges back scatter-free through the precomputed
    # ``rowpos`` output permutation (core/halo.py).  Fused levels run
    # their single combined GEMM sourced through ``comb_idx``.
    def _off_merge(y, src, key, plan: HaloPlan, offsets, caps, s_off,
                   width):
        maxb_o = s_off.shape[-1] // width
        if maxb_o == 0 or s_off.shape[0] == 0 or p == 1:
            return y
        buf = _landed(src, key, offsets, caps, width)
        xg = jnp.take(buf, plan.off_idx, axis=0)
        off = jnp.einsum("nkj,njv->nkv", s_off,
                         xg.reshape(s_off.shape[0], maxb_o * width, nv),
                         precision="highest")
        corrected = jnp.take(y, plan.bnd_rows, axis=0) + off
        return jnp.take(jnp.concatenate([y, corrected], axis=0),
                        plan.rowpos, axis=0)

    def _fused_level(src, key, plan: HaloPlan, offsets, caps, s_mar,
                     width):
        rows = s_mar.shape[0]
        maxb = s_mar.shape[-1] // width
        buf = _landed(src, key, offsets, caps, width) if p > 1 else src
        xg = jnp.take(buf, plan.comb_idx, axis=0)
        return jnp.einsum("nkj,njv->nkv", s_mar,
                          xg.reshape(rows, maxb * width, nv),
                          precision="highest")

    with phase("hgemv/off-gemm"):
        for l in range(lc, depth + 1):
            i = l - lc
            k = dshape.ranks[l]
            if k == 0 or (l == lc and p > 1):  # lc rode the C-level gather
                continue
            if yhat[l] is None:
                yhat[l] = _fused_level(xhat[l], l, d.hp_br[i],
                                       dshape.br_offsets[i],
                                       dshape.br_caps[i], d.s_br_mar[i], k)
            else:
                yhat[l] = _off_merge(yhat[l], xhat[l], l, d.hp_br[i],
                                     dshape.br_offsets[i],
                                     dshape.br_caps[i],
                                     d.s_br_mar_off[i], k)
        if y_de is None:
            y_de = _fused_level(x_leaves, DENSE, d.hp_dense,
                                dshape.dense_offsets, dshape.dense_caps,
                                d.dense_mar, m)
        else:
            y_de = _off_merge(y_de, x_leaves, DENSE, d.hp_dense,
                              dshape.dense_offsets, dshape.dense_caps,
                              d.dense_mar_off, m)
    return yhat, yhat_top, y_de


def _local_downsweep(dshape: DistH2Shape, d: DistH2Data, yhat, yhat_top,
                     axis):
    with phase("hgemv/downsweep"):
        depth, lc = dshape.depth, dshape.lc
        me = jax.lax.axis_index(axis)
        nv = yhat[depth].shape[-1]
        # replicated top downsweep 0 -> lc
        if lc > 0:
            acc = yhat_top[0]
            for l in range(1, lc + 1):
                par = jnp.repeat(acc, 2, axis=0)
                step = jnp.einsum("ckp,cpv->ckv", d.e_top[l], par,
                                  precision="highest")
                add = yhat_top[l] if l < lc else 0.0
                acc = step + add
            own = jax.lax.dynamic_slice_in_dim(acc, me, 1, axis=0)
            acc = yhat[lc] + own
        else:
            acc = yhat[lc]
        for l in range(lc + 1, depth + 1):
            par = jnp.repeat(acc, 2, axis=0)
            acc = yhat[l] + jnp.einsum("ckp,cpv->ckv", d.e_br[l - lc], par,
                                       precision="highest")
        return jnp.einsum("bmk,bkv->bmv", d.u_leaf, acc, precision="highest")


def _dense_phase(dshape: DistH2Shape, d: DistH2Data, x_leaves, axis,
                 comm: str, gathered: Optional[jax.Array] = None):
    """``gathered`` (allgather mode only) optionally supplies the already
    all_gather'ed full leaf tensor ``[2**depth, m, nv]`` so the exchange
    can be cut into its own stage program (obs segmented replay)."""
    p = dshape.p
    nloc = dshape.leaves_per_dev
    m = dshape.leaf_size
    nv = x_leaves.shape[-1]
    me = jax.lax.axis_index(axis)
    d_mar = d.dense_mar                       # [nloc, m, dmaxb*m] per device
    dmaxb = d_mar.shape[-1] // m
    if comm == "allgather" and p > 1:
        with phase("hgemv/exchange"):
            xg_full = gathered if gathered is not None else \
                jax.lax.all_gather(x_leaves, axis, tiled=True)
        with phase("hgemv/dense"):
            xg = jnp.take(xg_full, d.pd_col, axis=0)
    else:
        with phase("hgemv/exchange"):
            rad = dshape.dense_radius if p > 1 else 0
            src = jax.lax.optimization_barrier(
                x_leaves.astype(jnp.bfloat16)) \
                if comm == "ppermute-bf16" else x_leaves
            halo = _halo_exchange(src, axis, rad, p)
        with phase("hgemv/dense"):
            idx = d.pd_col - me * nloc + rad * nloc
            xg = jnp.take(halo, idx, axis=0).astype(x_leaves.dtype)
    with phase("hgemv/dense"):
        return jnp.einsum("nkj,njv->nkv", d_mar,
                          xg.reshape(nloc, dmaxb * m, nv), precision="highest")


def dist_h2_matvec_local(dshape: DistH2Shape, d: DistH2Data, x: jax.Array,
                         axis, comm: str = "halo-plan",
                         backend: str = "jnp",
                         schedule: str = "auto",
                         hide_flops: int = 0) -> jax.Array:
    """Per-device body (call inside shard_map). x: [n_local, nv].

    ``hide_flops > 0`` marks a solver-embedded call: the halo-plan
    exchange merges into one ``all_to_all`` and the auto schedule
    accounts for the solver compute available to hide it under.
    """
    nv = x.shape[-1]
    x_leaves = x.reshape(dshape.leaves_per_dev, dshape.leaf_size, nv)
    xhat, xhat_top = _local_upsweep(dshape, d, x_leaves, axis)
    if comm in ("halo-plan", "halo-plan-bf16"):
        yhat, yhat_top, y_de = _coupling_phase_overlap(
            dshape, d, xhat, xhat_top, x_leaves, axis, comm, backend,
            schedule, hide_flops=hide_flops)
    else:
        yhat, yhat_top = _coupling_phase(dshape, d, xhat, xhat_top, axis,
                                         comm)
        y_de = _dense_phase(dshape, d, x_leaves, axis, comm)
    y_lr = _local_downsweep(dshape, d, yhat, yhat_top, axis)
    return (y_lr + y_de).reshape(dshape.n_local(), nv)


def make_dist_matvec(dshape: DistH2Shape, mesh: Mesh, axis,
                     comm: str = "halo-plan", nv_axis: Optional[str] = None,
                     backend: str = "jnp", schedule: str = "auto",
                     hide_flops: int = 0):
    """Build the jitted distributed matvec for a mesh.

    ``axis``: mesh axis name (or tuple of names) carrying the block rows.
    ``nv_axis``: optional mesh axis to shard the vector batch over (the
    paper's multi-vector nv dimension — embarrassingly parallel).
    ``backend="pallas"`` routes the halo-plan send packing through the
    scalar-prefetch gather kernel (kernels/halo_pack.py).
    ``schedule`` picks the halo-plan GEMM schedule per level (see
    ``_use_split``): "overlap" = the §4.2 diag/off split, "fused" = one
    combined GEMM per level from the landed buffer, "auto" = static flop
    model.  ``hide_flops > 0`` requests the solver-embedded lowering
    (merged single-``all_to_all`` exchange + hide-aware auto).
    """
    specs = dist_specs(dshape, axis)
    xspec = P(axis, nv_axis)

    def fn(d: DistH2Data, x: jax.Array) -> jax.Array:
        return dist_h2_matvec_local(dshape, d, x, axis, comm, backend,
                                    schedule, hide_flops)

    shmapped = jax.shard_map(
        fn, mesh=mesh,
        in_specs=(specs, xspec),
        out_specs=xspec,
        check_vma=False)
    return jax.jit(shmapped)


# ---------------------------------------------------------------------------
# distributed orthogonalization + compression (symmetric structure)
# ---------------------------------------------------------------------------

def _branch_orthogonalize(dshape: DistH2Shape, leaf, e_br, e_top, axis):
    """Upsweep QR: local branch, then replicated top. Returns
    (new_leaf, new_e_br, new_e_top, r_br dict, r_top dict)."""
    depth, lc = dshape.depth, dshape.lc
    r: Dict[int, jax.Array] = {}
    q_leaf, r[depth] = jnp.linalg.qr(leaf, mode="reduced")
    new_e_br = [e_br[0]] + [None] * (depth - lc)
    for l in range(depth, lc, -1):
        e = e_br[l - lc]
        re = jnp.einsum("crk,ckp->crp", r[l], e, precision="highest")
        nn, kl, kp = re.shape
        stacked = re.reshape(nn // 2, 2 * kl, kp)
        q, rr = jnp.linalg.qr(stacked, mode="reduced")
        new_e_br[l - lc] = q.reshape(nn, kl, q.shape[-1])
        r[l - 1] = rr
    # gather branch-root R factors and continue on the replicated top
    r_top: Dict[int, jax.Array] = {
        lc: jax.lax.all_gather(r[lc], axis, tiled=True)}   # [2**lc, k, k]
    new_e_top = [e_top[0]] + [None] * lc
    for l in range(lc, 0, -1):
        e = e_top[l]
        re = jnp.einsum("crk,ckp->crp", r_top[l], e, precision="highest")
        nn, kl, kp = re.shape
        stacked = re.reshape(nn // 2, 2 * kl, kp)
        q, rr = jnp.linalg.qr(stacked, mode="reduced")
        new_e_top[l] = q.reshape(nn, kl, q.shape[-1])
        r_top[l - 1] = rr
    return q_leaf, new_e_br, new_e_top, r, r_top


def dist_orthogonalize_local(dshape: DistH2Shape, d: DistH2Data, axis
                             ) -> DistH2Data:
    """Distributed orthogonalization (symmetric structure).

    The S update needs the column node's R factor, which may live on a
    neighbor — fetched through the SAME compressed halo plan as the matvec
    (the node set a remote device references is identical), with
    ``blk_idx`` mapping each slab block to its column's landed-buffer slot.
    """
    assert dshape.symmetric, "distributed path assumes symmetric structure"
    depth, lc, p = dshape.depth, dshape.lc, dshape.p
    q_leaf, new_e_br, new_e_top, r, r_top = _branch_orthogonalize(
        dshape, d.u_leaf, d.e_br, d.e_top, axis)

    s_br_new, s_top_new = [], []
    for l in range(lc, depth + 1):
        i = l - lc
        rl = r[l]                                  # [nloc, k', k]
        if l == lc and p > 1:
            # the C-level gather feeding the top sweep already delivered
            # every device's R factor — no exchange at level lc
            r_cols = jnp.take(r_top[lc], d.s_br_cols[i], axis=0)
        else:
            buf = _halo.exchange(rl, d.hp_br[i], dshape.br_offsets[i],
                                 axis, p) if p > 1 else rl
            r_cols = jnp.take(buf, d.hp_br[i].blk_idx, axis=0)
        r_rows = jnp.take(rl, d.s_br_rows[i], axis=0)
        s_br_new.append(jnp.einsum("bij,bjk,blk->bil", r_rows, d.s_br[i],
                                   r_cols, precision="highest"))
    for l in range(lc):
        if dshape.top_counts[l] == 0:
            s_top_new.append(d.s_top[l])
            continue
        rr = jnp.take(r_top[l], d.s_top_rows[l], axis=0)
        rc = jnp.take(r_top[l], d.s_top_cols[l], axis=0)
        s_top_new.append(jnp.einsum("bij,bjk,blk->bil", rr, d.s_top[l], rc,
                                    precision="highest"))

    return _with_remarshaled(dshape, d, DistH2Data(
        u_leaf=q_leaf, v_leaf=q_leaf,
        e_br=new_e_br, f_br=new_e_br,
        s_br=s_br_new, s_br_rows=d.s_br_rows, s_br_cols=d.s_br_cols,
        e_top=new_e_top, f_top=new_e_top,
        s_top=s_top_new, s_top_rows=d.s_top_rows, s_top_cols=d.s_top_cols,
        dense=d.dense, d_rows=d.d_rows, d_cols=d.d_cols,
        pb_blk=d.pb_blk, pb_col=d.pb_col, s_br_mar=d.s_br_mar,
        pt_blk=d.pt_blk, pt_col=d.pt_col, s_top_mar=d.s_top_mar,
        pd_col=d.pd_col, dense_mar=d.dense_mar,
        hp_br=d.hp_br, hp_dense=d.hp_dense,
        s_br_mar_diag=d.s_br_mar_diag, s_br_mar_off=d.s_br_mar_off,
        dense_mar_diag=d.dense_mar_diag, dense_mar_off=d.dense_mar_off))


def _stack_local(blocks, idx, n_nodes, maxb):
    from .compression import _stack_blocks
    return _stack_blocks(blocks, idx, n_nodes, maxb)


def _with_remarshaled(dshape: DistH2Shape, d_old: DistH2Data,
                      d_new: DistH2Data) -> DistH2Data:
    """Refresh the marshaled S buffers from rewritten block values.

    Per-device gathers by the (unchanged) slot plans; call inside
    shard_map after a pass that rewrites ``s_br``/``s_top`` (the
    orthogonalization / compression S updates).  Dense is untouched.
    """
    depth, lc = dshape.depth, dshape.lc
    s_br_mar = [marshal_blocks(d_new.s_br[l - lc], d_old.pb_blk[l - lc],
                               dshape.nodes_local(l))
                for l in range(lc, depth + 1)]
    s_br_mar_diag = [marshal_blocks(d_new.s_br[l - lc],
                                    d_old.hp_br[l - lc].diag_blk,
                                    dshape.nodes_local(l))
                     for l in range(lc, depth + 1)]
    # the off twin's row axis is the boundary-row set, not the node set
    s_br_mar_off = [marshal_blocks(d_new.s_br[l - lc],
                                   d_old.hp_br[l - lc].off_blk,
                                   d_old.s_br_mar_off[l - lc].shape[0])
                    for l in range(lc, depth + 1)]
    s_top_mar = [marshal_blocks(d_new.s_top[l], d_old.pt_blk[l], 1 << l)
                 for l in range(lc)]
    return dataclasses.replace(d_new, s_br_mar=s_br_mar,
                               s_br_mar_diag=s_br_mar_diag,
                               s_br_mar_off=s_br_mar_off,
                               s_top_mar=s_top_mar)


def dist_compress_local(dshape: DistH2Shape, d: DistH2Data,
                        target_ranks: Sequence[int], axis) -> DistH2Data:
    """Distributed recompression with static target ranks (symmetric).

    Paper §5: downsweep (batched QR of stacked blocks, no communication below
    the C-level), upsweep truncation (batched SVD, one gather at the C-level),
    then coupling projection with a halo exchange for remote column maps.
    """
    assert dshape.symmetric
    depth, lc, p = dshape.depth, dshape.lc, dshape.p
    me = jax.lax.axis_index(axis)
    ranks = dshape.ranks
    tr = list(target_ranks)
    d = dist_orthogonalize_local(dshape, d, axis)

    # ---- weights downsweep (top replicated, branch local; zero comm) ----
    w_top: Dict[int, jax.Array] = {0: jnp.zeros((1, ranks[0], ranks[0]),
                                                d.u_leaf.dtype)}
    for l in range(1, lc + 1):
        nn = 1 << l
        kl, kp = ranks[l], ranks[l - 1]
        rpar = jnp.repeat(w_top[l - 1], 2, axis=0)
        par = jnp.einsum("cij,ckj->cik", rpar, d.e_top[l], precision="highest")
        pieces = [par]
        if l < lc and dshape.top_counts[l] > 0:
            st = jnp.swapaxes(d.s_top[l], -1, -2)
            pieces.append(_stack_local(st, d.s_top_rows[l], nn,
                                       dshape.row_maxb[l] or 1))
        stack = jnp.concatenate(pieces, axis=1)
        if stack.shape[1] < kl:
            stack = jnp.concatenate(
                [stack, jnp.zeros((nn, kl - stack.shape[1], kl),
                                  stack.dtype)], axis=1)
        w_top[l] = jnp.linalg.qr(stack, mode="r")[..., :kl, :]
    # level lc: include the local (single-node) branch blocks
    w: Dict[int, jax.Array] = {}
    own_top = jax.lax.dynamic_slice_in_dim(w_top[lc], me, 1, axis=0) \
        if lc > 0 else w_top[0]
    w[lc] = own_top
    # redo level lc with the branch coupling blocks folded in
    if dshape.br_counts[0] > 0:
        nloc = dshape.nodes_local(lc)
        kl = ranks[lc]
        if lc > 0:
            par_r = jnp.repeat(w_top[lc - 1], 2, axis=0)
            par_r = jax.lax.dynamic_slice_in_dim(par_r, me * nloc, nloc, 0)
            par = jnp.einsum("cij,ckj->cik", par_r,
                             jax.lax.dynamic_slice_in_dim(
                                 d.e_top[lc], me * nloc, nloc, 0),
                             precision="highest")
            pieces = [par]
        else:
            pieces = [jnp.zeros((nloc, ranks[0], kl), d.u_leaf.dtype)]
        st = jnp.swapaxes(d.s_br[0], -1, -2)
        pieces.append(_stack_local(st, d.s_br_rows[0], nloc,
                                   max(dshape.br_counts[0], 1)))
        stack = jnp.concatenate(pieces, axis=1)
        if stack.shape[1] < kl:
            stack = jnp.concatenate(
                [stack, jnp.zeros((nloc, kl - stack.shape[1], kl),
                                  stack.dtype)], axis=1)
        w[lc] = jnp.linalg.qr(stack, mode="r")[..., :kl, :]
    for l in range(lc + 1, depth + 1):
        i = l - lc
        nloc = dshape.nodes_local(l)
        kl = ranks[l]
        rpar = jnp.repeat(w[l - 1], 2, axis=0)
        par = jnp.einsum("cij,ckj->cik", rpar, d.e_br[i], precision="highest")
        pieces = [par]
        if dshape.br_counts[i] > 0:
            st = jnp.swapaxes(d.s_br[i], -1, -2)
            pieces.append(_stack_local(st, d.s_br_rows[i], nloc,
                                       max(dshape.br_counts[i], 1)))
        stack = jnp.concatenate(pieces, axis=1)
        if stack.shape[1] < kl:
            stack = jnp.concatenate(
                [stack, jnp.zeros((nloc, kl - stack.shape[1], kl),
                                  stack.dtype)], axis=1)
        w[l] = jnp.linalg.qr(stack, mode="r")[..., :kl, :]

    # ---- truncation upsweep: branch local -> gather at C-level -> top ----
    # the per-branch schedule is the single-device fused upsweep
    # (compression.truncation_* steps) run inside shard_map
    from .compression import truncation_inner_factors, \
        truncation_leaf_factors, truncation_project
    wq, _ = truncation_leaf_factors(w[depth])
    rq = min(tr[depth], wq.shape[-1])
    wk = wq[..., :rq]
    new_leaf = jnp.einsum("nmk,nkr->nmr", d.u_leaf, wk, precision="highest")
    pmap_: Dict[int, jax.Array] = {depth: jnp.swapaxes(wk, -1, -2)}
    new_e_br = [d.e_br[0]] + [None] * (depth - lc)
    for l in range(depth, lc, -1):
        stack, g, _ = truncation_inner_factors(pmap_[l], d.e_br[l - lc],
                                               w[l - 1])
        rl = stack.shape[1] // 2
        rp = min(tr[l - 1], g.shape[-1], 2 * rl)
        gk = g[..., :rp]
        new_e_br[l - lc] = gk.reshape(2 * stack.shape[0], rl, rp)
        pmap_[l - 1] = truncation_project(gk, stack)
    # gather branch-root projections, continue on top
    p_top: Dict[int, jax.Array] = {
        lc: jax.lax.all_gather(pmap_[lc], axis, tiled=True)}
    new_e_top = [d.e_top[0]] + [None] * lc
    for l in range(lc, 0, -1):
        stack, g, _ = truncation_inner_factors(p_top[l], d.e_top[l],
                                               w_top[l - 1])
        rl = stack.shape[1] // 2
        rp = min(tr[l - 1], g.shape[-1], 2 * rl)
        gk = g[..., :rp]
        new_e_top[l] = gk.reshape(2 * stack.shape[0], rl, rp)
        p_top[l - 1] = truncation_project(gk, stack)

    # ---- coupling projection (planned exchange for remote column maps;
    # level lc rides the C-level gather that opened the top sweep) ----
    s_br_new, s_top_new = [], []
    for l in range(lc, depth + 1):
        i = l - lc
        pl_ = pmap_[l]
        if l == lc and p > 1:
            pc = jnp.take(p_top[lc], d.s_br_cols[i], axis=0)
        else:
            buf = _halo.exchange(pl_, d.hp_br[i], dshape.br_offsets[i],
                                 axis, p) if p > 1 else pl_
            pc = jnp.take(buf, d.hp_br[i].blk_idx, axis=0)
        pr = jnp.take(pl_, d.s_br_rows[i], axis=0)
        s_br_new.append(jnp.einsum("brk,bkj,bsj->brs", pr, d.s_br[i], pc,
                                   precision="highest"))
    for l in range(lc):
        if dshape.top_counts[l] == 0:
            nb = d.s_top[l].shape[0]
            rnew = p_top[l].shape[1]
            s_top_new.append(jnp.zeros((nb, rnew, rnew), d.u_leaf.dtype))
            continue
        pr = jnp.take(p_top[l], d.s_top_rows[l], axis=0)
        pc = jnp.take(p_top[l], d.s_top_cols[l], axis=0)
        s_top_new.append(jnp.einsum("brk,bkj,bsj->brs", pr, d.s_top[l], pc,
                                    precision="highest"))

    return _with_remarshaled(dshape, d, DistH2Data(
        u_leaf=new_leaf, v_leaf=new_leaf,
        e_br=new_e_br, f_br=new_e_br,
        s_br=s_br_new, s_br_rows=d.s_br_rows, s_br_cols=d.s_br_cols,
        e_top=new_e_top, f_top=new_e_top,
        s_top=s_top_new, s_top_rows=d.s_top_rows, s_top_cols=d.s_top_cols,
        dense=d.dense, d_rows=d.d_rows, d_cols=d.d_cols,
        pb_blk=d.pb_blk, pb_col=d.pb_col, s_br_mar=d.s_br_mar,
        pt_blk=d.pt_blk, pt_col=d.pt_col, s_top_mar=d.s_top_mar,
        pd_col=d.pd_col, dense_mar=d.dense_mar,
        hp_br=d.hp_br, hp_dense=d.hp_dense,
        s_br_mar_diag=d.s_br_mar_diag, s_br_mar_off=d.s_br_mar_off,
        dense_mar_diag=d.dense_mar_diag, dense_mar_off=d.dense_mar_off))


def make_dist_compress(dshape: DistH2Shape, mesh: Mesh, axis,
                       target_ranks: Sequence[int]):
    specs = dist_specs(dshape, axis)

    def fn(d: DistH2Data) -> DistH2Data:
        return dist_compress_local(dshape, d, tuple(target_ranks), axis)

    out_specs = dist_specs(
        dataclasses.replace(dshape, ranks=tuple(target_ranks)), axis)
    shmapped = jax.shard_map(fn, mesh=mesh, in_specs=(specs,),
                             out_specs=out_specs, check_vma=False)
    return jax.jit(shmapped)


# ---------------------------------------------------------------------------
# communication model (for benchmarks / roofline)
# ---------------------------------------------------------------------------

def matvec_comm_bytes(dshape: DistH2Shape, nv: int, comm: str = "halo-plan",
                      bytes_per_el: int = 4) -> int:
    """Per-device collective bytes of one distributed matvec.

    ``allgather`` ships ``(p-1)`` full level copies and broadcast
    ``ppermute`` one copy per distinct shift: ``2*rad`` of them, or
    ``p-1`` once ``2*rad >= p`` makes shifts ``d`` and ``d-p`` name the
    same peers (the compiler merges those).  ``halo-plan`` ships only the
    compressed send lists — ``sum(caps)`` rows per level, the paper's
    §4.1 volume.  The branch-root gather is a tiled ``all_gather``: each
    device receives the other ``p-1`` slices (its own it already holds;
    ``root_gather_bytes``).  ``-bf16`` payload modes halve
    ``bytes_per_el`` at the call site.
    """
    total = root_gather_bytes(dshape, nv, comm, bytes_per_el)
    for l in range(dshape.lc, dshape.depth + 1):
        i = l - dshape.lc
        nloc = dshape.nodes_local(l)
        row = dshape.ranks[l] * nv * bytes_per_el
        if comm == "allgather":
            total += (dshape.p - 1) * nloc * row
        elif comm.startswith("halo-plan"):
            if l > dshape.lc:      # level lc rides the branch-root gather
                total += sum(dshape.br_caps[i]) * row
        else:
            total += _shifts(dshape.br_radius[i], dshape.p) * nloc * row
    nl = dshape.leaves_per_dev
    row = dshape.leaf_size * nv * bytes_per_el
    if comm == "allgather":
        total += (dshape.p - 1) * nl * row
    elif comm.startswith("halo-plan"):
        total += sum(dshape.dense_caps) * row
    else:
        total += _shifts(dshape.dense_radius, dshape.p) * nl * row
    return total


def root_gather_bytes(dshape: DistH2Shape, nv: int, comm: str = "halo-plan",
                      bytes_per_el: int = 4) -> int:
    """Per-device bytes of the branch-root ``all_gather``, where something
    reads it: level lc under halo-plan, the top tree wherever it holds
    coupling blocks.  Otherwise the compiled program drops it, and under
    ``allgather`` it is the same collective as level lc's own gather."""
    if comm.startswith("halo-plan") or (comm != "allgather"
                                        and any(dshape.top_counts)):
        return (dshape.p - 1) * dshape.ranks[dshape.lc] * nv * bytes_per_el
    return 0


def _shifts(rad: int, p: int) -> int:
    """Distinct peers of a radius-``rad`` broadcast halo over ``p``."""
    return min(2 * rad, p - 1)


def merged_exchange_bytes(dshape: DistH2Shape, nv: int,
                          comm: str = "halo-plan",
                          bytes_per_el: int = 4) -> int:
    """Per-device wire bytes of the solver lowering's merged exchange:
    one ``[p, capmax]`` ``all_to_all`` on the ``_hp_merged_layout``
    residue layout — ``(p-1) * capmax`` elements cross the wire (the own
    row stays local).  Replaces the per-offset halo-plan terms of
    ``matvec_comm_bytes`` when ``hide_flops > 0``; ``-bf16`` ships
    2-byte payloads.
    """
    if dshape.p <= 1:
        return 0
    _, tot = _hp_payload_layout(dshape, nv)
    if not tot:
        return 0
    capmax, _ = _hp_merged_layout(tot, dshape.p)
    bpe = 2 if comm.endswith("-bf16") else bytes_per_el
    return (dshape.p - 1) * capmax * bpe
