"""Pallas TPU kernel: gather-fused conflict-free block-sparse MV.

``yhat_t = sum_{s in row t} S_ts @ xhat_s`` (paper Algorithm 4).  The paper
marshals irregular tree data into conflict-free batches on the CPU; here the
*marshaling plan* (core/structure.py, DESIGN.md §3.5) is three small int32
arrays that ride in SMEM via scalar prefetch, and the gather happens in the
BlockSpec index maps: each grid step DMAs one S block and one xhat row
straight from their **natural layouts** — no pre-gathered ``xg_pad``, no
zero-padded HBM copy of S, no scatter on the way out.

Schedule: grid ``(rows, nv_tiles, maxb)`` with the slot axis innermost and
absent from the output index map, so Pallas keeps the ``yhat_t`` tile
resident in VMEM while the slot axis accumulates — the conflict-free
property (one writer per row).  ``@pl.when(j < cnt[r])`` skips the padding
slots (their index-map fetch is clamped in-range and discarded); the
``nv``-tile axis gives multi-vector throughput without growing the VMEM
working set past one ``[k, bnv]`` tile.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from . import pallas_call


def _fused_kernel(blk_ref, col_ref, cnt_ref, s_ref, x_ref, y_ref):
    r = pl.program_id(0)
    j = pl.program_id(2)

    @pl.when(j == 0)
    def _init():
        y_ref[...] = jnp.zeros_like(y_ref)

    @pl.when(j < cnt_ref[r])
    def _accumulate():
        y_ref[0] += jnp.dot(s_ref[0], x_ref[0],
                            preferred_element_type=y_ref.dtype,
                            precision="highest")


@functools.partial(jax.jit, static_argnames=("maxb", "bnv"))
def coupling_mv(s: jax.Array, x: jax.Array, blk: jax.Array, col: jax.Array,
                cnt: jax.Array, *, maxb: int, bnv: int = 128) -> jax.Array:
    """-> yhat [rows, k1, nv].

    s:   [nb, k1, k2]  blocks in natural (block-list) order
    x:   [nodes, k2, nv]  source vectors in natural (node) order
    blk: [rows*maxb] int32 slot -> block index (padding slots hold nb)
    col: [rows*maxb] int32 slot -> source node index
    cnt: [rows] int32 blocks per row
    """
    nb, k1, k2 = s.shape
    nv = x.shape[-1]
    rows = cnt.shape[0]
    bnv = min(bnv, nv)
    rem = (-nv) % bnv
    x_p = jnp.pad(x, ((0, 0), (0, 0), (0, rem))) if rem else x
    nvt = (nv + rem) // bnv

    def s_map(r, v, j, blk_, col_, cnt_):
        # clamp the padding sentinel (nb) in-range; @pl.when discards it
        return (jnp.minimum(blk_[r * maxb + j], nb - 1), 0, 0)

    def x_map(r, v, j, blk_, col_, cnt_):
        return (col_[r * maxb + j], 0, v)

    def y_map(r, v, j, blk_, col_, cnt_):
        return (r, 0, v)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=(rows, nvt, maxb),
        in_specs=[
            pl.BlockSpec((1, k1, k2), s_map),
            pl.BlockSpec((1, k2, bnv), x_map),
        ],
        out_specs=pl.BlockSpec((1, k1, bnv), y_map),
    )
    out = pallas_call(
        _fused_kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((rows, k1, nv + rem), s.dtype),
    )(blk, col, cnt, s, x_p)
    return out[..., :nv] if rem else out
