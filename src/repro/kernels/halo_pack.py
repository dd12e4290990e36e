"""Pallas TPU kernel: scalar-prefetch send-row packing for the halo plan.

The compressed halo exchange (core/halo.py, DESIGN.md §3) packs the planned
send rows ``x[send[j]]`` into one contiguous buffer per neighbor offset
before the ``ppermute``.  On TPU the natural way to build that buffer is a
DMA gather: the int32 send list rides in SMEM via scalar prefetch and the
BlockSpec index map streams each planned row straight from ``x``'s natural
layout into the packed output — no intermediate HBM copy of the whole
level, and the packing cost scales with ``cap`` (the compressed volume),
not ``nloc``.  Grid ``(cap,)``; a row is one ``[k, nv]`` tile.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from . import pallas_call


def _pack_kernel(idx_ref, x_ref, y_ref):
    y_ref[...] = x_ref[...]


@jax.jit
def halo_pack(x: jax.Array, idx: jax.Array) -> jax.Array:
    """-> packed [cap, k, nv].

    x:   [n, k, nv]  per-node rows in natural (node) order
    idx: [cap] int32 planned send rows (padding entries may repeat row 0)
    """
    n, k, nv = x.shape
    cap = idx.shape[0]

    def x_map(i, idx_):
        return (idx_[i], 0, 0)

    def y_map(i, idx_):
        return (i, 0, 0)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(cap,),
        in_specs=[pl.BlockSpec((1, k, nv), x_map)],
        out_specs=pl.BlockSpec((1, k, nv), y_map),
    )
    return pallas_call(
        _pack_kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((cap, k, nv), x.dtype),
    )(idx, x)
