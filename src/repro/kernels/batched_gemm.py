"""Pallas TPU kernel: batched GEMM  C[b] = A[b] @ B[b].

This is the workhorse of every H^2 phase (upsweep/downsweep transfers,
coupling multiply, dense leaves) — the TPU analogue of the MAGMA batched GEMM
the paper relies on.  TPU rethink vs the CUDA version:

* the batch dimension rides the *grid*, one (bm x bn) MXU tile per grid step;
* M/N/K are tiled with BlockSpecs so each step's working set
  (bm*bk + bk*bn + bm*bn floats) lives in VMEM;
* K is the innermost grid dimension and the output block index map ignores it,
  so Pallas keeps the C tile resident in VMEM and we accumulate across K
  steps (`@pl.when(k == 0)` zero-init) — the standard revisiting pattern;
* tiles default to MXU-aligned (128, 128) and fall back to the full (small)
  dimension for the k x k coupling blocks, which Mosaic pads internally.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from . import pallas_call


def _gemm_kernel(a_ref, b_ref, c_ref):
    k = pl.program_id(3)

    @pl.when(k == 0)
    def _init():
        c_ref[...] = jnp.zeros_like(c_ref)

    a = a_ref[0]          # [bm, bk]
    b = b_ref[0]          # [bk, bn]
    c_ref[0] += jnp.dot(a, b, preferred_element_type=c_ref.dtype,
                        precision="highest")


def _pick(block: int, dim: int) -> int:
    return dim if dim <= block else block


@functools.partial(jax.jit, static_argnames=("bm", "bn", "bk"))
def batched_gemm(a: jax.Array, b: jax.Array, *, bm: int = 128, bn: int = 128,
                 bk: int = 128) -> jax.Array:
    """C[bat] = A[bat] @ B[bat];  A: [B, M, K], B: [B, K, N] -> [B, M, N]."""
    nb, m, kdim = a.shape
    _, _, n = b.shape
    if 0 in (nb, m, n, kdim):      # zero-size batch/dims (e.g. rank-0 levels)
        return jnp.zeros((nb, m, n), a.dtype)
    bm, bn, bk = _pick(bm, m), _pick(bn, n), _pick(bk, kdim)
    # grid must tile exactly; fall back to full dims if not divisible
    if m % bm:
        bm = m
    if n % bn:
        bn = n
    if kdim % bk:
        bk = kdim
    grid = (nb, m // bm, n // bn, kdim // bk)
    return pallas_call(
        _gemm_kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, bm, bk), lambda b_, i, j, k: (b_, i, k)),
            pl.BlockSpec((1, bk, bn), lambda b_, i, j, k: (b_, k, j)),
        ],
        out_specs=pl.BlockSpec((1, bm, bn), lambda b_, i, j, k: (b_, i, j)),
        out_shape=jax.ShapeDtypeStruct((nb, m, n), a.dtype),
    )(a, b)
