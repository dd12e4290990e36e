"""Pure-jnp oracles for every Pallas kernel (the ``ref.py`` contract)."""
from __future__ import annotations

import jax
import jax.numpy as jnp


def batched_gemm(a: jax.Array, b: jax.Array) -> jax.Array:
    return jnp.einsum("bmk,bkn->bmn", a, b, precision="highest")


def batched_qr(a: jax.Array):
    return jnp.linalg.qr(a, mode="reduced")


def batched_qr_signfixed(a: jax.Array):
    """QR canonicalized to a non-negative R diagonal.

    The Pallas kernel emits this unique form directly, so the parity tests
    can compare Q columns and R rows elementwise instead of up-to-sign.
    """
    q, r = jnp.linalg.qr(a, mode="reduced")
    d = jnp.where(jnp.diagonal(r, axis1=-2, axis2=-1) < 0.0, -1.0, 1.0)
    return q * d[..., None, :], r * d[..., :, None]


def batched_svd(a: jax.Array):
    u, s, vt = jnp.linalg.svd(a, full_matrices=False)
    return u, s, vt


def coupling_mv(s: jax.Array, x: jax.Array, blk: jax.Array, col: jax.Array,
                cnt: jax.Array, *, maxb: int) -> jax.Array:
    """Plan-based block-sparse MV oracle: take-by-plan -> batched einsum ->
    reshape-sum (padding slots masked by the per-row counts)."""
    rows = cnt.shape[0]
    k1 = s.shape[-2]
    sg = jnp.take(s, blk, axis=0, mode="fill", fill_value=0)
    xg = jnp.take(x, col, axis=0)
    prod = jnp.einsum("bij,bjv->biv", sg, xg, precision="highest")
    mask = (jnp.arange(maxb, dtype=cnt.dtype)[None, :] < cnt[:, None])
    prod = prod.reshape(rows, maxb, k1, -1) * mask[:, :, None, None]
    return prod.sum(axis=1)
