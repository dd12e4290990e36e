"""Pallas TPU kernel: batched blocked (compact-WY) Householder QR.

The paper's compression leans on KBLAS batched QR of stacked
``(C_sp+1)k x k`` panels (Eq. 4).  TPU adaptation: blocked Householder QR
in compact-WY form so the MXU does the O(nk^2) work:

- the k columns are factored in *column panels* of width ``panel``; within
  a panel the reflectors are classical Householder steps (VPU rank-1
  updates on the [n, panel] slice only), run as a ``fori_loop`` that picks
  column j by mask — an unrolled loop compiles an order of magnitude
  slower, and Mosaic has no dynamic slice, scatter or mat-vec dot,
- each finished panel is aggregated as ``H_0 ... H_{p-1} = I - V T V^T``
  (compact WY, T upper triangular) and applied to the trailing columns as
  two batched GEMMs — the dominant cost rides the MXU instead of k
  scalar-at-a-time column sweeps,
- Q is accumulated panel-by-panel in reverse with the same WY GEMMs.

Small panels are batched: one grid step factors ``bb`` independent panels
(the ``[bb, n, k]`` block), so the contractions see an effective batch and
the grid does not degenerate to per-matrix steps when k is small.

The reflector buffer of the previous implementation (``vs_ref``, a
``[B, k, n]`` f32 pallas output) is gone: no caller consumed it, and it
cost an extra O(Bnk) HBM write per QR.  V/T live only in registers/VMEM
for the lifetime of a grid step.

Returns (Q, R) with Q: [B, n, k] (reduced), R: [B, k, k] upper-triangular
with non-negative diagonal (sign-fixed, so the factorization is unique for
full-rank panels).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from . import pallas_call


def _wy_apply(v: jax.Array, t: jax.Array, x: jax.Array,
              transpose_t: bool) -> jax.Array:
    """x <- (I - V T V^T) x (or T^T), batched over the leading axis."""
    w = jnp.einsum("bnp,bnc->bpc", v, x, precision="highest")
    w = jnp.einsum("bqp,bqc->bpc" if transpose_t else "bpq,bqc->bpc", t, w,
                   precision="highest")
    return x - jnp.einsum("bnp,bpc->bnc", v, w, precision="highest")


def _qr_body(a: jax.Array, panel: int):
    """Blocked reduced QR of [bb, n, k] (f32), sign-fixed diagonal.

    Returns (Q [bb, n, kn], R [bb, kn, k]) with kn = min(n, k) — the
    reduced-QR shapes, so wide panels (n < k, e.g. high-order Chebyshev
    leaf bases) get the upper-trapezoidal R jnp.linalg.qr would produce.
    """
    bb, n, k = a.shape
    kn = min(n, k)
    rows = jax.lax.broadcasted_iota(jnp.int32, (1, n, 1), 1)

    factors = []                              # per panel: (V, T)
    for s in range(0, kn, panel):
        pw = min(panel, kn - s)
        pan_cols = jax.lax.broadcasted_iota(jnp.int32, (1, 1, pw), 2)
        pan_rows = jax.lax.broadcasted_iota(jnp.int32, (1, pw, 1), 1)

        def reflect(j, carry, s=s, pan_cols=pan_cols, pan_rows=pan_rows):
            """Householder step j of the panel.  A loop (not unrolled) with
            column j picked by mask: the batched matrix-vector products
            are broadcast-multiply + reduce, which Mosaic lowers."""
            p, v_pan, t_pan = carry      # [bb, n, pw] x2, [bb, pw, pw]
            jj = s + j
            here = pan_cols == j
            col = jnp.sum(jnp.where(here, p, 0.0), axis=2, keepdims=True)
            x = jnp.where(rows >= jj, col, 0.0)                   # [bb, n, 1]
            sigma = jnp.sqrt(jnp.sum(x * x, axis=1, keepdims=True))
            xj = jnp.sum(jnp.where(rows == jj, x, 0.0), axis=1, keepdims=True)
            alpha = -jnp.where(xj >= 0.0, 1.0, -1.0) * sigma       # [bb, 1, 1]
            v = x - alpha * (rows == jj)
            vnorm = jnp.sqrt(jnp.sum(v * v, axis=1, keepdims=True))
            v = jnp.where(vnorm > 1e-30, v / jnp.maximum(vnorm, 1e-30), 0.0)
            # apply H = I - 2 v v^T to the panel columns (VPU)
            p = p - v * (2.0 * jnp.sum(v * p, axis=1, keepdims=True))
            # grow T: T[:j, j] = -2 T[:j,:j] (V[:,:j]^T v); T[j, j] = 2.
            # Columns >= j of V and rows >= j of T are still zero, so the
            # full-width products leave tcol[j:] = 0
            vtv = jnp.sum(v_pan * v, axis=1, keepdims=True)       # [bb, 1, pw]
            tcol = -2.0 * jnp.sum(t_pan * vtv, axis=2, keepdims=True)
            tcol = tcol + 2.0 * (pan_rows == j)                   # [bb, pw, 1]
            return (p, jnp.where(here, v, v_pan),
                    jnp.where(here, tcol, t_pan))

        zero = jnp.zeros((bb, n, pw), jnp.float32)
        p, v_pan, t_pan = jax.lax.fori_loop(
            0, pw, reflect,
            (a[:, :, s:s + pw], zero, jnp.zeros((bb, pw, pw), jnp.float32)))
        # trailing update with the aggregated panel (two GEMMs -> MXU):
        # A_tr <- (H_{pw-1}..H_0) A_tr = (I - V T^T V^T) A_tr
        parts = [a[:, :, :s], p]
        if s + pw < k:
            parts.append(_wy_apply(v_pan, t_pan, a[:, :, s + pw:],
                                   transpose_t=True))
        a = jnp.concatenate([x for x in parts if x.shape[2]], axis=2)
        factors.append((v_pan, t_pan))

    cols = jax.lax.broadcasted_iota(jnp.int32, (kn, k), 1)
    rws = jax.lax.broadcasted_iota(jnp.int32, (kn, k), 0)
    r = jnp.where(cols >= rws, a[:, :kn, :], 0.0)

    # Q = (I - V_0 T_0 V_0^T) ... (I - V_L T_L V_L^T) [I_kn; 0]
    q = jnp.broadcast_to(
        jnp.eye(n, kn, dtype=jnp.float32)[None], (bb, n, kn))
    for v_pan, t_pan in reversed(factors):
        q = _wy_apply(v_pan, t_pan, q, transpose_t=False)

    # sign-fix: non-negative R diagonal (unique factorization)
    # (the diagonal by mask, as a column for R's rows and a row for Q's
    # columns: Mosaic has no gather)
    diag = jnp.where(cols == rws, r, 0.0)
    r = r * jnp.where(jnp.sum(diag, axis=2, keepdims=True) < 0.0, -1.0, 1.0)
    q = q * jnp.where(jnp.sum(diag, axis=1, keepdims=True)[:, :, :kn] < 0.0,
                      -1.0, 1.0)
    return q, r


def _qr_kernel(a_ref, q_ref, r_ref, *, panel: int):
    q, r = _qr_body(a_ref[...].astype(jnp.float32), panel)
    q_ref[...] = q.astype(q_ref.dtype)
    r_ref[...] = r.astype(r_ref.dtype)


def _default_bb(nb: int, n: int) -> int:
    """Panels per grid step: batch small panels so contractions stay fat."""
    return max(1, min(nb, 512 // max(n, 1), 16))


@functools.partial(jax.jit, static_argnames=("panel", "bb"))
def batched_qr(a: jax.Array, *, panel: int = 8, bb: int | None = None):
    """A: [B, n, k] -> reduced (Q [B, n, kn], R [B, kn, k]), kn=min(n,k)."""
    nb, n, k = a.shape
    kn = min(n, k)
    if nb == 0 or k == 0 or n == 0:
        return (jnp.zeros((nb, n, kn), a.dtype),
                jnp.zeros((nb, kn, k), a.dtype))
    bb = bb or _default_bb(nb, n)
    pad = (-nb) % bb
    ap = jnp.concatenate(
        [a, jnp.zeros((pad, n, k), a.dtype)], axis=0) if pad else a
    nbp = nb + pad
    kern = functools.partial(_qr_kernel, panel=min(panel, kn))
    q, r = pallas_call(
        kern,
        grid=(nbp // bb,),
        in_specs=[pl.BlockSpec((bb, n, k), lambda b: (b, 0, 0))],
        out_specs=[
            pl.BlockSpec((bb, n, kn), lambda b: (b, 0, 0)),
            pl.BlockSpec((bb, kn, k), lambda b: (b, 0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((nbp, n, kn), a.dtype),
            jax.ShapeDtypeStruct((nbp, kn, k), a.dtype),
        ],
    )(ap)
    return q[:nb], r[:nb]
