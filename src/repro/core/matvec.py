"""H^2 matrix-(multi)vector product: upsweep, coupling multiply, downsweep.

Single-device version (paper §3, Algorithms 1/4/6).  Every tree level is one
batched contraction.  The block-sparse phases (coupling, dense leaves) are
*single-dispatch*: the construction-time marshaling plan (DESIGN.md §3.5)
lays every level out as conflict-free ``rows x maxb`` slots, so each phase
is one gather of the source vectors followed by ONE batched GEMM whose
contraction axis folds the per-row slot reduction — no scatter-add anywhere
in the hot path.  Hand-built data without a plan falls back to the seed
gather -> batched GEMM -> segment-sum pipeline (kept as the reference).

``backend`` selects the batched-GEMM implementation:
  - "jnp":    jnp.einsum (XLA batched dot) — default, used on CPU
  - "pallas": Pallas TPU kernels; the block-sparse phases use the
              gather-fused scalar-prefetch kernel (kernels/coupling_mv.py)
              reading S straight from its natural layout, the dense
              contractions use kernels/batched_gemm.py.  On CPU both run
              in interpret mode (tests only).
"""
from __future__ import annotations

import functools
from typing import List, Optional

import jax
import jax.numpy as jnp

from repro.obs.trace import phase

from .structure import H2Data, H2Shape


def _bgemm(a: jax.Array, b: jax.Array, backend: str) -> jax.Array:
    """Batched [B,m,k] @ [B,k,n] -> [B,m,n]."""
    if backend == "pallas":
        from repro.kernels import ops as kops
        return kops.batched_gemm(a, b)
    return jnp.einsum("bmk,bkn->bmn", a, b, precision="highest")


def upsweep(shape: H2Shape, data: H2Data, x_leaves: jax.Array,
            backend: str = "jnp") -> List[jax.Array]:
    """xhat[l] = V^T x at every level.  x_leaves: [2**depth, m, nv]."""
    depth = shape.depth
    xhat: List[Optional[jax.Array]] = [None] * (depth + 1)
    # leaf: xhat^q = V^T x  ([2**q, k, nv])
    xhat[depth] = _bgemm(jnp.swapaxes(data.v_leaf, -1, -2), x_leaves, backend)
    for l in range(depth, 0, -1):
        kl, klm1 = shape.ranks[l], shape.ranks[l - 1]
        nn = shape.nodes(l)
        # children-to-parent: xhat^{l-1}_t = sum_c F_c^T xhat^l_c
        nv = xhat[l].shape[-1]
        ft = jnp.swapaxes(data.f[l], -1, -2)          # [2**l, k_{l-1}, k_l]
        contrib = _bgemm(ft, xhat[l], backend)        # [2**l, k_{l-1}, nv]
        # explicit nv (not -1): k_{l-1} may be 0 above the coupling levels
        xhat[l - 1] = contrib.reshape(nn // 2, 2, klm1, nv).sum(axis=1)
    return xhat


def marshaled_multiply(blocks_mar: jax.Array, x: jax.Array,
                       col: jax.Array, backend: str = "jnp") -> jax.Array:
    """One marshaled block-sparse MV: ``y_r = sum_j B[r, j] x[col[r, j]]``.

    ``blocks_mar``: [rows, k1, maxb*k2] row-marshaled blocks (zero padding),
    ``x``: [nodes, k2, nv] source vectors, ``col``: [rows*maxb] slot plan.
    The slot reduction rides the GEMM contraction — single dispatch, no
    scatter.  Shared by the single-device matvec, the per-device phases in
    ``core.dist``, and the sketch sampler.
    """
    rows, k1, mk2 = blocks_mar.shape
    nv = x.shape[-1]
    xg = jnp.take(x, col, axis=0).reshape(rows, mk2, nv)
    return _bgemm(blocks_mar, xg, backend)


def coupling_multiply(shape: H2Shape, data: H2Data,
                      xhat: List[jax.Array], backend: str = "jnp"
                      ) -> List[jax.Array]:
    """yhat[l] = S^l xhat[l] — a block-sparse MV at every level.

    With a marshaling plan each level is a single dispatch: the jnp path
    contracts the row-marshaled ``s_mar`` against plan-gathered ``xhat``;
    the pallas path runs the gather-fused kernel on S's natural layout.
    """
    depth = shape.depth
    nv = xhat[depth].shape[-1]
    yhat: List[jax.Array] = []
    for l in range(depth + 1):
        nn = shape.nodes(l)
        kl = shape.ranks[l]
        if shape.coupling_counts[l] == 0 or kl == 0:
            yhat.append(jnp.zeros((nn, kl, nv), xhat[depth].dtype))
            continue
        if data.plan is None:
            # reference path: gather -> batched GEMM -> segmented scatter
            xs = jnp.take(xhat[l], data.s_cols[l], axis=0)   # [nb, k, nv]
            prod = _bgemm(data.s[l], xs, backend)            # [nb, k, nv]
            yhat.append(jax.ops.segment_sum(
                prod, data.s_rows[l], num_segments=nn,
                indices_are_sorted=True))
            continue
        if backend == "pallas" and kl > 0:
            from repro.kernels import ops as kops
            maxb = data.plan.sblk[l].shape[0] // nn
            yhat.append(kops.coupling_mv(
                data.s[l], xhat[l], data.plan.sblk[l], data.plan.scol[l],
                data.plan.scnt[l], maxb=maxb))
        else:
            yhat.append(marshaled_multiply(data.s_mar[l], xhat[l],
                                           data.plan.scol[l], backend))
    return yhat


def downsweep(shape: H2Shape, data: H2Data, yhat: List[jax.Array],
              backend: str = "jnp") -> jax.Array:
    """Accumulate yhat down the U tree; returns y_leaves [2**depth, m, nv]."""
    depth = shape.depth
    acc = yhat[0]
    for l in range(1, depth + 1):
        nn = shape.nodes(l)
        kl, klm1 = shape.ranks[l], shape.ranks[l - 1]
        # children += E_c @ parent
        par = jnp.repeat(acc, 2, axis=0)                     # [2**l, k_{l-1}, nv]
        acc = yhat[l] + _bgemm(data.e[l], par, backend)      # [2**l, k_l, nv]
    return _bgemm(data.u_leaf, acc, backend)                 # [2**q, m, nv]


def dense_multiply(shape: H2Shape, data: H2Data, x_leaves: jax.Array,
                   backend: str = "jnp") -> jax.Array:
    """A_de x — block-sparse MV over the dense leaves (single dispatch)."""
    if shape.dense_count == 0:
        return jnp.zeros_like(x_leaves)
    if data.plan is None:
        xs = jnp.take(x_leaves, data.d_cols, axis=0)         # [nbd, m, nv]
        prod = _bgemm(data.dense, xs, backend)
        return jax.ops.segment_sum(prod, data.d_rows,
                                   num_segments=shape.n_leaves,
                                   indices_are_sorted=True)
    if backend == "pallas":
        from repro.kernels import ops as kops
        maxb = data.plan.dblk.shape[0] // shape.n_leaves
        return kops.coupling_mv(data.dense, x_leaves, data.plan.dblk,
                                data.plan.dcol, data.plan.dcnt, maxb=maxb)
    return marshaled_multiply(data.dense_mar, x_leaves, data.plan.dcol,
                              backend)


@functools.partial(jax.jit, static_argnames=("shape", "backend"))
def h2_matvec(shape: H2Shape, data: H2Data, x: jax.Array,
              backend: str = "jnp") -> jax.Array:
    """y = A x with A = A_de + <U,S,V^T>;  x: [N, nv] in tree order."""
    nv = x.shape[-1]
    x_leaves = x.reshape(shape.n_leaves, shape.leaf_size, nv)
    with phase("hgemv/upsweep"):
        xhat = upsweep(shape, data, x_leaves, backend)
    with phase("hgemv/coupling-gemm"):
        yhat = coupling_multiply(shape, data, xhat, backend)
    with phase("hgemv/downsweep"):
        y_lr = downsweep(shape, data, yhat, backend)
    with phase("hgemv/dense"):
        y_de = dense_multiply(shape, data, x_leaves, backend)
    return (y_lr + y_de).reshape(shape.n, nv)


def h2_matvec_flops(shape: H2Shape, nv: int) -> int:
    """Model FLOPs of one HGEMV (2*m*n*k per GEMM) — roofline numerator."""
    fl = 0
    m, q = shape.leaf_size, shape.depth
    kq = shape.ranks[q]
    fl += 2 * shape.n_leaves * m * kq * nv * 2          # leaf V^T x and U yhat
    for l in range(1, q + 1):
        fl += 2 * shape.nodes(l) * shape.ranks[l] * shape.ranks[l - 1] * nv * 2
    for l in range(q + 1):
        fl += 2 * shape.coupling_counts[l] * shape.ranks[l] ** 2 * nv
    fl += 2 * shape.dense_count * m * m * nv
    return fl
