"""Level-by-level randomized rangefinder -> nested H^2 bases.

Input: per-level block-row sketches ``Y_l[t] = A(t, F_l(t)) Omega`` (from
``sample.sample_block_rows`` or the black-box prober).  Output: an
orthonormal *nested* basis tree (leaf bases + transfer matrices) in the
``H2Data`` layout, with per-level ranks chosen from the sketch spectrum.

Construction is the upsweep dual of the recompression in
``core/compression.py``:

- leaf level: stack each leaf's restriction of every ancestor-level sketch
  side by side -> candidate ``B_i = [Y_depth|_i, ..., Y_lmin|_i]``; QR +
  SVD of the small R factor orders the columns by singular value, giving
  the truncated leaf basis ``U_i``.
- inner level ``l-1``: project the coarser-level sketch columns into the
  children's coordinates (``C = U^T B``), stack the two children, and QR/SVD
  again -> transfer matrices ``E`` (so the explicit bases stay orthonormal
  by construction) and the next level's projected sketches.

Rank selection is *eager* (host) from jitted singular-value probes — the
same split as ``compression.pick_ranks_by_tol``: the hot numerical loop
(QR/SVD/GEMM) is jittable batched device code; only the integer rank picks
run on the host, after which all shapes are static.

``backend="pallas"`` routes the QR through ``kernels/batched_qr.py`` (the
TPU Householder panel kernel), exactly like the orthogonalization path.
"""
from __future__ import annotations

import functools
from typing import Callable, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp


def _batched_qr(a: jax.Array, backend: str) -> Tuple[jax.Array, jax.Array]:
    from repro.kernels.ops import backend_qr
    return backend_qr(a, backend)


def _batched_svd(a: jax.Array, backend: str, **kw):
    from repro.kernels.ops import backend_svd
    return backend_svd(a, backend, **kw)


@functools.partial(jax.jit, static_argnames=("backend",))
def orthonormal_basis(b: jax.Array, backend: str = "jnp"
                      ) -> Tuple[jax.Array, jax.Array]:
    """Orthonormalize sketch stacks, columns ordered by singular value.

    b: [nn, rows, R] -> (basis [nn, rows, p], svals [nn, p]) with
    p = min(rows, R); ``basis[..., :k]`` is the best rank-k sketch basis.
    The QR/SVD hot loop rides the blocked-WY QR and parallel-Jacobi SVD
    kernels when ``backend="pallas"`` — the same pair the recompression
    upsweep dispatches.
    """
    q, r = _batched_qr(b, backend)
    u, s, _ = _batched_svd(r, backend)
    return jnp.einsum("nrp,npj->nrj", q, u, precision="highest"), s


@functools.partial(jax.jit, static_argnames=("backend",))
def sketch_spectrum(y: jax.Array, backend: str = "jnp") -> jax.Array:
    """Singular values of each node's sketch — the residual estimator.

    The trailing singular values of ``Y = A Omega`` estimate the trailing
    spectrum of the sampled block row (Halko/Martinsson/Tropp): if
    ``sigma_j(Y) > tol * scale`` for all j up to the sample budget, the
    sketch is *saturated* and more samples are needed.
    """
    r = _batched_qr(y, backend)[1]
    if backend == "pallas":
        # spectrum only: skip the U-orthonormality polish QR entirely
        return _batched_svd(r, backend, polish=False)[1]
    return jnp.linalg.svd(r, compute_uv=False)


def pick_rank(svals: jax.Array, thresh: float, cap: int) -> int:
    """max over nodes of #{sigma > thresh}, clamped to [1, cap] (host)."""
    k = int(jnp.max(jnp.sum(svals > thresh, axis=-1)))
    return max(1, min(k, cap))


@functools.partial(jax.jit, static_argnames=("rank",))
def _truncate_project(basis: jax.Array, b: jax.Array, rank: int
                      ) -> Tuple[jax.Array, jax.Array]:
    u = basis[..., :rank]
    return u, jnp.einsum("nwk,nwR->nkR", u, b, precision="highest")


def build_nested_bases(sketches: Sequence[Optional[jax.Array]],
                       leaf_size: int, tol: float, max_rank: int,
                       backend: str = "jnp"
                       ) -> Tuple[jax.Array, List[jax.Array], Tuple[int, ...]]:
    """Sketches -> (u_leaf [2**q, m, k_q], transfers e[0..q], ranks).

    ``sketches[l]`` is ``[2**l, w_l, r_l]`` (or None when level ``l`` has no
    coupling blocks).  Transfer conventions match ``core.structure.H2Data``:
    ``e[l]: [2**l, k_l, k_{l-1}]``, explicit ``U^{l-1}|_c = U_c^l E_c``.
    Levels above the topmost coupling level get rank 0 (zero-size
    transfers); the matvec sweeps carry zeros through them.
    """
    depth = len(sketches) - 1
    m = leaf_size

    # column budget per level, coarse-to-fine concat order (prefix = coarser)
    widths = [0 if sketches[l] is None else int(sketches[l].shape[-1])
              for l in range(depth + 1)]
    col_end = [sum(widths[:l + 1]) for l in range(depth + 1)]
    if col_end[depth] == 0:
        raise ValueError("no coupling levels to sketch")

    parts = [sketches[l].reshape(1 << depth, m, widths[l])
             for l in range(depth + 1) if widths[l]]
    b = jnp.concatenate(parts, axis=-1)                  # [2**q, m, R_q]

    basis, s = orthonormal_basis(b, backend)
    scale = float(s.max())
    thresh = tol * scale
    ranks = [0] * (depth + 1)
    ranks[depth] = pick_rank(s, thresh, min(max_rank, int(s.shape[-1])))
    u_leaf, c = _truncate_project(basis, b, ranks[depth])

    e: List[Optional[jax.Array]] = [None] * (depth + 1)
    e[0] = jnp.zeros((0, 0, 0), b.dtype)
    for l in range(depth, 0, -1):
        nn = 1 << l
        kl = ranks[l]
        r_par = col_end[l - 1]                           # columns of levels < l
        if r_par == 0:                                   # top of coupling range
            ranks[l - 1] = 0
            e[l] = jnp.zeros((nn, kl, 0), b.dtype)
            c = jnp.zeros((nn // 2, 0, 0), b.dtype)
            continue
        stack = c[:, :, :r_par].reshape(nn // 2, 2 * kl, r_par)
        basis, s = orthonormal_basis(stack, backend)
        cap = min(max_rank, 2 * kl, r_par)
        ranks[l - 1] = pick_rank(s, thresh, cap)
        g, c = _truncate_project(basis, stack, ranks[l - 1])
        e[l] = g.reshape(nn, kl, ranks[l - 1])
    return u_leaf, e, tuple(ranks)


def explicit_bases(u_leaf: jax.Array, e: Sequence[jax.Array]
                   ) -> List[jax.Array]:
    """Expand nested bases to explicit per-level bases (device analogue of
    ``core.reconstruct.explicit_bases``): exp[l]: [2**l, w_l, k_l]."""
    depth = len(e) - 1
    exp: List[Optional[jax.Array]] = [None] * (depth + 1)
    exp[depth] = u_leaf
    for l in range(depth, 0, -1):
        ue = jnp.einsum("cwk,ckp->cwp", exp[l], e[l], precision="highest")
        nn, w, kp = ue.shape
        exp[l - 1] = ue.reshape(nn // 2, 2 * w, kp)
    return exp
