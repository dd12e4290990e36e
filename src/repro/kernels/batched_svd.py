"""Pallas TPU kernel: batched one-sided Jacobi SVD, Brent-Luk parallel order.

The paper's truncation phase runs KBLAS batched SVD on small ``k x k`` /
``2k x k`` blocks.  TPU adaptation: one-sided (Hestenes) Jacobi with the
Brent-Luk round-robin *parallel* ordering — instead of zeroing one Gram
entry at a time, every round rotates all ``floor(k/2)`` disjoint column
pairs at once: the pairs are selected with one-hot GEMMs, rotated on the
VPU, and scattered back with the same one-hots (``A <- A G``,
``V <- V G`` without forming ``G``).  A sweep is
``k-1`` rounds covering all pairs; sweeps repeat under a ``while_loop``
until the off-diagonal Gram norm drops below ``tol * ||A||_F^2`` (early
exit) or ``max_sweeps`` is reached — replacing the fixed 10-sweep loop of
the previous scalar-pair kernel.

Everything is branch-free and MXU-shaped: per round, the paired columns
are *selected* by one-hot matrices (built from the schedule by iota
comparison, no gathers), the rotation angles come from VPU column
reductions, and the final descending sort is a rank count applied as a
one-hot GEMM (Mosaic has no gather, scatter or sort).  Multiple matrices are
packed per grid step (``bb``) so the contractions keep an effective batch
when k is small.

One-sided Jacobi orthogonalizes the *columns* of A by right rotations:
``A -> A J``; at convergence ``A_fin = U diag(sigma)`` and ``J = V``, so

    U = A_fin / sigma,   sigma_i = ||A_fin[:, i]||,   V = J.

Gram-based Jacobi in f32 cannot resolve the mutual angles of columns whose
sigmas sit far below sigma_max (the recompression upsweep feeds graded
Chebyshev spectra with sigma ratios of 1e-7 and worse), leaving the small-
sigma U columns visibly non-orthogonal.  The truncation sweep consumes U
as an *orthonormal* basis, so by default the kernel output is polished
with one blocked-WY QR pass (``polish=True``): U columns become exactly
orthonormal while ``||A - U S V^T||`` stays O(eps * sigma_max), because a
column's QR correction is inversely proportional to the sigma it carries.

Returns (U [B,n,k], sigma [B,k], V^T [B,k,k]) with sigma sorted descending.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl

from . import pallas_call


def _brent_luk_schedule(m: int) -> np.ndarray:
    """Round-robin tournament pairing: [m-1 rounds, 2, m//2, 1] (p, q).

    Player 0 stays fixed, the rest rotate; every round pairs all m players
    into m/2 disjoint pairs, and m-1 rounds cover every pair exactly once.
    """
    assert m % 2 == 0
    arr = list(range(1, m))
    rounds = []
    for _ in range(m - 1):
        lineup = [0] + arr
        pairs = [(min(lineup[i], lineup[m - 1 - i]),
                  max(lineup[i], lineup[m - 1 - i])) for i in range(m // 2)]
        rounds.append(([p for p, _ in pairs], [q for _, q in pairs]))
        arr = arr[-1:] + arr[:-1]
    return np.asarray(rounds, np.int32)[..., None]   # [m-1, 2, m//2, 1]


def _bmm(x: jax.Array, y: jax.Array) -> jax.Array:
    """[b, n, i] @ [b, i, k] -> [b, n, k] at full f32 precision."""
    return jnp.einsum("bni,bik->bnk", x, y, precision="highest")


def _bmm_nt(x: jax.Array, y: jax.Array) -> jax.Array:
    """[b, n, k] @ [b, i, k]^T -> [b, n, i] at full f32 precision."""
    return jnp.einsum("bnk,bik->bni", x, y, precision="highest")


def _svd_kernel(sched_ref, a_ref, u_ref, s_ref, vt_ref, *,
                k: int, kn: int, max_sweeps: int, tol: float):
    # Every per-matrix quantity keeps singleton axes ([bb, 1, ke] rows,
    # [bb, ke, 1] columns): Mosaic lowers keepdims reductions and
    # broadcasts, not the relayouts a [bb, ke] vector would need.
    bb, n, ke = a_ref.shape
    hp = ke // 2
    rounds = sched_ref.shape[0]
    a0 = a_ref[...].astype(jnp.float32)
    # per-matrix Frobenius normalization: the convergence test becomes
    # scale-free and the Gram fourth powers cannot overflow f32
    fro = jnp.sqrt(jnp.sum(jnp.sum(a0 * a0, axis=2, keepdims=True),
                           axis=1, keepdims=True))                # [bb, 1, 1]
    scale = jnp.maximum(fro, 1e-30)
    a0 = a0 / scale
    sub = jax.lax.broadcasted_iota(jnp.int32, (1, ke, ke), 1)
    lane = jax.lax.broadcasted_iota(jnp.int32, (1, ke, ke), 2)
    eye = (sub == lane).astype(jnp.float32)                       # [1, ke, ke]
    v0 = jnp.broadcast_to(eye, (bb, ke, ke))
    col_iota = jax.lax.broadcasted_iota(jnp.int32, (1, hp, ke), 2)

    def round_step(r, carry):
        a, v = carry
        # round r's pairs as one-hot rows (the schedule is stored as
        # [hp, 1] columns so a ref read gives them in this orientation)
        ph = jnp.broadcast_to(
            (col_iota == sched_ref[r, 0][None]).astype(jnp.float32),
            (bb, hp, ke))
        qh = jnp.broadcast_to(
            (col_iota == sched_ref[r, 1][None]).astype(jnp.float32),
            (bb, hp, ke))
        # select the paired columns with one GEMM each (no gathers)
        ap, aq = _bmm_nt(a, ph), _bmm_nt(a, qh)                   # [bb, n, hp]
        app = jnp.sum(ap * ap, axis=1, keepdims=True)             # [bb, 1, hp]
        aqq = jnp.sum(aq * aq, axis=1, keepdims=True)
        apq = jnp.sum(ap * aq, axis=1, keepdims=True)
        # Jacobi rotation zeroing the (p,q) Gram entry
        tau = (aqq - app) / (2.0 * jnp.where(jnp.abs(apq) > 1e-30,
                                             apq, 1e-30))
        t = jnp.where(tau >= 0.0, 1.0, -1.0) / (
            jnp.abs(tau) + jnp.sqrt(1.0 + tau * tau))
        c = 1.0 / jnp.sqrt(1.0 + t * t)
        s = c * t
        rotate = jnp.abs(apq) > 1e-12 * jnp.sqrt(app * aqq + 1e-30)
        c = jnp.where(rotate, c, 1.0)
        s = jnp.where(rotate, s, 0.0)
        # every column sits in exactly one pair, so the rotated matrix is
        # the rotated pair columns scattered back by the same one-hots:
        #   a_p <- c a_p - s a_q,   a_q <- s a_p + c a_q
        vp, vq = _bmm_nt(v, ph), _bmm_nt(v, qh)
        return (_bmm(c * ap - s * aq, ph) + _bmm(s * ap + c * aq, qh),
                _bmm(c * vp - s * vq, ph) + _bmm(s * vp + c * vq, qh))

    def off_norms(a):
        """Per-matrix off-diagonal Gram norm, summed directly (a
        difference of fourth-power sums cancels catastrophically)."""
        gram = jnp.einsum("bnp,bnq->bpq", a, a, precision="highest")
        off = gram * (1.0 - eye)

        def total(x):
            return jnp.sum(jnp.sum(x, axis=2, keepdims=True), axis=1,
                           keepdims=True)                         # [bb, 1, 1]

        return total(off * off), total(gram * eye)

    def cond(carry):
        a, _, sweep = carry
        off_sq, total = off_norms(a)
        unconverged = jnp.max(jnp.where(off_sq > (tol * total) ** 2,
                                        1.0, 0.0))
        return jnp.logical_and(sweep < max_sweeps, unconverged > 0.0)

    def sweep_step(carry):
        a, v, sweep = carry
        a, v = jax.lax.fori_loop(0, rounds, round_step, (a, v))
        return a, v, sweep + 1

    a, v, _ = jax.lax.while_loop(cond, sweep_step, (a0, v0, 0))

    # sort descending by rank counting (no sort on the TPU); any pad
    # column (index >= k) goes last, ties keep their index order
    sig = jnp.sqrt(jnp.sum(a * a, axis=1, keepdims=True))         # [bb, 1, ke]
    key = jnp.where(lane[:, :1] < k, sig, -1.0)                   # i on lanes
    key_col = jnp.sum(eye * key, axis=2, keepdims=True)           # [bb, ke, 1]
    before = (key_col > key) | ((key_col == key) & (sub < lane))
    rank = jnp.sum(before.astype(jnp.float32), axis=1, keepdims=True)
    # permutation as one-hot matmul (keeps the data path gather-free):
    # pm[b, j, i] = 1 where column i moves to position j
    pm = (rank == sub.astype(jnp.float32)).astype(jnp.float32)
    a, v = _bmm_nt(a, pm), _bmm_nt(v, pm)
    sig = jnp.sqrt(jnp.sum(a * a, axis=1, keepdims=True))
    u = a / jnp.maximum(sig, 1e-30)
    sig = sig * scale                             # undo the normalization
    # reduced shapes (kn = min(n, k)), matching jnp.linalg.svd
    u_ref[...] = u[:, :, :kn].astype(u_ref.dtype)
    s_ref[...] = sig[:, :, :kn].astype(s_ref.dtype)
    vt_ref[...] = jnp.swapaxes(v, 1, 2)[:, :kn, :k].astype(vt_ref.dtype)


@functools.partial(jax.jit, static_argnames=("max_sweeps", "tol", "bb",
                                             "polish"))
def batched_svd(a: jax.Array, *, max_sweeps: int = 15, tol: float = 1e-6,
                bb: int | None = None, polish: bool = True):
    """A: [B, n, k] -> reduced (U [B,n,kn], sigma [B,kn], V^T [B,kn,k])
    with kn = min(n, k) and sigma descending — jnp.linalg.svd shapes."""
    from .batched_qr import _default_bb
    nb, n, k = a.shape
    kn = min(n, k)
    if nb == 0 or k == 0 or n == 0:
        return (jnp.zeros((nb, n, kn), a.dtype),
                jnp.zeros((nb, kn), a.dtype),
                jnp.zeros((nb, kn, k), a.dtype))
    ke = k + (k % 2)                           # pad to even player count
    bb = bb or _default_bb(nb, n)
    pad = (-nb) % bb
    ap = a
    if ke > k:
        ap = jnp.concatenate(
            [ap, jnp.zeros((nb, n, ke - k), a.dtype)], axis=2)
    if pad:
        ap = jnp.concatenate(
            [ap, jnp.zeros((pad, n, ke), a.dtype)], axis=0)
    nbp = nb + pad
    sched = jnp.asarray(_brent_luk_schedule(ke))
    kern = functools.partial(_svd_kernel, k=k, kn=kn,
                             max_sweeps=max_sweeps, tol=tol)
    # sigma travels as [B, 1, kn]: a block's last two dims then equal the
    # array's for any bb (a 2-D [bb, kn] block needs 8 | bb on the TPU)
    u, s, vt = pallas_call(
        kern,
        grid=(nbp // bb,),
        in_specs=[
            pl.BlockSpec(sched.shape, lambda b: (0, 0, 0, 0)),
            pl.BlockSpec((bb, n, ke), lambda b: (b, 0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((bb, n, kn), lambda b: (b, 0, 0)),
            pl.BlockSpec((bb, 1, kn), lambda b: (b, 0, 0)),
            pl.BlockSpec((bb, kn, k), lambda b: (b, 0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((nbp, n, kn), a.dtype),
            jax.ShapeDtypeStruct((nbp, 1, kn), a.dtype),
            jax.ShapeDtypeStruct((nbp, kn, k), a.dtype),
        ],
    )(sched, ap)
    u, s, vt = u[:nb], s[:nb, 0], vt[:nb]
    if polish:
        from .batched_qr import batched_qr
        u = batched_qr(u)[0]
    return u, s, vt
