"""Batched on-device kernel-block evaluation and sketching primitives.

This replaces the host ``build_dense`` / ``build_coupling`` loops of the
Chebyshev path with jitted, vmapped evaluation over the admissibility block
lists: every operation below is one batched device computation over all
blocks of a tree level (the marshaled-batch idiom the matvec already uses).

The central primitive is ``apply_kernel_blocks``: compute ``A_b @ B_b`` for
every block ``b = (t, s)`` of a level *without materializing* the ``[w, w]``
kernel blocks — the source axis is processed in static-size chunks inside a
``fori_loop``, so peak memory is ``nb * w * chunk`` regardless of ``w``.
Summing the per-block products by block row (``segment_sum``) yields the
randomized block-row sketch ``Y_t = sum_{s in F(t)} A(t,s) Omega_s``.

``kernel`` must be jnp-traceable (see ``core.kernels_fn`` with ``xp=jnp``);
it is closed over as a static jit argument.
"""
from __future__ import annotations

import functools
from typing import Callable

import jax
import jax.numpy as jnp


def _pad_chunks(x: jax.Array, axis: int, chunk: int, fill: str) -> jax.Array:
    """Pad ``axis`` up to a multiple of ``chunk``.

    ``fill="zero"`` pads with zeros (test matrices — padded columns
    contribute exactly 0); ``fill="edge"`` repeats the last slice (points —
    keeps kernel evaluations finite; their weight is a zero test row).
    """
    n = x.shape[axis]
    rem = (-n) % chunk
    if rem == 0:
        return x
    pad = [(0, 0)] * x.ndim
    pad[axis] = (0, rem)
    mode = "constant" if fill == "zero" else "edge"
    return jnp.pad(x, pad, mode=mode)


@functools.partial(jax.jit, static_argnames=("kernel", "chunk"))
def apply_kernel_blocks(xt: jax.Array, xs: jax.Array, b: jax.Array,
                        *, kernel: Callable, chunk: int = 256) -> jax.Array:
    """Per-block ``kernel(xt_b, xs_b) @ b_b`` without forming [w, w] blocks.

    xt: [nb, w, d] target points, xs: [nb, w, d] source points,
    b: [nb, w, r] per-block right-hand sides  ->  [nb, w, r].
    """
    nb, w, _ = xt.shape
    r = b.shape[-1]
    xs_p = _pad_chunks(xs, 1, chunk, "edge")
    b_p = _pad_chunks(b, 1, chunk, "zero")
    nchunks = xs_p.shape[1] // chunk

    def body(c, acc):
        xs_c = jax.lax.dynamic_slice_in_dim(xs_p, c * chunk, chunk, axis=1)
        b_c = jax.lax.dynamic_slice_in_dim(b_p, c * chunk, chunk, axis=1)
        kblk = kernel(xt[:, :, None, :], xs_c[:, None, :, :])   # [nb, w, chunk]
        return acc + jnp.einsum("bwc,bcr->bwr", kblk.astype(b.dtype), b_c,
                                precision="highest")

    y0 = jnp.zeros((nb, w, r), b.dtype)
    return jax.lax.fori_loop(0, nchunks, body, y0)


@functools.partial(jax.jit, static_argnames=("kernel", "chunk"))
def sample_block_rows(pts_lvl: jax.Array, s_rows: jax.Array,
                      s_cols: jax.Array, omega: jax.Array,
                      plan_blk: jax.Array = None, *,
                      kernel: Callable, chunk: int = 256) -> jax.Array:
    """Block-row sketches of one level's admissible far field.

    pts_lvl: [nn, w, d] per-node point sets (tree order reshaped),
    s_rows/s_cols: [nb] block lists (sorted by row), omega: [nn, w, r]
    per-node Gaussian test matrices -> Y: [nn, w, r] with
    ``Y[t] = sum_{b: row(b)=t} kernel(x_t, x_{s_b}) @ omega[s_b]``.

    When the construction's marshaling plan is passed (``plan_blk``: slot ->
    block with the padding sentinel nb, zeroed by the fill-mode gather) the
    block-row reduction is a gather into the conflict-free slot layout plus
    a dense reshape-sum — the same single-dispatch schedule as the matvec,
    no scatter.  Without a plan it falls back to ``segment_sum``.
    """
    nn = pts_lvl.shape[0]
    xt = jnp.take(pts_lvl, s_rows, axis=0)
    xs = jnp.take(pts_lvl, s_cols, axis=0)
    om = jnp.take(omega, s_cols, axis=0)
    y_b = apply_kernel_blocks(xt, xs, om, kernel=kernel, chunk=chunk)
    if plan_blk is None:
        return jax.ops.segment_sum(y_b, s_rows, num_segments=nn,
                                   indices_are_sorted=True)
    maxb = plan_blk.shape[0] // nn
    yg = jnp.take(y_b, plan_blk, axis=0, mode="fill", fill_value=0)
    return yg.reshape(nn, maxb, *y_b.shape[1:]).sum(axis=1)


@functools.partial(jax.jit, static_argnames=("kernel",))
def eval_dense_blocks(pts_leaf: jax.Array, d_rows: jax.Array,
                      d_cols: jax.Array, *, kernel: Callable) -> jax.Array:
    """All dense leaf blocks in one batched evaluation.

    pts_leaf: [2**depth, m, d] leaf point sets -> [nbd, m, m].
    """
    xt = jnp.take(pts_leaf, d_rows, axis=0)                     # [nbd, m, d]
    xs = jnp.take(pts_leaf, d_cols, axis=0)
    return kernel(xt[:, :, None, :], xs[:, None, :, :])


@functools.partial(jax.jit, static_argnames=("kernel", "chunk"))
def project_coupling_blocks(pts_lvl: jax.Array, s_rows: jax.Array,
                            s_cols: jax.Array, u_exp: jax.Array,
                            v_exp: jax.Array, *, kernel: Callable,
                            chunk: int = 256) -> jax.Array:
    """Coupling blocks ``S_b = U_t^T A(t,s) V_s`` for one level, batched.

    u_exp/v_exp: [nn, w, k] explicit (expanded) per-node bases.
    Computed as chunked ``A V`` followed by one batched GEMM -> [nb, k, k].
    """
    xt = jnp.take(pts_lvl, s_rows, axis=0)
    xs = jnp.take(pts_lvl, s_cols, axis=0)
    vs = jnp.take(v_exp, s_cols, axis=0)                        # [nb, w, k]
    av = apply_kernel_blocks(xt, xs, vs, kernel=kernel, chunk=chunk)
    ut = jnp.take(u_exp, s_rows, axis=0)                        # [nb, w, k]
    return jnp.einsum("bwk,bwj->bkj", ut, av, precision="highest")
