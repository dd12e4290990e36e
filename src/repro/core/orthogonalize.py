"""Basis orthogonalization (paper §5.2, last paragraphs).

Upsweep of batched QR: leaf bases are QR-factorized; at inner levels the
stacked (R_child @ E_child) pairs are QR-factorized to produce orthonormal
transfer matrices.  The per-level R factors re-express the coupling blocks:
``S'_ts = Ru_t @ S_ts @ Rv_s^T``.

After this pass, ``V^l_s{}^T V^l_s = I`` at every level — the precondition of
the compression downsweep (paper Eq. 4).
"""
from __future__ import annotations

import dataclasses
import functools
from typing import List, Tuple

import jax
import jax.numpy as jnp

from repro.obs.trace import phase

from .structure import H2Data, H2Shape, remarshal


def _batched_qr(a: jax.Array, backend: str) -> Tuple[jax.Array, jax.Array]:
    from repro.kernels.ops import backend_qr
    return backend_qr(a, backend)


def orthogonalize_tree(leaf: jax.Array, transfers: List[jax.Array],
                       backend: str = "jnp"
                       ) -> Tuple[jax.Array, List[jax.Array], List[jax.Array]]:
    """Orthogonalize one basis tree.

    Returns (new_leaf, new_transfers, r_factors) where ``r_factors[l]`` maps
    the old rank-k_l coordinates to the new orthonormal ones: old = new @ R.
    """
    depth = len(transfers) - 1
    r: List[jax.Array] = [None] * (depth + 1)
    q_leaf, r[depth] = _batched_qr(leaf, backend)          # [2**q, m, k] -> Q, R
    new_tr: List[jax.Array] = [transfers[0]] + [None] * depth
    for l in range(depth, 0, -1):
        e = transfers[l]                                    # [2**l, k_l, k_{l-1}]
        re = jnp.einsum("crk,ckp->crp", r[l], e,            # R_c @ E_c
                        precision="highest")
        nn = e.shape[0]
        kl = re.shape[1]
        klm1 = re.shape[2]
        stacked = re.reshape(nn // 2, 2 * kl, klm1)         # [2**{l-1}, 2k_l, k_{l-1}]
        q, rr = _batched_qr(stacked, backend)               # Q: [.., 2k_l, r'], R: [.., r', k_{l-1}]
        rp = q.shape[-1]
        new_tr[l] = q.reshape(nn, kl, rp)
        r[l - 1] = rr
    return q_leaf, new_tr, r


def _orthogonalize_impl(shape: H2Shape, data: H2Data, backend: str,
                        aliased: bool) -> H2Data:
    """Trace-level body shared by the public wrapper and the fused
    compression pipeline (``compression._orthogonalize_weights``).

    ``aliased`` must be decided on *concrete* data before tracing: inside a
    jit the two trees flatten to distinct tracers, so an ``is`` check here
    would silently factor the symmetric tree twice.
    """
    with phase("compress/orthogonalize"):
        u_leaf, e_new, ru = orthogonalize_tree(data.u_leaf, data.e, backend)
        if aliased and shape.symmetric:
            v_leaf, f_new, rv = u_leaf, e_new, ru
        else:
            v_leaf, f_new, rv = orthogonalize_tree(data.v_leaf, data.f,
                                                   backend)

    s_new = []
    with phase("compress/project-s"):
        for l in range(shape.depth + 1):
            if shape.coupling_counts[l] == 0:
                s_new.append(jnp.zeros((0, ru[l].shape[-2],
                                        rv[l].shape[-2]),
                                       data.u_leaf.dtype))
                continue
            rl = jnp.take(ru[l], data.s_rows[l], axis=0)    # [nb, k', k]
            rr = jnp.take(rv[l], data.s_cols[l], axis=0)
            s_new.append(jnp.einsum("bij,bjk,blk->bil", rl, data.s[l], rr,
                                    precision="highest"))
    # structure (and therefore the plan) is unchanged; S values are new,
    # so the marshaled buffers are regathered from the plan
    return remarshal(H2Data(
        u_leaf=u_leaf, v_leaf=v_leaf, e=e_new, f=f_new, s=s_new,
        s_rows=list(data.s_rows), s_cols=list(data.s_cols),
        dense=data.dense, d_rows=data.d_rows, d_cols=data.d_cols,
        plan=data.plan, dense_mar=data.dense_mar), dense=False)


@functools.partial(jax.jit, static_argnames=("shape", "backend", "aliased"))
def _orthogonalize_jit(shape: H2Shape, data: H2Data, backend: str,
                       aliased: bool) -> H2Data:
    return _orthogonalize_impl(shape, data, backend, aliased)


def orthogonalize(shape: H2Shape, data: H2Data, backend: str = "jnp"
                  ) -> H2Data:
    """Orthogonalize both basis trees and update the coupling blocks."""
    aliased = bool(shape.symmetric and data.v_leaf is data.u_leaf)
    out = _orthogonalize_jit(shape, data, backend, aliased)
    if aliased:
        # the jit boundary returns distinct (equal-valued) buffers for the
        # two trees; restore the alias so downstream `is`-based symmetric
        # fast paths (compression sweeps) keep factoring one tree
        out = dataclasses.replace(out, v_leaf=out.u_leaf, f=out.e)
    return out
