"""Plain readings of an H^2 operator held as factors (an answer to check).

An operator the program produced (leaf bases, transfers, couplings, dense
leaves, their block lists) is read back to the host and used by plain
code only: explicit bases per level, ``A x`` for selected leaves, and the
projection identity of a recompression.  Nothing here runs the program.
"""
from __future__ import annotations

import numpy as np


def to_host(data) -> dict:
    """The factor arrays of ``data`` (any object with the ``H2Data``
    attributes) as numpy, values in float64."""
    f64 = lambda a: np.asarray(a, np.float64)  # noqa: E731
    return {"u_leaf": f64(data.u_leaf), "v_leaf": f64(data.v_leaf),
            "e": [f64(a) for a in data.e], "f": [f64(a) for a in data.f],
            "s": [f64(a) for a in data.s],
            "s_rows": [np.asarray(a) for a in data.s_rows],
            "s_cols": [np.asarray(a) for a in data.s_cols],
            "dense": f64(data.dense), "d_rows": np.asarray(data.d_rows),
            "d_cols": np.asarray(data.d_cols)}


def explicit_bases(u_leaf, e: list) -> list:
    """Explicit basis of every node, per level: ``B[l]`` ``[2**l, N >> l,
    r_l]`` from the leaf bases and the transfers (``U_parent`` restricted
    to child c is ``U_c E_c``)."""
    depth = len(e) - 1
    b = [None] * (depth + 1)
    b[depth] = np.asarray(u_leaf)
    for l in range(depth, 0, -1):
        child = np.einsum("nwr,nrp->nwp", b[l], e[l])
        b[l - 1] = child.reshape(child.shape[0] // 2, -1, child.shape[-1])
    return b


def apply_rows(h: dict, bases: list, x: np.ndarray, leaves: np.ndarray,
               ein=None, xp=np):
    """Rows of ``A x`` in the given leaves (tree order), ``x`` ``[N, nv]``.

    ``ein``/``xp``: the einsum and array namespace (float64 numpy by
    default; the controls pass jax.numpy at a lower precision).
    """
    ein = ein or np.einsum
    nv = x.shape[1]
    depth = len(bases) - 1
    m = h["dense"].shape[1]
    out = xp.zeros((leaves.size, m, nv))
    for l, b in enumerate(bases):
        rows, cols = h["s_rows"][l], h["s_cols"][l]
        if rows.size == 0 or b.shape[-1] == 0:
            continue
        nn = b.shape[0]
        anc = leaves >> (depth - l)                   # node of each leaf
        need = np.isin(rows, anc)
        w = ein("nwr,nwv->nrv", xp.asarray(b), x.reshape(nn, -1, nv))
        z = xp.zeros((nn,) + w.shape[1:])
        contrib = ein("bij,bjv->biv", xp.asarray(h["s"][l][need]),
                      w[cols[need]])
        z = _scatter_add(xp, z, rows[need], contrib)
        # rows of leaf q inside its level-l ancestor
        per = b.shape[1] // m
        part = b.reshape(nn, per, m, -1)[anc, leaves % (1 << (depth - l))]
        out = out + ein("qmr,qrv->qmv", xp.asarray(part), z[anc])
    pos = {int(q): i for i, q in enumerate(leaves)}
    need = np.isin(h["d_rows"], leaves)
    slot = np.asarray([pos[int(r)] for r in h["d_rows"][need]], np.int64)
    contrib = ein("bij,bjv->biv", xp.asarray(h["dense"][need]),
                  x.reshape(-1, m, nv)[h["d_cols"][need]])
    return _scatter_add(xp, out, slot, contrib).reshape(-1, nv)


def _scatter_add(xp, target, idx, vals):
    if xp is np:
        np.add.at(target, idx, vals)
        return target
    return target.at[idx].add(vals)


def projection_gap(h: dict, bases: list, ref, kernel, rng,
                   count: int) -> float:
    """Largest relative gap of ``S'_ts = M_t S_ts M_s^T`` over ``count``
    blocks per level drawn by ``rng`` (symmetric operators: ``M = B^T L``,
    with B the answer's explicit basis, L the Lagrange basis and
    ``S_ts = K(xi_t, xi_s)`` the coupling of the plain Chebyshev reference
    ``ref``, a ``cheb_h2.ChebH2``).  The identity holds for a recompression
    onto orthonormal bases; bases that are not, or couplings that are not
    the projection, both break it."""
    from .cheb_h2 import box_grid
    gap = 0.0
    for l, b in enumerate(bases):
        nb = h["s"][l].shape[0]
        if nb == 0 or b.shape[-1] == 0:
            continue
        mu = np.einsum("nwr,nwk->nrk", b, ref.lagrange(l))
        sel = rng.choice(nb, size=min(nb, count), replace=False)
        r, c = h["s_rows"][l][sel], h["s_cols"][l][sel]
        grid = box_grid(ref.p, ref.lo[l], ref.hi[l])
        s_ref = kernel(grid[r][:, :, None, :], grid[c][:, None, :, :])
        pred = np.einsum("bik,bkj,bsj->bis", mu[r], s_ref, mu[c])
        diff = np.linalg.norm(h["s"][l][sel] - pred, axis=(1, 2))
        scale = np.linalg.norm(pred, axis=(1, 2))
        gap = max(gap, float((diff / np.maximum(scale, 1e-300)).max()))
    return gap


def projected_couplings(h: dict, bases: list, ref, ein,
                        chunk: int = 4096) -> list:
    """Every coupling block of an answer as the reference's projection
    ``M_t S_ts M_s^T`` (see ``projection_gap``), its products by ``ein``
    on the device in float32: the answer's couplings as the plain
    reference computes them at that precision (the controls)."""
    import jax.numpy as jnp
    from .cheb_h2 import box_grid
    out = []
    for l, b in enumerate(bases):
        nb = h["s"][l].shape[0]
        if nb == 0 or b.shape[-1] == 0:
            out.append(h["s"][l])
            continue
        mu = jnp.asarray(np.einsum("nwr,nwk->nrk", b, ref.lagrange(l)),
                         jnp.float32)
        grid = box_grid(ref.p, ref.lo[l], ref.hi[l])
        r, c = h["s_rows"][l], h["s_cols"][l]
        level = []
        for a in range(0, nb, chunk):
            rr, cc = r[a:a + chunk], c[a:a + chunk]
            s_ref = jnp.asarray(ref.kernel(grid[rr][:, :, None, :],
                                           grid[cc][:, None, :, :]),
                                jnp.float32)
            level.append(np.asarray(ein("bis,bjs->bij", ein(
                "bik,bkj->bij", mu[rr], s_ref), mu[cc]), np.float64))
        out.append(np.concatenate(level))
    return out
