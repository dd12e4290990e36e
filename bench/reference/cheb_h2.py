"""Plain Chebyshev H^2 operator: the semantics of the paper's construction.

An H^2 matrix built by Chebyshev interpolation (paper §5, §6.3) is, in exact
arithmetic, the block matrix

    A[t, s] = K(x_t, x_s)                          (t, s) a dense leaf pair
    A[t, s] = L_t(x_t) K(xi_t, xi_s) L_s(x_s)^T    (t, s) an admissible pair

where the pairs come from a balanced KD tree (median split on the widest
bounding-box side, stable order) and the dual-tree traversal with
``eta * |c_t - c_s| >= (diam_t + diam_s) / 2`` from level 1 down, ``xi_t``
is the tensor Chebyshev grid (first kind, ``p`` points per side) of node
t's bounding box, and ``L_t`` its Lagrange basis.  Nested bases (transfer
matrices) represent the same ``L_t`` exactly, so this reference applies
each level's interpolation directly and shares no code or data with the
program: it is built from the points and the kernel alone, in float64.
"""
from __future__ import annotations

import functools
import json
from typing import Callable, Iterator, List, Optional, Tuple

import numpy as np


def kd_tree(points: np.ndarray, leaf: int):
    """Balanced KD tree: ``(perm, lo, hi)``; ``lo[l]``/``hi[l]`` are the
    bounding boxes ``[2**l, dim]`` of level l in permuted order."""
    n, dim = points.shape
    depth = int(round(np.log2(n // leaf)))
    if leaf << depth != n:
        raise ValueError(f"N={n} is not leaf={leaf} times a power of two")
    perm = np.arange(n)
    for l in range(depth):
        idx = perm.reshape(1 << l, -1)
        sub = points[idx]
        axis = (sub.max(1) - sub.min(1)).argmax(-1)
        key = np.take_along_axis(sub, axis[:, None, None], 2)[..., 0]
        order = np.argsort(key, axis=1, kind="stable")
        perm = np.take_along_axis(idx, order, 1).ravel()
    pts = points[perm]
    lo = [pts.reshape(1 << l, -1, dim).min(1) for l in range(depth + 1)]
    hi = [pts.reshape(1 << l, -1, dim).max(1) for l in range(depth + 1)]
    return perm, lo, hi


def block_pairs(lo, hi, eta: float, min_level: int = 1):
    """Dual-tree traversal: per level the admissible ``(rows, cols)`` and
    the dense leaf pairs left at the bottom."""
    depth = len(lo) - 1
    far: List[Tuple[np.ndarray, np.ndarray]] = []
    ft = fs = np.zeros(1, np.int64)
    for l in range(depth + 1):
        adm = np.zeros(ft.shape, bool)
        if l >= min_level:
            c = 0.5 * (lo[l] + hi[l])
            d = np.linalg.norm(hi[l] - lo[l], axis=-1)
            adm = eta * np.linalg.norm(c[ft] - c[fs], axis=-1) >= \
                0.5 * (d[ft] + d[fs])
        far.append((ft[adm], fs[adm]))
        ft, fs = ft[~adm], fs[~adm]
        if l < depth:
            ft = np.stack([2 * ft, 2 * ft, 2 * ft + 1, 2 * ft + 1], 1).ravel()
            fs = np.stack([2 * fs, 2 * fs + 1, 2 * fs, 2 * fs + 1], 1).ravel()
    return far, (ft, fs)


def cheb_points(p: int) -> np.ndarray:
    """Chebyshev points of the first kind mapped to [0, 1]."""
    return 0.5 * (np.cos((2 * np.arange(p) + 1) * np.pi / (2 * p)) + 1.0)


def box_grid(p: int, lo: np.ndarray, hi: np.ndarray) -> np.ndarray:
    """Tensor Chebyshev grids of boxes ``[nb, dim]`` -> ``[nb, p**dim, dim]``
    (first coordinate slowest)."""
    nb, dim = lo.shape
    ax = lo[:, :, None] + (hi - lo)[:, :, None] * cheb_points(p)  # [nb,d,p]
    grids = np.meshgrid(*[np.arange(p)] * dim, indexing="ij")
    return np.stack([ax[:, d, g.ravel()] for d, g in enumerate(grids)], -1)


def box_lagrange(p: int, lo: np.ndarray, hi: np.ndarray,
                 pts: np.ndarray) -> np.ndarray:
    """Tensor Lagrange basis of each box at its points.

    ``lo``/``hi``: ``[nb, dim]``; ``pts``: ``[nb, w, dim]`` -> ``[nb, w,
    p**dim]``.  A flat side (zero width) weighs its p coincident nodes
    equally.
    """
    nb, w, dim = pts.shape
    t = 2.0 * cheb_points(p) - 1.0
    out = np.ones((nb, w, 1))
    for d in range(dim):
        width = (hi[:, d] - lo[:, d])[:, None]
        xr = 2.0 * (pts[..., d] - lo[:, None, d]) / np.where(
            width > 0, width, 1.0) - 1.0
        ld = np.ones((nb, w, p))
        for j in range(p):
            for q in range(p):
                if q != j:
                    ld[..., j] *= (xr - t[q]) / (t[j] - t[q])
        ld = np.where((width > 0)[..., None], ld, 1.0 / p)
        out = (out[..., :, None] * ld[..., None, :]).reshape(nb, w, -1)
    return out


class ChebH2:
    """The Chebyshev H^2 operator of ``kernel`` on ``points`` (float64)."""

    def __init__(self, points: np.ndarray, kernel: Callable, leaf: int,
                 p: int, eta: float, min_level: int = 1):
        self.points = np.asarray(points, np.float64)
        self.kernel, self.leaf, self.p = kernel, leaf, p
        self.perm, self.lo, self.hi = kd_tree(self.points, leaf)
        self.pts = self.points[self.perm]
        self.far, self.dense = block_pairs(self.lo, self.hi, eta, min_level)
        self.depth = len(self.lo) - 1

    @property
    def n(self) -> int:
        return self.points.shape[0]

    def lagrange(self, l: int) -> np.ndarray:
        nn = 1 << l
        return box_lagrange(self.p, self.lo[l], self.hi[l],
                            self.pts.reshape(nn, -1, self.pts.shape[1]))

    def coupling_blocks(self, l: int, keep: Optional[np.ndarray] = None,
                        chunk: int = 2048
                        ) -> Iterator[Tuple[np.ndarray, np.ndarray,
                                            np.ndarray]]:
        """``(rows, cols, S)`` chunks of level l, ``S = K(xi_t, xi_s)``;
        ``keep`` (mask over the level's nodes) selects the block rows."""
        rows, cols = self.far[l]
        if keep is not None:
            rows, cols = rows[keep[rows]], cols[keep[rows]]
        if rows.size == 0:
            return
        grid = box_grid(self.p, self.lo[l], self.hi[l])
        for a in range(0, rows.size, chunk):
            r, c = rows[a:a + chunk], cols[a:a + chunk]
            yield r, c, self.kernel(grid[r][:, :, None, :],
                                    grid[c][:, None, :, :])

    def dense_blocks(self, keep: Optional[np.ndarray] = None,
                     chunk: int = 512):
        """``(rows, cols, B)`` chunks of the dense leaf blocks; ``keep``
        (mask over leaves) selects the block rows."""
        rows, cols = self.dense
        if keep is not None:
            rows, cols = rows[keep[rows]], cols[keep[rows]]
        m, dim = self.leaf, self.pts.shape[1]
        leaves = self.pts.reshape(-1, m, dim)
        for a in range(0, rows.size, chunk):
            r, c = rows[a:a + chunk], cols[a:a + chunk]
            yield r, c, self.kernel(leaves[r][:, :, None, :],
                                    leaves[c][:, None, :, :])

    def apply(self, x: np.ndarray, row_nodes: Optional[np.ndarray] = None
              ) -> np.ndarray:
        """``A @ x`` in float64, ``x`` ``[N, nv]`` in the points' order.

        ``row_nodes``: optional boolean mask over leaves; only rows in
        those leaves are computed (others are returned as 0).
        """
        x = np.asarray(x, np.float64)
        nv = x.shape[1]
        xt = x[self.perm]
        y = np.zeros_like(xt)
        for l in range(self.depth + 1):
            rows, _ = self.far[l]
            if rows.size == 0:
                continue
            nn = 1 << l
            keep = None
            if row_nodes is not None:
                keep = row_nodes.reshape(nn, -1).any(1)
                if not keep[rows].any():
                    continue
            lag = self.lagrange(l)
            w = np.einsum("nwk,nwv->nkv", lag, xt.reshape(nn, -1, nv))
            z = np.zeros_like(w)
            for r, c, s in self.coupling_blocks(l, keep):
                np.add.at(z, r, np.einsum("bij,bjv->biv", s, w[c]))
            y += np.einsum("nwk,nkv->nwv", lag, z).reshape(-1, nv)
        m = self.leaf
        xl = xt.reshape(-1, m, nv)
        yl = y.reshape(-1, m, nv)
        for r, c, b in self.dense_blocks(row_nodes):
            np.add.at(yl, r, np.einsum("bij,bjv->biv", b, xl[c]))
        out = np.empty_like(y)
        out[self.perm] = y
        return out


@functools.lru_cache(maxsize=1)
def for_config(cfg_json: str) -> ChebH2:
    """The plain reference of a kernel-matrix configuration (its ``grid``,
    ``kernel``, ``leaf``, ``cheb_p``, ``eta``), built once per process."""
    from .grids import regular_grid
    from .kernels import by_name
    cfg = json.loads(cfg_json)
    return ChebH2(regular_grid(cfg["grid"]), by_name(cfg["kernel"]),
                  cfg["leaf"], cfg["cheb_p"], cfg["eta"])
