"""Plain reference of the §6.4 discretized operator h^2 (D + K + C).

Built from the configuration alone (grid, kernel, Chebyshev settings), in
float64 on the host:

  K  the Chebyshev H^2 operator of the fractional kernel on the n x n
     interior grid (``cheb_h2.ChebH2``: the semantics of the program's
     construction; its compression at ``h2_tol`` changes the solve's
     residual by under 1% of itself at n = 32, 64);
  D  row sums over the 3n x 3n extended grid of the positive kernel's
     Chebyshev H^2 operator, restricted to the interior (paper Eq. 10);
  C  the kappa-weighted 5-point ``-div kappa grad`` with zero Dirichlet
     halo, scaled by gamma = h^(-2 beta).

``device_apply(precision)`` puts the same blocks on the device in float32
for the precision control.
"""
from __future__ import annotations

import numpy as np

from .cheb_h2 import ChebH2
from .kernels import diffusivity, fractional
from .precision import einsum


def cell_grid(n: int, half_width: float) -> np.ndarray:
    """Cell-centred grid of spacing 2/n on [-half_width, half_width]^2."""
    h = 2.0 / n
    side = int(round(2 * half_width / h))
    ax = -half_width + h * (np.arange(side) + 0.5)
    xx, yy = np.meshgrid(ax, ax, indexing="ij")
    return np.stack([xx.ravel(), yy.ravel()], -1)


def stencil_apply(u: np.ndarray, kappa: np.ndarray, h: float, xp=np):
    """``-div kappa grad`` on ``u [n, n, nv]``: face averages of the
    edge-padded kappa, u = 0 outside the grid."""
    up = xp.pad(u, ((1, 1), (1, 1), (0, 0)))
    kp = np.pad(kappa, 1, mode="edge")
    c = kp[1:-1, 1:-1]
    faces = [(0.5 * (c + kp[2:, 1:-1]), up[2:, 1:-1]),
             (0.5 * (c + kp[:-2, 1:-1]), up[:-2, 1:-1]),
             (0.5 * (c + kp[1:-1, 2:]), up[1:-1, 2:]),
             (0.5 * (c + kp[1:-1, :-2]), up[1:-1, :-2])]
    lap = sum(f[..., None] * (nb - u) for f, nb in faces)
    return -lap / (h * h)


class FractionalReference:
    """h^2 (D + K + C) of one configuration, in float64."""

    def __init__(self, cfg: dict):
        n = cfg["n"]
        beta = cfg["kernel"]["beta"]
        self.n, self.h = n, 2.0 / n
        self.gamma = self.h ** (-2.0 * beta)
        pts = cell_grid(n, 1.0)
        self.kappa = diffusivity(pts).reshape(n, n)
        self.K = ChebH2(pts, fractional(beta, -1.0), cfg["leaf"],
                        cfg["cheb_p"], cfg["eta"])
        ext = cell_grid(n, 3.0)
        inside = (np.abs(ext[:, 0]) < 1.0) & (np.abs(ext[:, 1]) < 1.0)
        khat = ChebH2(ext, fractional(beta, +1.0), cfg["ext_leaf"],
                      cfg["cheb_p"], cfg["eta"])
        rows = inside[khat.perm].reshape(-1, cfg["ext_leaf"]).any(1)
        self.d = khat.apply(np.ones((ext.shape[0], 1)),
                            row_nodes=rows)[inside, 0]

    def apply(self, u: np.ndarray) -> np.ndarray:
        """``A u`` for ``u [n*n, nv]`` in grid order."""
        n, h = self.n, self.h
        cu = stencil_apply(u.reshape(n, n, -1), self.kappa, h)
        return h * h * (self.d[:, None] * u + self.K.apply(u)
                        + self.gamma * cu.reshape(n * n, -1))

    def residual(self, b: np.ndarray, x: np.ndarray) -> np.ndarray:
        """``||b - A x|| / ||b||`` per column of ``b``, ``x`` ``[n*n, nv]``."""
        b = np.asarray(b, np.float64)
        r = b - self.apply(np.asarray(x, np.float64))
        return np.linalg.norm(r, axis=0) / np.linalg.norm(b, axis=0)

    def device_apply(self, precision: str):
        """The same operator on the device in float32, every product at
        ``precision`` (the control's lower precision)."""
        import jax
        import jax.numpy as jnp

        n, h, gamma = self.n, self.h, self.gamma
        k = self.K
        f32 = jnp.float32
        levels = []
        for l in range(k.depth + 1):
            if k.far[l][0].size == 0:
                continue
            blocks = list(k.coupling_blocks(l))
            levels.append((l, jnp.asarray(k.lagrange(l), f32),
                           jnp.asarray(np.concatenate([b[0] for b in blocks])),
                           jnp.asarray(np.concatenate([b[1] for b in blocks])),
                           jnp.asarray(np.concatenate([b[2] for b in blocks]),
                                       f32)))
        dense = list(k.dense_blocks())
        drows = jnp.asarray(np.concatenate([b[0] for b in dense]))
        dcols = jnp.asarray(np.concatenate([b[1] for b in dense]))
        dblk = jnp.asarray(np.concatenate([b[2] for b in dense]), f32)
        perm = jnp.asarray(k.perm)
        inv = jnp.asarray(np.argsort(k.perm))
        d = jnp.asarray(self.d, f32)
        kappa = self.kappa
        ein = einsum(precision)
        m = k.leaf

        def k_apply(x):
            nv = x.shape[1]
            xt = x[perm]
            y = jnp.zeros_like(xt)
            for l, lag, rows, cols, s in levels:
                nn = 1 << l
                w = ein("nwk,nwv->nkv", lag, xt.reshape(nn, -1, nv))
                z = jax.ops.segment_sum(ein("bij,bjv->biv", s, w[cols]),
                                        rows, nn)
                y = y + ein("nwk,nkv->nwv", lag, z).reshape(-1, nv)
            yd = jax.ops.segment_sum(
                ein("bij,bjv->biv", dblk, xt.reshape(-1, m, nv)[dcols]),
                drows, xt.shape[0] // m)
            return (y + yd.reshape(-1, nv))[inv]

        def apply(u):
            cu = stencil_apply(u.reshape(n, n, -1), kappa, h, xp=jnp)
            return h * h * (d[:, None] * u + k_apply(u)
                            + gamma * cu.reshape(n * n, -1))

        return jax.jit(apply)
