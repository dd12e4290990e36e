"""Exact kernel rows times a block, on the device in float32 (chunked).

``rows_apply(kernel, pts_rows, pts_all, x, precision)`` returns
``K(pts_rows, pts_all) @ x`` evaluating ``chunk`` kernel rows at a time, so
the N x N matrix is never formed.  ``kernel`` is a jax.numpy kernel of
``reference.kernels``; the points come from the configuration, not from
the program.
"""
from __future__ import annotations

import numpy as np


def rows_apply(kernel, pts_rows: np.ndarray, pts_all: np.ndarray, x,
               precision: str = "highest", chunk: int = 128):
    import jax
    import jax.numpy as jnp

    from .precision import einsum
    ein = einsum(precision)

    @jax.jit
    def strip(pr, pa, xx):
        return ein("rn,nv->rv", kernel(pr[:, None, :], pa[None, :, :]), xx)

    pa = jnp.asarray(pts_all, jnp.float32)
    outs = [strip(jnp.asarray(pts_rows[a:a + chunk], jnp.float32), pa, x)
            for a in range(0, pts_rows.shape[0], chunk)]
    return np.concatenate([np.asarray(o, np.float64) for o in outs])
