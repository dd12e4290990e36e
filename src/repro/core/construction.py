"""Construct a concrete H^2 matrix from (points, kernel, admissibility).

Two construction paths share this entry point:

- ``method="cheb"`` (default) — the paper's path: cluster tree -> dual-tree
  traversal -> Chebyshev interpolation for the low-rank blocks, direct
  kernel evaluation for the dense leaves.  Runs on the host in numpy; the
  result is packaged as (H2Shape, H2Data-on-device).
- ``method="sketch"`` — the on-device randomized sketching path
  (``repro.sketch``): batched kernel-block sampling + nested-basis
  rangefinder, everything jitted device code.  Requires a jnp-traceable
  kernel (``kernels_fn`` factories with ``xp=jnp``); extra options go in
  ``sketch_opts`` (tol, max_rank, oversample, seed, chunk, backend).

The Chebyshev path's stages are host spans (``repro.obs.span``):
``construct/tree``, ``construct/admissibility``, ``construct/bases``,
``construct/couplings``, ``construct/dense`` and ``construct/plan`` (the
marshaling plan and the marshaled buffers).
"""
from __future__ import annotations

from typing import Callable, Optional, Tuple

import jax.numpy as jnp
import numpy as np

from repro.obs.trace import span

from .admissibility import BlockStructure, build_block_structure
from .chebyshev import (build_chebyshev_bases, build_coupling, build_dense)
from .clustering import ClusterTree, build_cluster_tree
from .structure import H2Data, H2Shape, build_coupling_plan, remarshal


def construct_h2(points: np.ndarray, kernel: Callable, leaf_size: int,
                 cheb_p: int, eta: float, dtype=jnp.float32,
                 min_level: int = 1, method: str = "cheb",
                 sketch_opts: Optional[dict] = None
                 ) -> Tuple[H2Shape, H2Data, ClusterTree, BlockStructure]:
    """Build an H^2 approximation of the kernel matrix K[i,j]=kernel(x_i,x_j).

    Returned matrix acts on vectors in *tree (permuted) order*; use
    ``tree.perm`` to map between orderings.
    """
    if method == "sketch":
        from repro.sketch.construct import sketch_construct
        return sketch_construct(points, kernel, leaf_size, eta,
                                min_level=min_level, dtype=dtype,
                                **(sketch_opts or {}))
    if method != "cheb":
        raise ValueError(f"unknown construction method {method!r}")
    with span("construct/tree"):
        tree = build_cluster_tree(points, leaf_size)
    with span("construct/admissibility"):
        bs = build_block_structure(tree, eta, min_level=min_level)
    dim = tree.dim
    k = cheb_p ** dim
    depth = tree.depth

    with span("construct/bases"):
        u_leaf_np, e_np = build_chebyshev_bases(tree, cheb_p)
        e_list = [jnp.zeros((0, 0, 0), dtype)]
        for l in range(1, depth + 1):
            e_list.append(jnp.asarray(e_np[l], dtype))
        u_leaf = jnp.asarray(u_leaf_np, dtype)

    with span("construct/couplings"):
        s_list, sr_list, sc_list = [], [], []
        for l in range(depth + 1):
            rows, cols = bs.s_rows[l], bs.s_cols[l]
            s_np = build_coupling(tree, cheb_p, l, rows, cols, kernel)
            s_list.append(jnp.asarray(s_np, dtype))
            sr_list.append(jnp.asarray(rows, jnp.int32))
            sc_list.append(jnp.asarray(cols, jnp.int32))

    with span("construct/dense"):
        dense = jnp.asarray(build_dense(tree, bs.d_rows, bs.d_cols, kernel),
                            dtype)

    with span("construct/plan"):
        plan = build_coupling_plan(depth, bs.s_rows, bs.s_cols,
                                   bs.d_rows, bs.d_cols)
        data = remarshal(H2Data(
            u_leaf=u_leaf, v_leaf=u_leaf,
            e=e_list, f=[x for x in e_list],
            s=s_list, s_rows=sr_list, s_cols=sc_list,
            dense=dense,
            d_rows=jnp.asarray(bs.d_rows, jnp.int32),
            d_cols=jnp.asarray(bs.d_cols, jnp.int32),
            plan=plan))

    shape = H2Shape(
        n=tree.n, leaf_size=leaf_size, depth=depth,
        ranks=tuple([k] * (depth + 1)),
        coupling_counts=bs.coupling_counts(),
        dense_count=int(bs.d_rows.shape[0]),
        symmetric=True,
        row_maxb=bs.row_maxb(), col_maxb=bs.col_maxb(),
        dense_maxb=int(plan.dblk.shape[0]) >> depth)
    return shape, data, tree, bs


def dense_reference(points: np.ndarray, kernel: Callable,
                    perm: np.ndarray) -> np.ndarray:
    """Exact dense kernel matrix in tree order (for small-N validation)."""
    p = points[perm] if perm is not None else points
    return kernel(p[:, None, :], p[None, :, :])
