"""Construct a concrete H^2 matrix from (points, kernel, admissibility).

Two construction paths share this entry point:

- ``method="cheb"`` (default) — the paper's path: cluster tree -> dual-tree
  traversal -> Chebyshev interpolation for the low-rank blocks, direct
  kernel evaluation for the dense leaves.  Runs on the host in numpy
  (``construct_h2_host``, whose factors stay host arrays, as a
  distributed operator needs them: ``dist.distribute_h2`` places each
  block row on its own device); ``construct_h2`` then moves the whole
  operator to the default device once, with its marshaling plan.
- ``method="sketch"`` — the on-device randomized sketching path
  (``repro.sketch``): batched kernel-block sampling + nested-basis
  rangefinder, everything jitted device code.  Requires a jnp-traceable
  kernel (``kernels_fn`` factories with ``xp=jnp``); extra options go in
  ``sketch_opts`` (tol, max_rank, oversample, seed, chunk, backend).

The Chebyshev path's stages are host spans (``repro.obs.span``):
``construct/tree``, ``construct/admissibility``, ``construct/bases``,
``construct/couplings``, ``construct/dense`` and, in ``construct_h2``,
``construct/plan`` (the move to the device, the marshaling plan and the
marshaled buffers).
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
    shape, host, tree, bs = construct_h2_host(points, kernel, leaf_size,
                                              cheb_p, eta, dtype, min_level)
    with span("construct/plan"):
        u_leaf = jnp.asarray(host.u_leaf)
        e_list = [jnp.asarray(x) for x in host.e]
        plan = build_coupling_plan(shape.depth, bs.s_rows, bs.s_cols,
                                   bs.d_rows, bs.d_cols)
        data = remarshal(H2Data(
            u_leaf=u_leaf, v_leaf=u_leaf,
            e=e_list, f=[x for x in e_list],
            s=[jnp.asarray(x) for x in host.s],
            s_rows=[jnp.asarray(x) for x in host.s_rows],
            s_cols=[jnp.asarray(x) for x in host.s_cols],
            dense=jnp.asarray(host.dense),
            d_rows=jnp.asarray(host.d_rows),
            d_cols=jnp.asarray(host.d_cols),
            plan=plan))
    return shape, data, tree, bs


def construct_h2_host(points: np.ndarray, kernel: Callable, leaf_size: int,
                      cheb_p: int, eta: float, dtype=jnp.float32,
                      min_level: int = 1
                      ) -> Tuple[H2Shape, H2Data, ClusterTree, BlockStructure]:
    """The Chebyshev construction with every factor a host (numpy) array
    in ``dtype``: no device holds any of it, and the data has no
    marshaling plan (``plan=None``).  ``construct_h2`` is this plus one
    move to the device; ``dist.distribute_h2`` partitions it and places
    each block row on its own device."""
    dtype = np.dtype(dtype)
    with span("construct/tree"):
        tree = build_cluster_tree(points, leaf_size)
    with span("construct/admissibility"):
        bs = build_block_structure(tree, eta, min_level=min_level)
    dim = tree.dim
    k = cheb_p ** dim
    depth = tree.depth

    with span("construct/bases"):
        u_leaf_np, e_np = build_chebyshev_bases(tree, cheb_p)
        e_list = [np.zeros((0, 0, 0), dtype)]
        for l in range(1, depth + 1):
            e_list.append(np.asarray(e_np[l], dtype))
        u_leaf = np.asarray(u_leaf_np, dtype)

    with span("construct/couplings"):
        s_list, sr_list, sc_list = [], [], []
        for l in range(depth + 1):
            rows, cols = bs.s_rows[l], bs.s_cols[l]
            s_np = build_coupling(tree, cheb_p, l, rows, cols, kernel)
            s_list.append(np.asarray(s_np, dtype))
            sr_list.append(np.asarray(rows, np.int32))
            sc_list.append(np.asarray(cols, np.int32))

    with span("construct/dense"):
        dense = np.asarray(build_dense(tree, bs.d_rows, bs.d_cols, kernel),
                           dtype)

    data = H2Data(u_leaf=u_leaf, v_leaf=u_leaf, e=e_list,
                  f=[x for x in e_list], s=s_list, s_rows=sr_list,
                  s_cols=sc_list, dense=dense,
                  d_rows=np.asarray(bs.d_rows, np.int32),
                  d_cols=np.asarray(bs.d_cols, np.int32))
    per_leaf = np.bincount(bs.d_rows, minlength=1 << depth)
    shape = H2Shape(
        n=tree.n, leaf_size=leaf_size, depth=depth,
        ranks=tuple([k] * (depth + 1)),
        coupling_counts=bs.coupling_counts(),
        dense_count=int(bs.d_rows.shape[0]),
        symmetric=True,
        row_maxb=bs.row_maxb(), col_maxb=bs.col_maxb(),
        dense_maxb=int(per_leaf.max()) if per_leaf.size else 0)
    return shape, data, tree, bs


def dense_reference(points: np.ndarray, kernel: Callable,
                    perm: np.ndarray) -> np.ndarray:
    """Exact dense kernel matrix in tree order (for small-N validation)."""
    p = points[perm] if perm is not None else points
    return kernel(p[:, None, :], p[None, :, :])
