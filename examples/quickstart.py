"""Quickstart: build an H^2 kernel matrix, apply it, recompress it.

    PYTHONPATH=src python examples/quickstart.py
"""
from pathlib import Path

import numpy as np
import jax.numpy as jnp

from repro.launch.compile_cache import enable_compile_cache
from repro.core.clustering import regular_grid_points
from repro.core.construction import construct_h2, dense_reference
from repro.core.kernels_fn import exponential_kernel
from repro.core.matvec import h2_matvec
from repro.core.compression import compress


def main(side: int = 64, leaf_size: int = 64):
    # 1. a 2D spatial-statistics kernel matrix (paper §6.1 test set)
    pts = regular_grid_points(side, 2)               # N = side^2 points
    kernel = exponential_kernel(correlation_length=0.1)
    shape, data, tree, bs = construct_h2(
        pts, kernel, leaf_size=leaf_size, cheb_p=6, eta=0.9)
    print(f"H2 matrix: N={shape.n}, depth={shape.depth}, "
          f"C_sp={bs.sparsity_constant()}, "
          f"low-rank scalars={shape.memory_lowrank():,} "
          f"(dense would be {shape.n**2:,})")

    # 2. matvec, validated against the dense matrix
    x = np.random.default_rng(0).standard_normal((shape.n, 4)).astype("f")
    y = np.asarray(h2_matvec(shape, data, jnp.asarray(x)))
    a_dense = dense_reference(pts, kernel, tree.perm)
    err = np.linalg.norm(y - a_dense @ x) / np.linalg.norm(a_dense @ x)
    print(f"matvec relative error vs dense: {err:.2e}")

    # 3. algebraic recompression (paper §5): rank-36 Chebyshev -> tau=1e-3
    cshape, cdata = compress(shape, data, tol=1e-3)
    y2 = np.asarray(h2_matvec(cshape, cdata, jnp.asarray(x)))
    err2 = np.linalg.norm(y2 - a_dense @ x) / np.linalg.norm(a_dense @ x)
    ratio = shape.memory_lowrank() / cshape.memory_lowrank()
    print(f"compressed ranks per level: {cshape.ranks}")
    print(f"low-rank memory reduction: {ratio:.1f}x "
          f"(paper reports ~6x at scale); matvec error now {err2:.2e}")
    return err, err2, ratio


if __name__ == "__main__":
    enable_compile_cache(Path(__file__).resolve().parents[1])
    main()
