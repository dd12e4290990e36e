"""The plain references against dense float64 numpy at n <= 32 (CPU)."""
import sys
from pathlib import Path

import numpy as np
import pytest

BENCH = Path(__file__).resolve().parents[1]
ROOT = BENCH.parent
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

from bench.reference import kernels  # noqa: E402
from bench.reference.cheb_h2 import ChebH2, box_lagrange, \
    cheb_points  # noqa: E402
from bench.reference.fractional import FractionalReference, cell_grid, \
    stencil_apply  # noqa: E402
from bench.reference.grids import grid_neighbours, regular_grid  # noqa: E402

FRAC16 = {"n": 16, "kernel": {"name": "fractional", "beta": 0.75},
          "leaf": 16, "ext_leaf": 36, "cheb_p": 6, "eta": 0.9}


def dense(kernel, pts):
    return kernel(pts[:, None, :], pts[None, :, :])


def test_lagrange_basis_interpolates_at_its_nodes():
    p = 5
    lo, hi = np.array([[0.2, -1.0]]), np.array([[0.7, 3.0]])
    t = cheb_points(p)
    nodes = np.stack(np.meshgrid(lo[0, 0] + 0.5 * t, -1.0 + 4.0 * t,
                                 indexing="ij"), -1).reshape(1, -1, 2)
    lag = box_lagrange(p, lo, hi, nodes)[0]
    np.testing.assert_allclose(lag, np.eye(p * p), atol=1e-12)


def test_cheb_h2_is_exact_for_a_low_degree_kernel():
    """Interpolation of degree p-1 per side reproduces a kernel that is a
    polynomial of degree <= 2 in each coordinate: every far block exact."""
    pts = cell_grid(32, 1.0)
    kern = lambda x, y: (1.0 + (x * y).sum(-1)) ** 2  # noqa: E731
    ref = ChebH2(pts, kern, 16, 6, 0.9)
    x = np.random.default_rng(0).standard_normal((pts.shape[0], 3))
    want = dense(kern, pts) @ x
    np.testing.assert_allclose(ref.apply(x), want, rtol=1e-10,
                               atol=1e-10 * np.abs(want).max())


@pytest.mark.parametrize("name, kern, err", [
    ("exponential", kernels.exponential(0.1), 5e-3),
    ("fractional", kernels.fractional(0.75), 1e-2)])
def test_cheb_h2_is_within_interpolation_error_of_the_kernel(name, kern,
                                                             err):
    pts = cell_grid(32, 1.0)
    ref = ChebH2(pts, kern, 16, 6, 0.9)
    x = np.random.default_rng(1).standard_normal((pts.shape[0], 2))
    want = dense(kern, pts) @ x
    got = ref.apply(x)
    assert np.linalg.norm(got - want) / np.linalg.norm(want) < err
    assert sum(r.size for r, _ in ref.far) > 0


def test_cheb_h2_partition_covers_every_pair_once():
    ref = ChebH2(cell_grid(16, 1.0), kernels.exponential(0.1), 16, 6, 0.9)
    cover = np.zeros((ref.n, ref.n), int)
    for l, (rows, cols) in enumerate(ref.far + [ref.dense]):
        w = ref.n >> (l if l < len(ref.far) else ref.depth)
        for r, c in zip(rows, cols):
            cover[r * w:(r + 1) * w, c * w:(c + 1) * w] += 1
    assert (cover == 1).all()


def test_row_subset_apply_matches_full_apply():
    pts = cell_grid(16, 3.0)
    ref = ChebH2(pts, kernels.fractional(0.75, +1.0), 36, 6, 0.9)
    inside = (np.abs(pts) < 1.0).all(1)
    rows = inside[ref.perm].reshape(-1, 36).any(1)
    ones = np.ones((pts.shape[0], 1))
    np.testing.assert_allclose(ref.apply(ones, row_nodes=rows)[inside],
                               ref.apply(ones)[inside], rtol=1e-13)


def test_stencil_matches_dense_assembly():
    n, h = 8, 0.25
    kappa = 1.0 + np.random.default_rng(2).random((n, n))
    cols = [stencil_apply(e.reshape(n, n, 1), kappa, h).ravel()
            for e in np.eye(n * n)]
    c = np.stack(cols, 1)
    np.testing.assert_allclose(c, c.T, rtol=1e-12)
    assert np.linalg.eigvalsh(c).min() > 0
    u = np.random.default_rng(3).standard_normal((n, n, 1))
    np.testing.assert_allclose(stencil_apply(u, kappa, h).ravel(),
                               c @ u.ravel(), rtol=1e-12)


def test_fractional_reference_operator():
    """D is the extended grid's row sums (within the interpolation error of
    the exact sums), K the interior operator, and A symmetric."""
    ref = FractionalReference(FRAC16)
    n, h = 16, 2.0 / 16
    pts, ext = cell_grid(n, 1.0), cell_grid(n, 3.0)
    d_exact = dense(kernels.fractional(0.75, +1.0), ext)[
        (np.abs(ext) < 1.0).all(1)].sum(1)
    assert np.abs(ref.d - d_exact).max() / d_exact.max() < 1e-2
    a = ref.apply(np.eye(n * n))
    np.testing.assert_allclose(a, a.T, rtol=1e-9, atol=1e-12 * abs(a).max())
    k_exact = dense(kernels.fractional(0.75), pts)
    kd = a / (h * h) - np.diag(ref.d) - ref.gamma * np.stack(
        [stencil_apply(e.reshape(n, n, 1), ref.kappa, h).ravel()
         for e in np.eye(n * n)], 1)
    assert np.linalg.norm(kd - k_exact) / np.linalg.norm(k_exact) < 1e-2


def test_fractional_reference_reproduces_the_programs_operator():
    """The reference's residual of the program's own solve equals the
    residual against the program's operator: same semantics."""
    import jax
    import jax.numpy as jnp
    from repro.apps.fractional import (FractionalProblem, make_operator,
                                       make_preconditioner)
    from repro.solvers import pcg

    prob = FractionalProblem(16).build()
    a, m = make_operator(prob), make_preconditioner(prob)
    b = jnp.asarray((2.0 / 16) ** 2 * (1.0 + 0.1 * np.random.default_rng(
        4).standard_normal(256)), jnp.float32)
    x = jax.jit(lambda v: pcg(a, v, m, tol=1e-8, maxiter=500).x)(b)
    own = float(jnp.linalg.norm(b - a(x)) / jnp.linalg.norm(b))
    ref = FractionalReference(FRAC16).residual(np.asarray(b)[:, None],
                                               np.asarray(x)[:, None])[0]
    assert abs(ref - own) < 0.2 * own


def test_grid_neighbours_are_adjacent():
    g = {"side": 8, "dim": 2, "lo": 0.0, "hi": 1.0}
    pts = regular_grid(g)
    nb = grid_neighbours(g, 3 * 8 + 4)
    d = np.linalg.norm(pts[nb] - pts[nb[0]], axis=1)
    np.testing.assert_allclose(np.sort(d)[1:], 1.0 / 7)
