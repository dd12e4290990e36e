"""Plain kernel functions of the benchmark's configurations (numpy, any dtype).

Written from the paper's formulas (arXiv:2109.05451, §6.1 and Eqs. 6-11),
not imported from the program, so that the reference shares no code with
the system under test.  Every function maps point arrays ``x [..., d]`` and
``y [..., d]`` (broadcast against each other) to kernel values.
"""
from __future__ import annotations

import numpy as np


def exponential(length: float, xp=np):
    """exp(-|x - y| / length): the §6.1/§6.2 covariance kernel.  ``xp``:
    the array namespace (numpy, or jax.numpy for the device)."""
    def k(x, y):
        return xp.exp(-xp.sqrt(((x - y) ** 2).sum(-1)) / length)
    return k


def _bump(t, c, ell):
    """Eq. (7): exp(-1 / (1 - r^2)) for |r| < 1, r = (t - c) / (ell / 2)."""
    r = (t - c) / (ell / 2.0)
    inside = np.abs(r) < 1.0
    rs = np.where(inside, r, 0.0)
    return np.where(inside, np.exp(-1.0 / (1.0 - rs ** 2)), 0.0)


def diffusivity(x):
    """Eq. (6): kappa(x) = 1 + f(x1; 0, 1.5) f(x2; 0, 2.0)."""
    return 1.0 + _bump(x[..., 0], 0.0, 1.5) * _bump(x[..., 1], 0.0, 2.0)


def fractional(beta: float, sign: float = -1.0):
    """sign * 2 sqrt(kappa(x) kappa(y)) / |x - y|^(2 + 2 beta), 0 at x = y.

    ``sign=-1`` is K of Eq. (11); ``sign=+1`` the positive kernel whose row
    sums over the extended grid give D (Eq. 10).
    """
    def k(x, y):
        r2 = ((x - y) ** 2).sum(-1)
        a = np.sqrt(diffusivity(x)) * np.sqrt(diffusivity(y))
        v = sign * 2.0 * a / np.maximum(r2, 1e-100) ** (1.0 + beta)
        return np.where(r2 == 0.0, 0.0, v)
    return k


def by_name(spec: dict, xp=np):
    """Kernel from a configuration's ``kernel`` entry."""
    name = spec["name"]
    if name == "exponential":
        return exponential(spec["length"], xp)
    if name == "fractional" and xp is np:
        return fractional(spec["beta"])
    raise ValueError(f"unknown kernel {name!r}")
