"""Point sets of the configurations, from their ``grid`` entry."""
from __future__ import annotations

import numpy as np


def regular_grid(grid: dict) -> np.ndarray:
    """The regular ``side**dim`` grid on ``[lo, hi]**dim``, first
    coordinate slowest (the paper's §6.1 test sets)."""
    axis = np.linspace(grid["lo"], grid["hi"], grid["side"])
    mesh = np.meshgrid(*[axis] * grid["dim"], indexing="ij")
    return np.stack([m.ravel() for m in mesh], axis=-1)


def grid_neighbours(grid: dict, j: int) -> np.ndarray:
    """Index j and its 2*dim axis neighbours on the grid (j interior)."""
    side, dim = grid["side"], grid["dim"]
    out = [j]
    for d in range(dim):
        step = side ** (dim - 1 - d)
        out += [j - step, j + step]
    return np.asarray(out)
