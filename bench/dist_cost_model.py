"""Work counts of one chip's share of a distributed H^2 application.

The operator in block rows over ``p`` chips (C-level ``lc = log2 p``): a
chip applies its own leaves' bases, the transfers of its nodes of levels
``lc+1..depth``, its coupling blocks of levels ``lc..depth`` and its dense
leaf blocks, and, replicated on every chip, the transfers of levels
``1..lc`` and the couplings of levels ``0..lc-1``.  Split counts are the
level's over ``p`` (the mean chip's share); the operations and bytes of a
level are ``bench/cost_model.py``'s.  The exchange itself moves no HBM
bytes counted here, and the whole operator against one chip's time would
read ``p`` times too high.

``shape`` is any object with ``cost_model``'s attributes (``n``,
``leaf_size``, ``depth``, ``ranks``, ``coupling_counts``, ``dense_count``,
``symmetric``), of the whole operator.
"""
from __future__ import annotations


def _share(shape, p: int, level_words, leaf_words, dense_words, vec_words):
    """Sum over the parts of one application, each weighted by the share
    of it one chip computes (1/p split, 1 replicated)."""
    lc = p.bit_length() - 1
    q = shape.depth
    total = leaf_words / p + dense_words / p + vec_words / p
    for l in range(q + 1):
        trans, coup = level_words(l)
        total += trans / (p if l > lc else 1)
        total += coup / (p if l >= lc else 1)
    return total


def share_flops(shape, p: int, nv: int) -> float:
    """Model FLOPs of one chip's share, 2*m*n*k per GEMM."""
    m, q = shape.leaf_size, shape.depth

    def level(l):
        trans = 2 * (1 << l) * shape.ranks[l] * shape.ranks[l - 1] * nv * 2 \
            if l > 0 else 0
        return trans, 2 * shape.coupling_counts[l] * shape.ranks[l] ** 2 * nv

    return _share(shape, p, level, 2 * (1 << q) * m * shape.ranks[q] * nv * 2,
                  2 * shape.dense_count * m * m * nv, 0)


def share_bytes(shape, p: int, nv: int, itemsize: int = 4) -> float:
    """Least HBM bytes of one chip's share: its factors read once, its
    rows of X read and of Y written."""
    m, q = shape.leaf_size, shape.depth
    trees = 1 if shape.symmetric else 2

    def level(l):
        trans = trees * (1 << l) * shape.ranks[l] * shape.ranks[l - 1] \
            if l > 0 else 0
        return trans, shape.coupling_counts[l] * shape.ranks[l] ** 2

    return itemsize * _share(shape, p, level,
                             trees * (1 << q) * m * shape.ranks[q],
                             shape.dense_count * m * m, 2 * shape.n * nv)
