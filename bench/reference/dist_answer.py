"""Plain read-back of an H^2 operator held in block rows over devices (an
answer to check).

The program's distributed operator (the ``DistH2Data`` attributes, with
its static shape) is read back to the host into the dict that
``h2_answer.to_host`` gives for a one-device operator: global leaf bases,
transfers per level, coupling and dense blocks with global row and column
indices, values in float64.  Nothing here runs the program.

The layout read: an array split by block rows has its leading axis in
``p`` equal parts, part d on device d.  Levels ``0..lc-1`` (``lc =
log2 p``) are whole on every device (``*_top``); levels ``lc..depth`` and
the dense leaves are split (``*_br``, indexed ``l - lc``, and ``dense``).
A split level's blocks sit in per-device slabs of ``nbmax`` slots, rows
local to the device (its first node is 0), columns global, the unused
slots last.  The slots in use are those the level's row-marshaled slot
plan names (``pb_blk``, ``nbmax`` for an empty slot); for the dense leaves
those the two halves of its exchange plan name (``hp_dense.diag_blk`` and
``off_blk``).  Which blocks that gives is the program's own account, so
``block_gap`` holds the read-back's block sets to the plain reference's.
"""
from __future__ import annotations

import types

import numpy as np


def _slab_blocks(vals, rows, cols, named: list, p: int, nbmax: int,
                 nloc: int):
    """A split level's blocks in slab order: values, global rows, cols."""
    idx, glob_rows = [], []
    for d in range(p):
        used = np.unique(np.concatenate([np.asarray(a).reshape(p, -1)[d]
                                         for a in named]))
        used = used[used < nbmax] + d * nbmax
        idx.append(used)
        glob_rows.append(np.asarray(rows)[used].astype(np.int64) + d * nloc)
    idx = np.concatenate(idx)
    return (np.asarray(vals, np.float64)[idx],
            np.concatenate(glob_rows).astype(np.int32),
            np.asarray(cols)[idx].astype(np.int32))


def to_host(data, shape) -> dict:
    """``h2_answer.to_host``'s dict of a distributed operator ``data``
    with static shape ``shape`` (``p``, ``lc``, ``depth``, ``br_counts``,
    ``dense_count``, ``leaf_size``)."""
    f64 = lambda a: np.asarray(a, np.float64)  # noqa: E731
    p, lc, depth = shape.p, shape.lc, shape.depth
    e = [f64(data.e_top[l] if l <= lc else data.e_br[l - lc])
         for l in range(depth + 1)]
    f = [f64(data.f_top[l] if l <= lc else data.f_br[l - lc])
         for l in range(depth + 1)]
    s, s_rows, s_cols = [], [], []
    for l in range(depth + 1):
        if l < lc:
            s.append(f64(data.s_top[l]))
            s_rows.append(np.asarray(data.s_top_rows[l]))
            s_cols.append(np.asarray(data.s_top_cols[l]))
            continue
        i = l - lc
        v, r, c = _slab_blocks(data.s_br[i], data.s_br_rows[i],
                               data.s_br_cols[i], [data.pb_blk[i]], p,
                               shape.br_counts[i], (1 << l) // p)
        s.append(v)
        s_rows.append(r)
        s_cols.append(c)
    dense, d_rows, d_cols = _slab_blocks(
        data.dense, data.d_rows, data.d_cols,
        [data.hp_dense.diag_blk, data.hp_dense.off_blk], p,
        shape.dense_count, (1 << depth) // p)
    return {"u_leaf": f64(data.u_leaf), "v_leaf": f64(data.v_leaf),
            "e": e, "f": f, "s": s, "s_rows": s_rows, "s_cols": s_cols,
            "dense": dense, "d_rows": d_rows, "d_cols": d_cols}


def block_gap(h: dict, ref, perm) -> int:
    """Blocks missing from or extra in an operator read back by
    ``to_host`` (a block read twice counts as extra), against the block
    sets of the plain reference ``ref`` (a ``cheb_h2.ChebH2``): each
    level's admissible pairs ``ref.far[l]`` and the dense leaf pairs
    ``ref.dense``.  Node numbers name the same nodes only where the
    program's tree order ``perm`` is the reference's; where it is not, or
    the depths differ, every block counts."""
    if len(h["s"]) != len(ref.far) or not np.array_equal(perm, ref.perm):
        return sum(r.size for r in h["s_rows"]) + h["d_rows"].size + \
            sum(r.size for r, _ in ref.far) + ref.dense[0].size
    gap = 0
    for rows, cols, want in zip(h["s_rows"] + [h["d_rows"]],
                                h["s_cols"] + [h["d_cols"]],
                                ref.far + [ref.dense]):
        got = np.asarray(rows, np.int64) << 32 | np.asarray(cols, np.int64)
        once = np.unique(got)
        gap += got.size - once.size + np.setxor1d(once, np.unique(
            np.asarray(want[0], np.int64) << 32 |
            np.asarray(want[1], np.int64))).size
    return int(gap)


def work_shape(h: dict):
    """The counts ``bench/cost_model.py`` reads (``n``, ``leaf_size``,
    ``depth``, ``ranks``, ``coupling_counts``, ``dense_count``,
    ``symmetric``) of an operator read back by ``to_host``."""
    depth = len(h["e"]) - 1
    m = h["dense"].shape[1]
    ranks = [h["u_leaf"].shape[-1]] * (depth + 1)
    for l in range(depth, 0, -1):
        ranks[l - 1] = h["e"][l].shape[-1]
    return types.SimpleNamespace(
        n=h["u_leaf"].shape[0] * m, leaf_size=m, depth=depth,
        ranks=tuple(ranks),
        coupling_counts=tuple(int(x.shape[0]) for x in h["s"]),
        dense_count=int(h["dense"].shape[0]), symmetric=True)
