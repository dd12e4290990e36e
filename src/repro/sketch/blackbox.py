"""Black-box H^2 construction from a matvec ``x -> A x`` (peeling probes).

Given only the *action* of an N x N **symmetric** operator (plus the point
geometry that fixes the tree and admissibility structure), build its H^2
representation.  This opens workloads where no kernel function exists:
squaring an existing H^2 operator (``A = B @ B``), re-compressing a sum of
symmetric operators, or building preconditioner factors from solvers.

Probing scheme (the levelwise variant of Lin–Lu–Ying peeling, batched):

- *Sketch probes* (per coupling level ``l``): the probe matrix carries an
  independent Gaussian block per tree node, supported on that node's rows
  only.  For an admissible pair ``(t, s)``, the rows of ``A @ probe``
  belonging to ``t`` in ``s``'s column group equal ``A(t,s) Omega_s``
  *exactly* — dual-tree admissibility assigns each (t,s) interaction to
  exactly one level, so node-supported probes cannot contaminate each
  other.  Segment-summing over a block row reproduces the same
  ``Y_l[t]`` block-row sketches the geometric sampler builds.
- *Coupling probes*: the same node-supported probes loaded with the
  explicit column bases ``V_s`` give ``A(t,s) V_s`` exactly, hence
  ``S = U^T (A V)``.
- *Dense extraction*: identity probes colored over the leaf near-field
  graph (greedy coloring; same-colored leaves share no dense block row)
  applied to the *residual* ``A - A_lowrank`` — far-field leakage into the
  extracted blocks is bounded by the sketch tolerance.

Cost: ``sum_l 2**l (r + k_l) + n_colors * m`` matvec columns — worthwhile
precisely when the matvec is fast (an existing H^2 operator), which is the
intended use.
"""
from __future__ import annotations

from typing import Callable, List, Optional, Tuple

import jax.numpy as jnp
import numpy as np

from repro.core.admissibility import BlockStructure, build_block_structure
from repro.core.clustering import ClusterTree, build_cluster_tree
from repro.core.matvec import h2_matvec
from repro.core.structure import H2Data, H2Shape

from . import rng
from .construct import _assemble, adaptive_sketches
from .rangefinder import build_nested_bases, explicit_bases

import dataclasses

import jax


def _node_probe(blocks: jnp.ndarray) -> jnp.ndarray:
    """Scatter per-node column blocks into a block-diagonal probe matrix.

    blocks: [nn, w, r] (node-supported columns) -> [nn*w, nn*r] with
    ``probe[s*w:(s+1)*w, s*r:(s+1)*r] = blocks[s]``.
    """
    nn, w, r = blocks.shape
    n = nn * w
    rows = jnp.arange(n)
    colbase = (rows // w) * r
    probe = jnp.zeros((n, nn * r), blocks.dtype)
    return probe.at[rows[:, None], colbase[:, None] + jnp.arange(r)[None, :]
                    ].set(blocks.reshape(n, r))


def _gather_block_reads(z: jnp.ndarray, nn: int, w: int, r: int,
                        s_rows: jnp.ndarray, s_cols: jnp.ndarray
                        ) -> jnp.ndarray:
    """Read per-block results [nb, w, r] out of a probed matvec [n, nn*r]."""
    z4 = z.reshape(nn, w, nn, r)
    return z4[s_rows, :, s_cols, :]


def _leaf_coloring(d_rows: np.ndarray, d_cols: np.ndarray,
                   n_leaves: int) -> Tuple[np.ndarray, int]:
    """Greedy coloring of the leaf near-field graph.

    Two leaves conflict when some block row contains dense blocks to both —
    then identity probes for them must not share columns.  Degree is
    bounded by C_sp^2, so a handful of colors suffice.
    """
    groups: List[List[int]] = [[] for _ in range(n_leaves)]
    for t, s in zip(d_rows, d_cols):
        groups[int(t)].append(int(s))
    adj: List[set] = [set() for _ in range(n_leaves)]
    for members in groups:
        for a in members:
            for b in members:
                if a != b:
                    adj[a].add(b)
    color = np.full(n_leaves, -1, np.int64)
    for s in range(n_leaves):
        used = {color[t] for t in adj[s] if color[t] >= 0}
        c = 0
        while c in used:
            c += 1
        color[s] = c
    return color, int(color.max()) + 1


def construct_from_matvec(matvec: Callable[[jnp.ndarray], jnp.ndarray],
                          points: np.ndarray, leaf_size: int, eta: float, *,
                          tol: float = 1e-4, max_rank: int = 64,
                          oversample: int = 10,
                          n_samples0: Optional[int] = None, seed: int = 0,
                          min_level: int = 1, dtype=jnp.float32,
                          backend: str = "jnp", check_symmetry: bool = True
                          ) -> Tuple[H2Shape, H2Data, ClusterTree,
                                     BlockStructure]:
    """Build an H^2 representation of a black-box *symmetric* operator.

    ``matvec`` maps [N, nv] -> [N, nv] in *tree (permuted) order* — wrap
    with ``tree.perm`` if the operator lives in original order.  Geometry
    (``points``) fixes the tree/admissibility; entries come only from
    ``matvec``.  Return signature matches ``construct_h2``.

    Like the rest of this repo's construction paths, the operator must be
    symmetric: only block *rows* are probed and the row basis doubles as
    the column basis (``v_leaf = u_leaf``).  A nonsymmetric operator would
    silently lose column-space directions, so by default two probe vectors
    verify ``<u, Av> == <v, Au>`` and a ``ValueError`` is raised otherwise
    (general operators need an ``rmatvec``; not implemented).
    """
    tree = build_cluster_tree(points, leaf_size)
    bs = build_block_structure(tree, eta, min_level=min_level)

    if check_symmetry:
        key = rng.stream_key(seed, 10_000)
        uv = jax.random.normal(key, (points.shape[0], 2), dtype)
        auv = matvec(uv)
        a = float(jnp.dot(uv[:, 0], auv[:, 1], precision="highest"))
        b = float(jnp.dot(uv[:, 1], auv[:, 0], precision="highest"))
        if abs(a - b) > 1e-3 * (abs(a) + abs(b) + 1e-30):
            raise ValueError(
                "construct_from_matvec supports symmetric operators only "
                f"(<u,Av>={a:.6g} != <v,Au>={b:.6g}); pass "
                "check_symmetry=False to override at your own risk")
    depth = tree.depth
    n = tree.n
    m = leaf_size
    counts = bs.coupling_counts()

    sr = [jnp.asarray(bs.s_rows[l], jnp.int32) for l in range(depth + 1)]
    sc = [jnp.asarray(bs.s_cols[l], jnp.int32) for l in range(depth + 1)]

    def sample_fn(r: int) -> List[Optional[jnp.ndarray]]:
        out: List[Optional[jnp.ndarray]] = []
        for l in range(depth + 1):
            if counts[l] == 0:
                out.append(None)
                continue
            nn = 1 << l
            w = n >> l
            omega = rng.level_gaussians(seed, l, nn, w, r, dtype)
            z = matvec(_node_probe(omega))
            y_b = _gather_block_reads(z, nn, w, r, sr[l], sc[l])
            out.append(jax.ops.segment_sum(y_b, sr[l], num_segments=nn,
                                           indices_are_sorted=True))
        return out

    if sum(counts) == 0:
        from .construct import _rank0_bases
        u_leaf, e, ranks = _rank0_bases(depth, m, dtype)
    else:
        sketches, _ = adaptive_sketches(sample_fn, tol, max_rank, oversample,
                                        n_samples0, backend)
        u_leaf, e, ranks = build_nested_bases(sketches, m, tol, max_rank,
                                              backend)
    u_exp = explicit_bases(u_leaf, e)

    # couplings: probe with the explicit column bases
    s_list = []
    for l in range(depth + 1):
        if counts[l] == 0:
            s_list.append(jnp.zeros((0, ranks[l], ranks[l]), dtype))
            continue
        nn = 1 << l
        w = n >> l
        kl = ranks[l]
        z = matvec(_node_probe(u_exp[l]))
        av = _gather_block_reads(z, nn, w, kl, sr[l], sc[l])   # [nb, w, k]
        ut = jnp.take(u_exp[l], sr[l], axis=0)
        s_list.append(jnp.einsum("bwk,bwj->bkj", ut, av, precision="highest"))

    # dense leaves: colored identity probes against the low-rank residual
    shape_lr, data_lr = _assemble(
        tree, dataclasses.replace(bs, d_rows=np.zeros(0, np.int64),
                                  d_cols=np.zeros(0, np.int64)),
        u_leaf, e, ranks, s_list, jnp.zeros((0, m, m), dtype), dtype)
    color_np, nc = _leaf_coloring(bs.d_rows, bs.d_cols, 1 << depth)
    rows = jnp.arange(n)
    colidx = jnp.asarray(color_np, jnp.int32)[rows // m] * m + rows % m
    probe = jnp.zeros((n, nc * m), dtype).at[rows, colidx].set(1.0)
    zr = matvec(probe) - h2_matvec(shape_lr, data_lr, probe)
    z4 = zr.reshape(1 << depth, m, nc, m)
    d_rows_j = jnp.asarray(bs.d_rows, jnp.int32)
    d_cols_j = jnp.asarray(bs.d_cols, jnp.int32)
    dense = z4[d_rows_j, :, jnp.asarray(color_np, jnp.int32)[d_cols_j], :]

    shape, data = _assemble(tree, bs, u_leaf, e, ranks, s_list, dense, dtype)
    return shape, data, tree, bs
