"""Stochastic a-posteriori certification of an operator apply (pillar 1b).

The estimate is the randomized Frobenius test of Boukaram et al.'s GPU
sketching-construction work (arXiv 2506.16759): for Gaussian probe block
``Omega in R^{n x probes}``,

    ||A_test Omega - A_ref Omega||_F / ||A_ref Omega||_F

concentrates around the relative operator error.  Probes come from the
counter-based streams of ``sketch.rng`` (a dedicated stream id far above
the per-level construction streams), so a certificate is bit-reproducible
for a given ``(seed, n, probes)`` and independent of how either apply is
batched.  Cost: ``probes`` matvecs of each apply — cheap enough to run
after construct / compress / low-rank update / ``repartition_h2``.

A NaN/Inf anywhere in the test apply surfaces as a non-finite estimate,
which fails the certificate — a corrupted operator cannot certify.
"""
from __future__ import annotations

import dataclasses
from typing import Callable, Optional

import jax.numpy as jnp
import numpy as np

from repro.core.structure import H2Data, H2Shape
from repro.obs.trace import phase
from repro.sketch.rng import node_gaussians, stream_key

# probe stream id: construction streams are tree levels (0..depth ~ 30),
# keep certification probes on a disjoint counter stream
CERT_STREAM = 10_007


@dataclasses.dataclass
class Certificate:
    """Outcome of one stochastic certification."""
    rel_err: float          # estimated relative operator error (nan = broken)
    tol: float
    ok: bool
    probes: int
    seed: int
    n: int

    def __bool__(self) -> bool:
        return self.ok


def probe_block(n: int, probes: int, seed: int = 0,
                dtype=jnp.float32) -> jnp.ndarray:
    """The deterministic Gaussian probe block ``[n, probes]``."""
    key = stream_key(seed, CERT_STREAM)
    ids = jnp.zeros((1,), jnp.uint32)
    return node_gaussians(key, ids, rows=n, cols=probes, dtype=dtype)[0]


def certify_matvec(apply_test: Callable, apply_ref: Callable, n: int, *,
                   probes: int = 8, seed: int = 0, tol: float = 1e-3,
                   dtype=jnp.float32) -> Certificate:
    """Estimate ``||A_test - A_ref|| / ||A_ref||`` from ``probes`` matvecs.

    Both applies take/return ``[n, nv]`` blocks.  ``ok`` is False when the
    estimate exceeds ``tol`` *or* is non-finite (NaN-poisoned operator).
    """
    with phase("guard/certify"):
        om = probe_block(n, probes, seed, dtype)
        yt = jnp.asarray(apply_test(om))
        yr = jnp.asarray(apply_ref(om))
        den = jnp.linalg.norm(yr)
        rel = jnp.linalg.norm(yt - yr) / jnp.where(den > 0, den, 1.0)
    rel = float(rel)
    return Certificate(rel_err=rel, tol=tol,
                       ok=bool(np.isfinite(rel) and rel <= tol),
                       probes=probes, seed=seed, n=n)


def kernel_reference_apply(points: np.ndarray, kernel: Callable,
                           perm: Optional[np.ndarray] = None,
                           chunk: int = 1024) -> Callable:
    """Reference ``x -> K x`` from the kernel itself, in row chunks.

    Evaluates ``chunk x n`` kernel strips so the dense ``n x n`` matrix is
    never materialized; with ``perm`` (``tree.perm``) the apply acts in
    tree order, matching a constructed H^2 operator.
    """
    p = points[perm] if perm is not None else points
    n = p.shape[0]

    def apply(x):
        x = jnp.asarray(x)
        outs = []
        for i0 in range(0, n, chunk):
            strip = jnp.asarray(kernel(p[i0:i0 + chunk, None, :],
                                       p[None, :, :]), x.dtype)
            outs.append(jnp.matmul(strip, x, precision="highest"))
        return jnp.concatenate(outs, axis=0)

    return apply


def certify_h2(shape: H2Shape, data: H2Data, apply_ref: Callable, *,
               probes: int = 8, seed: int = 0, tol: float = 1e-3,
               backend: str = "jnp") -> Certificate:
    """Certify a constructed H^2 operator against a reference apply."""
    from repro.core.matvec import h2_matvec
    dtype = data.u_leaf.dtype
    return certify_matvec(
        lambda x: h2_matvec(shape, data, x, backend), apply_ref, shape.n,
        probes=probes, seed=seed, tol=tol, dtype=dtype)
