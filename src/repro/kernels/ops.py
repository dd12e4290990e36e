"""Public entry points of the Pallas kernels, plus the backend dispatch the
core shares.

Each kernel interprets its body where its arrays live on the CPU (the test
suite runs that way, with ``JAX_PLATFORMS=cpu``) and compiles to Mosaic on
a TPU; the choice is made per lowering (``repro.kernels.pallas_call``), so
there is nothing to configure.  ``python chip_smoke.py`` runs the compiled
kernels on one chip against the ``jnp`` backend.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from .batched_gemm import batched_gemm
from .batched_qr import batched_qr
from .batched_svd import batched_svd
from .coupling_mv import coupling_mv
from .halo_pack import halo_pack

__all__ = ["backend_qr", "backend_qr_r", "backend_svd", "batched_gemm",
           "batched_qr", "batched_svd", "coupling_mv", "halo_pack"]


def backend_qr(a: jax.Array, backend: str = "jnp", **kw):
    """Backend-dispatched reduced QR (the one helper every caller shares:
    orthogonalize, compression weights, sketch rangefinder).

    Pallas kw: ``panel`` (column-panel width for the WY trailing updates)
    and ``bb`` (matrices factored per grid step).
    """
    if backend == "pallas":
        return batched_qr(a, **kw)
    return jnp.linalg.qr(a, mode="reduced")


def backend_qr_r(a: jax.Array, backend: str = "jnp", **kw) -> jax.Array:
    """R factor only."""
    if backend == "pallas":
        return batched_qr(a, **kw)[1]
    return jnp.linalg.qr(a, mode="r")


def backend_svd(a: jax.Array, backend: str = "jnp", **kw):
    """Backend-dispatched reduced SVD.

    Pallas kw: ``max_sweeps`` / ``tol`` (off-diagonal-norm early exit: stop
    when ``||offdiag(A^T A)||_F <= tol * ||A||_F^2``) and ``bb`` (matrices
    per grid step).
    """
    if backend == "pallas":
        return batched_svd(a, **kw)
    return jnp.linalg.svd(a, full_matrices=False)
