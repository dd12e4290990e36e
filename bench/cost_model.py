"""Work counts of one H^2 operator application, from the operator's shape.

The counts depend only on the operator's structure (level ranks, block
counts, leaf size) and the number of columns, never on how the program
computes the product (jnp or Pallas, marshaled or not), so a roofline share
read against them cannot be moved by changing the yardstick.

``shape`` is any object with the attributes of ``repro.core.structure.
H2Shape`` that are read here: ``n``, ``leaf_size``, ``depth``, ``ranks``,
``coupling_counts``, ``dense_count``, ``symmetric``.
"""
from __future__ import annotations


def h2_matvec_flops(shape, nv: int) -> int:
    """Model FLOPs of one HGEMV, 2*m*n*k per GEMM (the upsweep, the
    coupling multiply, the downsweep and the dense leaves)."""
    m, q = shape.leaf_size, shape.depth
    kq = shape.ranks[q]
    fl = 2 * (1 << q) * m * kq * nv * 2            # leaf V^T x and U yhat
    for l in range(1, q + 1):
        fl += 2 * (1 << l) * shape.ranks[l] * shape.ranks[l - 1] * nv * 2
    for l in range(q + 1):
        fl += 2 * shape.coupling_counts[l] * shape.ranks[l] ** 2 * nv
    fl += 2 * shape.dense_count * m * m * nv
    return fl


def h2_matvec_bytes(shape, nv: int, itemsize: int = 4) -> int:
    """Least HBM bytes of one HGEMV: every factor array read once (the row
    and column basis trees once each, or once in all for a symmetric
    operator), the coupling blocks, the dense leaf blocks, X read and Y
    written.  Index arrays and padding are not counted."""
    m, q = shape.leaf_size, shape.depth
    trees = 1 if shape.symmetric else 2
    words = trees * (1 << q) * m * shape.ranks[q]
    for l in range(1, q + 1):
        words += trees * (1 << l) * shape.ranks[l] * shape.ranks[l - 1]
    for l in range(q + 1):
        words += shape.coupling_counts[l] * shape.ranks[l] ** 2
    words += shape.dense_count * m * m
    words += 2 * shape.n * nv
    return words * itemsize


def least_time(flops: float, nbytes: float, peaks: dict):
    """``(seconds, bound)``: the larger of flops over the compute peak and
    bytes over the HBM bandwidth, and which of the two decided."""
    t_flops = flops / peaks["f32_highest_flops_per_s"]
    t_bytes = nbytes / peaks["hbm_bytes_per_s"]
    return (t_flops, "compute") if t_flops >= t_bytes else (t_bytes, "memory")
