"""Builder of a kernel-matrix deployment on a regular grid (§6.1/§6.2).

Points: the regular grid of the configuration (``reference.grids``, the
same points the reference uses).  The program's Chebyshev construction
``construct_h2`` builds the uncompressed H^2 operator on the host; the
traffic's driver compresses or applies it.
"""
from __future__ import annotations


def program_kernel(spec: dict):
    from repro.core import kernels_fn
    if spec["name"] == "exponential":
        return kernels_fn.exponential_kernel(spec["length"])
    raise ValueError(f"unknown kernel {spec['name']!r}")


def build(cfg: dict) -> dict:
    from bench.reference.grids import regular_grid
    from repro.core.construction import construct_h2

    pts = regular_grid(cfg["grid"])
    shape, data, tree, _ = construct_h2(
        pts, program_kernel(cfg["kernel"]), leaf_size=cfg["leaf"],
        cheb_p=cfg["cheb_p"], eta=cfg["eta"])
    return {"perm": tree.perm, "shape0": shape, "data0": data}
