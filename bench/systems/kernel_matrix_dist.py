"""Builder of a kernel-matrix deployment in block rows over ``p`` chips
(§6.1/§6.2, distributed).

Points: the regular grid of the configuration (``reference.grids``, the
same points the reference uses).  The program's Chebyshev construction
``construct_h2_host`` builds the uncompressed H^2 operator on the host, and
``distribute_h2`` places each block row from the host on its own chip of a
``p``-chip mesh (axis ``blk``), with the replicated top levels on every
chip: no chip ever holds the whole operator.  The compression to the
configuration's ``compress_tol`` (``make_dist_compress``) is built here;
the traffic's driver runs it.
"""
from __future__ import annotations


def build(cfg: dict) -> dict:
    # first: a program without the host build, the placement and the
    # compression to a tolerance fails here, before any work
    from repro.core.construction import construct_h2_host
    from repro.core.dist import distribute_h2, make_dist_compress
    import jax

    from bench.reference.grids import regular_grid
    from repro.core.kernels_fn import exponential_kernel

    if cfg["kernel"]["name"] != "exponential":
        raise ValueError(f"unknown kernel {cfg['kernel']['name']!r}")
    devices = jax.devices()[:cfg["p"]]
    if len(devices) < cfg["p"]:
        raise SystemExit(f"kernel_matrix_dist: {cfg['p']} chips needed, "
                         f"JAX found {len(devices)}")
    mesh = jax.make_mesh((cfg["p"],), ("blk",), devices=devices,
                         axis_types=(jax.sharding.AxisType.Auto,))
    shape, data, tree, _ = construct_h2_host(
        regular_grid(cfg["grid"]), exponential_kernel(cfg["kernel"]["length"]),
        leaf_size=cfg["leaf"], cheb_p=cfg["cheb_p"], eta=cfg["eta"])
    dshape, ddata = distribute_h2(shape, data, mesh, "blk")
    return {"perm": tree.perm, "mesh": mesh, "dshape0": dshape,
            "data0": ddata, "matvecs": {},
            "compress": make_dist_compress(dshape, mesh, "blk",
                                           tol=cfg["compress_tol"])}
