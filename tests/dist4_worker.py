"""Four-device checks of the host-resident distributed build and of the
distributed compression to a tolerance; run in a subprocess (the device
count must be set before JAX starts):

    python tests/dist4_worker.py partition|compress

Prints one "OK <name>" line per passing check; ``tests/test_dist4.py``
asserts on them.
"""
import os
import sys

os.environ["XLA_FLAGS"] = (
    "--xla_force_host_platform_device_count=4 "
    + os.environ.get("XLA_FLAGS", ""))

import jax                  # noqa: E402
import jax.numpy as jnp     # noqa: E402
import numpy as np          # noqa: E402
from jax.sharding import NamedSharding, PartitionSpec as P  # noqa: E402

from repro.core.clustering import regular_grid_points      # noqa: E402
from repro.core.compression import compress                 # noqa: E402
from repro.core.construction import construct_h2, construct_h2_host  # noqa
from repro.core.dist import (dist_compress_local, dist_specs,  # noqa
                             distribute_h2, make_dist_compress, make_dist_matvec,
                             partition_h2)
from repro.core.kernels_fn import exponential_kernel        # noqa: E402
from repro.core.matvec import h2_matvec                     # noqa: E402
from repro.obs.trace import REGISTRY, counter               # noqa: E402

KERNEL = exponential_kernel(0.1)


def check_partition(mesh):
    """Host construction + ``distribute_h2`` place the same bits, with the
    same shardings, as the device construction partitioned and placed
    leaf by leaf; no device receives more than its block row."""
    pts = regular_grid_points(32, 2)
    hs, hd, _, _ = construct_h2_host(pts, KERNEL, 16, 4, 0.9)
    _, data, _, _ = construct_h2(pts, KERNEL, 16, 4, 0.9)
    assert all(isinstance(a, np.ndarray) for a in jax.tree.leaves(hd))
    for name in ("u_leaf", "e", "s", "s_rows", "s_cols", "dense",
                 "d_rows", "d_cols"):
        for a, b in zip(jax.tree.leaves(getattr(hd, name)),
                        jax.tree.leaves(getattr(data, name))):
            b = np.asarray(b)
            assert a.dtype == b.dtype and np.array_equal(a, b), name
    print("OK host_construction")

    _, host_parts = partition_h2(hs, hd, 4)
    assert all(isinstance(a, np.ndarray)
               for a in jax.tree.leaves(host_parts))
    dshape, placed = distribute_h2(hs, hd, mesh)
    old_shape, old = partition_h2(hs, data, 4)
    assert dshape == old_shape
    old = jax.tree.map(lambda a, s: jax.device_put(a, NamedSharding(mesh, s)),
                       old, dist_specs(old_shape, "blk"))
    specs = jax.tree.leaves(dist_specs(dshape, "blk"),
                            is_leaf=lambda s: isinstance(s, P))
    new_leaves, old_leaves = jax.tree.leaves(placed), jax.tree.leaves(old)
    assert len(new_leaves) == len(old_leaves) == len(specs)
    for a, b, spec in zip(new_leaves, old_leaves, specs):
        assert a.sharding == NamedSharding(mesh, spec), (a.sharding, spec)
        assert a.dtype == b.dtype and np.array_equal(np.asarray(a),
                                                     np.asarray(b))
        rows = a.shape[0] // 4 if spec == P("blk") else a.shape[0]
        assert sorted(sh.device.id for sh in a.addressable_shards) == \
            [d.id for d in mesh.devices]
        assert all(sh.data.shape[:1] == (rows,)
                   for sh in a.addressable_shards)
    assert any(n == "build/partition" for n, _, _ in REGISTRY.recent())
    print("OK host_partition_placed", len(new_leaves))


def check_compress(mesh):
    """On a 64^2 grid: the ranks of ``make_dist_compress(tol)`` are those
    of the one-device ``compress(tol)``, picked with depth + 2 host syncs,
    and the distributed application of that operator matches the
    one-device one."""
    pts = regular_grid_points(64, 2)
    shape, data, _, _ = construct_h2(pts, KERNEL, 16, 4, 0.9)
    cs, cd = compress(shape, data, tol=1e-3)
    hs, hd, _, _ = construct_h2_host(pts, KERNEL, 16, 4, 0.9)
    dshape, dd = distribute_h2(hs, hd, mesh)
    syncs = counter("compress/host-syncs")
    dshape_c, dd_c = make_dist_compress(dshape, mesh, "blk", 1e-3)(dd)
    assert dshape_c.ranks == cs.ranks, (dshape_c.ranks, cs.ranks)
    assert counter("compress/host-syncs") - syncs == shape.depth + 2
    print("OK dist_tol_ranks", dshape_c.ranks)

    x = jnp.asarray(np.random.default_rng(0).standard_normal((shape.n, 8)),
                    jnp.float32)
    y1 = np.asarray(h2_matvec(cs, cd, x), np.float64)
    y4 = np.asarray(make_dist_matvec(dshape_c, mesh, "blk")(
        dd_c, jax.device_put(x, NamedSharding(mesh, P("blk")))), np.float64)
    err = np.linalg.norm(y4 - y1) / np.linalg.norm(y1)
    # Both sides compress with the same QR/SVD inputs and the same picks,
    # then apply the same factors; only the order of float32 sums may
    # differ between the batched one-device GEMMs and the per-device ones
    # (eps 1.2e-7 over a few hundred terms).  A different operator, as the
    # tolerance's own truncation error, reads 1e-3 or more.
    assert err <= 1e-6, err
    assert counter("dist/exchange-bytes") > 0
    print("OK dist_tol_matvec", err)

    # the same sweep at those ranks as one program (the abstract lowering's
    # entry): the same operator, to float32 rounding for the same reason
    static = jax.jit(jax.shard_map(
        lambda d: dist_compress_local(dshape, d, dshape_c.ranks, "blk"),
        mesh=mesh, in_specs=(dist_specs(dshape, "blk"),),
        out_specs=dist_specs(dshape_c, "blk"), check_vma=False))(dd)
    gap = 0.0
    for a, b in zip(jax.tree.leaves(static), jax.tree.leaves(dd_c)):
        assert a.shape == b.shape, (a.shape, b.shape)
        if a.size:
            a, b = np.asarray(a, np.float64), np.asarray(b, np.float64)
            gap = max(gap, np.abs(a - b).max() / max(np.abs(b).max(), 1.0))
    assert gap <= 1e-5, gap
    print("OK dist_static_sweep", gap)


def main():
    assert len(jax.devices()) == 4, jax.devices()
    mesh = jax.make_mesh((4,), ("blk",))
    {"partition": check_partition, "compress": check_compress}[
        sys.argv[1]](mesh)
    print("ALL_OK")


if __name__ == "__main__":
    main()
