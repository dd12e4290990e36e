"""Dry-run for the paper's own workloads: distributed H^2 matvec and
compression on the production meshes.

Structure sizing: the paper's 2D/3D exponential-kernel test sets with the
paper's local problem size (2^19 rows/device for matvec, §6.2) are too large
to build index arrays for on this host at full scale, so the block structure
is *measured* on a moderate-depth tree and extrapolated level-wise — interior
block-rows of a regular grid are translation-invariant, so per-level counts
converge to C_sp-bounded constants (paper §2.1).  All value/index arrays are
ShapeDtypeStructs; nothing is allocated.
"""
import os
os.environ["XLA_FLAGS"] = ("--xla_force_host_platform_device_count=512 "
                           + os.environ.get("XLA_FLAGS", ""))
os.environ["JAX_PLATFORMS"] = "cpu"     # a host-platform rehearsal by design

import argparse          # noqa: E402
import dataclasses       # noqa: E402
import json              # noqa: E402
import time              # noqa: E402
import traceback         # noqa: E402
from typing import Dict, Optional  # noqa: E402

import jax               # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np       # noqa: E402
from jax.sharding import NamedSharding, PartitionSpec as P  # noqa: E402

from repro.core.clustering import build_cluster_tree, regular_grid_points  # noqa: E402
from repro.core.admissibility import build_block_structure  # noqa: E402
from repro.core.dist import (DistH2Data, DistH2Shape, dist_specs,  # noqa: E402
                             dist_h2_matvec_local, dist_compress_local,
                             matvec_comm_bytes)
from repro.perf import hlo_cost, jaxpr_cost       # noqa: E402
from .mesh import make_production_mesh, data_axes  # noqa: E402


def measured_structure_stats(dim: int, depth_probe: int = 9, m: int = 64,
                             eta: float = 0.9) -> Dict:
    """Per-level (blocks/row, halo radius) constants from a probe tree."""
    side = int(round((m * (1 << depth_probe)) ** (1.0 / dim)))
    # snap to a power-of-two-compatible point count
    n = m * (1 << depth_probe)
    if dim == 2:
        side = int(np.sqrt(n))
    else:
        side = int(round(n ** (1 / 3)))
    pts = regular_grid_points(side, dim)
    # pad/trim to n by tiling the grid slightly larger then trimming
    if pts.shape[0] < n:
        reps = int(np.ceil(n / pts.shape[0]))
        pts = np.concatenate([pts + i * 1.5 for i in range(reps)])[:n]
    else:
        pts = pts[:n]
    tree = build_cluster_tree(pts, m)
    bs = build_block_structure(tree, eta)
    per_row = []
    for l in range(tree.depth + 1):
        nn = 1 << l
        per_row.append(bs.s_rows[l].shape[0] / nn)
    dense_per_row = bs.d_rows.shape[0] / (1 << tree.depth)
    return {"per_row": per_row, "dense_per_row": dense_per_row,
            "row_maxb": list(bs.row_maxb()), "Csp": bs.sparsity_constant()}


def synth_dist_shape(p: int, depth: int, m: int, k: int, stats: Dict
                     ) -> DistH2Shape:
    """Extrapolate the probe stats to a depth-``depth`` tree on p devices."""
    lc = int(np.log2(p))
    per_row = stats["per_row"]
    maxb = stats["row_maxb"]

    def level_stat(arr, l, default):
        # deep levels converge to the probe's deepest interior level
        if l < len(arr):
            return arr[l]
        return arr[-2] if len(arr) > 1 else default

    br_counts, br_rad, row_maxb = [], [], []
    br_offsets, br_caps = [], []
    for l in range(depth + 1):
        row_maxb.append(int(level_stat(maxb, l, 8)) or 0)
    for l in range(lc, depth + 1):
        nloc = (1 << l) // p
        cnt = int(np.ceil(level_stat(per_row, l, 6) * nloc))
        br_counts.append(max(cnt, 1))
        rad = 1 if l > lc else min(2, p - 1)
        br_rad.append(rad)
        # compressed-plan statics: boundary-band send caps per offset (the
        # interior of a regular grid never crosses devices, so the packed
        # rows per neighbor are O(row_maxb), independent of nloc)
        offs = tuple(d for d in range(-rad, rad + 1) if d != 0)
        cap = min(nloc, max(row_maxb[l], 1))
        br_offsets.append(offs)
        br_caps.append(tuple([cap] * len(offs)))
    top_counts = tuple(int(np.ceil(level_stat(per_row, l, 0) * (1 << l)))
                       for l in range(lc))
    nbd = max(int(np.ceil(stats["dense_per_row"] * ((1 << depth) // p))), 1)
    dense_maxb = max(int(np.ceil(stats["dense_per_row"])), 1)
    nl_loc = (1 << depth) // p
    dense_offs = (-1, 1)
    dense_caps = (min(nl_loc, dense_maxb), min(nl_loc, dense_maxb))
    return DistH2Shape(
        n=m * (1 << depth), leaf_size=m, depth=depth,
        ranks=tuple([k] * (depth + 1)), p=p, lc=lc,
        br_counts=tuple(br_counts), br_radius=tuple(br_rad),
        top_counts=top_counts, dense_count=nbd, dense_radius=1,
        row_maxb=tuple(row_maxb), symmetric=True,
        dense_maxb=dense_maxb,
        br_offsets=tuple(br_offsets), br_caps=tuple(br_caps),
        dense_offsets=dense_offs,
        dense_caps=dense_caps)


def abstract_dist_data(ds: DistH2Shape, dtype=jnp.float32) -> DistH2Data:
    sds = jax.ShapeDtypeStruct
    m, p = ds.leaf_size, ds.p
    nl = (1 << ds.depth)
    k = ds.ranks[0]
    e_br = [sds((p, 0, 0), dtype)]
    s_br, s_r, s_c = [], [], []
    for l in range(ds.lc + 1, ds.depth + 1):
        e_br.append(sds((1 << l, k, k), dtype))
    for i, l in enumerate(range(ds.lc, ds.depth + 1)):
        nb = p * ds.br_counts[i]
        s_br.append(sds((nb, k, k), dtype))
        s_r.append(sds((nb,), jnp.int32))
        s_c.append(sds((nb,), jnp.int32))
    e_top = [sds((0, 0, 0), dtype)] + \
        [sds((1 << l, k, k), dtype) for l in range(1, ds.lc + 1)]
    s_top, st_r, st_c = [], [], []
    for l in range(ds.lc):
        s_top.append(sds((ds.top_counts[l], k, k), dtype))
        st_r.append(sds((ds.top_counts[l],), jnp.int32))
        st_c.append(sds((ds.top_counts[l],), jnp.int32))
    nbd = p * ds.dense_count
    # marshaling plan + marshaled buffers (same static sizing rules as
    # partition_h2: per-level maxb >= 1 so empty levels stay well-formed)
    i32 = jnp.int32
    pb_blk, pb_col, s_br_mar = [], [], []
    for i, l in enumerate(range(ds.lc, ds.depth + 1)):
        nloc = ds.nodes_local(l)
        maxb = max(ds.row_maxb[l], 1)
        pb_blk.append(sds((p * nloc * maxb,), i32))
        pb_col.append(sds((p * nloc * maxb,), i32))
        s_br_mar.append(sds((p * nloc, k, maxb * k), dtype))
    pt_blk, pt_col, s_top_mar = [], [], []
    for l in range(ds.lc):
        maxb = ds.row_maxb[l]
        pt_blk.append(sds(((1 << l) * maxb,), i32))
        pt_col.append(sds(((1 << l) * maxb,), i32))
        s_top_mar.append(sds((1 << l, k, maxb * k), dtype))
    nl_loc_tot = nl
    # compressed halo plan + diag/off marshaled twins: interior rows of a
    # regular grid are diagonal-only, so the row-compressed off twin spans
    # only the O(boundary) rows (bounded here by the summed send caps)
    # while the diag twin keeps the full row_maxb slot width
    from repro.core.halo import HaloPlan
    hp_br, s_br_mar_diag, s_br_mar_off = [], [], []
    for i, l in enumerate(range(ds.lc, ds.depth + 1)):
        nloc = ds.nodes_local(l)
        maxb = max(ds.row_maxb[l], 1)
        n_bnd = min(nloc, sum(ds.br_caps[i]))
        maxb_o = min(maxb, 4)
        hp_br.append(HaloPlan(
            send=[sds((p * cap,), i32) for cap in ds.br_caps[i]],
            comb_idx=sds((p * nloc * maxb,), i32),
            diag_blk=sds((p * nloc * maxb,), i32),
            diag_col=sds((p * nloc * maxb,), i32),
            bnd_rows=sds((p * n_bnd,), i32),
            rowpos=sds((p * nloc,), i32),
            off_blk=sds((p * n_bnd * maxb_o,), i32),
            off_idx=sds((p * n_bnd * maxb_o,), i32),
            blk_idx=sds((p * ds.br_counts[i],), i32)))
        s_br_mar_diag.append(sds((p * nloc, k, maxb * k), dtype))
        s_br_mar_off.append(sds((p * n_bnd, k, maxb_o * k), dtype))
    nl_loc = nl // p
    d_bnd = min(nl_loc, sum(ds.dense_caps))
    dmaxb_o = min(ds.dense_maxb, 4)
    hp_dense = HaloPlan(
        send=[sds((p * cap,), i32) for cap in ds.dense_caps],
        comb_idx=sds((nl * ds.dense_maxb,), i32),
        diag_blk=sds((nl * ds.dense_maxb,), i32),
        diag_col=sds((nl * ds.dense_maxb,), i32),
        bnd_rows=sds((p * d_bnd,), i32),
        rowpos=sds((nl,), i32),
        off_blk=sds((p * d_bnd * dmaxb_o,), i32),
        off_idx=sds((p * d_bnd * dmaxb_o,), i32),
        blk_idx=sds((p * ds.dense_count,), i32))
    return DistH2Data(
        u_leaf=sds((nl, m, k), dtype), v_leaf=sds((nl, m, k), dtype),
        e_br=e_br, f_br=list(e_br),
        s_br=s_br, s_br_rows=s_r, s_br_cols=s_c,
        e_top=e_top, f_top=list(e_top),
        s_top=s_top, s_top_rows=st_r, s_top_cols=st_c,
        dense=sds((nbd, m, m), dtype), d_rows=sds((nbd,), jnp.int32),
        d_cols=sds((nbd,), jnp.int32),
        pb_blk=pb_blk, pb_col=pb_col, s_br_mar=s_br_mar,
        pt_blk=pt_blk, pt_col=pt_col, s_top_mar=s_top_mar,
        pd_col=sds((nl_loc_tot * ds.dense_maxb,), i32),
        dense_mar=sds((nl_loc_tot, m, ds.dense_maxb * m), dtype),
        hp_br=hp_br, hp_dense=hp_dense,
        s_br_mar_diag=s_br_mar_diag, s_br_mar_off=s_br_mar_off,
        dense_mar_diag=sds((nl_loc_tot, m, ds.dense_maxb * m), dtype),
        dense_mar_off=sds((p * d_bnd, m, dmaxb_o * m), dtype))


def lower_h2_cell(kind: str, *, dim: int, nv: int, multi_pod: bool,
                  per_dev_rows_log2: int = 19, m: int = 64, k: int = 64,
                  comm: str = "ppermute") -> Dict:
    mesh = make_production_mesh(multi_pod=multi_pod)
    daxes = data_axes(mesh)
    p = int(np.prod([mesh.shape[a] for a in daxes]))
    n_dev = int(np.prod(list(mesh.shape.values())))
    stats = measured_structure_stats(dim)
    depth = int(np.log2(p)) + per_dev_rows_log2 - int(np.log2(m))
    ds = synth_dist_shape(p, depth, m, k, stats)
    data_sds = abstract_dist_data(ds)
    axis = daxes if len(daxes) > 1 else daxes[0]
    specs = dist_specs(ds, axis)

    t0 = time.time()
    with mesh:
        data_sh = jax.tree.map(lambda s: NamedSharding(mesh, s), specs,
                               is_leaf=lambda x: isinstance(x, P))
        if kind == "pcg":
            # a whole distributed PCG solve as ONE program: the while_loop
            # body is the halo-plan matvec + psum'd dot products
            # (repro/solvers); trip count is data-dependent, so jaxpr
            # flops are per-iteration lower bounds
            from repro.solvers import pcg as _kpcg
            from repro.solvers.distributed import result_specs
            x_sds = jax.ShapeDtypeStruct((ds.n,), jnp.float32)
            x_sh = NamedSharding(mesh, P(axis))

            def step(d, b):
                def apply_a(xl):
                    return dist_h2_matvec_local(ds, d, xl[:, None], axis,
                                                comm)[:, 0]
                return _kpcg(apply_a, b, tol=1e-6, maxiter=10, axis=axis)

            out_sp = result_specs(P(axis))
            fn = jax.shard_map(step, mesh=mesh, in_specs=(specs, P(axis)),
                               out_specs=out_sp, check_vma=False)
            out_sh = jax.tree.map(lambda s: NamedSharding(mesh, s), out_sp,
                                  is_leaf=lambda x: isinstance(x, P))
            lowered = jax.jit(fn, in_shardings=(data_sh, x_sh),
                              out_shardings=out_sh).lower(data_sds, x_sds)
            jx = jaxpr_cost.analyze(fn, data_sds, x_sds)
        elif kind == "matvec":
            x_sds = jax.ShapeDtypeStruct((ds.n, nv), jnp.float32)
            x_sh = NamedSharding(mesh, P(axis, "model" if nv >= 16 else None))

            def step(d, x):
                return dist_h2_matvec_local(ds, d, x, axis, comm)

            fn = jax.shard_map(step, mesh=mesh,
                               in_specs=(specs, P(axis, None)),
                               out_specs=P(axis, None), check_vma=False)
            lowered = jax.jit(fn, in_shardings=(data_sh, x_sh),
                              out_shardings=x_sh).lower(data_sds, x_sds)
            jx = jaxpr_cost.analyze(fn, data_sds, x_sds)
        else:  # compress
            tgt = tuple([max(k // 4, 8)] * (depth + 1))

            def step(d):
                return dist_compress_local(ds, d, tgt, axis)

            out_specs = dist_specs(dataclasses.replace(ds, ranks=tgt), axis)
            fn = jax.shard_map(step, mesh=mesh, in_specs=(specs,),
                               out_specs=out_specs, check_vma=False)
            out_sh = jax.tree.map(lambda s: NamedSharding(mesh, s), out_specs,
                                  is_leaf=lambda x: isinstance(x, P))
            lowered = jax.jit(fn, in_shardings=(data_sh,),
                              out_shardings=out_sh).lower(data_sds)
            jx = jaxpr_cost.analyze(fn, data_sds)

    res = {"cell": f"h2-{dim}d-{kind}" + (f"-nv{nv}" if kind == "matvec"
                                          else ""),
           "platform": jax.default_backend(),
           "mesh": dict(mesh.shape), "n": ds.n, "depth": depth,
           "k": k, "m": m, "comm": comm,
           "lower_s": round(time.time() - t0, 1),
           "flops_per_device": jx["flops"] / n_dev,
           "bytes_per_device": jx["bytes"] / n_dev,
           "Csp": stats["Csp"]}
    if kind == "matvec":
        res["model_comm_bytes"] = matvec_comm_bytes(ds, nv, comm)
    elif kind == "pcg":
        from repro.solvers import krylov_comm_bytes
        res["model_comm_bytes_per_iter"] = krylov_comm_bytes(ds, 1, comm)
    t0 = time.time()
    compiled = lowered.compile()
    res["compile_s"] = round(time.time() - t0, 1)
    ca = compiled.cost_analysis()
    res["xla_flops"] = float(ca.get("flops", -1))
    hlo = compiled.as_text()
    res["collectives"] = hlo_cost.collective_bytes(hlo)
    try:
        ma = compiled.memory_analysis()
        res["memory"] = {kk: int(getattr(ma, kk)) for kk in
                         ("argument_size_in_bytes", "temp_size_in_bytes")
                         if hasattr(ma, kk)}
    except Exception:
        pass
    return res


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--rows-log2", type=int, default=19)
    ap.add_argument("--out", default="dryrun_h2.json")
    ap.add_argument("--cells", default="matvec1,matvec64,compress,pcg")
    args = ap.parse_args()
    results = []
    for dim in (2, 3):
        for cell in args.cells.split(","):
            try:
                if cell.startswith("matvec"):
                    nv = int(cell[len("matvec"):] or 1)
                    for comm in ("halo-plan", "ppermute", "allgather"):
                        r = lower_h2_cell("matvec", dim=dim, nv=nv,
                                          multi_pod=args.multi_pod,
                                          per_dev_rows_log2=args.rows_log2,
                                          comm=comm)
                        results.append(r)
                        print(f"OK {r['cell']} {comm}: "
                              f"flops/dev={r['flops_per_device']:.3e} "
                              f"coll={sum(r['collectives'].values()):.3e}B "
                              f"compile={r['compile_s']}s")
                elif cell == "pcg":
                    r = lower_h2_cell("pcg", dim=dim, nv=1,
                                      multi_pod=args.multi_pod,
                                      per_dev_rows_log2=args.rows_log2,
                                      comm="halo-plan")
                    results.append(r)
                    print(f"OK {r['cell']}: "
                          f"flops/dev={r['flops_per_device']:.3e} "
                          f"coll={sum(r['collectives'].values()):.3e}B "
                          f"compile={r['compile_s']}s")
                else:
                    r = lower_h2_cell("compress", dim=dim, nv=1,
                                      multi_pod=args.multi_pod,
                                      per_dev_rows_log2=args.rows_log2)
                    results.append(r)
                    print(f"OK {r['cell']}: "
                          f"flops/dev={r['flops_per_device']:.3e} "
                          f"compile={r['compile_s']}s")
            except Exception as e:
                results.append({"cell": f"h2-{dim}d-{cell}",
                                "error": f"{type(e).__name__}: {e}",
                                "traceback": traceback.format_exc()[-1500:]})
                print(f"FAIL h2-{dim}d-{cell}: {e}")
            with open(args.out, "w") as f:
                json.dump(results, f, indent=1)
    n_fail = sum(1 for r in results if "error" in r)
    print(f"{len(results) - n_fail} ok, {n_fail} failed")
    return 1 if n_fail else 0


if __name__ == "__main__":
    raise SystemExit(main())
