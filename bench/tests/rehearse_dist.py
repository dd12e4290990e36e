"""Tiny rehearsal of the four-chip cell on four fake CPU devices, through
the pieces the harness calls (``bench.run.Cell`` / ``bench.run.run``);
run in a subprocess (the device count is fixed when JAX starts):

    python bench/tests/rehearse_dist.py

Prints one "OK <name>" line per passing check;
``bench/tests/test_rehearsal_dist.py`` asserts on them.
"""
import json
import os
import sys
from pathlib import Path

os.environ["XLA_FLAGS"] = (
    "--xla_force_host_platform_device_count=4 "
    + os.environ.get("XLA_FLAGS", ""))
os.environ["JAX_PLATFORMS"] = "cpu"

BENCH = Path(__file__).resolve().parents[1]
ROOT = BENCH.parent
TINY = BENCH / "tests" / "data" / "tiny"
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

from bench import controls  # noqa: E402
from bench import run as harness  # noqa: E402

CELL = "cov2d-n32-p4.apply8-p4"
NEW = ["hgemv_ms.apply-p4", "exchange_ms.apply-p4", "exchange_gbps.apply-p4",
       "dist_roofline.apply-p4", "idle_pct.apply-p4", "unscoped_pct.apply-p4",
       "partition_s.apply-p4"]
SPEC = {
    "workloads": [{"name": CELL, "config": "cov2d-n32-p4",
                   "traffic": "apply8-p4", "chips": 4}],
    "end_to_end": [{"name": "apply_ms", "unit": "ms", "workloads": [CELL]},
                   {"name": "setup_s", "unit": "s"},
                   {"name": "peak_hbm_gib", "unit": "GiB",
                    "workloads": [CELL]}],
    "per_layer": [{"name": n, "unit": "-", "workloads": [CELL]}
                  for n in NEW],
}
SEED = 2**31 + 7
SCOPED = NEW[:4]
PEAKS = {"f32_highest_flops_per_s": 1e12, "hbm_bytes_per_s": 1e11}
# ops of one distributed application as a TPU trace names them (scope path,
# device seconds); the exchange is the first four
OPS = [("jit_f/shmap_body/hgemv/upsweep/hgemv/root-gather/all-gather", 1e-4),
       ("jit_f/shmap_body/hgemv/exchange/halo/pack/gather", 2e-4),
       ("jit_f/shmap_body/hgemv/exchange/halo/round/collective-permute", 3e-4),
       ("jit_f/shmap_body/hgemv/off-gemm/halo/land/slice", 4e-4),
       ("jit_f/shmap_body/hgemv/diag-gemm/dot_general", 5e-4),
       ("jit_f/shmap_body/hgemv/downsweep/dot_general", 6e-4)]


def scoped_readings(cell, ctx):
    """The scope readers on a four-device trace of ``ctx["matvecs"]``
    applications whose ops carry the program's scopes (``OPS``)."""
    from bench.trace_reduce import Op, Reduced
    devices = []
    for _ in range(4):
        t, ops = 0, []
        for _ in range(ctx["matvecs"]):
            for scope, secs in OPS:
                ops.append(Op(t, t + int(secs * 1e9), "op", scope))
                t += int(secs * 1e9)
        devices.append(ops)
    red = Reduced(devices, [(0, t, "bench/unit")], (0, t))
    values = {n: cell.reader(n)(dict(ctx, reduced=red)) for n in SCOPED}
    per = 1e3 * sum(s for _, s in OPS[:4])
    assert abs(values["exchange_ms.apply-p4"] - per) < 1e-9 * per, values
    assert abs(values["hgemv_ms.apply-p4"] -
               1e3 * sum(s for _, s in OPS)) < 1e-6, values
    return values


def over(compared):
    return [k for k, c in compared.items() if not c["value"] <= c["limit"]]


def main():
    import jax
    import numpy as np
    from bench.reference.cheb_h2 import for_config
    from bench.reference.dist_answer import block_gap, to_host
    from bench.reference.h2_answer import to_host as one_chip_to_host
    from repro.core.construction import construct_h2_host
    from repro.core.dist import partition_h2
    from repro.core.kernels_fn import exponential_kernel
    from bench.reference.grids import regular_grid

    devices = jax.devices()[:4]
    assert len(devices) == 4, jax.devices()

    # the factor read-back of a partition is the operator, block for block
    cfg = json.loads((TINY / "configs" / "cov2d-n32-p4.json").read_text())
    shape, data, tree, _ = construct_h2_host(
        regular_grid(cfg["grid"]), exponential_kernel(0.1), cfg["leaf"],
        cfg["cheb_p"], cfg["eta"])
    want = one_chip_to_host(data)
    dshape, ddata = partition_h2(shape, data, 4)
    got = to_host(ddata, dshape)

    def blocks(h, key, rows, cols):
        out = []
        for lv, (v, r, c) in enumerate(zip(h[key], h[rows], h[cols])):
            out += [(lv, int(a), int(b), x.tobytes())
                    for a, b, x in zip(r, c, np.asarray(v))]
        return sorted(out)

    assert blocks(got, "s", "s_rows", "s_cols") == \
        blocks(want, "s", "s_rows", "s_cols")
    assert blocks({k: [got[k]] for k in ("dense", "d_rows", "d_cols")},
                  "dense", "d_rows", "d_cols") == \
        blocks({k: [want[k]] for k in ("dense", "d_rows", "d_cols")},
               "dense", "d_rows", "d_cols")
    for key in ("u_leaf", "v_leaf"):
        assert np.array_equal(got[key], want[key]), key
    for key in ("e", "f"):
        assert all(np.array_equal(a, b) for a, b in zip(got[key], want[key]))
    print("OK readback")

    # the block sets against the plain reference's: a block left out, one
    # read twice, or another tree order, each show
    ref = for_config(json.dumps(cfg, sort_keys=True))
    perm = np.asarray(tree.perm)
    assert block_gap(got, ref, perm) == 0
    lv = max(range(len(got["s"])), key=lambda l: got["s"][l].shape[0])
    for cut in (slice(1, None), np.r_[0, np.arange(got["s"][lv].shape[0])]):
        assert block_gap(dict(got, s_rows=[
            r[cut] if l == lv else r for l, r in enumerate(got["s_rows"])],
            s_cols=[c[cut] if l == lv else c
                    for l, c in enumerate(got["s_cols"])]), ref, perm) == 1
    assert block_gap(dict(got, d_rows=got["d_rows"][1:],
                          d_cols=got["d_cols"][1:]), ref, perm) == 1
    assert block_gap(got, ref, perm[::-1]) > 0
    print("OK block_gap")

    cell = harness.Cell(CELL, SPEC, data=TINY)
    res = harness.run(cell, SEED, 1.0, False, jax, devices, {})
    json.dumps(res)
    assert res["correct"], res["compared"]
    assert set(res["metrics"]) == {"apply_ms", "setup_s", "peak_hbm_gib"}
    assert res["device"]["count"] == 4
    print("OK run", {k: v["value"] for k, v in res["compared"].items()})

    res = harness.run(cell, SEED + 1, 0.5, True, jax, devices, PEAKS)
    assert res["correct"], res["compared"]
    print("OK traced", {k: v["value"] for k, v in res["metrics"].items()})
    # the CPU trace names no scopes: there the readers of scopes leave
    # their metric out, and the others report
    missing = [n for n in NEW if n not in res["metrics"]]
    assert set(missing) <= set(SCOPED), (missing, res["metrics"])

    op = cell.op
    state = op.setup(cell.system.build(cell.config), cell.config,
                     cell.traffic, SEED)
    outputs = [op.unit(state, i) for i in range(4)]
    op.drain(state)
    values = scoped_readings(cell, dict(op.summarize(state, outputs, 1.0),
                                        peaks=PEAKS))
    op.release(state)
    assert all(v is not None and v > 0 for v in values.values()), values
    print("OK readers", values)
    r = cell.op.control(state, len(outputs), cell.config, cell.traffic,
                        SEED, "bf16x3", controls.no_limits())
    failed = [k for k, v in r.items() if not v["value"] <= cell.limits[k]]
    assert failed, r
    print("OK control", failed)

    for fault in cell.op.FAULTS:
        broken = harness.Cell(CELL, SPEC, data=TINY)
        broken.op = controls.planted(broken.op, fault)
        res = harness.run(broken, SEED, 0.5, False, jax, devices, {})
        assert res["correct"] is False and over(res["compared"]), \
            (fault, res["compared"])
        print("OK fault", fault, over(res["compared"]))
    print("ALL_OK")


if __name__ == "__main__":
    main()
