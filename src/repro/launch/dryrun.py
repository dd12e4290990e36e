"""Multi-pod dry-run: lower + compile every (architecture x input shape) cell
on the production meshes, print memory/cost analysis, dump roofline inputs.

    PYTHONPATH=src python -m repro.launch.dryrun --arch qwen3-0.6b \
        --shape train_4k [--multi-pod]
    PYTHONPATH=src python -m repro.launch.dryrun --all

The first two lines below MUST run before any jax import: the dry-run (and
only the dry-run) needs 512 placeholder CPU devices so jax.make_mesh can
build the production mesh.
"""
import os
os.environ["XLA_FLAGS"] = ("--xla_force_host_platform_device_count=512 "
                           + os.environ.get("XLA_FLAGS", ""))
os.environ["JAX_PLATFORMS"] = "cpu"     # a host-platform rehearsal by design

import argparse          # noqa: E402
import json              # noqa: E402
import re                # noqa: E402
import time              # noqa: E402
import traceback         # noqa: E402
from typing import Any, Dict, Optional   # noqa: E402

import jax               # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np       # noqa: E402
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P  # noqa: E402

from repro.configs.base import (ALIASES, ARCHS, SHAPES, get_config,  # noqa: E402
                                shape_applicable)
from repro.models import api                      # noqa: E402
from repro.models.config import ModelConfig       # noqa: E402
from repro.optim import adamw                     # noqa: E402
from repro.parallel.sharding import Rules, make_param_shardings  # noqa: E402
from repro.perf import jaxpr_cost, hlo_cost       # noqa: E402
from .mesh import make_production_mesh, data_axes  # noqa: E402
from .shapes import (abstract_cache, batch_specs, cache_spec_tree,  # noqa: E402
                     input_specs)

RESULTS_PATH = os.path.join(os.path.dirname(__file__),
                            "..", "..", "..", "dryrun_results.json")

# collective ops in post-SPMD HLO (per-device operand shapes)
_COLL_RE = re.compile(
    r"(all-gather|all-reduce|reduce-scatter|all-to-all|collective-permute)"
    r"(?:-start)?[^=]*=\s*(?:\()?\s*((?:[a-z0-9]+\[[^\]]*\][,\s]*)+)")
_SHAPE_RE = re.compile(r"([a-z0-9]+)\[([0-9,]*)\]")
_DTYPE_BYTES = {"f64": 8, "f32": 4, "bf16": 2, "f16": 2, "s64": 8, "u64": 8,
                "s32": 4, "u32": 4, "s16": 2, "u16": 2, "s8": 1, "u8": 1,
                "pred": 1, "f8e4m3fn": 1, "f8e5m2": 1}


def collective_bytes(hlo_text: str) -> Dict[str, int]:
    """Sum output bytes of every collective op in the (partitioned) HLO."""
    out: Dict[str, int] = {}
    for m in _COLL_RE.finditer(hlo_text):
        op = m.group(1)
        total = 0
        for dt, dims in _SHAPE_RE.findall(m.group(2)):
            n = 1
            for d in dims.split(","):
                if d:
                    n *= int(d)
            total += n * _DTYPE_BYTES.get(dt, 4)
        out[op] = out.get(op, 0) + total
    return out


def _cfg_for_dryrun(cfg: ModelConfig, shape_name: str) -> ModelConfig:
    import dataclasses
    over = {}
    # long-context decode needs bigger flash blocks never used (decode path);
    # keep defaults.  Loss chunk: keep [B,chunk,V] per-device manageable.
    if shape_name == "train_4k":
        over["loss_chunk"] = 512
    return dataclasses.replace(cfg, **over) if over else cfg


def lower_cell(arch: str, shape_name: str, *, multi_pod: bool = False,
               mesh: Optional[Mesh] = None, compile_: bool = True,
               variant: Optional[str] = None) -> Dict[str, Any]:
    """Lower+compile one cell; returns roofline inputs.

    ``variant`` selects a §Perf experiment:
      serve-nofsdp — params replicated over the data axes at serve time
                     (kills the per-step FSDP weight regather)
      opt-bf16     — AdamW moments in bf16 (8-bit-Adam-style state slimming)
      cache-2d     — long-context decode cache sequence-sharded over
                     (data x model) instead of model only
    """
    cfg = _cfg_for_dryrun(get_config(arch), shape_name)
    shape = SHAPES[shape_name]
    ok, reason = shape_applicable(cfg, shape_name)
    if not ok:
        return {"arch": arch, "shape": shape_name, "skipped": reason}

    if mesh is None:
        mesh = make_production_mesh(multi_pod=multi_pod)
    msize = mesh.shape["model"]
    daxes = data_axes(mesh)
    dsize = int(np.prod([mesh.shape[a] for a in daxes]))
    serve_fsdp = not (variant == "serve-nofsdp" and shape.kind != "train")
    seq_axes = tuple(daxes) + ("model",) if (
        variant == "cache-2d" and shape.global_batch % dsize != 0) else None
    rules = Rules(data_axes=daxes, model_axis="model",
                  attn_tp=(cfg.n_kv_heads % msize == 0),
                  batch_shardable=(shape.global_batch % dsize == 0),
                  fsdp=serve_fsdp, seq_axes_decode=seq_axes,
                  seq_parallel=(variant != "no-sp"))
    n_dev = int(np.prod(list(mesh.shape.values())))

    params_sds = api.abstract_params(cfg)
    if variant == "zero1":
        # ZeRO-1: params replicated over data (no per-layer regather);
        # optimizer state stays fully sharded
        import dataclasses as _dc
        param_sh = make_param_shardings(
            params_sds, _dc.replace(rules, fsdp=False), mesh)
    else:
        param_sh = make_param_shardings(params_sds, rules, mesh)
    batch_sds = input_specs(cfg, shape)
    batch_sh = jax.tree.map(lambda s: NamedSharding(mesh, s),
                            batch_specs(cfg, shape, rules))

    t0 = time.time()
    jx_cost = None
    with mesh:
        if shape.kind == "train":
            opt_cfg = adamw.AdamWConfig(
                master_dtype="bfloat16" if variant == "opt-bf16"
                else "float32")
            opt_sds = jax.eval_shape(
                lambda p: adamw.init_state(opt_cfg, p), params_sds)
            moment_sh = make_param_shardings(params_sds, rules, mesh) \
                if variant == "zero1" else param_sh
            opt_sh = adamw.AdamWState(
                step=NamedSharding(mesh, P()),
                m=jax.tree.map(lambda s: s, moment_sh),
                v=jax.tree.map(lambda s: s, moment_sh))

            def train_step(params, opt, batch):
                loss, grads = jax.value_and_grad(
                    lambda p: api.train_loss(cfg, p, batch, rules, msize,
                                             mesh))(params)
                new_p, new_opt, metrics = adamw.apply_updates(
                    opt_cfg, params, grads, opt)
                return new_p, new_opt, loss

            lowered = jax.jit(
                train_step,
                in_shardings=(param_sh, opt_sh, batch_sh),
                out_shardings=(param_sh, opt_sh, NamedSharding(mesh, P())),
            ).lower(params_sds, opt_sds, batch_sds)
            jx_cost = jaxpr_cost.analyze(train_step, params_sds, opt_sds,
                                         batch_sds)
        elif shape.kind == "prefill":
            def prefill_step(params, batch):
                return api.prefill(cfg, params, batch, rules, msize, mesh,
                                   cache_len=shape.seq_len)

            lowered = jax.jit(
                prefill_step,
                in_shardings=(param_sh, batch_sh),
            ).lower(params_sds, batch_sds)
            jx_cost = jaxpr_cost.analyze(prefill_step, params_sds, batch_sds)
        else:  # decode
            cache_sds = abstract_cache(cfg, shape)
            cache_sh = jax.tree.map(
                lambda s: NamedSharding(mesh, s),
                cache_spec_tree(cfg, cache_sds, rules, msize=msize,
                                dsize=dsize,
                                seq_2d=(variant == "cache-2d")))

            def serve_step(params, batch, cache, pos):
                return api.decode_step(cfg, params, batch, cache, pos,
                                       rules, msize, mesh)

            lowered = jax.jit(
                serve_step,
                in_shardings=(param_sh, batch_sh, cache_sh,
                              NamedSharding(mesh, P())),
                out_shardings=(NamedSharding(mesh, P(rules.dp, None)),
                               cache_sh),
                donate_argnums=(2,),
            ).lower(params_sds, batch_sds, cache_sds,
                    jax.ShapeDtypeStruct((), jnp.int32))
            jx_cost = jaxpr_cost.analyze(serve_step, params_sds, batch_sds,
                                         cache_sds,
                                         jax.ShapeDtypeStruct((), jnp.int32))

    lower_s = time.time() - t0
    res: Dict[str, Any] = {
        "arch": arch, "shape": shape_name, "mesh": dict(mesh.shape),
        "platform": jax.default_backend(),
        "n_devices": n_dev, "kind": shape.kind, "lower_s": round(lower_s, 1),
    }
    if jx_cost is not None:
        # global exact flops/bytes from the jaxpr walker (scan-corrected)
        res["jaxpr_flops_global"] = jx_cost["flops"]
        res["jaxpr_bytes_global"] = jx_cost["bytes"]
        res["flops_per_device"] = jx_cost["flops"] / n_dev
        res["bytes_per_device"] = jx_cost["bytes"] / n_dev
    if not compile_:
        return res
    t0 = time.time()
    compiled = lowered.compile()
    res["compile_s"] = round(time.time() - t0, 1)

    ca = compiled.cost_analysis()
    res["xla_flops"] = float(ca.get("flops", -1))       # loop-undercounted
    res["xla_bytes_accessed"] = float(ca.get("bytes accessed", -1))
    try:
        ma = compiled.memory_analysis()
        if ma is not None:
            res["memory"] = {
                k: int(getattr(ma, k)) for k in
                ("argument_size_in_bytes", "output_size_in_bytes",
                 "temp_size_in_bytes", "generated_code_size_in_bytes")
                if hasattr(ma, k)}
    except Exception:
        pass
    hlo = compiled.as_text()
    res["collectives"] = hlo_cost.collective_bytes(hlo)        # loop-corrected
    res["collectives_flat"] = hlo_cost.collective_bytes_flat(hlo)
    return res


def run_cells(archs, shapes, *, multi_pod=False, compile_=True,
              out_path: Optional[str] = None):
    mesh = make_production_mesh(multi_pod=multi_pod)
    results = []
    for arch in archs:
        for shape_name in shapes:
            tag = f"{arch} x {shape_name} x " \
                  f"{'2pod' if multi_pod else '1pod'}"
            try:
                r = lower_cell(arch, shape_name, multi_pod=multi_pod,
                               mesh=mesh, compile_=compile_)
                if "skipped" in r:
                    print(f"SKIP {tag}: {r['skipped']}")
                else:
                    print(f"OK   {tag}: "
                          f"flops/dev={r.get('flops_per_device', 0):.3e} "
                          f"lower={r.get('lower_s')}s "
                          f"compile={r.get('compile_s')}s "
                          f"coll={sum(r.get('collectives', {}).values()):.3e}B")
            except Exception as e:
                r = {"arch": arch, "shape": shape_name,
                     "error": f"{type(e).__name__}: {e}",
                     "traceback": traceback.format_exc()[-2000:]}
                print(f"FAIL {tag}: {r['error']}")
            r["multi_pod"] = multi_pod
            results.append(r)
            if out_path:
                with open(out_path, "w") as f:
                    json.dump(results, f, indent=1)
    return results


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None)
    ap.add_argument("--shape", default=None)
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--both-meshes", action="store_true")
    ap.add_argument("--no-compile", action="store_true")
    ap.add_argument("--out", default="dryrun_results.json")
    args = ap.parse_args()

    archs = ARCHS if (args.all or not args.arch) else [args.arch]
    shapes = list(SHAPES) if (args.all or not args.shape) else [args.shape]
    all_results = run_cells(archs, shapes, multi_pod=args.multi_pod,
                            compile_=not args.no_compile, out_path=args.out)
    if args.both_meshes:
        all_results += run_cells(archs, shapes, multi_pod=True,
                                 compile_=not args.no_compile,
                                 out_path=args.out.replace(".json",
                                                           "_2pod.json"))
    n_ok = sum(1 for r in all_results if "flops" in r or "lower_s" in r)
    n_skip = sum(1 for r in all_results if "skipped" in r)
    n_fail = sum(1 for r in all_results if "error" in r)
    print(f"\n{n_ok} ok, {n_skip} skipped, {n_fail} failed")
    return 0 if n_fail == 0 else 1


if __name__ == "__main__":
    raise SystemExit(main())
