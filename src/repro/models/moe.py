"""Mixture-of-Experts FFN with expert parallelism (qwen3-moe, grok-1).

Sharding over the ``model`` mesh axis (msize shards):
  * ``E % msize == 0`` (qwen3-moe: 128/16): classic EP — each shard owns
    ``E/msize`` experts; tokens are capacity-dispatched per data shard, each
    model shard computes its own experts, partial outputs are psum-combined.
  * ``E < msize`` (grok-1: 8 experts, 16 shards): **virtual experts** — each
    expert's FFN hidden dim F is split into ``v = msize/E`` slices and the
    weights are *stored* as [E*v, D, F/v]; shard m owns virtual expert m =
    (real expert m//v, F-slice m%v).  GLU/elementwise activations are exact
    under an F split, and the combining psum doubles as the F-slice sum.

Routing: softmax -> top-k -> renormalized gates, per-expert capacity
``C = ceil(T*k/E * cf)`` with sort-based dispatch (tokens over capacity drop
that expert's contribution).  ``cfg.moe_virtual`` (v) is fixed at config time
for the production mesh; the math is identical for any device count,
including the single-device smoke tests.
"""
from __future__ import annotations

import math
from typing import Any, Dict, Optional

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P

from repro.parallel.sharding import Rules
from .layers import dense_init


def moe_params(cfg, key, dtype) -> Dict[str, Any]:
    e, d, f = cfg.n_experts, cfg.d_model, cfg.moe_d_ff
    v = max(getattr(cfg, "moe_virtual", 1), 1)
    ev, fw = e * v, f // v
    keys = jax.random.split(key, 4)
    p = {
        "router": dense_init(keys[0], (d, e), dtype, scale=0.02),
        "moe_w1": dense_init(keys[1], (ev, d, fw), dtype),
        "moe_w2": dense_init(keys[2], (ev, fw, d), dtype),
    }
    if cfg.act == "swiglu":
        p["moe_w3"] = dense_init(keys[3], (ev, d, fw), dtype)
    return p


def _capacity(cfg, t_loc: int) -> int:
    c = int(math.ceil(t_loc * cfg.top_k * cfg.capacity_factor
                      / cfg.n_experts))
    return max(1, min(t_loc, c))


def _dispatch_indices(eid_flat: jax.Array, k: int, n_exp: int, cap: int):
    """Sort-based capacity dispatch: eid_flat [T*k] expert per choice.
    Returns (tok [E,C], slot [E,C], valid [E,C])."""
    order = jnp.argsort(eid_flat, stable=True)
    sorted_e = eid_flat[order]
    start = jnp.searchsorted(sorted_e, jnp.arange(n_exp, dtype=eid_flat.dtype))
    seg_len = jnp.append(start[1:], eid_flat.shape[0]) - start
    idx = start[:, None] + jnp.arange(cap)[None, :]
    valid = jnp.arange(cap)[None, :] < jnp.minimum(seg_len, cap)[:, None]
    idx = jnp.clip(idx, 0, eid_flat.shape[0] - 1)
    flat = jnp.take(order, idx)
    return flat // k, flat % k, valid


def _moe_shard(cfg, p_local, x, virt_offset):
    """One shard's contribution. x: [T, D]; p_local holds the shard's
    [e_loc, D, fw] weight slices; virt_offset: first virtual expert id.
    Returns the partial output [T, D] (psum over model completes it)."""
    t, d = x.shape
    v = max(getattr(cfg, "moe_virtual", 1), 1)
    e_loc = p_local["moe_w1"].shape[0]
    logits = (x @ p_local["router"]).astype(jnp.float32)
    probs = jax.nn.softmax(logits, axis=-1)
    gate, eid = jax.lax.top_k(probs, cfg.top_k)
    gate = gate / jnp.maximum(gate.sum(-1, keepdims=True), 1e-9)
    cap = _capacity(cfg, t)
    tok, slot, valid = _dispatch_indices(eid.reshape(-1).astype(jnp.int32),
                                         cfg.top_k, cfg.n_experts, cap)

    real_ids = (virt_offset + jnp.arange(e_loc)) // v       # [e_loc]
    tok_l = jnp.take(tok, real_ids, axis=0)                 # [e_loc, C]
    slot_l = jnp.take(slot, real_ids, axis=0)
    val_l = jnp.take(valid, real_ids, axis=0)

    xin = jnp.take(x, tok_l.reshape(-1), axis=0).reshape(e_loc, cap, d)
    xin = jnp.where(val_l[..., None], xin, 0.0)

    h = jnp.einsum("ecd,edf->ecf", xin, p_local["moe_w1"])
    if cfg.act == "swiglu":
        h = jax.nn.silu(h) * jnp.einsum("ecd,edf->ecf", xin,
                                        p_local["moe_w3"])
    elif cfg.act == "sq_relu":
        h = jnp.square(jax.nn.relu(h))
    else:
        h = jax.nn.gelu(h)
    out = jnp.einsum("ecf,efd->ecd", h, p_local["moe_w2"])

    g = jnp.take(gate.reshape(-1), tok_l * cfg.top_k + slot_l)
    out = out * jnp.where(val_l, g, 0.0)[..., None]
    y = jnp.zeros((t, d), out.dtype)
    y = y.at[tok_l.reshape(-1)].add(out.reshape(-1, d))
    return y


def moe_ffn(cfg, p, x: jax.Array, rules: Optional[Rules], mesh: Optional[Mesh]
            ) -> jax.Array:
    """x: [B, S, D] -> [B, S, D]."""
    b, s, d = x.shape
    if mesh is None or rules is None or rules.tp not in mesh.shape:
        y = _moe_shard(cfg, p, x.reshape(-1, d), 0)
        return y.reshape(b, s, d).astype(x.dtype)

    msize = mesh.shape[rules.tp]
    ev = p["moe_w1"].shape[0]
    e_loc = ev // msize

    def shard_fn(xb, pb):
        t_axis = jax.lax.axis_index(rules.tp)
        y = _moe_shard(cfg, pb, xb.reshape(-1, d), t_axis * e_loc)
        y = jax.lax.psum(y, rules.tp)
        return y.reshape(xb.shape)

    pspec = {k: (P() if k == "router" else P(rules.tp, None, None))
             for k in p}
    out = jax.shard_map(
        shard_fn, mesh=mesh,
        in_specs=(P(rules.dp, None, None), pspec),
        out_specs=P(rules.dp, None, None),
        check_vma=False)(x, p)
    return out.astype(x.dtype)
