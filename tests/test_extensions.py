"""Tests for the beyond-core extensions: the H² token-mixing layer, int8
KV-cache quantization, and the perf analyzers' edge cases."""
import numpy as np
import pytest
import jax
import jax.numpy as jnp

from repro.configs.base import get_config


class TestH2Mixer:
    def test_matches_dense_kernel_mix(self):
        from repro.models.h2mixer import (h2mixer_structure, h2mixer_params,
                                          h2mixer_apply)
        cfg = get_config("qwen3-0.6b").reduced(param_dtype="float32",
                                               act_dtype="float32")
        s = 128
        shape, data = h2mixer_structure(s, leaf_size=8, cheb_p=5,
                                        tol=None, corr=0.1)
        p = h2mixer_params(cfg, jax.random.PRNGKey(0), jnp.float32)
        p["gate"] = jnp.full_like(p["gate"], 10.0)      # tanh -> ~1
        x = jnp.asarray(np.random.default_rng(0).standard_normal(
            (2, s, cfg.d_model)), jnp.float32)
        y = h2mixer_apply(cfg, p, x, shape, data)
        # dense reference
        pos = np.arange(s)[:, None] / s
        a = np.exp(-np.abs(pos - pos.T) / 0.1)
        from repro.models.layers import rms_norm
        h = np.asarray(rms_norm(x, p["norm"], cfg.norm_eps) @ p["w_in"])
        mixed = np.einsum("st,btd->bsd", a, h)
        ref = np.asarray(x) + (mixed @ np.asarray(p["w_out"])) * \
            np.tanh(np.asarray(p["gate"]))
        np.testing.assert_allclose(np.asarray(y), ref, rtol=2e-2, atol=2e-2)

    def test_compressed_mixer_close(self):
        from repro.models.h2mixer import h2mixer_structure
        from repro.core.matvec import h2_matvec
        s = 256
        sh0, d0 = h2mixer_structure(s, tol=None)
        sh1, d1 = h2mixer_structure(s, tol=1e-4)
        x = jnp.asarray(np.random.default_rng(1).standard_normal((s, 4)),
                        jnp.float32)
        y0 = np.asarray(h2_matvec(sh0, d0, x))
        y1 = np.asarray(h2_matvec(sh1, d1, x))
        rel = np.linalg.norm(y1 - y0) / np.linalg.norm(y0)
        assert rel < 1e-2, rel
        assert sh1.memory_lowrank() < sh0.memory_lowrank()

    def test_o_n_memory(self):
        from repro.models.h2mixer import h2mixer_structure
        m1 = h2mixer_structure(256, tol=None)[0]
        m2 = h2mixer_structure(1024, tol=None)[0]
        total1 = m1.memory_lowrank() + m1.memory_dense()
        total2 = m2.memory_lowrank() + m2.memory_dense()
        assert total2 < 8 * total1     # ~linear, far below the 16x of dense


class TestKVQuant:
    def test_roundtrip_error(self):
        from repro.serving.kv_quant import quantize, dequantize
        x = jnp.asarray(np.random.default_rng(0).standard_normal(
            (2, 16, 4, 32)), jnp.float32)
        xq = dequantize(quantize(x))
        rel = float(jnp.linalg.norm(xq - x) / jnp.linalg.norm(x))
        assert rel < 1e-2, rel

    def test_quantized_decode_attention(self):
        from repro.serving.kv_quant import (quantize, decode_attention_q,
                                            update)
        from repro.models.layers import decode_attention
        rng = np.random.default_rng(1)
        b, s, h, hkv, hd = 2, 32, 4, 2, 16
        q = jnp.asarray(rng.standard_normal((b, 1, h, hd)), jnp.float32)
        k = jnp.asarray(rng.standard_normal((b, s, hkv, hd)), jnp.float32)
        v = jnp.asarray(rng.standard_normal((b, s, hkv, hd)), jnp.float32)
        mask = jnp.ones((b, s), bool)
        ref = decode_attention(q, k, v, mask)
        out = decode_attention_q(q, quantize(k), quantize(v), mask)
        rel = float(jnp.linalg.norm(out - ref) / jnp.linalg.norm(ref))
        assert rel < 3e-2, rel

    def test_update_appends(self):
        from repro.serving.kv_quant import quantize, dequantize, update
        base = jnp.zeros((1, 8, 2, 4), jnp.float32)
        c = quantize(base)
        step = jnp.ones((1, 1, 2, 4), jnp.float32) * 3.0
        c = update(c, step, 5)
        deq = dequantize(c)
        np.testing.assert_allclose(np.asarray(deq[0, 5]), 3.0, rtol=2e-2)
        np.testing.assert_allclose(np.asarray(deq[0, 4]), 0.0, atol=1e-6)

    def test_memory_halved(self):
        from repro.serving.kv_quant import cache_bytes
        full, quant = cache_bytes((128, 32768, 8, 128))
        assert quant < 0.6 * full


class TestPerfAnalyzers:
    def test_hlo_collective_parser_loop_exact(self):
        """The controlled validation from EXPERIMENTS.md §Roofline, kept as
        a regression test (needs >1 device: runs the parser on saved text
        semantics instead)."""
        from repro.perf import hlo_cost
        hlo = """
HloModule test

%cond (arg: (s32[], f32[4])) -> pred[] {
  %arg = (s32[], f32[4]) parameter(0)
  %i = s32[] get-tuple-element(%arg), index=0
  %c = s32[] constant(7)
  ROOT %cmp = pred[] compare(%i, %c), direction=LT
}

%body (arg: (s32[], f32[4])) -> (s32[], f32[4]) {
  %arg = (s32[], f32[4]) parameter(0)
  %x = f32[4] get-tuple-element(%arg), index=1
  %ag = f32[4]{0} all-gather(f32[4]{0} %x), dimensions={0}
  %a2a = (f32[4]{0}, f32[4]{0}, /*index=2*/f32[4]{0}) all-to-all(f32[4]{0} %x, f32[4]{0} %x, f32[4]{0} %x), dimensions={0}
  %i2 = s32[] get-tuple-element(%arg), index=0
  ROOT %t = (s32[], f32[4]) tuple(%i2, %ag)
}

ENTRY %main (p: f32[4]) -> f32[4] {
  %p = f32[4] parameter(0)
  %zero = s32[] constant(0)
  %init = (s32[], f32[4]) tuple(%zero, %p)
  %w = (s32[], f32[4]) while(%init), condition=%cond, body=%body
  ROOT %out = f32[4] get-tuple-element(%w), index=1
}
"""
        flat = hlo_cost.collective_bytes_flat(hlo)
        corr = hlo_cost.collective_bytes(hlo)
        assert flat["all-gather"] == 16
        assert corr["all-gather"] == 7 * 16, corr
        # a tuple-result collective (the merged all_to_all lowering) sums
        # EVERY result chunk — operand shapes and /*index=N*/ comments in
        # the printed tuple type must not confuse the parser
        assert flat["all-to-all"] == 3 * 16, flat
        assert corr["all-to-all"] == 7 * 3 * 16, corr

    def test_jaxpr_cost_shard_map_scaled(self):
        from repro.perf.jaxpr_cost import analyze
        import os
        mesh = jax.make_mesh((1,), ("d",))

        def f(x):
            def inner(xx):
                return xx @ xx
            return jax.shard_map(inner, mesh=mesh,
                                 in_specs=jax.sharding.PartitionSpec(),
                                 out_specs=jax.sharding.PartitionSpec(),
                                 check_vma=False)(x)

        x = jax.ShapeDtypeStruct((32, 32), jnp.float32)
        cost = analyze(f, x)
        assert cost["flops"] >= 2 * 32 ** 3
