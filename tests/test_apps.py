"""Application-level tests: fractional diffusion solver, end-to-end training
with failure injection, serving loop."""
import tempfile

import numpy as np
import pytest
import jax
import jax.numpy as jnp


class TestFractional:
    def test_matches_dense_direct_solve(self):
        from repro.apps.fractional import solve, dense_reference_solution
        res = solve(16, h2_tol=1e-7, tol=1e-10)
        u_ref = dense_reference_solution(16)
        err = np.linalg.norm(res["u"] - u_ref) / np.linalg.norm(u_ref)
        assert err < 2e-2, err

    def test_iterations_stay_flat(self):
        """Paper Fig 13: dimension-independent-ish Krylov iterations."""
        from repro.apps.fractional import solve
        i16 = solve(16)["iters"]
        i32 = solve(32)["iters"]
        assert i32 < 2.5 * i16, (i16, i32)
        assert i32 < 60

    def test_preconditioner_helps(self):
        from repro.apps.fractional import solve
        with_pre = solve(16, use_precond=True)
        without = solve(16, use_precond=False)
        assert with_pre["iters"] < without["iters"], \
            (with_pre["iters"], without["iters"])

    @staticmethod
    def _grid_perm(n):
        from repro.apps.fractional import interior_grid
        from repro.core.clustering import build_cluster_tree
        leaf = 16 if n <= 32 else 64                 # as the program picks
        perm = build_cluster_tree(interior_grid(n), leaf).perm
        unperm = np.empty_like(perm)
        unperm[perm] = np.arange(perm.size)
        return perm, unperm

    @pytest.mark.parametrize("n", [32, 64, 256])
    def test_grid_tree_maps_are_transposes_equal_to_gathers(self, n):
        from repro.apps.fractional import grid_tree_maps
        from repro.core.clustering import bit_transpose
        from repro.obs.trace import counter
        perm, unperm = self._grid_perm(n)
        assert bit_transpose(perm) is not None
        before = counter("solve/perm-transpose")
        to_tree, to_grid = grid_tree_maps(perm, unperm)
        assert counter("solve/perm-transpose") - before == 1
        rng = np.random.default_rng(n)
        u, v = (rng.standard_normal(n * n).astype(np.float32)
                for _ in range(2))
        np.testing.assert_array_equal(
            np.asarray(jax.jit(to_tree)(jnp.asarray(u))), u[perm])
        np.testing.assert_array_equal(
            np.asarray(jax.jit(to_grid)(jnp.asarray(v))), v[unperm])
        text = jax.jit(to_tree).lower(jnp.asarray(u)).as_text()
        assert "gather" not in text

    @pytest.mark.parametrize("n", [8, 64])
    def test_grid_tree_maps_gather_where_no_bit_permutation(self, n):
        from repro.apps.fractional import grid_tree_maps
        from repro.core.clustering import bit_transpose
        from repro.obs.trace import counter
        rng = np.random.default_rng(n)
        perm = np.concatenate([[0], 1 + rng.permutation(n * n - 1)])
        unperm = np.empty_like(perm)
        unperm[perm] = np.arange(perm.size)
        assert bit_transpose(perm) is None
        assert bit_transpose(perm[:-1]) is None      # N not a power of two
        seen = []                                    # a tracer: not concrete
        jax.make_jaxpr(lambda p: seen.append(bit_transpose(p)) or p)(
            jnp.asarray(self._grid_perm(32)[0]))
        assert seen == [None]
        counts = [counter(k) for k in ("solve/perm-gather",
                                       "solve/perm-transpose")]
        to_tree, to_grid = grid_tree_maps(perm, unperm)
        assert [counter(k) for k in ("solve/perm-gather",
                                     "solve/perm-transpose")] == \
            [counts[0] + 1, counts[1]]
        u = rng.standard_normal(n * n).astype(np.float32)
        np.testing.assert_array_equal(
            np.asarray(jax.jit(to_tree)(jnp.asarray(u))), u[perm])
        np.testing.assert_array_equal(
            np.asarray(jax.jit(to_grid)(jnp.asarray(u))), u[unperm])

    def test_operator_equals_its_gather_form_bitwise(self, monkeypatch):
        import repro.apps.fractional as frac
        from repro.obs.trace import counter
        prob = frac.FractionalProblem(32).build()
        u = jnp.asarray(np.random.default_rng(3).standard_normal(32 * 32),
                        jnp.float32)
        before = counter("solve/perm-transpose")
        got = jax.jit(frac.make_operator(prob))(u)
        assert counter("solve/perm-transpose") - before == 1
        monkeypatch.setattr(frac, "bit_transpose", lambda perm: None)
        want = jax.jit(frac.make_operator(prob))(u)
        np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


class TestTrainEndToEnd:
    def test_loss_drops_and_restart_works(self):
        from repro.configs.base import get_config
        from repro.launch.train import train
        from repro.runtime.fault import FailureInjector
        cfg = get_config("qwen3-0.6b").reduced(
            param_dtype="float32", act_dtype="float32", vocab=256)
        with tempfile.TemporaryDirectory() as ckpt:
            inj = FailureInjector(fail_at={12: "injected"})
            hist = train(cfg, steps=25, global_batch=4, seq_len=32,
                         ckpt_dir=ckpt, ckpt_every=5, injector=inj,
                         log_every=100)
        assert hist["restarts"] == 1
        assert np.mean(hist["loss"][-5:]) < np.mean(hist["loss"][:5])

    def test_resume_from_checkpoint(self):
        from repro.configs.base import get_config
        from repro.launch.train import train
        cfg = get_config("qwen3-0.6b").reduced(
            param_dtype="float32", act_dtype="float32", vocab=256)
        with tempfile.TemporaryDirectory() as ckpt:
            h1 = train(cfg, steps=10, global_batch=4, seq_len=32,
                       ckpt_dir=ckpt, ckpt_every=5, log_every=100)
            h2 = train(cfg, steps=15, global_batch=4, seq_len=32,
                       ckpt_dir=ckpt, ckpt_every=5, log_every=100)
            # second run resumed at step 10 -> only 5 new steps
            assert len(h2["loss"]) == 5

    def test_psgd_training_converges(self):
        from repro.configs.base import get_config
        from repro.launch.train import train
        cfg = get_config("qwen3-0.6b").reduced(
            param_dtype="float32", act_dtype="float32", vocab=128,
            n_layers=2)
        hist = train(cfg, steps=30, global_batch=4, seq_len=32,
                     use_psgd=True, log_every=100)
        assert np.mean(hist["loss"][-5:]) < np.mean(hist["loss"][:5])


class TestServe:
    def test_batched_server(self):
        from repro.configs.base import get_config
        from repro.launch.serve import BatchedServer, Request
        from repro.models import api
        cfg = get_config("qwen3-0.6b").reduced(
            param_dtype="float32", act_dtype="float32", vocab=128)
        params = api.init_params(cfg, jax.random.PRNGKey(0))
        server = BatchedServer(cfg, params, batch_size=2, max_len=32)
        rng = np.random.default_rng(0)
        reqs = [Request(rid=i, prompt=rng.integers(0, 128, 5).astype("i4"),
                        max_new=4) for i in range(2)]
        out = server.serve(reqs)
        assert set(out) == {0, 1}
        assert all(len(v) == 4 for v in out.values())

    def test_server_matches_prefill_greedy(self):
        """Server greedy decode == argmax chain from repeated prefill."""
        from repro.configs.base import get_config
        from repro.launch.serve import BatchedServer, Request
        from repro.models import api
        cfg = get_config("qwen3-0.6b").reduced(
            param_dtype="float32", act_dtype="float32", vocab=64,
            n_layers=2)
        params = api.init_params(cfg, jax.random.PRNGKey(1))
        server = BatchedServer(cfg, params, batch_size=1, max_len=32)
        rng = np.random.default_rng(3)
        prompt = rng.integers(0, 64, 6).astype("i4")
        out = server.serve([Request(rid=0, prompt=prompt, max_new=3)])[0]
        # reference: full re-prefill each step
        toks = list(prompt)
        ref = []
        for _ in range(3):
            batch = {"tokens": jnp.asarray(np.array(toks)[None, :])}
            logits, _ = api.prefill(cfg, params, batch)
            nxt = int(jnp.argmax(logits[0]))
            ref.append(nxt)
            toks.append(nxt)
        assert out == ref, (out, ref)
