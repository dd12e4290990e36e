"""Example entry points as CI smoke tests (small-N parametrization) so
the documented quickstart / serving paths cannot silently rot.

The example modules live outside the installed package; they are loaded
by file path and their ``main()`` is run at a reduced problem size.
"""
import importlib.util
import os
import sys

import numpy as np
import pytest

EXAMPLES_DIR = os.path.abspath(
    os.path.join(os.path.dirname(__file__), "..", "examples"))


def load_example(name):
    spec = importlib.util.spec_from_file_location(
        f"examples_{name}", os.path.join(EXAMPLES_DIR, f"{name}.py"))
    mod = importlib.util.module_from_spec(spec)
    sys.modules[spec.name] = mod
    spec.loader.exec_module(mod)
    return mod


@pytest.mark.slow
class TestQuickstart:
    @pytest.mark.parametrize("side,leaf", [(16, 16), (32, 16)])
    def test_runs_and_stays_accurate(self, side, leaf):
        mod = load_example("quickstart")
        err, err2, ratio = mod.main(side=side, leaf_size=leaf)
        # small-N parametrization has relatively coarser admissible blocks
        # than the documented side=64 run, so the bounds are looser
        assert err < 5e-3, err           # Chebyshev construction accuracy
        assert err2 < 2e-2, err2         # tau=1e-3 recompression accuracy
        assert ratio > 1.0, ratio        # recompression actually shrinks


@pytest.mark.slow
class TestServeSolver:
    def test_serving_loop_converges(self):
        mod = load_example("serve_h2_solver")
        r1, r2, rb = mod.main(side=16, leaf_size=16, tol=1e-5)
        # single-RHS requests served to tolerance on both operators
        assert r1.status == "ok" and r1.relres <= 1e-5
        assert r2.status == "ok" and r2.relres <= 1e-5
        # recompression must not change the served solution materially
        drift = float(np.linalg.norm(np.asarray(r1.x) - np.asarray(r2.x))
                      / np.linalg.norm(np.asarray(r1.x)))
        assert drift < 1e-2, drift
        # the continuous-batching panel served every Poisson request
        assert rb.metrics["completed"] == 8
        assert all(rb.completions[i].status == "ok" for i in range(8))
        assert max(rb.completions[i].relres for i in range(8)) <= 1e-5
        # the stream hit the operator (and compiled solver) in the cache
        assert rb.metrics["cache"]["hits"] >= 1
        assert rb.metrics["cache"]["misses"] == 2


class TestCompileCache:
    """Where the entry points keep JAX's persistent compilation cache."""

    @pytest.fixture(autouse=True)
    def _restore_cache_dir(self):
        import jax
        was = jax.config.jax_compilation_cache_dir
        yield
        jax.config.update("jax_compilation_cache_dir", was)

    def test_environment_variable_stands(self, tmp_path, monkeypatch):
        import jax
        from repro.launch.compile_cache import enable_compile_cache
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path / "c"))
        before = jax.config.jax_compilation_cache_dir
        assert enable_compile_cache(tmp_path) == str(tmp_path / "c")
        assert jax.config.jax_compilation_cache_dir == before

    def test_fixed_path_in_the_checkout(self, tmp_path, monkeypatch):
        import jax
        from repro.launch.compile_cache import enable_compile_cache
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
        want = str(tmp_path.resolve() / ".jax_cache")
        assert enable_compile_cache(tmp_path) == want
        assert enable_compile_cache(tmp_path) == want     # same every run
        assert jax.config.jax_compilation_cache_dir == want
