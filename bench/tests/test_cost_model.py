"""The benchmark's work counts against the program's own flop model and
the operator's array sizes (small CPU-built operators)."""
import json
import sys
from pathlib import Path

import pytest

BENCH = Path(__file__).resolve().parents[1]
ROOT = BENCH.parent
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

from bench import cost_model  # noqa: E402


@pytest.fixture(scope="module")
def operators():
    from repro.core.clustering import regular_grid_points
    from repro.core.compression import compress
    from repro.core.construction import construct_h2
    from repro.core.kernels_fn import exponential_kernel
    shape, data, _, _ = construct_h2(regular_grid_points(32, 2),
                                     exponential_kernel(0.1), 16, 6, 0.9)
    cshape, cdata = compress(shape, data, tol=1e-3)
    return [(shape, data), (cshape, cdata)]


@pytest.mark.parametrize("which", [0, 1])
@pytest.mark.parametrize("nv", [1, 64])
def test_flops_match_the_programs_model(operators, which, nv):
    from repro.core.matvec import h2_matvec_flops
    shape, _ = operators[which]
    assert cost_model.h2_matvec_flops(shape, nv) == \
        h2_matvec_flops(shape, nv)


@pytest.mark.parametrize("which", [0, 1])
def test_bytes_are_the_operators_arrays_and_the_blocks(operators, which):
    shape, data = operators[which]
    nv = 8
    words = data.u_leaf.size + sum(e.size for e in data.e) + \
        sum(s.size for s in data.s) + data.dense.size + 2 * shape.n * nv
    assert shape.symmetric
    assert cost_model.h2_matvec_bytes(shape, nv) == 4 * words


def test_least_time_names_its_bound():
    peaks = json.loads((BENCH / "peaks.json").read_text())["TPU v5 lite"]
    t, bound = cost_model.least_time(1e9, 1e3, peaks)
    assert bound == "compute" and t == pytest.approx(1e9 / 32.833e12)
    t, bound = cost_model.least_time(1e3, 819e6, peaks)
    assert bound == "memory" and t == pytest.approx(1e-3)
