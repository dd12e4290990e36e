"""The comparison that decides ``correct`` fails its control and its
faults (CPU, tiny sizes).

The control is the plain reference in the program's place with its
products at three bf16 passes (``bf16x3``, what ``precision="high"`` runs
on the TPU), one step below the configuration's ``highest``.  The faults
are each driver's ``FAULTS``, planted under the timed path of a whole run.
"""
import sys
from pathlib import Path

import pytest

BENCH = Path(__file__).resolve().parents[1]
ROOT = BENCH.parent
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

from bench import controls  # noqa: E402
from bench import run as harness  # noqa: E402
from test_rehearsal import SPEC, TINY  # noqa: E402

CELLS = {"solve": "frac2d-n16.solve", "apply": "cov2d-n32.apply8",
         "compress": "cov2d-n32.compress"}
SEED = 2**31 + 101


@pytest.fixture(scope="module")
def cells():
    import jax
    out = {}
    for op, name in CELLS.items():
        cell = harness.Cell(name, SPEC, data=TINY)
        out[op] = (cell, cell.system.build(cell.config))
    jax.clear_caches()
    return out


def over(readings, limits):
    return [k for k, v in readings.items() if not v["value"] <= limits[k]]


@pytest.mark.parametrize("op", sorted(CELLS))
def test_program_is_within_its_limits(cells, op):
    cell, system = cells[op]
    _, _, r = controls.readings(cell.op, system, cell.config, cell.traffic,
                                SEED, 4)
    r = {k: dict(v, value=v["value"]) for k, v in r.items()}
    assert over(r, cell.limits) == [], r


@pytest.mark.parametrize("op", sorted(CELLS))
def test_control_fails_a_limit(cells, op):
    cell, system = cells[op]
    state, outputs, _ = controls.readings(cell.op, system, cell.config,
                                          cell.traffic, SEED, 4)
    r = cell.op.control(state, len(outputs), cell.config, cell.traffic,
                        SEED, "bf16x3", controls.no_limits())
    assert over(r, cell.limits), r


FAULTS = {op: harness.load_module(BENCH / "ops" / f"{op}.py").FAULTS
          for op in CELLS}


@pytest.mark.parametrize("op, fault", [(op, f) for op in sorted(CELLS)
                                       for f in FAULTS[op]])
def test_fault_under_the_timed_path_makes_the_run_incorrect(op, fault):
    import jax
    cell = harness.Cell(CELLS[op], SPEC, data=TINY)
    cell.op = controls.planted(cell.op, fault)
    res = harness.run(cell, SEED, 0.5, False, jax, jax.devices()[:1], {})
    assert res["correct"] is False
    assert [k for k, c in res["compared"].items()
            if not c["value"] <= c["limit"]], res["compared"]
