"""CPU rehearsal of the four-chip cell on four fake devices
(``bench/tests/rehearse_dist.py``, in a subprocess: JAX fixes the device
count when it starts)."""
import os
import subprocess
import sys
from pathlib import Path

WORKER = Path(__file__).with_name("rehearse_dist.py")


def test_tiny_four_chip_cell_is_correct_and_reports_its_layers():
    proc = subprocess.run([sys.executable, str(WORKER)], capture_output=True,
                          text=True, timeout=300,
                          env=dict(os.environ, JAX_PLATFORMS="cpu"))
    out = proc.stdout
    assert proc.returncode == 0, out + proc.stderr
    for marker in ("OK readback", "OK block_gap", "OK run", "OK traced", "OK readers",
                   "OK control", "OK fault unchanged", "OK fault half",
                   "OK fault altered", "OK fault no-exchange", "ALL_OK"):
        assert marker in out, (marker, out, proc.stderr[-4000:])
