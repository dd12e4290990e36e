"""Host-resident distributed build and the distributed compression to a
tolerance, on four fake CPU devices (``tests/dist4_worker.py``, in a
subprocess: JAX fixes the device count when it starts)."""
import os
import subprocess
import sys

import pytest

WORKER = os.path.join(os.path.dirname(__file__), "dist4_worker.py")


def run_worker(check: str, timeout: float) -> str:
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env["PYTHONPATH"] = os.path.abspath(
        os.path.join(os.path.dirname(__file__), "..", "src"))
    proc = subprocess.run([sys.executable, WORKER, check],
                          capture_output=True, text=True, timeout=timeout,
                          env=env)
    assert proc.returncode == 0, proc.stdout + proc.stderr
    assert "ALL_OK" in proc.stdout, proc.stdout
    return proc.stdout


def test_host_construction_and_partition_place_the_same_shards():
    out = run_worker("partition", timeout=60)
    assert "OK host_construction" in out and \
        "OK host_partition_placed" in out


@pytest.fixture(scope="module")
def compress_out():
    return run_worker("compress", timeout=90)


def test_dist_compress_tol_picks_compress_tol_ranks(compress_out):
    assert "OK dist_tol_ranks" in compress_out


def test_dist_matvec_of_tol_compression_matches_one_device(compress_out):
    assert "OK dist_tol_matvec" in compress_out


def test_dist_compress_static_sweep_matches_tol_sweep(compress_out):
    assert "OK dist_static_sweep" in compress_out
