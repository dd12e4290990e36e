"""End-to-end LM training driver (deliverable b): trains a ~100M-param
qwen3-family model for a few hundred steps on synthetic structured data,
with checkpointing, an injected mid-run failure + automatic restart, and
PowerSGD gradient compression enabled.

    PYTHONPATH=src python examples/train_lm.py [--steps 300]
"""
import argparse
import tempfile
from pathlib import Path

import numpy as np

from repro.launch.compile_cache import enable_compile_cache
from repro.configs.base import get_config
from repro.launch.train import train
from repro.runtime.fault import FailureInjector


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--steps", type=int, default=300)
    ap.add_argument("--d-model", type=int, default=256)
    ap.add_argument("--layers", type=int, default=4)
    args = ap.parse_args()

    # a ~real (tens of millions of params) qwen3-family config that trains
    # at CPU speed; the full assigned configs are exercised by the dry-run
    cfg = get_config("qwen3-0.6b").reduced(
        d_model=args.d_model, n_layers=args.layers, n_heads=8, n_kv_heads=4,
        head_dim=32, d_ff=args.d_model * 4, vocab=8192,
        param_dtype="float32", act_dtype="float32")

    with tempfile.TemporaryDirectory() as ckpt:
        injector = FailureInjector(fail_at={args.steps // 2:
                                            "injected mid-run failure"})
        hist = train(cfg, steps=args.steps, global_batch=8, seq_len=128,
                     ckpt_dir=ckpt, ckpt_every=25, use_psgd=True,
                     injector=injector, log_every=25)
    first = np.mean(hist["loss"][:10])
    last = np.mean(hist["loss"][-10:])
    print(f"\nloss {first:.3f} -> {last:.3f}  "
          f"restarts={hist['restarts']} (1 injected)  "
          f"stragglers flagged={hist['stragglers']}")
    assert last < first, "training did not reduce the loss"
    assert hist["restarts"] == 1
    print("end-to-end training with failure/restart + PowerSGD: OK")


if __name__ == "__main__":
    enable_compile_cache(Path(__file__).resolve().parents[1])
    main()
