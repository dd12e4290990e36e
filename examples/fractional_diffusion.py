"""End-to-end fractional diffusion solve (paper §6.4, Fig. 13).

    PYTHONPATH=src python examples/fractional_diffusion.py [--n 32]

Builds the H^2-compressed dense operator K, the diagonal D via an H^2 matvec
with the all-ones vector on the extended grid, the sparse regularization C,
and solves h^2(D+K+C)u = b with multigrid-preconditioned CG.  Reports the
iteration counts whose flatness across N demonstrates the paper's
dimension-independent convergence.
"""
import argparse
import time
from pathlib import Path

import numpy as np

from repro.launch.compile_cache import enable_compile_cache
from repro.apps.fractional import solve, dense_reference_solution


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--n", type=int, default=32, help="grid side")
    ap.add_argument("--validate", action="store_true",
                    help="compare against O(N^2) dense direct solve")
    args = ap.parse_args()

    sizes = [16, args.n] if args.n != 16 else [16]
    for n in sizes:
        t0 = time.perf_counter()
        res = solve(n)
        dt = time.perf_counter() - t0
        print(f"n={n:4d}  N={n*n:6d}  iters={res['iters']:3d}  "
              f"relres={res['relres']:.2e}  wall={dt:.1f}s")
        if args.validate and n <= 16:
            u_ref = dense_reference_solution(n)
            err = (np.linalg.norm(res["u"] - u_ref)
                   / np.linalg.norm(u_ref))
            print(f"          validation vs dense direct solve: "
                  f"rel err {err:.2e}")
    print("(paper Fig. 13: iterations 24->32 from 512^2 to 4096^2 — "
          "the same dimension-independent behaviour)")


if __name__ == "__main__":
    enable_compile_cache(Path(__file__).resolve().parents[1])
    main()
