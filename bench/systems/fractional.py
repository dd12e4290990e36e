"""Builder of the §6.4 fractional-diffusion deployment.

Drives the program's own set-up, ``FractionalProblem(n).build()``: the
Chebyshev H^2 construction of K, its compression to ``h2_tol``, and the
diagonal D from the extended-grid operator.  Returns the program's problem
dict (operator, D, diffusivity, grid maps).
"""
from __future__ import annotations


def build(cfg: dict) -> dict:
    from repro.apps.fractional import FractionalProblem

    fp = FractionalProblem(cfg["n"], beta=cfg["kernel"]["beta"],
                           h2_tol=cfg["h2_tol"], cheb_p=cfg["cheb_p"],
                           eta=cfg["eta"])
    prob = fp.build()
    if prob["shape"].leaf_size != cfg["leaf"]:
        raise ValueError(f"program chose leaf {prob['shape'].leaf_size}, "
                         f"the configuration states {cfg['leaf']}")
    return prob
