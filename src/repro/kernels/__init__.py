"""Pallas TPU kernels for the paper's batched hot spots (see ops.py)."""
from __future__ import annotations

from typing import Callable

import jax
from jax.experimental import pallas as pl


def pallas_call(kernel: Callable, **kw) -> Callable:
    """``pl.pallas_call`` that runs where its arrays live.

    On the CPU the kernel body is interpreted; on every other platform it
    compiles to Mosaic.  The choice is made when the program is lowered
    for its platform (``lax.platform_dependent``), so a program compiled
    for a TPU never carries the interpreter.
    """
    interpreted = pl.pallas_call(kernel, interpret=True, **kw)
    compiled = pl.pallas_call(kernel, **kw)

    def call(*args):
        return jax.lax.platform_dependent(*args, cpu=interpreted,
                                          default=compiled)

    return call
