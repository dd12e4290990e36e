"""Seeds of a run: ``--seed`` may exceed 32 bits, JAX keys take 32."""
from __future__ import annotations


def jax_key(jax, seed: int):
    """A JAX PRNG key from both 32-bit halves of ``seed``."""
    return jax.random.fold_in(jax.random.key(seed & 0xFFFFFFFF), seed >> 32)
