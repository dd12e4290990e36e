"""Matrix products at a stated precision, for the references and controls.

``einsum(precision)`` returns an einsum whose products run at
``precision``: one of JAX's (``"highest"``, ``"high"``, ``"default"``),
which the TPU honours, or ``"bf16x3"``, the same three-pass bf16 product
as ``"high"`` written out (hi*hi + hi*lo + lo*hi of the bf16 halves, float32
sums), which gives the control's lower precision on any platform.
"""
from __future__ import annotations

import functools


def _split(a):
    import jax.numpy as jnp
    hi = a.astype(jnp.bfloat16).astype(jnp.float32)
    return hi, (a - hi).astype(jnp.bfloat16).astype(jnp.float32)


def _bf16x3(spec, a, b):
    import jax.numpy as jnp
    (ah, al), (bh, bl) = _split(a), _split(b)
    ein = functools.partial(jnp.einsum, precision="highest")
    return ein(spec, ah, bh) + (ein(spec, ah, bl) + ein(spec, al, bh))


def einsum(precision: str):
    import jax.numpy as jnp
    if precision == "bf16x3":
        return _bf16x3
    return functools.partial(jnp.einsum, precision=precision)
