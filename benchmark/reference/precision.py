"""Matrix products at a stated precision, for the references.

``f32``  — true float32 (``precision=HIGHEST``): the reference proper.
``bf16`` — operands rounded to bfloat16, f32 accumulation: what the
           configurations state for the programs (information only).
``fp8``  — operands rounded to 4 significant bits (e4m3's mantissa),
           f32 accumulation: the nearest precision *below* bfloat16, the
           control that `correct` has to refuse.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

PRECISIONS = ("f32", "bf16", "fp8")


def _round_fp8(x):
    """Round to e4m3's 1+3 significant bits (range is not clipped: the
    values here are far inside it).  The derivative is the identity, as a
    hardware convert's is: rounding has none of its own."""
    x = x.astype(jnp.float32)
    m, e = jnp.frexp(jax.lax.stop_gradient(x))
    return x + jax.lax.stop_gradient(jnp.ldexp(jnp.round(m * 16.0) / 16.0, e) - x)


def rounded(x, prec: str):
    if prec == "f32":
        return x.astype(jnp.float32)
    if prec == "bf16":
        return x.astype(jnp.bfloat16).astype(jnp.float32)
    if prec == "fp8":
        return _round_fp8(x)
    raise ValueError(f"unknown precision {prec!r}")


def einsum(spec: str, a, b, prec: str):
    return jnp.einsum(
        spec, rounded(a, prec), rounded(b, prec),
        precision=jax.lax.Precision.HIGHEST,
        preferred_element_type=jnp.float32,
    )
