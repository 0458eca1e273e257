"""Operand roundings: the reference (none) and the controls below it."""

from __future__ import annotations


def exact(x):
    return x


def bfloat16(x):
    """Round to bfloat16 and back: the step below float32."""
    import jax.numpy as jnp

    return x.astype(jnp.bfloat16).astype(x.dtype)


def float8(x):
    """Round to float8 (e4m3) with one scale per tensor, and back: the step
    below bfloat16."""
    import jax.numpy as jnp

    x32 = x.astype(jnp.float32)
    scale = jnp.maximum(jnp.max(jnp.abs(x32)), 1e-30) / 448.0
    q = (x32 / scale).astype(jnp.float8_e4m3fn).astype(jnp.float32)
    return (q * scale).astype(x.dtype)


def int4(x):
    """Round integers to 4 bits with one scale per tensor, and back (as
    float32): the step below int8."""
    import jax.numpy as jnp

    x32 = x.astype(jnp.float32)
    scale = jnp.maximum(jnp.max(jnp.abs(x32)), 1.0) / 7.0
    return jnp.clip(jnp.round(x32 / scale), -8, 7) * scale


CASTS = {"exact": exact, "bfloat16": bfloat16, "float8": float8,
         "int4": int4}
