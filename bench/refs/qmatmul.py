"""The paper's quantized matmul: int8 operands, int32 accumulation and
bias, requantized to int8 by ``round(acc * scale)`` clipped to [-128, 127]
(round half to even)."""

from __future__ import annotations

from bench.refs import precision


def qmatmul(x, w, bias, scale: float, cast=precision.exact):
    import jax
    import jax.numpy as jnp

    if cast is precision.exact:
        acc = jnp.dot(x.astype(jnp.int32), w.astype(jnp.int32))
        acc = acc + bias.astype(jnp.int32)[None, :]
        scaled = acc.astype(jnp.float32) * jnp.float32(scale)
    else:  # a lower precision: rounded operands, float accumulation
        acc = jnp.dot(cast(x), cast(w),
                      precision=jax.lax.Precision.HIGHEST)
        scaled = (acc + bias.astype(jnp.float32)[None, :]) * jnp.float32(
            scale)
    return jnp.clip(jnp.round(scaled), -128, 127).astype(jnp.int8)
