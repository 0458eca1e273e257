"""Causal grouped-query attention in float32: q (B, Hq, Lq, D), k and v
(B, Hkv, Lk, D); query head h reads key/value head h // (Hq / Hkv); the
queries sit at the last Lq positions of the keys."""

from __future__ import annotations

import math

from bench.refs import precision


def attention(q, k, v, cast=precision.exact):
    import jax
    import jax.numpy as jnp

    hi = jax.lax.Precision.HIGHEST
    b, hq, lq, d = q.shape
    hkv, lk = k.shape[1], k.shape[2]
    g = hq // hkv
    q = cast(q.astype(jnp.float32))
    k = cast(jnp.repeat(k.astype(jnp.float32), g, axis=1))
    v = cast(jnp.repeat(v.astype(jnp.float32), g, axis=1))
    s = jnp.einsum("bhqd,bhkd->bhqk", q, k, precision=hi) / math.sqrt(d)
    rows = jnp.arange(lq)[:, None] + (lk - lq)
    cols = jnp.arange(lk)[None, :]
    s = jnp.where(cols <= rows, s, -jnp.inf)
    p = jax.nn.softmax(s, axis=-1)
    return jnp.einsum("bhqk,bhkd->bhqd", cast(p), v, precision=hi)
