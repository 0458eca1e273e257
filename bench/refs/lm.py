"""A dense grouped-query-attention language model, layer by layer, in
float32 at "highest" matmul precision: RMS norm with a scale, rotary
position embedding on the two halves of each head, causal softmax
attention, a SwiGLU feed-forward block (silu(x Wg) * (x Wu)) Wd, residual
adds, a final norm and the output head (or the transposed embedding when
tied). It is run in blocks: one jitted layer, called once per layer with
that layer's weights, so it holds one layer at a time."""

from __future__ import annotations

import functools
import math

from bench.refs import precision


def _mm(a, b, cast):
    import jax
    import jax.numpy as jnp

    return jnp.matmul(cast(a), cast(b), precision=jax.lax.Precision.HIGHEST)


def rms_norm(x, scale, eps):
    import jax.numpy as jnp

    return x * (1.0 / jnp.sqrt(jnp.mean(x * x, axis=-1, keepdims=True)
                               + eps)) * scale


def rope(x, theta):
    """x (B, S, H, D) at positions 0..S-1: rotate (first half, second half)
    pairs by position * theta ** (-2i / D)."""
    import jax.numpy as jnp

    s, d = x.shape[1], x.shape[-1]
    freqs = 1.0 / (theta ** (jnp.arange(0, d, 2, dtype=jnp.float32) / d))
    ang = jnp.arange(s, dtype=jnp.float32)[:, None] * freqs[None, :]
    cos, sin = jnp.cos(ang)[None, :, None, :], jnp.sin(ang)[None, :, None, :]
    x1, x2 = x[..., : d // 2], x[..., d // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def layer(x, w, cfg: dict, cast=precision.exact):
    """One decoder layer over x (B, S, D)."""
    import jax
    import jax.numpy as jnp

    b, s, d = x.shape
    hq, hkv, hd = cfg["n_heads"], cfg["n_kv_heads"], cfg["head_dim"]
    eps, a = cfg["norm_eps"], w["attn"]
    h = rms_norm(x, w["ln1"], eps)
    q = rope(_mm(h, a["wq"], cast).reshape(b, s, hq, hd), cfg["rope_theta"])
    k = rope(_mm(h, a["wk"], cast).reshape(b, s, hkv, hd),
             cfg["rope_theta"])
    v = _mm(h, a["wv"], cast).reshape(b, s, hkv, hd)
    g = hq // hkv
    k, v = jnp.repeat(k, g, axis=2), jnp.repeat(v, g, axis=2)
    hi = jax.lax.Precision.HIGHEST
    sc = jnp.einsum("bqhd,bkhd->bhqk", cast(q), cast(k),
                    precision=hi) / math.sqrt(hd)
    causal = jnp.arange(s)[None, :] <= jnp.arange(s)[:, None]
    p = jax.nn.softmax(jnp.where(causal, sc, -jnp.inf), axis=-1)
    o = jnp.einsum("bhqk,bkhd->bqhd", cast(p), cast(v),
                   precision=hi).reshape(b, s, hq * hd)
    x = x + _mm(o, a["wo"], cast)
    h = rms_norm(x, w["ln2"], eps)
    m = w["mlp"]
    ff = jax.nn.silu(_mm(h, m["w_gate"], cast)) * _mm(h, m["w_up"], cast)
    return x + _mm(ff, m["w_down"], cast)


def logits(x, top: dict, cfg: dict, cast=precision.exact):
    """Final norm and output head over x (B, S, D): (B, S, vocab)."""
    h = rms_norm(x, top["final_norm"], cfg["norm_eps"])
    head = top["embedding"].T if cfg["tie_embeddings"] else top["lm_head"]
    return _mm(h, head, cast)[..., : cfg["vocab_size"]]


def forward(tokens, top: dict, layer_weights, cfg: dict, positions,
            cast=precision.exact):
    """Logits (B, len(positions), vocab) at the given positions of
    ``tokens`` (B, S). ``layer_weights(i)`` gives layer i's weights; each is
    dropped once its layer has run."""
    import jax
    import jax.numpy as jnp

    run_layer = jax.jit(functools.partial(layer, cfg=cfg, cast=cast))
    x = jnp.take(top["embedding"], tokens, axis=0)
    for i in range(cfg["n_layers"]):
        x = run_layer(x, layer_weights(i))
    head = jax.jit(functools.partial(logits, cfg=cfg, cast=cast))
    return head(x[:, jnp.asarray(positions)], top)
