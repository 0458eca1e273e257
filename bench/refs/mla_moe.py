"""A DeepSeek-V3-family language model (Moonlight-16B-A3B) layer by layer,
in float32 at "highest" matmul precision, for one expert-parallel shard.

Each layer: RMS norm with a scale; multi-head latent attention in its
decompressed form only (the query ``x Wq`` split per head into a no-rope
and a rope part; the latent ``c = rms_norm(x Wkv_a[:, :C])`` expanded by
``Wkv_b`` into per-head no-rope keys and values; one rope key
``x Wkv_a[:, C:]`` shared by every head; scores over the concatenated
no-rope and rope parts scaled by ``1 / sqrt(nope + rope)``, causal
softmax, ``Wo``); a residual add; RMS norm; the feed-forward block; a
residual add. Layers ``0 .. first_k_dense_replace - 1`` have a dense
SwiGLU block ``(silu(x Wg) * (x Wu)) Wd``; the others route and add the
shared experts (one SwiGLU block of ``n_shared_experts *
moe_intermediate_size``). Then a final norm and the output head.

The router: logits ``x Wr`` over ``router_width`` experts, scores
``sigmoid(logits)``; the ``num_experts_per_tok`` experts with the largest
``score + bias`` are picked, each weighted by its unbiased score over the
sum of the picked scores, times ``routed_scaling_factor``. The experts are
computed densely: for each held expert, every token times its routing
weight, which is zero for a token not routed to it.

Departures from the published model, each shared with the program:

- one expert-parallel shard: only the ``n_routed_experts`` experts from
  ``expert_offset`` on are held and computed; what the other experts would
  add is left out (the router still scores all ``router_width``);
- a slice of the vocabulary (``vocab_size`` ids, embedding and head);
- ``num_hidden_layers`` of the published layers;
- the rope rotates (first half, second half) pairs of the rope dims, where
  the published code rotates interleaved pairs: a fixed permutation of
  random weights' rope columns;
- random weights from the seed, the correction bias among them.
"""

from __future__ import annotations

import functools
import math

from bench.refs import precision
from bench.refs.lm import _mm, rms_norm, rope


def attention(x, a, cfg: dict, cast=precision.exact):
    """Causal MLA over x (B, S, D), decompressed; the normed input."""
    import jax
    import jax.numpy as jnp

    b, s, _ = x.shape
    h, c = cfg["num_attention_heads"], cfg["kv_lora_rank"]
    n, r, v = (cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"],
               cfg["v_head_dim"])
    theta = cfg["rope_theta"]
    q = _mm(x, a["wq"], cast).reshape(b, s, h, n + r)
    q = jnp.concatenate([q[..., :n], rope(q[..., n:], theta)], -1)
    kv = _mm(x, a["wkv_a"], cast)
    latent = rms_norm(kv[..., :c], a["kv_norm"], cfg["rms_norm_eps"])
    k_pe = rope(kv[..., None, c:], theta)  # (B, S, 1, r)
    kvb = _mm(latent, a["wkv_b"], cast).reshape(b, s, h, n + v)
    k = jnp.concatenate(
        [kvb[..., :n], jnp.broadcast_to(k_pe, (b, s, h, r))], -1)
    vals = kvb[..., n:]
    hi = jax.lax.Precision.HIGHEST
    sc = jnp.einsum("bqhd,bkhd->bhqk", cast(q), cast(k),
                    precision=hi) / math.sqrt(n + r)
    causal = jnp.arange(s)[None, :] <= jnp.arange(s)[:, None]
    p = jax.nn.softmax(jnp.where(causal, sc, -jnp.inf), axis=-1)
    o = jnp.einsum("bhqk,bkhd->bqhd", cast(p), cast(vals),
                   precision=hi).reshape(b, s, h * v)
    return _mm(o, a["wo"], cast)


def swiglu(x, m, cast=precision.exact):
    import jax

    return _mm(jax.nn.silu(_mm(x, m["w_gate"], cast))
               * _mm(x, m["w_up"], cast), m["w_down"], cast)


def route(x, w, cfg: dict, cast=precision.exact):
    """Expert ids (..., k) and their weights (..., k)."""
    import jax
    import jax.numpy as jnp

    scores = jax.nn.sigmoid(_mm(x, w["router"], cast))
    _, idx = jax.lax.top_k(scores + w["router_bias"],
                           cfg["num_experts_per_tok"])
    picked = jnp.take_along_axis(scores, idx, axis=-1)
    weights = picked / picked.sum(axis=-1, keepdims=True)
    return idx, weights * cfg["routed_scaling_factor"]


def routed(x, w, cfg: dict, cast=precision.exact):
    """The held experts' part, dense: every held expert over every token,
    times the token's weight for it (zero when not routed to it)."""
    import jax.numpy as jnp

    idx, weights = route(x, w, cfg, cast)
    e = w["experts"]
    out = jnp.zeros_like(x)
    for j in range(e["w_gate"].shape[0]):
        mine = (idx == cfg["expert_offset"] + j)
        gate = jnp.sum(jnp.where(mine, weights, 0.0), axis=-1)
        expert = {name: e[name][j] for name in ("w_gate", "w_up", "w_down")}
        out = out + gate[..., None] * swiglu(x, expert, cast)
    return out


def layer(x, w, cfg: dict, dense: bool, cast=precision.exact):
    """One decoder layer over x (B, S, D)."""
    eps = cfg["rms_norm_eps"]
    x = x + attention(rms_norm(x, w["ln1"], eps), w["attn"], cfg, cast)
    h = rms_norm(x, w["ln2"], eps)
    if dense:
        return x + swiglu(h, w["mlp"], cast)
    return x + routed(h, w, cfg, cast) + swiglu(h, w["shared"], cast)


def logits(x, top: dict, cfg: dict, cast=precision.exact):
    """Final norm and output head over x (B, S, D): (B, S, vocab)."""
    h = rms_norm(x, top["final_norm"], cfg["rms_norm_eps"])
    head = (top["embedding"].T if cfg["tie_word_embeddings"]
            else top["lm_head"])
    return _mm(h, head, cast)[..., : cfg["vocab_size"]]


def forward(tokens, top: dict, layer_weights, cfg: dict, positions,
            cast=precision.exact):
    """Logits (B, len(positions), vocab) at the given positions of
    ``tokens`` (B, S). ``layer_weights(i)`` gives layer i's weights; each is
    dropped once its layer has run."""
    import jax
    import jax.numpy as jnp

    runs = {dense: jax.jit(functools.partial(layer, cfg=cfg, dense=dense,
                                             cast=cast))
            for dense in (True, False)}
    x = jnp.take(top["embedding"], tokens, axis=0)
    for i in range(cfg["num_hidden_layers"]):
        x = runs[i < cfg["first_k_dense_replace"]](x, layer_weights(i))
    head = jax.jit(functools.partial(logits, cfg=cfg, cast=cast))
    return head(x[:, jnp.asarray(positions)], top)
