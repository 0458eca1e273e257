"""Decoder-only transformer (dense GQA family; also the VLM backbone).

Layers are stacked and iterated with ``lax.scan`` so the compiled HLO is one
layer body regardless of depth (compile-time sanity for 40-layer × 512-device
dry-runs). Per-layer attention window sizes ride alongside the stacked params
as a scanned array, which lets one scan body express full, sliding-window and
local:global interleaved patterns (gemma3's 5:1, danube's SWA).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.configs.base import ArchConfig
from repro.models import layers as L


def _init_layer(key, cfg: ArchConfig):
    ka, km = jax.random.split(key)
    p = {
        "ln1": L.init_norm(cfg.d_model),
        "attn": L.init_attention(ka, cfg),
        "ln2": L.init_norm(cfg.d_model),
        "mlp": L.init_mlp(km, cfg.d_model, cfg.d_ff, cfg.act),
    }
    return p


def init_params(key, cfg: ArchConfig):
    ke, kl = jax.random.split(key)
    layer_keys = jax.random.split(kl, cfg.n_layers)
    params = {
        **L.init_embedding(ke, cfg),
        "layers": jax.vmap(lambda k: _init_layer(k, cfg))(layer_keys),
        "final_norm": L.init_norm(cfg.d_model),
    }
    return params


# The leaves the model only ever reads through ``.astype(x.dtype)``: the
# projections, the embedding and the output head. Norm scales are read in
# float32 by ``rms_norm`` and stay as they are.
_SERVED_IN_COMPUTE_DTYPE = frozenset(
    [("embedding",), ("lm_head",)]
    + [("layers", "attn", w) for w in ("wq", "wk", "wv", "wo")]
    + [("layers", "mlp", w) for w in ("w_up", "w_gate", "w_down")])


@functools.partial(jax.jit, static_argnums=1)
def _cast_leaves(leaves, dtype):
    return [leaf.astype(dtype) for leaf in leaves]


def serving_params(params, cfg: ArchConfig,
                   leaves=_SERVED_IN_COMPUTE_DTYPE):
    """``params`` with the projection, embedding and output-head leaves
    (the paths in ``leaves``) cast to ``cfg.dtype`` in one jitted call, for
    serving: the model casts them to that dtype at every use, so the cast
    copy gives the same logits and is read at half the bytes of float32
    masters. Every other leaf, and a leaf already at ``cfg.dtype``, is
    passed through as it is."""
    dtype = jnp.dtype(cfg.dtype)
    flat, treedef = jax.tree_util.tree_flatten_with_path(params)
    todo = [i for i, (path, leaf) in enumerate(flat)
            if tuple(k.key for k in path) in leaves
            and leaf.dtype != dtype]
    if not todo:
        return params
    leaves = [leaf for _, leaf in flat]
    for i, cast in zip(todo, _cast_leaves([leaves[i] for i in todo], dtype)):
        leaves[i] = cast
    return jax.tree_util.tree_unflatten(treedef, leaves)


def window_array(cfg: ArchConfig):
    return jnp.asarray([cfg.window_for_layer(i) for i in range(cfg.n_layers)],
                       jnp.int32)


def _block(x, lp, window, cfg: ArchConfig, positions, mrope_positions):
    h = L.rms_norm(x, lp["ln1"], cfg.norm_eps)
    attn_out, _ = L.attention(h, lp["attn"], cfg, positions, window,
                              mrope_positions)
    x = x + attn_out
    h = L.rms_norm(x, lp["ln2"], cfg.norm_eps)
    return L.shard_act(x + L.mlp(h, lp["mlp"], cfg.act), seq_model=True)


def forward(params, tokens, cfg: ArchConfig, *, inputs_embeds=None,
            mrope_positions=None, remat: str = "full"):
    """tokens (B, S) -> logits (B, S, V).

    ``inputs_embeds`` (B, S, D) overrides the token embedding where finite —
    the VLM stub frontend injects precomputed patch embeddings this way.
    """
    dtype = jnp.dtype(cfg.dtype)
    x = L.embed(tokens, params, cfg, dtype)
    if inputs_embeds is not None:
        n = inputs_embeds.shape[1]
        x = jnp.concatenate([inputs_embeds.astype(dtype), x[:, n:]], axis=1)
    b, s = tokens.shape
    positions = jnp.broadcast_to(jnp.arange(s, dtype=jnp.int32), (b, s))

    def body(carry, per_layer):
        lp, window = per_layer
        return _block(carry, lp, window, cfg, positions, mrope_positions), None

    if remat == "full":
        body = jax.checkpoint(body,
                              policy=jax.checkpoint_policies.nothing_saveable)
    elif remat == "dots":
        body = jax.checkpoint(
            body, policy=jax.checkpoint_policies.dots_with_no_batch_dims_saveable)

    x, _ = jax.lax.scan(body, x, (params["layers"], window_array(cfg)))
    x = L.rms_norm(x, params["final_norm"], cfg.norm_eps)
    return L.unembed(x, params, cfg)


# -------------------------------------------------------------------- decode --

def init_cache(cfg: ArchConfig, batch: int, max_len: int, dtype=None):
    dtype = dtype or jnp.dtype(cfg.dtype)
    t_alloc = L.ring_cache_len(cfg, max_len)  # = max_len unless RING_KV
    shape = (cfg.n_layers, batch, t_alloc, cfg.n_kv_heads, cfg.head_dim)
    return {"k": jnp.zeros(shape, dtype), "v": jnp.zeros(shape, dtype)}


def decode_step(params, cache, tokens, pos, cfg: ArchConfig, *,
                mrope_positions=None):
    """One-token decode. tokens (B, 1); pos () int32 — write position.

    Each layer reads its slice of the stacked (L, B, T, H, hd) cache, with
    its token's k and v seen at their slot (``layers.with_row``), and
    returns those rows; after the layer scan one update writes the L rows
    at the slot (``layers.write_rows``). Nothing else of the cache is
    written, so a cache donated to the compiled step is updated in place
    (``Server`` donates it).

    Returns (logits (B, V), new_cache)."""
    dtype = jnp.dtype(cfg.dtype)
    x = L.embed(tokens, params, cfg, dtype)
    # uniform static window (e.g. danube's SWA-everywhere): enables the
    # window-sliced cache read perf knob
    uniform_w = None
    if (cfg.window_pattern and cfg.window_pattern[0] > 0
            and all(w == cfg.window_pattern[0] for w in cfg.window_pattern)):
        uniform_w = cfg.window_pattern[0]
    ring = uniform_w is not None

    def body(x_c, per_layer):
        lp, window, li = per_layer
        h = L.rms_norm(x_c, lp["ln1"], cfg.norm_eps)
        attn_out, k, v = L.attention_decode(
            h, lp["attn"], cfg, L.cache_layer(cache["k"], li),
            L.cache_layer(cache["v"], li), pos, window, mrope_positions,
            static_window=uniform_w, ring=ring)
        x2 = x_c + attn_out
        h = L.rms_norm(x2, lp["ln2"], cfg.norm_eps)
        return x2 + L.mlp(h, lp["mlp"], cfg.act), (k, v)

    layer_ids = jnp.arange(cfg.n_layers, dtype=jnp.int32)
    x, (ks, vs) = jax.lax.scan(
        body, x, (params["layers"], window_array(cfg), layer_ids))
    slot = L.cache_slot(pos, cache["k"].shape[2], ring)
    x = L.rms_norm(x, params["final_norm"], cfg.norm_eps)
    logits = L.unembed(x, params, cfg)
    return logits[:, 0], {"k": L.write_rows(cache["k"], ks, slot),
                          "v": L.write_rows(cache["v"], vs, slot)}


def prefill(params, tokens, cfg: ArchConfig, max_len: int, *,
            inputs_embeds=None, mrope_positions=None):
    """Forward + cache construction for serving. Returns (logits, cache)."""
    dtype = jnp.dtype(cfg.dtype)
    x = L.embed(tokens, params, cfg, dtype)
    if inputs_embeds is not None:
        n = inputs_embeds.shape[1]
        x = jnp.concatenate([inputs_embeds.astype(dtype), x[:, n:]], axis=1)
    b, s = tokens.shape
    positions = jnp.broadcast_to(jnp.arange(s, dtype=jnp.int32), (b, s))

    def body(carry, per_layer):
        lp, window = per_layer
        h = L.rms_norm(carry, lp["ln1"], cfg.norm_eps)
        attn_out, (k, v) = L.attention(h, lp["attn"], cfg, positions, window,
                                       mrope_positions)
        x2 = carry + attn_out
        h = L.rms_norm(x2, lp["ln2"], cfg.norm_eps)
        out = x2 + L.mlp(h, lp["mlp"], cfg.act)
        k, v = L.ring_store(k.astype(dtype), cfg, max_len), \
            L.ring_store(v.astype(dtype), cfg, max_len)
        return out, (k, v)

    x, (ks, vs) = jax.lax.scan(body, x, (params["layers"], window_array(cfg)))
    x = L.rms_norm(x, params["final_norm"], cfg.norm_eps)
    return L.unembed(x, params, cfg), {"k": ks, "v": vs}
