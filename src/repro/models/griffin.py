"""Griffin / RecurrentGemma hybrid: RG-LRU recurrent blocks + local attention.

The 26 layers follow the repeating pattern (rec, rec, attn). To keep the
compiled HLO one-unit-sized, layers are scanned in *units* of the pattern
(8 full units for 26 layers) with the leftover recurrent blocks scanned as a
tail stack. The RG-LRU linear recurrence runs as a ``jax.lax.
associative_scan`` over the sequence (train/prefill) and an O(1) state
update at decode. The elementwise gate math (i_t ⊙ x_t accumulation) is the
model-level consumer of the paper's Algorithm-2 (vmacc) intrinsic.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from repro.configs.base import ArchConfig
from repro.models import layers as L

C_GATE = 8.0  # RG-LRU gate exponent constant (Griffin, eq. 4)


# ----------------------------------------------------------------- init ------

def _init_rec(key, cfg: ArchConfig):
    d, w = cfg.d_model, cfg.lru_width or cfg.d_model
    ks = jax.random.split(key, 6)
    s_d = 1.0 / math.sqrt(d)
    s_w = 1.0 / math.sqrt(w)
    return {
        "ln1": L.init_norm(d),
        "w_x": jax.random.normal(ks[0], (d, w), jnp.float32) * s_d,
        "w_y": jax.random.normal(ks[1], (d, w), jnp.float32) * s_d,
        "conv_w": jax.random.normal(ks[2], (cfg.conv_kernel, w),
                                    jnp.float32) * 0.1,
        "conv_b": jnp.zeros((w,), jnp.float32),
        "w_a": jax.random.normal(ks[3], (w, w), jnp.float32) * s_w,
        "b_a": jnp.zeros((w,), jnp.float32),
        "w_i": jax.random.normal(ks[4], (w, w), jnp.float32) * s_w,
        "b_i": jnp.zeros((w,), jnp.float32),
        "lam": jnp.full((w,), 2.0, jnp.float32),  # Λ init: a ~ 0.95
        "w_out": jax.random.normal(ks[5], (w, d), jnp.float32) * s_w,
        "ln2": L.init_norm(d),
        "mlp": L.init_mlp(jax.random.fold_in(key, 7), d, cfg.d_ff, cfg.act),
    }


def _init_attn(key, cfg: ArchConfig):
    ka, km = jax.random.split(key)
    return {
        "ln1": L.init_norm(cfg.d_model),
        "attn": L.init_attention(ka, cfg),
        "ln2": L.init_norm(cfg.d_model),
        "mlp": L.init_mlp(km, cfg.d_model, cfg.d_ff, cfg.act),
    }


def _unit_counts(cfg: ArchConfig):
    pat = len(cfg.block_pattern)  # (rec, rec, attn)
    n_units = cfg.n_layers // pat
    n_tail = cfg.n_layers - n_units * pat  # leftover 'rec' blocks
    return n_units, n_tail


def init_params(key, cfg: ArchConfig):
    ke, k1, k2, k3, kt = jax.random.split(key, 5)
    n_units, n_tail = _unit_counts(cfg)
    params = {
        **L.init_embedding(ke, cfg),
        "units": {
            "rec1": jax.vmap(lambda k: _init_rec(k, cfg))(
                jax.random.split(k1, n_units)),
            "rec2": jax.vmap(lambda k: _init_rec(k, cfg))(
                jax.random.split(k2, n_units)),
            "attn": jax.vmap(lambda k: _init_attn(k, cfg))(
                jax.random.split(k3, n_units)),
        },
        "final_norm": L.init_norm(cfg.d_model),
    }
    if n_tail:
        params["tail"] = jax.vmap(lambda k: _init_rec(k, cfg))(
            jax.random.split(kt, n_tail))
    return params


# ----------------------------------------------------------------- RG-LRU ----

def _gates(branch, p):
    r = jax.nn.sigmoid(branch @ p["w_a"].astype(branch.dtype)
                       + p["b_a"].astype(branch.dtype))
    i = jax.nn.sigmoid(branch @ p["w_i"].astype(branch.dtype)
                       + p["b_i"].astype(branch.dtype))
    log_a = (-C_GATE * jax.nn.softplus(p["lam"].astype(jnp.float32))
             * r.astype(jnp.float32))
    a = jnp.exp(log_a)
    beta = jnp.sqrt(jnp.maximum(1.0 - a * a, 1e-12))
    return a, beta * i.astype(jnp.float32) * branch.astype(jnp.float32)


def rg_lru(branch, p, h0=None):
    """Linear recurrence h_t = a_t h_{t-1} + β_t i_t x_t via associative scan.
    branch (B,S,W). Returns (h (B,S,W), h_last (B,W))."""
    a, b = _gates(branch, p)
    # pin batch sharding of the f32 gate tensors: the associative scan
    # communicates along S, so GSPMD must keep B partitioned
    a, b = L.shard_act(a), L.shard_act(b)
    if h0 is not None:
        b = b.at[:, 0].add(a[:, 0] * h0.astype(jnp.float32))

    def combine(left, right):
        a1, b1 = left
        a2, b2 = right
        return a1 * a2, a2 * b1 + b2

    _, h = jax.lax.associative_scan(combine, (a, b), axis=1)
    return h.astype(branch.dtype), h[:, -1]


def _conv(branch, p):
    from repro.models.ssm import causal_conv
    return causal_conv(branch, p["conv_w"], p["conv_b"])


def recurrent_block_seq(x, p, cfg: ArchConfig):
    """Temporal mixing of one recurrent block over a sequence."""
    branch = _conv(x @ p["w_x"].astype(x.dtype), p)
    h, _ = rg_lru(branch, p)
    y = jax.nn.gelu(x @ p["w_y"].astype(x.dtype)) * h
    return y @ p["w_out"].astype(x.dtype)


def _rec_layer(x, p, cfg: ArchConfig):
    h = L.rms_norm(x, p["ln1"], cfg.norm_eps)
    x = x + recurrent_block_seq(h, p, cfg)
    h = L.rms_norm(x, p["ln2"], cfg.norm_eps)
    return x + L.mlp(h, p["mlp"], cfg.act)


def _attn_layer(x, p, cfg: ArchConfig, positions, window):
    h = L.rms_norm(x, p["ln1"], cfg.norm_eps)
    out, kv = L.attention(h, p["attn"], cfg, positions, window)
    x = x + out
    h = L.rms_norm(x, p["ln2"], cfg.norm_eps)
    return x + L.mlp(h, p["mlp"], cfg.act), kv


def forward(params, tokens, cfg: ArchConfig, *, remat: str = "full"):
    dtype = jnp.dtype(cfg.dtype)
    x = L.embed(tokens, params, cfg, dtype)
    b, s = tokens.shape
    positions = jnp.broadcast_to(jnp.arange(s, dtype=jnp.int32), (b, s))
    window = cfg.window_pattern[0] if cfg.window_pattern else -1

    def body(carry, unit):
        h = _rec_layer(carry, unit["rec1"], cfg)
        h = _rec_layer(h, unit["rec2"], cfg)
        h, _ = _attn_layer(h, unit["attn"], cfg, positions, window)
        return L.shard_act(h, seq_model=True), None

    if remat == "full":
        body = jax.checkpoint(body,
                              policy=jax.checkpoint_policies.nothing_saveable)
    x, _ = jax.lax.scan(body, x, params["units"])
    if "tail" in params:
        def tail_body(carry, p):
            return _rec_layer(carry, p, cfg), None
        if remat == "full":
            tail_body = jax.checkpoint(
                tail_body, policy=jax.checkpoint_policies.nothing_saveable)
        x, _ = jax.lax.scan(tail_body, x, params["tail"])
    x = L.rms_norm(x, params["final_norm"], cfg.norm_eps)
    return L.unembed(x, params, cfg)


# -------------------------------------------------------------------- decode --

def init_cache(cfg: ArchConfig, batch: int, max_len: int, dtype=None):
    dtype = dtype or jnp.dtype(cfg.dtype)
    n_units, n_tail = _unit_counts(cfg)
    w = cfg.lru_width or cfg.d_model
    k = cfg.conv_kernel - 1
    t_alloc = L.ring_cache_len(cfg, max_len)
    cache = {
        "k": jnp.zeros((n_units, batch, t_alloc, cfg.n_kv_heads,
                        cfg.head_dim), dtype),
        "v": jnp.zeros((n_units, batch, t_alloc, cfg.n_kv_heads,
                        cfg.head_dim), dtype),
        "h1": jnp.zeros((n_units, batch, w), jnp.float32),
        "c1": jnp.zeros((n_units, batch, k, w), dtype),
        "h2": jnp.zeros((n_units, batch, w), jnp.float32),
        "c2": jnp.zeros((n_units, batch, k, w), dtype),
    }
    if n_tail:
        cache["ht"] = jnp.zeros((n_tail, batch, w), jnp.float32)
        cache["ct"] = jnp.zeros((n_tail, batch, k, w), dtype)
    return cache


def _rec_decode(x, p, cfg: ArchConfig, h_prev, conv_c):
    """x (B,D) one token. Returns (out, h, conv_c)."""
    hx = L.rms_norm(x, p["ln1"], cfg.norm_eps)
    branch = hx @ p["w_x"].astype(x.dtype)              # (B,W)
    window = jnp.concatenate([conv_c, branch[:, None]], axis=1)
    w = p["conv_w"].astype(x.dtype)
    branch = (window * w[None]).sum(axis=1) + p["conv_b"].astype(x.dtype)
    conv_c = window[:, 1:]
    a, b = _gates(branch, p)
    h = a * h_prev + b                                   # (B,W) f32
    y = jax.nn.gelu(hx @ p["w_y"].astype(x.dtype)) * h.astype(x.dtype)
    x = x + y @ p["w_out"].astype(x.dtype)
    hh = L.rms_norm(x, p["ln2"], cfg.norm_eps)
    return x + L.mlp(hh, p["mlp"], cfg.act), h, conv_c


def decode_step(params, cache, tokens, pos, cfg: ArchConfig):
    dtype = jnp.dtype(cfg.dtype)
    x = L.embed(tokens, params, cfg, dtype)             # (B,1,D)
    window = cfg.window_pattern[0] if cfg.window_pattern else -1

    def body(carry, per_unit):
        unit, k_c, v_c, h1, c1, h2, c2 = per_unit
        h = carry[:, 0]
        h, h1, c1 = _rec_decode(h, unit["rec1"], cfg, h1, c1)
        h, h2, c2 = _rec_decode(h, unit["rec2"], cfg, h2, c2)
        h = h[:, None]
        hn = L.rms_norm(h, unit["attn"]["ln1"], cfg.norm_eps)
        out, k, v = L.attention_decode(hn, unit["attn"]["attn"], cfg, k_c,
                                       v_c, pos, window,
                                       static_window=window, ring=window > 0)
        slot = L.cache_slot(pos, k_c.shape[1], window > 0)
        k_c = jax.lax.dynamic_update_slice(k_c, k.astype(k_c.dtype),
                                           (0, slot, 0, 0))
        v_c = jax.lax.dynamic_update_slice(v_c, v.astype(v_c.dtype),
                                           (0, slot, 0, 0))
        h = h + out
        hn = L.rms_norm(h, unit["attn"]["ln2"], cfg.norm_eps)
        h = h + L.mlp(hn, unit["attn"]["mlp"], cfg.act)
        return h, (k_c, v_c, h1, c1, h2, c2)

    x, (nk, nv, h1, c1, h2, c2) = jax.lax.scan(
        body, x, (params["units"], cache["k"], cache["v"], cache["h1"],
                  cache["c1"], cache["h2"], cache["c2"]))
    new_cache = dict(cache, k=nk, v=nv, h1=h1, c1=c1, h2=h2, c2=c2)
    if "tail" in params:
        def tail_body(carry, per):
            p, ht, ct = per
            h, ht, ct = _rec_decode(carry[:, 0], p, cfg, ht, ct)
            return h[:, None], (ht, ct)
        x, (ht, ct) = jax.lax.scan(tail_body, x,
                                   (params["tail"], cache["ht"],
                                    cache["ct"]))
        new_cache.update(ht=ht, ct=ct)
    x = L.rms_norm(x, params["final_norm"], cfg.norm_eps)
    return L.unembed(x, params, cfg)[:, 0], new_cache


def prefill(params, tokens, cfg: ArchConfig, max_len: int):
    """Forward with cache capture (attention KV + final recurrent states)."""
    dtype = jnp.dtype(cfg.dtype)
    x = L.embed(tokens, params, cfg, dtype)
    b, s = tokens.shape
    positions = jnp.broadcast_to(jnp.arange(s, dtype=jnp.int32), (b, s))
    window = cfg.window_pattern[0] if cfg.window_pattern else -1
    kc = cfg.conv_kernel - 1

    def rec_seq(carry, p):
        h = L.rms_norm(carry, p["ln1"], cfg.norm_eps)
        branch = h @ p["w_x"].astype(h.dtype)
        branch = _conv(branch, p)
        hseq, h_last = rg_lru(branch, p)
        y = jax.nn.gelu(h @ p["w_y"].astype(h.dtype)) * hseq
        out = carry + y @ p["w_out"].astype(h.dtype)
        hh = L.rms_norm(out, p["ln2"], cfg.norm_eps)
        conv_tail = (h @ p["w_x"].astype(h.dtype))[:, -kc:]
        return out + L.mlp(hh, p["mlp"], cfg.act), (h_last, conv_tail)

    def body(carry, unit):
        h, (h1, c1) = rec_seq(carry, unit["rec1"])
        h, (h2, c2) = rec_seq(h, unit["rec2"])
        h, (kk, vv) = _attn_layer(h, unit["attn"], cfg, positions, window)
        kk = L.ring_store(kk.astype(dtype), cfg, max_len)
        vv = L.ring_store(vv.astype(dtype), cfg, max_len)
        return h, (kk, vv, h1, c1, h2, c2)

    x, (ks, vs, h1, c1, h2, c2) = jax.lax.scan(body, x, params["units"])
    cache = {"k": ks, "v": vs, "h1": h1, "c1": c1, "h2": h2, "c2": c2}
    if "tail" in params:
        def tail_body(carry, p):
            out, (ht, ct) = rec_seq(carry, p)
            return out, (ht, ct)
        x, (ht, ct) = jax.lax.scan(tail_body, x, params["tail"])
        cache.update(ht=ht, ct=ct)
    x = L.rms_norm(x, params["final_norm"], cfg.norm_eps)
    return L.unembed(x, params, cfg), cache
