"""Shared model building blocks (pure functional JAX).

Parameters are pytrees of f32 arrays ("master" precision); compute casts to
the config dtype (bf16 by default). Tensor contractions route through
``jnp``/``lax`` so XLA/GSPMD partitions them on the production mesh; the
Pallas kernels in ``repro.kernels`` are the tuned single-chip hot paths
benchmarked separately (DESIGN.md §5).
"""

from __future__ import annotations

import dataclasses
import math

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import ArchConfig

# ----------------------------------------------------- activation sharding --
# GSPMD occasionally drops the batch sharding of buffers it stacks across
# scan steps (layer-carry stacks, loss-region logits). The launch layer
# installs the mesh axes here and the models pin the residual stream / logits
# with explicit constraints. No-op when unset (single-device tests).
#
# ``seq_model`` additionally shards the sequence dim of the between-layer
# residual stream over the model axis — Megatron-style sequence parallelism,
# which divides the remat-scan carry stacks (the dominant train-memory term)
# by the TP degree at the cost of a gather/scatter pair per layer.
_BATCH_AXES: tuple | None = None
_BATCH_SIZE: int = 1
_MODEL_AXIS: str | None = None
_MODEL_SIZE: int = 1
_SEQ_SHARD: bool = True


def set_activation_sharding(batch_axes, batch_size, model_axis="model",
                            model_size=1, seq_shard=True):
    global _BATCH_AXES, _BATCH_SIZE, _MODEL_AXIS, _MODEL_SIZE, _SEQ_SHARD
    _BATCH_AXES = tuple(batch_axes) if batch_axes else None
    _BATCH_SIZE = batch_size
    _MODEL_AXIS = model_axis
    _MODEL_SIZE = model_size
    _SEQ_SHARD = seq_shard


def clear_activation_sharding():
    global _BATCH_AXES, _MODEL_AXIS
    _BATCH_AXES = None
    _MODEL_AXIS = None


def shard_expert(x):
    """Constrain (B, E, ...) expert-parallel buffers: batch over DP axes,
    experts over the model axis (EP)."""
    if _BATCH_AXES is None or x.ndim < 2:
        return x
    from jax.sharding import PartitionSpec as P
    axes = [P.UNCONSTRAINED] * x.ndim
    if x.shape[0] % _BATCH_SIZE == 0 and x.shape[0] >= _BATCH_SIZE:
        axes[0] = _BATCH_AXES
    if x.shape[1] % _MODEL_SIZE == 0 and x.shape[1] >= _MODEL_SIZE:
        axes[1] = _MODEL_AXIS
    return jax.lax.with_sharding_constraint(x, P(*axes))


def shard_act(x, last_dim_model: bool = False, seq_model: bool = False):
    """Constrain (B, [S,] ..., D) activations: batch over the DP axes;
    optionally the seq dim (residual carries) or the last dim (padded vocab
    logits) over the model axis. Dims that don't divide stay unconstrained."""
    if _BATCH_AXES is None or x.ndim < 2:
        return x
    from jax.sharding import PartitionSpec as P
    axes = [P.UNCONSTRAINED] * x.ndim
    if x.shape[0] % _BATCH_SIZE == 0 and x.shape[0] >= _BATCH_SIZE:
        axes[0] = _BATCH_AXES
    if (seq_model and _SEQ_SHARD and x.ndim >= 3
            and x.shape[1] % _MODEL_SIZE == 0 and x.shape[1] >= _MODEL_SIZE):
        axes[1] = _MODEL_AXIS
    if last_dim_model and x.shape[-1] % _MODEL_SIZE == 0:
        axes[-1] = _MODEL_AXIS
    return jax.lax.with_sharding_constraint(x, P(*axes))


# --------------------------------------------------------------------- init --

def _dense_init(key, shape, scale=None):
    fan_in = shape[0]
    scale = scale if scale is not None else 1.0 / math.sqrt(fan_in)
    return (jax.random.normal(key, shape, jnp.float32) * scale)


def init_norm(d: int):
    return jnp.ones((d,), jnp.float32)


# --------------------------------------------------------------------- norms --

def rms_norm(x, scale, eps: float = 1e-6):
    dtype = x.dtype
    x = x.astype(jnp.float32)
    var = jnp.mean(x * x, axis=-1, keepdims=True)
    out = x * jax.lax.rsqrt(var + eps) * scale.astype(jnp.float32)
    return out.astype(dtype)


def layer_norm(x, scale, bias, eps: float = 1e-5):
    dtype = x.dtype
    x = x.astype(jnp.float32)
    mu = x.mean(axis=-1, keepdims=True)
    var = jnp.mean((x - mu) ** 2, axis=-1, keepdims=True)
    out = (x - mu) * jax.lax.rsqrt(var + eps)
    out = out * scale.astype(jnp.float32) + bias.astype(jnp.float32)
    return out.astype(dtype)


# ---------------------------------------------------------------------- rope --

def _rope_freqs(head_dim: int, theta: float):
    return 1.0 / (theta ** (np.arange(0, head_dim, 2) / head_dim))


def apply_rope(x, positions, theta: float):
    """x (..., S, H, D); positions (..., S) int32."""
    d = x.shape[-1]
    freqs = jnp.asarray(_rope_freqs(d, theta), jnp.float32)  # (D/2,)
    angles = positions[..., None].astype(jnp.float32) * freqs  # (..., S, D/2)
    cos = jnp.cos(angles)[..., None, :]  # (..., S, 1, D/2)
    sin = jnp.sin(angles)[..., None, :]
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)
    return out.astype(x.dtype)


def apply_mrope(x, positions, theta: float, sections: tuple[int, ...]):
    """Multimodal RoPE (Qwen2-VL): ``positions`` is (B, 3, S) — one position
    stream per (temporal, height, width) — and the head_dim/2 frequency
    bands are split into ``sections`` consuming their own stream."""
    d = x.shape[-1]
    freqs = jnp.asarray(_rope_freqs(d, theta), jnp.float32)  # (D/2,)
    # section id per frequency band
    sec_id = np.zeros(d // 2, np.int32)
    start = 0
    for i, s in enumerate(sections):
        sec_id[start:start + s] = i
        start += s
    sec_id = jnp.asarray(sec_id)
    pos = jnp.take(positions.astype(jnp.float32), sec_id, axis=1)  # (B, D/2, S)
    pos = jnp.moveaxis(pos, 1, -1)  # (B, S, D/2)
    angles = pos * freqs
    cos = jnp.cos(angles)[..., None, :]
    sin = jnp.sin(angles)[..., None, :]
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)
    return out.astype(x.dtype)


# ----------------------------------------------------------------- attention --

def init_attention(key, cfg: ArchConfig):
    d = cfg.d_model
    ks = jax.random.split(key, 4)
    return {
        "wq": _dense_init(ks[0], (d, cfg.q_dim)),
        "wk": _dense_init(ks[1], (d, cfg.kv_dim)),
        "wv": _dense_init(ks[2], (d, cfg.kv_dim)),
        "wo": _dense_init(ks[3], (cfg.q_dim, d)),
    }


def _qkv(x, p, cfg: ArchConfig):
    b, s, _ = x.shape
    q = (x @ p["wq"].astype(x.dtype)).reshape(b, s, cfg.n_heads, cfg.head_dim)
    k = (x @ p["wk"].astype(x.dtype)).reshape(b, s, cfg.n_kv_heads,
                                              cfg.head_dim)
    v = (x @ p["wv"].astype(x.dtype)).reshape(b, s, cfg.n_kv_heads,
                                              cfg.head_dim)
    return q, k, v


# Chunked online-softmax attention: the same KV-blocking the Pallas flash
# kernel implements, expressed as a lax.scan so plain XLA/GSPMD compiles it
# on any backend without materializing (S, T) score tensors. The q-head
# einsum layout keeps the head dim shardable over the ``model`` mesh axis.
ATTN_CHUNK = 1024
_COL_SENTINEL = 2**30  # padded key slots: fails both validity and causality


def _sdpa(q, k, v, rows, cols, window=-1, causal=True):
    """q (B,S,Hq,D); k/v (B,T,Hkv,D); rows (S,)/cols (T,) global positions.

    ``window``: -1 (or traced negative) = unlimited; else sliding window.
    Returns (B, S, Hq*D) in q.dtype.
    """
    b, s, hq, d = q.shape
    t, hkv = k.shape[1], k.shape[2]
    group = hq // hkv
    if group > 1:
        k = jnp.repeat(k, group, axis=2)
        v = jnp.repeat(v, group, axis=2)
    scale = 1.0 / math.sqrt(d)
    c = min(ATTN_CHUNK, t)
    pad = (-t) % c
    if pad:
        k = jnp.pad(k, ((0, 0), (0, pad), (0, 0), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, pad), (0, 0), (0, 0)))
        cols = jnp.concatenate(
            [cols, jnp.full((pad,), _COL_SENTINEL, jnp.int32)])
    nc = (t + pad) // c
    k_c = k.reshape(b, nc, c, hq, d).transpose(1, 0, 2, 3, 4)
    v_c = v.reshape(b, nc, c, hq, d).transpose(1, 0, 2, 3, 4)
    cols_c = cols.reshape(nc, c)
    rows_b = rows[None, None, :, None]  # (1,1,S,1)

    def body(carry, inp):
        m, l, acc = carry
        kk, vv, cc = inp
        sc = jnp.einsum("bshd,bchd->bhsc", q, kk,
                        preferred_element_type=jnp.float32) * scale
        cc_b = cc[None, None, None, :]
        pred = cc_b < _COL_SENTINEL
        if causal:
            pred = jnp.logical_and(pred, cc_b <= rows_b)
            pred = jnp.logical_and(
                pred, jnp.logical_or(window < 0, rows_b - cc_b < window))
        sc = jnp.where(pred, sc, -1e30)
        m_new = jnp.maximum(m, sc.max(axis=-1, keepdims=True))
        p = jnp.exp(sc - m_new)
        alpha = jnp.exp(m - m_new)
        l_new = alpha * l + p.sum(axis=-1, keepdims=True)
        acc_new = acc * alpha + jnp.einsum(
            "bhsc,bchd->bhsd", p.astype(vv.dtype), vv,
            preferred_element_type=jnp.float32)
        return (m_new, l_new, acc_new), None

    init = (jnp.full((b, hq, s, 1), -1e30, jnp.float32),
            jnp.zeros((b, hq, s, 1), jnp.float32),
            jnp.zeros((b, hq, s, d), jnp.float32))
    # Recompute chunk scores in backward instead of stacking (nc, B, H, S, C)
    # f32 residuals — the flash-attention memory property under autodiff.
    body = jax.checkpoint(body,
                          policy=jax.checkpoint_policies.nothing_saveable)
    (m, l, acc), _ = jax.lax.scan(body, init, (k_c, v_c, cols_c))
    out = acc / jnp.maximum(l, 1e-20)
    return out.transpose(0, 2, 1, 3).reshape(b, s, hq * d).astype(q.dtype)


def causal_window_mask(s: int, t: int, window, offset: int = 0):
    """(1, s, t) boolean mask (kept for tests/reference paths)."""
    rows = jnp.arange(s)[:, None] + offset
    cols = jnp.arange(t)[None, :]
    mask = cols <= rows
    win_ok = jnp.logical_or(window < 0, rows - cols < window)
    return jnp.logical_and(mask, win_ok)[None]


def attention(x, p, cfg: ArchConfig, positions, window=-1,
              mrope_positions=None):
    """Full-sequence (train/prefill) attention. Returns (out, (k, v))."""
    q, k, v = _qkv(x, p, cfg)
    if cfg.mrope_sections and mrope_positions is not None:
        q = apply_mrope(q, mrope_positions, cfg.rope_theta,
                        cfg.mrope_sections)
        k = apply_mrope(k, mrope_positions, cfg.rope_theta,
                        cfg.mrope_sections)
    else:
        q = apply_rope(q, positions, cfg.rope_theta)
        k = apply_rope(k, positions, cfg.rope_theta)
    s = x.shape[1]
    idx = jnp.arange(s, dtype=jnp.int32)
    out = _sdpa(q, k, v, rows=idx, cols=idx, window=window, causal=True)
    return out @ p["wo"].astype(x.dtype), (k, v)


# When enabled (perf knob), decode with a *static* sliding window reads only
# the last `window` cache positions (dynamic slice) instead of scanning the
# full cache and masking — an O(T/window) HBM-traffic reduction for
# windowed-attention archs at long context.
DECODE_WINDOW_SLICING = False

# Ring-buffer KV caches (perf knob): for uniform static-window archs the
# cache is ALLOCATED at window size and written at pos % window — O(window)
# memory and traffic regardless of context length, with no dynamic-slice
# collectives (the slice the window_slice knob needs crosses shards).
RING_KV = False


def set_decode_window_slicing(enabled: bool):
    global DECODE_WINDOW_SLICING
    DECODE_WINDOW_SLICING = enabled


def set_ring_kv(enabled: bool):
    global RING_KV
    RING_KV = enabled


def ring_cache_len(cfg, max_len: int) -> int:
    """Allocation length for a KV cache: the static window when the ring
    knob is on and every layer shares one positive window."""
    if (RING_KV and cfg.window_pattern and cfg.window_pattern[0] > 0
            and all(w == cfg.window_pattern[0] for w in cfg.window_pattern)):
        return min(max_len, cfg.window_pattern[0])
    return max_len


def ring_positions(pos, t: int):
    """Absolute position stored in each ring slot (negative = unwritten)."""
    idx = jnp.arange(t, dtype=jnp.int32)
    return pos - jnp.mod(pos - idx, t)


def ring_store(k, cfg, max_len: int):
    """Lay prefill keys (B, S, H, D) out into the (possibly ring) cache
    (B, T_alloc, H, D): pad when it fits, else keep the last T_alloc
    positions at slots ``abs_pos % T_alloc``."""
    b, s, h, d = k.shape
    t_alloc = ring_cache_len(cfg, max_len)
    if t_alloc >= s:
        return jnp.pad(k, ((0, 0), (0, t_alloc - s), (0, 0), (0, 0)))
    tail = k[:, s - t_alloc:]
    slots = np.arange(s - t_alloc, s) % t_alloc  # static permutation
    out = jnp.zeros((b, t_alloc, h, d), k.dtype)
    return out.at[:, slots].set(tail)


def cache_slot(pos, t: int, ring: bool = False):
    """The slot of a T-long cache that position ``pos`` is written to:
    ``pos``, or ``pos % T`` in a ring buffer."""
    return jnp.mod(pos, t) if ring else pos


def cache_layer(cache, layer):
    """Layer ``layer`` of a stacked (L, B, T, ...) cache, for a decode step
    to read inside its layer loop.

    On the CPU the cache and the index pass an optimization barrier first.
    That backend converts bf16 dot operands to f32, and XLA would otherwise
    take the slice of a loop-invariant f32 copy of the whole stack, made
    once before the loop: twice the cache's memory. Fenced, the slice and
    its conversion stay inside the loop. On the TPU, which feeds bf16 to
    the MXU, there is no fence, so the slice fuses into the attention that
    reads it. The branch is taken for the platform the program is lowered
    for."""
    def fenced(cache, layer):
        return jax.lax.optimization_barrier((cache, layer))

    cache, layer = jax.lax.platform_dependent(
        cache, layer, cpu=fenced, default=lambda c, i: (c, i))
    return jax.lax.dynamic_index_in_dim(cache, layer, 0, keepdims=False)


def with_row(cache, row, slot):
    """``cache`` (B, T, ...) as it reads once ``row`` (B, 1, ...) is written
    at ``slot``: the same values a write gives, selected where they are
    read, so attention sees the new token without the cache being copied
    to write it."""
    at = jnp.arange(cache.shape[1]) == slot
    at = at.reshape((1, -1) + (1,) * (cache.ndim - 2))
    return jnp.where(at, row.astype(cache.dtype), cache)


def write_rows(cache, rows, slot):
    """Write every layer's new row, ``rows`` (L, B, 1, ...), into the
    stacked (L, B, T, ...) ``cache`` at ``slot``: one update of L x B rows,
    nothing else read or written, in place when the cache is donated."""
    return jax.lax.dynamic_update_slice(
        cache, rows.astype(cache.dtype), (0, 0, slot) + (0,) * (cache.ndim - 3))


def attention_decode(x, p, cfg: ArchConfig, k_cache, v_cache, pos, window=-1,
                     mrope_positions=None, static_window: int | None = None,
                     ring: bool = False):
    """Single-token decode that leaves the cache to its caller. x (B,1,D);
    caches (B,T,Hkv,D), read and not written; pos () int32.

    Attention sees the token's own k and v at its slot (:func:`cache_slot`)
    as a write there would leave them (:func:`with_row`).
    ``ring``: the cache is a ring buffer of length T (= the static window);
    the slot is ``pos % T`` and key positions are reconstructed per slot.

    Returns (out, k, v): k and v are the (B,1,Hkv,D) rows to write at the
    slot."""
    b, s, _ = x.shape
    q, k, v = _qkv(x, p, cfg)
    positions = jnp.full((b, s), pos, jnp.int32)
    if cfg.mrope_sections and mrope_positions is not None:
        q = apply_mrope(q, mrope_positions, cfg.rope_theta, cfg.mrope_sections)
        k = apply_mrope(k, mrope_positions, cfg.rope_theta, cfg.mrope_sections)
    else:
        q = apply_rope(q, positions, cfg.rope_theta)
        k = apply_rope(k, positions, cfg.rope_theta)
    t = k_cache.shape[1]
    slot = cache_slot(pos, t, ring)
    k_use = with_row(k_cache, k, slot)
    v_use = with_row(v_cache, v, slot)
    rows = jnp.full((s,), pos, jnp.int32)
    if ring:
        cols = ring_positions(pos, t)
        cols = jnp.where(cols >= 0, cols, _COL_SENTINEL)
    elif (DECODE_WINDOW_SLICING and static_window is not None
            and 0 < static_window < t):
        w = static_window
        start = jnp.clip(pos - w + 1, 0, t - w)
        k_use = jax.lax.dynamic_slice_in_dim(k_use, start, w, axis=1)
        v_use = jax.lax.dynamic_slice_in_dim(v_use, start, w, axis=1)
        cols = start + jnp.arange(w, dtype=jnp.int32)
    else:
        cols = jnp.arange(t, dtype=jnp.int32)
    out = _sdpa(q, k_use.astype(x.dtype), v_use.astype(x.dtype),
                rows=rows, cols=cols, window=window, causal=True)
    return out @ p["wo"].astype(x.dtype), k, v


# ---------------------------------------------------------------------- mlp --

def init_mlp(key, d: int, f: int, act: str):
    ks = jax.random.split(key, 3)
    p = {"w_up": _dense_init(ks[0], (d, f)),
         "w_down": _dense_init(ks[1], (f, d))}
    if act == "silu":  # gated (SwiGLU)
        p["w_gate"] = _dense_init(ks[2], (d, f))
    return p


def mlp(x, p, act: str):
    up = x @ p["w_up"].astype(x.dtype)
    if act == "silu":
        gate = jax.nn.silu(x @ p["w_gate"].astype(x.dtype))
        h = gate * up
    else:
        h = jax.nn.gelu(up)
    return h @ p["w_down"].astype(x.dtype)


# ------------------------------------------------------------------ embedding --

def init_embedding(key, cfg: ArchConfig):
    # vocab padded to 128 (shards evenly over any mesh axis); padded logits
    # are masked in unembed so the extra rows are inert.
    p = {"embedding": _dense_init(key, (cfg.padded_vocab, cfg.d_model),
                                  scale=1.0 / math.sqrt(cfg.d_model))}
    if not cfg.tie_embeddings:
        p["lm_head"] = _dense_init(
            jax.random.fold_in(key, 1), (cfg.d_model, cfg.padded_vocab))
    return p


def embed(tokens, p, cfg: ArchConfig, dtype):
    x = jnp.take(p["embedding"], tokens, axis=0).astype(dtype)
    if cfg.embed_scale:
        x = x * jnp.asarray(math.sqrt(cfg.d_model), dtype)
    return x


def unembed(x, p, cfg: ArchConfig):
    if cfg.tie_embeddings:
        w = p["embedding"].T
    else:
        w = p["lm_head"]
    logits = shard_act(x @ w.astype(x.dtype), last_dim_model=True)
    if cfg.padded_vocab != cfg.vocab_size:
        valid = jnp.arange(cfg.padded_vocab) < cfg.vocab_size
        logits = jnp.where(valid, logits, -1e30)
        logits = shard_act(logits, last_dim_model=True)
    return logits


# --------------------------------------------------------------------- loss --

def lm_loss(logits, labels, mask=None):
    """Mean cross-entropy in f32. logits (B,S,V); labels (B,S) int32."""
    logits = logits.astype(jnp.float32)
    logz = jax.nn.logsumexp(logits, axis=-1)
    gold = jnp.take_along_axis(logits, labels[..., None], axis=-1)[..., 0]
    nll = logz - gold
    if mask is None:
        return nll.mean()
    mask = mask.astype(jnp.float32)
    return (nll * mask).sum() / jnp.maximum(mask.sum(), 1.0)
