"""Multi-head latent attention (MLA, the DeepSeek-V2/V3 attention), without
query compression.

Per layer, with ``n``, ``r`` and ``v`` the no-rope, rope and value widths
of a head, ``C`` the latent width and ``H`` heads:

- ``wq`` (D, H*(n+r)): the query, split per head into ``q_nope`` and
  ``q_pe``;
- ``wkv_a`` (D, C+r): the latent ``c = rms_norm(x wkv_a[:, :C], kv_norm)``
  and one rope key ``k_pe = rope(x wkv_a[:, C:])`` that every head shares;
- ``wkv_b`` (C, H*(n+v)): per head, ``W_UK`` (C, n) and ``W_UV`` (C, v),
  which expand the latent into ``k_nope = c W_UK`` and ``v = c W_UV``;
- ``wo`` (H*v, D).

A head's score is ``(q_nope . k_nope + q_pe . k_pe) / sqrt(n + r)``. The
rope rotates the (first half, second half) pairs of the ``r`` rope dims,
as ``layers.apply_rope`` does everywhere in this repository; the published
code rotates interleaved pairs, which is a fixed permutation of the rope
columns of ``wq`` and ``wkv_a``.

Two forms give the same numbers. Prefill (:func:`prefill`) decompresses:
it expands the latent into per-head keys and values and attends as usual.
Decode (:func:`decode`) is absorbed: ``W_UK`` is folded into the query,
``q_lat = q_nope W_UK^T``, so scores are taken against the cached latent
(``C`` wide) plus the cached rope key (``r`` wide), and ``W_UV`` is applied
after the weighted sum. The cache is the latent and the rope key, ``C + r``
numbers a position a layer, not ``2 H`` head vectors.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from repro.configs.base import ArchConfig
from repro.models import layers as L

# Prefill attends a group of sequences at a time, about this many rows of
# queries, so the (group, H, S, S) score block stays a few hundred MB at
# the batch and prompt a server prefills.
PREFILL_ROWS = 8192

# The cache's positions are allocated in multiples of the TPU tile's 128
# lanes. At such a length the chip's default layouts of both leaves are the
# ones the decode step's attention reads (the rope key's positions lie
# along the lanes), so a step reads each layer's cache where it lies rather
# than through a copy to another layout. The positions past ``max_len``
# are never written and never visible.
CACHE_ALIGN = 128


def init(key, cfg: ArchConfig):
    d, h, c = cfg.d_model, cfg.n_heads, cfg.kv_lora_rank
    n, r, v = cfg.qk_nope_head_dim, cfg.qk_rope_head_dim, cfg.v_head_dim
    ks = jax.random.split(key, 4)
    return {
        "wq": L._dense_init(ks[0], (d, h * (n + r))),
        "wkv_a": L._dense_init(ks[1], (d, c + r)),
        "kv_norm": L.init_norm(c),
        "wkv_b": L._dense_init(ks[2], (c, h * (n + v))),
        "wo": L._dense_init(ks[3], (h * v, d)),
    }


def init_cache(cfg: ArchConfig, batch: int, max_len: int, dtype=None):
    """The latent ``ckv`` (L, B, T, C) and rope key ``kpe`` (L, B, T, r),
    with T ``max_len`` rounded up to a multiple of CACHE_ALIGN."""
    dtype = dtype or jnp.dtype(cfg.dtype)
    lead = (cfg.n_layers, batch, -(-max_len // CACHE_ALIGN) * CACHE_ALIGN)
    return {"ckv": jnp.zeros(lead + (cfg.kv_lora_rank,), dtype),
            "kpe": jnp.zeros(lead + (cfg.qk_rope_head_dim,), dtype)}


def _scale(cfg: ArchConfig) -> float:
    return 1.0 / math.sqrt(cfg.qk_nope_head_dim + cfg.qk_rope_head_dim)


def _query(x, p, cfg: ArchConfig, positions):
    """x (B, S, D) -> q_nope (B, S, H, n), q_pe (B, S, H, r) roped."""
    b, s, _ = x.shape
    n = cfg.qk_nope_head_dim
    q = (x @ p["wq"].astype(x.dtype)).reshape(b, s, cfg.n_heads, -1)
    return q[..., :n], L.apply_rope(q[..., n:], positions, cfg.rope_theta)


def _latent(x, p, cfg: ArchConfig, positions):
    """x (B, S, D) -> the normed latent (B, S, C) and the roped shared key
    (B, S, r)."""
    c = cfg.kv_lora_rank
    kv = x @ p["wkv_a"].astype(x.dtype)
    ckv = L.rms_norm(kv[..., :c], p["kv_norm"], cfg.norm_eps)
    kpe = L.apply_rope(kv[..., None, c:], positions, cfg.rope_theta)
    return ckv, kpe[:, :, 0]


def _up_weights(p, cfg: ArchConfig, dtype):
    """W_UK (C, H, n) and W_UV (C, H, v) out of ``wkv_b``."""
    n = cfg.qk_nope_head_dim
    w = p["wkv_b"].astype(dtype).reshape(cfg.kv_lora_rank, cfg.n_heads, -1)
    return w[..., :n], w[..., n:]


def _group(b: int, s: int) -> int:
    return math.gcd(b, max(1, PREFILL_ROWS // s))


def prefill(x, p, cfg: ArchConfig, positions):
    """Full-sequence causal MLA, decompressed. x (B, S, D); positions
    (B, S). Returns (out (B, S, D), (ckv (B, S, C), kpe (B, S, r))): the
    latent and rope key to cache."""
    b, s, _ = x.shape
    h, n, v = cfg.n_heads, cfg.qk_nope_head_dim, cfg.v_head_dim
    q_nope, q_pe = _query(x, p, cfg, positions)
    ckv, kpe = _latent(x, p, cfg, positions)
    w_uk, w_uv = _up_weights(p, cfg, x.dtype)
    causal = jnp.arange(s)[None, :] <= jnp.arange(s)[:, None]

    def attend(group):
        qn, qp, c, kp = group  # one group of sequences
        k_nope = jnp.einsum("gtc,chn->gthn", c, w_uk)
        vals = jnp.einsum("gtc,chv->gthv", c, w_uv)
        sc = (jnp.einsum("gshn,gthn->ghst", qn, k_nope,
                         preferred_element_type=jnp.float32)
              + jnp.einsum("gshr,gtr->ghst", qp, kp,
                           preferred_element_type=jnp.float32))
        sc = jnp.where(causal, sc * _scale(cfg), -1e30)
        w = jax.nn.softmax(sc, axis=-1).astype(x.dtype)
        return jnp.einsum("ghst,gthv->gshv", w, vals)

    g = _group(b, s)
    with jax.named_scope("repro.mla.prefill"):
        split = lambda a: a.reshape((b // g, g) + a.shape[1:])
        o = jax.lax.map(attend, (split(q_nope), split(q_pe), split(ckv),
                                 split(kpe)))
        o = o.reshape(b, s, h * v)
    return o @ p["wo"].astype(x.dtype), (ckv, kpe)


def decode(x, p, cfg: ArchConfig, ckv_cache, kpe_cache, pos):
    """One-token MLA, absorbed, over the latent cache. x (B, 1, D); caches
    (B, T, C) and (B, T, r), read and not written, attended with the
    token's own latent and rope key at ``pos`` as a write there would
    leave them (``layers.with_row``); pos () int32. Returns (out (B, 1, D),
    ckv (B, 1, C), kpe (B, 1, r)): the entries to write at ``pos``."""
    b = x.shape[0]
    h, v = cfg.n_heads, cfg.v_head_dim
    positions = jnp.full((b, 1), pos, jnp.int32)
    q_nope, q_pe = _query(x, p, cfg, positions)
    ckv, kpe = _latent(x, p, cfg, positions)
    c_use = L.with_row(ckv_cache, ckv, pos)
    k_use = L.with_row(kpe_cache, kpe, pos)
    with jax.named_scope("repro.mla.decode"):
        w_uk, w_uv = _up_weights(p, cfg, x.dtype)
        q_lat = jnp.einsum("bhn,chn->bhc", q_nope[:, 0], w_uk)
        sc = (jnp.einsum("bhc,btc->bht", q_lat, c_use.astype(x.dtype),
                         preferred_element_type=jnp.float32)
              + jnp.einsum("bhr,btr->bht", q_pe[:, 0],
                           k_use.astype(x.dtype),
                           preferred_element_type=jnp.float32))
        visible = jnp.arange(ckv_cache.shape[1]) <= pos
        sc = jnp.where(visible, sc * _scale(cfg), -1e30)
        w = jax.nn.softmax(sc, axis=-1).astype(x.dtype)
        o_lat = jnp.einsum("bht,btc->bhc", w, c_use.astype(x.dtype))
        o = jnp.einsum("bhc,chv->bhv", o_lat, w_uv).reshape(b, 1, h * v)
    return o @ p["wo"].astype(x.dtype), ckv, kpe
