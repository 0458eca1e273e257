"""Mixture-of-Experts transformer (Qwen2-MoE, and the DeepSeek-V3 family
as Moonlight-16B-A3B).

A stack of ``cfg.n_dense_layers`` leading dense layers (a ``d_ff`` SwiGLU
FFN) and then expert layers; attention is standard GQA/MHA or, with
``cfg.kv_lora_rank``, multi-head latent attention (:mod:`repro.models.mla`).

The router (:func:`route`) is ``cfg.router``: ``"softmax"`` takes the
softmax over the top-k logits; ``"sigmoid"`` selects the top k of
``sigmoid(logit) + router_bias`` and weights each selected expert by its
unbiased score, normalised over the k and times ``cfg.routed_scaling``.
Router logits are taken in float32 at full matmul precision (a TPU's
default runs a float32 matmul as one bfloat16 pass), since near-ties in
the top k decide which experts a token gets.

An expert layer holds ``cfg.experts_held`` experts from
``cfg.expert_offset`` on (all of them when 0): its expert weights have that
many rows. It routes over every expert and computes only its own experts'
part of the result, as expert parallelism asks of one shard; shared
experts are added in full. The expert part has two paths:

- serving (:func:`prefill`, :func:`decode_step`) is dropless
  (:func:`held_experts`): the rows routed to the held experts are sorted
  by expert and go through one grouped matmul (``jax.lax.ragged_dot``) per
  projection, so every routed token is computed and no expert computes a
  row it was not given;
- training (:func:`forward`, the loss) keeps the capacity-bounded,
  sort-free dispatch (scatter into per-expert slot buffers,
  ``capacity_factor``), grouped by batch row, whose (B, E, cap, D)
  buffers shard cleanly (batch over data, experts over the ``model`` axis)
  and whose hand-written backward passes keep the batch sharding. It drops
  tokens beyond an expert's capacity, which a server may not.

Experts are padded up to a multiple of the EP axis when needed (60 -> 64
for qwen2-moe); padded experts are never routed to.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp

from repro.configs.base import ArchConfig
from repro.models import layers as L
from repro.models import mla
from repro.models import transformer as T

# The serving path runs the expert layer over this many tokens at a time,
# so the sorted rows and their hidden activations stay small at prefill,
# and prefills about PREFILL_TOKENS tokens (whole sequences) at a time.
FFN_ROWS = 8192
PREFILL_TOKENS = 16384


def padded_experts(cfg: ArchConfig, ep: int = 16) -> int:
    e = cfg.n_experts
    return ((e + ep - 1) // ep) * ep if e % ep else e


def _init_attention(key, cfg: ArchConfig):
    return mla.init(key, cfg) if cfg.mla else L.init_attention(key, cfg)


def _init_layer(key, cfg: ArchConfig):
    ka, kr, ke, ks, kb = jax.random.split(key, 5)
    d, fe = cfg.d_model, cfg.moe_d_ff
    e = padded_experts(cfg)
    held = cfg.experts_held or e
    scale = 1.0 / math.sqrt(d)

    def expert_mats(k):
        k1, k2, k3 = jax.random.split(k, 3)
        return {
            "w_gate": jax.random.normal(k1, (held, d, fe), jnp.float32)
                      * scale,
            "w_up": jax.random.normal(k2, (held, d, fe), jnp.float32) * scale,
            "w_down": jax.random.normal(k3, (held, fe, d), jnp.float32)
                      * (1.0 / math.sqrt(fe)),
        }

    p = {
        "ln1": L.init_norm(d),
        "attn": _init_attention(ka, cfg),
        "ln2": L.init_norm(d),
        "router": jax.random.normal(kr, (d, e), jnp.float32) * scale,
        "experts": expert_mats(ke),
    }
    if cfg.router == "sigmoid":
        # the selection-only correction bias; non-zero, so that selecting
        # by biased and weighting by unbiased scores differ
        p["router_bias"] = 0.1 * jax.random.normal(kb, (e,), jnp.float32)
    if cfg.n_shared_experts:
        p["shared"] = L.init_mlp(ks, d, cfg.n_shared_experts * cfg.moe_d_ff,
                                 "silu")
    return p


def _init_dense_layer(key, cfg: ArchConfig):
    ka, km = jax.random.split(key)
    return {"ln1": L.init_norm(cfg.d_model),
            "attn": _init_attention(ka, cfg),
            "ln2": L.init_norm(cfg.d_model),
            "mlp": L.init_mlp(km, cfg.d_model, cfg.d_ff, cfg.act)}


def init_params(key, cfg: ArchConfig):
    ke, kl, kd = jax.random.split(key, 3)
    n_moe = cfg.n_layers - cfg.n_dense_layers
    params = {
        **L.init_embedding(ke, cfg),
        "layers": jax.vmap(lambda k: _init_layer(k, cfg))(
            jax.random.split(kl, n_moe)),
        "final_norm": L.init_norm(cfg.d_model),
    }
    if cfg.n_dense_layers:
        params["dense_layers"] = jax.vmap(
            lambda k: _init_dense_layer(k, cfg))(
                jax.random.split(kd, cfg.n_dense_layers))
    return params


# The leaves the model reads only through ``.astype(x.dtype)``: the
# projections (attention, latent attention, dense and shared FFNs, the
# held experts), the embedding and the output head. The router, its bias
# and the norm scales are read in float32 and stay as they are.
_ATTN = ("wq", "wk", "wv", "wo", "wkv_a", "wkv_b")
_MLP = ("w_up", "w_gate", "w_down")
SERVED_IN_COMPUTE_DTYPE = frozenset(
    [("embedding",), ("lm_head",)]
    + [(stack, "attn", w) for stack in ("layers", "dense_layers")
       for w in _ATTN]
    + [("dense_layers", "mlp", w) for w in _MLP]
    + [("layers", group, w) for group in ("experts", "shared")
       for w in _MLP])


def serving_params(params, cfg: ArchConfig):
    """``params`` with the projection, expert, embedding and head leaves
    cast to ``cfg.dtype`` once (see :func:`transformer.serving_params`)."""
    return T.serving_params(params, cfg, SERVED_IN_COMPUTE_DTYPE)


# ------------------------------------------------------------------ router --

def route(x, lp, cfg: ArchConfig):
    """x (..., D) -> (expert ids (..., k) int32, weights (..., k) f32)."""
    with jax.named_scope("repro.moe.route"):
        logits = jnp.matmul(x.astype(jnp.float32),
                            lp["router"].astype(jnp.float32),
                            precision=jax.lax.Precision.HIGHEST)
        e = logits.shape[-1]
        pad = jnp.arange(e) >= cfg.n_experts  # padding: never routed to
        k = cfg.top_k
        if cfg.router == "sigmoid":
            scores = jax.nn.sigmoid(logits)
            biased = jnp.where(pad, -jnp.inf, scores + lp["router_bias"])
            _, idx = jax.lax.top_k(biased, k)
            w = jnp.take_along_axis(scores, idx, axis=-1)
            w = w / (w.sum(axis=-1, keepdims=True) + 1e-20)
            w = w * cfg.routed_scaling
        else:
            vals, idx = jax.lax.top_k(jnp.where(pad, -1e30, logits), k)
            w = jax.nn.softmax(vals, axis=-1)
    return idx.astype(jnp.int32), w


# ---------------------------------------------------- serving: held experts --

def held_experts(x, idx, w, we, cfg: ArchConfig):
    """The held experts' part of the routed result, dropless. x (N, D);
    idx, w (N, k) over all experts; ``we`` the held experts' weights.

    Assignments to held experts are sorted by expert (the others sort
    last) and the first ``N * min(k, held)`` of them, as many as can be
    held since a token's k experts are distinct, run through one grouped
    matmul per projection; each row comes back times its weight and is
    added to its token."""
    n, d = x.shape
    k = idx.shape[-1]
    held = we["w_gate"].shape[0]
    with jax.named_scope("repro.moe.experts"):
        local = idx - cfg.expert_offset
        mine = (local >= 0) & (local < held)
        group = jnp.where(mine, local, held).reshape(-1)
        order = jnp.argsort(group, stable=True)[: n * min(k, held)]
        sizes = jnp.zeros((held + 1,), jnp.int32).at[group].add(1)[:held]
        rows = order // k
        xs = x[rows]
        gate = jax.lax.ragged_dot(xs, we["w_gate"].astype(x.dtype), sizes)
        up = jax.lax.ragged_dot(xs, we["w_up"].astype(x.dtype), sizes)
        ys = jax.lax.ragged_dot(jax.nn.silu(gate) * up,
                                we["w_down"].astype(x.dtype), sizes)
        weight = jnp.where(mine, w, 0.0).reshape(-1)[order][:, None]
        # rows past the held ones have weight 0 (and no defined value)
        ys = jnp.where(weight > 0, ys.astype(jnp.float32) * weight, 0.0)
        out = jnp.zeros((n, d), jnp.float32).at[rows].add(ys)
    return out.astype(x.dtype)


def _moe_rows(h, lp, cfg: ArchConfig):
    """The expert layer's FFN over rows h (N, D): held routed experts plus
    shared experts."""
    idx, w = route(h, lp, cfg)
    y = held_experts(h, idx, w, lp["experts"], cfg)
    if cfg.n_shared_experts:
        with jax.named_scope("repro.moe.shared"):
            y = y + L.mlp(h, lp["shared"], "silu")
    return y


def _by_row_chunks(fn, h):
    """fn over the rows of h (B, S, D), FFN_ROWS tokens at a time."""
    b, s, d = h.shape
    n = b * s
    c = math.gcd(n, FFN_ROWS)
    out = jax.lax.map(fn, h.reshape(n // c, c, d))
    return out.reshape(b, s, d)


# ------------------------------------------------- training: capacity path --
# Scatter dispatch / gather combine as a custom_vjp pair. Reason: XLA's
# *transpose* of a batched scatter materializes element-wise u32 index masks
# (TB-scale at train_4k) and drops the batch sharding. Writing the backward
# passes explicitly — the bwd of dispatch is a gather at the same slots, the
# bwd of combine is a scatter-add — keeps both directions as ordinary
# primals with pinned shardings.


def _batched_scatter(slot, vals, n_slots, add=False):
    """vmapped 1-D scatter -> HLO scatter with operand batching dims, which
    GSPMD partitions along B (plain advanced indexing does not)."""
    d = vals.shape[-1]

    def one(idx_row, val_row):
        buf = jnp.zeros((n_slots + 1, d), val_row.dtype)
        if add:
            return buf.at[idx_row].add(val_row)
        return buf.at[idx_row].set(val_row)

    return jax.vmap(one)(slot, vals)[:, :n_slots]


@functools.partial(jax.custom_vjp, nondiff_argnums=(2,))
def _dispatch(x_rep, slot, n_slots):
    """(B, Sk, D) tokens -> (B, n_slots, D) expert slot buffer."""
    return L.shard_act(_batched_scatter(slot, x_rep, n_slots))


def _dispatch_fwd(x_rep, slot, n_slots):
    return _dispatch(x_rep, slot, n_slots), slot


def _dispatch_bwd(n_slots, slot, g):
    keep = (slot < n_slots)[..., None]
    idx = jnp.minimum(slot, n_slots - 1)[..., None]
    d_x = jnp.take_along_axis(g, idx, axis=1)
    return L.shard_act(jnp.where(keep, d_x, 0)), None


_dispatch.defvjp(_dispatch_fwd, _dispatch_bwd)


@functools.partial(jax.custom_vjp, nondiff_argnums=(2,))
def _combine(out_flat, slot, n_slots):
    """(B, n_slots, D) expert outputs -> (B, Sk, D) per-token outputs."""
    keep = (slot < n_slots)[..., None]
    idx = jnp.minimum(slot, n_slots - 1)[..., None]
    g = jnp.take_along_axis(out_flat, idx, axis=1)
    return L.shard_act(jnp.where(keep, g, 0))


def _combine_fwd(out_flat, slot, n_slots):
    return _combine(out_flat, slot, n_slots), slot


def _combine_bwd(n_slots, slot, g):
    keep = (slot < n_slots)[..., None]
    buf = _batched_scatter(slot, jnp.where(keep, g, 0), n_slots, add=True)
    return L.shard_act(buf), None


_combine.defvjp(_combine_fwd, _combine_bwd)


def moe_ffn(x, lp, cfg: ArchConfig):
    """x (B, S, D) -> (B, S, D): the held routed experts, capacity-bounded,
    plus shared experts (the training path).

    Dispatch is *grouped by batch row* (GShard-style groups = data shards):
    the capacity cumsum runs along S within each row, vectorized over the
    batch-sharded B dim — no cross-device token reordering, so dispatch
    buffers stay sharded (B over data, E over model/EP) and the only MoE
    collective is the expert einsum's reduce, inserted by GSPMD. An
    assignment beyond its expert's ``cap`` slots, or to an expert not
    held, is dropped."""
    b, s, d = x.shape
    we = lp["experts"]
    e = we["w_gate"].shape[0]
    k = cfg.top_k

    sel, gates = route(x, lp, cfg)                     # (B, S, k)
    gates = gates.astype(x.dtype)
    local = sel - cfg.expert_offset
    mine = ((local >= 0) & (local < e)).reshape(b, s * k)

    cap = max(8, int(math.ceil(s * k / e * cfg.capacity_factor)))
    flat_sel = jnp.where(mine, local.reshape(b, s * k), e)   # (B, S*k)
    # Sort-based position-in-expert (Megablocks-style): avoids the
    # (B, S*k, E) one-hot cumsum, which at train_4k scale is a TB-class
    # tensor. argsort is stable, so earlier tokens keep capacity priority —
    # identical keep-policy to the cumsum formulation.
    order = jnp.argsort(flat_sel, axis=1)              # (B, S*k)
    sorted_e = jnp.take_along_axis(flat_sel, order, axis=1)
    starts = jax.vmap(
        lambda se: jnp.searchsorted(se, jnp.arange(e + 1)))(sorted_e)
    pos_sorted = (jnp.arange(s * k)[None]
                  - jnp.take_along_axis(starts, sorted_e, axis=1))
    pos = jnp.zeros((b, s * k), jnp.int32).at[
        jnp.arange(b)[:, None], order].set(pos_sorted.astype(jnp.int32))
    keep = (pos < cap) & mine
    slot = jnp.where(keep, flat_sel * cap + pos, e * cap)   # (B, S*k)

    x_rep = L.shard_act(jnp.repeat(x, k, axis=1))      # (B, S*k, D)
    buf = _dispatch(x_rep, slot, e * cap)
    expert_in = L.shard_expert(buf.reshape(b, e, cap, d))

    gate_h = jax.nn.silu(jnp.einsum("becd,edf->becf", expert_in,
                                    we["w_gate"].astype(x.dtype)))
    up_h = jnp.einsum("becd,edf->becf", expert_in,
                      we["w_up"].astype(x.dtype))
    out = jnp.einsum("becf,efd->becd", L.shard_expert(gate_h * up_h),
                     we["w_down"].astype(x.dtype))

    out_flat = L.shard_expert(out).reshape(b, e * cap, d)
    gathered = _combine(out_flat, slot, e * cap)
    y = (gathered.reshape(b, s, k, d) * gates[..., None]).sum(axis=2)

    if cfg.n_shared_experts:
        y = y + L.mlp(x, lp["shared"], "silu")
    return y


# ------------------------------------------------------------------ stacks --

def _attend(h, attn, cfg: ArchConfig, positions):
    """Full-sequence attention: (out, cache entries {name: (B, S, ...)})."""
    if cfg.mla:
        out, (ckv, kpe) = mla.prefill(h, attn, cfg, positions)
        return out, {"ckv": ckv, "kpe": kpe}
    out, (k, v) = L.attention(h, attn, cfg, positions)
    return out, {"k": k, "v": v}


def _attend_decode(h, attn, cfg: ArchConfig, c, pos):
    """One-token attention over one layer's cache entries ``c``, read and
    not written: (out, the token's entries {name: (B, 1, ...)})."""
    if cfg.mla:
        out, ckv, kpe = mla.decode(h, attn, cfg, c["ckv"], c["kpe"], pos)
        return out, {"ckv": ckv, "kpe": kpe}
    out, k, v = L.attention_decode(h, attn, cfg, c["k"], c["v"], pos)
    return out, {"k": k, "v": v}


def _stacks(params, cfg: ArchConfig, dense_ffn, moe_ffn_):
    """[(stacked layer params, their FFN, index of their first layer)]."""
    out = []
    if cfg.n_dense_layers:
        out.append((params["dense_layers"], dense_ffn, 0))
    out.append((params["layers"], moe_ffn_, cfg.n_dense_layers))
    return out


def forward(params, tokens, cfg: ArchConfig, *, remat: str = "full"):
    """tokens (B, S) -> logits (B, S, V), through the capacity path."""
    dtype = jnp.dtype(cfg.dtype)
    x = L.embed(tokens, params, cfg, dtype)
    b, s = tokens.shape
    positions = jnp.broadcast_to(jnp.arange(s, dtype=jnp.int32), (b, s))
    dense_ffn = lambda h, lp: L.mlp(h, lp["mlp"], cfg.act)
    moe = lambda h, lp: moe_ffn(h, lp, cfg)

    for stack, ffn, _ in _stacks(params, cfg, dense_ffn, moe):
        def body(carry, lp, ffn=ffn):
            h = L.rms_norm(carry, lp["ln1"], cfg.norm_eps)
            x2 = carry + _attend(h, lp["attn"], cfg, positions)[0]
            h = L.rms_norm(x2, lp["ln2"], cfg.norm_eps)
            return L.shard_act(x2 + ffn(h, lp), seq_model=True), None

        if remat == "full":
            body = jax.checkpoint(
                body, policy=jax.checkpoint_policies.nothing_saveable)
        x, _ = jax.lax.scan(body, x, stack)
    x = L.rms_norm(x, params["final_norm"], cfg.norm_eps)
    return L.unembed(x, params, cfg)


def init_cache(cfg: ArchConfig, batch: int, max_len: int, dtype=None):
    if cfg.mla:
        return mla.init_cache(cfg, batch, max_len, dtype)
    return T.init_cache(cfg, batch, max_len, dtype)


def _serve_ffns(cfg: ArchConfig):
    dense = lambda h, lp: _by_row_chunks(
        lambda r: L.mlp(r, lp["mlp"], cfg.act), h)
    moe = lambda h, lp: _by_row_chunks(lambda r: _moe_rows(r, lp, cfg), h)
    return dense, moe


def decode_step(params, cache, tokens, pos, cfg: ArchConfig):
    """One-token decode through the dropless held-expert path. Each layer
    reads its slice of the stacked cache and returns its token's entries,
    which one update writes after the layers (as
    :func:`transformer.decode_step` does), so a donated cache is updated
    in place. Returns (logits (B, V), cache)."""
    dtype = jnp.dtype(cfg.dtype)
    x = L.embed(tokens, params, cfg, dtype)

    rows = []
    for stack, ffn, first in _stacks(params, cfg, *_serve_ffns(cfg)):
        def body(x_c, per_layer, ffn=ffn):
            lp, li = per_layer
            c = {name: L.cache_layer(a, li) for name, a in cache.items()}
            h = L.rms_norm(x_c, lp["ln1"], cfg.norm_eps)
            attn_out, new = _attend_decode(h, lp["attn"], cfg, c, pos)
            x2 = x_c + attn_out
            h = L.rms_norm(x2, lp["ln2"], cfg.norm_eps)
            return x2 + ffn(h, lp), new

        n = jax.tree.leaves(stack)[0].shape[0]
        ids = first + jnp.arange(n, dtype=jnp.int32)
        x, new = jax.lax.scan(body, x, (stack, ids))
        rows.append(new)
    cache = {name: L.write_rows(a, jnp.concatenate([r[name] for r in rows]),
                                pos) for name, a in cache.items()}
    x = L.rms_norm(x, params["final_norm"], cfg.norm_eps)
    return L.unembed(x, params, cfg)[:, 0], cache


def _prefill_rows(params, tokens, cfg: ArchConfig, max_len: int):
    """Prefill of one group of sequences: (logits, its cache)."""
    dtype = jnp.dtype(cfg.dtype)
    x = L.embed(tokens, params, cfg, dtype)
    b, s = tokens.shape
    positions = jnp.broadcast_to(jnp.arange(s, dtype=jnp.int32), (b, s))
    cache = init_cache(cfg, b, max_len, dtype)

    for stack, ffn, first in _stacks(params, cfg, *_serve_ffns(cfg)):
        def body(carry, per_layer, ffn=ffn):
            x_c, c_all = carry
            lp, li = per_layer
            h = L.rms_norm(x_c, lp["ln1"], cfg.norm_eps)
            attn_out, entries = _attend(h, lp["attn"], cfg, positions)
            c_all = {name: jax.lax.dynamic_update_slice(
                a, entries[name][None].astype(dtype),
                (li,) + (0,) * (a.ndim - 1)) for name, a in c_all.items()}
            x2 = x_c + attn_out
            h = L.rms_norm(x2, lp["ln2"], cfg.norm_eps)
            return (x2 + ffn(h, lp), c_all), None

        n = jax.tree.leaves(stack)[0].shape[0]
        ids = first + jnp.arange(n, dtype=jnp.int32)
        (x, cache), _ = jax.lax.scan(body, (x, cache), (stack, ids))
    x = L.rms_norm(x, params["final_norm"], cfg.norm_eps)
    return L.unembed(x, params, cfg), cache


def prefill(params, tokens, cfg: ArchConfig, max_len: int):
    """Forward through the dropless held-expert path, and the cache
    (MLA: the latent and rope key; else K and V) of every layer, padded to
    ``max_len`` positions. Returns (logits (B, S, V), cache).

    Groups of about PREFILL_TOKENS tokens run in turn, each writing its
    rows of the logits and the cache in place, so a large batch holds one
    group's activations at a time beside the outputs."""
    b, s = tokens.shape
    g = math.gcd(b, max(1, PREFILL_TOKENS // s))
    shapes = jax.eval_shape(
        lambda t: _prefill_rows(params, t, cfg, max_len), tokens[:g])

    def body(i, out):
        logits, cache = out
        rows = jax.lax.dynamic_slice_in_dim(tokens, i * g, g)
        part_logits, part = _prefill_rows(params, rows, cfg, max_len)
        logits = jax.lax.dynamic_update_slice_in_dim(logits, part_logits,
                                                     i * g, 0)
        cache = {name: jax.lax.dynamic_update_slice_in_dim(
            a, part[name], i * g, 1) for name, a in cache.items()}
        return logits, cache

    logits = jnp.zeros((b,) + shapes[0].shape[1:], shapes[0].dtype)
    cache = init_cache(cfg, b, max_len)
    return jax.lax.fori_loop(0, b // g, body, (logits, cache))
