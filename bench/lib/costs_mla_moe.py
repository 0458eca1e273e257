"""Operations and least HBM bytes of a DeepSeek-V3-family decode step
(latent attention, a leading dense layer, routed and shared experts), from
shapes.

As in ``costs.py``: bytes at the compute dtype, each operand read once and
each result written once; a multiply-add is two operations. ``cfg`` holds
the configuration file's keys (the published config's names;
``n_routed_experts`` is the experts this chip holds, ``router_width`` the
experts the router scores).

The routed rows a held expert gets in a step are known only at run time.
Operations count the expected number, ``batch * num_experts_per_tok *
n_routed_experts / router_width``; bytes count what any routing reads: the
held experts' weights, and every token's row in and out.
"""

from __future__ import annotations

from bench.lib.costs import DTYPE_BYTES, Cost


def _sizes(cfg: dict):
    return (cfg["hidden_size"], cfg["num_attention_heads"],
            cfg["kv_lora_rank"], cfg["qk_nope_head_dim"],
            cfg["qk_rope_head_dim"], cfg["v_head_dim"])


def _layers(cfg: dict) -> tuple[int, int]:
    """(dense layers, expert layers)."""
    dense = cfg["first_k_dense_replace"]
    return dense, cfg["num_hidden_layers"] - dense


def attention_weights(cfg: dict) -> int:
    """Numbers in one layer's latent attention weights (``wq``, ``wkv_a``,
    ``kv_norm``, ``wkv_b``, ``wo``)."""
    d, h, c, n, r, v = _sizes(cfg)
    return d * h * (n + r) + d * (c + r) + c + c * h * (n + v) + h * v * d


def mla_decode(cfg: dict, batch: int, context: int,
               dtype: str = "bfloat16") -> Cost:
    """The absorbed attention of one decode step, every layer: the latent
    and rope cache read over ``context`` positions, ``W_UK`` and ``W_UV``
    read, the query (no-rope and rope parts) in and the heads' output
    out. Operations: the query's absorption, the scores over latent and
    rope key, the weighted sum of latents, and ``W_UV``."""
    _, h, c, n, r, v = _sizes(cfg)
    layers = cfg["num_hidden_layers"]
    wb = DTYPE_BYTES[dtype]
    per_layer = (batch * context * (c + r)  # the cache
                 + c * h * (n + v)  # W_UK and W_UV
                 + batch * h * (n + r) + batch * h * v)  # q in, o out
    macs = batch * h * (n * c + context * (c + r) + context * c + c * v)
    return Cost(2.0 * layers * macs, wb * layers * per_layer, dtype)


def held_experts(cfg: dict, batch: int, dtype: str = "bfloat16") -> Cost:
    """The held experts of one decode step, every expert layer: their
    weights read, each token's row in and out."""
    d, f = cfg["hidden_size"], cfg["moe_intermediate_size"]
    held = cfg["n_routed_experts"]
    _, moe = _layers(cfg)
    wb = DTYPE_BYTES[dtype]
    rows = (batch * cfg["num_experts_per_tok"] * held
            / cfg["router_width"])
    return Cost(2.0 * moe * rows * 3 * d * f,
                wb * moe * (held * 3 * d * f + 2 * batch * d), dtype)


def decode_step(cfg: dict, batch: int, context: int,
                dtype: str = "bfloat16") -> Cost:
    """One decode step: every weight read once (the embedding only at the
    batch's rows), the latent cache read over ``context`` positions and one
    position written, the logits written."""
    d, _, c, _, r, _ = _sizes(cfg)
    v, fd = cfg["vocab_size"], cfg["intermediate_size"]
    fs = cfg["n_shared_experts"] * cfg["moe_intermediate_size"]
    e = cfg["router_width"]
    dense, moe = _layers(cfg)
    layers = dense + moe
    wb = DTYPE_BYTES[dtype]
    attn = attention_weights(cfg)
    # every weight but the held experts' and the attention's, which the
    # two parts below count
    dense_weights = (dense * 3 * d * fd
                     + moe * (3 * d * fs + d * e + e)  # shared, router, bias
                     + layers * 2 * d + d + d * v)  # norms, head
    mla = mla_decode(cfg, batch, context, dtype)
    experts = held_experts(cfg, batch, dtype)
    # the attention projections beyond W_UK and W_UV, which mla counts
    proj = attn - c * cfg["num_attention_heads"] * (
        cfg["qk_nope_head_dim"] + cfg["v_head_dim"])
    nbytes = (wb * (dense_weights + layers * proj + batch * d)
              + mla.bytes + experts.bytes
              + wb * layers * batch * (c + r)  # one cache position written
              + wb * batch * v)  # logits
    flops = (2.0 * batch * (dense_weights + layers * proj)
             + mla.flops + experts.flops)
    return Cost(flops, nbytes, dtype)
