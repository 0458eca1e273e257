"""Seeded weights of a DeepSeek-V3-family model (latent attention, a
leading dense layer, routed and shared experts), made on the device.

As in ``weights.py``, each layer's leaves come from a key of its own, so
the served stack (every layer at once, in one jitted call) and the
reference (one layer at a time) draw the same numbers. Weights are made
in float32, as master weights; the program's ``Server`` casts what it
reads in the compute dtype once. Only the experts this shard holds
(``n_routed_experts`` of them) are made; the router scores all
``router_width``. The tree is the layout the program's MoE model takes:
``dense_layers`` for the first ``first_k_dense_replace`` layers,
``layers`` for the expert layers.
"""

from __future__ import annotations

import functools

from bench.lib import seeds
from bench.lib.weights import _norm, _normal, top, top_key

_ATTN = ("wq", "wkv_a", "kv_norm", "wkv_b", "wo")
_FFN = ("w_up", "w_gate", "w_down")


def vocab_cfg(cfg: dict) -> dict:
    """The keys ``weights.top`` reads, under their names there."""
    return {"vocab_size": cfg["vocab_size"], "d_model": cfg["hidden_size"],
            "tie_embeddings": cfg["tie_word_embeddings"]}


def _attention(key, cfg: dict) -> dict:
    import jax

    d, h, c = (cfg["hidden_size"], cfg["num_attention_heads"],
               cfg["kv_lora_rank"])
    n, r, v = (cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"],
               cfg["v_head_dim"])
    k = dict(zip(_ATTN, jax.random.split(key, len(_ATTN))))
    return {"wq": _normal(k["wq"], (d, h * (n + r)), d),
            "wkv_a": _normal(k["wkv_a"], (d, c + r), d),
            "kv_norm": _norm(k["kv_norm"], c),
            "wkv_b": _normal(k["wkv_b"], (c, h * (n + v)), c),
            "wo": _normal(k["wo"], (h * v, d), h * v)}


def _ffn(key, d: int, f: int, lead=()) -> dict:
    import jax

    k = dict(zip(_FFN, jax.random.split(key, len(_FFN))))
    return {"w_up": _normal(k["w_up"], lead + (d, f), d),
            "w_gate": _normal(k["w_gate"], lead + (d, f), d),
            "w_down": _normal(k["w_down"], lead + (f, d), f)}


def layer(key, cfg: dict, dense: bool) -> dict:
    """One layer's weights from its key (jit- and vmap-able)."""
    import jax
    import jax.numpy as jnp

    d = cfg["hidden_size"]
    k = dict(zip(("ln1", "attn", "ln2", "ffn", "router", "bias", "shared"),
                 jax.random.split(key, 7)))
    out = {"ln1": _norm(k["ln1"], d), "attn": _attention(k["attn"], cfg),
           "ln2": _norm(k["ln2"], d)}
    if dense:
        out["mlp"] = _ffn(k["ffn"], d, cfg["intermediate_size"])
        return out
    e, f = cfg["router_width"], cfg["moe_intermediate_size"]
    out["router"] = _normal(k["router"], (d, e), d)
    # the correction bias, seeded and non-zero: it decides the selection
    # and must not reach the weights
    out["router_bias"] = 0.1 * jax.random.normal(k["bias"], (e,),
                                                 jnp.float32)
    out["experts"] = _ffn(k["ffn"], d, f, (cfg["n_routed_experts"],))
    out["shared"] = _ffn(k["shared"], d, cfg["n_shared_experts"] * f)
    return out


def layer_key(seed: int, i: int):
    return seeds.key(seed, seeds.WEIGHTS, 1, i)


def is_dense(cfg: dict, i: int) -> bool:
    return i < cfg["first_k_dense_replace"]


def layer_weights(seed: int, cfg: dict):
    """``i -> layer i's weights``, one jitted maker per kind of layer."""
    import jax

    makers = {dense: jax.jit(functools.partial(layer, cfg=cfg, dense=dense))
              for dense in (True, False)}
    return lambda i: makers[is_dense(cfg, i)](layer_key(seed, i))


def make_top(seed: int, cfg: dict) -> dict:
    """Embedding, output head and final norm."""
    import jax

    return jax.jit(functools.partial(top, cfg=vocab_cfg(cfg)))(top_key(seed))


def served(seed: int, cfg: dict) -> dict:
    """The whole model, the dense and the expert layers each stacked, made
    in one jitted call."""
    import jax
    import jax.numpy as jnp

    n = cfg["num_hidden_layers"]
    dense = [i for i in range(n) if is_dense(cfg, i)]
    moe = [i for i in range(n) if not is_dense(cfg, i)]
    keys = {name: jnp.stack([layer_key(seed, i) for i in ids])
            for name, ids in (("dense_layers", dense), ("layers", moe))
            if ids}

    @jax.jit
    def make(keys, tkey):
        out = top(tkey, vocab_cfg(cfg))
        for name, k in keys.items():
            out[name] = jax.vmap(functools.partial(
                layer, cfg=cfg, dense=name == "dense_layers"))(k)
        return out

    return make(keys, top_key(seed))
