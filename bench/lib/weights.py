"""Seeded weights of a dense GQA language model, made on the device.

The served stack (every layer at once, in one jitted call) and the
reference (one layer at a time) draw the same numbers: each layer's leaves
come from a key of its own, so the reference never holds the whole model.
Weights are made in float32, the type in which the program keeps and
serves them. The tree is the layout the program's dense model takes.
"""

from __future__ import annotations

import math

from bench.lib import seeds

_LAYER_LEAVES = ("ln1", "wq", "wk", "wv", "wo", "ln2", "w_up", "w_gate",
                 "w_down")


def padded_vocab(cfg: dict) -> int:
    return -(-cfg["vocab_size"] // 128) * 128


def _normal(key, shape, fan_in):
    import jax
    import jax.numpy as jnp

    return jax.random.normal(key, shape, jnp.float32) / math.sqrt(fan_in)


def _norm(key, d):
    import jax
    import jax.numpy as jnp

    # scales near 1, not all 1, so that a norm applied without its scale
    # shows in the logits
    return 1.0 + 0.1 * jax.random.normal(key, (d,), jnp.float32)


def layer(key, cfg: dict) -> dict:
    """One layer's weights from its key (jit- and vmap-able)."""
    import jax

    d, f = cfg["d_model"], cfg["d_ff"]
    qd = cfg["n_heads"] * cfg["head_dim"]
    kvd = cfg["n_kv_heads"] * cfg["head_dim"]
    k = dict(zip(_LAYER_LEAVES, jax.random.split(key, len(_LAYER_LEAVES))))
    return {
        "ln1": _norm(k["ln1"], d),
        "attn": {"wq": _normal(k["wq"], (d, qd), d),
                 "wk": _normal(k["wk"], (d, kvd), d),
                 "wv": _normal(k["wv"], (d, kvd), d),
                 "wo": _normal(k["wo"], (qd, d), qd)},
        "ln2": _norm(k["ln2"], d),
        "mlp": {"w_up": _normal(k["w_up"], (d, f), d),
                "w_gate": _normal(k["w_gate"], (d, f), d),
                "w_down": _normal(k["w_down"], (f, d), f)},
    }


def top(key, cfg: dict) -> dict:
    """Embedding, output head and final norm."""
    import jax

    d, v = cfg["d_model"], padded_vocab(cfg)
    ke, kh, kn = jax.random.split(key, 3)
    out = {"embedding": _normal(ke, (v, d), d), "final_norm": _norm(kn, d)}
    if not cfg["tie_embeddings"]:
        out["lm_head"] = _normal(kh, (d, v), d)
    return out


def layer_key(seed: int, i: int):
    return seeds.key(seed, seeds.WEIGHTS, 1, i)


def top_key(seed: int):
    return seeds.key(seed, seeds.WEIGHTS, 0)


def served(seed: int, cfg: dict) -> dict:
    """The whole model, stacked by layer, made in one jitted call."""
    import jax
    import jax.numpy as jnp

    keys = jnp.stack([layer_key(seed, i) for i in range(cfg["n_layers"])])

    @jax.jit
    def make(keys, tkey):
        return {**top(tkey, cfg),
                "layers": jax.vmap(lambda k: layer(k, cfg))(keys)}

    return make(keys, top_key(seed))
