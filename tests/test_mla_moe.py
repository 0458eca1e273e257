"""Latent attention and the held-expert layer against the plain reference
(``bench/refs/mla_moe.py``), at a small size on the CPU in float32, on
seeded random weights: the served logits, the absorbed decode, the expert
shares, dropless routing, the router's weights, and the weights a server
keeps."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench.drivers import serve_mla_moe as driver
from bench.lib import weights_mla_moe
from bench.refs import mla_moe as ref
from bench.tests.fixtures_mla_moe import TINY_MOE
from repro.configs import get_config
from repro.core import tracing
from repro.models import layers as L
from repro.models import mla, moe
from repro.models.model_zoo import build
from repro.runtime.serve_loop import Server


def _close(a, b, tol=1e-4):
    a, b = np.asarray(a, np.float32), np.asarray(b, np.float32)
    scale = max(1.0, float(np.abs(b).max()))
    np.testing.assert_allclose(a, b, rtol=0, atol=tol * scale)


@pytest.mark.parametrize("offset,chunked", [(0, False), (6, False),
                                            (2, True)])
def test_server_prefill_and_decode_match_reference(offset, chunked,
                                                   monkeypatch):
    """Every served step's logits (the prefill's last position, then each
    decode step through the latent cache) against the reference's full
    forward pass over prompt plus served tokens; ``chunked`` prefills the
    batch in groups, attends and runs the expert layer in chunks, as a
    large batch does."""
    if chunked:
        monkeypatch.setattr(moe, "PREFILL_TOKENS", 16)  # 2 sequences
        monkeypatch.setattr(moe, "FFN_ROWS", 8)
        monkeypatch.setattr(mla, "PREFILL_ROWS", 8)  # 1 sequence
    cfg = dict(TINY_MOE, expert_offset=offset)
    seed, prompt, steps = 5, 8, 6
    server = Server(build(driver.program_config(cfg), remat="none"),
                    weights_mla_moe.served(seed, cfg),
                    max_len=prompt + steps + 1)
    prompts = np.random.default_rng(0).integers(
        0, cfg["vocab_size"], (3, prompt)).astype(np.int32)
    res = server.generate(prompts, steps)
    want = ref.forward(jnp.asarray(res.tokens[:, : prompt + steps - 1]),
                       weights_mla_moe.make_top(seed, cfg),
                       weights_mla_moe.layer_weights(seed, cfg), cfg,
                       positions=np.arange(prompt - 1, prompt + steps - 1))
    assert len(res.logits) == steps
    for i, got in enumerate(res.logits):
        _close(got[:, : cfg["vocab_size"]], want[:, i])


@pytest.mark.parametrize("prompt", [1, 5])
def test_absorbed_decode_matches_decompressed(prompt):
    """Decoding one position at a time over the latent cache (W_UK folded
    into the query, W_UV after the weighted sum) gives what the
    decompressed full-sequence attention gives at that position."""
    cfg = driver.program_config(TINY_MOE)
    p = mla.init(jax.random.key(1), cfg)
    b, s = 2, 9
    x = jax.random.normal(jax.random.key(2), (b, s, cfg.d_model))
    pos = jnp.broadcast_to(jnp.arange(s, dtype=jnp.int32), (b, s))
    full, _ = mla.prefill(x, p, cfg, pos)
    _, (ckv, kpe) = mla.prefill(x[:, :prompt], p, cfg, pos[:, :prompt])
    pad = ((0, 0), (0, s - prompt), (0, 0))
    ckv, kpe = jnp.pad(ckv, pad), jnp.pad(kpe, pad)
    for t in range(prompt, s):
        out, c, k = mla.decode(x[:, t:t + 1], p, cfg, ckv, kpe,
                               jnp.int32(t))
        ckv, kpe = ckv.at[:, t].set(c[:, 0]), kpe.at[:, t].set(k[:, 0])
        _close(out[:, 0], full[:, t], 1e-5)


def test_expert_shares_add_up_to_the_uncut_layer():
    """Eight shares of 16 experts, two each: their routed parts, with the
    shared expert counted once, add up to the reference's whole layer."""
    cfg = dict(TINY_MOE, router_width=16, n_routed_experts=16,
               expert_offset=0, num_experts_per_tok=4)
    w = weights_mla_moe.layer(jax.random.key(3), cfg, dense=False)
    h = jax.random.normal(jax.random.key(4), (24, cfg["hidden_size"]))
    whole = ref.routed(h, w, cfg) + ref.swiglu(h, w["shared"])

    parts = L.mlp(h, w["shared"], "silu")  # what every share computes alike
    for j in range(8):
        share = dataclasses.replace(driver.program_config(cfg),
                                    experts_held=2, expert_offset=2 * j)
        idx, weights = moe.route(h, w, share)
        mine = {k: a[2 * j: 2 * j + 2] for k, a in w["experts"].items()}
        parts = parts + moe.held_experts(h, idx, weights, mine, share)
    _close(parts, whole, 1e-5)


def test_routing_is_dropless_where_the_capacity_path_drops():
    """A bias that sends every token to one expert: the capacity path
    drops the tokens past its slots, the serving path drops none."""
    cfg = dict(TINY_MOE, router_width=4, n_routed_experts=4,
               expert_offset=0, num_experts_per_tok=2)
    pc = dataclasses.replace(driver.program_config(cfg), capacity_factor=1.0,
                             n_shared_experts=0)
    w = weights_mla_moe.layer(jax.random.key(5), cfg, dense=False)
    w["router_bias"] = w["router_bias"].at[0].set(10.0)
    del w["shared"]
    s = 64  # every token to expert 0, which has max(8, 64 * 2 / 4) slots
    h = jax.random.normal(jax.random.key(6), (1, s, cfg["hidden_size"]))
    whole = ref.routed(h, w, cfg)
    assert bool((ref.route(h, w, cfg)[0] == 0).any(-1).all())

    idx, weights = moe.route(h[0], w, pc)
    _close(moe.held_experts(h[0], idx, weights, w["experts"], pc),
           whole[0], 1e-5)
    capacity = moe.moe_ffn(h, w, pc)
    kept = np.abs(np.asarray(capacity - whole)).max(-1)[0] < 1e-4
    assert kept[:32].all() and not kept[32:].any()


@pytest.mark.parametrize("router", ["program", "reference"])
def test_router_selects_by_biased_and_weights_by_unbiased_scores(router):
    cfg = dict(TINY_MOE, router_width=6, num_experts_per_tok=2)
    logits = jnp.array([2.0, 1.9, 0.0, -1.0, 1.0, 0.5])
    d = cfg["hidden_size"]
    w = {"router": jnp.zeros((d, 6)).at[0].set(logits),
         "router_bias": jnp.array([0.0, 0.0, 3.0, 0.0, 0.0, 0.0])}
    x = jnp.zeros((1, d)).at[0, 0].set(1.0)
    if router == "program":
        idx, weights = moe.route(x, w, driver.program_config(cfg))
    else:
        idx, weights = ref.route(x, w, cfg)
    # by score + bias: expert 2 (0.5 + 3.0) and expert 0 (0.88)
    assert sorted(np.asarray(idx[0]).tolist()) == [0, 2]
    scores = jax.nn.sigmoid(logits)
    want = {int(e): float(scores[e] / (scores[0] + scores[2]) * 2.446)
            for e in (0, 2)}
    got = dict(zip(np.asarray(idx[0]).tolist(),
                   np.asarray(weights[0]).tolist()))
    assert got == pytest.approx(want, rel=1e-5)
    assert got[2] < got[0]  # biased scores would weight expert 2 above 0


@pytest.mark.parametrize("arch", ["moonlight_16b_a3b", "qwen2_moe_a2_7b"])
def test_serving_params_casts_moe_leaves_once(arch):
    cfg = dataclasses.replace(get_config(arch).reduced(), dtype="bfloat16")
    bundle = build(cfg, remat="none")
    params = bundle.init(jax.random.key(0))
    served = bundle.serving_params(params)
    flat = jax.tree_util.tree_flatten_with_path(params)[0]
    kept = set()
    for (path, orig), leaf in zip(flat, jax.tree.leaves(served)):
        name = tuple(k.key for k in path)
        if name in moe.SERVED_IN_COMPUTE_DTYPE:
            assert leaf.dtype == jnp.bfloat16, name
        else:  # the router, its bias and the norm scales
            assert leaf is orig and leaf.dtype == jnp.float32, name
            kept.add(name[-1])
    want = {"router", "ln1", "ln2", "final_norm"}
    if cfg.mla:
        want |= {"router_bias", "kv_norm"}
    assert kept == want
    assert any(n[:2] == ("layers", "experts") for n in
               moe.SERVED_IN_COMPUTE_DTYPE)
    again = bundle.serving_params(served)
    assert all(a is b for a, b in zip(jax.tree.leaves(again),
                                      jax.tree.leaves(served)))


def test_server_counts_experts_held_and_cache_bytes():
    cfg = TINY_MOE
    pc = driver.program_config(cfg)
    with tracing.enabled() as rec:
        server = Server(build(pc, remat="none"),
                        weights_mla_moe.served(1, cfg), max_len=12)
        server.generate(np.zeros((2, 8), np.int32), 2)
    counters = rec.summary()["counters"]
    assert counters["repro.moe.experts_held"] == 2
    latent = cfg["kv_lora_rank"] + cfg["qk_rope_head_dim"]
    t_alloc = mla.CACHE_ALIGN  # 12 positions, rounded up to the tile
    assert counters["repro.serve.cache_bytes"] == (
        cfg["num_hidden_layers"] * 2 * t_alloc * latent * 4)
