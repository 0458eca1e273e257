"""The ``serve_mla_moe`` cell at smoke size on the CPU: the driver runs end
to end and is correct; a run with its timed path broken underneath comes
out as not correct, and so does the float8 control; the least bytes match
a hand count; the trace reduction's scopes and the readers.

Faults the cell can have: a token altered where it is produced, the
experts computed for no held share or for all of them (where the cell
holds 2 of 8), and a decode step that returns its latent cache unchanged.
It has no exchange between chips."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench.drivers import serve_mla_moe
from bench.lib import costs_mla_moe, harness, weights_mla_moe
from bench.refs import precision
from bench.tests import fixtures
from bench.tests.fixtures_mla_moe import TINY_MOE, TINY_MOE_SERVE

LIMITS = {"served_logit_gap": 1e-3, "served_logit_gap_mean": 1e-4}
FAULT_TRAFFIC = dict(TINY_MOE_SERVE, gen_len=12)


def _run(monkeypatch=None, traffic=TINY_MOE_SERVE, program=None,
         served=None, broken=None):
    """Run the cell at smoke size, with the program's configuration, the
    weights it serves or its decode step replaced."""
    from repro.models import model_zoo

    if program is not None:
        monkeypatch.setattr(serve_mla_moe, "program_config", program)
    if served is not None:
        monkeypatch.setattr(weights_mla_moe, "served", served)
    if broken is not None:
        build = model_zoo.build

        def patched(cfg, **kw):
            bundle = build(cfg, **kw)
            bundle.decode_fn = broken(bundle.decode_fn)
            return bundle

        monkeypatch.setattr(model_zoo, "build", patched)
    cell = fixtures.cell(TINY_MOE, traffic, LIMITS)
    return cell, serve_mla_moe.run(fixtures.context(cell))


def test_mla_moe_driver_runs_and_checks():
    _, out = _run()
    counters = out.record["counters"]
    assert counters["serve.tokens"] == counters["serve.batches"] * 4 * 4
    assert out.end_to_end["serve_tokens_per_s"] > 0
    assert [c.name for c in out.checks] == ["served_logit_gap",
                                            "served_logit_gap_mean"]
    assert all(c.ok for c in out.checks), out.checks


def _token_altered(monkeypatch):
    def broken(decode):
        def step(p, c, t, pos):
            logits, cache = decode(p, c, t, pos)
            return jnp.roll(logits, 1, axis=-1), cache
        return step
    return {"broken": broken}


def _latent_cache_unchanged(monkeypatch):
    def broken(decode):
        def step(p, c, t, pos):
            return decode(p, c, t, pos)[0], c
        return step
    return {"broken": broken}


def _no_held_share(monkeypatch):
    """The program holds experts no token is routed to: its expert part
    is zero."""
    program = serve_mla_moe.program_config
    return {"program": lambda cfg: dataclasses.replace(
        program(cfg), expert_offset=cfg["router_width"])}


def _all_experts(monkeypatch):
    """The program computes every expert, not the 2 the cell holds."""
    program, served = serve_mla_moe.program_config, weights_mla_moe.served
    whole = lambda cfg: dict(cfg, n_routed_experts=cfg["router_width"],
                             expert_offset=0)
    return {"program": lambda cfg: program(whole(cfg)),
            "served": lambda seed, cfg: served(seed, whole(cfg))}


def test_mla_moe_sound_run_is_correct(monkeypatch):
    cell, out = _run(monkeypatch, FAULT_TRAFFIC)
    assert harness.result(cell, False, out, {})["correct"]


@pytest.mark.parametrize("fault", [_token_altered, _no_held_share,
                                   _all_experts, _latent_cache_unchanged],
                         ids=["token_altered", "no_held_share",
                              "all_experts", "latent_cache_unchanged"])
def test_mla_moe_fault_is_not_correct(monkeypatch, fault):
    cell, out = _run(monkeypatch, FAULT_TRAFFIC, **fault(monkeypatch))
    assert not harness.result(cell, False, out, {})["correct"]


def test_mla_moe_control_is_not_correct():
    """The reference in float8 picks tokens whose reference logits lie
    below the best by more than the limits."""
    cfg, traffic, seed = TINY_MOE, FAULT_TRAFFIC, 11
    tokens = np.asarray(jax.random.randint(
        jax.random.key(0), (4, traffic["prompt_len"] + traffic["gen_len"]),
        0, cfg["vocab_size"]))
    ref = serve_mla_moe.reference_logits(tokens, cfg, traffic, seed)
    low = serve_mla_moe.reference_logits(tokens, cfg, traffic, seed,
                                         cast=precision.float8)
    control = serve_mla_moe.gaps(ref, jnp.argmax(low, -1))
    assert all(control[name] > limit for name, limit in LIMITS.items())


# hidden 4, 2 heads, latent 2, no-rope 1, rope 1, value 1, dense FFN 3,
# expert FFN 2, 2 of 4 experts held, 1 shared, 2 per token, a dense layer
# and an expert layer, vocabulary 5
HAND = {"hidden_size": 4, "num_attention_heads": 2, "kv_lora_rank": 2,
        "qk_nope_head_dim": 1, "qk_rope_head_dim": 1, "v_head_dim": 1,
        "intermediate_size": 3, "moe_intermediate_size": 2,
        "n_routed_experts": 2, "router_width": 4, "n_shared_experts": 1,
        "num_experts_per_tok": 2, "first_k_dense_replace": 1,
        "num_hidden_layers": 2, "vocab_size": 5}


def test_costs_mla_moe_match_a_hand_count():
    # a layer's attention over 3 positions, batch 1: the cache 3 * (2 + 1),
    # W_UK and W_UV 2 * 2 * (1 + 1), q in 2 * (1 + 1), o out 2 * 1
    assert costs_mla_moe.mla_decode(HAND, 1, 3).bytes == 2 * 2 * (9 + 8 + 4
                                                                  + 2)
    # the expert layer: 2 experts of 3 * 4 * 2, a row in and out of 4
    experts = costs_mla_moe.held_experts(HAND, 1)
    assert experts.bytes == 2 * (2 * 3 * 4 * 2 + 2 * 4)
    # expected rows 1 * 2 * 2 / 4 = 1, times 3 matrices of 4 * 2
    assert experts.flops == 2 * 1 * 3 * 4 * 2
    # dense FFN 36; shared 24, router 16, bias 4; norms 2 * 2 * 4, final
    # norm 4, head 20; attention per layer 16 + 12 + 2 + 8 + 8 = 46, less
    # W_UK and W_UV (8): 38 a layer; the embedding row 4
    weights = 36 + 24 + 16 + 4 + 16 + 4 + 20 + 2 * 38 + 4
    step = costs_mla_moe.decode_step(HAND, 1, 3)
    assert step.bytes == (2 * weights + 92 + experts.bytes
                          + 2 * 2 * 3  # a cache position per layer
                          + 2 * 5)  # the logits


def test_decode_scopes_put_the_grouped_matmul_under_the_experts():
    hlo = "\n".join([
        '  %fusion.1 = bf16[8] fusion(%a), metadata={op_name='
        '"jit(<lambda>)/while/body/repro.mla.decode/dot_general"}',
        '  %fusion.2 = f32[8] fusion(%b), metadata={op_name='
        '"jit(<lambda>)/while/body/repro.moe.route/logistic"}',
        '  %ragged-dot-none = bf16[8] custom-call(%c), '
        'custom_call_target="tpu_custom_call", '
        'metadata={op_name="ragged-dot-none"}',
        '  %fusion.3 = bf16[8] fusion(%d), metadata={op_name='
        '"jit(<lambda>)/while/body/rms_norm"}'])
    assert serve_mla_moe.decode_scopes(hlo) == {
        "fusion.1": "repro.mla.decode", "fusion.2": "repro.moe.route",
        "ragged-dot-none": "repro.moe.experts"}


RECORD = {
    "counters": {"serve.batches": 2, "serve.prefill_s": 3.0,
                 "serve.decode_steps": 510, "serve.decode_s": 5.1},
    "trace": {"window": {"busy_s": 3.0, "window_s": 4.0},
              "decode": {"steps": 255, "device_s": 2.0, "bytes_s": 1.0,
                         "scopes": {
                             "repro.mla.decode": {"device_s": 0.8,
                                                  "bytes_s": 0.4},
                             "repro.moe.experts": {"device_s": 0.5,
                                                   "bytes_s": 0.3}}}}}


@pytest.mark.parametrize("name,want", [
    ("moonlight.prefill_ms", 1500.0), ("moonlight.decode_step_ms", 10.0),
    ("moonlight.idle_share", 25.0), ("moonlight.decode_hbm_share", 50.0),
    ("moonlight.mla_hbm_share", 50.0),
    ("moonlight.experts_hbm_share", 60.0)])
def test_moonlight_readers(name, want):
    read = harness.metric_reader(name)
    assert read(RECORD) == pytest.approx(want)
    assert read({"counters": {}, "trace": {}}) is None
