"""The decode step updates its cache in place: one row a layer is written,
the server donates the cache to the compiled step, and the tokens are
what a step without donation gives."""

import dataclasses
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench.drivers import serve_mla_moe
from bench.lib import weights_mla_moe
from bench.tests.fixtures_mla_moe import TINY_MOE
from repro.core import tracing
from repro.launch.serve import serving_config
from repro.models import layers as L
from repro.models.model_zoo import build
from repro.runtime.serve_loop import Server


def _dense():
    cfg = serving_config("yi_6b", smoke=True)
    return cfg, build(cfg, remat="none").init(jax.random.key(0))


def _mla():
    return (serve_mla_moe.program_config(TINY_MOE),
            weights_mla_moe.served(1, TINY_MOE))


def _windowed():
    # every layer one static window of 16: the ring and window-sliced paths
    return dataclasses.replace(serving_config("h2o_danube_1_8b", smoke=True),
                               n_layers=2), None


SERVED = {"dense_gqa": _dense, "mla_latent": _mla}

# (config, knob turned on while the step is built, position written)
STEP_CASES = {
    "dense_gqa": (_dense, None, 11),
    "mla_latent": (_mla, None, 11),
    "ring": (_windowed, L.set_ring_kv, 37),  # wraps: slot 37 % 16
    "window_sliced": (_windowed, L.set_decode_window_slicing, 29),
}


@pytest.mark.parametrize("case", sorted(STEP_CASES))
def test_decode_step_writes_only_position_pos_of_every_layer(case):
    make, knob, pos = STEP_CASES[case]
    cfg, params = make()
    bundle = build(cfg, remat="none")
    if params is None:
        params = bundle.init(jax.random.key(0))
    if knob:
        knob(True)
    try:
        cache = bundle.init_cache(2, 48)
        keys = jax.random.split(jax.random.key(1), len(cache))
        cache = {name: jax.random.normal(k, a.shape, a.dtype)
                 for k, (name, a) in zip(keys, sorted(cache.items()))}
        tokens = jnp.array([[3], [5]], jnp.int32)
        _, new = jax.jit(bundle.decode_fn)(bundle.serving_params(params),
                                           cache, tokens, jnp.int32(pos))
    finally:
        if knob:
            knob(False)
    for name, old in cache.items():
        old, got = np.asarray(old), np.asarray(new[name])
        assert got.shape == old.shape and got.dtype == old.dtype
        slot = pos % old.shape[2]
        others = np.ones(old.shape[2], bool)
        others[slot] = False
        np.testing.assert_array_equal(got[:, :, others], old[:, :, others])
        # the token's row is written in every layer
        for layer in range(old.shape[0]):
            assert not np.array_equal(got[layer, :, slot],
                                      old[layer, :, slot]), (name, layer)


def _server(make):
    cfg, params = make()
    return Server(build(cfg, remat="none"), params, max_len=16)


@pytest.mark.parametrize("config", sorted(SERVED))
def test_donating_server_serves_the_tokens_of_a_plain_decode_loop(config):
    server = _server(SERVED[config])
    bundle = server.bundle
    prompts = np.random.default_rng(2).integers(
        0, bundle.cfg.vocab_size, (3, 8)).astype(np.int32)
    with tracing.enabled() as rec:
        res = server.generate(prompts, 6)
    counters = rec.summary()["counters"]
    assert counters["repro.serve.cache_donated_bytes"] > 0
    assert (counters["repro.serve.cache_donated_bytes"]
            == counters["repro.serve.cache_bytes"])

    decode = jax.jit(bundle.decode_fn)  # nothing donated
    logits, cache = server._prefill(server.params,
                                    {"tokens": jnp.asarray(prompts)})
    tok = jnp.argmax(logits[:, -1], axis=-1).astype(jnp.int32)
    want = [tok]
    for i in range(5):
        logits, cache = decode(server.params, cache, tok[:, None],
                               jnp.int32(8 + i))
        tok = jnp.argmax(logits, axis=-1).astype(jnp.int32)
        want.append(tok)
    np.testing.assert_array_equal(res.tokens[:, 8:], np.stack(want, 1))


@pytest.mark.parametrize("config", sorted(SERVED))
def test_compiled_decode_program_aliases_every_cache_leaf(config):
    server = _server(SERVED[config])
    cache = jax.eval_shape(lambda: server.bundle.init_cache(3, 16))
    n_params = len(jax.tree.leaves(server.params))
    n_cache = len(jax.tree.leaves(cache))
    text = server._decode.lower(
        server.params, cache, jax.ShapeDtypeStruct((3, 1), jnp.int32),
        jax.ShapeDtypeStruct((), jnp.int32)).compile().as_text()
    # the module's first line: input_output_alias={ {1}: (12, {}, ...), ...
    header = text.splitlines()[0]
    params = sorted(int(p) for p in re.findall(r"\}: \((\d+), ", header))
    assert params == list(range(n_params, n_params + n_cache))


def test_a_step_without_donation_leaves_the_prefill_cache_and_counts_zero():
    """The counter reads what was consumed: a step that copies its cache
    leaves the prefill's cache alive and counts nothing."""
    server = _server(_dense)
    bundle = server.bundle
    server._decode = jax.jit(bundle.decode_fn)
    with tracing.enabled() as rec:
        server.generate(np.zeros((2, 8), np.int32), 3)
    counters = rec.summary()["counters"]
    assert counters["repro.serve.cache_donated_bytes"] == 0
    assert counters["repro.serve.cache_bytes"] > 0
