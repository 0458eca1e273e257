"""The plain references agree with the program at tiny sizes on the CPU,
and their controls (one precision lower) do not."""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench.drivers import serve
from bench.lib import weights
from bench.refs import attention, lm, precision, qmatmul
from bench.tests import fixtures


def _ints(key, shape, lo, hi, dtype):
    return jax.random.randint(key, shape, lo, hi, dtype)


def test_qmatmul_matches_the_program_exactly():
    from repro import kernels
    from repro.core import V5E, concretize, fixed_library_schedule
    from repro.core import qmatmul as program_qmatmul

    wl = program_qmatmul(32, 256, 128)
    ks = jax.random.split(jax.random.key(0), 3)
    x = _ints(ks[0], (32, 128), -20, 21, jnp.int8)
    w = _ints(ks[1], (128, 256), -20, 21, jnp.int8)
    b = _ints(ks[2], (256,), -1000, 1001, jnp.int32)
    fn = kernels.build(wl, concretize(wl, V5E, fixed_library_schedule(
        wl, V5E)), interpret=True)
    want = qmatmul.qmatmul(x, w, b, scale=0.01)
    assert jnp.array_equal(fn(x, w, b), want)
    # requantization is live: outputs spread over the int8 range unclipped
    assert 20 < int(jnp.max(jnp.abs(want.astype(jnp.int32)))) < 128
    low = qmatmul.qmatmul(x, w, b, scale=0.01, cast=precision.int4)
    assert int(jnp.max(jnp.abs(low.astype(jnp.int32)
                               - want.astype(jnp.int32)))) >= 2


def test_attention_matches_the_program():
    from repro import kernels
    from repro.core import V5E, concretize, fixed_library_schedule
    from repro.core import attention as program_attention

    wl = program_attention(1, 4, 2, 64, 64, 64, "float32")
    ks = jax.random.split(jax.random.key(1), 3)
    q = jax.random.normal(ks[0], (1, 4, 64, 64))
    k = jax.random.normal(ks[1], (1, 2, 64, 64))
    v = jax.random.normal(ks[2], (1, 2, 64, 64))
    fn = kernels.build(wl, concretize(wl, V5E, fixed_library_schedule(
        wl, V5E)), interpret=True)
    want = attention.attention(q, k, v)
    rel = lambda a: float(jnp.max(jnp.abs(a - want)) / jnp.max(jnp.abs(want)))
    assert rel(fn(q, k, v)) < 1e-5
    assert rel(attention.attention(q, k, v, cast=precision.bfloat16)) > 1e-4


def test_lm_reference_matches_the_program_forward():
    from repro.models.model_zoo import build

    cfg = fixtures.TINY_LM
    seed = 2 ** 40 + 3
    bundle = build(serve.program_config(cfg), remat="none")
    params = weights.served(seed, cfg)
    tokens = jnp.asarray(np.random.default_rng(0).integers(
        0, cfg["vocab_size"], (2, 12)), jnp.int32)
    with jax.default_matmul_precision("highest"):
        got = bundle.forward(params, {"tokens": tokens})
    make_layer = jax.jit(functools.partial(weights.layer, cfg=cfg))
    want = lm.forward(tokens, weights.top(weights.top_key(seed), cfg),
                      lambda i: make_layer(weights.layer_key(seed, i)), cfg,
                      positions=np.arange(12))
    scale = float(jnp.max(jnp.abs(want)))
    assert float(jnp.max(jnp.abs(got - want))) < 1e-4 * scale
    low = lm.forward(tokens, weights.top(weights.top_key(seed), cfg),
                     lambda i: make_layer(weights.layer_key(seed, i)), cfg,
                     positions=np.arange(12), cast=precision.float8)
    assert float(jnp.max(jnp.abs(low - want))) > 1e-2 * scale


def test_served_weights_are_the_reference_layers():
    cfg = fixtures.TINY_LM
    stacked = weights.served(5, cfg)
    # jitted, as the reference makes each layer
    one = jax.jit(functools.partial(weights.layer, cfg=cfg))(
        weights.layer_key(5, 1))
    for a, b in zip(jax.tree.leaves(one), jax.tree.leaves(
            jax.tree.map(lambda x: x[1], stacked["layers"]))):
        assert jnp.array_equal(a, b)


@pytest.mark.parametrize("seed", [5, 2 ** 40 + 5])
def test_large_seeds_keep_their_high_bits(seed):
    a = weights.layer(weights.layer_key(seed, 0), fixtures.TINY_LM)
    b = weights.layer(weights.layer_key(5 if seed > 5 else 2 ** 40 + 5, 0),
                      fixtures.TINY_LM)
    assert not jnp.array_equal(a["ln1"], b["ln1"])
