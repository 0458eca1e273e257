"""The serve cell: a run with its timed path broken underneath comes out as
not correct, and so does the control: the reference one precision lower,
put in the program's place. Smoke size on the CPU, with the chip's look
skipped.

Faults the cell can have: a token altered where it is produced, half of
the batch left out, and a decode step that returns its state (the KV
cache) unchanged. It has no exchange between chips."""

import jax
import jax.numpy as jnp
import pytest

from bench.drivers import serve
from bench.lib import harness
from bench.refs import precision
from bench.tests import fixtures

SERVE_LIMITS = {"served_logit_gap": 1e-3}


def _correct(cell, outcome):
    return harness.result(cell, False, outcome, {})["correct"]


def _serve(monkeypatch, broken, traffic=fixtures.TINY_SERVE):
    """Run the serve cell at smoke size with the program's decode step
    wrapped by ``broken(decode_fn)``."""
    from repro.models import model_zoo

    build = model_zoo.build

    def patched(cfg, **kw):
        bundle = build(cfg, **kw)
        bundle.decode_fn = broken(bundle.decode_fn)
        return bundle

    monkeypatch.setattr(model_zoo, "build", patched)
    cell = fixtures.cell(fixtures.TINY_LM, traffic, SERVE_LIMITS)
    return _correct(cell, serve.run(fixtures.context(cell)))


def _token_altered(decode):
    def step(p, c, t, pos):
        logits, cache = decode(p, c, t, pos)
        return jnp.roll(logits, 1, axis=-1), cache
    return step


def _half_batch(decode):
    def step(p, c, t, pos):
        logits, cache = decode(p, c, t, pos)
        rows = jnp.arange(logits.shape[0])[:, None] < logits.shape[0] // 2
        return jnp.where(rows, logits, 0.0), cache
    return step


def _state_unchanged(decode):
    def step(p, c, t, pos):
        logits, _ = decode(p, c, t, pos)
        return logits, c
    return step


def test_serve_sound_run_is_correct(monkeypatch):
    assert _serve(monkeypatch, lambda d: d)


@pytest.mark.parametrize("broken", [_token_altered, _half_batch,
                                    _state_unchanged],
                         ids=["token_altered", "half_batch_left_out",
                              "state_unchanged"])
def test_serve_fault_is_not_correct(monkeypatch, broken):
    traffic = dict(fixtures.TINY_SERVE, gen_len=12)
    assert not _serve(monkeypatch, broken, traffic)


def test_serve_control_is_not_correct():
    """The reference in float8 picks tokens whose reference logit lies
    below the best by more than the limit."""
    import numpy as np

    cfg, traffic = fixtures.TINY_LM, dict(fixtures.TINY_SERVE, gen_len=12)
    seed = 11
    tokens = np.asarray(jax.random.randint(
        jax.random.key(0), (4, traffic["prompt_len"] + traffic["gen_len"]),
        0, cfg["vocab_size"]))
    ref = serve.reference_logits(tokens, cfg, traffic, seed)
    low = serve.reference_logits(tokens, cfg, traffic, seed,
                                 cast=precision.float8)
    gap = float(serve.logit_gaps(ref, jnp.argmax(low, -1)).max())
    assert gap > SERVE_LIMITS["served_logit_gap"]
