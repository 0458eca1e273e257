"""The tune cell: a run with its timed path broken underneath comes out as
not correct, and so does the control: the reference one precision lower,
put in the program's place. Smoke size on the CPU, with the chip's look
skipped.

Faults the cell can have: an answer altered where it is produced, and
half of the rows left out. It has no state a step returns and no exchange
between chips."""

import functools

import jax
import jax.numpy as jnp
import pytest

from bench.drivers import tune
from bench.lib import harness
from bench.refs import precision
from bench.refs import qmatmul as ref_qmatmul
from bench.tests import fixtures

TUNE_LIMITS = {"qmatmul_max_int_diff": 0, "net_calls_missing": 0}


def _correct(cell, outcome):
    return harness.result(cell, False, outcome, {})["correct"]


def _tune(monkeypatch, broken):
    """Run the tune cell at smoke size with every built kernel wrapped by
    ``broken(workload, fn)``."""
    from repro import kernels

    fixtures.fake_chip(monkeypatch)
    build = kernels.build
    monkeypatch.setattr(kernels, "build",
                        lambda wl, p, **k: broken(wl, build(wl, p, **k)))
    cell = fixtures.cell(fixtures.TINY_NET, fixtures.TINY_TUNE, TUNE_LIMITS)
    return _correct(cell, tune.run(fixtures.context(cell)))


def _altered(wl, fn):
    return jax.jit(lambda *a: fn(*a).at[0, 0].add(1))


def _half_rows(wl, fn):
    def half(*a):
        out = fn(*a)
        rows = jnp.arange(out.shape[-2])[:, None] < out.shape[-2] // 2
        return jnp.where(rows, out, jnp.zeros_like(out))
    return jax.jit(half)


def _control(wl, fn):
    return jax.jit(functools.partial(ref_qmatmul.qmatmul, scale=0.01,
                                     cast=precision.int4))


def test_tune_sound_run_is_correct(monkeypatch):
    assert _tune(monkeypatch, lambda wl, fn: fn)


@pytest.mark.parametrize("broken", [_altered, _half_rows, _control],
                         ids=["answer_altered", "half_rows_left_out",
                              "control_one_precision_lower"])
def test_tune_fault_is_not_correct(monkeypatch, broken):
    assert not _tune(monkeypatch, broken)


def test_tune_control_fails_each_family(monkeypatch):
    """The control fails the int8 number of every family it computes."""
    import numpy as np

    from bench.lib import netops

    ops = netops.forward_ops(fixtures.TINY_NET, fixtures.TINY_TUNE)
    inputs = tune.make_inputs(ops, fixtures.TINY_TUNE, 3)
    want = tune.reference_outputs(ops, inputs, fixtures.TINY_NET, {})
    low = tune.reference_outputs(ops, inputs, fixtures.TINY_NET,
                                 tune.CONTROL)
    worst = tune.compare(ops, low, want)
    assert worst["qmatmul"] > TUNE_LIMITS["qmatmul_max_int_diff"]
    assert np.isfinite(list(worst.values())).all()


