"""Both drivers end to end at smoke size on the CPU, through ``run``: the
window, the pass or the batches, and the check against the references."""

import pytest

from bench.drivers import serve, tune
from bench.tests import fixtures


@pytest.fixture
def chip(monkeypatch):
    fixtures.fake_chip(monkeypatch)


def test_tune_driver_runs_and_checks(chip):
    c = fixtures.cell(fixtures.TINY_NET, fixtures.TINY_TUNE,
                      {"qmatmul_max_int_diff": 0, "net_calls_missing": 0})
    out = tune.run(fixtures.context(c))
    assert out.end_to_end["tune_trials_per_s"] > 0
    assert out.end_to_end["tuned_net_ms"] > 0
    assert out.attempted == out.record["counters"]["tune.trials"] > 0
    assert [ch.name for ch in out.checks] == ["qmatmul_max_int_diff"]
    assert all(ch.ok for ch in out.checks), out.checks


def test_serve_driver_runs_and_checks():
    c = fixtures.cell(fixtures.TINY_LM, fixtures.TINY_SERVE,
                      {"served_logit_gap": 1e-3})
    out = serve.run(fixtures.context(c))
    counters = out.record["counters"]
    assert counters["serve.tokens"] == counters["serve.batches"] * 4 * 4
    assert out.end_to_end["serve_tokens_per_s"] > 0
    assert out.checks[0].ok, out.checks
