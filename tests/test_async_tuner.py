"""Pipelined (async) tuner loop: bit-identical degradation to the
synchronous path, timing-independent deterministic replay while
speculating — for every kernel family and depth — real measure/search
overlap, and runner errors surfacing through every measurement path."""

import math
import threading

import pytest

from repro.core import (AnalyticRunner, InterpretRunner, TuningDatabase,
                        TuningSession, INTERPRET, V5E,
                        fixed_library_schedule, tune)
from repro.core import workload as W

from _test_runners import FailingAnalytic
from _test_runners import SlowAnalytic as _SlowAnalytic


def test_async_tune_bit_identical_to_sync_on_analytic():
    """Acceptance: the pipelined executor on an instantaneous runner clamps
    to depth 1 and must reproduce the synchronous trajectory exactly —
    same history, same order, same best — for a fixed seed."""
    wl = W.matmul(256, 1024, 512, "bfloat16")
    sync = tune(wl, V5E, AnalyticRunner(V5E), trials=24, seed=7)
    piped = tune(wl, V5E, AnalyticRunner(V5E), trials=24, seed=7,
                 pipeline_depth=4)
    assert piped.pipeline_depth == 1  # clamped: nothing to overlap
    assert piped.best_schedule == sync.best_schedule
    assert piped.best_latency == sync.best_latency
    assert piped.history == sync.history  # bit-identical, order included
    assert piped.overlap_s == 0.0


def test_async_tune_writes_same_database_records(tmp_path):
    db_sync = TuningDatabase(str(tmp_path / "sync.json"))
    db_async = TuningDatabase(str(tmp_path / "async.json"))
    wl = W.vmacc(256, 512)
    tune(wl, V5E, AnalyticRunner(V5E), trials=12, seed=0, database=db_sync)
    tune(wl, V5E, AnalyticRunner(V5E), trials=12, seed=0, database=db_async,
         pipeline_depth=3)
    assert db_sync.history(wl, V5E.name) == db_async.history(wl, V5E.name)


def test_speculative_pipeline_replays_deterministically():
    """Depth > 1 on a slow runner speculates against predicted latencies;
    reconciliation points are algorithmic, not timed, so two runs replay
    the identical history regardless of wall-clock jitter."""
    wl = W.matmul(512, 512, 512, "bfloat16")
    r1 = tune(wl, V5E, _SlowAnalytic(V5E, 0.01), trials=16, seed=3,
              pipeline_depth=3)
    r2 = tune(wl, V5E, _SlowAnalytic(V5E, 0.01), trials=16, seed=3,
              pipeline_depth=3)
    assert r1.pipeline_depth == 3
    assert r1.history == r2.history
    assert r1.best_schedule == r2.best_schedule
    assert r1.trials == 16


def test_speculative_pipeline_overlaps_and_stays_competitive():
    wl = W.matmul(512, 2048, 2048, "bfloat16")
    runner = _SlowAnalytic(V5E, 0.02)
    res = tune(wl, V5E, runner, trials=24, seed=0, pipeline_depth=3)
    # measurement time was really spent, and some of it was hidden behind
    # the evolution of the next generation
    assert res.measure_time_s > 0
    assert res.overlap_s > 0
    assert 0 < res.overlap_fraction <= 1
    # speculation must not wreck search quality: still beats the library
    fixed = AnalyticRunner(V5E).run(wl, fixed_library_schedule(wl, V5E))
    assert res.best_latency <= fixed
    assert math.isfinite(res.best_latency)


def test_sync_tune_reports_zero_overlap():
    wl = W.matmul(256, 256, 256, "bfloat16")
    res = tune(wl, V5E, AnalyticRunner(V5E), trials=12, seed=0)
    assert res.pipeline_depth == 1
    assert res.overlap_s == 0.0 and res.overlap_fraction == 0.0
    assert res.measure_time_s > 0


def test_warm_start_measured_first_in_pipelined_mode():
    wl = W.matmul(256, 512, 512, "bfloat16")
    seed_schedule = fixed_library_schedule(wl, V5E)
    res = tune(wl, V5E, _SlowAnalytic(V5E, 0.005), trials=8, seed=0,
               warm_start=[seed_schedule], pipeline_depth=2)
    assert res.warm_started == 1
    assert res.trials == 8
    assert res.history[0][0] == seed_schedule  # submission order preserved


# ------------------------------------------- timing-independent replay ----

FAMILY_WORKLOADS = [
    W.matmul(256, 512, 512, "bfloat16"),
    W.qmatmul(64, 512, 256),
    W.gemv(2048, 4096, "bfloat16"),
    W.vmacc(256, 512),
    W.attention(1, 4, 2, 256, 256, 64, "bfloat16"),
]


@pytest.mark.parametrize("depth", [1, 2, 3])
@pytest.mark.parametrize("wl", FAMILY_WORKLOADS, ids=lambda wl: wl.op)
def test_trajectory_identical_whatever_the_measurement_delay(wl, depth):
    """At equal effective depth a fixed seed's history is bit-identical
    whether measurement is instantaneous or takes jittered milliseconds:
    reconcile points are algorithmic, never timed."""
    runs = [tune(wl, V5E, _SlowAnalytic(V5E, delay, jitter, seed=depth),
                 trials=12, seed=5, batch=3, pipeline_depth=depth)
            for delay, jitter in ((0.0, 0.0), (0.001, 0.004))]
    assert [r.pipeline_depth for r in runs] == [depth, depth]
    assert runs[0].history == runs[1].history
    assert runs[0].best_schedule == runs[1].best_schedule
    assert runs[0].trials == runs[1].trials > 0


# ------------------------------------------------ a runner that raises ----

WL_FAIL = W.matmul(256, 512, 512, "bfloat16")


def _error_within(fn, timeout_s=30.0):
    """Run ``fn`` on a thread; fail the test if it has not returned or
    raised within ``timeout_s`` (a deadlock), else return what it raised."""
    box = {}

    def target():
        try:
            fn()
        except BaseException as e:  # the error under test
            box["error"] = e

    thread = threading.Thread(target=target, daemon=True)
    thread.start()
    thread.join(timeout_s)
    assert not thread.is_alive(), "a failing runner deadlocked the tuner"
    return box.get("error")


def _session(runner, ops, interleave):
    return TuningSession(V5E, runner, database=TuningDatabase(), batch=3,
                         interleave=interleave).tune_model(
        ops, total_trials=12, seed=0)


@pytest.mark.parametrize("case", ["tune-depth1", "tune-depth2",
                                  "tune-depth3", "interleaved-session",
                                  "baselines-wave"])
def test_runner_error_surfaces_without_deadlock(case):
    """A runner that raises mid-search: the error reaches the caller on
    the synchronous path, through the scheduler's tickets when pipelined or
    interleaved, and from the baselines wave; no measurement thread is
    left running."""
    if case.startswith("tune"):
        depth = int(case[-1])
        runner = FailingAnalytic(V5E, fail_at=2)
        error = _error_within(lambda: tune(
            WL_FAIL, V5E, runner, trials=12, seed=0, batch=3,
            pipeline_depth=depth))
    elif case == "interleaved-session":
        runner = FailingAnalytic(V5E, fail_at=3)
        error = _error_within(lambda: _session(
            runner, [(1, WL_FAIL), (1, W.vmacc(256, 512))], True))
    else:
        # one workload: searched synchronously, then its library baseline
        # is measured last, as the session's only scheduled batch
        counting = FailingAnalytic(V5E, fail_at=0)
        _session(counting, [(1, WL_FAIL)], None)
        runner = FailingAnalytic(V5E, fail_at=counting.batches)
        error = _error_within(lambda: _session(runner, [(1, WL_FAIL)],
                                               None))
    assert isinstance(error, RuntimeError)
    assert f"measurement failed on batch {runner.fail_at}" in str(error)
    assert not any(t.name == "measure-serial" and t.is_alive()
                   for t in threading.enumerate())


@pytest.mark.slow
def test_async_tune_interpret_overlap_end_to_end():
    """Real Pallas builds: the pipelined loop hides part of the measurement
    wall-time behind candidate evolution."""
    wl = W.matmul(8, 8, 8, "float32")
    runner = InterpretRunner(INTERPRET, repeats=1, warmup=0)
    res = tune(wl, INTERPRET, runner, trials=8, seed=0, pipeline_depth=2)
    assert math.isfinite(res.best_latency) and res.best_latency > 0
    assert res.overlap_fraction > 0
