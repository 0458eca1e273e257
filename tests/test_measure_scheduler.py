"""Measurement scheduler suite.

Covers the MeasureScheduler/SerialMeasureQueue subsystem (per-key FIFO,
span-accurate overlap accounting, the capacity hint), batched session
baselines, and the TuneDriver wall-time attribution fix (first propose ->
last reconcile)."""

import math
import time

from repro.core import (INTERPRET, AnalyticRunner, InterpretRunner,
                        MeasureScheduler, SerialMeasureQueue, TuningDatabase,
                        TuningSession, V5E)
from repro.core import tuner as tuner_lib
from repro.core import workload as W

from _test_runners import SlowAnalytic


WL_A = W.matmul(128, 128, 128, "bfloat16")
WL_B = W.vmacc(64, 256)
WL_C = W.matmul(256, 128, 128, "bfloat16")


def _schedules(wl, n, seed=0):
    from repro.core import TraceSampler, concretize, space_for

    space = space_for(wl, V5E)
    sampler = TraceSampler(seed)
    out, sigs = [], set()
    tries = 0
    while len(out) < n and tries < 500 * n:
        tries += 1
        s = sampler.sample(space)
        if concretize(wl, V5E, s).valid and s.signature() not in sigs:
            sigs.add(s.signature())
            out.append(s)
    assert len(out) == n
    return out


# ------------------------------------------------------- scheduler basics ----

def test_serial_queue_wraps_sync_runner_bit_identically():
    """The default adapter: any plain Runner gains the submission protocol
    with results identical to its own run_batch."""
    runner = AnalyticRunner(V5E)
    schedules = _schedules(WL_A, 6)
    q = SerialMeasureQueue(runner)
    try:
        t1 = q.submit_batch(WL_A, schedules[:3])
        t2 = q.submit_batch(WL_A, schedules[3:])
        assert t1.result() == runner.run_batch(WL_A, schedules[:3])
        assert t2.result() == runner.run_batch(WL_A, schedules[3:])
        assert t1.measure_s >= 0 and t1.interval() is not None
    finally:
        q.close()


def test_scheduler_preserves_per_key_fifo_order():
    """A key's batches come back in its own submission order even when the
    backend completes them out of order (slow first batch)."""
    sched = MeasureScheduler(SlowAnalytic(V5E, 0.005))
    try:
        a1 = _schedules(WL_A, 2)
        a2 = _schedules(WL_A, 2, seed=1)
        sched.submit("a", WL_A, a1)
        sched.submit("a", WL_A, a2)
        key1, batch1, lats1, _, _ = sched.collect_next()
        key2, batch2, lats2, _, _ = sched.collect_next()
        assert key1 == key2 == "a"
        assert [s.signature() for s in batch1] == [s.signature() for s in a1]
        assert [s.signature() for s in batch2] == [s.signature() for s in a2]
        assert lats1 == AnalyticRunner(V5E).run_batch(WL_A, a1)
    finally:
        sched.close()


def test_scheduler_overlap_is_span_accurate():
    """overlap + waited-measure <= measuring span (interval arithmetic,
    not summed totals), and a fully-waited depth-1 submit shows ~0 overlap."""
    sched = MeasureScheduler(SlowAnalytic(V5E, 0.02))
    try:
        sched.submit(0, WL_A, _schedules(WL_A, 2))
        sched.collect_next()  # immediate blocking wait: nothing overlapped
        span = sched.measure_span_s()
        assert span > 0
        assert sched.overlap_s() <= 0.005  # only submit->wait jitter
        # now overlap for real: work between submit and collect
        sched.submit(0, WL_A, _schedules(WL_A, 2, seed=1))
        time.sleep(0.015)  # "search work" while the batch measures
        sched.collect_next()
        assert sched.overlap_s() > 0.005
        assert sched.overlap_s() <= sched.measure_span_s() + 1e-9
    finally:
        sched.close()


def test_max_inflight_hints():
    """The runners' capacity hints, and the scheduler's own: one
    measurement thread, so capacity 1 whatever the runner declares."""
    assert AnalyticRunner(V5E).max_inflight == 1
    assert InterpretRunner(INTERPRET).max_inflight == 1
    assert not hasattr(SlowAnalytic(V5E), "max_inflight")
    for runner in (AnalyticRunner(V5E), InterpretRunner(INTERPRET),
                   SlowAnalytic(V5E)):
        sched = MeasureScheduler(runner)
        assert sched.max_inflight == 1
        sched.close()


# ------------------------------------------------------ batched baselines ----

class _RecordingRunner:
    """Overlap-capable runner that records every batch it measures, with
    analytic latencies."""

    overlap_capable = True
    name = "recording"

    def __init__(self, hw):
        self.hw = hw
        self._inner = AnalyticRunner(hw)
        self.batches: list[tuple[str, int]] = []

    def run(self, workload, schedule):
        return self._inner.run(workload, schedule)

    def run_batch(self, workload, schedules):
        self.batches.append((workload.key(), len(schedules)))
        return self._inner.run_batch(workload, schedules)


def test_session_baselines_submitted_as_one_wave():
    """The fixed-library baselines are measured as one wave after the
    search, in workload order, with per-workload attribution preserved."""
    runner = _RecordingRunner(V5E)
    ops = [(1, WL_A), (2, WL_B), (1, WL_C)]
    res = TuningSession(V5E, runner, database=TuningDatabase()).tune_model(
        ops, total_trials=12, seed=0)
    tail = runner.batches[-3:]  # the baseline wave comes last
    assert [n for _, n in tail] == [1, 1, 1]
    assert [k for k, _ in tail] == [WL_A.key(), WL_B.key(), WL_C.key()]
    for rep in res.reports:
        runner_fixed = AnalyticRunner(V5E).run(
            rep.workload, __import__(
                "repro.core.dispatch", fromlist=["fixed_library_schedule"]
            ).fixed_library_schedule(rep.workload, V5E))
        assert rep.fixed_latency == runner_fixed or not math.isfinite(
            runner_fixed)


# -------------------------------------------------- wall-time attribution ----

def test_driver_wall_time_excludes_construction_gap():
    """Regression (t_start double-set): a driver's wall time spans first
    propose -> last reconcile, not construction -> last reconcile."""
    runner = AnalyticRunner(V5E)
    driver = tuner_lib.TuneDriver(WL_B, V5E, runner, trials=6, seed=0)
    time.sleep(0.25)  # construction-to-start gap must not be attributed
    t0 = time.perf_counter()
    while (batch := driver.propose()) is not None:
        driver.reconcile(batch, runner.run_batch(WL_B, batch))
    active = time.perf_counter() - t0
    res = driver.finish()
    assert res.wall_time_s <= active + 0.05
    assert res.wall_time_s < 0.2  # far below the 0.25 s gap


def test_interleaved_drivers_attribute_only_their_own_span():
    """Interleaved attribution: drivers are constructed up front; each
    driver's wall time must stay within the session's driving span, not
    include the setup sleep."""
    runner = SlowAnalytic(V5E, 0.002)
    drivers = [
        tuner_lib.TuneDriver(wl, V5E, runner, trials=4, seed=i, batch=2)
        for i, wl in enumerate((WL_A, WL_B))]
    time.sleep(0.25)
    t0 = time.perf_counter()
    tuner_lib.run_scheduled(drivers, runner, depth=1)
    driving = time.perf_counter() - t0
    for d in drivers:
        res = d.finish()
        assert res.wall_time_s <= driving + 0.05


def test_never_driven_driver_reports_zero_wall_time():
    driver = tuner_lib.TuneDriver(WL_B, V5E, AnalyticRunner(V5E), trials=4)
    assert driver.finish().wall_time_s == 0.0


# ------------------------------------------------------- session summary ----

def test_session_summary_carries_span_and_queue_mode():
    ops = [(1, WL_A), (1, WL_B)]
    res = TuningSession(V5E, SlowAnalytic(V5E, 0.002),
                        database=TuningDatabase()).tune_model(
        ops, total_trials=8, seed=0)
    summary = res.summary()
    assert res.interleaved and summary["interleaved"] is True
    assert summary["measure_span_s"] > 0
    assert res.measure_span_s <= res.measure_time_s + 1e-9
