"""Shared test runners for the tuner/session suites."""

import random
import time

from repro.core import AnalyticRunner


class SlowAnalytic:
    """Deterministic analytic latencies behind an artificial measurement
    delay — the container-scale stand-in for a target that takes seconds per
    batch. Each batch waits ``delay_s`` plus a seeded uniform draw in
    ``[0, jitter_s)``. ``overlap_capable`` so the tuner pipeline and sessions
    treat it like real hardware."""

    overlap_capable = True

    def __init__(self, hw, delay_s=0.01, jitter_s=0.0, seed=0):
        self.hw = hw
        self.delay_s = delay_s
        self.jitter_s = jitter_s
        self._rng = random.Random(seed)
        self.name = "slow-analytic"
        self._inner = AnalyticRunner(hw)

    def _wait(self):
        time.sleep(self.delay_s + self.jitter_s * self._rng.random())

    def run(self, workload, schedule):
        self._wait()
        return self._inner.run(workload, schedule)

    def run_batch(self, workload, schedules):
        self._wait()
        return self._inner.run_batch(workload, schedules)


class FailingAnalytic(SlowAnalytic):
    """A :class:`SlowAnalytic` whose ``fail_at``-th batch (1-based) raises,
    as a runner whose target fails mid-search would."""

    def __init__(self, hw, fail_at, delay_s=0.001):
        super().__init__(hw, delay_s)
        self.fail_at = fail_at
        self.batches = 0

    def run_batch(self, workload, schedules):
        self.batches += 1
        if self.batches == self.fail_at:
            raise RuntimeError(f"measurement failed on batch {self.batches}")
        return super().run_batch(workload, schedules)
