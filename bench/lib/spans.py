"""Benchmark-side spans around the calls into each layer of the program.

Each span is a ``jax.profiler.TraceAnnotation`` (so a traced run sees it on
the host's timeline, beside the device's operations) and a host-clock
total, so untraced runs can read time per layer too.
"""

from __future__ import annotations

import contextlib
import time


class Spans:
    """Named host spans: total seconds and count per name."""

    def __init__(self):
        self.total_s: dict[str, float] = {}
        self.count: dict[str, int] = {}

    @contextlib.contextmanager
    def __call__(self, name: str):
        import jax

        t0 = time.perf_counter()
        try:
            with jax.profiler.TraceAnnotation(name):
                yield
        finally:
            self.total_s[name] = (self.total_s.get(name, 0.0)
                                  + time.perf_counter() - t0)
            self.count[name] = self.count.get(name, 0) + 1
