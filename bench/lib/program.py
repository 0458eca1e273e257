"""The program's own spans and counters (``repro/core/tracing.py``), for
the per-layer readers.

A traced run profiles one stretch of its window: the first tuning round,
or the first batch. While a profiler collects, the program records its
spans and counters, and keeps them for the process; the readers run in
that process after the window, and read them there. A program without the
recorder gives None, and the reader leaves its metric out.
"""

from __future__ import annotations

TRIALS = "repro.session.trials"
COMPILES = "repro.xla.compiles"


def summary() -> dict | None:
    """``{"spans": {name: {"count", "total_s", "self_s"}}, "counters":
    {name: n}}`` of what the program recorded while profiled, or None
    when it recorded nothing."""
    try:
        from repro.core import tracing
    except ImportError:
        return None
    s = tracing.profiled().summary()
    return s if s["spans"] else None


def per_trial_ms(names) -> float | None:
    """Milliseconds of the spans ``names`` (their self time) per tuning
    trial of the recorded round."""
    s = summary()
    if s is None or not s["counters"].get(TRIALS):
        return None
    spans = s["spans"]
    if not any(n in spans for n in names):
        return None
    return (1e3 * sum(spans[n]["self_s"] for n in names if n in spans)
            / s["counters"][TRIALS])


def per_span_ms(name: str) -> float | None:
    """Mean milliseconds of one span ``name``."""
    s = summary()
    span = s and s["spans"].get(name)
    if not span:
        return None
    return 1e3 * span["total_s"] / span["count"]
