"""Device-idle time put down to the program's spans.

The program's spans (``repro/core/tracing.py``, names starting with
``repro.``) land on a traced run's host plane, one line per thread, on
the device events' clock. This reads them from the same ``*.xplane.pb``
that ``bench/lib/trace.py`` reduces, and puts every idle moment of the
first device in a window down to the innermost program span around it
(the shortest, whatever its thread), or to ``OUTSIDE``. A span is a leaf
when no span of its thread lies inside it.
"""

from __future__ import annotations

import dataclasses

from bench.lib import trace

PREFIX = "repro."
OUTSIDE = "outside the program's spans"


@dataclasses.dataclass(frozen=True)
class HostSpan:
    start_ns: float
    end_ns: float
    name: str
    thread: str
    leaf: bool = True


def with_leaves(spans: list[HostSpan]) -> list[HostSpan]:
    """``spans`` sorted by start, each marked a leaf or not."""
    spans = sorted(spans, key=lambda s: (s.start_ns, -s.end_ns))
    out = []
    for i, s in enumerate(spans):
        leaf, j = True, i + 1
        # a thread's spans nest: one of them starting inside s is inside s
        while leaf and j < len(spans) and spans[j].start_ns < s.end_ns:
            leaf = spans[j].thread != s.thread
            j += 1
        out.append(dataclasses.replace(s, leaf=leaf))
    return out


def load(path: str) -> list[HostSpan]:
    """The program's spans on the host planes of the profile at ``path``."""
    from jax.profiler import ProfileData

    spans = []
    for plane in ProfileData.from_file(path).planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            thread = f"{plane.name}/{line.name}"
            spans.extend(HostSpan(ev.start_ns, ev.start_ns + ev.duration_ns,
                                  ev.name, thread)
                         for ev in line.events if ev.name.startswith(PREFIX))
    return with_leaves(spans)


def idle_intervals(tr: trace.Trace, lo: float,
                   hi: float) -> list[tuple[float, float]]:
    """The first device's idle intervals in [lo, hi], in ns."""
    if not tr.ops:
        return []
    out, t = [], lo
    for s, e in trace._merged(next(iter(tr.ops.values())), lo, hi):
        if s > t:
            out.append((t, s))
        t = max(t, e)
    if hi > t:
        out.append((t, hi))
    return out


def idle_by_span(spans: list[HostSpan], tr: trace.Trace, lo: float,
                 hi: float) -> dict:
    """``{"idle_s", "leaf_s", "by_span": {name: s}}``: the window's idle
    seconds, the part of them inside a leaf span, and their split by the
    innermost span around each idle moment (``OUTSIDE`` for none)."""
    # one sweep over every boundary: +1 opens, -1 closes, in time order
    events = []
    for s in spans:
        if s.end_ns > max(lo, s.start_ns) and s.start_ns < hi:
            events.append((s.start_ns, 1, s))
            events.append((s.end_ns, -1, s))
    for a, b in idle_intervals(tr, lo, hi):
        events.append((a, 1, None))
        events.append((b, -1, None))
    events.sort(key=lambda ev: (ev[0], ev[1]))
    by_span: dict[str, float] = {}
    leaf_ns = idle_ns = 0.0
    active: list[HostSpan] = []
    gaps_open, t_prev = 0, lo
    for t, step, span in events:
        if gaps_open and t > t_prev:
            inner = min(active, key=lambda s: s.end_ns - s.start_ns,
                        default=None)
            name = inner.name if inner is not None else OUTSIDE
            by_span[name] = by_span.get(name, 0.0) + (t - t_prev) * 1e-9
            idle_ns += t - t_prev
            if inner is not None and inner.leaf:
                leaf_ns += t - t_prev
        t_prev = max(t_prev, t)
        if span is None:
            gaps_open += step
        elif step > 0:
            active.append(span)
        else:
            active.remove(span)
    return {"idle_s": idle_ns * 1e-9, "leaf_s": leaf_ns * 1e-9,
            "by_span": dict(sorted(by_span.items(), key=lambda kv: -kv[1]))}

