"""Reduction of a JAX profiler trace (``*.xplane.pb``) to device metrics.

What is read, and where (the layout a TPU's trace has; see
``bench/tests/data``):

- each device plane (``/device:TPU:<n>``) has a line ``XLA Ops``, one
  event per operation run, named by its HLO instruction
  (``%name = type opcode(...)``), and a line ``XLA Modules``, one event
  per program run, named ``<jit name>(<fingerprint>)``;
- the host planes hold the benchmark's own spans
  (``jax.profiler.TraceAnnotation``), whose names start with ``bench.``.

Busy time is the union of a device's operation intervals; idle is the rest
of the window. Operations are attributed to the benchmark's named scopes
(``jax.named_scope``) through the compiled program's HLO text, which maps
each instruction name to its ``op_name`` path. Device and host times share
the trace's clock; they agree to about a millisecond.
"""

from __future__ import annotations

import dataclasses
import glob
import os
import re

SPAN_PREFIX = "bench."
_INSTR = re.compile(r"^%?(?P<name>[^\s=]+)\s*=")
_OPCODE = re.compile(r"\s(?P<op>[a-z][\w\-]*)\(")
_HLO_LINE = re.compile(
    r"^\s*(?:ROOT\s+)?%?(?P<name>[^\s=]+)\s*=.*?op_name=\"(?P<op>[^\"]*)\"")


@dataclasses.dataclass(frozen=True)
class Event:
    start_ns: float
    end_ns: float
    name: str

    @property
    def seconds(self) -> float:
        return (self.end_ns - self.start_ns) * 1e-9


@dataclasses.dataclass
class Trace:
    ops: dict[str, list[Event]]  # device plane -> leaf operations
    modules: dict[str, list[Event]]  # device plane -> program runs
    spans: list[Event]  # the benchmark's host spans

    def span(self, name: str) -> tuple[float, float]:
        """(start, end) in ns of the first host span called ``name``."""
        for ev in self.spans:
            if ev.name == name:
                return ev.start_ns, ev.end_ns
        raise KeyError(f"no host span {name!r} in the trace")


def profile(logdir: str):
    """A profiler session writing to ``logdir``: device and host events,
    without Python's function calls (they would dwarf the rest)."""
    import jax

    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    return jax.profiler.trace(logdir, profiler_options=opts)


def find_xplane(logdir: str) -> str:
    paths = sorted(glob.glob(os.path.join(logdir, "**", "*.xplane.pb"),
                             recursive=True))
    if not paths:
        raise FileNotFoundError(f"no *.xplane.pb under {logdir}")
    return paths[-1]


def _leaves(events: list[Event]) -> list[Event]:
    """Drop events that contain the event after them (a parent around its
    children), so no device time is counted twice."""
    events = sorted(events, key=lambda e: (e.start_ns, -e.end_ns))
    out = []
    for i, ev in enumerate(events):
        nxt = events[i + 1] if i + 1 < len(events) else None
        if (nxt is not None and nxt.start_ns < ev.end_ns
                and nxt.end_ns <= ev.end_ns):
            continue
        out.append(ev)
    return out


def load(path: str) -> Trace:
    from jax.profiler import ProfileData

    data = ProfileData.from_file(path)
    ops: dict[str, list[Event]] = {}
    modules: dict[str, list[Event]] = {}
    spans: list[Event] = []
    for plane in data.planes:
        if plane.name.startswith("/device:"):
            lines = {line.name: line for line in plane.lines}
            if "XLA Ops" not in lines:
                continue
            ops[plane.name] = _leaves([
                Event(ev.start_ns, ev.start_ns + ev.duration_ns, ev.name)
                for ev in lines["XLA Ops"].events])
            modules[plane.name] = sorted(
                (Event(ev.start_ns, ev.start_ns + ev.duration_ns, ev.name)
                 for ev in (lines["XLA Modules"].events
                            if "XLA Modules" in lines else ())),
                key=lambda e: e.start_ns)
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for ev in line.events:
                    if ev.name.startswith(SPAN_PREFIX):
                        spans.append(Event(ev.start_ns,
                                           ev.start_ns + ev.duration_ns,
                                           ev.name))
    spans.sort(key=lambda e: e.start_ns)
    return Trace(ops, modules, spans)


def _clipped(events, lo, hi):
    for ev in events:
        s, e = max(ev.start_ns, lo), min(ev.end_ns, hi)
        if e > s:
            yield s, e, ev


def _merged(events, lo, hi) -> list[tuple[float, float]]:
    out: list[list[float]] = []
    for s, e, _ in sorted(_clipped(events, lo, hi)):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def busy_s(trace: Trace, lo: float, hi: float) -> float:
    """Seconds in which an operation ran, averaged over the devices."""
    if not trace.ops:
        return 0.0
    per = [sum(e - s for s, e in _merged(evs, lo, hi)) * 1e-9
           for evs in trace.ops.values()]
    return sum(per) / len(per)


def _label(trace: Trace, t: float) -> str:
    """The innermost benchmark span around host time ``t``."""
    best = None
    for ev in trace.spans:
        if ev.start_ns <= t <= ev.end_ns and (
                best is None or ev.end_ns - ev.start_ns
                < best.end_ns - best.start_ns):
            best = ev
    return best.name if best is not None else "outside the benchmark's spans"


def idle_gaps(trace: Trace, lo: float, hi: float,
              top: int = 10) -> list[list]:
    """The longest idle gaps of the first device in [lo, hi], each labelled
    with what the host was doing (the innermost benchmark span around the
    gap's middle): ``[[label, seconds], ...]``, longest first."""
    if not trace.ops:
        return []
    busy = _merged(next(iter(trace.ops.values())), lo, hi)
    gaps, t = [], lo
    for s, e in busy:
        if s > t:
            gaps.append((t, s))
        t = max(t, e)
    if hi > t:
        gaps.append((t, hi))
    gaps.sort(key=lambda g: g[0] - g[1])
    return [[_label(trace, (s + e) / 2), (e - s) * 1e-9]
            for s, e in gaps[:top]]


def opcode(event_name: str) -> str:
    m = _OPCODE.search(event_name)
    return m.group("op") if m else event_name.split(" ", 1)[0]


def instruction(event_name: str) -> str:
    m = _INSTR.match(event_name)
    return m.group("name") if m else event_name


def instruction_scopes(hlo_text: str, prefix: str) -> dict[str, str]:
    """Instruction name -> the first element of its ``op_name`` path that
    starts with ``prefix`` (a named scope the benchmark set)."""
    out = {}
    for line in hlo_text.splitlines():
        m = _HLO_LINE.match(line)
        if not m:
            continue
        for part in m.group("op").split("/"):
            if part.startswith(prefix):
                out[m.group("name")] = part
                break
    return out


def module_runs(trace: Trace, lo: float, hi: float,
                prefix: str = "") -> dict[str, list[float]]:
    """Program name -> the device seconds of each of its runs that start in
    [lo, hi], for names starting with ``prefix`` (first device)."""
    out: dict[str, list[float]] = {}
    if not trace.modules:
        return out
    for ev in next(iter(trace.modules.values())):
        if lo <= ev.start_ns <= hi and ev.name.startswith(prefix):
            out.setdefault(ev.name, []).append(ev.seconds)
    return out


def _in_modules(trace: Trace, plane: str, lo: float, hi: float,
                prefix: str):
    runs = [(ev.start_ns, ev.end_ns) for ev in trace.modules.get(plane, ())
            if ev.name.startswith(prefix) and lo <= ev.start_ns <= hi]
    for s, e, ev in _clipped(trace.ops[plane], lo, hi):
        if any(rs <= s and e <= re_ for rs, re_ in runs):
            yield ev


def scope_events(trace: Trace, lo: float, hi: float,
                 scopes: dict[str, str],
                 module_prefix: str) -> dict[str, list[float]]:
    """Device seconds of each operation per named scope, over the runs of
    the programs named ``module_prefix...`` in [lo, hi] (first device)."""
    out: dict[str, list[float]] = {}
    if not trace.ops:
        return out
    plane = next(iter(trace.ops))
    for ev in _in_modules(trace, plane, lo, hi, module_prefix):
        scope = scopes.get(instruction(ev.name))
        if scope is not None:
            out.setdefault(scope, []).append(ev.seconds)
    return out


def top_ops(trace: Trace, lo: float, hi: float,
            top: int = 10) -> list[list]:
    """Device time by HLO opcode in [lo, hi] (first device), longest
    first: ``[[opcode, seconds], ...]``."""
    if not trace.ops:
        return []
    totals: dict[str, float] = {}
    for s, e, ev in _clipped(next(iter(trace.ops.values())), lo, hi):
        name = opcode(ev.name)
        totals[name] = totals.get(name, 0.0) + (e - s) * 1e-9
    ranked = sorted(totals.items(), key=lambda kv: -kv[1])
    return [[k, v] for k, v in ranked[:top]]
