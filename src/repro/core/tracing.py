"""The program's spans and counters: where the host spends its time.

Off by default. Off, :func:`span` hands out one shared no-op context
manager (no clock read, no allocation) and :func:`count` returns at once,
so instrumented code costs a flag check.

Recording is on inside :func:`enabled`, which yields the
:class:`Recorder` that collects, and while a JAX profiler session is
collecting host events (``jax.profiler.trace``): such a profile then
holds the program's spans beside the device's operations, and
:func:`profiled` keeps their totals for the process. Nothing is written
out; the caller reads :meth:`Recorder.summary`.

A span records its name, its ``time.perf_counter`` start and end, its
parent (the innermost span open on the same thread, so spans of a
measurement thread are roots of their own) and optional attributes, which
its children inherit (a batch id, say). Recorded, it also opens a
``jax.profiler.TraceAnnotation`` of the same name, so under a profiler it
lands on the trace's host plane, on the device events' clock. Every name
starts with ``repro.``.

This module does not import ``jax``: it uses it once something else has.
"""

from __future__ import annotations

import contextlib
import dataclasses
import sys
import threading
import time

_OFF = contextlib.nullcontext()


@dataclasses.dataclass(eq=False)
class SpanRecord:
    name: str
    parent: SpanRecord | None
    attrs: dict
    start: float = 0.0
    end: float = 0.0
    child_s: float = 0.0  # the time the span's children cover

    @property
    def seconds(self) -> float:
        return self.end - self.start

    @property
    def self_s(self) -> float:
        return self.seconds - self.child_s


class Recorder:
    """Finished spans, in the order they closed, and counters."""

    def __init__(self):
        self.spans: list[SpanRecord] = []
        self.counters: dict[str, float] = {}
        self._lock = threading.Lock()
        self._local = threading.local()

    def _stack(self) -> list[SpanRecord]:
        """The open spans of the calling thread, innermost last."""
        stack = getattr(self._local, "stack", None)
        if stack is None:
            stack = self._local.stack = []
        return stack

    def _add(self, name: str, n: float) -> None:
        with self._lock:
            self.counters[name] = self.counters.get(name, 0) + n

    def _finish(self, record: SpanRecord) -> None:
        with self._lock:
            self.spans.append(record)

    def summary(self) -> dict:
        """``{"spans": {name: {"count", "total_s", "self_s"}},
        "counters": {name: n}}``; a span's self time is its duration less
        what its children cover."""
        with self._lock:
            spans, counters = list(self.spans), dict(self.counters)
        out: dict[str, dict] = {}
        for r in spans:
            s = out.setdefault(r.name,
                               {"count": 0, "total_s": 0.0, "self_s": 0.0})
            s["count"] += 1
            s["total_s"] += r.seconds
            s["self_s"] += r.self_s
        return {"spans": out, "counters": counters}


_active: Recorder | None = None  # set inside enabled()
_PROFILED = Recorder()  # what was recorded while a profiler collected


def _profiling() -> bool:
    jax = sys.modules.get("jax")
    return jax is not None and jax.profiler.TraceAnnotation.is_enabled()


def _recorder() -> Recorder | None:
    if _active is not None:
        return _active
    return _PROFILED if _profiling() else None


class _Span:
    __slots__ = ("_rec", "_name", "_attrs", "_record", "_annotation")

    def __init__(self, rec: Recorder, name: str, attrs: dict):
        self._rec, self._name, self._attrs = rec, name, attrs
        self._annotation = None

    def __enter__(self) -> SpanRecord:
        stack = self._rec._stack()
        parent = stack[-1] if stack else None
        attrs = self._attrs
        if parent is not None and parent.attrs:
            attrs = {**parent.attrs, **attrs}
        self._record = SpanRecord(self._name, parent, attrs)
        jax = sys.modules.get("jax")
        if jax is not None:
            self._annotation = jax.profiler.TraceAnnotation(self._name)
            self._annotation.__enter__()
        stack.append(self._record)
        self._record.start = time.perf_counter()
        return self._record

    def __exit__(self, *exc) -> bool:
        record = self._record
        record.end = time.perf_counter()
        self._rec._stack().pop()
        if self._annotation is not None:
            self._annotation.__exit__(*exc)
        if record.parent is not None:
            record.parent.child_s += record.seconds
        self._rec._finish(record)
        return False


def span(name: str, **attrs):
    """A context manager timing the enclosed code as ``name``; the shared
    no-op while recording is off."""
    rec = _recorder()
    if rec is None:
        return _OFF
    return _Span(rec, name, attrs)


def count(name: str, n: float = 1) -> None:
    """Add ``n`` to the counter ``name`` while recording is on."""
    rec = _recorder()
    if rec is not None:
        rec._add(name, n)


@contextlib.contextmanager
def enabled():
    """Record into a fresh :class:`Recorder` for the enclosed code, in
    every thread; yields it."""
    global _active
    previous, _active = _active, Recorder()
    try:
        yield _active
    finally:
        _active = previous


def profiled() -> Recorder:
    """What this process recorded while a profiler collected and no
    :func:`enabled` recorder was open."""
    return _PROFILED
