"""The program's span recorder (``core/tracing.py``), the compile counter
of ``runtime/compile_cache.py``, and the spans of the tuner and session."""

import gc
import threading
from types import SimpleNamespace

import pytest

from repro.core import (V5E, AnalyticRunner, TuningSession, matmul, tracing,
                        tune)


def _loop(n):
    for _ in range(n):
        with tracing.span("repro.test"):
            pass


def test_off_hands_out_the_shared_noop_and_records_nothing(monkeypatch):
    def clock():
        raise AssertionError("the clock was read")

    monkeypatch.setattr(tracing, "time", SimpleNamespace(perf_counter=clock))
    fresh = tracing.Recorder()
    monkeypatch.setattr(tracing, "_PROFILED", fresh)
    assert tracing.span("repro.a") is tracing.span("repro.b", batch=1)
    _loop(10)
    tracing.count("repro.c")
    gc.collect()
    gc.disable()
    try:
        before = gc.get_count()[0]
        _loop(1000)
        allocated = gc.get_count()[0] - before
    finally:
        gc.enable()
    assert allocated < 10  # recording on, the same loop makes 2000 objects
    assert fresh.summary() == {"spans": {}, "counters": {}}


def test_on_records_nesting_parents_self_time_and_counters():
    with tracing.enabled() as rec:
        with tracing.span("repro.outer", batch=3) as outer:
            with tracing.span("repro.inner") as inner:
                pass
            with tracing.span("repro.inner"):
                pass
        tracing.count("repro.things", 2)
        tracing.count("repro.things")
    assert tracing.span("repro.after") is tracing._OFF
    assert inner.parent is outer and outer.parent is None
    assert inner.attrs == {"batch": 3}  # children share the parent's
    assert [r.name for r in rec.spans] == ["repro.inner", "repro.inner",
                                           "repro.outer"]
    s = rec.summary()
    assert s["counters"] == {"repro.things": 3}
    assert s["spans"]["repro.inner"]["count"] == 2
    o = s["spans"]["repro.outer"]
    assert o["count"] == 1 and o["total_s"] == pytest.approx(outer.seconds)
    assert o["self_s"] == pytest.approx(
        outer.seconds - s["spans"]["repro.inner"]["total_s"])
    assert 0.0 <= o["self_s"] <= o["total_s"]


def test_a_span_closes_on_an_exception():
    with tracing.enabled() as rec:
        with pytest.raises(ValueError):
            with tracing.span("repro.outer"):
                with tracing.span("repro.fails"):
                    raise ValueError("no")
        with tracing.span("repro.next") as nxt:
            pass
    assert [r.name for r in rec.spans] == ["repro.fails", "repro.outer",
                                           "repro.next"]
    assert nxt.parent is None  # the stack was unwound
    assert all(r.end >= r.start > 0 for r in rec.spans)


def test_each_thread_has_a_stack_of_its_own():
    seen = {}

    def measure():
        with tracing.span("repro.thread") as r:
            seen["thread"] = r

    with tracing.enabled() as rec:
        with tracing.span("repro.main") as main:
            t = threading.Thread(target=measure)
            t.start()
            t.join()
    assert seen["thread"].parent is None  # a root, not main's child
    assert main.child_s == 0.0
    assert rec.summary()["spans"]["repro.thread"]["count"] == 1


def test_a_profiler_session_records_and_annotates(monkeypatch, tmp_path):
    import jax

    fresh = tracing.Recorder()
    monkeypatch.setattr(tracing, "_PROFILED", fresh)
    with jax.profiler.trace(str(tmp_path)):
        _loop(3)
    _loop(2)  # the profiler stopped: off again
    assert fresh.summary()["spans"]["repro.test"]["count"] == 3
    assert tracing.profiled() is fresh
    from jax.profiler import ProfileData

    path = next(tmp_path.rglob("*.xplane.pb"))
    names = {ev.name for plane in ProfileData.from_file(str(path)).planes
             if plane.name.startswith("/host:")
             for line in plane.lines for ev in line.events}
    assert "repro.test" in names  # on the host plane, beside the device's


def test_compile_counter_counts_a_new_program_once():
    import jax
    import jax.numpy as jnp

    from repro.runtime.compile_cache import compile_cache_stats

    x = jax.device_put(jnp.arange(8.0))
    before = compile_cache_stats()
    f = jax.jit(lambda a: a * 3.0 - 1.0)
    with tracing.enabled() as rec:
        f(x).block_until_ready()
    first = compile_cache_stats()
    f(x).block_until_ready()
    again = compile_cache_stats()
    assert first["compiles"] == before["compiles"] + 1
    assert first["compile_s"] > before["compile_s"]
    assert again["compiles"] == first["compiles"]
    assert rec.summary()["counters"] == {"repro.xla.compiles": 1}


def test_a_program_loaded_from_the_persistent_cache_is_no_compile():
    """JAX times a load from the persistent cache under the compile event
    too, after the load's own event in the same thread."""
    from repro.runtime import compile_cache as cc

    before = cc.compile_cache_stats()["compiles"]
    cc._on_duration(cc._LOAD_EVENT, 0.01)
    cc._on_duration(cc._COMPILE_EVENT, 0.02)  # the load
    assert cc.compile_cache_stats()["compiles"] == before
    cc._on_duration(cc._COMPILE_EVENT, 0.5)  # a compile
    assert cc.compile_cache_stats()["compiles"] == before + 1


def test_tune_history_is_the_same_with_the_recorder_on():
    wl = matmul(64, 128, 256, "bfloat16")
    off = tune(wl, V5E, AnalyticRunner(V5E), trials=16, seed=5, batch=4)
    with tracing.enabled() as rec:
        on = tune(wl, V5E, AnalyticRunner(V5E), trials=16, seed=5, batch=4)
    assert on.history == off.history
    spans = rec.summary()["spans"]
    batches = spans["repro.tuner.measure"]["count"]
    assert batches >= 1
    assert spans["repro.tuner.reconcile"]["count"] == batches
    assert spans["repro.tuner.propose"]["count"] == batches + 1  # then None


def test_session_spans_and_trial_count():
    ops = [(2, matmul(64, 128, 256, "bfloat16")),
           (1, matmul(64, 256, 128, "bfloat16"))]
    with tracing.enabled() as rec:
        res = TuningSession(V5E, AnalyticRunner(V5E)).tune_model(
            ops, total_trials=16, seed=1)
    s = rec.summary()
    assert s["counters"]["repro.session.trials"] == res.total_trials
    spans = s["spans"]
    assert spans["repro.session.tune_model"]["count"] == 1
    assert spans["repro.session.baselines"]["count"] == 1
    covered = sum(spans[n]["total_s"] for n in
                  ("repro.tuner.propose", "repro.tuner.measure",
                   "repro.tuner.reconcile", "repro.session.baselines"))
    assert covered <= spans["repro.session.tune_model"]["total_s"]
    assert spans["repro.session.tune_model"]["self_s"] == pytest.approx(
        spans["repro.session.tune_model"]["total_s"] - covered)
