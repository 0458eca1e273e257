"""The readers of the program's own spans and counters, and the idle
attribution to them, on hand-made records and events."""

import pathlib

import pytest

from bench.lib import attribution, harness, program
from bench.lib.trace import Event, Trace

MS = 1e6  # ns


def _span(total_s, count=1, self_s=None):
    return count, total_s, total_s if self_s is None else self_s


TUNE = {
    "spans": {
        "repro.session.tune_model": _span(10.0, self_s=0.1),
        "repro.session.baselines": _span(1.0, self_s=1.0),
        "repro.tuner.propose": _span(0.2, count=6),
        "repro.tuner.measure": _span(8.0, count=5, self_s=0.02),
        "repro.tuner.reconcile": _span(0.08, count=5),
        "repro.runner.run": _span(8.5, count=25, self_s=0.05),
        "repro.runner.inputs": _span(0.3, count=5),
        "repro.runner.reference": _span(0.5, count=5),
        "repro.runner.compile": _span(6.0, count=20),
        "repro.runner.check": _span(0.2, count=20),
        "repro.runner.time": _span(1.45, count=25),
    },
    "counters": {"repro.session.trials": 20, "repro.xla.compiles": 90,
                 "repro.runner.reused": 5},
}
SERVE = {
    "spans": {
        "repro.serve.generate": _span(3.4, self_s=0.01),
        "repro.serve.prefill": _span(0.19),
        "repro.serve.decode_dispatch": _span(0.381, count=127),
        "repro.serve.token_fetch": _span(2.794, count=127),
    },
    "counters": {},
}
EXPECTED = {
    "tune.tuner_ms_per_trial": (TUNE, 1e3 * (0.1 + 0.2 + 0.02 + 0.08) / 20),
    "tune.compile_ms_per_trial": (TUNE, 1e3 * 6.0 / 20),
    "tune.check_ms_per_trial": (TUNE, 1e3 * (0.3 + 0.5 + 0.2) / 20),
    "tune.timing_ms_per_trial": (TUNE, 1e3 * 1.45 / 20),
    "tune.compiles_per_trial": (TUNE, 90 / 20),
    "serve.dispatch_ms_per_step": (SERVE, 3.0),
    "serve.fetch_ms_per_step": (SERVE, 22.0),
    "serve.window_compiles": (SERVE, 0),
}
RECORD = {"counters": {}, "spans": {}, "trace": {}}


@pytest.fixture
def profiled(monkeypatch):
    """Installs, as what the program recorded while profiled, a recorder
    holding the spans and counters of one of the records above."""
    from repro.core import tracing

    def install(record):
        rec = tracing.Recorder()
        for name, (count, total_s, self_s) in record["spans"].items():
            rec.spans.extend(
                tracing.SpanRecord(name, None, {}, 0.0, total_s / count,
                                   (total_s - self_s) / count)
                for _ in range(count))
        rec.counters.update(record["counters"])
        monkeypatch.setattr(tracing, "_PROFILED", rec)

    return install


@pytest.mark.parametrize("name", sorted(EXPECTED))
def test_reader_on_a_synthetic_record(name, profiled):
    record, want = EXPECTED[name]
    read = harness.metric_reader(name)
    profiled(record)
    assert read(RECORD) == pytest.approx(want)
    # the other cell's record, and a run whose program recorded nothing
    profiled(SERVE if record is TUNE else TUNE)
    assert read(RECORD) is None
    profiled({"spans": {}, "counters": {}})
    assert read(RECORD) is None
    assert program.summary() is None


def test_readers_read_what_the_program_recorded_while_profiled(
        monkeypatch, tmp_path):
    import jax

    from repro.core import tracing

    monkeypatch.setattr(tracing, "_PROFILED", tracing.Recorder())
    with jax.profiler.trace(str(tmp_path)):
        with tracing.span("repro.serve.generate"):
            for _ in range(4):
                with tracing.span("repro.serve.token_fetch"):
                    pass
    assert program.summary()["spans"]["repro.serve.token_fetch"][
        "count"] == 4
    assert harness.metric_reader("serve.fetch_ms_per_step")(RECORD) >= 0.0
    assert harness.metric_reader("serve.window_compiles")(RECORD) == 0


def test_new_metrics_are_in_the_benchmark_with_their_cells():
    bench = harness.read_json(harness.BENCH.parent / "BENCHMARK.json")
    entries = {m["name"]: m for m in bench["per_layer"]}
    for name, (rec, _) in EXPECTED.items():
        cell = ("mobilellm-tune-proj-seq64" if rec is TUNE
                else "yi6b-serve-decode")
        assert entries[name]["workloads"] == [cell]
        assert entries[name]["source"] in ("program_span", "program_counter")


def _spans():
    """Main thread: a session [0, 20] around a compile [2, 9] and a check
    [9, 12]; a measuring thread: a timed call [14, 18]."""
    main, other = "/host:CPU/main", "/host:CPU/measure"
    return attribution.with_leaves([
        attribution.HostSpan(0, 20 * MS, "repro.session.tune_model", main),
        attribution.HostSpan(2 * MS, 9 * MS, "repro.runner.compile", main),
        attribution.HostSpan(9 * MS, 12 * MS, "repro.runner.check", main),
        attribution.HostSpan(14 * MS, 18 * MS, "repro.runner.time", other),
    ])


def test_leaves_are_spans_with_nothing_of_their_thread_inside():
    leaves = {s.name: s.leaf for s in _spans()}
    assert leaves == {"repro.session.tune_model": False,
                      "repro.runner.compile": True,
                      "repro.runner.check": True,
                      "repro.runner.time": True}


def test_idle_time_goes_to_the_innermost_span_around_it():
    # busy [1, 3], [10, 11], [15, 16]; the window [-2, 22]
    ops = [Event(1 * MS, 3 * MS, "%a = f32[] add()"),
           Event(10 * MS, 11 * MS, "%b = f32[] add()"),
           Event(15 * MS, 16 * MS, "%c = f32[] add()")]
    tr = Trace({"/device:TPU:0": ops}, {}, [])
    out = attribution.idle_by_span(_spans(), tr, -2 * MS, 22 * MS)
    # idle: [-2, 1] (2 outside, 1 session), [3, 10] (6 compile, 1 check),
    # [11, 15] (1 check, 2 session, 1 time), [16, 22] (2 time, 2 session,
    # 2 outside)
    assert out["idle_s"] == pytest.approx(20e-3)
    assert out["by_span"] == pytest.approx({
        attribution.OUTSIDE: 4e-3, "repro.session.tune_model": 5e-3,
        "repro.runner.compile": 6e-3, "repro.runner.check": 2e-3,
        "repro.runner.time": 3e-3})
    assert out["leaf_s"] == pytest.approx(11e-3)
    empty = Trace({"/device:TPU:0": []}, {}, [])
    assert attribution.idle_by_span([], empty, 0, 5 * MS)["by_span"] == {
        attribution.OUTSIDE: pytest.approx(5e-3)}


def test_a_trace_without_program_spans_is_all_outside():
    """The recorded v5e trace of ``test_bench_trace.py`` predates the
    program's spans: its idle time is all outside them, and matches the
    busy time the existing reduction finds."""
    from bench.lib import trace

    path = str(pathlib.Path(__file__).resolve().parent / "data"
               / "fixture.xplane.pb")
    tr = trace.load(path)
    lo, hi = tr.span("bench.window")
    assert attribution.load(path) == []
    out = attribution.idle_by_span([], tr, lo, hi)
    assert list(out["by_span"]) == [attribution.OUTSIDE]
    assert out["idle_s"] == pytest.approx(
        (hi - lo) * 1e-9 - trace.busy_s(tr, lo, hi))
    assert out["leaf_s"] == 0.0
