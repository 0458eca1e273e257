"""The trace reduction: on hand-made events, where every number can be
checked by hand, and on a small trace recorded on one TPU v5e.

``data/fixture.xplane.pb``: a jitted program ``prog`` with three named
scopes (``net.qmm0``, a Pallas int8 matmul; ``net.attn0``, the float32
attention kernel; ``net.xladot``, an XLA dot), run three times inside the
host span ``bench.window``, each run in a span ``bench.pass``, then a 10 ms
sleep. ``data/fixture.hlo.txt``: that program's HLO instructions with their
``op_name``, from the same program compiled for a described v5e; the
instruction names are the trace's. The device's clock runs about half a
millisecond behind the host's there, so the first run starts before the
window's span: the tests read programs over the whole trace."""

import pathlib

import pytest

from bench.lib import trace
from bench.lib.trace import Event, Trace

DATA = pathlib.Path(__file__).resolve().parent / "data"
MS = 1e6  # ns


def _hand_made():
    ops = [Event(0, 2 * MS, "%fusion.1 = f32[8]{0} fusion(f32[8]{0} %a)"),
           Event(1 * MS, 3 * MS, "%copy.2 = f32[8]{0} copy(f32[8]{0} %b)"),
           Event(5 * MS, 6 * MS,
                 "%k.3 = s8[64,576]{1,0} custom-call(s8[64,576]{1,0} %x), "
                 'custom_call_target="tpu_custom_call"'),
           Event(8 * MS, 10 * MS, "%dot.4 = f32[8]{0} dot(f32[8]{0} %c)")]
    modules = [Event(0, 6.5 * MS, "jit_net_pass(123)"),
               Event(7.5 * MS, 10 * MS, "jit_other(9)")]
    spans = [Event(-1 * MS, 12 * MS, "bench.window"),
             Event(3.5 * MS, 4.5 * MS, "bench.runner"),
             Event(6.2 * MS, 8 * MS, "bench.tune.round")]
    return Trace({"/device:TPU:0": ops}, {"/device:TPU:0": modules}, spans)


def test_busy_time_is_the_union_of_operations():
    tr = _hand_made()
    lo, hi = tr.span("bench.window")
    # [0, 3] (two overlapping operations), [5, 6], [8, 10]
    assert trace.busy_s(tr, lo, hi) == pytest.approx(6e-3)
    assert trace.busy_s(tr, 1 * MS, 5.5 * MS) == pytest.approx(2.5e-3)


def test_idle_gaps_are_labelled_by_the_host_span_around_them():
    tr = _hand_made()
    lo, hi = tr.span("bench.window")
    gaps = trace.idle_gaps(tr, lo, hi)
    # [3, 5], [6, 8] and [10, 12] ms, longest first, ties in time order;
    # then [-1, 0] ms before the first operation
    assert gaps == [["bench.runner", pytest.approx(2e-3)],
                    ["bench.tune.round", pytest.approx(2e-3)],
                    ["bench.window", pytest.approx(2e-3)],
                    ["bench.window", pytest.approx(1e-3)]]


def test_operations_inside_a_parent_are_counted_once():
    parent = Event(0, 10, "%while.1 = () while()")
    kids = [Event(1, 4, "%a.1 = f32[] add()"),
            Event(5, 9, "%b.2 = f32[] mul()")]
    assert trace._leaves([parent] + kids) == kids


def test_scopes_and_programs():
    tr = _hand_made()
    lo, hi = tr.span("bench.window")
    scopes = trace.instruction_scopes(
        '%k.3 = s8[64,576]{1,0} custom-call(...), metadata={op_name='
        '"jit(net_pass)/bench.op.q+o/jit(f)/pallas_call"}\n'
        'ROOT %dot.4 = f32[8]{0} dot(...), metadata={op_name="jit(x)/dot"}',
        "bench.op.")
    assert scopes == {"k.3": "bench.op.q+o"}
    assert trace.scope_events(tr, lo, hi, scopes, "jit_net_pass") == {
        "bench.op.q+o": [pytest.approx(1e-3)]}
    assert trace.module_runs(tr, lo, hi, "jit_") == {
        "jit_net_pass(123)": [pytest.approx(6.5e-3)],
        "jit_other(9)": [pytest.approx(2.5e-3)]}
    assert trace.top_ops(tr, lo, hi) == [
        ["fusion", pytest.approx(2e-3)], ["copy", pytest.approx(2e-3)],
        ["dot", pytest.approx(2e-3)], ["custom-call", pytest.approx(1e-3)]]
    assert trace.opcode('%copy-start.1 = (f32[1]{0:T(8,128)S(1)}, u32[]'
                        '{:S(2)}) copy-start(f32[1]{0} %k)') == "copy-start"


@pytest.fixture(scope="module")
def recorded():
    return trace.load(str(DATA / "fixture.xplane.pb"))


ALL = (float("-inf"), float("inf"))


def test_recorded_trace_has_the_layout_read(recorded):
    assert list(recorded.ops) == ["/device:TPU:0"]
    assert [s.name for s in recorded.spans] == ["bench.window"] + [
        "bench.pass"] * 3
    runs = trace.module_runs(recorded, *ALL, "jit_prog")
    assert [len(v) for v in runs.values()] == [3]
    lo, hi = recorded.span("bench.window")
    busy = trace.busy_s(recorded, lo, hi)
    ops = sum(e - s for s, e, _ in trace._clipped(
        recorded.ops["/device:TPU:0"], lo, hi)) * 1e-9
    assert 0 < busy <= ops <= (hi - lo) * 1e-9
    assert busy >= 0.9 * ops  # a TPU runs one operation at a time


def test_recorded_trace_idle_gap_after_the_passes(recorded):
    lo, hi = recorded.span("bench.window")
    label, seconds = trace.idle_gaps(recorded, lo, hi)[0]
    assert label == "bench.window"  # outside every bench.pass
    assert 0.010 < seconds < 0.02  # the 10 ms sleep, and the host's return


def test_recorded_trace_time_by_named_scope(recorded):
    scopes = trace.instruction_scopes(
        (DATA / "fixture.hlo.txt").read_text(), "net.")
    assert scopes["f.2"] == "net.qmm0" and scopes["f.3"] == "net.attn0"
    events = trace.scope_events(recorded, *ALL, scopes, "jit_prog")
    assert set(events) == {"net.qmm0", "net.attn0", "net.xladot"}
    # each scope's kernel or dot ran once in each of the three runs
    assert all(len(v) >= 3 for v in events.values())
    runs = trace.module_runs(recorded, *ALL, "jit_prog")
    assert sum(map(sum, events.values())) <= sum(map(sum, runs.values()))
