"""The tune driver: the paper's tuning session on the chip, then the
network it tuned.

Window: rounds of ``TuningSession.tune_model`` on ``DeviceRunner`` over the
network's distinct operators, each round at the session's floor budget
(``trials_per_op`` per distinct operator), all rounds into one
``TuningDatabase``, until ``--seconds`` have passed. Each round is a tuning
session as a user starts one: a new runner, and JAX's in-memory caches and
the program's build cache emptied, so every candidate it measures is
compiled, checked against the program's reference and timed. JAX's
persistent compilation cache is off during the window for the same reason:
with it on, a later run would load what an earlier one compiled. Set-up
runs one such round, into a database of its own, so that what a process
initialises once is not in the window.

After the window the network pass runs: every operator ``count`` times in
one jitted program, each call on inputs of its own, under the schedules
``core/dispatch.best_schedule`` resolves from the window's database. Its
outputs are compared with the plain references in ``bench/refs``. Its
passes run in a loop inside that program, one dispatch for all of them;
the loop's host-clock time over its passes is ``tuned_net_ms``. The library and XLA passes are printed on a
line of their own for the paper's comparison.
"""

from __future__ import annotations

import contextlib
import functools
import math
import time

from bench.lib import harness, netops, seeds, trace
from bench.refs import precision
from bench.refs import qmatmul as ref_qmatmul

PASS_MODULE = "jit_net_pass"
SCOPE = "bench.op."
# the library and XLA passes are printed, not metrics: a short loop will do
BASELINE_PASS_SECONDS = 0.5


class TimedRunner:
    """Delegates to the program's runner and times every call into it."""

    def __init__(self, inner, spans):
        self._inner = inner
        self._spans = spans

    def __getattr__(self, name):
        return getattr(self._inner, name)

    def run(self, workload, schedule):
        with self._spans("bench.runner"):
            return self._inner.run(workload, schedule)

    def run_batch(self, workload, schedules):
        with self._spans("bench.runner"):
            return self._inner.run_batch(workload, schedules)


def program_workload(op: netops.Op):
    from repro.core import workload as W

    if op.kind == "qmatmul":
        return W.qmatmul(*op.dims)
    raise ValueError(f"unknown operator kind {op.kind!r}")


def persistent_cache(on: bool) -> None:
    import jax
    from jax.experimental.compilation_cache import compilation_cache

    jax.config.update("jax_enable_compilation_cache", on)
    compilation_cache.reset_cache()


def fresh_session_state() -> None:
    """What a new tuning session starts with: no compiled program in
    memory and an empty build cache."""
    import jax
    from repro.core.build_cache import clear_build_cache

    jax.clear_caches()
    clear_build_cache()


def round_seed(seed: int, r: int) -> int:
    return int(seeds.rng(seed, seeds.TUNE, r).integers(0, 2 ** 31))


def tune_window(ops, traffic: dict, seed: int, seconds: float, spans,
                on_round=None):
    """Run tuning rounds into one database until ``seconds`` have passed.
    Returns (database, counters, window seconds, hardware)."""
    from repro.core import DeviceRunner, TuningDatabase, TuningSession

    workloads = [(op.count, program_workload(op)) for op in ops]
    db = TuningDatabase()
    counters = {"tune.rounds": 0, "tune.trials": 0, "tune.refused": 0,
                "tune.wrong": 0}
    hw = None
    t0 = time.perf_counter()
    while True:
        r = counters["tune.rounds"]
        with (on_round(r) if on_round else contextlib.nullcontext()):
            with spans("bench.tune.round"):
                fresh_session_state()
                runner = TimedRunner(DeviceRunner(), spans)
                hw = runner.hw
                session = TuningSession(hw, runner, database=db)
                res = session.tune_model(
                    workloads,
                    total_trials=traffic["trials_per_op"] * len(workloads),
                    seed=round_seed(seed, r), model="bench")
        counters["tune.rounds"] += 1
        counters["tune.trials"] += res.total_trials
        fails = res.failures or {}
        counters["tune.wrong"] += fails.get("wrong", 0)
        counters["tune.refused"] += sum(
            n for reason, n in fails.items() if reason != "wrong")
        if time.perf_counter() - t0 >= seconds:
            break
    return db, counters, time.perf_counter() - t0, hw


def make_inputs(ops, traffic: dict, seed: int):
    """Inputs of every call of the pass, made on the device in one jitted
    call: for each operator, ``count`` argument tuples of its own. Each
    operand is drawn for all of an operator's calls at once and then split,
    so the program holds three generators per operator, not three per
    call, and compiles in seconds."""
    import jax
    import jax.numpy as jnp

    r, br = traffic["int8_range"], traffic["bias_range"]

    def calls(key, op):
        ks = jax.random.split(key, 3)
        m, n, k = op.dims
        c = op.count
        xs = jax.random.randint(ks[0], (c, m, k), -r, r + 1, jnp.int8)
        ws = jax.random.randint(ks[1], (c, k, n), -r, r + 1, jnp.int8)
        bs = jax.random.randint(ks[2], (c, n), -br, br + 1, jnp.int32)
        return [(xs[i], ws[i], bs[i]) for i in range(c)]

    @jax.jit
    def make(key):
        return [calls(jax.random.fold_in(key, i), op)
                for i, op in enumerate(ops)]

    return make(seeds.key(seed, seeds.INPUTS))


def build_pass(ops, fns):
    """One jitted program ``net(inputs, passes)`` that runs ``passes``
    passes of the network in a loop on the device. A pass calls ``fns[i]``
    on every argument tuple of operator i, under the named scope
    ``bench.op.<name>``, and the loop carries every output of the pass, so
    the compiler can neither drop nor merge a call. Each pass's operands go
    through a barrier with the loop's counter, so no call is hoisted out of
    the loop and run once. One dispatch runs all the passes: the host's
    handling of the pass's hundreds of arguments is paid once, not once
    a pass."""
    import jax
    import jax.numpy as jnp
    from jax import lax

    def one_pass(inputs):
        outs = []
        for op, fn, calls in zip(ops, fns, inputs):
            with jax.named_scope(SCOPE + op.name):
                outs.append([fn(*args) for args in calls])
        return outs

    def net_pass(inputs, passes):
        def body(i, outs):
            _, args = lax.optimization_barrier((i, inputs))
            return one_pass(args)

        zeros = jax.tree.map(lambda s: jnp.zeros(s.shape, s.dtype),
                             jax.eval_shape(one_pass, inputs))
        return lax.fori_loop(0, passes, body, zeros)

    return jax.jit(net_pass)


def time_pass(fn, inputs, min_s: float):
    """Host-clock seconds per pass of one dispatch of ``fn`` running at
    least 10 passes, and enough of them to last at least ``min_s``; the
    outputs of its last pass; the number of passes."""
    import jax

    out = jax.block_until_ready(fn(inputs, 1))  # compiles
    t0 = time.perf_counter()
    jax.block_until_ready(fn(inputs, 10))
    per_pass = max((time.perf_counter() - t0) / 10, 1e-6)
    passes = max(10, math.ceil(min_s / per_pass))
    t0 = time.perf_counter()
    out = jax.block_until_ready(fn(inputs, passes))
    return (time.perf_counter() - t0) / passes, out, passes


def resolved_fns(ops, db, hw, how: str):
    """Each operator's callable: ``tuned`` resolves through the dispatch
    chain from ``db`` (tuned, then fixed, then XLA), ``library`` takes the
    fixed library schedule, ``xla`` XLA's own lowering."""
    from repro import kernels
    from repro.core import concretize, dispatch

    fns, provenance = [], {}
    for op in ops:
        wl = program_workload(op)
        if how == "xla":
            sched, prov = None, "xla"
        elif how == "library":
            sched, prov = dispatch.fixed_library_schedule(wl, hw), "fixed"
        else:
            sched, prov = dispatch.best_schedule(wl, hw, database=db)
        provenance[prov] = provenance.get(prov, 0) + op.count
        fns.append(kernels.xla_baseline(wl) if sched is None else
                   kernels.build(wl, concretize(wl, hw, sched),
                                 interpret=False))
    return fns, provenance


def database_table(ops, db, hw) -> list[str]:
    """Per operator, every candidate the window measured: its block, grid
    and order, its best and its count of recorded latencies, the one
    dispatch picks marked with ``*``."""
    from repro.core import concretize
    from repro.core.schedule import Schedule

    lines = []
    for op in ops:
        wl = program_workload(op)
        best = db.best(wl, hw.name)
        best_sig = (concretize(wl, hw, best[0]).signature()
                    if best is not None else None)
        seen: dict[tuple, list[float]] = {}
        for rec in db.history(wl, hw.name):
            p = concretize(wl, hw, Schedule.from_json(rec["schedule"]))
            seen.setdefault((p.signature(), p.block, p.grid, p.order),
                            []).append(rec["latency_s"])
        cands = [f"{'*' if sig == best_sig else ''}{block}/{grid}/{order} "
                 f"{1e6 * min(lats):.1f}us x{len(lats)}"
                 for (sig, block, grid, order), lats in
                 sorted(seen.items(), key=lambda kv: min(kv[1]))]
        lines.append(f"{op.name}: " + "; ".join(cands))
    return lines


def reference_outputs(ops, inputs, cfg: dict, cast=precision.exact):
    """The plain references' outputs for every call of the pass. ``cast``
    rounds their operands to a lower precision: the control."""
    import jax

    refs = {
        "qmatmul": jax.jit(functools.partial(
            ref_qmatmul.qmatmul, scale=cfg["requant_scale"],
            cast=cast.get("qmatmul", precision.exact))),
    }
    return [[refs[op.kind](*args) for args in calls]
            for op, calls in zip(ops, inputs)]


def compare(ops, got, want) -> dict[str, float]:
    """Worst disagreement per operator kind: the largest integer
    difference over every call."""
    import jax.numpy as jnp

    worst: dict[str, float] = {}
    for op, gs, ws in zip(ops, got, want):
        for g, w in zip(gs, ws):
            err = float(jnp.max(jnp.abs(g.astype(jnp.int32)
                                        - w.astype(jnp.int32))))
            worst[op.kind] = max(worst.get(op.kind, 0.0), err)
    return worst


# the control of each kernel family: its reference one precision below
# what the configuration states (int4 below int8)
CONTROL = {"qmatmul": precision.int4}

CHECK_NAMES = {"qmatmul": "qmatmul_max_int_diff"}


def checks(worst: dict, limits: dict) -> list:
    return [harness.Check(CHECK_NAMES[k], v, limits[CHECK_NAMES[k]])
            for k, v in sorted(worst.items())]


def calls_missing(ops, net: dict, limits: dict) -> harness.Check:
    """Operators whose named scope ran fewer device operations per traced
    pass than the operator has calls: a call dropped or merged."""
    passes = max(net["passes"], 1)
    missing = sum(1 for op in ops
                  if net["scope_events"].get(SCOPE + op.name, 0) / passes
                  < op.count)
    return harness.Check("net_calls_missing", missing,
                         limits["net_calls_missing"])


def run(ctx) -> harness.Outcome:
    import shutil
    import tempfile

    import jax

    from bench.lib.spans import Spans
    from repro.runtime.compile_cache import enable_compile_cache

    cfg, traffic = ctx.cell.config, ctx.cell.traffic
    ops = netops.forward_ops(cfg, traffic)
    enable_compile_cache()  # set-up's programs load from the cache
    spans = Spans()
    tmp = tempfile.mkdtemp(prefix="bench-trace-")
    try:
        inputs = make_inputs(ops, traffic, ctx.seed)
        jax.block_until_ready(inputs)
        persistent_cache(False)
        # one round into a database of its own: what a process compiles or
        # starts once (the compiler, the runner) is set-up, so the window's
        # first round costs what its others do, in a cold run or a warm one
        tune_window(ops, traffic, ctx.seed, 0.0, Spans())
        record = {"counters": {}, "spans": {}, "trace": {}}

        def on_round(r):  # traced runs profile the first round
            stack = contextlib.ExitStack()
            if ctx.trace and r == 0:
                stack.enter_context(trace.profile(tmp + "/tune"))
                stack.enter_context(spans("bench.window"))
            return stack

        t_window = time.perf_counter()
        setup_s = t_window - ctx.t_start
        db, counters, window_s, hw = tune_window(
            ops, traffic, ctx.seed, ctx.seconds, spans, on_round)
        stats = ctx.devices[0].memory_stats() or {}
        memory = stats.get("peak_bytes_in_use", 0)

        fns, provenance = resolved_fns(ops, db, hw, "tuned")
        net = build_pass(ops, fns)
        net_s, outs, passes = time_pass(net, inputs, traffic["pass_seconds"])
        counters["net.passes"] = passes
        counters.update({f"net.provenance.{k}": v
                         for k, v in provenance.items()})
        if ctx.trace:
            net_trace = _trace_net(net, inputs, ops, ctx.peaks, traffic,
                                   tmp + "/net")
            record["trace"]["net"] = net_trace
            per_pass = max(net_trace["passes"], 1)
            print("tune: traced pass, device ms per pass by operator: "
                  + ", ".join(f"{k} {1e3 * v / per_pass:.4f}" for k, v in
                              sorted(net_trace["scope_s"].items())),
                  flush=True)
        baseline = {}
        for how in ("library", "xla"):
            f, _ = resolved_fns(ops, db, hw, how)
            baseline[how] = time_pass(build_pass(ops, f), inputs,
                                      BASELINE_PASS_SECONDS)[0]
        print(f"tune: net pass tuned {net_s * 1e3:.4f} ms, library "
              f"{baseline['library'] * 1e3:.4f} ms, xla "
              f"{baseline['xla'] * 1e3:.4f} ms; provenance {provenance}; "
              f"{counters['tune.trials']} trials in {window_s:.3f} s over "
              f"{counters['tune.rounds']} rounds", flush=True)
        for line in database_table(ops, db, hw):
            print(f"tune: measured {line}", flush=True)
        found = checks(compare(ops, outs,
                               reference_outputs(ops, inputs, cfg, {})),
                       ctx.cell.limits)
        if ctx.trace:
            found.append(calls_missing(ops, record["trace"]["net"],
                                       ctx.cell.limits))
        record["counters"] = counters
        record["spans"] = dict(spans.total_s)
        record["window_s"] = window_s
        busy = window = breakdown = None
        if ctx.trace:
            tr = trace.load(trace.find_xplane(tmp + "/tune"))
            lo, hi = tr.span("bench.window")
            busy, window = trace.busy_s(tr, lo, hi), (hi - lo) * 1e-9
            record["trace"]["window"] = {"busy_s": busy, "window_s": window}
            breakdown = {"device_ops": trace.top_ops(tr, lo, hi),
                         "idle_gaps": trace.idle_gaps(tr, lo, hi)}
        trials = counters["tune.trials"]
        return harness.Outcome(
            end_to_end={"setup_s": setup_s,
                        "tune_trials_per_s": trials / window_s,
                        "tuned_net_ms": net_s * 1e3},
            record=record, checks=found,
            attempted=trials,
            failed=counters["tune.refused"] + counters["tune.wrong"],
            memory_peak_bytes=int(memory), busy_s=busy, window_s=window,
            breakdown=breakdown)
    finally:
        shutil.rmtree(tmp, ignore_errors=True)


def _trace_net(net, inputs, ops, peaks, traffic, logdir) -> dict:
    """Trace a few passes and reduce them: the pass program's device
    seconds, the operations found under each operator's named scope, and
    each operator's least and compute-bound seconds per pass."""
    import jax

    from bench.lib.spans import Spans

    scopes = trace.instruction_scopes(
        net.lower(inputs, 1).compile().as_text(), SCOPE)
    passes = traffic["traced_passes"]
    spans = Spans()
    with trace.profile(logdir):
        with spans("bench.net"):
            jax.block_until_ready(net(inputs, passes))
    tr = trace.load(trace.find_xplane(logdir))
    lo, hi = tr.span("bench.net")
    runs = trace.module_runs(tr, lo, hi, PASS_MODULE)
    events = trace.scope_events(tr, lo, hi, scopes, PASS_MODULE)
    return {
        "passes": passes * sum(len(v) for v in runs.values()),
        "pass_device_s": sum(sum(v) for v in runs.values()),
        "scope_s": {k: sum(v) for k, v in events.items()},
        "scope_events": {k: len(v) for k, v in events.items()},
        "least_s": {SCOPE + op.name: op.count * op.cost().least_s(peaks)
                    for op in ops},
        "compute_s": {SCOPE + op.name: op.count * op.cost().compute_s(peaks)
                      for op in ops},
    }
