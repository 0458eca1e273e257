"""Benchmark harness — one suite per paper table/figure.

Prints ``name,us_per_call,derived`` CSV rows (derived = speedup vs the
suite's baseline or the suite-specific metric).

Suites (paper artifact -> suite):
  Fig. 3/6  matmul suite          tuned vs fixed-library vs XLA, sizes x dtypes
  Fig. 4    hardware sweep        per-config re-tuning vs carried schedules
  Fig. 5/9  trace analysis        store fraction + instruction census + code size
  Fig. 7/10 complete networks     per-op tuned network latency vs baselines
  SIV       tuning cost           seconds per tuning iteration

Two measurement targets, mirroring the paper's FPGA/QEMU duality
(DESIGN.md §5): ``interpret`` = wall-clock of the Pallas kernels on this
host; ``analytic`` = the v5e latency model used for TPU-target numbers.
"""

from __future__ import annotations

import argparse
import os
import sys
import time

import numpy as np

_ROOT = os.path.join(os.path.dirname(os.path.abspath(__file__)), "..")
sys.path.insert(0, _ROOT)
sys.path.insert(0, os.path.join(_ROOT, "src"))

from benchmarks import nets
from repro.core import (AnalyticRunner, InterpretRunner, TuningDatabase,
                        TuningSession, V5E, V5E_MXU256, V5E_VMEM32,
                        V5E_VMEM64, INTERPRET, concretize,
                        fixed_library_schedule, space_for, tune,
                        v1_distinct_configs, xla_latency)
from repro.core.space import instruction_census
from repro.core import workload as W

ROWS: list[str] = []


def emit(name: str, us: float, derived: str = "") -> None:
    row = f"{name},{us:.2f},{derived}"
    ROWS.append(row)
    print(row, flush=True)


# --------------------------------------------------------------- Fig. 3/6 ----

def matmul_suite(trials: int = 24) -> None:
    """Tuned vs fixed-library vs XLA across sizes and dtypes.

    interpret rows: real wall-clock on this host (small sizes).
    analytic rows: v5e model (production sizes)."""
    # measured (host, interpret mode)
    for size in (16, 32, 64, 128):
        for dtype in ("float32", "int8"):
            wl = (W.qmatmul(size, size, size) if dtype == "int8"
                  else W.matmul(size, size, size, dtype))
            runner = InterpretRunner(INTERPRET, repeats=2)
            res = tune(wl, INTERPRET, runner, trials=trials, seed=0)
            fx = runner.run(wl, fixed_library_schedule(wl, INTERPRET))
            xla = xla_latency(wl)
            emit(f"matmul_interp/{dtype}/{size}/tuned", res.best_latency * 1e6,
                 f"vs_fixed={fx / res.best_latency:.2f}x")
            emit(f"matmul_interp/{dtype}/{size}/fixed", fx * 1e6, "")
            emit(f"matmul_interp/{dtype}/{size}/xla", xla * 1e6, "")
    # v5e analytic (paper-scale shapes)
    for size in (128, 256, 512, 1024, 2048):
        for dtype in ("bfloat16", "int8", "float32"):
            wl = (W.qmatmul(size, size, size) if dtype == "int8"
                  else W.matmul(size, size, size, dtype))
            runner = AnalyticRunner(V5E)
            res = tune(wl, V5E, runner, trials=48, seed=0)
            fx = runner.run(wl, fixed_library_schedule(wl, V5E))
            emit(f"matmul_v5e/{dtype}/{size}/tuned", res.best_latency * 1e6,
                 f"vs_fixed={fx / res.best_latency:.2f}x")
            emit(f"matmul_v5e/{dtype}/{size}/fixed", fx * 1e6, "")


# ----------------------------------------------------------------- Fig. 4 ----

def hw_sweep(trials: int = 48) -> None:
    """The VLEN-sweep experiment: the fixed library's schedule is frozen at
    one config; the tuner re-tunes per config. Derived column = penalty of
    shipping the *other* config's tuned schedule (schedule non-transfer)."""
    wl = W.matmul(4096, 4096, 4096, "bfloat16")
    tuned = {}
    for hw in (V5E_VMEM32, V5E_VMEM64, V5E, V5E_MXU256):
        res = tune(wl, hw, AnalyticRunner(hw), trials=trials, seed=0)
        tuned[hw.name] = res
        fx = AnalyticRunner(hw).run(wl, fixed_library_schedule(wl, hw))
        emit(f"hw_sweep/{hw.name}/tuned", res.best_latency * 1e6,
             f"vs_fixed={fx / res.best_latency:.2f}x")
    # cross-transfer: v5e-tuned schedule carried onto the 32MiB part
    carried = AnalyticRunner(V5E_VMEM32).run(wl, tuned[V5E.name].best_schedule)
    native = tuned[V5E_VMEM32.name].best_latency
    emit("hw_sweep/carried_v5e_schedule_on_vmem32",
         carried * 1e6 if np.isfinite(carried) else -1.0,
         f"penalty_vs_retuned={'inf' if not np.isfinite(carried) else f'{carried / native:.2f}x'}")


# --------------------------------------------------------------- Fig. 5/9 ----

def trace_analysis(trials: int = 32) -> None:
    """Instruction census of tuned vs library schedules: store fraction
    (paper: tuned <1%) and total block-instruction count; plus the code-size
    analogue (bytes of specialized kernel IR vs the full multi-variant
    library)."""
    import jax
    from repro import kernels

    # int8 QNN matmul, deep K: the Fig. 5 setting (muRISCV-NN's int8 path)
    wl = W.qmatmul(4096, 4096, 8192)
    res = tune(wl, V5E, AnalyticRunner(V5E), trials=trials, seed=0)
    p_tuned = res.best_params
    p_fixed = concretize(wl, V5E, fixed_library_schedule(wl, V5E))
    c_tuned = instruction_census(wl, p_tuned)
    c_fixed = instruction_census(wl, p_fixed)
    emit("trace/tuned/store_fraction", c_tuned["store_fraction"] * 1e6,
         f"total_insns={c_tuned['total']:.0f}")
    emit("trace/fixed/store_fraction", c_fixed["store_fraction"] * 1e6,
         f"total_insns={c_fixed['total']:.0f}")
    emit("trace/insn_reduction", 0.0,
         f"tuned_vs_fixed={c_fixed['total'] / c_tuned['total']:.2f}x")

    # code size: deployment ships ONE specialized kernel; the hand-written
    # library ships every granularity variant (the paper's ~90% reduction).
    small = W.matmul(128, 128, 128, "float32")
    sp = space_for(small, INTERPRET)
    t0 = None
    tuned_ir = len(jax.jit(kernels.build(
        small, concretize(small, INTERPRET,
                          tune(small, INTERPRET,
                               AnalyticRunner(INTERPRET), trials=8,
                               seed=0).best_schedule),
        interpret=True)).lower(
        *[jax.ShapeDtypeStruct(a.shape, a.dtype)
          for a in small.example_inputs()]).as_text())
    lib_ir = 0
    from repro.core.schedule import Schedule
    for name in sp["variant"]:
        p = concretize(small, INTERPRET, Schedule.fixed(variant=name))
        lib_ir += len(jax.jit(kernels.build(small, p, interpret=True)).lower(
            *[jax.ShapeDtypeStruct(a.shape, a.dtype)
              for a in small.example_inputs()]).as_text())
    emit("trace/code_size_tuned_bytes", float(tuned_ir),
         f"library={lib_ir}B reduction={(1 - tuned_ir / lib_ir) * 100:.0f}%")


# -------------------------------------------------------------- Fig. 7/10 ----

def networks(trials: int = 16, measured: bool = True) -> None:
    """Complete networks through TuningSession: each net's unique workloads
    tune once under a shared budget (dedup + database warm-start across nets
    — later nets reuse earlier nets' records for shared shapes), summed with
    repeat counts under tuned / fixed-library / XLA mappings. v5e-analytic
    for all nets; wall-clock interpret for the small ones."""
    db = TuningDatabase()
    improvements_fixed, improvements_xla = [], []
    for net_name, builder in nets.NETWORKS.items():
        ops = builder()
        session = TuningSession(V5E, AnalyticRunner(V5E), database=db)
        res = session.tune_model(ops, total_trials=trials * len(ops), seed=0,
                                 model=net_name)
        t_tuned, t_fixed = res.tuned_latency, res.fixed_latency
        emit(f"net_v5e/{net_name}/tuned", t_tuned * 1e6,
             f"vs_fixed={t_fixed / t_tuned:.2f}x "
             f"unique={len(res.reports)}/{len(ops)}")
        emit(f"net_v5e/{net_name}/fixed", t_fixed * 1e6, "")
        improvements_fixed.append(1 - t_tuned / t_fixed)
    emit("net_v5e/mean_improvement_vs_fixed", 0.0,
         f"{np.mean(improvements_fixed) * 100:.0f}%")

    if measured:
        # wall-clock on this host with batched (thread-pool) candidate
        # builds. tuned-vs-fixed compares two Pallas schedules on the SAME
        # (interpret) runtime — the like-for-like comparison; the XLA row is
        # the compiled-runtime reference (its absolute time is not
        # comparable to interpret-mode numbers).
        for net_name in ("bert-tiny", "anomaly-detection"):
            ops = nets.NETWORKS[net_name]()
            runner = InterpretRunner(INTERPRET, repeats=2)
            # overlap-capable runner + multi-workload model -> the session
            # interleaves one workload's measurement with another's search
            session = TuningSession(INTERPRET, runner, database=db)
            res = session.tune_model(
                ops, total_trials=max(8, trials // 2) * len(ops), seed=0,
                model=net_name)
            t_tuned, t_fixed = res.tuned_latency, res.fixed_latency
            t_xla = sum(r.count * xla_latency(r.workload, repeats=2)
                        for r in res.reports)
            emit(f"net_interp/{net_name}/tuned", t_tuned * 1e6,
                 f"vs_fixed={t_fixed / t_tuned:.2f}x "
                 f"tune_wall_s={res.wall_time_s:.1f} "
                 f"overlap={res.overlap_fraction:.2f}")
            emit(f"net_interp/{net_name}/fixed", t_fixed * 1e6, "")
            emit(f"net_interp/{net_name}/xla_ref", t_xla * 1e6,
                 "compiled-runtime reference")
            improvements_xla.append(1 - min(t_tuned / t_fixed, 1.0))
        emit("net_interp/mean_improvement_vs_fixed_measured", 0.0,
             f"{np.mean(improvements_xla) * 100:.0f}%")


# ----------------------------------------------------------- design space ----

def space_cardinality() -> None:
    """Size of the generative design-space program per workload vs the old
    flat (independent-categorical, 3-point-SCALES) space — both counted as
    *distinct postprocessor-valid concrete configurations*, the honest
    metric (nominal flat-space products overcount clamp-duplicated scales).
    Doubles as the CI search-space smoke: the program space must be strictly
    larger for the op families with tile splits."""
    cases = [
        ("matmul", W.matmul(2048, 2048, 2048, "bfloat16")),
        ("qmatmul", W.qmatmul(2048, 2048, 2048)),
        # composite (non-pow2) reduction extent: real factorizations reach
        # splits the halving-ladder scale grid never could (k = 3 * 4096,
        # the transformer FFN shape)
        ("gemv", W.gemv(4096, 12288, "bfloat16")),
        ("vmacc", W.vmacc(2048, 2048)),
        ("attention", W.attention(1, 8, 8, 1024, 1024, 128, "bfloat16")),
    ]
    for name, wl in cases:
        prog = space_for(wl, V5E)
        v2 = prog.distinct_configs()
        v1 = v1_distinct_configs(wl, V5E)
        traces = prog.cardinality()
        emit(f"space/{name}/v2_configs", float(v2),
             f"v1={v1} ratio={v2 / max(v1, 1):.2f}x traces={traces}")
        if name in ("matmul", "qmatmul", "gemv", "vmacc"):
            assert v2 > v1, (
                f"{name}: program space ({v2}) must be strictly larger "
                f"than the v1 flat space ({v1})")
        if name == "vmacc":
            # the bc (column) axis is a real split now, not a
            # variant-derived constant: several kernel-lowerable candidates
            # must exist for a wide-c workload (gated by the kernel's
            # supports_block_shape check)
            ctx = {"variant": prog["variant"][0]}
            ctx["br"] = prog.candidates("br", ctx)[0]
            bc_cands = prog.candidates("bc", ctx)
            emit("space/vmacc/bc_axis", float(len(bc_cands)),
                 f"candidates={list(bc_cands)}")
            assert len(bc_cands) >= 2, (
                f"vmacc bc axis collapsed to {bc_cands}: the column "
                f"split should offer multiple kernel-supported tiles")
        if name == "gemv":
            # the bn (output-row / J) axis is a real split now, not a
            # variant-derived constant: several kernel-lowerable candidates
            # must exist for a wide-n workload (gated by the kernel's
            # supports_block_shape check)
            ctx = {"variant": prog["variant"][0]}
            ctx["bk"] = prog.candidates("bk", ctx)[0]
            bn_cands = prog.candidates("bn", ctx)
            emit("space/gemv/bn_axis", float(len(bn_cands)),
                 f"candidates={list(bn_cands)}")
            assert len(bn_cands) >= 2, (
                f"gemv bn axis collapsed to {bn_cands}: the output-row "
                f"split should offer multiple kernel-supported tiles")


def static_suite() -> None:
    """Static feasibility analysis vs exhaustive dynamic enumeration.

    For every registered kernel family x hardware config: run the static
    analyzer, exhaustively enumerate the same program's traces through the
    dynamic postprocessor pipeline (the ground truth), and assert the
    verdicts agree *exactly* — same trace counts, same per-decision
    feasible sets. Reports the fraction of the raw space proven infeasible
    (what the tuner never has to sample and a board never has to measure)
    and runs the sweep-level space lint as a hard gate: the registered
    space definitions must be provably clean (no empty feasible sets, no
    name collisions, no capability-ignoring splits)."""
    from repro.core import lint_space
    from repro.core import static_analysis as static_lib
    from repro.core.schedule import Schedule

    configs = (V5E, V5E_VMEM32, V5E_VMEM64, V5E_MXU256)
    cases = [
        ("matmul", W.matmul(512, 512, 512, "bfloat16")),
        ("qmatmul", W.qmatmul(512, 512, 512)),
        ("gemv", W.gemv(1024, 4096, "bfloat16")),
        ("vmacc", W.vmacc(2048, 2048)),
        ("attention", W.attention(1, 8, 8, 512, 512, 128)),
    ]
    for name, wl in cases:
        for hw in configs:
            report = static_lib.analyze(wl, hw)
            assert report.exhaustive, f"{name}@{hw.name}: space too large"
            # ground truth: every trace through the dynamic pipeline
            prog = space_for(wl, hw)
            total = valid = 0
            feasible = {ins.name: set() for ins in prog.instructions}
            for t in prog.traces(limit=static_lib.DEFAULT_TRACE_LIMIT):
                total += 1
                if prog.validate(Schedule.fixed(**t)).valid:
                    valid += 1
                    for k, v in t.items():
                        feasible[k].add(v)
            assert (report.total_traces, report.valid_traces) == \
                (total, valid), (
                f"{name}@{hw.name}: analyzer counted "
                f"{report.total_traces}/{report.valid_traces} traces, "
                f"dynamic enumeration {total}/{valid}")
            for k, vals in feasible.items():
                assert set(report.feasible[k]) == vals, (
                    f"{name}@{hw.name}: feasible set of {k!r} diverged: "
                    f"static {sorted(report.feasible[k], key=repr)} vs "
                    f"dynamic {sorted(vals, key=repr)}")
            emit(f"static/{name}/{hw.name}/infeasible_fraction",
                 report.infeasible_fraction,
                 f"traces={report.total_traces} "
                 f"valid={report.valid_traces} "
                 f"dead_values={report.pruned_value_count}")
        diags = lint_space(wl, configs)
        hard = [d for d in diags if d.rule != static_lib.RULE_DEAD]
        assert not hard, (
            f"{name}: space definition lint failed: "
            f"{[str(d) for d in hard]}")
        emit(f"static/{name}/lint", 0.0,
             f"diagnostics={len(diags)} hard=0")


# ---------------------------------------------------- candidate batches ----

def _candidate_population(wl, hw, limit=16):
    """Up to ``limit`` distinct valid schedules for one workload (the
    candidate batch its tuning task would measure)."""
    from repro.core import TraceSampler

    space = space_for(wl, hw)
    sampler = TraceSampler(0)
    out, sigs = [], set()
    for _ in range(200 * limit):
        s = sampler.sample(space)
        if len(out) >= limit:
            break
        if concretize(wl, hw, s).valid and s.signature() not in sigs:
            sigs.add(s.signature())
            out.append(s)
    return out


# ------------------------------------------------------ learned proposals ----

def learn_suite(trials: int = 48) -> None:
    """Learned proposals vs uniform sampling at equal budget — the
    measurements-to-target comparison behind the probabilistic-program
    refactor. For each workload: seed a database by tuning a *neighboring*
    shape, then tune the target twice with the same seed — once with
    proposal learning off (the pre-refactor uniform sampler), once with the
    proposals warm-started from the database's transferred posteriors
    (``transfer_distributions``). The learned search must reach the uniform
    search's best latency using **no more measurements** (fewer on at least
    one workload) — measurement count is the scarce resource once boards
    are real (9-12 s per candidate in the paper). Deterministic: analytic
    runner, fixed seeds. Doubles as the CI learn smoke."""
    cases = [
        ("matmul", W.matmul(512, 2048, 2048, "bfloat16"),
         W.matmul(1024, 2048, 2048, "bfloat16")),
        ("gemv", W.gemv(2048, 8192, "bfloat16"),
         W.gemv(2048, 4096, "bfloat16")),
        ("vmacc", W.vmacc(2048, 2048), W.vmacc(1024, 2048)),
    ]
    runner = AnalyticRunner(V5E)
    fewer = 0
    for name, target, neighbor in cases:
        db = TuningDatabase()
        tune(neighbor, V5E, runner, trials=trials, seed=0, database=db)
        uniform = tune(target, V5E, runner, trials=trials, seed=1,
                       learn_proposals=False)
        priors = db.transfer_distributions(target, V5E.name)
        learned = tune(target, V5E, runner, trials=trials, seed=1,
                       prior_distributions=priors)
        goal = uniform.best_latency * (1 + 1e-9)

        def count_to_goal(res):
            for i, (_s, lat) in enumerate(res.history):
                if lat <= goal:
                    return i + 1
            return None

        n_uniform = count_to_goal(uniform)
        n_learned = count_to_goal(learned)
        emit(f"learn/{name}/learned_best", learned.best_latency * 1e6,
             f"uniform_best={uniform.best_latency * 1e6:.2f} "
             f"measurements_to_target={n_learned}/{n_uniform} "
             f"entropy={learned.mean_proposal_entropy:.2f} "
             f"prior_decisions={len(priors)}")
        assert n_learned is not None, (
            f"{name}: learned proposals never reached the uniform search's "
            f"best latency within {trials} measurements")
        assert n_learned <= n_uniform, (
            f"{name}: learned proposals needed {n_learned} measurements to "
            f"reach the uniform best; uniform needed {n_uniform}")
        if n_learned < n_uniform:
            fewer += 1
    emit("learn/workloads_with_fewer_measurements", float(fewer),
         f"of {len(cases)}")
    assert fewer >= 1, (
        "learned proposals matched but never beat the uniform measurement "
        "count on any workload")


# ------------------------------------------------- budget reallocation ----

def sched_suite() -> None:
    """Entropy-gated budget reallocation. Doubles as the CI sched smoke;
    every claim is asserted.

    At deterministic depth 1, vs the no-policy baseline, the entropy stop
    policy must spend strictly fewer total measurements, fewer on at least
    one workload, and reach equal-or-better best latency on *every*
    workload (curtailed searches release budget; still-improving ones draw
    it back through the shared ledger at ``reallocate_fraction=0.5``)."""
    # entropy-gated budget reallocation, deterministic regime: equal
    # per-workload budgets (floor = share), analytic latencies, forced
    # interleave at depth 1 so histories depend only on each driver's own
    # reconcile order. The policy curtails converged searches and re-grants
    # half the released budget to still-improving ones.
    # flops-weighted budget split: the big matmul gets the long budget
    # (and plateaus well before spending it — curtailed, releasing ~40
    # trials), the small ops get the floor (and exhaust it while still
    # improving — they draw grants back from the ledger)
    ent_ops = [(1, W.matmul(512, 2048, 2048, "bfloat16")),
               (1, W.gemv(2048, 8192, "bfloat16")),
               (1, W.vmacc(2048, 2048))]
    runs = {}
    for mode, policy in (("no_stop", "none"), ("entropy", "entropy")):
        runs[mode] = TuningSession(
            V5E, AnalyticRunner(V5E), database=TuningDatabase(),
            min_trials=24, interleave=True, stop_policy=policy,
            plateau_patience=28, reallocate_fraction=0.5).tune_model(
            ent_ops, total_trials=48 * len(ent_ops), seed=0,
            model=f"sched-{mode}")
        emit(f"sched/entropy_{mode}/total_trials",
             float(runs[mode].total_trials),
             f"stops={runs[mode].stopped_early} "
             f"released={runs[mode].released_trials} "
             f"realloc={runs[mode].reallocated_trials}")
    base, pol = runs["no_stop"], runs["entropy"]
    fewer = 0
    for a, b in zip(base.reports, pol.reports):
        emit(f"sched/entropy/{a.workload.key()}/best",
             b.best_latency * 1e6,
             f"no_stop_best={a.best_latency * 1e6:.2f} "
             f"trials={b.trials}/{a.trials} "
             f"stopped={b.stopped_early} granted={b.budget_granted}")
        assert b.best_latency <= a.best_latency * (1 + 1e-9), (
            f"entropy policy regressed {a.workload.key()}: "
            f"{b.best_latency} vs {a.best_latency}")
        if b.trials < a.trials:
            fewer += 1
    assert pol.stopped_early >= 1, (
        "entropy stop policy never curtailed a converged search")
    assert pol.total_trials < base.total_trials, (
        f"entropy policy spent {pol.total_trials} measurements, baseline "
        f"{base.total_trials}: must be strictly fewer")
    assert fewer >= 1, (
        "entropy policy never spent fewer measurements on any workload")


# ---------------------------------------------------- cross-hw transfer ----

def transfer_study(trials: int = 16) -> None:
    """ROADMAP cross-hardware transfer study (paper Fig. 4 at scale): seed
    a database by tuning a shape set on v5e, then sweep every hardware
    config, reporting the warm-start hit rate — the fraction of transferred
    records that concretize valid on the target — and warm-vs-cold best
    latency at equal trial budget."""
    shapes = [
        W.matmul(512, 512, 512, "bfloat16"),
        W.matmul(1024, 1024, 1024, "bfloat16"),
        W.qmatmul(512, 512, 512),
        W.gemv(2048, 8192, "bfloat16"),
    ]
    db = TuningDatabase()
    for wl in shapes:
        tune(wl, V5E, AnalyticRunner(V5E), trials=trials, seed=0,
             database=db)
    for hw in (V5E_VMEM32, V5E_VMEM64, V5E, V5E_MXU256):
        usable = requested = measured = 0
        ratios = []
        for wl in shapes:
            seeds = db.transfer_candidates(wl, hw.name, limit=4)
            requested += len(seeds)
            usable += sum(1 for s in seeds if concretize(wl, hw, s).valid)
            runner = AnalyticRunner(hw)
            warm = tune(wl, hw, runner, trials=trials, seed=1,
                        warm_start=seeds)
            cold = tune(wl, hw, runner, trials=trials, seed=1)
            measured += warm.warm_started
            ratios.append(cold.best_latency / warm.best_latency)
        hit = usable / max(requested, 1)
        emit(f"transfer/{hw.name}/warm_start_hit_rate", hit * 100,
             f"usable={usable}/{requested} measured={measured} "
             f"warm_vs_cold={np.mean(ratios):.3f}x")


# --------------------------------------------------------- session report ----

def session_report(db: TuningDatabase) -> list[tuple[str, float, str]]:
    """Per-model latency/overlap trends across the sessions recorded in a
    tuning database (ROADMAP: session-level reporting). Returns
    ``(name, us, derived)`` rows; the trend column is the best-latency delta
    vs the previous session of the same model."""
    rows: list[tuple[str, float, str]] = []
    by_model: dict[str, list[tuple[int, dict]]] = {}
    for i, s in enumerate(db.sessions):
        model = s.get("model") or f"{s.get('hw', '?')}/{s.get('runner', '?')}"
        by_model.setdefault(model, []).append((i, s))
    for model, entries in by_model.items():
        prev_latency = None
        best_latency = float("inf")
        for i, s in entries:
            tuned = s.get("tuned_latency_s")
            # skip degenerate summaries (empty op list, sanitized non-finite)
            if not isinstance(tuned, (int, float)) or tuned <= 0:
                continue
            if prev_latency is not None:
                trend = f"vs_prev={tuned / prev_latency:.3f}x"
            else:
                trend = "vs_prev=baseline"
            overlap = s.get("overlap_fraction")
            overlap_txt = (f"{overlap:.2f}"
                           if isinstance(overlap, (int, float)) else "n/a")
            speedup = s.get("speedup_vs_fixed")
            speedup_txt = (f"{speedup:.2f}x"
                           if isinstance(speedup, (int, float)) else "n/a")
            # proposal-convergence trend: mean normalized posterior entropy
            # at session end (1.0 = uniform; falling across sessions =
            # the proposals are learning); n/a for pre-learning sessions
            # or learning-off runs (sanitized NaN -> None)
            entropy = s.get("proposal_entropy")
            entropy_txt = (f"{entropy:.2f}"
                           if isinstance(entropy, (int, float)) else "n/a")
            # adaptation column: curtailed searches / reallocated trials
            # (0 for non-adaptive sessions, n/a for summaries recorded
            # before the adaptation layer existed)
            if "stopped_early" in s:
                adapt_txt = (f"stops={s.get('stopped_early', 0)}"
                             f"/realloc={s.get('reallocated_trials', 0)}")
            else:
                adapt_txt = "stops=n/a"
            # build-cache hit rate of the session's kernel builds (n/a for
            # summaries recorded before the content-addressed cache, or
            # for build-free analytic sessions that never probed it)
            bc = s.get("build_cache")
            probes = (bc.get("hits", 0) + bc.get("misses", 0)
                      if isinstance(bc, dict) else 0)
            bc_txt = f"{bc['hits'] / probes:.2f}" if probes else "n/a"
            rows.append((f"report/{model}/session{i}", tuned * 1e6,
                         f"{trend} speedup_vs_fixed={speedup_txt} "
                         f"overlap={overlap_txt} "
                         f"entropy={entropy_txt} "
                         f"{adapt_txt} "
                         f"build_cache_hit={bc_txt} "
                         f"trials={s.get('total_trials', '?')}"))
            prev_latency = tuned
            best_latency = min(best_latency, tuned)
        if prev_latency is not None:
            valid = [s.get("tuned_latency_s") for _, s in entries]
            first = next(t for t in valid
                         if isinstance(t, (int, float)) and t > 0)
            rows.append((f"report/{model}/trend", best_latency * 1e6,
                         f"sessions={len(entries)} "
                         f"best_vs_first={best_latency / first:.3f}x"))
    return rows


def report(db_path: str | None) -> None:
    path = db_path or os.environ.get("REPRO_TUNING_DB")
    if not path or not os.path.exists(path):
        print(f"# no tuning database at {path!r}; run a tuning session first",
              file=sys.stderr)
        return
    db = TuningDatabase(path)
    if not db.sessions:
        print(f"# database {path} holds no session summaries", file=sys.stderr)
        return
    for name, us, derived in session_report(db):
        emit(name, us, derived)


# ------------------------------------------------------------ tuning cost ----

def tuning_cost() -> None:
    """Paper §IV: 9-12 s per candidate on FPGA. Ours, per runner; plus the
    measure/search pipeline: synchronous vs pipelined tuning wall-time on
    the interpret runner, with the measured-while-evolving (overlap)
    fraction, so pipeline efficiency shows up in the bench trajectory."""
    wl = W.matmul(128, 256, 256, "float32")
    for runner, hw in ((InterpretRunner(INTERPRET, repeats=2), INTERPRET),
                       (AnalyticRunner(V5E), V5E)):
        t0 = time.perf_counter()
        res = tune(wl, hw, runner, trials=16, seed=0)
        per = (time.perf_counter() - t0) / max(res.trials, 1)
        emit(f"tuning_cost/{runner.name}/s_per_candidate", per * 1e6,
             f"trials={res.trials}")
    # measure/search overlap, speculative: same budget, depth 2. NB the
    # speculative trajectory measures *different* candidates than sync, so
    # single-run wall-time deltas mix pipelining with build-cost luck —
    # the overlap fraction is the clean signal here.
    runner = InterpretRunner(INTERPRET, repeats=2)
    sync = tune(wl, INTERPRET, runner, trials=16, seed=0)
    piped = tune(wl, INTERPRET, runner, trials=16, seed=0, pipeline_depth=2)
    emit("tuning_cost/interpret/sync_wall", sync.wall_time_s * 1e6,
         f"overlap={sync.overlap_fraction:.4f}")
    emit("tuning_cost/interpret/pipelined_wall", piped.wall_time_s * 1e6,
         f"overlap={piped.overlap_fraction:.4f} "
         f"wall_vs_sync={sync.wall_time_s / piped.wall_time_s:.2f}x "
         f"(trajectories differ)")
    # like-for-like: serial vs interleaved session at depth 1 measure the
    # SAME candidates per workload (no speculation; different op families,
    # fresh databases, so warm-start chaining cannot diverge either) — the
    # wall-time delta is pure measure/search pipelining.
    ops = [(1, W.matmul(16, 16, 16, "float32")), (1, W.vmacc(8, 8))]
    serial = TuningSession(
        INTERPRET, InterpretRunner(INTERPRET, repeats=2),
        database=TuningDatabase(), min_trials=4,
        interleave=False).tune_model(ops, total_trials=8, seed=0)
    inter = TuningSession(
        INTERPRET, InterpretRunner(INTERPRET, repeats=2),
        database=TuningDatabase(), min_trials=4,
        interleave=True).tune_model(ops, total_trials=8, seed=0)
    emit("tuning_cost/session/serial_wall", serial.wall_time_s * 1e6,
         "overlap=0.00")
    emit("tuning_cost/session/interleaved_wall", inter.wall_time_s * 1e6,
         f"overlap={inter.overlap_fraction:.4f} "
         f"wall_vs_serial={serial.wall_time_s / inter.wall_time_s:.2f}x "
         f"(same candidates)")


# ------------------------------------------------- continuous tuning ----

def serve_suite(trials: int = 8) -> None:
    """Traffic-driven continuous tuning in the serving path (ISSUE 9).

    A real (reduced-config) server starts against an empty tuned artifact:
    the cold round dispatches every decode workload through the fixed
    library and records the misses into a TrafficLog; a background
    ContinuousTuner drains the log, tunes the hottest shapes, and saves
    the artifact; the hot-swapping global database then flips subsequent
    rounds' dispatch to tuned provenance — same process, no restart.
    Asserted: the cold round has zero tuned dispatches, replayed traffic
    converges to >= 1 tuned dispatch with none left on the fixed library,
    and an unseen near-miss shape resolves "bucketed" to the nearest tuned
    bucket. Doubles as the CI serve smoke."""
    import shutil
    import tempfile

    import jax

    from repro.configs import get_config
    from repro.configs.base import ShapeSpec
    from repro.core import (ContinuousTuner, TrafficLog, best_schedule,
                            reset_global_database)
    from repro.models.model_zoo import build
    from repro.runtime.serve_loop import Server, decode_ops

    cfg = get_config("yi_6b").reduced()
    bundle = build(cfg, remat="none")
    params = bundle.init(jax.random.key(0))
    batch_size, prompt, steps = 2, 8, 2
    ops = decode_ops(cfg, batch_size)
    total_ops = sum(count for count, _ in ops)

    def mix(d):
        return " ".join(f"{k}={v}" for k, v in sorted(d.items()))

    old_env = os.environ.get("REPRO_TUNING_DB")
    tmpdir = tempfile.mkdtemp(prefix="serve_suite_")
    os.environ["REPRO_TUNING_DB"] = os.path.join(tmpdir, "database.json")
    reset_global_database()
    traffic = TrafficLog()
    tuner = ContinuousTuner(traffic, V5E, runner=AnalyticRunner(V5E),
                            db_path=os.environ["REPRO_TUNING_DB"],
                            trials_per_shape=max(trials, 4),
                            max_shapes_per_cycle=len(ops),
                            poll_interval_s=0.01)
    server = Server(bundle, params, max_len=prompt + steps + 1, hw=V5E,
                    serve_ops=ops, traffic=traffic)
    batch = bundle.make_batch(
        0, ShapeSpec("serve", prompt, batch_size, "decode"), train=False)
    prompts = np.asarray(batch.pop("tokens"))
    try:
        cold = server.generate(prompts, steps, extra_batch=batch or None)
        assert cold.dispatch.get("tuned", 0) == 0, (
            f"serve: cold server already tuned ({mix(cold.dispatch)}) — "
            "artifact isolation broken")
        emit("serve/cold/decode_wall", cold.decode_s * 1e6,
             mix(cold.dispatch))
        tuner.start()
        converged = None
        for rnd in range(1, 6):
            assert tuner.wait_idle(timeout=300.0), \
                "serve: continuous tuner never drained the traffic log"
            res = server.generate(prompts, steps, extra_batch=batch or None)
            emit(f"serve/round{rnd}/decode_wall", res.decode_s * 1e6,
                 mix(res.dispatch))
            if res.dispatch.get("tuned", 0) >= 1:
                converged = res
                break
        assert converged is not None, (
            "serve: no tuned dispatch after replayed traffic — the "
            "serving-tuning loop never closed")
        assert converged.dispatch.get("fixed", 0) == 0, (
            f"serve: shapes left on the fixed library after tuning "
            f"({mix(converged.dispatch)})")
        emit("serve/converged/tuned_ops",
             float(converged.dispatch.get("tuned", 0)), f"of {total_ops}")
        emit("serve/tuner_cycles", float(tuner.cycles),
             f"shapes={tuner.shapes_tuned}")
        # an unseen near-miss shape (k doubled on the hottest decode op)
        # must ride the nearest tuned bucket, not the fixed library
        b, n, k = ops[0][1].dims
        near = W.matmul(b, n, 2 * k, ops[0][1].dtype)
        _, provenance = best_schedule(near, V5E)
        assert provenance == "bucketed", (
            f"serve: near-miss shape resolved {provenance!r}, expected "
            "'bucketed'")
        emit("serve/near_miss/provenance", 0.0, provenance)
    finally:
        tuner.stop()
        if old_env is None:
            os.environ.pop("REPRO_TUNING_DB", None)
        else:
            os.environ["REPRO_TUNING_DB"] = old_env
        reset_global_database()
        shutil.rmtree(tmpdir, ignore_errors=True)


# ------------------------------------------- content-addressed caching ----

def cache_suite(trials: int = 16) -> None:
    """Content-addressed build/measurement caching (ISSUE 10).

    Three measurements, two of them asserted:

    1. duplicate-concretization rate — how often a tuning search asks for
       a (workload, hw, trace) lowering the memoized ``concretize`` has
       already derived (static screen, runner, record paths all re-touch
       the same trace);
    2. warm-vs-cold interpret build wall — a second identical batch on the
       :class:`InterpretRunner` must perform **zero** Pallas builds and
       finish **>= 2x** faster (asserted), since trace+lower+first-run
       dominates cold batch wall;
    3. serve-loop steady state — a ``build_kernels=True`` server's first
       dispatch pass pays the builds; every later generate must perform
       **zero** builds (asserted).
    """
    import shutil
    import tempfile

    import jax

    from repro.configs import get_config
    from repro.configs.base import ShapeSpec
    from repro.core import (build_cache_stats, clear_build_cache,
                            clear_concretize_cache, concretize_cache_stats,
                            reset_global_database)
    from repro.models.model_zoo import build
    from repro.runtime.serve_loop import Server, decode_ops

    # 1. duplicate-concretization rate under a real analytic search
    for wl in (W.matmul(512, 512, 512, "bfloat16"),
               W.gemv(2048, 2048, "bfloat16")):
        clear_concretize_cache()
        tune(wl, V5E, AnalyticRunner(V5E), trials=trials, seed=0)
        s = concretize_cache_stats()
        rate = s["hits"] / max(s["hits"] + s["misses"], 1)
        emit(f"cache/concretize/{wl.op}/dup_rate_pct", rate * 100,
             f"hits={s['hits']} misses={s['misses']}")

    # 2. warm-vs-cold build wall on the interpret runner
    wl = W.matmul(128, 128, 128, "float32")
    schedules = _candidate_population(wl, INTERPRET, limit=4)
    runner = InterpretRunner(INTERPRET, repeats=1, warmup=0)
    clear_build_cache()
    before = build_cache_stats()
    t0 = time.perf_counter()
    runner.run_batch(wl, schedules)
    cold = time.perf_counter() - t0
    mid = build_cache_stats()
    t0 = time.perf_counter()
    runner.run_batch(wl, schedules)
    warm = time.perf_counter() - t0
    after = build_cache_stats()
    assert after["misses"] == mid["misses"], (
        f"cache: warm batch rebuilt "
        f"({after['misses'] - mid['misses']} builds)")
    speedup = cold / max(warm, 1e-9)
    assert speedup >= 2.0, (
        f"cache: warm batch only {speedup:.2f}x faster than cold — the "
        "build cache is not absorbing trace+lower+first-run")
    emit("cache/interpret/cold_batch_wall", cold * 1e6,
         f"builds={mid['misses'] - before['misses']}")
    emit("cache/interpret/warm_batch_wall", warm * 1e6,
         f"speedup={speedup:.2f}x hits={after['hits'] - mid['hits']}")

    # 3. serve loop: first dispatch pass builds, steady state never does
    cfg = get_config("yi_6b").reduced()
    bundle = build(cfg, remat="none")
    params = bundle.init(jax.random.key(0))
    batch_size, prompt, steps = 2, 8, 2
    ops = decode_ops(cfg, batch_size)

    old_env = os.environ.get("REPRO_TUNING_DB")
    tmpdir = tempfile.mkdtemp(prefix="cache_suite_")
    os.environ["REPRO_TUNING_DB"] = os.path.join(tmpdir, "database.json")
    reset_global_database()
    server = Server(bundle, params, max_len=prompt + steps + 1, hw=INTERPRET,
                    serve_ops=ops, build_kernels=True)
    batch = bundle.make_batch(
        0, ShapeSpec("serve", prompt, batch_size, "decode"), train=False)
    prompts = np.asarray(batch.pop("tokens"))
    try:
        clear_build_cache()
        cold_stats = build_cache_stats()
        res = server.generate(prompts, steps, extra_batch=batch or None)
        mid = build_cache_stats()
        first_builds = mid["misses"] - cold_stats["misses"]
        assert first_builds > 0, (
            "cache: first dispatch pass built nothing — build_kernels is "
            "not reaching the kernel builder")
        emit("cache/serve/first_pass_decode_wall", res.decode_s * 1e6,
             f"builds={first_builds}")
        res = server.generate(prompts, steps, extra_batch=batch or None)
        after = build_cache_stats()
        steady = after["misses"] - mid["misses"]
        assert steady == 0, (
            f"cache: steady-state serve performed {steady} builds — the "
            "dispatch pass is not content-addressed")
        emit("cache/serve/steady_state_builds", float(steady),
             f"hits={after['hits'] - mid['hits']}")
    finally:
        if old_env is None:
            os.environ.pop("REPRO_TUNING_DB", None)
        else:
            os.environ["REPRO_TUNING_DB"] = old_env
        reset_global_database()
        shutil.rmtree(tmpdir, ignore_errors=True)


SUITES = {
    "space": space_cardinality,
    "static": static_suite,
    "matmul": matmul_suite,
    "hw_sweep": hw_sweep,
    "trace": trace_analysis,
    "networks": networks,
    "tuning_cost": tuning_cost,
    "transfer": transfer_study,
    "learn": learn_suite,
    "sched": sched_suite,
    "serve": serve_suite,
    "cache": cache_suite,
}

_NO_TRIALS_ARG = ("tuning_cost", "space", "static", "sched")


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--suite", choices=list(SUITES) + ["all"], default="all")
    ap.add_argument("--trials", type=int, default=None)
    ap.add_argument("--report", action="store_true",
                    help="print per-model latency/overlap trends across the "
                         "sessions stored in the tuning database, then exit")
    ap.add_argument("--db", default=None,
                    help="tuning database path for --report "
                         "(default: $REPRO_TUNING_DB)")
    args = ap.parse_args()
    import jax

    from repro.runtime.compile_cache import enable_compile_cache

    cache_dir = enable_compile_cache()
    dev = jax.devices()[0]
    print(f"# device: {dev.platform} {dev.device_kind} x{len(jax.devices())}"
          f"; compile cache: {cache_dir}")
    print("name,us_per_call,derived")
    if args.report:
        report(args.db)
        return
    t0 = time.perf_counter()
    for name, fn in SUITES.items():
        if args.suite not in ("all", name):
            continue
        kwargs = {}
        if args.trials is not None and name not in _NO_TRIALS_ARG:
            kwargs = {"trials": args.trials}
        fn(**kwargs)
    print(f"# total wall time: {time.perf_counter() - t0:.1f}s")


if __name__ == "__main__":
    main()
