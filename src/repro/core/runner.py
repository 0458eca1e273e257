"""Candidate measurement runners.

The paper measures candidates on two kinds of targets: FPGA-implemented SoCs
(microTVM) and a real board (TVM runtime), plus QEMU for trace analysis.
Here the runners are:

- :class:`DeviceRunner` — compiles the candidate Pallas kernel for the
  attached TPU, checks its first result against the op's reference, and
  times it on the device (the real-board analogue). It measures in this
  process, because one process holds the chip, and it refuses to run
  anywhere but on a TPU.
- :class:`InterpretRunner` — builds the candidate Pallas kernel with
  ``interpret=True`` and measures wall-clock on the host: the CPU path,
  whose times say nothing about a TPU.
- :class:`AnalyticRunner` — deterministic TPU-v5e latency model: a roofline
  over {MXU compute, HBM traffic} with per-grid-step overhead and MXU
  utilization derating (the QEMU analogue). Uncalibrated against the chip.

All satisfy the same ``Runner`` protocol; ``tuner.tune`` is agnostic. The
``overlap_capable`` class attribute tells the tuner whether measurement on
this runner has real latency worth hiding behind search: runners that
declare it ``True`` (``InterpretRunner``) opt into the pipelined
(speculative) tuner loop and interleaved sessions, which measure through
the single thread of a :class:`~repro.core.measure_scheduler.
SerialMeasureQueue`; the others (``DeviceRunner``, ``AnalyticRunner``) are
searched synchronously, on the calling thread, and keep the exact
synchronous search trajectory (see ``tuner.effective_pipeline_depth``).
A session's fixed-library baselines go through that queue on every
runner.

The optional ``max_inflight`` hint says how many submitted batches make
physical progress concurrently: 1 for every runner here.
``tuner.effective_pipeline_depth`` clamps a requested speculation depth to
``max_inflight + 1`` (one batch measuring plus one being evolved) — deeper
requests would only park batches in the queue while the search speculates
against stale predictions — and the
:class:`~repro.core.measure_scheduler.AdaptiveDepthPolicy` treats the same
bound as its growth ceiling. Runners that declare no hint are taken at the
requested depth.

Caching and dedup (the content-addressed layer)
-----------------------------------------------
Candidate evaluation is layered over two value-keyed caches plus an
optional batch-level dedup, all anchored on content signatures
(``Schedule.signature()`` for traces, ``KernelParams.signature()`` for
concrete lowerings — never object identity):

- ``space.concretize`` is memoized per (workload key, hardware name,
  schedule signature) in a bounded process-wide LRU — pure derivation,
  always on, semantically invisible. :class:`AnalyticRunner` and the
  static analyzer ride the same memo, so the analytic fast path stops
  re-deriving identical params. Invalidated only by
  ``space.clear_concretize_cache()`` (tests that monkeypatch the variant
  registry).
- ``kernels.build`` is backed by the process-wide
  :class:`~repro.core.build_cache.BuildCache`, keyed by
  ``(params.signature(), interpret)``. :meth:`InterpretRunner._prepare`
  additionally skips its first-run validation on a cache hit (the cached
  callable already survived one), so a repeated signature costs neither
  the lower nor the validation run. Also always on: the build is a pure
  function of the key, so results — and fixed-seed tuning histories —
  are bit-identical with the cache enabled. Invalidated only by
  ``build_cache.clear_build_cache()``.
- **Batch-level measurement dedup** is a ``dedup`` knob (default False)
  on :class:`InterpretRunner` and :class:`AnalyticRunner`: same-signature
  candidates
  within one batch measure once and the latency fans out by submission
  position. This *is* a semantic choice on noisy runners (position i
  reports position j's sample instead of its own draw), hence off by
  default there; on the deterministic :class:`AnalyticRunner` dedup-on is
  provably identical to dedup-off (hypothesis-tested), making it pure
  saving.
"""

from __future__ import annotations

import dataclasses
import os
import queue
import threading
import time
from typing import Callable, Protocol, Sequence

import numpy as np

from repro.core import space as space_lib
from repro.core import tracing
from repro.core.hardware import HardwareConfig, for_device_kind
from repro.core.schedule import Schedule
from repro.core.workload import Workload

INVALID = float("inf")


class Runner(Protocol):
    name: str
    hw: HardwareConfig
    # Optional (duck-typed, defaults False): True if measurement has real
    # wall-clock latency the tuner can hide search work behind.
    # overlap_capable: bool
    # Optional (duck-typed): how many submitted batches make physical
    # progress concurrently — the bound effective_pipeline_depth clamps
    # speculation depth to (+1). Absent = capacity unknown: the depth clamp
    # is skipped.
    # max_inflight: int

    def run(self, workload: Workload, schedule: Schedule) -> float:
        """Latency in seconds; inf if the candidate is invalid."""
        ...

    def run_batch(self, workload: Workload,
                  schedules: Sequence[Schedule]) -> list[float]:
        """Latencies for a batch of candidates, aligned with ``schedules``."""
        ...


def run_batch(runner: Runner, workload: Workload,
              schedules: Sequence[Schedule]) -> list[float]:
    """Measure a batch on any runner, falling back to serial ``run`` calls
    for runners that predate the batched protocol."""
    batched = getattr(runner, "run_batch", None)
    if batched is not None:
        return list(batched(workload, schedules))
    return [runner.run(workload, s) for s in schedules]


@dataclasses.dataclass
class InterpretRunner:
    hw: HardwareConfig
    repeats: int = 3
    warmup: int = 1
    name: str = "interpret"
    # Batched measurement: candidate *builds* (trace + lower + first run, the
    # expensive and crash-prone phase) overlap on a thread pool; wall-clock
    # *timing* stays serial so measurements never contend for the host.
    max_workers: int = 0  # 0 -> min(cpu_count, 8)
    build_timeout_s: float = 60.0
    # Measure each distinct trace signature in a batch once and fan the
    # latency out by submission position. Off by default: on a noisy
    # wall-clock runner, reusing a latency sample is a semantic choice
    # (see the module docstring).
    dedup: bool = False
    # Real wall-clock measurement: the tuner may pipeline search behind it.
    overlap_capable = True
    # One measurement host: submitted batches progress one at a time.
    max_inflight = 1

    def _prepare(self, workload: Workload,
                 schedule: Schedule) -> Callable | None:
        """Build + validate one candidate; ``None`` if it is invalid or its
        Pallas build/first-run crashes (failure stays isolated to this
        candidate). Builds are served from the process-wide
        :class:`~repro.core.build_cache.BuildCache`; a cached callable
        already survived its first-run validation, so a hit skips that
        run too — the expensive phase disappears entirely for repeated
        signatures."""
        from repro import kernels  # lazy: avoid import cycle
        from repro.core.build_cache import global_build_cache

        params = space_lib.concretize(workload, self.hw, schedule)
        if not params.valid:
            return None
        already_built = (params.signature(), True) in global_build_cache()
        try:
            fn = kernels.build(workload, params, interpret=True)
            if not already_built:
                fn(*workload.example_inputs()).block_until_ready()
        except Exception:
            return None
        return fn

    def run(self, workload: Workload, schedule: Schedule) -> float:
        fn = self._prepare(workload, schedule)
        if fn is None:
            return INVALID
        return _best_time(fn, workload.example_inputs(), self.warmup,
                          self.repeats)

    def run_batch(self, workload: Workload,
                  schedules: Sequence[Schedule]) -> list[float]:
        """Build the batch concurrently, then time survivors serially.

        At most ``workers`` threads are created, pulling candidate indices
        from a shared queue — thread creation is bounded by the pool size,
        not the batch size. With ``dedup`` on, only the first
        occurrence of each trace signature is built and timed; duplicates
        receive its latency by position.

        A *crashing* build costs only its own slot. A *hung* build cannot
        be killed from a thread: it wedges its worker (and the one queue
        item it held) until the batch deadline — ``build_timeout_s`` per
        concurrency wave, not per candidate, so stalls never accumulate
        unboundedly — expires; the remaining workers keep draining the
        queue. Workers are daemon threads, so a wedged build can never
        block interpreter exit either.
        """
        schedules = list(schedules)
        if len(schedules) <= 1:
            return [self.run(workload, s) for s in schedules]
        n = len(schedules)
        # position -> first position carrying the same trace signature
        rep = list(range(n))
        if self.dedup:
            first: dict = {}
            for i, s in enumerate(schedules):
                rep[i] = first.setdefault(s.signature(), i)
        distinct = [i for i in range(n) if rep[i] == i]
        workers = self.max_workers or min(len(distinct),
                                          os.cpu_count() or 1, 8)
        workers = max(1, min(workers, len(distinct)))
        results: list[Callable | None] = [None] * n
        finished = [threading.Event() for _ in range(n)]
        pending: queue.SimpleQueue = queue.SimpleQueue()
        for i in distinct:
            pending.put(i)

        def worker() -> None:
            while True:
                try:
                    i = pending.get_nowait()
                except queue.Empty:
                    return
                try:
                    results[i] = self._prepare(workload, schedules[i])
                finally:
                    finished[i].set()

        for _ in range(workers):
            threading.Thread(target=worker, daemon=True).start()
        # ceil: full-queue passes over the pool
        waves = -(-len(distinct) // workers)
        deadline = time.monotonic() + self.build_timeout_s * waves
        inputs = workload.example_inputs()
        latencies = [INVALID] * n
        for i in distinct:
            ok = finished[i].wait(timeout=max(0.0,
                                              deadline - time.monotonic()))
            if ok and results[i] is not None:
                latencies[i] = _best_time(results[i], inputs, self.warmup,
                                          self.repeats)
        for i in range(n):
            if rep[i] != i:
                latencies[i] = latencies[rep[i]]
        return latencies


@dataclasses.dataclass
class AnalyticRunner:
    """Deterministic v5e latency model (documented in DESIGN.md §5)."""

    hw: HardwareConfig
    name: str = "analytic"
    # Evaluate each distinct trace signature in a batch once. The model is
    # a deterministic function of the concretized params, so dedup-on is
    # provably identical to dedup-off (hypothesis-tested) — still off by
    # default to keep one uniform contract across runners.
    dedup: bool = False
    # Instantaneous measurement: nothing for the tuner pipeline to hide
    # behind, so speculative search would only degrade quality (tuner.py
    # clamps the pipeline depth to 1 for this runner).
    overlap_capable = False
    max_inflight = 1

    def run(self, workload: Workload, schedule: Schedule) -> float:
        # concretize is memoized process-wide (see the module docstring),
        # so repeated evaluations of one signature skip the re-derivation.
        params = space_lib.concretize(workload, self.hw, schedule)
        return self.latency(workload, params)

    def run_batch(self, workload: Workload,
                  schedules: Sequence[Schedule]) -> list[float]:
        # The model is deterministic: the batch is exactly the serial path.
        if not self.dedup:
            return [self.run(workload, s) for s in schedules]
        memo: dict = {}
        out = []
        for s in schedules:
            sig = s.signature()
            if sig not in memo:
                memo[sig] = self.run(workload, s)
            out.append(memo[sig])
        return out

    def latency(self, workload: Workload,
                params: space_lib.KernelParams) -> float:
        if not params.valid:
            return INVALID
        hw = self.hw
        # --- compute term with MXU utilization derating ---------------------
        flops = workload.flops()
        # padded-shape waste counts as issued compute
        pad = (float(np.prod(params.padded_dims))
               / max(float(np.prod(workload.dims)), 1.0))
        bm = params.block[0]
        bn = params.block[1] if len(params.block) > 1 else hw.mxu_dim
        bk = params.block[2] if len(params.block) > 2 else bn
        if params.op in ("matmul", "qmatmul", "gemv", "attention"):
            util = (min(bm, hw.mxu_dim) / hw.mxu_dim) \
                 * (min(bn, hw.mxu_dim) / hw.mxu_dim) \
                 * (min(bk, hw.mxu_dim) / hw.mxu_dim)
            util = max(util, 1e-3) ** (1.0 / 3.0)  # geometric-mean derate
        else:
            util = 1.0  # VPU elementwise
        t_compute = flops * pad / (hw.peak_flops(workload.dtype) * util)
        # --- memory term ------------------------------------------------------
        traffic = space_lib.hbm_traffic_bytes(workload, params)
        t_memory = traffic / hw.hbm_bandwidth
        # --- grid overhead ----------------------------------------------------
        steps = float(np.prod(params.grid))
        t_overhead = steps * hw.grid_step_overhead_s
        # DMA/compute overlap: roofline max, plus fixed per-step cost.
        return max(t_compute, t_memory) + t_overhead


def place_inputs(workload: Workload) -> tuple:
    """``workload.example_inputs`` on JAX's default device, in the compute
    dtype: what a timed call is given, so no timing includes a copy from
    the host or a dtype conversion."""
    import jax
    import jax.numpy as jnp

    return tuple(jax.device_put(jnp.asarray(a, dtype))
                 for a, (_, dtype) in zip(workload.example_inputs(),
                                          workload.input_specs()))


def _best_time(fn: Callable, inputs, warmup: int, repeats: int) -> float:
    """Best of ``repeats`` host-clock timings of ``fn(*inputs)``, each ended
    by ``block_until_ready``, after ``warmup`` untimed calls."""
    for _ in range(warmup):
        fn(*inputs).block_until_ready()
    best = INVALID
    for _ in range(repeats):
        t0 = time.perf_counter()
        fn(*inputs).block_until_ready()
        best = min(best, time.perf_counter() - t0)
    return best


def xla_latency(workload: Workload, repeats: int = 3) -> float:
    """Measure the XLA default lowering of the op (the paper's
    GCC/LLVM-autovectorization baseline) with wall-clock on this host, on
    inputs placed on the device beforehand (see :func:`place_inputs`)."""
    from repro import kernels

    return _best_time(kernels.xla_baseline(workload), place_inputs(workload),
                      warmup=1, repeats=repeats)


# ----------------------------------------------------------- the chip path --

def default_backend() -> str:
    """JAX's default backend in this process (a seam tests steer)."""
    import jax

    return jax.default_backend()


def attached_device():
    """The device a :class:`DeviceRunner` measures on: JAX's first (a seam
    tests steer)."""
    import jax

    return jax.devices()[0]


# Largest |kernel - reference| over the largest |reference| a compiled
# kernel may show, by the workload's input dtype. The reference runs on the
# same device inputs at "highest" matmul precision. Integer families
# (qmatmul, int8 matmul) must match exactly.
TOLERANCE = {
    # bf16 results: kernel and reference round nearly the same f32 value to
    # bf16, which can land one bf16 ulp apart — at most 2^-7 of the largest
    # magnitude. Two such ulps of slack.
    "bfloat16": 2.0 ** -6,
    # f32 results: only the summation order of the f32 accumulation
    # differs (~1e-6 of the largest magnitude at K = 4096). A contraction
    # the compiler ran as one bf16 pass (~2^-9 per product) fails it.
    "float32": 2.0 ** -10,
}

# Compile refusals are counted by reason; a wrong first result as "wrong".
FAILURE_REASONS = ("vmem", "alignment", "other", "wrong")


def refusal_reason(exc: BaseException) -> str:
    """Classify a compiler refusal: out of VMEM, a block shape the TPU
    cannot tile, or anything else."""
    msg = str(exc)
    if "divisible" in msg or "align" in msg.lower():
        return "alignment"
    if "vmem" in msg.lower():
        return "vmem"
    return "other"


def _is_integer(dtype) -> bool:
    return np.issubdtype(np.dtype(dtype), np.integer)


class DeviceRunner:
    """Compiled Pallas kernels measured on the attached TPU, in process.

    - Requires that JAX's first device is a TPU, and derives the hardware
      configuration from its ``device_kind``; anything else raises. It
      never falls back to the CPU, to interpret mode or to a default part.
    - Inputs are made on the device once per workload (``example_inputs``
      moved there in the compute dtype), so timed calls see device arrays
      only.
    - Each distinct kernel (``KernelParams.signature()``) is compiled
      ahead of time, once. A compiler refusal is counted by reason
      (:func:`refusal_reason`) and the candidate is ``INVALID``.
    - The first result of each kernel is checked against the op's
      reference at ``TOLERANCE``; a wrong kernel is counted as ``"wrong"``,
      is ``INVALID`` and is never timed.
    - A valid kernel is warmed up (``WARMUP`` calls) and timed with
      ``block_until_ready``; the best of ``REPEATS`` is its latency.

    ``failures(workload)`` and ``max_error(workload)`` report per
    workload; the tuner copies them onto its results.

    Each candidate is a ``repro.runner.run`` span of ``core/tracing.py``,
    split into ``inputs``, ``compile`` (refusals included), ``reference``,
    ``check`` and ``time``; a signature compiled before counts as
    ``repro.runner.reused``.
    """

    WARMUP = 2
    REPEATS = 5
    name = "device"
    # One device: timing stays in the calling thread, in submission order.
    overlap_capable = False
    max_inflight = 1

    def __init__(self):
        device = attached_device()
        if device.platform != "tpu":
            raise RuntimeError(
                f"DeviceRunner needs a TPU, but JAX's first device is "
                f"{device.platform} ({device.device_kind})")
        self.device = device
        self.hw = for_device_kind(device.device_kind)
        self._inputs: dict[str, tuple] = {}
        self._refs: dict[str, object] = {}
        # signature -> compiled kernel, or None once refused or wrong
        self._compiled: dict[tuple, Callable | None] = {}
        self._failures: dict[str, dict[str, int]] = {}
        self._max_error: dict[str, float] = {}
        # reason -> the first line of the first refusal of that reason
        self.first_refusal: dict[str, str] = {}

    # ---- per-workload state ------------------------------------------------
    def inputs(self, workload: Workload) -> tuple:
        key = workload.key()
        if key not in self._inputs:
            with tracing.span("repro.runner.inputs"):
                self._inputs[key] = place_inputs(workload)
        return self._inputs[key]

    def reference_output(self, workload: Workload):
        import jax
        from repro import kernels

        key = workload.key()
        if key not in self._refs:
            inputs = self.inputs(workload)
            with tracing.span("repro.runner.reference"), \
                    jax.default_matmul_precision("highest"):
                self._refs[key] = jax.jit(kernels.reference(workload))(
                    *inputs)
        return self._refs[key]

    def failures(self, workload: Workload) -> dict[str, int]:
        """Compile refusals by reason, plus wrong results, of this
        workload's kernels so far."""
        counts = self._failures.get(workload.key(), {})
        return {r: counts.get(r, 0) for r in FAILURE_REASONS}

    def max_error(self, workload: Workload) -> float:
        """Largest normalized error (see ``TOLERANCE``) among this
        workload's accepted kernels; NaN before any was checked."""
        return self._max_error.get(workload.key(), float("nan"))

    def clear(self) -> None:
        """Drop the device inputs, references and compiled kernels (the
        counters stay)."""
        self._inputs.clear()
        self._refs.clear()
        self._compiled.clear()

    # ---- measurement -------------------------------------------------------
    def _count(self, workload: Workload, reason: str) -> None:
        counts = self._failures.setdefault(workload.key(), {})
        counts[reason] = counts.get(reason, 0) + 1

    def error(self, workload: Workload, out) -> float:
        """Normalized error of ``out`` against the reference: max |diff| for
        integer results, max |diff| / max |reference| for float ones;
        infinite on a shape mismatch."""
        import jax.numpy as jnp

        ref = self.reference_output(workload)
        if out.shape != ref.shape:
            return INVALID
        diff = jnp.max(jnp.abs(out.astype(jnp.float32)
                               - ref.astype(jnp.float32)))
        if _is_integer(ref.dtype):
            return float(diff)
        return float(diff / jnp.max(jnp.abs(ref.astype(jnp.float32))))

    def _prepare(self, workload: Workload,
                 schedule: Schedule) -> Callable | None:
        """Compile and check one candidate; ``None`` if it is invalid,
        refused or wrong."""
        from repro import kernels

        params = space_lib.concretize(workload, self.hw, schedule)
        if not params.valid:
            return None
        sig = params.signature()
        if sig in self._compiled:
            tracing.count("repro.runner.reused")
            return self._compiled[sig]
        inputs = self.inputs(workload)
        self._compiled[sig] = None
        try:
            with tracing.span("repro.runner.compile"):
                fn = kernels.build(workload, params, interpret=False)
                compiled = fn.lower(*inputs).compile()
        except Exception as exc:  # a refusal is a result, and it is counted
            reason = refusal_reason(exc)
            self._count(workload, reason)
            first_line = (str(exc).splitlines() or [""])[0]
            self.first_refusal.setdefault(
                reason, f"{type(exc).__name__}: {first_line}")
            return None
        ref = self.reference_output(workload)
        with tracing.span("repro.runner.check"):
            err = self.error(workload, compiled(*inputs))
        tol = 0.0 if _is_integer(ref.dtype) else TOLERANCE[workload.dtype]
        if not err <= tol:
            self._count(workload, "wrong")
            return None
        key = workload.key()
        self._max_error[key] = max(self._max_error.get(key, 0.0), err)
        self._compiled[sig] = compiled
        return compiled

    def run(self, workload: Workload, schedule: Schedule) -> float:
        with tracing.span("repro.runner.run"):
            fn = self._prepare(workload, schedule)
            if fn is None:
                return INVALID
            inputs = self.inputs(workload)
            with tracing.span("repro.runner.time"):
                return _best_time(fn, inputs, self.WARMUP, self.REPEATS)

    def run_batch(self, workload: Workload,
                  schedules: Sequence[Schedule]) -> list[float]:
        return [self.run(workload, s) for s in schedules]
