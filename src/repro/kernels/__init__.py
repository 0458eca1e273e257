"""Pallas micro-kernels + the build/reference dispatch used by the tuner.

Each kernel package has:
  kernel.py — ``pl.pallas_call`` + explicit BlockSpec VMEM tiling,
  ops.py    — jitted public wrapper (padding, dtype policy),
  ref.py    — pure-jnp oracle used by tests and as the XLA baseline.

``build(workload, params)`` is the tuner's builder: it turns a concrete
schedule (:class:`KernelParams`) into a measurable callable — the analogue
of MetaSchedule emitting C/LLVM for one candidate.

**What is cached where.** The per-op ``ops.build`` is a pure function of
``(params, interpret)`` — the returned callable closes over nothing else —
so :func:`build` routes through the process-wide content-addressed
:class:`~repro.core.build_cache.BuildCache`, keyed by
``(params.signature(), interpret)``. Two different schedule traces that
concretize to the same lowering get the *same* callable back; repeated
resolutions in the serving path, repeated candidates in a tuning batch,
and repeated tasks landing on a persistent measurement-pool worker all
skip the rebuild. The cache stores only what ``ops.build`` returns; a
raising build caches nothing (retried next call). Invalidation: none in
normal operation (the builder is deterministic per signature) —
``repro.core.build_cache.clear_build_cache()`` resets it for tests that
monkeypatch kernel modules. Pass ``cache=False`` to force an uncached
build, or an explicit :class:`BuildCache` to isolate one (tests).
"""

from __future__ import annotations

from repro.core.build_cache import BuildCache, global_build_cache
from repro.core.space import KernelParams, concretize
from repro.core.workload import Workload


def _build_uncached(params: KernelParams, interpret: bool):
    if params.op in ("matmul",):
        from repro.kernels.matmul import ops
        return ops.build(params, interpret=interpret)
    if params.op == "qmatmul":
        from repro.kernels.qmatmul import ops
        return ops.build(params, interpret=interpret)
    if params.op == "gemv":
        from repro.kernels.gemv import ops
        return ops.build(params, interpret=interpret)
    if params.op == "vmacc":
        from repro.kernels.vmacc import ops
        return ops.build(params, interpret=interpret)
    if params.op == "attention":
        from repro.kernels.flash_attention import ops
        return ops.build(params, interpret=interpret)
    raise ValueError(f"no kernel registered for op {params.op}")


def build(workload: Workload, params: KernelParams, *, interpret,
          cache: BuildCache | bool | None = None):
    """Concrete schedule -> jitted callable over ``workload.example_inputs``.

    ``interpret`` has no default: False compiles for the TPU, True runs the
    Pallas interpreter on any backend (see
    :func:`~repro.kernels.matmul.kernel.matmul_pallas`). Served from the
    process-wide build cache by default (see the module docstring);
    ``cache=False`` bypasses it, an explicit :class:`BuildCache` replaces
    it."""
    if cache is False:
        return _build_uncached(params, interpret)
    bc = cache if isinstance(cache, BuildCache) else global_build_cache()
    key = (params.signature(), bool(interpret))
    return bc.get_or_build(
        key, lambda: _build_uncached(params, interpret))


def reference(workload: Workload):
    """The pure-jnp oracle for an op family."""
    if workload.op == "matmul":
        from repro.kernels.matmul.ref import matmul_ref
        return matmul_ref
    if workload.op == "qmatmul":
        from repro.kernels.qmatmul.ref import qmatmul_ref
        return qmatmul_ref
    if workload.op == "gemv":
        from repro.kernels.gemv.ref import gemv_ref
        return gemv_ref
    if workload.op == "vmacc":
        from repro.kernels.vmacc.ref import vmacc_ref
        return vmacc_ref
    if workload.op == "attention":
        from repro.kernels.flash_attention.ref import attention_ref
        import functools
        return functools.partial(attention_ref,
                                 causal="causal" in workload.tags)
    raise ValueError(f"no reference for op {workload.op}")


def xla_baseline(workload: Workload):
    """XLA's own lowering of the op — the paper's compiler-autovectorization
    baseline (jitted jnp, no Pallas)."""
    import jax

    ref = reference(workload)
    return jax.jit(ref)
