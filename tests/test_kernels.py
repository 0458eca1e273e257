"""Per-kernel correctness: shape/dtype sweeps + hypothesis properties, all
validated in interpret mode against the pure-jnp oracles in ref.py."""

from types import SimpleNamespace

import numpy as np
import pytest
from jax.experimental.pallas import tpu as pltpu

try:
    from hypothesis import given, settings, strategies as st
except ImportError:  # optional dev dep: property tests skip, the rest run
    from _hypothesis_stub import given, settings, st

from repro import kernels
from repro.core import INTERPRET, TraceSampler, concretize, space_for
from repro.core import workload as W

HW = INTERPRET


def _run(wl, seed=0):
    space = space_for(wl, HW)
    s = TraceSampler(seed).sample(space)
    p = concretize(wl, HW, s)
    if not p.valid:
        pytest.skip("sampled schedule invalid for this workload")
    fn = kernels.build(wl, p, interpret=True)
    ref = kernels.reference(wl)
    inputs = wl.example_inputs(seed)
    got = np.asarray(fn(*inputs)).astype(np.float64)
    want = np.asarray(ref(*inputs)).astype(np.float64)
    return got, want


# ---------------------------------------------------------------- matmul ----

@pytest.mark.parametrize("m,n,k", [(8, 8, 8), (16, 128, 64), (100, 60, 36),
                                   (1, 256, 256), (128, 128, 384)])
@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
def test_matmul_sweep(m, n, k, dtype):
    got, want = _run(W.matmul(m, n, k, dtype))
    tol = 1e-4 if dtype == "float32" else 5e-2
    np.testing.assert_allclose(got, want, rtol=tol, atol=tol * 10)


@settings(max_examples=15, deadline=None)
@given(m=st.integers(1, 96), n=st.integers(1, 96), k=st.integers(1, 96),
       seed=st.integers(0, 3))
def test_matmul_property(m, n, k, seed):
    got, want = _run(W.matmul(m, n, k, "float32"), seed)
    np.testing.assert_allclose(got, want, rtol=1e-4, atol=1e-3)


# The legacy interpreter, and the one that keeps TPU memory semantics: it
# refuses an output block that is revisited out of order, which the
# compiled kernel would not read back from HBM.
INTERPRETERS = pytest.mark.parametrize(
    "interpret", [True, pltpu.InterpretParams()],
    ids=["legacy", "tpu_semantics"])


def _store_heavy(wl, blocks):
    """A store-heavy (accumulate=False) schedule: sampled when ``blocks`` is
    None, else on the widest variant with those tile splits pinned."""
    space = space_for(wl, HW)
    if blocks is None:
        s = TraceSampler(0).sample(space)
    else:
        names = ("bm", "bn", "bk") if wl.op == "matmul" else ("bn", "bk")
        pinned = dict(zip(names, blocks), variant=space["variant"][0])
        s = space.replay(pinned, TraceSampler(0).rng)
        assert tuple(s[n] for n in names) == blocks
    p = concretize(wl, HW, s.replace("accumulate", False))
    assert p.valid and not p.accumulate, p
    return p


def test_matmul_store_heavy_schedule_matches():
    """accumulate=False (k-outer, partials via HBM) must stay correct."""
    wl = W.matmul(64, 96, 160, "float32")
    fn = kernels.build(wl, _store_heavy(wl, None), interpret=True)
    x, w = wl.example_inputs()
    np.testing.assert_allclose(np.asarray(fn(x, w)), x @ w, rtol=1e-4,
                               atol=1e-3)


@INTERPRETERS
@pytest.mark.parametrize("m,n,blocks", [(64, 96, (16, 32, 32)),
                                        (64, 64, (64, 64, 32))],
                         ids=["12_out_blocks", "1_out_block"])
def test_matmul_store_heavy_blocks_match(interpret, m, n, blocks):
    """The store-heavy matmul with many output blocks (each revisited out
    of order) and with one (revisits back to back)."""
    wl = W.matmul(m, n, 160, "float32")
    p = _store_heavy(wl, blocks)
    fn = kernels.build(wl, p, interpret=interpret, cache=False)
    x, w = wl.example_inputs()
    np.testing.assert_allclose(np.asarray(fn(x, w)), x @ w, rtol=1e-4,
                               atol=1e-3)


@INTERPRETERS
@pytest.mark.parametrize("n,blocks", [(96, (32, 32)), (64, (64, 32))],
                         ids=["3_out_blocks", "1_out_block"])
def test_gemv_store_heavy_schedule_matches(interpret, n, blocks):
    """The gemv counterpart: partial output rows through HBM."""
    wl = W.gemv(n, 160)
    p = _store_heavy(wl, blocks)
    assert p.grid[1] > 1  # several k steps: the partials are revisited
    fn = kernels.build(wl, p, interpret=interpret, cache=False)
    x, w = wl.example_inputs()
    np.testing.assert_allclose(np.asarray(fn(x, w)), x @ w, rtol=1e-4,
                               atol=1e-3)


# ---------------------------------------------------------------- qmatmul ---

@pytest.mark.parametrize("m,n,k", [(16, 16, 32), (64, 48, 100), (33, 65, 17)])
def test_qmatmul_exact(m, n, k):
    wl = W.qmatmul(m, n, k)
    got, want = _run(wl)
    np.testing.assert_array_equal(got, want)  # int8 requant path is exact


# ------------------------------------------------------------------ gemv ----

@pytest.mark.parametrize("n,k", [(8, 8), (128, 512), (100, 300), (1, 64)])
def test_gemv_sweep(n, k):
    got, want = _run(W.gemv(n, k))
    np.testing.assert_allclose(got, want, rtol=1e-4, atol=1e-3)


@pytest.mark.parametrize("bn_tiles", [2, 4])
def test_gemv_bn_split_kernel_correct(bn_tiles):
    """The widened bn (output-row) split lowers and computes correctly:
    a multi-lane bn block — impossible before the split, when bn was
    variant-derived — matches the reference."""
    wl = W.gemv(64, 96)
    lane = HW.lane_align(wl.dtype)
    space = space_for(wl, HW)
    bn = bn_tiles * lane
    variant = next(v for v in space["variant"] if v != "j1")
    s = space.replay({"variant": variant, "bn": bn}, TraceSampler(0).rng)
    assert s["bn"] == bn  # the pinned split survived coherent replay
    from repro.kernels.gemv.ops import supports_block_shape
    assert supports_block_shape(bn, s["bk"], lane)
    p = concretize(wl, HW, s)
    assert p.valid, p.why_invalid
    assert p.block[0] == bn
    fn = kernels.build(wl, p, interpret=True)
    x, w = wl.example_inputs()
    np.testing.assert_allclose(np.asarray(fn(x, w)),
                               np.asarray(x, np.float32) @ w, rtol=1e-4,
                               atol=1e-3)


def test_gemv_j1_variant():
    """The paper's J=1 fallback intrinsic must be registered and correct.
    It matches single-row outputs only: the TPU lowers a (bk, 1) weight
    tile only when that row is the whole output."""
    from repro.core import intrinsics
    assert "j1" not in [v.name for v in
                        intrinsics.variants_for(W.gemv(96, 256), HW)]
    wl = W.gemv(1, 256)
    names = [v.name for v in intrinsics.variants_for(wl, HW)]
    assert "j1" in names
    space = space_for(wl, HW)
    s = TraceSampler(0).sample(space).replace("variant", "j1")
    p = concretize(wl, HW, s)
    assert p.valid and p.block[0] == 1, p
    fn = kernels.build(wl, p, interpret=True)
    x, w = wl.example_inputs()
    np.testing.assert_allclose(np.asarray(fn(x, w)),
                               np.asarray(x, np.float32) @ w, rtol=1e-4,
                               atol=1e-3)


# ----------------------------------------------------------------- vmacc ----

@settings(max_examples=10, deadline=None)
@given(r=st.integers(1, 70), c=st.integers(1, 200), seed=st.integers(0, 3))
def test_vmacc_property(r, c, seed):
    got, want = _run(W.vmacc(r, c), seed)
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-5)


# ------------------------------------------------------------- attention ----

@pytest.mark.parametrize("b,hq,hkv,ql,kl,d", [
    (1, 2, 2, 32, 32, 16),     # MHA
    (2, 4, 2, 64, 64, 32),     # GQA group 2
    (1, 8, 1, 48, 48, 64),     # MQA, ragged seq
    (1, 2, 1, 17, 33, 8),      # non-aligned, cross lengths
])
def test_attention_causal_sweep(b, hq, hkv, ql, kl, d):
    wl = W.attention(b, hq, hkv, ql, kl, d, causal=True)
    got, want = _run(wl)
    np.testing.assert_allclose(got, want, rtol=2e-3, atol=2e-3)


def test_attention_non_causal():
    wl = W.attention(2, 2, 2, 24, 40, 16, causal=False)
    got, want = _run(wl)
    np.testing.assert_allclose(got, want, rtol=2e-3, atol=2e-3)


@pytest.mark.slow
def test_attention_all_variants_agree():
    """Every registered (block_q, block_kv) granularity computes the same
    attention — the multi-VL registration is semantics-preserving."""
    wl = W.attention(1, 2, 1, 40, 40, 16, causal=True)
    space = space_for(wl, HW)
    ref = kernels.reference(wl)
    inputs = wl.example_inputs()
    want = np.asarray(ref(*inputs))
    for name in space["variant"]:
        from repro.core.schedule import Schedule
        p = concretize(wl, HW, Schedule.fixed(variant=name))
        fn = kernels.build(wl, p, interpret=True)
        np.testing.assert_allclose(np.asarray(fn(*inputs)), want, rtol=2e-3,
                                   atol=2e-3, err_msg=name)


# ----------------------------------------------------- xla baseline parity --

@pytest.mark.parametrize("op", ["matmul", "gemv", "vmacc"])
def test_xla_baseline_matches_reference(op):
    wl = {"matmul": W.matmul(32, 48, 64),
          "gemv": W.gemv(48, 96),
          "vmacc": W.vmacc(24, 36)}[op]
    fn = kernels.xla_baseline(wl)
    ref = kernels.reference(wl)
    inputs = wl.example_inputs()
    np.testing.assert_allclose(np.asarray(fn(*inputs)),
                               np.asarray(ref(*inputs)), rtol=1e-5,
                               atol=1e-5)


# ------------------------------------------------------------- chip path ----
# DeviceRunner decides from the device JAX reports; the tests steer that
# seam of core/runner.py.

def _fake_v5e(monkeypatch):
    from repro.core import runner as runner_lib

    monkeypatch.setattr(runner_lib, "attached_device", lambda: SimpleNamespace(
        platform="tpu", device_kind="TPU v5 lite"))


def test_device_runner_refuses_cpu_backend():
    from repro.core import DeviceRunner

    with pytest.raises(RuntimeError, match="needs a TPU"):
        DeviceRunner()


def test_unknown_device_kind_raises(monkeypatch):
    from repro.core import V5E, DeviceRunner
    from repro.core import runner as runner_lib
    from repro.core.hardware import for_device_kind

    assert for_device_kind("TPU v5 lite") is V5E
    monkeypatch.setattr(runner_lib, "attached_device", lambda: SimpleNamespace(
        platform="tpu", device_kind="TPU v99"))
    with pytest.raises(ValueError, match="no hardware configuration"):
        DeviceRunner()


def test_device_runner_checks_counts_and_never_times_a_wrong_kernel(
        monkeypatch):
    """The measurement logic, with kernels built in interpret mode: a
    correct kernel is timed and its error recorded; a refused build is
    counted by reason; a wrong result is counted and INVALID."""
    import math

    import jax

    from repro.core import (V5E, DeviceRunner, Schedule,
                            fixed_library_schedule)
    from repro.core.runner import INVALID, TOLERANCE

    _fake_v5e(monkeypatch)
    build = kernels.build
    monkeypatch.setattr(kernels, "build", lambda wl, p, interpret=True,
                        cache=None: build(wl, p, interpret=True, cache=cache))
    runner = DeviceRunner()
    assert runner.hw is V5E
    wl = W.gemv(256, 512, "bfloat16")
    assert math.isfinite(runner.run(wl, fixed_library_schedule(wl, V5E)))
    assert 0.0 <= runner.max_error(wl) <= TOLERANCE["bfloat16"]

    def sched(bk, bn=128):  # kernels other than the library's
        return Schedule.fixed(variant="vl_512", bk=bk, bn=bn,
                              accumulate=True)

    def refuse(msg):
        def fail(*a, **k):
            raise RuntimeError(msg)
        return fail

    monkeypatch.setattr(kernels, "build", refuse(
        "Ran out of memory in memory space vmem"))
    assert runner.run(wl, sched(128)) == INVALID
    monkeypatch.setattr(kernels, "build", refuse(
        "block shape must be divisible by 8 and 128"))
    assert runner.run(wl, sched(256)) == INVALID
    monkeypatch.setattr(kernels, "build", lambda wl_, p, **k: jax.jit(
        lambda x, w: build(wl_, p, interpret=True)(x, w) + 1.0))
    assert runner.run(wl, sched(512, bn=256)) == INVALID
    assert runner.failures(wl) == {"vmem": 1, "alignment": 1, "other": 0,
                                   "wrong": 1}
    assert runner.first_refusal == {
        "vmem": "RuntimeError: Ran out of memory in memory space vmem",
        "alignment": "RuntimeError: block shape must be divisible by 8 and "
                     "128"}


def test_device_runner_spans_split_each_candidate(monkeypatch):
    """Traced, a valid candidate's run is split into inputs, compile,
    reference, check and time; a refused one records only its compile; a
    signature compiled before is counted as reused."""
    from repro.core import V5E, DeviceRunner, Schedule, fixed_library_schedule
    from repro.core import tracing

    _fake_v5e(monkeypatch)
    build = kernels.build
    monkeypatch.setattr(kernels, "build", lambda wl, p, interpret=True,
                        cache=None: build(wl, p, interpret=True, cache=cache))
    runner = DeviceRunner()
    wl = W.gemv(256, 512, "bfloat16")
    library = fixed_library_schedule(wl, V5E)
    with tracing.enabled() as valid:
        runner.run(wl, library)
    spans = valid.summary()["spans"]
    phases = ["repro.runner.inputs", "repro.runner.compile",
              "repro.runner.reference", "repro.runner.check",
              "repro.runner.time"]
    assert sorted(spans) == sorted(phases + ["repro.runner.run"])
    assert all(spans[n]["count"] == 1 for n in spans)
    run = spans["repro.runner.run"]
    assert run["self_s"] == pytest.approx(
        run["total_s"] - sum(spans[n]["total_s"] for n in phases))

    def refuse(*a, **k):
        raise RuntimeError("Ran out of memory in memory space vmem")

    monkeypatch.setattr(kernels, "build", refuse)
    with tracing.enabled() as refused:
        runner.run(wl, Schedule.fixed(variant="vl_512", bk=128, bn=128,
                                      accumulate=True))
        runner.run(wl, library)
    s = refused.summary()
    assert {n: v["count"] for n, v in s["spans"].items()} == {
        "repro.runner.run": 2, "repro.runner.compile": 1,
        "repro.runner.time": 1}
    assert s["counters"] == {"repro.runner.reused": 1}
