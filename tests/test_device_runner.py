"""The chip's measurement path on the CPU.

``DeviceRunner`` runs here on a faked v5e: JAX's first device is reported
as a ``TPU v5 lite`` and ``kernels.build`` builds in interpret mode, so the
runner's own logic — compile once, check the first result against the
reference, time only a correct kernel, count refusals — runs unchanged.
Covers the library schedule of every kernel family, the refusal and
wrong-result counting per family, and a serial ``TuningSession`` on the
runner whose records resolve through dispatch and sit beside a session
summary saved before the simulated measurement farm was removed.
"""

import json
import math
from types import SimpleNamespace

import jax
import pytest

from repro import kernels
from repro.core import (V5E, DeviceRunner, TuningDatabase, TuningSession,
                        best_schedule, fixed_library_schedule, kernel_params,
                        tracing)
from repro.core import runner as runner_lib
from repro.core import workload as W
from repro.core.runner import INVALID, TOLERANCE

_BUILD = kernels.build


def _interpret_build(wl, p, interpret=True, cache=None):
    return _BUILD(wl, p, interpret=True, cache=cache)


def _fake_v5e(mp):
    mp.setattr(runner_lib, "attached_device", lambda: SimpleNamespace(
        platform="tpu", device_kind="TPU v5 lite"))
    mp.setattr(kernels, "build", _interpret_build)


@pytest.fixture
def runner(monkeypatch):
    _fake_v5e(monkeypatch)
    return DeviceRunner()


# One small workload per kernel family and compute dtype; the integer
# family must match its reference exactly.
LIBRARY_CASES = [
    W.qmatmul(64, 256, 256),
    W.matmul(128, 256, 256, "float32"),
    W.matmul(128, 256, 256, "bfloat16"),
    W.gemv(256, 512, "float32"),
    W.gemv(256, 512, "bfloat16"),
    W.vmacc(64, 256, "float32"),
    W.vmacc(64, 256, "bfloat16"),
    W.attention(1, 2, 1, 128, 128, 64, "float32"),
    W.attention(1, 2, 1, 128, 128, 64, "bfloat16"),
]

FAMILY_CASES = [
    W.qmatmul(64, 256, 256),
    W.matmul(128, 256, 256, "bfloat16"),
    W.gemv(256, 512, "bfloat16"),
    W.vmacc(64, 256, "bfloat16"),
    W.attention(1, 2, 1, 128, 128, 64, "bfloat16"),
]


def _case_id(wl):
    return f"{wl.op}-{wl.dtype}"


@pytest.mark.parametrize("wl", LIBRARY_CASES, ids=_case_id)
def test_device_runner_times_library_schedule_within_tolerance(runner, wl):
    library = fixed_library_schedule(wl, V5E)
    with tracing.enabled() as rec:
        latency = runner.run(wl, library)
    assert math.isfinite(latency) and latency > 0
    err = runner.max_error(wl)
    if wl.op == "qmatmul":
        assert err == 0.0
    else:
        assert 0.0 <= err <= TOLERANCE[wl.dtype]
    assert runner.failures(wl) == {"vmem": 0, "alignment": 0, "other": 0,
                                   "wrong": 0}
    assert rec.summary()["spans"]["repro.runner.time"]["count"] == 1
    # a second run reuses the compiled kernel
    with tracing.enabled() as again:
        assert math.isfinite(runner.run(wl, library))
    assert again.summary()["counters"] == {"repro.runner.reused": 1}


def _refuse(*args, **kwargs):
    raise RuntimeError("Ran out of memory in memory space vmem")


def _wrong(wl, p, **kwargs):
    fn = _interpret_build(wl, p)
    return jax.jit(lambda *xs: fn(*xs) + 1)


@pytest.mark.parametrize("fault", ["refused", "wrong"])
@pytest.mark.parametrize("wl", FAMILY_CASES, ids=_case_id)
def test_device_runner_counts_fault_and_never_times_it(runner, monkeypatch,
                                                       wl, fault):
    """A refused build is counted by its reason, a wrong first result as
    ``wrong``; either way the candidate is INVALID and never timed, and the
    failure is not compiled again."""
    monkeypatch.setattr(kernels, "build",
                        _refuse if fault == "refused" else _wrong)
    library = fixed_library_schedule(wl, V5E)
    with tracing.enabled() as rec:
        assert runner.run(wl, library) == INVALID
        assert runner.run(wl, library) == INVALID
    spans = rec.summary()["spans"]
    assert "repro.runner.time" not in spans
    assert spans["repro.runner.compile"]["count"] == 1
    reason = "vmem" if fault == "refused" else "wrong"
    expected = {"vmem": 0, "alignment": 0, "other": 0, "wrong": 0}
    expected[reason] = 1
    assert runner.failures(wl) == expected
    assert math.isnan(runner.max_error(wl))


# ----------------------------------------------- a session on the runner ----

SESSION_OPS = [(1, W.qmatmul(16, 128, 256)), (2, W.qmatmul(16, 256, 128))]


@pytest.fixture(scope="module")
def device_session():
    """One serial session on the faked v5e, shared by the cases below."""
    with pytest.MonkeyPatch.context() as mp:
        _fake_v5e(mp)
        db = TuningDatabase()
        runner = DeviceRunner()
        result = TuningSession(V5E, runner, database=db, batch=4,
                               min_trials=4).tune_model(
            SESSION_OPS, total_trials=8, seed=0, model="device-qmatmul")
    return db, runner, result


def test_device_session_records_land_in_database(device_session):
    db, runner, result = device_session
    assert not result.interleaved and result.runner_name == "device"
    assert all(r.trials >= 1 for r in result.reports)
    assert result.total_trials <= 8
    for (_, wl), report in zip(SESSION_OPS, result.reports):
        records = db.history(wl, V5E.name)
        assert records and {r["runner"] for r in records} == {"device"}
        schedule, latency = db.best(wl, V5E.name)
        assert latency == report.best_latency and math.isfinite(latency)
        assert math.isfinite(report.fixed_latency)
        assert runner.max_error(wl) == 0.0  # int8: exact or refused
    assert result.failures is not None
    assert set(result.failures) == {"vmem", "alignment", "other", "wrong"}
    assert db.sessions[-1]["runner"] == "device"


def test_device_session_records_resolve_through_dispatch(device_session):
    db, _, result = device_session
    for (_, wl), report in zip(SESSION_OPS, result.reports):
        schedule, provenance = best_schedule(wl, V5E, database=db)
        assert provenance == "tuned"
        assert schedule.signature() == report.best_schedule.signature()
        params, provenance = kernel_params(wl, V5E, database=db)
        assert provenance == "tuned" and params.valid


def test_session_summary_saved_before_farm_removal_still_loads(
        device_session, tmp_path):
    """A database whose stored session summary still carries the removed
    farm keys loads, keeps that summary as data, and takes new sessions
    beside it; readers of the summaries pass over the extra keys."""
    from benchmarks.run import session_report

    db, _, result = device_session
    path = tmp_path / "db.json"
    db.save(str(path))
    payload = json.loads(path.read_text())
    old = dict(payload["sessions"][-1], model="device-qmatmul-old",
               multi_queue=True, preemptions=2,
               board_stats={"boards": {"b0": {"utilization": 0.5}},
                            "requeues": 1})
    payload["sessions"].insert(0, old)
    path.write_text(json.dumps(payload))

    loaded = TuningDatabase(str(path))
    assert len(loaded.sessions) == 2
    assert loaded.sessions[0]["board_stats"]["requeues"] == 1
    current = result.summary()
    assert not {"multi_queue", "board_stats", "preemptions"} & set(current)
    loaded.add_session(current)
    loaded.save()
    reloaded = TuningDatabase(str(path))
    assert len(reloaded.sessions) == 3
    for _, wl in SESSION_OPS:
        assert best_schedule(wl, V5E, database=reloaded)[1] == "tuned"
    names = [name for name, _, _ in session_report(reloaded)]
    assert "report/device-qmatmul-old/session0" in names
    assert "report/device-qmatmul/session1" in names
