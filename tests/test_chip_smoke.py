"""``chip_smoke.py``'s phases at a tiny size on the CPU.

The script needs a TPU, so the test steers the two seams it stands on: the
device ``DeviceRunner`` sees (a described v5e) and the kernel build
(interpret mode instead of compiled). What it checks is the control flow:
the phases run, report and pass or fail as on the chip. It measures nothing.
"""

import dataclasses
import sys
from types import SimpleNamespace

import pytest

import chip_smoke
from repro import kernels
from repro.configs import get_config
from repro.core import attention, qmatmul, vmacc
from repro.core import runner as runner_lib
from repro.runtime.serve_loop import decode_ops


@pytest.fixture
def fake_chip(monkeypatch):
    monkeypatch.setattr(runner_lib, "attached_device", lambda: SimpleNamespace(
        platform="tpu", device_kind="TPU v5 lite"))
    build = kernels.build
    monkeypatch.setattr(kernels, "build", lambda wl, p, interpret=True,
                        cache=None: build(wl, p, interpret=True, cache=cache))


def test_tune_phase_reports_every_family(fake_chip, capsys):
    tiny = dataclasses.replace(get_config("yi_6b"), d_model=256, n_heads=2,
                               n_kv_heads=1, d_ff=384, vocab_size=512)
    ops = (decode_ops(tiny, 1)[:1] + decode_ops(tiny, 4)[:1]
           + [(1, qmatmul(32, 256, 256)), (1, vmacc(16, 256, "bfloat16")),
              (1, attention(1, 2, 1, 128, 128, 128, "bfloat16"))])
    chip_smoke.phase_tune(ops, trials_per_op=2)
    lines = capsys.readouterr().out.splitlines()
    rows = [ln for ln in lines if ln.startswith("[tune] ") and "tuned=" in ln]
    assert [r.split()[1] for r in rows] == ["gemv", "matmul", "qmatmul",
                                            "vmacc", "attention"]
    assert all("INVALID" not in r for r in rows)
    assert "'wrong': 0" in lines[-1]


def test_tune_phase_fails_on_a_wrong_kernel(fake_chip, monkeypatch):
    import jax

    build = kernels.build
    monkeypatch.setattr(kernels, "build", lambda wl, p, **k: jax.jit(
        lambda *a: build(wl, p, interpret=True)(*a) * 2))
    with pytest.raises(SystemExit):
        chip_smoke.phase_tune([(1, vmacc(16, 256, "bfloat16"))],
                              trials_per_op=1)


def test_serve_phase_checks_logits(capsys):
    chip_smoke.phase_serve(smoke=True, layers=None, prompt_len=6,
                           gen_steps=3, requests=2)
    out = capsys.readouterr().out
    assert out.count("[serve] request") == 2
    assert "logit error vs f32 reference" in out


def test_no_tpu_fails_without_a_result(monkeypatch, capsys):
    monkeypatch.setattr(sys, "argv", ["chip_smoke.py"])
    with pytest.raises(SystemExit) as exc:
        chip_smoke.main()
    assert exc.value.code != 0
    assert '"ok"' not in capsys.readouterr().out
