"""Small cells for the CPU tests: the drivers run end to end at smoke size,
with Pallas kernels in interpret mode and the chip's look skipped."""

from __future__ import annotations

import time
from types import SimpleNamespace

from bench.lib import harness
from bench.lib.peaks import peaks_for

TINY_NET = {
    "name": "tiny_net", "n_layers": 2, "d_model": 128, "n_heads": 2,
    "n_kv_heads": 1, "head_dim": 64, "d_ff": 256, "vocab_size": 256,
    "tie_embeddings": True, "act": "silu", "projection_dtype": "int8",
    "attention_dtype": "float32", "requant_scale": 0.01,
}
TINY_TUNE = {
    "driver": "tune", "seq": 32, "projection": "qmatmul",
    "families": ["q", "kv", "o", "gate_up", "down", "lm_head"],
    "trials_per_op": 1, "int8_range": 20, "bias_range": 1000,
    "pass_seconds": 0.0, "traced_passes": 2,
}
TINY_LM = {
    "name": "tiny_lm", "program_arch": "yi_6b", "n_layers": 2,
    "d_model": 64, "n_heads": 4, "n_kv_heads": 2, "head_dim": 16,
    "d_ff": 128, "vocab_size": 256, "rope_theta": 10000.0,
    "norm_eps": 1e-5, "tie_embeddings": False, "act": "silu",
    "compute_dtype": "float32", "weights_dtype": "float32",
}
TINY_SERVE = {"driver": "serve", "batch": 4, "prompt_len": 8, "gen_len": 4,
              "check_requests": 4}


def cell(config, traffic, limits, name="tiny"):
    return harness.Cell(name, config["name"], config, "tiny-traffic",
                        traffic, limits, 1, [], [])


def context(c, seed=7, seconds=0.0, trace=False):
    import jax

    return harness.Context(c, seed, seconds, trace, time.perf_counter(),
                           peaks_for("TPU v5 lite"), jax.devices()[:1])


def fake_chip(monkeypatch):
    """The program's runner sees a described v5e, and kernels build for the
    interpreter."""
    from repro import kernels
    from repro.core import runner as runner_lib

    monkeypatch.setattr(runner_lib, "attached_device",
                        lambda: SimpleNamespace(platform="tpu",
                                                device_kind="TPU v5 lite"))
    build = kernels.build
    monkeypatch.setattr(kernels, "build",
                        lambda wl, p, interpret=True, cache=None:
                        build(wl, p, interpret=True, cache=cache))
