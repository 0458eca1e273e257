"""Start-up check on one TPU: tune a network's operators, then serve it.

Runs the paper's flow through the normal entry points, in one process (one
process holds the chip):

1. device: print what JAX sees; fail when it is not a TPU;
2. compile cache: place JAX's persistent cache (``runtime/compile_cache``);
3. tune: a ``TuningSession`` on ``DeviceRunner`` over yi-6b's decode
   operators at published widths — gemv at batch 1, matmul at batch 4 —
   plus one qmatmul, vmacc and attention workload, so that all five kernel
   families run compiled; per operator, the tuned, library and XLA device
   latency and the largest error against the reference;
4. serve: the ``launch/serve.py`` server on yi-6b at published widths, cut
   to 8 of its 32 layers (f32 weights of 32 layers do not fit 16 GB),
   answering a few batched requests; its logits checked against an f32
   forward pass of the same tokens.

The last line of standard output is a JSON object naming the device; it is
printed only when every phase passed. Any failure exits non-zero.

Run:  python chip_smoke.py      (on a machine with one TPU)
"""

from __future__ import annotations

import json
import math
import os
import sys

ROOT = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.join(ROOT, "src"))

TRIALS_PER_OP = 4  # tuning trials per operator
SERVE_LAYERS = 8  # of yi-6b's 32: what one chip holds in f32
SERVE_BATCH = 4
PROMPT_LEN = 32
GEN_STEPS = 8
REQUESTS = 3


def fail(msg: str) -> None:
    print(f"FAIL: {msg}", file=sys.stderr, flush=True)
    sys.exit(1)


def phase_device():
    import jax

    devices = jax.devices()
    dev = devices[0]
    print(f"[device] platform={dev.platform} kind={dev.device_kind} "
          f"count={len(devices)}", flush=True)
    if dev.platform != "tpu":
        fail(f"no TPU present: JAX's first device is {dev.platform}")
    return dev, len(devices)


def phase_cache():
    from repro.runtime.compile_cache import (compile_cache_stats,
                                             enable_compile_cache)

    print(f"[cache] dir={enable_compile_cache()} "
          "min_compile_time_secs=0", flush=True)
    return compile_cache_stats


def _us(seconds: float) -> str:
    return f"{seconds * 1e6:.1f}us" if math.isfinite(seconds) else "INVALID"


def phase_tune(ops, trials_per_op: int = TRIALS_PER_OP):
    """Tune ``ops`` on the chip; print one line per operator and the
    session's failure counts. Returns the runner (its inputs released)."""
    from repro.core import (DeviceRunner, TuningDatabase, TuningSession,
                            fixed_library_schedule, xla_latency)
    from repro.core.runner import TOLERANCE

    runner = DeviceRunner()
    session = TuningSession(runner.hw, runner, database=TuningDatabase())
    result = session.tune_model(ops, total_trials=trials_per_op * len(ops),
                                seed=0, model="yi-6b")
    bad = []
    for rep in result.reports:
        wl = rep.workload
        library = runner.run(wl, fixed_library_schedule(wl, runner.hw))
        xla = xla_latency(wl, repeats=runner.REPEATS)
        err = runner.max_error(wl)
        tol = TOLERANCE.get(wl.dtype, 0.0)
        print(f"[tune] {wl.op:9s} {'x'.join(map(str, wl.dims)):22s} "
              f"{wl.dtype:8s} tuned={_us(rep.best_latency)} "
              f"library={_us(library)} xla={_us(xla)} "
              f"max_err={err:.3g} (tol {tol:.3g}) trials={rep.trials} "
              f"failures={runner.failures(wl)}", flush=True)
        if not (math.isfinite(rep.best_latency) and math.isfinite(library)
                and math.isfinite(xla) and err <= tol):
            bad.append(wl.key())
    print(f"[tune] session failures={result.failures} "
          f"trials={result.total_trials} wall={result.wall_time_s:.1f}s",
          flush=True)
    for reason, message in sorted(runner.first_refusal.items()):
        print(f"[tune] first {reason} refusal: {message}", flush=True)
    if result.failures["wrong"]:
        fail(f"{result.failures['wrong']} kernel(s) disagree with "
             "their reference")
    if bad:
        fail(f"no valid tuned/library/XLA measurement for {bad}")
    runner.clear()
    return runner


def phase_serve(arch: str = "yi_6b", layers: int | None = SERVE_LAYERS,
                batch: int = SERVE_BATCH, prompt_len: int = PROMPT_LEN,
                gen_steps: int = GEN_STEPS, requests: int = REQUESTS,
                smoke: bool = False):
    """Serve ``requests`` batches through the launcher's server and check
    the last one's logits against the f32 reference."""
    import numpy as np

    from repro.configs import get_config
    from repro.launch.serve import (LOGIT_TOLERANCE, build_server,
                                    check_logits, serving_config)

    cfg = serving_config(arch, smoke=smoke, layers=layers)
    published = get_config(arch)
    print(f"[serve] {cfg.name} depth={cfg.n_layers} of {published.n_layers} "
          f"layers, d_model={cfg.d_model} d_ff={cfg.d_ff} "
          f"heads={cfg.n_heads}:{cfg.n_kv_heads} vocab={cfg.vocab_size} "
          f"batch={batch} prompt={prompt_len} gen={gen_steps}", flush=True)
    server, prompts, extra = build_server(cfg, batch, prompt_len, gen_steps)
    rng = np.random.default_rng(1)
    res = None
    for i in range(requests):
        if i:
            prompts = rng.integers(0, cfg.vocab_size,
                                   size=prompts.shape).astype(np.int32)
        res = server.generate(prompts, gen_steps, extra_batch=extra)
        note = " (includes compilation)" if i == 0 else ""
        print(f"[serve] request {i}: prefill={res.prefill_s * 1e3:.2f}ms "
              f"decode={res.decode_s * 1e3:.2f}ms for {gen_steps - 1} "
              f"steps{note}", flush=True)
    errors = check_logits(server, res, prompt_len)
    print("[serve] logit error vs f32 reference (prefill, decode steps): "
          + " ".join(f"{e:.4g}" for e in errors)
          + f" (tol {LOGIT_TOLERANCE})", flush=True)
    if not max(errors) <= LOGIT_TOLERANCE:
        fail("served logits disagree with the f32 reference")


def main() -> None:
    try:
        from repro.configs import get_config
        from repro.core import attention, qmatmul, vmacc
        from repro.runtime.serve_loop import decode_ops
    except ImportError as exc:
        fail(f"the repository's src/ is not next to this script ({exc})")

    dev, count = phase_device()
    cache_stats = phase_cache()
    yi = get_config("yi_6b")
    ops = (decode_ops(yi, 1) + decode_ops(yi, 4)
           + [(1, qmatmul(256, 4096, 4096)),
              (1, vmacc(512, 4096, "bfloat16")),
              (1, attention(1, 32, 4, 512, 512, 128, "bfloat16"))])
    phase_tune(ops)
    print(f"[cache] after tuning: {cache_stats()}", flush=True)
    phase_serve()
    print(f"[cache] after serving: {cache_stats()}", flush=True)
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind, "count": count}}))


if __name__ == "__main__":
    main()
