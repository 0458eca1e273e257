"""Serving launcher: batched prefill+decode for any architecture.

By default the launcher serves the architecture's published configuration.
``--smoke`` serves its reduced configuration instead (small widths, for the
CPU tests and CI), and ``--layers N`` cuts the depth to N layers with every
width as published, so that a model fits one chip; the cut is printed.
``--check`` compares the served logits against a plain forward pass of the
same tokens in f32 at "highest" matmul precision, over the float32 master
weights (:func:`check_logits`), not the server's compute-dtype copy of them.

With ``--continuous-tune`` the launcher closes the serving↔tuning loop the
way a production deployment would: the server resolves each decode step's
workloads through the dispatch chain, records misses into a
:class:`~repro.core.traffic.TrafficLog`, a background
:class:`~repro.core.traffic.ContinuousTuner` tunes the hottest shapes and
saves the artifact, and the hot-swapping ``global_database()`` flips later
rounds' dispatch to ``"tuned"`` — same process, no restart. It tunes on the
chip (``DeviceRunner``) on a TPU, and against the analytic model of the
chip elsewhere.
"""

from __future__ import annotations

import argparse
import dataclasses
import os

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs import ARCH_IDS, get_config
from repro.configs.base import ArchConfig, ShapeSpec
from repro.core import (AnalyticRunner, ContinuousTuner, DeviceRunner,
                        TrafficLog, V5E, default_db_path,
                        reset_global_database)
from repro.models.model_zoo import build
from repro.runtime.compile_cache import enable_compile_cache
from repro.runtime.serve_loop import GenerationResult, Server, decode_ops

# Largest |served - reference| logit over the largest |reference| logit, per
# compared position. The server computes in bf16 (8 significant bits): each
# projection, norm and residual add rounds at about 2^-9, and over 8 layers
# that compounds to about 1.5% of the largest logit (a width-1024, 8-layer
# stand-in of yi-6b, bf16 vs this reference, on the CPU). 5% is three times
# that; a wrong kernel, cache slot or position gives errors of order 100%.
LOGIT_TOLERANCE = 0.05
# decode steps compared after the prefill's last position
CHECKED_DECODE_STEPS = 4


def serving_config(arch: str, smoke: bool = False,
                   layers: int | None = None) -> ArchConfig:
    """The configuration to serve: published, or reduced with ``smoke``;
    ``layers`` cuts the depth and keeps every width."""
    cfg = get_config(arch)
    if smoke:
        cfg = cfg.reduced()
    if layers is not None and layers < cfg.n_layers:
        cfg = dataclasses.replace(cfg, n_layers=layers)
    return cfg


def master_params(cfg: ArchConfig, seed: int = 0):
    """The seeded float32 master weights :func:`build_server` serves."""
    return jax.jit(build(cfg, remat="none").init)(jax.random.key(seed))


def build_server(cfg: ArchConfig, batch: int, prompt_len: int,
                 gen_steps: int, seed: int = 0, **server_kwargs):
    """A :class:`Server` over the seeded :func:`master_params` and a
    seeded batch of prompts: ``(server, prompts, extra_batch)``."""
    bundle = build(cfg, remat="none")
    server = Server(bundle, master_params(cfg, seed),
                    max_len=prompt_len + gen_steps + 1, **server_kwargs)
    inputs = bundle.make_batch(
        seed, ShapeSpec("serve", prompt_len, batch, "decode"), train=False)
    prompts = np.asarray(inputs.pop("tokens"))
    return server, prompts, inputs or None


def check_logits(server: Server, result: GenerationResult,
                 prompt_len: int, seed: int = 0) -> list[float]:
    """Normalized logit errors of the prefill's last position and the first
    ``CHECKED_DECODE_STEPS`` decode steps, against a plain forward pass of
    the same tokens in f32 at "highest" matmul precision over the float32
    :func:`master_params` of ``seed``, from which :func:`build_server` made
    the server (which keeps them cast to its compute dtype, so its own
    tree would hide their rounding)."""
    cfg = dataclasses.replace(server.bundle.cfg, dtype="float32")
    steps = min(len(result.logits), CHECKED_DECODE_STEPS + 1)
    tokens = jnp.asarray(result.tokens[:, :prompt_len + steps - 1])
    ref_bundle = build(cfg, remat="none")
    with jax.default_matmul_precision("highest"):
        ref = jax.jit(lambda p, t: ref_bundle.forward(p, {"tokens": t}))(
            master_params(cfg, seed), tokens)
    v = cfg.vocab_size
    errors = []
    for i in range(steps):
        want = ref[:, prompt_len - 1 + i, :v].astype(jnp.float32)
        got = result.logits[i][:, :v].astype(jnp.float32)
        errors.append(float(jnp.max(jnp.abs(got - want))
                            / jnp.max(jnp.abs(want))))
    return errors


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", choices=ARCH_IDS, default="yi_6b")
    ap.add_argument("--smoke", action="store_true",
                    help="serve the reduced configuration (CPU tests, CI)")
    ap.add_argument("--layers", type=int, default=None,
                    help="cut the depth to this many layers, widths kept")
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--prompt-len", type=int, default=16)
    ap.add_argument("--gen-steps", type=int, default=16)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--check", action="store_true",
                    help="compare the served logits with an f32 forward")
    ap.add_argument("--continuous-tune", action="store_true",
                    help="record dispatch misses and background-tune the "
                         "hottest shapes; the server hot-swaps the tuned "
                         "artifact between rounds")
    ap.add_argument("--rounds", type=int, default=3,
                    help="traffic rounds to serve in continuous-tune mode")
    ap.add_argument("--tune-db", default=None,
                    help="tuned-artifact path (default: REPRO_TUNING_DB "
                         "or tuned/database.json)")
    ap.add_argument("--tune-trials", type=int, default=16,
                    help="search trials per traffic shape per cycle")
    args = ap.parse_args()

    cache_dir = enable_compile_cache()
    cfg = serving_config(args.arch, args.smoke, args.layers)
    published = get_config(args.arch).n_layers
    depth = (f"{cfg.n_layers} of {published} layers"
             if cfg.n_layers < published and not args.smoke
             else f"{cfg.n_layers} layers")

    hw = serve_ops = traffic = tuner = None
    if args.continuous_tune:
        if args.tune_db:
            os.environ["REPRO_TUNING_DB"] = args.tune_db
        reset_global_database()
        on_tpu = jax.default_backend() == "tpu"
        runner = DeviceRunner() if on_tpu else AnalyticRunner(V5E)
        hw = runner.hw
        serve_ops = decode_ops(cfg, args.batch)
        traffic = TrafficLog()
        tuner = ContinuousTuner(traffic, hw, runner=runner,
                                db_path=default_db_path(),
                                trials_per_shape=args.tune_trials,
                                max_shapes_per_cycle=len(serve_ops),
                                seed=args.seed).start()

    server, prompts, extra = build_server(
        cfg, args.batch, args.prompt_len, args.gen_steps, args.seed,
        hw=hw, serve_ops=serve_ops, traffic=traffic)
    device = jax.devices()[0]
    print(f"arch={cfg.name} depth={depth} d_model={cfg.d_model} "
          f"batch={args.batch} prompt={args.prompt_len} "
          f"gen={args.gen_steps} device={device.platform}/"
          f"{device.device_kind} compile-cache={cache_dir}")
    rounds = args.rounds if args.continuous_tune else 1
    res = None
    for rnd in range(rounds):
        res = server.generate(prompts, args.gen_steps, extra_batch=extra)
        tok_s = args.batch * args.gen_steps / max(res.decode_s, 1e-9)
        line = (f"prefill {res.prefill_s * 1e3:.1f} ms; decode "
                f"{res.decode_s * 1e3:.1f} ms ({tok_s:.1f} tok/s)")
        if res.dispatch is not None:
            mix = " ".join(f"{k}={v}"
                           for k, v in sorted(res.dispatch.items()))
            line += f"; dispatch: {mix}"
        print(f"round {rnd}: {line}" if rounds > 1 else line)
        if tuner is not None:
            tuner.wait_idle(timeout=300.0)  # let the cycle land first
    if tuner is not None:
        tuner.stop()
        print(f"continuous tuning: {tuner.cycles} cycle(s), "
              f"{tuner.shapes_tuned} shape(s) -> {tuner.database.path}")
    print("sample:", res.tokens[0, : args.prompt_len + 8].tolist())
    if args.check:
        errors = check_logits(server, res, args.prompt_len, args.seed)
        print("logit error vs f32 reference (prefill, then decode steps): "
              + " ".join(f"{e:.4f}" for e in errors)
              + f" (tolerance {LOGIT_TOLERANCE})")
        if not max(errors) <= LOGIT_TOLERANCE:
            raise SystemExit("served logits disagree with the reference")


if __name__ == "__main__":
    main()
