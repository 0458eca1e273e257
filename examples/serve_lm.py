"""Batched-serving example: prefill a batch of prompts, decode greedily,
report prefill latency and decode throughput. Exercises the same
prefill_fn/decode_fn the multi-pod dry-run lowers as ``serve_step``.

With ``--continuous-tune`` the example also demonstrates the
serving↔tuning loop synchronously and in-process: the first generate
dispatches every decode workload through the fixed library (cold
database) while recording the misses, one ContinuousTuner cycle tunes the
recorded shapes against the shared in-memory database, and the second
generate resolves them with "tuned" provenance — no restart, no files.

Run:  python examples/serve_lm.py [--arch mamba2_780m]
      python examples/serve_lm.py --continuous-tune
"""

import argparse
import os
import sys

import jax
import numpy as np

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))

from repro.configs import ARCH_IDS, get_config
from repro.configs.base import ShapeSpec
from repro.core import (AnalyticRunner, ContinuousTuner, TrafficLog,
                        TuningDatabase, V5E)
from repro.models.model_zoo import build
from repro.runtime.serve_loop import Server, decode_ops


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", choices=ARCH_IDS, default="gemma3_1b")
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--prompt-len", type=int, default=32)
    ap.add_argument("--gen-steps", type=int, default=32)
    ap.add_argument("--continuous-tune", action="store_true",
                    help="demo the miss-record -> tune -> re-dispatch loop")
    ap.add_argument("--tune-trials", type=int, default=16)
    args = ap.parse_args()

    cfg = get_config(args.arch).reduced()
    bundle = build(cfg, remat="none")
    params = bundle.init(jax.random.key(0))

    hw = serve_ops = traffic = database = None
    if args.continuous_tune:
        hw = V5E
        serve_ops = decode_ops(cfg, args.batch)
        traffic = TrafficLog()
        database = TuningDatabase()  # in-memory, shared with the tuner
    server = Server(bundle, params,
                    max_len=args.prompt_len + args.gen_steps + 1,
                    hw=hw, serve_ops=serve_ops, traffic=traffic,
                    database=database)

    batch = bundle.make_batch(
        7, ShapeSpec("serve", args.prompt_len, args.batch, "decode"),
        train=False)
    prompts = np.asarray(batch.pop("tokens"))
    res = server.generate(prompts, args.gen_steps,
                          extra_batch=batch or None)

    tok_s = args.batch * args.gen_steps / max(res.decode_s, 1e-9)
    print(f"arch={cfg.name} ({cfg.family}) batch={args.batch}")
    print(f"prefill({args.prompt_len} tok): {res.prefill_s * 1e3:8.1f} ms")
    print(f"decode ({args.gen_steps} steps): {res.decode_s * 1e3:8.1f} ms "
          f"= {tok_s:.1f} tok/s")
    for row in res.tokens[:2]:
        print("  gen:", row[args.prompt_len:args.prompt_len + 12].tolist())

    if args.continuous_tune:
        def mix(d):
            return " ".join(f"{k}={v}" for k, v in sorted(d.items()))

        print(f"cold dispatch: {mix(res.dispatch)} "
              f"({traffic.pending(hw.name)} miss shape(s) recorded)")
        # the analytic model of the chip: this example runs on the CPU
        tuner = ContinuousTuner(traffic, hw, runner=AnalyticRunner(hw),
                                database=database,
                                trials_per_shape=args.tune_trials,
                                max_shapes_per_cycle=len(serve_ops))
        tuner.tune_once()
        res = server.generate(prompts, args.gen_steps,
                              extra_batch=batch or None)
        print(f"after {tuner.shapes_tuned}-shape tuning cycle: "
              f"{mix(res.dispatch)}")


if __name__ == "__main__":
    main()
