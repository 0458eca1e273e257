"""Readings for the correctness limits, on the chip, in one process.

    python bench/calibrate.py --workload <cell> --seeds <n> [--first <seed>]

For each seed it drives the cell's timed path for a short window (one
tuning round, or one batch) and prints, as one JSON line, the numbers the
run compares: the program's, against the plain reference, and the
control's, which is the reference computed one precision below what the
configuration states (int4 below int8, float8 below bfloat16). A limit
lies between the largest program reading and the smallest control
reading. The benchmark's own runs never run this.
"""

import os
import sys
import time

_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path[:0] = [_ROOT, os.path.join(_ROOT, "src")]


def tune_readings(cell, seed, peaks):
    from bench.drivers import tune
    from bench.lib import netops
    from bench.lib.spans import Spans

    ops = netops.forward_ops(cell.config, cell.traffic)
    inputs = tune.make_inputs(ops, cell.traffic, seed)
    tune.persistent_cache(False)
    db, counters, _, hw = tune.tune_window(ops, cell.traffic, seed, 0.0,
                                           Spans())
    fns, provenance = tune.resolved_fns(ops, db, hw, "tuned")
    outs = tune.build_pass(ops, fns)(inputs, 1)
    want = tune.reference_outputs(ops, inputs, cell.config, {})
    control = tune.reference_outputs(ops, inputs, cell.config, tune.CONTROL)
    return {"program": tune.compare(ops, outs, want),
            "control": tune.compare(ops, control, want),
            "provenance": provenance, "trials": counters["tune.trials"],
            "wrong": counters["tune.wrong"]}


def serve_readings(cell, seed, peaks):
    import gc

    import jax.numpy as jnp

    from bench.drivers import serve
    from bench.refs import precision

    cfg, traffic = cell.config, cell.traffic
    server = serve.make_server(cfg, traffic, seed)
    res = server.generate(next(serve.prompts(cfg, traffic, seed, 0)),
                          traffic["gen_len"])
    served = [res.tokens]
    del res, server
    gc.collect()
    tokens = serve.sample_requests(served, traffic, seed)
    p = traffic["prompt_len"]
    ref = serve.reference_logits(tokens, cfg, traffic, seed)
    low = serve.reference_logits(tokens, cfg, traffic, seed,
                                 cast=precision.float8)
    program = float(serve.logit_gaps(ref, tokens[:, p:]).max())
    control = float(serve.logit_gaps(ref, jnp.argmax(low, -1)).max())
    return {"program": {"served_logit_gap": program},
            "control": {"served_logit_gap": control}}


def main(argv=None):
    import argparse
    import json

    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, default=12)
    ap.add_argument("--first", type=int, default=1_000_003)
    args = ap.parse_args(argv)

    from bench.lib import harness
    from bench.lib.peaks import peaks_for

    cell = harness.load_cell(args.workload)
    harness.prepare_environment()
    devices = harness.require_devices(cell.chips)
    peaks = peaks_for(devices[0].device_kind)
    if cell.driver == "serve":
        from repro.runtime.compile_cache import enable_compile_cache

        enable_compile_cache()
    reading = {"tune": tune_readings, "serve": serve_readings}[cell.driver]
    for i in range(args.seeds):
        seed = args.first + 7919 * i
        t0 = time.perf_counter()
        out = reading(cell, seed, peaks)
        out.update(cell=cell.name, seed=seed,
                   seconds=round(time.perf_counter() - t0, 3))
        print(json.dumps(out), flush=True)


if __name__ == "__main__":
    main()
