"""Readings for the ``served_logit_gap`` and ``served_logit_gap_mean``
limits of a ``serve_mla_moe`` cell, on the chip, in one process.

    python bench/calibrate_mla_moe.py --workload <cell> --seeds <n> \
        [--first <seed>]

For each seed it serves one batch of the cell's traffic through the
program's ``Server`` over that seed's weights, checks the requests the
benchmark would sample, and prints one JSON line: the program's gaps
against the plain reference, and the control's, the tokens the reference
computed in float8 (one precision below the configuration's bfloat16)
would serve. A limit lies between the largest program reading and the
smallest control reading, where the two do not overlap. The benchmark's
own runs never run this.
"""

import os
import sys
import time

_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path[:0] = [_ROOT, os.path.join(_ROOT, "src")]


def readings(cell, seed: int) -> dict:
    import gc

    import jax.numpy as jnp

    from bench.drivers import serve, serve_mla_moe as drv
    from bench.refs import precision

    cfg, traffic = cell.config, cell.traffic
    server = drv.make_server(cfg, traffic, seed)
    res = server.generate(next(serve.prompts(cfg, traffic, seed, 0)),
                          traffic["gen_len"])
    served = [res.tokens]
    del res, server
    gc.collect()
    tokens = serve.sample_requests(served, traffic, seed)
    ref = drv.reference_logits(tokens, cfg, traffic, seed)
    low = drv.reference_logits(tokens, cfg, traffic, seed,
                               cast=precision.float8)
    return {"program": drv.gaps(ref, tokens[:, traffic["prompt_len"]:]),
            "control": drv.gaps(ref, jnp.argmax(low, -1))}


def main(argv=None):
    import argparse
    import json

    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, default=12)
    ap.add_argument("--first", type=int, default=1_000_003)
    args = ap.parse_args(argv)

    from bench.lib import harness
    from repro.runtime.compile_cache import enable_compile_cache

    cell = harness.load_cell(args.workload)
    harness.prepare_environment()
    harness.require_devices(cell.chips)
    enable_compile_cache()
    for i in range(args.seeds):
        seed = args.first + 7919 * i
        t0 = time.perf_counter()
        out = readings(cell, seed)
        out.update(cell=cell.name, seed=seed,
                   seconds=round(time.perf_counter() - t0, 3))
        print(json.dumps(out), flush=True)


if __name__ == "__main__":
    main()
