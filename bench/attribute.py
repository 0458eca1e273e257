"""Where a cell's traced window leaves the device idle, by the program's
spans, on the chip.

    python bench/attribute.py --workload <cell> [--seed <n>] [--repeat <n>]

It runs the cell's set-up and then the stretch a ``--trace 1`` run
profiles (one tuning round after set-up's own, or one batch after the
warm-up), under the profiler, ``--repeat`` times. For each it prints one
JSON line: the stretch's seconds and idle device seconds, the idle time
put down to the innermost program span around each idle moment and the
part of it inside a leaf span (``bench/lib/attribution.py``), the same
split of each of its longest idle gaps, and the program's own per-span
totals and counters for that stretch. The benchmark's own runs never run
this.
"""

import os
import sys

GAPS = 5  # the longest idle gaps, each split by span

_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path[:0] = [_ROOT, os.path.join(_ROOT, "src")]


def tune_stretch(cell, seed):
    """Set-up's round, then a function running one profiled round."""
    from bench.drivers import tune
    from bench.lib import netops
    from bench.lib.spans import Spans

    ops = netops.forward_ops(cell.config, cell.traffic)
    tune.persistent_cache(False)
    tune.tune_window(ops, cell.traffic, seed, 0.0, Spans())
    return lambda spans: tune.tune_window(ops, cell.traffic, seed, 0.0,
                                          spans)


def serve_stretch(cell, seed):
    """The server after its warm-up, and a function serving one batch."""
    from bench.drivers import serve

    server = serve.make_server(cell.config, cell.traffic, seed)
    server.generate(next(serve.prompts(cell.config, cell.traffic, seed, 1)),
                    2)
    batches = serve.prompts(cell.config, cell.traffic, seed, 0)
    return lambda spans: server.generate(next(batches),
                                         cell.traffic["gen_len"])


def main(argv=None):
    import argparse
    import json
    import shutil
    import tempfile

    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, default=1_000_003)
    ap.add_argument("--repeat", type=int, default=1)
    args = ap.parse_args(argv)

    from bench.lib import attribution, harness, trace
    from bench.lib.spans import Spans

    cell = harness.load_cell(args.workload)
    harness.prepare_environment()
    harness.require_devices(cell.chips)
    from repro.core import tracing
    from repro.runtime.compile_cache import enable_compile_cache

    enable_compile_cache()
    stretch = {"tune": tune_stretch, "serve": serve_stretch}[cell.driver](
        cell, args.seed)
    for i in range(args.repeat):
        tmp = tempfile.mkdtemp(prefix="bench-attribute-")
        spans = Spans()
        try:
            with tracing.enabled() as rec:
                with trace.profile(tmp), spans("bench.window"):
                    stretch(spans)
            tr = trace.load(trace.find_xplane(tmp))
            host = attribution.load(trace.find_xplane(tmp))
        finally:
            shutil.rmtree(tmp, ignore_errors=True)
        lo, hi = tr.span("bench.window")
        longest = sorted(attribution.idle_intervals(tr, lo, hi),
                         key=lambda g: g[0] - g[1])[:GAPS]
        print(json.dumps({
            "cell": cell.name, "seed": args.seed, "repeat": i,
            "window_s": (hi - lo) * 1e-9,
            **attribution.idle_by_span(host, tr, lo, hi),
            "longest_gaps": [attribution.idle_by_span(host, tr, a, b)
                             for a, b in longest],
            "program": rec.summary()}), flush=True)


if __name__ == "__main__":
    main()
