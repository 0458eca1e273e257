"""The serve driver: offline batch generation through the program's
``Server``, checked against the plain reference.

Set-up makes the weights on the device from the seed (one jitted call, in
float32, the type the program keeps and serves them in), builds the
program's ``Server`` over them, and serves two tokens of one warm-up
batch, which compiles (or loads from the persistent cache) the prefill and
decode programs at the window's shapes. The window is a closed loop of one
client: batches of ``batch`` prompts of ``prompt_len`` tokens, drawn from
the seed, each generating ``gen_len`` tokens greedily, until ``--seconds``
have passed.

After the window the server and its weights are freed, and a sample of
finished requests, drawn from the seed, is checked: the reference runs once
over each prompt with its served tokens, and the number compared is the
widest gap by which a served token's logit lies below the reference's best
at that position.
"""

from __future__ import annotations

import contextlib
import dataclasses
import gc
import shutil
import tempfile
import time

import numpy as np

from bench.lib import costs, harness, seeds, trace, weights
from bench.refs import lm, precision

CHECK_NAME = "served_logit_gap"


def program_config(cfg: dict):
    """The program's configuration object for the file's sizes."""
    from repro.configs import get_config

    base = get_config(cfg["program_arch"])
    return dataclasses.replace(
        base, n_layers=cfg["n_layers"], d_model=cfg["d_model"],
        n_heads=cfg["n_heads"], n_kv_heads=cfg["n_kv_heads"],
        head_dim=cfg["head_dim"], d_ff=cfg["d_ff"],
        vocab_size=cfg["vocab_size"], rope_theta=cfg["rope_theta"],
        norm_eps=cfg["norm_eps"], tie_embeddings=cfg["tie_embeddings"],
        act=cfg["act"], dtype=cfg["compute_dtype"], window_pattern=())


def make_server(cfg: dict, traffic: dict, seed: int):
    from repro.models.model_zoo import build
    from repro.runtime.serve_loop import Server

    bundle = build(program_config(cfg), remat="none")
    params = weights.served(seed, cfg)
    return Server(bundle, params,
                  max_len=traffic["prompt_len"] + traffic["gen_len"] + 1)


def prompts(cfg: dict, traffic: dict, seed: int, stream: int):
    """Batches of prompts, drawn from the seed: every batch has the same
    shape, so every seed gives the same work."""
    rng = seeds.rng(seed, seeds.PROMPTS, stream)
    shape = (traffic["batch"], traffic["prompt_len"])
    while True:
        yield rng.integers(0, cfg["vocab_size"], size=shape).astype(np.int32)


def serve_window(server, batches, traffic: dict, seconds: float, spans,
                 on_batch=None):
    """Serve batches until ``seconds`` have passed. Returns the served
    tokens of each batch, the counters and the window's seconds."""
    served, prefill_s, decode_s = [], 0.0, 0.0
    t0 = time.perf_counter()
    while True:
        i = len(served)
        with (on_batch(i) if on_batch else contextlib.nullcontext()):
            with spans("bench.serve.generate"):
                res = server.generate(next(batches), traffic["gen_len"])
        served.append(res.tokens)
        prefill_s += res.prefill_s
        decode_s += res.decode_s
        del res  # its step logits
        if time.perf_counter() - t0 >= seconds:
            break
    window_s = time.perf_counter() - t0
    n = len(served)
    counters = {"serve.batches": n,
                "serve.requests": n * traffic["batch"],
                "serve.tokens": n * traffic["batch"] * traffic["gen_len"],
                "serve.decode_steps": n * (traffic["gen_len"] - 1),
                "serve.prefill_s": prefill_s, "serve.decode_s": decode_s}
    return served, counters, window_s


def sample_requests(served, traffic: dict, seed: int):
    """(batch, row) of the requests checked, drawn from the seed: distinct
    rows, so both halves of a batch are in the sample."""
    rng = seeds.rng(seed, seeds.SAMPLE)
    n = traffic["check_requests"]
    rows = rng.choice(traffic["batch"], size=n, replace=False)
    batches = rng.integers(0, len(served), size=n)
    return np.stack([served[b][r] for b, r in zip(batches, rows)])


def reference_logits(tokens, cfg: dict, traffic: dict, seed: int,
                     cast=precision.exact):
    """Reference logits at every position that chose a served token:
    (requests, gen_len, vocab)."""
    import functools

    import jax
    import jax.numpy as jnp

    p, g = traffic["prompt_len"], traffic["gen_len"]
    make_layer = jax.jit(functools.partial(weights.layer, cfg=cfg))
    top = jax.jit(functools.partial(weights.top, cfg=cfg))(
        weights.top_key(seed))
    return lm.forward(jnp.asarray(tokens[:, : p + g - 1]), top,
                      lambda i: make_layer(weights.layer_key(seed, i)), cfg,
                      positions=np.arange(p - 1, p + g - 1), cast=cast)


def logit_gaps(ref_logits, chosen):
    """Per position, how far the reference's logit of ``chosen`` lies below
    its best: (requests, gen_len)."""
    import jax.numpy as jnp

    picked = jnp.take_along_axis(ref_logits, jnp.asarray(chosen)[..., None],
                                 axis=-1)[..., 0]
    return ref_logits.max(axis=-1) - picked


def served_gap(tokens, cfg: dict, traffic: dict, seed: int) -> float:
    """The widest gap of a served token below the reference's best."""
    p = traffic["prompt_len"]
    ref = reference_logits(tokens, cfg, traffic, seed)
    return float(logit_gaps(ref, tokens[:, p:]).max())


def _decode_reduction(tr, lo, hi, cfg, traffic, peaks) -> dict:
    """The decode step program's runs in the traced batch, and the seconds
    those steps take at the HBM and at the compute peak. The decode program
    is the one that ran once per decode step with the most device time."""
    steps = traffic["gen_len"] - 1
    runs = trace.module_runs(tr, lo, hi)
    per_step = [v for v in runs.values() if len(v) == steps]
    decode = max(per_step, key=sum) if per_step else []
    p, b = traffic["prompt_len"], traffic["batch"]
    step_costs = [costs.decode_step(cfg, b, p + i + 1, cfg["compute_dtype"])
                  for i in range(steps)]
    return {"steps": len(decode), "device_s": sum(decode),
            "bytes_s": sum(c.bytes_s(peaks) for c in step_costs),
            "compute_s": sum(c.compute_s(peaks) for c in step_costs)}


def run(ctx) -> harness.Outcome:
    from bench.lib.spans import Spans
    from repro.runtime.compile_cache import (compile_cache_stats,
                                             enable_compile_cache)

    cfg, traffic = ctx.cell.config, ctx.cell.traffic
    enable_compile_cache()
    spans = Spans()
    tmp = tempfile.mkdtemp(prefix="bench-trace-")
    try:
        with spans("bench.serve.setup"):
            server = make_server(cfg, traffic, ctx.seed)
            # two tokens compile (or load) the prefill and the decode step
            server.generate(next(prompts(cfg, traffic, ctx.seed, 1)), 2)
        cache = compile_cache_stats()
        batches = prompts(cfg, traffic, ctx.seed, 0)

        def on_batch(i):  # traced runs profile the first batch
            stack = contextlib.ExitStack()
            if ctx.trace and i == 0:
                stack.enter_context(trace.profile(tmp))
                stack.enter_context(spans("bench.window"))
            return stack

        t_window = time.perf_counter()
        setup_s = t_window - ctx.t_start
        served, counters, window_s = serve_window(
            server, batches, traffic, ctx.seconds, spans, on_batch)
        stats = ctx.devices[0].memory_stats() or {}
        memory = stats.get("peak_bytes_in_use", 0)
        counters["setup.cache_requests"] = cache["requests"]
        counters["setup.cache_hits"] = cache["hits"]
        del server
        gc.collect()

        record = {"counters": counters, "spans": dict(spans.total_s),
                  "window_s": window_s, "trace": {}}
        busy = window = breakdown = None
        if ctx.trace:
            tr = trace.load(trace.find_xplane(tmp))
            lo, hi = tr.span("bench.window")
            busy, window = trace.busy_s(tr, lo, hi), (hi - lo) * 1e-9
            record["trace"]["window"] = {"busy_s": busy, "window_s": window}
            record["trace"]["decode"] = _decode_reduction(
                tr, lo, hi, cfg, traffic, ctx.peaks)
            breakdown = {"device_ops": trace.top_ops(tr, lo, hi),
                         "idle_gaps": trace.idle_gaps(tr, lo, hi)}
        tokens = sample_requests(served, traffic, ctx.seed)
        gap = served_gap(tokens, cfg, traffic, ctx.seed)
        print(f"serve: {counters['serve.batches']} batches, "
              f"{counters['serve.tokens']} tokens in {window_s:.3f} s; "
              f"prefill {counters['serve.prefill_s']:.3f} s, decode "
              f"{counters['serve.decode_s']:.3f} s; checked "
              f"{tokens.shape[0]} requests", flush=True)
        return harness.Outcome(
            end_to_end={"setup_s": setup_s,
                        "serve_tokens_per_s":
                            counters["serve.tokens"] / window_s},
            record=record,
            checks=[harness.Check(CHECK_NAME, gap,
                                  ctx.cell.limits[CHECK_NAME])],
            attempted=counters["serve.requests"], failed=0,
            memory_peak_bytes=int(memory), busy_s=busy, window_s=window,
            breakdown=breakdown)
    finally:
        shutil.rmtree(tmp, ignore_errors=True)
