"""The serve driver for a DeepSeek-V3-family model (latent attention,
routed and shared experts) on one expert-parallel shard: offline batch
generation through the program's ``Server``, checked against the plain
reference ``refs/mla_moe.py``.

It is ``serve.py`` with this family's weights, reference and costs: the
window (``serve.serve_window``), the prompts, the sample of requests
checked and the logit gaps are ``serve.py``'s own. Two numbers are
checked: the widest gap of a served token below the reference's best
(``served_logit_gap``, as the ``serve`` cells check) and the mean gap over
every position checked (``served_logit_gap_mean``). Near-ties in the top-6
routing make a few positions of any bfloat16 run flip experts, which
widens the widest gap to where a float8 run's lies; the mean tells them
apart. Set-up makes the
float32 master weights on the device from the seed (one jitted call),
builds the program's ``Server`` over them (which keeps its own copy cast
to the compute dtype), and serves two tokens of one warm-up batch, which
compiles (or loads) the prefill and decode programs at the window's
shapes.

A traced run profiles the window's first batch and reduces the decode
program's runs to device seconds, overall and under the program's named
scopes (``repro.mla.decode``, ``repro.moe.experts``, ``repro.moe.route``,
``repro.moe.shared``), from the compiled decode program's HLO. The held
experts' grouped matmul (``jax.lax.ragged_dot``) compiles to a kernel
whose HLO metadata names it ``ragged-dot...`` rather than its scope, so
those instructions are put under ``repro.moe.experts``, where the
program calls them.
"""

from __future__ import annotations

import contextlib
import dataclasses
import gc
import shutil
import tempfile
import time

import numpy as np

from bench.drivers import serve
from bench.lib import costs_mla_moe, harness, trace, weights_mla_moe
from bench.refs import mla_moe, precision

CHECK_NAME = serve.CHECK_NAME  # the widest gap
MEAN_CHECK_NAME = "served_logit_gap_mean"
SCOPES = ("repro.mla.decode", "repro.moe.experts", "repro.moe.route",
          "repro.moe.shared")
GROUPED_MATMUL = "ragged-dot"  # op_name of the grouped matmul's kernel


def program_config(cfg: dict):
    """The program's configuration object for the file's sizes."""
    from repro.configs import get_config

    base = get_config(cfg["program_arch"])
    return dataclasses.replace(
        base, n_layers=cfg["num_hidden_layers"], d_model=cfg["hidden_size"],
        n_heads=cfg["num_attention_heads"],
        n_kv_heads=cfg["num_key_value_heads"], head_dim=cfg["v_head_dim"],
        d_ff=cfg["intermediate_size"], vocab_size=cfg["vocab_size"],
        n_experts=cfg["router_width"], experts_held=cfg["n_routed_experts"],
        expert_offset=cfg["expert_offset"],
        n_shared_experts=cfg["n_shared_experts"],
        top_k=cfg["num_experts_per_tok"],
        moe_d_ff=cfg["moe_intermediate_size"], router=cfg["scoring_func"],
        routed_scaling=cfg["routed_scaling_factor"],
        n_dense_layers=cfg["first_k_dense_replace"],
        kv_lora_rank=cfg["kv_lora_rank"],
        qk_nope_head_dim=cfg["qk_nope_head_dim"],
        qk_rope_head_dim=cfg["qk_rope_head_dim"],
        v_head_dim=cfg["v_head_dim"], rope_theta=float(cfg["rope_theta"]),
        norm_eps=cfg["rms_norm_eps"],
        tie_embeddings=cfg["tie_word_embeddings"], act=cfg["hidden_act"],
        dtype=cfg["compute_dtype"], window_pattern=())


def make_server(cfg: dict, traffic: dict, seed: int):
    from repro.models.model_zoo import build
    from repro.runtime.serve_loop import Server

    bundle = build(program_config(cfg), remat="none")
    params = weights_mla_moe.served(seed, cfg)
    return Server(bundle, params,
                  max_len=traffic["prompt_len"] + traffic["gen_len"] + 1)


def reference_logits(tokens, cfg: dict, traffic: dict, seed: int,
                     cast=precision.exact):
    """Reference logits at every position that chose a served token:
    (requests, gen_len, vocab)."""
    import jax.numpy as jnp

    p, g = traffic["prompt_len"], traffic["gen_len"]
    return mla_moe.forward(
        jnp.asarray(tokens[:, : p + g - 1]),
        weights_mla_moe.make_top(seed, cfg),
        weights_mla_moe.layer_weights(seed, cfg), cfg,
        positions=np.arange(p - 1, p + g - 1), cast=cast)


def gaps(ref_logits, chosen) -> dict[str, float]:
    """The widest and the mean gap of the chosen tokens below the
    reference's best, over every position checked."""
    g = serve.logit_gaps(ref_logits, chosen)
    return {CHECK_NAME: float(g.max()), MEAN_CHECK_NAME: float(g.mean())}


def served_gaps(tokens, cfg: dict, traffic: dict,
                seed: int) -> dict[str, float]:
    """How far the served tokens lie below the reference's best."""
    ref = reference_logits(tokens, cfg, traffic, seed)
    return gaps(ref, tokens[:, traffic["prompt_len"]:])


def decode_scopes(hlo_text: str) -> dict[str, str]:
    """Instruction name -> the program's scope it runs under."""
    scopes = {name: scope for name, scope in
              trace.instruction_scopes(hlo_text, "repro.").items()
              if scope in SCOPES}
    for line in hlo_text.splitlines():
        m = trace._HLO_LINE.match(line)
        if m and m.group("op").startswith(GROUPED_MATMUL):
            scopes[m.group("name")] = "repro.moe.experts"
    return scopes


def decode_hlo(server, batch: int, device) -> str:
    """The compiled decode program's HLO text, for the server's shapes."""
    import jax
    import jax.numpy as jnp

    one = jax.sharding.SingleDeviceSharding(device)
    cache = jax.tree.map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=one),
        jax.eval_shape(lambda: server.bundle.init_cache(batch,
                                                        server.max_len)))
    tokens = jax.ShapeDtypeStruct((batch, 1), jnp.int32, sharding=one)
    pos = jax.ShapeDtypeStruct((), jnp.int32, sharding=one)
    return server._decode.lower(server.params, cache, tokens,
                                pos).compile().as_text()


def _decode_reduction(tr, lo, hi, cfg, traffic, peaks, hlo_text) -> dict:
    """The decode program's runs in the traced batch (the program run once
    per decode step with the most device time), their device seconds in
    all and under each scope, and the seconds their least bytes take at
    819 GB/s."""
    steps = traffic["gen_len"] - 1
    runs = {name: v for name, v in trace.module_runs(tr, lo, hi).items()
            if len(v) == steps}
    out = {"steps": 0, "device_s": 0.0, "bytes_s": 0.0, "scopes": {}}
    if not runs:
        return out
    name = max(runs, key=lambda n: sum(runs[n]))
    p, b = traffic["prompt_len"], traffic["batch"]
    contexts = [p + i + 1 for i in range(steps)]
    events = trace.scope_events(tr, lo, hi, decode_scopes(hlo_text), name)
    least = {
        "repro.mla.decode": sum(
            costs_mla_moe.mla_decode(cfg, b, t).bytes_s(peaks)
            for t in contexts),
        "repro.moe.experts": steps * costs_mla_moe.held_experts(
            cfg, b).bytes_s(peaks)}
    out.update(steps=len(runs[name]), device_s=sum(runs[name]),
               bytes_s=sum(costs_mla_moe.decode_step(cfg, b, t).bytes_s(peaks)
                           for t in contexts))
    out["scopes"] = {scope: {"device_s": sum(events.get(scope, ())),
                             "bytes_s": least.get(scope)}
                     for scope in SCOPES if scope in events}
    return out


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
            server.generate(next(serve.prompts(cfg, traffic, ctx.seed, 1)),
                            2)
        cache = compile_cache_stats()
        batches = serve.prompts(cfg, traffic, ctx.seed, 0)

        def on_batch(i):  # traced runs profile the first batch
            stack = contextlib.ExitStack()
            if ctx.trace and i == 0:
                stack.enter_context(trace.profile(tmp))
                stack.enter_context(spans("bench.window"))
            return stack

        t_window = time.perf_counter()
        setup_s = t_window - ctx.t_start
        served, counters, window_s = serve.serve_window(
            server, batches, traffic, ctx.seconds, spans, on_batch)
        stats = ctx.devices[0].memory_stats() or {}
        memory = stats.get("peak_bytes_in_use", 0)
        counters["setup.cache_requests"] = cache["requests"]
        counters["setup.cache_hits"] = cache["hits"]
        hlo_text = (decode_hlo(server, traffic["batch"], ctx.devices[0])
                    if ctx.trace else "")
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
            decode = _decode_reduction(tr, lo, hi, cfg, traffic, ctx.peaks,
                                       hlo_text)
            record["trace"]["decode"] = decode
            breakdown = {"device_ops": trace.top_ops(tr, lo, hi),
                         "idle_gaps": trace.idle_gaps(tr, lo, hi),
                         "decode_scopes": {k: v["device_s"] for k, v in
                                           decode["scopes"].items()}}
        tokens = serve.sample_requests(served, traffic, ctx.seed)
        readings = served_gaps(tokens, cfg, traffic, ctx.seed)
        print(f"serve_mla_moe: {counters['serve.batches']} batches, "
              f"{counters['serve.tokens']} tokens in {window_s:.3f} s; "
              f"prefill {counters['serve.prefill_s']:.3f} s, decode "
              f"{counters['serve.decode_s']:.3f} s; peak "
              f"{int(memory)} bytes; checked {tokens.shape[0]} requests",
              flush=True)
        return harness.Outcome(
            end_to_end={"setup_s": setup_s,
                        "serve_tokens_per_s":
                            counters["serve.tokens"] / window_s},
            record=record,
            checks=[harness.Check(name, value, ctx.cell.limits[name])
                    for name, value in readings.items()],
            attempted=counters["serve.requests"], failed=0,
            memory_peak_bytes=int(memory), busy_s=busy, window_s=window,
            breakdown=breakdown)
    finally:
        shutil.rmtree(tmp, ignore_errors=True)
