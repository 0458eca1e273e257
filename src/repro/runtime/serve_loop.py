"""Batched serving loop: prefill + decode with a static KV budget.

This is also where the dispatch chain meets real traffic: a Server built
with a hardware config and a per-decode-step op list (:func:`decode_ops`)
resolves each step's tensor workloads through
``repro.core.dispatch.best_schedule`` — tuned → bucketed → fixed → xla —
and reports the provenance mix on every :class:`GenerationResult`. Misses
flow into the attached :class:`~repro.core.traffic.TrafficLog`, which a
:class:`~repro.core.traffic.ContinuousTuner` drains in the background; the
hot-swapping ``global_database()`` then flips later dispatches to
``"tuned"`` without a server restart. Built without a hardware config (the
default), the server is the plain pre-dispatch serving loop.
"""

from __future__ import annotations

import dataclasses
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import tracing
from repro.core.workload import Workload, gemv, matmul
from repro.models.model_zoo import ModelBundle


@dataclasses.dataclass
class GenerationResult:
    tokens: np.ndarray  # (B, prompt + n_steps) — exactly n_steps generated
    prefill_s: float
    decode_s: float
    steps: int
    # provenance -> op count of this step's dispatch resolution
    # ("tuned"/"bucketed"/"fixed"/"xla"); None when the server was built
    # without a dispatch layer (hw=None)
    dispatch: dict[str, int] | None = None
    # the (B, V) logits each generated token was taken from, on the device:
    # the prefill's last position first, then one per decode step
    logits: list = dataclasses.field(default_factory=list)


def decode_ops(cfg, batch: int) -> list[tuple[int, Workload]]:
    """The per-decode-step dense projections of an ArchConfig, as
    ``[(count, Workload), ...]`` at the benchmarks/nets.py granularity (one
    entry per projection family, repeat counts for the layer stack).

    ``batch == 1`` lowers the projections to ``gemv`` — the single-stream
    edge-decode shape the paper tunes — larger batches to skinny matmuls.
    This is what a dispatch-aware :class:`Server` resolves every step, and
    what :func:`repro.core.dispatch.ensure_tuned` pre-tunes offline.

    Only dense projections are modelled. For a mixture of experts the FFN
    entries are the shared experts' (``n_shared_experts * moe_d_ff`` wide,
    one expert's width where it has none; the leading dense layers' ``d_ff``
    FFN is not listed); the router, the
    held experts' grouped matmul (``jax.lax.ragged_dot`` over a routed row
    count known only at run time) and its dispatch have no workload
    family. Latent attention's projections (``wq``, ``wkv_a``, ``wkv_b``,
    ``wo``) and the attention over any cache are not listed either.
    """
    dtype = cfg.dtype if cfg.dtype in ("float32", "bfloat16") else "bfloat16"

    def proj(n: int, k: int) -> Workload:
        return (gemv(n, k, dtype) if batch == 1
                else matmul(batch, n, k, dtype))

    ff = cfg.d_ff
    if cfg.family == "moe":
        ff = cfg.n_shared_experts * cfg.moe_d_ff or cfg.moe_d_ff
    n_up = 2 if cfg.act == "silu" else 1  # gated acts: up + gate projections
    return [
        (cfg.n_layers, proj(cfg.q_dim + 2 * cfg.kv_dim, cfg.d_model)),  # QKV
        (cfg.n_layers, proj(cfg.d_model, cfg.q_dim)),      # attention out
        (n_up * cfg.n_layers, proj(ff, cfg.d_model)),      # FFN up (+ gate)
        (cfg.n_layers, proj(cfg.d_model, ff)),             # FFN down
        (1, proj(cfg.padded_vocab, cfg.d_model)),          # LM head
    ]


class Server:
    """Minimal batched server: a fixed batch of requests is prefetched,
    prefilled once, then decoded greedily step-by-step (one jitted decode
    step reused across positions — the serve_step the dry-run lowers).

    ``hw`` + ``serve_ops`` attach the dispatch layer: every ``generate``
    resolves each serve op through the four-rung chain against ``database``
    (default: the hot-swapping ``global_database()``) and records misses
    into ``traffic`` — the serving side of the continuous-tuning loop.

    ``build_kernels=True`` additionally builds each resolved schedule's
    Pallas kernel during the dispatch pass, for the backend the server runs
    on (compiled on a TPU, interpret mode elsewhere); failed builds are
    counted in ``build_failures``. Builds go through the content-addressed
    process-wide :class:`~repro.core.build_cache.BuildCache`, so only the
    *first* resolution of each distinct concrete lowering pays the build —
    steady state (the same ops resolving to the same schedules, generate
    after generate) performs zero builds, which ``--suite cache`` asserts.
    Prefill and decode are each one jitted program."""

    def __init__(self, bundle: ModelBundle, params, max_len: int = 256,
                 hw=None, serve_ops=None, traffic=None, database=None,
                 build_kernels: bool = False):
        self.bundle = bundle
        # the weights read only in the compute dtype, cast to it once here
        # rather than in every prefill and decode program; no reference to
        # ``params`` is kept, so the caller's float32 masters can be freed
        with tracing.span("repro.serve.prepare_params"):
            self.params = jax.block_until_ready(bundle.serving_params(params))
        tracing.count("repro.serve.params_cast_bytes", sum(
            new.nbytes for old, new in zip(jax.tree.leaves(params),
                                           jax.tree.leaves(self.params))
            if new is not old))
        if bundle.cfg.family == "moe":
            tracing.count("repro.moe.experts_held", bundle.cfg.held_experts)
        self.max_len = max_len
        self.hw = hw
        self.serve_ops = list(serve_ops or ())
        self.traffic = traffic
        self.database = database
        self.build_kernels = build_kernels
        self.build_failures = 0  # resolved schedules whose build raised
        self.batches = 0  # generate calls so far: each one's batch id
        self._prefill = jax.jit(
            lambda p, batch: bundle.prefill_fn(p, batch, max_len))
        # the cache is donated: each step writes its token's row into it
        # in place, and ``generate`` rebinds ``cache`` to every result
        self._decode = jax.jit(
            lambda p, c, t, pos: bundle.decode_fn(p, c, t, pos),
            donate_argnums=(1,))

    def resolve_dispatch(self) -> dict[str, int] | None:
        """One dispatch pass over the serve ops: provenance -> op count.
        None when no dispatch layer is attached. Each pass re-resolves
        through the database (hot-swap visible); per-op cost is O(1) via
        the dispatch caches."""
        if self.hw is None or not self.serve_ops:
            return None
        from repro.core.dispatch import best_schedule  # lazy: jax-free core

        counts: dict[str, int] = {}
        with tracing.span("repro.serve.resolve_dispatch"):
            for count, wl in self.serve_ops:
                sched, provenance = best_schedule(wl, self.hw,
                                                  database=self.database,
                                                  traffic=self.traffic,
                                                  count=count)
                counts[provenance] = counts.get(provenance, 0) + count
                if self.build_kernels and sched is not None:
                    self._build_kernel(wl, sched)
        return counts

    def _build_kernel(self, wl: Workload, sched) -> None:
        """Build one resolved op's kernel through the process-wide build
        cache (a repeat of an already-built signature is a cache hit, no
        build), compiled when the server runs on a TPU and in interpret
        mode elsewhere. An "xla" resolution never reaches here (sched is
        None) and a schedule that doesn't concretize on this shape is
        skipped. A build that raises is counted in ``build_failures`` and
        the dispatch pass goes on serving."""
        from repro import kernels
        from repro.core import space as space_lib

        params = space_lib.concretize(wl, self.hw, sched)
        if not params.valid:
            return
        try:
            kernels.build(wl, params,
                          interpret=jax.default_backend() != "tpu")
        except Exception:  # counted, not hidden
            self.build_failures += 1

    def generate(self, prompts: np.ndarray, n_steps: int,
                 extra_batch: dict | None = None) -> GenerationResult:
        """Prefill ``prompts`` and decode ``n_steps`` tokens greedily.
        Traced (``core/tracing.py``), the call is a ``repro.serve.generate``
        span with the batch id ``batch``, split into
        ``repro.serve.resolve_dispatch``, ``repro.serve.prefill`` and, per
        decode step, ``repro.serve.decode_dispatch`` (the host enqueues the
        step and its argmax) and ``repro.serve.token_fetch`` (the host waits
        for the token). Counters: ``repro.serve.cache_bytes``, the cache the
        prefill returns, and ``repro.serve.cache_donated_bytes``, the part
        of it the first decode step consumed as its donated buffer (0 when
        donation did not engage)."""
        self.batches += 1
        with tracing.span("repro.serve.generate", batch=self.batches):
            return self._generate(prompts, n_steps, extra_batch)

    def _generate(self, prompts: np.ndarray, n_steps: int,
                  extra_batch: dict | None) -> GenerationResult:
        dispatch = self.resolve_dispatch()
        b, s = prompts.shape
        batch = {"tokens": jnp.asarray(prompts)}
        if extra_batch:
            batch.update({k: jnp.asarray(v) for k, v in extra_batch.items()})

        with tracing.span("repro.serve.prefill"):
            t0 = time.perf_counter()
            logits, cache = self._prefill(self.params, batch)
            tracing.count("repro.serve.cache_bytes",
                          sum(a.nbytes for a in jax.tree.leaves(cache)))
            step_logits = [logits[:, -1]]
            next_tok = jnp.argmax(logits[:, -1], axis=-1).astype(jnp.int32)
            jax.block_until_ready(next_tok)
            prefill_s = time.perf_counter() - t0

        # the prefill argmax is the *first* generated token, so it counts
        # against n_steps: n_steps=0 emits nothing (tokens == prompts) and
        # the result always has exactly prompt + n_steps columns
        out = [np.asarray(next_tok)] if n_steps > 0 else []
        prefilled = jax.tree.leaves(cache)
        t0 = time.perf_counter()
        for i in range(n_steps - 1):
            with tracing.span("repro.serve.decode_dispatch"):
                pos = jnp.int32(s + i)
                logits, cache = self._decode(self.params, cache,
                                             next_tok[:, None], pos)
                step_logits.append(logits)
                next_tok = jnp.argmax(logits, axis=-1).astype(jnp.int32)
            if prefilled:  # what of the prefill's cache the step took over
                tracing.count("repro.serve.cache_donated_bytes", sum(
                    a.nbytes for a in prefilled if a.is_deleted()))
                prefilled = None
            with tracing.span("repro.serve.token_fetch"):
                out.append(np.asarray(next_tok))
        jax.block_until_ready(next_tok)
        decode_s = time.perf_counter() - t0

        gen = (np.stack(out, axis=1) if out
               else np.zeros((b, 0), dtype=prompts.dtype))
        return GenerationResult(np.concatenate([prompts, gen], axis=1),
                                prefill_s, decode_s, n_steps, dispatch,
                                step_logits[:n_steps])
