"""Uniform model API over all architecture families.

``build(cfg)`` returns a :class:`ModelBundle` with the same five entry
points regardless of family — the train/serve loops and the dry-run treat
every architecture identically:

    init(key) -> params
    loss_fn(params, batch) -> scalar f32 loss        (train_step target)
    prefill_fn(params, batch, max_len) -> (logits, cache)
    decode_fn(params, cache, tokens, pos) -> (logits (B,V), cache)
    init_cache(batch_size, max_len) -> cache pytree

and ``serving_params(params) -> params``, the tree a server keeps: for the
dense, VLM and MoE families the weights read only in the compute dtype are
cast to it once (:func:`repro.models.transformer.serving_params`, with the
MoE family's leaves from :mod:`repro.models.moe`); every other family
serves the tree it is given.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Callable

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import ArchConfig, ShapeSpec
from repro.models import encdec, griffin, layers, moe, ssm, transformer


@dataclasses.dataclass
class ModelBundle:
    cfg: ArchConfig
    init: Callable
    loss_fn: Callable
    forward: Callable
    prefill_fn: Callable
    decode_fn: Callable
    init_cache: Callable
    make_batch: Callable
    serving_params: Callable


def _lm_loss_from_logits(logits, tokens):
    inputs_labels = tokens[:, 1:]
    return layers.lm_loss(logits[:, :-1], inputs_labels)


def build(cfg: ArchConfig, remat: str = "full") -> ModelBundle:
    fam = cfg.family
    serving_params = lambda params: params  # dense, VLM, MoE override

    if fam in ("dense", "vlm"):
        mod = transformer

        def loss_fn(params, batch):
            tokens = batch["tokens"]
            logits = mod.forward(
                params, tokens[:, :-1], cfg,
                inputs_embeds=batch.get("patch_embeds"),
                mrope_positions=batch.get("mrope_positions"), remat=remat)
            return layers.lm_loss(logits, tokens[:, 1:])

        def forward(params, batch):
            return mod.forward(params, batch["tokens"], cfg,
                               inputs_embeds=batch.get("patch_embeds"),
                               mrope_positions=batch.get("mrope_positions"),
                               remat=remat)

        def prefill_fn(params, batch, max_len):
            return mod.prefill(params, batch["tokens"], cfg, max_len,
                               inputs_embeds=batch.get("patch_embeds"),
                               mrope_positions=batch.get("mrope_positions"))

        def decode_fn(params, cache, tokens, pos):
            return mod.decode_step(params, cache, tokens, pos, cfg)

        init_cache = lambda b, t: mod.init_cache(cfg, b, t)
        init = lambda key: mod.init_params(key, cfg)
        serving_params = lambda params: mod.serving_params(params, cfg)

    elif fam == "moe":
        mod = moe

        def loss_fn(params, batch):
            tokens = batch["tokens"]
            logits = mod.forward(params, tokens[:, :-1], cfg, remat=remat)
            return layers.lm_loss(logits, tokens[:, 1:])

        def forward(params, batch):
            return mod.forward(params, batch["tokens"], cfg, remat=remat)

        def prefill_fn(params, batch, max_len):
            return mod.prefill(params, batch["tokens"], cfg, max_len)

        def decode_fn(params, cache, tokens, pos):
            return mod.decode_step(params, cache, tokens, pos, cfg)

        init_cache = lambda b, t: mod.init_cache(cfg, b, t)
        init = lambda key: mod.init_params(key, cfg)
        serving_params = lambda params: mod.serving_params(params, cfg)

    elif fam == "ssm":
        mod = ssm

        def loss_fn(params, batch):
            tokens = batch["tokens"]
            logits = mod.forward(params, tokens[:, :-1], cfg, remat=remat)
            return layers.lm_loss(logits, tokens[:, 1:])

        def forward(params, batch):
            return mod.forward(params, batch["tokens"], cfg, remat=remat)

        def prefill_fn(params, batch, max_len):
            return mod.prefill(params, batch["tokens"], cfg, max_len)

        def decode_fn(params, cache, tokens, pos):
            return mod.decode_step(params, cache, tokens, pos, cfg)

        init_cache = lambda b, t: mod.init_cache(cfg, b, t)
        init = lambda key: mod.init_params(key, cfg)

    elif fam == "hybrid":
        mod = griffin

        def loss_fn(params, batch):
            tokens = batch["tokens"]
            logits = mod.forward(params, tokens[:, :-1], cfg, remat=remat)
            return layers.lm_loss(logits, tokens[:, 1:])

        def forward(params, batch):
            return mod.forward(params, batch["tokens"], cfg, remat=remat)

        def prefill_fn(params, batch, max_len):
            return mod.prefill(params, batch["tokens"], cfg, max_len)

        def decode_fn(params, cache, tokens, pos):
            return mod.decode_step(params, cache, tokens, pos, cfg)

        init_cache = lambda b, t: mod.init_cache(cfg, b, t)
        init = lambda key: mod.init_params(key, cfg)

    elif fam == "encdec":
        mod = encdec

        def loss_fn(params, batch):
            tokens = batch["tokens"]
            logits = mod.forward(params, batch["frames"], tokens[:, :-1],
                                 cfg, remat=remat)
            return layers.lm_loss(logits, tokens[:, 1:])

        def forward(params, batch):
            return mod.forward(params, batch["frames"], batch["tokens"], cfg,
                               remat=remat)

        def prefill_fn(params, batch, max_len):
            return mod.prefill(params, batch["frames"], batch["tokens"], cfg,
                               max_len)

        def decode_fn(params, cache, tokens, pos):
            return mod.decode_step(params, cache, tokens, pos, cfg)

        init_cache = lambda b, t: mod.init_cache(cfg, b, t)
        init = lambda key: mod.init_params(key, cfg)

    else:
        raise ValueError(f"unknown family {fam}")

    def make_batch(seed: int, shape: ShapeSpec, train: bool = True):
        """Concrete batch for smoke tests / examples (numpy, host-side)."""
        rng = np.random.default_rng(seed)
        b, s = shape.global_batch, shape.seq_len
        extra = 1 if train else 0
        batch: dict[str, Any] = {
            "tokens": rng.integers(0, cfg.vocab_size,
                                   size=(b, s + extra)).astype(np.int32)
        }
        if fam == "vlm":
            n_patch = min(64, s // 2)
            batch["patch_embeds"] = rng.standard_normal(
                (b, n_patch, cfg.d_model)).astype(np.float32)
            pos = np.broadcast_to(np.arange(s), (b, 3, s)).astype(np.int32)
            batch["mrope_positions"] = np.ascontiguousarray(pos)
        if fam == "encdec":
            batch["frames"] = rng.standard_normal(
                (b, cfg.encoder_seq, cfg.d_model)).astype(np.float32)
        return batch

    return ModelBundle(cfg, init, loss_fn, forward, prefill_fn, decode_fn,
                       init_cache, make_batch, serving_params)
