"""Blockwise (flash) attention Pallas kernel with GQA and causal masking.

The long-context serving hot spot. Schedule-wise this is the same paper
pattern one level up: the online-softmax running state (m, l, acc) lives in
VMEM scratch across the KV grid — accumulate in-core, store the output tile
once at the last KV step — and the (block_q × block_kv) granularity is a
registered intrinsic-variant ladder the tuner picks from.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core.space import KernelParams
from repro.kernels.matmul.kernel import compiler_params

NEG_INF = -1e30


def _fa_kernel(q_ref, k_ref, v_ref, o_ref, acc_ref, m_ref, l_ref, *,
               kv_steps: int, scale: float, causal: bool, kv_len: int,
               bq: int, bkv: int, offset: int) -> None:
    """``offset = kv_len - q_len``: bottom-right-aligned causality (query i
    sits at absolute position i + offset), the decode-style convention."""
    iq = pl.program_id(1)
    jk = pl.program_id(2)

    @pl.when(jk == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    # Causal: skip KV blocks entirely above the diagonal of this Q block.
    live = (jk * bkv <= iq * bq + bq - 1 + offset) if causal else True

    @pl.when(live)
    def _body():
        q = q_ref[0].astype(jnp.float32)
        k = k_ref[0].astype(jnp.float32)
        v = v_ref[0].astype(jnp.float32)
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) * scale
        rows = (iq * bq + offset
                + jax.lax.broadcasted_iota(jnp.int32, (bq, bkv), 0))
        cols = jk * bkv + jax.lax.broadcasted_iota(jnp.int32, (bq, bkv), 1)
        mask = cols < kv_len  # padded KV tail
        if causal:
            mask = jnp.logical_and(mask, cols <= rows)
        s = jnp.where(mask, s, NEG_INF)

        m_prev = m_ref[:, :1]
        l_prev = l_ref[:, :1]
        m_new = jnp.maximum(m_prev, s.max(axis=-1, keepdims=True))
        p = jnp.exp(s - m_new)
        alpha = jnp.exp(m_prev - m_new)
        l_new = alpha * l_prev + p.sum(axis=-1, keepdims=True)
        acc_ref[...] = acc_ref[...] * alpha + jnp.dot(
            p, v, preferred_element_type=jnp.float32)
        m_ref[...] = jnp.broadcast_to(m_new, m_ref.shape)
        l_ref[...] = jnp.broadcast_to(l_new, l_ref.shape)

    @pl.when(jk == kv_steps - 1)
    def _store():
        l = l_ref[:, :1]
        l = jnp.where(l == 0.0, 1.0, l)  # fully-masked (padded) rows
        o_ref[0] = (acc_ref[...] / l).astype(o_ref.dtype)


def flash_attention_pallas(q, k, v, params: KernelParams,
                           interpret=True):
    """q (BH, pq, pd); k, v (BHkv, pkv, pd) -> (BH, pq, pd).

    ``params.padded_dims = (b, hq, hkv, pq, pkv, d_padded)``; the true KV
    length rides in ``params.dims[4]`` for masking.
    """
    b, hq, hkv, pq, pkv, pd = params.padded_dims
    kv_len = params.dims[4]
    d_real = params.dims[5]
    bq, bkv = params.block
    group = hq // hkv
    grid = (b * hq, pq // bq, pkv // bkv)
    kernel = functools.partial(
        _fa_kernel, kv_steps=grid[2], scale=1.0 / math.sqrt(d_real),
        causal=params.order == "qk_causal", kv_len=kv_len, bq=bq, bkv=bkv,
        offset=kv_len - params.dims[3])
    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, bq, pd), lambda h, i, j: (h, i, 0)),
            pl.BlockSpec((1, bkv, pd), lambda h, i, j: (h // group, j, 0)),
            pl.BlockSpec((1, bkv, pd), lambda h, i, j: (h // group, j, 0)),
        ],
        out_specs=pl.BlockSpec((1, bq, pd), lambda h, i, j: (h, i, 0)),
        out_shape=jax.ShapeDtypeStruct((b * hq, pq, pd), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((bq, pd), jnp.float32),
            pltpu.VMEM((bq, 128), jnp.float32),
            pltpu.VMEM((bq, 128), jnp.float32),
        ],
        compiler_params=compiler_params(params),
        name="flash_attention",
        interpret=interpret,
    )(q, k, v)
