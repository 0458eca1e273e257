"""Quantized int8 matmul + bias + requantize — the paper's QNN operation.

The paper evaluates int8 matmuls "as they normally appear in Quantized
Neural Networks" [Jacob et al.]: ``C_i8 = requant(A_i8 @ B_i8 + D_i32)``.
On RVV the int32 accumulation happens in widened vector registers; on TPU
the MXU accumulates int8×int8 into int32, and requantization runs on the
VPU. TPU has no fixed-point requant pipeline, so the scale is applied in
f32 — a documented hardware-adaptation decision (DESIGN.md §2): the
*schedule* semantics (accumulate in-core, store the narrow result once) are
preserved; only the scalar rescale arithmetic changes unit.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core.space import KernelParams
from repro.kernels.matmul.kernel import compiler_params


def _qmm_kernel(x_ref, w_ref, bias_ref, scale_ref, o_ref, acc_ref,
                *, k_steps: int) -> None:
    k = pl.program_id(2)

    @pl.when(k == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    acc_ref[...] += jnp.dot(x_ref[...], w_ref[...],
                            preferred_element_type=jnp.int32)

    @pl.when(k == k_steps - 1)
    def _requant():
        acc = acc_ref[...] + bias_ref[...].astype(jnp.int32)
        scaled = acc.astype(jnp.float32) * scale_ref[0]
        o_ref[...] = jnp.clip(jnp.round(scaled), -128, 127).astype(jnp.int8)


def qmatmul_pallas(x, w, bias, scale, params: KernelParams,
                   interpret=True):
    """int8 (pm,pk) @ (pk,pn) + bias(pn,) -> requantized int8 (pm,pn)."""
    pm, pn, pk = params.padded_dims
    bm, bn, bk = params.block
    gm, gn, gk = pm // bm, pn // bn, pk // bk
    kernel = functools.partial(_qmm_kernel, k_steps=gk)
    return pl.pallas_call(
        kernel,
        grid=(gm, gn, gk),
        in_specs=[
            pl.BlockSpec((bm, bk), lambda i, j, k: (i, k)),
            pl.BlockSpec((bk, bn), lambda i, j, k: (k, j)),
            pl.BlockSpec((1, bn), lambda i, j, k: (0, j)),
            pl.BlockSpec(memory_space=pltpu.SMEM),
        ],
        out_specs=pl.BlockSpec((bm, bn), lambda i, j, k: (i, j)),
        out_shape=jax.ShapeDtypeStruct((pm, pn), jnp.int8),
        scratch_shapes=[pltpu.VMEM((bm, bn), jnp.int32)],
        compiler_params=compiler_params(params),
        name="qmatmul",
        interpret=interpret,
    )(x, w, bias, scale)
