"""Vector–matrix multiply kernel — Algorithm 1 at its literal shape.

The paper's first intrinsic computes ``C[J] += A[VL] · B[J, VL]``: one input
vector against J matrix rows, reducing along VL, accumulating in vector
registers, storing once. This kernel is the decode-time GEMV
(``x[1,K] @ W[K,N]``) with block (bn, bk) standing in for (J, VL):
the K-grid reduces into a VMEM accumulator (the vredsum/vslideup register
accumulation) and the single store happens on the last K step. The
store-heavy form (``accumulate=False``) puts K outermost and moves each
partial output row through HBM, as the matmul kernel does.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core.space import KernelParams
from repro.kernels.matmul.kernel import compiler_params


def _gemv_kernel(x_ref, w_ref, o_ref, acc_ref, *, k_steps: int) -> None:
    k = pl.program_id(1)

    @pl.when(k == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    acc_ref[...] += jnp.dot(x_ref[...], w_ref[...],
                            preferred_element_type=jnp.float32)

    @pl.when(k == k_steps - 1)
    def _store():
        o_ref[...] = acc_ref[...].astype(o_ref.dtype)


def _gemv_noacc_kernel(x_ref, w_ref, o_hbm, part_ref, *, bn: int) -> None:
    k, j = pl.program_id(0), pl.program_id(1)
    block = o_hbm.at[:, pl.ds(pl.multiple_of(j * bn, bn), bn)]
    prod = jnp.dot(x_ref[...], w_ref[...], preferred_element_type=jnp.float32)

    @pl.when(k == 0)
    def _first():
        part_ref[...] = prod

    @pl.when(k > 0)
    def _revisit():
        pltpu.sync_copy(block, part_ref)
        part_ref[...] += prod

    pltpu.sync_copy(part_ref, block)


def gemv_pallas(x, w, params: KernelParams, interpret=True):
    """x (1, pk) @ w (pk, pn) -> (1, pn). ``interpret`` as in
    :func:`~repro.kernels.matmul.kernel.matmul_pallas`."""
    pn, pk = params.padded_dims
    bn, bk = params.block
    gn, gk = pn // bn, pk // bk
    if params.accumulate:
        return pl.pallas_call(
            functools.partial(_gemv_kernel, k_steps=gk),
            grid=(gn, gk),
            in_specs=[pl.BlockSpec((1, bk), lambda j, k: (0, k)),
                      pl.BlockSpec((bk, bn), lambda j, k: (k, j))],
            out_specs=pl.BlockSpec((1, bn), lambda j, k: (0, j)),
            out_shape=jax.ShapeDtypeStruct((1, pn), jnp.float32),
            scratch_shapes=[pltpu.VMEM((1, bn), jnp.float32)],
            compiler_params=compiler_params(params),
            name="gemv",
            interpret=interpret,
        )(x, w)
    return pl.pallas_call(
        functools.partial(_gemv_noacc_kernel, bn=bn),
        grid=(gk, gn),
        in_specs=[pl.BlockSpec((1, bk), lambda k, j: (0, k)),
                  pl.BlockSpec((bk, bn), lambda k, j: (k, j))],
        out_specs=pl.BlockSpec(memory_space=pl.ANY),
        out_shape=jax.ShapeDtypeStruct((1, pn), jnp.float32),
        scratch_shapes=[pltpu.VMEM((1, bn), jnp.float32)],
        compiler_params=compiler_params(params),
        name="gemv",
        interpret=interpret,
    )(x, w)
