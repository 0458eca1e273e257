"""Tiled matmul Pallas kernel — the Algorithm-1 analogue on TPU.

The paper's intrinsic keeps partial results in vector registers, merges them
with ``vslideup``, and stores each output element exactly once (<1 % store
instructions). The TPU translation: a f32 accumulator living in VMEM scratch
across the K-grid, with the HBM store issued only on the last K step
(``accumulate=True``). The contrasting store-heavy schedule (muRISCV-NN-like,
and what a naive XLA tiling does when K doesn't fit) makes K the outer grid
dimension so partial sums round-trip through HBM (``accumulate=False``); the
tuner picks between them per workload×hardware.

The store-heavy form keeps its output in HBM (``memory_space=pl.ANY``) and
moves each partial block with synchronous copies: the first K step writes
it, every later one reads it back, adds, and writes it again. Pallas's
output pipelining would not do: with K outermost an output block is
revisited out of order, and the compiled kernel never reads a revisited
output block back from HBM. Every kernel asks the compiler for the scoped
VMEM the schedule was concretized against (``params.vmem_limit``).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core.space import KernelParams


def _acc_kernel(x_ref, w_ref, o_ref, acc_ref, *, k_steps: int,
                acc_dtype) -> None:
    """K-inner grid, scratch accumulator, single store (Algorithm 1)."""
    k = pl.program_id(2)

    @pl.when(k == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    acc_ref[...] += jnp.dot(x_ref[...], w_ref[...],
                            preferred_element_type=acc_dtype)

    @pl.when(k == k_steps - 1)
    def _store():
        o_ref[...] = acc_ref[...].astype(o_ref.dtype)


def _noacc_kernel(x_ref, w_ref, o_hbm, part_ref, *, bm: int, bn: int,
                  acc_dtype) -> None:
    """K-outer grid: the output block is revisited ``k_steps`` times with
    full HBM write-back in between (the store-heavy baseline schedule)."""
    k, i, j = pl.program_id(0), pl.program_id(1), pl.program_id(2)
    block = o_hbm.at[pl.ds(pl.multiple_of(i * bm, bm), bm),
                     pl.ds(pl.multiple_of(j * bn, bn), bn)]
    prod = jnp.dot(x_ref[...], w_ref[...], preferred_element_type=acc_dtype)

    @pl.when(k == 0)
    def _first():
        part_ref[...] = prod

    @pl.when(k > 0)
    def _revisit():
        pltpu.sync_copy(block, part_ref)
        part_ref[...] += prod

    pltpu.sync_copy(part_ref, block)


def compiler_params(params: KernelParams):
    """The scoped-VMEM limit of the part ``params`` was concretized for."""
    return pltpu.CompilerParams(vmem_limit_bytes=params.vmem_limit or None)


def matmul_pallas(x: jax.Array, w: jax.Array, params: KernelParams,
                  interpret=True) -> jax.Array:
    """``x @ w`` with the schedule in ``params``. Shapes already padded to
    ``params.padded_dims``; returns the padded (pm, pn) product.
    ``interpret`` is passed to ``pallas_call`` as is: False compiles for the
    TPU, True runs the legacy interpreter, a ``pltpu.InterpretParams`` the
    interpreter that keeps TPU memory semantics."""
    pm, pn, pk = params.padded_dims
    bm, bn, bk = params.block
    gm, gn, gk = pm // bm, pn // bn, pk // bk
    int_path = x.dtype in (jnp.int8.dtype, jnp.uint8.dtype)
    acc_dtype = jnp.int32 if int_path else jnp.float32

    if params.accumulate:
        if params.order == "nmk":
            grid = (gn, gm, gk)
            x_map = lambda j, i, k: (i, k)
            w_map = lambda j, i, k: (k, j)
            o_map = lambda j, i, k: (i, j)
        else:  # "mnk"
            grid = (gm, gn, gk)
            x_map = lambda i, j, k: (i, k)
            w_map = lambda i, j, k: (k, j)
            o_map = lambda i, j, k: (i, j)
        kernel = functools.partial(_acc_kernel, k_steps=gk,
                                   acc_dtype=acc_dtype)
        return pl.pallas_call(
            kernel,
            grid=grid,
            in_specs=[pl.BlockSpec((bm, bk), x_map),
                      pl.BlockSpec((bk, bn), w_map)],
            out_specs=pl.BlockSpec((bm, bn), o_map),
            out_shape=jax.ShapeDtypeStruct((pm, pn), acc_dtype),
            scratch_shapes=[pltpu.VMEM((bm, bn), acc_dtype)],
            compiler_params=compiler_params(params),
            name="matmul",
            interpret=interpret,
        )(x, w)

    # store-heavy: K outermost, partial sums through HBM
    kernel = functools.partial(_noacc_kernel, bm=bm, bn=bn,
                               acc_dtype=acc_dtype)
    return pl.pallas_call(
        kernel,
        grid=(gk, gm, gn),
        in_specs=[pl.BlockSpec((bm, bk), lambda k, i, j: (i, k)),
                  pl.BlockSpec((bk, bn), lambda k, i, j: (k, j))],
        out_specs=pl.BlockSpec(memory_space=pl.ANY),
        out_shape=jax.ShapeDtypeStruct((pm, pn), acc_dtype),
        scratch_shapes=[pltpu.VMEM((bm, bn), acc_dtype)],
        compiler_params=compiler_params(params),
        name="matmul",
        interpret=interpret,
    )(x, w)
