"""Element-wise multiply-accumulate kernel — Algorithm 2 (``vmacc``).

The paper's second intrinsic serves layers with no reduction dimension
(depthwise convolutions, gating / element-wise layers): load A, B and the
accumulator C, issue ``vmacc``, store once. On TPU this is a VPU-tile
kernel: (block_rows × block_cols) VMEM blocks, one fused multiply-add per
block, one store. Used by the RG-LRU gates (RecurrentGemma) and SSM gating
paths in the model zoo.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.core.space import KernelParams
from repro.kernels.matmul.kernel import compiler_params


def _vmacc_kernel(a_ref, b_ref, c_ref, o_ref) -> None:
    o_ref[...] = a_ref[...] * b_ref[...] + c_ref[...]


def vmacc_pallas(a, b, c, params: KernelParams, interpret=True):
    pr, pc = params.padded_dims
    br, bc = params.block
    spec = pl.BlockSpec((br, bc), lambda i, j: (i, j))
    return pl.pallas_call(
        _vmacc_kernel,
        grid=(pr // br, pc // bc),
        in_specs=[spec, spec, spec],
        out_specs=spec,
        out_shape=jax.ShapeDtypeStruct((pr, pc), a.dtype),
        compiler_params=compiler_params(params),
        name="vmacc",
        interpret=interpret,
    )(a, b, c)
