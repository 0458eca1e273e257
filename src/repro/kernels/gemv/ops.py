"""Jitted wrapper for the GEMV kernel, plus its block-shape capability."""

from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.core.space import KernelParams
from repro.kernels.gemv.kernel import gemv_pallas


def supports_block_shape(bn: int, bk: int, lane: int) -> bool:
    """Kernel-side generality check for a (bn, bk) block.

    The Pallas kernel tiles x as ``(1, bk)``, w as ``(bk, bn)`` and the
    output (plus the VMEM accumulator) as ``(1, bn)``; both grid axes cover
    the padded extents exactly. That lowers for any positive ``bk`` that is
    a lane multiple and any ``bn`` that is either a lane multiple (a full
    output tile per step) or exactly 1 (the paper's J=1 fallback row
    kernel, which the TPU compiles only when the output is that one row:
    the alignment postprocessor enforces it). Ragged ``bn`` between 1 and
    a lane would leave a partially masked last-dim store the kernel does
    not implement — the design-space program consults this before offering
    a ``bn`` split candidate.
    """
    if bn < 1 or bk < 1:
        return False
    if bk % lane:
        return False
    return bn == 1 or bn % lane == 0


def build(params: KernelParams, interpret: bool = True):
    n, _k = params.dims
    pn, pk = params.padded_dims
    compute_dtype = jnp.dtype(params.dtype)

    @jax.jit
    def f(x, w):
        x = jnp.pad(x.astype(compute_dtype), ((0, 0), (0, pk - x.shape[1])))
        w = jnp.pad(w.astype(compute_dtype),
                    ((0, pk - w.shape[0]), (0, pn - w.shape[1])))
        out = gemv_pallas(x, w, params, interpret=interpret)
        return out[:, :n]

    return f


@jax.jit
def xla_gemv(x, w):
    return jnp.dot(x.astype(jnp.float32), w.astype(jnp.float32))
