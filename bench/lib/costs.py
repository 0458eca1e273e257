"""Operations and least HBM bytes of the benchmark's work, from shapes.

Bytes are counted at the compute dtype the configuration states, each
operand read once and each result written once, so no later change of
storage dtype can push a share of a roofline over 100%. Operations count a
multiply-add as two.
"""

from __future__ import annotations

import dataclasses

from bench.lib.peaks import Peaks

DTYPE_BYTES = {"float32": 4, "bfloat16": 2, "float16": 2, "int32": 4,
               "int8": 1, "uint8": 1}


@dataclasses.dataclass(frozen=True)
class Cost:
    flops: float
    bytes: float
    dtype: str  # the contraction's dtype, which picks the compute peak

    def least_s(self, peaks: Peaks) -> float:
        """Least time on the chip: the larger of the compute and the memory
        bound."""
        return max(self.flops / peaks.compute_per_s(self.dtype),
                   self.bytes / peaks.hbm_bytes_per_s)

    def compute_s(self, peaks: Peaks) -> float:
        return self.flops / peaks.compute_per_s(self.dtype)

    def bytes_s(self, peaks: Peaks) -> float:
        return self.bytes / peaks.hbm_bytes_per_s


def qmatmul(m: int, n: int, k: int) -> Cost:
    """int8 (m, k) @ (k, n) + int32 bias (n,), requantized to int8."""
    return Cost(2.0 * m * n * k,
                m * k + k * n + DTYPE_BYTES["int32"] * n + m * n, "int8")


def matmul(m: int, n: int, k: int, dtype: str) -> Cost:
    b = DTYPE_BYTES[dtype]
    return Cost(2.0 * m * n * k, b * (m * k + k * n + m * n), dtype)


def attention(b: int, hq: int, hkv: int, lq: int, lkv: int, d: int,
              dtype: str, causal: bool = True) -> Cost:
    """Scores and weighted values, QK^T and PV. Under a causal mask only the
    visible (query, key) pairs count; queries sit at the end of the keys."""
    if causal:
        offset = lkv - lq
        pairs = sum(min(lkv, i + 1 + offset) for i in range(lq))
    else:
        pairs = lq * lkv
    flops = 2.0 * 2.0 * b * hq * pairs * d
    nbytes = DTYPE_BYTES[dtype] * (2 * b * hq * lq * d + 2 * b * hkv * lkv * d)
    return Cost(flops, nbytes, dtype)


def decode_step(cfg: dict, batch: int, context: int,
                dtype: str = "bfloat16") -> Cost:
    """One decode step of a dense GQA LM: every weight matrix read once,
    the KV cache read over ``context`` positions and one position written,
    the logits written. ``cfg`` holds the configuration file's sizes."""
    d, f, v = cfg["d_model"], cfg["d_ff"], cfg["vocab_size"]
    hq, hkv, hd = cfg["n_heads"], cfg["n_kv_heads"], cfg["head_dim"]
    layers = cfg["n_layers"]
    per_layer = d * hq * hd + 2 * d * hkv * hd + hq * hd * d + 3 * d * f
    weights = layers * per_layer + v * d  # plus the output head
    wb = DTYPE_BYTES[dtype]
    kv = 2 * layers * batch * hkv * hd * wb
    nbytes = (wb * weights + kv * context + kv  # cache read, one slot written
              + wb * batch * v)  # logits
    flops = (2.0 * batch * weights
             + 2.0 * 2.0 * layers * batch * hq * hd * context)
    return Cost(flops, nbytes, dtype)


def share(least_s: float, measured_s: float) -> float | None:
    """Least time over measured time, in percent; None without a
    measurement."""
    if not measured_s > 0:
        return None
    return 100.0 * least_s / measured_s
