"""Published peaks of each accelerator the benchmark runs on.

Keyed by ``device_kind`` as JAX reports it. A kind that is not here is an
error, never a default: a share of an unknown peak means nothing.
"""

from __future__ import annotations

import dataclasses

# Google Cloud documentation, "TPU v5e" (per chip): 197 TFLOP/s bf16,
# 393 TOP/s int8, 16 GB of HBM at 819 GB/s. No float32 peak is published;
# float32 contractions run as several bf16 passes on the MXU, so the bf16
# peak bounds them and is used for them.
_TABLE = {
    "TPU v5 lite": dict(
        source="Google Cloud documentation, TPU v5e, per chip",
        bf16_flops_per_s=197e12,
        int8_ops_per_s=393e12,
        hbm_bytes_per_s=819e9,
        hbm_bytes=16e9,
    ),
}


@dataclasses.dataclass(frozen=True)
class Peaks:
    kind: str
    source: str
    bf16_flops_per_s: float
    int8_ops_per_s: float
    hbm_bytes_per_s: float
    hbm_bytes: float

    def compute_per_s(self, dtype: str) -> float:
        """Peak operations per second for a contraction in ``dtype``."""
        if dtype in ("int8", "uint8"):
            return self.int8_ops_per_s
        if dtype in ("bfloat16", "float16", "float32"):
            return self.bf16_flops_per_s
        raise ValueError(f"no published peak for dtype {dtype!r}")


def peaks_for(kind: str) -> Peaks:
    """The peaks of the device ``kind``; unknown kinds raise."""
    try:
        return Peaks(kind=kind, **_TABLE[kind])
    except KeyError:
        raise ValueError(f"no published peaks for device kind {kind!r} "
                         f"(known: {sorted(_TABLE)})") from None
