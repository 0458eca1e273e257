"""Hardware configurations — the TPU analogue of the paper's VLEN parameter.

The paper tunes the same workload on FPGA SoCs with VLEN in {256, 512, 1024}
bits and shows hand-written kernels degrade across configs while tuned
schedules adapt. Here a :class:`HardwareConfig` captures the TPU parameters
that play the same role: VMEM capacity and MXU geometry bound the micro-kernel
block sizes (as VLEN bounds VL), while peak FLOP/s, HBM and ICI bandwidths
feed the analytic roofline runner and the roofline report.
"""

from __future__ import annotations

import dataclasses

GiB = 1024**3
MiB = 1024**2


@dataclasses.dataclass(frozen=True)
class HardwareConfig:
    """Parameters of one accelerator configuration (the "VLEN" of this work)."""

    name: str
    # Peak compute, FLOP/s per chip, by compute dtype.
    peak_flops_bf16: float
    peak_flops_f32: float
    peak_flops_int8: float
    # Memory system.
    hbm_bandwidth: float  # bytes/s
    hbm_capacity: int  # bytes
    # bytes (bounds the block working set, like VLEN); every kernel asks the
    # compiler for all of it (``vmem_limit_bytes``)
    vmem_capacity: int
    # Interconnect (per-link, one direction).
    ici_bandwidth: float  # bytes/s
    # Fraction of VMEM a kernel's buffers may occupy (the footprint model,
    # ``space.vmem_footprint``, counts every buffer Pallas allocates,
    # double-buffering included). The rest is headroom for the compiler's
    # own temporaries and semaphores. This is the one authoritative
    # bound shared by the dynamic postprocessor (``postproc_vmem_fit``) and
    # the static feasibility analyzer (``core/static_analysis.py``) — tuning
    # it per part (or per compiler release) must move both in lockstep.
    vmem_headroom: float = 0.9
    # Compute unit geometry.
    mxu_dim: int = 128  # systolic array is mxu_dim x mxu_dim
    vpu_lanes: int = 128
    vpu_sublanes: int = 8
    # Fixed overhead charged per Pallas grid step by the analytic model
    # (instruction issue + DMA setup); exposes the paper's "too-small VL is
    # not worth vectorizing" effect (they stop at VL=4, we stop at one tile).
    grid_step_overhead_s: float = 1.5e-6

    @property
    def vmem_budget(self) -> float:
        """Usable VMEM bytes for a block working set (capacity x headroom) —
        the single bound both validation paths compare footprints against."""
        return self.vmem_capacity * self.vmem_headroom

    def peak_flops(self, dtype: str) -> float:
        if dtype in ("int8", "uint8"):
            return self.peak_flops_int8
        if dtype in ("bfloat16", "float16"):
            return self.peak_flops_bf16
        return self.peak_flops_f32

    def sublane_align(self, dtype: str) -> int:
        """Minimum tile size in the second-to-last dim for this dtype."""
        packing = {"float32": 1, "bfloat16": 2, "float16": 2, "int8": 4,
                   "uint8": 4, "int32": 1}.get(dtype, 1)
        return self.vpu_sublanes * packing

    def lane_align(self, dtype: str) -> int:  # last-dim tile multiple
        del dtype
        return self.vpu_lanes


# TPU v5e — the production target.
V5E = HardwareConfig(
    name="tpu_v5e",
    peak_flops_bf16=197e12,
    peak_flops_f32=98.5e12,
    peak_flops_int8=394e12,
    hbm_bandwidth=819e9,
    hbm_capacity=16 * GiB,
    vmem_capacity=128 * MiB,
    ici_bandwidth=50e9,
)

# The "VLEN sweep" analogue: same chip family, different on-chip memory /
# compute-unit geometry. The paper's Figure 4 experiment re-tunes per config.
V5E_VMEM32 = dataclasses.replace(V5E, name="tpu_v5e_vmem32", vmem_capacity=32 * MiB)
V5E_VMEM64 = dataclasses.replace(V5E, name="tpu_v5e_vmem64", vmem_capacity=64 * MiB)
V5E_MXU256 = dataclasses.replace(
    V5E, name="tpu_v5e_mxu256", mxu_dim=256,
    peak_flops_bf16=4 * 197e12, peak_flops_f32=4 * 98.5e12,
    peak_flops_int8=4 * 394e12,
)

# CPU-interpret "hardware": what the InterpretRunner actually times on this
# container. Block alignment constraints are relaxed (interpret mode has no
# MXU), mirroring how the paper used both QEMU and FPGA targets.
INTERPRET = HardwareConfig(
    name="cpu_interpret",
    peak_flops_bf16=1e11,
    peak_flops_f32=1e11,
    peak_flops_int8=1e11,
    hbm_bandwidth=20e9,
    hbm_capacity=8 * GiB,
    vmem_capacity=128 * MiB,
    ici_bandwidth=1e9,
    mxu_dim=8,
    vpu_lanes=8,
    vpu_sublanes=1,
    grid_step_overhead_s=50e-6,
)

SWEEP = (V5E_VMEM32, V5E_VMEM64, V5E)

_REGISTRY = {hw.name: hw for hw in (V5E, V5E_VMEM32, V5E_VMEM64, V5E_MXU256, INTERPRET)}


# ``device_kind`` as JAX reports it -> the configuration of that part.
_BY_DEVICE_KIND = {"TPU v5 lite": V5E}


def get(name: str) -> HardwareConfig:
    return _REGISTRY[name]


def for_device_kind(kind: str) -> HardwareConfig:
    """The configuration of an attached device. A kind with no entry is an
    error, never a default: its VMEM size and peaks are unknown."""
    try:
        return _BY_DEVICE_KIND[kind]
    except KeyError:
        raise ValueError(
            f"no hardware configuration for device kind {kind!r} "
            f"(known: {sorted(_BY_DEVICE_KIND)})") from None
