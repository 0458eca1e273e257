"""Compile-only checks for a TPU v5e, made without one.

The TPU compiler is installed with JAX and compiles for a described chip
that is not attached: it refuses what the chip's compiler would refuse (a
block the TPU cannot tile, more VMEM than a kernel may use, a program that
does not fit the device's memory), which interpret mode cannot show. Nothing
runs, so these tests say nothing about results or times.

The topology is described inside a module fixture, never at import: only one
process at a time may load the TPU library, and every test worker imports
this file.
"""

import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro import kernels
from repro.configs import get_config
from repro.core import (V5E, Schedule, attention, concretize,
                        fixed_library_schedule, gemv, matmul, qmatmul,
                        space_for, vmacc)
from repro.launch.serve import serving_config
from repro.models.model_zoo import build

HBM_LIMIT = 15.75e9  # what the v5e compiler reports as usable HBM


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    assert topo.devices[0].device_kind == "TPU v5 lite"
    return SingleDeviceSharding(topo.devices[0])


def _compile_kernel(wl, params, sharding):
    assert params.valid, params.why_invalid
    fn = kernels.build(wl, params, interpret=False, cache=False)
    specs = [jax.ShapeDtypeStruct(shape, jnp.dtype(dtype), sharding=sharding)
             for shape, dtype in wl.input_specs()]
    compiled = jax.jit(fn).lower(*specs).compile()
    assert "tpu_custom_call" in compiled.as_text()  # the Pallas kernel
    return compiled


# The chip smoke run's widths: yi-6b decode projections at batch 1 (gemv)
# and batch 4 (matmul), plus one workload of each other family.
LIBRARY_WORKLOADS = {
    "gemv_lm_head": gemv(64000, 4096, "bfloat16"),
    "gemv_ffn_up": gemv(11008, 4096, "bfloat16"),
    "matmul_ffn_down": matmul(4, 4096, 11008, "bfloat16"),
    "qmatmul": qmatmul(256, 4096, 4096),
    "vmacc": vmacc(512, 4096, "bfloat16"),
    "attention": attention(1, 32, 4, 512, 512, 128, "bfloat16"),
}


@pytest.mark.parametrize("name", sorted(LIBRARY_WORKLOADS))
def test_library_kernel_compiles(one_chip, name):
    wl = LIBRARY_WORKLOADS[name]
    _compile_kernel(wl, concretize(wl, V5E, fixed_library_schedule(wl, V5E)),
                    one_chip)


# one workload of each kernel family, by the name its pallas_call carries
FAMILY_WORKLOADS = {
    "gemv": "gemv_ffn_up",
    "matmul": "matmul_ffn_down",
    "qmatmul": "qmatmul",
    "vmacc": "vmacc",
    "flash_attention": "attention",
}


@pytest.mark.parametrize("family", sorted(FAMILY_WORKLOADS))
def test_kernel_is_named_by_its_family(one_chip, family):
    """A profile names the kernel: the lowered program carries the family's
    name on its Pallas call."""
    wl = LIBRARY_WORKLOADS[FAMILY_WORKLOADS[family]]
    params = concretize(wl, V5E, fixed_library_schedule(wl, V5E))
    fn = kernels.build(wl, params, interpret=False, cache=False)
    specs = [jax.ShapeDtypeStruct(shape, jnp.dtype(dtype), sharding=one_chip)
             for shape, dtype in wl.input_specs()]
    assert f'kernel_name = "{family}"' in jax.jit(fn).lower(*specs).as_text()


def _largest_valid(wl, accumulate):
    """The space-valid candidate of one accumulate form with the largest
    modelled VMEM footprint."""
    prog = space_for(wl, V5E)
    best = None
    for trace in prog.traces():
        if trace.get("accumulate", True) != accumulate:
            continue
        p = prog.validate(Schedule.fixed(**trace))
        if p.valid and (best is None or p.vmem_bytes > best.vmem_bytes):
            best = p
    return best


@pytest.mark.parametrize("wl", [matmul(1024, 4096, 4096, "bfloat16"),
                                gemv(64000, 4096, "bfloat16")],
                         ids=["matmul_1024x4096x4096", "gemv_64000x4096"])
@pytest.mark.parametrize("accumulate", [True, False],
                         ids=["accumulate", "store_heavy"])
def test_largest_space_valid_candidate_compiles(one_chip, wl, accumulate):
    """"Space-valid" must mean "compiles": the candidate the footprint model
    puts closest to the VMEM budget is accepted by the chip's compiler."""
    params = _largest_valid(wl, accumulate)
    assert params.vmem_bytes <= V5E.vmem_budget
    assert params.vmem_limit == V5E.vmem_capacity
    _compile_kernel(wl, params, one_chip)


def test_yi_6b_decode_step_fits_one_chip(one_chip):
    """yi-6b at published widths, cut to 8 of 32 layers: the server's
    decode step (f32 weights, batch 4, a 1024-position cache) fits 16 GB."""
    cfg = serving_config("yi_6b", layers=8)
    published = get_config("yi_6b")
    assert (cfg.d_model, cfg.d_ff, cfg.n_heads, cfg.n_kv_heads,
            cfg.vocab_size) == (published.d_model, published.d_ff,
                                published.n_heads, published.n_kv_heads,
                                published.vocab_size)
    bundle = build(cfg, remat="none")

    def on_chip(tree):
        return jax.tree.map(lambda s: jax.ShapeDtypeStruct(
            s.shape, s.dtype, sharding=one_chip), tree)

    params = on_chip(jax.eval_shape(bundle.init, jax.random.key(0)))
    cache = on_chip(jax.eval_shape(lambda: bundle.init_cache(4, 1024)))
    tokens = jax.ShapeDtypeStruct((4, 1), jnp.int32, sharding=one_chip)
    pos = jax.ShapeDtypeStruct((), jnp.int32, sharding=one_chip)
    compiled = jax.jit(bundle.decode_fn).lower(params, cache, tokens,
                                               pos).compile()
    mem = compiled.memory_analysis()
    total = (mem.argument_size_in_bytes + mem.output_size_in_bytes
             + mem.temp_size_in_bytes + mem.generated_code_size_in_bytes
             - mem.alias_size_in_bytes)
    assert mem.argument_size_in_bytes > 7e9  # the published widths, in f32
    assert total < HBM_LIMIT, mem
