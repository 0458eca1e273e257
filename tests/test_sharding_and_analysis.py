"""Sharding-rule unit tests (AbstractMesh — no devices needed) and the
HLO cost-analyzer calibration tests."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

from repro.launch import hlo_analysis
from repro.runtime import sharding as sh
from repro.runtime.sharding import abstract_mesh

MESH = abstract_mesh((16, 16), ("data", "model"))
POD_MESH = abstract_mesh((2, 16, 16), ("pod", "data", "model"))


def test_param_rules():
    # embedding (padded vocab): vocab over model, d over data (FSDP)
    assert sh.spec_for("embedding", (49280, 2048), MESH) == P("model", "data")
    # attention projections: FSDP on d_model, TP on heads
    assert sh.spec_for("layers/attn/wq", (40, 2048, 2048), MESH) == \
        P(None, "data", "model")
    assert sh.spec_for("layers/attn/wo", (40, 2048, 2048), MESH) == \
        P(None, "model", "data")
    # kv projection with 8 heads * 64 = 512 still divides both axes
    assert sh.spec_for("layers/attn/wk", (40, 2048, 512), MESH) == \
        P(None, "data", "model")
    # MoE experts: EP over model
    assert sh.spec_for("layers/experts/w_gate", (24, 64, 2048, 1408),
                       MESH) == P(None, "model", "data")
    # small/non-divisible dims replicate (divisibility fallback)
    assert sh.spec_for("layers/ln1", (40, 2048), MESH) == P()
    assert sh.spec_for("layers/attn/wk", (2, 24, 24), MESH) == P()


def test_pod_axis_only_extends_batch():
    assert sh.batch_axes(POD_MESH) == ("pod", "data")
    assert sh.batch_axes(MESH) == ("data",)
    # params never shard over 'pod' (pure DP across pods)
    spec = sh.spec_for("layers/mlp/w_up", (40, 2048, 8192), POD_MESH)
    assert "pod" not in jax.tree.leaves(spec)


def test_cache_rules():
    # default: context-parallel (sequence-sharded) cache
    s = sh.cache_sharding(MESH, (24, 128, 32768, 16, 128))
    assert s.spec == P(None, ("data",), "model")
    # heads preference when requested and divisible
    s = sh.cache_sharding(MESH, (24, 128, 32768, 16, 128), prefer="heads")
    assert s.spec == P(None, ("data",), None, "model")
    # tiny batch, single kv head: sequence sharding is the only option
    s = sh.cache_sharding(MESH, (26, 1, 524288, 1, 256))
    assert s.spec == P(None, None, "model")


# ------------------------------------------------------- HLO cost analyzer ----

def _compile(fn, *args):
    return jax.jit(fn).lower(*args).compile()


def test_analyzer_counts_single_matmul():
    x = jax.ShapeDtypeStruct((256, 512), jnp.float32)
    w = jax.ShapeDtypeStruct((512, 128), jnp.float32)
    c = _compile(lambda a, b: a @ b, x, w)
    s = hlo_analysis.analyze(c.as_text())
    assert s.flops == pytest.approx(2 * 256 * 512 * 128, rel=0.01)


def test_analyzer_multiplies_scan_trip_count():
    """The reason this analyzer exists: XLA cost_analysis counts while
    bodies once; ours multiplies by the trip count."""
    def scanned(x, ws):
        def body(c, w):
            return c @ w, None
        out, _ = jax.lax.scan(body, x, ws)
        return out

    x = jax.ShapeDtypeStruct((128, 128), jnp.float32)
    ws = jax.ShapeDtypeStruct((12, 128, 128), jnp.float32)
    c = _compile(scanned, x, ws)
    s = hlo_analysis.analyze(c.as_text())
    expect = 12 * 2 * 128**3
    assert s.flops == pytest.approx(expect, rel=0.01)
    xla = c.cost_analysis().get("flops", 0.0)
    assert xla < 0.2 * expect  # documents the undercount we correct


def test_analyzer_nested_scans():
    def nested(x, ws):
        def outer(c, w):
            def inner(ci, _):
                return ci @ w, None
            ci, _ = jax.lax.scan(inner, c, None, length=3)
            return ci, None
        out, _ = jax.lax.scan(outer, x, ws)
        return out

    x = jax.ShapeDtypeStruct((64, 64), jnp.float32)
    ws = jax.ShapeDtypeStruct((5, 64, 64), jnp.float32)
    c = _compile(nested, x, ws)
    s = hlo_analysis.analyze(c.as_text())
    assert s.flops == pytest.approx(5 * 3 * 2 * 64**3, rel=0.02)


def test_analyzer_shape_bytes():
    assert hlo_analysis.shape_bytes("bf16[16,128]{1,0}") == 16 * 128 * 2
    assert hlo_analysis.shape_bytes("(f32[4,4], s8[8])") == 64 + 8
    assert hlo_analysis.shape_bytes("f32[]") == 4
    assert hlo_analysis.shape_dims("f32[3,5,7]{2,1,0}") == [3, 5, 7]


def test_analyzer_census_categories():
    c = _compile(lambda a: jnp.tanh(a) @ a, jax.ShapeDtypeStruct(
        (64, 64), jnp.float32))
    s = hlo_analysis.analyze(c.as_text())
    assert s.op_census.get("compute", 0) >= 1
    assert s.n_instructions > 0
