"""The yardstick's arithmetic against hand counts: operations and least
bytes of each cell's operators and of the decode step, the peak table, and
shares of a roofline."""

import json
import pathlib

import pytest

from bench.lib import costs, netops
from bench.lib.peaks import peaks_for

ROOT = pathlib.Path(__file__).resolve().parents[2]
V5E = peaks_for("TPU v5 lite")


def _config(name):
    return json.loads((ROOT / "bench" / "configs" / f"{name}.json")
                      .read_text())


def _traffic(name):
    return json.loads((ROOT / "bench" / "traffic" / f"{name}.json")
                      .read_text())


def test_qmatmul_counts():
    c = costs.qmatmul(64, 576, 576)
    assert c.flops == 2 * 64 * 576 * 576 == 42_467_328
    # x, w and the output at one byte, the int32 bias at four
    assert c.bytes == 36_864 + 331_776 + 2_304 + 36_864


def test_causal_attention_counts():
    c = costs.attention(1, 9, 3, 64, 64, 64, "float32")
    # 64 * 65 / 2 visible pairs, QK^T and PV at two operations each
    assert c.flops == 4 * 9 * 2080 * 64 == 4_792_320
    assert c.bytes == 4 * (2 * 9 * 64 * 64 + 2 * 3 * 64 * 64) == 393_216
    full = costs.attention(1, 9, 3, 64, 64, 64, "float32", causal=False)
    assert full.flops == 4 * 9 * 64 * 64 * 64


def test_decode_step_counts():
    cfg = _config("yi_6b")
    c = costs.decode_step(cfg, batch=16, context=512)
    per_layer = 4096 * 4096 * 2 + 2 * 4096 * 512 + 3 * 4096 * 11008
    assert per_layer == 173_015_040
    weights = 8 * per_layer + 64000 * 4096
    kv_per_position = 2 * 8 * 16 * 4 * 128 * 2
    assert c.bytes == (2 * weights + kv_per_position * 513
                       + 2 * 16 * 64000) == 3_429_056_512
    assert c.flops == (2 * 16 * weights + 4 * 8 * 16 * 32 * 128 * 512
                       ) == 53_754_200_064
    assert c.least_s(V5E) == pytest.approx(3_429_056_512 / 819e9)


def test_mobilellm_operators_match_the_paper_list():
    every = dict(_traffic("tune-proj-seq64"),
                 families=["q", "kv", "attention", "o", "gate_up", "down",
                           "lm_head"])
    ops = netops.forward_ops(_config("mobilellm_125m"), every)
    assert [(o.name, o.kind, o.dims, o.count) for o in ops] == [
        ("q+o", "qmatmul", (64, 576, 576), 60),
        ("kv", "qmatmul", (64, 192, 576), 60),
        ("attention", "attention", (1, 9, 3, 64, 64, 64), 30),
        ("gate_up", "qmatmul", (64, 1536, 576), 60),
        ("down", "qmatmul", (64, 576, 1536), 30),
        ("lm_head", "qmatmul", (64, 32000, 576), 1),
    ]
    proj = netops.forward_ops(_config("mobilellm_125m"),
                              _traffic("tune-proj-seq64"))
    assert [o.name for o in proj] == ["q+o", "kv", "gate_up", "down",
                                      "lm_head"]
    weights = sum(o.count * o.dims[1] * o.dims[2] for o in proj)
    # the network's int8 weights, in bytes
    assert weights == (19_906_560 + 6_635_520 + 53_084_160 + 26_542_080
                       + 18_432_000) == 124_600_320
    # memory bound: the weights alone take 0.152 ms at 819 GB/s, the
    # activations, biases and outputs of 211 calls the rest
    least = sum(o.count * o.cost().least_s(V5E) for o in proj)
    assert 124_600_320 / 819e9 < least < 0.18e-3


def test_share_at_the_least_time_is_full():
    c = costs.qmatmul(64, 32000, 576)
    assert costs.share(c.least_s(V5E), c.least_s(V5E)) == pytest.approx(100)
    assert costs.share(1.0, 0.0) is None


def test_peaks_are_published_v5e_figures():
    assert V5E.compute_per_s("bfloat16") == 197e12
    assert V5E.compute_per_s("int8") == 393e12
    assert V5E.compute_per_s("float32") == 197e12
    assert V5E.hbm_bytes_per_s == 819e9


def test_unknown_device_kind_raises():
    with pytest.raises(ValueError, match="no published peaks"):
        peaks_for("TPU v99")
