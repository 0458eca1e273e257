"""The harness finds cells, configurations, traffic, limits and metric
readers by name; it prints no result without a TPU; its result line has the
contract's shape; ``BENCHMARK.json`` keeps the contract's form."""

import json
import os
import pathlib
import re
import subprocess
import sys

import pytest

from bench.lib import harness

ROOT = pathlib.Path(__file__).resolve().parents[2]
BENCHMARK = json.loads((ROOT / "BENCHMARK.json").read_text())
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")


@pytest.mark.parametrize("cell", [w["name"] for w in BENCHMARK["workloads"]])
def test_every_cell_loads_with_its_files(cell):
    c = harness.load_cell(cell)
    assert c.chips == 1
    assert {"setup_s"} < {m["name"] for m in c.end_to_end}
    assert c.per_layer
    for m in c.per_layer:
        assert callable(harness.metric_reader(m["name"]))
    assert harness.load_driver(c.driver).run


def test_benchmark_json_keeps_the_contract_form():
    assert set(BENCHMARK) == {"command", "paths", "run_seconds", "configs",
                              "workloads", "end_to_end", "per_layer"}
    names = []
    for c in BENCHMARK["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert c["file"].startswith("bench/")
        assert (ROOT / c["file"]).exists()
        names.append(c["name"])
    used = {w["config"] for w in BENCHMARK["workloads"]}
    assert used == {c["name"] for c in BENCHMARK["configs"]}
    e2e = {m["name"] for m in BENCHMARK["end_to_end"]}
    assert "setup_s" in e2e
    for m in BENCHMARK["end_to_end"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better", "bound",
                                          "source"}
        assert 0.01 <= m["bound"] <= 0.25
        assert m["source"] in ("host_clock", "device_trace")
    layers = {}
    for m in BENCHMARK["per_layer"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better", "source",
                                          "layer", "moves"}
        assert m["moves"] in e2e
        layers.setdefault(m["layer"], m["layer"])
    for m in BENCHMARK["end_to_end"] + BENCHMARK["per_layer"]:
        assert NAME.match(m["name"]) and UNIT.match(m["unit"])
        assert m["better"] in ("lower", "higher")
        names.append(m["name"])
        for w in m.get("workloads", ()):
            assert w in {c["name"] for c in BENCHMARK["workloads"]}
    for w in BENCHMARK["workloads"]:
        assert NAME.match(w["name"]) and NAME.match(w["traffic"])
        assert len(w["why"]) <= 200
        names.append(w["name"])
    assert len(names) == len(set(names))
    assert len(json.dumps(BENCHMARK)) < 64 * 1024


def _copy_bench(tmp_path):
    """The benchmark's files plus a cell, a configuration, a traffic mix,
    limits and a per-layer metric, added as files only."""
    import shutil

    bench = tmp_path / "bench"
    shutil.copytree(ROOT / "bench", bench,
                    ignore=shutil.ignore_patterns("__pycache__"))
    (bench / "configs" / "tiny.json").write_text(json.dumps(
        {"name": "tiny", "d_model": 8}))
    (bench / "traffic" / "tiny-mix.json").write_text(json.dumps(
        {"driver": "serve", "batch": 2}))
    (bench / "workloads" / "tiny-cell.json").write_text(json.dumps(
        {"limits": {"served_logit_gap": 0.5}}))
    (bench / "metrics" / "tiny.tokens_seen.py").write_text(
        "def read(rec):\n    return rec['counters'].get('tiny.tokens')\n")
    benchmark = json.loads(json.dumps(BENCHMARK))
    benchmark["configs"].append({"name": "tiny", "source": "a paper",
                                 "file": "bench/configs/tiny.json",
                                 "reduced": [], "why": "fixture"})
    benchmark["workloads"].append({"name": "tiny-cell", "config": "tiny",
                                   "traffic": "tiny-mix", "chips": 1,
                                   "why": "fixture"})
    benchmark["end_to_end"].insert(0, {
        "name": "serve_tokens_per_s", "unit": "tokens/s", "better": "higher",
        "bound": 0.01, "source": "host_clock", "workloads": ["tiny-cell"]})
    benchmark["per_layer"].append({
        "name": "tiny.tokens_seen", "unit": "tokens", "better": "higher",
        "source": "program_counter", "layer": "server",
        "moves": "serve_tokens_per_s", "workloads": ["tiny-cell"]})
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(benchmark))
    return bench, benchmark


def test_cell_and_metric_added_as_files_are_found_by_name(tmp_path):
    bench, benchmark = _copy_bench(tmp_path)
    cell = harness.load_cell("tiny-cell", benchmark, bench)
    assert cell.config == {"name": "tiny", "d_model": 8}
    assert cell.driver == "serve" and cell.traffic["batch"] == 2
    assert cell.limits == {"served_logit_gap": 0.5}
    assert [m["name"] for m in cell.end_to_end] == ["serve_tokens_per_s",
                                                    "setup_s"]
    assert [m["name"] for m in cell.per_layer] == ["tiny.tokens_seen"]
    rec = {"counters": {"tiny.tokens": 12}}
    assert harness.per_layer_metrics(cell, rec, bench) == {
        "tiny.tokens_seen": {"value": 12, "unit": "tokens"}}
    # a reader that finds nothing returns nothing: the metric is left out
    assert harness.per_layer_metrics(cell, {"counters": {}}, bench) == {}


def test_result_line_puts_the_checks_last():
    cell = harness.load_cell("mobilellm-tune-proj-seq64")
    out = harness.Outcome(
        end_to_end={"setup_s": 1.5, "tune_trials_per_s": 3.0,
                    "tuned_net_ms": 0.9},
        record={}, checks=[harness.Check("qmatmul_max_int_diff", 0, 0)],
        attempted=32, failed=0, memory_peak_bytes=10)
    line = harness.result(cell, False, out, {"platform": "tpu",
                                             "kind": "TPU v5 lite",
                                             "count": 1})
    assert list(line) == ["correct", "attempted", "failed", "metrics",
                          "device", "checks"]
    assert line["correct"] is True
    assert line["metrics"] == {
        "tune_trials_per_s": {"value": 3.0, "unit": "trials/s"},
        "tuned_net_ms": {"value": 0.9, "unit": "ms"},
        "setup_s": {"value": 1.5, "unit": "s"}}
    assert line["device"]["memory_peak_bytes"] == 10
    out.checks = [harness.Check("qmatmul_max_int_diff", 1, 0)]
    assert harness.result(cell, False, out, {})["correct"] is False
    out.checks = []
    assert harness.result(cell, False, out, {})["correct"] is False


def _run_bench(cwd, env_extra):
    env = dict(os.environ, JAX_PLATFORMS="cpu", **env_extra)
    return subprocess.run(
        [sys.executable, "bench/run.py", "--workload",
         "mobilellm-tune-proj-seq64",
         "--seed", str(2 ** 33 + 1), "--seconds", "1", "--trace", "0"],
        cwd=cwd, env=env, capture_output=True, text=True, timeout=120)


def test_no_result_without_a_tpu():
    proc = _run_bench(ROOT, {})
    assert proc.returncode == harness.EXIT_NO_DEVICE
    assert proc.stdout == ""
    assert "no TPU" in proc.stderr


def test_no_result_without_the_program(tmp_path):
    bench, _ = _copy_bench(tmp_path)
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(BENCHMARK))
    proc = _run_bench(tmp_path, {})
    assert proc.returncode != 0
    assert proc.stdout == ""
