"""The benchmark's one command, driven by data.

``python bench/run.py --workload <cell> --seed <n> --seconds <s>
--trace <0|1>`` finds everything by name:

- the cell in ``BENCHMARK.json`` (its configuration, traffic and chips);
- the configuration in ``bench/configs/<config>.json``;
- the traffic mix in ``bench/traffic/<traffic>.json``, which names its
  driver, ``bench/drivers/<driver>.py``;
- the cell's correctness limits in ``bench/workloads/<cell>.json``;
- each per-layer metric's reader in ``bench/metrics/<metric>.py``.

A driver's ``run(ctx)`` makes the inputs from the seed, sets up, measures
for ``--seconds`` and checks what the timed path produced against the plain
reference. The harness prints the checks (each number beside its limit) as
the last lines of standard error and one JSON line last on standard
output. Without a TPU, or with fewer chips than the cell asks for, it
prints no result and exits non-zero.
"""

from __future__ import annotations

import argparse
import dataclasses
import importlib
import importlib.util
import json
import os
import pathlib
import sys

ROOT = pathlib.Path(__file__).resolve().parents[2]
BENCH = ROOT / "bench"
# JAX's persistent compilation cache: a fixed directory inside the checkout
CACHE_DIR = ROOT / ".jax_cache" / "bench"

EXIT_NO_DEVICE = 3


class NoDevice(RuntimeError):
    """No accelerator, or fewer chips than the cell asks for."""


@dataclasses.dataclass
class Cell:
    name: str
    config_name: str
    config: dict
    traffic_name: str
    traffic: dict
    limits: dict
    chips: int
    end_to_end: list[dict]  # BENCHMARK.json entries this cell reports
    per_layer: list[dict]

    @property
    def driver(self) -> str:
        return self.traffic["driver"]


@dataclasses.dataclass
class Check:
    """One number compared, beside its limit: it passes when
    ``value <= limit``."""

    name: str
    value: float
    limit: float

    @property
    def ok(self) -> bool:
        return self.value <= self.limit


@dataclasses.dataclass
class Outcome:
    """What a driver hands back to the harness."""

    end_to_end: dict[str, float]
    record: dict  # spans, counters and trace reductions for the readers
    checks: list[Check]
    attempted: int
    failed: int
    memory_peak_bytes: int
    busy_s: float | None = None  # traced runs only
    window_s: float | None = None
    breakdown: dict | None = None


@dataclasses.dataclass
class Context:
    cell: Cell
    seed: int
    seconds: float
    trace: bool
    t_start: float  # perf_counter at process start
    peaks: object
    devices: list


def read_json(path: pathlib.Path) -> dict:
    with open(path) as f:
        return json.load(f)


def _reports(entry: dict, cell: str) -> bool:
    return "workloads" not in entry or cell in entry["workloads"]


def load_cell(name: str, benchmark: dict | None = None,
              bench_dir: pathlib.Path = BENCH) -> Cell:
    """The cell ``name`` with its configuration, traffic and limits."""
    benchmark = benchmark or read_json(bench_dir.parent / "BENCHMARK.json")
    cells = {w["name"]: w for w in benchmark["workloads"]}
    if name not in cells:
        raise KeyError(f"no cell {name!r} in BENCHMARK.json "
                       f"(cells: {sorted(cells)})")
    w = cells[name]
    configs = {c["name"]: c for c in benchmark["configs"]}
    config = read_json(bench_dir.parent / configs[w["config"]]["file"])
    traffic = read_json(bench_dir / "traffic" / f"{w['traffic']}.json")
    limits = read_json(bench_dir / "workloads" / f"{name}.json")["limits"]
    e2e = [m for m in benchmark["end_to_end"] if _reports(m, name)]
    reported = {m["name"] for m in e2e}
    per_layer = [m for m in benchmark["per_layer"]
                 if ("workloads" in m and name in m["workloads"])
                 or ("workloads" not in m and m["moves"] in reported)]
    return Cell(name, w["config"], config, w["traffic"], traffic, limits,
                int(w["chips"]), e2e, per_layer)


def metric_reader(name: str, bench_dir: pathlib.Path = BENCH):
    """``read(record) -> float | None`` of the per-layer metric ``name``."""
    path = bench_dir / "metrics" / f"{name}.py"
    spec = importlib.util.spec_from_file_location(
        "bench_metric_" + name.replace(".", "_").replace("-", "_"), path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module.read


def load_driver(kind: str):
    return importlib.import_module(f"bench.drivers.{kind}")


def prepare_environment() -> pathlib.Path:
    """Place JAX's persistent compilation cache inside the checkout, before
    JAX is imported. The program's own cache placement reads the same
    variable, so program and benchmark share this one directory."""
    os.environ["JAX_COMPILATION_CACHE_DIR"] = str(CACHE_DIR)
    return CACHE_DIR


def require_devices(chips: int) -> list:
    """JAX's devices; a platform other than TPU, or too few, raises."""
    import jax

    devices = jax.devices()
    if devices[0].platform != "tpu":
        raise NoDevice(f"no TPU: JAX's first device is "
                       f"{devices[0].platform}")
    if len(devices) < chips:
        raise NoDevice(f"the cell asks for {chips} chips, JAX sees "
                       f"{len(devices)}")
    return devices[:chips]


def per_layer_metrics(cell: Cell, record: dict,
                      bench_dir: pathlib.Path = BENCH) -> dict:
    out = {}
    for m in cell.per_layer:
        value = metric_reader(m["name"], bench_dir)(record)
        if value is not None:
            out[m["name"]] = {"value": value, "unit": m["unit"]}
    return out


def end_to_end_metrics(cell: Cell, values: dict[str, float]) -> dict:
    return {m["name"]: {"value": values[m["name"]], "unit": m["unit"]}
            for m in cell.end_to_end}


def result(cell: Cell, traced: bool, outcome: Outcome, device: dict,
           bench_dir: pathlib.Path = BENCH) -> dict:
    """The result line: ``correct``, ``attempted``, ``failed``,
    ``metrics`` and ``device`` (and ``breakdown`` when traced), and the
    numbers compared, each with its limit, last."""
    metrics = (per_layer_metrics(cell, outcome.record, bench_dir)
               if traced else end_to_end_metrics(cell, outcome.end_to_end))
    device = dict(device, memory_peak_bytes=outcome.memory_peak_bytes)
    if traced:
        device.update(busy_s=outcome.busy_s, window_s=outcome.window_s)
    line = {
        "correct": bool(outcome.checks)
                   and all(c.ok for c in outcome.checks),
        "attempted": outcome.attempted,
        "failed": outcome.failed,
        "metrics": metrics,
        "device": device,
    }
    if traced and outcome.breakdown is not None:
        line["breakdown"] = outcome.breakdown
    line["checks"] = {c.name: {"value": c.value, "limit": c.limit}
                      for c in outcome.checks}
    return line


def parse_args(argv):
    ap = argparse.ArgumentParser(prog="bench/run.py")
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    return ap.parse_args(argv)


def _log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


def main(argv, t_start: float) -> int:
    args = parse_args(argv)
    cell = load_cell(args.workload)
    prepare_environment()
    try:
        devices = require_devices(cell.chips)
    except NoDevice as exc:
        _log(f"bench: {exc}; no result")
        return EXIT_NO_DEVICE
    from bench.lib.peaks import peaks_for

    kind = devices[0].device_kind
    ctx = Context(cell, args.seed, args.seconds, bool(args.trace), t_start,
                  peaks_for(kind), devices)
    outcome = load_driver(cell.driver).run(ctx)
    line = result(cell, ctx.trace, outcome, {
        "platform": devices[0].platform, "kind": kind,
        "count": len(devices)})
    for c in outcome.checks:
        _log(f"check {c.name} = {c.value!r} limit {c.limit!r} "
             f"{'ok' if c.ok else 'FAILED'}")
    print(json.dumps(line), flush=True)
    return 0
