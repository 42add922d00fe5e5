"""What every cell shares: finding its files by name, and its result line.

`BENCHMARK.json` names each cell's configuration and traffic mix and
lists the per-layer metrics. Each of those lives in a file of its own:

  bench/configs/<config>.json       the deployment, its source and cuts
  bench/traffic/<traffic>.json      the mix: its parameters, and in
                                    `generator` the generator that reads them
  bench/generators/<generator>.py   makes a run's calls from the mix and
                                    `--seed`, and submits each to the system
  bench/layer_metrics/<metric>.py   a reader: `read(ctx) -> float | None`
  bench/systems/<system>.py         the driver named by a config's `system`
  bench/reference/<system>.py       that system's plain reference

so a later change adds a cell, a mix, a kind of traffic or a metric by
adding files and entries, never by editing one.
"""
from __future__ import annotations

import dataclasses
import importlib
import importlib.util
import json
import os
import sys
from typing import Any

import numpy as np

BENCH_DIR = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH_DIR)


def load_benchmark(root: str = ROOT) -> dict:
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        return json.load(f)


def _names(directory: str, suffix: str) -> list:
    if not os.path.isdir(directory):
        return []
    return sorted(n[:-len(suffix)] for n in os.listdir(directory)
                  if n.endswith(suffix) and not n.startswith("_"))


def list_configs(bench_dir: str = BENCH_DIR) -> list:
    return _names(os.path.join(bench_dir, "configs"), ".json")


def list_mixes(bench_dir: str = BENCH_DIR) -> list:
    return _names(os.path.join(bench_dir, "traffic"), ".json")


def list_generators(bench_dir: str = BENCH_DIR) -> list:
    return _names(os.path.join(bench_dir, "generators"), ".py")


def list_layer_metrics(bench_dir: str = BENCH_DIR) -> list:
    return _names(os.path.join(bench_dir, "layer_metrics"), ".py")


def load_json(bench_dir: str, kind: str, name: str) -> dict:
    with open(os.path.join(bench_dir, kind, name + ".json")) as f:
        return json.load(f)


def _module(kind: str, name: str, bench_dir: str):
    """`bench/<kind>/<name>.py`, loaded by its path (names may hold dots)."""
    path = os.path.join(bench_dir, kind, name + ".py")
    spec = importlib.util.spec_from_file_location(
        f"bench_{kind}_" + name.replace(".", "_").replace("-", "_"), path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


def layer_reader(name: str, bench_dir: str = BENCH_DIR):
    """The `read` function of `bench/layer_metrics/<name>.py`."""
    return _module("layer_metrics", name, bench_dir).read


def traffic_generator(mix: dict, bench_dir: str = BENCH_DIR):
    """The module `bench/generators/<mix["generator"]>.py`."""
    return _module("generators", mix["generator"], bench_dir)


def rng(seed: int, stream: int = 0) -> np.random.Generator:
    """A generator for `--seed` (any whole number) and a named stream."""
    return np.random.default_rng([seed % 2**64, stream])


def system_driver(system: str):
    return importlib.import_module(f"bench.systems.{system}")


@dataclasses.dataclass
class Cell:
    """One cell of `BENCHMARK.json`, resolved to its files, for one run."""

    name: str
    config_name: str
    traffic_name: str
    chips: int
    cfg: dict
    mix: dict
    seed: int = 0
    seconds: float = 10.0
    trace: bool = False
    bench_dir: str = BENCH_DIR

    @classmethod
    def from_benchmark(cls, name: str, bench: dict,
                       bench_dir: str = BENCH_DIR, **kw) -> "Cell":
        cells = {w["name"]: w for w in bench["workloads"]}
        if name not in cells:
            raise KeyError(f"no workload {name!r}; known: {sorted(cells)}")
        w = cells[name]
        confs = {c["name"]: c for c in bench["configs"]}
        conf = confs[w["config"]]
        with open(os.path.join(os.path.dirname(bench_dir), conf["file"])) as f:
            cfg = json.load(f)
        mix = load_json(bench_dir, "traffic", w["traffic"])
        return cls(name=name, config_name=w["config"],
                   traffic_name=w["traffic"], chips=int(w["chips"]),
                   cfg=cfg, mix=mix, bench_dir=bench_dir, **kw)


def metrics_of(bench: dict, cell: str, kind: str) -> list:
    """The `end_to_end` or `per_layer` entries that this cell reports."""
    return [m for m in bench[kind]
            if "workloads" not in m or cell in m["workloads"]]


def read_layer_metrics(bench: dict, cell: str, ctx: Any,
                       bench_dir: str = BENCH_DIR) -> dict:
    """Each per-layer metric of the cell that its reader finds."""
    out = {}
    for m in metrics_of(bench, cell, "per_layer"):
        value = layer_reader(m["name"], bench_dir)(ctx)
        if value is not None:
            out[m["name"]] = {"value": value, "unit": m["unit"]}
    return out


def result_line(*, correct: bool, attempted: int, failed: int,
                metrics: dict, device: dict, checks: list,
                breakdown: dict | None = None) -> str:
    """The run's last line of standard output; `checks` comes last."""
    line = {"correct": bool(correct), "attempted": int(attempted),
            "failed": int(failed), "metrics": metrics, "device": device}
    if breakdown is not None:
        line["breakdown"] = breakdown
    line["checks"] = {c.name: {"value": c.value, "limit": c.limit}
                      for c in checks}
    return json.dumps(line)


@dataclasses.dataclass
class Check:
    """One number compared with the reference, and its limit."""

    name: str
    value: float
    limit: float

    @property
    def ok(self) -> bool:
        return self.value <= self.limit


def print_checks(checks: list):
    """The numbers compared, as the last lines of standard error."""
    for c in checks:
        print(f"check {c.name}: {c.value!r} (limit {c.limit!r}) "
              f"{'ok' if c.ok else 'FAILED'}", file=sys.stderr, flush=True)
