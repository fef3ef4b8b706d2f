"""Resolve a cell's names to files. Everything that belongs to one
configuration, one traffic mix or one per-layer metric is a file found by
the name `BENCHMARK.json` gives it; nothing here lists them.

    workload.config   -> perfbench/configs/<config>.json
    config["family"]  -> perfbench/families/<family>.py
    workload.traffic  -> perfbench/traffic/<traffic>.json
    per_layer[].name  -> perfbench/layer_metrics/<name>.py  (``read(run)``)
    device_kind       -> a key of perfbench/peaks.json
"""

from __future__ import annotations

import dataclasses
import importlib.util
import json
import pathlib
from types import ModuleType

HERE = pathlib.Path(__file__).resolve().parent
ROOT = HERE.parent


def load_py(path: pathlib.Path) -> ModuleType:
    """Import one file by path (its name may hold '-' or '.')."""
    if not path.is_file():
        raise FileNotFoundError(f"no such file: {path}")
    name = "perfbench_file_" + "".join(
        c if c.isalnum() else "_" for c in str(path.relative_to(HERE)))
    spec = importlib.util.spec_from_file_location(name, path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


def load_json(path: pathlib.Path):
    with open(path) as f:
        return json.load(f)


@dataclasses.dataclass(frozen=True)
class Cell:
    name: str
    chips: int
    config_name: str
    config: dict          # the configuration file
    family: ModuleType    # perfbench/families/<family>.py
    traffic: dict         # the traffic file
    end_to_end: tuple     # metric entries of BENCHMARK.json reported here
    per_layer: tuple


def _reported_here(metrics: list, workload: str) -> tuple:
    return tuple(m for m in metrics
                 if workload in m.get("workloads", [workload]))


def resolve(workload: str, benchmark: dict | None = None) -> Cell:
    bench = benchmark or load_json(ROOT / "BENCHMARK.json")
    entries = [w for w in bench["workloads"] if w["name"] == workload]
    if not entries:
        raise KeyError(f"BENCHMARK.json has no workload {workload!r}")
    entry = entries[0]
    config = load_json(HERE / "configs" / f"{entry['config']}.json")
    traffic = load_json(HERE / "traffic" / f"{entry['traffic']}.json")
    if traffic["chips"] != entry["chips"]:
        raise ValueError(
            f"{workload}: BENCHMARK.json asks for {entry['chips']} chip(s), "
            f"traffic {entry['traffic']!r} is written for {traffic['chips']}")
    return Cell(
        name=workload, chips=entry["chips"], config_name=entry["config"],
        config=config,
        family=load_py(HERE / "families" / f"{config['family']}.py"),
        traffic=traffic,
        end_to_end=_reported_here(bench["end_to_end"], workload),
        per_layer=_reported_here(bench["per_layer"], workload))


def layer_reader(metric_name: str):
    """The ``read(run)`` of one per-layer metric."""
    return load_py(HERE / "layer_metrics" / f"{metric_name}.py").read


def peaks(device_kind: str) -> dict:
    """The chip's published peaks; a device that is not in the table is an
    error, never a default."""
    table = load_json(HERE / "peaks.json")
    if device_kind not in table:
        raise KeyError(
            f"device kind {device_kind!r} is not in perfbench/peaks.json "
            f"(known: {sorted(table)})")
    return table[device_kind]
