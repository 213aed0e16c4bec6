"""Find a cell's pieces by name: its entry in ``BENCHMARK.json``, its
configuration and traffic files, its limits and its metric readers."""
from __future__ import annotations

import dataclasses
import importlib.util
import json
import os

HERE = os.path.dirname(os.path.abspath(__file__))


@dataclasses.dataclass
class Cell:
    name: str
    chips: int
    config: dict  # the configuration file's content
    traffic: dict  # the traffic file's content
    end_to_end: list  # BENCHMARK.json entries this cell reports
    per_layer: list
    limits: dict  # {number: limit}; empty where no limits file exists


def _load_json(path):
    with open(path) as f:
        return json.load(f)


def _applies(metric: dict, cell: str) -> bool:
    return "workloads" not in metric or cell in metric["workloads"]


def resolve(root: str, workload: str, base: str = HERE) -> Cell:
    """The cell named ``workload`` of ``<root>/BENCHMARK.json``, its files
    found under ``base`` (the benchmark's directory)."""
    bench = _load_json(os.path.join(root, "BENCHMARK.json"))
    cells = {w["name"]: w for w in bench["workloads"]}
    if workload not in cells:
        raise KeyError(f"no workload {workload!r} in BENCHMARK.json (have {sorted(cells)})")
    w = cells[workload]
    configs = {c["name"]: c for c in bench["configs"]}
    config = _load_json(os.path.join(root, configs[w["config"]]["file"]))
    traffic = _load_json(os.path.join(base, "traffic", w["traffic"] + ".json"))
    limits_path = os.path.join(base, "limits", workload + ".json")
    limits = _load_json(limits_path)["limits"] if os.path.exists(limits_path) else {}
    return Cell(
        name=workload,
        chips=int(w["chips"]),
        config=config,
        traffic=traffic,
        end_to_end=[m for m in bench["end_to_end"] if _applies(m, workload)],
        per_layer=[m for m in bench["per_layer"] if _applies(m, workload)],
        limits={k: float(v["limit"]) for k, v in limits.items()},
    )


def _module(kind: str, name: str, base: str = HERE):
    path = os.path.join(base, kind, name + ".py")
    spec = importlib.util.spec_from_file_location(f"chipbench.{kind}.{name}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def reader(name: str, base: str = HERE):
    """The per-layer metric reader ``metrics/<name>.py``."""
    return _module("metrics", name, base)


def end_to_end(name: str):
    """The end-to-end metric ``end_to_end/<name>.py``."""
    return _module("end_to_end", name)


def builder(name: str):
    """The deployment builder ``builders/<name>.py``."""
    return _module("builders", name)
