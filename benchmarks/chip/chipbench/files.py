"""Where a cell's files live, found by name.

``BENCHMARK.json`` names a configuration and a traffic mix for each cell,
and each per-layer metric by name. Each of those is a file of its own:

    <root>/configs/<config>.json    sizes, source, program and reference
    <root>/traffic/<traffic>.json   parameters of the general generator
    <root>/metrics/<metric>.py      a reader: ``read(run) -> float | None``

so a later change adds a cell, configuration or metric by adding files
and entries, never by editing one that is there.
"""
from __future__ import annotations

import importlib.util
import json
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]          # benchmarks/chip
REPO = ROOT.parents[1]


def load_benchmark(repo: Path = REPO) -> dict:
    return json.loads((repo / "BENCHMARK.json").read_text())


def workload(bench: dict, name: str) -> dict:
    for w in bench["workloads"]:
        if w["name"] == name:
            return w
    raise KeyError(f"no workload {name!r} in BENCHMARK.json; have "
                   f"{[w['name'] for w in bench['workloads']]}")


def config_of(name: str, root: Path = ROOT) -> dict:
    return json.loads((root / "configs" / f"{name}.json").read_text())


def traffic_of(name: str, root: Path = ROOT) -> dict:
    return json.loads((root / "traffic" / f"{name}.json").read_text())


def metric_reader(name: str, root: Path = ROOT):
    """The ``read`` function of ``metrics/<name>.py``."""
    path = root / "metrics" / f"{name}.py"
    spec = importlib.util.spec_from_file_location(
        "chipbench_metric_" + name.replace(".", "_").replace("-", "_"), path)
    if spec is None:
        raise FileNotFoundError(path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def metrics_for(bench: dict, cell: str, section: str) -> list[dict]:
    """The metrics of ``section`` ("end_to_end" or "per_layer") that the
    cell reports: those without a ``workloads`` key, and those that list
    the cell."""
    return [m for m in bench[section]
            if "workloads" not in m or cell in m["workloads"]]
