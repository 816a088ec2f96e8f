"""Find a cell, its configuration, its traffic mix, its reference module
and its metric readers by the names ``BENCHMARK.json`` and the
configuration file give them."""
from __future__ import annotations

import importlib
import importlib.util
import json
from pathlib import Path
from typing import Callable, List

HERE = Path(__file__).resolve().parent
ROOT = HERE.parents[1]


class BenchError(RuntimeError):
    """The run cannot give a result (no chip, a wrong configuration)."""


def _json(path: Path) -> dict:
    with open(path) as f:
        return json.load(f)


def load_benchmark(root: Path = ROOT) -> dict:
    return _json(root / "BENCHMARK.json")


def cell(bench: dict, name: str) -> dict:
    """The workload entry of ``BENCHMARK.json`` merged with the engine
    settings and checks in ``cells/<name>.json``."""
    for w in bench["workloads"]:
        if w["name"] == name:
            return {**w, **_json(HERE / "cells" / f"{name}.json")}
    raise KeyError(f"no workload {name!r} in BENCHMARK.json; have "
                   f"{[w['name'] for w in bench['workloads']]}")


def config(bench: dict, name: str, root: Path = ROOT) -> dict:
    for c in bench["configs"]:
        if c["name"] == name:
            return _json(root / c["file"])
    raise KeyError(f"no configuration {name!r} in BENCHMARK.json")


def reference(config: dict):
    """``references/<name>.py`` for the configuration's ``"reference"``:
    its sizes, weights, forward pass and work counts
    (``references/__init__.py``)."""
    name = config["reference"]
    have = sorted(p.stem for p in (HERE / "references").glob("*.py")
                  if not p.stem.startswith("_"))
    if name not in have:
        raise BenchError(f"the configuration names reference {name!r}; "
                         f"references/ holds {have}")
    return importlib.import_module(f"benchmarks.chip.references.{name}")


def traffic(name: str) -> dict:
    return _json(HERE / "traffic" / f"{name}.json")


def end_to_end(bench: dict, cell_name: str) -> List[dict]:
    return [m for m in bench["end_to_end"]
            if cell_name in m.get("workloads", [cell_name])]


def per_layer(bench: dict, cell_name: str) -> List[dict]:
    return [m for m in bench["per_layer"]
            if cell_name in m.get("workloads", [cell_name])]


def reader(metric: str) -> Callable:
    """``metrics/<metric>.py``'s ``read(record, reduced)``."""
    path = HERE / "metrics" / f"{metric}.py"
    spec = importlib.util.spec_from_file_location(
        "bench_metric_" + metric.replace(".", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read
