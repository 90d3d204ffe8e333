"""What a cell is made of, read from data.

``BENCHMARK.json`` is the registry: a cell (``workloads`` entry) names a
configuration and a traffic mix; a per-layer metric names its cells.
Everything that belongs to one of them sits in a file the name leads to:

    configs[].file                      the deployment (shapes, guarantees,
                                        ``adapter`` and ``reference`` modules)
    benchmark/traffic/<traffic>.json    the traffic mix (parameters only)
    benchmark/metrics/<metric>.json     the metric's ``reader`` and its arguments
    benchmark/adapters/<adapter>.py     set up, feed, observe, verify one
                                        kind of deployment
    benchmark/readers/<reader>.py       trace / counters / spans -> one number

A later PR adds files and entries and edits none that is there.
"""

from __future__ import annotations

import dataclasses
import importlib
import json
import os
from typing import Any, Dict, List

from benchmark.harness import traffic

BENCH_DIR = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH_DIR)


class SpecError(ValueError):
    """BENCHMARK.json or a file it leads to is missing or inconsistent."""


def _load_json(path: str) -> Dict[str, Any]:
    try:
        with open(path) as f:
            return json.load(f)
    except (OSError, json.JSONDecodeError) as e:
        raise SpecError(f"{os.path.relpath(path, ROOT)}: {e}") from e


@dataclasses.dataclass
class Cell:
    name: str
    chips: int
    config: Dict[str, Any]
    traffic: Dict[str, Any]
    #: BENCHMARK.json entries of the metrics this cell reports
    end_to_end: List[Dict[str, Any]]
    per_layer: List[Dict[str, Any]]


def benchmark() -> Dict[str, Any]:
    return _load_json(os.path.join(ROOT, "BENCHMARK.json"))


def cell_names() -> List[str]:
    return [w["name"] for w in benchmark()["workloads"]]


def _in_cell(metric: Dict[str, Any], cell: str) -> bool:
    return "workloads" not in metric or cell in metric["workloads"]


def _load_traffic(name: str) -> Dict[str, Any]:
    path = os.path.join(BENCH_DIR, "traffic", name + ".json")
    params = _load_json(path)
    try:
        traffic.check(params)
    except ValueError as e:
        raise SpecError(f"{os.path.relpath(path, ROOT)}: {e}") from e
    return params


def load_cell(name: str) -> Cell:
    bench = benchmark()
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise SpecError(f"no cell {name!r}; BENCHMARK.json has "
                        f"{sorted(cells)}")
    w = cells[name]
    configs = {c["name"]: c for c in bench["configs"]}
    if w["config"] not in configs:
        raise SpecError(f"cell {name!r} names unknown config {w['config']!r}")
    end_to_end = [m for m in bench["end_to_end"] if _in_cell(m, name)]
    moved = {m["name"] for m in end_to_end}
    per_layer = [m for m in bench["per_layer"]
                 if _in_cell(m, name) and m["moves"] in moved]
    return Cell(
        name=name, chips=int(w["chips"]),
        config=_load_json(os.path.join(ROOT, configs[w["config"]]["file"])),
        traffic=_load_traffic(w["traffic"]),
        end_to_end=end_to_end, per_layer=per_layer,
    )


def metric_file(name: str) -> Dict[str, Any]:
    return _load_json(os.path.join(BENCH_DIR, "metrics", name + ".json"))


def plugin(kind: str, name: str):
    """The module ``benchmark/<kind>/<name>.py`` (kind: adapters, readers,
    references) — imported by the name a data file gives."""
    if not name.replace("_", "").isalnum():
        raise SpecError(f"bad {kind} name {name!r}")
    try:
        return importlib.import_module(f"benchmark.{kind}.{name}")
    except ModuleNotFoundError as e:
        if e.name == f"benchmark.{kind}.{name}":
            raise SpecError(f"no module benchmark/{kind}/{name}.py") from e
        raise
