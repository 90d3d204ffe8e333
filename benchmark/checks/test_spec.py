"""BENCHMARK.json and the files it leads to hold together.

    python -m pytest benchmark/checks -q        (not part of tier-1)
"""

import json
import os
import re
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
sys.path.insert(0, ROOT)

from benchmark.harness import roofline, spec  # noqa: E402

NAME = re.compile(r"^[A-Za-z0-9][A-Za-z0-9_.\-]{0,63}$")


def test_contract_shape():
    b = spec.benchmark()
    assert set(b) == {"command", "paths", "run_seconds", "configs",
                      "workloads", "end_to_end", "per_layer"}
    assert 1 <= b["run_seconds"] <= 51 and isinstance(b["run_seconds"], int)
    names = [x["name"] for k in ("configs", "workloads", "end_to_end",
                                 "per_layer") for x in b[k]]
    assert len(names) == len(set(names)) and all(NAME.match(n) for n in names)
    assert all(len(x["why"]) <= 200 for x in b["configs"] + b["workloads"])
    assert os.path.getsize(os.path.join(ROOT, "BENCHMARK.json")) < 64 * 1024
    e2e = {m["name"]: m for m in b["end_to_end"]}
    assert e2e["setup_s"]["bound"] == 0.25
    assert all(0.01 <= m["bound"] <= 0.25 for m in e2e.values())
    assert all(m["source"] in ("host_clock", "device_trace")
               for m in e2e.values())
    assert sum(w["chips"] == 4 for w in b["workloads"]) <= max(
        1, len(b["workloads"]) // 2)
    for c in b["configs"]:
        assert c["file"].startswith(tuple(p + "/" for p in b["paths"]))


@pytest.mark.parametrize("cell", spec.cell_names())
def test_every_cell_resolves_to_files_and_plugins(cell):
    c = spec.load_cell(cell)
    assert c.chips in (1, 4)
    adapter = spec.plugin("adapters", c.config["adapter"]).Adapter
    for method in ("prepare", "run", "verify", "health", "host_spans",
                   "extras"):
        assert callable(getattr(adapter, method))
    assert hasattr(spec.plugin("references", c.config["reference"]),
                   "Reference")
    assert c.traffic["mode"] in ("flood", "paced")
    assert {"setup_s"} < {m["name"] for m in c.end_to_end}
    assert c.per_layer, "a cell reports at least one per-layer metric"
    for m in c.per_layer:
        mf = spec.metric_file(m["name"])
        reader = spec.plugin("readers", mf["reader"])
        assert callable(reader.read)
        if "cost" in mf.get("args", {}):
            flops, nbytes = getattr(roofline, mf["args"]["cost"])(c.config)
            assert flops > 0 and nbytes > 0
    for key in ("source", "deployment", "guarantees", "assumed", "reduced"):
        assert c.config[key], key


def test_only_plain_file_names_under_paths():
    for path in spec.benchmark()["paths"]:
        for base, dirs, files in os.walk(os.path.join(ROOT, path)):
            dirs[:] = [d for d in dirs if d != "__pycache__"]
            for f in files:
                rel = os.path.relpath(os.path.join(base, f), ROOT)
                assert re.match(r"^[A-Za-z0-9_.\-/]+$", rel), rel


def test_knn_digest_cost_from_the_configuration_shapes():
    cfg = json.load(open(os.path.join(
        ROOT, "benchmark", "configs", "knn-beijing-1m.json")))
    flops, nbytes = roofline.knn_wire_digest(cfg)
    assert nbytes == 6 * 500_000 + 8 * 16_384 and flops == 10 * 500_000
