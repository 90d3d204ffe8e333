"""The readers of the program's own leaf spans, on hand-built traces.

    python -m pytest benchmark/checks -q        (not part of tier-1)
"""

import os
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
sys.path.insert(0, ROOT)

from benchmark.harness import spec  # noqa: E402
from benchmark.harness.main import Trace  # noqa: E402
from benchmark.readers import (  # noqa: E402
    program_span_ms_per_window,
    program_span_p50_ms,
    program_span_us_per_event,
    unwrapped_runs_per_window,
)

SPANS = [("h2d", 2_000), ("dispatch:wire_digest_pallas", 500),
         ("dispatch:knn_merge_digest_list", 300), ("d2h", 1_200),
         ("h2d", 1_000), ("fetch", 9_000), ("commit", 300_000),
         ("commit.egress", 100_000), ("commit", 280_000),
         ("commit", 310_000), ("gc.full", 80_000), ("gc.full", 100_000)]


def trace(spans=SPANS, windows=2, events=1_000, device=..., counters=None):
    if device is ...:
        device = {"module_runs": 14}
    return Trace(
        cell=None, feed=None, events=events, windows=windows, host=[],
        spans=[{"name": n, "ts": i, "dur": d, "args": {}}
               for i, (n, d) in enumerate(spans)],
        counters={"kernel_calls": 8} if counters is None else counters,
        device=device, peaks=None, memory_peak_bytes=None, extras={})


def test_exact_name_and_prefix():
    ms = program_span_ms_per_window.read
    assert ms(trace(), names=["h2d"]) == pytest.approx(1.5)
    assert ms(trace(), names=["dispatch:"]) == pytest.approx(0.4)
    assert ms(trace(), names=["h2d", "dispatch:", "d2h"]) == \
        pytest.approx(2.5)
    # "d2h" is a leaf's exact name: the operators' "fetch" phase span, which
    # holds it, is not counted with it
    assert ms(trace(), names=["d2h"]) == pytest.approx(0.6)
    # a prefix has to end in ":" — "dispatch" alone is an exact name
    assert ms(trace(), names=["dispatch"]) is None


def test_commit_does_not_count_its_children():
    assert program_span_p50_ms.read(trace(), name="commit") == \
        pytest.approx(300.0)
    assert program_span_p50_ms.read(trace(), name="commit.egress") == \
        pytest.approx(100.0)
    assert program_span_ms_per_window.read(trace(), names=["commit"]) == \
        pytest.approx(445.0)


def test_per_event():
    assert program_span_us_per_event.read(trace(), names=["gc.full"]) == \
        pytest.approx(180.0)
    assert program_span_us_per_event.read(
        trace(events=0), names=["gc.full"]) is None


@pytest.mark.parametrize("read, args", [
    (program_span_ms_per_window.read, {"names": ["checkpoint.write"]}),
    (program_span_us_per_event.read, {"names": ["checkpoint.write"]}),
    (program_span_p50_ms.read, {"name": "checkpoint.write"}),
])
def test_empty_selection_gives_none(read, args):
    # what a parent commit without the span gives: no value, no exception
    assert read(trace(), **args) is None
    assert read(trace(spans=[]), **args) is None


def test_unwrapped_runs():
    assert unwrapped_runs_per_window.read(trace()) == pytest.approx(3.0)
    assert unwrapped_runs_per_window.read(
        trace(device={"module_runs": 8})) == 0.0
    # a rehearsal: no device plane in the trace
    assert unwrapped_runs_per_window.read(trace(device=None)) is None
    assert unwrapped_runs_per_window.read(trace(windows=0)) is None
    assert unwrapped_runs_per_window.read(trace(counters={})) is None


NEW = {
    "walk_link_ms_per_window": ["sncb.paced"],
    "commit_span_ms_p50": ["sncb.paced"],
    "commit_egress_ms_p50": ["sncb.paced"],
    "commit_state_ms_p50": ["sncb.paced"],
    "checkpoint_pickle_ms_p50": ["sncb.paced"],
    "checkpoint_write_ms_p50": ["sncb.paced"],
    "h2d_ms_per_window": ["knn.paced"],
    "dispatch_ms_per_window": ["knn.paced"],
    "d2h_ms_per_window": ["knn.paced"],
    "unwrapped_runs_per_window": ["sncb.paced", "knn.paced"],
}


@pytest.mark.parametrize("name", sorted(NEW))
def test_new_metric_resolves_and_reads_in_its_cells(name):
    entry = {m["name"]: m for m in spec.benchmark()["per_layer"]}[name]
    assert entry["workloads"] == NEW[name]
    for cell in NEW[name]:
        assert name in {m["name"] for m in spec.load_cell(cell).per_layer}
    mf = spec.metric_file(name)
    value = spec.plugin("readers", mf["reader"]).read(
        trace(), **mf.get("args", {}))
    # SPANS holds every selected name but the three only a real commit emits
    if name in ("commit_state_ms_p50", "checkpoint_pickle_ms_p50",
                "checkpoint_write_ms_p50"):
        assert value is None
    else:
        assert value is not None and value > 0
