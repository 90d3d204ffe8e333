"""The trace reduction on hand-made intervals and on a document recorded on
a TPU v5e (``fixtures/``; see ``fixtures/README``).

    python -m pytest benchmark/checks -q        (not part of tier-1)
"""

import gzip
import json
import os
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.dirname(os.path.dirname(HERE)))

from benchmark.harness import xplane  # noqa: E402
from benchmark.harness.main import host_timeline  # noqa: E402


def test_union_clip_total_and_gaps():
    ev = [("a", 0.0, 2.0), ("b", 1.0, 2.0), ("c", 5.0, 1.0), ("d", 9.5, 2.0)]
    clipped = xplane.clip(ev, 0.5, 10.0)
    assert clipped == [("a", 0.5, 1.5), ("b", 1.0, 2.0), ("c", 5.0, 1.0),
                       ("d", 9.5, 0.5)]
    busy = xplane.busy_intervals(clipped)
    assert busy == [(0.5, 3.0), (5.0, 6.0), (9.5, 10.0)]
    assert xplane.total(busy) == pytest.approx(4.0)
    assert xplane.gaps(busy, 0.5, 10.0) == [(3.0, 5.0), (6.0, 9.5)]
    assert xplane.gaps([], 1.0, 2.0) == [(1.0, 2.0)]
    assert xplane.gaps([(0.0, 5.0)], 1.0, 2.0) == []


def test_by_name_and_program_labels():
    mods = [("jit_step(123456)", 0.0, 1.0), ("jit_merge(9)", 2.0, 1.0)]
    ops = [("fusion.1", 0.1, 0.2), ("copy.2", 0.5, 0.1), ("fusion.1", 2.2, 0.3),
           ("stray", 5.0, 0.1)]
    lab = xplane.label_ops(ops, mods)
    assert [n for n, _s, _d in lab] == [
        "jit_step/fusion.1", "jit_step/copy.2", "jit_merge/fusion.1",
        "?/stray"]
    assert xplane.by_name(lab, top=2) == [["jit_merge/fusion.1", 0.3],
                                          ["jit_step/fusion.1", 0.2]]


def test_idle_gaps_are_named_by_the_host_span_that_covers_them():
    idle = [(0.0, 4.0), (6.0, 7.0)]
    host = [("ingest", 0.0, 3.0), ("window", 3.0, 0.5), ("commit", 6.5, 2.0)]
    assert xplane.attribute(idle, host) == [
        ["ingest", 3.0], ["other", 1.0], ["commit", 0.5], ["window", 0.5]]


def test_reduce_on_a_hand_made_document():
    doc = {"devices": {"/device:TPU:0": {
        xplane.OPS_LINE: [("f", 1.0, 1.0), ("g", 1.5, 1.0), ("f", 8.0, 0.5)],
        xplane.MODULES_LINE: [("jit_a(1)", 0.9, 1.7), ("jit_a(1)", 7.9, 0.7)]},
        "/device:TPU:1": {xplane.OPS_LINE: [], xplane.MODULES_LINE: []}},
        "marks": [("bench:open", 0.0, 0.0), ("bench:close", 10.0, 0.0)]}
    lo, hi = xplane.mark_time(doc, "open"), xplane.mark_time(doc, "close")
    out = xplane.reduce(doc, lo, hi, [("ingest", 0.0, 6.0),
                                      ("window", 6.0, 4.0)])
    assert out["devices_used"] == 1 and out["window_s"] == 10.0
    assert out["busy_s"] == pytest.approx(2.0)
    assert out["module_runs"] == 2
    assert out["programs"] == {"jit_a": {
        "runs": 2, "seconds": pytest.approx(2.4), "ops": ["f", "g"]}}
    assert out["device_ops"][0] == ["jit_a/f", pytest.approx(1.5)]
    # idle: [0,1) + [2.5,8) + [8.5,10) = 8 s; ingest covers 1 + 3.5
    gaps = dict(out["idle_gaps"])
    assert gaps["ingest"] == pytest.approx(4.5)
    assert gaps["window"] == pytest.approx(3.5)


def test_host_timeline_names_every_second_between_pulls():
    class Feed:
        pulls = [(0, 0.0, 0.0, 0.0), (10, 1.0, 1.5, 0.0), (20, 4.0, 4.0, 0.0)]
        t_closed = 5.0

    named = [("window", 2.0, 1.0), ("commit", 3.0, 0.5)]
    tl = host_timeline(Feed, named)
    assert tl == [("ingest", 0.0, 1.0), ("generate", 1.0, 0.5),
                  ("ingest", 1.5, 0.5), ("window", 2.0, 1.0),
                  ("commit", 3.0, 0.5), ("ingest", 3.5, 0.5),
                  ("ingest", 4.0, 1.0)]
    assert sum(d for _n, _s, d in tl) == pytest.approx(5.0)


def _fixtures():
    return sorted(f for f in os.listdir(os.path.join(HERE, "fixtures"))
                  if f.endswith(".json.gz"))


@pytest.mark.parametrize("name", _fixtures())
def test_recorded_trace_reduces_to_the_numbers_worked_out_by_hand(name):
    with gzip.open(os.path.join(HERE, "fixtures", name), "rt") as f:
        rec = json.load(f)
    doc = rec["doc"]
    for dev in doc["devices"].values():
        for line in dev:
            dev[line] = [tuple(e) for e in dev[line]]
    doc["marks"] = [tuple(e) for e in doc["marks"]]
    lo, hi = xplane.mark_time(doc, "open"), xplane.mark_time(doc, "close")
    out = xplane.reduce(doc, lo, hi, [tuple(s) for s in rec["host"]])
    exp = rec["expected"]
    assert out["devices_used"] == exp["devices_used"]
    assert out["module_runs"] == exp["module_runs"]
    assert out["busy_s"] == pytest.approx(exp["busy_s"], rel=1e-9)
    assert out["window_s"] == pytest.approx(exp["window_s"], rel=1e-9)
    assert out["device_ops"][0][0] == exp["top_op"]
    # busy + idle = window, and the idle seconds are all attributed
    idle = sum(t for _n, t in out["idle_gaps"])
    assert out["busy_s"] + idle == pytest.approx(out["window_s"], rel=1e-6)
