"""``benchmark/readers/span_uncovered_us_per_event.py`` on hand-built traces:
what of a parent span no other span of its thread covers. And the 18 metric
files of the three operator loops' spans: each names a reader that is there
and the span the program emits under that name.
"""

import functools
import os

import pytest

from benchmark.harness import spec
from benchmark.harness.main import Trace
from benchmark.readers import program_span_us_per_event
from benchmark.readers import span_uncovered_us_per_event as uncovered

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def trace(spans, events=1_000):
    """``spans``: (name, ts, dur) or (name, ts, dur, tid), microseconds."""
    return Trace(
        cell=None, feed=None, events=events, windows=1, host=[],
        spans=[{"name": s[0], "ts": s[1], "dur": s[2],
                "tid": s[3] if len(s) > 3 else 1, "args": {}} for s in spans],
        counters={}, device=None, peaks=None, memory_peak_bytes=None,
        extras={})


PARENT = ("p", 1_000, 1_000)

#: name -> (spans beside PARENT, uncovered microseconds of the parent)
CASES = {
    "no_child": ([], 1_000),
    "two_children_apart": ([("a", 1_000, 300), ("b", 1_500, 200)], 500),
    # a leaf nested in a phase counts once: the union, not the sum
    "nested_children": ([("a", 1_100, 600), ("d2h", 1_200, 300),
                         ("d2h.wait", 1_210, 100)], 400),
    "overlapping_children": ([("a", 1_000, 500), ("b", 1_300, 500)], 200),
    # another thread's span lies inside the parent's time: not subtracted
    "child_on_another_tid": ([("a", 1_100, 400, 2), ("b", 1_600, 100)], 900),
    # whole microseconds: a last child may read one past its parent's end
    "child_one_past_the_end": ([("a", 1_500, 501)], 500),
    "spans_outside": ([("a", 100, 800), ("b", 2_000, 50), ("c", 2_500, 9)],
                      1_000),
    # a span that started before the parent is not inside it
    "started_before": ([("a", 900, 400)], 1_000),
    "child_covers_all": ([("a", 1_000, 1_000)], 0),
}


@pytest.mark.parametrize("case", sorted(CASES))
def test_uncovered_time_of_one_parent(case):
    others, want = CASES[case]
    got = uncovered.read(trace([PARENT] + others), parent="p")
    assert got == pytest.approx(want / 1_000)
    # the order of emission is no matter (a parent is emitted last)
    got = uncovered.read(trace(others[::-1] + [PARENT]), parent="p")
    assert got == pytest.approx(want / 1_000)


def test_parents_sum_and_each_sees_its_own_thread():
    spans = [("p", 0, 100), ("a", 10, 50),             # 50 uncovered
             ("p", 200, 100), ("a", 200, 100),         # 0
             ("p", 150, 400, 7), ("b", 160, 100, 7)]   # 300, thread 7's
    assert uncovered.read(trace(spans, events=10), parent="p") \
        == pytest.approx(35.0)


@pytest.mark.parametrize("spans, events", [
    ([], 1_000), ([("a", 0, 10), ("p.child", 0, 5)], 1_000),
    ([PARENT], 0),
], ids=["no_span", "no_parent", "no_event"])
def test_none_where_there_is_nothing_to_read(spans, events):
    # what the parent commit gives, which has no such span: no value
    assert uncovered.read(trace(spans, events=events), parent="p") is None


# -- the metric files ----------------------------------------------------------

WIRE, JOIN, RANGE = ["knn_wire.flood", "knn.flood"], ["join.flood"], \
    ["range_poly.flood"]
#: the trajectory join's cell (PR 39) runs the join's assembly, capacity pick
#: and SoA passes under the same span names, so it joined those lists
TJOIN = ["tjoin.flood"]
#: the crowded twin of ``join.flood`` (PR 41) runs the join's whole path, so
#: it joined, at its end, every list ``join.flood`` is on
SKEW = ["join_skew.flood"]
#: metric -> (what it reads, its cells, its layer)
METRICS = {
    "wire_pane_us_per_event": ("wire.pane", WIRE, "ship_fetch"),
    "wire_unspanned_us_per_event": (("wire.pane",), WIRE, "operators"),
    "wire_step_args_us_per_event": ("wire.step_args", WIRE, "operators"),
    "wire_merge_args_us_per_event": ("wire.merge_args", WIRE, "operators"),
    "wire_slice_us_per_event": ("wire.slice", WIRE, "operators"),
    "wire_d2h_wait_us_per_event": ("d2h.wait", WIRE, "ship_fetch"),
    "join_window_us_per_event": ("join.window", JOIN + SKEW, "ship_fetch"),
    "join_unspanned_us_per_event": (("join.window",), JOIN + SKEW,
                                    "operators"),
    "join_assemble_left_us_per_event": ("join.assemble_left",
                                        JOIN + TJOIN + SKEW, "operators"),
    "join_capacity_us_per_event": ("join.capacity", JOIN + TJOIN + SKEW,
                                   "operators"),
    "join_d2h_wait_us_per_event": ("d2h.wait", JOIN + SKEW, "ship_fetch"),
    "range_window_us_per_event": ("range.window", RANGE, "ship_fetch"),
    "range_unspanned_us_per_event": (("range.window",), RANGE, "operators"),
    "range_d2h_wait_us_per_event": ("d2h.wait", RANGE, "ship_fetch"),
    "soa_consolidate_us_per_event": ("soa.consolidate",
                                     RANGE + JOIN + TJOIN + SKEW,
                                     "host_ingest"),
    "soa_center_us_per_event": ("soa.center", RANGE + JOIN + TJOIN + SKEW,
                                "host_ingest"),
    "soa_cells_us_per_event": ("soa.cells", RANGE + JOIN + TJOIN + SKEW,
                               "host_ingest"),
    "soa_pad_us_per_event": ("soa.pad", RANGE + JOIN + TJOIN + SKEW,
                             "host_ingest"),
}


@functools.lru_cache(maxsize=None)
def _package_source():
    texts = []
    for base, _dirs, files in os.walk(os.path.join(REPO, "spatialflink_tpu")):
        for f in files:
            if f.endswith(".py"):
                with open(os.path.join(base, f)) as fh:
                    texts.append(fh.read())
    return "\n".join(texts)


@pytest.mark.parametrize("name", sorted(METRICS))
def test_metric_file_reads_the_span_the_program_emits(name):
    reads, cells, layer = METRICS[name]
    (entry,) = [m for m in spec.benchmark()["per_layer"] if m["name"] == name]
    assert entry == {"name": name, "unit": "us", "better": "lower",
                     "source": "program_span", "layer": layer,
                     "moves": "events_per_s", "workloads": cells}
    mf = spec.metric_file(name)
    reader = spec.plugin("readers", mf["reader"])
    if isinstance(reads, tuple):  # the parent's uncovered rest
        assert reader is uncovered and mf["args"] == {"parent": reads[0]}
        span = reads[0]
    else:
        assert reader is program_span_us_per_event
        assert mf["args"] == {"names": [reads]}
        span = reads
    # the name is one the program emits: it stands in the package's source
    assert f'"{span}"' in _package_source(), span
    # and a trace that holds it gives a number, one that does not gives None
    assert reader.read(trace([(span, 0, 500), ("x", 10, 100)]),
                       **mf["args"]) is not None
    assert reader.read(trace([("x", 10, 100)]), **mf["args"]) is None


#: PR 39's per-layer metrics, appended behind the 18: (reader, what it reads)
TJOIN_METRICS = {
    "tjoin_window_us_per_event": ("program_span_us_per_event",
                                  {"names": ["tjoin.window"]}),
    "tjoin_dedup_dispatch_us_per_event": (
        "program_span_us_per_event",
        {"names": ["dispatch:traj_pair_dedup_kernel"]}),
    "tjoin_retries_per_window": (
        "counter_ratio", {"num": ["tjoin.cap_retries",
                                  "tjoin.budget_retries"],
                          "den": ["results"]}),
    "tjoin_collapse_share": ("counter_ratio", {"num": ["tjoin.tpairs"],
                                               "den": ["tjoin.pairs"]}),
    "tjoin_unspanned_us_per_event": ("span_uncovered_us_per_event",
                                     {"parent": "tjoin.window"}),
    "tjoin_d2h_wait_us_per_event": ("program_span_us_per_event",
                                    {"names": ["d2h.wait"]}),
    "tjoin_d2h_bytes_per_tpair": ("counter_ratio", {"num": ["d2h_bytes"],
                                                    "den": ["tjoin.tpairs"]}),
    "tjoin_dedup_roofline": ("tjoin_dedup_roofline",
                             {"programs": ["jit_traj_pair_dedup_kernel"]}),
}


@pytest.mark.parametrize("name", sorted(TJOIN_METRICS))
def test_tjoin_metric_file_reads_what_the_program_emits(name):
    reader, args = TJOIN_METRICS[name]
    (entry,) = [m for m in spec.benchmark()["per_layer"] if m["name"] == name]
    assert entry["workloads"] == TJOIN and entry["moves"] == "events_per_s"
    mf = spec.metric_file(name)
    assert mf["reader"] == reader and mf["args"] == args
    assert callable(spec.plugin("readers", reader).read)
    # every span, counter or program the metric reads is one the package
    # emits: its name stands in the package's source
    src = _package_source()
    for said in [a for v in args.values()
                 for a in (v if isinstance(v, list) else [v])]:
        if said in ("results", "d2h_bytes", "d2h.wait"):
            continue  # the harness's own counters; ``fetch``'s child span
        word = said.split(":")[-1].split(".")[-1]
        word = word[4:] if word.startswith("jit_") else word
        assert word in src, said
    assert '"tjoin.window"' in src and "def record_tjoin" in src


def test_benchmark_json_only_grew():
    """The 18 entries stand where they stood, in the order they came, the
    trajectory join's eight behind them, the crowded join's three after
    those and the join's wait for its producer at the end of ``per_layer``,
    and the file stays well inside its size limit."""
    names = [m["name"] for m in spec.benchmark()["per_layer"]]
    assert len(names) == len(set(names))
    assert set(names[-30:-12]) == set(METRICS)
    assert set(names[-12:-4]) == set(TJOIN_METRICS)
    assert names[-4:] == ["join_skew_extract_roofline", "join_lanes_per_pair",
                          "join_pairs_per_window", "join_await_us_per_event"]
    with open(os.path.join(REPO, "BENCHMARK.json")) as f:
        assert len(f.read()) < 64 * 1024
