"""``knn-beijing-1m-wire`` / ``knn_wire.flood``: the files load, a segment is
a pane, and the adapter's ``verify`` tells a wrong neighbour, a missing window
and a pane that is not the reference's quantisation from a good run.

    python -m pytest benchmark/checks -q        (not part of tier-1)
"""

import os
import sys

import numpy as np
import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
sys.path.insert(0, ROOT)

from benchmark.adapters.knn_wire_direct import Adapter  # noqa: E402
from benchmark.harness import spec, traffic  # noqa: E402
from benchmark.references.knn_beijing import Reference  # noqa: E402

NEW_METRICS = {
    "wire_prepare_us_per_event", "wire_h2d_us_per_event",
    "wire_dispatch_us_per_event", "wire_d2h_us_per_event",
    "wire_runs_per_result", "wire_unwrapped_runs_per_result",
    "wire_fetches_per_result", "wire_pad_lane_share"}
APPENDED = {
    "ingest_us_per_event", "h2d_bytes_per_event", "ship_fetch_us_per_event",
    "kernel_ms_per_window", "knn_digest_roofline", "device_idle_share",
    "peak_hbm_bytes"}


def test_the_cell_loads_through_spec_with_its_siblings_shapes():
    cell = spec.load_cell("knn_wire.flood")
    cfg, tr = cell.config, cell.traffic
    sib = spec.load_cell("knn.flood").config
    assert cell.chips == 1 and cfg["name"] == "knn-beijing-1m-wire"
    assert cfg["adapter"] == "knn_wire_direct"
    assert cfg["reference"] == sib["reference"] == "knn_beijing"
    for key in ("window_s", "slide_s", "fire_delay_ms", "grid_cells",
                "query_point", "radius", "k", "expect_digest"):
        assert cfg[key] == sib[key], key
    for key in ("event_rate_eps", "ids", "id_assignment", "bbox", "positions",
                "t0_ms", "rehearsal"):
        assert cfg["stream"][key] == sib["stream"][key], key
    for key in ("delivery", "result_counts_when"):
        assert cfg["guarantees"][key] == sib["guarantees"][key]
    assert cfg["guarantees"]["checked"].startswith(sib["guarantees"]["checked"])
    assert [r.split(":")[0] for r in cfg["reduced"]] == ["stream_seconds"]
    pane = cfg["stream"]["event_rate_eps"] * cfg["slide_s"]
    assert tr["mode"] == "flood" and tr["batch_events"] == pane == 500_000
    assert tr["pool_events"] == 16 * pane and tr["warmup_results"] == 2
    assert "stream_eps" not in tr  # a pooled flood has no end (PR 31)
    assert {m["name"] for m in cell.end_to_end} == {"events_per_s", "setup_s"}
    assert {m["name"] for m in cell.per_layer} == NEW_METRICS | APPENDED
    for name in NEW_METRICS:
        spec.plugin("readers", spec.metric_file(name)["reader"])
    entry = next(c for c in spec.benchmark()["configs"]
                 if c["name"] == "knn-beijing-1m-wire")
    assert entry["reduced"] == ["stream_seconds"]
    assert len(entry["source"]) <= 200 and "north_star" in entry["source"]


@pytest.fixture(scope="module")
def prepared():
    """The adapter at rehearsal size, prepared, and the reference's own
    answer for each of the first five windows standing in for a run."""
    cell = spec.load_cell("knn_wire.flood")
    stream_cfg = traffic.effective(cell.config["stream"], True)
    tr = traffic.effective(cell.traffic, True)
    windows = traffic.Windows(10_000, 5_000, 0, int(stream_cfg["t0_ms"]))
    stream, _w = traffic.build_stream(stream_cfg, tr, windows, 2**31 + 33,
                                      6.0, False)
    ad = Adapter(cell.config, stream_cfg, "/nonexistent", rehearsal=True)
    ad.prepare(stream, windows)
    cfg = cell.config
    ref = Reference(bbox=stream_cfg["bbox"], query=cfg["query_point"],
                    radius=float(cfg["radius"]), k=int(cfg["k"]),
                    ids=int(stream_cfg["ids"]))
    xq, yq = ref.quantize(stream.x, stream.y)
    got = []
    for k in range(5):
        lo, hi = max(0, (k - 1) * ad.pane), (k + 1) * ad.pane
        mins = ref.minima(xq[lo:hi], yq[lo:hi], stream.ids[lo:hi])
        order = np.argsort(mins, kind="stable")
        segs = order[:ref.k][mins[order[:ref.k]] <= ref.radius]
        got.append((windows.end(k), segs.astype(np.int32), mins[segs],
                    len(segs)))
    return ad, got, tr


def _verify(ad, got):
    ad.got = list(got)
    return ad.verify(feed=None)


def test_verify_passes_the_references_own_answers(prepared):
    ad, got, tr = prepared
    assert len(ad.panes) == tr["pool_events"] // tr["batch_events"] == 8
    assert all(p.shape == (3, ad.pane) and p.dtype == np.uint16
               for p in ad.panes)
    out = _verify(ad, got)
    assert out["checked"] == 5 and out["wrong"] == {} and not out["problems"]
    assert out["panes_checked"] == 8 and out["distinct_windows"] == 5
    assert all(nv >= 1 for _e, _s, _d, nv in got), "degenerate: empty windows"


def test_verify_reports_a_wrong_neighbour(prepared):
    ad, got, _tr = prepared
    end, segs, dists, nv = got[2]
    segs = segs.copy()
    segs[0] = next(i for i in range(256) if i not in set(segs.tolist()))
    out = _verify(ad, got[:2] + [(end, segs, dists, nv)] + got[3:])
    assert list(out["wrong"]) == [2] and out["wrong"][2]


def test_verify_reports_a_missing_window(prepared):
    ad, got, _tr = prepared
    out = _verify(ad, got[:1] + got[2:])
    assert any("missing or out of order" in p for p in out["problems"])


def test_verify_reports_a_pane_that_is_not_the_references_quantisation(
        prepared):
    ad, got, _tr = prepared
    kept = ad.panes[3]
    try:
        ad.panes[3] = kept.copy()
        ad.panes[3][0, 17] ^= 1  # one lattice step in x, one point
        out = _verify(ad, got)
    finally:
        ad.panes[3] = kept
    assert out["problems"] == [
        "packed pane 3 differs from the reference's quantisation of its "
        "events"]


@pytest.mark.parametrize("lo,hi", [(0, 10_000), (25_000, 75_000),
                                   (50_000, 150_000)])
def test_a_segment_that_is_not_a_whole_pane_is_refused(prepared, lo, hi):
    ad, _got, _tr = prepared
    assert ad.pane == 50_000
    with pytest.raises(ValueError, match="not a whole pane"):
        ad._pane_of(lo, hi)


def test_a_pane_is_the_same_array_every_cycle_of_the_pool(prepared):
    ad, _got, _tr = prepared
    n = len(ad.panes)
    for j in (0, 3, n - 1):
        first = ad._pane_of(j * ad.pane, (j + 1) * ad.pane)
        again = ad._pane_of((j + 5 * n) * ad.pane, (j + 5 * n + 1) * ad.pane)
        assert first is again is ad.panes[j]
