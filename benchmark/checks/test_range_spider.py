"""``range-polygons-10k-spider`` / ``range_spider.flood``: the files load, the
adapter maps the harness's stream and makes the zones before the clock and
hands the readers the index's size and work, the roofline's share, the
precision control.

    python -m pytest benchmark/checks -q        (not part of tier-1)
"""

import json
import os
import sys

import numpy as np
import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
sys.path.insert(0, ROOT)

from benchmark.adapters import range_polygons_soa, range_polygons_spider  # noqa: E402
from benchmark.checks import range_spider_precision_control as control  # noqa: E402
from benchmark.harness import spec, traffic  # noqa: E402
from benchmark.harness.main import Trace  # noqa: E402
from benchmark.readers import counter_ratio, extras_value  # noqa: E402
from benchmark.readers import range_spider_roofline  # noqa: E402
from benchmark.references import spider_gaussian, spider_polygons  # noqa: E402

CELL = "range_spider.flood"
NEW_METRICS = {"range_spider_roofline", "range_edge_slots_per_point",
               "range_index_bytes"}


def test_the_cell_loads_through_spec():
    cell = spec.load_cell(CELL)
    cfg, tr = cell.config, cell.traffic
    twin = spec.load_cell("range_poly.flood").config
    assert cell.chips == 1 and cfg["name"] == "range-polygons-10k-spider"
    assert cfg["adapter"] == "range_polygons_spider"
    assert cfg["reference"] == "range_polygons_grid"
    # range-polygons-1k's settings but for where the points and zones lie
    for key in ("window_s", "slide_s", "fire_delay_ms", "grid_cells",
                "approximate", "tolerance_deg"):
        assert cfg[key] == twin[key], key
    # the guarantees word for word, but for the kernel the run is held to
    assert cfg["guarantees"] == dict(twin["guarantees"], checked=twin[
        "guarantees"]["checked"].replace("`pruned`", "`cell_major`"))
    assert cfg["expect_range_kernel"] == "cell_major"
    same = {k: v for k, v in cfg["stream"].items() if k != "positions"}
    assert same == {k: v for k, v in twin["stream"].items()
                    if k != "positions"}
    assert cfg["stream"]["positions"] == {
        "distribution": "gaussian", "mean": 0.5, "sigma": 0.1,
        "generator": "spider_gaussian"}
    q = cfg["query_polygons"]
    assert (q["count"], q["generator"], q["polysize"], q["maxseg"],
            q["mean"], q["sigma"], q["radius"]) == (
        10_000, "spider_polygons", 0.01, 32, 0.5, 0.1, 0.002)
    assert cfg["expect_range_kernel"] == "cell_major"
    assert [r.split(":")[0] for r in cfg["reduced"]] == [
        "stream_seconds", "record_format"]
    assert any("radius uniform in (0, polysize / 2]" in a
               for a in cfg["assumed"])
    # 4 distinct windows, two of them before the clock
    assert tr["mode"] == "flood" and tr["batch_events"] == 10_000
    assert tr["pool_events"] == 4_000_000 and tr["warmup_results"] == 2
    assert {m["name"] for m in cell.end_to_end} == {"events_per_s", "setup_s"}
    reported = {m["name"] for m in cell.per_layer}
    assert NEW_METRICS <= reported
    # what range_poly.flood reports of the shared path, but its roofline
    mine = {m["name"] for m in spec.load_cell("range_poly.flood").per_layer}
    assert mine - reported == {"range_polygons_roofline"}
    assert reported - mine == NEW_METRICS
    entry = next(c for c in spec.benchmark()["configs"]
                 if c["name"] == cfg["name"])
    assert entry["reduced"] == ["stream_seconds", "record_format"]
    assert entry["source"] == cfg["source"] and len(entry["source"]) <= 200


def _prepared(seed=2**31 + 47):
    cell = spec.load_cell(CELL)
    stream_cfg = traffic.effective(cell.config["stream"], True)
    tr = traffic.effective(cell.traffic, True)
    windows = traffic.Windows(10_000, 10_000, 0, int(stream_cfg["t0_ms"]))
    stream, _w = traffic.build_stream(stream_cfg, tr, windows, seed, 12.0,
                                      False)
    uniform = (stream.x.copy(), stream.y.copy())
    ad = range_polygons_spider.Adapter(cell.config, stream_cfg, "/nonexistent",
                                       rehearsal=True)
    return ad, stream, windows, uniform, stream_cfg


def test_adapter_maps_the_stream_and_makes_the_zones_before_the_clock():
    ad, stream, windows, (ux, uy), stream_cfg = _prepared()
    assert isinstance(ad, range_polygons_soa.Adapter)
    ad.prepare(stream, windows)
    x, y = spider_gaussian.positions(ux, uy, stream_cfg["bbox"])
    assert np.array_equal(stream.x, x) and np.array_equal(stream.y, y)
    assert ad.stream is stream  # verify reads the mapped points
    # the zones: the rehearsal's count, Spider's, seeded from the uniform draw
    assert len(ad.polygons) == 2_000
    seed = int(ux[:2].view(np.uint64).sum() % (1 << 32))
    want = spider_polygons.polygons(seed, 2_000, stream_cfg["bbox"],
                                    polysize=0.01, maxseg=32)
    assert all(np.array_equal(p.rings[0], r) for p, r in zip(ad.polygons,
                                                             want))
    # the same seed, the same zones; another seed, others
    ad2, s2, w2, _u, _c = _prepared()
    ad2.prepare(s2, w2)
    assert np.array_equal(ad2.polygons[0].rings[0], ad.polygons[0].rings[0])
    ad3, s3, w3, _u3, _c3 = _prepared(seed=7)
    ad3.prepare(s3, w3)
    assert not np.array_equal(ad3.polygons[0].rings[0],
                              ad.polygons[0].rings[0])
    # the operator: nothing given but conf and grid
    assert ad.op.last_range_kernel is None and ad.op.grid.n == 100


def test_the_query_set_is_held_to_the_configuration():
    ad, stream, windows, _u, cfg = _prepared()
    ad.prepare(stream, windows)
    hold = range_polygons_spider._hold_query_set
    hold(ad.polygons, 2_000, cfg["bbox"], 0.01, 32)
    with pytest.raises(spec.SpecError):
        hold(ad.polygons[:-1], 2_000, cfg["bbox"], 0.01, 32)
    with pytest.raises(spec.SpecError):  # wider than polysize of a span
        hold(ad.polygons, 2_000, cfg["bbox"], 0.005, 32)
    with pytest.raises(spec.SpecError):  # more segments than maxseg
        hold(ad.polygons, 2_000, cfg["bbox"], 0.01, 8)


def test_extras_hand_over_the_index_and_nothing_a_parent_lacks():
    ad, *_ = _prepared()
    ad.range_open = {"windows": 1, "points": 10, "lanes": 16, "matches": 8,
                     "cand_retries": 0, "budget_retries": 0,
                     "edge_slots": 1_000, "cand": 128, "budget": 0,
                     "index_bytes": 16_000_000}
    ad.range_close = {"windows": 3, "points": 30, "lanes": 48, "matches": 24,
                      "cand_retries": 0, "budget_retries": 0,
                      "edge_slots": 5_000, "cand": 128, "budget": 0,
                      "index_bytes": 16_000_000}
    out = ad.extras()
    assert out["range.edge_slots"] == 4_000
    assert out["range.index_bytes"] == 16_000_000
    assert out["range.points"] == 20 and out["range.cand"] == 128
    # the parent's program keeps neither: nothing is made up
    for k in ("edge_slots", "index_bytes"):
        ad.range_open.pop(k)
        ad.range_close.pop(k)
    assert not {"range.edge_slots", "range.index_bytes"} & set(ad.extras())
    ad.range_close = None  # telemetry off
    assert ad.extras() == {}


def _trace(**kw):
    base = dict(cell=None, feed=None, events=1_000_000, windows=10, host=[],
                spans=[], counters={}, device=None, peaks=None,
                memory_peak_bytes=None, extras={})
    base.update(kw)
    return Trace(**base)


def test_edge_slots_per_point_and_index_bytes():
    slots = spec.metric_file("range_edge_slots_per_point")
    size = spec.metric_file("range_index_bytes")
    t = _trace(extras={"range.edge_slots": 11 * 1_190_000_000,
                       "range.points": 11 * 1_000_000,
                       "range.index_bytes": 16_439_432})
    assert counter_ratio.read(t, **slots["args"]) == pytest.approx(1_190)
    assert extras_value.read(t, **size["args"]) == 16_439_432.0
    parent = _trace(extras={"range.points": 11 * 1_000_000})
    assert counter_ratio.read(parent, **slots["args"]) is None
    assert extras_value.read(parent, **size["args"]) is None


def test_roofline_share_of_the_windows_time():
    cell = spec.load_cell(CELL)
    with open(os.path.join(spec.BENCH_DIR, "harness", "peaks.json")) as f:
        peaks = json.load(f)["TPU v5 lite"]
    ops, nbytes = range_spider_roofline.cost(cell.config)
    assert ops == pytest.approx(4.77e9, rel=0.01)
    assert nbytes == pytest.approx(26.9e6, rel=0.01)
    # 197e12 / (2 x 4 x 128^2) = 1.503 GHz, times 1,024 lanes
    vpu = range_spider_roofline.vpu_ops_per_s(peaks)
    assert vpu == pytest.approx(197e12 / 128)
    least = max(ops / vpu, nbytes / 819e9)
    assert least == pytest.approx(ops / vpu)  # the operations bind
    assert 2.5e-3 < least < 3.5e-3 and ops / vpu > 50 * nbytes / 819e9
    device = {"programs": {
        "jit_range_polygons_cell_major_fused": {"runs": 11, "seconds": 0.5,
                                                "ops": []},
        "jit_range_polygons_pruned_fused": {"runs": 1, "seconds": 9.0,
                                            "ops": []}}}
    t = _trace(cell=cell, device=device, peaks=peaks)
    (entry,) = [m for m in spec.benchmark()["per_layer"]
                if m["name"] == "range_spider_roofline"]
    args = spec.metric_file(entry["name"])["args"]
    assert range_spider_roofline.read(t, **args) == pytest.approx(
        10 * least / 0.5 * 100.0)
    assert range_spider_roofline.read(_trace(cell=cell, peaks=peaks, device={
        "programs": {"jit_other": {"runs": 1, "seconds": 1.0, "ops": []}}}),
        **args) is None
    # a device whose vector unit is not listed: no share, never a guess
    assert range_spider_roofline.read(
        _trace(cell=cell, device=device, peaks=dict(peaks, name="TPU vX")),
        **args) is None


@pytest.mark.parametrize("metric", sorted(NEW_METRICS))
def test_new_metric_files_name_a_reader(metric):
    mf = spec.metric_file(metric)
    assert callable(spec.plugin("readers", mf["reader"]).read)
    (entry,) = [m for m in spec.benchmark()["per_layer"]
                if m["name"] == metric]
    assert entry["workloads"] == [CELL] and entry["moves"] == "events_per_s"


def test_precision_control_fails_in_bfloat16_and_passes_in_float32():
    """At rehearsal size here; the same script at full size is the cell's
    control (PERF.md section 4)."""
    ad, ref_mod = control.prepared(2**31 + 47, rehearsal=True)
    low = control.control(ad, ref_mod, control.DTYPES["bfloat16"], 1)
    assert low["wrong"] and low["points_wrong_outside_band"] > 0
    ad, ref_mod = control.prepared(2**31 + 47, rehearsal=True)
    own = control.control(ad, ref_mod, control.DTYPES["float32"], 1)
    assert own["wrong"] == {} and own["problems"] == []
    assert own["matches"] > 10_000
