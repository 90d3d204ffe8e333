"""``range-polygons-1k`` / ``range_poly.flood``: the files load, the adapter's
``verify`` catches what it says it checks, the roofline reader's arithmetic.

    python -m pytest benchmark/checks -q        (not part of tier-1)
"""

import os
import sys

import numpy as np
import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
sys.path.insert(0, ROOT)

from benchmark.adapters.range_polygons_soa import (  # noqa: E402
    Adapter,
    _hold_query_set,
)
from benchmark.checks import range_polygons_precision_control as precision  # noqa: E402
from benchmark.harness import spec, traffic  # noqa: E402
from benchmark.harness.main import Trace  # noqa: E402
from benchmark.readers import counter_ratio, range_polygons_roofline  # noqa: E402

NEW_METRICS = {
    "range_assemble_us_per_event", "range_select_us_per_event",
    "range_h2d_us_per_event", "range_dispatch_us_per_event",
    "range_d2h_us_per_event", "range_retries_per_window",
    "range_match_share", "range_fetches_per_window",
    "range_polygons_roofline"}


def test_the_cell_loads_through_spec():
    cell = spec.load_cell("range_poly.flood")
    cfg, tr = cell.config, cell.traffic
    assert cell.chips == 1 and cfg["name"] == "range-polygons-1k"
    assert cfg["stream"]["event_rate_eps"] == 100_000
    assert cfg["stream"]["bbox"] == [115.5, 39.6, 117.6, 41.1]
    assert (cfg["window_s"], cfg["slide_s"], cfg["fire_delay_ms"]) == \
        (10, 10, 0)
    assert cfg["grid_cells"] == 100 and cfg["approximate"] is False
    assert cfg["query_polygons"]["count"] == 1000
    assert cfg["query_polygons"]["radius"] == 0.002
    assert cfg["expect_range_kernel"] == "pruned"
    assert cfg["rehearsal"].get("grid_cells", 100) == 100  # the grid is kept
    span = cfg["stream"]["bbox"][2] - cfg["stream"]["bbox"][0]
    assert cfg["tolerance_deg"] == pytest.approx(
        4 * float(np.finfo(np.float32).eps) * span, rel=0.01)
    assert "float32" in cfg["tolerance_why"]
    assert [r.split(":")[0] for r in cfg["reduced"]] == \
        ["stream_seconds", "record_format"]
    assert tr["mode"] == "flood" and tr["batch_events"] == 10_000
    assert tr["pool_events"] == 8_000_000 and tr["warmup_results"] == 2
    assert "stream_eps" not in tr  # a pooled flood has no end
    assert {m["name"] for m in cell.end_to_end} == {"events_per_s", "setup_s"}
    reported = {m["name"] for m in cell.per_layer}
    assert NEW_METRICS <= reported
    assert {"ingest_us_per_event", "h2d_bytes_per_event", "peak_hbm_bytes",
            "ship_fetch_us_per_event", "kernel_ms_per_window",
            "device_idle_share"} <= reported
    entry = next(c for c in spec.benchmark()["configs"]
                 if c["name"] == "range-polygons-1k")
    assert entry["reduced"] == ["stream_seconds", "record_format"]
    assert len(entry["source"]) <= 200 and "configs[2]" in entry["source"] \
        and "PointPolygonRangeQuery.java:31-160" in entry["source"] \
        and "HelperClass.java:387-439" in entry["source"]


@pytest.mark.parametrize("metric", sorted(NEW_METRICS))
def test_new_metric_files_name_a_reader(metric):
    mf = spec.metric_file(metric)
    assert callable(spec.plugin("readers", mf["reader"]).read)
    entry = next(m for m in spec.benchmark()["per_layer"]
                 if m["name"] == metric)
    assert entry["workloads"] == ["range_poly.flood"]
    assert entry["moves"] == "events_per_s"


# -- verify ---------------------------------------------------------------------


@pytest.fixture(scope="module")
def checked():
    """The adapter at rehearsal size with a run's worth of exact results:
    nine windows (the ninth repeats the first, a cycle on), made from the
    reference's own answer, so that ``verify`` passes them as they are."""
    cell = spec.load_cell("range_poly.flood")
    stream_cfg = traffic.effective(cell.config["stream"], True)
    tr = traffic.effective(cell.traffic, True)
    windows = traffic.Windows(10_000, 10_000, 0, int(stream_cfg["t0_ms"]))
    stream, _w = traffic.build_stream(stream_cfg, tr, windows, 2**31 + 35,
                                      6.0, False)
    ad = Adapter(cell.config, stream_cfg, "/nonexistent", rehearsal=True)
    assert ad.n_polygons == cell.config["rehearsal"]["query_polygons"]["count"]
    ad.prepare(stream, windows)
    ref_mod = spec.plugin("references", cell.config["reference"])
    radius = cell.config["query_polygons"]["radius"]
    ref = ref_mod.Reference(
        bbox=stream_cfg["bbox"], grid_cells=ad.grid_cells, radius=radius,
        tol=cell.config["tolerance_deg"],
        polygons=[[np.asarray(r) for r in p.rings] for p in ad.polygons])
    per_window = stream.rate_eps * 10
    for k in range(9):
        win = ad._chunk(k * per_window, (k + 1) * per_window)
        idx, d = ref.matches(win["x"], win["y"])
        keep = d <= radius
        ad.got.append((windows.end(k),
                       {f: v[idx[keep]] for f, v in win.items()},
                       d[keep].astype(np.float32)))
    return ad, ref, radius


def _verify(ad, got):
    saved, ad.got = ad.got, got
    try:
        return ad.verify(None)
    finally:
        ad.got = saved


def test_verify_passes_exact_results_and_holds_a_repeat_to_its_first(checked):
    ad, _ref, _radius = checked
    out = _verify(ad, ad.got)
    assert out["wrong"] == {} and out["problems"] == []
    assert out["checked"] == 9 and out["distinct_windows"] == 8
    assert out["repeats_equal_to_a_checked_result"] == 1
    assert out["polygons"] == 250
    assert out["matches"] == sum(len(d) for _e, _m, d in ad.got) > 0
    # same polygons from the same stream, others from another seed's
    again = Adapter(ad.cfg, ad.stream_cfg, "/nonexistent", rehearsal=True)
    again.prepare(ad.stream, ad.windows)
    assert all(np.array_equal(a.rings[0], b.rings[0])
               for a, b in zip(ad.polygons, again.polygons))


def test_verify_gives_the_readings_its_limits_are_held_against(checked):
    ad, _ref, _radius = checked
    out = _verify(ad, ad.got)  # the reference's own answer, as float32
    assert out["points_wrong_outside_band"] == 0
    assert 0 < out["max_distance_deviation_deg"] < 2.0 ** -24 * 0.002 * 2
    shifted = _verify(ad, _mutated(ad, 1, _shift))
    assert shifted["max_distance_deviation_deg"] == pytest.approx(1e-5,
                                                                  rel=1e-2)
    dropped = _verify(ad, _mutated(ad, 1, _drop))
    assert dropped["points_wrong_outside_band"] == 1


@pytest.mark.parametrize("dtype,correct", [("bfloat16", False),
                                           ("float32", True)])
def test_precision_control(dtype, correct):
    """One precision below the configuration's comes out not correct through
    the adapter's own ``verify``; the configuration's own passes, with room
    under ``tolerance_deg``. (The readings at the cell's load: PERF.md.)"""
    ad, ref_mod = precision.prepared(2**31 + 36, rehearsal=True)
    out = precision.control(ad, ref_mod, precision.DTYPES[dtype], 2)
    assert out["checked"] == 2 and out["problems"] == []
    tol = ad.cfg["tolerance_deg"]
    if correct:
        assert out["wrong"] == {}
        assert out["points_wrong_outside_band"] == 0
        assert out["max_distance_deviation_deg"] < tol / 4
    else:
        assert sorted(out["wrong"]) == [0, 1]
        assert out["points_wrong_outside_band"] > 100
        assert out["max_distance_deviation_deg"] > 100 * tol


def _rect(x0, y0, w=0.021, h=0.015):
    return np.array([[x0, y0], [x0 + w, y0], [x0 + w, y0 + h], [x0, y0 + h],
                     [x0, y0]])


@pytest.mark.parametrize("name,rings,says", [
    ("as_configured", [_rect(116.0, 40.0), _rect(117.0, 40.5)], None),
    ("one_short", [_rect(116.0, 40.0)], "not 2 polygons"),
    ("open_ring", [_rect(116.0, 40.0)[:4], _rect(117.0, 40.5)[:4]],
     "5-vertex"),
    ("another_span", [_rect(116.0, 40.0, w=0.03), _rect(117.0, 40.5)],
     "one grid cell's span"),
    ("clockwise", [_rect(116.0, 40.0)[::-1], _rect(117.0, 40.5)],
     "closed rectangle"),
    ("outside", [_rect(117.59, 40.0), _rect(117.0, 40.5)], "leaves the bbox"),
    ("the_same_twice", [_rect(116.0, 40.0), _rect(116.0, 40.0)],
     "share a corner"),
])
def test_the_query_set_is_held_to_the_configuration(name, rings, says):
    from types import SimpleNamespace

    polygons = [SimpleNamespace(rings=[r]) for r in rings]
    bbox = [115.5, 39.6, 117.6, 41.1]
    if says is None:
        _hold_query_set(polygons, 2, bbox, 100)
    else:
        with pytest.raises(spec.SpecError, match=says):
            _hold_query_set(polygons, 2, bbox, 100)


def _mutated(ad, which, change):
    got = list(ad.got)
    end, matched, dist = got[which]
    got[which] = (end, *change({k: v.copy() for k, v in matched.items()},
                               dist.copy()))
    return got


def _drop(matched, dist):
    i = int(np.argmin(dist))  # a point inside a polygon: far from the band
    return {k: np.delete(v, i) for k, v in matched.items()}, \
        np.delete(dist, i)


def _double(matched, dist):
    return {k: np.append(v, v[3]) for k, v in matched.items()}, \
        np.append(dist, dist[3])


def _shift(matched, dist):
    dist[5] += np.float32(1e-5)
    return matched, dist


def _foreign(matched, dist):
    matched["oid"][2] += 1  # a row the window does not hold
    return matched, dist


@pytest.mark.parametrize("name,change,says", [
    ("dropped_match", _drop, "missing"),
    ("doubled_row", _double, "emitted twice"),
    ("shifted_distance", _shift, "distances differ"),
    ("foreign_row", _foreign, "not rows of the window"),
])
def test_verify_catches(checked, name, change, says):
    ad, _ref, _radius = checked
    for which in (1, 8):  # a first sight of a window, and a repeat
        out = _verify(ad, _mutated(ad, which, change))
        assert list(out["wrong"]) == [which], name
        assert any(says in b for b in out["wrong"][which]), out["wrong"]


def test_verify_catches_an_added_non_match(checked):
    ad, ref, radius = checked
    per_window = ad.stream.rate_eps * 10
    win = ad._chunk(2 * per_window, 3 * per_window)
    idx, _d = ref.matches(win["x"], win["y"])
    far = int(np.setdiff1d(np.arange(per_window), idx)[0])

    def add(matched, dist):
        return {k: np.append(v, win[k][far]) for k, v in matched.items()}, \
            np.append(dist, np.float32(radius))

    out = _verify(ad, _mutated(ad, 2, add))
    assert list(out["wrong"]) == [2]
    assert any("beyond the radius" in b for b in out["wrong"][2])


def test_verify_catches_a_window_out_of_order(checked):
    ad, _ref, _radius = checked
    got = list(ad.got)
    got[3], got[4] = got[4], got[3]
    out = _verify(ad, got)
    assert any("out of order" in p for p in out["problems"])
    out = _verify(ad, ad.got[:2] + ad.got[3:])  # one never came
    assert any("missing" in p for p in out["problems"])


def test_the_parent_program_is_refused_before_it_runs(checked, monkeypatch):
    from spatialflink_tpu.operators import PointPolygonRangeQuery

    ad, _ref, _radius = checked

    def old_init(self, conf, grid, mesh=None):  # keeps no last_range_kernel
        self.conf, self.grid, self.mesh = conf, grid, mesh

    monkeypatch.setattr(PointPolygonRangeQuery, "__init__", old_init)
    fresh = Adapter(ad.cfg, ad.stream_cfg, "/nonexistent", rehearsal=True)
    with pytest.raises(spec.SpecError, match="last_range_kernel"):
        fresh.prepare(ad.stream, ad.windows)


# -- readers ----------------------------------------------------------------------


def _trace(**kw):
    base = dict(cell=None, feed=None, events=10_000_000, windows=10, host=[],
                spans=[], counters={}, device=None, peaks=None,
                memory_peak_bytes=None, extras={})
    base.update(kw)
    return Trace(**base)


def test_roofline_cost_by_hand():
    cfg = spec.load_cell("range_poly.flood").config
    ops, nbytes = range_polygons_roofline.cost(cfg)
    assert ops == 8.0 * 1_000_000 * 1_000
    assert nbytes == 1_000_000 * 13 + 1_000_000 * 5 + 1_000 * 64 \
        == 18_064_000
    assert nbytes / 819e9 == pytest.approx(22.06e-6, rel=1e-3)
    assert ops / 197e12 == pytest.approx(40.61e-6, rel=1e-3)


def test_roofline_share_of_the_windows_time():
    cell = spec.load_cell("range_poly.flood")
    peaks = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
    ops, _nbytes = range_polygons_roofline.cost(cell.config)
    least = ops / 197e12  # the operations bound it
    device = {"programs": {
        "jit_range_polygons_pruned_fused": {"runs": 11, "seconds": 4.0,
                                            "ops": []},
        "jit_range_polygons_pruned_compact_fused": {"runs": 1, "seconds": 1.0,
                                                    "ops": []},
        "jit_convert_element_type": {"runs": 10, "seconds": 0.5, "ops": []}}}
    t = _trace(cell=cell, device=device, peaks=peaks)
    # a re-run counts in the time, not in the work: 10 windows over 5 s
    assert range_polygons_roofline.read(
        t, programs=["jit_range_polygons_pruned"]) == \
        pytest.approx(10 * least / 5.0 * 100.0)
    assert range_polygons_roofline.read(_trace(cell=cell, peaks=peaks, device={
        "programs": {"jit_other": {"runs": 1, "seconds": 1.0, "ops": []}}}),
        programs=["jit_range_polygons_pruned"]) is None
    assert range_polygons_roofline.read(_trace(cell=cell),
                                        programs=["x"]) is None


def test_counter_metrics_and_a_program_without_the_counters():
    t = _trace(counters={"d2h_transfers": 10},
               extras={"range.matches": 1_380_000, "range.points": 10_000_000,
                       "range.cand_retries": 0, "range.budget_retries": 0})
    read = lambda name: counter_ratio.read(  # noqa: E731
        t, **spec.metric_file(name)["args"])
    assert read("range_match_share") == pytest.approx(0.138)
    assert read("range_retries_per_window") == 0.0
    assert read("range_fetches_per_window") == 1.0
    parent = _trace(counters={"d2h_transfers": 20})  # keeps no range block
    for name in ("range_match_share", "range_retries_per_window"):
        assert counter_ratio.read(
            parent, **spec.metric_file(name)["args"]) is None
