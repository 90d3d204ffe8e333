"""``join-tdrive-2x100k-skew`` / ``join_skew.flood``: the files load, the
adapter maps the harness's stream before the clock and hands the readers what
the operator picked, the roofline's cost by hand, the precision control.

    python -m pytest benchmark/checks -q        (not part of tier-1)
"""

import math
import os
import sys

import numpy as np
import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
sys.path.insert(0, ROOT)

from benchmark.adapters import join_soa, join_soa_skew  # noqa: E402
from benchmark.checks import join_skew_precision_control as control  # noqa: E402
from benchmark.harness import spec, traffic  # noqa: E402
from benchmark.harness.main import Trace  # noqa: E402
from benchmark.readers import counter_ratio, join_gauss_roofline  # noqa: E402
from benchmark.references import spider_gaussian  # noqa: E402

CELL = "join_skew.flood"
NEW_METRICS = {"join_skew_extract_roofline", "join_lanes_per_pair",
               "join_pairs_per_window"}


def test_the_cell_loads_through_spec():
    cell = spec.load_cell(CELL)
    cfg, tr = cell.config, cell.traffic
    twin = spec.load_cell("join.flood").config
    assert cell.chips == 1 and cfg["name"] == "join-tdrive-2x100k-skew"
    assert cfg["adapter"] == "join_soa_skew"
    assert cfg["reference"] == "join_tdrive"
    # join-tdrive-2x100k to the letter but for where the points lie
    for key in ("window_s", "slide_s", "fire_delay_ms", "grid_cells",
                "radius", "approximate", "tolerance_deg",
                "expect_join_backend", "guarantees"):
        assert cfg[key] == twin[key], key
    same = {k: v for k, v in cfg["stream"].items()
            if k not in ("positions", "rehearsal")}
    assert same == {k: v for k, v in twin["stream"].items()
                    if k not in ("positions", "rehearsal")}
    assert cfg["stream"]["positions"] == {
        "distribution": "gaussian", "mean": 0.5, "sigma": 0.1,
        "generator": "spider_gaussian"}
    assert "float32" in cfg["tolerance_why"]
    assert [r.split(":")[0] for r in cfg["reduced"]] == ["stream_seconds"]
    assert any("sigma = 0.1" in a for a in cfg["assumed"])
    assert any("redraws" in a for a in cfg["assumed"])
    for key in ("source", "deployment", "why"):
        assert cfg[key]
    # 4 distinct windows, and every one of them seen before the clock
    assert tr["mode"] == "flood" and tr["batch_events"] == 10_000
    assert tr["pool_events"] == 4_000_000 and tr["warmup_results"] == 4
    assert "stream_eps" not in tr
    per_window = cfg["stream"]["event_rate_eps"] * cfg["window_s"]
    assert tr["pool_events"] // per_window == tr["warmup_results"]
    assert {m["name"] for m in cell.end_to_end} == {"events_per_s", "setup_s"}
    reported = {m["name"] for m in cell.per_layer}
    assert NEW_METRICS <= reported
    # what join.flood reports of the shared path, but the uniform roofline
    mine = {m["name"] for m in spec.load_cell("join.flood").per_layer}
    assert mine - reported == {"join_extract_roofline"}
    entry = next(c for c in spec.benchmark()["configs"]
                 if c["name"] == "join-tdrive-2x100k-skew")
    assert entry["reduced"] == ["stream_seconds"]
    assert entry["source"] == cfg["source"] and len(entry["source"]) <= 200
    assert "Spider" in entry["source"] and "N(0.5, 0.1)" in entry["source"]


def _prepared(seed=2**31 + 41):
    cell = spec.load_cell(CELL)
    stream_cfg = traffic.effective(cell.config["stream"], True)
    tr = traffic.effective(cell.traffic, True)
    windows = traffic.Windows(5000, 5000, 0, int(stream_cfg["t0_ms"]))
    stream, _w = traffic.build_stream(stream_cfg, tr, windows, seed, 12.0,
                                      False)
    uniform = (stream.x.copy(), stream.y.copy())
    ad = join_soa_skew.Adapter(cell.config, stream_cfg, "/nonexistent",
                               rehearsal=True)
    return ad, stream, windows, uniform, stream_cfg


def test_adapter_maps_the_seeded_stream_before_the_clock():
    ad, stream, windows, (ux, uy), stream_cfg = _prepared()
    assert isinstance(ad, join_soa.Adapter)
    ad.prepare(stream, windows)
    x, y = spider_gaussian.positions(ux, uy, stream_cfg["bbox"])
    assert np.array_equal(stream.x, x) and np.array_equal(stream.y, y)
    assert ad.stream is stream  # verify reads the mapped points
    assert not np.array_equal(stream.x, ux)
    # the operator is join.flood's: nothing given but conf and grid
    assert (ad.op.cap, ad.op.join_backend, ad.op.join_budget) == (64, None, 0)
    # the same seed gives the same stream, another seed another
    ad2, s2, w2, _u, _c = _prepared()
    ad2.prepare(s2, w2)
    assert np.array_equal(s2.x, stream.x) and np.array_equal(s2.y, stream.y)
    _ad3, s3, _w3, _u3, _c3 = _prepared(seed=7)
    assert not np.array_equal(s3.x, ux)
    # a large seed (the driver's are past 2^31) builds a stream too
    assert len(stream.x) == 48_000
    # a chunk is a view into the mapped pool
    c = ad._chunk(3000, 4000)
    assert np.shares_memory(c["x"], stream.x)


def test_extras_hand_over_the_pick_and_nothing_a_parent_lacks():
    ad, *_ = _prepared()
    ad.join_open = {"pairs": 10, "windows": 1, "cap_retries": 0,
                    "budget_retries": 1, "bucket_lanes": 100, "cap": 64,
                    "budget": 1024, "refine": 1, "fullest_cell": 70,
                    "bucket_cells": 900}
    ad.join_close = {"pairs": 50, "windows": 5, "cap_retries": 0,
                     "budget_retries": 1, "bucket_lanes": 900, "cap": 128,
                     "budget": 2048, "refine": 4, "fullest_cell": 1240,
                     "bucket_cells": 14_400}
    assert ad.extras() == {
        "join.pairs": 40, "join.windows": 4, "join.cap_retries": 0,
        "join.budget_retries": 0, "join.cap": 128, "join.budget": 2048,
        "join.bucket_lanes": 800, "join.refine": 4,
        "join.fullest_cell": 1240, "join.bucket_cells": 14_400}
    # the parent's program keeps none of the new ones: nothing is made up
    ad.join_open = {"pairs": 10, "windows": 1, "cap_retries": 0,
                    "budget_retries": 0, "cap": 64, "budget": 1024}
    ad.join_close = {"pairs": 50, "windows": 5, "cap_retries": 0,
                     "budget_retries": 0, "cap": 128, "budget": 2048}
    assert set(ad.extras()) == {
        "join.pairs", "join.windows", "join.cap_retries",
        "join.budget_retries", "join.cap", "join.budget"}
    ad.join_close = None  # telemetry off
    assert ad.extras() == {}


def _trace(**kw):
    base = dict(cell=None, feed=None, events=1_000_000, windows=10, host=[],
                spans=[], counters={}, device=None, peaks=None,
                memory_peak_bytes=None, extras={})
    base.update(kw)
    return Trace(**base)


def test_gauss_roofline_cost_by_hand():
    cfg = spec.load_cell(CELL).config
    ops, nbytes = join_gauss_roofline.cost(cfg)
    n, sx, sy = 500_000, 0.21, 0.15
    pairs = n * n * 0.002 ** 2 / (4 * sx * sy)
    assert pairs == pytest.approx(7_936_508, rel=1e-4)
    assert nbytes == pytest.approx(2 * n * 8 + 12 * pairs)
    # 9 key cells of 0.021 x 0.021 around a point, at the density's mean
    assert ops == pytest.approx(
        8 * 9 * n * n * 0.021 ** 2 / (4 * math.pi * sx * sy))
    assert ops == pytest.approx(2.005e10, rel=1e-3)
    # 8 x the uniform twin's pairs: the crowding is the deployment
    from benchmark.readers import join_roofline

    _o, twin_bytes = join_roofline.cost(spec.load_cell("join.flood").config)
    assert (nbytes - 8e6) / (twin_bytes - 8e6) == pytest.approx(7.96, rel=1e-2)


def test_gauss_roofline_share_of_the_windows_time():
    cell = spec.load_cell(CELL)
    peaks = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
    ops, nbytes = join_gauss_roofline.cost(cell.config)
    least = max(ops / 197e12, nbytes / 819e9)
    assert least == pytest.approx(nbytes / 819e9)  # the bytes bound it
    device = {"programs": {
        "jit_join_window_pallas": {"runs": 11, "seconds": 3.0, "ops": []},
        "jit_join_window_cells": {"runs": 10, "seconds": 0.5, "ops": []},
        "jit_head_pairs": {"runs": 10, "seconds": 0.5, "ops": []}}}
    t = _trace(cell=cell, device=device, peaks=peaks)
    # the bucket cells' program is part of what the extraction cost
    assert join_gauss_roofline.read(t, programs=["jit_join_window"]) == \
        pytest.approx(10 * least / 3.5 * 100.0)
    assert join_gauss_roofline.read(_trace(cell=cell, peaks=peaks, device={
        "programs": {"jit_other": {"runs": 1, "seconds": 1.0, "ops": []}}}),
        programs=["jit_join_window"]) is None
    assert join_gauss_roofline.read(_trace(cell=cell), programs=["x"]) is None


def test_lanes_per_pair_and_pairs_per_window():
    lanes = spec.metric_file("join_lanes_per_pair")
    pairs = spec.metric_file("join_pairs_per_window")
    # 11 joined windows between the open and the close, 10 results inside
    t = _trace(extras={"join.bucket_lanes": 11 * 160_000 * 9 * 128 ** 2,
                       "join.pairs": 11 * 7_936_508, "join.windows": 11})
    assert counter_ratio.read(t, **lanes["args"]) == pytest.approx(
        2_972.6, rel=1e-3)
    assert counter_ratio.read(t, **pairs["args"]) == pytest.approx(7_936_508)
    # join.flood's shapes: 10,000 cells x 9 x 128^2 over ~ 1.0 M pairs
    u = _trace(extras={"join.bucket_lanes": 10 * 10_000 * 9 * 128 ** 2,
                       "join.pairs": 9_973_310})
    assert counter_ratio.read(u, **lanes["args"]) == pytest.approx(
        1_478.5, rel=1e-3)
    # a program that keeps no such counter (the parent): nothing to report
    parent = _trace(extras={"join.pairs": 9_973_310})
    assert counter_ratio.read(parent, **lanes["args"]) is None


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
    ad, ref_mod = control.prepared(2**31 + 41, rehearsal=True)
    low = control.control(ad, ref_mod, control.DTYPES["bfloat16"], 2)
    assert low["wrong"] and all(
        any("missing" in line for line in bad) for bad in low["wrong"].values())
    ad, ref_mod = control.prepared(2**31 + 41, rehearsal=True)
    own = control.control(ad, ref_mod, control.DTYPES["float32"], 2)
    assert own["wrong"] == {} and own["problems"] == []
    assert own["pairs"] > 1_500


def test_the_feeds_end_reaches_the_operator_as_an_exception():
    """Nothing is flushed after the close: the proxy hands the feed's
    segments on, raises where they end, and is the feed in all else."""

    class Feed:
        t_closed = None

        def segments(self):
            yield (0, 10)
            yield (10, 20)
            self.t_closed = 1.0

    feed = Feed()
    proxy = join_soa_skew._EndsByRaising(feed)
    got = []
    with pytest.raises(join_soa_skew._FeedEnded):
        for seg in proxy.segments():
            got.append(seg)
    assert got == [(0, 10), (10, 20)]
    assert proxy.t_closed == 1.0 and feed.t_closed == 1.0
