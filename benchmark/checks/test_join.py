"""``join-tdrive-2x100k`` / ``join.flood``: the files load, the demultiplexer
hands both sides every event once, the readers' arithmetic.

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

from benchmark.adapters.join_soa import Adapter, Demux  # noqa: E402
from benchmark.harness import spec, traffic  # noqa: E402
from benchmark.harness.main import Trace  # noqa: E402
from benchmark.readers import counter_ratio, join_roofline  # noqa: E402

NEW_METRICS = {
    "join_extract_roofline", "join_d2h_bytes_per_pair",
    "join_assemble_us_per_event", "join_h2d_us_per_event",
    "join_dispatch_us_per_event", "join_d2h_us_per_event",
    "join_retries_per_window"}


def test_the_cell_loads_through_spec():
    cell = spec.load_cell("join.flood")
    cfg, tr = cell.config, cell.traffic
    assert cell.chips == 1 and cfg["name"] == "join-tdrive-2x100k"
    assert cfg["stream"]["event_rate_eps"] == 200_000  # both streams
    assert (cfg["window_s"], cfg["slide_s"], cfg["fire_delay_ms"]) == (5, 5, 0)
    assert cfg["grid_cells"] == 100 and cfg["radius"] == 0.002
    assert cfg["approximate"] is False
    assert cfg["expect_join_backend"] == "pallas"
    # 4 x float32 eps x the bbox span, and its reason beside it
    span = cfg["stream"]["bbox"][2] - cfg["stream"]["bbox"][0]
    assert cfg["tolerance_deg"] == pytest.approx(
        4 * float(np.finfo(np.float32).eps) * span, rel=0.01)
    assert "float32" in cfg["tolerance_why"]
    assert [r.split(":")[0] for r in cfg["reduced"]] == ["stream_seconds"]
    assert tr["mode"] == "flood" and tr["batch_events"] == 10_000
    assert tr["pool_events"] == 8_000_000 and tr["warmup_results"] == 2
    assert "stream_eps" not in tr  # a pooled flood has no end (PR 31)
    assert {m["name"] for m in cell.end_to_end} == {"events_per_s", "setup_s"}
    reported = {m["name"] for m in cell.per_layer}
    assert NEW_METRICS <= reported
    assert {"ingest_us_per_event", "h2d_bytes_per_event", "peak_hbm_bytes",
            "ship_fetch_us_per_event", "kernel_ms_per_window",
            "device_idle_share"} <= reported
    entry = next(c for c in spec.benchmark()["configs"]
                 if c["name"] == "join-tdrive-2x100k")
    assert entry["reduced"] == ["stream_seconds"]
    assert len(entry["source"]) <= 200 and "configs[3]" in entry["source"] \
        and "PointPointJoinQuery.java:124-183" in entry["source"]


def test_the_reference_is_the_tests_original_byte_for_byte():
    with open(os.path.join(ROOT, "tests", "join_reference.py"), "rb") as f:
        original = f.read()
    with open(os.path.join(ROOT, "benchmark", "references",
                           "join_tdrive.py"), "rb") as f:
        assert f.read() == original


@pytest.mark.parametrize("first", [0, 1], ids=["even_start", "odd_start"])
def test_demux_hands_both_sides_every_event_once(first):
    n, batch = 10_000 + first, 700
    segs = [(lo, min(lo + batch, n)) for lo in range(first, n, batch)]
    pulled_at = []
    chunk = lambda lo, hi: {"i": np.arange(lo, hi)}
    demux = Demux(iter(segs), chunk)
    sides = (demux.side(0), demux.side(1))
    got = ([], [])
    # the operator's order: the left side until it has a window (here, 5
    # chunks), then the right side up to there, and so on
    done = [False, False]
    while not all(done):
        for which in (0, 1):
            for _ in range(5):
                c = next(sides[which], None)
                if c is None:
                    done[which] = True
                    break
                got[which].append(c["i"])
                pulled_at.append((demux.pulled, max(demux.handed)))
    a, b = (np.concatenate(g) for g in got)
    assert (a % 2 == 0).all() and (b % 2 == 1).all()
    assert np.array_equal(np.sort(np.concatenate([a, b])),
                          np.arange(first, n))
    assert (np.diff(a) > 0).all() and (np.diff(b) > 0).all()  # in order
    assert demux.pulled == len(segs) == demux.handed[0] == demux.handed[1]
    # the feed is never more than the one segment in hand ahead of the side
    # that leads
    assert all(p - h <= 0 for p, h in pulled_at)


def test_adapter_chunks_are_views_with_advancing_timestamps():
    cell = spec.load_cell("join.flood")
    stream_cfg = traffic.effective(cell.config["stream"], True)
    tr = traffic.effective(cell.traffic, True)
    windows = traffic.Windows(5000, 5000, 0, int(stream_cfg["t0_ms"]))
    stream, _w = traffic.build_stream(stream_cfg, tr, windows, 2**31 + 5,
                                      12.0, False)
    ad = Adapter(cell.config, stream_cfg, "/nonexistent", rehearsal=True)
    assert ad.grid_cells == cell.config["rehearsal"]["grid_cells"]
    ad.stream, ad.windows = stream, windows
    ad.ts_pool = stream.ts(0, stream.pool)
    ad.cycle_ms = stream.pool * 1000 // stream.rate_eps
    lo = stream.pool + 3000  # second cycle of the pool
    c = ad._chunk(lo, lo + 1000)
    assert np.array_equal(c["ts"], stream.ts(lo, lo + 1000))
    assert np.shares_memory(c["x"], stream.x)
    assert np.array_equal(c["x"], stream.x[3000:4000])


def _trace(**kw):
    base = dict(cell=None, feed=None, events=1_000_000, windows=10, host=[],
                spans=[], counters={}, device=None, peaks=None,
                memory_peak_bytes=None, extras={})
    base.update(kw)
    return Trace(**base)


def test_join_roofline_cost_by_hand():
    cfg = spec.load_cell("join.flood").config
    ops, nbytes = join_roofline.cost(cfg)
    n = 500_000
    pairs = n * n * math.pi * 0.002 ** 2 / (2.1 * 1.5)
    assert pairs == pytest.approx(997_331, rel=1e-4)
    assert nbytes == pytest.approx(2 * n * 8 + 12 * pairs)
    # cell side 0.021: 100 columns x ceil(1.5 / 0.021) = 72 rows hold points
    assert ops == pytest.approx(8 * n * 9 * n / 7200)


def test_join_roofline_share_of_the_windows_time():
    cell = spec.load_cell("join.flood")
    peaks = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
    ops, nbytes = join_roofline.cost(cell.config)
    least = max(ops / 197e12, nbytes / 819e9)
    assert least == pytest.approx(nbytes / 819e9)  # the bytes bound it
    device = {"programs": {
        "jit_join_window_pallas": {"runs": 11, "seconds": 4.0, "ops": []},
        "jit_head_pairs": {"runs": 10, "seconds": 0.5, "ops": []}}}
    t = _trace(cell=cell, device=device, peaks=peaks)
    # a re-run counts in the time, not in the work: 10 windows over 4 s
    assert join_roofline.read(t, programs=["jit_join_window"]) == \
        pytest.approx(10 * least / 4.0 * 100.0)
    assert join_roofline.read(_trace(cell=cell, peaks=peaks, device={
        "programs": {"jit_other": {"runs": 1, "seconds": 1.0, "ops": []}}}),
        programs=["jit_join_window"]) is None
    assert join_roofline.read(_trace(cell=cell), programs=["x"]) is None


def test_counter_ratio_and_a_program_without_the_counter():
    t = _trace(counters={"d2h_bytes": 126_000_080},
               extras={"join.pairs": 9_973_000, "join.cap_retries": 0,
                       "join.budget_retries": 0})
    assert counter_ratio.read(t, num=["d2h_bytes"], den=["join.pairs"]) == \
        pytest.approx(126_000_080 / 9_973_000)
    assert counter_ratio.read(
        t, num=["join.cap_retries", "join.budget_retries"],
        den=["results"]) == 0.0
    parent = _trace(counters={"d2h_bytes": 5})  # keeps no join counters
    assert counter_ratio.read(parent, num=["d2h_bytes"],
                              den=["join.pairs"]) is None
    assert counter_ratio.read(
        parent, num=["join.cap_retries", "join.budget_retries"],
        den=["results"]) is None


@pytest.mark.parametrize("metric", sorted(NEW_METRICS))
def test_new_metric_files_name_a_reader(metric):
    mf = spec.metric_file(metric)
    assert callable(spec.plugin("readers", mf["reader"]).read)
    # harness/roofline.py is not where this cell's cost function lives
    assert "cost" not in mf.get("args", {})
