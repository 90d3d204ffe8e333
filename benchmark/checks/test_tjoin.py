"""``tjoin-tdrive-2x100k`` / ``tjoin.flood``: the files load, the reference
against the O(n^2) loop, the adapter's ``verify`` catches what it says it
checks (and the bfloat16 control fails it), the readers' arithmetic.

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

from benchmark.adapters.tjoin_soa import TJOIN_COUNTERS, Adapter  # noqa: E402
from benchmark.checks import tjoin_precision_control as precision  # noqa: E402
from benchmark.harness import spec, traffic  # noqa: E402
from benchmark.harness.main import Trace  # noqa: E402
from benchmark.readers import counter_ratio, tjoin_dedup_roofline  # noqa: E402
from benchmark.references import tjoin_tdrive  # noqa: E402

NEW_METRICS = {
    "tjoin_window_us_per_event", "tjoin_dedup_dispatch_us_per_event",
    "tjoin_retries_per_window", "tjoin_collapse_share",
    "tjoin_unspanned_us_per_event", "tjoin_d2h_wait_us_per_event",
    "tjoin_d2h_bytes_per_tpair", "tjoin_dedup_roofline"}
SHARED = {
    "ingest_us_per_event", "h2d_bytes_per_event", "ship_fetch_us_per_event",
    "kernel_ms_per_window", "device_idle_share", "peak_hbm_bytes",
    "soa_consolidate_us_per_event", "soa_center_us_per_event",
    "soa_cells_us_per_event", "soa_pad_us_per_event", "join_extract_roofline",
    "join_assemble_us_per_event", "join_assemble_left_us_per_event",
    "join_capacity_us_per_event"}


def test_the_cell_loads_through_spec():
    cell = spec.load_cell("tjoin.flood")
    cfg, tr = cell.config, cell.traffic
    join = spec.load_cell("join.flood").config
    assert cell.chips == 1 and cfg["name"] == "tjoin-tdrive-2x100k"
    assert cfg["adapter"] == "tjoin_soa" and cfg["reference"] == "tjoin_tdrive"
    # join-tdrive-2x100k's stream to the letter
    assert cfg["stream"] == join["stream"]
    for same in ("window_s", "slide_s", "fire_delay_ms", "grid_cells",
                 "radius", "approximate", "tolerance_deg",
                 "expect_join_backend", "rehearsal"):
        assert cfg[same] == join[same], same
    assert (cfg["window_s"], cfg["slide_s"]) == (5, 5)
    assert cfg["num_segments"] == cfg["stream"]["ids"] == 16_384
    assert "float32" in cfg["tolerance_why"]
    assert [r.split(":")[0] for r in cfg["reduced"]] == ["stream_seconds"]
    assert tr["mode"] == "flood" and tr["batch_events"] == 10_000
    assert tr["pool_events"] == 8_000_000 and tr["warmup_results"] == 2
    assert tr["rehearsal"] == {"batch_events": 1000, "pool_events": 800_000}
    assert "stream_eps" not in tr  # a pooled flood has no end
    assert {m["name"] for m in cell.end_to_end} == {"events_per_s", "setup_s"}
    reported = {m["name"] for m in cell.per_layer}
    assert NEW_METRICS <= reported and SHARED <= reported
    entry = next(c for c in spec.benchmark()["configs"]
                 if c["name"] == "tjoin-tdrive-2x100k")
    assert entry["reduced"] == ["stream_seconds"]
    assert len(entry["source"]) <= 200 \
        and "PointPointTJoinQuery.java:183+" in entry["source"] \
        and "TJoinQuery.java:60-154" in entry["source"]


@pytest.mark.parametrize("seed", [0, 1])
def test_the_reference_equals_the_double_loop(seed):
    rng = np.random.default_rng(seed)
    n = 400
    lx, ly, rx, ry = (rng.uniform(0, 10, n) for _ in range(4))
    lo, ro = rng.integers(0, 15, n), rng.integers(0, 11, n)
    ref = tjoin_tdrive.Reference(bbox=(0, 0, 10, 10), grid_cells=20,
                                 radius=0.5, tol=0.0, num_ids=15)
    a, b, d = ref.tpairs(lx, ly, lo, rx, ry, ro)
    want = tjoin_tdrive.brute_force(lx, ly, lo, rx, ry, ro, 0.5)
    assert len(want) > 30
    assert {(int(p), int(q)): v for p, q, v in zip(a, b, d)} == \
        pytest.approx(want)


def _adapter_with_windows():
    return precision.prepared(2**31 + 39, rehearsal=True)


def test_verify_passes_float32_and_fails_bfloat16():
    """The control: the reference's own answer on inputs rounded to bfloat16
    in the program's place is not correct; in float32 it is."""
    ad, ref_mod = _adapter_with_windows()
    ok = precision.control(ad, ref_mod, np.float32, 2)
    assert ok["checked"] == 2 and not ok["wrong"] and not ok["problems"]
    assert 0 < ok["max_distance_deviation_deg"] < ad.cfg["tolerance_deg"]
    low = precision.control(ad, ref_mod, precision.DTYPES["bfloat16"], 2)
    assert set(low["wrong"]) == {0, 1}
    said = " ".join(low["wrong"][0])
    assert "missing" in said and "beyond the radius" in said
    assert low["max_distance_deviation_deg"] > 100 * ad.cfg["tolerance_deg"]


def test_verify_catches_a_window_out_of_order_a_short_one_and_a_double():
    ad, ref_mod = _adapter_with_windows()
    precision.control(ad, ref_mod, np.float32, 3)
    good = list(ad.got)
    ad.got = [good[0], good[2]]
    assert "missing or out of order" in ad.verify(None)["problems"][0]
    end, lo, ro, dd, count, _over = good[1]
    ad.got = [good[0], (end, lo, ro, dd, count, 3)]
    assert "overflow 3" in ad.verify(None)["wrong"][1][0]
    ad.got = [good[0], (end, np.append(lo, lo[0]), np.append(ro, ro[0]),
                        np.append(dd, dd[0]), count + 1, 0)]
    assert "twice" in " ".join(ad.verify(None)["wrong"][1])
    ad.got = [good[0], (end, lo[1:], ro[1:], dd[1:], count - 1, 0)]
    assert "missing" in " ".join(ad.verify(None)["wrong"][1])


def test_health_holds_the_sizes_and_the_backend():
    ad, _ref = _adapter_with_windows()
    assert ad.health()["problems"] == []  # the CPU backend: any extraction
    ad.sizes_open = ad._sizes()
    ad.op.tpair_budget = 2048
    assert "grew inside the window" in ad.health()["problems"][0]
    ad.sizes_open = ad._sizes()
    ad.tjoin_open = {"cap_retries": 0, "budget_retries": 0}
    ad.tjoin_close = {"cap_retries": 0, "budget_retries": 1, "windows": 4,
                      "pairs": 10, "tpairs": 9, "cap": 128, "budget": 2048,
                      "tpair_budget": 2048}
    h = ad.health()
    assert h["retries_in_window"] == 1 and "re-runs" in h["problems"][0]
    extras = ad.extras()
    assert extras["tjoin.budget_retries"] == 1 and extras["tjoin.cap"] == 128
    assert {f"tjoin.{k}" for k in TJOIN_COUNTERS} <= set(extras)


def test_a_program_without_the_contract_is_refused_cleanly(monkeypatch):
    """The parent's operator keeps no ``tpair_budget``: ``prepare`` raises
    ``SpecError`` before anything runs."""
    from spatialflink_tpu.operators import trajectory

    class Old(trajectory.SpatialOperator):
        pass

    monkeypatch.setattr(trajectory, "PointPointTJoinQuery", Old)
    with pytest.raises(spec.SpecError, match="tpair_budget"):
        precision.prepared(7, rehearsal=True)


def _trace(**kw):
    base = dict(cell=None, feed=None, events=1_000_000, windows=10, host=[],
                spans=[], counters={}, device=None, peaks=None,
                memory_peak_bytes=None, extras={})
    base.update(kw)
    return Trace(**base)


def test_dedup_roofline_cost_by_hand():
    cfg = spec.load_cell("tjoin.flood").config
    pairs, tpairs = tjoin_dedup_roofline.expected(cfg)
    n = 500_000
    assert pairs == pytest.approx(n * n * math.pi * 0.002 ** 2 / (2.1 * 1.5))
    assert pairs == pytest.approx(997_331, rel=1e-4)
    # collisions ~ pairs^2 / (2 ids^2) ~ 1,850
    assert pairs - tpairs == pytest.approx(pairs ** 2 / (2 * 16_384 ** 2),
                                           rel=0.01)
    ops, nbytes = tjoin_dedup_roofline.cost(cfg)
    assert nbytes == pytest.approx(12 * pairs + 12 * tpairs)
    assert ops == pytest.approx(3 * pairs * math.log2(pairs))


def test_dedup_roofline_share_of_the_windows_time():
    cell = spec.load_cell("tjoin.flood")
    peaks = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
    ops, nbytes = tjoin_dedup_roofline.cost(cell.config)
    least = max(ops / 197e12, nbytes / 819e9)
    assert least == pytest.approx(nbytes / 819e9)  # the bytes bound it
    device = {"programs": {
        "jit_traj_pair_dedup_kernel": {"runs": 11, "seconds": 0.4, "ops": []},
        "jit_join_window_pallas": {"runs": 10, "seconds": 0.9, "ops": []}}}
    t = _trace(cell=cell, device=device, peaks=peaks)
    got = tjoin_dedup_roofline.read(t, programs=["jit_traj_pair_dedup"])
    assert got == pytest.approx(10 * least / 0.4 * 100.0) and got < 100
    # the parent's program has no such kernel: nothing, and no error
    assert tjoin_dedup_roofline.read(_trace(cell=cell, peaks=peaks, device={
        "programs": {"jit_join_window_pallas": {"runs": 1, "seconds": 1.0,
                                                "ops": []}}}),
        programs=["jit_traj_pair_dedup"]) is None
    assert tjoin_dedup_roofline.read(_trace(cell=cell),
                                     programs=["x"]) is None


def test_counter_metrics_and_a_program_without_the_counters():
    t = _trace(counters={"d2h_bytes": 125_830_000},
               extras={"tjoin.pairs": 9_973_000, "tjoin.tpairs": 9_954_000,
                       "tjoin.cap_retries": 0, "tjoin.budget_retries": 0})
    share = spec.metric_file("tjoin_collapse_share")
    assert counter_ratio.read(t, **share["args"]) == \
        pytest.approx(9_954_000 / 9_973_000)
    per = spec.metric_file("tjoin_d2h_bytes_per_tpair")
    assert counter_ratio.read(t, **per["args"]) == \
        pytest.approx(125_830_000 / 9_954_000)
    retries = spec.metric_file("tjoin_retries_per_window")
    assert counter_ratio.read(t, **retries["args"]) == 0.0
    parent = _trace(counters={"d2h_bytes": 5})  # keeps no tjoin counters
    for m in (share, per, retries):
        assert counter_ratio.read(parent, **m["args"]) is None


@pytest.mark.parametrize("metric", sorted(NEW_METRICS))
def test_new_metric_files_name_a_reader(metric):
    mf = spec.metric_file(metric)
    assert callable(spec.plugin("readers", mf["reader"]).read)
    entry = next(m for m in spec.benchmark()["per_layer"]
                 if m["name"] == metric)
    assert entry["workloads"] == ["tjoin.flood"]
    assert entry["moves"] == "events_per_s"
