"""Trend store (tools/sfprof/trend.py + the ``trend`` CLI): history
ingestion across every record shape (ledgers, streams, legacy BENCH_r*
supervisor records, bare bench records), the
skip-with-counted-evidence contract, MAD-band gating, and taint
rejection."""

import json
import os

import pytest

from tools.sfprof import trend
from tools.sfprof.cli import main as sfprof_main


# -- corpus builders ----------------------------------------------------------


def _bench(value, config="cfg_a", smoke=True, device="TFRT_CPU_0",
           resident=None, pipeline=False, tainted=None):
    out = {
        "metric": config, "value": float(value), "unit": "points/s",
        "device": device, "smoke": smoke,
        "pipeline": {"armed": bool(pipeline)},
    }
    if resident is not None:
        out["device_resident_points_per_sec"] = float(resident)
    if tainted is not None:
        out["tainted"] = tainted
    return out


def _supervisor(value, n=1, rc=0, **kw):
    return {"n": n, "cmd": "python bench.py", "rc": rc,
            "parsed": _bench(value, **kw)}


def _write(tmp_path, name, doc):
    p = tmp_path / name
    p.write_text(json.dumps(doc) + "\n")
    return str(p)


def _history_dir(tmp_path, values=(90e3, 100e3, 110e3, 120e3), **kw):
    d = tmp_path / "hist"
    d.mkdir(exist_ok=True)
    for i, v in enumerate(values):
        _write(d, f"r{i:02d}.json", _supervisor(v, n=i, **kw))
    return str(d)


def _ledger(value, tainted=None, created=1000.0, **kw):
    doc = {
        "ledger_version": 1, "created_unix": created,
        "env": {"backend": "cpu", "devices": ["TFRT_CPU_0"]},
        "snapshot": {"compiles": 0, "bytes_h2d": 0, "bytes_d2h": 0,
                     "max_watermark_lag_ms": 0, "late_dropped": 0,
                     "dropped_events": 0, "kernels": {}},
        "kernels": [], "events": [],
        "bench": _bench(value, **kw),
    }
    if tainted is not None:
        doc["tainted"] = tainted
    return doc


TAINT = {"kind": "ablation", "kernels": ["k"],
         "substituted_calls": {"k": 3}, "learning_calls": {"k": 1}}


# -- ingestion across record shapes -------------------------------------------


def test_ingest_supervisor_ledger_and_bare(tmp_path):
    d = tmp_path / "mix"
    d.mkdir()
    _write(d, "a_supervisor.json", _supervisor(100e3))
    _write(d, "b_ledger.json", _ledger(110e3))
    _write(d, "d_bare.json", _bench(130e3))
    points, skipped = trend.ingest_paths([str(d)])
    assert skipped == []
    assert sorted(p["value"] for p in points) \
        == [100e3, 110e3, 130e3]
    # One series: every shape lands on the same key.
    assert len(trend.build_series(points)) == 1


def test_ingest_stream_via_recovery(tmp_path):
    lines = [
        {"t": "prologue", "stream_version": 1, "ledger_version": 1,
         "created_unix": 5.0, "env": {"python": "3"}},
        {"t": "checkpoint", "seq": 1, "unix": 6.0,
         "snapshot": {"compiles": 0}, "kernels": []},
        {"t": "epilogue", "seq": 1, "unix": 7.0, "reason": "complete",
         "bench": _bench(140e3)},
    ]
    p = tmp_path / "run.stream.jsonl"
    p.write_text("".join(json.dumps(ln) + "\n" for ln in lines))
    points, skipped = trend.ingest_paths([str(p)])
    assert skipped == []
    assert points[0]["value"] == 140e3


def test_legacy_failures_skip_with_counted_evidence(tmp_path):
    d = tmp_path / "hist"
    d.mkdir()
    # A timed-out run: rc=124, parsed null — skipped, not a crash.
    _write(d, "r05.json", {"n": 5, "cmd": "python bench.py", "rc": 124,
                           "tail": "WARNING: platform experimental\n",
                           "parsed": None})
    # rc=0 but only a tail: the one-line contract means the last JSON
    # line IS the record.
    _write(d, "r06.json", {
        "n": 6, "cmd": "python bench.py", "rc": 0, "parsed": None,
        "tail": "WARNING: noise\n" + json.dumps(_bench(150e3)) + "\n",
    })
    # Unparseable tail, rc=0: skipped with its reason.
    _write(d, "r07.json", {"n": 7, "cmd": "python bench.py", "rc": 0,
                           "parsed": None, "tail": "no json here"})
    # A zero-value error record (honest outage output): skipped.
    _write(d, "r08.json", _supervisor(0.0))
    # Garbage file: skipped, never a crash.
    (d / "r09.json").write_text("{not json")
    points, skipped = trend.ingest_paths([str(d)])
    assert [p["value"] for p in points] == [150e3]
    reasons = " | ".join(s["reason"] for s in skipped)
    assert "rc=124" in reasons
    assert "no parseable record" in reasons
    assert "zero/absent EPS" in reasons
    assert len(skipped) == 4


def test_tainted_history_is_skipped_with_reason(tmp_path):
    d = tmp_path / "hist"
    d.mkdir()
    _write(d, "clean.json", _ledger(100e3))
    _write(d, "stubbed.json", _ledger(900e3, tainted=TAINT))
    # Taint riding only the snapshot (the stream-recovery shape) must
    # also be caught.
    snap_tainted = _ledger(901e3)
    snap_tainted["snapshot"]["tainted"] = TAINT
    _write(d, "stubbed2.json", snap_tainted)
    points, skipped = trend.ingest_paths([str(d)])
    assert [p["value"] for p in points] == [100e3]
    assert all("tainted: ablation" in s["reason"] for s in skipped)
    assert len(skipped) == 2


def test_series_keys_separate_device_smoke_and_pipeline(tmp_path):
    d = tmp_path / "hist"
    d.mkdir()
    _write(d, "a.json", _bench(1.0, smoke=True))
    _write(d, "b.json", _bench(2.0, smoke=False))
    _write(d, "c.json", _bench(3.0, smoke=False, device="TPU v5 lite0"))
    _write(d, "d.json", _bench(4.0, smoke=False, device="TPU v5 lite0",
                               pipeline=True))
    points, _ = trend.ingest_paths([str(d)])
    assert len(trend.build_series(points)) == 4
    assert trend.device_class("TPU v5 lite0") == "tpu"
    assert trend.device_class("TFRT_CPU_0") == "cpu"


# -- robust stats + gate math -------------------------------------------------


def test_gate_metric_mad_band_and_relative_floor():
    hist = [90e3, 100e3, 110e3, 120e3]  # median 105k, MAD 10k
    ok = trend.gate_metric(hist, 95e3, mad_k=4.0, eps_tol=0.5)
    assert ok["ok"] is True
    # Below the MAD band AND below median/2: regression.
    bad = trend.gate_metric(hist, 40e3, mad_k=4.0, eps_tol=0.5)
    assert bad["ok"] is False
    # Outside the MAD band but above the relative floor: tolerated
    # (both legs must agree — a tight series must not flag noise).
    tight = [100e3, 100e3, 100e3, 100e3]  # MAD 0
    assert trend.gate_metric(tight, 60e3, 4.0, 0.5)["ok"] is True
    assert trend.gate_metric(tight, 49e3, 4.0, 0.5)["ok"] is False
    # Faster is never a regression.
    assert trend.gate_metric(hist, 10 * 120e3, 4.0, 0.5)["ok"] is True


# -- the CLI gate -------------------------------------------------------------


def test_trend_gate_pass_and_injected_regression(tmp_path, capsys):
    hist = _history_dir(tmp_path, resident=400e3)
    good = _write(tmp_path, "good.json",
                  _ledger(101e3, resident=410e3))
    assert sfprof_main(["trend", hist, "--gate", good]) == 0
    out = capsys.readouterr().out
    assert "gate verdict: PASS" in out
    bad = _write(tmp_path, "bad.json", _ledger(30e3, resident=410e3))
    assert sfprof_main(["trend", hist, "--gate", bad]) == 1
    out = capsys.readouterr().out
    assert "FAIL points_per_sec" in out
    assert "gate verdict: FAIL" in out


def test_trend_gate_resident_column(tmp_path):
    hist = _history_dir(tmp_path, resident=400e3)
    # e2e fine, resident collapsed: the silicon column gates too.
    bad_res = _write(tmp_path, "badres.json",
                     _ledger(101e3, resident=30e3))
    assert sfprof_main(["trend", hist, "--gate", bad_res]) == 1


def test_trend_gate_rejects_tainted_candidate(tmp_path, capsys):
    hist = _history_dir(tmp_path)
    cand = _write(tmp_path, "stub.json",
                  _ledger(500e3, tainted=TAINT))
    assert sfprof_main(["trend", hist, "--gate", cand]) == 1
    out = capsys.readouterr().out
    assert "REJECT" in out and "tainted" in out and "ablation" in out


def test_trend_gate_insufficient_history(tmp_path, capsys):
    hist = _history_dir(tmp_path, values=(100e3,))
    cand = _write(tmp_path, "c.json", _ledger(100e3))
    # Advisory by default; the CI mode (--require-history) fails.
    assert sfprof_main(["trend", hist, "--gate", cand]) == 0
    assert "insufficient history" in capsys.readouterr().out
    assert sfprof_main(["trend", hist, "--gate", cand,
                        "--require-history"]) == 1


def test_trend_gate_excludes_candidate_from_its_own_history(tmp_path):
    # The SFT_LEDGER_DIR layout: the candidate sits IN the history dir.
    d = tmp_path / "hist"
    d.mkdir()
    for i, v in enumerate((90e3, 100e3, 110e3, 120e3)):
        _write(d, f"r{i:02d}.json", _supervisor(v, n=i))
    cand = _write(d, "candidate.json", _ledger(95e3))
    assert sfprof_main(["trend", str(d), "--gate", cand]) == 0


def test_twin_artifacts_of_one_capture_count_once(tmp_path):
    """The SFT_LEDGER_DIR layout writes a ledger AND its stream per
    capture; the stream's recovery carries the identical bench record.
    The series must count each capture once — twin double-counting
    shrinks the MAD and gates candidates against themselves."""
    d = tmp_path / "hist"
    d.mkdir()
    for i, v in enumerate((90e3, 100e3, 110e3)):
        _write(d, f"r{i:02d}.json", _supervisor(v, n=i))
        # The stream twin of the same capture (identical bench record).
        (d / f"r{i:02d}.stream.jsonl").write_text("".join(
            json.dumps(ln) + "\n" for ln in [
                {"t": "prologue", "stream_version": 1,
                 "ledger_version": 1, "created_unix": float(i),
                 "env": {}},
                {"t": "epilogue", "seq": 0, "unix": float(i) + 1,
                 "reason": "complete", "bench": _bench(v)},
            ]))
    points, skipped = trend.ingest_paths([str(d)])
    assert skipped == []
    assert len(points) == 6
    (series,) = trend.build_series(points).values()
    assert [p["value"] for p in series] == [90e3, 100e3, 110e3]


def test_trend_gate_self_exclusion_covers_the_stream_twin(tmp_path):
    """A candidate whose OWN run also sits in history under another
    path (its stream twin) must not be gated against itself: with only
    twins in the dir, the gate reports insufficient history."""
    d = tmp_path / "hist"
    d.mkdir()
    cand = _write(d, "cfg.json", _ledger(200e3))
    (d / "cfg.stream.jsonl").write_text("".join(
        json.dumps(ln) + "\n" for ln in [
            {"t": "prologue", "stream_version": 1, "ledger_version": 1,
             "created_unix": 1.0, "env": {}},
            {"t": "epilogue", "seq": 0, "unix": 2.0,
             "reason": "complete", "bench": _bench(200e3)},
        ]))
    assert sfprof_main(["trend", str(d), "--gate", cand,
                        "--require-history"]) == 1


def test_trend_gate_min_history_zero_never_crashes(tmp_path, capsys):
    """--min-history 0 with an empty series must hit the insufficient-
    history path (stats need >= 1 point), not an IndexError — the exit
    code contract is 0/1/2, never a traceback."""
    d = tmp_path / "hist"
    d.mkdir()
    cand = _write(tmp_path, "c.json", _ledger(100e3))
    assert sfprof_main(["trend", str(d), "--gate", cand,
                        "--min-history", "0"]) == 0
    assert "insufficient history" in capsys.readouterr().out


def test_point_key_carries_armed_codec():
    pt, reason = trend.point_from_bench(
        dict(_bench(100e3), pipeline={"armed": True,
                                      "armed_codec": "delta"}),
        "x.json")
    assert reason is None
    assert pt["pipeline"] is True and pt["codec"] == "delta"
    key = dict(zip(trend.SERIES_KEY_FIELDS, trend.series_key(pt)))
    assert key["codec"] == "delta"


def test_trend_gate_unreadable_candidate(tmp_path):
    hist = _history_dir(tmp_path)
    assert sfprof_main(["trend", hist, "--gate",
                        str(tmp_path / "absent.json")]) == 2


def test_trend_json_schema(tmp_path, capsys):
    hist = _history_dir(tmp_path)
    cand = _write(tmp_path, "c.json", _ledger(101e3))
    assert sfprof_main(["trend", hist, "--gate", cand, "--json"]) == 0
    out = json.loads(capsys.readouterr().out)
    (row,) = out["series"]
    assert row["key"]["config"] == "cfg_a"
    assert row["key"]["device_class"] == "cpu"
    assert row["n"] == 4 and row["median"] == 105e3
    assert out["gate"]["checks"][0]["metric"] == "points_per_sec"
    assert out["gate"]["checks"][0]["ok"] is True
    assert out["skipped"] == []


def test_trend_without_gate_reports_series(tmp_path, capsys):
    hist = _history_dir(tmp_path)
    assert sfprof_main(["trend", hist]) == 0
    out = capsys.readouterr().out
    assert "1 series" in out and "median=105000.0" in out


# -- the committed CI fixture stays self-consistent ---------------------------


REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
FIXTURE_DIR = os.path.join(REPO, "tests", "fixtures", "trend")


def test_committed_ci_fixture_matches_the_smoke_key(tmp_path):
    """The toy trajectory tools.ci gates the smoke ledger against: it
    must ingest cleanly (one skipped outage record — the evidence
    contract), form ONE smoke/cpu series with enough history, and
    accept a typical smoke capture while rejecting a collapsed one."""
    points, skipped = trend.ingest_paths([FIXTURE_DIR])
    assert len(points) >= trend.DEFAULT_MIN_HISTORY
    assert len(skipped) == 1 and "rc=124" in skipped[0]["reason"]
    series = trend.build_series(points)
    (key,) = series.keys()
    key_d = dict(zip(trend.SERIES_KEY_FIELDS, key))
    assert key_d["config"] \
        == "continuous_knn_k50_1M_window_points_per_sec_per_chip"
    assert key_d["device_class"] == "cpu"
    assert key_d["smoke"] is True
    assert key_d["pipeline"] is False
    # A smoke record 5x the fixture median passes; a collapsed one
    # (50x under) fails — the CI chain gates something real.
    ok = _write(tmp_path, "ok.json", _ledger(
        500e3, config=key_d["config"], resident=2e6))
    assert sfprof_main(["trend", FIXTURE_DIR, "--gate", ok,
                        "--require-history"]) == 0
    broken = _write(tmp_path, "broken.json", _ledger(
        2e3, config=key_d["config"], resident=2e6))
    assert sfprof_main(["trend", FIXTURE_DIR, "--gate", broken,
                        "--require-history"]) == 1
