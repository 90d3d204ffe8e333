"""Roofline bound-classification (tools/sfprof/roofline.py): verdicts
pinned on a synthetic ledger corpus — one fixture per bound class —
plus the evidence-chain and CLI (--json) surfaces."""

import json

import pytest

from tools.sfprof import roofline
from tools.sfprof.cli import main as sfprof_main

WALL_US = 100_000  # one 100 ms traced span for every fixture


def _ev(name, ts, dur, tid=1):
    return {"name": name, "ph": "X", "ts": ts, "dur": dur,
            "pid": 7, "tid": tid}


def _kernel(name, calls, steady_ms, flops=1e3, nbytes=1e3):
    steady_ns = int(steady_ms * 1e6)
    return {
        "kernel": name, "signature": "()", "calls": calls,
        "dispatch_ns": steady_ns + 1_000_000,
        "first_call_ns": 1_000_000,
        "steady_ns": steady_ns,
        "cost": {"flops": flops, "bytes_accessed": nbytes},
    }


def _doc(snapshot=None, kernels=None, backend="cpu"):
    snap = {
        "compiles": 1, "bytes_h2d": 0, "bytes_d2h": 0,
        "max_watermark_lag_ms": 0, "late_dropped": 0,
        "dropped_events": 0, "kernels": {},
    }
    snap.update(snapshot or {})
    return {
        "ledger_version": 1, "created_unix": 0.0,
        "env": {"backend": backend},
        "snapshot": snap, "kernels": kernels or [], "events": [],
        "bench": None,
    }


def _one_window_events():
    """A single window span covering the whole wall (keeps host share
    at its unattributed residue only when children fill it)."""
    return [
        _ev("window.x", 0, WALL_US),
        _ev("compute", 0, WALL_US),  # fully attributed: no residue
    ]


# -- the five bound classes ---------------------------------------------------


def test_link_bound():
    # 2.3 MB over a 28 MB/s link ≈ 82 ms of a 100 ms span.
    doc = _doc(snapshot={
        "bytes_h2d": 2_000_000, "bytes_d2h": 300_000,
        "link_probe": {"roundtrip_mbps_p50": 28.0},
    })
    bound = roofline.classify(doc, _one_window_events())
    assert bound["verdict"] == "link-bound"
    assert bound["dominant"] is True
    assert 0.7 < bound["fractions"]["link"] < 1.0
    assert any("probe p50 28.0 MB/s" in e for e in bound["evidence"])


def test_link_share_unknown_without_probe():
    doc = _doc(snapshot={"bytes_h2d": 2_000_000})
    bound = roofline.classify(doc, _one_window_events())
    assert bound["fractions"]["link"] is None
    assert any("no LinkProbe bandwidth gauge" in e
               for e in bound["evidence"])


def test_host_bound():
    # Two windows with a 60 ms gap between them, nothing attributed
    # inside either: 60 ms gap + 40 ms residue = the whole wall.
    events = [
        _ev("window.x", 0, 20_000),
        _ev("window.x", 80_000, 20_000),
    ]
    bound = roofline.classify(_doc(), events)
    assert bound["verdict"] == "host-bound"
    assert bound["dominant"] is True
    assert any("inter-window gaps" in e for e in bound["evidence"])


def test_dispatch_bound_overhead():
    # 80 ms of steady dispatch over 100 calls whose cost-model work is
    # microscopic: per-dispatch overhead, not device work.
    kernels = [_kernel("tiny", calls=101, steady_ms=80.0,
                       flops=1e3, nbytes=1e3)]
    bound = roofline.classify(_doc(kernels=kernels),
                              _one_window_events())
    assert bound["verdict"] == "dispatch-bound"
    assert any("per-dispatch overhead" in e for e in bound["evidence"])


def test_compute_bound():
    # Same 80 ms of dispatch, but the cost model accounts for it with
    # flops (0.8 ms/call ≈ 4e7 flop at the 5e10 flop/s cpu model) and
    # intensity far above the machine balance point.
    kernels = [_kernel("mm", calls=101, steady_ms=80.0,
                       flops=4.0e7, nbytes=1e4)]
    bound = roofline.classify(_doc(kernels=kernels),
                              _one_window_events())
    assert bound["verdict"] == "compute-bound"
    assert any("arithmetic intensity" in e for e in bound["evidence"])


def test_memory_bound():
    # Bytes account for the dispatch time; intensity below balance.
    kernels = [_kernel("scatter", calls=101, steady_ms=80.0,
                       flops=1e4, nbytes=1.6e7)]
    bound = roofline.classify(_doc(kernels=kernels),
                              _one_window_events())
    assert bound["verdict"] == "memory-bound"


def test_inconclusive_without_spans():
    bound = roofline.classify(_doc(), [])
    assert bound["verdict"] == "inconclusive"
    assert bound["wall_us"] is None


def test_weak_dominance_flagged():
    # Every component tiny relative to wall: verdict still names the
    # largest, but says so.
    kernels = [_kernel("k", calls=3, steady_ms=2.0)]
    bound = roofline.classify(_doc(kernels=kernels),
                              _one_window_events())
    assert bound["verdict"] in roofline.BOUND_KINDS
    assert bound["dominant"] is False
    assert any("weak dominance" in e for e in bound["evidence"])


def test_machine_model_override_flips_verdict():
    # The compute-bound fixture becomes overhead-dominated under a
    # 1000x faster machine model: the ridge is configurable.
    kernels = [_kernel("mm", calls=101, steady_ms=80.0,
                       flops=4.0e7, nbytes=1e4)]
    doc = _doc(kernels=kernels)
    assert roofline.classify(doc, _one_window_events())["verdict"] \
        == "compute-bound"
    fast = roofline.classify(doc, _one_window_events(),
                             peak_flops=5e13, peak_bw=2e13)
    assert fast["verdict"] == "dispatch-bound"


def test_per_operator_breakdown():
    events = [
        _ev("window.a", 0, 50_000),
        _ev("ship", 0, 30_000),
        _ev("compute", 30_000, 15_000),
        _ev("window.b", 50_000, 50_000),
        _ev("compute", 50_000, 45_000),
    ]
    bound = roofline.classify(_doc(), events)
    per = bound["per_operator"]
    assert per["window.a"]["verdict"] == "link-bound"
    assert per["window.b"]["verdict"] == "dispatch-bound"
    assert per["window.a"]["phases_us"]["transfer"] == 30_000


def test_link_leaves_nested_or_bare_keep_the_transfer_total():
    """Leaves under their phase spans change no total (top-level children
    only); a bare leaf — a path that fetches outside any ``fetch`` phase
    span — still counts as transfer (it was named ``fetch`` before)."""
    phases_only = [
        _ev("window.a", 0, 50_000),
        _ev("ship", 0, 20_000),
        _ev("compute", 20_000, 10_000),
        _ev("fetch", 30_000, 10_000),
    ]
    with_leaves = phases_only + [
        _ev("h2d", 1_000, 18_000),
        _ev("dispatch:k", 21_000, 8_000),
        _ev("d2h", 31_000, 8_000),
    ]
    want = roofline.classify(_doc(), phases_only)["per_operator"]
    assert roofline.classify(_doc(), with_leaves)["per_operator"] == want
    assert want["window.a"]["phases_us"]["transfer"] == 30_000
    bare = [
        _ev("window.b", 0, 50_000),
        _ev("h2d", 0, 5_000),
        _ev("dispatch:k", 5_000, 10_000),
        _ev("d2h", 15_000, 30_000),
    ]
    per = roofline.classify(_doc(), bare)["per_operator"]["window.b"]
    assert per["phases_us"]["transfer"] == 35_000
    assert per["phases_us"]["compute"] == 10_000
    assert per["verdict"] == "link-bound"


def test_verdict_vocabulary_is_closed():
    # Dashboards and the trend store key on the verdict strings.
    assert set(roofline.BOUND_KINDS) == {
        "link-bound", "host-bound", "dispatch-bound", "compute-bound",
        "memory-bound", "inconclusive",
    }


# -- CLI surfaces -------------------------------------------------------------


def _write(tmp_path, doc, events, name="l.json"):
    doc = dict(doc, events=events)
    p = tmp_path / name
    p.write_text(json.dumps(doc))
    return str(p)


def test_report_prints_verdict_with_evidence_chain(tmp_path, capsys):
    doc = _doc(snapshot={
        "bytes_h2d": 2_000_000, "bytes_d2h": 300_000,
        "link_probe": {"roundtrip_mbps_p50": 28.0},
    })
    path = _write(tmp_path, doc, _one_window_events())
    assert sfprof_main(["report", path]) == 0
    out = capsys.readouterr().out
    assert "roofline bound classification" in out
    assert "verdict: link-bound" in out
    assert "↳" in out  # the sfcheck-style evidence chain


def test_report_json_carries_roofline(tmp_path, capsys):
    doc = _doc(snapshot={
        "bytes_h2d": 2_000_000, "bytes_d2h": 300_000,
        "link_probe": {"roundtrip_mbps_p50": 28.0},
    })
    path = _write(tmp_path, doc, _one_window_events())
    assert sfprof_main(["report", path, "--json"]) == 0
    out = json.loads(capsys.readouterr().out)
    assert out["roofline"]["verdict"] == "link-bound"
    assert out["roofline"]["evidence"]
    assert out["ledger"]["env"]["backend"] == "cpu"
    assert out["attribution"]["operators"]["window.x"]["windows"] == 1


def test_health_json_carries_roofline(tmp_path, capsys):
    doc = _doc(snapshot={
        "bytes_h2d": 2_000_000, "bytes_d2h": 300_000,
        "link_probe": {"roundtrip_mbps_p50": 28.0},
    })
    path = _write(tmp_path, doc, _one_window_events())
    assert sfprof_main(["health", path, "--json"]) == 0
    out = json.loads(capsys.readouterr().out)
    assert out["failed"] == 0
    assert out["roofline"]["verdict"] == "link-bound"
    assert set(out) == {
        "ledger", "schema_problems", "checks", "failed", "roofline", "notes"}
    names = [c["name"] for c in out["checks"]]
    assert "recompile_churn_max_signatures" in names
    # Exit contract unchanged: the human and json paths agree.
    assert sfprof_main(["health", path]) == 0
    human = capsys.readouterr().out
    assert "bound: link-bound" in human


def test_health_json_schema_failure(tmp_path, capsys):
    p = tmp_path / "broken.json"
    p.write_text(json.dumps({"ledger_version": 1}))
    assert sfprof_main(["health", str(p), "--json"]) == 1
    out = json.loads(capsys.readouterr().out)
    assert out["schema_problems"]
    assert out["failed"] == len(out["schema_problems"])
