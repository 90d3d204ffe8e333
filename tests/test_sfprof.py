"""sfprof tests — the per-kernel runtime table + lazy cost capture
(telemetry side), the run-ledger schema, span attribution, and the CLI
contracts (report / diff --gate / health exit codes)."""

import json
import threading

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from spatialflink_tpu.telemetry import (
    LEDGER_VERSION,
    instrument_jit,
    telemetry,
)
from tools.sfprof import attribution
from tools.sfprof import ledger as ledger_mod
from tools.sfprof.cli import compare, main as sfprof_main


@pytest.fixture(autouse=True)
def _telemetry_off():
    """Leave the process-global singleton disabled AND reset: this file
    runs before test_telemetry.py, whose disabled-by-default test asserts
    the pristine zero counters (disable() alone keeps state readable)."""
    cap = telemetry.max_events
    yield
    telemetry.max_events = cap
    telemetry.enable()  # enable() resets all state...
    telemetry.disable()  # ...and leave it off for the next test


# -- per-kernel runtime table -------------------------------------------------


def test_kernel_table_counts_dispatch_and_first_call():
    telemetry.enable()
    f = instrument_jit(jax.jit(lambda x: x * 2 + 1), name="twice")
    f(jnp.ones((64,), jnp.float32))
    f(jnp.ones((64,), jnp.float32))
    f(jnp.ones((128,), jnp.float32))
    rows = telemetry.kernel_table()
    assert len(rows) == 2  # one row per (kernel, signature)
    (r64,) = [r for r in rows if "(64,)" in r["signature"]]
    (r128,) = [r for r in rows if "(128,)" in r["signature"]]
    assert r64["kernel"] == "twice" and r64["calls"] == 2
    assert r128["calls"] == 1
    # First call includes the XLA compile; cumulative >= first > 0.
    assert r64["dispatch_ns"] >= r64["first_call_ns"] > 0
    assert r64["cost"] is None  # lazy — nothing captured on the hot path
    json.dumps(rows)  # JSON-safe as exported


def test_disabled_is_a_noop():
    telemetry.enable()
    telemetry.disable()  # enable() resets state; leave it clean AND off
    f = instrument_jit(jax.jit(lambda x: x + 1), name="off")
    f(jnp.ones((8,), jnp.float32))
    assert telemetry.kernel_table() == []
    telemetry.capture_costs()  # no state, no raise
    assert telemetry.kernel_table() == []


def test_cost_capture_flops_bytes_zero_device_round_trips():
    telemetry.enable()
    f = instrument_jit(jax.jit(lambda x: (x @ x).sum()), name="mm")
    f(jnp.ones((32, 32), jnp.float32))
    h2d, d2h = telemetry.h2d_transfers, telemetry.d2h_transfers
    # AOT lower/compile from stashed avals: any implicit transfer in
    # either direction would trip the guard.
    with jax.transfer_guard("disallow"):
        telemetry.capture_costs()
    (row,) = telemetry.kernel_table()
    cost = row["cost"]
    assert "error" not in cost
    assert cost["flops"] > 0  # XLA:CPU cost analysis delivers flops
    assert cost["bytes_accessed"] > 0
    assert cost["peak_memory_bytes"] > 0
    assert telemetry.h2d_transfers == h2d
    assert telemetry.d2h_transfers == d2h
    telemetry.capture_costs()  # idempotent: costs captured once
    (row2,) = telemetry.kernel_table()
    assert row2["cost"] == cost


def test_cost_capture_through_jitted_statics():
    """operators/base.py:jitted routes statics as kwargs via partial —
    the deferred lowering must replay them as static values, arrays as
    avals."""
    from spatialflink_tpu.operators.base import jitted

    telemetry.enable()

    def scaled_sum(x, *, k):
        return (x * k).sum()

    f = jitted(scaled_sum, "k")
    f(jnp.ones((16,), jnp.float32), k=3)
    telemetry.capture_costs()
    (row,) = [r for r in telemetry.kernel_table()
              if r["kernel"] == "scaled_sum"]
    assert "error" not in row["cost"]
    assert row["cost"]["flops"] > 0


def test_cost_capture_namedtuple_args():
    """Pane-scan kernels take NamedTuple carries positionally; the
    deferred-lowering aval mirror must rebuild them via the positional
    ctor (a NamedTuple rejects the single-iterable tuple ctor), or cost
    capture silently dies for exactly the flagship kernels."""
    from typing import NamedTuple

    class Carry(NamedTuple):
        seg: object
        rep: object

    telemetry.enable()

    def step(carry, x):
        return Carry(carry.seg + x.sum(), carry.rep), x * 2

    f = instrument_jit(jax.jit(step), name="nt_step")
    c = Carry(jnp.float32(0.0), jnp.int32(0))
    f(c, jnp.ones((16,), jnp.float32))
    with jax.transfer_guard("disallow"):
        telemetry.capture_costs()
    (row,) = [r for r in telemetry.kernel_table()
              if r["kernel"] == "nt_step"]
    assert row["cost"] and "error" not in row["cost"]
    assert row["cost"]["flops"] > 0


def test_cost_capture_dict_args_and_no_buffer_pinning():
    """Dict-of-array args recurse to avals like tuples do; an arbitrary
    object that could hide a device buffer makes _lower_ctx give up
    (cost honestly unavailable) instead of pinning it in the table."""
    telemetry.enable()
    f = instrument_jit(jax.jit(lambda d: d["x"] * 2 + d["y"]),
                       name="dicty")
    f({"x": jnp.ones((16,), jnp.float32),
       "y": jnp.ones((16,), jnp.float32)})
    with jax.transfer_guard("disallow"):
        telemetry.capture_costs()
    (row,) = [r for r in telemetry.kernel_table()
              if r["kernel"] == "dicty"]
    assert row["cost"] and "error" not in row["cost"]

    from spatialflink_tpu.telemetry import _lower_ctx

    class Opaque:
        pass

    jf = jax.jit(lambda x: x)
    assert _lower_ctx(jf, (Opaque(),), {}) is None


def test_uninstrumentable_callable_records_error_not_crash():
    telemetry.enable()
    f = instrument_jit(lambda x: np.asarray(x) + 1, name="plain")
    f(np.ones(4, np.float32))
    telemetry.capture_costs()
    (row,) = telemetry.kernel_table()
    # A plain callable has no AOT surface: cost stays honest-unavailable.
    assert row["cost"] is None or "error" in row["cost"]


# -- run ledger ---------------------------------------------------------------


def _make_ledger(tmp_path, name="ledger.json", bench=None):
    telemetry.enable()
    f = instrument_jit(jax.jit(lambda x: x * 2), name="double")
    with telemetry.span("window.demo", window=0):
        with telemetry.span("assemble"):
            pass
        with telemetry.span("ship"):
            pass
        with telemetry.span("compute"):
            f(jnp.ones((64,), jnp.float32))
        with telemetry.span("fetch"):
            telemetry.fetch(jnp.ones((64,), jnp.float32))
    if bench is None:
        bench = {
            "config": "continuous_knn_k50_5s_sliding",
            "points_per_sec": 70_000_000.0,
            "device_resident_points_per_sec": 100_000_000.0,
            "value": 70_000_000.0,
        }
    path = str(tmp_path / name)
    telemetry.write_ledger(path, bench=bench)
    telemetry.disable()
    return path


def test_ledger_version_constants_in_sync():
    """Writer (telemetry) and validator (tools/sfprof) deliberately don't
    import each other — this is the cross-pin both files point at."""
    assert ledger_mod.LEDGER_VERSION == LEDGER_VERSION


def test_ledger_schema_valid_and_complete(tmp_path):
    path = _make_ledger(tmp_path)
    doc = ledger_mod.load(path)
    assert ledger_mod.validate(doc) == []
    assert doc["ledger_version"] == LEDGER_VERSION
    assert doc["env"]["backend"] == "cpu"
    assert doc["env"]["jax"] == jax.__version__
    assert doc["snapshot"]["bytes_d2h"] > 0
    names = [e["name"] for e in doc["events"]]
    assert "window.demo" in names
    (row,) = [r for r in doc["kernels"] if r["kernel"] == "double"]
    # write_ledger captured costs lazily on the way out.
    assert row["cost"] and row["cost"].get("flops", 0) > 0


def test_validate_flags_broken_documents(tmp_path):
    path = _make_ledger(tmp_path)
    doc = ledger_mod.load(path)

    missing = {k: v for k, v in doc.items() if k != "snapshot"}
    assert any("snapshot" in p for p in ledger_mod.validate(missing))

    wrong_ver = dict(doc, ledger_version=LEDGER_VERSION + 1)
    assert any("ledger_version" in p
               for p in ledger_mod.validate(wrong_ver))

    # The fstring-numpy bug class: a numpy scalar repr in a string field.
    leaked = dict(doc, bench={"note": "rate was np.float32(1234.5)"})
    assert any("numpy scalar repr" in p
               for p in ledger_mod.validate(leaked))

    assert ledger_mod.validate([1, 2]) == ["ledger is not a JSON object"]


def test_write_ledger_sanitizes_nonfinite(tmp_path):
    """Regression: a NaN/Inf in the bench record used to raise out of
    write_ledger (allow_nan=False) at the very END of a run — losing the
    whole capture. Non-finite floats now become null, counted in the
    ``nonfinite_values`` warning field, and the document stays
    schema-valid."""
    telemetry.enable()
    path = telemetry.write_ledger(
        str(tmp_path / "nan.json"),
        bench={"value": float("nan"), "rate": float("inf"),
               "series": [1.0, float("-inf"), 3.0], "fine": 7.0},
    )
    doc = ledger_mod.load(path)
    assert ledger_mod.validate(doc) == []
    assert doc["bench"]["value"] is None
    assert doc["bench"]["rate"] is None
    assert doc["bench"]["series"] == [1.0, None, 3.0]
    assert doc["bench"]["fine"] == 7.0
    assert doc["nonfinite_values"] == 3
    # A clean ledger carries no warning field at all.
    clean = ledger_mod.load(_make_ledger(tmp_path, name="clean.json"))
    assert "nonfinite_values" not in clean


def test_load_any_accepts_trace_shapes(tmp_path):
    # JSON-lines trace (the SFT_TRACE_PATH format).
    jl = tmp_path / "t.jsonl"
    evs = [{"name": "window.x", "ph": "X", "ts": 0, "dur": 5,
            "pid": 1, "tid": 1},
           {"name": "compute", "ph": "X", "ts": 1, "dur": 3,
            "pid": 1, "tid": 1}]
    jl.write_text("".join(json.dumps(e) + "\n" for e in evs))
    doc, events = ledger_mod.load_any(str(jl))
    assert doc is None and len(events) == 2
    # {"traceEvents": [...]} document.
    td = tmp_path / "t.json"
    td.write_text(json.dumps({"traceEvents": evs}))
    doc, events = ledger_mod.load_any(str(td))
    assert doc is None and len(events) == 2
    # Ledger.
    lp = _make_ledger(tmp_path)
    doc, events = ledger_mod.load_any(lp)
    assert doc is not None and events == doc["events"]


# -- span attribution ---------------------------------------------------------


def _ev(name, ts, dur, tid=1):
    return {"name": name, "ph": "X", "ts": ts, "dur": dur,
            "pid": 7, "tid": tid}


def test_attribution_phases_residue_and_nesting():
    events = [
        _ev("window.knn", 0, 100),
        _ev("assemble", 0, 30),
        _ev("compute", 30, 50),
        _ev("pane.digest", 35, 10),  # nested in compute: not re-counted
        _ev("fetch", 85, 10),
    ]
    windows, ops = attribution.attribute_windows(events)
    (w,) = windows
    assert w["operator"] == "window.knn"
    assert w["phases"] == {"assemble": 30, "compute": 50, "fetch": 10}
    assert w["unattributed_us"] == 10  # 80..85 — reported, never silent
    assert w["attributed_frac"] == pytest.approx(0.9)
    agg = ops["window.knn"]
    assert agg["windows"] == 1 and agg["dur_us"] == 100
    assert (sum(agg["phases"].values()) + agg["unattributed_us"]
            == agg["dur_us"])


def test_attribution_separates_threads_and_windows():
    events = [
        _ev("window.a", 0, 50, tid=1),
        _ev("compute", 0, 50, tid=1),
        _ev("window.a", 100, 50, tid=1),
        _ev("compute", 100, 25, tid=1),
        # Same ts range on ANOTHER thread: not a child of tid=1 windows.
        _ev("compute", 0, 40, tid=2),
    ]
    windows, ops = attribution.attribute_windows(events)
    assert len(windows) == 2
    assert ops["window.a"]["windows"] == 2
    assert ops["window.a"]["phases"]["compute"] == 75
    assert ops["window.a"]["unattributed_us"] == 25


def test_host_gap_detection():
    events = [
        _ev("window.a", 0, 50),
        _ev("window.a", 90, 50),   # 40 µs host gap
        _ev("window.a", 141, 50),  # 1 µs gap
    ]
    gaps = attribution.host_gaps(events)
    assert [g["gap_us"] for g in gaps] == [40, 1]
    assert gaps[0]["after"] == "window.a"


# -- CLI: report --------------------------------------------------------------


def test_report_cli_on_ledger(tmp_path, capsys):
    path = _make_ledger(tmp_path)
    assert sfprof_main(["report", path]) == 0
    out = capsys.readouterr().out
    assert "window.demo" in out
    for phase in ("assemble", "ship", "compute", "fetch"):
        assert phase in out
    assert "unattributed" in out  # the residue is always reported
    assert "double" in out  # kernel table rendered
    assert "np." not in out  # egress stays numpy-repr-free


def test_report_cli_on_raw_trace(tmp_path, capsys):
    jl = tmp_path / "t.jsonl"
    jl.write_text(json.dumps(_ev("window.x", 0, 10)) + "\n"
                  + json.dumps(_ev("compute", 0, 9)) + "\n")
    assert sfprof_main(["report", str(jl)]) == 0
    out = capsys.readouterr().out
    assert "window.x" in out and "compute" in out


def test_report_cli_unreadable_input(tmp_path, capsys):
    assert sfprof_main(["report", str(tmp_path / "absent.json")]) == 2


def test_report_and_health_json_on_real_ledger(tmp_path, capsys):
    """--json on a ledger telemetry actually wrote (not a synthetic
    fixture): parseable single document, roofline verdict present,
    checks mirrored, exit codes unchanged."""
    path = _make_ledger(tmp_path)
    assert sfprof_main(["report", path, "--json"]) == 0
    rep = json.loads(capsys.readouterr().out)
    assert rep["roofline"]["verdict"] in (
        "link-bound", "host-bound", "dispatch-bound", "compute-bound",
        "memory-bound", "inconclusive")
    assert rep["roofline"]["evidence"]
    assert "window.demo" in rep["attribution"]["operators"]
    assert any(r["kernel"] == "double" for r in rep["kernels"])
    assert sfprof_main(["health", path, "--json"]) == 0
    hea = json.loads(capsys.readouterr().out)
    assert hea["failed"] == 0
    assert set(hea) == {
        "ledger", "schema_problems", "checks", "failed", "roofline", "notes"}
    assert hea["roofline"]["verdict"] == rep["roofline"]["verdict"]
    assert {c["name"] for c in hea["checks"]} >= {
        "recompile_churn_max_signatures", "late_dropped",
        "max_watermark_lag_ms", "dropped_trace_events"}


# -- CLI: diff / gate ---------------------------------------------------------


def test_diff_gate_self_diff_exits_zero(tmp_path):
    path = _make_ledger(tmp_path)
    assert sfprof_main(["diff", path, path, "--gate"]) == 0


def test_diff_gate_flags_injected_eps_regression(tmp_path, capsys):
    path = _make_ledger(tmp_path)
    doc = ledger_mod.load(path)
    bad = dict(doc)
    bad["bench"] = dict(doc["bench"])
    bad["bench"]["points_per_sec"] = doc["bench"]["points_per_sec"] / 10
    bad["bench"]["value"] = doc["bench"]["value"] / 10
    bad_path = tmp_path / "bad.json"
    bad_path.write_text(json.dumps(bad))

    assert sfprof_main(["diff", path, str(bad_path), "--gate"]) == 1
    out = capsys.readouterr().out
    assert "regression" in out
    # Without --gate the same diff is informational: exit 0.
    assert sfprof_main(["diff", path, str(bad_path)]) == 0
    # Inside the ±50% band: not a regression.
    near = dict(doc)
    near["bench"] = dict(doc["bench"],
                         points_per_sec=doc["bench"]["points_per_sec"] * 0.7,
                         value=doc["bench"]["value"] * 0.7)
    near_path = tmp_path / "near.json"
    near_path.write_text(json.dumps(near))
    assert sfprof_main(["diff", path, str(near_path), "--gate"]) == 0


def test_diff_latency_and_counter_bands(tmp_path):
    path = _make_ledger(tmp_path)
    doc = ledger_mod.load(path)
    bad = dict(doc)
    bad["snapshot"] = dict(doc["snapshot"])
    bad["snapshot"]["window_latency_p50_ms"] = 1e6  # far past 2x + 1ms
    bad["snapshot"]["dropped_events"] = 99  # any increase regresses
    bad_path = tmp_path / "slow.json"
    bad_path.write_text(json.dumps(bad))
    rows = compare(doc, ledger_mod.load(str(bad_path)),
                   eps_tol=0.5, lat_tol=1.0)
    verdicts = {r["name"]: r["verdict"] for r in rows}
    assert verdicts["snapshot.window_latency_p50_ms"] == "regression"
    assert verdicts["snapshot.dropped_events"] == "regression"
    assert sfprof_main(["diff", path, str(bad_path), "--gate"]) == 1


def test_diff_gate_fails_when_candidate_loses_a_metric(tmp_path):
    """A gateable metric the candidate ledger LOST entirely (broken
    telemetry, truncated bench block) must gate as a regression — the
    gate cannot pass on silence. Metrics new in B stay informational."""
    path = _make_ledger(tmp_path)
    doc = ledger_mod.load(path)
    lost = json.loads(json.dumps(doc))
    del lost["bench"]["points_per_sec"]
    lost_path = tmp_path / "lost.json"
    lost_path.write_text(json.dumps(lost))
    assert sfprof_main(["diff", path, str(lost_path), "--gate"]) == 1
    # The reverse direction — B gained a metric A lacks — is fine.
    assert sfprof_main(["diff", str(lost_path), path, "--gate"]) == 0


def test_diff_link_annotation_never_gates(tmp_path, capsys):
    """Link-probe gauges ANNOTATE a diff (link degraded vs chip slow)
    but never gate it, and never widen the bands: two ledgers identical
    except for a 2x-degraded link must still self-diff clean — with the
    degradation called out in the output."""
    path = _make_ledger(tmp_path)
    doc = ledger_mod.load(path)
    for name, bw in (("fast.json", 28.0), ("slow_link.json", 11.0)):
        d = json.loads(json.dumps(doc))
        d["snapshot"]["link_probe"] = {
            "samples": 8, "latency_ms_p50": 1.0, "latency_ms_last": 1.0,
            "roundtrip_mbps_p50": bw, "roundtrip_mbps_last": bw,
            "payload_bytes": 262144,
        }
        (tmp_path / name).write_text(json.dumps(d))
    fast, slow = str(tmp_path / "fast.json"), str(tmp_path / "slow_link.json")
    assert sfprof_main(["diff", fast, slow, "--gate"]) == 0  # not gated
    out = capsys.readouterr().out
    assert "DEGRADED" in out and "link" in out
    assert sfprof_main(["diff", fast, fast, "--gate"]) == 0
    assert "comparable links" in capsys.readouterr().out


# -- CLI: health --------------------------------------------------------------


def test_health_clean_ledger_exits_zero(tmp_path, capsys):
    path = _make_ledger(tmp_path)
    assert sfprof_main(["health", path]) == 0
    out = capsys.readouterr().out
    assert "0 failed" in out


def test_health_flags_each_pathology(tmp_path):
    path = _make_ledger(tmp_path)
    doc = ledger_mod.load(path)

    def write(mut, name):
        bad = json.loads(json.dumps(doc))
        mut(bad)
        p = tmp_path / name
        p.write_text(json.dumps(bad))
        return str(p)

    churn = write(lambda d: d["snapshot"]["kernels"].update(spin=64),
                  "churn.json")
    assert sfprof_main(["health", churn]) == 1
    dropped = write(lambda d: d["snapshot"].update(dropped_events=7),
                    "dropped.json")
    assert sfprof_main(["health", dropped]) == 1
    late = write(lambda d: d["snapshot"].update(late_dropped=3),
                 "late.json")
    assert sfprof_main(["health", late]) == 1
    lag = write(lambda d: d["snapshot"].update(max_watermark_lag_ms=99_999),
                "lag.json")
    assert sfprof_main(["health", lag]) == 1
    over = write(lambda d: d["bench"].update(cmp_overflow=2), "over.json")
    assert sfprof_main(["health", over]) == 1
    # Thresholds are arguments: the same churn passes a higher bar.
    assert sfprof_main(["health", churn,
                        "--recompile-threshold", "100"]) == 0
    # An invalid document fails health outright.
    broken = write(lambda d: d.pop("kernels"), "broken.json")
    assert sfprof_main(["health", broken]) == 1


# -- instrumentation must not leak across threads -----------------------------


def test_kernel_table_thread_safe_updates():
    telemetry.enable()
    f = instrument_jit(jax.jit(lambda x: x + 1), name="mt")
    x = jnp.ones((32,), jnp.float32)
    f(x)  # compile once before the race

    def worker():
        for _ in range(50):
            f(x)

    threads = [threading.Thread(target=worker) for _ in range(4)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    (row,) = telemetry.kernel_table()
    assert row["calls"] == 1 + 4 * 50


def test_report_and_health_collective_split(tmp_path, capsys):
    """The per-kind collective classes (halo vs gather vs reduce) and
    the replication-ratio line (collective bytes / boundary-state
    bytes the halo wrappers declared) — text, --json, and health
    notes all carry the same split."""
    telemetry.enable()
    telemetry.account_collective("ppermute", 6_000, axis="data", calls=6)
    telemetry.account_collective("all_gather", 80_000, axis="data",
                                 calls=4)
    telemetry.account_collective("psum", 64, axis="data", calls=2)
    telemetry.account_halo_state(3_000)
    path = str(tmp_path / "halo_ledger.json")
    telemetry.write_ledger(path, bench={
        "config": "range_8shard_halo", "points_per_sec": 50_000.0,
        "value": 50_000.0,
    })
    telemetry.disable()

    assert sfprof_main(["report", path]) == 0
    out = capsys.readouterr().out
    assert "by class" in out
    assert "halo=" in out and "gather=" in out and "reduce=" in out
    assert "replication ratio" in out
    assert "boundary-pane state" in out  # the ↳ evidence line

    assert sfprof_main(["report", path, "--json"]) == 0
    rep = json.loads(capsys.readouterr().out)
    split = rep["collective_split"]
    assert split["by_class"]["halo"]["bytes"] == 6_000
    assert split["by_class"]["halo"]["kinds"] == ["ppermute"]
    assert split["by_class"]["gather"]["bytes"] == 80_000
    assert split["by_class"]["reduce"]["bytes"] == 64
    assert split["halo_state_bytes"] == 3_000
    assert split["replication_ratio"] == pytest.approx(
        (6_000 + 80_000 + 64) / 3_000)

    assert sfprof_main(["health", path, "--json"]) == 0
    hea = json.loads(capsys.readouterr().out)
    assert hea["notes"]["collective_split"]["by_class"]["halo"][
        "bytes"] == 6_000
