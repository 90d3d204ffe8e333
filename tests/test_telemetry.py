"""Runtime telemetry tests (telemetry.py): span tracing + Chrome-trace
validity, recompile detection, device-boundary accounting, watermark/late
gauges, metric-registry export, and the disabled-by-default contract.
"""

import json
import warnings

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from spatialflink_tpu.grid import UniformGrid
from spatialflink_tpu.operators import base as base_mod
from spatialflink_tpu.mn.metrics import MetricRegistry
from spatialflink_tpu.models.objects import Point
from spatialflink_tpu.operators import (
    PointPointRangeQuery,
    QueryConfiguration,
    QueryType,
)
from spatialflink_tpu.streams.soa import SoaWindowAssembler
from spatialflink_tpu.streams.windows import (
    TumblingEventTimeWindows,
    WindowAssembler,
)
from spatialflink_tpu.telemetry import (
    RecompileWarning,
    abstract_signature,
    instrument_jit,
    load_trace,
    telemetry,
)

GRID = UniformGrid(20, 0.0, 10.0, 0.0, 10.0)


@pytest.fixture(autouse=True)
def _telemetry_off():
    """Every test leaves the process-global singleton disabled, with the
    event-buffer cap restored (enable() resets counters but deliberately
    not the configured cap — a test shrinking it must not leak that into
    later files)."""
    cap = telemetry.max_events
    yield
    telemetry.max_events = cap
    telemetry.disable()


# -- disabled-by-default contract ---------------------------------------------


def test_disabled_by_default_and_free():
    assert telemetry.enabled is False
    # The disabled span is ONE shared null object — no per-call allocation
    # in operator hot paths while telemetry is off.
    assert telemetry.span("window.x") is telemetry.span("window.y")
    telemetry.account_h2d(4096)
    telemetry.account_d2h(4096)
    telemetry.record_late_drop()
    telemetry.record_watermark_lag(17)
    telemetry.record_jit_call("k", ((4,),))
    assert telemetry.h2d_bytes == 0
    assert telemetry.d2h_bytes == 0
    assert telemetry.late_drops == 0
    assert telemetry.max_watermark_lag_ms == 0
    assert telemetry.compile_count == 0


def test_fetch_passthrough_when_disabled():
    out = telemetry.fetch(jnp.arange(8))
    np.testing.assert_array_equal(np.asarray(out), np.arange(8))
    assert telemetry.d2h_transfers == 0


def test_enable_resets_state():
    telemetry.enable()
    telemetry.account_h2d(100)
    telemetry.record_watermark_lag(9)
    telemetry.enable()
    assert telemetry.h2d_bytes == 0
    assert telemetry.max_watermark_lag_ms == 0


# -- spans / Chrome trace -----------------------------------------------------


def test_spans_nest_and_trace_is_chrome_loadable(tmp_path):
    path = tmp_path / "trace.jsonl"
    telemetry.enable(trace_path=str(path))
    with telemetry.span("window.test", events=3):
        with telemetry.span("assemble"):
            pass
        with telemetry.span("compute"):
            pass
    telemetry.disable()

    doc = load_trace(str(path))
    json.dumps(doc)  # must be valid JSON end to end
    assert set(doc) == {"traceEvents"}
    spans = [e for e in doc["traceEvents"] if e["ph"] == "X"]
    meta = [e for e in doc["traceEvents"] if e["ph"] == "M"]
    assert {m["name"] for m in meta} == {"process_name", "thread_name"}
    evs = {e["name"]: e for e in spans}
    assert set(evs) == {"window.test", "assemble", "compute"}
    for e in spans:
        # Chrome-trace complete events: microsecond ts/dur, pid/tid.
        assert isinstance(e["ts"], int) and isinstance(e["dur"], int)
        assert "pid" in e and "tid" in e
    win = evs["window.test"]
    assert win["args"] == {"events": 3}
    for child in ("assemble", "compute"):
        c = evs[child]
        assert win["ts"] <= c["ts"]
        # +1 µs tolerance for the independent ns→µs floor of ts and dur.
        assert c["ts"] + c["dur"] <= win["ts"] + win["dur"] + 1


def test_window_spans_feed_latency_histogram():
    telemetry.enable()
    with telemetry.span("window.knn"):
        pass
    with telemetry.span("assemble"):  # non-window span: not a latency
        pass
    assert telemetry.window_latency.count == 1
    s = telemetry.summary()
    assert s["window_latency_p50_ms"] is not None
    assert s["window_latency_p95_ms"] is not None


def test_event_buffer_caps_and_counts_drops():
    telemetry.enable()
    telemetry.max_events = 4
    for i in range(6):
        with telemetry.span(f"s{i}"):
            pass
    assert len(telemetry.events) == 4
    assert telemetry.dropped_events == 2


def test_trace_file_roundtrip_and_drop_counter_pinned(tmp_path):
    """The in-memory buffer caps at max_events (drops COUNTED, exported
    in snapshot()); the trace FILE keeps every event — the cap bounds
    memory, not the artifact. load_trace round-trips what _write_trace
    wrote, in emit order."""
    path = tmp_path / "cap.jsonl"
    telemetry.enable(trace_path=str(path))
    telemetry.max_events = 2
    for i in range(5):
        with telemetry.span(f"s{i}"):
            pass
    assert len(telemetry.events) == 2
    assert telemetry.dropped_events == 3
    assert telemetry.snapshot()["dropped_events"] == 3
    telemetry.disable()

    doc = load_trace(str(path))
    json.dumps(doc)
    spans = [e for e in doc["traceEvents"] if e["ph"] == "X"]
    assert [e["name"] for e in spans] == [f"s{i}" for i in range(5)]
    # Buffered events and file events agree where both exist.
    assert spans[:2] == telemetry.events


def test_disable_mid_span_exit_is_silent(tmp_path):
    """A span open across disable() must exit silently (the _emit_span
    early return): no raise — the trace file is already closed — no
    event, no latency observation."""
    telemetry.enable(trace_path=str(tmp_path / "mid.jsonl"))
    sp = telemetry.span("window.mid")
    sp.__enter__()
    telemetry.disable()
    assert sp.__exit__(None, None, None) is False  # and no exception
    assert all(e.get("name") != "window.mid" for e in telemetry.events)
    assert telemetry.window_latency.count == 0


def test_trace_metadata_names_process_and_threads(tmp_path):
    """ph:"M" metadata: process_name once per pid (at enable), thread_name
    once per NEW tid at its first event — so Perfetto rows carry thread
    names instead of raw idents."""
    import threading

    path = tmp_path / "meta.jsonl"
    telemetry.enable(trace_path=str(path))
    with telemetry.span("window.a"):
        pass
    with telemetry.span("window.b"):
        pass

    def emit():
        with telemetry.span("window.worker"):
            pass

    t = threading.Thread(target=emit, name="op-worker")
    t.start()
    t.join()
    telemetry.disable()

    evs = load_trace(str(path))["traceEvents"]
    procs = [e for e in evs if e["ph"] == "M"
             and e["name"] == "process_name"]
    threads = [e for e in evs if e["ph"] == "M"
               and e["name"] == "thread_name"]
    assert len(procs) == 1  # once per pid
    assert procs[0]["args"]["name"].startswith("spatialflink_tpu:")
    assert len(threads) == 2  # once per tid, not per event
    names = {e["tid"]: e["args"]["name"] for e in threads}
    assert "op-worker" in names.values()
    # Each thread_name precedes that tid's first span in file order.
    for tid, _name in names.items():
        first_meta = next(i for i, e in enumerate(evs)
                          if e["ph"] == "M" and e.get("tid") == tid)
        first_span = next(i for i, e in enumerate(evs)
                          if e["ph"] == "X" and e.get("tid") == tid)
        assert first_meta < first_span


def test_account_d2h_emits_counter_event_like_h2d(tmp_path):
    """The counter-event symmetry: account_d2h emits the same ph:"C"
    running-total counter account_h2d does, so device→host traffic is
    visible in Perfetto too (it used to update totals invisibly)."""
    path = tmp_path / "counters.jsonl"
    telemetry.enable(trace_path=str(path))
    telemetry.account_h2d(64)
    telemetry.account_d2h(128)
    telemetry.account_d2h(128)
    telemetry.disable()

    counters = [e for e in load_trace(str(path))["traceEvents"]
                if e["ph"] == "C"]
    h2d = [e["args"]["bytes"] for e in counters
           if e["name"] == "h2d_bytes"]
    d2h = [e["args"]["bytes"] for e in counters
           if e["name"] == "d2h_bytes"]
    assert h2d == [64]
    assert d2h == [128, 256]  # running totals, mirroring h2d


# -- recompile detection ------------------------------------------------------


def test_recompile_detector_two_bucket_sizes_two_events():
    telemetry.enable()
    f = instrument_jit(jax.jit(lambda x: x * 2), name="double")
    f(jnp.ones((64,), jnp.float32))
    f(jnp.ones((64,), jnp.float32))  # same abstract shape → no new event
    assert telemetry.compile_count == 1
    f(jnp.ones((128,), jnp.float32))  # bucket growth → second compile
    assert telemetry.compile_count == 2
    assert telemetry.distinct_shapes("double") == 2
    kernels = [k for k, _ in telemetry.compile_events]
    assert kernels == ["double", "double"]


def test_recompile_threshold_warns_once():
    telemetry.enable(recompile_warn_threshold=3)
    f = instrument_jit(jax.jit(lambda x: x + 1), name="churny")
    with warnings.catch_warnings():
        warnings.simplefilter("error", RecompileWarning)
        f(jnp.ones((8,), jnp.float32))
        f(jnp.ones((16,), jnp.float32))  # below threshold: silent
    with pytest.warns(RecompileWarning, match="churny"):
        f(jnp.ones((32,), jnp.float32))  # crosses threshold
    with warnings.catch_warnings():
        warnings.simplefilter("error", RecompileWarning)
        f(jnp.ones((64,), jnp.float32))  # warned already: once per kernel


def test_recompile_detector_sees_tuple_arg_shape_churn():
    """Container args recurse: the knn pane digests arrive as tuples of
    arrays, and repadding every element to a grown nseg is a REAL jit
    recompile — a signature that collapsed tuples to 'tuple' would record
    one compile forever and the detector would miss its primary target."""
    telemetry.enable()
    f = instrument_jit(
        jax.jit(lambda xs, bases: sum(xs) + bases), name="merge"
    )
    small = tuple(jnp.ones((64,), jnp.float32) for _ in range(2))
    grown = tuple(jnp.ones((128,), jnp.float32) for _ in range(2))
    bases = jnp.zeros((), jnp.float32)
    f(small, bases)
    f(small, bases)  # same leaf avals → no new event
    assert telemetry.compile_count == 1
    f(grown, bases)  # every tuple element repadded → second compile
    assert telemetry.compile_count == 2
    assert telemetry.distinct_shapes("merge") == 2


def test_abstract_signature_statics_and_dtypes():
    a64 = np.zeros((4, 2), np.float32)
    assert abstract_signature((a64,)) == abstract_signature(
        (np.ones((4, 2), np.float32),)
    )  # values don't key the cache, avals do
    assert abstract_signature((a64,)) != abstract_signature(
        (np.zeros((4, 2), np.float64),)
    )  # dtype does
    # kwargs are static arguments: the VALUE keys the compile cache.
    assert abstract_signature((), {"k": 5}) != abstract_signature(
        (), {"k": 6}
    )
    # kwarg CONTAINERS of arrays contribute avals, not repr — repr
    # would materialize the arrays (a device fetch per call; the pane
    # scan's lps_expire tuples hit this)
    t1 = (np.zeros((8, 4), np.int32), np.zeros((8, 4), bool))
    t2 = (np.ones((8, 4), np.int32), np.ones((8, 4), bool))
    assert abstract_signature((), {"e": t1}) == abstract_signature(
        (), {"e": t2}
    )  # same avals, different values → one compile
    assert abstract_signature((), {"e": t1}) != abstract_signature(
        (), {"e": (np.zeros((4, 4), np.int32), np.zeros((4, 4), bool))}
    )


def test_instrument_jit_passes_attributes_through():
    jf = jax.jit(lambda x: x + 1)
    f = instrument_jit(jf, name="attrs")
    # Equality, not identity: every attribute access on the jitted
    # callable builds a fresh bound-method object.
    assert f.lower == jf.lower


# -- device-boundary accounting -----------------------------------------------


def test_fetch_accounts_bytes_and_emits_event():
    telemetry.enable()
    x = jnp.arange(1024, dtype=jnp.float32)
    out = telemetry.fetch((x, x))
    np.testing.assert_array_equal(out[0], np.arange(1024, dtype=np.float32))
    assert telemetry.d2h_transfers == 1
    assert telemetry.d2h_bytes == 2 * 1024 * 4
    (ev,) = [e for e in telemetry.events if e["name"] == "fetch"]
    assert ev["args"]["bytes"] == 2 * 1024 * 4


def test_operator_ship_path_accounts_h2d():
    telemetry.enable()
    conf = QueryConfiguration(QueryType.WindowBased, window_size=10,
                              slide_step=5)
    op = PointPointRangeQuery(conf, GRID)
    op.device_q(np.zeros((16, 2)), np.float32)
    assert telemetry.h2d_transfers == 1
    assert telemetry.h2d_bytes == 16 * 2 * 4  # float32 after centering cast
    # Batch-metadata lanes (valid/cell/oid) count too — the AoS window
    # paths ship them alongside the coordinates.
    base_mod.ship(np.ones(16, bool), np.zeros(16, np.int32))
    assert telemetry.h2d_bytes == 16 * 2 * 4 + 16 + 16 * 4


# -- watermark / lateness gauges ----------------------------------------------


def _soa_chunk(*ts):
    a = np.asarray(ts, np.int64)
    return {
        "ts": a,
        "x": np.zeros(len(a)),
        "y": np.zeros(len(a)),
        "oid": np.zeros(len(a), np.int32),
    }


def test_soa_assembler_feeds_gauges():
    telemetry.enable()
    asm = SoaWindowAssembler(10, 5)
    asm.feed(_soa_chunk(1, 3, 9))
    asm.feed(_soa_chunk(27))  # fires [0,10) at wm=27 → lag 17
    assert telemetry.max_watermark_lag_ms == 17
    asm.feed(_soa_chunk(2))  # older than every live window
    asm.feed(_soa_chunk(38))  # next consolidation trims+counts the drop
    assert asm.dropped_late == 1
    assert telemetry.late_drops == 1
    assert telemetry.max_watermark_lag_ms == 17
    # flush()'s artificial end-of-stream watermark must not spike the lag
    # gauge.
    asm.flush()
    assert telemetry.max_watermark_lag_ms == 17


def test_object_assembler_feeds_gauges():
    telemetry.enable()
    asm = WindowAssembler(
        TumblingEventTimeWindows(10), timestamp_fn=lambda e: e.timestamp
    )
    asm.feed(Point(obj_id="a", timestamp=1, x=0.0, y=0.0))
    fired = asm.feed(Point(obj_id="a", timestamp=25, x=0.0, y=0.0))
    assert len(fired) == 1  # [0,10) fired at wm=25
    assert telemetry.max_watermark_lag_ms == 15
    asm.feed(Point(obj_id="a", timestamp=2, x=0.0, y=0.0))  # dropped late
    assert telemetry.late_drops == 1


# -- telemetry must never change results --------------------------------------


def test_range_query_results_identical_with_telemetry(rng, tmp_path):
    conf = QueryConfiguration(QueryType.WindowBased, window_size=10,
                              slide_step=5)
    pts = [
        Point(obj_id=f"d{i % 7}", timestamp=int(i * 75),
              x=float(rng.uniform(0, 10)), y=float(rng.uniform(0, 10)))
        for i in range(400)
    ]
    q = Point(x=5.0, y=5.0)

    def run():
        return [
            (r.start, r.end, sorted(id(o) for o in r.objects))
            for r in PointPointRangeQuery(conf, GRID).run(iter(pts), [q], 2.0)
        ]

    baseline = run()
    telemetry.enable(trace_path=str(tmp_path / "range_trace.jsonl"))
    instrumented = run()
    telemetry.disable()
    assert instrumented == baseline

    # The per-window phase spans landed, nested under window.range, and
    # the trace is loadable.
    doc = load_trace(str(tmp_path / "range_trace.jsonl"))
    names = [e["name"] for e in doc["traceEvents"]]
    assert "window.range" in names
    for phase in ("assemble", "ship", "compute", "fetch"):
        assert phase in names, phase
    assert telemetry.window_latency.count == names.count("window.range")
    # Instrumentation rides the operator's own fetches, never adds one:
    # exactly one counted d2h transfer per "fetch" phase span (the byte-
    # carrying fetch events and the phase spans share the name; tell them
    # apart by the args payload).
    fetch_spans = [e for e in doc["traceEvents"]
                   if e["name"] == "fetch" and "bytes" not in e.get("args", {})]
    assert telemetry.d2h_transfers == len(fetch_spans)
    assert telemetry.h2d_bytes > 0 and telemetry.d2h_bytes > 0


# -- export ------------------------------------------------------------------


def test_summary_is_json_safe_and_has_bench_fields():
    telemetry.enable()
    telemetry.account_h2d(np.int64(4096))  # numpy scalars at the boundary
    telemetry.record_watermark_lag(np.int32(12))
    s = telemetry.summary()
    json.dumps(s)  # must never raise
    assert set(s) >= {
        "compiles", "bytes_h2d", "bytes_d2h", "window_latency_p50_ms",
        "window_latency_p95_ms", "max_watermark_lag_ms", "late_dropped",
    }
    assert type(s["bytes_h2d"]) is int and s["bytes_h2d"] == 4096
    assert s["max_watermark_lag_ms"] == 12
    # Empty histogram percentiles export as None, not NaN (strict JSON).
    assert s["window_latency_p50_ms"] is None
    assert "NaN" not in json.dumps(s)
    json.dumps(telemetry.snapshot())


def test_register_metrics_exports_gauges():
    telemetry.enable()
    telemetry.record_watermark_lag(33)
    telemetry.record_late_drop(2)
    telemetry.account_h2d(128)
    reg = MetricRegistry()
    telemetry.register_metrics(reg)
    snap = reg.snapshot()
    assert snap["watermark_lag_ms_max"] == 33
    assert snap["late_dropped_total"] == 2
    assert snap["h2d_bytes_total"] == 128
    json.dumps(snap)


def test_reporter_line_gains_telemetry_columns(tmp_path):
    from spatialflink_tpu.mn import MetricRegistry, NESFileReporter

    telemetry.enable()
    telemetry.record_watermark_lag(21)
    telemetry.record_late_drop(3)
    rep = NESFileReporter(MetricRegistry(), "qtel", out_dir=str(tmp_path))
    line = rep.report(now=1_700_000_000.0)
    assert "watermark_lag_ms_max=21" in line
    assert "late_dropped_total=3" in line
    assert "compiles_total=0" in line
    telemetry.disable()
    # Off → the reference's exact column set, no telemetry columns.
    line = rep.report(now=1_700_000_001.0)
    assert "watermark_lag_ms_max" not in line
