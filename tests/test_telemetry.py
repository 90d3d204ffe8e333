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


def _inside(child, parent, slack_us=1):
    """Chrome-trace containment (ts and dur are floored to µs apart)."""
    return (child["ts"] >= parent["ts"] - slack_us
            and child["ts"] + child["dur"]
            <= parent["ts"] + parent["dur"] + slack_us)


def _named(name):
    return [e for e in telemetry.events if e["name"] == name]


@pytest.fixture(autouse=True)
def _telemetry_off():
    """Every test starts AND leaves the process-global singleton disabled
    with zero counters (an earlier file of the same xdist worker may have
    left them non-zero: disable() keeps what a run counted), and with the
    event-buffer cap restored (enable() resets counters but deliberately
    not the configured cap — a test shrinking it must not leak that into
    later files)."""
    telemetry.disable()
    telemetry._reset_state()
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
    # The byte-carrying leaf is named "d2h": "fetch" is the operators'
    # phase span, and a sum by name must not count the wait twice.
    (ev,) = [e for e in telemetry.events if e["name"] == "d2h"]
    assert ev["ph"] == "X" and ev["args"]["bytes"] == 2 * 1024 * 4
    assert not [e for e in telemetry.events if e["name"] == "fetch"]


@pytest.mark.parametrize("tree", [
    lambda x: x,
    lambda x: (x, x[:5]),
    lambda x: {"rows": [x, x * 2], "n": 7, "host": np.arange(3)},
], ids=["array", "tuple", "mixed_tree"])
@pytest.mark.parametrize("node", [None, "q9"])
def test_fetch_splits_its_leaf_into_the_wait_and_the_copy(tree, node):
    """Exactly one ``d2h.wait`` inside each ``d2h``: the time until every
    leaf is computed, before the copy. ``d2h`` keeps its name, its bytes and
    its count; disabled, nothing is emitted and the result is the same."""
    x = jnp.arange(1024, dtype=jnp.float32)
    plain = telemetry.fetch(tree(x))
    assert not telemetry.events and telemetry.d2h_transfers == 0
    telemetry.enable()
    with telemetry.scope(node):
        out = [telemetry.fetch(tree(x)) for _ in range(3)]
    leaves = jax.tree_util.tree_leaves
    for o in out:
        assert jax.tree_util.tree_structure(o) \
            == jax.tree_util.tree_structure(plain)
        assert all(np.array_equal(a, b)
                   for a, b in zip(leaves(o), leaves(plain)))
    spans = [e for e in telemetry.events if e["ph"] == "X"]
    d2h = [e for e in spans if e["name"] == "d2h"]
    waits = [e for e in spans if e["name"] == "d2h.wait"]
    assert len(spans) == 6 and len(d2h) == len(waits) == 3
    nbytes = sum(getattr(a, "nbytes", 0) for a in leaves(plain))
    assert telemetry.d2h_transfers == 3 and telemetry.d2h_bytes == 3 * nbytes
    for wait, leaf in zip(waits, d2h):
        assert leaf["args"]["bytes"] == nbytes
        assert wait["ts"] <= leaf["ts"] + leaf["dur"]  # before the copy
        assert "bytes" not in wait.get("args", {})
        assert wait["tid"] == leaf["tid"]
        assert leaf["ts"] <= wait["ts"]
        assert wait["ts"] + wait["dur"] <= leaf["ts"] + leaf["dur"] + 2
        assert wait["dur"] <= leaf["dur"]
        for e in (wait, leaf):
            assert e.get("args", {}).get("node") == node
    # the child is emitted once the leaf has closed, so that its own emit
    # is no part of ``d2h``
    assert [e["name"] for e in spans] == ["d2h", "d2h.wait"] * 3


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
    # exactly one counted d2h transfer — one byte-carrying "d2h" leaf —
    # per "fetch" phase span, each leaf inside its phase span.
    fetch_spans = [e for e in doc["traceEvents"] if e["name"] == "fetch"]
    d2h = [e for e in doc["traceEvents"] if e["name"] == "d2h"]
    assert all("bytes" not in e.get("args", {}) for e in fetch_spans)
    assert telemetry.d2h_transfers == len(fetch_spans) == len(d2h)
    for leaf, phase in zip(d2h, fetch_spans):
        assert _inside(leaf, phase)
    assert sum(e["args"]["bytes"] for e in d2h) == telemetry.d2h_bytes
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


# -- leaf spans at the link's choke points ------------------------------------


def test_ship_emits_one_h2d_leaf_with_the_counted_bytes():
    telemetry.enable()
    before = telemetry.h2d_bytes
    base_mod.ship(np.ones(16, bool), None, np.zeros(16, np.int32))
    (ev,) = _named("h2d")
    assert ev["ph"] == "X" and ev["cat"] == "telemetry"
    assert ev["args"] == {"bytes": 16 + 16 * 4, "arrays": 2}
    assert ev["args"]["bytes"] == telemetry.h2d_bytes - before
    assert telemetry.h2d_transfers == 1


def test_device_q_emits_one_h2d_leaf_with_the_counted_bytes():
    telemetry.enable()
    conf = QueryConfiguration(QueryType.WindowBased, window_size=10,
                              slide_step=5)
    PointPointRangeQuery(conf, GRID).device_q(np.zeros((16, 2)), np.float32)
    (ev,) = _named("h2d")
    assert ev["args"] == {"bytes": 16 * 2 * 4, "arrays": 1}
    assert telemetry.h2d_bytes == 16 * 2 * 4 and telemetry.h2d_transfers == 1


def test_h2d_counts_the_dtype_that_crosses():
    """With x64 off (the chip) a float64 / int64 host array lands as its
    32-bit twin: the counter holds what crossed, not the host's nbytes."""
    telemetry.enable()
    f64, i64 = np.zeros(8, np.float64), np.zeros(8, np.int64)
    base_mod.ship(f64, i64)  # the tests run x64 ON: 8 bytes each
    assert telemetry.h2d_bytes == 2 * 8 * 8
    jax.config.update("jax_enable_x64", False)
    try:
        base_mod.ship(f64, i64, np.zeros(8, np.uint16))
    finally:
        jax.config.update("jax_enable_x64", True)
    assert _named("h2d")[-1]["args"]["bytes"] == 2 * 8 * 4 + 8 * 2


def test_link_sites_hold_the_shared_null_span_when_disabled():
    null = telemetry.span("anything")
    for name in ("h2d", "commit", "commit.egress", "commit.state",
                 "checkpoint.pickle", "checkpoint.write"):
        assert telemetry.span(name) is null
    # ...and the sites themselves leave no trace behind.
    base_mod.ship(np.zeros(4, np.int32))
    f = instrument_jit(jax.jit(lambda x: x + 1), name="off_kernel")
    telemetry.fetch(f(jnp.arange(4)))
    assert telemetry.events == [] and telemetry.kernel_table() == []
    assert telemetry.h2d_transfers == 0 and telemetry.d2h_transfers == 0


def test_instrument_jit_emits_one_dispatch_span_per_call():
    telemetry.enable()
    f = instrument_jit(jax.jit(lambda x: x * 2), name="leaf_kernel")
    f(jnp.arange(4))
    f(jnp.arange(4))
    f(jnp.arange(8))
    spans = _named("dispatch:leaf_kernel")
    assert [e["args"]["new_signature"] for e in spans] == [True, False, True]
    assert all(e["ph"] == "X" for e in spans)
    rows = [r for r in telemetry.kernel_table()
            if r["kernel"] == "leaf_kernel"]
    assert sum(r["calls"] for r in rows) == len(spans) == 3
    # The table's dispatch time IS the spans' (one pair of clock reads).
    assert sum(r["dispatch_ns"] for r in rows) // 1000 >= \
        sum(e["dur"] for e in spans)
    # Same naming as the compile instant.
    assert len(_named("compile:leaf_kernel")) == 2


def test_leaves_carry_the_node_tag():
    telemetry.enable()
    f = instrument_jit(jax.jit(lambda x: x + 1), name="tagged")
    with telemetry.scope("q7"):
        (x,) = base_mod.ship(np.arange(4, dtype=np.int32))
        telemetry.fetch(f(x))
    for name in ("h2d", "dispatch:tagged", "d2h"):
        (ev,) = _named(name)
        assert ev["args"]["node"] == "q7", name


# -- the cyclic GC ------------------------------------------------------------


def test_full_gc_pass_is_one_span_young_passes_only_count():
    import gc

    telemetry.enable()
    full0 = len(_named("gc.full"))
    gc.collect()
    full = _named("gc.full")
    assert len(full) == full0 + 1
    args = full[-1]["args"]
    assert type(args["collected"]) is int
    assert type(args["uncollectable"]) is int
    assert telemetry.gc_full_passes == len(full)
    young0, spans0 = telemetry.gc_young_passes, len(telemetry.events)
    gc.collect(0)
    assert telemetry.gc_young_passes == young0 + 1
    assert len(telemetry.events) == spans0  # no event per young pass
    block = telemetry.snapshot()["gc"]
    assert block["full_passes"] == telemetry.gc_full_passes
    assert block["young_passes"] == telemetry.gc_young_passes
    assert block["full_ns"] >= full[-1]["dur"] * 1000
    assert block["young_ns"] > 0


def test_gc_full_span_names_the_node_it_interrupted():
    import gc

    telemetry.enable()
    with telemetry.scope("staytime"):
        gc.collect()
    assert _named("gc.full")[-1]["args"]["node"] == "staytime"


def test_gc_callback_installed_once_and_removed_on_disable():
    import gc

    n0 = len(gc.callbacks)
    telemetry.enable()
    telemetry.enable()
    assert len(gc.callbacks) == n0 + 1
    telemetry.disable()
    assert len(gc.callbacks) == n0
    gc.collect()  # off: nothing installed, nothing counted
    assert telemetry.gc_full_passes == 0 and "gc" not in telemetry.snapshot()


# -- one clock with the device trace ------------------------------------------


def test_span_lands_on_a_host_plane_of_the_profilers_trace(tmp_path):
    """Under jax.profiler.start_trace a telemetry span is a TraceMe of the
    same name: the .xplane.pb holds it on a host plane, on the profiler's
    clock, beside whatever the device planes hold."""
    import glob

    telemetry.enable()
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 1
    jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
    try:
        f = instrument_jit(jax.jit(lambda x: x + 1), name="on_the_trace")
        with telemetry.span("window.clock_probe"):
            telemetry.fetch(f(jnp.arange(4)))
    finally:
        jax.profiler.stop_trace()
    (path,) = glob.glob(str(tmp_path / "**" / "*.xplane.pb"), recursive=True)
    on_host = {}
    for plane in jax.profiler.ProfileData.from_file(path).planes:
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                for ev in line.events:
                    on_host[ev.name] = (ev.start_ns, ev.duration_ns)
    for name in ("window.clock_probe", "dispatch:on_the_trace", "d2h"):
        assert name in on_host, (name, sorted(on_host)[:40])
    # On the profiler's clock the leaves lie inside the window span, as
    # they do on telemetry's own.
    w0, wd = on_host["window.clock_probe"]
    for name in ("dispatch:on_the_trace", "d2h"):
        t0, d = on_host[name]
        assert w0 <= t0 and t0 + d <= w0 + wd, name
