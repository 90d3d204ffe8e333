"""Runtime telemetry: spans, device-boundary accounting, recompile
detection, watermark-lag gauges.

The reference instruments its pipelines with Flink/NES metrics (``com/mn/``);
those host-side counters are ported in ``mn/``. This module adds the layer
the JVM build never needed: visibility at the HOST↔DEVICE boundary, where
every perf pathology this codebase has hit lives —

- per-window eager-op recompiles (seconds each) → the recompile
  detector keyed by (kernel, abstract shape signature);
- transfers over the host↔device link → host→device / device→host byte
  accounting at the batch-shipping entry points (``operators/base.py``);
- syncs nobody accounted → the ``fetch`` helper: ONE place where the
  host waits for the device, timed and byte-counted via a real
  ``jax.device_get`` (a result the host never read is not a result a
  window can commit);
- windows firing late / events dropped → watermark-lag and late-drop
  gauges fed by the ``streams/`` assemblers.

Contract: **disabled by default and free when disabled** (operator hot
paths do one ``telemetry.enabled`` attribute check per window, nothing
per event); when enabled, instrumentation adds **zero device round trips**
beyond the operator's own fetches — byte accounting reads host-array
``nbytes`` before shipping, and ``fetch`` REPLACES (never duplicates) the
operator's existing device→host materialization.

Spans emit Chrome-trace/Perfetto-compatible complete events ("ph": "X",
microsecond ts/dur) as JSON-lines; ``load_trace`` wraps a trace file into
the standard ``{"traceEvents": [...]}`` document. Spans named
``window.*`` additionally feed a ``FixedBucketLatency`` histogram, so
p50/p95 window latency lands in NES reporter lines and ``summary()``.

On top of the raw signals sits the **run ledger** (``write_ledger``): a
per-(kernel, signature) runtime table fed by ``instrument_jit`` (call
count, cumulative dispatch wall-ns, first-call compile-inclusive
latency) plus lazy host-side XLA cost capture (``capture_costs`` —
AOT ``lower().compile().cost_analysis()/memory_analysis()`` from
recorded avals, never live arrays, zero device round trips), exported
as ONE schema-versioned JSON document that ``tools/sfprof`` reports,
diffs, and gates on.
"""

from __future__ import annotations

import gc
import json
import os
import sys
import threading
import time
import warnings
from collections import deque
from typing import Any, Dict, Optional, Tuple

from spatialflink_tpu.faults import faults
from spatialflink_tpu.mn.metrics import FixedBucketLatency, json_safe


#: Run-ledger schema version (bump on any breaking change to the document
#: layout). Twin constant: tools/sfprof/ledger.py:LEDGER_VERSION — the
#: validator deliberately doesn't import this package, so bump BOTH
#: (tests/test_sfprof.py cross-pins them). v2: per-node attribution
#: (snapshot ``nodes`` block, kernel-row ``node`` column) + collective
#: accounting (snapshot ``collectives`` block); v3: event-time
#: end-to-end latency (snapshot ``e2e`` block — per-stage + per-node
#: FixedBucketLatency gauges). v1/v2 documents remain readable (the new
#: blocks are additive and appear only when their producers ran).
LEDGER_VERSION = 3

#: Ledger-STREAM record-layout version (the JSONL segment format behind
#: ``SFT_LEDGER_STREAM``). Twin constant: tools/sfprof/stream.py:
#: STREAM_VERSION — same no-cross-import rule, same cross-pin test.
#: v2: checkpoints carry the v2 snapshot blocks above; v3: checkpoints
#: may carry the ``e2e`` block and a ``<stream>.blackbox.json`` flight-
#: recorder dump may sit beside the stream (``sfprof recover`` folds a
#: present dump in). The grammar itself is unchanged, so v1/v2 streams
#: still recover.
STREAM_VERSION = 3


def _sanitize_nonfinite(value):
    """(sanitized, count): every non-finite float (NaN/±Inf) anywhere in
    the structure becomes ``None``, counted. A NaN at the very END of a
    run used to raise out of ``write_ledger`` (``allow_nan=False``) and
    lose the whole capture — sanitize-and-count keeps the artifact and
    makes the corruption visible (``nonfinite_values`` field) instead."""
    if isinstance(value, float):
        if value != value or value in (float("inf"), float("-inf")):
            return None, 1
        return value, 0
    if isinstance(value, dict):
        n = 0
        out = {}
        for k, v in value.items():
            out[k], dn = _sanitize_nonfinite(v)
            n += dn
        return out, n
    if isinstance(value, (list, tuple)):
        n = 0
        out = []
        for v in value:
            sv, dn = _sanitize_nonfinite(v)
            out.append(sv)
            n += dn
        return out, n
    return value, 0


class RecompileWarning(UserWarning):
    """One kernel crossed the distinct-abstract-shape threshold — bucket
    churn or an accidentally dynamic shape is forcing XLA recompiles."""


def _arg_signature(a):
    """One argument's contribution to the abstract signature. Arrays →
    (shape, dtype) — the aval; tuples/lists/dicts recurse (jit flattens
    pytrees, so a container of arrays recompiles whenever ANY leaf's
    shape changes — e.g. the knn pane digests repadded to a grown nseg;
    repr of a container would MATERIALIZE its arrays, a device fetch per
    call); other leaves contribute only their type (jit treats distinct
    Python scalars of one type as one aval)."""
    shape = getattr(a, "shape", None)
    dtype = getattr(a, "dtype", None)
    if shape is not None and dtype is not None:
        return (tuple(shape), str(dtype))
    if isinstance(a, (tuple, list)):
        return (type(a).__name__, tuple(_arg_signature(x) for x in a))
    if isinstance(a, dict):
        return ("dict", tuple(
            (str(k), _arg_signature(v)) for k, v in sorted(a.items())
        ))
    return type(a).__name__


def abstract_signature(args: tuple, kwargs: Optional[dict] = None) -> Tuple:
    """Hashable proxy of jax.jit's cache key for a call.

    Positional arguments go through ``_arg_signature`` (avals for arrays,
    recursive for containers); keyword arguments holding arrays or
    containers of arrays do too (e.g. the pane scan's ``lps_expire``
    array tuples — repr would MATERIALIZE the arrays, a device fetch
    per call), while every other kwarg contributes (name, repr(value))
    because scalar/string kwargs in this codebase are static arguments,
    where the VALUE keys the compile cache.
    """
    parts = [_arg_signature(a) for a in args]
    for k in sorted(kwargs or ()):
        v = kwargs[k]
        shape = getattr(v, "shape", None)
        dtype = getattr(v, "dtype", None)
        if shape is not None and dtype is not None:
            parts.append((k, (tuple(shape), str(dtype))))
        elif isinstance(v, (tuple, list, dict)):
            parts.append((k, _arg_signature(v)))
        else:
            parts.append((k, repr(v)))
    return tuple(parts)


class _NullSpan:
    """No-op context manager returned while telemetry is disabled — one
    shared instance, so the disabled-path cost is a truthiness check."""

    __slots__ = ()

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        return False


_NULL_SPAN = _NullSpan()


class _Span:
    """One timed phase. Besides the Chrome-trace event it emits on exit,
    the span enters a ``jax.profiler.TraceAnnotation`` of the same name:
    a level check with no profiler session, and under
    ``jax.profiler.start_trace`` the span lands on the host plane of the
    same ``.xplane.pb`` as the device's ``XLA Modules`` / ``XLA Ops``
    lines, on the profiler's clock. ``dur_ns`` is readable after exit
    (``instrument_jit`` feeds the kernel table from it)."""

    __slots__ = ("_tel", "name", "args", "_t0", "_ann", "dur_ns")

    def __init__(self, tel: "Telemetry", name: str, args: Dict[str, Any]):
        self._tel = tel
        self.name = name
        self.args = args

    def __enter__(self):
        from jax.profiler import TraceAnnotation

        self._ann = TraceAnnotation(self.name)
        self._ann.__enter__()
        self._t0 = time.perf_counter_ns()
        return self

    def __exit__(self, *exc):
        self.dur_ns = time.perf_counter_ns() - self._t0
        self._ann.__exit__(*exc)
        self._tel._emit_span(self.name, self._t0, self.dur_ns, self.args)
        return False


class _Scope:
    """Node-attribution scope: pushes a node name onto the emitting
    thread's scope stack for the duration of the ``with`` block.
    Innermost wins (``current_node`` reads the top), so the DAG's
    per-node scopes override the driver's operator-level one."""

    __slots__ = ("_tel", "node")

    def __init__(self, tel: "Telemetry", node: str):
        self._tel = tel
        self.node = node

    def __enter__(self):
        tls = self._tel._tls
        stack = getattr(tls, "stack", None)
        if stack is None:
            stack = tls.stack = []
        stack.append(self.node)
        return self

    def __exit__(self, *exc):
        self._tel._tls.stack.pop()
        return False


class Telemetry:
    """Process-global telemetry registry (the ``ops/counters.py`` idiom:
    one module singleton, ``enable()`` to opt in)."""

    def __init__(self, max_events: int = 262_144):
        self.enabled = False
        self.max_events = max_events
        self.recompile_warn_threshold = 8
        self.trace_path: Optional[str] = None
        self._trace_file = None
        # Append-only ledger stream (SFT_LEDGER_STREAM): JSONL segments —
        # versioned prologue, window-boundary checkpoint/span-batch
        # flushes, sealing epilogue. tools/sfprof recover rebuilds a
        # gateable ledger from a truncated stream.
        self.stream_path: Optional[str] = None
        self._stream_file = None
        self._stream_sealed = False
        self.stream_flush_interval_s = 1.0
        # Optional verdict callback installed by slo.install(): called at
        # ledger-write/seal time to embed the live SLO verdict block.
        self.slo_provider = None
        # Optional overload-state callback installed by
        # overload.install(): snapshot() embeds it as ["overload"], so
        # shed/degradation/circuit counters ride every ledger-stream
        # checkpoint and survive a mid-overload crash.
        self.overload_provider = None
        # Optional standing-query-registry callback installed by
        # qserve.install(): snapshot() embeds it as ["qserve"], so
        # registered/evicted/bucket-occupancy/recompile counters ride
        # ledger-stream checkpoints like the overload block does.
        self.qserve_provider = None
        # Optional composed-dataflow callback installed by
        # dag.install(): snapshot() embeds it as ["dag"] — per-node
        # backend/retry/failover/degraded/lag counters, the post-hoc
        # half of the per-node SLO twin (tools/sfprof/slo.py
        # node_budgets).
        self.dag_provider = None
        self._lock = threading.RLock()
        # Node-attribution scope stack: THREAD-CONFINED (a scope entered
        # on the driver thread tags only that thread's emissions) so
        # concurrent operator threads can never cross-tag each other.
        self._tls = threading.local()
        self._reset_state()

    def _reset_state(self):
        self.events: list = []
        self.dropped_events = 0
        self._since_flush = 0
        self.h2d_bytes = 0
        self.h2d_transfers = 0
        self.d2h_bytes = 0
        self.d2h_transfers = 0
        self.compile_events: list = []  # (kernel, signature), append order
        self._shapes_seen: Dict[str, set] = {}
        self._warned_kernels: set = set()
        self.max_watermark_lag_ms = 0
        self.late_drops = 0
        self.window_latency = FixedBucketLatency()
        # Watermark-lag distribution (not just the max): the SLO engine's
        # p99-freshness checks and the ledger's watermark_lag_p99_ms ride
        # this histogram.
        self.watermark_lag = FixedBucketLatency()
        # Link-probe rolling samples (LinkProbe.sample → record_link_sample):
        # bounded; snapshot() exports p50/last gauges.
        self._link_samples: list = []
        # Ledger-stream bookkeeping: events since the last stream flush,
        # monotonically increasing segment seq, flush pacing clock, and
        # the running count of sanitized non-finite values.
        self._stream_pending: list = []
        self._stream_seq = 0
        self._stream_last_flush = time.monotonic()
        self.nonfinite_values = 0
        # Fault-tolerance counters: injected-fault firings per point
        # (faults.py) and the driver's self-healing actions (driver.py) —
        # retries of a failed window and device→fallback failovers. All
        # land in snapshot()["driver"]/["faults"] so sfprof health and
        # the SLO engine's budgets can see them in any ledger.
        self.fault_fires: Dict[str, int] = {}
        self.driver_retries = 0
        self.driver_failovers = 0
        # engine → {capacity bucket → {"picks", "max_live"}} — the
        # compaction control plane's pick log (ops/compaction.py).
        self._compaction: Dict[str, Dict[int, Dict[str, int]]] = {}
        # (kernel, signature) → {"calls", "dispatch_ns", "first_call_ns",
        # "cost", "lower"} — the per-kernel runtime table behind
        # kernel_table()/capture_costs() (fed by instrument_jit).
        self._kernel_stats: Dict[Tuple[str, Tuple], Dict[str, Any]] = {}
        # Window point join (operators/join_query.py:run_soa via
        # record_join): counters pairs / peel_passes / windows / cap_retries
        # / budget_retries / bucket_lanes and the gauges cap / budget (the
        # rung and the pair budget in use) / refine / bucket_cells (the
        # bucket grid in use) / fullest_cell (the last window's), and
        # prefetched (record_join_prefetch: windows the join's producer
        # thread had assembled when its loop asked) —
        # snapshot()["join"], empty until the first joined window.
        self._join: Dict[str, int] = {}
        # Window trajectory join (operators/trajectory.py:TJoinQuery.run_soa
        # via record_tjoin): counters windows / pairs (point pairs) /
        # tpairs / peel_passes / cap_retries / budget_retries / id_lanes
        # and the gauges cap / budget / tpair_budget —
        # snapshot()["tjoin"], empty until the first joined window.
        self._tjoin: Dict[str, int] = {}
        # Wire-pane kNN (operators/knn_query.py:run_wire_panes via
        # record_wire_pane): panes taken, their points, the lanes shipped
        # for them (each pane padded up to its bucket) and the padding's
        # share of those; the assembler_* counters of the pane assembler
        # that feeds it (streams/wire.py via record_wire_assembler) —
        # snapshot()["wire"], empty until the first pane.
        self._wire: Dict[str, int] = {}
        # Point–polygon range query (operators/range_query.py:run_soa via
        # record_range): counters windows / points / lanes / matches /
        # cand_retries / budget_retries / index_windows and the gauges
        # cand / budget (the cell table's slots a cell and the compact
        # kernel's lane budget in use), the counter edge_slots (polygon
        # edges the programs evaluated); the gauges index_slots /
        # index_entries / index_cells / index_bytes of a polygon set's grid
        # index (record_range_index, once an evaluator), and prefetched
        # (record_range_prefetch: windows the range query's producer thread
        # had assembled when its loop asked) — snapshot()["range"], empty
        # until the first evaluator or window.
        self._range: Dict[str, int] = {}
        # A fired SoA window's device lanes (operators/base.py:point_lanes
        # via record_soa_lanes): counters windows / points / lanes (Σ
        # bucket) / blocks (Σ blocks its points were walked in) —
        # snapshot()["soa"], empty until the first window.
        self._soa: Dict[str, int] = {}
        # tids already named via a ph:"M" thread_name metadata event.
        self._named_tids: set = set()
        # Per-node attribution buckets: node name (or None = unscoped) →
        # counter dict. EVERY accounting site below updates exactly one
        # bucket, so bucket totals sum EXACTLY to the untagged globals —
        # the conservation invariant tests/test_dag.py asserts. The
        # snapshot exports them (None → "(unscoped)") only once a real
        # node has been seen, keeping un-scoped ledgers byte-compatible
        # with the v1 reader.
        self._node_acct: Dict[Optional[str], Dict[str, Any]] = {}
        # Mesh-collective accounting (account_collective): kind →
        # {"calls", "bytes"} plus per-axis byte totals — host-side
        # trace-time estimates from static shapes, never a device
        # round trip.
        self._collectives: Dict[str, Dict[str, int]] = {}
        self._collective_axes: Dict[str, int] = {}
        # Grid-partitioned halo accounting (parallel/halo.py): unpadded
        # boundary-state bytes the halo exchanges existed to move — the
        # denominator of sfprof's replication-ratio line (accounted
        # collective bytes ÷ boundary-state bytes).
        self._halo_state_bytes = 0
        # Cross-shard watermark coordination (parallel/halo.py /
        # operators' partitioned paths): shard id → max event-time seen.
        # The merged min over shards is the source-clock watermark the
        # composed DAG may safely advance to.
        self._shard_watermarks: Dict[int, int] = {}
        # Overload shed accounting (record_shed): global twin of the
        # per-node "shed_events"/"shed_bytes" bucket columns.
        self.shed_events = 0
        self.shed_bytes = 0
        # Event-time end-to-end latency (record_e2e): how stale a
        # committed result is relative to the event time that produced
        # it — the real-time criterion, not processing latency. One
        # FixedBucketLatency per stage globally plus per (node, stage);
        # open per-window entries are bounded (E2E_OPEN_MAX, evictions
        # counted) so the gauge stays fixed-memory like everything else
        # here. The anchor pins the capture's wall↔event-time mapping:
        # synthetic event clocks (bench replays) get honest staleness
        # instead of a wall-minus-epoch-zero absurdity.
        self._e2e_anchor: Optional[Tuple[float, float]] = None
        self._e2e_open: Dict[int, Dict[str, float]] = {}
        self._e2e_evicted = 0
        self._e2e_stages: Dict[str, FixedBucketLatency] = {}
        self._e2e_nodes: Dict[str, Dict[str, FixedBucketLatency]] = {}
        # Cyclic-GC accounting (_gc_callback, installed by enable()):
        # every full (generation-2) pass is a ``gc.full`` span; the
        # young generations only add to these integers (no event per
        # young pass — the event buffer is capped).
        self._gc_t0: Optional[int] = None
        self.gc_full_passes = 0
        self.gc_full_ns = 0
        self.gc_young_passes = 0
        self.gc_young_ns = 0
        # Flight recorder (the crash black box): bounded ring of the
        # last-N window-span summaries + instant events, dumped to
        # <stream>.blackbox.json on fault fire and stream seal (which
        # covers dial timeout, disable, and normal completion) — the
        # r3–r5 lesson that the most valuable telemetry is whatever
        # survived the crash. SFT_BLACKBOX sizes the ring; "0" disables.
        try:
            bb_n = int(os.environ.get("SFT_BLACKBOX", "64"))
        except ValueError:
            bb_n = 64
        self._blackbox: Optional[deque] = (
            deque(maxlen=bb_n) if bb_n > 0 else None
        )

    # -- lifecycle ------------------------------------------------------------

    def enable(self, trace_path: Optional[str] = None,
               recompile_warn_threshold: int = 8,
               stream_path: Optional[str] = None,
               stream_flush_interval_s: Optional[float] = None):
        """Reset all state, start recording, and hook the cyclic GC
        (``_gc_callback``; ``disable()`` unhooks it, so a disabled
        process leaves ``gc.callbacks`` alone). ``trace_path``: optional
        Chrome-trace JSON-lines file (events also buffer in memory, capped
        at ``max_events``). ``stream_path``: optional append-only ledger
        stream (JSONL) — a versioned prologue now, checkpoint + span-batch
        segments at window boundaries (paced by
        ``stream_flush_interval_s``, default 1 s or the
        ``SFT_LEDGER_STREAM_INTERVAL_S`` env), a sealing epilogue at
        ``write_ledger``/``disable``. A run killed mid-stream loses at
        most one flush interval; ``tools/sfprof recover`` rebuilds the
        ledger from the truncated stream."""
        with self._lock:
            self.disable()
            self._reset_state()
            self.recompile_warn_threshold = int(recompile_warn_threshold)
            self.trace_path = trace_path
            self.stream_path = stream_path
            self._stream_sealed = False
            if stream_path:
                if stream_flush_interval_s is None:
                    stream_flush_interval_s = float(os.environ.get(
                        "SFT_LEDGER_STREAM_INTERVAL_S", "1.0"))
                self.stream_flush_interval_s = float(stream_flush_interval_s)
                d = os.path.dirname(os.path.abspath(stream_path))
                os.makedirs(d, exist_ok=True)
                self._stream_file = open(stream_path, "w")
                # Prologue env is deliberately jax-free: enable() must
                # not import jax (bench enables before the backend is
                # settled in some paths); the full env block rides the
                # epilogue's ledger / the recovered document notes the
                # difference.
                self._write_stream({
                    "t": "prologue",
                    "stream_version": STREAM_VERSION,
                    "ledger_version": LEDGER_VERSION,
                    "created_unix": time.time(),
                    "env": {
                        "python": sys.version.split()[0],
                        "pid": os.getpid(),
                        "argv0": os.path.basename(sys.argv[0] or "python"),
                    },
                })
                self._stream_file.flush()
            if trace_path:
                d = os.path.dirname(os.path.abspath(trace_path))
                os.makedirs(d, exist_ok=True)
                self._trace_file = open(trace_path, "w")
                # Chrome-trace metadata: name the process once per pid so
                # Perfetto shows the program, not a bare number. Threads
                # are named lazily — one ph:"M" per NEW tid at its first
                # event (_emit) — because operator threads don't exist yet
                # at enable() time.
                self._write_trace({
                    "name": "process_name", "ph": "M", "pid": os.getpid(),
                    "args": {"name": "spatialflink_tpu:"
                             + os.path.basename(sys.argv[0] or "python")},
                })
            self.enabled = True
            if self._gc_callback not in gc.callbacks:
                gc.callbacks.append(self._gc_callback)
        # A plan armed BEFORE telemetry came up (the SFT_FAULT_PLAN
        # import-time path every chaos subprocess uses) would otherwise
        # never record its fault_armed event — emit it now so any
        # telemetry-enabled chaos run carries the armed schedule, not
        # just the firings (faults.arm() covers the arm-after-enable
        # order).
        if faults.armed:
            self.emit_instant(
                "fault_armed", plan=[r.to_dict() for r in faults.rules]
            )

    def disable(self):
        """Stop recording and SEAL both sinks: the ledger stream gets its
        epilogue (a disable() with no ``write_ledger`` used to leave the
        stream unsealed — indistinguishable from a crash), and the trace
        file is explicitly flushed before close so a mid-run disable can
        never strand ``_since_flush`` buffered events."""
        with self._lock:
            self.enabled = False
            if self._gc_callback in gc.callbacks:
                gc.callbacks.remove(self._gc_callback)
            self.seal_stream("disabled")
            if self._trace_file is not None:
                self._trace_file.flush()
                self._since_flush = 0
                self._trace_file.close()
                self._trace_file = None

    FLUSH_EVERY = 256

    def flush_trace(self):
        """Drain the buffered trace writer NOW. Call before a timed
        region: emits inside it then start from a fresh FLUSH_EVERY
        budget, so the periodic disk flush can't land mid-measurement
        (a latency probe)."""
        with self._lock:
            if self._trace_file is not None:
                self._trace_file.flush()
                self._since_flush = 0

    def _write_trace(self, event: dict):
        """Buffered trace write (caller holds the lock). No per-event
        flush — a synchronous flush per span would serialize operator
        threads through disk I/O and distort the spans being measured;
        the buffer drains every FLUSH_EVERY events and on disable()."""
        self._trace_file.write(json.dumps(event) + "\n")
        self._since_flush += 1
        if self._since_flush >= self.FLUSH_EVERY:
            self._trace_file.flush()
            self._since_flush = 0

    # -- ledger stream ---------------------------------------------------------

    def _write_stream(self, record: dict):
        """One JSONL stream record (caller holds the lock). Non-finite
        floats are sanitized to null and counted — a strict-JSON raise
        here would lose the stream's whole point (crash resilience)."""
        record, n = _sanitize_nonfinite(json_safe(record))
        if n:
            self.nonfinite_values += n
        self._stream_file.write(json.dumps(record, allow_nan=False) + "\n")

    def maybe_flush_stream(self, force: bool = False):
        """Window-boundary stream flush: a span batch (events since the
        last flush) + a full checkpoint (snapshot + kernel table), paced
        by ``stream_flush_interval_s`` so the disk work stays off the
        per-window hot path. ``force=True`` flushes regardless — phase
        boundaries and SLO violations use it."""
        with self._lock:
            if self._stream_file is None or self._stream_sealed:
                return
            now = time.monotonic()
            if (not force and now - self._stream_last_flush
                    < self.stream_flush_interval_s):
                return
            self._stream_last_flush = now
            self._flush_stream_locked()

    def _flush_stream_locked(self):
        self._stream_seq += 1
        seq = self._stream_seq
        if self._stream_pending:
            self._write_stream({
                "t": "spans", "seq": seq, "events": self._stream_pending,
            })
            self._stream_pending = []
        ck = {
            "t": "checkpoint", "seq": seq, "unix": time.time(),
            "snapshot": self.snapshot(), "kernels": self.kernel_table(),
        }
        if self.nonfinite_values:
            ck["nonfinite_values"] = self.nonfinite_values
        self._write_stream(ck)
        self._stream_file.flush()

    def seal_stream(self, reason: str, bench: Optional[dict] = None,
                    slo: Optional[dict] = None):
        """Terminal stream segment: final span batch + checkpoint, then
        the epilogue carrying the termination ``reason`` (and the bench
        record / SLO verdict when the run completed normally). Idempotent
        — the first seal wins; later calls (e.g. ``disable()`` after
        ``write_ledger``) are no-ops."""
        with self._lock:
            if self._stream_file is None or self._stream_sealed:
                return
            # Flight-recorder dump rides EVERY seal — dial_timeout,
            # disable, and normal completion alike (ISSUE: the black box
            # is cheapest exactly when nobody thinks they need it). The
            # marker instant lands in the final span batch below.
            bb = self.dump_blackbox(reason)
            if bb is not None and self.enabled:
                self.emit_instant("blackbox_dumped",
                                  reason=str(reason), path=bb)
            self._flush_stream_locked()
            if slo is None and self.slo_provider is not None:
                try:
                    slo = self.slo_provider()  # sfcheck: ok=lock-discipline -- documented one-way lock order: the SLO engine re-enters this RLock on the same thread (safe) and the overload controller queues its emits (overload._emit_locked) instead of ever taking this lock
                except Exception:  # a broken verdict must not block the seal
                    slo = None
            ep = {
                "t": "epilogue", "seq": self._stream_seq,
                "unix": time.time(), "reason": str(reason),
            }
            if bench is not None:
                ep["bench"] = bench
            if slo is not None:
                ep["slo"] = slo
            if self.nonfinite_values:
                ep["nonfinite_values"] = self.nonfinite_values
            self._write_stream(ep)
            self._stream_file.flush()
            self._stream_file.close()
            self._stream_file = None
            self._stream_sealed = True

    # -- cyclic GC -------------------------------------------------------------

    def _gc_callback(self, phase: str, info: Dict[str, int]):
        """``gc.callbacks`` hook, installed only while enabled. The
        interpreter runs one collection at a time and calls this on the
        thread that triggered it, possibly while that thread is inside
        ``_emit`` (the lock is an RLock): clock reads, integer adds and
        one ``_emit_span`` only. The scope tags each full pass with the
        node it interrupted."""
        if phase == "start":
            self._gc_t0 = time.perf_counter_ns()
            return
        t0, self._gc_t0 = self._gc_t0, None
        if t0 is None:  # enabled between a pass's start and its stop
            return
        dur_ns = time.perf_counter_ns() - t0
        if info["generation"] < 2:
            self.gc_young_passes += 1
            self.gc_young_ns += dur_ns
            return
        self.gc_full_passes += 1
        self.gc_full_ns += dur_ns
        self._emit_span("gc.full", t0, dur_ns, {
            "collected": info["collected"],
            "uncollectable": info["uncollectable"],
        })

    # -- node-attribution scope ------------------------------------------------

    def scope(self, node: Optional[str]):
        """Tag everything emitted by THIS thread inside the ``with``
        block with ``node``: spans, instant events, h2d/d2h/wire bytes,
        recompile detections, fault firings, shed counts, collective
        bytes, and kernel-table rows. ``None`` is a no-op (the qserve
        standalone-vs-DAG conditional), and an unset scope costs one
        thread-local read at each accounting site — nothing per event."""
        if node is None:
            return _NULL_SPAN
        return _Scope(self, str(node))

    def current_node(self) -> Optional[str]:
        """The innermost active scope's node name on this thread."""
        stack = getattr(self._tls, "stack", None)
        return stack[-1] if stack else None

    def _node_bucket(self, node: Optional[str]) -> Dict[str, Any]:
        """This node's accounting bucket (caller holds the lock)."""
        b = self._node_acct.get(node)
        if b is None:
            b = self._node_acct[node] = {
                "spans": 0, "span_us": 0, "windows": 0, "events": 0,
                "window_latency": FixedBucketLatency(),
                "h2d_bytes": 0, "h2d_transfers": 0,
                "d2h_bytes": 0, "d2h_transfers": 0,
                "compiles": 0, "instants": 0, "fault_fires": 0,
                "shed_events": 0, "shed_bytes": 0,
                "collective_calls": 0, "collective_bytes": 0,
                "dispatch_ns": 0, "kernel_calls": 0,
            }
        return b

    def node_rollup(self) -> Dict[str, Dict[str, Any]]:
        """JSON-safe per-node counter rollup (the snapshot ``nodes``
        block): one row per seen node, ``(unscoped)`` for emissions made
        outside any scope. Empty dict while no real node has been
        scoped — the byte-compat contract for un-scoped runs."""
        with self._lock:
            if not any(k is not None for k in self._node_acct):
                return {}
            out: Dict[str, Dict[str, Any]] = {}
            for node, b in self._node_acct.items():
                lat = b["window_latency"]
                p50 = lat.percentile(0.50)
                p95 = lat.percentile(0.95)
                row = {k: v for k, v in b.items()
                       if k != "window_latency"}
                row["window_latency_p50_ms"] = None if p50 != p50 else p50
                row["window_latency_p95_ms"] = None if p95 != p95 else p95
                out[node if node is not None else "(unscoped)"] = row
        return json_safe(out)

    # -- spans ----------------------------------------------------------------

    def span(self, name: str, **args):
        """Context manager timing one phase. Nesting renders naturally in
        Chrome tracing (same tid, contained ts/dur). ``window.*`` spans
        also feed the window-latency histogram."""
        if not self.enabled:
            return _NULL_SPAN
        return _Span(self, name, args)

    def emit_span(self, name: str, t0_ns: int, dur_ns: int, **args):
        """A span the caller timed itself on ``time.perf_counter_ns``: for
        a phase no ``with`` block can bracket (a generator's step, emitted
        only once it is known to have produced something). No profiler
        annotation goes with it."""
        self._emit_span(name, t0_ns, dur_ns, args)

    def _emit_span(self, name, t0_ns, dur_ns, args):
        if not self.enabled:  # disabled mid-span
            return
        node = self.current_node()
        ev = {
            "name": name,
            "cat": "telemetry",
            "ph": "X",
            "ts": t0_ns // 1000,
            "dur": dur_ns // 1000,
            "pid": os.getpid(),
            "tid": threading.get_ident(),
        }
        if node is not None:
            args = dict(args or ())
            args.setdefault("node", node)
        if args:
            ev["args"] = json_safe(args)
        self._emit(ev)
        with self._lock:
            b = self._node_bucket(node)
            b["spans"] += 1
            b["span_us"] += dur_ns // 1000
            if name.startswith("node."):
                # The DAG's per-node container spans: per-node window
                # count / event count / latency (window.* spans nested
                # inside would double-count the same wall time).
                b["windows"] += 1
                ev_n = (args or {}).get("events")
                if isinstance(ev_n, (int, float)):
                    b["events"] += int(ev_n)
                b["window_latency"].observe(dur_ns / 1e6)
        if name.startswith("window"):
            with self._lock:
                self.window_latency.observe(dur_ns / 1e6)
                # Flight recorder: the ring keeps the last-N window
                # summaries so a crash dump shows what the run was DOING,
                # not just its counters.
                self._blackbox_append({
                    "t": "window", "name": name, "ts": ev["ts"],
                    "dur_us": ev["dur"], "args": ev.get("args", {}),
                })
            # Window boundary = the stream's flush point (interval-paced
            # inside, so per-window cost is one clock read + a compare).
            self.maybe_flush_stream()

    def emit_instant(self, name: str, **args):
        """Structured instant event (``ph:"i"``) into the buffer, trace
        file, and ledger stream — the SLO engine's violation events and
        any other out-of-band markers ride this."""
        if not self.enabled:
            return
        node = self.current_node()
        if node is not None:
            args = dict(args)
            args.setdefault("node", node)
        ts = time.perf_counter_ns() // 1000
        safe_args = json_safe(args)
        with self._lock:
            self._node_bucket(node)["instants"] += 1
            # Flight recorder: instants ride the ring too — a crash dump
            # without the fault/failover markers around it is useless.
            self._blackbox_append({"t": "instant", "name": name,
                                   "ts": ts, "args": safe_args})
        self._emit({
            "name": name, "cat": "telemetry", "ph": "i",
            "ts": ts, "pid": os.getpid(),
            "tid": threading.get_ident(), "s": "t",
            "args": safe_args,
        })

    def _emit(self, event: dict):
        with self._lock:
            if len(self.events) < self.max_events:
                self.events.append(event)
            else:
                self.dropped_events += 1
            if self._stream_file is not None and not self._stream_sealed:
                # The stream keeps EVERY event (like the trace file): the
                # max_events cap bounds memory, not the artifact; pending
                # drains into a span batch at each stream flush.
                self._stream_pending.append(event)
            if self._trace_file is not None:
                tid = event.get("tid")
                if tid is not None and tid not in self._named_tids:
                    # First event from this thread: emit its thread_name
                    # metadata so the trace row reads e.g. "MainThread"
                    # / the operator thread's name instead of a raw
                    # ident. _emit runs on the emitting thread, so
                    # current_thread() IS the thread being named.
                    self._named_tids.add(tid)
                    self._write_trace({
                        "name": "thread_name", "ph": "M",
                        "pid": event.get("pid", os.getpid()), "tid": tid,
                        "args": {"name": threading.current_thread().name},
                    })
                self._write_trace(event)

    # -- device-boundary accounting -------------------------------------------

    def account_h2d(self, nbytes: int):
        """Bytes about to ship host→device (read from the HOST array before
        the transfer — no device round trip)."""
        if not self.enabled:
            return
        with self._lock:
            self.h2d_bytes += int(nbytes)
            self.h2d_transfers += 1
            b = self._node_bucket(self.current_node())
            b["h2d_bytes"] += int(nbytes)
            b["h2d_transfers"] += 1
            if self._trace_file is not None:
                self._write_trace({
                    "name": "h2d_bytes", "ph": "C",
                    "ts": time.perf_counter_ns() // 1000,
                    "pid": os.getpid(), "args": {"bytes": self.h2d_bytes},
                })

    def account_d2h(self, nbytes: int):
        """Bytes fetched device→host (counted at the true-sync fetch).
        Mirrors ``account_h2d`` exactly — including the Chrome-trace
        ``ph:"C"`` counter event, so d2h traffic renders as a Perfetto
        counter track too (the h2d/d2h asymmetry hid egress bytes)."""
        if not self.enabled:
            return
        with self._lock:
            self.d2h_bytes += int(nbytes)
            self.d2h_transfers += 1
            b = self._node_bucket(self.current_node())
            b["d2h_bytes"] += int(nbytes)
            b["d2h_transfers"] += 1
            if self._trace_file is not None:
                self._write_trace({
                    "name": "d2h_bytes", "ph": "C",
                    "ts": time.perf_counter_ns() // 1000,
                    "pid": os.getpid(), "args": {"bytes": self.d2h_bytes},
                })

    def fetch(self, x):
        """True-sync device→host fetch with timing + byte accounting
        (one ``d2h`` span carrying ``bytes``, and inside it one
        ``d2h.wait``).

        The tree's ONE synchronization point (sfcheck sync-discipline):
        a real ``jax.device_get``, so every wait on the device is timed
        and its bytes counted. Accepts any pytree; returns host numpy. Use this IN PLACE OF the
        operator's ``np.asarray``/``device_get`` so accounting rides the
        fetch the operator was doing anyway (zero extra round trips).

        ``d2h.wait`` is the leaf's own child (no profiler annotation goes
        with it): the time until every leaf of ``x`` is computed
        (``block_until_ready`` launches nothing: in a synchronous loop it
        is the inputs landing, the program, and whatever was queued before
        it). The rest of ``d2h`` is the copy. A loop that blocks nowhere
        else keeps the device busy only while it is inside ``d2h.wait``.
        """
        import jax

        if faults.armed:  # chaos injection point (faults.py)
            faults.hit("device.fetch")
        if not self.enabled:
            return jax.device_get(x)
        # The leaf is named ``d2h``, not ``fetch``: the operators' phase
        # spans own that name, and a sum by name must not count the wait
        # twice.
        with _Span(self, "d2h", {}) as sp:
            t0 = time.perf_counter_ns()
            jax.block_until_ready(x)
            waited_ns = time.perf_counter_ns() - t0
            out = jax.device_get(x)
            nbytes = 0
            for leaf in jax.tree_util.tree_leaves(out):
                nbytes += getattr(leaf, "nbytes", 0)
            sp.args["bytes"] = int(nbytes)
        # Timed by hand and emitted once the leaf has closed: the child's
        # own emit (tens of microseconds on a cold cache) is then no part
        # of ``d2h``, which reads what it read without the split.
        self._emit_span("d2h.wait", t0, waited_ns, None)
        self.account_d2h(nbytes)
        return out

    # -- recompile detection --------------------------------------------------

    def record_jit_call(self, kernel: str, signature: Tuple) -> bool:
        """Record a call into a jitted kernel. A signature not seen before
        for this kernel is one XLA compile (jit's cache key is the abstract
        shapes + statics this signature proxies). Crossing
        ``recompile_warn_threshold`` distinct signatures warns once —
        catching bucket-size churn and accidentally dynamic shapes.
        Returns True iff the signature is NEW (so the caller can do
        first-call-only work, e.g. stash avals for cost capture)."""
        if not self.enabled:
            return False
        node = self.current_node()
        warn_n = None
        with self._lock:
            seen = self._shapes_seen.setdefault(kernel, set())
            if signature in seen:
                return False
            seen.add(signature)
            self.compile_events.append((kernel, signature))
            # A compile is charged to the node whose call triggered it
            # (XLA compiles once per signature, so exactly one bucket
            # gets it — node compile totals sum to the global count).
            self._node_bucket(node)["compiles"] += 1
            if (len(seen) >= self.recompile_warn_threshold
                    and kernel not in self._warned_kernels):
                self._warned_kernels.add(kernel)
                warn_n = len(seen)
        compile_args: Dict[str, Any] = {"signature": repr(signature)}
        if node is not None:
            compile_args["node"] = node
        self._emit({
            "name": f"compile:{kernel}", "cat": "telemetry", "ph": "i",
            "ts": time.perf_counter_ns() // 1000, "pid": os.getpid(),
            "tid": threading.get_ident(), "s": "t",
            "args": compile_args,
        })
        if warn_n is not None:
            warnings.warn(
                f"kernel '{kernel}' has compiled for {warn_n} distinct "
                f"abstract shapes (threshold "
                f"{self.recompile_warn_threshold}): each is seconds of XLA "
                "compile + a device round trip — check for bucket-size "
                "churn or an un-bucketed dynamic dimension",
                RecompileWarning,
                stacklevel=3,
            )
        return True

    @property
    def compile_count(self) -> int:
        return len(self.compile_events)

    def distinct_shapes(self, kernel: str) -> int:
        with self._lock:
            return len(self._shapes_seen.get(kernel, ()))

    # -- per-kernel runtime table + cost capture -------------------------------

    def record_kernel_time(self, kernel: str, signature: Tuple,
                           dur_ns: int, lower_ctx=None):
        """One dispatch into an instrumented kernel: accumulate call count
        and dispatch wall-ns per (kernel, signature); the first call's
        duration is kept separately (it includes the XLA compile).
        ``lower_ctx`` — a ``(fn, abstract_args, abstract_kwargs)`` triple
        built from ShapeDtypeStructs, never live arrays — is stashed so
        ``capture_costs`` can lower/compile host-side LATER, strictly off
        the hot path."""
        if not self.enabled:
            return
        node = self.current_node()
        with self._lock:
            # Keyed per (kernel, signature, node): one kernel dispatched
            # by two DAG nodes gets one row EACH, so per-node dispatch
            # totals sum to the global table (conservation) instead of
            # blending into one unattributable row.
            key = (kernel, signature, node)
            st = self._kernel_stats.get(key)
            if st is None:
                st = self._kernel_stats[key] = {
                    "calls": 0,
                    "dispatch_ns": 0,
                    "first_call_ns": int(dur_ns),
                    "cost": None,
                    "lower": lower_ctx,
                }
            elif lower_ctx is not None and st["lower"] is None \
                    and st["cost"] is None:
                st["lower"] = lower_ctx
            st["calls"] += 1
            st["dispatch_ns"] += int(dur_ns)
            b = self._node_bucket(node)
            b["kernel_calls"] += 1
            b["dispatch_ns"] += int(dur_ns)

    def capture_costs(self):
        """Lazy host-side XLA cost/memory analysis, once per (kernel,
        signature). AOT ``fn.lower(*avals).compile()`` never executes the
        program and moves no data, so this adds ZERO device round trips
        (pinned under ``jax.transfer_guard`` in tests) — it only costs
        host compile time, which is why it runs here (write_ledger /
        explicit call) and never on the hot path. Idempotent; a kernel
        that won't lower records ``{"error": ...}`` instead of blocking
        the ledger."""
        with self._lock:
            pending = [
                st for st in self._kernel_stats.values()
                if st["cost"] is None and st["lower"] is not None
            ]
        for st in pending:
            fn, a_args, a_kwargs = st["lower"]
            cost = _analyze_cost(fn, a_args, a_kwargs)
            with self._lock:
                st["cost"] = cost
                st["lower"] = None

    def kernel_table(self) -> list:
        """JSON-safe per-(kernel, signature) rows: calls, cumulative
        dispatch wall-ns, first-call (compile-inclusive) ns, the derived
        ``steady_ns`` (cumulative MINUS the first call — a compile here
        is ~1-2 s against sub-ms dispatches, so ranking by the raw
        cumulative would just rank compiles), and the captured cost
        block (None until ``capture_costs`` runs). Sorted by steady
        dispatch time, heaviest first."""
        with self._lock:
            rows = []
            for (kernel, sig, node), st in self._kernel_stats.items():
                row = {
                    "kernel": kernel,
                    "signature": repr(sig),
                    "calls": st["calls"],
                    "dispatch_ns": st["dispatch_ns"],
                    "first_call_ns": st["first_call_ns"],
                    "steady_ns": max(
                        st["dispatch_ns"] - st["first_call_ns"], 0
                    ),
                    "cost": st["cost"],
                }
                if node is not None:
                    # v2 column, present only on scoped rows — un-scoped
                    # runs emit the exact v1 row shape.
                    row["node"] = node
                rows.append(row)
        rows.sort(key=lambda r: (-r["steady_ns"], -r["dispatch_ns"],
                                 r["kernel"]))
        return json_safe(rows)

    # -- run ledger ------------------------------------------------------------

    def write_ledger(self, path: str, bench: Optional[dict] = None,
                     mesh=None, capture_costs: bool = True) -> str:
        """One schema-versioned JSON run-ledger document: environment
        (python/jax/backend/devices, optional mesh shape), the full
        ``snapshot()``, the per-kernel runtime table (costs captured
        lazily here unless ``capture_costs=False``), the buffered span
        events (so ``tools/sfprof report`` can attribute phases without
        a separate trace file), and the caller's bench record. Strict
        JSON (``allow_nan=False``) — but a NaN/Inf anywhere is sanitized
        to null and COUNTED (``nonfinite_values``) rather than raised: a
        raise at the very end of a run used to lose the whole capture.
        Seals the ledger stream (``reason: complete``) when one is open.
        Consumed by ``python -m tools.sfprof`` (report / diff --gate /
        health)."""
        import jax

        if capture_costs:
            self.capture_costs()
        env = {
            "python": sys.version.split()[0],
            "jax": jax.__version__,
            "backend": jax.default_backend(),
            "device_count": jax.device_count(),
            "devices": [str(d) for d in jax.devices()[:8]],
            "x64": bool(jax.config.jax_enable_x64),
            "pid": os.getpid(),
            "argv0": os.path.basename(sys.argv[0] or "python"),
        }
        if mesh is not None:
            env["mesh"] = {str(k): int(v)
                           for k, v in dict(mesh.shape).items()}
        with self._lock:
            events = list(self.events)
        slo_block = None
        if self.slo_provider is not None:
            try:
                slo_block = json_safe(self.slo_provider())
            except Exception:  # a broken verdict must not block the ledger
                slo_block = None
        doc = {
            "ledger_version": LEDGER_VERSION,
            "created_unix": time.time(),
            "env": env,
            "snapshot": self.snapshot(),
            "kernels": self.kernel_table(),
            "events": events,
            "bench": json_safe(bench) if bench is not None else None,
        }
        if slo_block is not None:
            doc["slo"] = slo_block
        doc, nonfinite = _sanitize_nonfinite(doc)
        if nonfinite:
            doc["nonfinite_values"] = nonfinite
        d = os.path.dirname(os.path.abspath(path))
        os.makedirs(d, exist_ok=True)
        with open(path, "w") as f:
            json.dump(doc, f, allow_nan=False)
            f.write("\n")
        self.seal_stream("complete", bench=doc["bench"], slo=slo_block)
        return path

    # -- compaction bucket accounting -----------------------------------------

    def record_compaction(self, engine: str, capacity: int, live: int):
        """One host-side bucket pick by the live-slot compaction control
        plane (ops/compaction.py): ``engine`` compiled/ran at static
        capacity ``capacity`` for an observed live occupancy of
        ``live``. Per-(engine, bucket) pick counts + max observed live
        land in ``snapshot()`` — occupancy drift shows up as bucket
        churn here, and as at most ladder-many distinct signatures in
        the recompile detector (the bucket is a static of the scan)."""
        if not self.enabled:
            return
        with self._lock:
            d = self._compaction.setdefault(engine, {}).setdefault(
                int(capacity), {"picks": 0, "max_live": 0}
            )
            d["picks"] += 1
            d["max_live"] = max(d["max_live"], int(live))
        self._emit({
            "name": f"compaction:{engine}", "cat": "telemetry", "ph": "i",
            "ts": time.perf_counter_ns() // 1000, "pid": os.getpid(),
            "tid": threading.get_ident(), "s": "t",
            "args": {"capacity": int(capacity), "live": int(live)},
        })

    def compaction_buckets(self, engine: str) -> Dict[int, Dict[str, int]]:
        with self._lock:
            return {
                k: dict(v)
                for k, v in self._compaction.get(engine, {}).items()
            }

    def record_join(self, pairs: int, cap_retries: int, budget_retries: int,
                    cap: int, budget: int, peel_passes: int = 0,
                    fullest_cell: int = 0, refine: int = 1,
                    bucket_cells: int = 0, bucket_lanes: int = 0):
        """One window of the SoA point join, fetched: ``pairs`` found,
        the vector passes the Pallas extraction took them out in
        (``pairs ÷ peel_passes`` = hits a pass carries; 0 from the XLA
        program, which has no such loop), the re-runs it took (a bucket
        layout or a pair budget the window did not fit), the capacity rung
        and budget it ended on, and what holding the window cost:
        ``fullest_cell`` — the most points of one side in one cell of the
        key grid —, the refinement ``refine`` of the bucket grid picked for
        it, that grid's ``bucket_cells``, and ``bucket_lanes`` — buckets ×
        span² × cap², the pair lanes every run of the extraction evaluated
        (``bucket_lanes ÷ pairs`` = lanes a pair found). Lands in
        ``snapshot()["join"]`` as the counters ``pairs``, ``peel_passes``,
        ``windows``, ``cap_retries``, ``budget_retries``, ``bucket_lanes``
        and the gauges ``cap``, ``budget``, ``fullest_cell``, ``refine``,
        ``bucket_cells``. Per window, never per event."""
        if not self.enabled:
            return
        with self._lock:
            j = self._join
            for key, n in (("pairs", pairs), ("peel_passes", peel_passes),
                           ("windows", 1), ("cap_retries", cap_retries),
                           ("budget_retries", budget_retries),
                           ("bucket_lanes", bucket_lanes)):
                j[key] = j.get(key, 0) + int(n)
            j["cap"], j["budget"] = int(cap), int(budget)
            j["fullest_cell"], j["refine"] = int(fullest_cell), int(refine)
            j["bucket_cells"] = int(bucket_cells)

    def record_join_prefetch(self):
        """One two-sided window of a join's ``run_soa`` that the producer
        thread had assembled before the loop asked for it
        (``operators/join_query.py:_aligned_soa_windows``, on
        ``operators/base.py:assembled_ahead``). Lands in
        ``snapshot()["join"]`` as the counter ``prefetched`` (the trajectory
        join's too); ``prefetched ÷ windows`` = how often assembly ran beside
        the loop. Per window, never per event."""
        if not self.enabled:
            return
        with self._lock:
            j = self._join
            j["prefetched"] = j.get("prefetched", 0) + 1

    def record_tjoin(self, pairs: int, tpairs: int, cap_retries: int,
                     budget_retries: int, cap: int, budget: int,
                     tpair_budget: int, peel_passes: int = 0,
                     refine: int = 1, id_lanes: bool = False):
        """One window of the SoA trajectory join, fetched: the point
        ``pairs`` the extraction found (they stay on the device), the
        distinct trajectory pairs ``tpairs`` the dedup made of them
        (``tpairs ÷ pairs`` = what the dedup collapses to), the re-runs it
        took (a bucket capacity or a pair budget the window did not fit;
        the dedup's output is as long as the pair list, so it has nothing
        to overflow) and the sizes it ended on (``tpair_budget``: the
        largest trajectory-pair count whose fetch programs are compiled).
        ``id_lanes``: the extraction carried the two sides' id lanes, so
        its pairs came out as trajectory ids (the dedup gathered none).
        Lands in ``snapshot()["tjoin"]`` as the counters ``windows``,
        ``pairs``, ``tpairs``, ``peel_passes``, ``cap_retries``,
        ``budget_retries``, ``id_lanes`` and the gauges ``cap``, ``budget``,
        ``tpair_budget``, ``refine`` (the bucket grid's refinement, as the
        point join's). Per window, never per event."""
        if not self.enabled:
            return
        with self._lock:
            j = self._tjoin
            for key, n in (("windows", 1), ("pairs", pairs),
                           ("tpairs", tpairs), ("peel_passes", peel_passes),
                           ("cap_retries", cap_retries),
                           ("budget_retries", budget_retries),
                           ("id_lanes", id_lanes)):
                j[key] = j.get(key, 0) + int(n)
            j["cap"], j["budget"] = int(cap), int(budget)
            j["tpair_budget"], j["refine"] = int(tpair_budget), int(refine)

    def record_range(self, points: int, lanes: int, matches: int,
                     cand_retries: int, budget_retries: int, cand: int,
                     budget: int, edge_slots: int):
        """One window of the SoA point range query, fetched: its
        ``points``, the ``lanes`` shipped for them (the padding bucket),
        the ``matches`` the host selected, the re-runs the compact polygon
        kernel took (more candidate lanes than its ``budget``;
        ``cand_retries`` stays 0 since the pruned kernels read a point's
        candidates off the cell table: nothing there can overflow), and the
        sizes the window ran with: ``cand`` = K, the cell table's slots a
        cell, and ``budget`` (0 where the kernel that ran has no such
        size), and the polygon ``edge_slots`` its program evaluated,
        padding and re-runs included (counted from the kernel's plan: lanes
        × K × E through a cell-table row; cell-major, tile × the steps of
        each batch of tiles, a step ``width`` entries of E slots for every
        tile of the batch; 0 for a point or linestring set). Lands in
        ``snapshot()["range"]`` as the counters ``windows``, ``points``,
        ``lanes``, ``matches``, ``cand_retries``, ``budget_retries``,
        ``index_windows`` (windows answered through the cell table: those
        with ``cand`` > 0), ``edge_slots`` and the gauges ``cand``,
        ``budget``. Per window, never per event."""
        if not self.enabled:
            return
        with self._lock:
            r = self._range
            for key, n in (("windows", 1), ("points", points),
                           ("lanes", lanes), ("matches", matches),
                           ("cand_retries", cand_retries),
                           ("budget_retries", budget_retries),
                           ("index_windows", cand > 0),
                           ("edge_slots", edge_slots)):
                r[key] = r.get(key, 0) + int(n)
            r["cand"], r["budget"] = int(cand), int(budget)

    def record_range_prefetch(self):
        """One window of the range query's ``run_soa`` that the producer
        thread had assembled before the loop asked for it
        (``operators/base.py:assembled_ahead``). Lands in
        ``snapshot()["range"]`` as the counter ``prefetched``;
        ``prefetched ÷ windows`` = how often assembly ran beside the loop.
        Per window, never per event."""
        if not self.enabled:
            return
        with self._lock:
            r = self._range
            r["prefetched"] = r.get("prefetched", 0) + 1

    def record_range_index(self, slots: int, entries: int, cells: int,
                           nbytes: int):
        """The cell → candidate-polygons index of a polygon range query,
        once when its evaluator is built: gauges ``index_slots`` (K),
        ``index_entries`` (Σ list lengths), ``index_cells`` (cells with
        a non-empty list) and ``index_bytes`` (what the index the kernel
        reads holds on the device: the K-wide edge rows, or the ragged edge
        table and its run starts) of ``snapshot()["range"]``.
        ``index_entries ÷ (index_cells × index_slots)`` is the share of the
        slots gathered in non-empty rows that hold a real polygon."""
        if not self.enabled:
            return
        with self._lock:
            self._range.update(index_slots=int(slots),
                               index_entries=int(entries),
                               index_cells=int(cells),
                               index_bytes=int(nbytes))

    def record_soa_lanes(self, points: int, lanes: int, blocks: int):
        """One point slice made into device lanes by ``operators/base.py:
        point_lanes`` (a fired SoA window; a pane of ``run_soa_panes``): its
        ``points``, the bucket-length ``lanes`` written once for them and
        the ``blocks`` the points were walked in. Lands in
        ``snapshot()["soa"]`` as the counters ``windows``, ``points``,
        ``lanes``, ``blocks``; ``points ÷ blocks`` is the mean block — that
        the blocked passes ran, and at what grain. Per window, never per
        block or event."""
        if not self.enabled:
            return
        with self._lock:
            s = self._soa
            for key, v in (("windows", 1), ("points", points),
                           ("lanes", lanes), ("blocks", blocks)):
                s[key] = s.get(key, 0) + int(v)

    def record_wire_pane(self, n: int, bucket: int):
        """One pane taken by ``run_wire_panes``: ``n`` points padded up to
        ``bucket`` lanes before the ship. Lands in ``snapshot()["wire"]``
        as the counters ``panes``, ``points`` (Σ n), ``lanes`` (Σ bucket)
        and ``pad_lanes`` (Σ bucket − n). Per pane, never per event."""
        if not self.enabled:
            return
        with self._lock:
            w = self._wire
            for key, v in (("panes", 1), ("points", n), ("lanes", bucket),
                           ("pad_lanes", bucket - n)):
                w[key] = w.get(key, 0) + int(v)

    def record_wire_assembler(self, chunks: int, rows: int,
                              rows_moved: int, grows: int):
        """One pane closed by ``streams/wire.py:WirePaneAssembler``: the
        chunks and rows it took in since its last record, the rows it
        copied after their first write (buffer regrowth + the emitted
        copy) and the regrowths. Lands in ``snapshot()["wire"]`` as the
        counters ``assembler_chunks``, ``assembler_rows``,
        ``assembler_rows_moved``, ``assembler_grows``;
        ``assembler_rows_moved ÷ assembler_rows`` is the copy
        amplification. Per closed pane, never per chunk or event."""
        if not self.enabled:
            return
        with self._lock:
            w = self._wire
            for key, v in (("assembler_chunks", chunks),
                           ("assembler_rows", rows),
                           ("assembler_rows_moved", rows_moved),
                           ("assembler_grows", grows)):
                w[key] = w.get(key, 0) + int(v)

    # -- mesh-collective accounting (parallel/) --------------------------------

    def account_collective(self, kind: str, nbytes: int,
                           axis: Optional[str] = None,
                           calls: int = 1):
        """Logical bytes one mesh collective moves (psum / pmin / pmax /
        ppermute / broadcast), accounted HOST-SIDE from static trace-time
        shapes by the ``parallel/`` wrappers — never a device round trip.
        These are the all-gather/halo baselines ROADMAP item 2's
        grid-partitioned scale-out must beat; ``sfprof report`` surfaces
        them as the ``collective`` phase and roofline signal."""
        if not self.enabled:
            return
        with self._lock:
            st = self._collectives.setdefault(
                kind, {"calls": 0, "bytes": 0}
            )
            st["calls"] += int(calls)
            st["bytes"] += int(nbytes)
            if axis is not None:
                self._collective_axes[axis] = (
                    self._collective_axes.get(axis, 0) + int(nbytes)
                )
            b = self._node_bucket(self.current_node())
            b["collective_calls"] += int(calls)
            b["collective_bytes"] += int(nbytes)

    def account_halo_state(self, nbytes: int):
        """Unpadded boundary-state bytes one halo exchange shipped
        (parallel/halo.py) — the true lanes behind the padded ppermute
        payload, and the denominator of sfprof's replication-ratio line.
        Host-side static metadata, same contract as
        :meth:`account_collective`."""
        if not self.enabled:
            return
        with self._lock:
            self._halo_state_bytes += int(nbytes)

    def collective_gauges(self) -> Optional[Dict[str, Any]]:
        """Collective summary (None before the first accounted
        collective): total calls/bytes, per-kind and per-axis splits,
        plus the halo boundary-state bytes once a halo kernel has run
        (absent otherwise — the additive-keys compat contract)."""
        with self._lock:
            if not self._collectives:
                return None
            out = {
                "calls": sum(s["calls"]
                             for s in self._collectives.values()),
                "bytes": sum(s["bytes"]
                             for s in self._collectives.values()),
                "by_kind": {k: dict(s)
                            for k, s in self._collectives.items()},
                "by_axis": dict(self._collective_axes),
            }
            if self._halo_state_bytes:
                out["halo_state_bytes"] = self._halo_state_bytes
            return json_safe(out)

    # -- overload shed accounting (overload.py) --------------------------------

    def record_shed(self, n_events: int, nbytes: int = 0):
        """Events the overload controller shed before they reached an
        assembler. The controller keeps its own per-reason/per-tenant
        breakdown (snapshot ``overload`` block); this global + per-node
        twin exists so shed counts obey the same conservation invariant
        as bytes and dispatch time (DAG sheds happen at the SHARED
        source, so they land in the ``(unscoped)`` bucket)."""
        if not self.enabled:
            return
        with self._lock:
            self.shed_events += int(n_events)
            self.shed_bytes += int(nbytes)
            b = self._node_bucket(self.current_node())
            b["shed_events"] += int(n_events)
            b["shed_bytes"] += int(nbytes)

    # -- watermark / lateness gauges ------------------------------------------

    def record_watermark_lag(self, lag_ms: int):
        """Event-time ms between a fired window's end and the watermark at
        fire time — how late the window fired relative to its span. Feeds
        both the max gauge and the lag histogram (the SLO engine's p99
        freshness checks read the distribution, not just the worst case)."""
        if not self.enabled:
            return
        with self._lock:
            self.watermark_lag.observe(float(lag_ms))
            if lag_ms > self.max_watermark_lag_ms:
                self.max_watermark_lag_ms = int(lag_ms)

    def record_shard_watermark(self, shard: int, watermark_ms: int):
        """Per-shard event-time high-water mark on the grid-partitioned
        path (parallel/halo.py feeds it from each window's owned rows).
        The MERGED watermark — min over shards — is what the source
        clock may advance to: one straggling shard holds the whole
        partitioned pipeline's event time, which is exactly what these
        gauges make visible."""
        if not self.enabled:
            return
        with self._lock:
            prev = self._shard_watermarks.get(int(shard))
            if prev is None or int(watermark_ms) > prev:
                self._shard_watermarks[int(shard)] = int(watermark_ms)

    def shard_watermark_gauges(self) -> Optional[Dict[str, Any]]:
        """Cross-shard watermark summary (None before the first
        partitioned window): per-shard high-water marks (sorted string
        keys — the JSON-stable shape), the merged min-watermark, and the
        shard count."""
        with self._lock:
            if not self._shard_watermarks:
                return None
            return json_safe({
                "per_shard": {
                    str(s): self._shard_watermarks[s]
                    for s in sorted(self._shard_watermarks)
                },
                "merged_min": min(self._shard_watermarks.values()),
                "shards": len(self._shard_watermarks),
            })

    # -- link-health probe gauges ----------------------------------------------

    LINK_SAMPLES_MAX = 256

    def record_link_sample(self, latency_ms: float, roundtrip_mbps: float,
                           payload_bytes: int):
        """One LinkProbe round trip: rolling host↔device latency/bandwidth
        gauges (bounded window), an instant trace event, and — because a
        probe sample is exactly the moment to persist — a paced stream
        flush."""
        if not self.enabled:
            return
        sample = {
            "unix": time.time(),
            "latency_ms": float(latency_ms),
            "roundtrip_mbps": float(roundtrip_mbps),
            "payload_bytes": int(payload_bytes),
        }
        with self._lock:
            self._link_samples.append(sample)
            if len(self._link_samples) > self.LINK_SAMPLES_MAX:
                del self._link_samples[0]
        self.emit_instant("link_probe", latency_ms=float(latency_ms),
                          roundtrip_mbps=float(roundtrip_mbps))
        self.maybe_flush_stream()

    def link_gauges(self) -> Optional[Dict[str, Any]]:
        """Rolling link-health summary (None before the first sample):
        sample count + p50/last latency and round-trip bandwidth.
        ``sfprof diff`` uses it to ANNOTATE (never widen) its tolerance
        bands — a degraded link explains an
        e2e EPS drop without excusing a device-resident one."""
        with self._lock:
            samples = list(self._link_samples)
        if not samples:
            return None
        lat = sorted(s["latency_ms"] for s in samples)
        bw = sorted(s["roundtrip_mbps"] for s in samples)
        mid = len(samples) // 2
        return json_safe({
            "samples": len(samples),
            "latency_ms_p50": lat[mid],
            "latency_ms_last": samples[-1]["latency_ms"],
            "roundtrip_mbps_p50": bw[mid],
            "roundtrip_mbps_last": samples[-1]["roundtrip_mbps"],
            "payload_bytes": samples[-1]["payload_bytes"],
        })

    def record_late_drop(self, n: int = 1):
        if not self.enabled:
            return
        with self._lock:
            self.late_drops += int(n)

    # -- event-time end-to-end latency (latency lineage) -----------------------

    #: Stage vocabulary, pipeline order. ``assemble`` = window fired at
    #: the source clock; ``ship``/``compute``/``fetch`` = the device
    #: boundary crossings (the driver's synchronous loop stamps
    #: ``compute`` once, when the processor returns);
    #: ``commit`` = the sink's transactional append
    #: — the only number that answers "how stale is a committed result
    #: relative to the event time that produced it?".
    E2E_STAGES = ("assemble", "ship", "compute", "fetch", "commit")

    #: Open per-window entries are bounded: a window that never commits
    #: (shed, crashed, replaced) must not leak memory forever. Oldest
    #: win-end evicts first; evictions are counted in the ``e2e`` block.
    E2E_OPEN_MAX = 4096

    def record_e2e(self, win_end_ms, stage: str,
                   node: Optional[str] = None) -> Optional[float]:
        """One stage boundary of one window's latency lineage.

        The first stamp for a window anchors it: its ``assemble``
        latency is the anchored event-time staleness — wall-now minus
        the *virtual* wall time of the window's end event, where the
        capture-wide anchor (first stamp ever) maps event-time ms onto
        the wall clock. Synthetic event clocks (bench replays running
        faster or slower than real time) therefore measure honest
        pipeline staleness instead of wall-minus-epoch nonsense. Every
        later stage records ``assemble latency + wall elapsed since the
        window's first stamp`` — monotone by construction, so per-stage
        differences are real wall durations and the critical-path
        conservation receipt (segments sum ≤ commit e2e) holds per
        window. ``commit`` closes the entry. Returns the observed
        latency in ms (None while disabled)."""
        if not self.enabled:
            return None
        now_mono = time.monotonic()
        with self._lock:
            key = int(win_end_ms)
            entry = self._e2e_open.get(key)
            if entry is None:
                wall = time.time()
                if self._e2e_anchor is None:
                    self._e2e_anchor = (float(wall), float(win_end_ms))
                a_wall, a_ev = self._e2e_anchor
                virtual_wall = a_wall + (float(win_end_ms) - a_ev) / 1e3
                entry = {
                    "assemble_ms": max((wall - virtual_wall) * 1e3, 0.0),
                    "t0": now_mono,
                }
                if len(self._e2e_open) >= self.E2E_OPEN_MAX:
                    self._e2e_open.pop(min(self._e2e_open))
                    self._e2e_evicted += 1
                self._e2e_open[key] = entry
            if stage == "assemble":
                lat_ms = entry["assemble_ms"]
            else:
                lat_ms = (entry["assemble_ms"]
                          + (now_mono - entry["t0"]) * 1e3)
            self._e2e_bucket(None, stage).observe(lat_ms)
            if node is None:
                node = self.current_node()
            if node is not None:
                self._e2e_bucket(node, stage).observe(lat_ms)
            if stage == "commit":
                self._e2e_open.pop(key, None)
        return float(lat_ms)

    def _e2e_bucket(self, node: Optional[str],
                    stage: str) -> FixedBucketLatency:
        """The (node, stage) latency histogram (caller holds the lock);
        ``node=None`` is the global per-stage gauge."""
        d = (self._e2e_stages if node is None
             else self._e2e_nodes.setdefault(str(node), {}))
        b = d.get(stage)
        if b is None:
            b = d[stage] = FixedBucketLatency()
        return b

    def e2e_stage_percentiles(self, stage: str,
                              node: Optional[str] = None):
        """(p50_ms, p99_ms) for one stage's gauge — global when ``node``
        is None, the node's own otherwise; (None, None) before the first
        observation (the SLO engine's silence-fails rule handles it)."""
        with self._lock:
            d = (self._e2e_stages if node is None
                 else self._e2e_nodes.get(str(node), {}))
            lat = d.get(stage)
            if lat is None or not lat.count:
                return (None, None)
            p50 = lat.percentile(0.50)
            p99 = lat.percentile(0.99)
        return (None if p50 != p50 else float(p50),
                None if p99 != p99 else float(p99))

    def e2e_gauges(self) -> Optional[Dict[str, Any]]:
        """The snapshot ``e2e`` block (None before the first stamp —
        un-armed runs keep the v2 snapshot shape byte-compatible):
        per-stage count/sum/p50/p99 globally and per node, the capture
        anchor, and the open-entry gauge + eviction count."""
        with self._lock:
            if not self._e2e_stages and not self._e2e_nodes:
                return None

            def block(d: Dict[str, FixedBucketLatency]) -> Dict[str, Any]:
                out = {}
                for stage, lat in d.items():
                    p50 = lat.percentile(0.50)
                    p99 = lat.percentile(0.99)
                    out[stage] = {
                        "count": lat.count,
                        "sum_ms": lat.sum_ms,
                        "p50_ms": None if p50 != p50 else p50,
                        "p99_ms": None if p99 != p99 else p99,
                    }
                return out

            out: Dict[str, Any] = {"stages": block(self._e2e_stages)}
            if self._e2e_nodes:
                out["nodes"] = {n: block(d)
                                for n, d in self._e2e_nodes.items()}
            if self._e2e_anchor is not None:
                out["anchor"] = {"wall_unix": self._e2e_anchor[0],
                                 "event_ms": self._e2e_anchor[1]}
            out["open_windows"] = len(self._e2e_open)
            if self._e2e_evicted:
                out["evicted"] = self._e2e_evicted
        return json_safe(out)

    # -- flight recorder (the crash black box) ---------------------------------

    def dump_blackbox(self, reason: str) -> Optional[str]:
        """Write the flight-recorder ring beside the ledger stream as
        ``<stream>.blackbox.json`` — the last-N window summaries +
        instants plus a counter snapshot, strict JSON so a truncation-
        proof reader (``sfprof blackbox`` / ``recover``) always parses
        it. No-op without a ring (SFT_BLACKBOX=0) or a stream path (the
        dump names its stream — a black box with no flight is noise).
        Best-effort on a dying process: an OSError is swallowed, never
        raised into the crash path that triggered the dump."""
        with self._lock:
            if self._blackbox is None or self.stream_path is None:
                return None
            path = self.stream_path + ".blackbox.json"
            doc = {
                "blackbox_version": 1,
                "reason": str(reason),
                "unix": time.time(),
                "stream": self.stream_path,
                "ring": list(self._blackbox),
                "counters": {
                    "events": len(self.events),
                    "dropped_events": self.dropped_events,
                    "h2d_bytes": self.h2d_bytes,
                    "d2h_bytes": self.d2h_bytes,
                    "compiles": len(self.compile_events),
                    "late_drops": self.late_drops,
                    "fault_fires": dict(self.fault_fires),
                    "driver_retries": self.driver_retries,
                    "driver_failovers": self.driver_failovers,
                },
            }
            e2e = self.e2e_gauges()
            if e2e is not None:
                doc["e2e"] = e2e
            doc, _ = _sanitize_nonfinite(json_safe(doc))
            try:
                with open(path, "w") as f:
                    json.dump(doc, f, allow_nan=False)
                    f.write("\n")
            except OSError:
                return None
        return path

    def _blackbox_append(self, rec: Dict[str, Any]):
        """Ring append (caller holds the lock; no-op when disabled)."""
        if self._blackbox is not None:
            self._blackbox.append(rec)

    # -- fault tolerance (faults.py / driver.py) -------------------------------

    def record_fault(self, point: str, kind: str = "raise", hit: int = 0):
        """One injected fault fired. NB the telemetry↔faults cycle runs
        ONE way: this module imports ``faults`` at module scope (for the
        armed checks), so faults.py must reach telemetry only through
        its lazy per-call imports — never at import time. The instant
        event is force-flushed: a fault is exactly the record that must
        survive the crash it is about to cause."""
        if not self.enabled:
            return
        with self._lock:
            self.fault_fires[point] = self.fault_fires.get(point, 0) + 1
            self._node_bucket(self.current_node())["fault_fires"] += 1
        self.emit_instant(f"fault_fired:{point}", kind=kind, hit=int(hit))
        self.maybe_flush_stream(force=True)
        # Flight-recorder dump AFTER the force flush (the stream already
        # has the fault record) and BEFORE faults._fire's os._exit on
        # the abort kind — this call is the last code an aborting
        # process runs with its telemetry intact.
        bb = self.dump_blackbox(f"fault:{point}")
        if bb is not None:
            self.emit_instant("blackbox_dumped",
                              reason=f"fault:{point}", path=bb)
            self.maybe_flush_stream(force=True)

    def record_driver_retry(self, window_start: int, attempt: int,
                            error: str):
        """The driver retried a failed window on the same backend."""
        if not self.enabled:
            return
        with self._lock:
            self.driver_retries += 1
        self.emit_instant("driver_retry", window_start=int(window_start),
                          attempt=int(attempt), error=str(error)[:200])

    def record_driver_failover(self, window_start: int, error: str):
        """The driver switched device → fallback backend mid-stream.
        Force-flushed for the same reason as faults: the failover marker
        must survive whatever killed the device path."""
        if not self.enabled:
            return
        with self._lock:
            self.driver_failovers += 1
        self.emit_instant("failover", window_start=int(window_start),
                          to="fallback", error=str(error)[:200])
        self.maybe_flush_stream(force=True)

    # -- export ---------------------------------------------------------------

    def register_metrics(self, registry):
        """Wire the telemetry gauges into an ``mn.metrics.MetricRegistry``
        so ``snapshot()`` (and anything reading it — NES reporter lines,
        sink-owned registries) carries the new columns."""
        registry.gauge("watermark_lag_ms_max",
                       lambda: self.max_watermark_lag_ms)
        registry.gauge("late_dropped_total", lambda: self.late_drops)
        registry.gauge("telemetry_compiles_total",
                       lambda: len(self.compile_events))
        registry.gauge("h2d_bytes_total", lambda: self.h2d_bytes)
        registry.gauge("d2h_bytes_total", lambda: self.d2h_bytes)
        registry.gauge(
            "compaction_buckets_total",
            lambda: sum(len(v) for v in self._compaction.values()),
        )

    def summary(self) -> Dict[str, Any]:
        """The summary JSON block: strictly JSON-safe (numpy scalars →
        builtins, NaN percentiles → None so strict parsers never choke)."""
        with self._lock:
            p50 = self.window_latency.percentile(0.50)
            p95 = self.window_latency.percentile(0.95)
            lag99 = self.watermark_lag.percentile(0.99)
            out = {
                "compiles": len(self.compile_events),
                "bytes_h2d": self.h2d_bytes,
                "bytes_d2h": self.d2h_bytes,
                "window_latency_p50_ms": None if p50 != p50 else p50,
                "window_latency_p95_ms": None if p95 != p95 else p95,
                "max_watermark_lag_ms": self.max_watermark_lag_ms,
                "watermark_lag_p99_ms": None if lag99 != lag99 else lag99,
                "late_dropped": self.late_drops,
            }
        return json_safe(out)

    def snapshot(self) -> Dict[str, Any]:
        """Full JSON-safe state dump (summary + transfer/trace counts)."""
        out = self.summary()
        with self._lock:
            out.update(
                h2d_transfers=self.h2d_transfers,
                d2h_transfers=self.d2h_transfers,
                events=len(self.events),
                dropped_events=self.dropped_events,
                kernels={k: len(v) for k, v in self._shapes_seen.items()},
                compaction={
                    eng: {str(cap): dict(st) for cap, st in caps.items()}
                    for eng, caps in self._compaction.items()
                },
                # Self-healing visibility: always present so sfprof
                # health / SLO budgets can gate on zero, not on absence.
                driver={
                    "retries": self.driver_retries,
                    "failovers": self.driver_failovers,
                },
            )
            if self.fault_fires:
                out["faults"] = dict(self.fault_fires)
            if self.gc_full_passes or self.gc_young_passes:
                out["gc"] = {
                    "full_passes": self.gc_full_passes,
                    "full_ns": self.gc_full_ns,
                    "young_passes": self.gc_young_passes,
                    "young_ns": self.gc_young_ns,
                }
            if self.shed_events or self.shed_bytes:
                out["shed"] = {"events": self.shed_events,
                               "bytes": self.shed_bytes}
            if self._join:
                out["join"] = dict(self._join)
            if self._tjoin:
                out["tjoin"] = dict(self._tjoin)
            if self._wire:
                out["wire"] = dict(self._wire)
            if self._range:
                out["range"] = dict(self._range)
            if self._soa:
                out["soa"] = dict(self._soa)
        if self.overload_provider is not None:
            try:
                out["overload"] = json_safe(self.overload_provider())  # sfcheck: ok=lock-discipline -- stream-flush checkpoints call this under Telemetry._lock by design; the provider contract (documented at overload.OverloadController._lock) forbids providers from taking telemetry's lock — overload queues transition emits for after release
            except Exception:  # a broken provider must not break snapshots
                pass
        if self.qserve_provider is not None:
            try:
                out["qserve"] = json_safe(self.qserve_provider())  # sfcheck: ok=lock-discipline -- same provider contract as overload_provider above: the qserve registry is lock-free host state and only re-enters this RLock on the same thread (distinct_shapes)
            except Exception:  # a broken provider must not break snapshots
                pass
        if self.dag_provider is not None:
            try:
                out["dag"] = json_safe(self.dag_provider())  # sfcheck: ok=lock-discipline -- same provider contract: the DAG's node-state dicts are driver-thread confined host state; the provider takes no locks
            except Exception:  # a broken provider must not break snapshots
                pass
        link = self.link_gauges()
        if link is not None:
            out["link_probe"] = link
        # v3 block: event-time end-to-end latency — additive, absent
        # until the first record_e2e stamp, so un-armed runs keep the
        # v2 snapshot shape byte-compatible.
        e2e = self.e2e_gauges()
        if e2e is not None:
            out["e2e"] = e2e
        # v2 blocks, both strictly additive and absent until their
        # producers run — an un-scoped, collective-free run snapshots
        # the exact v1 shape (the byte-compat contract for old readers).
        nodes = self.node_rollup()
        if nodes:
            out["nodes"] = nodes
        coll = self.collective_gauges()
        if coll is not None:
            out["collectives"] = coll
        shard_wm = self.shard_watermark_gauges()
        if shard_wm is not None:
            out["shard_watermarks"] = shard_wm
        return json_safe(out)


telemetry = Telemetry()


def enable(trace_path: Optional[str] = None, recompile_warn_threshold: int = 8):
    telemetry.enable(trace_path, recompile_warn_threshold)


def disable():
    telemetry.disable()


def span(name: str, **args):
    return telemetry.span(name, **args)


def scope(node: Optional[str]):
    return telemetry.scope(node)


def fetch(x):
    return telemetry.fetch(x)


def write_ledger(path: str, bench: Optional[dict] = None, mesh=None,
                 capture_costs: bool = True) -> str:
    return telemetry.write_ledger(path, bench=bench, mesh=mesh,
                                  capture_costs=capture_costs)


class LinkProbe:
    """Host↔device link-health probe: a tiny FIXED-SHAPE device round
    trip measuring latency (8-float RTT) and bandwidth (one fixed
    payload, default 256 KiB, shipped out and fetched back).

    The ``jax.device_get`` IS the measurement. Bandwidth is reported as
    the ROUND-TRIP aggregate: ``2·payload/elapsed``.

    Call ``sample()`` only at phase boundaries — never inside a window
    span — so probe traffic lands in host gaps, not in measured windows.
    Samples feed the rolling gauges in ``telemetry`` (snapshot's
    ``link_probe`` block), and ``sfprof diff`` annotates its verdicts
    with the link ratio so "chip slow" is distinguishable from "link
    degraded"."""

    def __init__(self, device=None, payload_bytes: int = 262_144,
                 tel: Optional[Telemetry] = None):
        import numpy as np

        self.device = device
        self.payload_bytes = int(payload_bytes)
        self._tel = tel
        # Fixed shapes, allocated once: the probe must never cause an
        # XLA compile (device_put/get are pure transfers) nor shape churn.
        self._tiny = np.zeros(8, np.float32)
        self._payload = np.zeros(max(self.payload_bytes // 4, 1),
                                 np.float32)

    def sample(self) -> Dict[str, float]:
        """One probe round trip; records into the telemetry gauges (when
        enabled) and returns the raw sample."""
        import jax

        t0 = time.perf_counter()
        jax.device_get(jax.device_put(self._tiny, self.device))
        latency_ms = (time.perf_counter() - t0) * 1e3
        t1 = time.perf_counter()
        got = jax.device_get(jax.device_put(self._payload, self.device))
        dt = max(time.perf_counter() - t1, 1e-9)
        roundtrip_mbps = 2.0 * float(got.nbytes) / dt / 1e6
        tel = self._tel if self._tel is not None else telemetry
        tel.record_link_sample(latency_ms, roundtrip_mbps,
                               int(got.nbytes))
        return {
            "latency_ms": float(latency_ms),
            "roundtrip_mbps": float(roundtrip_mbps),
            "payload_bytes": int(got.nbytes),
        }


def _abstract_leaf(a):
    """ShapeDtypeStruct mirror of one call argument for DEFERRED AOT
    lowering: arrays become avals (no reference to the device buffer is
    retained — keeping donated inputs alive would defeat
    ``donate_argnums``), tuple/list/NamedTuple/dict containers recurse,
    static scalars/strings keep their value (it keys the compile cache),
    and any other leaf type raises — an object we can't prove
    buffer-free must not be pinned in the stats table."""
    shape = getattr(a, "shape", None)
    dtype = getattr(a, "dtype", None)
    if shape is not None and dtype is not None:
        import jax

        return jax.ShapeDtypeStruct(tuple(shape), dtype)
    if isinstance(a, (tuple, list)):
        parts = [_abstract_leaf(x) for x in a]
        if hasattr(a, "_fields"):  # NamedTuple carries (pane scans, …)
            return type(a)(*parts)  # positional ctor, not an iterable
        return type(a)(parts)
    if isinstance(a, dict):
        return {k: _abstract_leaf(v) for k, v in a.items()}
    if a is None or isinstance(
            a, (bool, int, float, complex, str, bytes, type)):
        return a  # static scalar: the value keys the compile cache
    # Anything else (custom pytree, exotic object) could hide a device
    # buffer — refuse rather than pin it in _kernel_stats (the caller
    # records cost as unavailable instead).
    raise TypeError(
        f"unsupported leaf for deferred lowering: {type(a).__name__}"
    )


def _lower_ctx(fn, args, kwargs):
    """(fn, abstract args, abstract kwargs) for a later host-side
    ``fn.lower(...)`` — or None when ``fn`` has no AOT surface (e.g. a
    plain callable wrapped for signature tracking only)."""
    if not hasattr(fn, "lower"):
        return None
    try:
        return (
            fn,
            tuple(_abstract_leaf(a) for a in args),
            {k: _abstract_leaf(v) for k, v in kwargs.items()},
        )
    except Exception:  # exotic arg types: skip cost capture, pin nothing
        return None


def _analyze_cost(fn, args, kwargs) -> Dict[str, Any]:
    """Host-side XLA cost + memory analysis of one (kernel, signature).

    AOT lower/compile from avals: nothing executes, nothing crosses the
    device boundary. Failures come back as ``{"error": ...}`` so one
    unlowerable program never blocks the ledger."""
    try:
        compiled = fn.lower(*args, **kwargs).compile()
    except Exception as e:
        return {"error": f"{type(e).__name__}: {e}"}
    out: Dict[str, Any] = {}
    try:
        ca = compiled.cost_analysis()
        for src, dst in (("flops", "flops"),
                         ("bytes accessed", "bytes_accessed"),
                         ("transcendentals", "transcendentals")):
            if ca and src in ca:
                out[dst] = float(ca[src])
    except Exception:  # pragma: no cover - backend without cost analysis
        pass
    try:
        mem = compiled.memory_analysis()
        for attr, dst in (("temp_size_in_bytes", "temp_bytes"),
                          ("argument_size_in_bytes", "argument_bytes"),
                          ("output_size_in_bytes", "output_bytes"),
                          ("generated_code_size_in_bytes", "code_bytes")):
            v = getattr(mem, attr, None)
            if v is not None:
                out[dst] = int(v)
        if "temp_bytes" in out:
            # Peak working set of one dispatch: arguments + outputs +
            # XLA temp buffers (the quantity that overflows HBM).
            out["peak_memory_bytes"] = (out["temp_bytes"]
                                        + out.get("argument_bytes", 0)
                                        + out.get("output_bytes", 0))
    except Exception:  # pragma: no cover - backend without memory stats
        pass
    return out or {"error": "cost analysis unavailable on this backend"}


def instrument_jit(fn, name: Optional[str] = None):
    """Wrap a compiled callable with recompile-signature tracking and the
    per-(kernel, signature) runtime table.

    ``operators/base.py:jitted`` routes every operator kernel through this.
    Disabled-path cost: one attribute check per call (calls here are per WINDOW, never per
    record). Enabled, each call is one ``dispatch:<kernel>`` span (the
    ``compile:<kernel>`` instant's naming) whose duration also feeds the
    locked table update; a NEW signature additionally stashes
    ShapeDtypeStruct avals
    so ``telemetry.capture_costs()`` can lower/compile host-side later —
    nothing device-facing happens on the call path. Attributes of the
    underlying jit object (``lower``, …) pass through.

    This is also the ``device.dispatch`` chaos injection point
    (faults.py): it lives HERE — not in ``jitted`` — so the mesh window
    programs that skip ``jitted`` are injectable too.
    """
    label = name or getattr(fn, "__name__", repr(fn))

    class _Instrumented:
        __slots__ = ()

        def __call__(self, *args, **kwargs):
            if faults.armed:  # chaos injection point (faults.py)
                faults.hit("device.dispatch")
            if not telemetry.enabled:
                return fn(*args, **kwargs)
            sig = abstract_signature(args, kwargs)
            is_new = telemetry.record_jit_call(label, sig)
            with _Span(telemetry, f"dispatch:{label}",
                       {"new_signature": is_new}) as sp:
                out = fn(*args, **kwargs)
            telemetry.record_kernel_time(
                label, sig, sp.dur_ns,
                lower_ctx=_lower_ctx(fn, args, kwargs) if is_new else None,
            )
            return out

        def __getattr__(self, attr):
            return getattr(fn, attr)

    wrapped = _Instrumented()
    return wrapped


def load_trace(path: str) -> Dict[str, Any]:
    """Read a JSON-lines trace file into the standard Chrome-trace document
    ``{"traceEvents": [...]}`` (loadable by chrome://tracing / Perfetto)."""
    events = []
    with open(path) as f:
        for line in f:
            line = line.strip()
            if line:
                events.append(json.loads(line))
    return {"traceEvents": events}
