"""qserve — multi-tenant continuous-query serving.

GeoFlink's execution model is one spatial query per Flink job (CIKM 2020
§IV; the IEEE Access 2022 evaluation never goes beyond per-operator
grids). The production shape of the ROADMAP north star is the opposite:
THOUSANDS of standing range/kNN queries — registered and unregistered by
many tenants while the stream runs — against ONE object stream. This
module is that serving layer:

- **Standing-query registry** (:class:`QueryRegistry`): tenants register
  / unregister :class:`StandingQuery`\\ s via :class:`QServeCommand`\\ s
  riding the event stream (commands apply at window boundaries, in
  event-time order, exactly once — the ``_applied`` uid set makes
  refires and crash/retry replays idempotent). Registration strings
  (qid, tenant) intern into the OPERATOR's objID table — one intern
  home, never a second string table.
- **Bucketed batched evaluation**: live queries group by
  ``(kind, k-rung, radius-class)`` and each bucket pads onto a
  power-of-two capacity rung via the existing compaction ladder
  (``ops/compaction.py:pick_capacity`` — the overload
  ``clamp_compaction`` rung floors qserve rungs too), then evaluates as
  ONE vmapped fixed-shape program per window
  (``ops/query_registry.py:registry_bucket_kernel``; per-query radius
  is a traced operand, padding lanes are masked). Registration churn
  therefore moves between at most ladder-many compiled signatures per
  (rung, nseg) pair — the telemetry recompile detector is the guard,
  and the rung picks land in ``snapshot()["compaction"]`` under
  ``qserve_bucket``. On a mesh the same bucket runs through
  ``parallel/sharded.py:sharded_registry_bucket`` (bit-parity pinned).
- **Per-tenant QoS** (scoping PR 9's global machinery): registration
  admission and per-window result budgets come from
  ``overload.OverloadPolicy.tenant_budgets`` — a class over budget has
  its registrations rejected (``qserve_evicted``) or its result rows
  truncated, counted PER CLASS in ``snapshot()["overload"]["tenants"]``
  and budgeted by ``slo.SloSpec.tenant_budgets`` (post-hoc twin:
  ``sfprof health --slo``) — one firehose tenant degrades itself, never
  the fleet.
- **Crash safety**: the registry state (queries + applied-command uids
  + counters) snapshots with the operator (checkpoint.py), so a kill
  mid-registration-churn resumes to byte-identical per-tenant egress
  (``qserve.register`` injection point, chaos-matrix leg).

Wiring follows the telemetry idiom: :func:`install` puts one registry in
the module slot and ``telemetry.snapshot()["qserve"]`` carries its
counters (registered/evicted/bucket occupancy/recompiles) on every
ledger-stream checkpoint. ``SFT_QSERVE`` (inline JSON or a path —
``envvars.py``) supplies a serving config to ``streaming_job`` option 9
and the bench harness: ``{"queries": [...], "tenant_budgets": {...},
"cap_max": N}``.

PARITY.md "Continuous-query serving" documents the deliberate deviations
from the reference's one-query-per-job model.
"""

from __future__ import annotations

import json
import math
import os
from dataclasses import asdict, dataclass
from typing import Any, Dict, Iterable, Iterator, List, Optional, Tuple

import numpy as np

from spatialflink_tpu import overload
from spatialflink_tpu.faults import faults
from spatialflink_tpu.operators.base import (
    SpatialOperator,
    flags_for_queries,
    jitted,
    ship,
)
from spatialflink_tpu.models.batch import PointBatch
from spatialflink_tpu.models.objects import Point
from spatialflink_tpu.streams.columns import WindowColumns
from spatialflink_tpu.telemetry import telemetry
from spatialflink_tpu.utils.padding import next_bucket

QSERVE_VERSION = 1

#: Smallest bucket-capacity / result rung. Matches the compaction
#: ladder's floor so the per-bucket compile bound is len(capacity_ladder
#: (cap_max, 8)) ≤ 8 programs per (rung, nseg) pair.
QUERY_RUNG_MIN = 8

#: Default bucket-capacity ceiling (one bucket never exceeds this many
#: query lanes; a class's registrations beyond it are evicted, counted).
QUERY_CAP_MAX = 1024

#: Radius-class base (degrees ≈ 110 m): queries whose radii fall in the
#: same power-of-two band share a bucket. Grouping-only — the radius is
#: a TRACED per-query operand, so the class never keys a compile; it
#: keeps a bucket's pruning tables (and therefore its candidate
#: densities) homogeneous so one fat-radius query cannot dominate a
#: bucket of tight ones.
RADIUS_CLASS_BASE = 0.001

_KINDS = ("range", "knn")


@dataclass(frozen=True)
class StandingQuery:
    """One registered continuous query.

    ``k``: for ``knn`` the neighbor count; for ``range`` the result
    capacity (max matches returned per window — distinct in-radius
    objects beyond it are counted per window into the registry's
    ``range_result_overflow`` via the kernel's unclamped ``within``).
    """

    qid: str
    tenant: str
    kind: str
    x: float
    y: float
    radius: float
    k: int = 10
    tenant_class: str = "default"

    def __post_init__(self):
        if self.kind not in _KINDS:
            raise ValueError(
                f"unknown query kind {self.kind!r} (kinds: {_KINDS})"
            )
        if not self.qid:
            raise ValueError("qid must be non-empty")
        if not (float(self.radius) > 0.0):
            raise ValueError(f"radius must be positive, got {self.radius!r}")
        if int(self.k) < 1:
            raise ValueError(f"k must be >= 1, got {self.k!r}")

    def to_dict(self) -> Dict[str, Any]:
        return asdict(self)


@dataclass(frozen=True)
class QServeCommand:
    """A registration command riding the event stream. ``uid`` must be
    unique per logical command: it is the exactly-once key — sliding-
    window refires and crash/retry replays of a window re-apply commands
    through the registry's ``_applied`` set, so a duplicate uid is a
    no-op by construction."""

    timestamp: int
    action: str  # "register" | "unregister"
    uid: str
    query: Optional[StandingQuery] = None  # register
    qid: Optional[str] = None  # unregister

    #: Overload admission treats control-plane items as zero load and
    #: NEVER sheds them (overload._measure_item): shedding a command
    #: would silently diverge the registry from the command stream.
    control_plane = True

    def __post_init__(self):
        if self.action not in ("register", "unregister"):
            raise ValueError(f"unknown qserve action {self.action!r}")
        if self.action == "register" and self.query is None:
            raise ValueError("register command needs a query")
        if self.action == "unregister" and not self.qid:
            raise ValueError("unregister command needs a qid")
        if not self.uid:
            raise ValueError("command uid must be non-empty")


def query_rung(q: StandingQuery) -> int:
    """Result-capacity rung: smallest power of two ≥ k (floor 8) — the
    ONLY per-query value that becomes a compile-time static."""
    return int(next_bucket(max(int(q.k), 1), minimum=QUERY_RUNG_MIN))


def radius_class(radius: float) -> int:
    """Power-of-two radius band above ``RADIUS_CLASS_BASE`` (grouping
    key only — never a static; see the module docstring)."""
    r = float(radius)
    if r <= RADIUS_CLASS_BASE:
        return 0
    return max(0, int(math.ceil(math.log2(r / RADIUS_CLASS_BASE))))


def bucket_key(q: StandingQuery) -> Tuple[str, int, int]:
    return (q.kind, query_rung(q), radius_class(q.radius))


def bucket_key_str(key: Tuple[str, int, int]) -> str:
    return f"{key[0]}_k{int(key[1])}_rc{int(key[2])}"


class QueryRegistry:
    """The standing-query set + exactly-once command application.

    Single-threaded by design (driver-thread confined, like operator
    state) — no lock, so the telemetry provider can never deadlock.
    ``interner`` is the OWNING OPERATOR's objID interner: qid/tenant
    strings intern there on successful registration (one intern home —
    asserted by tests/test_qserve.py)."""

    def __init__(self, grid, interner, cap_max: int = QUERY_CAP_MAX):
        self.grid = grid
        self.interner = interner
        self.cap_max = int(cap_max)
        self._queries: Dict[str, StandingQuery] = {}
        self._flags: Dict[str, np.ndarray] = {}  # qid → neighbor table
        self._versions: Dict[Tuple[str, int, int], int] = {}
        self._bucket_live: Dict[Tuple[str, int, int], int] = {}
        #: command uid → command event-time (the exactly-once set;
        #: pruned behind the watermark by ``prune_applied``)
        self._applied: Dict[str, int] = {}
        #: bumped on restore so operator-side device caches keyed on
        #: (epoch, version) can never serve a pre-restore array.
        self.epoch = 0
        self.registered_total = 0
        self.unregistered_total = 0
        self.evicted_total = 0
        self.range_result_overflow = 0
        # Last window charged to the overflow counter — a driver RETRY
        # re-runs the same window's process(), and without this marker
        # the re-run would double-count (the _applied-set idea applied
        # to a per-window accumulator).
        self._overflow_window: Optional[int] = None
        self._overflow_last = 0

    def __len__(self) -> int:
        return len(self._queries)

    def query(self, qid: str) -> Optional[StandingQuery]:
        return self._queries.get(qid)

    def flags(self, qid: str) -> np.ndarray:
        return self._flags[qid]

    def version(self, key: Tuple[str, int, int]) -> int:
        return self._versions.get(key, 0)

    def _bump(self, key: Tuple[str, int, int]):
        self._versions[key] = self._versions.get(key, 0) + 1

    # -- command application (exactly once) ------------------------------------

    def apply(self, cmd: QServeCommand) -> bool:
        """Apply one command; returns True iff it changed the registry.
        Duplicate uids (window refires, crash/retry replays) are
        no-ops — THE exactly-once contract the chaos matrix pins."""
        if faults.armed:  # chaos injection point (faults.py)
            faults.hit("qserve.register")
        if cmd.uid in self._applied:
            return False
        self._applied[cmd.uid] = int(cmd.timestamp)
        if cmd.action == "register":
            return self._register(cmd.query)
        return self._unregister(cmd.qid)

    def prune_applied(self, watermark_ts: int, horizon_ms: int):
        """Drop applied-uid entries whose command timestamp is older
        than ``watermark - horizon``: a command can only replay via a
        sliding-window refire or a checkpoint-resume replay, both of
        which reach back at most one window span (+ lateness) behind
        the watermark — older uids can never be re-seen, so keeping
        them would grow the set (and every checkpoint serializing it)
        linearly with the run's LIFETIME command count."""
        cut = int(watermark_ts) - int(horizon_ms)
        stale = [uid for uid, ts in self._applied.items() if ts < cut]
        for uid in stale:
            del self._applied[uid]

    def record_range_overflow(self, window_start: int, count: int):
        """Charge one window's range-result truncation (distinct
        in-radius objects beyond each range query's ``k`` cap) to the
        running counter, idempotently: re-charging the SAME window (a
        driver retry re-running ``process``) replaces the previous
        charge instead of accumulating it."""
        if self._overflow_window == int(window_start):
            self.range_result_overflow -= self._overflow_last
        self._overflow_window = int(window_start)
        self._overflow_last = int(count)
        self.range_result_overflow += int(count)

    def _register(self, q: StandingQuery) -> bool:
        if q.qid in self._queries:
            return False  # idempotent re-register
        key = bucket_key(q)
        if self._bucket_live.get(key, 0) >= self.cap_max:
            # The rung ladder tops out at cap_max — beyond it the bucket
            # cannot hold another lane. Deterministic eviction, counted.
            self.evicted_total += 1
            if telemetry.enabled:
                telemetry.emit_instant(
                    "qserve_evicted", qid=q.qid,
                    tenant_class=q.tenant_class, reason="bucket_full",
                )
            return False
        if not overload.admit_tenant_query(q.tenant_class):
            # Per-tenant-class admission budget (overload.py
            # tenant_budgets): the CLASS is over its standing-query
            # budget — reject and count, fleet untouched.
            self.evicted_total += 1
            if telemetry.enabled:
                telemetry.emit_instant(
                    "qserve_evicted", qid=q.qid,
                    tenant_class=q.tenant_class, reason="tenant_budget",
                )
            return False
        # ONE intern home: registration strings join the operator's
        # objID table (dense ids reused for deterministic routing).
        self.interner.intern(q.tenant)
        self.interner.intern(q.qid)
        self._queries[q.qid] = q
        self._flags[q.qid] = flags_for_queries(
            self.grid, q.radius, [Point(x=q.x, y=q.y)]
        )
        self._bucket_live[key] = self._bucket_live.get(key, 0) + 1
        self.registered_total += 1
        self._bump(key)
        if telemetry.enabled:
            telemetry.emit_instant(
                "qserve_registered", qid=q.qid, tenant=q.tenant,
                tenant_class=q.tenant_class, kind=q.kind,
            )
        return True

    def _unregister(self, qid: str) -> bool:
        q = self._queries.pop(qid, None)
        if q is None:
            return False  # idempotent re-unregister
        self._flags.pop(qid, None)
        key = bucket_key(q)
        self._bucket_live[key] = max(0, self._bucket_live.get(key, 1) - 1)
        overload.release_tenant_query(q.tenant_class)
        self.unregistered_total += 1
        self._bump(key)
        if telemetry.enabled:
            telemetry.emit_instant(
                "qserve_unregistered", qid=qid,
                tenant_class=q.tenant_class,
            )
        return True

    # -- bucketing -------------------------------------------------------------

    def buckets(self) -> Dict[Tuple[str, int, int], List[StandingQuery]]:
        """Live queries grouped by (kind, k-rung, radius-class), qid-
        sorted within each bucket — the deterministic evaluation order
        the byte-identical-egress contract rides on."""
        out: Dict[Tuple[str, int, int], List[StandingQuery]] = {}
        for qid in sorted(self._queries):
            q = self._queries[qid]
            out.setdefault(bucket_key(q), []).append(q)
        return out

    # -- checkpoint state ------------------------------------------------------

    def state(self) -> Dict[str, Any]:
        return {
            "version": QSERVE_VERSION,
            "queries": [
                self._queries[qid].to_dict()
                for qid in sorted(self._queries)
            ],
            "applied": sorted(
                [uid, int(ts)] for uid, ts in self._applied.items()
            ),
            "counters": {
                "registered_total": int(self.registered_total),
                "unregistered_total": int(self.unregistered_total),
                "evicted_total": int(self.evicted_total),
                "range_result_overflow": int(self.range_result_overflow),
                "overflow_window": self._overflow_window,
                "overflow_last": int(self._overflow_last),
            },
        }

    def restore(self, state: Dict[str, Any]):
        ver = state.get("version", QSERVE_VERSION)
        if ver != QSERVE_VERSION:
            raise ValueError(
                f"qserve state version {ver} != supported {QSERVE_VERSION}"
            )
        self._queries = {}
        self._flags = {}
        for d in state["queries"]:
            q = StandingQuery(**d)
            self._queries[q.qid] = q
            # Flag tables are derived data — rebuilt from the grid (the
            # join-pane-carry restore idiom in checkpoint.py).
            self._flags[q.qid] = flags_for_queries(
                self.grid, q.radius, [Point(x=q.x, y=q.y)]
            )
        self._applied = {uid: int(ts) for uid, ts in state["applied"]}
        self._bucket_live = {}
        for q in self._queries.values():
            key = bucket_key(q)
            self._bucket_live[key] = self._bucket_live.get(key, 0) + 1
        c = state["counters"]
        self.registered_total = int(c["registered_total"])
        self.unregistered_total = int(c["unregistered_total"])
        self.evicted_total = int(c["evicted_total"])
        self.range_result_overflow = int(c["range_result_overflow"])
        ow = c.get("overflow_window")
        self._overflow_window = None if ow is None else int(ow)
        self._overflow_last = int(c.get("overflow_last", 0))
        self._versions = {}
        self.epoch += 1  # invalidate any operator-side device caches

    # -- telemetry provider ----------------------------------------------------

    def snapshot(self) -> Dict[str, Any]:
        """The ``snapshot()["qserve"]`` block (telemetry installs this
        as ``qserve_provider``): registered/evicted counters, per-bucket
        occupancy vs its current rung, and the bucket kernel's compiled-
        signature count — the ≤K churn contract made visible."""
        from spatialflink_tpu.ops.compaction import pick_capacity

        buckets = {
            bucket_key_str(key): {
                "live": len(qs),
                "capacity": int(pick_capacity(
                    len(qs), self.cap_max, minimum=QUERY_RUNG_MIN
                )),
            }
            for key, qs in sorted(self.buckets().items())
        }
        return {
            "version": QSERVE_VERSION,
            "registered": len(self._queries),
            "registered_total": int(self.registered_total),
            "unregistered_total": int(self.unregistered_total),
            "evicted_total": int(self.evicted_total),
            "range_result_overflow": int(self.range_result_overflow),
            "buckets": buckets,
            "recompiles": telemetry.distinct_shapes(
                "registry_bucket_kernel"
            ),
        }


def bucket_host_arrays(grid, queries: List[StandingQuery], cap: int,
                       flags_of=None):
    """Padded host arrays for one bucket: (qxy (cap, 2) f64 UNcentered,
    radius (cap,), qvalid (cap,), tables (cap, num_cells+1) uint8).
    Shared by the operator (which centers qxy at its device boundary)
    and the bench harness. ``flags_of(q)`` overrides the per-query
    neighbor-table source (default: compute from the grid)."""
    if len(queries) > cap:
        raise ValueError(f"{len(queries)} queries exceed the {cap} rung")
    qxy = np.zeros((cap, 2), np.float64)
    radius = np.zeros(cap, np.float64)
    qvalid = np.zeros(cap, bool)
    tables = np.zeros((cap, grid.num_cells + 1), np.uint8)
    for i, q in enumerate(queries):
        qxy[i] = (q.x, q.y)
        radius[i] = float(q.radius)
        qvalid[i] = True
        tables[i] = (
            flags_of(q) if flags_of is not None
            else flags_for_queries(grid, q.radius, [Point(x=q.x, y=q.y)])
        )
    return qxy, radius, qvalid, tables


@dataclass
class QServeWindowResult:
    """One window's served results, routed per tenant.

    ``rows``: (tenant_class, tenant, qid, objID, dist) in deterministic
    bucket/qid/rank order — AFTER per-tenant-class result budgets
    truncated each class's rows (overload.tenant_result_allowance)."""

    start: int
    end: int
    rows: List[Tuple[str, str, str, Any, float]]
    window_count: int

    def lines(self) -> Iterator[str]:
        """The per-tenant egress line format (streaming_job option 9 and
        the chaos harness byte-compare these)."""
        for cls, tenant, qid, obj, dist in self.rows:
            yield (f"{tenant},{qid},{self.start},{self.end},"
                   f"{obj},{float(dist)!r}")

    def by_tenant(self) -> Dict[str, List[Tuple[str, Any, float]]]:
        out: Dict[str, List[Tuple[str, Any, float]]] = {}
        for _cls, tenant, qid, obj, dist in self.rows:
            out.setdefault(tenant, []).append((qid, obj, float(dist)))
        return out


class QServeOperator(SpatialOperator):
    """The serving operator: Point events + QServeCommands in, per-
    tenant standing-query results out, on the shared dataflow driver
    (checkpoint/retry/chaos semantics identical to the query operators).
    """

    def __init__(self, conf, grid, mesh=None, cap_max: int = QUERY_CAP_MAX):
        super().__init__(conf, grid, mesh=mesh)
        self.qserve_registry = QueryRegistry(
            grid, self.interner, cap_max=cap_max
        )
        self._bucket_dev: Dict[Tuple[str, int, int], Dict[str, Any]] = {}
        self._last_rung: Dict[Tuple[str, int, int], int] = {}

    @property
    def registry(self) -> QueryRegistry:
        return self.qserve_registry

    def _eval_bucket(self, kernel, mesh, xy_d, valid_d, cell_d, oid_d,
                     arrays, key, rung, cap, nseg, dtype):
        """Dispatch ONE bucket's vmapped program (mesh or single-chip)
        under its ``compute`` span — the bucket-evaluation unit the
        node-attribution scope tags."""
        with telemetry.span("compute", bucket=bucket_key_str(key)):
            if mesh is not None:
                from spatialflink_tpu.parallel.sharded \
                    import sharded_registry_bucket

                return sharded_registry_bucket(
                    mesh, xy_d, valid_d, cell_d,
                    arrays["tables"], oid_d,
                    arrays["qxy"], arrays["radius"],
                    arrays["qvalid"],
                    k=rung, num_segments=nseg,
                )
            return kernel(
                xy_d, valid_d, cell_d,
                arrays["tables"], oid_d,
                arrays["qxy"], arrays["radius"],
                arrays["qvalid"],
                k=rung, num_segments=nseg,
                query_block=min(cap, 32),
            )

    def _bucket_device_arrays(self, key, qs, cap, dtype):
        """Device-cached bucket operand set, keyed on (registry epoch,
        bucket version, rung, dtype) — churnless windows re-ship
        NOTHING; a register/unregister in the bucket bumps its version
        and rebuilds once."""
        reg = self.qserve_registry
        ck = (reg.epoch, reg.version(key), int(cap), np.dtype(dtype).str)
        hit = self._bucket_dev.get(key)
        if hit is not None and hit["ck"] == ck:
            return hit
        qxy, radius, qvalid, tables = bucket_host_arrays(
            self.grid, qs, cap, flags_of=lambda q: reg.flags(q.qid)
        )
        tables_d, radius_d, qvalid_d = ship(tables, radius, qvalid)
        dev = {
            "ck": ck,
            "qxy": self.device_q(qxy, dtype),  # centered like the points
            "tables": tables_d,
            "radius": radius_d,
            "qvalid": qvalid_d,
        }
        self._bucket_dev[key] = dev
        return dev

    def run(
        self,
        stream: Iterable,
        dtype=np.float64,
        mesh=None,
        driver=None,
    ) -> Iterator[QServeWindowResult]:
        """Serve the stream: commands apply at window fires (event-time
        order, exactly once), every bucket evaluates as one program, and
        results route per tenant under the per-class result budgets.
        ``driver=`` opts into checkpointing/retry exactly like the other
        operators; registry state rides the operator checkpoint."""
        from spatialflink_tpu.driver import strict_driver
        from spatialflink_tpu.ops.compaction import pick_capacity
        from spatialflink_tpu.ops.query_registry import (
            registry_bucket_kernel,
        )

        if self.conf.allowed_lateness_ms > 0:
            # The query_panes rule: a late-event REFIRE re-runs a
            # window already charged to the per-window QoS/overflow
            # accumulators (whose retry-idempotence markers only cover
            # consecutive re-charges), double-counting sheds — and the
            # applied-uid pruning horizon assumes refires reach at most
            # one window span back. Reject rather than drift.
            raise ValueError(
                "QServeOperator does not support allowed_lateness "
                "(late-window refires would double-charge the per-"
                "tenant shed and range-overflow accumulators)"
            )
        mesh = mesh if mesh is not None else self.mesh
        drv = driver if driver is not None else strict_driver()
        drv.attach(self)
        reg = self.qserve_registry
        if registry() is not reg:
            # Module slot for ledger/stream checkpoints — THIS run's
            # registry becomes the provider (a stale previous run's
            # counters must never ride this run's checkpoints), and it
            # stays installed for the seal (the driver-controller
            # idiom; tests clean the slot via qserve.uninstall()).
            install(reg)
        kernel = jitted(
            registry_bucket_kernel, "k", "num_segments", "query_block"
        )

        def process(win) -> QServeWindowResult:
            return self.serve_window(win, kernel, dtype=dtype, mesh=mesh)

        drv.bind(self, process, fallback=None)
        yield from drv.run(stream)

    def serve_window(self, win, kernel, dtype=np.float64,
                     mesh=None) -> QServeWindowResult:
        """Event-list entry of :meth:`serve_columns` — :meth:`run`'s
        process: the window's Points and commands as one list. Builds
        the same columns the composed DAG shares between its nodes
        (streams/columns.py) and serves from them."""
        return self.serve_columns(
            WindowColumns.from_events(win.events, self.interner),
            win.start, win.end, kernel, dtype=dtype, mesh=mesh,
        )

    def serve_columns(self, cols: WindowColumns, start: int, end: int,
                      kernel, dtype=np.float64,
                      mesh=None) -> QServeWindowResult:
        """One window's serving pass: apply the window's commands
        exactly once, evaluate every bucket as one program, ONE true
        sync for all buckets, per-tenant-class result budgets. The
        shared core of :meth:`run`'s process and the composed DAG's
        qserve node (dag.py) — both route retries through the
        retry-idempotent accumulators (record_range_overflow,
        tenant_result_allowance), so re-running a window is safe.

        ``cols`` is the window's columnar view over THIS operator's
        interner: its point rows (``Point`` or ``GpsEvent``) are the
        served batch, straight from the arrays — no ``Point`` is built,
        no list walked — and the ``QServeCommand``s among its
        ``others`` are the window's commands."""
        from spatialflink_tpu.ops.compaction import pick_capacity

        reg = self.qserve_registry
        cmds = sorted(
            (e for e in cols.others if isinstance(e, QServeCommand)),
            key=lambda c: (c.timestamp, c.uid),
        )
        n_events = len(cmds) + len(cols)
        with telemetry.span("window.qserve", start=start, events=n_events):
            for cmd in cmds:
                reg.apply(cmd)
            # The exactly-once uid set only needs to reach as far
            # back as a refire/resume can (one window span +
            # lateness + slide behind this fire) — prune beyond it
            # so checkpoints don't grow with lifetime command count.
            reg.prune_applied(
                start,
                self.conf.window_size_ms
                + self.conf.allowed_lateness_ms
                + self.conf.slide_step_ms,
            )
            buckets = reg.buckets()
            # Evict device arrays of buckets churn has emptied —
            # a dead bucket must not pin its (cap, num_cells+1)
            # tables in device memory for the rest of the run.
            for key in [k for k in self._bucket_dev
                        if k not in buckets]:
                del self._bucket_dev[key]
            rows: List[Tuple[str, str, str, Any, float]] = []
            win_overflow = 0
            if len(cols) and buckets:
                with telemetry.span("assemble"):
                    # Host batch in float64 (centering/casting happens
                    # at the device boundary, see point_batch).
                    batch = PointBatch.from_arrays(
                        cols.lonlat(), cols.ts, cols.oid,
                        dtype=np.float64,
                    ).with_cells(self.grid)
                    nseg = next_bucket(
                        max(self.interner.num_segments, 1),
                        minimum=64,
                    )
                with telemetry.span("ship"):
                    valid_d, cell_d, oid_d = ship(
                        batch.valid, batch.cell, batch.oid
                    )
                    xy_d = self.device_xy(batch, dtype)
                pending = []
                # Bucket-level attribution only when STANDALONE: under
                # the DAG the whole window already carries the "qserve"
                # node scope, and splintering it per bucket would break
                # the per-node conservation rollup into bucket shards.
                standalone = telemetry.current_node() is None
                for key in sorted(buckets):
                    qs = buckets[key]
                    bucket_node = (f"qserve:{bucket_key_str(key)}"
                                   if standalone else None)
                    with telemetry.scope(bucket_node):
                        cap = pick_capacity(
                            len(qs), reg.cap_max,
                            minimum=QUERY_RUNG_MIN
                        )
                        telemetry.record_compaction(
                            "qserve_bucket", cap, len(qs)
                        )
                        if self._last_rung.get(key) != cap:
                            # A rung move is one (bounded) XLA compile
                            # — worth an instant marker in the stream.
                            self._last_rung[key] = cap
                            telemetry.emit_instant(
                                f"qserve_rung:{bucket_key_str(key)}",
                                capacity=int(cap), live=len(qs),
                            )
                        arrays = self._bucket_device_arrays(
                            key, qs, cap, dtype
                        )
                        rung = int(key[1])
                        res = self._eval_bucket(
                            kernel, mesh, xy_d, valid_d, cell_d,
                            oid_d, arrays, key, rung, cap, nseg,
                            dtype,
                        )
                    pending.append((qs, res))
                # ONE true sync for ALL buckets (the flush_pending
                # idiom): every bucket's dispatch is in flight
                # before the window pays its single device→host
                # round trip — per-bucket fetches would serialize
                # ~bucket-count device syncs per window.
                with telemetry.span("fetch"):
                    fetched = telemetry.fetch([
                        (r.num_valid, r.within, r.segment, r.dist)
                        for _qs, r in pending
                    ])
                for (qs, _r), (nvs, within, segs, dists) in zip(
                        pending, fetched):
                    for lane, q in enumerate(qs):
                        nv = int(nvs[lane])
                        if q.kind == "range":
                            # Truncation against the QUERY's own
                            # result cap (k ≤ rung): any distinct
                            # in-radius object beyond the k rows
                            # returned is an incomplete range
                            # result, counted.
                            win_overflow += max(
                                int(within[lane]) - int(q.k), 0
                            )
                        for r_ in range(min(nv, int(q.k))):
                            rows.append((
                                q.tenant_class, q.tenant, q.qid,
                                self.interner.lookup(
                                    int(segs[lane, r_])
                                ),
                                float(dists[lane, r_]),
                            ))
            reg.record_range_overflow(start, win_overflow)
            # Per-tenant-class result budgets: each class keeps its
            # first `allowance` rows (deterministic bucket/qid/rank
            # order), the excess is counted against THE CLASS only.
            counts: Dict[str, int] = {}
            for row in rows:
                counts[row[0]] = counts.get(row[0], 0) + 1
            allow = {
                cls: overload.tenant_result_allowance(
                    cls, n, window_start=start)
                for cls, n in sorted(counts.items())
            }
            kept: List[Tuple[str, str, str, Any, float]] = []
            used: Dict[str, int] = {}
            for row in rows:
                used[row[0]] = used.get(row[0], 0) + 1
                if used[row[0]] <= allow[row[0]]:
                    kept.append(row)
            return QServeWindowResult(start, end, kept, n_events)



# -- module-level wiring (the telemetry/overload singleton idiom) --------------

_registry: Optional[QueryRegistry] = None


def install(reg: QueryRegistry) -> QueryRegistry:
    """Make ``reg`` the process-global registry:
    ``telemetry.snapshot()["qserve"]`` carries its counters on every
    ledger-stream checkpoint."""
    global _registry
    _registry = reg
    telemetry.qserve_provider = reg.snapshot
    return reg


def uninstall():
    global _registry
    if _registry is not None:
        telemetry.qserve_provider = None
    _registry = None


def registry() -> Optional[QueryRegistry]:
    return _registry


# -- SFT_QSERVE serving config -------------------------------------------------

_CONFIG_KEYS = ("queries", "tenant_budgets", "cap_max")


def config_from_env() -> Optional[Dict[str, Any]]:
    """``SFT_QSERVE``: inline JSON or a path to a JSON file (the
    SFT_FAULT_PLAN convention). Strict parse — an unknown key is a
    config typo, and a typo'd budget silently ignored is the worst
    failure mode a QoS config can have."""
    spec = os.environ.get("SFT_QSERVE")
    if not spec:
        return None
    text = spec.strip()
    if not text.startswith("{"):
        with open(text) as f:
            text = f.read()
    cfg = json.loads(text)
    if not isinstance(cfg, dict):
        raise ValueError(f"SFT_QSERVE must be a JSON object, got {cfg!r}")
    unknown = sorted(set(cfg) - set(_CONFIG_KEYS))
    if unknown:
        raise ValueError(
            f"unknown SFT_QSERVE keys: {unknown} (keys: {_CONFIG_KEYS})"
        )
    return cfg


def queries_from_config(cfg: Dict[str, Any]) -> List[StandingQuery]:
    return [StandingQuery(**d) for d in cfg.get("queries", [])]


def boot_commands(queries: List[StandingQuery],
                  timestamp: int = 0) -> List[QServeCommand]:
    """Registration commands for a static startup query set (uids are
    deterministic — replayable, so --checkpoint resumes stay exact)."""
    return [
        QServeCommand(timestamp=int(timestamp), action="register",
                      uid=f"boot:{q.qid}", query=q)
        for q in queries
    ]
