"""Trajectory-stream operators: tRange, tKnn, tJoin, tAggregate, tStats,
tFilter — the ``spatialOperators/t*`` families re-designed as segment
reductions over windowed batches.

Reference surface kept: ``TRangeQuery``, ``TKNNQuery``, ``TJoinQuery``,
``TAggregateQuery``, ``TStatsQuery``, ``TFilterQuery`` with the concrete
Point* aliases. Output objects mirror the reference's tuples (windowed
sub-trajectory LineStrings, per-cell aggregates, per-trajectory stats).
"""

from __future__ import annotations

import time
from dataclasses import dataclass
from typing import Dict, Iterable, Iterator, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from spatialflink_tpu.models.batch import PointBatch
from spatialflink_tpu.models.objects import LineString, Point, Polygon
from spatialflink_tpu.operators.base import (
    SpatialOperator,
    flags_for_queries,
    jitted,
    pack_query_geometries,
    ship,
    window_program,
)
from spatialflink_tpu.operators.join_query import (
    HeldJoin,
    JoinCapacity,
    _TaggedEvent,
    headroom_bucket,
    merge_by_timestamp,
)
from spatialflink_tpu.ops.join import head_pairs, pallas_join_supported
from spatialflink_tpu.telemetry import telemetry
from spatialflink_tpu.ops.knn import knn_points_fused
from spatialflink_tpu.ops.trajectory import (
    MAX_TRAJ_IDS,
    traj_cell_spans_kernel,
    traj_pair_dedup_kernel,
    traj_pair_ids,
    traj_range_hits_fused,
    traj_stats_kernel,
    traj_stats_sorted_fused,
)
from spatialflink_tpu.streams.windows import WindowBatch
from spatialflink_tpu.utils.padding import next_bucket


def sub_trajectory(events: Sequence[Point], obj_id: str, win_start: int) -> LineString:
    """Windowed sub-trajectory LineString: points of one objID sorted by ts
    (GenerateWindowedTrajectory, tJoin/TJoinQuery.java:165-192)."""
    pts = sorted(events, key=lambda p: p.timestamp)
    coords = np.array([[p.x, p.y] for p in pts], float)
    return LineString(obj_id=obj_id, timestamp=win_start, coords=coords)


def group_by_oid(events: Sequence[Point]) -> Dict[str, List[Point]]:
    groups: Dict[str, List[Point]] = {}
    for p in events:
        groups.setdefault(p.obj_id, []).append(p)
    return groups


# ---------------------------------------------------------------------------
# tRange


@dataclass
class TRangeResult:
    start: int
    end: int
    trajectories: List[LineString]  # one windowed sub-trajectory per hit objID
    window_count: int


class TRangeQuery(SpatialOperator):
    """Trajectory range vs polygon set: a trajectory qualifies if any of its
    window points lies inside any query polygon
    (tRange/TRangeQuery.java:33-63, PointPolygonTRangeQuery.java:53-177).
    Grid prefilter: only points whose cell is flagged for some polygon's
    gridIDsSet (radius 0 → candidate cells only) reach the containment test.
    """

    def run(
        self,
        stream: Iterable[Point],
        query_polygons: Sequence[Polygon],
        dtype=np.float64,
        mesh=None,
    ) -> Iterator[TRangeResult]:
        mesh = mesh if mesh is not None else self.mesh
        verts, ev = pack_query_geometries(query_polygons, np.float64)
        qv = self.device_verts(verts, dtype)
        qe = jnp.asarray(ev)

        def program(nseg):
            return window_program(
                mesh, traj_range_hits_fused, (0, 1, 2), 5,
                reduce=True, num_segments=nseg,
            )

        for win in self.windows(stream):
            batch = self.point_batch(win.events)
            nseg = next_bucket(max(self.interner.num_segments, 1), minimum=64)
            hits = np.asarray(
                program(nseg)(
                    self.device_xy(batch, dtype), jnp.asarray(batch.valid),
                    jnp.asarray(batch.oid), qv, qe,
                )
            )
            groups = group_by_oid(win.events)
            out = [
                sub_trajectory(evs, oid_str, win.start)
                for oid_str, evs in groups.items()
                if hits[self.interner.intern(oid_str)]
            ]
            yield TRangeResult(win.start, win.end, out, len(win.events))


    def run_soa(self, chunks, query_polygons: Sequence[Polygon],
                num_segments: int, dtype=np.float64):
        """SoA fast path: point chunks {"ts","x","y","oid"} (dense int32
        oids in [0, num_segments)) → per-window (start, end, hit_oids,
        window_count) — the containment + per-trajectory any-hit program
        of run() with no per-object Python."""
        from spatialflink_tpu.operators.base import (
            check_oid_range,
            soa_point_batches,
        )

        verts, ev = pack_query_geometries(query_polygons, np.float64)
        qv = self.device_verts(verts, dtype)
        qe = jnp.asarray(ev)
        program = jitted(traj_range_hits_fused, "num_segments")
        for win, xy, valid, cell, oid in soa_point_batches(
            self.grid, chunks, self.conf, dtype
        ):
            check_oid_range(oid[:win.count], num_segments)
            xy_d, valid_d, oid_d = ship(xy, valid, oid)
            hits = telemetry.fetch(program(
                xy_d, valid_d, oid_d, qv, qe, num_segments=num_segments,
            ))
            yield (win.start, win.end, np.flatnonzero(hits), win.count)


class PointPolygonTRangeQuery(TRangeQuery):
    """tRange/PointPolygonTRangeQuery.java."""


# ---------------------------------------------------------------------------
# tKnn


@dataclass
class TKnnResult:
    start: int
    end: int
    neighbors: List[Tuple[str, float, LineString]]  # (objID, minDist, sub-traj)
    window_count: int


class TKNNQuery(SpatialOperator):
    """k nearest trajectories to a query point: min distance per objID over
    the window, top-k objIDs, each materialized as its windowed
    sub-trajectory (tKnn/TKNNQuery.java:50-163,
    PointPointTKNNQuery.java:181-310). The reference's three extra shuffles
    (rejoin raw stream, per-objID window, global windowAll top-k) collapse
    into the kNN kernel + host sub-trajectory assembly.
    """

    def run(
        self,
        stream: Iterable[Point],
        query_point: Point,
        radius: float,
        k: int,
        dtype=np.float64,
        mesh=None,
    ) -> Iterator[TKnnResult]:
        mesh = mesh if mesh is not None else self.mesh
        flags = flags_for_queries(self.grid, radius, [query_point])
        flags_d = jnp.asarray(flags)
        q = self.device_q([query_point.x, query_point.y], dtype)

        def program(nseg):
            return window_program(
                mesh, knn_points_fused, (0, 1, 2, 4), 7,
                topk=True, k=k, num_segments=nseg,
            )

        for win in self.windows(stream):
            batch = self.point_batch(win.events)
            nseg = next_bucket(max(self.interner.num_segments, 1), minimum=64)
            res = program(nseg)(
                self.device_xy(batch, dtype), jnp.asarray(batch.valid),
                jnp.asarray(batch.cell), flags_d,
                jnp.asarray(batch.oid), q, radius,
            )
            groups = group_by_oid(win.events)
            out = []
            for i in range(int(res.num_valid)):
                oid_str = self.interner.lookup(int(res.segment[i]))
                out.append(
                    (oid_str, float(res.dist[i]),
                     sub_trajectory(groups[oid_str], oid_str, win.start))
                )
            yield TKnnResult(win.start, win.end, out, len(win.events))


    def run_soa(self, chunks, query_point: Point, radius: float, k: int,
                num_segments: int, dtype=np.float64):
        """High-rate SoA path: per window, the k nearest trajectories as
        (start, end, oids, min_dists, num_valid) arrays — the kNN kernel's
        per-objID segment-min IS the per-trajectory min distance
        (tKnn/PointPointTKNNQuery.java:181-310's deepest hot path), no
        object materialization."""
        from spatialflink_tpu.operators.base import soa_point_batches

        flags = flags_for_queries(self.grid, radius, [query_point])
        flags_d = jnp.asarray(flags)
        q = self.device_q([query_point.x, query_point.y], dtype)
        kern = jitted(knn_points_fused, "k", "num_segments")
        for win, xy, valid, cell, oid in soa_point_batches(
            self.grid, chunks, self.conf, dtype
        ):
            xy_d, valid_d, cell_d, oid_d = ship(xy, valid, cell, oid)
            res = kern(
                xy_d, valid_d, cell_d, flags_d, oid_d, q, radius,
                k=k, num_segments=num_segments,
            )
            nv = int(telemetry.fetch(res.num_valid))
            segs, dists = telemetry.fetch((res.segment[:nv], res.dist[:nv]))
            yield (win.start, win.end, segs, dists, nv)


class PointPointTKNNQuery(TKNNQuery):
    """tKnn/PointPointTKNNQuery.java."""


# ---------------------------------------------------------------------------
# tJoin


@dataclass
class TJoinResult:
    start: int
    end: int
    pairs: List[Tuple[LineString, LineString, float]]  # (traj, queryTraj, minDist)
    window_count: int


class TJoinQuery(JoinCapacity, SpatialOperator):
    """Trajectory join: trajectory pairs whose points come within r inside
    the window, each pair emitted once as paired windowed sub-trajectories
    (tJoin/TJoinQuery.java:60-154, PointPointTJoinQuery.java:183+).

    Dedup: the reference keeps the latest matching point pair per
    (traj, queryTraj) (TJoinQuery dedup map); here the pair's reported
    distance is the *minimum* point distance in the window — same pair set,
    a strictly more informative representative (documented deviation).
    ``run_single`` self-joins a stream (PointPointTJoinQuery.runSingle:57).

    **Exact on every window handed back** (``run`` and ``run_soa``): the
    point join runs under ``JoinCapacity``'s contract, shared with
    ``PointPointJoinQuery`` — the bucket capacity from the window's
    fullest cell (``cap`` is only its first rung), the pair budget with a
    quarter of headroom, a window either one fails to hold run again and
    never handed back short. The dedup is sparse (``ops/trajectory.py:
    traj_pair_dedup_kernel``: the pair list sorted by a packed (left id,
    right id) key and the distance, the run starts sorted to the front;
    nothing sized by the number of ids, at most 46,340 a side) and as
    long as the pair list, so it has nothing to overflow. The point pairs
    never leave the device: per window the scalars cross (pair count,
    overflow, peel passes, trajectory-pair count, one fetch), then the
    trajectory pairs in the padding bucket of their count (12 B each).

    ``mesh=`` executes the point-pair join shard_mapped (the dedup stage
    runs on the compacted pairs); under a mesh the capacity applies per
    shard.
    """

    def __init__(self, conf, grid, cap: int = 64, mesh=None):
        super().__init__(conf, grid, mesh=mesh)
        self._init_join_capacity(cap)
        #: Trajectory-pair budget: the largest count whose fetch programs
        #: (the padding buckets' slices) are compiled; grown with the pair
        #: budget's headroom policy, persists across windows.
        self.tpair_budget = 0

    def _tpairs_until_held(self, lcell, lvalid, rcell, rvalid,
                           num_ids: int, call, oids=None) -> HeldJoin:
        """The window's point join under the capacity and budget contract
        (``_join_until_held``) with the dedup program dispatched behind it,
        so that the join's scalars and the trajectory-pair count cross in
        one fetch. The dedup takes the pair list as trajectory ids: as the
        join hands it over where the extraction carried the id lanes as its
        payload (``run_soa``), else mapped from the points' indices through
        ``oids`` — the two sides' device id lanes — by ``traj_pair_ids``
        (``run``). The held join's ``followed`` is the ``TrajPairs`` (on the
        device), ``followed_scalars`` its count."""
        if num_ids > MAX_TRAJ_IDS:
            raise ValueError(
                f"{num_ids} trajectory ids a side: the dedup's int32 pair "
                f"key left · ids + right holds at most {MAX_TRAJ_IDS}"
            )
        dedup = jitted(traj_pair_dedup_kernel)
        ids = np.int32(num_ids)

        def follow(res):
            left, right = res.left_index, res.right_index
            if oids is not None:
                left, right = jitted(traj_pair_ids)(left, right, *oids)
            tp = dedup(left, right, res.dist, ids)
            return tp, (tp.count,)

        return self._join_until_held(lcell, lvalid, rcell, rvalid, call,
                                     follow)

    def _fetch_tpairs(self, tp, tcount: int):
        """The ``tcount`` trajectory pairs of ``tp`` on the host — (left
        ids, right ids, minimum distances), fetched in the padding bucket
        of their count and cut to it. A count past ``tpair_budget`` grows
        it (the pair budget's headroom policy) and compiles the slicing
        programs a count under the new budget asks for at once, not inside
        a later window."""
        head = jitted(head_pairs, "bucket")
        arrays = (tp.left_oid, tp.right_oid, tp.dist)
        lanes = len(tp.dist)
        if tcount > self.tpair_budget:
            self.tpair_budget = headroom_bucket(tcount)
            for b in (self.tpair_budget // 2, self.tpair_budget):
                head(*arrays, bucket=min(b, lanes))
        lo, ro, dd = telemetry.fetch(
            head(*arrays, bucket=min(next_bucket(tcount), lanes))
        )
        return lo[:tcount], ro[:tcount], dd[:tcount]

    def run(
        self,
        stream: Iterable[Point],
        query_stream: Iterable[Point],
        radius: float,
        dtype=np.float64,
        mesh=None,
    ) -> Iterator[TJoinResult]:
        from spatialflink_tpu.operators.join_query import grid_hash_join_batches

        mesh = mesh if mesh is not None else self.mesh
        merged = (
            _TaggedEvent(ev.timestamp, tag, ev)
            for tag, ev in merge_by_timestamp(stream, query_stream)
        )
        offsets = jnp.asarray(self.grid.neighbor_offsets(radius))

        for win in self.windows(merged):
            left_ev = [t.event for t in win.events if t.tag == 0]
            right_ev = [t.event for t in win.events if t.tag == 1]
            if not left_ev or not right_ev:
                yield TJoinResult(win.start, win.end, [], len(win.events))
                continue
            lb = self.point_batch(left_ev)
            rb = self.point_batch(right_ev)
            # Device-compacted point-pair join (Pallas extraction on TPU)
            # under PointPointJoinQuery's capacity and budget contract,
            # then per-(traj, traj) min distance + compaction on device —
            # the reference's dedup map (TJoinQuery.java:60-154) without
            # the per-matching-point host loop. The interner's ids are the
            # trajectory ids: no window-local relabel.
            self.join_budget = max(
                self.join_budget, 1024, min(4 * lb.capacity, 262_144)
            )
            loid, roid = ship(lb.oid, rb.oid)
            # the batches carry the key grid's cells and nothing finer
            self._open_join(
                radius, pallas=mesh is None and pallas_join_supported())
            held = self._tpairs_until_held(
                lb.cell, lb.valid, rb.cell, rb.valid,
                max(self.interner.num_segments, 1),
                lambda _refine, cap, budget: grid_hash_join_batches(
                    self.grid, lb, rb, radius, cap, offsets,
                    max_pairs=budget, dtype=dtype, mesh=mesh,
                ),
                oids=(loid, roid),
            )
            lgroups = group_by_oid(left_ev)
            rgroups = group_by_oid(right_ev)
            # Vectorized pair decode — the dedup'd pair list is the only
            # thing that crosses into Python (no per-point-pair loop).
            l_ids, r_ids, dists = self._fetch_tpairs(
                held.followed, *held.followed_scalars)
            found: List[Tuple[str, str, float]] = sorted(
                (self.interner.lookup(int(a)), self.interner.lookup(int(b)),
                 float(d))
                for a, b, d in zip(l_ids, r_ids, dists)
            )
            pairs = [
                (sub_trajectory(lgroups[a], a, win.start),
                 sub_trajectory(rgroups[b], b, win.start), d)
                for a, b, d in found
            ]
            yield TJoinResult(win.start, win.end, pairs, len(win.events))

    def run_single(self, stream, radius, dtype=np.float64):
        """Self-join: pairs within one stream, excluding identity pairs."""
        events = list(stream)
        for res in self.run(iter(events), iter(list(events)), radius, dtype=dtype):
            res.pairs = [
                (a, b, d) for a, b, d in res.pairs if a.obj_id != b.obj_id
            ]
            yield res

    def run_soa(
        self,
        left_chunks,
        right_chunks,
        radius: float,
        num_segments: int,
        max_pairs: int = 262_144,
        dtype=np.float64,
    ):
        """SoA fast path for tJoin: two point chunk streams
        {"ts","x","y","oid"} (dense int32 oids in [0, num_segments)) →
        per-window RAW trajectory-pair arrays
        (start, end, left_oids, right_oids, min_dists, count, overflow),
        the pairs ascending by (left id, right id) — the reference's
        windowBased tJoin (tJoin/PointPointTJoinQuery.java:183+) with zero
        per-point-pair Python: the grid-hash point join and the sparse
        per-trajectory-pair min-distance dedup (ops/trajectory.py:
        traj_pair_dedup_kernel) both run on device, the second fed the
        first's pairs where they lie — as trajectory ids: the extraction's
        bucket sort carries each point's id where it would carry its index
        (``_window_call``'s ``payload``), so no pair is mapped to its ids
        by a gather. Windows align on the shared slide grid; one-sided
        windows yield zero pairs.

        Exact on every yielded window (``overflow == 0``), as
        ``PointPointJoinQuery.run_soa`` and by the same code
        (``JoinCapacity``): capacity from the window's fullest cell, the
        pair budget (``max_pairs`` its first value) with a quarter of
        headroom over the last count, a window either fails to hold run
        again, never yielded short. The dedup's output is as long as the
        pair list and cannot overflow; ``tpair_budget`` (same headroom
        policy) is the count up to which the slicing programs of the fetch
        are compiled, a new one's at once and not inside a later window.
        What crosses to the host a window: one fetch of four scalars (pair
        count, overflow, peel passes, trajectory-pair count), then the
        trajectory pairs in the padding bucket of their count — left id,
        right id (int32), minimum distance: 12 B each. The point pairs
        never cross. ``num_segments`` at most 46,340 (the dedup's int32
        pair key).

        Both sides are assembled one window ahead on a producer thread, as
        ``PointPointJoinQuery.run_soa`` and by the same code
        (``join_query._aligned_soa_windows``): window n + 1's chunk pulls and
        assembly (``join.assemble_left``, ``join.assemble``, ``soa.*``,
        emitted on that thread) run while this loop ships, joins, dedups and
        fetches window n; the loop pulls no chunk, so a window's result goes
        out with no pull after its trigger. The capacity contract's state,
        every JAX call, ``record_tjoin`` and the op counters stay on the
        loop's thread.

        With telemetry on: one parent span ``tjoin.window`` a two-sided
        window (args ``n``: events of both sides), emitted by hand at the
        hand-back, from the moment the loop asks for the window to just
        before the yield; inside it ``join.await`` (the wait for the
        producer), ``tjoin.ids`` (the id-range check of both sides), ``h2d``,
        ``join.capacity``, ``dispatch:*`` (the extraction,
        ``traj_pair_dedup_kernel``, ``head_pairs``) and both ``d2h``; one
        ``record_tjoin`` a window (``snapshot()["tjoin"]``; its ``id_lanes``
        counts the windows whose extraction carried the ids). A one-sided
        window emits none."""
        from spatialflink_tpu.operators.base import check_oid_range
        from spatialflink_tpu.operators.join_query import (
            _aligned_soa_windows,
            _point_sides,
            _record_windows,
            window_join_program,
        )

        fn, self.last_join_backend = window_join_program()
        self._open_join(
            radius, refinable=True,
            pallas=self.last_join_backend == "pallas", dtype=dtype,
        )
        self.join_budget = max(self.join_budget, max_pairs)
        empty = (np.empty(0, np.int32), np.empty(0, np.int32), np.empty(0))
        for kind, wl, wr, asked_ns in _aligned_soa_windows(
            left_chunks, right_chunks,
            *_point_sides(self.grid, self.conf, dtype),
            lambda w: w[0].start, lambda w: w[0].start,
        ):
            _record_windows(wl, wr)
            if kind != "both":
                w = wl[0] if kind == "left" else wr[0]
                yield (w.start, w.end, *empty, 0, 0)
                continue
            win, lxy, lvalid, lcell, loid = wl
            rwin, rxy, rvalid, rcell, roid = wr
            with telemetry.span("tjoin.ids"):
                check_oid_range(loid[:win.count], num_segments)
                check_oid_range(roid[:rwin.count], num_segments)
            # Ship once, outside the retry loops: re-runs reuse the same
            # (immutable) device buffers instead of re-crossing the link,
            # and bytes_h2d counts each lane exactly once.
            (lxy_d, lvalid_d, lcell_d, loid_d,
             rxy_d, rvalid_d, rcell_d, roid_d) = ship(
                lxy, lvalid, lcell, loid, rxy, rvalid, rcell, roid
            )
            # The extraction carries the id lanes where it would carry the
            # points' indices: its pairs come out as trajectory ids.
            held = self._tpairs_until_held(
                lcell, lvalid, rcell, rvalid, num_segments,
                self._window_call(
                    fn, (lxy_d, lvalid_d, lcell_d),
                    (rxy_d, rvalid_d, rcell_d), radius,
                    payload=(loid_d, roid_d),
                ),
            )
            (tcount,) = held.followed_scalars
            lo, ro, dd = self._fetch_tpairs(held.followed, tcount)
            telemetry.record_tjoin(
                pairs=held.count, tpairs=tcount,
                cap_retries=held.cap_retries,
                budget_retries=held.budget_retries, cap=self.join_cap,
                budget=self.join_budget, tpair_budget=self.tpair_budget,
                peel_passes=held.peel_passes, refine=self.join_refine,
                id_lanes=True,
            )
            self._grow_budget(held.count)  # headroom for the next window
            if asked_ns is not None:
                telemetry.emit_span(
                    "tjoin.window", asked_ns,
                    time.perf_counter_ns() - asked_ns,
                    n=win.count + rwin.count,
                )
            yield (win.start, win.end, lo, ro, dd, tcount, 0)

    def run_soa_panes(
        self,
        left_chunks,
        right_chunks,
        radius: float,
        num_segments: int,
        cap_w: int = 64,
        pair_sel: int = 16,
        dtype=np.float64,
        mesh=None,
        backend: str = "auto",
        cap_c: Optional[int] = None,
        driver=None,
    ):
        """Extreme-overlap sliding tJoin via the device pane-carry engine
        (ops/tjoin_panes.py): window state lives ON DEVICE in ring-buffer
        bucket planes, each slide does O(new-pane) join work, and the
        whole bounded stream runs as ONE ``lax.scan`` dispatch — the
        10s/10ms configs (ppw = 1000) stop paying the ppw× full-window
        recompute of ``run_soa``. Yields the same per-window tuples
        (start, end, left_oids, right_oids, min_dists, count, overflow)
        with identical pair sets/min dists (parity test) — pairs ordered
        by flat pair key rather than dedup compaction order.

        Bounded streams only (the retry contract re-scans with doubled
        ``cap_w``/``pair_sel`` on overflow). In-order events; windows
        fire when they contain ≥1 event on either side (the assembler
        contract). Digest memory = ppw·num_segments²·4 bytes — sized
        for the domain's dozens-to-hundreds of vehicles; a guard raises
        past ~2 GB rather than OOMing the device.

        ``mesh`` (defaults to the operator's): probe-parallel execution
        over the ``data`` axis — pane points shard, window/digest state
        replicates, contributions all-gather per slide
        (ops/tjoin_panes.py). Bit-identical to single-device
        (tests/test_parallel_operators.py).

        ``backend``: "auto" routes to the NATIVE C++ engine on CPU hosts
        (native/sfnative.cpp:sf_tjoin_panes — per-cell lists with
        amortized expiry, no cap/sel budgets, exact by construction;
        the same device/native split as traj_stats_sliding) and to the
        device scan on TPU or when ``mesh`` is set; "device"/"native"
        force a path (forced-native raises if the library is missing —
        never silently measures the other engine). Native min-distances
        match the x64 device engine to 1e-12 (FMA contraction freedom).

        ``cap_c``: the device scan's live-slot probe capacity
        (ops/tjoin_panes.py compacted probe). Default None lets the
        host control plane pick the bucket: exact per-cell window
        occupancy (ops/compaction.py:max_window_cell_count) → smallest
        capacity-ladder rung, recorded in telemetry — the scan then
        probes O(live-rounded-up) slots per neighbor cell instead of
        O(cap_w), compiling at most ladder-many (≤6) programs across
        any occupancy mix. 0 forces the full-ring probe (the
        TPU-preferred form and the compaction parity oracle); an
        explicit positive value seeds the ladder but the cmp_overflow
        retry still climbs it if the pick was too small — exactness
        always wins over a forced bucket.

        ``driver``: window emission routed through the shared dataflow
        driver (spatialflink_tpu/driver.py:run_precomputed) — the
        checkpointed position counts FIRED WINDOWS, and a resume (after
        this method deterministically re-runs the scan over the
        replayed bounded chunks) skips the already-committed prefix.
        Without one, a strict driver reproduces the old plain loop
        exactly. An active overload ``pane_backend`` degradation rung
        (overload.py) biases ``backend="auto"`` toward the native
        engine when it is available; forced backends are never
        overridden.
        """
        from spatialflink_tpu.operators.base import check_oid_range, jitted
        from spatialflink_tpu.ops.tjoin_panes import (
            tjoin_pane_init,
            tjoin_pane_scan,
        )
        from spatialflink_tpu.utils.padding import next_bucket as _nb

        conf = self.conf
        mesh = mesh if mesh is not None else self.mesh
        size, slide = conf.window_size_ms, conf.slide_step_ms
        if size % slide != 0:
            raise ValueError("run_soa_panes requires size % slide == 0")
        if conf.allowed_lateness_ms > 0:
            raise ValueError(
                "run_soa_panes does not support allowed_lateness; use "
                "run_soa()"
            )
        ppw = size // slide
        g = self.grid
        import jax as _jax

        # Honor the requested dtype with the usual effective-f64 rule
        # (operators/base.py:center_coords): an f64 request without x64
        # lands as f32 on device, so prep in f32 from the start.
        f_dtype = np.dtype(dtype)
        if f_dtype == np.float64 and not _jax.config.jax_enable_x64:
            f_dtype = np.dtype(np.float32)
        budget = ppw * num_segments * num_segments * 4
        if budget > 2 << 30:
            raise ValueError(
                f"pane digest memory ppw·K² = {budget / 1e9:.1f} GB "
                "exceeds the 2 GB guard; reduce num_segments or overlap"
            )

        def collect(chunks):
            ts = []
            xs = []
            ys = []
            oids = []
            for ch in chunks:
                ts.append(np.asarray(ch["ts"], np.int64))
                xs.append(np.asarray(ch["x"], np.float64))
                ys.append(np.asarray(ch["y"], np.float64))
                oids.append(np.asarray(ch["oid"], np.int32))
            if not ts:
                z = np.zeros(0)
                return z.astype(np.int64), z, z, z.astype(np.int32)
            return (np.concatenate(ts), np.concatenate(xs),
                    np.concatenate(ys), np.concatenate(oids))

        lt, lx, ly, lo = collect(left_chunks)
        rt, rx, ry, ro = collect(right_chunks)
        check_oid_range(lo, num_segments)
        check_oid_range(ro, num_segments)
        if len(lt) == 0 and len(rt) == 0:
            return
        all_t = np.concatenate([lt, rt])
        p_first = int(all_t.min() // slide)
        p_last = int(all_t.max() // slide)
        # Trailing empty panes flush the windows that still contain the
        # last events (the assembler's end-of-stream flush).
        n_slides = (p_last - p_first + 1) + (ppw - 1)
        # The scan stacks an (n_slides, K²) wmins output on device —
        # it scales with the stream's TIME SPAN, not ppw; guard it like
        # the digest (raise, don't OOM). Long streams: call in chunks.
        out_bytes = n_slides * num_segments * num_segments * 4
        if out_bytes > 2 << 30:
            raise ValueError(
                f"pane scan output n_slides·K² = {out_bytes / 1e9:.1f} GB "
                f"exceeds the 2 GB guard ({n_slides} slides); feed the "
                "stream in shorter bounded chunks or reduce num_segments"
            )

        def pane_fields(t_arr, x_arr, y_arr, o_arr):
            """Per-pane padded (S, PC) field arrays + per-pane counts."""
            pane = (t_arr // slide - p_first).astype(np.int64)
            order = np.argsort(pane, kind="stable")
            pane_s = pane[order]
            counts = np.bincount(pane_s, minlength=n_slides).astype(np.int64)
            pc = int(_nb(max(int(counts.max()) if len(counts) else 1, 1),
                         minimum=8))
            if mesh is not None:  # pane points shard over the data axis
                nd = int(mesh.shape["data"])
                pc = ((pc + nd - 1) // nd) * nd
            S = n_slides
            fx = np.zeros((S, pc), f_dtype)
            fy = np.zeros((S, pc), f_dtype)
            fo = np.zeros((S, pc), np.int32)
            fv = np.zeros((S, pc), bool)
            fxi = np.zeros((S, pc), np.int32)
            fyi = np.zeros((S, pc), np.int32)
            fcell = np.zeros((S, pc), np.int32)
            frank = np.zeros((S, pc), np.int32)
            starts = np.concatenate([[0], np.cumsum(counts)])
            lane = np.arange(len(t_arr)) - starts[pane_s]
            from spatialflink_tpu.operators.base import center_coords

            xy = np.stack([x_arr, y_arr], axis=1)
            cxy = center_coords(g, xy, f_dtype)
            xi = np.floor((x_arr - g.min_x) / g.cell_length).astype(np.int64)
            yi = np.floor((y_arr - g.min_y) / g.cell_length).astype(np.int64)
            ing = (xi >= 0) & (xi < g.n) & (yi >= 0) & (yi < g.n)
            cell = np.where(ing, xi * g.n + yi, 0).astype(np.int32)
            fx[pane_s, lane] = cxy[order, 0]
            fy[pane_s, lane] = cxy[order, 1]
            fo[pane_s, lane] = o_arr[order]
            fv[pane_s, lane] = ing[order]
            fxi[pane_s, lane] = xi[order].astype(np.int32)
            fyi[pane_s, lane] = yi[order].astype(np.int32)
            fcell[pane_s, lane] = cell[order]
            if with_ranks:
                # Ring-slot ranks are a DEVICE-engine input (fixed-cap
                # scatter slots); the native engine's dynamic per-cell
                # lists need none — skip the per-batch grouping sort.
                from spatialflink_tpu.ops.tjoin_panes import pane_cell_ranks

                frank[pane_s, lane] = pane_cell_ranks(
                    pane_s, cell[order], valid=ing[order]
                ).astype(np.int32)
            ing_s = ing[order]
            occ_in = (pane_s[ing_s], cell[order][ing_s])
            return (fx, fy, fxi, fyi, fcell, frank, fo, fv), counts, occ_in

        if backend not in ("auto", "device", "native"):
            raise ValueError(f"unknown tjoin panes backend {backend!r}")
        use_native = False
        if backend == "native" or (backend == "auto" and mesh is None):
            from spatialflink_tpu import native as _native
            from spatialflink_tpu.streams.panes import (
                _device_backend_preferred,
            )

            native_ok = _native.available()
            if backend == "native":
                if mesh is not None:
                    raise ValueError(
                        "backend='native' cannot run on a mesh"
                    )
                if not native_ok:
                    raise RuntimeError(
                        "backend='native' was forced but the native "
                        "library is unavailable (build native/ with "
                        "make) — refusing to silently run the device "
                        "engine instead"
                    )
                use_native = True
            else:
                # An active overload ``pane_backend`` rung biases auto
                # toward the native engine (frees the loaded device
                # path); a missing library keeps the device engine — a
                # degradation rung must never turn into a crash.
                from spatialflink_tpu import overload as _overload

                prefer_native = _overload.pane_backend() == "native"
                use_native = native_ok and (
                    prefer_native or not _device_backend_preferred()
                )

        with_ranks = not use_native
        lfields, lcounts, locc_in = pane_fields(lt, lx, ly, lo)
        rfields, rcounts, rocc_in = pane_fields(rt, rx, ry, ro)
        layers = g.candidate_layers(radius)

        occ = None
        if not use_native:
            from spatialflink_tpu.ops.compaction import (
                compact_probe_preferred,
                max_window_cell_count,
                pick_capacity,
            )

            if cap_c is None:
                if compact_probe_preferred():
                    # Host control plane: exact live-occupancy bound →
                    # ladder rung. Reading the live counts here is the
                    # point — the device program only ever sees the
                    # static bucket.
                    with telemetry.span("compaction.plan",
                                        engine="tjoin_pane_scan"):
                        occ = max(
                            max_window_cell_count(*locc_in, ppw),
                            max_window_cell_count(*rocc_in, ppw),
                        )
                        cap_c = pick_capacity(occ, cap_w)
                    telemetry.record_compaction(
                        "tjoin_pane_scan", cap_c, occ
                    )
                else:
                    cap_c = 0  # full-ring row-gather probe (TPU form)

        if use_native:
            def flat(fields):
                fx, fy, _xi, _yi, fcell, _rank, fo, fv = fields
                m = fv.ravel()
                S, pc = fv.shape
                pane = np.repeat(
                    np.arange(S, dtype=np.int32), pc
                )[m]
                return (pane, fx.ravel()[m], fy.ravel()[m],
                        fcell.ravel()[m], fo.ravel()[m])

            wmins = _native.tjoin_panes_native(
                *flat(lfields), *flat(rfields),
                n_slides, g.n, layers, ppw, num_segments, radius,
            )
        else:
            wmins = None
        scan = jitted(
            tjoin_pane_scan,
            "grid_n", "cap_w", "layers", "ppw", "num_ids", "pair_sel",
            "cap_c", "mesh",
        )

        def run_scan(carry, statics):
            """One full (monolithic) scan pass over the batch's panes."""
            ts_dev = jnp.asarray(np.arange(n_slides, dtype=np.int32))
            if mesh is not None:
                # Mesh scans route through the ACCOUNTED parallel/
                # entry: its host side feeds the all-gather/psum
                # footprint to telemetry.account_collective from
                # static shapes (the collective-accounting
                # invariant), then runs the same cached program.
                from spatialflink_tpu.parallel.sharded import (
                    sharded_tjoin_pane_scan,
                )

                return sharded_tjoin_pane_scan(
                    mesh, carry, ts_dev,
                    tuple(jnp.asarray(a) for a in lfields),
                    tuple(jnp.asarray(a) for a in rfields),
                    radius,
                    **{k: v for k, v in statics.items()
                       if k != "mesh"},
                )
            return scan(
                carry, ts_dev,
                tuple(jnp.asarray(a) for a in lfields),
                tuple(jnp.asarray(a) for a in rfields),
                radius, **statics,
            )

        while wmins is None:  # device engine + overflow retry
            carry = tjoin_pane_init(
                g.num_cells, cap_w, ppw, num_segments,
                jnp.dtype(f_dtype),
            )
            # Pane indices are REBASED to 0 (the panes.py int32 lesson:
            # absolute epoch-ms pane indices ~1.7e11 overflow int32);
            # the kernel's ring/alive logic is shift-invariant and the
            # host maps slide s back to absolute time below.
            final, wmins = run_scan(carry, dict(
                grid_n=g.n, cap_w=cap_w, layers=layers, ppw=ppw,
                num_ids=num_segments, pair_sel=pair_sel, cap_c=cap_c,
                mesh=mesh,
            ))
            cap_over = int(final.cap_overflow)
            sel_over = int(final.sel_overflow)
            cmp_over = int(final.cmp_overflow)
            if cap_over == 0 and sel_over == 0 and cmp_over == 0:
                break
            # Bounded-stream retry: grow whichever budget overflowed and
            # re-scan (same idiom as the pruned joins' _pruned_block_pairs).
            wmins = None  # this scan's output is inexact — re-scan
            if cap_over:
                cap_w *= 2
                if occ is not None:  # ladder re-pick under the new cap
                    cap_c = pick_capacity(occ, cap_w)
            if sel_over:
                pair_sel *= 2
            if cmp_over and cap_c:
                # A probed cell held more live points than the bucket
                # (only reachable with a forced/stale cap_c — the
                # host-planned pick is exact): climb the ladder. The
                # true occupancy was never measured, only that it
                # exceeded the old rung — record that LOWER BOUND, not
                # a fabricated live count (code review).
                live_floor = cap_c + 1
                cap_c = min(max(cap_c * 2, cap_c + 1), cap_w)
                telemetry.record_compaction(
                    "tjoin_pane_scan", cap_c, live_floor
                )

        wmins = np.asarray(wmins)  # (S, K²)
        # Rolling per-side window event counts decide which windows fire.
        def rolling_counts(c):
            cc = np.concatenate([[0], np.cumsum(c)])
            lo_i = np.maximum(np.arange(n_slides) - ppw + 1, 0)
            return cc[np.arange(n_slides) + 1] - cc[lo_i]

        lwin = rolling_counts(lcounts)
        rwin = rolling_counts(rcounts)

        def decode(s) -> tuple:
            t_pane = p_first + s
            start = (t_pane - ppw + 1) * slide
            row = wmins[s]
            hit = np.nonzero(np.isfinite(row))[0]
            return (
                start, start + size,
                (hit // num_segments).astype(np.int32),
                (hit % num_segments).astype(np.int32),
                row[hit].astype(np.float64),
                int(len(hit)), 0,
            )

        # Window emission through the shared dataflow driver: the scan
        # above is deterministic over the (bounded, replayed) chunks, so
        # a resumed run recomputes it and the driver skips the windows
        # already committed — run_precomputed's contract. The default
        # strict driver reproduces the old plain yield loop bit-for-bit.
        from spatialflink_tpu.driver import strict_driver

        drv = driver if driver is not None else strict_driver()
        drv.attach(self)
        drv.bind(self, decode)
        fired = (s for s in range(n_slides)
                 if lwin[s] != 0 or rwin[s] != 0)
        yield from drv.run_precomputed(fired)


class PointPointTJoinQuery(TJoinQuery):
    """tJoin/PointPointTJoinQuery.java."""


# ---------------------------------------------------------------------------
# tAggregate


@dataclass
class TAggregateResult:
    """Per-cell heatmap entry: (cellName, count, {objID: temporalLen} or
    {'' : aggregate}) — the reference's Tuple4<gridID, count, map, latency>
    (TAggregateQuery.java:150-250)."""

    start: int
    end: int
    cells: Dict[str, Tuple[int, Dict[str, int]]]
    window_count: int


class TAggregateQuery(SpatialOperator):
    """Per-cell trajectory temporal-length heatmap with ALL/SUM/AVG/MIN/MAX
    aggregates and inactive-trajectory deletion
    (tAggregate/TAggregateQuery.java:53-250; windowed variant
    PointTAggregateQuery.java:63+).

    Continuous state (the reference's MapState) is carried across windows as
    numpy arrays keyed by interned (cell, objID) pairs; each window updates
    it with one segment-reduction kernel over the batch.
    """

    def __init__(self, conf, grid, aggregate: str = "SUM",
                 inactive_threshold_ms: int = 0, mesh=None):
        super().__init__(conf, grid, mesh=mesh)
        if aggregate.upper() not in ("ALL", "SUM", "AVG", "MIN", "MAX"):
            raise ValueError(f"bad aggregate {aggregate!r}")
        self.aggregate = aggregate.upper()
        self.inactive_threshold_ms = inactive_threshold_ms
        # MapState analog as parallel sorted arrays keyed by
        # cell << 32 | interned objID — merged per window with vectorized
        # numpy (round 1's per-pair Python dict merge capped throughput).
        self._skeys = np.empty(0, np.int64)
        self._smin = np.empty(0, np.int64)
        self._smax = np.empty(0, np.int64)

    def run(self, stream: Iterable[Point], dtype=np.float64,
            mesh=None) -> Iterator[TAggregateResult]:
        mesh = mesh if mesh is not None else self.mesh
        for win in self.windows(stream):
            batch = self.point_batch(win.events)
            n = len(win.events)
            self._ingest_window(
                batch.ts, batch.cell, batch.oid, batch.valid, n, mesh
            )
            yield self._aggregate_state(win)

    def _ingest_window(self, ts_p, cell_p, oid_p, valid_p, n, mesh=None):
        """One window's (cell, objID) span reduction merged into the
        MapState-analog arrays, incl. inactive-trajectory deletion
        (TAggregateQuery.deleteHalted…) — shared by run()/run_soa()."""
        key64 = (
            cell_p[:n].astype(np.int64) << 32
        ) | oid_p[:n].astype(np.int64)
        uniq_keys, inverse = np.unique(key64, return_inverse=True)
        pair_id = np.zeros(len(valid_p), np.int32)
        pair_id[:n] = inverse.astype(np.int32)
        num_pairs = next_bucket(len(uniq_keys), minimum=64)
        spans = window_program(
            mesh, traj_cell_spans_kernel, (0, 1, 2), 3,
            reduce=True, num_pairs=num_pairs,
        )(jnp.asarray(ts_p), jnp.asarray(pair_id), jnp.asarray(valid_p))
        mn = np.asarray(spans.min_ts)[: len(uniq_keys)]
        mx = np.asarray(spans.max_ts)[: len(uniq_keys)]
        self._merge_state(uniq_keys, mn, mx)
        if self.inactive_threshold_ms > 0 and len(mx):
            horizon = max(int(mx.max()), 0) - self.inactive_threshold_ms
            keep = self._smax >= horizon
            self._skeys = self._skeys[keep]
            self._smin = self._smin[keep]
            self._smax = self._smax[keep]

    def run_soa(self, chunks, dtype=np.float64):
        """SoA fast path: point chunks {"ts","x","y","oid"} (dense int32
        oids) → per-window TAggregateResult with the same MapState-carry
        semantics as run(); in ALL mode the per-trajectory keys are the
        dense int ids (the chunk contract's id space — callers own the
        string mapping)."""
        from spatialflink_tpu.operators.base import soa_point_batches
        from spatialflink_tpu.utils.padding import pad_to_bucket

        for win, xy, valid, cell, oid in soa_point_batches(
            self.grid, chunks, self.conf, dtype
        ):
            ts_p = pad_to_bucket(
                np.asarray(win.arrays["ts"], np.int64), len(valid)  # sfcheck: ok=recompile-surface -- `valid` is already bucket-padded by device_point_args; len(valid) IS the ladder bucket, not a raw count
            )
            self._ingest_window(ts_p, cell, oid, valid, win.count)
            yield self._aggregate_state(win, lookup=str)

    def _merge_state(self, keys: np.ndarray, mn: np.ndarray, mx: np.ndarray):
        """min/max-merge the window's (key, span) table into the sorted
        state arrays — all vectorized (searchsorted + boolean masks)."""
        pos = np.searchsorted(self._skeys, keys)
        in_range = pos < len(self._skeys)
        hit = np.zeros(len(keys), bool)
        hit[in_range] = self._skeys[pos[in_range]] == keys[in_range]
        hp = pos[hit]
        np.minimum.at(self._smin, hp, mn[hit])
        np.maximum.at(self._smax, hp, mx[hit])
        if (~hit).any():
            order_keys = np.concatenate([self._skeys, keys[~hit]])
            order = np.argsort(order_keys, kind="stable")
            self._skeys = order_keys[order]
            self._smin = np.concatenate([self._smin, mn[~hit]])[order]
            self._smax = np.concatenate([self._smax, mx[~hit]])[order]

    def _aggregate_state(self, win, lookup=None) -> TAggregateResult:
        lookup = lookup if lookup is not None else self.interner.lookup
        count = len(win.events) if hasattr(win, "events") else win.count
        out: Dict[str, Tuple[int, Dict[str, int]]] = {}
        if not len(self._skeys):
            return TAggregateResult(win.start, win.end, out, count)
        cells = (self._skeys >> 32).astype(np.int64)
        oids = (self._skeys & 0xFFFFFFFF).astype(np.int64)
        lens = self._smax - self._smin
        # State is key-sorted, so cells are grouped: reduce per contiguous run.
        starts = np.flatnonzero(np.r_[True, cells[1:] != cells[:-1]])
        ends = np.r_[starts[1:], len(cells)]
        for s, e in zip(starts, ends):
            cell = int(cells[s])
            name = (
                self.grid.cell_name(cell)
                if cell < self.grid.num_cells else "out"
            )
            cnt = int(e - s)
            seg = lens[s:e]
            if self.aggregate == "ALL":
                out[name] = (cnt, {
                    lookup(int(o)): int(v)
                    for o, v in zip(oids[s:e], seg)
                })
            elif self.aggregate == "SUM":
                out[name] = (cnt, {"": int(seg.sum())})
            elif self.aggregate == "AVG":
                out[name] = (cnt, {"": round(float(seg.sum()) / cnt)})
            elif self.aggregate == "MIN":
                i = int(np.argmin(seg))
                out[name] = (cnt, {lookup(int(oids[s + i])): int(seg[i])})
            else:  # MAX
                i = int(np.argmax(seg))
                out[name] = (cnt, {lookup(int(oids[s + i])): int(seg[i])})
        return TAggregateResult(win.start, win.end, out, count)


class PointTAggregateQuery(TAggregateQuery):
    """tAggregate/PointTAggregateQuery.java."""


# ---------------------------------------------------------------------------
# tStats


@dataclass
class TStatsResult:
    """Per-trajectory stats per window: the reference's
    Tuple4<objID, spatialLength, temporalLength, spatial/temporal>
    (TStatsQuery.java:137-144)."""

    start: int
    end: int
    stats: Dict[str, Tuple[float, int, float]]  # objID → (spatial, temporal, ratio)
    window_count: int


class TStatsQuery(SpatialOperator):
    """Running spatial/temporal length + avg speed per trajectory
    (tStats/TStatsQuery.java:44-189).

    WindowBased recomputes per window (the WFunction variant); RealTime
    carries running totals across micro-batches like the ValueState
    flatmap, including its drop-out-of-order behavior (only timestamps
    strictly greater than the last seen advance the state).
    """

    def __init__(self, conf, grid, mesh=None):
        super().__init__(conf, grid, mesh=mesh)
        self._running: Dict[str, Tuple[float, int, int, float, float]] = {}
        # oid → (spatial, temporal, last_ts, last_x, last_y)

    def run(self, stream: Iterable[Point], dtype=np.float64,
            mesh=None, driver=None) -> Iterator[TStatsResult]:
        """Window loop lifted into the shared dataflow driver
        (spatialflink_tpu/driver.py): pass ``driver=`` to OPT INTO
        auto-checkpointing, retry-with-backoff, and device→numpy
        failover. Without one, a strict driver reproduces the old plain
        loop exactly — errors propagate immediately, nothing degrades.
        """
        from spatialflink_tpu.driver import strict_driver
        from spatialflink_tpu.operators.query_config import QueryType

        mesh = mesh if mesh is not None else self.mesh
        realtime = self.conf.query_type in (QueryType.RealTime, QueryType.RealTimeNaive)
        kern = jax.jit(traj_stats_kernel, static_argnames=("num_segments",))

        def process(win) -> TStatsResult:
            if realtime:
                # Arrival order matters: the ValueState flatmap drops
                # out-of-order tuples as they arrive (TStatsQuery.java:118).
                return self._realtime_update(win, win.events)
            with telemetry.span(
                "window.tstats", start=win.start, events=len(win.events)
            ):
                events = sorted(win.events,
                                key=lambda p: (p.obj_id, p.timestamp))
                batch = PointBatch.from_points(events, interner=self.interner,
                                               dtype=np.float64)
                nseg = next_bucket(max(self.interner.num_segments, 1),
                                   minimum=64)
                ts_d, oid_d, valid_d = ship(
                    batch.ts, batch.oid, batch.valid
                )
                if mesh is not None:
                    # Sequence-parallel: (oid, ts)-sorted points sharded over
                    # the data axis, shard-boundary pairs recovered by the
                    # ppermute halo (parallel/sharded.py:sharded_traj_stats).
                    from spatialflink_tpu.parallel.sharded import (
                        sharded_traj_stats,
                    )

                    sp, tp, cnt, _speed = sharded_traj_stats(
                        mesh,
                        self.device_q(batch.xy, dtype),
                        ts_d, oid_d, valid_d,
                        num_segments=nseg,
                    )
                    spatial, temporal, count = telemetry.fetch((sp, tp, cnt))
                else:
                    res = kern(
                        self.device_q(batch.xy, dtype),
                        ts_d, oid_d, valid_d,
                        num_segments=nseg,
                    )
                    spatial, temporal, count = telemetry.fetch(
                        (res.spatial_length, res.temporal_length, res.count)
                    )
                return self._decode_window(win, events, spatial, temporal,
                                           count)

        if realtime:
            # The ValueState flatmap mutates per-oid running state as it
            # walks events — re-running a half-applied window would
            # double-count. Mark it so a configured driver never retries
            # it (driver.py honors `idempotent = False`); there is no
            # fallback either, for the same reason.
            process.idempotent = False
        fallback = None if realtime else self._numpy_window_process(dtype)
        drv = driver if driver is not None else strict_driver()
        drv.bind(self, process, fallback=fallback)
        if self.conf.query_type == QueryType.CountBased:
            from spatialflink_tpu.operators.base import count_window_batches

            yield from drv.run_windows(count_window_batches(
                stream, self.conf.count_window_size,
                self.conf.count_window_size,
            ))
        else:
            yield from drv.run(stream)

    def _numpy_window_process(self, dtype):
        """Numpy twin of the windowed device path — the driver's failover
        route. Same (oid, ts) sort, same centered/cast coordinates
        (operators/base.center_coords), same segment sums, so a
        mid-stream backend switch changes no results
        (tests/test_driver.py pins parity)."""
        from spatialflink_tpu.operators.base import center_coords

        def process(win) -> TStatsResult:
            events = sorted(win.events, key=lambda p: (p.obj_id, p.timestamp))
            batch = PointBatch.from_points(events, interner=self.interner,
                                           dtype=np.float64)
            nseg = next_bucket(max(self.interner.num_segments, 1), minimum=64)
            n = len(events)
            xy = center_coords(self.grid, batch.xy[:n], dtype)
            oid = np.asarray(batch.oid[:n], np.int64)
            ts = np.asarray(batch.ts[:n], np.int64)
            spatial = np.zeros(nseg, xy.dtype)
            temporal = np.zeros(nseg, xy.dtype)
            count = np.bincount(oid, minlength=nseg) if n else \
                np.zeros(nseg, np.int64)
            if n > 1:
                same = oid[1:] == oid[:-1]
                d = xy[1:] - xy[:-1]
                seg_d = np.sqrt(np.sum(d * d, axis=-1))
                np.add.at(spatial, oid[1:], np.where(same, seg_d, 0))
                np.add.at(temporal, oid[1:],
                          np.where(same, (ts[1:] - ts[:-1]).astype(xy.dtype),
                                   0))
            return self._decode_window(win, events, spatial, temporal, count)

        return process

    def _decode_window(self, win, events, spatial, temporal, count) -> TStatsResult:
        stats = {}
        for oid_str in {p.obj_id for p in events}:
            i = self.interner.intern(oid_str)
            if count[i] > 0:
                t = int(temporal[i])
                stats[oid_str] = (
                    float(spatial[i]), t,
                    float(spatial[i] / t) if t > 0 else 0.0,
                )
        return TStatsResult(win.start, win.end, stats, len(win.events))

    def run_soa(self, chunks, num_segments: int, dtype=np.float64):
        """High-rate SoA path: chunks of {"ts","x","y","oid"} arrays →
        per-window (start, end, spatial, temporal, count) arrays indexed by
        dense oid. The (oid, ts) sort happens ON DEVICE
        (traj_stats_sorted_fused) — no per-event Python objects or host
        sorting anywhere (the round-1 throughput cap)."""
        from spatialflink_tpu.operators.base import soa_point_batches
        from spatialflink_tpu.ops.counters import counters

        kern = jitted(traj_stats_sorted_fused, "num_segments")
        for win, xy, valid, cell, oid in soa_point_batches(
            self.grid, chunks, self.conf, dtype
        ):
            n = win.count
            if counters.enabled and n > 1:
                # The sorted kernel evaluates one candidate distance per
                # adjacent lane pair (masked off across trajectory breaks).
                counters.record_candidates(n - 1, n - 1)
            ts = np.zeros(len(valid), np.int64)
            ts[:n] = np.asarray(win.arrays["ts"], np.int64)
            xy_d, ts_d, oid_d, valid_d = ship(xy, ts, oid, valid)
            res = kern(
                xy_d, ts_d, oid_d, valid_d, num_segments=num_segments,
            )
            spatial, temporal, count = telemetry.fetch(
                (res.spatial_length, res.temporal_length, res.count)
            )
            yield (win.start, win.end, spatial, temporal, count)

    def _realtime_update(self, win, events) -> TStatsResult:
        stats = {}
        for p in events:
            st = self._running.get(p.obj_id)
            if st is None:
                self._running[p.obj_id] = (0.0, 0, p.timestamp, p.x, p.y)
            else:
                spatial, temporal, last_ts, lx, ly = st
                if p.timestamp > last_ts:  # drop out-of-order (TStatsQuery.java:118)
                    spatial += float(np.hypot(p.x - lx, p.y - ly))
                    temporal += p.timestamp - last_ts
                    self._running[p.obj_id] = (spatial, temporal, p.timestamp, p.x, p.y)
            spatial, temporal, *_ = self._running[p.obj_id]
            stats[p.obj_id] = (
                spatial, temporal, spatial / temporal if temporal > 0 else 0.0
            )
        return TStatsResult(win.start, win.end, stats, len(events))


class PointTStatsQuery(TStatsQuery):
    """tStats windowed/realtime variants for point streams."""


# ---------------------------------------------------------------------------
# tFilter


@dataclass
class TFilterResult:
    start: int
    end: int
    trajectories: List[LineString]
    window_count: int


class TFilterQuery(SpatialOperator):
    """Keep only the given trajectory IDs; emit windowed sub-trajectories
    (tFilter/PointTFilterQuery.java:50-122). Pure host control plane —
    there is no geometry to compute."""

    def run(
        self, stream: Iterable[Point], traj_ids: Sequence[str]
    ) -> Iterator[TFilterResult]:
        wanted = set(traj_ids)
        for win in self.windows(stream):
            groups = group_by_oid([p for p in win.events if p.obj_id in wanted])
            out = [
                sub_trajectory(evs, oid, win.start) for oid, evs in sorted(groups.items())
            ]
            yield TFilterResult(win.start, win.end, out, len(win.events))

    def run_soa(self, chunks, traj_ids: Sequence[int]):
        """SoA fast path: per window, the selected trajectories as sorted
        arrays — (start, end, oids (m,), ts (m,), xy (m, 2), count) with
        rows lexsorted by (oid, ts), ready for vectorized sub-trajectory
        slicing. ``traj_ids`` are the dense int ids of the chunk contract."""
        from spatialflink_tpu.ops.counters import counters
        from spatialflink_tpu.streams.soa import SoaWindowAssembler

        wanted = np.asarray(sorted(traj_ids), np.int32)
        asm = SoaWindowAssembler(
            self.conf.window_size_ms, self.conf.slide_step_ms,
            ooo_ms=self.conf.allowed_lateness_ms,
        )
        for win in asm.stream(chunks):
            if counters.enabled:
                counters.record_window(win.count, 0, 0)
            oid = np.asarray(win.arrays["oid"], np.int32)
            keep = np.isin(oid, wanted)
            # Mask BEFORE the float64 conversion: typical filters keep a
            # tiny fraction of the window.
            ts = np.asarray(win.arrays["ts"][keep], np.int64)
            xy = np.stack(
                [np.asarray(win.arrays["x"][keep], np.float64),
                 np.asarray(win.arrays["y"][keep], np.float64)],
                axis=1,
            )
            o = oid[keep]
            order = np.lexsort((ts, o))
            yield (
                win.start, win.end, o[order], ts[order], xy[order],
                win.count,
            )


class PointTFilterQuery(TFilterQuery):
    """tFilter/PointTFilterQuery.java."""
