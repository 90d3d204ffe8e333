"""Range-query operators — the 9-class (stream-type × query-type) matrix of
``spatialOperators/range/`` re-designed as batched TPU window programs.

API parity: ``XYRangeQuery(conf, grid).run(stream, query_set, radius)``
yields per-window results (the reference returns a DataStream of matched
objects per window firing; RealTime mode yields per micro-batch).

The GeoFlink pruning semantics are preserved per class:
  - point streams: per-point cell flag gather → guaranteed emit / candidate
    exact distance (range/RangeQuery.java:37-145, PointPointRangeQuery.java);
  - polygon/linestring streams: per-object flag = max flag over the cells
    its bbox overlaps (the reference replicates objects per overlapped cell
    and filters per cell — same set semantics, no replication here).
"""

from __future__ import annotations

import functools
import time
from dataclasses import dataclass
from typing import Iterable, Iterator, List, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from spatialflink_tpu.grid import UniformGrid
from spatialflink_tpu.models.batch import GeometryBatch, PointBatch
from spatialflink_tpu.models.objects import LineString, Point, Polygon, SpatialObject
from spatialflink_tpu.operators.base import (
    SpatialOperator,
    assembled_ahead,
    center_coords,
    count_window_batches,
    flags_for_queries,
    jitted,
    pack_cell_candidates,
    pack_cell_edges,
    pack_entry_edges,
    pack_query_geometries,
    pack_query_points,
    ship,
    soa_point_batches,
    window_program,
)
from spatialflink_tpu.ops.range import (
    geometry_range_query_kernel,
    range_points_fused,
    range_polygons_fused,
    range_polylines_fused,
)
from spatialflink_tpu.telemetry import telemetry


#: the polygon kernels that read the grid index
INDEXED = ("pruned", "pruned_compact", "cell_major")


@dataclass
class RangeResult:
    """One fired window's matches."""

    start: int
    end: int
    objects: List[SpatialObject]
    dists: np.ndarray
    window_count: int  # events in the window before filtering


class _PointStreamRangeQuery(SpatialOperator):
    """Point stream vs {point, polygon, linestring} query set."""

    query_kind = "point"

    def __init__(self, conf, grid, mesh=None):
        super().__init__(conf, grid, mesh)
        # The pruned polygon kernels' sizes: the width K of the newest
        # query set's cell table (read off the set when its evaluator is
        # built), and the compact kernel's lane budget, which persists
        # across windows and runs: crowded data pays its re-run once.
        self._ncand = 8
        self._cand_budget = 4096
        #: the kernel the newest evaluator built: "points", "polylines",
        #: or for a polygon set "dense", "pruned", "pruned_compact",
        #: "cell_major"
        self.last_range_kernel = None

    def _window_evaluator(self, query_set, flags, radius, dtype, mesh):
        """Build ``(launch, settle)`` for this family's query kind — ONE
        place for kernel selection, query packing, the polygon grid index
        and the compact kernel's budget re-run (the budget persists on the
        operator). Shared by run() and run_soa().

        ``launch(common)`` dispatches the window's program and returns its
        device outputs; ``settle(out, common)`` fetches them and returns
        host ``(keep, dist, budget_retries, edge_slots)`` — ``edge_slots``
        the polygon edges the program evaluated, padding included (0 for a
        point or linestring set). The compact kernel's overflow scalar and
        the cell-major kernel's edge count cross WITH keep and dist, in the
        one ``telemetry.fetch``; while the budget did not hold, settle grows
        it and launches again — the one re-run left: nothing else can
        overflow.

        Polygon selection: large exact-mode query sets go through a cell →
        candidate-polygons table, built here, once per query set, in float64
        on the host (``pack_cell_candidates``), laid out as the edge planes
        the kernel gathers one row of per point (``pack_cell_edges``, on the
        centred and cast rings) and shipped in one counted crossing before
        the first window. Its width K — the fullest cell's list on the
        bucket ladder — is a property of the query set, so a crowded set
        costs a wider K from the start and never a re-run; ``self._ncand``
        and the gauge ``cand`` report it. Sparse candidate unions (<25%
        flag occupancy) additionally compact candidate lanes first.

        Where a K-wide row would hold more than ``CELL_MAJOR_ROW`` edge
        slots (K × E, E the most edges of a polygon of the set) the index
        is read the other way round (``cell_major``): each cell's list a
        contiguous run of one edge table, the window's points sorted by
        cell on the device and a tile of them evaluated against its cells'
        run. A K-wide row costs every point K·E edge slots and the table
        ``cells × 4·E·K`` floats; a tile costs the steps of its cells' run,
        and a tile whose cells list nothing costs nothing of its own — so
        the occupancy test is not asked there: it counts the flagged cells,
        not what their lists hold. Both read the same lists and give the
        same kept lanes.
        Approximate mode stays dense — its keep-set ignores distances, so
        min-over-candidates dists would diverge from the dense min-over-all
        on kept lanes.
        """
        approx = self.conf.approximate_query

        def settle_plain(out, common):
            keep, dist = telemetry.fetch(out)
            return keep, dist, 0, 0

        if self.query_kind == "point":
            self.last_range_kernel = "points"
            pk = window_program(
                mesh, range_points_fused, (0, 1, 2), 6, approximate=approx
            )
            q = self.device_q(pack_query_points(query_set, np.float64), dtype)
            return (lambda common: pk(*common, q, radius)), settle_plain

        verts, ev = pack_query_geometries(query_set, np.float64)
        if self.query_kind == "linestring":
            self.last_range_kernel = "polylines"
            lk = window_program(
                mesh, range_polylines_fused, (0, 1, 2), 7, approximate=approx
            )
            qv, qe = self.device_q(verts, dtype), jnp.asarray(ev)
            return (lambda common: lk(*common, qv, qe, radius)), settle_plain

        use_pruned = len(query_set) >= 64 and mesh is None and not approx
        if not use_pruned:
            self.last_range_kernel = "dense"
            polyk = window_program(
                mesh, range_polygons_fused, (0, 1, 2), 7, approximate=approx
            )
            qv, qe = self.device_q(verts, dtype), jnp.asarray(ev)
            slots = len(query_set) * ev.shape[1]

            def settle_dense(out, common):
                keep, dist = telemetry.fetch(out)
                return keep, dist, 0, common[0].shape[0] * slots

            return ((lambda common: polyk(*common, qv, qe, radius)),
                    settle_dense)

        from spatialflink_tpu.ops.range import (
            CELL_MAJOR_ROW,
            CELL_MAJOR_TILE,
            CELL_MAJOR_WIDTH,
            POINT_CHUNK,
            range_polygons_cell_major_fused,
            range_polygons_pruned_compact_fused,
            range_polygons_pruned_fused,
        )

        # The grid index, once per query set: K is read off it here, before
        # the first window, so the program's shapes are settled in the
        # warm-up. Counted crossings, as the rings take on the other paths.
        index = pack_cell_candidates(self.grid, verts, ev, radius)
        self._ncand = index.slots
        centred = center_coords(self.grid, verts, dtype)
        # a K-wide row's edge slots: K polygons of E edges (the most any
        # polygon of the set has), what every point evaluates through it
        row_slots = index.slots * max(int(ev.sum(axis=1).max(initial=0)), 1)

        if row_slots > CELL_MAJOR_ROW:
            self.last_range_kernel = "cell_major"
            edges = pack_entry_edges(index.polys, centred, ev,
                                     CELL_MAJOR_WIDTH)
            # a step: the tile against ``width`` entries of 4 · E planes
            step_slots = CELL_MAJOR_TILE * CELL_MAJOR_WIDTH * (
                edges.shape[1] // 4)
            tables = [self.device_table(a) for a in (edges, index.starts)]
            telemetry.record_range_index(
                index.slots, index.entries, index.cells,
                sum(a.nbytes for a in tables))
            cellk = jitted(range_polygons_cell_major_fused, "tile", "width",
                           "batch", "approximate")

            def settle_cells(out, common):
                keep, dist, steps = telemetry.fetch(out)
                return keep, dist, 0, int(steps) * step_slots

            return (lambda common: cellk(
                *common, *tables, radius, tile=CELL_MAJOR_TILE,
                width=CELL_MAJOR_WIDTH)), settle_cells

        cell_edges = self.device_table(pack_cell_edges(index.table, centred,
                                                       ev))
        telemetry.record_range_index(index.slots, index.entries, index.cells,
                                     cell_edges.nbytes)

        if float((flags > 0).mean()) >= 0.25:
            self.last_range_kernel = "pruned"
            prunedk = jitted(
                range_polygons_pruned_fused, "point_chunk", "approximate"
            )

            def settle_pruned(out, common):
                keep, dist = telemetry.fetch(out)
                lanes = -(-common[0].shape[0] // POINT_CHUNK) * POINT_CHUNK
                return keep, dist, 0, lanes * row_slots

            return (
                lambda common: prunedk(*common, cell_edges, radius)
            ), settle_pruned

        self.last_range_kernel = "pruned_compact"
        compactk = jitted(
            range_polygons_pruned_compact_fused, "budget", "point_chunk"
        )

        def launch(common):
            return compactk(
                *common, cell_edges, radius, budget=self._cand_budget
            )

        def settle(out, common):
            budget_retries = 0
            edge_slots = self._cand_budget * row_slots
            while True:
                keep, dist, b_over = telemetry.fetch(out)
                if int(b_over) == 0:
                    return keep, dist, budget_retries, edge_slots
                need = self._cand_budget + int(b_over)
                self._cand_budget = int(2 ** np.ceil(np.log2(need)))
                budget_retries += 1
                out = launch(common)
                edge_slots += self._cand_budget * row_slots

        return launch, settle

    def run(
        self,
        stream: Iterable[Point],
        query_set: Sequence[SpatialObject],
        radius: float,
        dtype=np.float64,
        mesh=None,
        driver=None,
    ) -> Iterator[RangeResult]:
        """Window loop lifted into the shared dataflow driver
        (spatialflink_tpu/driver.py): pass ``driver=`` to OPT INTO
        auto-checkpointing, retry-with-backoff, and device→numpy
        failover. Without one, a strict driver reproduces the old plain
        loop exactly — errors propagate immediately, nothing degrades.
        """
        mesh = mesh if mesh is not None else self.mesh
        if not isinstance(query_set, (list, tuple)):
            query_set = [query_set]
        flags = flags_for_queries(self.grid, radius, query_set)

        from spatialflink_tpu.driver import strict_driver
        from spatialflink_tpu.ops.counters import count_candidates, counters

        # Attach (= load any checkpoint) BEFORE touching the device: a
        # run resumed after failover (backend "fallback") means the
        # device path already died — often a dead device, where even the
        # setup transfers below would hang the resume at a device_put.
        drv = driver if driver is not None else strict_driver()
        drv.attach(self)
        launch = settle = flags_d = None
        if drv.backend == "device":
            flags_d = jnp.asarray(flags)
            launch, settle = self._window_evaluator(query_set, flags, radius,
                                                    dtype, mesh)

        def process(win) -> RangeResult:
            # assemble → ship → compute → fetch phase spans (see
            # knn_query.run); yield outside the window span.
            with telemetry.span(
                "window.range", start=win.start, events=len(win.events)
            ):
                with telemetry.span("assemble"):
                    batch = self.point_batch(win.events)
                    if counters.enabled:
                        cand = count_candidates(
                            flags, batch.cell, len(win.events)
                        )
                        counters.record_window(
                            len(win.events), cand, cand * len(query_set)
                        )
                with telemetry.span("ship"):
                    valid_d, cell_d = ship(
                        batch.valid, batch.cell
                    )
                    common = (
                        self.device_xy(batch, dtype),
                        valid_d,
                        cell_d,
                        flags_d,
                    )
                with telemetry.span("compute"):
                    out = launch(common)
                with telemetry.span("fetch"):
                    keep, dist, _, _ = settle(out, common)
                idx = np.nonzero(keep)[0]
                objs = [win.events[i] for i in idx]
                return RangeResult(
                    win.start, win.end, objs, dist[idx], len(win.events)
                )

        fallback = None
        if self.query_kind == "point":
            fallback = self._numpy_window_process(query_set, flags, radius,
                                                  dtype)
        drv.bind(self, process if drv.backend == "device" else None,
                 fallback=fallback)
        from spatialflink_tpu.operators.query_config import QueryType

        if self.conf.query_type == QueryType.CountBased:
            yield from drv.run_windows(count_window_batches(
                stream, self.conf.count_window_size,
                self.conf.count_window_size,
            ))
        else:
            yield from drv.run(stream)

    def _numpy_window_process(self, query_set, flags, radius, dtype):
        """The numpy twin of the point-kind device path — the driver's
        failover route. Same math as ops/range.py:range_points_fused on
        the SAME centered/cast coordinates (operators/base.center_coords)
        so a mid-stream backend switch changes no results
        (tests/test_driver.py pins parity)."""
        from spatialflink_tpu.operators.base import center_coords

        q_host = center_coords(
            self.grid, pack_query_points(query_set, np.float64), dtype
        )
        approx = self.conf.approximate_query

        def process(win) -> RangeResult:
            batch = self.point_batch(win.events)
            n = len(win.events)
            xy = center_coords(self.grid, batch.xy[:n], dtype)
            d = xy[:, None, :] - q_host[None, :, :]
            min_dist = np.sqrt(np.sum(d * d, axis=-1)).min(axis=1)
            f = flags[batch.cell[:n]]
            hit = (f == 1) if approx else ((f == 1) & (min_dist <= radius))
            keep = batch.valid[:n] & ((f == 2) | hit)
            idx = np.nonzero(keep)[0]
            return RangeResult(
                win.start, win.end, [win.events[i] for i in idx],
                min_dist[idx], n,
            )

        return process

    def run_partitioned(
        self,
        stream: Iterable[Point],
        query_set: Sequence[SpatialObject],
        radius: float,
        mesh,
        dtype=np.float64,
        driver=None,
    ) -> Iterator[RangeResult]:
        """Grid-partitioned scale-out route (parallel/halo.py): window
        state lives sharded by contiguous flat-cell range and only
        boundary-cell query panes halo-exchange — no per-window
        broadcast of the query set. Point query sets only (the per-pair
        layer math needs a cell per query lane).

        The partition plan is placed on the operator BEFORE the driver
        attaches, so a ``--checkpoint`` resume restores the CHECKPOINTED
        plan (checkpoint.py validates the shard count) and re-dispatches
        onto the same placement. Results are decoded exactly like
        ``run()``'s; distances come from the per-pair kernel
        (ops/halo.py — PARITY.md "Grid-partitioned placement" notes the
        measure-zero radius-tie deviation from the flag-table path).
        """
        if self.query_kind != "point":
            raise ValueError(
                "run_partitioned supports point query sets only "
                f"(operator query_kind is {self.query_kind!r})"
            )
        from spatialflink_tpu.driver import strict_driver
        from spatialflink_tpu.parallel.halo import sharded_range_halo
        from spatialflink_tpu.parallel.partition import plan_partition

        if not isinstance(query_set, (list, tuple)):
            query_set = [query_set]
        n_shards = int(mesh.shape["data"])
        self.partition_plan = plan_partition(self.grid, n_shards, radius)
        drv = driver if driver is not None else strict_driver()
        drv.attach(self)  # may adopt a checkpointed plan (same shards)
        plan = self.partition_plan
        q_xy = pack_query_points(query_set, np.float64)
        q_cell = self.grid.assign_cells_np(q_xy)
        q_valid = np.ones(len(query_set), bool)
        approx = self.conf.approximate_query

        def process(win) -> RangeResult:
            with telemetry.span(
                "window.range_halo", start=win.start,
                events=len(win.events),
            ):
                batch = self.point_batch(win.events)
                n = len(win.events)
                ts = np.fromiter(
                    (e.timestamp for e in win.events), np.int64, count=n,
                )
                keep, dist = sharded_range_halo(
                    mesh, plan, batch.xy[:n].astype(dtype),
                    batch.valid[:n], batch.cell[:n],
                    q_xy.astype(dtype), q_cell, q_valid, radius,
                    approximate=approx, ts=ts,
                )
                idx = np.nonzero(keep)[0]
                return RangeResult(
                    win.start, win.end, [win.events[i] for i in idx],
                    dist[idx], n,
                )

        drv.bind(self, process)
        yield from drv.run(stream)

    def run_soa(
        self,
        chunks,
        query_set: Sequence[SpatialObject],
        radius: float,
        dtype=np.float64,
    ):
        """High-rate SoA path: chunks of {"ts","x","y",...} arrays →
        per-window (start, end, matched_arrays, dists), where
        ``matched_arrays`` is the window's SoA sliced down to the matching
        events (so callers get the actual matches, not just a count).
        Works for every query kind of the family (point / polygon /
        linestring query sets), with run()'s exact kernel selection —
        including the pruned/compact large-polygon-set paths.

        The window is assembled one window ahead on the producer thread
        ``range-assembly`` (``base.assembled_ahead``): window n + 1's chunk
        pulls and ``soa_point_batches`` (``range.assemble``, ``soa.*``,
        emitted on that thread) run while this loop ships, runs, fetches and
        selects window n; the loop pulls no chunk, so a window's result goes
        out with no pull after its trigger. The op counters, every JAX call,
        ``range.select`` and ``record_range`` stay on the loop's thread, and
        the results are the synchronous loop's.

        With telemetry on, one parent span ``range.window`` a window (args
        ``n``), emitted by hand at the hand-back: from the moment the loop
        asks for the window to just before the yield, so it holds
        ``range.await`` (the wait for the producer: near nothing when the
        window was assembled beside the last one), ``h2d``, ``dispatch:*``,
        ``d2h`` and ``range.select`` and none of the consumer's time;
        ``record_range_prefetch`` counts the windows that were ready when
        asked for (``snapshot()["range"]["prefetched"]``)."""
        if not isinstance(query_set, (list, tuple)):
            query_set = [query_set]
        flags = flags_for_queries(self.grid, radius, query_set)
        flags_d = jnp.asarray(flags)
        launch, settle = self._window_evaluator(query_set, flags, radius,
                                                dtype, mesh=None)
        kernel = self.last_range_kernel
        from spatialflink_tpu.ops.counters import count_candidates, counters

        def windows(pulls):
            return soa_point_batches(self.grid, pulls, self.conf, dtype,
                                     span="range.assemble", counted=False)

        for (win, xy, valid, cell, _), asked_ns in assembled_ahead(
            (chunks,), windows, "range-assembly", "range.await",
            lambda _item: telemetry.record_range_prefetch(),
        ):
            if counters.enabled:
                counters.record_window(win.count, 0, 0)
                cand = count_candidates(flags, cell, win.count)
                counters.record_candidates(cand, cand * len(query_set))
            # ship/fetch through telemetry: the oid lane is NOT shipped on
            # this path, so accounting at the ship site keeps bytes_h2d
            # honest; settle's fetch is the path's one crossing back a
            # window (one more a budget re-run).
            common = (*ship(xy, valid, cell), flags_d)
            keep, dist, budget_retries, edge_slots = settle(launch(common),
                                                            common)
            n = win.count
            with telemetry.span("range.select") as sp:
                idx = np.nonzero(np.asarray(keep)[:n])[0]
                matched = {k: np.asarray(v)[idx]
                           for k, v in win.arrays.items()}
                dists = np.asarray(dist)[:n][idx]
                if telemetry.enabled:
                    sp.args["matches"] = len(idx)
            telemetry.record_range(
                points=n, lanes=len(valid), matches=len(idx),
                cand_retries=0, budget_retries=budget_retries,
                cand=self._ncand if kernel in INDEXED else 0,
                budget=self._cand_budget if kernel == "pruned_compact" else 0,
                edge_slots=edge_slots,
            )
            if asked_ns is not None:
                telemetry.emit_span(
                    "range.window", asked_ns,
                    time.perf_counter_ns() - asked_ns, n=n,
                )
            yield win.start, win.end, matched, dists


class PointPointRangeQuery(_PointStreamRangeQuery):
    """range/PointPointRangeQuery.java (realtime :44-108, window :111-187)."""

    query_kind = "point"

    def query_incremental(
        self,
        stream: Iterable[Point],
        query_point: Point,
        radius: float,
        dtype=np.float64,
    ) -> Iterator[RangeResult]:
        """Incremental sliding-window variant (PointPointRangeQuery.java:195-296):
        per window, previously-qualified results are re-emitted from carried
        state; the distance kernel only evaluates the window's NEWEST slide
        pane (ts >= end - slide). Carried results older than start + slide
        are dropped. Per-window device work shrinks from O(window) to
        O(slide).

        Semantics caveats (inherent to the carry protocol, same as the
        reference's Java incremental variant): events arriving out of order
        by more than one slide step miss their pane evaluation and are
        dropped, so results equal ``run()`` only for in-order streams; and
        allowed-lateness refires would double-emit carried results, so a
        non-zero ``allowed_lateness`` is rejected.
        """
        if self.conf.allowed_lateness_ms > 0:
            raise ValueError(
                "query_incremental does not support allowed_lateness "
                "(late-window refires would double-emit carried results); "
                "use run() for late-tolerant streams"
            )
        flags = flags_for_queries(self.grid, radius, [query_point])
        flags_d = jnp.asarray(flags)
        pk = jitted(range_points_fused, "approximate")
        q = self.device_q([[query_point.x, query_point.y]], dtype)
        slide_ms = self.conf.slide_step_ms
        carry: List[tuple] = []  # (event, dist)

        for win in self.windows(stream):
            objects: List[SpatialObject] = []
            dists: List[float] = []
            next_carry = []
            for ev, d in carry:
                if win.start <= ev.timestamp < win.end:
                    objects.append(ev)
                    dists.append(d)
                    if ev.timestamp >= win.start + slide_ms:
                        next_carry.append((ev, d))
            new_events = [
                e for e in win.events if e.timestamp >= win.end - slide_ms
            ]
            if new_events:
                batch = self.point_batch(new_events)
                valid_d, cell_d = ship(batch.valid, batch.cell)
                keep, dist = pk(
                    self.device_xy(batch, dtype), valid_d, cell_d, flags_d,
                    q, radius, approximate=self.conf.approximate_query,
                )
                keep = np.asarray(keep)
                dist = np.asarray(dist)
                for i in np.nonzero(keep)[0]:
                    ev, d = new_events[i], float(dist[i])
                    objects.append(ev)
                    dists.append(d)
                    if ev.timestamp >= win.start + slide_ms:
                        next_carry.append((ev, d))
            carry = next_carry
            yield RangeResult(
                win.start, win.end, objects, np.asarray(dists), len(win.events)
            )




class PointPolygonRangeQuery(_PointStreamRangeQuery):
    """range/PointPolygonRangeQuery.java:31-160 (bbox-approx mode at :76-80
    becomes the ``approximate_query`` flag)."""

    query_kind = "polygon"


class PointLineStringRangeQuery(_PointStreamRangeQuery):
    """range/PointLineStringRangeQuery.java."""

    query_kind = "linestring"


class _GeometryStreamRangeQuery(SpatialOperator):
    """Polygon/LineString stream vs {point, polygon, linestring} query set."""

    query_kind = "point"
    stream_polygonal = True

    def _kernel_statics(self):
        return dict(
            approximate=self.conf.approximate_query,
            obj_polygonal=self.stream_polygonal,
            query_polygonal=self.query_kind == "polygon",
        )

    def _query_arrays(self, query_set):
        """(qverts, qev) for the packed query set — points become
        degenerate 2-vertex polylines. Shared by run() and run_soa()."""
        if self.query_kind == "point":
            q = pack_query_points(query_set, np.float64)
            return (
                np.repeat(q[:, None, :], 2, axis=1),
                np.ones((len(query_set), 1), bool),
            )
        return pack_query_geometries(query_set, np.float64)

    def run(
        self,
        stream: Iterable[Polygon | LineString],
        query_set: Sequence[SpatialObject],
        radius: float,
        dtype=np.float64,
        mesh=None,
    ) -> Iterator[RangeResult]:
        mesh = mesh if mesh is not None else self.mesh
        if not isinstance(query_set, (list, tuple)):
            query_set = [query_set]
        flags = flags_for_queries(self.grid, radius, query_set)
        statics = self._kernel_statics()
        if mesh is not None:
            from spatialflink_tpu.parallel.sharded import sharded_window_kernel

            gk = sharded_window_kernel(
                mesh, geometry_range_query_kernel, (0, 1, 2, 3), 7, **statics
            )
        else:
            gk = functools.partial(
                jitted(
                    geometry_range_query_kernel,
                    "approximate", "obj_polygonal", "query_polygonal",
                ),
                **statics,
            )
        qverts, qev = self._query_arrays(query_set)
        qv, qe = self.device_verts(qverts, dtype), jnp.asarray(qev)

        from spatialflink_tpu.models.batch import flag_prefix_planes

        prefix = flag_prefix_planes(self.grid, flags)
        for win in self.windows(stream):
            with telemetry.span(
                "window.range_geometry", start=win.start,
                events=len(win.events),
            ):
                batch = self.geometry_batch(win.events, mesh=mesh)
                oflags = batch.any_cell_flagged(
                    self.grid, flags, prefix=prefix
                )
                ev_d, valid_d, oflags_d = ship(
                    batch.edge_valid, batch.valid, oflags
                )
                keep, dist = gk(
                    self.device_verts(batch.verts, dtype),
                    ev_d, valid_d, oflags_d, qv, qe, radius,
                )
                keep, dist = telemetry.fetch((keep, dist))
                idx = np.nonzero(keep)[0]
                objs = [win.events[i] for i in idx]
                out = RangeResult(
                    win.start, win.end, objs, dist[idx], len(win.events)
                )
            yield out

    def run_soa(
        self,
        chunks,
        query_set: Sequence[SpatialObject],
        radius: float,
        dtype=np.float64,
    ):
        """Ragged-SoA fast path: geometry chunks
        ``{"ts","oid","lengths","verts"}`` (packed single boundary chains,
        dense int32 oids) → per-window (start, end, kept_indices,
        kept_oids, dists, window_count) arrays through the SAME fused
        kernel as ``run()`` with zero per-object Python
        (GeometryBatch.from_ragged + RaggedSoaWindowAssembler)."""
        from spatialflink_tpu.models.batch import flag_prefix_planes
        from spatialflink_tpu.streams.soa import RaggedSoaWindowAssembler

        if not isinstance(query_set, (list, tuple)):
            query_set = [query_set]
        flags = flags_for_queries(self.grid, radius, query_set)
        gk = functools.partial(
            jitted(
                geometry_range_query_kernel,
                "approximate", "obj_polygonal", "query_polygonal",
            ),
            **self._kernel_statics(),
        )
        qverts, qev = self._query_arrays(query_set)
        qv, qe = self.device_verts(qverts, dtype), jnp.asarray(qev)

        prefix = flag_prefix_planes(self.grid, flags)
        asm = RaggedSoaWindowAssembler(
            self.conf.window_size_ms, self.conf.slide_step_ms,
            ooo_ms=self.conf.allowed_lateness_ms,
        )
        for win in asm.stream(chunks):
            batch = GeometryBatch.from_ragged(
                win.ts, win.oid, win.lengths, win.verts,
                edge_valid_flat=win.edge_valid, dtype=np.float64,
            )
            oflags = batch.any_cell_flagged(self.grid, flags, prefix=prefix)
            ev_d, valid_d, oflags_d = ship(
                batch.edge_valid, batch.valid, oflags
            )
            keep, dist = gk(
                self.device_verts(batch.verts, dtype),
                ev_d, valid_d, oflags_d, qv, qe, radius,
            )
            keep, dist = telemetry.fetch((keep, dist))
            idx = np.nonzero(keep)[0]
            yield (
                win.start, win.end, idx, win.oid[idx],
                np.asarray(dist)[idx], win.count,
            )


class PolygonPointRangeQuery(_GeometryStreamRangeQuery):
    """range/PolygonPointRangeQuery.java."""

    query_kind = "point"


class PolygonPolygonRangeQuery(_GeometryStreamRangeQuery):
    """range/PolygonPolygonRangeQuery.java."""

    query_kind = "polygon"


class PolygonLineStringRangeQuery(_GeometryStreamRangeQuery):
    """range/PolygonLineStringRangeQuery.java."""

    query_kind = "linestring"


class LineStringPointRangeQuery(_GeometryStreamRangeQuery):
    """range/LineStringPointRangeQuery.java."""

    query_kind = "point"
    stream_polygonal = False


class LineStringPolygonRangeQuery(_GeometryStreamRangeQuery):
    """range/LineStringPolygonRangeQuery.java."""

    query_kind = "polygon"
    stream_polygonal = False


class LineStringLineStringRangeQuery(_GeometryStreamRangeQuery):
    """range/LineStringLineStringRangeQuery.java."""

    query_kind = "linestring"
    stream_polygonal = False
