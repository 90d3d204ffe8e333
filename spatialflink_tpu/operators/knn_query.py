"""Continuous kNN operators — the ``spatialOperators/knn/`` matrix.

The reference's two-stage per-cell-PQ → windowAll-merge pipeline
(knn/PointPointKNNQuery.java:132-201 + KNNQuery.java:204-308) becomes a
single fused program per window: masked distance → segment-min per objID →
lax.top_k (ops/knn.py). Output mirrors the reference's
``Tuple3<winStart, winEnd, PQ<(obj, dist)>>``: a KnnWindowResult carrying
the ordered (objID, dist, representative object) list.
"""

from __future__ import annotations

import functools
import time
from dataclasses import dataclass
from typing import Iterable, Iterator, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from spatialflink_tpu import overload
from spatialflink_tpu.models.objects import LineString, Point, Polygon, SpatialObject
from spatialflink_tpu.operators.base import (
    SpatialOperator,
    check_oid_range,
    flags_for_queries,
    jitted,
    pack_query_geometries,
    ship,
    window_program,
)
from spatialflink_tpu.ops.knn import (
    knn_geometry_query_kernel,
    knn_points_fused,
    knn_polygon_fused,
    knn_polyline_fused,
)
from spatialflink_tpu.telemetry import instrument_jit, telemetry
from spatialflink_tpu.utils.padding import next_bucket


def _wire_digest_program(kind: str, step):
    """The per-pane digest step as one instrumented program. ``step`` is a
    ``functools.partial`` (ops/wire_knn.py binds the statics), which has no
    ``__name__``: jitted as it is, it reaches the device trace as
    ``jit__unknown`` and the kernel table not at all. Wrapped in a function
    that carries the name, the ``XLA Modules`` line reads
    ``jit_wire_digest_<kind>`` and ``instrument_jit`` counts every pane."""
    def wire_digest(wire_s, n_valid, query_xy, scale, origin, radius):
        return step(wire_s, n_valid, query_xy, scale, origin, radius)

    name = wire_digest.__name__ = f"wire_digest_{kind}"
    return instrument_jit(jax.jit(wire_digest), name=name)


@dataclass
class KnnWindowResult:
    """Ordered top-k per window (ascending distance, objID-deduped)."""

    start: int
    end: int
    neighbors: List[Tuple[str, float, SpatialObject]]  # (objID, dist, object)
    window_count: int


@dataclass
class MultiKnnWindowResult:
    """One window's top-k for every query point of a batched query set."""

    start: int
    end: int
    results: List[KnnWindowResult]  # index-aligned with the query batch
    window_count: int


class _PointStreamKNNQuery(SpatialOperator):
    """Point stream; query = point / polygon / linestring."""

    query_kind = "point"

    def _packed_query(self, query_obj):
        """Query verts/edge mask used for DISTANCE evaluation.

        In approximate mode (QueryConfiguration.approximate_query) a
        polygon query is replaced by its closed bbox ring: point-in-rect
        → 0, else min edge distance — exactly the reference's
        getPointPolygonBBoxMinEuclideanDistance case analysis
        (knn/PointPolygonKNNQuery.java:132-146, DistanceFunctions.java:
        150-200), with zero kernel changes. A linestring query is
        deliberately NOT substituted: the reference's "approximate"
        branch calls getPointLineStringMinEuclideanDistance — the EXACT
        point-to-segments distance (DistanceFunctions.java:87-90), so
        approximate == exact there (quirk preserved; PARITY.md). A point
        query has no approximate branch in the reference at all
        (knn/PointPointKNNQuery.java reads but never uses the flag).
        Cell flags always come from the ORIGINAL geometry — the
        reference computes neighboring cells identically in both modes.
        """
        if self.conf.approximate_query and self.query_kind == "polygon":
            x0, y0, x1, y1 = query_obj.bbox()
            ring = np.asarray(
                [[x0, y0], [x1, y0], [x1, y1], [x0, y1], [x0, y0]],
                np.float64,
            )
            return ring, np.ones(4, bool)
        verts, ev = pack_query_geometries([query_obj], np.float64)
        return verts[0], ev[0]

    def run(
        self,
        stream: Iterable[Point],
        query_obj: SpatialObject,
        radius: float,
        k: int,
        dtype=np.float64,
        mesh=None,
        driver=None,
    ) -> Iterator[KnnWindowResult]:
        """Window loop lifted into the shared dataflow driver
        (spatialflink_tpu/driver.py): pass ``driver=`` to OPT INTO
        auto-checkpointing, retry-with-backoff, and device→numpy
        failover (point-query kind — the geometry kinds have no numpy
        twin). Without one, a strict driver reproduces the old plain
        loop exactly — errors propagate immediately, nothing degrades.
        """
        mesh = mesh if mesh is not None else self.mesh
        flags = flags_for_queries(self.grid, radius, [query_obj])

        from spatialflink_tpu.driver import strict_driver
        from spatialflink_tpu.ops.counters import count_candidates, counters

        # Attach (= load any checkpoint) BEFORE touching the device: a
        # run resumed after failover means the device path already died
        # — setup transfers would hang the resume at a device_put.
        drv = driver if driver is not None else strict_driver()
        drv.attach(self)
        process = None
        if drv.backend == "device":
            flags_d = jnp.asarray(flags)
            geom_kernel = (
                knn_polygon_fused if self.query_kind == "polygon"
                else knn_polyline_fused
            )

            def programs(nseg):
                return (
                    window_program(
                        mesh, knn_points_fused, (0, 1, 2, 4), 7,
                        topk=True, k=k, num_segments=nseg,
                    ),
                    window_program(
                        mesh, geom_kernel, (0, 1, 2, 4), 8,
                        topk=True, k=k, num_segments=nseg,
                    ),
                )

            if self.query_kind == "point":
                q = self.device_q([query_obj.x, query_obj.y], dtype)
            else:
                verts, ev = self._packed_query(query_obj)
                qv, qe = self.device_q(verts, dtype), jnp.asarray(ev)

            def process(win) -> KnnWindowResult:
                # Telemetry phases per window: assemble (host batch
                # build) → ship (host→device) → compute (kernel
                # dispatch) → fetch (device→host decode). The yield
                # stays OUTSIDE the window span so consumer time never
                # pollutes window latency.
                with telemetry.span(
                    "window.knn", start=win.start, events=len(win.events)
                ):
                    with telemetry.span("assemble"):
                        batch = self.point_batch(win.events)
                        if counters.enabled:
                            cand = count_candidates(
                                flags, batch.cell, len(win.events)
                            )
                            counters.record_window(len(win.events), cand,
                                                   cand)
                        nseg = next_bucket(
                            max(self.interner.num_segments, 1), minimum=64
                        )
                        kp, kpoly = programs(nseg)
                    with telemetry.span("ship"):
                        valid_d, cell_d, oid_d = ship(
                            batch.valid, batch.cell, batch.oid
                        )
                        args = (
                            self.device_xy(batch, dtype),
                            valid_d,
                            cell_d,
                            flags_d,
                            oid_d,
                        )
                    with telemetry.span("compute"):
                        if self.query_kind == "point":
                            res = kp(*args, q, radius)
                        else:
                            res = kpoly(*args, qv, qe, radius)
                    return self._decode(win, res, k)

        fallback = None
        if self.query_kind == "point":
            fallback = self._numpy_window_process(query_obj, flags, radius,
                                                  k, dtype)
        drv.bind(self, process, fallback=fallback)
        from spatialflink_tpu.operators.query_config import QueryType

        if self.conf.query_type == QueryType.CountBased:
            from spatialflink_tpu.operators.base import count_window_batches

            yield from drv.run_windows(count_window_batches(
                stream, self.conf.count_window_size,
                self.conf.count_window_size,
            ))
        else:
            yield from drv.run(stream)

    def _numpy_window_process(self, query_obj, flags, radius, k, dtype):
        """Numpy twin of the point-query device path — the driver's
        failover route. Same centered/cast coordinates
        (operators/base.center_coords), same masked segment-min and the
        same top-k tie-break as ops/knn.py (``lax.top_k`` over
        ``-seg_min`` puts equal distances in ascending segment-id order;
        a stable argsort over ``seg_min`` does too), so a mid-stream
        backend switch changes no results (tests/test_driver.py pins
        parity)."""
        from spatialflink_tpu.operators.base import center_coords

        q_host = center_coords(
            self.grid,
            np.asarray([[query_obj.x, query_obj.y]], np.float64), dtype,
        )[0]

        def process(win) -> KnnWindowResult:
            batch = self.point_batch(win.events)
            n = len(win.events)
            nseg = next_bucket(max(self.interner.num_segments, 1),
                               minimum=64)
            xy = center_coords(self.grid, batch.xy[:n], dtype)
            d = xy - q_host[None, :]
            dist = np.sqrt(np.sum(d * d, axis=-1))
            f = flags[batch.cell[:n]]
            mask = batch.valid[:n] & (f > 0) & (dist <= radius)
            big = np.finfo(dist.dtype).max
            masked = np.where(mask, dist, big).astype(dist.dtype)
            oid = np.asarray(batch.oid[:n], np.int64)
            seg_min = np.full(nseg, big, dist.dtype)
            np.minimum.at(seg_min, oid, masked)
            int_big = np.iinfo(np.int32).max
            rep = np.full(nseg, int_big, np.int64)
            winner = mask & (masked == seg_min[oid])
            np.minimum.at(rep, oid[winner],
                          np.arange(n, dtype=np.int64)[winner])
            order = np.argsort(seg_min, kind="stable")
            nv = min(int((seg_min < big).sum()), k)
            neighbors = [
                (self.interner.lookup(int(s)), float(seg_min[s]),
                 win.events[int(rep[s])])
                for s in order[:nv]
            ]
            return KnnWindowResult(win.start, win.end, neighbors,
                                   len(win.events))

        return process

    def _decode(self, win, res, k) -> KnnWindowResult:
        # telemetry.fetch is the SAME device_get the bare np.asarray would
        # do — it replaces the fetch (true sync + d2h byte accounting),
        # never adds one.
        with telemetry.span("fetch"):
            nv = int(telemetry.fetch(res.num_valid))
            segs, dists, idxs = telemetry.fetch(
                (res.segment[:nv], res.dist[:nv], res.index[:nv])
            )
        neighbors = [
            (self.interner.lookup(int(s)), float(d), win.events[int(i)])
            for s, d, i in zip(segs, dists, idxs)
        ]
        return KnnWindowResult(win.start, win.end, neighbors, len(win.events))

    def query_panes(
        self,
        stream: Iterable[Point],
        query_obj: SpatialObject,
        radius: float,
        k: int,
        dtype=np.float64,
        flush_at_end: bool = True,
    ) -> Iterator[KnnWindowResult]:
        """Incremental sliding-window kNN via pane-digest carry.

        The kNN analog of the reference's ListState carry-over
        (range/PointPointRangeQuery.java:195-296): each ``slide``-wide pane
        is digested ONCE into per-object (min-dist, representative) arrays
        (ops/knn.py:knn_pane_digest); every window's result is a device-side
        min-merge + top-k over its ``size/slide`` carried digests. Per-slide
        device work drops from O(window) to O(pane) + O(panes × segments).

        Bit-identical to ``run()`` for in-order streams (parity test);
        the same caveats as ``query_incremental`` apply: events out of
        order by more than one slide pane would miss their pane's digest,
        and allowed-lateness refires would double-count — so a non-zero
        ``allowed_lateness`` is rejected and in-order delivery is assumed.
        """
        from spatialflink_tpu.operators.query_config import QueryType
        from spatialflink_tpu.ops.knn import (
            knn_merge_digest_list,
            knn_pane_digest_compact,
            knn_pane_digest_geometry_compact,
        )

        conf = self.conf
        if conf.query_type == QueryType.CountBased:
            raise ValueError("query_panes requires time-based sliding windows")
        if conf.allowed_lateness_ms > 0:
            raise ValueError(
                "query_panes does not support allowed_lateness (late-window "
                "refires would double-count carried panes); use run()"
            )
        size, slide = conf.window_size_ms, conf.slide_step_ms
        if conf.query_type in (QueryType.RealTime, QueryType.RealTimeNaive):
            size = slide = conf.realtime_batch_ms
        if size % slide != 0:
            raise ValueError("query_panes requires size % slide == 0")

        # Pane digests run the top-k-compacted kernels (ops/knn.py) with
        # cell/flags=None: for IN-GRID points the radius test subsumes the
        # grid pruning for a single query (bit-parity with the flagged
        # scatter digest, tests/test_knn_compact.py), and skipping the
        # per-point flag gather is the single biggest TPU win in this
        # path. Out-of-extent points (cell == num_cells, whose flag entry
        # is hard-coded 0 — the reference's key-never-matches semantics)
        # are excluded HOST-side by and-ing them out of `valid` below.
        if self.query_kind == "point":
            q = self.device_q([query_obj.x, query_obj.y], dtype)
            digest_fn = functools.partial(
                jitted(knn_pane_digest_compact, "num_segments", "cand"),
                cand=4096,
            )
        else:
            verts, ev = self._packed_query(query_obj)
            qv, qe = self.device_q(verts, dtype), jnp.asarray(ev)
            digest_fn = functools.partial(
                jitted(knn_pane_digest_geometry_compact,
                       "num_segments", "query_polygonal", "cand"),
                query_polygonal=self.query_kind == "polygon",
                cand=4096,
            )
        merge = jitted(knn_merge_digest_list, "k")
        int_big = np.iinfo(np.int32).max
        zero = np.int32(0)

        # pane start → (nseg, seg_min, rep, events) | None (empty).
        # Digests hold pane-LOCAL representative indices; window-local base
        # offsets are applied inside the jitted merge, so carried indices
        # never grow with the stream (unbounded-stream-safe).
        # The dict is OPERATOR-OWNED state — the pane-carry analog of the
        # reference's ListState (range/PointPointRangeQuery.java:234-246) —
        # so checkpoint.py can snapshot/restore it (with the window
        # assembler below); one logical stream per operator instance.
        if getattr(self, "_pane_carry", None) is None:
            self._pane_carry = {}
        panes: dict = self._pane_carry
        empties: dict = {}  # nseg → cached empty digest (one-time device op)

        def empty_digest(nseg):
            if nseg not in empties:
                # Match the live digests' dtype exactly: a default-dtype
                # jnp.full under x64 would promote a float32 pipeline's
                # merge to float64, shrinking the absent-object sentinel
                # below finfo.max and surfacing ghost neighbors.
                sm_dtype = jax.dtypes.canonicalize_dtype(np.dtype(dtype))
                empties[nseg] = (
                    jnp.full((nseg,), np.finfo(sm_dtype).max, sm_dtype),  # sfcheck: ok=hotpath-interproc -- dict-memoized (`empties`): one alloc per nseg bucket, not per window
                    jnp.full((nseg,), int_big, jnp.int32),  # sfcheck: ok=hotpath-interproc -- same memoized empty-digest constant as above
                )
            return empties[nseg]

        def grow(entry, nseg):
            # One-time re-pad when the interned-id bucket grows (log2 many
            # times total — not a per-window device op).
            e_nseg, sm, rp, evs = entry
            pad = nseg - e_nseg
            fbig = jnp.asarray(jnp.finfo(sm.dtype).max, sm.dtype)
            return (
                nseg,
                jnp.concatenate([sm, jnp.full((pad,), fbig, sm.dtype)]),  # sfcheck: ok=hotpath-interproc -- documented one-time re-pad on bucket growth (log2 many total), not a per-window op
                jnp.concatenate([rp, jnp.full((pad,), int_big, jnp.int32)]),  # sfcheck: ok=hotpath-interproc -- same one-time bucket-growth re-pad as above
                evs,
            )

        for win in self._checkpointable_windows(stream, flush_at_end):
            starts = range(win.start, win.end, slide)
            for ps in starts:
                if ps in panes:
                    continue
                evs = [e for e in win.events if ps <= e.timestamp < ps + slide]
                if not evs:
                    panes[ps] = None
                    continue
                with telemetry.span("pane.digest", pane=ps, events=len(evs)):
                    batch = self.point_batch(evs)
                    # pane-capacity bucket occupancy → telemetry (the
                    # same per-bucket log the wire path and the tJoin
                    # compaction planner feed — ops/compaction.py)
                    telemetry.record_compaction(
                        "knn_pane_digest", batch.capacity, len(evs)
                    )
                    nseg = next_bucket(
                        max(self.interner.num_segments, 1), minimum=64
                    )
                    in_grid = batch.valid & (batch.cell < self.grid.num_cells)
                    in_grid_d, oid_d = ship(in_grid, batch.oid)
                    args = (
                        self.device_xy(batch, dtype),
                        in_grid_d,
                        None,  # cell/flags skipped — see comment above
                        None,
                        oid_d,
                    )
                    if self.query_kind == "point":
                        d = digest_fn(*args, q, radius, zero,
                                      num_segments=nseg)
                    else:
                        d = digest_fn(*args, qv, qe, radius, zero,
                                      num_segments=nseg)
                    panes[ps] = (nseg, d.seg_min, d.rep, evs)
            for ps in [p for p in panes if p < win.start]:
                del panes[ps]

            with telemetry.span("window.knn_panes", start=win.start,
                                events=len(win.events)):
                nseg = max(p[0] for p in panes.values() if p is not None)
                for ps in starts:
                    if panes[ps] is not None and panes[ps][0] < nseg:
                        panes[ps] = grow(panes[ps], nseg)
                live = [panes[ps] for ps in starts]
                emt = empty_digest(nseg)
                sms = tuple(emt[0] if p is None else p[1] for p in live)
                rps = tuple(emt[1] if p is None else p[2] for p in live)
                bases, acc = [], 0
                for p in live:
                    bases.append(acc)
                    acc += 0 if p is None else len(p[3])
                res = merge(sms, rps, np.asarray(bases, np.int32), k=k)

                spans = [(b, p[3]) for b, p in zip(bases, live)
                         if p is not None]
                nv = int(telemetry.fetch(res.num_valid))
                segs, dists, idxs = telemetry.fetch(  # bulk fetches, no per-
                    (res.segment[:nv], res.dist[:nv], res.index[:nv])
                )  # element device round trips
                neighbors = []
                for s, d, gi in zip(segs, dists, idxs):
                    ev = None
                    for base, evs in spans:
                        if base <= gi < base + len(evs):
                            ev = evs[gi - base]
                            break
                    neighbors.append(
                        (self.interner.lookup(int(s)), float(d), ev)
                    )
                out = KnnWindowResult(
                    win.start, win.end, neighbors, len(win.events)
                )
            yield out


class PointPointKNNQuery(_PointStreamKNNQuery):
    """knn/PointPointKNNQuery.java:132-201 (+ KNNQuery.java merge)."""

    query_kind = "point"

    def run_soa(
        self,
        chunks,
        query_point: Point,
        radius: float,
        k: int,
        num_segments: int,
        dtype=np.float64,
    ):
        """High-rate SoA path: chunks of {"ts","x","y","oid"} arrays →
        per-window KnnResult-shaped tuples (start, end, oids, dists,
        num_valid). ``oid`` must already be dense int32 in
        [0, num_segments) — e.g. the native parser's interned device ids."""
        from spatialflink_tpu.operators.base import soa_point_batches
        from spatialflink_tpu.ops.counters import count_candidates, counters

        flags = flags_for_queries(self.grid, radius, [query_point])
        flags_d = jnp.asarray(flags)
        q = self.device_q([query_point.x, query_point.y], dtype)
        kp = jitted(knn_points_fused, "k", "num_segments")
        for win, xy, valid, cell, oid in soa_point_batches(
            self.grid, chunks, self.conf, dtype
        ):
            with telemetry.span("window.knn_soa", start=win.start,
                                events=win.count):
                check_oid_range(oid[:win.count], num_segments)
                if counters.enabled:
                    cand = count_candidates(flags, cell, win.count)
                    counters.record_candidates(cand, cand)
                xy_d, valid_d, cell_d, oid_d = ship(xy, valid, cell, oid)
                res = kp(
                    xy_d, valid_d, cell_d, flags_d, oid_d,
                    q, radius, k=k, num_segments=num_segments,
                )
                nv = int(telemetry.fetch(res.num_valid))
                segs, dists = telemetry.fetch(
                    (res.segment[:nv], res.dist[:nv])
                )
            yield (win.start, win.end, segs, dists, nv)


    def run_multi(
        self,
        stream: Iterable[Point],
        query_points: Sequence[Point],
        radius: float,
        k: int,
        dtype=np.float64,
        mesh=None,
    ) -> Iterator[MultiKnnWindowResult]:
        """Batched multi-query kNN: ONE fused program per window answers
        the whole query-point set (ops/knn.py:knn_multi_query_kernel),
        instead of one program per query point — the kNN analog of the
        range family's query-set batching. Each query prunes by its own
        neighbor-cell flag table, so per-query results are identical to
        ``run()`` with that single query (parity test).

        ``mesh=``: points shard over ``data``; a 2-D mesh additionally
        shards the query batch and its flag tables over ``query``
        (parallel/sharded.py:sharded_knn_multi; winner order matches
        single-device, distances to 1 ulp)."""
        from spatialflink_tpu.ops.knn import knn_multi_query_kernel

        from spatialflink_tpu.utils.padding import pad_to_bucket

        mesh = mesh if mesh is not None else self.mesh
        nq = len(query_points)
        if nq == 0:
            return
        tables = np.stack(
            [flags_for_queries(self.grid, radius, [q]) for q in query_points]
        )
        qb = next_bucket(nq, minimum=8)
        if mesh is not None:
            # The padded query count must divide by the query axis (which
            # need not be a power of two — round up to a multiple).
            qa = int(mesh.shape.get("query", 1))
            if qb % qa:
                qb = ((qb // qa) + 1) * qa
        block = min(qb, 32)
        # Padded query lanes carry zero flag tables → empty results.
        tables = pad_to_bucket(tables, qb)
        qxy = pad_to_bucket(
            np.asarray([[q.x, q.y] for q in query_points], np.float64), qb
        )
        tables_d = jnp.asarray(tables)
        q_d = self.device_q(qxy, dtype)
        kernel = jitted(
            knn_multi_query_kernel, "k", "num_segments", "query_block"
        )

        for win in self.windows(stream):
            batch = self.point_batch(win.events)
            nseg = next_bucket(max(self.interner.num_segments, 1), minimum=64)
            valid_d, cell_d, oid_d = ship(batch.valid, batch.cell, batch.oid)
            args = (
                self.device_xy(batch, dtype),
                valid_d,
                cell_d,
                tables_d,
                oid_d,
                q_d,
            )
            if mesh is not None:
                from spatialflink_tpu.parallel.sharded import sharded_knn_multi

                res = sharded_knn_multi(
                    mesh, *args, radius, k=k, num_segments=nseg,
                )
            else:
                res = kernel(
                    *args, radius, k=k, num_segments=nseg, query_block=block,
                )
            segs, dists, idxs, nvs = telemetry.fetch(  # (Q, k) bulk fetches
                (res.segment, res.dist, res.index, res.num_valid)
            )
            per_query = []
            for qi in range(nq):
                nv = int(nvs[qi])
                neighbors = [
                    (self.interner.lookup(int(segs[qi, i])),
                     float(dists[qi, i]), win.events[int(idxs[qi, i])])
                    for i in range(nv)
                ]
                per_query.append(
                    KnnWindowResult(win.start, win.end, neighbors,
                                    len(win.events))
                )
            yield MultiKnnWindowResult(
                win.start, win.end, per_query, len(win.events)
            )

    def run_soa_panes(
        self,
        chunks,
        query_point: Point,
        radius: float,
        k: int,
        num_segments: int,
        dtype=np.float64,
        flush_at_end: bool = True,
    ):
        """SoA pane-digest carry: ``run_soa``'s contract (yields
        (start, end, oids, dists, num_valid) per window) at O(pane) device
        work per slide instead of O(window). Same in-order/no-lateness
        caveats as ``query_panes``."""
        from spatialflink_tpu.operators.base import device_point_args
        from spatialflink_tpu.ops.knn import (
            knn_merge_digest_list,
            knn_pane_digest_compact,
        )
        from spatialflink_tpu.streams.soa import SoaWindowAssembler

        conf = self.conf
        if conf.allowed_lateness_ms > 0:
            raise ValueError(
                "run_soa_panes does not support allowed_lateness; use run_soa"
            )
        size, slide = conf.window_size_ms, conf.slide_step_ms
        if size % slide != 0:
            raise ValueError("run_soa_panes requires size % slide == 0")

        q = self.device_q([query_point.x, query_point.y], dtype)
        # Compact digest, cell/flags=None; out-of-extent points excluded
        # host-side via `valid` — see query_panes.
        digest = functools.partial(
            jitted(knn_pane_digest_compact, "num_segments", "cand"),
            cand=4096,
        )
        merge = jitted(knn_merge_digest_list, "k")
        ppw = size // slide
        no_bases = np.zeros(ppw, np.int32)  # indices unused by this yield

        # Operator-owned, checkpointable — see query_panes.
        if getattr(self, "_pane_carry_soa", None) is None:
            self._pane_carry_soa = {}
        panes: dict = self._pane_carry_soa
        emt = None
        asm = SoaWindowAssembler(size, slide, ooo_ms=0)
        for win in self._checkpointable_soa_windows(asm, chunks,
                                                    flush_at_end):
            ts = np.asarray(win.arrays["ts"], np.int64)
            for ps in range(win.start, win.end, slide):
                if ps in panes:
                    continue
                lo = int(np.searchsorted(ts, ps, side="left"))
                hi = int(np.searchsorted(ts, ps + slide, side="left"))
                if hi <= lo:
                    panes[ps] = None
                    continue
                # O(pane), not O(window): carried panes were checked when
                # first digested.
                check_oid_range(win.arrays["oid"][lo:hi], num_segments)
                xy64 = np.stack(
                    [np.asarray(win.arrays["x"][lo:hi], np.float64),
                     np.asarray(win.arrays["y"][lo:hi], np.float64)],
                    axis=1,
                )
                xy_p, valid_p, cell_p, oid_p = device_point_args(
                    self.grid, xy64, win.arrays["oid"][lo:hi], dtype
                )
                in_grid = valid_p & (cell_p < self.grid.num_cells)
                # cell_p is used host-side only on this path (the kernel
                # gets cell=None) — ship exactly the three shipped lanes.
                xy_d, in_grid_d, oid_d = ship(xy_p, in_grid, oid_p)
                d = digest(
                    xy_d, in_grid_d, None, None, oid_d,
                    q, radius, np.int32(0), num_segments=num_segments,
                )
                panes[ps] = (d.seg_min, d.rep)
            for ps in [p for p in panes if p < win.start]:
                del panes[ps]

            live = [panes[ps] for ps in range(win.start, win.end, slide)]
            if emt is None:
                ref = next(p for p in live if p is not None)
                emt = (
                    jnp.full_like(ref[0], jnp.finfo(ref[0].dtype).max),  # sfcheck: ok=hotpath-interproc -- once per run (`emt is None` guard), not per window
                    jnp.full_like(ref[1], jnp.iinfo(jnp.int32).max),  # sfcheck: ok=hotpath-interproc -- same once-per-run empty-pane constant as above
                )
            sms = tuple(emt[0] if p is None else p[0] for p in live)
            rps = tuple(emt[1] if p is None else p[1] for p in live)
            res = merge(sms, rps, no_bases, k=k)
            nv = int(telemetry.fetch(res.num_valid))
            segs, dists = telemetry.fetch((res.segment[:nv], res.dist[:nv]))
            yield (win.start, win.end, segs, dists, nv)


    def run_wire_panes(
        self,
        slides,
        query_point: Point,
        radius: float,
        k: int,
        num_segments: int,
        wire_format,
        start_ms: int = 0,
        strategy: str = "auto",
        cand: int = 8192,
        interpret: bool = False,
        flush_at_end: bool = True,
    ):
        """Wire-plane pane-carry kNN — the HEADLINE program as a shipped
        operator path (ops/wire_knn.py).

        ``slides``: iterable of (3, n_i) uint16 PLANE-MAJOR pane arrays
        in the 6 B/pt wire format (streams/wire.py) — rows x_q, y_q,
        interned-int16-oid bits — one array per ``slide_step`` pane, in
        event-time order (``streams/wire.py:wire_panes`` produces them
        from any SoA chunk stream, e.g. the native CSV parser's arrays
        or a batched Kafka consumer). Pane i covers
        [start_ms + i·slide, start_ms + (i+1)·slide); every window
        OVERLAPPING a received NON-EMPTY pane fires — including the
        leading partial windows (negative-offset starts, matching
        run_soa_panes's earliest_window_of semantics) and, with
        ``flush_at_end``, the trailing partials. Windows whose every
        pane held zero events (gap windows — the assembler on the SoA
        path never builds them) are suppressed, so the window SET
        equals run_soa_panes's exactly (tests/test_wire_knn.py pins set
        equality), yielding ``run_soa``'s (start, end, oids, dists,
        num_valid) contract. Variable pane sizes share one compiled
        step via ladder-bucketed padding (ops/compaction.py:
        wire_pane_bucket — the digest scans O(pane-rounded-up) lanes,
        each pick recorded per bucket in telemetry) + an ``n_valid``
        mask (padding can never match — parity-tested).

        ``strategy``: 'auto' adopts the fused Pallas extraction on TPU
        only after a first-pane self-check against the XLA step (set
        equality + ≤1 ulp — select_wire_digest_step's contract; overflow
        beyond the candidate budget falls back IN-PROGRAM, so results
        are exact either way); 'xla'/'pallas' force. The chosen kind is
        recorded on ``self.last_wire_digest_kind``.

        With telemetry on, one parent span ``wire.pane`` a received pane
        (args ``n``), emitted by hand: from the pane's receipt from
        ``slides`` to just before its result is yielded (a pane that
        yields nothing: to the end of its body), so it holds none of the
        consumer's time. Its children tile it: ``wire.prepare``, ``h2d``,
        ``wire.step_args`` (what is built on the device a pane before the
        digest step), ``dispatch:*``, ``wire.merge_args`` (the ring, the
        carry, the tuples ``merge`` takes), ``d2h`` twice and, between the
        two, ``wire.slice`` (the eager ``[:nv]`` slices). Under a
        ``batch_slides`` rung the parent closes before the batch's first
        yield; the synthetic panes of ``flush_at_end`` have none.
        """
        from spatialflink_tpu.operators.query_config import QueryType
        from spatialflink_tpu.ops.compaction import wire_pane_bucket
        from spatialflink_tpu.ops.knn import knn_merge_digest_list
        from spatialflink_tpu.ops.wire_knn import select_wire_digest_step

        conf = self.conf
        if conf.query_type == QueryType.CountBased:
            raise ValueError(
                "run_wire_panes requires time-based sliding windows"
            )
        size, slide_ms = conf.window_size_ms, conf.slide_step_ms
        if conf.query_type in (QueryType.RealTime, QueryType.RealTimeNaive):
            size = slide_ms = conf.realtime_batch_ms
        if size % slide_ms != 0:
            raise ValueError("run_wire_panes requires size % slide == 0")
        ppw = size // slide_ms

        q = jnp.asarray(
            np.asarray([query_point.x, query_point.y], np.float32)
        )
        scale = jnp.asarray(wire_format.scale)
        origin = jnp.asarray(wire_format.origin)
        r32 = jnp.asarray(radius, jnp.float32)
        merge = jitted(knn_merge_digest_list, "k")
        no_bases = np.zeros(ppw, np.int32)  # indices unused by this yield
        jstep = None
        self.last_wire_digest_kind = None
        empty = (
            jnp.full((num_segments,),
                     np.float32(np.finfo(np.float32).max), jnp.float32),
            jnp.full((num_segments,), np.iinfo(np.int32).max, jnp.int32),
        )

        # Operator-owned, checkpointable state (the wire path's
        # ListState analog): the live digest ring + the next logical
        # pane index. checkpoint.py:operator_state snapshots it; a
        # restored operator continues MID-WINDOW when the caller feeds
        # the remaining panes (paired with WireKafkaSource's offsets,
        # kill-and-resume covers ingest + operator;
        # tests/test_checkpoint_panes.py). The carry is consumed ONLY
        # right after restore_operator (the _wire_pane_restored flag):
        # unlike the timestamp-keyed run_soa_panes carry, this one is
        # pane-INDEX based, so resuming it on an ordinary second call
        # would silently time-shift every window.
        saved = None
        if getattr(self, "_wire_pane_restored", False):
            saved = getattr(self, "_wire_pane_carry", None)
        self._wire_pane_restored = False
        if saved is not None:
            pane0 = int(saved["next_pane"])
            digests = [
                (jnp.asarray(s), jnp.asarray(r)) for s, r in saved["digests"]
            ]
            # Pre-counts snapshots lack the event-count ring: assume the
            # carried panes were non-empty (fire conservatively — the
            # old every-window-fires behavior for exactly those panes).
            counts = [int(c) for c in saved.get(
                "counts", [1] * len(digests)
            )]
        else:
            pane0 = 0
            # Seed the ring with ppw-1 empty digests so the LEADING
            # partial windows fire (run_soa_panes parity: its assembler
            # starts at earliest_window_of the first event).
            digests = [empty] * (ppw - 1)
            counts = [0] * (ppw - 1)
        self._wire_pane_carry = {
            "next_pane": pane0, "digests": list(digests),
            "counts": list(counts),
        }

        def merge_args():
            """The ring as the two tuples ``merge`` takes; None for a gap
            window. Gap-window suppression: a window none of whose panes
            held an event does not exist on the SoA path (the assembler
            only builds windows containing events) — skip it here too.
            Event count, NOT digest liveness, decides: a window of events
            all out of radius still fires (nv = 0)."""
            if not any(counts):
                return None
            return (tuple(s for s, _ in digests),
                    tuple(r for _, r in digests))

        def merge_window(pane_i, ring):
            if ring is None:
                return None
            res = merge(*ring, no_bases, k=k)
            return (start_ms + (pane_i - ppw + 1) * slide_ms, res)

        def fetch_one(w_start, res):
            nv = int(telemetry.fetch(res.num_valid))
            with telemetry.span("wire.slice"):
                rows = (res.segment[:nv], res.dist[:nv])
            segs, dists = telemetry.fetch(rows)
            return (w_start, w_start + size, segs, dists, nv)

        #: perf_counter_ns at the open pane's receipt; None between panes,
        #: past the pane's hand-back, and with telemetry off
        pane_t0 = None

        def close_pane():
            """Emit the open pane's parent span, once: called right before
            every yield (the pane's events already head ``counts``), so no
            parent holds a consumer's time."""
            nonlocal pane_t0
            if pane_t0 is not None:
                t0, pane_t0 = pane_t0, None
                telemetry.emit_span(
                    "wire.pane", t0, time.perf_counter_ns() - t0,
                    n=counts[-1],
                )

        pending: list = []

        def carry_now(next_pane):
            return {
                "next_pane": next_pane, "digests": list(digests),
                "counts": list(counts),
            }

        def flush_pending():
            # ONE device→host sync for the whole batch: full (k,) lanes
            # fetched, host-sliced by num_valid — identical values to
            # the per-window fetch, device round trips ÷ batch width.
            if not pending:
                return
            handles = [
                (r.num_valid, r.segment, r.dist) for (_, r), _ in pending
            ]
            fetched = telemetry.fetch(handles)
            for ((w_start, _), carry), (nv_a, seg_a, dist_a) in zip(
                    pending, fetched):
                # Publish the ring state as of this window's pane BEFORE
                # yielding it: a checkpoint taken at any yield must
                # never count a still-pending window as emitted (the
                # carry would otherwise skip past unfetched windows on
                # resume — lost egress).
                self._wire_pane_carry = carry
                nv = int(nv_a)
                close_pane()
                yield (w_start, w_start + size, np.asarray(seg_a)[:nv],
                       np.asarray(dist_a)[:nv], nv)
            del pending[:]

        def emit(pane_i, carry, ring):
            """Yield-ready results for this pane's window (if any).

            Under an active overload ``batch_slides`` degradation rung
            (spatialflink_tpu/overload.py) the result handles of N
            windows batch into one fetch via ``flush_pending`` — on
            this path the per-window device round trip IS the overload
            cost. The default width of 1 keeps the original
            fetch-per-window sequence bit-for-bit, including the
            carry-advances-per-pane checkpoint behavior; while a batch
            is open the carry stays at the last YIELDED window's pane
            (flush_pending advances it per yield).
            """
            out = merge_window(pane_i, ring)
            if out is None:
                if not pending:
                    self._wire_pane_carry = carry
                return
            width = overload.batch_slides()
            if width <= 1 and not pending:
                self._wire_pane_carry = carry
                result = fetch_one(*out)
                close_pane()
                yield result
                return
            pending.append((out, carry))
            if len(pending) >= max(width, 1):
                yield from flush_pending()

        def check_pane(wire_p):
            if (wire_p.ndim != 2 or wire_p.shape[0] != 3
                    or wire_p.dtype != np.uint16):
                raise ValueError(
                    "run_wire_panes expects (3, n) uint16 plane-major "
                    f"panes, got {wire_p.dtype} {wire_p.shape}"
                )
            # The uint16 bits, as the device upcasts them
            # (ops/wire_knn.py:wire_plane_coords): an id with its top bit
            # set — a negative int16 at the producer — reads 32768-65535
            # there and the segment reductions would drop the point, so
            # the unsigned view refuses it in the same pass as an id
            # >= num_segments.
            check_oid_range(wire_p[2], num_segments)

        i = pane0 - 1
        last_carry = self._wire_pane_carry
        for i, wire_p in enumerate(slides, start=pane0):
            if telemetry.enabled:
                pane_t0 = time.perf_counter_ns()
            # The host's share of a pane before it crosses: everything
            # between the hand-over and ``ship``.
            with telemetry.span("wire.prepare") as sp:
                wire_p = np.asarray(wire_p)
                check_pane(wire_p)
                n = wire_p.shape[1]
                nb = wire_pane_bucket(n)
                if nb != n:
                    wire_p = np.concatenate(
                        [wire_p, np.zeros((3, nb - n), np.uint16)], axis=1
                    )
                # (the disabled-telemetry null span has no args)
                getattr(sp, "args", {}).update(n=n, bucket=nb)
            telemetry.record_wire_pane(n, nb)
            (wire_d,) = ship(wire_p)
            with telemetry.span("wire.step_args"):
                n_d = jnp.int32(n)
            if jstep is None:
                kind, step = select_wire_digest_step(
                    wire_d, n_d, q, scale, origin, r32,
                    num_segments=num_segments, cand=cand,
                    interpret=interpret, strategy=strategy,
                )
                self.last_wire_digest_kind = kind
                jstep = _wire_digest_program(kind, step)
            d = jstep(wire_d, n_d, q, scale, origin, r32)
            with telemetry.span("wire.merge_args"):
                digests.append((d.seg_min, d.rep))
                del digests[:-ppw]
                counts.append(n)
                del counts[:-ppw]
                last_carry = carry_now(i + 1)
                ring = merge_args()
            yield from emit(i, last_carry, ring)
            close_pane()  # a pane that yielded nothing
        # Flush iff ≥1 REAL pane exists in the logical stream: consumed
        # this call (i advanced past pane0-1) or before the checkpoint
        # (pane0 > 0). A restore taken before any pane must NOT flush —
        # an uninterrupted empty run yields nothing.
        if flush_at_end and (i >= pane0 or pane0 > 0):
            # Trailing partial windows: panes shift out, empties in.
            # Synthetic panes never advance the carry — entries keep the
            # last REAL pane's ring.
            for j in range(1, ppw):
                digests.append(empty)
                del digests[:-ppw]
                counts.append(0)
                del counts[:-ppw]
                yield from emit(i + j, last_carry, merge_args())
        yield from flush_pending()
        # End-of-call invariant (what the call-boundary checkpoint
        # callers pair with source offsets): every consumed REAL pane is
        # in the carry, whether or not its window was emitted.
        self._wire_pane_carry = last_carry


class PointPolygonKNNQuery(_PointStreamKNNQuery):
    """knn/PointPolygonKNNQuery.java:67-88 (incl. runLatency variants —
    latency accounting lives in the metrics layer here)."""

    query_kind = "polygon"


class PointLineStringKNNQuery(_PointStreamKNNQuery):
    """knn/PointLineStringKNNQuery.java."""

    query_kind = "linestring"


class _GeometryStreamKNNQuery(SpatialOperator):
    """Polygon/LineString stream; query point or geometry.

    Distance per object = ``geometry_pair_distance`` — the JTS
    ``getDistance`` semantics of the reference's Polygon/LineString KNN
    loops (DistanceFunctions.java:15-54): 0 on overlap/containment,
    including a query point inside a polygonal stream object. A Point
    query packs as a degenerate one-edge boundary.
    """

    stream_polygonal = True  # Polygon* subclasses; LineString* override

    def _device_query_bbox(self, query_obj, dtype):
        """Query bbox as a centered device (4,) array for approximate
        mode — a Point query degenerates to [x, y, x, y], which reduces
        bbox↔bbox to the reference's point↔bbox case analysis
        (knn/PolygonPointKNNQuery.java:95)."""
        from spatialflink_tpu.operators.join_query import _centered_bbox

        bb = np.asarray([query_obj.bbox()], np.float64)
        # pad=False: this box is the distance operand, not a prune box.
        return jnp.asarray(_centered_bbox(self.grid, bb, dtype, pad=False)[0])

    def _query_arrays(self, query_obj):
        """(qverts, qev, query_polygonal) — a Point query packs as a
        degenerate one-edge boundary. Shared by run() and run_soa()."""
        if isinstance(query_obj, Point):
            qverts = np.asarray(
                [[query_obj.x, query_obj.y], [query_obj.x, query_obj.y]],
                np.float64,
            )
            return qverts, np.asarray([True], bool), False
        verts, ev = pack_query_geometries([query_obj], np.float64)
        return verts[0], ev[0], isinstance(query_obj, Polygon)

    def run(
        self,
        stream: Iterable[Polygon | LineString],
        query_obj: SpatialObject,
        radius: float,
        k: int,
        dtype=np.float64,
        mesh=None,
    ) -> Iterator[KnnWindowResult]:
        mesh = mesh if mesh is not None else self.mesh
        flags = flags_for_queries(self.grid, radius, [query_obj])
        qverts, qev, query_polygonal = self._query_arrays(query_obj)
        qv = self.device_verts(qverts, dtype)
        qe = jnp.asarray(qev)
        approx = self.conf.approximate_query
        if approx:
            qbb = self._device_query_bbox(query_obj, dtype)

        from spatialflink_tpu.models.batch import flag_prefix_planes

        prefix = flag_prefix_planes(self.grid, flags)
        for win in self.windows(stream):
            batch = self.geometry_batch(win.events, mesh=mesh)
            nseg = next_bucket(max(self.interner.num_segments, 1), minimum=64)
            oflags = batch.any_cell_flagged(self.grid, flags, prefix=prefix)
            if approx:
                # Approximate mode: bbox ↔ bbox distance (GeometryBatch
                # already carries per-object bboxes), same candidate
                # cells and radius/top-k contract as exact mode.
                from spatialflink_tpu.operators.join_query import (
                    _centered_bbox,
                )
                from spatialflink_tpu.ops.knn import knn_geometry_bbox_kernel

                ka = window_program(
                    mesh, knn_geometry_bbox_kernel, (0, 1, 2, 3), 6,
                    topk=True, k=k, num_segments=nseg,
                )
                bb_d, valid_d, oflags_d, oid_d = ship(
                    _centered_bbox(self.grid, batch.bbox, dtype, pad=False),
                    batch.valid, oflags, batch.oid,
                )
                res = ka(bb_d, valid_d, oflags_d, oid_d, qbb, radius)
            else:
                statics = dict(
                    k=k, num_segments=nseg,
                    obj_polygonal=self.stream_polygonal,
                    query_polygonal=query_polygonal,
                )
                kg = window_program(
                    mesh, knn_geometry_query_kernel, (0, 1, 2, 3, 4), 8,
                    topk=True, **statics,
                )
                ev_d, valid_d, oflags_d, oid_d = ship(
                    batch.edge_valid, batch.valid, oflags, batch.oid
                )
                res = kg(
                    self.device_verts(batch.verts, dtype),
                    ev_d, valid_d, oflags_d, oid_d, qv, qe, radius,
                )
            nv = int(telemetry.fetch(res.num_valid))
            segs, dists, idxs = telemetry.fetch(  # bulk fetches, no per-
                (res.segment[:nv], res.dist[:nv], res.index[:nv])
            )  # element device round trips
            neighbors = [
                (self.interner.lookup(int(s)), float(d), win.events[int(i)])
                for s, d, i in zip(segs, dists, idxs)
            ]
            yield KnnWindowResult(win.start, win.end, neighbors, len(win.events))


    def run_soa(
        self,
        chunks,
        query_obj: SpatialObject,
        radius: float,
        k: int,
        num_segments: int,
        dtype=np.float64,
    ):
        """Ragged-SoA fast path for geometry-stream kNN: chunks
        ``{"ts","oid","lengths","verts"}`` → per-window
        (start, end, oids, dists, num_valid) through the same
        knn_geometry_query_kernel as ``run()``, zero per-object Python."""
        from spatialflink_tpu.models.batch import (
            GeometryBatch,
            flag_prefix_planes,
        )
        from spatialflink_tpu.streams.soa import RaggedSoaWindowAssembler

        flags = flags_for_queries(self.grid, radius, [query_obj])
        qverts, qev, query_polygonal = self._query_arrays(query_obj)
        qv = self.device_verts(qverts, dtype)
        qe = jnp.asarray(qev)
        approx = self.conf.approximate_query
        if approx:
            from spatialflink_tpu.operators.join_query import _centered_bbox
            from spatialflink_tpu.ops.knn import knn_geometry_bbox_kernel

            qbb = self._device_query_bbox(query_obj, dtype)
            ka = functools.partial(
                jitted(knn_geometry_bbox_kernel, "k", "num_segments"),
                k=k, num_segments=num_segments,
            )
        kg = functools.partial(
            jitted(
                knn_geometry_query_kernel,
                "k", "num_segments", "obj_polygonal", "query_polygonal",
            ),
            k=k, num_segments=num_segments,
            obj_polygonal=self.stream_polygonal,
            query_polygonal=query_polygonal,
        )

        prefix = flag_prefix_planes(self.grid, flags)
        asm = RaggedSoaWindowAssembler(
            self.conf.window_size_ms, self.conf.slide_step_ms,
            ooo_ms=self.conf.allowed_lateness_ms,
        )
        for win in asm.stream(chunks):
            check_oid_range(win.oid[:win.count], num_segments)
            batch = GeometryBatch.from_ragged(
                win.ts, win.oid, win.lengths, win.verts,
                edge_valid_flat=win.edge_valid, dtype=np.float64,
            )
            oflags = batch.any_cell_flagged(self.grid, flags, prefix=prefix)
            if approx:
                bb_d, valid_d, oflags_d, oid_d = ship(
                    _centered_bbox(self.grid, batch.bbox, dtype, pad=False),
                    batch.valid, oflags, batch.oid,
                )
                res = ka(bb_d, valid_d, oflags_d, oid_d, qbb, radius)
            else:
                ev_d, valid_d, oflags_d, oid_d = ship(
                    batch.edge_valid, batch.valid, oflags, batch.oid
                )
                res = kg(
                    self.device_verts(batch.verts, dtype),
                    ev_d, valid_d, oflags_d, oid_d, qv, qe, radius,
                )
            nv = int(telemetry.fetch(res.num_valid))
            segs, dists = telemetry.fetch((res.segment[:nv], res.dist[:nv]))
            yield (win.start, win.end, segs, dists, nv)


class PolygonPointKNNQuery(_GeometryStreamKNNQuery):
    """knn/PolygonPointKNNQuery.java."""


class PolygonPolygonKNNQuery(_GeometryStreamKNNQuery):
    """knn/PolygonPolygonKNNQuery.java."""


class PolygonLineStringKNNQuery(_GeometryStreamKNNQuery):
    """knn/PolygonLineStringKNNQuery.java."""


class LineStringPointKNNQuery(_GeometryStreamKNNQuery):
    """knn/LineStringPointKNNQuery.java."""

    stream_polygonal = False


class LineStringPolygonKNNQuery(_GeometryStreamKNNQuery):
    """knn/LineStringPolygonKNNQuery.java."""

    stream_polygonal = False


class LineStringLineStringKNNQuery(_GeometryStreamKNNQuery):
    """knn/LineStringLineStringKNNQuery.java."""

    stream_polygonal = False
