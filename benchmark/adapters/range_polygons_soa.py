"""Adapter: a point stream filtered, window by window, against a standing set
of query polygons, fed the way a deployment feeds it.

    SoA chunks {ts, x, y, oid}  ->  PointPolygonRangeQuery(conf, grid)
        .run_soa(chunks, polygons, radius)

The operator is built with nothing but its configuration and grid, and
``run_soa`` is called with its default ``dtype``: the kernel it picks is what
is measured. The polygons are the deployment's data, made from the run's seed
by the library's port of the upstream's generator
(``utils/helper.py:generate_query_polygons``); the reference is handed their
rings, and ``prepare`` holds the set to what the configuration says it is
(:func:`_hold_query_set`): both sides are fed it, so ``correct`` could not see
the port drift. This path has no driver and no sink: a result counts when ``run_soa``
yields the window's matched rows and distances, fetched. The run ends like a
consumer that goes away: the feed stops, the open window is dropped.

Every window is compared with the plain reference. A pool replayed cyclically
makes windows repeat, so the reference is computed once per distinct window of
the pool; a repeat that equals an already checked result array for array (but
for the timestamps, which go on by whole cycles) is held to that check, any
other is compared in full.
"""

from __future__ import annotations

import time
from typing import Any, Dict, Iterator, List, Optional, Tuple

import numpy as np

from benchmark.harness import spec

#: the operator's range counters, as ``telemetry.snapshot()["range"]`` names
#: them; ``extras`` hands the readers their change over the window
RANGE_COUNTERS = ("windows", "points", "lanes", "matches", "cand_retries",
                  "budget_retries")
RANGE_GAUGES = ("cand", "budget")


class Adapter:
    #: a result is out when the operator yields it: no stamps at triggers
    split_at_triggers = False

    def __init__(self, config: Dict[str, Any], stream_cfg: Dict[str, Any],
                 workdir: str, rehearsal: bool):
        self.cfg, self.stream_cfg = config, stream_cfg
        small = config.get("rehearsal", {}) if rehearsal else {}
        self.grid_cells = int(small.get("grid_cells", config["grid_cells"]))
        self.n_polygons = int(small.get(
            "query_polygons", config["query_polygons"])["count"])
        self.got: List[Tuple[int, Dict[str, np.ndarray], np.ndarray]] = []
        self.window_spans: List[Tuple[str, float, float]] = []
        self.t_handed = 0.0  # when the newest segment was handed over
        self.range_open: Optional[Dict[str, int]] = None
        self.range_close: Optional[Dict[str, int]] = None

    def prepare(self, stream, windows) -> None:
        from spatialflink_tpu.grid import UniformGrid
        from spatialflink_tpu.operators import (
            PointPolygonRangeQuery,
            QueryConfiguration,
            QueryType,
        )
        from spatialflink_tpu.utils.helper import generate_query_polygons

        self.stream, self.windows = stream, windows
        min_x, min_y, max_x, max_y = self.stream_cfg["bbox"]
        grid = UniformGrid(self.grid_cells, min_x, max_x, min_y, max_y)
        conf = QueryConfiguration(
            QueryType.WindowBased, window_size=float(self.cfg["window_s"]),
            slide_step=float(self.cfg["slide_s"]),
            approximate_query=bool(self.cfg["approximate"]))
        self.op = PointPolygonRangeQuery(conf, grid)
        if not hasattr(self.op, "last_range_kernel"):
            # ``checked`` holds the run to the kernel that ran: a program
            # that cannot say which it built cannot run this cell.
            raise spec.SpecError(
                "this program's PointPolygonRangeQuery keeps no "
                "last_range_kernel: it cannot run a cell of "
                f"{self.cfg['name']}")
        # The run's seed is not handed to an adapter: the polygons take
        # theirs from the seeded stream, so the same --seed gives the same
        # polygons and another seed gives others.
        seed = int(np.asarray(stream.x[:2]).view(np.uint64).sum() % (1 << 32))
        self.polygons = generate_query_polygons(
            self.n_polygons, min_x, min_y, max_x, max_y,
            grid_size=int(self.cfg["grid_cells"]), seed=seed)
        _hold_query_set(self.polygons, self.n_polygons, self.stream_cfg["bbox"],
                        int(self.cfg["grid_cells"]))
        # One cycle's timestamps, built before the window: a chunk is views
        # into the pool plus, past the first cycle, one offset added.
        self.ts_pool = stream.ts(0, stream.pool)
        self.cycle_ms = stream.pool * 1000 // stream.rate_eps

    def _chunk(self, lo: int, hi: int) -> Dict[str, np.ndarray]:
        s = self.stream
        a, b = lo % s.pool, (hi - 1) % s.pool + 1
        if b <= a:
            raise ValueError("a segment may not wrap the pool: make "
                             "pool_events a multiple of batch_events")
        ts = self.ts_pool[a:b]
        if lo >= s.pool:
            ts = ts + (lo // s.pool) * self.cycle_ms
        return {"ts": ts, "x": s.x[a:b], "y": s.y[a:b], "oid": s.ids[a:b]}

    def _chunks(self, segments, clock) -> Iterator[Dict[str, np.ndarray]]:
        for lo, hi in segments:
            self.t_handed = clock()
            yield self._chunk(lo, hi)

    def _range_counters(self) -> Optional[Dict[str, int]]:
        """The program's range counters now (None: telemetry is off, or this
        program keeps none)."""
        from spatialflink_tpu.telemetry import telemetry

        return telemetry.snapshot().get("range") if telemetry.enabled else None

    def run(self, feed) -> None:
        clock = time.perf_counter
        feed.on_open.append(
            lambda: setattr(self, "range_open", self._range_counters()))
        feed.on_close.append(
            lambda: setattr(self, "range_close", self._range_counters()))
        for _start, end, matched, dist in self.op.run_soa(
                self._chunks(feed.segments(), clock), self.polygons,
                float(self.cfg["query_polygons"]["radius"])):
            if feed.t_closed is not None:
                break  # the feed has ended: this is the open window's flush
            t = clock()
            feed.result(end, t)
            self.window_spans.append(("window", self.t_handed,
                                      t - self.t_handed))
            self.got.append((end, matched, dist))

    def health(self) -> Dict[str, Any]:
        import jax

        op = self.op
        kernel = getattr(op, "last_range_kernel", None)
        problems = []
        want = self.cfg["expect_range_kernel"]
        if jax.default_backend() == "tpu" and kernel != want:
            problems.append(
                f"the range kernel was {kernel!r}, expected {want!r} on a "
                "TPU (a program that cannot say keeps no last_range_kernel)")
        return {"problems": problems, "range_kernel": kernel,
                "range_cand": getattr(op, "_ncand", None),
                "range_budget": getattr(op, "_cand_budget", None)}

    def verify(self, feed) -> Dict[str, Any]:
        ref_mod = spec.plugin("references", self.cfg["reference"])
        cfg, s, wn = self.cfg, self.stream, self.windows
        ref = ref_mod.Reference(
            bbox=self.stream_cfg["bbox"], grid_cells=self.grid_cells,
            polygons=[[np.asarray(r, np.float64) for r in p.rings]
                      for p in self.polygons],
            radius=float(cfg["query_polygons"]["radius"]),
            tol=float(cfg["tolerance_deg"]))
        per_window = int(s.rate_eps * wn.size_ms // 1000)
        if wn.size_ms != wn.slide_ms or s.pool % per_window:
            return {"checked": 0, "wrong": {}, "problems": [
                "the adapter checks tumbling windows that divide the pool"]}
        #: per distinct window of the pool: the reference's matches, and the
        #: result arrays already found right
        want: Dict[int, Any] = {}
        passed: Dict[int, Tuple[Dict[str, np.ndarray], np.ndarray]] = {}
        wrong: Dict[int, List[str]] = {}
        problems = []
        edge = matches = repeats = outside = 0
        deviation = 0.0
        for i, (end, matched, dist) in enumerate(self.got):
            k = wn.k_of(end)
            if k != i:
                problems.append(f"result {i} is window {k}: a window is "
                                "missing or out of order")
                break
            matches += len(dist)
            lo = k * per_window
            key = lo % s.pool
            cycle_ms = (lo // s.pool) * self.cycle_ms
            ok = passed.get(key)
            if ok is not None and _same(ok, matched, dist, cycle_ms):
                repeats += 1
                continue
            window = self._chunk(lo, lo + per_window)
            if key not in want:
                want[key] = ref.matches(window["x"], window["y"])
                edge += ref.edge_points(want[key])
            bad, read = ref.check(want[key], window, matched, dist)
            deviation = max(deviation, read["max_distance_deviation"])
            outside += read["points_wrong_outside_band"]
            if bad:
                wrong[k] = bad
            else:
                first = dict(matched, ts=matched["ts"] - cycle_ms)
                passed.setdefault(key, (first, dist))
        return {"checked": len(self.got), "wrong": wrong,
                "problems": problems, "distinct_windows": len(want),
                "repeats_equal_to_a_checked_result": repeats,
                "polygons": len(self.polygons), "matches": matches,
                "points_in_tolerance_band": edge,
                # the two readings the limits are held against, over every
                # window compared in full (tolerance_deg; 0)
                "max_distance_deviation_deg": deviation,
                "points_wrong_outside_band": outside}

    def host_spans(self, feed, telemetry_events
                   ) -> List[Tuple[str, float, float]]:
        """``window`` = from the hand-over of the segment that lets the
        window fire to the result on the host: consolidation, centring, cell
        assignment and padding (``range.assemble``), ship, the program, the
        fetch, the selection of the matches (``range.select``). The harness
        adds ``generate`` and calls the rest between two pulls ``ingest``:
        the chunks appended to the assembler before the window fires."""
        return list(self.window_spans)

    def extras(self) -> Dict[str, Any]:
        """``range.<counter>``: the operator's range counters over the
        window; ``range.cand`` / ``range.budget``: the gauges at its close.
        Empty where the program keeps none."""
        a, b = self.range_open, self.range_close
        if b is None:
            return {}
        a = a or {}
        out = {f"range.{k}": b.get(k, 0) - a.get(k, 0)
               for k in RANGE_COUNTERS}
        out.update({f"range.{k}": b[k] for k in RANGE_GAUGES if k in b})
        return out


def _hold_query_set(polygons, count: int, bbox, grid_cells: int) -> None:
    """The query set is data that program and reference are both fed, made
    by the program's port of the upstream's generator: hold it to what the
    configuration says it is, so that a drift in the port fails the cell
    where ``correct`` could not see it. ``count`` closed 5-vertex rings,
    each an axis-aligned rectangle of one grid cell's span, counter-clockwise
    from its lower-left corner, inside the bbox."""
    min_x, min_y, max_x, max_y = bbox
    span = np.array([(max_x - min_x) / grid_cells, (max_y - min_y) / grid_cells])
    if len(polygons) != count or any(
            len(p.rings) != 1 or np.shape(p.rings[0]) != (5, 2)
            for p in polygons):
        raise spec.SpecError(f"the query set is not {count} polygons of one "
                             "5-vertex ring each")
    rings = np.stack([np.asarray(p.rings[0], np.float64) for p in polygons])
    corner = rings[:, :1]
    want = corner + span * np.array([[0, 0], [1, 0], [1, 1], [0, 1], [0, 0]])
    if not np.allclose(rings, want, rtol=0, atol=1e-9):
        raise spec.SpecError("a query ring is not the closed rectangle of "
                             f"one grid cell's span {span.tolist()}")
    if (rings < np.array([min_x, min_y]) - 1e-9).any() or \
            (rings > np.array([max_x, max_y]) + 1e-9).any():
        raise spec.SpecError("a query ring leaves the bbox")
    if len({tuple(r[0]) for r in rings}) != count:
        raise spec.SpecError("two query rings share a corner: the set is "
                             "not placed uniformly")


def _same(ok, matched, dist, cycle_ms: int) -> bool:
    """Is this result the checked one of the same pool window, array for
    array, its timestamps gone on by ``cycle_ms``?"""
    first, first_dist = ok
    return (len(dist) == len(first_dist)
            and np.array_equal(dist, first_dist)
            and set(matched) == set(first)
            and all(np.array_equal(
                matched[k] - cycle_ms if k == "ts" else matched[k], first[k])
                for k in first))
