"""Adapter: ``range_polygons_soa``'s geofencing on a city that crowds its
centre, against zones that crowd it too.

The harness builds its one seeded stream with positions uniform over the
bbox; before the clock, ``prepare`` maps every event's draw to the
configuration's ``stream.positions`` — Spider's Gaussian
(``benchmark/references/spider_gaussian.py``, a pure function of the draw,
as ``join_soa_skew`` maps its points) — and builds the query set with the
configuration's ``query_polygons.generator``: Spider's polygons
(``benchmark/references/spider_polygons.py``), their seed taken from the bits
of the stream's first two coordinates before the mapping, as the base takes
the seed of its rectangles. Program and reference are both fed the set, so
``prepare`` holds it to what the configuration says it is
(:func:`_hold_query_set`). The operator (``PointPolygonRangeQuery(conf,
grid)``, nothing else given), the feed, ``verify`` against
``range_polygons`` on the mapped points, ``health`` and the host spans are
the base's.

Beside the base's counters the readers get the work and the size of the
zones' index: the counter ``range.edge_slots`` (the polygon edges the
window's programs evaluated, padding included) and the gauge
``range.index_bytes`` (the index's device bytes) — each only where the
program keeps it; the metrics that read them report nothing otherwise.
"""

from __future__ import annotations

from typing import Any, Dict

import numpy as np

from benchmark.adapters import range_polygons_soa
from benchmark.harness import spec

#: what this deployment adds to ``range_polygons_soa``'s counters and gauges
SPIDER_COUNTERS = ("edge_slots",)
SPIDER_GAUGES = ("index_bytes",)


class Adapter(range_polygons_soa.Adapter):

    def prepare(self, stream, windows) -> None:
        from spatialflink_tpu.grid import UniformGrid
        from spatialflink_tpu.models.objects import Polygon
        from spatialflink_tpu.operators import (
            PointPolygonRangeQuery,
            QueryConfiguration,
            QueryType,
        )

        self.stream, self.windows = stream, windows
        bbox = self.stream_cfg["bbox"]
        # the seed as the base takes it: from the uniform draw, so the same
        # --seed gives the same zones and another seed gives others
        seed = int(np.asarray(stream.x[:2]).view(np.uint64).sum() % (1 << 32))
        how = self.stream_cfg["positions"]
        if how["distribution"] != "gaussian":
            raise ValueError(f"range_polygons_spider maps to a gaussian, not "
                             f"to {how['distribution']!r}")
        stream.x, stream.y = spec.plugin("references", how["generator"]) \
            .positions(stream.x, stream.y, bbox, mean=float(how["mean"]),
                       sigma=float(how["sigma"]))
        q = self.cfg["query_polygons"]
        rings = spec.plugin("references", q["generator"]).polygons(
            seed, self.n_polygons, bbox, polysize=float(q["polysize"]),
            maxseg=int(q["maxseg"]), mean=float(q["mean"]),
            sigma=float(q["sigma"]))
        self.polygons = [Polygon(obj_id=f"zone{i}", rings=[r])
                         for i, r in enumerate(rings)]
        _hold_query_set(self.polygons, self.n_polygons, bbox,
                        float(q["polysize"]), int(q["maxseg"]))
        min_x, min_y, max_x, max_y = bbox
        self.op = PointPolygonRangeQuery(
            QueryConfiguration(
                QueryType.WindowBased, window_size=float(self.cfg["window_s"]),
                slide_step=float(self.cfg["slide_s"]),
                approximate_query=bool(self.cfg["approximate"])),
            UniformGrid(self.grid_cells, min_x, max_x, min_y, max_y))
        # One cycle's timestamps, built before the window, as the base does.
        self.ts_pool = stream.ts(0, stream.pool)
        self.cycle_ms = stream.pool * 1000 // stream.rate_eps

    def extras(self) -> Dict[str, Any]:
        out = super().extras()
        a, b = self.range_open or {}, self.range_close
        if b is None:
            return out
        out.update({f"range.{k}": b[k] - a.get(k, 0)
                    for k in SPIDER_COUNTERS if k in b})
        out.update({f"range.{k}": b[k] for k in SPIDER_GAUGES if k in b})
        return out


def _hold_query_set(polygons, count: int, bbox, polysize: float,
                    maxseg: int) -> None:
    """The query set is data that program and reference are both fed: hold
    it to what the configuration says it is, so that a drift in the
    generator fails the cell where ``correct`` could not see it: ``count``
    polygons, each one closed ring of 3 to ``maxseg`` segments whose box is
    no wider than ``polysize`` of each span (every vertex lies within
    ``polysize`` / 2 of its centre). That the rings are simple is the
    generator's construction, held in tier-1."""
    min_x, min_y, max_x, max_y = bbox
    span = np.array([max_x - min_x, max_y - min_y])
    if len(polygons) != count or any(len(p.rings) != 1 for p in polygons):
        raise spec.SpecError(f"the query set is not {count} polygons of one "
                             "ring each")
    for p in polygons:
        ring = np.asarray(p.rings[0], np.float64)
        if not 4 <= len(ring) <= maxseg + 1 or \
                not np.array_equal(ring[0], ring[-1]):
            raise spec.SpecError(f"{p.obj_id}: not a closed ring of 3 to "
                                 f"{maxseg} segments")
        if (np.ptp(ring, axis=0) > polysize * span * (1 + 1e-9)).any():
            raise spec.SpecError(f"{p.obj_id}: wider than polysize "
                                 f"{polysize} of a span")
