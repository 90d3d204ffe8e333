"""Plain reference of the windowed point-polygon range query (numpy only).

A point of the window matches when its distance to the nearest polygon of the
standing query set is at most ``radius``; the distance is 0 inside a polygon
(even-odd over all its rings, so a hole's inside is outside) and the least
point-segment distance to its rings' edges otherwise — what JTS's
``point.distance(polygon)`` gives the upstream's
``range/PointPolygonRangeQuery.java``.

It uses no code of the package: float64 throughout, uncentred degrees, rings
as lists of vertices. Per polygon it takes the points inside the polygon's
bounding box grown by ``radius + tol`` (an x-sort of its own, then a mask) and
computes those exactly, edge by edge. Tier-1 holds it to the O(N x P x E) loop
(:func:`brute_force`) on general rings. Points outside the deployment's grid
(``n x n`` square cells of side ``(max_x - min_x) / n`` from the bbox's lower
corner) never match: the upstream keys them to no cell.

The upstream's grid pruning is exact only while no cell is *guaranteed*
(``floor(radius / (cell x sqrt 2) - 1) < 0``, i.e. radius under 1.41 cells): a
guaranteed cell emits its points with no distance test. The reference refuses
a radius past that: the semantics there are another query's.

Tolerance, and why: the chip computes in float32 on bbox-centred coordinates
(|x| <= span / 2, one ulp 1.2e-7 there), so a point whose float64 distance
lies within ``tol`` of the radius may fall either way, and a reported
distance may differ from the float64 one by ``tol``. Every point outside that
band must be there exactly once, with its own row, and no other point may be.
"""

from __future__ import annotations

import math
from typing import Dict, List, Sequence, Tuple

import numpy as np

Rings = Sequence[np.ndarray]       # one polygon: exterior first, then holes
Want = Tuple[np.ndarray, np.ndarray]  # window index (ascending), distance

_MIX = (0x9E3779B97F4A7C15, 0xC2B2AE3D27D4EB4F, 0x165667B19E3779F9,
        0x27D4EB2F165667C5)


def _closed(ring) -> np.ndarray:
    ring = np.asarray(ring, np.float64)
    if not np.array_equal(ring[0], ring[-1]):
        ring = np.concatenate([ring, ring[:1]])
    return ring


def polygon_distance(x: np.ndarray, y: np.ndarray, rings: Rings) -> np.ndarray:
    """Distance of each point to one polygon: 0 inside (even-odd over every
    ring's edges, a +x ray, half-open in y), else the least distance to an
    edge."""
    inside = np.zeros(len(x), bool)
    dmin = np.full(len(x), np.inf)
    for ring in rings:
        ring = _closed(ring)
        for (x1, y1), (x2, y2) in zip(ring[:-1], ring[1:]):
            if y1 != y2:
                inside ^= ((y1 > y) != (y2 > y)) & (
                    x < x1 + (y - y1) / (y2 - y1) * (x2 - x1))
            dx, dy = x2 - x1, y2 - y1
            l2 = dx * dx + dy * dy
            t = (np.clip(((x - x1) * dx + (y - y1) * dy) / l2, 0.0, 1.0)
                 if l2 > 0 else 0.0)
            dmin = np.minimum(dmin, np.hypot(x - (x1 + t * dx),
                                             y - (y1 + t * dy)))
    return np.where(inside, 0.0, dmin)


def brute_force(x, y, polygons: Sequence[Rings], radius: float
                ) -> List[Tuple[int, float]]:
    """The O(N x P x E) loop, one point, one polygon, one edge at a time:
    what the reference is itself held to, at a size where it is affordable.
    ``[(index, distance)]`` of the points with ``distance <= radius``."""
    out = []
    for i in range(len(x)):
        best = math.inf
        for rings in polygons:
            crossings, d = 0, math.inf
            for ring in rings:
                ring = _closed(ring)
                for (x1, y1), (x2, y2) in zip(ring[:-1], ring[1:]):
                    if (y1 > y[i]) != (y2 > y[i]) and \
                            x[i] < x1 + (y[i] - y1) / (y2 - y1) * (x2 - x1):
                        crossings += 1
                    dx, dy = x2 - x1, y2 - y1
                    l2 = dx * dx + dy * dy
                    t = 0.0 if l2 == 0 else min(1.0, max(0.0, (
                        (x[i] - x1) * dx + (y[i] - y1) * dy) / l2))
                    d = min(d, math.hypot(x[i] - (x1 + t * dx),
                                          y[i] - (y1 + t * dy)))
            best = min(best, 0.0 if crossings % 2 else d)
        if best <= radius:
            out.append((i, best))
    return out


def row_keys(arrays: Dict[str, np.ndarray]) -> np.ndarray:
    """One uint64 a row of {ts, x, y, oid}: the bit patterns mixed, so that
    two rows get the same key only when all four fields are equal (but for
    a collision of the hash, of chance 2^-64 a pair)."""
    key = np.zeros(len(arrays["ts"]), np.uint64)
    for name, mult in zip(("ts", "x", "y", "oid"), _MIX):
        a = np.ascontiguousarray(arrays[name])
        if a.dtype.kind == "f":
            a = a.astype(np.float64)
        else:
            a = a.astype(np.int64)
        bits = a.view(np.uint64)
        key = (key ^ bits) * np.uint64(mult)
        key ^= key >> np.uint64(29)
    return key


class Reference:
    """Expected matches of one window against the standing polygons.

    ``bbox``: (min_x, min_y, max_x, max_y) of the deployment's grid;
    ``grid_cells``: its cells per side; ``polygons``: per polygon its rings
    (exterior, then holes), each an (R, 2) array, closed or not; ``tol``:
    half-width of the band around ``radius`` in which a point may fall either
    way, and the most a reported distance may be off by."""

    def __init__(self, *, bbox: Sequence[float], grid_cells: int,
                 polygons: Sequence[Rings], radius: float, tol: float):
        self.min_x, self.min_y, self.max_x, _max_y = (float(v) for v in bbox)
        self.n = int(grid_cells)
        self.cell = (self.max_x - self.min_x) / self.n
        self.radius, self.tol = float(radius), float(tol)
        if math.floor(self.radius / (self.cell * math.sqrt(2.0)) - 1) >= 0:
            raise ValueError(
                f"radius {radius} gives the grid of {self.cell}-wide cells a "
                "guaranteed layer, whose points the upstream emits with no "
                "distance test: not the query this reference answers")
        self.polygons = [[_closed(r) for r in rings] for rings in polygons]

    def in_grid(self, x, y) -> np.ndarray:
        xi = np.floor((np.asarray(x, np.float64) - self.min_x) / self.cell)
        yi = np.floor((np.asarray(y, np.float64) - self.min_y) / self.cell)
        return (xi >= 0) & (xi < self.n) & (yi >= 0) & (yi < self.n)

    def matches(self, x, y) -> Want:
        """Every point with ``distance <= radius + tol`` (so that the band's
        points can be told from wrong ones), as (window index ascending, its
        distance to the nearest polygon)."""
        x, y = np.asarray(x, np.float64), np.asarray(y, np.float64)
        reach = self.radius + self.tol
        live = np.nonzero(self.in_grid(x, y))[0]
        order = live[np.argsort(x[live], kind="stable")]
        xs = x[order]
        best = np.full(len(x), np.inf)
        for rings in self.polygons:
            v = np.concatenate(rings)
            lo = np.searchsorted(xs, v[:, 0].min() - reach, side="left")
            hi = np.searchsorted(xs, v[:, 0].max() + reach, side="right")
            idx = order[lo:hi]
            idx = idx[(y[idx] >= v[:, 1].min() - reach)
                      & (y[idx] <= v[:, 1].max() + reach)]
            if len(idx):
                best[idx] = np.minimum(
                    best[idx], polygon_distance(x[idx], y[idx], rings))
        idx = np.nonzero(best <= reach)[0]
        return idx, best[idx]

    def compare(self, want: Want, window: Dict[str, np.ndarray],
                matched: Dict[str, np.ndarray], dist) -> List[str]:
        """What is wrong with one yielded window ([] = nothing): ``want``
        from :meth:`matches` on the same points; ``window`` the window's own
        {ts, x, y, oid}; ``matched`` and ``dist`` as ``run_soa`` yields
        them."""
        return self.check(want, window, matched, dist)[0]

    def check(self, want: Want, window: Dict[str, np.ndarray],
              matched: Dict[str, np.ndarray], dist
              ) -> Tuple[List[str], Dict[str, float]]:
        """:meth:`compare`'s list, and the readings it was decided on:
        ``max_distance_deviation`` (the largest |reported - reference|
        over the matched points the reference knows; limit ``tol``) and
        ``points_wrong_outside_band`` (missing + beyond the radius, outside
        the band; limit 0)."""
        bad: List[str] = []
        read = {"max_distance_deviation": 0.0, "points_wrong_outside_band": 0}
        fields = ("ts", "x", "y", "oid")
        if set(matched) != set(window) or not set(fields) <= set(matched):
            return [f"matched arrays {sorted(matched)}, the window's "
                    f"{sorted(window)}"], read
        dist = np.asarray(dist, np.float64)
        m = len(dist)
        if any(len(matched[k]) != m for k in matched):
            return [f"{m} distances beside arrays of "
                    f"{[len(matched[k]) for k in fields]} rows"], read
        # Which row of the window is each matched row?
        wkey = row_keys(window)
        order = np.argsort(wkey, kind="stable")
        wsorted = wkey[order]
        mkey = row_keys(matched)
        at = np.searchsorted(wsorted, mkey, side="left")
        at_c = np.minimum(at, max(len(wsorted) - 1, 0))
        own = (wsorted[at_c] == mkey) if len(wsorted) else np.zeros(m, bool)
        if not own.all():
            i = int(np.nonzero(~own)[0][0])
            bad.append(f"{int((~own).sum())} matched rows are not rows of "
                       f"the window, first at {i}")
        uniq, counts = np.unique(mkey[own], return_counts=True)
        held = (np.searchsorted(wsorted, uniq, side="right")
                - np.searchsorted(wsorted, uniq, side="left"))
        twice = int(np.maximum(counts - held, 0).sum())
        if twice:
            bad.append(f"{twice} rows emitted twice")
        got = order[at_c[own]]  # window index of each matched row
        got_d = dist[own]
        widx, wd = want
        must = widx[wd <= self.radius - self.tol]
        missing = np.setdiff1d(must, got)
        if len(missing):
            bad.append(f"{len(missing)} points missing, first row "
                       f"{int(missing[0])}")
        at = np.searchsorted(widx, got)
        at_w = np.minimum(at, max(len(widx) - 1, 0))
        known = (widx[at_w] == got) if len(widx) else np.zeros(len(got), bool)
        if not known.all():
            bad.append(f"{int((~known).sum())} points beyond the radius, "
                       f"first row {int(got[~known][0])}")
        read["points_wrong_outside_band"] = len(missing) + int((~known).sum())
        dev = np.abs(got_d[known] - wd[at_w[known]])
        if len(dev):
            read["max_distance_deviation"] = float(dev.max())
        off = dev > self.tol
        if off.any():
            bad.append(f"{int(off.sum())} distances differ from the "
                       f"reference's by more than {self.tol!r}")
        return bad, read

    def edge_points(self, want: Want) -> int:
        """Points of ``want`` inside the band around the radius."""
        return int((np.abs(want[1] - self.radius) <= self.tol).sum())
