"""``range_polygons``' plain reference at 10^4 zones (numpy only).

The semantics, the tolerance band and the comparison are ``range_polygons``'
to the letter (:class:`Reference` is its subclass and changes nothing but how
:meth:`Reference.matches` finds the points near a polygon and how it takes a
ring's edges). At 10,000 zones over points that crowd the centre the base's
x-sort selects, per zone, every point of a 0.025-degree strip of the city —
tens of thousands where a few hundred lie in the zone's box — and walks a
ring edge by edge: 24 s a window of 1,000,000 points. Here:

- the points in the grid are bucketed by their cell (one sort by ``xi * n +
  yi``, the base's own floor arithmetic); a zone's box grown by ``radius +
  tol`` takes the buckets of the cells it spans — by the same floor
  expression, which is monotone, so no point of the box is in another
  bucket — and then the box's own points by a mask;
- :func:`polygon_distance` takes all of a ring's edges at once, a (points,
  edges) array each, with ``range_polygons.brute_force``'s expressions, and
  roots the least squared distance once (the root is monotone).

It uses no code of the package; tier-1 holds it to ``brute_force``.
"""

from __future__ import annotations

import numpy as np

from benchmark.references import range_polygons
from benchmark.references.range_polygons import Rings, Want


def polygon_distance(x: np.ndarray, y: np.ndarray, rings: Rings) -> np.ndarray:
    """Distance of each point to one polygon: 0 inside (even-odd over every
    ring's edges, a +x ray, half-open in y), else the least distance to an
    edge."""
    x = np.asarray(x, np.float64)[:, None]
    y = np.asarray(y, np.float64)[:, None]
    crossings = np.zeros(len(x), np.int64)
    d2min = np.full(len(x), np.inf)
    for ring in rings:
        ring = range_polygons._closed(ring)
        x1, y1 = ring[:-1, 0], ring[:-1, 1]
        x2, y2 = ring[1:, 0], ring[1:, 1]
        dx, dy = x2 - x1, y2 - y1
        l2 = dx * dx + dy * dy
        inv = np.divide(1.0, l2, out=np.zeros_like(l2), where=l2 > 0)
        ax, ay = x - x1, y - y1
        with np.errstate(divide="ignore", invalid="ignore"):
            # a horizontal edge spans no y, so its quotient is never used
            crossings += (((y1 > y) != (y2 > y))
                          & (x < x1 + ay / dy * dx)).sum(axis=1)
        t = ax * dx
        t += ay * dy
        t *= inv  # a degenerate edge: t = 0, its first end
        np.clip(t, 0.0, 1.0, out=t)
        ax -= t * dx
        ay -= t * dy
        ax *= ax
        ay *= ay
        ax += ay
        d2min = np.minimum(d2min, ax.min(axis=1, initial=np.inf))
    return np.where(crossings % 2 == 1, 0.0, np.sqrt(d2min))


class Reference(range_polygons.Reference):
    __doc__ = range_polygons.Reference.__doc__

    def matches(self, x, y) -> Want:
        """Every point with ``distance <= radius + tol``, as (window index
        ascending, its distance to the nearest polygon): the base's answer,
        found through the points' cell buckets."""
        x, y = np.asarray(x, np.float64), np.asarray(y, np.float64)
        reach = self.radius + self.tol
        n = self.n

        def cells(px, py):
            return (np.floor((np.asarray(px, np.float64) - self.min_x)
                             / self.cell),
                    np.floor((np.asarray(py, np.float64) - self.min_y)
                             / self.cell))

        xi, yi = cells(x, y)
        live = np.nonzero((xi >= 0) & (xi < n) & (yi >= 0) & (yi < n))[0]
        key = (xi[live] * n + yi[live]).astype(np.int64)
        order = live[np.argsort(key, kind="stable")]
        starts = np.searchsorted(np.sort(key), np.arange(n * n + 1))
        best = np.full(len(x), np.inf)
        for rings in self.polygons:
            v = np.concatenate(rings)
            lo, hi = v.min(axis=0) - reach, v.max(axis=0) + reach
            # the box's corner cells, clipped to the grid (a box off the
            # grid takes a bucket on its edge, which the mask then empties)
            (cx0, cx1), (cy0, cy1) = (
                np.clip(c, 0, n - 1).astype(np.int64)
                for c in cells([lo[0], hi[0]], [lo[1], hi[1]]))
            idx = np.concatenate([
                order[starts[c * n + cy0]:starts[c * n + cy1 + 1]]
                for c in range(cx0, cx1 + 1)])
            idx = idx[(x[idx] >= lo[0]) & (x[idx] <= hi[0])
                      & (y[idx] >= lo[1]) & (y[idx] <= hi[1])]
            if len(idx):
                best[idx] = np.minimum(
                    best[idx], polygon_distance(x[idx], y[idx], rings))
        idx = np.nonzero(best <= reach)[0]
        return idx, best[idx]
