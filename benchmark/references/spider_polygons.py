"""Spider's polygons on its Gaussian distribution, as a plain function of a
seed (numpy and float64 only; no code of the package).

Source: Katiyar, Vu, Eldawy, Migliorini, Belussi, "Spider: a spatial data
generator", ACM SIGSPATIAL 2020 (spider.cs.ucr.edu), geometry *polygon*,
distribution *Gaussian*, with its parameters ``cardinality`` (the count),
``polysize`` and ``maxseg``. Written from memory (no network here): the laws
below are ``assumed`` in the configuration's file. Each polygon is made so:

- its centre is N(``mean``, ``sigma``) on each axis of the unit square (the
  distribution ``spider_gaussian`` maps the points through);
- its segment count is uniform in [3, ``maxseg``];
- that many angles are drawn uniform in [0, 2 pi) and sorted, and drawn
  again while two neighbours (the last and the first included) lie pi or
  more apart: the centre then lies inside the ring;
- the vertex at each angle lies at a radius uniform in (0, ``polysize`` / 2]
  from the centre (unit-square units: ``polysize`` is a share of each span);
- the ring is closed and mapped onto the deployment's bbox.

Sorted distinct angles less than pi apart and positive radii make a ring
star-shaped about its centre, and so simple: each edge stays inside the wedge
between its two vertices' rays, and the wedges do not overlap. (Without the
redraw, a polygon of three vertices would leave its centre outside three
times in four, and its ring could cross itself.) A centre beyond 5 sigma (about 1 in 10^6) stays where
it fell, as the positions' tail does: a polygon outside the grid is in no
cell's list and near no point of the grid, in program and reference alike.
"""

from __future__ import annotations

from typing import List, Sequence, Tuple

import numpy as np


def draw(seed: int, count: int, *, polysize: float, maxseg: int,
         mean: float = 0.5, sigma: float = 0.1
         ) -> Tuple[np.ndarray, np.ndarray, np.ndarray, np.ndarray]:
    """The draw behind :func:`polygons`, in the unit square: ``(centre
    (count, 2), segments (count,), angle (total,), radius (total,))``, a
    polygon's angles (sorted) and radii one run of ``segments`` each, in
    polygon order."""
    if maxseg < 3:
        raise ValueError(f"maxseg {maxseg}: a ring needs 3 segments")
    rng = np.random.default_rng(seed)
    centre = rng.normal(mean, sigma, (count, 2))
    segments = rng.integers(3, maxseg + 1, count)
    total = int(segments.sum())
    # (0, polysize / 2]: 1 - u for u uniform in [0, 1)
    radius = (polysize / 2.0) * (1.0 - rng.uniform(0.0, 1.0, total))
    owner = np.repeat(np.arange(count), segments)
    first = np.cumsum(segments) - segments
    angle = np.empty(total)
    redraw = np.ones(total, bool)
    while redraw.any():
        at = np.nonzero(redraw)[0]  # whole polygons, in owner order
        fresh = rng.uniform(0.0, 2.0 * np.pi, len(at))
        # sorted within each polygon: by owner, then by angle
        angle[at] = fresh[np.lexsort((fresh, owner[at]))]
        # a gap of pi or more (the wrap-around one included) would leave
        # the centre outside the ring, which could then cross itself
        gap = np.diff(angle, append=0.0)
        gap[first + segments - 1] = angle[first] + 2.0 * np.pi \
            - angle[first + segments - 1]
        redraw = (np.maximum.reduceat(gap, first) >= np.pi)[owner]
    return centre, segments, angle, radius


def polygons(seed: int, count: int, bbox: Sequence[float], *,
             polysize: float, maxseg: int, mean: float = 0.5,
             sigma: float = 0.1) -> List[np.ndarray]:
    """``count`` closed rings, each a (segments + 1, 2) float64 array of
    degrees over ``bbox`` = (min_x, min_y, max_x, max_y); the same ``seed``
    gives the same rings."""
    centre, segments, angle, radius = draw(
        seed, count, polysize=polysize, maxseg=maxseg, mean=mean, sigma=sigma)
    min_x, min_y, max_x, max_y = (float(v) for v in bbox)
    span = np.array([max_x - min_x, max_y - min_y])
    owner = np.repeat(np.arange(count), segments)
    unit = centre[owner] + radius[:, None] * np.stack(
        [np.cos(angle), np.sin(angle)], axis=1)
    verts = np.array([min_x, min_y]) + unit * span
    first = np.cumsum(segments) - segments
    return [np.concatenate([verts[a:a + k], verts[a:a + 1]])
            for a, k in zip(first.tolist(), segments.tolist())]
