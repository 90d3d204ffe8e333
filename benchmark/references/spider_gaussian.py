"""Spider's Gaussian positions, as a plain function of a uniform draw (numpy
and float64 only; no code of the package).

Source: Katiyar, Vu, Eldawy, Migliorini, Belussi, "Spider: a spatial data
generator", ACM SIGSPATIAL 2020 (spider.cs.ucr.edu; the same distribution as
SpatialHadoop's ``RandomSpatialGenerator``), distribution *Gaussian*: each
coordinate N(0.5, 0.1) on the unit square, mapped onto the deployment's bbox
— the stand-in the spatial-join literature uses for a city that crowds its
centre. Written from memory (no network here): the 0.1, the 0.5 and the
treatment of the tail are ``assumed`` in the configuration's file.

The harness hands an adapter its seeded stream with positions uniform over
the bbox. Box-Muller of an event's two uniform coordinates *is* a Gaussian
draw, so the mapping is a pure function of that stream, event by event:

    u1 = (x - min_x) / span_x        u2 = (y - min_y) / span_y
    z1 = sqrt(-2 ln(1 - u1)) cos(2 pi u2)
    z2 = sqrt(-2 ln(1 - u1)) sin(2 pi u2)
    x' = cx + sigma span_x z1        y' = cy + sigma span_y z2

with (cx, cy) the bbox's point at ``mean`` of each span. Same seed, same
stream, same positions. Spider redraws a point that leaves the unit square;
here it stays where it fell: a point beyond 5 sigma (about 1 in 10^6) lies
outside the bbox — on a square grid laid from the bbox's lower corner it may
still be inside the grid on the longer axis's far side — and a point outside
the deployment's grid joins nothing, in program and reference alike.
"""

from __future__ import annotations

from typing import Sequence, Tuple

import numpy as np


def positions(x, y, bbox: Sequence[float], mean: float = 0.5,
              sigma: float = 0.1) -> Tuple[np.ndarray, np.ndarray]:
    """``(x', y')``: the uniform draw ``(x, y)`` over ``bbox`` =
    (min_x, min_y, max_x, max_y), mapped to N(``mean``, ``sigma``) of each
    span (float64)."""
    min_x, min_y, max_x, max_y = (float(v) for v in bbox)
    span_x, span_y = max_x - min_x, max_y - min_y
    u1 = (np.asarray(x, np.float64) - min_x) / span_x
    u2 = (np.asarray(y, np.float64) - min_y) / span_y
    # 1 - u1 lies in (0, 1]; a draw that rounded up to the bbox's edge would
    # give ln 0: it is held at the smallest step below 1 instead
    rho = np.sqrt(-2.0 * np.log(np.maximum(1.0 - u1, 2.0 ** -53)))
    angle = 2.0 * np.pi * u2
    return (min_x + mean * span_x + sigma * span_x * rho * np.cos(angle),
            min_y + mean * span_y + sigma * span_y * rho * np.sin(angle))
