"""Plain reference of the continuous kNN over 6 B/pt wire records (numpy only).

Copied from ``chip_smoke.py`` (``knn_reference`` and leg B's comparison; the
original is listed in PERF.md for a later PR to drop). It quantises with the
wire format's published parameters — ``scale`` is the smallest ``m * 2^e >=
span / 65535`` with an 8-bit ``m``, ``origin`` the bbox corner in float32 — and
measures float32 distances on the dequantised coordinates, which is what every
consumer of the same records computes; it uses no code of the package.
"""

from __future__ import annotations

import math
from typing import List, Sequence, Tuple

import numpy as np

U16_MAX = 65535


def wire_scale(span: float) -> float:
    """Smallest ``m * 2^e`` >= span/65535 with an integer ``m`` of 8 bits
    (``uint16 * scale`` is then exact in float32)."""
    target = span / U16_MAX
    e = math.floor(math.log2(target)) - 7
    m = math.ceil(target / 2.0 ** e)
    if m > 255:
        m, e = 128, e + 1
    return m * 2.0 ** e


class Reference:
    """Brute-force expected neighbours of one query point.

    ``bbox``: (min_x, min_y, max_x, max_y); ``query``: (x, y)."""

    def __init__(self, *, bbox, query: Sequence[float], radius: float, k: int,
                 ids: int):
        min_x, min_y, max_x, max_y = bbox
        self.origin = np.asarray([min_x, min_y], np.float32)
        self.scale = np.asarray([wire_scale(max_x - min_x),
                                 wire_scale(max_y - min_y)], np.float32)
        self.query = np.asarray(query, np.float32)
        self.radius = np.float32(radius)
        self.k, self.ids = int(k), int(ids)
        # float32 distances near the radius: the device's fused multiply-add
        # and sqrt may round differently from numpy's by a few ulps.
        self.tol = 8 * np.spacing(self.radius)

    def quantize(self, x, y) -> Tuple[np.ndarray, np.ndarray]:
        o, s = self.origin.astype(np.float64), self.scale.astype(np.float64)
        xq = np.clip(np.floor((np.asarray(x, np.float64) - o[0]) / s[0]),
                     0, U16_MAX).astype(np.uint16)
        yq = np.clip(np.floor((np.asarray(y, np.float64) - o[1]) / s[1]),
                     0, U16_MAX).astype(np.uint16)
        return xq, yq

    def minima(self, xq, yq, oid) -> np.ndarray:
        """Per object id the minimum float32 distance over the window's points
        within ``radius + tol`` (+inf where none is): the slack keeps a
        reference value for an object the device puts just inside."""
        xf = xq.astype(np.float32) * self.scale[0] + self.origin[0]
        yf = yq.astype(np.float32) * self.scale[1] + self.origin[1]
        dx, dy = xf - self.query[0], yf - self.query[1]
        dist = np.sqrt(dx * dx + dy * dy)
        mins = np.full(self.ids, np.inf, np.float32)
        hit = dist <= self.radius + self.tol
        np.minimum.at(mins, oid[hit], dist[hit])
        return mins

    def compare(self, mins: np.ndarray, segs, dists, nv: int) -> List[str]:
        """Problems of one window's result against its ``minima``."""
        segs, dists = np.asarray(segs), np.asarray(dists)
        r, tol, k = self.radius, self.tol, self.k
        bad: List[str] = []
        n_in = int((mins <= r).sum())
        near_edge = int((np.abs(mins - r) <= tol).sum())
        if not (nv == len(segs) == len(dists) and nv <= k):
            return [f"result shape nv={nv} segs={len(segs)} dists={len(dists)}"]
        if abs(nv - min(k, n_in)) > near_edge:
            bad.append(f"nv {nv}, reference has {n_in} objects in radius")
        if not (np.all(np.isfinite(dists)) and np.all(np.diff(dists) >= 0)):
            bad.append("distances not finite ascending")
        if len(set(segs.tolist())) != nv:
            bad.append("duplicate object ids")
        if np.any((segs < 0) | (segs >= self.ids)):
            return bad + ["object id out of range"]
        if not np.all(np.abs(dists - mins[segs]) <= tol):
            bad.append("a neighbour's distance differs from its object's "
                       "brute-force minimum")
        if nv:
            rest = np.ones(len(mins), bool)
            rest[segs] = False
            if rest.any() and mins[rest].min() < dists[-1] - tol:
                bad.append("a closer object was left out of the top-k")
        return bad
