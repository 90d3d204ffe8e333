"""Plain reference of the windowed trajectory join (numpy only).

The query (upstream ``tJoin/PointPointTJoinQuery.java:183+``, the dedup map of
``TJoinQuery.java:60-154``): within one window, every pair (trajectory of
stream A, trajectory of stream B) that has a point of each within ``radius``
of the other, once, with a representative distance — here the pair's *minimum*
point distance (the program's documented choice, PARITY.md deviation #4).

It uses no code of the package: float64 throughout on the coordinates as they
come (uncentred); the point pairs within reach come from the point join's
plain reference (``references/join_tdrive.py``: a hash grid of its own, held
to the O(n^2) loop in tier-1), and are grouped here by (left id, right id)
with one sort. :func:`brute_force` is the O(n^2) loop this file is itself held
to. Points outside the deployment's grid never join (``join_tdrive``).

Tolerance, and why: the chip computes distances in float32 on bbox-centred
coordinates (one ulp 1.2e-7 degrees there), so a trajectory pair whose float64
minimum lies within ``tol`` of the radius may fall either way. Every pair
whose minimum is further inside must be there exactly once, no pair whose
minimum is further outside may be, and every reported minimum lies within
``tol`` of the reference's.
"""

from __future__ import annotations

from typing import Dict, List, Sequence, Tuple

import numpy as np

from benchmark.references import join_tdrive

TPairs = Tuple[np.ndarray, np.ndarray, np.ndarray]  # left id, right id, min d


def brute_force(lx, ly, lo, rx, ry, ro, radius: float
                ) -> Dict[Tuple[int, int], float]:
    """The O(n^2) double loop: {(left id, right id): minimum distance} over
    the point pairs with ``d <= radius``."""
    out: Dict[Tuple[int, int], float] = {}
    for i in range(len(lx)):
        for j in range(len(rx)):
            d = ((lx[i] - rx[j]) ** 2 + (ly[i] - ry[j]) ** 2) ** 0.5
            if d <= radius:
                key = (int(lo[i]), int(ro[j]))
                out[key] = min(out.get(key, d), d)
    return out


class Reference:
    """Expected trajectory pairs of one window of two point sets.

    ``bbox``, ``grid_cells``, ``radius``, ``tol`` as the point join's
    reference takes them; ``num_ids``: ids lie in ``[0, num_ids)`` (only used
    to key a pair by one int64)."""

    def __init__(self, *, bbox: Sequence[float], grid_cells: int,
                 radius: float, tol: float, num_ids: int):
        self.points = join_tdrive.Reference(
            bbox=bbox, grid_cells=grid_cells, radius=radius, tol=tol)
        self.radius, self.tol = float(radius), float(tol)
        self.num_ids = int(num_ids)

    def tpairs(self, lx, ly, lo, rx, ry, ro) -> TPairs:
        """Every trajectory pair whose minimum point distance is
        ``<= radius + tol`` (so that the band's pairs can be told from wrong
        ones), as (left id, right id, minimum), sorted by (left, right)."""
        li, ri, d = self.points.pairs(lx, ly, rx, ry)
        lo, ro = np.asarray(lo, np.int64), np.asarray(ro, np.int64)
        for ids in (lo, ro):
            if len(ids) and (ids.min() < 0 or ids.max() >= self.num_ids):
                raise ValueError("an id outside [0, num_ids)")
        key = lo[li] * self.num_ids + ro[ri]
        order = np.lexsort((d, key))
        key, d = key[order], d[order]
        first = np.ones(len(key), bool)
        first[1:] = key[1:] != key[:-1]
        key, d = key[first], d[first]
        return key // self.num_ids, key % self.num_ids, d

    def _match(self, want: TPairs, left_oid, right_oid, dist):
        """Sorted keys of ``want`` and of what was yielded, the yielded
        distances in that order, and for each yielded pair whether ``want``
        has it and where."""
        wl, wr, wd = want
        key = wl * self.num_ids + wr  # sorted already: (left, right) order
        got = (np.asarray(left_oid, np.int64) * self.num_ids
               + np.asarray(right_oid, np.int64))
        order = np.argsort(got, kind="stable")
        got, dd = got[order], np.asarray(dist, np.float64)[order]
        at = np.minimum(np.searchsorted(key, got), max(len(key) - 1, 0))
        known = (key[at] == got) if len(key) else np.zeros(len(got), bool)
        return key, got, dd, at, known

    def compare(self, want: TPairs, left_oid, right_oid, dist, count: int,
                overflow: int) -> List[str]:
        """What is wrong with one yielded window ([] = nothing): ``want`` from
        :meth:`tpairs` on the same points; the rest as ``run_soa`` yields
        it (three arrays of ``count`` trajectory pairs)."""
        bad: List[str] = []
        if overflow != 0:
            bad.append(f"overflow {overflow}: the window was yielded short")
        lens = {len(left_oid), len(right_oid), len(dist)}
        if lens != {count}:
            return bad + [f"count {count} but arrays of {sorted(lens)} yielded"]
        lo, ro = np.asarray(left_oid), np.asarray(right_oid)
        if count and (min(lo.min(), ro.min()) < 0
                      or max(lo.max(), ro.max()) >= self.num_ids):
            return bad + ["an id inside the count is out of range"]
        key, got, dd, at, known = self._match(want, lo, ro, dist)
        twice = int((got[1:] == got[:-1]).sum())
        if twice:
            bad.append(f"{twice} trajectory pairs emitted twice")
        wd = want[2]
        must = (np.abs(wd - self.radius) > self.tol) & (wd <= self.radius)
        missing = np.setdiff1d(key[must], got, assume_unique=True)
        if len(missing):
            k = int(missing[0])
            bad.append(f"{len(missing)} trajectory pairs missing, first "
                       f"{(k // self.num_ids, k % self.num_ids)}")
        if not known.all():
            k = int(got[~known][0])
            bad.append(f"{int((~known).sum())} trajectory pairs beyond the "
                       f"radius, first {(k // self.num_ids, k % self.num_ids)}")
        off = np.abs(dd[known] - wd[at[known]]) > self.tol
        if off.any():
            bad.append(f"{int(off.sum())} minimum distances differ from the "
                       f"reference's by more than {self.tol!r}")
        return bad

    def max_deviation(self, want: TPairs, left_oid, right_oid, dist) -> float:
        """Largest |reported minimum - the reference's| over the yielded
        pairs the reference has (0.0 where there is none)."""
        _key, _got, dd, at, known = self._match(want, left_oid, right_oid,
                                                dist)
        if not known.any():
            return 0.0
        return float(np.abs(dd[known] - want[2][at[known]]).max())

    def edge_tpairs(self, want: TPairs) -> int:
        """Trajectory pairs of ``want`` whose minimum lies inside the band
        around the radius."""
        return int((np.abs(want[2] - self.radius) <= self.tol).sum())
