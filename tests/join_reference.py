"""Plain reference of the windowed point-point distance join (numpy only).

This file is in the repo twice, byte for byte: ``tests/join_reference.py``
(the original, which tier-1 checks against the O(n^2) double loop) and
``benchmark/references/join_tdrive.py`` (the benchmark's copy, which decides
``correct`` in ``join.*`` cells). It uses no code of the package: float64
throughout, a hash grid of its own whose cell side is the radius, every left
point compared with the right points of its 3x3 reference cells, ``d <= r``
kept. Points outside the deployment's grid (``n x n`` square cells of side
``(max_x - min_x) / n`` from the bbox's lower corner) never join: the upstream
keys them to no cell.

Tolerance, and why: the chip computes distances in float32 on bbox-centred
coordinates (|x| <= span / 2, one ulp 1.2e-7 there), so a pair whose float64
distance lies within ``tol`` of the radius may fall either way. Every pair
outside that band must be there exactly once, and no other pair may be.
"""

from __future__ import annotations

from typing import List, Sequence, Tuple

import numpy as np

Pairs = Tuple[np.ndarray, np.ndarray, np.ndarray]  # left index, right index, d


def brute_force(lx, ly, rx, ry, radius: float) -> List[Tuple[int, int, float]]:
    """The O(n^2) double loop: what the hash-grid reference is itself held
    to, at a size where the loop is affordable."""
    out = []
    for i in range(len(lx)):
        for j in range(len(rx)):
            d = ((lx[i] - rx[j]) ** 2 + (ly[i] - ry[j]) ** 2) ** 0.5
            if d <= radius:
                out.append((i, j, d))
    return out


class Reference:
    """Expected pairs of one window of two point sets.

    ``bbox``: (min_x, min_y, max_x, max_y) of the deployment's grid;
    ``grid_cells``: its cells per side; ``tol``: half-width of the band around
    ``radius`` in which a pair may fall either way."""

    def __init__(self, *, bbox: Sequence[float], grid_cells: int,
                 radius: float, tol: float):
        self.min_x, self.min_y, self.max_x, _max_y = (float(v) for v in bbox)
        self.n = int(grid_cells)
        self.cell = (self.max_x - self.min_x) / self.n
        self.radius, self.tol = float(radius), float(tol)

    def in_grid(self, x, y) -> np.ndarray:
        xi = np.floor((np.asarray(x, np.float64) - self.min_x) / self.cell)
        yi = np.floor((np.asarray(y, np.float64) - self.min_y) / self.cell)
        return (xi >= 0) & (xi < self.n) & (yi >= 0) & (yi < self.n)

    def pairs(self, lx, ly, rx, ry) -> Pairs:
        """Every pair with ``d <= radius + tol`` (so that the band's pairs can
        be told from wrong ones), as (left index, right index, distance),
        sorted by (left, right)."""
        lx, ly, rx, ry = (np.asarray(a, np.float64) for a in (lx, ly, rx, ry))
        reach = self.radius + self.tol
        lkeep = np.nonzero(self.in_grid(lx, ly))[0]
        rkeep = np.nonzero(self.in_grid(rx, ry))[0]
        if not len(lkeep) or not len(rkeep):
            e = np.empty(0, np.int64)
            return e, e, np.empty(0)
        # A hash grid of the reference's own: cells of side `reach`, so a
        # partner lies in the 3x3 cells around a point's own.
        x0 = min(lx[lkeep].min(), rx[rkeep].min()) - reach
        y0 = min(ly[lkeep].min(), ry[rkeep].min()) - reach
        span_y = max(ly[lkeep].max(), ry[rkeep].max()) - y0
        ny = int(np.floor(span_y / reach)) + 3
        lcx = np.floor((lx[lkeep] - x0) / reach).astype(np.int64)
        lcy = np.floor((ly[lkeep] - y0) / reach).astype(np.int64)
        rkey = (np.floor((rx[rkeep] - x0) / reach).astype(np.int64) * ny
                + np.floor((ry[rkeep] - y0) / reach).astype(np.int64))
        order = np.argsort(rkey, kind="stable")
        rkey_sorted, rsorted = rkey[order], rkeep[order]
        got_l, got_r, got_d = [], [], []
        for dx in (-1, 0, 1):
            for dy in (-1, 0, 1):
                key = (lcx + dx) * ny + (lcy + dy)
                lo = np.searchsorted(rkey_sorted, key, side="left")
                n = np.searchsorted(rkey_sorted, key, side="right") - lo
                total = int(n.sum())
                if not total:
                    continue
                # candidate k of left point p is right point lo[p] + k
                li = np.repeat(np.arange(len(lkeep)), n)
                first = np.repeat(np.cumsum(n) - n, n)
                ri = rsorted[np.repeat(lo, n) + np.arange(total) - first]
                li = lkeep[li]
                d = np.sqrt((lx[li] - rx[ri]) ** 2 + (ly[li] - ry[ri]) ** 2)
                keep = d <= reach
                got_l.append(li[keep])
                got_r.append(ri[keep])
                got_d.append(d[keep])
        if not got_l:
            e = np.empty(0, np.int64)
            return e, e, np.empty(0)
        li, ri, d = (np.concatenate(a) for a in (got_l, got_r, got_d))
        order = np.lexsort((ri, li))
        return li[order], ri[order], d[order]

    def compare(self, want: Pairs, left_index, right_index, dist, count: int,
                overflow: int, n_right: int) -> List[str]:
        """What is wrong with one yielded window ([] = nothing): ``want`` from
        :meth:`pairs` on the same points; the rest as ``run_soa`` yields it;
        ``n_right`` = points of the right side (to key a pair by one number).
        """
        bad: List[str] = []
        if overflow != 0:
            bad.append(f"overflow {overflow}: the window was yielded short")
        left_index, right_index, dist = (
            np.asarray(a) for a in (left_index, right_index, dist))
        if count > len(left_index):
            return bad + [f"count {count} but {len(left_index)} slots yielded"]
        if (left_index[count:] != -1).any() or (right_index[count:] != -1).any():
            bad.append("a slot past the count is not -1")
        li = left_index[:count].astype(np.int64)
        ri = right_index[:count].astype(np.int64)
        dd = np.asarray(dist[:count], np.float64)
        if count and (li.min() < 0 or ri.min() < 0 or ri.max() >= n_right):
            return bad + ["an index inside the count is out of range"]
        got = li * n_right + ri
        order = np.argsort(got, kind="stable")
        got, dd = got[order], dd[order]
        twice = int((got[1:] == got[:-1]).sum())
        if twice:
            bad.append(f"{twice} pairs emitted twice")
        wl, wr, wd = want
        key = wl * n_right + wr  # sorted already: (left, right) order
        must = np.abs(wd - self.radius) > self.tol  # and inside: d <= r - tol
        must &= wd <= self.radius
        missing = np.setdiff1d(key[must], got, assume_unique=True)
        if len(missing):
            k = int(missing[0])
            bad.append(f"{len(missing)} pairs missing, first "
                       f"{(k // n_right, k % n_right)}")
        at = np.searchsorted(key, got)
        at_c = np.minimum(at, max(len(key) - 1, 0))
        known = (key[at_c] == got) if len(key) else np.zeros(len(got), bool)
        if not known.all():
            k = int(got[~known][0])
            bad.append(f"{int((~known).sum())} pairs beyond the radius, first "
                       f"{(k // n_right, k % n_right)}")
        off = np.abs(dd[known] - wd[at_c[known]]) > self.tol
        if off.any():
            bad.append(f"{int(off.sum())} distances differ from the "
                       f"reference's by more than {self.tol!r}")
        return bad

    def edge_pairs(self, want: Pairs) -> int:
        """Pairs of ``want`` inside the band around the radius."""
        return int((np.abs(want[2] - self.radius) <= self.tol).sum())
