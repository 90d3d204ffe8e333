"""Plain reference of the seven-node SNCB DAG (numpy and Python only).

Copied from ``chip_smoke.py`` (``sncb_reference`` / ``verify_sncb``; the
original is listed in PERF.md for a later PR to drop) and cut per window, so
that the comparison names the windows that differ. Nothing of the package's
kernels or operators is used: the zone files are read as data, the UTM
projection is Snyder's series (the package uses Krueger's), containment is an
even-odd ray cast in float64.

Semantics, for an in-order Point stream ``objID,timestamp,x,y`` (no speed, no
brake pressure, so Q2 and Q5 have nothing to aggregate and must stay silent):

q1        events inside a high-risk zone buffered by 20 m + 20 m, arrival order
q3 / q4   per device the window's trajectory as WKT (q4: middle half of the bbox)
staytime  per grid cell the time its devices dwelt, gaps charged to the earlier point
qserve    per standing query the k nearest distinct devices within its radius
"""

from __future__ import annotations

import json
import math
import os
import re
from typing import Any, Dict, List, Sequence, Tuple

import numpy as np

#: Points closer than this to a zone's decision boundary (metres) may be
#: classified either way: the device tests containment in float32 on
#: zone-centred coordinates (|coord| < 2^15 m, ulp <= 4 mm; distance error a
#: few ulps). Fixed beforehand from the dtype, not fitted.
ZONE_TOL_M = 0.05

NODES = ("q1", "q2", "q3", "q4", "q5", "staytime", "qserve")


def _utm31n(lon_deg, lat_deg):
    """WGS84 -> ETRS89 / UTM 31N metres (EPSG:25831), Snyder's series
    (USGS PP 1395 eqs. 8-9 ... 8-13); agrees with Krueger's to < 1 mm
    inside the zone."""
    a = 6378137.0
    f = 1.0 / 298.257222101
    e2 = f * (2.0 - f)
    ep2 = e2 / (1.0 - e2)
    k0 = 0.9996
    phi = np.deg2rad(np.asarray(lat_deg, np.float64))
    lam = np.deg2rad(np.asarray(lon_deg, np.float64) - 3.0)
    s, c, t = np.sin(phi), np.cos(phi), np.tan(phi)
    n = a / np.sqrt(1.0 - e2 * s * s)
    tt, cc, aa = t * t, ep2 * c * c, lam * c
    m = a * (
        (1 - e2 / 4 - 3 * e2**2 / 64 - 5 * e2**3 / 256) * phi
        - (3 * e2 / 8 + 3 * e2**2 / 32 + 45 * e2**3 / 1024) * np.sin(2 * phi)
        + (15 * e2**2 / 256 + 45 * e2**3 / 1024) * np.sin(4 * phi)
        - (35 * e2**3 / 3072) * np.sin(6 * phi)
    )
    east = 500_000.0 + k0 * n * (
        aa + (1 - tt + cc) * aa**3 / 6
        + (5 - 18 * tt + tt * tt + 72 * cc - 58 * ep2) * aa**5 / 120
    )
    north = k0 * (m + n * t * (
        aa**2 / 2 + (5 - tt + 9 * cc + 4 * cc * cc) * aa**4 / 24
        + (61 - 58 * tt + tt * tt + 600 * cc - 330 * ep2) * aa**6 / 720
    ))
    return east, north


def load_zone_rings(path: str) -> List[List[np.ndarray]]:
    """Exterior + hole rings (lon/lat) of a GeoJSON or POLYGON-WKT zone file."""
    with open(path) as f:
        text = f.read()
    polys = []
    if path.endswith(".geojson"):
        for feat in json.loads(text)["features"]:
            geom = feat["geometry"]
            sets = ([geom["coordinates"]] if geom["type"] == "Polygon"
                    else geom["coordinates"])
            polys += [[np.asarray(r, np.float64) for r in rings]
                      for rings in sets]
    else:
        if not text.strip().upper().startswith("POLYGON"):
            raise ValueError(f"{path}: POLYGON WKT only")
        polys.append([
            np.asarray([[float(v) for v in pt.split()]
                        for pt in ring.split(",")], np.float64)
            for ring in re.findall(r"\(([^()]+)\)", text)
        ])
    return polys


def zone_margin(polys, lon, lat, buffer_m):
    """Per point the signed slack (metres) of "inside any polygon OR within
    ``buffer_m`` of its boundary": positive = in, |slack| = distance from the
    decision boundary."""
    ex, ny = _utm31n(lon, lat)
    best = np.full(len(ex), -np.inf)
    for rings in polys:
        inside = np.zeros(len(ex), bool)
        dmin = np.full(len(ex), np.inf)
        for ring in rings:
            rx, ry = _utm31n(ring[:, 0], ring[:, 1])
            for i in range(len(rx) - 1):
                x1, y1, x2, y2 = rx[i], ry[i], rx[i + 1], ry[i + 1]
                if y1 != y2:  # even-odd ray cast
                    cross = ((y1 > ny) != (y2 > ny)) & (
                        ex < x1 + (ny - y1) / (y2 - y1) * (x2 - x1))
                    inside ^= cross
                dx, dy = x2 - x1, y2 - y1
                l2 = dx * dx + dy * dy
                t = np.clip(((ex - x1) * dx + (ny - y1) * dy) / l2, 0, 1) \
                    if l2 > 0 else 0.0
                dmin = np.minimum(dmin, np.hypot(ex - (x1 + t * dx),
                                                 ny - (y1 + t * dy)))
        slack = np.where(inside, buffer_m + dmin, buffer_m - dmin)
        best = np.maximum(best, slack)
    return best


def _wkt(lon, lat):
    if len(lon) == 1:
        return f"POINT ({lon[0]:g} {lat[0]:g})"
    return ("LINESTRING ("
            + ", ".join(f"{x:g} {y:g}" for x, y in zip(lon, lat)) + ")")


class Reference:
    """Per-window expected egress of every node, over one in-order stream.

    ``queries``: standing queries as dicts ``{tenant, qid, x, y, radius, k}``.
    ``bbox``: (min_x, min_y, max_x, max_y). ``names``: device names by id index.
    """

    def __init__(self, ts, dev_idx, lon, lat, *, names: Sequence[str],
                 bbox, grid_n: int, queries: Sequence[Dict[str, Any]],
                 risk_zone_file: str, zone_buffer_m: float):
        self.ts, self.dev, self.lon, self.lat = ts, dev_idx, lon, lat
        self.names, self.grid_n, self.queries = list(names), grid_n, queries
        min_x, min_y, max_x, max_y = bbox
        self.bbox = bbox
        cell = (max_x - min_x) / grid_n
        self.margin = zone_margin(load_zone_rings(risk_zone_file), lon, lat,
                                  zone_buffer_m)
        qx, qy = (max_x - min_x) / 4.0, (max_y - min_y) / 4.0
        self.in_q4 = ((lon >= min_x + qx) & (lon <= max_x - qx)
                      & (lat >= min_y + qy) & (lat <= max_y - qy))
        xi = np.floor((lon - min_x) / cell).astype(np.int64)
        yi = np.floor((lat - min_y) / cell).astype(np.int64)
        in_grid = (xi >= 0) & (xi < grid_n) & (yi >= 0) & (yi < grid_n)
        self.cell = np.where(in_grid, xi * grid_n + yi, grid_n * grid_n)
        # Device distances are float32 on bbox-centred coordinates.
        self.dist_tol = 16 * float(np.finfo(np.float32).eps) * (max_x - min_x)

    def window(self, start: int, end: int) -> Dict[str, Any]:
        """Expected egress of window [start, end): exact line lists for
        q2..q5 and staytime, ``(definite, ambiguous)`` lines for q1, and for
        qserve ``{(tenant, qid): [(device, dist), ...]}``."""
        lo, hi = np.searchsorted(self.ts, [start, end], side="left")
        w = slice(lo, hi)
        w_ts, w_dev = self.ts[w], self.dev[w]
        w_lon, w_lat = self.lon[w], self.lat[w]
        names, grid_n = self.names, self.grid_n
        out: Dict[str, Any] = {"q2": [], "q5": [], "events": int(hi - lo)}
        w_margin = self.margin[w]
        sure, amb = [], []
        for i in np.nonzero(w_margin > -ZONE_TOL_M)[0]:
            line = (f"{start},{end},{names[w_dev[i]]},"
                    f"{float(w_lon[i])!r},{float(w_lat[i])!r}")
            (sure if w_margin[i] >= ZONE_TOL_M else amb).append(line)
        out["q1"] = (sure, amb)
        order = np.argsort(w_ts, kind="stable")
        for node, keep in (("q3", None), ("q4", self.in_q4[w])):
            lines = []
            for d, name in enumerate(names):
                sel = order[(w_dev[order] == d)
                            & (True if keep is None else keep[order])]
                if len(sel):
                    lines.append(
                        f"{start},{end},{name},"
                        f"{_wkt(w_lon[sel].tolist(), w_lat[sel].tolist())}")
            out[node] = lines
        # staytime: consecutive same-device gaps go to the EARLIER point's
        # cell; a cell with >= 1 pair is emitted (even at 0 ms).
        dwell = np.zeros(grid_n * grid_n + 1, np.int64)  # last = "out"
        pairs = np.zeros(grid_n * grid_n + 1, np.int64)
        w_cell = self.cell[w]
        for d in range(len(names)):
            sel = order[w_dev[order] == d]
            np.add.at(dwell, w_cell[sel][:-1], np.diff(w_ts[sel]))
            np.add.at(pairs, w_cell[sel][:-1], 1)
        rows = sorted(
            ("out" if c == grid_n * grid_n
             else f"{c // grid_n:05d}{c % grid_n:05d}", int(dwell[c]))
            for c in np.nonzero(pairs)[0].tolist())
        out["staytime"] = [f"{start},{end},{n},{ms}" for n, ms in rows]
        # qserve: per standing query the k nearest DISTINCT devices by their
        # minimum distance within the radius (range and knn share the shape).
        out["qserve"] = {}
        for q in self.queries:
            dist = np.hypot(w_lon - q["x"], w_lat - q["y"])
            mins = [(float(dist[w_dev == d].min()), names[d])
                    for d in range(len(names)) if (w_dev == d).any()]
            mins = sorted(m for m in mins if m[0] <= q["radius"])
            out["qserve"][(q["tenant"], q["qid"])] = [
                (n, d_) for d_, n in mins[:int(q["k"])]]
        return out

    def compare(self, want: Dict[str, Any], got: Dict[str, List[str]]
                ) -> List[str]:
        """Problems of one window's committed lines (``got[node]``) against
        :meth:`window`'s ``want``; empty when they agree."""
        bad: List[str] = []
        for node in ("q2", "q3", "q4", "q5", "staytime"):
            have, exp = got.get(node, []), want[node]
            if len(have) != len(exp):
                bad.append(f"{node}: {len(have)} lines, reference {len(exp)}")
                continue
            for i, (g, w_) in enumerate(zip(have, exp)):
                if g != w_:
                    bad.append(f"{node}: line {i} differs: got {g[:120]!r} "
                               f"want {w_[:120]!r}")
                    break
        have = got.get("q1", [])
        sure, amb = want["q1"]
        sure_s, amb_s, have_s = set(sure), set(amb), set(have)
        missing = [ln for ln in sure if ln not in have_s]
        extra = [g for g in have if g not in sure_s and g not in amb_s]
        if missing:
            bad.append(f"q1: {len(missing)} in-zone events missing, "
                       f"first {missing[0]!r}")
        if extra:
            bad.append(f"q1: {len(extra)} out-of-zone events emitted, "
                       f"first {extra[0]!r}")
        if not missing and [g for g in have if g in sure_s] != sure:
            bad.append("q1: events out of arrival order")
        got_q: Dict[Tuple[str, str], List[Tuple[str, float]]] = {}
        for g in got.get("qserve", []):
            tenant, qid, _s, _e, obj, dist = g.split(",")
            got_q.setdefault((tenant, qid), []).append((obj, float(dist)))
        k_of = {(q["tenant"], q["qid"]): int(q["k"]) for q in self.queries}
        tol = self.dist_tol
        for key, exp in want["qserve"].items():
            have_q = got_q.pop(key, [])
            exp_d = dict(exp)
            ds = [d for _o, d in have_q]
            if len(have_q) != len(exp) or len(have_q) > k_of[key]:
                bad.append(f"qserve {key}: {len(have_q)} rows, reference "
                           f"{len(exp)}")
            elif not all(math.isfinite(d) for d in ds):
                bad.append(f"qserve {key}: non-finite distance")
            elif any(o not in exp_d or abs(d - exp_d[o]) > tol
                     for o, d in have_q):
                bad.append(f"qserve {key}: rows {have_q[:3]} differ from "
                           f"reference {exp[:3]}")
            elif any(b < a - tol for a, b in zip(ds, ds[1:])):
                bad.append(f"qserve {key}: distances not ascending")
        if got_q:
            bad.append(f"qserve: rows for unexpected keys {sorted(got_q)[:3]}")
        return bad


def read_committed(out_dir: str) -> Dict[Tuple[int, int], Dict[str, List[str]]]:
    """Every node's committed sink file, cut per window:
    ``{(start, end): {node: [lines, in file order]}}``."""
    by_window: Dict[Tuple[int, int], Dict[str, List[str]]] = {}
    for node in NODES:
        path = os.path.join(out_dir, f"{node}.csv")
        with open(path) as f:
            for line in f.read().splitlines():
                if node == "qserve":
                    _t, _q, s, e = line.split(",", 4)[:4]
                else:
                    s, e = line.split(",", 2)[:2]
                by_window.setdefault((int(s), int(e)), {}) \
                    .setdefault(node, []).append(line)
    return by_window
