"""chip_smoke.py — does the program still start, and answer right, on the chip?

One process drives the two paths a user of this system calls, at sizes a
deployment would call real, on ONE TPU chip, and checks every window
against a plain numpy reference written here (nothing of the package's
kernels or operators is reused on the reference side):

- **Leg A — the served path through the CLI entry.**
  ``spatialflink_tpu.streaming_job.main`` with query option 10 (the
  seven-node SNCB DAG: Q1–Q5, StayTime, qserve) on a replayable ``csv:``
  source written from ``--seed``, ``--output <dir>`` and ``--checkpoint``
  on — transactional per-node sinks under one unit checkpoint. Settings
  are the upstream benchmark's (sncb/tests/BenchmarkRunner.java:25-38):
  20,000 events/s, 10 devices, the Brussels bbox, Q1 zones buffered
  20 m, 10 s windows sliding by 5 s, epoch-millisecond event times.
- **Leg B — the hot operator path at headline width.**
  ``PointPointKNNQuery.run_wire_panes`` fed by ``streams.wire.wire_panes``
  on the upstream's 100×100 Beijing grid: 1M-point windows sliding by
  500k points, k=50, r=0.05, 16,384 object ids, ``strategy="auto"``.
- **Leg C — the join on its TPU-default backend** (a short one: neither
  leg above joins, and on a TPU ``PointPointJoinQuery`` and ``TJoinQuery``
  default to the Pallas hit-extraction kernel): ``run_soa`` over one
  window of two 16,384-point streams, r=0.002, vs a brute-force cross
  join.

It refuses to run anywhere but on a TPU (exit 2, no result line), fails
on any window that differs from its reference, on any retry / failover /
degraded window, and on a selectable kernel that ended up off its TPU
form. Nothing is caught and turned into exit 0. The last stdout line is
``{"ok": true, "device": {...}}``.

    python chip_smoke.py [--seed N] [--duration-s S] [--workdir DIR]
"""

from __future__ import annotations

import argparse
import json
import math
import os
import re
import shutil
import sys
import tempfile
import time

import numpy as np

# Upstream SNCB benchmark settings (BenchmarkRunner.java:25-38).
SNCB_EPS = 20_000
SNCB_DEVICES = 10
SNCB_DURATION_S = 30
SNCB_WINDOW_S, SNCB_SLIDE_S = 10, 5
#: First event time: an epoch-ms value (NOT 0-based — the chip runs with
#: x64 off, and int32 time arithmetic must survive real timestamps).
T0_MS = 1_700_000_000_000

# Headline kNN settings (BASELINE.json config 2).
KNN_WINDOW_POINTS = 1_000_000
KNN_SLIDE_POINTS = 500_000
KNN_WINDOWS = 4
KNN_K = 50
KNN_RADIUS = 0.05
KNN_SEGMENTS = 16_384

#: Points closer than this to a zone's decision boundary (metres) may be
#: classified either way: the device tests containment in float32 on
#: zone-centred coordinates (|coord| < 2^15 m → ulp ≤ 4 mm; distance
#: error a few ulps). Fixed beforehand from the dtype, not fitted.
ZONE_TOL_M = 0.05


class SmokeFailure(AssertionError):
    """A leg's output disagreed with its reference or its health counts."""


def _check(cond, msg):
    if not cond:
        raise SmokeFailure(msg)


# ---------------------------------------------------------------------------
# Compile-time accounting (jax.monitoring; cold = XLA compiles, warm =
# persistent-cache retrievals — both land in the backend-compile event).


class CompileClock:
    def __init__(self):
        from jax import monitoring

        self.seconds = 0.0
        self.programs = 0
        self.cache_hits = 0
        self.cache_misses = 0
        monitoring.register_event_duration_secs_listener(self._on_duration)
        monitoring.register_event_listener(self._on_event)

    def _on_duration(self, event, secs, **_kw):
        if event == "/jax/core/compile/backend_compile_duration":
            self.seconds += float(secs)
            self.programs += 1

    def _on_event(self, event, **_kw):
        if event == "/jax/compilation_cache/cache_hits":
            self.cache_hits += 1
        elif event == "/jax/compilation_cache/cache_misses":
            self.cache_misses += 1

    def mark(self):
        return (self.seconds, self.programs, self.cache_hits,
                self.cache_misses)

    def since(self, mark):
        s, p, h, m = mark
        return {
            "compile_s": round(self.seconds - s, 3),
            "programs": self.programs - p,
            "cache_hits": self.cache_hits - h,
            "cache_misses": self.cache_misses - m,
        }


# ---------------------------------------------------------------------------
# Leg A reference: the SNCB DAG's semantics in plain numpy / Python.


def _utm31n(lon_deg, lat_deg):
    """WGS84 → ETRS89 / UTM 31N metres (EPSG:25831), Snyder's series
    (USGS PP 1395 eqs. 8-9…8-13) — deliberately NOT the package's
    Krüger series; the two agree to < 1 mm inside the zone."""
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


def _load_zone_rings(name):
    """Exterior+hole rings (lon/lat) of a bundled SNCB zone resource."""
    from spatialflink_tpu.sncb.common import RESOURCE_DIR

    with open(os.path.join(RESOURCE_DIR, name)) as f:
        text = f.read()
    polys = []
    if name.endswith(".geojson"):
        for feat in json.loads(text)["features"]:
            geom = feat["geometry"]
            sets = ([geom["coordinates"]] if geom["type"] == "Polygon"
                    else geom["coordinates"])
            polys += [[np.asarray(r, np.float64) for r in rings]
                      for rings in sets]
    else:  # WKT POLYGON ((x y, ...), (hole ...))
        _check(text.strip().upper().startswith("POLYGON"),
               f"{name}: reference parser handles POLYGON WKT only")
        polys.append([
            np.asarray([[float(v) for v in pt.split()]
                        for pt in ring.split(",")], np.float64)
            for ring in re.findall(r"\(([^()]+)\)", text)
        ])
    return polys


def _zone_margin(polys, lon, lat, buffer_m):
    """Per point: signed slack (metres) of "inside any polygon OR within
    ``buffer_m`` of its boundary" — positive = in, negative = out, and
    |slack| is how far the point sits from the decision boundary."""
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
        # inside: slack = distance to the buffered outline ≥ buffer_m
        slack = np.where(inside, buffer_m + dmin, buffer_m - dmin)
        best = np.maximum(best, slack)
    return best


def _wkt(lon, lat):
    if len(lon) == 1:
        return f"POINT ({lon[0]:g} {lat[0]:g})"
    return ("LINESTRING ("
            + ", ".join(f"{x:g} {y:g}" for x, y in zip(lon, lat)) + ")")


def sncb_reference(ts, dev, lon, lat, *, grid_bbox, grid_n, queries,
                   window_ms, slide_ms):
    """Expected per-node egress of the composed SNCB DAG for an in-order
    Point stream (device id, epoch-ms time, lon, lat — the CLI's CSV
    schema carries no speed or brake pressure, so Q2 and Q5 have nothing
    to aggregate and must stay silent).

    Returns ``{node: ...}``: exact line lists for q3/q4/staytime, for q1
    ``(lines, ambiguous_lines)``, for qserve ``{(start, end, tenant,
    qid): [(device, dist), ...]}``; q2/q5 → ``[]``."""
    min_x, min_y, max_x, max_y = grid_bbox
    cell = (max_x - min_x) / grid_n
    risk = _load_zone_rings("high_risk_zones.geojson")
    # build_sncb_dag: zones buffered 20 m, Q1 adds its 20 m proximity.
    margin = _zone_margin(risk, lon, lat, 40.0)
    qx, qy = (max_x - min_x) / 4.0, (max_y - min_y) / 4.0
    in_q4 = ((lon >= min_x + qx) & (lon <= max_x - qx)
             & (lat >= min_y + qy) & (lat <= max_y - qy))
    xi = np.floor((lon - min_x) / cell).astype(np.int64)
    yi = np.floor((lat - min_y) / cell).astype(np.int64)
    in_grid = (xi >= 0) & (xi < grid_n) & (yi >= 0) & (yi < grid_n)
    names = sorted(set(dev))
    dev_idx = np.searchsorted(names, dev)

    out = {"q1": ([], []), "q2": [], "q3": [], "q4": [], "q5": [],
           "staytime": [], "qserve": {}}
    first = (int(ts[0]) // slide_ms) * slide_ms - window_ms + slide_ms
    last = (int(ts[-1]) // slide_ms) * slide_ms
    for start in range(first, last + 1, slide_ms):
        end = start + window_ms
        lo, hi = np.searchsorted(ts, [start, end], side="left")
        if hi <= lo:
            continue
        w = slice(lo, hi)
        w_ts, w_dev, w_lon, w_lat = ts[w], dev_idx[w], lon[w], lat[w]
        # q1 — events in the buffered high-risk zones, arrival order.
        w_margin = margin[w]
        for i in np.nonzero(w_margin > -ZONE_TOL_M)[0]:
            line = (f"{start},{end},{names[w_dev[i]]},"
                    f"{float(w_lon[i])!r},{float(w_lat[i])!r}")
            (out["q1"][0] if w_margin[i] >= ZONE_TOL_M
             else out["q1"][1]).append(line)
        # q3/q4 — per-device trajectory WKT, points in (stable) time order.
        order = np.argsort(w_ts, kind="stable")
        for node, keep in (("q3", None), ("q4", in_q4[w])):
            for d, name in enumerate(names):
                sel = order[(w_dev[order] == d)
                            & (True if keep is None else keep[order])]
                if len(sel):
                    out[node].append(
                        f"{start},{end},{name},"
                        f"{_wkt(w_lon[sel].tolist(), w_lat[sel].tolist())}")
        # staytime — consecutive same-device gaps go to the EARLIER
        # point's cell; a cell with ≥1 pair is emitted (even at 0 ms).
        dwell = np.zeros(grid_n * grid_n + 1, np.int64)  # last = "out"
        pairs = np.zeros(grid_n * grid_n + 1, np.int64)
        w_cell = np.where(in_grid[w], xi[w] * grid_n + yi[w],
                          grid_n * grid_n)
        for d in range(len(names)):
            sel = order[w_dev[order] == d]
            np.add.at(dwell, w_cell[sel][:-1], np.diff(w_ts[sel]))
            np.add.at(pairs, w_cell[sel][:-1], 1)
        rows = sorted(
            ("out" if c == grid_n * grid_n
             else f"{c // grid_n:05d}{c % grid_n:05d}", int(dwell[c]))
            for c in np.nonzero(pairs)[0].tolist())
        out["staytime"] += [f"{start},{end},{n},{ms}" for n, ms in rows]
        # qserve — per standing query, the k nearest DISTINCT devices by
        # min distance within the radius (range and knn share the shape).
        for q in queries:
            dist = np.hypot(w_lon - q.x, w_lat - q.y)
            mins = [(float(dist[w_dev == d].min()), names[d])
                    for d in range(len(names)) if (w_dev == d).any()]
            mins = sorted(m for m in mins if m[0] <= q.radius)
            out["qserve"][(start, end, q.tenant, q.qid)] = [
                (n, d_) for d_, n in mins[:int(q.k)]]
    return out


def _read_lines(path):
    with open(path) as f:
        return f.read().splitlines()


def verify_sncb(out_dir, ts, dev, lon, lat, grid_n):
    """Every node's committed sink file vs :func:`sncb_reference` on the
    same events. Returns per-node line counts (+ how many q1 boundary
    events were tolerated)."""
    from spatialflink_tpu.dag import SNCB_BBOX, default_sncb_queries

    min_x, max_x, min_y, max_y = SNCB_BBOX
    queries = default_sncb_queries()
    ref = sncb_reference(
        ts, dev, lon, lat, grid_bbox=(min_x, min_y, max_x, max_y),
        grid_n=grid_n, queries=queries,
        window_ms=SNCB_WINDOW_S * 1000, slide_ms=SNCB_SLIDE_S * 1000,
    )
    # Device distances are float32 on bbox-centred coordinates.
    dist_tol = 16 * float(np.finfo(np.float32).eps) * (max_x - min_x)
    report = {}
    for node in ("q2", "q3", "q4", "q5", "staytime"):
        got = _read_lines(os.path.join(out_dir, f"{node}.csv"))
        want = ref[node]
        _check(len(got) == len(want),
               f"{node}: {len(got)} lines committed, reference has "
               f"{len(want)}")
        for i, (g, w_) in enumerate(zip(got, want)):
            _check(g == w_, f"{node}: line {i} differs\n  got  "
                            f"{g[:160]}\n  want {w_[:160]}")
        report[node] = len(got)
    # q1: definite lines must all be present, in order; extras only from
    # the ambiguous (boundary) set.
    got = _read_lines(os.path.join(out_dir, "q1.csv"))
    definite, ambiguous = ref["q1"]
    amb, sure, have = set(ambiguous), set(definite), set(got)
    missing = [ln for ln in definite if ln not in have]
    extra = [g for g in got if g not in sure and g not in amb]
    _check(not missing, f"q1: {len(missing)} in-zone events missing, "
                        f"first {missing[:1]}")
    _check(not extra, f"q1: {len(extra)} out-of-zone events emitted, "
                      f"first {extra[:1]}")
    _check([g for g in got if g in sure] == definite,
           "q1: events out of arrival order")
    report["q1"] = len(got)
    report["q1_boundary_events"] = len(got) - len(definite)
    # qserve: per (window, query) the ranked device list.
    got_q = {}
    for g in _read_lines(os.path.join(out_dir, "qserve.csv")):
        tenant, qid, start, end, obj, dist = g.split(",")
        got_q.setdefault((int(start), int(end), tenant, qid), []).append(
            (obj, float(dist)))
    kq = {q.qid: q for q in queries}
    for key, want in ref["qserve"].items():
        have = got_q.pop(key, [])
        _check(len(have) == len(want),
               f"qserve {key}: {len(have)} rows, reference {len(want)}")
        _check(all(math.isfinite(d) for _o, d in have),
               f"qserve {key}: non-finite distance")
        want_d = dict(want)
        for (obj, d) in have:
            _check(obj in want_d and abs(d - want_d[obj]) <= dist_tol,
                   f"qserve {key}: {obj} at {d!r}, reference "
                   f"{want_d.get(obj)!r}")
        ds = [d for _o, d in have]
        _check(all(b >= a - dist_tol for a, b in zip(ds, ds[1:])),
               f"qserve {key}: distances not ascending")
        _check(len(have) <= int(kq[key[3]].k), f"qserve {key}: > k rows")
    _check(not got_q, f"qserve: rows for unexpected keys {sorted(got_q)[:3]}")
    report["qserve"] = sum(len(v) for v in ref["qserve"].values())
    return report


# ---------------------------------------------------------------------------
# Leg A


def write_sncb_inputs(workdir, seed, eps, duration_s, devices, grid_n):
    """The seeded, replayable CSV (objID,timestamp,x,y — the reference's
    point schema) + the yml that selects option 10 on the Brussels bbox.
    Returns (csv_path, yml_path, arrays)."""
    from spatialflink_tpu.dag import SNCB_BBOX

    min_x, max_x, min_y, max_y = SNCB_BBOX
    rng = np.random.default_rng(seed)
    n = int(eps * duration_s)
    ts = T0_MS + (np.arange(n, dtype=np.int64) * 1000) // int(eps)
    lon = rng.uniform(min_x, max_x, n)
    lat = rng.uniform(min_y, max_y, n)
    dev = np.asarray([f"dev{i % devices}" for i in range(n)])
    csv_path = os.path.join(workdir, "sncb_events.csv")
    with open(csv_path, "w") as f:
        f.write("\n".join(
            f"{d},{t},{x!r},{y!r}"
            for d, t, x, y in zip(dev.tolist(), ts.tolist(), lon.tolist(),
                                  lat.tolist())))
        f.write("\n")
    yml_path = os.path.join(workdir, "sncb-conf.yml")
    with open(yml_path, "w") as f:
        f.write(f"""\
clusterMode: False
inputStream1:
  topicName: "sncb"
  format: "CSV"
  dateFormat: null
  csvTsvSchemaAttr: [0, 1, 2, 3]
  gridBBox: [{min_x}, {min_y}, {max_x}, {max_y}]
  numGridCells: {grid_n}
  delimiter: ","
query:
  option: 10
window:
  type: "TIME"
  interval: {SNCB_WINDOW_S}
  step: {SNCB_SLIDE_S}
""")
    return csv_path, yml_path, (ts, dev, lon, lat)


def leg_a(workdir, *, seed=0, eps=SNCB_EPS, duration_s=SNCB_DURATION_S,
          devices=SNCB_DEVICES, grid_n=100, clock=None):
    """The served path: CLI entry → 7-node SNCB DAG → transactional
    sinks + unit checkpoint, compared with :func:`sncb_reference`."""
    from spatialflink_tpu import dag as dag_mod
    from spatialflink_tpu import streaming_job

    os.makedirs(workdir, exist_ok=True)
    csv_path, yml_path, (ts, dev, lon, lat) = write_sncb_inputs(
        workdir, seed, eps, duration_s, devices, grid_n)
    out_dir = os.path.join(workdir, "egress")
    ckpt = os.path.join(workdir, "unit.ckpt")
    _check(not os.path.exists(ckpt) and not os.path.exists(out_dir),
           f"{workdir} holds an earlier run — a resume would skip the "
           "stream; pass an empty --workdir")
    mark = clock.mark() if clock else None
    t0 = time.perf_counter()
    rc = streaming_job.main([
        "--config", yml_path, "--source", f"csv:{csv_path}",
        "--output", out_dir, "--checkpoint", ckpt,
    ])
    wall = time.perf_counter() - t0
    _check(rc == 0, f"streaming_job.main returned {rc}")
    _check(os.path.exists(ckpt), "no unit checkpoint was published")

    dag = dag_mod.active()
    _check(dag is not None, "option 10 left no DAG installed")
    nodes = dag.snapshot()["nodes"]
    health = {
        name: {k: st[k] for k in ("backend", "windows", "results",
                                  "retries", "failovers",
                                  "degraded_windows")}
        for name, st in nodes.items()
    }
    dag_mod.uninstall()
    for name, st in health.items():
        _check(st["backend"] == "device" and not (
            st["retries"] or st["failovers"] or st["degraded_windows"]),
            f"node {name} left the device path: {st}")
        _check(not nodes[name].get("breaker", {}).get("opens"),
               f"node {name}: circuit breaker opened")

    t1 = time.perf_counter()
    lines = verify_sncb(out_dir, ts, dev, lon, lat, grid_n)
    windows = max(st["windows"] for st in health.values())
    full = max(0, (duration_s - SNCB_WINDOW_S) // SNCB_SLIDE_S + 1)
    return {
        "leg": "A:sncb_dag_cli", "events": int(len(ts)),
        "windows": int(windows), "full_windows": int(full),
        "wall_s": round(wall, 3),
        "events_per_s": round(len(ts) / wall, 1),
        "reference_s": round(time.perf_counter() - t1, 3),
        "lines": lines, "nodes": health,
        **(clock.since(mark) if clock else {}),
    }


# ---------------------------------------------------------------------------
# Leg B


def knn_reference(xq, yq, oid, scale, origin, query, radius,
                  num_segments):
    """Brute-force per-object minimum distance over one window's wire
    records: the wire format's own dequantization (uint16 × scale +
    origin in float32 — exact by the format's contract) and float32
    distances. Returns the (num_segments,) minima, +inf = no point of
    that object within ``radius``."""
    xf = xq.astype(np.float32) * scale[0] + origin[0]
    yf = yq.astype(np.float32) * scale[1] + origin[1]
    dx, dy = xf - query[0], yf - query[1]
    dist = np.sqrt(dx * dx + dy * dy)
    mins = np.full(num_segments, np.inf, np.float32)
    hit = dist <= radius
    np.minimum.at(mins, oid[hit], dist[hit])
    return mins


def leg_b(*, seed=0, window_points=KNN_WINDOW_POINTS,
          slide_points=KNN_SLIDE_POINTS, n_windows=KNN_WINDOWS, k=KNN_K,
          radius=KNN_RADIUS, num_segments=KNN_SEGMENTS,
          expect_digest=None, interpret=False, clock=None):
    """The hot operator path: SoA chunks → wire panes → run_wire_panes
    (strategy auto), every window vs :func:`knn_reference`."""
    from __graft_entry__ import BEIJING_GRID_ARGS, QUERY_POINT
    from spatialflink_tpu.grid import UniformGrid
    from spatialflink_tpu.models.objects import Point
    from spatialflink_tpu.operators import (
        PointPointKNNQuery,
        QueryConfiguration,
        QueryType,
    )
    from spatialflink_tpu.streams.wire import WireFormat, wire_panes

    _check(window_points % slide_points == 0, "window must be whole slides")
    ppw = window_points // slide_points
    slide_ms = 5_000
    n_panes = n_windows + ppw - 1
    n = n_panes * slide_points
    grid = UniformGrid(**BEIJING_GRID_ARGS)
    wf = WireFormat.for_grid(grid)
    rng = np.random.default_rng(seed + 1)
    x = rng.uniform(grid.min_x, grid.max_x, n)
    y = rng.uniform(grid.min_y, grid.max_y, n)
    oid = rng.integers(0, num_segments, n).astype(np.int64)
    ts = T0_MS + (np.arange(n, dtype=np.int64) * slide_ms) // slide_points
    chunk = 250_000
    chunks = [
        {"ts": ts[i:i + chunk], "x": x[i:i + chunk], "y": y[i:i + chunk],
         "oid": oid[i:i + chunk]}
        for i in range(0, n, chunk)
    ]
    conf = QueryConfiguration(
        QueryType.WindowBased, window_size=slide_ms * ppw / 1000.0,
        slide_step=slide_ms / 1000.0,
    )
    op = PointPointKNNQuery(conf, grid)
    qp = Point(x=QUERY_POINT[0], y=QUERY_POINT[1])
    mark = clock.mark() if clock else None
    t0 = time.perf_counter()
    got = list(op.run_wire_panes(
        wire_panes(iter(chunks), wf, slide_ms, T0_MS), qp, radius, k,
        num_segments, wf, start_ms=T0_MS, strategy="auto",
        interpret=interpret,
    ))
    wall = time.perf_counter() - t0
    kind = op.last_wire_digest_kind
    if expect_digest is not None:
        _check(kind == expect_digest,
               f"wire digest ended on {kind!r}, expected {expect_digest!r} "
               "(a failed self-check is a kernel defect on this backend)")

    # The reference quantizes with the format's published parameters.
    t1 = time.perf_counter()
    scale, origin = wf.scale.astype(np.float64), wf.origin.astype(np.float64)
    q64 = np.floor((np.stack([x, y], 1) - origin) / scale)
    xyq = np.clip(q64, 0, 65535).astype(np.uint16)
    q32 = np.asarray(QUERY_POINT, np.float32)
    r32 = np.float32(radius)
    # float32 distances near the radius: the device's fused multiply-add
    # and sqrt may round differently from numpy's by a few ulps.
    tol = 8 * np.spacing(r32)
    window_ms = slide_ms * ppw
    _check(len(got) == n_panes + ppw - 1,
           f"{len(got)} windows fired, expected {n_panes + ppw - 1}")
    full = 0
    for w_i, (start, end, segs, dists, nv) in enumerate(got):
        want_start = T0_MS + (w_i - ppw + 1) * slide_ms
        _check((start, end) == (want_start, want_start + window_ms),
               f"window {w_i}: span {(start, end)}")
        lo, hi = np.searchsorted(ts, [start, end], side="left")
        full += int(hi - lo == window_points)
        # Reference minima over every point within radius + tol, so an
        # object the device puts just inside the radius still has one.
        mins = knn_reference(xyq[lo:hi, 0], xyq[lo:hi, 1], oid[lo:hi],
                             wf.scale, wf.origin, q32, r32 + tol,
                             num_segments)
        segs, dists = np.asarray(segs), np.asarray(dists)
        n_in = int((mins <= r32).sum())
        near_edge = int((np.abs(mins - r32) <= tol).sum())
        _check(nv == len(segs) == len(dists) and nv <= k,
               f"window {w_i}: result shape nv={nv} segs={len(segs)}")
        _check(abs(nv - min(k, n_in)) <= near_edge,
               f"window {w_i}: nv {nv}, reference has {n_in} objects "
               "in radius")
        _check(np.all(np.isfinite(dists)) and np.all(np.diff(dists) >= 0),
               f"window {w_i}: distances not finite ascending")
        _check(len(set(segs.tolist())) == nv,
               f"window {w_i}: duplicate object ids")
        _check(np.all(np.abs(dists - mins[segs]) <= tol),
               f"window {w_i}: a neighbour's distance differs from its "
               "object's brute-force minimum")
        if nv:
            rest = np.ones(len(mins), bool)
            rest[segs] = False
            _check(not rest.any() or mins[rest].min() >= dists[-1] - tol,
                   f"window {w_i}: a closer object was left out of the "
                   "top-k")
    _check(full >= n_windows, f"only {full} full windows, need {n_windows}")
    return {
        "leg": "B:knn_wire_panes", "events": int(n),
        "windows": len(got), "full_windows": int(full),
        "wall_s": round(wall, 3),
        "events_per_s": round(n / wall, 1),
        "reference_s": round(time.perf_counter() - t1, 3),
        "wire_digest": kind,
        **(clock.since(mark) if clock else {}),
    }


# ---------------------------------------------------------------------------
# Leg C


def leg_c(*, seed=0, points=16_384, radius=0.002, clock=None):
    """``PointPointJoinQuery.run_soa`` (backend auto: Pallas on a TPU,
    XLA elsewhere) over ONE window of two point streams, vs a float64
    brute-force cross join on the same Beijing grid."""
    from __graft_entry__ import BEIJING_GRID_ARGS
    from spatialflink_tpu.grid import UniformGrid
    from spatialflink_tpu.operators import (
        PointPointJoinQuery,
        QueryConfiguration,
        QueryType,
    )

    grid = UniformGrid(**BEIJING_GRID_ARGS)
    rng = np.random.default_rng(seed + 2)

    def side():
        return {
            "ts": T0_MS + np.sort(rng.integers(0, 10_000, points)),
            "x": rng.uniform(grid.min_x, grid.max_x, points),
            "y": rng.uniform(grid.min_y, grid.max_y, points),
            "oid": np.arange(points, dtype=np.int64),
        }

    left, right = side(), side()
    conf = QueryConfiguration(QueryType.WindowBased, window_size=10,
                              slide_step=10)
    mark = clock.mark() if clock else None
    t0 = time.perf_counter()
    got = list(PointPointJoinQuery(conf, grid).run_soa(
        iter([left]), iter([right]), radius))
    wall = time.perf_counter() - t0
    _check(len(got) == 1, f"{len(got)} join windows fired, expected 1")
    start, end, li, ri, dd, count, overflow = got[0]
    _check((start, end) == (T0_MS, T0_MS + 10_000),
           f"join window span {(start, end)}")
    _check(overflow == 0, f"join overflow {overflow}: result not exact")
    li, ri, dd = (np.asarray(a)[:count] for a in (li, ri, dd))
    _check(count == len(li) and (li >= 0).all() and (ri >= 0).all(),
           f"join count {count} vs {int((np.asarray(li) >= 0).sum())} "
           "valid pairs")
    # Device distances are float32 on bbox-centred coordinates
    # (|coord| ≤ span/2: one ulp per operand, a few per distance).
    tol = 4 * float(np.finfo(np.float32).eps) * (grid.max_x - grid.min_x)
    have = {}
    for a, b, d in zip(li.tolist(), ri.tolist(), dd.tolist()):
        _check((a, b) not in have, f"join pair {(a, b)} emitted twice")
        have[(a, b)] = d
    n_edge = 0
    rows = 128  # brute force in cache-sized row blocks, buffers reused
    d2, dy = np.empty((rows, points)), np.empty((rows, points))
    for lo in range(0, points, rows):
        n_rows = min(rows, points - lo)
        d2, dy = d2[:n_rows], dy[:n_rows]
        np.subtract(left["x"][lo:lo + rows, None], right["x"][None, :],
                    out=d2)
        np.subtract(left["y"][lo:lo + rows, None], right["y"][None, :],
                    out=dy)
        d2 *= d2
        dy *= dy
        d2 += dy
        for a, b in zip(*np.nonzero(d2 <= (radius + tol) ** 2)):
            ref = math.sqrt(d2[a, b])
            pair = (lo + int(a), int(b))
            if abs(ref - radius) <= tol:  # on the radius: either way
                n_edge += 1
                have.pop(pair, None)
                continue
            _check(pair in have, f"join missed pair {pair} at {ref!r}")
            _check(abs(have.pop(pair) - ref) <= tol,
                   f"join pair {pair}: distance differs from {ref!r}")
    _check(not have, f"join emitted {len(have)} pairs beyond the radius, "
                     f"first {sorted(have)[:1]}")
    return {
        "leg": "C:join_run_soa", "events": 2 * points, "windows": 1,
        "pairs": int(count), "radius_edge_pairs": n_edge,
        "wall_s": round(wall, 3),
        **(clock.since(mark) if clock else {}),
    }


# ---------------------------------------------------------------------------


def environment_report():
    import jax
    import jaxlib

    from spatialflink_tpu import native
    from spatialflink_tpu.ops.compaction import compact_probe_preferred
    from spatialflink_tpu.ops.join import pallas_join_supported
    from spatialflink_tpu.ops.select import onehot_select_preferred
    from spatialflink_tpu.streams.panes import _device_backend_preferred

    try:
        import libtpu

        libtpu_version = libtpu.__version__
    except ImportError:
        libtpu_version = None
    dev = jax.devices()[0]
    return {
        "platform": dev.platform, "device_kind": dev.device_kind,
        "device_count": len(jax.devices()),
        "jax": jax.__version__, "jaxlib": jaxlib.__version__,
        "libtpu": libtpu_version,
        "x64": bool(jax.config.jax_enable_x64),
        "compile_cache_dir": jax.config.jax_compilation_cache_dir,
        "native_built": bool(native.available()),
        "selection": {
            "join_backend": "pallas" if pallas_join_supported() else "xla",
            "pane_backend": ("device" if _device_backend_preferred()
                             else "native"),
            "select": "onehot" if onehot_select_preferred() else "topk",
            "tjoin_probe": ("compact" if compact_probe_preferred()
                            else "full_ring"),
        },
    }


def run(*, seed=0, duration_s=SNCB_DURATION_S, workdir=None):
    """All legs at full size, on a TPU only; returns the report dict
    (raises on any failure). The CPU tests call the legs themselves, at
    toy sizes — a CPU pass is never a chip result."""
    import jax

    import spatialflink_tpu  # noqa: F401  (fails here outside the repo)

    platform = jax.devices()[0].platform
    if platform != "tpu":
        sys.stderr.write(
            f"chip_smoke: JAX found platform {platform!r}, not 'tpu' — "
            "this smoke only counts on the chip and refuses to run "
            "anywhere else.\n")
        raise SystemExit(2)
    clock = CompileClock()
    env = environment_report()
    print(json.dumps({"environment": env}), flush=True)
    own_dir = workdir is None
    workdir = workdir or tempfile.mkdtemp(prefix="sft_chip_smoke_")
    reduced = []
    if duration_s < SNCB_DURATION_S:
        reduced.append(
            f"leg A event-time duration {duration_s}s of the upstream's "
            f"{SNCB_DURATION_S}s (rate, devices, widths unchanged)")
    try:
        a = leg_a(os.path.join(workdir, "leg_a"), seed=seed,
                  duration_s=duration_s, clock=clock)
        print(json.dumps(a), flush=True)
        b = leg_b(seed=seed, expect_digest="pallas", clock=clock)
        print(json.dumps(b), flush=True)
        c = leg_c(clock=clock, seed=seed)
        print(json.dumps(c), flush=True)
    finally:
        if own_dir:
            shutil.rmtree(workdir, ignore_errors=True)
    dev = jax.devices()[0]
    return {
        "ok": True,
        "device": {"platform": dev.platform, "kind": dev.device_kind,
                   "count": len(jax.devices())},
        "seed": seed, "reduced": reduced,
        "compile_s": round(clock.seconds, 3),
        "compile_cache": {"hits": clock.cache_hits,
                          "misses": clock.cache_misses},
        "legs": {"A": {k: a[k] for k in ("wall_s", "compile_s", "events",
                                         "windows")},
                 "B": {k: b[k] for k in ("wall_s", "compile_s", "events",
                                         "windows", "wire_digest")},
                 "C": {k: c[k] for k in ("wall_s", "compile_s", "events",
                                         "pairs")}},
    }


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--duration-s", type=int, default=SNCB_DURATION_S,
                    help="leg A event-time duration (a cut is printed "
                         "under 'reduced')")
    ap.add_argument("--workdir", default=None)
    args = ap.parse_args(argv)
    report = run(seed=args.seed, duration_s=args.duration_s,
                 workdir=args.workdir)
    print(json.dumps({"summary": report}), flush=True)
    # The contract line, last: the device exactly as JAX reports it.
    print(json.dumps({"ok": True, "device": report["device"]}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
