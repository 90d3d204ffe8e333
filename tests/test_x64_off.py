"""The chip's numerics on the CPU (ROADMAP C12).

Every other test runs under ``tests/conftest.py``: x64 ON. The chip runs
with x64 OFF, where a float64 host array lands as float32 on the device
and an int64 as int32. This file is the tier that sees that: each served
path runs once in a child process started WITHOUT the conftest (this
file, as a script: ``python tests/test_x64_off.py <child>``), at toy
size, on the CPU backend, and prints one JSON line of named verdicts;
a module-scoped fixture runs each child once and one test case reads
each verdict. The references are plain numpy in float64 (or, for the
wire kNN, the wire format's own float32 dequantisation) — numpy does not
read jax's x64 flag — with the tolerance bands fixed beforehand from the
dtype: ``ZONE_TOL_M`` for zone containment, ``4 · eps32 · span`` for
centred distances.

Not here, deliberately: the device tStats pane engine's float32 prefix
sums (ROADMAP C12 / PERF.md §7) — a known error this tier would expose
and the PR that repairs it will pin.
"""

from __future__ import annotations

import contextlib
import itertools
import json
import math
import os
import subprocess
import sys
import tempfile

import numpy as np

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

T0_MS = 1_700_000_000_000  # epoch-ms event times: int32 must survive them
WINDOW_MS, SLIDE_MS = 10_000, 5_000
DEVICES = 10
GRID_N = 100

DAG_NODES = ("q1", "q2", "q3", "q4", "q5", "staytime", "qserve")
#: (name, UniformGrid arguments). Beijing: conf/geoflink-conf.yml;
#: Brussels: dag.SNCB_BBOX; NYC: the TLC trip-record extent for
#: BASELINE.json's taxi-trajectory config (assumed: the repo carries no
#: NYC grid) — the western hemisphere, where the centre is negative.
GRIDS = (
    ("beijing", dict(num_partitions=100, min_x=115.5, max_x=117.6,
                     min_y=39.6, max_y=41.1)),
    ("brussels", dict(num_partitions=100, min_x=4.25, max_x=4.50,
                      min_y=50.75, max_y=50.95)),
    ("nyc", dict(num_partitions=100, min_x=-74.26, max_x=-73.70,
                 min_y=40.49, max_y=40.92)),
)
ZONE_SETS = ("high_risk", "maintenance", "fence")

CASES = {
    "sncb": (
        [f"device.{n}" for n in DAG_NODES]
        + [f"fallback.{n}" for n in ("q1", "staytime")]
        + [f"fields.{route}.{n}" for route in ("device", "fallback")
           for n in ("q2", "q5")]
    ),
    "knn": ["wire_panes.xla", "wire_panes.pallas_interpret"],
    "join": ["run_soa.xla", "run_soa.pallas_interpret"],
    "tjoin": ["run_soa.uniform", "run_soa.trips"],
    "range": ["run_soa.dense", "run_soa.pruned", "run_soa.pruned_compact"],
    "numerics": (
        [f"center_coords.{name}" for name, _ in GRIDS]
        + [f"contains_any_zone.{z}" for z in ZONE_SETS]
    ),
}


# ---------------------------------------------------------------------------
# The children (run as a script: no conftest, x64 off).


def _verdict(problems):
    return {"ok": not problems, "problems": [str(p)[:300] for p in problems][:5]}


@contextlib.contextmanager
def _routed(route):
    """``device``: nothing changes. ``fallback``: the four twin-carrying
    nodes run their ``fallback_process`` for the length of the block —
    the DAG, its sinks and its renderers as always, only the node's
    computation is the twin's."""
    from spatialflink_tpu import dag as dag_mod

    classes = () if route == "device" else (
        dag_mod.Q1Node, dag_mod.Q2Node, dag_mod.Q5Node,
        dag_mod.StayTimeNode)
    saved = [c.process for c in classes]
    for c in classes:
        c.process = c.fallback_process
    try:
        yield
    finally:
        for c, proc in zip(classes, saved):
            c.process = proc


def _window_starts(ts):
    first = (int(ts[0]) // SLIDE_MS) * SLIDE_MS - WINDOW_MS + SLIDE_MS
    last = (int(ts[-1]) // SLIDE_MS) * SLIDE_MS
    return range(first, last + 1, SLIDE_MS)


def _sncb_cli_problems(inputs, workdir, ts, dev, lon, lat):
    """Per node, what differs between the CLI's committed egress (under
    ``workdir``, from the csv and yml in ``inputs``) and the benchmark's
    plain reference over every window the stream touches."""
    from benchmark.references import sncb_brussels as ref_mod
    from spatialflink_tpu import dag as dag_mod
    from spatialflink_tpu import streaming_job

    out_dir = os.path.join(workdir, "egress")
    rc = streaming_job.main([
        "--config", os.path.join(inputs, "sncb-conf.yml"),
        "--source", f"csv:{os.path.join(inputs, 'sncb_events.csv')}",
        "--output", out_dir,
        "--checkpoint", os.path.join(workdir, "unit.ckpt"),
    ])
    nodes = dag_mod.active().snapshot()["nodes"]
    dag_mod.uninstall()
    problems = {n: [] for n in DAG_NODES}
    if rc:
        return {n: [f"streaming_job.main returned {rc}"] for n in DAG_NODES}
    for name, st in nodes.items():
        if st["backend"] != "device" or st["retries"] or st["failovers"] \
                or st["degraded_windows"]:
            problems[name].append(f"left the device path: {st}")
    min_x, max_x, min_y, max_y = dag_mod.SNCB_BBOX
    names = sorted(set(dev.tolist()))
    ref = ref_mod.Reference(
        ts, np.searchsorted(names, dev), lon, lat, names=names,
        bbox=(min_x, min_y, max_x, max_y), grid_n=GRID_N,
        queries=[dict(tenant=q.tenant, qid=q.qid, x=q.x, y=q.y,
                      radius=q.radius, k=q.k)
                 for q in dag_mod.default_sncb_queries()],
        risk_zone_file=os.path.join(
            REPO, "spatialflink_tpu", "sncb", "resources",
            "high_risk_zones.geojson"),
        zone_buffer_m=40.0,  # zones buffered 20 m + Q1's 20 m proximity
    )
    committed = ref_mod.read_committed(out_dir)
    lines = {n: 0 for n in DAG_NODES}
    for start in _window_starts(ts):
        key = (start, start + WINDOW_MS)
        got = committed.pop(key, {})
        for n, rows in got.items():
            lines[n] += len(rows)
        for bad in ref.compare(ref.window(*key), got):
            problems[bad.split(":")[0].split(" ")[0]].append(
                f"window {key}: {bad}")
    for key, got in committed.items():
        for n in got:
            problems[n].append(f"lines of a window {key} no event is in")
    # Non-vacuous: what the CSV schema can feed produced egress (no speed,
    # no brake pressure: q2 and q5 stay silent, as their reference says).
    for n in ("q1", "q3", "q4", "staytime", "qserve"):
        if not lines[n]:
            problems[n].append("no line committed: the comparison is empty")
    return problems


def _fields_stream(seed):
    """GpsEvents WITH speed and brake pressures (what Q2 and Q5 aggregate
    and the CLI's CSV schema cannot carry), crowded around the maintenance
    and fence zones, none within ``ZONE_TOL_M`` of a zone boundary."""
    from benchmark.references import sncb_brussels as ref_mod
    from spatialflink_tpu.sncb.common import GpsEvent

    res = os.path.join(REPO, "spatialflink_tpu", "sncb", "resources")
    rng = np.random.default_rng(seed)
    n = 2_400
    ts = T0_MS + np.arange(n, dtype=np.int64) * 10  # 100 events/s, 24 s
    lon = rng.uniform(4.37, 4.42, n)
    lat = rng.uniform(50.84, 50.87, n)
    d = np.arange(n) % DEVICES
    fa = np.where(d % 2 == 0, rng.uniform(0.0, 1.0, n),
                  rng.uniform(0.2, 0.5, n))
    ff = np.where(d % 3 == 0, rng.uniform(0.0, 1.0, n),
                  rng.uniform(0.0, 0.4, n))
    speed = np.where(d < 5, rng.uniform(40.0, 80.0, n),
                     rng.uniform(0.0, 30.0, n))
    maint = ref_mod.zone_margin(ref_mod.load_zone_rings(
        os.path.join(res, "maintenance_areas.geojson")), lon, lat, 0.0)
    fence = ref_mod.zone_margin(ref_mod.load_zone_rings(
        os.path.join(res, "q5_fence.wkt")), lon, lat, 20.0)
    keep = (np.abs(maint) > ref_mod.ZONE_TOL_M) \
        & (np.abs(fence) > ref_mod.ZONE_TOL_M)
    cols = [a[keep] for a in (ts, d, lon, lat, fa, ff, speed, maint, fence)]
    events = [
        GpsEvent(device_id=f"dev{int(di)}", lon=float(x), lat=float(y),
                 ts=int(t), gps_speed=float(v), fa=float(a), ff=float(b))
        for t, di, x, y, a, b, v in zip(*(c.tolist() for c in cols[:7]))
    ]
    return events, cols


def _fields_reference(cols):
    """Expected q2 / q5 lines, float64, per the upstream's Q2_BrakeMonitor
    and Q5_TrajAndSpeedFence: devices in name order within a window."""
    ts, d, lon, lat, fa, ff, speed, maint, fence = cols
    want = {"q2": [], "q5": []}
    for start in _window_starts(ts):
        end = start + WINDOW_MS
        lo, hi = np.searchsorted(ts, [start, end], side="left")
        w = np.arange(lo, hi)
        for di in range(DEVICES):
            rows = w[(d[w] == di) & (maint[w] < 0)]  # outside maintenance
            if len(rows):
                var_fa = float(fa[rows].max() - fa[rows].min())
                var_ff = float(ff[rows].max() - ff[rows].min())
                if var_fa > 0.6 and var_ff <= 0.5:
                    want["q2"].append(
                        f"{start},{end},dev{di},{var_fa!r},{var_ff!r},"
                        f"{len(rows)}")
            rows = w[(d[w] == di) & (fence[w] > 0)]  # inside the fence
            if len(rows):
                v = speed[rows].tolist()
                avg, low = math.fsum(v) / len(v), min(v)
                if avg > 50.0 or low > 20.0:
                    pts = ", ".join(f"{x:g} {y:g}" for x, y in
                                    zip(lon[rows].tolist(),
                                        lat[rows].tolist()))
                    wkt = (f"POINT ({pts})" if len(rows) == 1
                           else f"LINESTRING ({pts})")
                    want["q5"].append((f"{start},{end},dev{di}", avg, low,
                                       wkt))
    return want


def _fields_problems(workdir, events, want):
    from spatialflink_tpu import dag as dag_mod
    from spatialflink_tpu.grid import UniformGrid

    min_x, max_x, min_y, max_y = dag_mod.SNCB_BBOX
    dag = dag_mod.build_sncb_dag(
        workdir, grid=UniformGrid(GRID_N, min_x, max_x, min_y, max_y),
        qserve_queries=dag_mod.default_sncb_queries())
    for _res in dag.run(itertools.chain(dag.qserve_boot, iter(events))):
        pass
    dag_mod.uninstall()

    def lines(node):
        with open(os.path.join(workdir, f"{node}.csv")) as f:
            return f.read().splitlines()

    problems = {"q2": [], "q5": []}
    got = lines("q2")
    if got != want["q2"]:
        diff = [i for i, (g, w_) in enumerate(zip(got, want["q2"]))
                if g != w_]
        problems["q2"].append(
            f"{len(got)} lines, reference {len(want['q2'])}; first "
            f"difference at {diff[:1]}")
    got = lines("q5")
    if len(got) != len(want["q5"]):
        problems["q5"].append(
            f"{len(got)} lines, reference {len(want['q5'])}")
    for i, (g, (head, avg, low, wkt)) in enumerate(zip(got, want["q5"])):
        s, e, dev_, g_avg, g_low, g_wkt = g.split(",", 5)
        if (f"{s},{e},{dev_}" != head or g_wkt != wkt
                or not math.isclose(float(g_avg), avg, rel_tol=1e-12)
                or float(g_low) != low):
            problems["q5"].append(f"line {i}: got {g[:120]!r}")
            break
    for node in ("q2", "q5"):
        if not want[node]:
            problems[node].append("the reference is empty: nothing compared")
    return problems


def child_sncb():
    import chip_smoke

    out = {}
    with tempfile.TemporaryDirectory(prefix="x64off_sncb_") as tmp:
        arrays = chip_smoke.write_sncb_inputs(
            tmp, 3, 400, 20, DEVICES, GRID_N)[2]
        events, cols = _fields_stream(seed=5)
        want = _fields_reference(cols)
        for route in ("device", "fallback"):
            with _routed(route):
                cli = _sncb_cli_problems(
                    tmp, os.path.join(tmp, route), *arrays)
                fields = _fields_problems(
                    os.path.join(tmp, f"fields_{route}"), events, want)
            for node in (DAG_NODES if route == "device"
                         else ("q1", "staytime")):
                out[f"{route}.{node}"] = _verdict(cli[node])
            for node in ("q2", "q5"):
                out[f"fields.{route}.{node}"] = _verdict(fields[node])
    return out


def child_knn():
    """``run_wire_panes`` on both digest forms against chip_smoke's leg-B
    reference (numpy: per-object minima of float32 distances on the wire
    records' own dequantisation, every window, top-k order and all)."""
    import chip_smoke

    out = {}
    for name, interpret, kind in (("xla", False, "xla"),
                                  ("pallas_interpret", True, "pallas")):
        try:
            rep = chip_smoke.leg_b(
                seed=3, window_points=5000, slide_points=2500, n_windows=4,
                num_segments=256, expect_digest=kind, interpret=interpret)
            bad = [] if rep["full_windows"] >= 4 else [f"windows: {rep}"]
        except chip_smoke.SmokeFailure as e:
            bad = [e]
        out[f"wire_panes.{name}"] = _verdict(bad)
    return out


def child_join():
    """``PointPointJoinQuery.run_soa`` on both kernels: the pair set equals
    the float64 reference's outside the band of 4 · eps32 · span
    (1.0e-6 deg on the Beijing grid) around the radius."""
    sys.path.insert(0, os.path.join(REPO, "tests"))
    from __graft_entry__ import BEIJING_GRID_ARGS
    from join_reference import Reference
    from spatialflink_tpu.grid import UniformGrid
    from spatialflink_tpu.operators import (
        PointPointJoinQuery,
        QueryConfiguration,
        QueryType,
    )

    grid = UniformGrid(**BEIJING_GRID_ARGS)
    radius, n = 0.002, 8_000
    rng = np.random.default_rng(11)

    def side():
        return {"ts": T0_MS + np.sort(rng.integers(0, 10_000, n)),
                "x": rng.uniform(grid.min_x, grid.max_x, n),
                "y": rng.uniform(grid.min_y, grid.max_y, n),
                "oid": np.arange(n, dtype=np.int64)}

    left, right = side(), side()
    tol = 4 * float(np.finfo(np.float32).eps) * (grid.max_x - grid.min_x)
    ref = Reference(bbox=(grid.min_x, grid.min_y, grid.max_x, grid.max_y),
                    grid_cells=100, radius=radius, tol=tol)
    want = ref.pairs(left["x"], left["y"], right["x"], right["y"])
    conf = QueryConfiguration(QueryType.WindowBased, window_size=10,
                              slide_step=10)
    out = {}
    for backend in ("xla", "pallas_interpret"):
        # cap=8: under one point a cell; the default 64 only pads the
        # buckets (the ladder climbs if a cell holds more).
        got = list(PointPointJoinQuery(conf, grid, cap=8,
                                       join_backend=backend)
                   .run_soa(iter([left]), iter([right]), radius))
        bad = []
        if len(got) != 1:
            bad.append(f"{len(got)} windows fired, expected 1")
        else:
            start, end, li, ri, dd, count, overflow = got[0]
            if (start, end) != (T0_MS, T0_MS + 10_000):
                bad.append(f"window span {(start, end)}")
            bad += ref.compare(want, li, ri, dd, int(count), int(overflow),
                               n_right=n)
            if not int(count):
                bad.append("no pair found: the comparison is empty")
        out[f"run_soa.{backend}"] = _verdict(bad)
    return out


def child_tjoin():
    """``PointPointTJoinQuery.run_soa`` (the capacity contract, the sparse
    dedup): the trajectory-pair set and its minimum distances equal the
    float64 reference's outside the band of 4 · eps32 · span around the
    radius — on uniform points with 16,384 ids (ids² = 2²⁸ possible keys)
    and on coherent trips, where many point pairs collapse to one pair."""
    from __graft_entry__ import BEIJING_GRID_ARGS
    from benchmark.references.tjoin_tdrive import Reference
    from spatialflink_tpu.grid import UniformGrid
    from spatialflink_tpu.operators import QueryConfiguration, QueryType
    from spatialflink_tpu.operators.trajectory import PointPointTJoinQuery

    grid = UniformGrid(**BEIJING_GRID_ARGS)
    radius, n, ids = 0.002, 8_000, 16_384
    rng = np.random.default_rng(12)
    bbox = (grid.min_x, grid.min_y, grid.max_x, grid.max_y)

    def uniform():
        return {"ts": T0_MS + np.sort(rng.integers(0, 10_000, n)),
                "x": rng.uniform(grid.min_x, grid.max_x, n),
                "y": rng.uniform(grid.min_y, grid.max_y, n),
                "oid": rng.integers(0, ids, n).astype(np.int64)}

    def trips(shift):
        """40 taxis of 200 fixes each, every taxi a straight run of 0.0004°
        steps; the other side's taxi of the same number runs ``shift``
        beside it, so 200+ point pairs make one trajectory pair."""
        taxi = np.repeat(np.arange(40), 200)
        step = np.tile(np.arange(200), 40)
        x0 = grid.min_x + 0.05 + 0.05 * taxi
        y0 = grid.min_y + 0.05 + 0.03 * taxi
        return {"ts": T0_MS + (step * 50).astype(np.int64),
                "x": x0 + 0.0004 * step + shift, "y": y0 + shift,
                "oid": (taxi * 400 + 7).astype(np.int64)}

    def in_time(c):
        order = np.argsort(c["ts"], kind="stable")
        return {k: v[order] for k, v in c.items()}

    tol = 4 * float(np.finfo(np.float32).eps) * (grid.max_x - grid.min_x)
    ref = Reference(bbox=bbox, grid_cells=100, radius=radius, tol=tol,
                    num_ids=ids)
    conf = QueryConfiguration(QueryType.WindowBased, window_size=10,
                              slide_step=10)
    out = {}
    for name, left, right in (
            ("uniform", uniform(), uniform()),
            ("trips", in_time(trips(0.0)), in_time(trips(0.0007)))):
        want = ref.tpairs(left["x"], left["y"], left["oid"],
                          right["x"], right["y"], right["oid"])
        op = PointPointTJoinQuery(conf, grid)
        got = list(op.run_soa(iter([left]), iter([right]), radius,
                              num_segments=ids))
        bad = []
        if len(got) != 1:
            bad.append(f"{len(got)} windows fired, expected 1")
        else:
            start, end, lo, ro, dd, count, overflow = got[0]
            if (start, end) != (T0_MS, T0_MS + 10_000):
                bad.append(f"window span {(start, end)}")
            bad += ref.compare(want, lo, ro, dd, int(count), int(overflow))
            if not int(count):
                bad.append("no pair found: the comparison is empty")
            if name == "trips" and int(count) != 40:
                bad.append(f"{int(count)} trajectory pairs, expected the 40 "
                           "side-by-side taxis")
        out[f"run_soa.{name}"] = _verdict(bad)
    return out


def child_range():
    """``PointPolygonRangeQuery.run_soa`` through each of its polygon
    kernels: the matched set equals the float64 reference's outside the band
    of 4 · eps32 · span (1.0e-6 deg on the Beijing grid) around the radius,
    every matched row is the window's own, every distance within the band of
    the reference's (float32 ray casting and distances on centred
    coordinates against float64 on raw degrees)."""
    from __graft_entry__ import BEIJING_GRID_ARGS
    from benchmark.references.range_polygons import Reference
    from spatialflink_tpu.grid import UniformGrid
    from spatialflink_tpu.operators import (
        PointPolygonRangeQuery,
        QueryConfiguration,
        QueryType,
    )
    from spatialflink_tpu.utils.helper import generate_query_polygons

    grid = UniformGrid(**BEIJING_GRID_ARGS)
    bbox = (grid.min_x, grid.min_y, grid.max_x, grid.max_y)
    radius, n = 0.002, 20_000
    tol = 4 * float(np.finfo(np.float32).eps) * (grid.max_x - grid.min_x)
    rng = np.random.default_rng(23)
    window = {"ts": T0_MS + np.sort(rng.integers(0, 10_000, n)),
              "x": rng.uniform(grid.min_x, grid.max_x, n),
              "y": rng.uniform(grid.min_y, grid.max_y, n),
              "oid": np.arange(n, dtype=np.int64)}
    conf = QueryConfiguration(QueryType.WindowBased, window_size=10,
                              slide_step=10)
    out = {}
    for kernel, count in (("dense", 40), ("pruned", 400),
                          ("pruned_compact", 90)):
        polygons = generate_query_polygons(count, *bbox, grid_size=100,
                                           seed=count)
        ref = Reference(bbox=bbox, grid_cells=100, radius=radius, tol=tol,
                        polygons=[[np.asarray(r) for r in p.rings]
                                  for p in polygons])
        op = PointPolygonRangeQuery(conf, grid)
        got = list(op.run_soa(iter([window]), polygons, radius))
        bad = []
        if op.last_range_kernel != kernel:
            bad.append(f"the operator picked {op.last_range_kernel!r}")
        if len(got) != 1:
            bad.append(f"{len(got)} windows fired, expected 1")
        else:
            start, end, matched, dist = got[0]
            if (start, end) != (T0_MS, T0_MS + 10_000):
                bad.append(f"window span {(start, end)}")
            if dist.dtype != np.float32:
                bad.append(f"distances came back {dist.dtype}: x64 is on?")
            bad += ref.compare(ref.matches(window["x"], window["y"]),
                               window, matched, dist)
            if not len(dist):
                bad.append("no point matched: the comparison is empty")
        out[f"run_soa.{kernel}"] = _verdict(bad)
    return out


def _metric_margin(zones, pts):
    """Signed slack (metres, float64) of "inside any zone OR within its
    buffer of its boundary": an even-odd ray cast and point-to-segment
    distances over the zones' own metric rings."""
    x, y = pts[:, 0], pts[:, 1]
    best = np.full(len(pts), -np.inf)
    for z in zones:
        inside = np.zeros(len(pts), bool)
        dmin = np.full(len(pts), np.inf)
        for ring in z.rings_metric:
            ring = np.asarray(ring, np.float64)
            if not np.array_equal(ring[0], ring[-1]):
                ring = np.concatenate([ring, ring[:1]])
            for (x1, y1), (x2, y2) in zip(ring[:-1], ring[1:]):
                if y1 != y2:
                    inside ^= ((y1 > y) != (y2 > y)) & (
                        x < x1 + (y - y1) / (y2 - y1) * (x2 - x1))
                dx, dy = x2 - x1, y2 - y1
                l2 = dx * dx + dy * dy
                t = (np.clip(((x - x1) * dx + (y - y1) * dy) / l2, 0, 1)
                     if l2 > 0 else 0.0)
                dmin = np.minimum(dmin, np.hypot(x - (x1 + t * dx),
                                                 y - (y1 + t * dy)))
        best = np.maximum(best, np.where(inside, z.buffer_m + dmin,
                                         z.buffer_m - dmin))
    return best


def child_numerics():
    import jax
    import jax.numpy as jnp

    from benchmark.references.sncb_brussels import ZONE_TOL_M
    from spatialflink_tpu.grid import UniformGrid
    from spatialflink_tpu.operators.base import center_coords
    from spatialflink_tpu.sncb.common import PolygonLoader, contains_any_zone

    out = {}
    rng = np.random.default_rng(17)
    eps32 = float(np.finfo(np.float32).eps)
    dist = jax.jit(lambda a, b: jnp.sqrt(jnp.sum((a - b) ** 2, axis=-1)))
    for name, args in GRIDS:
        grid = UniformGrid(**args)
        span = grid.max_x - grid.min_x
        n = 4_096
        a = np.stack([rng.uniform(grid.min_x, grid.max_x, n),
                      rng.uniform(grid.min_y, grid.max_y, n)], axis=1)
        # Partners one metre to a few hundred metres away: where a float32
        # ulp at the raw coordinate's magnitude is the whole answer.
        b = a + rng.uniform(-1.0, 1.0, (n, 2)) * rng.choice(
            [1e-5, 1e-4, 2e-3], (n, 1))
        truth = np.sqrt(np.sum((a - b) ** 2, axis=-1))
        bad = []
        ca, cb = (center_coords(grid, v, np.float64) for v in (a, b))
        if ca.dtype != np.float32:
            bad.append(f"a float64 request came back {ca.dtype}, not the "
                       "float32 the device will hold")
        if np.abs(ca).max() > span:
            bad.append(f"|centred| up to {float(np.abs(ca).max())!r}: not "
                       "centred on the grid")
        got = np.asarray(dist(jnp.asarray(ca), jnp.asarray(cb)), np.float64)
        worst = float(np.abs(got - truth).max())
        if worst > 4 * eps32 * span:
            bad.append(f"centred distances off by {worst!r}, bound "
                       f"{4 * eps32 * span!r}")
        # The band is a real one: the same float32 arithmetic on the raw
        # coordinates does not fit in it.
        raw = np.asarray(dist(jnp.asarray(a), jnp.asarray(b)), np.float64)
        if float(np.abs(raw - truth).max()) <= 4 * eps32 * span:
            bad.append("uncentred float32 distances pass too: the case "
                       "shows nothing")
        out[f"center_coords.{name}"] = _verdict(bad)

    zone_sets = {
        "high_risk": PolygonLoader.load_geojson_buffered(
            "high_risk_zones.geojson", 40.0),
        "maintenance": PolygonLoader.load_geojson_buffered(
            "maintenance_areas.geojson", 0.0),
        "fence": PolygonLoader.load_wkt_buffered("q5_fence.wkt", 20.0),
    }
    for name, zones in zone_sets.items():
        rings = np.concatenate([np.asarray(r, np.float64)
                                for z in zones for r in z.rings_metric])
        bad = []
        if rings[:, 1].min() < 5.6e6:
            bad.append(f"northings from {float(rings[:, 1].min())!r}: not "
                       "the magnitude the case is about")
        reach = zones[0].buffer_m + 3.0
        lo, hi = rings.min(axis=0) - reach, rings.max(axis=0) + reach
        cloud = rng.uniform(lo, hi, (20_000, 2))
        # ... and a ring of points within 2 m of the decision boundary,
        # where a float32 ulp of 0.5 m at 5.6e6 m would decide.
        margin = _metric_margin(zones, cloud)
        near = cloud[np.abs(margin) < 2.0]
        pts = np.concatenate([cloud[:4_000], near])
        margin = _metric_margin(zones, pts)
        clear = np.abs(margin) > ZONE_TOL_M
        got = np.asarray(contains_any_zone(zones, pts), bool)
        wrong = np.nonzero(clear & (got != (margin > 0)))[0]
        if len(wrong):
            i = int(wrong[0])
            bad.append(f"{len(wrong)} points misclassified, first "
                       f"{pts[i].tolist()} margin {float(margin[i])!r} m")
        close = int((clear & (np.abs(margin) < 0.5)).sum())
        if close < 50 or not got.any() or got.all():
            bad.append(f"only {close} points within 0.5 m of the boundary, "
                       f"{int(got.sum())} of {len(got)} inside: the case "
                       "shows nothing")
        out[f"contains_any_zone.{name}"] = _verdict(bad)
    return out


CHILDREN = {"sncb": child_sncb, "knn": child_knn, "join": child_join,
            "tjoin": child_tjoin, "range": child_range,
            "numerics": child_numerics}


def main(argv):
    sys.path.insert(0, REPO)
    import jax

    cases = CHILDREN[argv[0]]()
    print(json.dumps({"x64": bool(jax.config.jax_enable_x64),
                      "backend": jax.default_backend(), "cases": cases}),
          flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))


# ---------------------------------------------------------------------------
# The tests (under conftest: they only start the children and read).

import pytest  # noqa: E402


def _run_child(name):
    env = {k: v for k, v in os.environ.items()
           if k not in ("JAX_ENABLE_X64", "XLA_FLAGS", "SFT_FAULT_PLAN",
                        "SFT_OVERLOAD_POLICY", "SFT_QSERVE", "SFT_ABLATE")}
    env["JAX_PLATFORMS"] = "cpu"
    p = subprocess.run([sys.executable, os.path.abspath(__file__), name],
                       cwd=REPO, env=env, capture_output=True, text=True,
                       timeout=900)
    assert p.returncode == 0, p.stderr[-3000:]
    doc = json.loads(p.stdout.strip().splitlines()[-1])
    assert doc["x64"] is False and doc["backend"] == "cpu", doc
    assert sorted(doc["cases"]) == sorted(CASES[name])
    return doc["cases"]


@pytest.fixture(scope="module")
def sncb_child():
    return _run_child("sncb")


@pytest.fixture(scope="module")
def knn_child():
    return _run_child("knn")


@pytest.fixture(scope="module")
def join_child():
    return _run_child("join")


@pytest.fixture(scope="module")
def tjoin_child():
    return _run_child("tjoin")


@pytest.fixture(scope="module")
def range_child():
    return _run_child("range")


@pytest.fixture(scope="module")
def numerics_child():
    return _run_child("numerics")


@pytest.mark.parametrize("case", CASES["sncb"])
def test_sncb_dag_matches_float64_reference(sncb_child, case):
    assert sncb_child[case]["ok"], sncb_child[case]["problems"]


@pytest.mark.parametrize("case", CASES["knn"])
def test_wire_knn_matches_reference(knn_child, case):
    assert knn_child[case]["ok"], knn_child[case]["problems"]


@pytest.mark.parametrize("case", CASES["join"])
def test_join_pairs_match_float64_reference(join_child, case):
    assert join_child[case]["ok"], join_child[case]["problems"]


@pytest.mark.parametrize("case", CASES["tjoin"])
def test_tjoin_pairs_match_float64_reference(tjoin_child, case):
    assert tjoin_child[case]["ok"], tjoin_child[case]["problems"]


@pytest.mark.parametrize("case", CASES["range"])
def test_range_polygons_match_float64_reference(range_child, case):
    assert range_child[case]["ok"], range_child[case]["problems"]


@pytest.mark.parametrize("case", CASES["numerics"])
def test_centring_survives_the_float32_cast(numerics_child, case):
    assert numerics_child[case]["ok"], numerics_child[case]["problems"]
