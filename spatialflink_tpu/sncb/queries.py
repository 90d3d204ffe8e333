"""SNCB domain queries Q1–Q5 (``GeoFlink/sncb/queries/``).

Each ``build(events, ...)`` consumes an iterable of GpsEvents and yields
result records, mirroring the reference's ``Q*.build(env, events, …)``
DataStream pipelines. All use event-time windows with 5 s
bounded-out-of-orderness (each reference query assigns
``BoundedOutOfOrdernessTimestampExtractor(Time.seconds(5))``).

CRS note: the reference mixes metric (EPSG:25831-buffered) polygons with
raw lon/lat points inside a single degree-based grid (Q1_HighRisk.java:52-78
feeds metric PreparedGeometry rings into a WGS84 UniformGrid) — geometrically
inconsistent. This build does what the query *means*: points are enriched
to metric coordinates (vectorized UTM on device) and all zone containment /
proximity tests run in meters.
"""

from __future__ import annotations

import math
from dataclasses import dataclass
from typing import Dict, Iterable, Iterator, List, Sequence

import numpy as np

from spatialflink_tpu.sncb.common import (
    BufferedZone,
    CRSUtils,
    EnrichedEvent,
    GpsEvent,
    contains_any_zone,
)
from spatialflink_tpu.sncb.ops import (
    TrajOut,
    TrajSpeedOut,
    VarOut,
    traj_speed,
    trajectory_wkt,
    variation,
)
from spatialflink_tpu.streams.columns import WindowColumns
from spatialflink_tpu.streams.windows import (
    SlidingEventTimeWindows,
    TumblingEventTimeWindows,
    WindowAssembler,
)

_LATENESS_MS = 5_000  # Time.seconds(5) in every Q*.build


def _windows(events, size_ms, slide_ms, lateness_ms=_LATENESS_MS):
    asm = WindowAssembler(
        SlidingEventTimeWindows(size_ms, slide_ms),
        timestamp_fn=lambda e: e.timestamp,
        max_out_of_orderness_ms=lateness_ms,
    )
    yield from asm.stream(events)


def keyed_windows(events, size_ms, slide_ms, key_fn, lateness_ms=_LATENESS_MS):
    """keyBy(key).window(...) analog: per fired window, per key present."""
    for win in _windows(events, size_ms, slide_ms, lateness_ms):
        groups: Dict[str, List] = {}
        for e in win.events:
            groups.setdefault(key_fn(e), []).append(e)
        for key in sorted(groups):
            yield key, win.start, win.end, groups[key]


def _zone_filter(events: Sequence[GpsEvent], zones, keep_inside: bool,
                 backend: str = "device") -> List[GpsEvent]:
    """Batched zone containment filter over metric coordinates.
    ``backend="numpy"`` routes the host twin (contains_any_zone_np) —
    the per-node failover route of the composed DAG (dag.py)."""
    if not events:
        return []
    from spatialflink_tpu.ops.counters import counters

    if counters.enabled:
        # Each event is distance/containment-tested against every zone —
        # the distCompCounter analog for the SNCB zone kernels.
        counters.record_candidates(len(events), len(events) * len(zones))
    xy = CRSUtils.enrich_batch(events)
    if backend == "numpy":
        from spatialflink_tpu.sncb.common import contains_any_zone_np

        inside = contains_any_zone_np(zones, xy)
    else:
        inside = contains_any_zone(zones, xy)
    keep = inside if keep_inside else ~inside
    return [e for e, k in zip(events, keep) if k]


def q1_high_risk(
    events: Iterable[GpsEvent],
    high_risk_zones: Sequence[BufferedZone],
    radius_m: float = 20.0,
    window_s: int = 10,
) -> Iterator[EnrichedEvent]:
    """Q1: events near buffered high-risk polygons, re-emitted per 10 s
    tumbling window (Q1_HighRisk.java:30-105; the range query at :73-78).

    ``radius_m`` is the proximity radius in meters (the reference's
    0.001-degree radius against metric polygons is the CRS inconsistency
    described in the module docstring; 0.001° ≈ tens of meters at Brussels
    latitudes, hence the 20 m default).
    """
    zones = buffer_q1_zones(high_risk_zones, radius_m)
    for win in _windows(events, window_s * 1000, window_s * 1000):
        yield from q1_window(win.events, zones)


def q2_brake_monitor(
    events: Iterable[GpsEvent],
    maintenance_zones: Sequence[BufferedZone],
    window_s: float = 10.0,
    slide_ms: int = 10,
    var_fa_min: float = 0.6,
    var_ff_max: float = 0.5,
) -> Iterator[VarOut]:
    """Q2: exclude maintenance areas → per-device 10s/10ms sliding windows →
    brake-pressure variation filter varFA > 0.6 ∧ varFF ≤ 0.5
    (Q2_BrakeMonitor.java:25-103).

    Parity note: the reference's Point→EnrichedEvent remap drops the FA/FF
    fields before VariationAgg reads them (Q2_BrakeMonitor.java maps a fresh
    GpsEvent carrying only id/ts/lon/lat), so upstream every window computes
    variation of nothing. This build keeps the fields — the behavior the
    query obviously intends.
    """
    filtered = _batchwise_zone_exclude(events, maintenance_zones)
    for dev, start, end, evs in keyed_windows(
        filtered, int(window_s * 1000), slide_ms, key_fn=lambda e: e.device_id
    ):
        var_fa, var_ff = variation(evs)
        if var_fa > var_fa_min and var_ff <= var_ff_max:
            yield VarOut(dev, var_fa, var_ff, start, end, len(evs))


def _batchwise_zone_exclude(events, zones, chunk=8192):
    """Stream-preserving batched exclude filter (PolygonExcludeFn analog)."""
    buf: List[GpsEvent] = []
    for e in events:
        buf.append(e)
        if len(buf) >= chunk:
            yield from _zone_filter(buf, zones, keep_inside=False)
            buf = []
    if buf:
        yield from _zone_filter(buf, zones, keep_inside=False)


def _batchwise_zone_include(events, zones, chunk=8192):
    buf: List[GpsEvent] = []
    for e in events:
        buf.append(e)
        if len(buf) >= chunk:
            yield from _zone_filter(buf, zones, keep_inside=True)
            buf = []
    if buf:
        yield from _zone_filter(buf, zones, keep_inside=True)


def q3_trajectory(
    events: Iterable[GpsEvent], window_s: float = 10.0, slide_ms: int = 10
) -> Iterator[TrajOut]:
    """Q3: per-device sliding-window trajectory WKT
    (Q3_Trajectory.java:17-58)."""
    for dev, start, end, evs in keyed_windows(
        events, int(window_s * 1000), slide_ms, key_fn=lambda e: e.device_id
    ):
        yield TrajOut(dev, trajectory_wkt(evs), start, end)


def q4_trajectory_restricted(
    events: Iterable[GpsEvent],
    min_lon: float, max_lon: float, min_lat: float, max_lat: float,
    t_min: int, t_max: int,
    window_s: float = 10.0, slide_ms: int = 10,
) -> Iterator[TrajOut]:
    """Q4: Q3 with bbox/time-range predicate pushdown
    (Q4_TrajectoryRestricted.java:18-70)."""
    filtered = (
        e for e in events
        if min_lon <= e.lon <= max_lon and min_lat <= e.lat <= max_lat
        and t_min <= e.ts <= t_max
    )
    yield from q3_trajectory(filtered, window_s, slide_ms)


def q5_traj_speed_fence(
    events: Iterable[GpsEvent],
    fence_zones: Sequence[BufferedZone],
    avg_threshold: float = 50.0,
    min_threshold: float = 20.0,
    window_s: float = 45.0,
    slide_s: float = 5.0,
) -> Iterator[TrajSpeedOut]:
    """Q5: geofence include → per-device 45s/5s windows → trajectory + speed
    stats, threshold filter avg > a ∨ min > m (Q5_TrajAndSpeedFence.java:25-104)."""
    fenced = _batchwise_zone_include(events, fence_zones)
    for dev, start, end, evs in keyed_windows(
        fenced, int(window_s * 1000), int(slide_s * 1000),
        key_fn=lambda e: e.device_id,
    ):
        wkt, avg_speed, min_speed = traj_speed(evs)
        if avg_speed > avg_threshold or (
            min_speed == min_speed and min_speed > min_threshold
        ):
            yield TrajSpeedOut(dev, wkt, avg_speed, min_speed, start, end)


def q2_brake_monitor_batch(
    events: Sequence[GpsEvent],
    maintenance_zones: Sequence[BufferedZone],
    window_s: float = 10.0,
    slide_ms: int = 10,
    var_fa_min: float = 0.6,
    var_ff_max: float = 0.5,
) -> List[VarOut]:
    """Vectorized replay of Q2 over a bounded stream: identical outputs to
    ``q2_brake_monitor`` but computed via pane decomposition
    (streams/panes.py) — O(events) instead of O(events × overlap). This is
    what makes the reference's 10s/10ms window config (1000× overlap)
    tractable at benchmark rates.
    """
    from spatialflink_tpu.streams.panes import sliding_aggregate
    from spatialflink_tpu.utils.interning import Interner

    events = list(events)
    filtered = _zone_filter(events, maintenance_zones, keep_inside=False)
    if not filtered:
        return []
    interner = Interner()
    key = interner.intern_many(e.device_id for e in filtered)
    ts = np.array([e.ts for e in filtered], np.int64)
    fa = np.array([e.fa if e.fa is not None else np.nan for e in filtered])
    ff = np.array([e.ff if e.ff is not None else np.nan for e in filtered])
    # None fields are skipped by the reference accumulator: use ±inf-neutral
    # values (NaN-safe min/max via masking).
    fa_min_in = np.where(np.isnan(fa), np.inf, fa)
    fa_max_in = np.where(np.isnan(fa), -np.inf, fa)
    ff_min_in = np.where(np.isnan(ff), np.inf, ff)
    ff_max_in = np.where(np.isnan(ff), -np.inf, ff)

    win = sliding_aggregate(
        ts, key, interner.num_segments,
        int(window_s * 1000), slide_ms,
        min_fields={"fa_min": fa_min_in, "ff_min": ff_min_in},
        max_fields={"fa_max": fa_max_in, "ff_max": ff_max_in},
    )
    var_fa = win.maxs["fa_max"] - win.mins["fa_min"]
    var_ff = win.maxs["ff_max"] - win.mins["ff_min"]
    hit = (win.count > 0) & (var_fa > var_fa_min) & (var_ff <= var_ff_max)
    out: List[VarOut] = []
    size_ms = int(window_s * 1000)
    for w, k in zip(*np.nonzero(hit)):
        out.append(
            VarOut(
                interner.lookup(int(k)), float(var_fa[w, k]), float(var_ff[w, k]),
                int(win.starts[w]), int(win.starts[w]) + size_ms,
                int(win.count[w, k]),
            )
        )
    out.sort(key=lambda o: (o.win_start, o.device_id))
    return out


# ---------------------------------------------------------------------------
# Window-scoped query cores — one fired window's COLUMNS in, result
# records out. These are the node bodies of the composed SNCB DAG
# (spatialflink_tpu/dag.py): the DAG shares ONE window clock across all
# queries (amortizing ingest/interning — the deliberate deviation from
# the per-query window configs above, PARITY.md "Composed dataflow"),
# and turns each fired window into a streams/columns.py WindowColumns
# ONCE; every core below computes from those arrays — no core walks the
# event objects. Zone containment is a boolean mask over the view's
# shared metric coordinates (one UTM pass a window, however many zone
# queries read it); per-device work is a stable sort by dense id and
# segment reductions / one join per device. ``backend`` routes the zone
# kernels: "device" (contains_any_zone) or "numpy"
# (contains_any_zone_np) — the per-node failover route; results match
# to float ulps. The records are the per-event walk's, byte for byte
# (tests/test_window_columns.py holds each core to the walk it
# replaced). ``q1_window`` is the one event-list entry left: the
# streaming ``q1_high_risk`` above calls it, and it builds the same
# columns for the same core.


def buffer_q1_zones(high_risk_zones: Sequence[BufferedZone],
                    radius_m: float = 20.0) -> List[BufferedZone]:
    """Q1's proximity widening (build once, not per window)."""
    return [
        BufferedZone(z.rings_metric, z.buffer_m + radius_m, z.name)
        for z in high_risk_zones
    ]


def _zone_mask(gps: WindowColumns, zones,
               backend: str = "device") -> np.ndarray:
    """(N,) bool over the GPS rows: inside any buffered zone. The
    column form of ``_zone_filter``: same kernel, same ``xy`` across
    the link, a mask instead of a rebuilt object list."""
    n = len(gps)
    if not n:
        return np.zeros(0, bool)
    from spatialflink_tpu.ops.counters import counters

    if counters.enabled:
        counters.record_candidates(n, n * len(zones))
    if backend == "numpy":
        from spatialflink_tpu.sncb.common import contains_any_zone_np

        return contains_any_zone_np(zones, gps.metric_xy())
    return contains_any_zone(zones, gps.metric_xy())


def _trajectory_wkts(gps: WindowColumns, rows: np.ndarray) -> List[tuple]:
    """``(device_id, wkt)`` per device present in ``rows``, in device-id
    order: points sorted by timestamp, ties in window order
    (``trajectory_wkt`` over columns — one ``%g`` pass over the sorted
    coordinates, one join per device)."""
    grouped, starts, ends, by_name = gps.by_device(rows, by_ts=True)
    # Python floats (.tolist()) through %g: the walk's f"{float(v):g}".
    pts = ["%g %g" % xy for xy in zip(gps.lon[grouped].tolist(),
                                      gps.lat[grouped].tolist())]
    starts, ends = starts.tolist(), ends.tolist()
    out = []
    for dev, k in by_name:
        s, e = starts[k], ends[k]
        out.append((dev, f"POINT ({pts[s]})" if e - s == 1
                    else "LINESTRING (" + ", ".join(pts[s:e]) + ")"))
    return out


def q1_columns(cols: WindowColumns, zones: Sequence[BufferedZone],
               backend: str = "device") -> List[EnrichedEvent]:
    """Q1 core: events near the (pre-buffered) high-risk zones,
    enriched to metric coords (Q1_HighRisk.java:73-78)."""
    gps = cols.gps()
    hit = np.flatnonzero(_zone_mask(gps, zones, backend))
    metric = gps.metric_xy()[hit].tolist()
    out = []
    for p, (x_m, y_m) in zip(gps.pos[hit].tolist(), metric):
        raw = gps.events[p]
        out.append(EnrichedEvent(raw=raw, x_wgs84=raw.lon, y_wgs84=raw.lat,
                                 x_metric=x_m, y_metric=y_m))
    return out


def q1_window(events: Sequence[GpsEvent],
              zones: Sequence[BufferedZone],
              backend: str = "device") -> List[EnrichedEvent]:
    """Event-list entry of :func:`q1_columns` (the streaming
    ``q1_high_risk``'s per-window call)."""
    return q1_columns(WindowColumns.from_events(events), zones, backend)


def q2_columns(cols: WindowColumns,
               maintenance_zones: Sequence[BufferedZone],
               start: int, end: int,
               var_fa_min: float = 0.6, var_ff_max: float = 0.5,
               backend: str = "device") -> List[VarOut]:
    """Q2 core: maintenance-zone exclude → per-device brake-pressure
    variation → varFA > a ∧ varFF ≤ b filter (Q2_BrakeMonitor.java).
    ``variation`` as segment reductions: absent fields skipped, an
    all-absent device reads −inf, ``count`` counts events."""
    gps = cols.gps()
    rows = np.flatnonzero(~_zone_mask(gps, maintenance_zones, backend))
    if not len(rows):
        return []
    grouped, starts, ends, by_name = gps.by_device(rows)

    def spread(col):
        v = col[grouped]
        absent = np.isnan(v)
        lo = np.minimum.reduceat(np.where(absent, np.inf, v), starts)
        hi = np.maximum.reduceat(np.where(absent, -np.inf, v), starts)
        return np.where(hi >= lo, hi - lo, -np.inf).tolist()

    var_fa, var_ff = spread(gps.fa), spread(gps.ff)
    counts = (ends - starts).tolist()
    return [
        VarOut(dev, var_fa[k], var_ff[k], start, end, counts[k])
        for dev, k in by_name
        if var_fa[k] > var_fa_min and var_ff[k] <= var_ff_max
    ]


def q3_columns(cols: WindowColumns, start: int, end: int) -> List[TrajOut]:
    """Q3 core: per-device window trajectory WKT (Q3_Trajectory.java)."""
    gps = cols.gps()
    return [TrajOut(dev, wkt, start, end)
            for dev, wkt in _trajectory_wkts(gps, np.arange(len(gps)))]


def q4_columns(cols: WindowColumns, start: int, end: int,
               min_lon: float, max_lon: float,
               min_lat: float, max_lat: float,
               t_min: int, t_max: int) -> List[TrajOut]:
    """Q4 core: Q3 with bbox/time-range predicate pushdown
    (Q4_TrajectoryRestricted.java)."""
    gps = cols.gps()
    keep = ((gps.lon >= min_lon) & (gps.lon <= max_lon)
            & (gps.lat >= min_lat) & (gps.lat <= max_lat)
            & (gps.ts >= t_min) & (gps.ts <= t_max))
    return [TrajOut(dev, wkt, start, end)
            for dev, wkt in _trajectory_wkts(gps, np.flatnonzero(keep))]


def q5_columns(cols: WindowColumns,
               fence_zones: Sequence[BufferedZone],
               start: int, end: int,
               avg_threshold: float = 50.0, min_threshold: float = 20.0,
               backend: str = "device") -> List[TrajSpeedOut]:
    """Q5 core: geofence include → per-device trajectory + speed stats,
    avg > a ∨ min > m filter (Q5_TrajAndSpeedFence.java). The average
    is ``traj_speed``'s: a left-to-right sum over the device's present
    speeds in WINDOW order (a pairwise numpy sum is another float)."""
    gps = cols.gps()
    rows = np.flatnonzero(_zone_mask(gps, fence_zones, backend))
    if not len(rows):
        return []
    grouped, starts, ends, by_name = gps.by_device(rows)
    speed = gps.gps_speed[grouped]
    starts, ends = starts.tolist(), ends.tolist()
    out: List[TrajSpeedOut] = []
    # Same rows, same devices: the two groupings agree on device order.
    for (dev, wkt), (_dev, k) in zip(_trajectory_wkts(gps, rows), by_name):
        v = speed[starts[k]:ends[k]]
        speeds = v[~np.isnan(v)].tolist()
        if speeds:
            avg_speed, min_speed = sum(speeds) / len(speeds), min(speeds)
        else:
            avg_speed, min_speed = 0.0, math.nan
        if avg_speed > avg_threshold or (
            min_speed == min_speed and min_speed > min_threshold
        ):
            out.append(
                TrajSpeedOut(dev, wkt, avg_speed, min_speed, start, end)
            )
    return out


# Class-style aliases mirroring the reference entry points.
class Q1_HighRisk:
    build = staticmethod(q1_high_risk)


class Q2_BrakeMonitor:
    build = staticmethod(q2_brake_monitor)


class Q3_Trajectory:
    build = staticmethod(q3_trajectory)


class Q4_TrajectoryRestricted:
    build = staticmethod(q4_trajectory_restricted)


class Q5_TrajAndSpeedFence:
    build = staticmethod(q5_traj_speed_fence)
