"""StayTime app — per-cell dwell-time heatmaps (``GeoFlink/apps/StayTime.java``).

Three queries, matching StayTime.java:35-150:
  - ``cell_stay_time``: per trajectory per window, walk ts-ordered points
    and attribute each consecutive time gap to the earlier point's grid
    cell; then sum per cell (CellStayTimeWinFunction :216-396 +
    CellStayTimeAggregateWinFunction :433-447). Output per window:
    {cellName: totalStayTimeMs}.
  - ``cell_sensor_range_intersection``: per window, count sensor polygons
    whose geometry intersects each cell's boundary box
    (CellSensorIntersectionWinFunction :398-430).
  - ``normalized_cell_stay_time``: join on cell:
    (stayTime/1000 / sensorCount) * windowSize
    (normalizedCellStayTimeWinFunction :189-213).
"""

from __future__ import annotations

from typing import Dict, Iterable, Iterator, Optional, Sequence, Set, Tuple

import numpy as np

from spatialflink_tpu.grid import UniformGrid
from spatialflink_tpu.models.objects import Point, Polygon
from spatialflink_tpu.streams.windows import SlidingEventTimeWindows, WindowAssembler


def _windows(events, window_s: int, slide_s: int, lateness_s: int):
    asm = WindowAssembler(
        SlidingEventTimeWindows(window_s * 1000, slide_s * 1000),
        timestamp_fn=lambda e: e.timestamp,
        max_out_of_orderness_ms=lateness_s * 1000,
    )
    yield from asm.stream(events)


def _any_edge_hits_rect(p: np.ndarray, q: np.ndarray,
                        x1: float, y1: float, x2: float, y2: float) -> bool:
    """True if any segment p[i]→q[i] intersects the axis-aligned rectangle
    (Liang–Barsky clip, vectorized over segments)."""
    if len(p) == 0:
        return False
    d = q - p
    t0 = np.zeros(len(p))
    t1 = np.ones(len(p))
    ok = np.ones(len(p), bool)
    for dim, lo, hi in ((0, x1, x2), (1, y1, y2)):
        dd = d[:, dim]
        pp = p[:, dim]
        with np.errstate(divide="ignore", invalid="ignore"):
            tlo = (lo - pp) / dd
            thi = (hi - pp) / dd
        enter = np.where(dd >= 0, tlo, thi)
        exit_ = np.where(dd >= 0, thi, tlo)
        par = dd == 0
        ok &= ~(par & ((pp < lo) | (pp > hi)))
        t0 = np.where(par, t0, np.maximum(t0, enter))
        t1 = np.where(par, t1, np.minimum(t1, exit_))
    return bool((ok & (t0 <= t1)).any())


def cell_stay_time(
    points: Iterable[Point],
    traj_ids: Set[str],
    allowed_lateness_s: int,
    window_s: int,
    slide_s: int,
    grid: UniformGrid,
) -> Iterator[Tuple[int, int, Dict[str, float]]]:
    """Yield (winStart, winEnd, {cellName: stayTimeMs}) per fired window.

    Consecutive-point time gaps are attributed to the earlier point's cell
    (vectorized with numpy over the ts-sorted per-trajectory arrays — the
    same walk as CellStayTimeWinFunction's loop)."""
    for win in _windows(points, window_s, slide_s, allowed_lateness_s):
        evs = [p for p in win.events if not traj_ids or p.obj_id in traj_ids]
        if not evs:
            continue
        yield (win.start, win.end, stay_time_window(evs, grid))


def stay_time_window(evs, grid: UniformGrid) -> Dict[str, float]:
    """One window's {cellName: stayTimeMs} — the host walk shared by
    the streaming generator above and the composed DAG's StayTime node
    fallback route (dag.py). ``evs`` carries ``obj_id``/``timestamp``/
    ``x``/``y`` attributes (Points or GpsEvent-likes adapted by the
    caller)."""
    per_cell: Dict[str, float] = {}
    by_obj: Dict[str, list] = {}
    for p in evs:
        by_obj.setdefault(p.obj_id, []).append(p)
    for pts in by_obj.values():
        pts.sort(key=lambda p: p.timestamp)
        if len(pts) < 2:
            continue
        ts = np.array([p.timestamp for p in pts], np.int64)
        cells = grid.assign_cells_np(
            np.array([[p.x, p.y] for p in pts], float)
        )
        gaps = ts[1:] - ts[:-1]
        for cell, gap in zip(cells[:-1], gaps):
            name = grid.cell_name(int(cell)) if cell < grid.num_cells else "out"
            per_cell[name] = per_cell.get(name, 0.0) + float(gap)
    return per_cell


def cell_stay_time_soa(
    chunks,
    window_s: int,
    slide_s: int,
    grid: UniformGrid,
    allowed_lateness_s: int = 0,
    oid_allow: Optional[np.ndarray] = None,
) -> Iterator[Tuple[int, int, np.ndarray, np.ndarray]]:
    """SoA/device fast path for ``cell_stay_time``: point chunks
    {"ts","x","y","oid"} (dense int32 oids) → per window
    (start, end, cell_ids, dwell_ms) raw arrays via ONE segment-sum
    kernel per window (ops/trajectory.py:stay_time_cells_kernel) — the
    per-trajectory Python walk of the object path collapses into a
    device reduction (apps/StayTime.java:216-396). ``cell_ids`` may
    include ``grid.num_cells`` (the object path's "out" bucket);
    ``oid_allow``: optional bool mask over dense oids (the trajIdSet
    filter) — filtered points are COMPACTED out before pairing, exactly
    like the object path's pre-filter (masking alone would break
    consecutive pairs differently). Parity test: tests/test_apps.py."""
    import jax.numpy as jnp

    from spatialflink_tpu.operators.base import jitted
    from spatialflink_tpu.ops.trajectory import stay_time_cells_kernel
    from spatialflink_tpu.streams.soa import SoaWindowAssembler
    from spatialflink_tpu.utils.padding import next_bucket

    kernel = jitted(stay_time_cells_kernel, "num_cells")
    asm = SoaWindowAssembler(
        window_s * 1000, slide_s * 1000,
        ooo_ms=allowed_lateness_s * 1000,
    )
    for win in asm.stream(chunks):
        ts = np.asarray(win.arrays["ts"], np.int64)[:win.count]
        oid = np.asarray(win.arrays["oid"], np.int64)[:win.count]
        xy = np.stack(
            [np.asarray(win.arrays["x"], np.float64)[:win.count],
             np.asarray(win.arrays["y"], np.float64)[:win.count]],
            axis=1,
        )
        if oid_allow is not None:
            keep = oid_allow[oid]
            ts, oid, xy = ts[keep], oid[keep], xy[keep]
        if len(ts) == 0:
            # Object-path parity: a window with no surviving events is
            # SUPPRESSED (cell_stay_time's `if not evs: continue`), while
            # one with events but no pairs fires empty.
            continue
        hit, dwell = stay_time_window_soa(ts, oid, xy, grid, kernel)
        yield (win.start, win.end, hit, dwell)


def stay_time_window_soa(ts, oid, xy, grid: UniformGrid, kernel):
    """One window's (cell_ids, dwell_ms) via the segment-sum kernel —
    the device core shared by ``cell_stay_time_soa`` and the composed
    DAG's StayTime node (dag.py, straight from the window's columns).
    ``ts``/``oid`` integer arrays, ``xy`` (N, 2) float64; ``kernel`` a
    jitted stay_time_cells_kernel. Crosses
    the link through ``ship`` / ``telemetry.fetch`` like the operators."""
    from spatialflink_tpu.operators.base import ship
    from spatialflink_tpu.telemetry import telemetry
    from spatialflink_tpu.utils.padding import next_bucket

    if len(ts) < 2:
        return np.empty(0, np.int32), np.empty(0, np.int64)
    order = np.lexsort((ts, oid))
    cells = grid.assign_cells_np(xy[order])
    nb = next_bucket(len(ts), minimum=8)
    pad = nb - len(ts)
    t_rel = ts[order] - int(ts.min())  # int32-safe on non-x64 devices
    tp = np.concatenate([t_rel, np.zeros(pad, np.int64)]).astype(np.int32)
    op_ = np.concatenate(
        [oid[order], np.full(pad, -1, np.int64)]).astype(np.int32)
    cp = np.concatenate(
        [cells, np.full(pad, grid.num_cells, np.int64)]).astype(np.int32)
    vp = np.concatenate([np.ones(len(ts), bool), np.zeros(pad, bool)])
    dwell, cnt = telemetry.fetch(
        kernel(*ship(tp, cp, op_, vp), num_cells=grid.num_cells))
    dwell = dwell.astype(np.int64)
    hit = np.nonzero(cnt)[0].astype(np.int32)
    return hit, dwell[hit]


def cell_sensor_range_intersection(
    polygons: Iterable[Polygon],
    traj_ids: Set[str],
    allowed_lateness_s: int,
    window_s: int,
    slide_s: int,
    grid: UniformGrid,
) -> Iterator[Tuple[int, int, Dict[str, int]]]:
    """Yield (winStart, winEnd, {cellName: intersectingSensorCount}).

    A sensor-range polygon counts for every cell whose square its bbox
    geometry intersects; the reference replicates each polygon to its
    gridIDsSet and then exact-tests intersection against the cell boundary
    polygon — bbox-vs-cell intersection reproduces that for the rectangular
    sensor ranges the app targets, with an exact edge/containment test for
    the general case."""
    from spatialflink_tpu.ops.polygon import pack_rings, points_in_polygon
    import jax.numpy as jnp

    for win in _windows(polygons, window_s, slide_s, allowed_lateness_s):
        evs = [p for p in win.events if not traj_ids or p.obj_id in traj_ids]
        per_cell: Dict[str, int] = {}
        for poly in evs:
            for cell in poly.grid_cells(grid):
                xi, yi = divmod(int(cell), grid.n)
                x1 = grid.min_x + xi * grid.cell_length
                y1 = grid.min_y + yi * grid.cell_length
                x2, y2 = x1 + grid.cell_length, y1 + grid.cell_length
                # Exact test: any cell corner in polygon, any polygon vertex
                # in cell, or any polygon edge crossing the cell rectangle
                # (covers thin strips passing through with no vertex inside).
                verts, ev = poly.packed()
                corners = jnp.asarray(
                    [[x1, y1], [x2, y1], [x2, y2], [x1, y2]], float
                )
                corner_in = bool(
                    np.asarray(
                        points_in_polygon(corners, jnp.asarray(verts), jnp.asarray(ev))
                    ).any()
                )
                pv = np.concatenate(poly.rings, axis=0)
                vert_in = bool(
                    ((pv[:, 0] >= x1) & (pv[:, 0] <= x2)
                     & (pv[:, 1] >= y1) & (pv[:, 1] <= y2)).any()
                )
                edge_cross = corner_in or vert_in or _any_edge_hits_rect(
                    verts[:-1][ev], verts[1:][ev], x1, y1, x2, y2
                )
                if corner_in or vert_in or edge_cross:
                    name = grid.cell_name(int(cell))
                    per_cell[name] = per_cell.get(name, 0) + 1
        yield (win.start, win.end, per_cell)


def normalized_cell_stay_time(
    points: Iterable[Point],
    traj_ids_point: Set[str],
    polygons: Iterable[Polygon],
    traj_ids_sensor: Set[str],
    allowed_lateness_s: int,
    window_s: int,
    slide_s: int,
    grid: UniformGrid,
) -> Iterator[Tuple[str, int, int, float]]:
    """Join stay time with sensor coverage per (cell, window):
    normalized = (stayTimeMs/1000 / sensorCount) * windowSize
    (normalizedCellStayTimeWinFunction, StayTime.java:199-211).
    Yields (cellName, winStart, winEnd, normalizedStayTime)."""
    stay = {
        (s, e): cells
        for s, e, cells in cell_stay_time(
            points, traj_ids_point, allowed_lateness_s, window_s, slide_s, grid
        )
    }
    sensors = {
        (s, e): cells
        for s, e, cells in cell_sensor_range_intersection(
            polygons, traj_ids_sensor, allowed_lateness_s, window_s, slide_s, grid
        )
    }
    for span in sorted(set(stay) & set(sensors)):
        for cell, st in sorted(stay[span].items()):
            cnt = sensors[span].get(cell)
            if cnt:
                yield (cell, span[0], span[1], (st / 1000.0 / cnt) * window_s)
