"""Batched range-query kernels.

Replaces the reference's per-cell windowed inner loops
(range/PointPointRangeQuery.java:111-187, range/PointPolygonRangeQuery.java:37-160)
with one fused XLA program per window batch:

  gather cell flag → guaranteed? emit : candidate? exact distance ≤ r.

GeoFlink's core pruning trick is kept exactly: points whose cell is in the
**guaranteed** set are emitted with no distance computation; only points in
**candidate** cells get exact distances (PointPointRangeQuery.java:152-186).
On TPU we compute the (masked) distances for all lanes anyway — branchless —
and the flag decides emission, which is both simpler and faster than a
gather/compact.

``approximate`` mode mirrors the reference's ``approximateQuery`` flag:
candidate-cell points are emitted without the exact distance check
(PointPolygonRangeQuery.java:76-80).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from spatialflink_tpu.ops.distances import (
    pairwise_distance,
    point_polyline_distance,
    point_segment_distance,
)
from spatialflink_tpu.ops.polygon import points_in_polygon, ray_crosses

__all__ = [
    "range_query_kernel",
    "range_query_polygons_kernel",
    "range_query_polygons_pruned_kernel",
    "range_query_polylines_kernel",
    "geometry_range_query_kernel",
    "geometry_pair_distance",
    "range_points_fused",
    "range_polygons_fused",
    "range_polygons_pruned_fused",
    "range_polygons_cell_major_fused",
    "range_polylines_fused",
]


#: points a block of the pruned kernel's ``lax.map``
POINT_CHUNK = 8192
#: the cell-major kernel's tile (sorted points evaluated together), step
#: (index entries a tile reads at a time) and batch (tiles a step
#: evaluates together), the fastest of the forms timed on a TPU v5e at
#: range_spider.flood's size (PERF.md)
CELL_MAJOR_TILE = 512
CELL_MAJOR_WIDTH = 16
CELL_MAJOR_BATCH = 32
#: a query set whose K-wide cell row would hold more edge slots than this
#: (K, the fullest list up the bucket ladder, × E) is read cell-major
#: (operators/range_query.py): between the two nearest readings on a TPU
#: v5e, 256 slots where the K-wide row is 2 × faster and 512 where the
#: cell-major program is 7.8 × faster (PERF.md)
CELL_MAJOR_ROW = 256


def _emit_mask(valid, flags, min_dist, radius, approximate: bool):
    guaranteed = flags == 2
    candidate = flags == 1
    if approximate:
        hit = candidate
    else:
        hit = candidate & (min_dist <= radius)
    return valid & (guaranteed | hit)


def range_query_kernel(
    xy: jnp.ndarray,
    valid: jnp.ndarray,
    flags: jnp.ndarray,
    query_xy: jnp.ndarray,
    radius,
    approximate: bool = False,
):
    """Point stream vs point query set.

    ``xy``: (N, 2); ``valid``: (N,) bool; ``flags``: (N,) uint8 per-point
    pruning flags (gathered via ops.cells.gather_cell_flags); ``query_xy``:
    (Q, 2). Returns (keep (N,) bool, min_dist (N,)). min_dist for
    guaranteed-only emissions is still exact (computed branchlessly).
    """
    d = pairwise_distance(xy, query_xy)  # (N, Q)
    min_dist = jnp.min(d, axis=1)
    return _emit_mask(valid, flags, min_dist, radius, approximate), min_dist


def range_query_polygons_kernel(
    xy: jnp.ndarray,
    valid: jnp.ndarray,
    flags: jnp.ndarray,
    poly_verts: jnp.ndarray,
    poly_edge_valid: jnp.ndarray,
    radius,
    approximate: bool = False,
    poly_chunk: int = 32,
):
    """Point stream vs polygon query set (JTS-distance semantics: 0 inside).

    ``poly_verts``: (P, V, 2) packed rings per query polygon;
    ``poly_edge_valid``: (P, V-1). The batched form of
    PointPolygonRangeQuery's window loop (range/PointPolygonRangeQuery.java:37-101).

    Large query sets (the 1k-polygon benchmark config) are processed in
    ``poly_chunk``-polygon blocks via ``lax.map`` so the (chunk, N, E)
    intermediate stays bounded instead of materializing (P, N, E). When P
    isn't a multiple of the chunk, it is padded with all-invalid dummy
    polygons (infinite distance, never inside).
    """
    def one_poly(verts, ev):
        edge_d = point_polyline_distance(xy, verts, ev)
        inside = points_in_polygon(xy, verts, ev)
        return jnp.where(inside, jnp.zeros((), edge_d.dtype), edge_d)

    min_dist = _chunked_min_over_geoms(
        one_poly, poly_verts, poly_edge_valid, poly_chunk
    )
    return _emit_mask(valid, flags, min_dist, radius, approximate), min_dist


def range_query_polygons_pruned_kernel(
    xy: jnp.ndarray,
    valid: jnp.ndarray,
    cell: jnp.ndarray,
    flags: jnp.ndarray,
    cell_edges: jnp.ndarray,
    radius,
    point_chunk: int = POINT_CHUNK,
    approximate: bool = False,
):
    """Large-query-set point–polygon range through a grid index.

    The dense kernel evaluates every (point, polygon, edge) triple — P·E
    edge distances per point. For big query sets (the 1000-polygon config)
    almost all pairs are far apart, and which polygons can be within
    ``radius`` of a point is a property of the point's grid cell. Row
    ``cell`` of ``cell_edges`` (num_cells + 1, 4, E, K)
    (operators/base.py:pack_cell_candidates + pack_cell_edges, built once
    per query set) holds the cell's K candidate polygons as the ray cast
    and the point–segment distance consume them — the endpoint planes x1,
    y1, x2, y2 of up to E edges each — so a point gathers ONE row and
    evaluates K·E edges: no per-point ranking, no ring gather, no
    re-layout. An empty edge slot (a shorter polygon, an empty candidate
    slot) is a degenerate segment at ``pack_cell_edges``' far point:
    never crossed, and farther than any real edge.

    Exactness contract: a cell's list holds every polygon within
    ``radius`` (and the float32 band) of any point assigned to the cell, so
    on every kept lane whose nearest polygon is within ``radius`` keep and
    min_dist are bit-exact with the dense kernel — the same expressions,
    and the minimum over the list is the minimum over all polygons. K is
    read off the query set before the first window, so nothing can
    overflow and nothing is re-run. Dropped lanes report the minimum over
    their cell's list only — the distance to the far point (≈ 1.4e18)
    where the row is empty, as the out-of-grid row always is — and so does
    a guaranteed (flag 2) lane whose nearest polygon lies beyond
    ``radius``.

    Points stream through ``point_chunk``-sized lax.map blocks so the
    gathered (chunk, 4·E·K) rows stay bounded; a block transposes them so
    that points lie on the minor axis and the K slots beside it. Returns
    (keep, min_dist).
    """
    n = xy.shape[0]
    n_rows, _, n_edges, slots = cell_edges.shape
    rows = cell_edges.reshape(n_rows, -1)

    def chunk_fn(args):
        xy_c, valid_c, cell_c, flags_c = args
        x1, y1, x2, y2 = rows[cell_c].T.reshape(4, n_edges, slots, -1)
        edge_d = point_segment_distance(
            xy_c, jnp.stack([x1, y1], axis=-1), jnp.stack([x2, y2], axis=-1)
        )  # (E, K, C)
        crossings = ray_crosses(xy_c[:, 0], xy_c[:, 1], x1, y1, x2, y2)
        inside = jnp.sum(crossings.astype(jnp.int32), axis=0) % 2 == 1
        poly_d = jnp.where(
            inside, jnp.zeros((), edge_d.dtype), jnp.min(edge_d, axis=0)
        )  # (K, C)
        min_d = jnp.min(poly_d, axis=0)
        keep = _emit_mask(valid_c, flags_c, min_d, radius, approximate)
        return keep, min_d

    pad = (-n) % point_chunk
    if pad:
        xy = jnp.concatenate([xy, jnp.zeros((pad, 2), xy.dtype)])
        valid = jnp.concatenate([valid, jnp.zeros((pad,), bool)])
        cell = jnp.concatenate([cell, jnp.full((pad,), n_rows - 1, cell.dtype)])
        flags = jnp.concatenate([flags, jnp.zeros((pad,), flags.dtype)])
    n_blocks = (n + pad) // point_chunk
    keep_b, dist_b = jax.lax.map(
        chunk_fn,
        (
            xy.reshape(n_blocks, point_chunk, 2),
            valid.reshape(n_blocks, point_chunk),
            cell.reshape(n_blocks, point_chunk),
            flags.reshape(n_blocks, point_chunk),
        ),
    )
    return keep_b.reshape(-1)[:n], dist_b.reshape(-1)[:n]


def range_polygons_pruned_fused(xy, valid, cell, flags_table, cell_edges,
                                radius, point_chunk: int = POINT_CHUNK,
                                approximate: bool = False):
    from spatialflink_tpu.ops.cells import gather_cell_flags

    return range_query_polygons_pruned_kernel(
        xy, valid, cell, gather_cell_flags(cell, flags_table), cell_edges,
        radius, point_chunk=point_chunk, approximate=approximate,
    )


def range_query_polygons_pruned_compact_kernel(
    xy: jnp.ndarray,
    valid: jnp.ndarray,
    cell: jnp.ndarray,
    flags: jnp.ndarray,
    cell_edges: jnp.ndarray,
    radius,
    budget: int,
    point_chunk: int = 8192,
):
    """Candidate-compacted form of the pruned kernel.

    Grid flags already exclude most of a window (typically >90% of lanes
    have flags == 0 and can never be emitted); this kernel gathers the
    ≤ ``budget`` candidate lanes on device — their coordinates, flags and
    cells — and runs the grid-indexed evaluation only on them: the one
    place compaction beats the mask-don't-compact default, because the
    per-lane work here (a row of the cell table, K·E exact edges) is ~100×
    an elementwise op.

    Returns (keep (N,), min_dist (N,) — +big on lanes that were not
    evaluated — budget_overflow). Exactness contract: ``budget_overflow``
    0 ⇒ keep/min_dist(kept) are bit-exact; nonzero means more than
    ``budget`` candidate lanes existed (retry with a bigger budget).
    Exact mode only (the approximate keep-set is flag-driven and needs no
    distances — use the dense kernel's approximate path).
    """
    n = xy.shape[0]
    lanes = valid & (flags > 0)
    n_cand = jnp.sum(lanes.astype(jnp.int32))
    idx = jnp.nonzero(lanes, size=budget, fill_value=n)[0]
    in_range = idx < n
    safe = jnp.minimum(idx, n - 1)
    xy_c = jnp.where(in_range[:, None], xy[safe], 0.0)
    flags_c = jnp.where(in_range, flags[safe], 0)

    keep_c, dist_c = range_query_polygons_pruned_kernel(
        xy_c, in_range, cell[safe], flags_c, cell_edges, radius,
        point_chunk=min(point_chunk, budget),
    )

    big = jnp.asarray(jnp.finfo(dist_c.dtype).max, dist_c.dtype)
    # Scatter through the RAW indices: padding lanes carry idx == n, which
    # mode="drop" discards (clipped indices would overwrite lane n-1).
    keep = jnp.zeros(n, bool).at[idx].set(keep_c, mode="drop")
    dist = jnp.full(n, big, dist_c.dtype).at[idx].set(dist_c, mode="drop")
    return keep, dist, jnp.maximum(n_cand - budget, 0)


def range_polygons_pruned_compact_fused(
    xy, valid, cell, flags_table, cell_edges, radius, budget: int,
    point_chunk: int = 8192,
):
    from spatialflink_tpu.ops.cells import gather_cell_flags

    return range_query_polygons_pruned_compact_kernel(
        xy, valid, cell, gather_cell_flags(cell, flags_table), cell_edges,
        radius, budget=budget, point_chunk=point_chunk,
    )


def range_polygons_cell_major_fused(
    xy: jnp.ndarray,
    valid: jnp.ndarray,
    cell: jnp.ndarray,
    flags_table: jnp.ndarray,
    entry_edges: jnp.ndarray,
    starts: jnp.ndarray,
    radius,
    tile: int = CELL_MAJOR_TILE,
    width: int = CELL_MAJOR_WIDTH,
    batch: int = CELL_MAJOR_BATCH,
    approximate: bool = False,
):
    """Point–polygon range through the ragged grid index, cell-major.

    The pruned kernel gathers one K-wide row of the cell table a point: a
    set whose fullest cell lists K polygons of E edges makes every point
    evaluate K·E edge slots and the table ``cells × 4·E·K`` floats, however
    short most lists are. Here every cell's list is one contiguous run of
    ``entry_edges`` (entries + ``width``, 4 · E) — cell g's entries are
    ``[starts[g], starts[g + 1])`` (operators/base.py:pack_cell_candidates
    + pack_entry_edges) — and the window's points are put in cell order on
    the device (one ``lax.sort`` by cell, an invalid lane as out of grid; x,
    y and the lane index ride along). A tile of ``tile`` consecutive sorted
    points then lies in the cells from its first point's to its last
    point's, and the entries of all of them are one run, ``[starts[first],
    starts[last + 1])``: the tile is evaluated against that run and nothing
    else, in ``ceil(run / width)`` steps of ``(width, E, tile)`` blocks,
    ``batch`` tiles of like steps at a time (:func:`_cell_major_tiles`) — a
    tile whose cells list nothing costs a step only where its batch holds
    one that takes some. Keep and distance go back to
    lane order by a second sort, on the carried lane index (keep in the sign
    bit of the distance): no scatter.

    Exactness: the run holds every point's own list (its cell lies between
    the tile's first and last), and whatever a step reads past the run is
    the next cells' entries or the table's far rows — so a point is
    evaluated against a superset of its list. Any superset of a list that
    holds every polygon within ``radius`` (and the float32 band) gives the
    same keep and the same kept distance: the nearest polygon is in the
    list, and a minimum over more polygons is the same minimum. A polygon
    listed twice changes neither. Per (point, edge) the expressions are the pruned
    kernel's, and "0 inside a polygon, else the least edge distance, least
    over polygons" is "0 inside any polygon, else the least distance over
    all edges", so kept lanes agree with it — bit for bit wherever the
    backend rounds the two layouts' arithmetic alike (XLA:CPU's vector code
    for a broadcast edge can differ from a gathered one's in the last bits
    of a coordinate, well inside the float32 band). A dropped lane reports
    the least distance over its tile's run, or a value past any real
    distance where the run is empty.

    Returns (keep, min_dist, steps): a lane of a tile evaluated that tile's
    share of ``steps · width · E`` edge slots, so the window evaluated
    ``steps · width · E · tile`` of them, padding included.
    """
    n = xy.shape[0]
    n_rows = flags_table.shape[0]  # num_cells + 1: the last is out of grid
    pad = (-n) % tile
    lane = jnp.arange(n + pad, dtype=jnp.int32)
    # an invalid lane sorts as out of grid, whose flag is 0: never kept
    cell = jnp.where(valid, cell, n_rows - 1)
    if pad:
        xy = jnp.concatenate([xy, jnp.zeros((pad, 2), xy.dtype)])
        cell = jnp.concatenate([cell,
                                jnp.full((pad,), n_rows - 1, cell.dtype)])
    cell_s, x_s, y_s, lane_s = jax.lax.sort(
        (cell, xy[:, 0], xy[:, 1], lane), num_keys=1)
    n_tiles = (n + pad) // tile
    lo = starts[cell_s.reshape(n_tiles, tile)[:, 0]]
    hi = starts[cell_s.reshape(n_tiles, tile)[:, -1] + 1]
    dist_s, steps = _cell_major_tiles(x_s, y_s, lo, hi, entry_edges, tile,
                                      width, batch)
    keep_s = _emit_mask(True, flags_table[cell_s], dist_s, radius,
                        approximate)
    # keep rides back in the distance's sign bit (a distance is >= 0): one
    # payload for the sort back to lane order, both recovered exactly
    signed = jnp.where(keep_s, dist_s, -dist_s)
    _lane, signed = jax.lax.sort((lane_s, signed), num_keys=1)
    return (~jnp.signbit(signed))[:n], jnp.abs(signed)[:n], jnp.sum(steps)


def _cell_major_tiles(x_s, y_s, lo, hi, entry_edges, tile, width, batch):
    """The tile loop. A tile's run takes ``ceil((hi - lo) / width)`` steps
    of ``width`` entries, all E slots of each. The tiles are put in order of
    their steps and taken ``batch`` at a time: a batch runs its longest
    tile's steps, each step evaluating every tile of it against its own next
    ``width`` entries — a tile past its run reads the entries after it, or
    the table's far last row where it would run off the end, so it is still
    evaluated against a superset of its lists. Ordering keeps a batch's
    tiles of like steps; batching keeps the loop's iterations — device
    operations, each one a profiler event — to about ``1 / batch`` of one
    tile at a time. Returns (distance a sorted lane, steps evaluated: a
    batch's longest run × ``batch``, summed)."""
    n_tiles = lo.shape[0]
    steps = (hi - lo + (width - 1)) // width
    x_t, y_t = x_s.reshape(n_tiles, tile), y_s.reshape(n_tiles, tile)
    pad = (-n_tiles) % batch
    if pad:  # tiles of no run, ordered first; their rows are dropped
        x_t = jnp.concatenate([x_t, jnp.zeros((pad, tile), x_t.dtype)])
        y_t = jnp.concatenate([y_t, jnp.zeros((pad, tile), y_t.dtype)])
        lo = jnp.concatenate([lo, jnp.zeros(pad, lo.dtype)])
        steps = jnp.concatenate([steps, jnp.zeros(pad, steps.dtype)])
    order = jnp.argsort(steps)
    steps_b = steps[order].reshape(-1, batch)
    runs = jnp.max(steps_b, axis=1)
    big = jnp.asarray(jnp.finfo(x_s.dtype).max, x_s.dtype)
    reach = jnp.arange(width, dtype=lo.dtype)

    def batch_fn(args):
        x_g, y_g, lo_g, run = args  # (B, T), (B, T), (B,), ()
        xg, yg = x_g[:, None, None, :], y_g[:, None, None, :]  # (B, 1, 1, T)
        p = jnp.stack([xg, yg], axis=-1)

        def step(k, carry):
            inside, dmin = carry
            e = jnp.take(entry_edges, lo_g[:, None] + k * width + reach,
                         axis=0, mode="clip").reshape(batch, width, 4, -1)
            # (B, W, E, 1) each
            x1, y1, x2, y2 = (e[:, :, i, :, None] for i in range(4))
            edge_d = point_segment_distance(
                p, jnp.stack([x1, y1], axis=-1), jnp.stack([x2, y2], axis=-1)
            )  # (B, W, E, T)
            crossings = ray_crosses(xg, yg, x1, y1, x2, y2)
            odd = jnp.sum(crossings.astype(jnp.int32), axis=2) % 2 == 1
            return (inside | jnp.any(odd, axis=1),
                    jnp.minimum(dmin, jnp.min(edge_d, axis=(1, 2))))

        inside, dmin = jax.lax.fori_loop(
            0, run, step, (jnp.zeros((batch, tile), bool),
                           jnp.full((batch, tile), big, x_s.dtype)))
        return jnp.where(inside, jnp.zeros((), x_s.dtype), dmin)

    dist_b = jax.lax.map(batch_fn, (
        x_t[order].reshape(-1, batch, tile),
        y_t[order].reshape(-1, batch, tile), lo[order].reshape(-1, batch),
        runs,
    ))
    # back to tile order: the inverse permutation, one row gather
    dist = dist_b.reshape(-1, tile)[jnp.argsort(order)][:n_tiles]
    return dist.reshape(-1), jnp.sum(runs) * batch


def _chunked_min_over_geoms(one_fn, verts, edge_valid, chunk):
    """min over geometries of per-geometry point distances, processed in
    ``chunk``-geometry lax.map blocks so the (chunk, N, E) intermediate
    stays bounded. Short sets take the plain vmap path; padding uses
    all-invalid dummies (infinite distance, never inside)."""
    p = verts.shape[0]
    if p <= chunk:
        return jnp.min(jax.vmap(one_fn)(verts, edge_valid), axis=0)
    pad = (-p) % chunk
    if pad:
        verts = jnp.concatenate(
            [verts, jnp.zeros((pad,) + verts.shape[1:], verts.dtype)], axis=0
        )
        edge_valid = jnp.concatenate(
            [edge_valid, jnp.zeros((pad,) + edge_valid.shape[1:], bool)], axis=0
        )
    vb = verts.reshape(-1, chunk, *verts.shape[1:])
    eb = edge_valid.reshape(-1, chunk, *edge_valid.shape[1:])
    block_min = jax.lax.map(
        lambda be: jnp.min(jax.vmap(one_fn)(be[0], be[1]), axis=0), (vb, eb)
    )  # (P/chunk, N)
    return jnp.min(block_min, axis=0)


def range_query_polylines_kernel(
    xy: jnp.ndarray,
    valid: jnp.ndarray,
    flags: jnp.ndarray,
    line_verts: jnp.ndarray,
    line_edge_valid: jnp.ndarray,
    radius,
    approximate: bool = False,
    line_chunk: int = 32,
):
    """Point stream vs linestring query set (min edge distance).

    Batched form of PointLineStringRangeQuery's loop
    (range/PointLineStringRangeQuery.java). Large query sets are chunked
    like range_query_polygons_kernel.
    """
    def one_line(v, e):
        return point_polyline_distance(xy, v, e)

    min_dist = _chunked_min_over_geoms(
        one_line, line_verts, line_edge_valid, line_chunk
    )
    return _emit_mask(valid, flags, min_dist, radius, approximate), min_dist


# Fused variants: cell-flag gather + query in ONE jitted program, so the
# per-window path costs a single dispatch (no eager gather round trip).


def range_points_fused(xy, valid, cell, flags_table, query_xy, radius,
                       approximate: bool = False):
    from spatialflink_tpu.ops.cells import gather_cell_flags

    return range_query_kernel(
        xy, valid, gather_cell_flags(cell, flags_table), query_xy, radius,
        approximate=approximate,
    )


def range_polygons_fused(xy, valid, cell, flags_table, poly_verts,
                         poly_edge_valid, radius, approximate: bool = False):
    from spatialflink_tpu.ops.cells import gather_cell_flags

    return range_query_polygons_kernel(
        xy, valid, gather_cell_flags(cell, flags_table), poly_verts,
        poly_edge_valid, radius, approximate=approximate,
    )


def range_polylines_fused(xy, valid, cell, flags_table, line_verts,
                          line_edge_valid, radius, approximate: bool = False):
    from spatialflink_tpu.ops.cells import gather_cell_flags

    return range_query_polylines_kernel(
        xy, valid, gather_cell_flags(cell, flags_table), line_verts,
        line_edge_valid, radius, approximate=approximate,
    )


def _vert_valid(edge_valid: jnp.ndarray) -> jnp.ndarray:
    """(..., V-1) edge mask → (..., V) vertex mask (a vertex is real if it
    bounds a real edge)."""
    z = jnp.zeros(edge_valid.shape[:-1] + (1,), bool)
    return (
        jnp.concatenate([edge_valid, z], axis=-1)
        | jnp.concatenate([z, edge_valid], axis=-1)
    )


def geometry_pair_distance(
    averts: jnp.ndarray,
    aev: jnp.ndarray,
    bverts: jnp.ndarray,
    bev: jnp.ndarray,
    a_polygonal: bool = False,
    b_polygonal: bool = False,
) -> jnp.ndarray:
    """JTS-compatible distance between two packed boundaries (scalars).

    Non-overlapping: min over vertex→other-boundary distances both ways
    (exact for polyline pairs, since the closest approach involves a vertex
    of one of them). Overlap/containment: JTS returns 0 when geometries
    intersect — detected here as any valid vertex of one polygonal geometry
    containing a vertex of the other (and vice versa). Edge-crossing overlap
    with no contained vertex yields a near-zero edge distance already.
    """
    big = jnp.asarray(jnp.finfo(averts.dtype).max, averts.dtype)
    a_ok = _vert_valid(aev)
    b_ok = _vert_valid(bev)
    d_ab = jnp.where(a_ok, point_polyline_distance(averts, bverts, bev), big)
    d_ba = jnp.where(b_ok, point_polyline_distance(bverts, averts, aev), big)
    d = jnp.minimum(jnp.min(d_ab), jnp.min(d_ba))
    zero = jnp.zeros((), averts.dtype)
    if b_polygonal:
        a_in_b = jnp.any(points_in_polygon(averts, bverts, bev) & a_ok)
        d = jnp.where(a_in_b, zero, d)
    if a_polygonal:
        b_in_a = jnp.any(points_in_polygon(bverts, averts, aev) & b_ok)
        d = jnp.where(b_in_a, zero, d)
    return d


def geometry_range_query_kernel(
    obj_verts: jnp.ndarray,
    obj_edge_valid: jnp.ndarray,
    valid: jnp.ndarray,
    flags: jnp.ndarray,
    query_verts: jnp.ndarray,
    query_edge_valid: jnp.ndarray,
    radius,
    approximate: bool = False,
    obj_polygonal: bool = False,
    query_polygonal: bool = False,
):
    """Geometry stream (polygons/linestrings) vs geometry query set.

    ``obj_verts``: (N, V, 2) per-object packed boundaries; distances via
    ``geometry_pair_distance`` (JTS semantics incl. overlap→0) — the batched
    form of e.g. PolygonPolygonRangeQuery's window loop.
    """
    def pair(averts, aev):
        return jax.vmap(
            lambda qverts, qev: geometry_pair_distance(
                averts, aev, qverts, qev, obj_polygonal, query_polygonal
            )
        )(query_verts, query_edge_valid)  # (Q,)

    d = jax.vmap(pair)(obj_verts, obj_edge_valid)  # (N, Q)
    min_dist = jnp.min(d, axis=1)
    return _emit_mask(valid, flags, min_dist, radius, approximate), min_dist
