"""Trajectory segment kernels.

The reference's trajectory operators keep per-objID state in Flink keyed
state (ValueState/MapState) and iterate per record
(tStats/TStatsQuery.java:44-145, tAggregate/TAggregateQuery.java:53-250).
Here a window's points are sorted by (objID, ts) once on the host and every
per-trajectory statistic is a segment reduction over the interned objID —
one fused XLA program per window instead of per-record state mutation.
"""

from __future__ import annotations

from typing import NamedTuple

import jax
import jax.numpy as jnp

from spatialflink_tpu.ops.distances import point_point_distance


class TrajStats(NamedTuple):
    """Per-segment (per-objID) trajectory statistics for a window.

    Mirrors the output tuple of TStatsQuery (objID, spatialLength,
    temporalLength, spatialLength/temporalLength — TStatsQuery.java:137-144).
    """

    spatial_length: jnp.ndarray  # (U,)
    temporal_length: jnp.ndarray  # (U,) ms
    count: jnp.ndarray  # (U,) points per trajectory
    avg_speed: jnp.ndarray  # (U,) spatial/temporal (0 where temporal == 0)


def traj_stats_kernel(
    xy: jnp.ndarray,
    ts: jnp.ndarray,
    oid: jnp.ndarray,
    valid: jnp.ndarray,
    num_segments: int,
) -> TrajStats:
    """Inputs must be pre-sorted by (oid, ts); padding lanes carry
    oid = num_segments - 1 … any valid id with valid=False (they're masked).

    Consecutive-point distances within each trajectory are summed per
    segment; out-of-order duplicates (equal timestamps) contribute like the
    reference's window variant, which walks points in sorted order.
    """
    same_traj = (oid[1:] == oid[:-1]) & valid[1:] & valid[:-1]
    seg_d = point_point_distance(xy[1:], xy[:-1])
    seg_t = (ts[1:] - ts[:-1]).astype(seg_d.dtype)
    contrib_d = jnp.where(same_traj, seg_d, 0)
    contrib_t = jnp.where(same_traj, seg_t, 0)
    # Segment sums keyed by the *later* point's trajectory.
    spatial = jax.ops.segment_sum(contrib_d, oid[1:], num_segments=num_segments)
    temporal = jax.ops.segment_sum(contrib_t, oid[1:], num_segments=num_segments)
    count = jax.ops.segment_sum(
        valid.astype(jnp.int32), oid, num_segments=num_segments
    )
    speed = jnp.where(temporal > 0, spatial / jnp.where(temporal > 0, temporal, 1), 0.0)
    return TrajStats(spatial, temporal, count, speed)


def traj_stats_sorted_fused(
    xy: jnp.ndarray,
    ts: jnp.ndarray,
    oid: jnp.ndarray,
    valid: jnp.ndarray,
    num_segments: int,
) -> TrajStats:
    """traj_stats over an UNsorted batch: the (oid, ts) sort happens on
    device (lexsort) so SoA windows go straight from the assembler into one
    fused program — no host-side Python sort of event objects
    (the round-1 throughput cap, TStatsQuery.java:148-189's window walk).
    Invalid lanes sort to the end (oid forced past every real id)."""
    oid_sort = jnp.where(valid, oid, num_segments)
    order = jnp.lexsort((ts, oid_sort))
    return traj_stats_kernel(
        xy[order], ts[order], oid[order], valid[order],
        num_segments=num_segments,
    )


class TrajPaneStats(NamedTuple):
    """Device pane-sliding tStats output: (num_oids, n_starts) matrices,
    oid-major (the segment-sum layout); the host wrapper transposes and
    applies the alive-window filter. ``temporal``/``count`` are int32 —
    exact on every backend (per-oid ms totals are bounded by the stream
    span, which the wrapper checks fits int32)."""

    spatial: jnp.ndarray  # (K, n_starts)
    temporal: jnp.ndarray  # (K, n_starts) int32 ms
    count: jnp.ndarray  # (K, n_starts) int32


def traj_stats_pane_kernel(
    ts_rel: jnp.ndarray,
    x: jnp.ndarray,
    y: jnp.ndarray,
    oid: jnp.ndarray,
    valid: jnp.ndarray,
    num_oids: int,
    slide_ms: int,
    ppw: int,
    n_panes: int,
) -> TrajPaneStats:
    """Pane-decomposed sliding tStats ON DEVICE — the TPU form of
    streams/panes.py:traj_stats_sliding (itself the vectorized analog of
    the reference's per-record accumulator walk, TStatsQuery.java:44-145).

    Inputs are pre-sorted by (oid, ts) with padding at the end
    (valid=False). ``ts_rel`` is int32 REBASED time: the host wrapper
    subtracts ``p_lo·slide_ms`` so epoch-ms values survive the int32
    world of a non-x64 device (raw epoch ms ~1.7e12 would silently wrap;
    pane arithmetic is shift-invariant, so rebasing changes nothing).
    ``n_panes`` is a static bucket.

    Everything is expressed as SORTED segment sums + cumulative sums —
    no data-dependent scatters: the (oid, ts) sort makes every flat
    ``oid·n_panes + pane`` id non-decreasing, which XLA lowers to an
    efficient sorted-segment reduction instead of a serialized scatter.
    Window sums are cumsum differences gathered at STATIC row offsets,
    and the start-boundary corrections (a consecutive-point segment must
    not count for windows that begin after its earlier point) are two
    more sorted segment sums into a difference array + one cumsum —
    the interval-subtract of the host path, TPU-shaped. Temporal sums
    stay integer end to end (int32-exact; floats would round above
    2^24 on f32 devices).
    """
    k = num_oids
    n_starts = n_panes + ppw - 1
    nseg_flat = k * n_panes
    ts_rel = ts_rel.astype(jnp.int32)
    pane = jnp.clip(ts_rel // slide_ms, 0, n_panes - 1)
    sentinel = jnp.int32(nseg_flat)
    ids_pt = jnp.where(
        valid, oid.astype(jnp.int32) * n_panes + pane, sentinel
    )

    cnt = jax.ops.segment_sum(
        valid.astype(jnp.int32), ids_pt, num_segments=nseg_flat + 1,
        indices_are_sorted=True,
    )[:nseg_flat].reshape(k, n_panes)

    same = (oid[1:] == oid[:-1]) & valid[1:] & valid[:-1]
    dx = x[1:] - x[:-1]
    dy = y[1:] - y[:-1]
    f_dtype = x.dtype
    seg_d = jnp.where(same, jnp.sqrt(dx * dx + dy * dy),
                      jnp.zeros((), f_dtype))
    seg_dt = jnp.where(same, ts_rel[1:] - ts_rel[:-1], jnp.int32(0))
    ids_seg = ids_pt[1:]  # always the later point's id — stays sorted;
    # non-segments contribute zeros (cheaper than breaking sortedness
    # with a sentinel mid-stream).
    pane_d = jax.ops.segment_sum(
        seg_d, ids_seg, num_segments=nseg_flat + 1, indices_are_sorted=True,
    )[:nseg_flat].reshape(k, n_panes)
    pane_dt = jax.ops.segment_sum(
        seg_dt, ids_seg, num_segments=nseg_flat + 1, indices_are_sorted=True,
    )[:nseg_flat].reshape(k, n_panes)

    # Rolling window sums: one cumsum + static-offset row gathers.
    row = jnp.arange(n_starts, dtype=jnp.int32) - (ppw - 1)
    row_hi = jnp.clip(row + ppw, 0, n_panes)
    row_lo = jnp.clip(row, 0, n_panes)

    def rolling(a):
        c = jnp.concatenate(
            [jnp.zeros((k, 1), a.dtype), jnp.cumsum(a, axis=1)], axis=1
        )
        return c[:, row_hi] - c[:, row_lo]

    w_d = rolling(pane_d)
    w_dt = rolling(pane_dt)
    w_cnt = rolling(cnt)

    # Start-boundary corrections. t_prev_eff keeps ids monotone across
    # trajectory boundaries (those lanes carry zero data anyway).
    t_prev_eff = jnp.where(same, ts_rel[:-1], ts_rel[1:])
    seg_pane = ts_rel[1:] // slide_ms  # rebased pane of the later point
    first_b = jnp.maximum(t_prev_eff // slide_ms + 1,
                          seg_pane - ppw + 1)
    base = -(ppw - 1)  # rebased window-start pane of start-index 0
    si0 = jnp.clip(first_b - base, 0, n_starts)
    si1 = jnp.clip(seg_pane - base + 1, 0, n_starts)
    has = same & (si0 < si1) & valid[1:]
    d_corr = jnp.where(has, seg_d, jnp.zeros((), f_dtype))
    t_corr = jnp.where(has, seg_dt, jnp.int32(0))
    stride = n_starts + 1
    oid_b = oid[1:].astype(jnp.int32) * stride
    ids0 = jnp.where(valid[1:], oid_b + si0, jnp.int32(k * stride))
    ids1 = jnp.where(valid[1:], oid_b + si1, jnp.int32(k * stride))

    def interval(vals, ids):
        return jax.ops.segment_sum(
            vals, ids, num_segments=k * stride + 1, indices_are_sorted=True,
        )[:k * stride].reshape(k, stride)

    diff_d = interval(d_corr, ids0) - interval(d_corr, ids1)
    diff_t = interval(t_corr, ids0) - interval(t_corr, ids1)
    w_d = w_d - jnp.cumsum(diff_d, axis=1)[:, :n_starts]
    w_dt = w_dt - jnp.cumsum(diff_t, axis=1)[:, :n_starts]
    return TrajPaneStats(w_d, w_dt, w_cnt)


def stay_time_cells_kernel(
    ts: jnp.ndarray,
    cell: jnp.ndarray,
    oid: jnp.ndarray,
    valid: jnp.ndarray,
    num_cells: int,
) -> jnp.ndarray:
    """Per-cell dwell time for one window: consecutive same-trajectory
    time gaps attributed to the EARLIER point's grid cell, summed per
    cell — the device form of the StayTime app's per-trajectory walk
    (apps/StayTime.java:216-396 CellStayTimeWinFunction + :433-447
    aggregate). Inputs pre-sorted by (oid, ts), padding at the end;
    out-of-grid points carry ``cell == num_cells`` and land in the last
    ("out") bucket. Returns ((num_cells + 1,) int32 ms sums,
    (num_cells + 1,) int32 pair counts)."""
    same = (oid[1:] == oid[:-1]) & valid[1:] & valid[:-1]
    gaps = jnp.where(same, (ts[1:] - ts[:-1]).astype(jnp.int32),
                     jnp.int32(0))
    key = jnp.where(same & valid[:-1], cell[:-1].astype(jnp.int32),
                    jnp.int32(num_cells + 1))
    dwell = jax.ops.segment_sum(
        gaps, key, num_segments=num_cells + 2
    )[:num_cells + 1]
    # Pair counts distinguish "cell with only zero-length gaps" (the
    # object path still emits the key, value 0) from "no pairs".
    count = jax.ops.segment_sum(
        same.astype(jnp.int32), key, num_segments=num_cells + 2
    )[:num_cells + 1]
    return dwell, count


class TrajPairs(NamedTuple):
    """Deduped trajectory-pair join output (device-compacted), as long as
    the pair list it was made of (a window has no more trajectory pairs
    than point pairs, so nothing here can overflow).

    ``left_oid`` / ``right_oid``: int32 object ids of each distinct pair,
    ascending by (left, right), -1 padding; ``dist``: the pair's minimum
    point distance (dtype max on padding); ``count``: () number of distinct
    pairs, the leading lanes.
    """

    left_oid: jnp.ndarray
    right_oid: jnp.ndarray
    dist: jnp.ndarray
    count: jnp.ndarray


#: ids a side the packed int32 pair key ``left · S + right`` can hold
MAX_TRAJ_IDS = 46_340


def traj_pair_dedup_kernel(
    left_id: jnp.ndarray,
    right_id: jnp.ndarray,
    dist: jnp.ndarray,
    num_ids,
) -> TrajPairs:
    """Compact join pairs → distinct (trajectory, trajectory) pairs with
    their minimum distance, entirely on device and **sparse**: time
    O(P log P) and memory O(P) in the P lanes of the pair list (the pair
    budget); no array, table or loop is sized by the number of ids, and no
    lane is gathered.

    Replaces the reference's per-record dedup map (latest pair per
    (traj, queryTraj), tJoin/TJoinQuery.java:60-154): the pairs are keyed
    ``left id · num_ids + right id`` and sorted by (key, distance), so
    every trajectory pair's matches lie in one run led by its closest
    match; a second sort, by "the key on a run's first lane, the padding
    key elsewhere", moves the run starts to the front in key order (a
    compaction without a scatter or a ``nonzero``: on a v5e two sorts of
    2²¹ lanes cost 8 ms, the same compaction through two scatters 20 and
    through ``nonzero`` 90–130; PERF.md §6, PR 39).

    ``left_id``/``right_id``/``dist``: the pair list as trajectory ids — a
    CompactJoinResult whose extraction carried the id lanes as its payload
    (``ops/join.py:bucketize_planes``), or one mapped from indices by
    ``traj_pair_ids`` — in ``[0, num_ids)``, -1 padding; ``num_ids`` (a
    traced scalar: no program per id count) at most ``MAX_TRAJ_IDS``, so
    that the key fits int32 — the callers refuse more.
    """
    ok = left_id >= 0
    pad = jnp.iinfo(jnp.int32).max
    big = jnp.asarray(jnp.finfo(dist.dtype).max, dist.dtype)
    num_ids = jnp.asarray(num_ids, jnp.int32)
    key = jnp.where(ok, left_id * num_ids + right_id, pad)
    key, dmin = jax.lax.sort((key, jnp.where(ok, dist, big)), num_keys=2)
    first = (key != pad) & jnp.concatenate(
        [jnp.ones((1,), bool), key[1:] != key[:-1]]
    )
    count = jnp.sum(first.astype(jnp.int32))
    key, dmin = jax.lax.sort((jnp.where(first, key, pad), dmin), num_keys=1)
    found = key != pad
    return TrajPairs(
        jnp.where(found, key // num_ids, -1),
        jnp.where(found, key % num_ids, -1),
        jnp.where(found, dmin, big),
        count,
    )


def traj_pair_ids(left_index, right_index, left_oid, right_oid):
    """A pair list of batch indices (-1 padding) → the same list as
    trajectory ids, for ``traj_pair_dedup_kernel``: ``left_oid`` /
    ``right_oid`` are the two batches' id lanes. A gather a pair: for a
    join whose extraction could not carry the ids (``TJoinQuery.run``)."""
    def ids(index, oid):
        return jnp.where(index >= 0, oid[jnp.maximum(index, 0)], -1)

    return ids(left_index, left_oid), ids(right_index, right_oid)


class TrajAggregate(NamedTuple):
    """Per-(cell, objID) temporal lengths for the heatmap aggregate."""

    min_ts: jnp.ndarray  # (P,) per unique (cell, objID) pair
    max_ts: jnp.ndarray  # (P,)


def traj_cell_spans_kernel(
    ts: jnp.ndarray,
    pair_id: jnp.ndarray,
    valid: jnp.ndarray,
    num_pairs: int,
    axis_name=None,
) -> TrajAggregate:
    """Min/max timestamp per dense (cell, objID) pair id.

    The batched form of TAggregateQuery's MapState min/max tracking
    (TAggregateQuery.java:150-250): pair ids are host-interned
    (np.unique over cell*U+oid), the kernel reduces timestamps. With
    ``axis_name`` (inside shard_map) the per-shard reductions
    pmin/pmax-reduce across the mesh axis.
    """
    big = jnp.iinfo(ts.dtype).max
    small = jnp.iinfo(ts.dtype).min
    mn = jax.ops.segment_min(
        jnp.where(valid, ts, big), pair_id, num_segments=num_pairs
    )
    mx = jax.ops.segment_max(
        jnp.where(valid, ts, small), pair_id, num_segments=num_pairs
    )
    if axis_name is not None:
        mn = jax.lax.pmin(mn, axis_name)
        mx = jax.lax.pmax(mx, axis_name)
    return TrajAggregate(mn, mx)


def traj_hits_kernel(
    inside_any: jnp.ndarray,
    oid: jnp.ndarray,
    valid: jnp.ndarray,
    num_segments: int,
    axis_name=None,
) -> jnp.ndarray:
    """(U,) bool: does any point of each trajectory satisfy the predicate?

    Used by tRange: 'if any point of the trajectory is inside any query
    polygon, the whole (windowed) trajectory qualifies'
    (tRange/PointPolygonTRangeQuery.java:53-177). With ``axis_name``
    (inside shard_map) the per-shard segment reduction pmax-reduces across
    the mesh axis — a trajectory's points may land on any shard.
    """
    hit = (inside_any & valid).astype(jnp.int32)
    seg = jax.ops.segment_max(hit, oid, num_segments=num_segments)
    if axis_name is not None:
        seg = jax.lax.pmax(seg, axis_name)
    return seg > 0


def traj_range_hits_fused(
    xy: jnp.ndarray,
    valid: jnp.ndarray,
    oid: jnp.ndarray,
    query_verts: jnp.ndarray,
    query_edge_valid: jnp.ndarray,
    num_segments: int,
    axis_name=None,
) -> jnp.ndarray:
    """tRange's fused per-window program: batched containment against the
    query polygon set + per-trajectory any-hit reduction — single- and
    multi-chip paths share it (the mesh path all-reduces via the
    traj_hits_kernel axis hook)."""
    from spatialflink_tpu.ops.polygon import points_in_polygon

    inside = jax.vmap(
        lambda v, e: points_in_polygon(xy, v, e)
    )(query_verts, query_edge_valid)
    return traj_hits_kernel(
        jnp.any(inside, axis=0), oid, valid, num_segments,
        axis_name=axis_name,
    )
