"""Batched spatial-join kernels.

The reference joins two streams by replicating every query object to all of
its neighbor cells (a flatMap that multiplies the query stream by the
neighbor-cell count, JoinQuery.java:73-137), equi-joining on gridID over a
window, then distance-filtering (join/PointPointJoinQuery.java:124-183).

The TPU design inverts this: no replication. The query side is sorted by
cell once per window (a device sort); for each ordinary-side point we gather
the query points of its (2L+1)² neighbor cells through a CSR-style
searchsorted index and evaluate distances in one block — a grid-hash join
that rides the MXU instead of exploding the shuffle.

``cross_join_kernel`` is the RealTimeNaive path (constant-key cross join,
join/PointPointJoinQuery.java:186-243).
"""

from __future__ import annotations

from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp

from spatialflink_tpu.ops.distances import point_point_distance
from spatialflink_tpu.runtime import on_tpu


class JoinResult(NamedTuple):
    """For each left point: matching right-side indices within radius.

    ``pair_mask``: (N, K*cap) bool; ``right_index``: (N, K*cap) int32 index
    into the *original* right batch (-1 where masked); ``dist``: (N, K*cap);
    ``overflow``: () int32 — number of right points dropped because a cell
    exceeded ``cap`` (0 means the join is exact).
    """

    pair_mask: jnp.ndarray
    right_index: jnp.ndarray
    dist: jnp.ndarray
    overflow: jnp.ndarray


def sort_by_cell(cells: jnp.ndarray, n_total_cells: int):
    """Sort a batch by cell id; returns (sorted_cells, order).

    Invalid/out-of-grid entries must already carry cell id n_total_cells so
    they sort to the end.
    """
    order = jnp.argsort(cells)
    return cells[order], order.astype(jnp.int32)


def join_kernel(
    left_xy: jnp.ndarray,
    left_valid: jnp.ndarray,
    left_cell_xy_idx: jnp.ndarray,
    right_xy_sorted: jnp.ndarray,
    right_valid_sorted: jnp.ndarray,
    right_cells_sorted: jnp.ndarray,
    right_order: jnp.ndarray,
    neighbor_offsets: jnp.ndarray,
    grid_n: int,
    radius,
    cap: int,
) -> JoinResult:
    """Grid-hash join: left points vs cell-sorted right points.

    ``left_cell_xy_idx``: (N, 2) int32 (xi, yi) cell indices of left points;
    ``right_*_sorted``: right batch pre-sorted by flat cell id (see
    ``sort_by_cell``), ``right_order`` maps sorted position → original index;
    ``neighbor_offsets``: (K, 2) static (dx, dy) covering the candidate
    square (grid.neighbor_offsets — the same cells the reference's
    replication flatMap targets, JoinQuery.java:73-90); ``cap``: static max
    right points gathered per cell.
    """
    n = left_xy.shape[0]
    k = neighbor_offsets.shape[0]
    num_cells = grid_n * grid_n

    # Neighbor flat cell ids per left point: (N, K); invalid → num_cells+1
    # (past every real right cell, so searchsorted yields an empty span).
    nx = left_cell_xy_idx[:, 0:1] + neighbor_offsets[None, :, 0]
    ny = left_cell_xy_idx[:, 1:2] + neighbor_offsets[None, :, 1]
    in_grid = (nx >= 0) & (nx < grid_n) & (ny >= 0) & (ny < grid_n)
    ncell = jnp.where(in_grid, nx * grid_n + ny, num_cells + 1)

    start = jnp.searchsorted(right_cells_sorted, ncell.reshape(-1), side="left")
    end = jnp.searchsorted(right_cells_sorted, ncell.reshape(-1), side="right")
    start = start.reshape(n, k).astype(jnp.int32)
    end = end.reshape(n, k).astype(jnp.int32)
    span = end - start

    m = right_xy_sorted.shape[0]
    lane = jnp.arange(cap, dtype=jnp.int32)  # (cap,)
    pos = start[:, :, None] + lane[None, None, :]  # (N, K, cap)
    lane_ok = lane[None, None, :] < span[:, :, None]
    pos_c = jnp.clip(pos, 0, m - 1)

    # Gather x and y planes separately: a (N, K, cap, 2) gather would be
    # tiled to 128 lanes on its trailing dim-2 axis on TPU (64× HBM waste).
    cand_x = right_xy_sorted[:, 0][pos_c]  # (N, K, cap)
    cand_y = right_xy_sorted[:, 1][pos_c]
    cand_valid = right_valid_sorted[pos_c] & lane_ok
    dx = cand_x - left_xy[:, 0][:, None, None]
    dy = cand_y - left_xy[:, 1][:, None, None]
    d = jnp.sqrt(dx * dx + dy * dy)
    pair = cand_valid & left_valid[:, None, None] & (d <= radius)

    right_idx = jnp.where(cand_valid, right_order[pos_c], -1)
    # Only real (valid) left lanes claim overflow: padding lanes map to an
    # arbitrary cell (often the grid origin) and would otherwise report
    # phantom drops, breaking the overflow==0 exactness contract.
    overflow = jnp.sum(
        jnp.where(left_valid[:, None], jnp.maximum(span - cap, 0), 0)
    )
    return JoinResult(
        pair.reshape(n, k * cap),
        right_idx.reshape(n, k * cap),
        d.reshape(n, k * cap),
        overflow,
    )


class CompactJoinResult(NamedTuple):
    """Device-compacted join output: only the matching pairs cross the
    host boundary (the dense (N, K·cap) mask stays on device).

    ``left_index``/``right_index``: (max_pairs,) original-batch indices,
    -1 padding — or, from the bucketed programs given ``left_payload`` /
    ``right_payload``, the paired points' payload values (the trajectory
    join's ids); ``dist``: (max_pairs,); ``count``: () true number of pairs
    (> max_pairs means truncation); ``overflow``: () cell-capacity drops;
    ``peel_passes``: () vector passes the Pallas extraction took to lift
    the ``count`` hits out of their blocks (ops/pallas_join.py) — None
    from every program that has no such loop.
    """

    left_index: jnp.ndarray
    right_index: jnp.ndarray
    dist: jnp.ndarray
    count: jnp.ndarray
    overflow: jnp.ndarray
    peel_passes: Optional[jnp.ndarray] = None


def join_kernel_compact(
    left_xy: jnp.ndarray,
    left_valid: jnp.ndarray,
    left_cell_xy_idx: jnp.ndarray,
    right_xy_sorted: jnp.ndarray,
    right_valid_sorted: jnp.ndarray,
    right_cells_sorted: jnp.ndarray,
    right_order: jnp.ndarray,
    neighbor_offsets: jnp.ndarray,
    grid_n: int,
    radius,
    cap: int,
    max_pairs: int,
) -> CompactJoinResult:
    """Grid-hash join with on-device pair compaction (static ``max_pairs``).

    Fetching the dense pair mask costs O(N·K·cap) transfer per window;
    real joins are sparse, so compacting on device turns egress into
    O(max_pairs)."""
    res = join_kernel(
        left_xy, left_valid, left_cell_xy_idx,
        right_xy_sorted, right_valid_sorted, right_cells_sorted, right_order,
        neighbor_offsets, grid_n=grid_n, radius=radius, cap=cap,
    )
    n, kc = res.pair_mask.shape
    flat = res.pair_mask.reshape(-1)
    (hit_idx,) = jnp.nonzero(flat, size=max_pairs, fill_value=-1)
    found = hit_idx >= 0
    hit_c = jnp.maximum(hit_idx, 0)
    left_idx = jnp.where(found, (hit_c // kc).astype(jnp.int32), -1)
    right_idx = jnp.where(found, res.right_index.reshape(-1)[hit_c], -1)
    dist = jnp.where(found, res.dist.reshape(-1)[hit_c], jnp.inf)
    count = jnp.sum(flat.astype(jnp.int32))
    return CompactJoinResult(left_idx, right_idx, dist, count, res.overflow)


def join_window_compact(
    left_xy: jnp.ndarray,
    left_valid: jnp.ndarray,
    left_cell_xy_idx: jnp.ndarray,
    right_xy: jnp.ndarray,
    right_valid: jnp.ndarray,
    right_cells: jnp.ndarray,
    neighbor_offsets: jnp.ndarray,
    grid_n: int,
    radius,
    cap: int,
    max_pairs: int,
) -> CompactJoinResult:
    """One fused program for a whole join window: cell-sort the right side,
    grid-hash join, compact pairs — a single dispatch per window (separate
    eager sort/gather steps each cost a host round trip)."""
    order = jnp.argsort(right_cells).astype(jnp.int32)
    return join_kernel_compact(
        left_xy, left_valid, left_cell_xy_idx,
        right_xy[order], right_valid[order], right_cells[order], order,
        neighbor_offsets, grid_n=grid_n, radius=radius, cap=cap,
        max_pairs=max_pairs,
    )


def pallas_join_supported() -> bool:
    """True when the Pallas hit-extraction join can run compiled — TPU
    only. CPU uses the XLA bucketed kernel (faster there than the
    Pallas interpreter)."""
    return on_tpu()


#: Lanes of one row of the sorted lanes' (rows, 128) view: a TPU vector
#: register's lane count, so a gathered row is an aligned, contiguous 512 B.
_WINDOW_ROW = 128


def _as_rows(lane, rows: int, fill):
    """``lane`` padded with ``fill`` to ``rows`` rows of ``_WINDOW_ROW``."""
    pad = jnp.full(rows * _WINDOW_ROW - lane.shape[0], fill, lane.dtype)
    return jnp.concatenate([lane, pad]).reshape(rows, _WINDOW_ROW)


def _run_starts(sorted_keys, num_keys: int):
    """For every key k in 0..num_keys, the first position of the sorted
    lanes whose key is >= k (int32; ``num_keys + 1`` of them: run k is
    ``[starts[k], starts[k + 1])``).

    Two levels, no loop: the row a run starts in is the last whose first
    key is below k — a compare of the keys against every row's first key,
    counted — and the place in it the count of that one gathered row's
    keys below k. A binary search (``jnp.searchsorted``) is a 20-trip
    loop of ``num_keys``-element gathers, 1.34 ms a side at 10,001 keys
    and 2¹⁹ lanes against 0.05 ms for this (my chip run, PR 40); the
    compares, ``num_keys · n / 128``, stay far below the join's own
    ``num_keys · span² · cap²``."""
    rows = sorted_keys.shape[0] // _WINDOW_ROW + 1
    keyed = _as_rows(sorted_keys, rows, jnp.iinfo(sorted_keys.dtype).max)
    key = jnp.arange(num_keys + 1, dtype=sorted_keys.dtype)[:, None]
    row = jnp.sum(keyed[:, 0][None, :] < key, axis=1, dtype=jnp.int32)
    row = jnp.maximum(row - 1, 0)
    below = jnp.sum(keyed[row] < key, axis=1, dtype=jnp.int32)
    return row * _WINDOW_ROW + below


def _cell_windows(lane, start, cap: int):
    """``lane[start[c] : start[c] + cap]`` for every cell ``c`` — (cells,
    cap), zeros past the lane's end — with no index a lane.

    The lane is viewed as rows of 128; a cell's window lies in the
    ``ceil(cap / 128) + 1`` rows from ``start // 128`` on, which one
    aligned row gather a row fetches, and a seven-step barrel shifter
    (static rolls and selects on the bits of ``start % 128``) moves it to
    the front. The plain form — one ``lax.gather`` with
    ``slice_sizes=(cap,)`` at ``start`` — lowers on a v5e to a loop of one
    trip a cell: 7.85 ms a plane of 10,000 cells against 0.15 ms for this
    one (my chip run, PR 40)."""
    k = -(-cap // _WINDOW_ROW) + 1
    # start <= len(lane): every fetched row exists.
    rows = _as_rows(lane, lane.shape[0] // _WINDOW_ROW + k, 0)
    first = start // _WINDOW_ROW
    win = rows[first[:, None] + jnp.arange(k, dtype=first.dtype)[None, :]]
    win = win.reshape(start.shape[0], k * _WINDOW_ROW)
    shift = start % _WINDOW_ROW
    for b in range(_WINDOW_ROW.bit_length() - 1):
        step = ((shift >> b) & 1)[:, None] == 1
        win = jnp.where(step, jnp.roll(win, -(1 << b), axis=1), win)
    return win[:, :cap]


def bucketize_planes(xy, valid, cells, grid_n: int, cap: int, payload=None):
    """Lay a cell-assigned point batch out as dense (grid_n, grid_n, cap)
    bucket planes: x, y, payload (-1 = empty slot), plus the count of
    in-grid points dropped beyond ``cap`` (overflow).

    ``payload``: one int32 a lane, ``>= 0`` on every valid lane — what the
    join emits for a point it pairs (the trajectory join hands its id lanes
    in); None is each point's index in the batch (``jnp.arange(n)``).

    One stable sort by cell carries x, y and the payload along, so a
    cell's points are a contiguous run of the sorted lanes in index
    order — the slot order is deterministic — and a plane's row ``c`` is
    the ``cap``-lane window at the run's start (``_cell_windows``), masked
    past ``min(count[c], cap)``. The runs' starts are looked up a cell,
    not a point (``_run_starts``). No step indexes by point: a
    computed-index gather or scatter runs element by element on a v5e
    (7.5–8.2 ns an element: my chip runs, PR 27 and PR 39), and the
    permutation gathers and slot scatters this replaced were 22 of a
    side's 24.5 ms at 2¹⁹ lanes (my chip run, PR 40; 1.4 ms now).

    Invalid/out-of-grid points (cell >= grid_n²) sort behind every cell's
    run and are neither stored nor counted as overflow, matching the
    reference's key semantics (out-of-grid objects never join,
    HelperClass.assignGridCellID)."""
    num_cells = grid_n * grid_n
    n = xy.shape[0]
    cells = jnp.where(valid, cells, num_cells)
    sorted_cells, sx, sy, sload = jax.lax.sort(
        (cells, xy[:, 0], xy[:, 1],
         jnp.arange(n, dtype=jnp.int32) if payload is None
         else jnp.asarray(payload, jnp.int32)),
        num_keys=1, is_stable=True,
    )
    starts = _run_starts(sorted_cells, num_cells)
    start, count = starts[:-1], starts[1:] - starts[:-1]
    overflow = jnp.sum(jnp.maximum(count - cap, 0), dtype=jnp.int32)
    live = (
        jnp.arange(cap, dtype=jnp.int32)[None, :]
        < jnp.minimum(count, cap)[:, None]
    )
    shape = (grid_n, grid_n, cap)

    def plane(lane, empty):
        return jnp.where(
            live, _cell_windows(lane, start, cap), empty
        ).reshape(shape)

    return plane(sx, 0), plane(sy, 0), plane(sload, -1), overflow


def join_window_cells(left_xy, left_cells, right_xy, right_cells, origin,
                      inv_side, grid_n: int, refine: int):
    """Both sides' bucket cells on the key grid refined ``refine`` times a
    side — ``(grid_n · refine)²`` square buckets over the key grid's own
    square — for a window whose crowded key cells no capacity rung holds
    (``operators/join_query.py:JoinCapacity``). The exact join needs no
    more of its buckets than a side of at least the radius: every partner
    of a point then lies in the 3 × 3 buckets around its own.

    A bucket index is ``floor((x − origin) · inv_side)`` of the coordinates
    the extraction itself compares (centred float32 on the chip), clamped
    to the grid: monotone in the coordinate, so two points within the
    radius of each other lie at most one bucket apart on each axis as long
    as the bucket's side keeps a float32 margin over the radius (the
    contract's to see to). Which points join at all stays the key grid's
    word: a lane whose key cell is outside (``>= grid_n²``: out-of-grid or
    padding) gets the refined grid's outside cell, so points outside the
    deployment's grid never join, whatever grid the buckets are laid on."""
    nf = grid_n * refine
    origin = jnp.asarray(origin, left_xy.dtype)
    inv_side = jnp.asarray(inv_side, left_xy.dtype)

    def axis(column, k):
        index = jnp.floor((column - origin[k]) * inv_side)
        return jnp.clip(index, 0, nf - 1).astype(jnp.int32)

    def side(xy, cells):
        # a column at a time: an (n, 2) operand is tiled to 128 lanes
        return jnp.where(cells < grid_n * grid_n,
                         axis(xy[:, 0], 0) * nf + axis(xy[:, 1], 1), nf * nf)

    return side(left_xy, left_cells), side(right_xy, right_cells)


#: Pair-mask lanes one band of grid rows may hold in join_window_bucketed
#: (span² · rows · grid_n · capL · capR booleans, and the prefix sum
#: ``jnp.nonzero`` runs over them): 2²⁵ keeps a band at 32 MB of flags.
BUCKETED_BAND_LANES = 1 << 25


def join_window_bucketed(
    left_xy: jnp.ndarray,
    left_valid: jnp.ndarray,
    left_cells: jnp.ndarray,
    right_xy: jnp.ndarray,
    right_valid: jnp.ndarray,
    right_cells: jnp.ndarray,
    grid_n: int,
    layers: int,
    radius,
    cap_left: int,
    cap_right: int,
    max_pairs: int,
    band_rows: int | None = None,
    left_payload=None,
    right_payload=None,
) -> CompactJoinResult:
    """Dense-bucket grid join — the XLA formulation (off the TPU; on it
    the Pallas extraction of ops/pallas_join.py takes the same planes).

    TPU gathers with computed indices run on the scalar core (~10⁸
    elements/s), so the searchsorted+gather join costs seconds per
    million-point window. Here BOTH sides are laid out once as dense
    (grid_n, grid_n, cap) bucket planes (``bucketize_planes``: a sort and
    a window a cell, no per-point index) and every neighbor lookup is a
    static shift of the (padded) right planes — fully vectorized, no
    per-candidate gather. Per (2·layers+1)² shift: one (cells, capL, capR)
    distance block, compacted with ``jnp.nonzero(size=max_pairs)``.

    The pair mask is never whole: the grid is walked in bands of
    ``band_rows`` cell rows (default: as many as keep a band's
    span² · rows · grid_n · capL · capR flags within
    ``BUCKETED_BAND_LANES``), each band compacted on its own and laid
    behind the one before it. A grid that fits one band gives the pair
    order it always gave (shift-major).

    ``left_cells``/``right_cells``: flat cell ids (num_cells = out-of-grid).
    ``left_payload``/``right_payload``: what a pair carries of each point
    (``bucketize_planes``' payload: int32, ``>= 0``); None, its index.
    Overflow counts points beyond a side's bucket capacity (result is exact
    iff overflow == 0, same contract as join_kernel).
    """
    span = 2 * layers + 1
    f_dtype = left_xy.dtype
    capl, capr = cap_left, cap_right
    if band_rows is None:
        band_rows = BUCKETED_BAND_LANES // (span * span * grid_n * capl * capr)
    band_rows = max(1, min(int(band_rows), grid_n))  # sfcheck: ok=trace-hygiene -- static band shape, a Python int at trace time (never traced)
    n_bands = -(-grid_n // band_rows)

    lx, ly, lidx, l_over = bucketize_planes(
        left_xy, left_valid, left_cells, grid_n, cap_left, left_payload
    )
    rx, ry, ridx, r_over = bucketize_planes(
        right_xy, right_valid, right_cells, grid_n, cap_right, right_payload
    )
    # Left rows padded to whole bands, right planes by `layers` all round
    # (and by the same rows): every neighbour access is an in-bounds slice,
    # and a padding slot carries idx = -1, which never matches.
    extra = n_bands * band_rows - grid_n
    lpad = ((0, extra), (0, 0), (0, 0))
    rpad = ((layers, layers + extra), (layers, layers), (0, 0))
    lxp, lyp = jnp.pad(lx, lpad), jnp.pad(ly, lpad)
    lidxp = jnp.pad(lidx, lpad, constant_values=-1)
    rxp, ryp = jnp.pad(rx, rpad), jnp.pad(ry, rpad)
    ridxp = jnp.pad(ridx, rpad, constant_values=-1)
    cpad = grid_n + 2 * layers
    lflat = (lxp.reshape(-1), lyp.reshape(-1), lidxp.reshape(-1))
    rflat = (rxp.reshape(-1), ryp.reshape(-1), ridxp.reshape(-1))
    block = band_rows * grid_n * capl * capr
    r2 = radius * radius

    def band(r0):
        """Rows [r0, r0 + band_rows): (left, right, dist) of up to
        ``max_pairs`` hits, -1 / inf past them, and the band's true count."""
        lrows = lambda p, c: jax.lax.dynamic_slice(
            p, (r0, 0, 0), (band_rows, grid_n, c))
        blx, bly = lrows(lxp, capl), lrows(lyp, capl)
        blvalid = lrows(lidxp, capl) >= 0
        # One pair-mask plane per neighbor shift, stacked: (span², band
        # cells, capL, capR) bools. Distances are NOT materialized —
        # they're recomputed only at the compacted hit positions.
        masks = []
        for dx in range(-layers, layers + 1):
            for dy in range(-layers, layers + 1):
                rrows = lambda p: jax.lax.dynamic_slice(
                    p, (r0 + layers + dx, layers + dy, 0),
                    (band_rows, grid_n, capr))
                sx, sy, sidx = rrows(rxp), rrows(ryp), rrows(ridxp)
                ddx = blx[:, :, :, None] - sx[:, :, None, :]
                ddy = bly[:, :, :, None] - sy[:, :, None, :]
                pair = (
                    blvalid[:, :, :, None]
                    & (sidx[:, :, None, :] >= 0)
                    & (ddx * ddx + ddy * ddy <= r2)
                )
                masks.append(pair.reshape(-1))
        flat = jnp.concatenate(masks)  # (span² · band cells · capL · capR,)
        n_hit = jnp.sum(flat, dtype=jnp.int32)
        (hit,) = jnp.nonzero(flat, size=max_pairs, fill_value=-1)
        found = hit >= 0
        hit_c = jnp.maximum(hit, 0)
        shift_id = hit_c // block
        within = hit_c % block
        cell = within // (capl * capr)
        l_lane = (within // capr) % capl
        r_lane = within % capr
        # The shift mapped cell (i, j) → right cell (i+dx, j+dy); in the
        # padded right plane that is (i + layers + dx, j + layers + dy),
        # and shift_id counts dx + layers, dy + layers.
        ci = r0 + cell // grid_n
        cj = cell % grid_n
        ri = ci + shift_id // span
        rj = cj + shift_id % span
        l_slot = (ci * grid_n + cj) * capl + l_lane
        r_slot = (ri * cpad + rj) * capr + r_lane
        # Recompute distances at the (≤ max_pairs) hits only.
        ddx = lflat[0][l_slot] - rflat[0][r_slot]
        ddy = lflat[1][l_slot] - rflat[1][r_slot]
        return (
            jnp.where(found, lflat[2][l_slot], -1),
            jnp.where(found, rflat[2][r_slot], -1),
            jnp.where(found, jnp.sqrt(ddx * ddx + ddy * ddy),
                      jnp.asarray(jnp.inf, f_dtype)),
            n_hit,
        )

    if n_bands == 1:
        left_out, right_out, dist_out, count = band(0)
        return CompactJoinResult(
            left_out, right_out, dist_out, count, l_over + r_over)

    def lay(b, carry):
        # Each band's hits go behind the ones before: its padding is
        # overwritten by the next band, and the buffers are two budgets
        # long so that an update never has to be clamped back.
        outl, outr, outd, count = carry
        bl, br, bd, n_hit = band(b * band_rows)
        at = jnp.minimum(count, max_pairs)
        return (
            jax.lax.dynamic_update_slice(outl, bl, (at,)),
            jax.lax.dynamic_update_slice(outr, br, (at,)),
            jax.lax.dynamic_update_slice(outd, bd, (at,)),
            count + n_hit,
        )

    outl, outr, outd, count = jax.lax.fori_loop(
        0, n_bands, lay,
        (
            jnp.full(2 * max_pairs, -1, jnp.int32),
            jnp.full(2 * max_pairs, -1, jnp.int32),
            jnp.full(2 * max_pairs, jnp.inf, f_dtype),
            jnp.zeros((), jnp.int32),
        ),
    )
    return CompactJoinResult(
        outl[:max_pairs], outr[:max_pairs], outd[:max_pairs], count,
        l_over + r_over,
    )


def head_pairs(left_index, right_index, dist, bucket: int):
    """The first ``bucket`` slots of a CompactJoinResult's three pair
    arrays — sliced on the device, so that the host fetches a padding
    bucket of the count it has just read and not the whole budget."""
    return left_index[:bucket], right_index[:bucket], dist[:bucket]


def point_geometry_join_kernel(
    pxy: jnp.ndarray,
    pvalid: jnp.ndarray,
    gverts: jnp.ndarray,
    gev: jnp.ndarray,
    gvalid: jnp.ndarray,
    radius,
    polygonal: bool = True,
):
    """Point batch ⋈ geometry batch: (M, N) mask + distances.

    JTS semantics: distance 0 for points inside polygonal geometries. The
    batched form of join/PointPolygonJoinQuery's window loop. Note the grid
    prune of the reference is purely a shuffle optimization — the distance
    filter decides membership, so the dense masked evaluation returns the
    identical pair set.
    """
    from spatialflink_tpu.ops.polygon import points_in_polygon
    from spatialflink_tpu.ops.distances import point_polyline_distance

    def one_geom(verts, ev):
        d = point_polyline_distance(pxy, verts, ev)
        if polygonal:
            inside = points_in_polygon(pxy, verts, ev)
            d = jnp.where(inside, jnp.zeros((), d.dtype), d)
        return d

    d = jax.vmap(one_geom)(gverts, gev)  # (M, N)
    mask = (d <= radius) & pvalid[None, :] & gvalid[:, None]
    return mask, d


def geometry_geometry_join_kernel(
    averts: jnp.ndarray,
    aev: jnp.ndarray,
    avalid: jnp.ndarray,
    bverts: jnp.ndarray,
    bev: jnp.ndarray,
    bvalid: jnp.ndarray,
    radius,
    a_polygonal: bool = True,
    b_polygonal: bool = True,
):
    """Geometry ⋈ geometry: (L, R) mask + JTS-compatible distances
    (overlap/containment → 0 via geometry_pair_distance)."""
    from spatialflink_tpu.ops.range import geometry_pair_distance

    def pair(av, ae):
        return jax.vmap(
            lambda bv, be: geometry_pair_distance(
                av, ae, bv, be, a_polygonal, b_polygonal
            )
        )(bverts, bev)

    d = jax.vmap(pair)(averts, aev)  # (L, R)
    mask = (d <= radius) & avalid[:, None] & bvalid[None, :]
    return mask, d


def _onehot_select_preferred() -> bool:
    from spatialflink_tpu.ops.select import onehot_select_preferred

    return onehot_select_preferred()


def _block_candidates(block_bbox, gbbox, gvalid, radius, cand: int):
    """Block-level bbox pruning + per-block candidate compaction.

    ``block_bbox``: (NB, 4) minx,miny,maxx,maxy per block (±inf when the
    block is empty); ``gbbox``: (M, 4) per-geometry bboxes. A geometry is
    a candidate for a block iff the bboxes overlap after expanding the
    geometry's by ``radius``. Returns (gids (NB, cand) int32, cvalid
    (NB, cand) bool, overflow () int32) — overflow counts candidates
    dropped beyond ``cand`` (the caller's retry contract: exact iff 0).
    """
    gx0 = gbbox[:, 0] - radius
    gy0 = gbbox[:, 1] - radius
    gx1 = gbbox[:, 2] + radius
    gy1 = gbbox[:, 3] + radius
    ov = (
        (block_bbox[:, 0:1] <= gx1[None, :])
        & (block_bbox[:, 2:3] >= gx0[None, :])
        & (block_bbox[:, 1:2] <= gy1[None, :])
        & (block_bbox[:, 3:4] >= gy0[None, :])
        & gvalid[None, :]
    )  # (NB, M)
    # First-cand selection per row, ascending geometry id — strategy per
    # backend (identical results; see _onehot_select_preferred).
    m = ov.shape[1]
    if _onehot_select_preferred():
        from spatialflink_tpu.ops.select import first_k_onehot

        hit, ncand, overflow = first_k_onehot(ov, cand)  # (NB, M, cand)
        gids = jnp.sum(
            hit * jnp.arange(m, dtype=jnp.int32)[None, :, None], axis=1,
            dtype=jnp.int32,
        )  # (NB, cand)
    else:
        ncand = jnp.sum(ov.astype(jnp.int32), axis=1)
        overflow = jnp.sum(jnp.maximum(ncand - cand, 0))
        # top_k over the 0/1 mask: ones first, ties by ascending index —
        # the indices ARE the candidate geometry ids.
        _vals, gids = jax.lax.top_k(ov.astype(jnp.int32), cand)
        gids = gids.astype(jnp.int32)
    c_ids = jnp.arange(cand, dtype=jnp.int32)
    cvalid = c_ids[None, :] < jnp.minimum(ncand, cand)[:, None]
    return gids, cvalid, overflow


def _masked_block_bbox(x, y, valid):
    """(NB, B) coords + validity → (NB, 4) bbox over valid lanes."""
    big = jnp.asarray(jnp.finfo(x.dtype).max, x.dtype)
    return jnp.stack([
        jnp.min(jnp.where(valid, x, big), axis=1),
        jnp.min(jnp.where(valid, y, big), axis=1),
        jnp.max(jnp.where(valid, x, -big), axis=1),
        jnp.max(jnp.where(valid, y, -big), axis=1),
    ], axis=1)


class PrunedJoinPairs(NamedTuple):
    """Output of the pruned geometry joins: compacted pairs + the TWO
    exactness counters of the retry contract — ``cand_overflow`` (a tile
    had more than ``cand`` bbox-overlapping geometries; grow ``cand``)
    and ``pair_overflow`` (a single left item matched more than
    ``pair_cap`` geometries; grow ``pair_cap``). Exact iff both are 0.
    """

    left_index: jnp.ndarray
    right_index: jnp.ndarray
    dist: jnp.ndarray
    count: jnp.ndarray
    cand_overflow: jnp.ndarray
    pair_overflow: jnp.ndarray


def _compact_pairs(mask, dmat, borig, gids, pair_cap: int, max_pairs: int):
    """(NB, cand, B) mask/dists → flat pairs via PER-ITEM selection.

    A single jnp.nonzero over the full NB·cand·B domain costs ~9 ns/lane
    on TPU (~86 ms at 131k-point windows) — the same pathology the
    Pallas join avoids. Instead: a prefix-sum one-hot select keeps up to
    ``pair_cap`` matches per left item (domain NB·cand·B, but pure VPU
    compare/select — no serialization), then the final nonzero runs over
    only N·pair_cap lanes (cand/pair_cap-fold smaller). Items matching
    more than ``pair_cap`` geometries report pair_overflow (retry).
    Returns (left, right, dist, count, pair_overflow).
    """
    b = mask.shape[2]
    # Per-item selection along the candidate axis (moved last for the
    # shared selection primitives).
    mask_t = jnp.moveaxis(mask, 1, -1)  # (NB, B, cand)
    dmat_t = jnp.moveaxis(dmat, 1, -1)  # (NB, B, cand)
    slots = jnp.arange(pair_cap, dtype=jnp.int32)
    if _onehot_select_preferred():
        from spatialflink_tpu.ops.select import first_k_onehot

        hit, per_item, pair_overflow = first_k_onehot(mask_t, pair_cap)
        # hit: (NB, B, cand, pair_cap); one-hot sums select exactly one
        # term — bit-exact for the distance.
        gsel = jnp.sum(
            hit * gids[:, None, :, None], axis=2, dtype=jnp.int32
        )  # (NB, B, pair_cap)
        dsel = jnp.sum(
            jnp.where(hit, dmat_t[:, :, :, None],
                      jnp.zeros((), dmat.dtype)),
            axis=2,
        )
    else:
        # CPU & friends: top_k over the 0/1 mask (the one-hot tensor is
        # measurably slower than the vectorized sort on XLA:CPU — same
        # per-backend gate as ops/knn.py's compact digest; identical
        # selection, ties broken by ascending candidate slot).
        per_item = jnp.sum(mask_t.astype(jnp.int32), axis=-1)
        pair_overflow = jnp.sum(jnp.maximum(per_item - pair_cap, 0))
        _vals, csel = jax.lax.top_k(mask_t.astype(jnp.int8), pair_cap)
        gsel = jnp.take_along_axis(
            jnp.broadcast_to(gids[:, None, :], mask_t.shape), csel, axis=-1
        ).astype(jnp.int32)
        dsel = jnp.take_along_axis(dmat_t, csel, axis=-1)
    svalid = (
        slots[None, None, :] < jnp.minimum(per_item, pair_cap)[:, :, None]
    )  # (NB, B, pair_cap)

    flat = svalid.reshape(-1)
    count = jnp.sum(per_item, dtype=jnp.int32)
    (hit_i,) = jnp.nonzero(flat, size=max_pairs, fill_value=-1)
    found = hit_i >= 0
    h = jnp.maximum(hit_i, 0)
    bi = h // (b * pair_cap)
    li = (h // pair_cap) % b
    left = jnp.where(found, borig[bi, li], -1)
    right = jnp.where(found, gsel.reshape(-1)[h], -1)
    dist = jnp.where(found, dsel.reshape(-1)[h],
                     jnp.asarray(jnp.inf, dmat.dtype))
    return left, right, dist, count, pair_overflow


def point_geometry_join_pruned_kernel(
    pxy: jnp.ndarray,
    pvalid: jnp.ndarray,
    gverts: jnp.ndarray,
    gev: jnp.ndarray,
    gvalid: jnp.ndarray,
    gbbox: jnp.ndarray,
    radius,
    polygonal: bool,
    block: int,
    cand: int,
    max_pairs: int,
    pair_cap: int = 8,
    approx: bool = False,
) -> PrunedJoinPairs:
    """Grid-pruned point ⋈ geometry join, device-extracted.

    The dense kernel (point_geometry_join_kernel) evaluates every
    (point, geometry) V-vertex distance — O(N·M·V). This is the device-
    side form of the reference's gridIDsSet replication
    (join/JoinQuery.java:73-137) re-designed for TPU:

      1. sort points by grid cell (spatial locality — one device argsort),
      2. split into ``block``-point tiles; per tile, a 4-compare bbox test
         against every geometry's radius-expanded bbox (O(N/B · M), cheap),
      3. compact ≤ ``cand`` candidate geometries per tile (lax.top_k),
      4. exact V-vertex distances tile × candidates — O(N·cand·V), a
         M/cand-fold cut,
      5. per-item selection (≤ ``pair_cap`` matches per point) + one
         small jnp.nonzero so only pairs cross the host boundary.

    Exact iff BOTH overflow counters are 0 (PrunedJoinPairs: grow
    ``cand`` on cand_overflow — at cand == M the prune is a no-op — and
    ``pair_cap`` on pair_overflow — at pair_cap == cand a point cannot
    exceed it). Pair set identical to the dense kernel (parity test
    tests/test_join_pruned.py); JTS semantics kept (inside polygonal → 0).

    The caller orders the points for spatial locality HOST-side (numpy
    argsort by cell, ~1 ms at 131k and overlapped with device work — a
    device argsort measured 13 ms on v5e, 2.5× the rest of this kernel);
    ``left_index`` refers to input positions (map back through the host
    order). Locality only affects pruning EFFICIENCY, never correctness.
    """
    from spatialflink_tpu.ops.distances import point_polyline_distance
    from spatialflink_tpu.ops.polygon import points_in_polygon

    # Static clamps: cand cannot exceed the geometry count, pair_cap
    # cannot exceed cand (an item's matches come from its tile's cand
    # list) — unclamped values would crash only on the top_k backends.
    # Clamp keys on gbbox so approximate callers may pass dummy verts.
    cand = min(cand, gbbox.shape[0])
    pair_cap = min(pair_cap, cand)
    n = pxy.shape[0]
    nb = -(-n // block)
    npad = nb * block
    pad = npad - n
    order = jnp.arange(n, dtype=jnp.int32)
    sx = jnp.pad(pxy, ((0, pad), (0, 0)))
    sv = jnp.pad(pvalid, (0, pad))
    so = jnp.pad(order, (0, pad), constant_values=-1)
    bx = sx.reshape(nb, block, 2)
    bvalid = sv.reshape(nb, block)
    borig = so.reshape(nb, block)

    bbox = _masked_block_bbox(bx[:, :, 0], bx[:, :, 1], bvalid)
    gids, cvalid, overflow = _block_candidates(
        bbox, gbbox, gvalid, radius, cand
    )

    if approx:
        # Approximate mode: per-pair distance = point → candidate's
        # BOUNDING BOX (ops/distances.py:bbox_point_min_distance), the
        # device form of the reference's approximateQuery branches
        # (join/PolygonPointJoinQuery.java, getPoint*BBoxMinEuclidean-
        # Distance). The operator also routes the point-ordinary
        # "emit all grid candidates" semantics here by passing
        # CELL-INDEX coordinates + layer-expanded cell boxes with
        # radius 0 (see join_query._PointGeometryJoinQuery).
        from spatialflink_tpu.ops.distances import bbox_point_min_distance

        cgb = gbbox[gids]  # (NB, cand, 4)
        dmat = bbox_point_min_distance(
            bx[:, None, :, :], cgb[:, :, None, :]
        )  # (NB, cand, block)
    else:
        cgv = gverts[gids]  # (NB, cand, V, 2)
        cge = gev[gids]  # (NB, cand, V-1)

        def one_geom(bxy, verts, ev):
            d = point_polyline_distance(bxy, verts, ev)
            if polygonal:
                inside = points_in_polygon(bxy, verts, ev)
                d = jnp.where(inside, jnp.zeros((), d.dtype), d)
            return d

        dmat = jax.vmap(
            lambda bxy, gv, ge: jax.vmap(
                lambda v, e: one_geom(bxy, v, e)
            )(gv, ge)
        )(bx, cgv, cge)  # (NB, cand, block)

    mask = (
        (dmat <= radius)
        & bvalid[:, None, :]
        & cvalid[:, :, None]
    )
    left, right, dist, count, pair_over = _compact_pairs(
        mask, dmat, borig, gids, pair_cap, max_pairs
    )
    return PrunedJoinPairs(left, right, dist, count, overflow, pair_over)


def geometry_geometry_join_pruned_kernel(
    averts: jnp.ndarray,
    aev: jnp.ndarray,
    avalid: jnp.ndarray,
    abbox: jnp.ndarray,
    bverts: jnp.ndarray,
    bev: jnp.ndarray,
    bvalid: jnp.ndarray,
    bbbox: jnp.ndarray,
    radius,
    a_polygonal: bool,
    b_polygonal: bool,
    block: int,
    cand: int,
    max_pairs: int,
    pair_cap: int = 8,
    approx: bool = False,
) -> PrunedJoinPairs:
    """Grid-pruned geometry ⋈ geometry join, device-extracted.

    Same tile/candidate scheme as the point version: the caller orders
    the left side for locality HOST-side (the operator sorts by quantized
    bbox center — join_query._GeometryGeometryJoinQuery._window_pairs,
    the single home of that key logic); tile bboxes are unioned over
    member bboxes. ``left_index`` refers to input positions. Exact iff
    BOTH ``cand_overflow`` AND ``pair_overflow`` are 0 (PrunedJoinPairs
    retry contract — grow ``cand`` / ``pair_cap`` respectively); parity
    with geometry_geometry_join_kernel incl. overlap→0 distances
    (tests/test_join_pruned.py).
    """
    from spatialflink_tpu.ops.range import geometry_pair_distance

    cand = min(cand, bbbox.shape[0])  # see point kernel's clamps
    pair_cap = min(pair_cap, cand)
    la = averts.shape[0]
    nb = -(-la // block)
    npad = nb * block
    order = jnp.arange(la, dtype=jnp.int32)
    pad = npad - la

    s_bbox = jnp.pad(abbox, ((0, pad), (0, 0)))
    sv = jnp.pad(avalid, (0, pad))
    so = jnp.pad(order, (0, pad), constant_values=-1)
    t_bbox = s_bbox.reshape(nb, block, 4)
    bval = sv.reshape(nb, block)
    borig = so.reshape(nb, block)

    big = jnp.asarray(jnp.finfo(t_bbox.dtype).max, t_bbox.dtype)
    tile_bbox = jnp.stack([
        jnp.min(jnp.where(bval, t_bbox[:, :, 0], big), axis=1),
        jnp.min(jnp.where(bval, t_bbox[:, :, 1], big), axis=1),
        jnp.max(jnp.where(bval, t_bbox[:, :, 2], -big), axis=1),
        jnp.max(jnp.where(bval, t_bbox[:, :, 3], -big), axis=1),
    ], axis=1)
    gids, cvalid, overflow = _block_candidates(
        tile_bbox, bbbox, bvalid, radius, cand
    )

    if approx:
        # Approximate mode: per-pair distance = bbox ↔ bbox min distance
        # (the reference's getBBoxBBoxMinEuclideanDistance branches in
        # every geometry-geometry join, e.g.
        # join/LineStringLineStringJoinQuery.java:173-180).
        from spatialflink_tpu.ops.distances import bbox_bbox_min_distance

        cbb = bbbox[gids]  # (NB, cand, 4)
        dmat = bbox_bbox_min_distance(
            t_bbox[:, None, :, :], cbb[:, :, None, :]
        )  # (NB, cand, block)
    else:
        sav = jnp.pad(averts, ((0, pad), (0, 0), (0, 0)))
        sae = jnp.pad(aev, ((0, pad), (0, 0)))
        tav = sav.reshape(nb, block, averts.shape[1], 2)
        tae = sae.reshape(nb, block, aev.shape[1])
        cbv = bverts[gids]  # (NB, cand, Vb, 2)
        cbe = bev[gids]

        def pair_d(av, ae, bv, be):
            return geometry_pair_distance(av, ae, bv, be, a_polygonal,
                                          b_polygonal)

        # (NB, cand, block): for each tile, candidate × member distances.
        dmat = jax.vmap(
            lambda avs, aes, bvs, bes: jax.vmap(
                lambda bv, be: jax.vmap(
                    lambda av, ae: pair_d(av, ae, bv, be)
                )(avs, aes)
            )(bvs, bes)
        )(tav, tae, cbv, cbe)

    mask = (
        (dmat <= radius)
        & bval[:, None, :]
        & cvalid[:, :, None]
    )
    left, right, dist, count, pair_over = _compact_pairs(
        mask, dmat, borig, gids, pair_cap, max_pairs
    )
    return PrunedJoinPairs(left, right, dist, count, overflow, pair_over)


def cross_join_kernel(
    left_xy: jnp.ndarray,
    left_valid: jnp.ndarray,
    right_xy: jnp.ndarray,
    right_valid: jnp.ndarray,
    radius,
) -> JoinResult:
    """Naive all-pairs join (the reference's RealTimeNaive mode,
    join/PointPointJoinQuery.java:186-243). (N, M) distance matrix, masked."""
    d = point_point_distance(left_xy[:, None, :], right_xy[None, :, :])
    pair = left_valid[:, None] & right_valid[None, :] & (d <= radius)
    m = right_xy.shape[0]
    right_idx = jnp.broadcast_to(jnp.arange(m, dtype=jnp.int32)[None, :], d.shape)
    return JoinResult(pair, right_idx, d, jnp.zeros((), jnp.int32))
