"""Range/kNN/join kernel parity vs brute-force numpy re-derivations of the
reference's window-loop semantics (guaranteed emit, candidate distance check,
per-objID min-dist dedup, grid-hash join)."""

import jax.numpy as jnp
import numpy as np
import pytest

from spatialflink_tpu.grid import UniformGrid
from spatialflink_tpu.models.batch import PointBatch
from spatialflink_tpu.ops.cells import gather_cell_flags
from spatialflink_tpu.ops.join import cross_join_kernel, join_kernel, sort_by_cell
from spatialflink_tpu.ops.knn import knn_kernel
from spatialflink_tpu.ops.polygon import pack_rings
from spatialflink_tpu.ops.range import (
    range_query_kernel,
    range_query_polygons_kernel,
)

GRID = dict(min_x=0.0, max_x=10.0, min_y=0.0, max_y=10.0)


def make_batch(rng, n=777, bucket=1024):
    xy = rng.uniform(0, 10, size=(n, 2))
    ts = rng.integers(0, 10_000, n)
    oid = rng.integers(0, 60, n).astype(np.int32)
    return PointBatch.from_arrays(xy, ts, oid, bucket=bucket)


def brute_range(grid, flags, batch, q, r):
    """Reference semantics: guaranteed → emit; candidate → min dist ≤ r."""
    keep = np.zeros(batch.capacity, bool)
    for i in range(batch.capacity):
        if not batch.valid[i]:
            continue
        c = int(batch.cell[i])
        f = int(flags[c])
        if f == 2:
            keep[i] = True
        elif f == 1:
            d = np.min(np.linalg.norm(q - batch.xy[i], axis=1))
            keep[i] = d <= r
    return keep


@pytest.mark.parametrize("radius", [0.3, 1.5, 4.0])
def test_range_kernel_matches_brute(rng, radius):
    grid = UniformGrid(20, **GRID)
    batch = make_batch(rng).with_cells(grid)
    q = np.array([[5.0, 5.0], [2.0, 8.0]])
    flags = grid.neighbor_flags(radius, [grid.flat_cell(*p) for p in q])
    pflags = np.asarray(gather_cell_flags(jnp.asarray(batch.cell), jnp.asarray(flags)))
    keep, dist = range_query_kernel(
        jnp.asarray(batch.xy), jnp.asarray(batch.valid), jnp.asarray(pflags),
        jnp.asarray(q), radius,
    )
    np.testing.assert_array_equal(np.asarray(keep), brute_range(grid, flags, batch, q, radius))


def test_range_approximate_emits_candidates_unchecked(rng):
    grid = UniformGrid(20, **GRID)
    batch = make_batch(rng).with_cells(grid)
    q = np.array([[5.0, 5.0]])
    r = 1.0
    flags = grid.neighbor_flags(r, [grid.flat_cell(5.0, 5.0)])
    pflags = np.asarray(gather_cell_flags(jnp.asarray(batch.cell), jnp.asarray(flags)))
    keep, _ = range_query_kernel(
        jnp.asarray(batch.xy), jnp.asarray(batch.valid), jnp.asarray(pflags),
        jnp.asarray(q), r, approximate=True,
    )
    expect = batch.valid & (pflags > 0)
    np.testing.assert_array_equal(np.asarray(keep), expect)


def test_range_polygon_query(rng):
    grid = UniformGrid(20, **GRID)
    batch = make_batch(rng).with_cells(grid)
    ring = np.array([[4.0, 4.0], [6.0, 4.0], [6.0, 6.0], [4.0, 6.0]])
    verts, ev = pack_rings([ring], pad_to=8)
    r = 0.5
    cells = grid.bbox_cells(4.0, 4.0, 6.0, 6.0)
    flags = grid.neighbor_flags(r, cells)
    pflags = np.asarray(gather_cell_flags(jnp.asarray(batch.cell), jnp.asarray(flags)))
    keep, dist = range_query_polygons_kernel(
        jnp.asarray(batch.xy), jnp.asarray(batch.valid), jnp.asarray(pflags),
        jnp.asarray(verts)[None], jnp.asarray(ev)[None], r,
    )
    keep = np.asarray(keep)
    # Brute force: inside or within r of boundary, for candidate cells;
    # guaranteed cells emitted regardless.
    for i in range(batch.capacity):
        if not batch.valid[i]:
            assert not keep[i]
            continue
        f = int(flags[int(batch.cell[i])])
        x, y = batch.xy[i]
        inside = 4 <= x <= 6 and 4 <= y <= 6
        edge_d = min(
            max(4 - x, 0, x - 6) if 4 <= y <= 6 else np.inf,
            max(4 - y, 0, y - 6) if 4 <= x <= 6 else np.inf,
            min(np.hypot(x - cx, y - cy) for cx in (4, 6) for cy in (4, 6)),
        )
        d = 0.0 if inside else edge_d
        expect = f == 2 or (f == 1 and d <= r)
        assert keep[i] == expect, (i, f, x, y, d)


def brute_knn(batch, flags_per_point, q, r, k):
    best = {}
    for i in range(batch.capacity):
        if not batch.valid[i] or flags_per_point[i] == 0:
            continue
        d = np.linalg.norm(batch.xy[i] - q)
        if d <= r:
            o = int(batch.oid[i])
            if o not in best or d < best[o]:
                best[o] = d
    return sorted(best.items(), key=lambda kv: kv[1])[:k]


@pytest.mark.parametrize("k", [1, 5, 50])
def test_knn_kernel_matches_brute(rng, k):
    grid = UniformGrid(20, **GRID)
    batch = make_batch(rng).with_cells(grid)
    q = np.array([5.0, 5.0])
    r = 3.0
    flags = grid.neighbor_flags(r, [grid.flat_cell(*q)])
    pflags = np.asarray(gather_cell_flags(jnp.asarray(batch.cell), jnp.asarray(flags)))
    res = knn_kernel(
        jnp.asarray(batch.xy), jnp.asarray(batch.valid), jnp.asarray(pflags),
        jnp.asarray(batch.oid), jnp.asarray(q), r, k, num_segments=64,
    )
    expect = brute_knn(batch, pflags, q, r, k)
    nv = int(res.num_valid)
    assert nv == len(expect)
    got = [(int(res.segment[i]), float(res.dist[i])) for i in range(nv)]
    for (go, gd), (eo, ed) in zip(got, expect):
        assert gd == pytest.approx(ed, rel=1e-12)
        assert go == eo
    # Padding slots marked -1
    assert all(int(res.segment[i]) == -1 for i in range(nv, k))
    # Representative index points at a point of that object achieving min dist
    for i in range(nv):
        idx, seg = int(res.index[i]), int(res.segment[i])
        assert int(batch.oid[idx]) == seg
        assert np.linalg.norm(batch.xy[idx] - q) == pytest.approx(res.dist[i], rel=1e-12)


def test_knn_empty_result(rng):
    grid = UniformGrid(20, **GRID)
    batch = make_batch(rng, n=10).with_cells(grid)
    q = np.array([500.0, 500.0])  # far outside; no cells flagged
    flags = grid.neighbor_flags(0.5, [grid.flat_cell(*q)])
    pflags = np.asarray(gather_cell_flags(jnp.asarray(batch.cell), jnp.asarray(flags)))
    res = knn_kernel(
        jnp.asarray(batch.xy), jnp.asarray(batch.valid), jnp.asarray(pflags),
        jnp.asarray(batch.oid), jnp.asarray(q), 0.5, 5, num_segments=64,
    )
    assert int(res.num_valid) == 0
    assert all(int(s) == -1 for s in np.asarray(res.segment))


def brute_join(a, b, r):
    pairs = set()
    for i in range(len(a.xy)):
        if not a.valid[i]:
            continue
        for j in range(len(b.xy)):
            if not b.valid[j]:
                continue
            if np.linalg.norm(a.xy[i] - b.xy[j]) <= r:
                pairs.add((i, j))
    return pairs


def test_grid_hash_join_matches_brute(rng):
    grid = UniformGrid(20, **GRID)
    r = 0.8
    a = make_batch(rng, n=300, bucket=512).with_cells(grid)
    b = make_batch(rng, n=200, bucket=256).with_cells(grid)
    cells_sorted, order = sort_by_cell(jnp.asarray(b.cell), grid.num_cells)
    bxy_sorted = jnp.asarray(b.xy)[order]
    bvalid_sorted = jnp.asarray(b.valid)[order]
    # Left cell (xi, yi) indices
    xi = np.floor((a.xy[:, 0] - grid.min_x) / grid.cell_length).astype(np.int32)
    yi = np.floor((a.xy[:, 1] - grid.min_y) / grid.cell_length).astype(np.int32)
    res = join_kernel(
        jnp.asarray(a.xy), jnp.asarray(a.valid), jnp.asarray(np.stack([xi, yi], 1)),
        bxy_sorted, bvalid_sorted, cells_sorted, order,
        jnp.asarray(grid.neighbor_offsets(r)), grid.n, r, cap=32,
    )
    assert int(res.overflow) == 0
    got = set()
    pm = np.asarray(res.pair_mask)
    ri = np.asarray(res.right_index)
    for i in range(a.capacity):
        for slot in np.nonzero(pm[i])[0]:
            got.add((i, int(ri[i, slot])))
    assert got == brute_join(a, b, r)


def test_join_overflow_counted(rng):
    grid = UniformGrid(20, **GRID)
    r = 0.5
    # 100 points in the same tiny spot → one cell with >cap points
    xy = np.full((100, 2), 5.05) + rng.normal(0, 0.001, (100, 2))
    b = PointBatch.from_arrays(xy, bucket=128).with_cells(grid)
    a = PointBatch.from_arrays(np.array([[5.05, 5.05]]), bucket=256).with_cells(grid)
    cells_sorted, order = sort_by_cell(jnp.asarray(b.cell), grid.num_cells)
    xi = np.floor((a.xy[:, 0] - grid.min_x) / grid.cell_length).astype(np.int32)
    yi = np.floor((a.xy[:, 1] - grid.min_y) / grid.cell_length).astype(np.int32)
    res = join_kernel(
        jnp.asarray(a.xy), jnp.asarray(a.valid), jnp.asarray(np.stack([xi, yi], 1)),
        jnp.asarray(b.xy)[order], jnp.asarray(b.valid)[order], cells_sorted, order,
        jnp.asarray(grid.neighbor_offsets(r)), grid.n, r, cap=16,
    )
    assert int(res.overflow) > 0


def test_join_overflow_ignores_padding_lanes(rng):
    """Padding (invalid) left lanes map to cell (0,0) — a real grid cell —
    and must not claim overflow (ADVICE round-1 finding: the overflow==0
    exactness contract has to be tight)."""
    grid = UniformGrid(20, **GRID)
    r = 0.5
    # Crowd the grid-origin cell on the right side beyond cap.
    bxy = np.full((80, 2), 0.05) + rng.normal(0, 0.001, (80, 2))
    b = PointBatch.from_arrays(bxy, bucket=128).with_cells(grid)
    # One real left point far away; batch padded to 256 lanes whose cell
    # indices are (0, 0) → the origin cell's crowd is in their span.
    a = PointBatch.from_arrays(np.array([[9.0, 9.0]]), bucket=256).with_cells(grid)
    cells_sorted, order = sort_by_cell(jnp.asarray(b.cell), grid.num_cells)
    xi = np.floor((a.xy[:, 0] - grid.min_x) / grid.cell_length).astype(np.int32)
    yi = np.floor((a.xy[:, 1] - grid.min_y) / grid.cell_length).astype(np.int32)
    res = join_kernel(
        jnp.asarray(a.xy), jnp.asarray(a.valid), jnp.asarray(np.stack([xi, yi], 1)),
        jnp.asarray(b.xy)[order], jnp.asarray(b.valid)[order], cells_sorted, order,
        jnp.asarray(grid.neighbor_offsets(r)), grid.n, r, cap=16,
    )
    assert int(res.overflow) == 0


def test_cross_join_matches_brute(rng):
    r = 1.2
    a = make_batch(rng, n=50, bucket=64)
    b = make_batch(rng, n=40, bucket=64)
    res = cross_join_kernel(
        jnp.asarray(a.xy), jnp.asarray(a.valid), jnp.asarray(b.xy), jnp.asarray(b.valid), r
    )
    got = set()
    pm = np.asarray(res.pair_mask)
    for i in range(a.capacity):
        for j in np.nonzero(pm[i])[0]:
            got.add((i, int(j)))
    assert got == brute_join(a, b, r)


def test_any_cell_flagged_matches_per_object_loop(rng):
    """Vectorized prefix-sum rectangle test == per-object cell loop."""
    from spatialflink_tpu.models.batch import GeometryBatch
    from spatialflink_tpu.models.objects import Polygon

    grid = UniformGrid(20, **GRID)
    polys = []
    for i in range(60):
        cx, cy = rng.uniform(-1, 11), rng.uniform(-1, 11)  # some out of grid
        w, h = rng.uniform(0.1, 2.5), rng.uniform(0.1, 2.5)
        polys.append(Polygon(
            obj_id=f"p{i}", timestamp=i,
            rings=[np.array([[cx, cy], [cx + w, cy], [cx + w, cy + h],
                             [cx, cy + h], [cx, cy]])],
        ))
    gb = GeometryBatch.from_objects(polys)
    flags = grid.neighbor_flags(1.2, [grid.flat_cell(5.0, 5.0)])
    got = gb.any_cell_flagged(grid, flags)
    # Brute force: per object, max flag over bbox-overlapped cells.
    for i in range(gb.capacity):
        if not gb.valid[i]:
            assert got[i] == 0
            continue
        cells = grid.bbox_cells(*gb.bbox[i])
        expect = flags[cells].max() if len(cells) else 0
        assert got[i] == expect, (i, gb.bbox[i])


def test_polygon_kernel_chunked_matches_unchunked(rng):
    """Large polygon sets via lax.map chunks == plain vmap path."""
    from spatialflink_tpu.ops.range import range_query_polygons_kernel
    from spatialflink_tpu.operators.base import pack_query_geometries
    from spatialflink_tpu.utils.helper import generate_query_polygons

    grid = UniformGrid(20, **GRID)
    batch = make_batch(rng, n=400, bucket=512).with_cells(grid)
    polys = generate_query_polygons(70, 0, 0, 10, 10, seed=5)  # > chunk of 32
    verts, ev = pack_query_geometries(polys)
    cells = [c for p in polys for c in p.grid_cells(grid)]
    flags = grid.neighbor_flags(0.3, cells)
    pflags = np.asarray(gather_cell_flags(jnp.asarray(batch.cell), jnp.asarray(flags)))
    args = (jnp.asarray(batch.xy), jnp.asarray(batch.valid), jnp.asarray(pflags),
            jnp.asarray(verts), jnp.asarray(ev), 0.3)
    keep_c, dist_c = range_query_polygons_kernel(*args, poly_chunk=32)
    keep_u, dist_u = range_query_polygons_kernel(*args, poly_chunk=128)
    np.testing.assert_array_equal(np.asarray(keep_c), np.asarray(keep_u))
    np.testing.assert_allclose(np.asarray(dist_c), np.asarray(dist_u), rtol=1e-12)


def test_bucketed_join_matches_brute(rng):
    """Dense-bucket (roll-shift) join == brute force, exact when no overflow."""
    from spatialflink_tpu.ops.join import join_window_bucketed

    grid = UniformGrid(20, **GRID)
    r = 0.8
    a = make_batch(rng, n=300, bucket=512).with_cells(grid)
    b = make_batch(rng, n=200, bucket=256).with_cells(grid)
    layers = grid.candidate_layers(r)
    res = join_window_bucketed(
        jnp.asarray(a.xy), jnp.asarray(a.valid), jnp.asarray(a.cell),
        jnp.asarray(b.xy), jnp.asarray(b.valid), jnp.asarray(b.cell),
        grid_n=grid.n, layers=layers, radius=r,
        cap_left=16, cap_right=16, max_pairs=65536,
    )
    assert int(res.overflow) == 0
    count = int(res.count)
    assert count <= 65536
    li = np.asarray(res.left_index)
    ri = np.asarray(res.right_index)
    got = {(int(x), int(y)) for x, y in zip(li, ri) if x >= 0}
    assert len(got) == count
    assert got == brute_join(a, b, r)


def test_bucketed_join_overflow_and_truncation(rng):
    from spatialflink_tpu.ops.join import join_window_bucketed

    grid = UniformGrid(20, **GRID)
    # 60 points in one cell with cap 16 → overflow reported.
    xy = np.full((60, 2), 5.05) + rng.normal(0, 0.001, (60, 2))
    b = PointBatch.from_arrays(xy, bucket=64).with_cells(grid)
    a = PointBatch.from_arrays(np.array([[5.05, 5.05]]), bucket=256).with_cells(grid)
    res = join_window_bucketed(
        jnp.asarray(a.xy), jnp.asarray(a.valid), jnp.asarray(a.cell),
        jnp.asarray(b.xy), jnp.asarray(b.valid), jnp.asarray(b.cell),
        grid_n=grid.n, layers=1, radius=0.5,
        cap_left=4, cap_right=16, max_pairs=4096,
    )
    assert int(res.overflow) > 0
    # Truncation signalling: tiny max_pairs → count > max_pairs sentinel.
    a2 = make_batch(rng, n=200, bucket=256).with_cells(grid)
    b2 = make_batch(rng, n=200, bucket=256).with_cells(grid)
    res2 = join_window_bucketed(
        jnp.asarray(a2.xy), jnp.asarray(a2.valid), jnp.asarray(a2.cell),
        jnp.asarray(b2.xy), jnp.asarray(b2.valid), jnp.asarray(b2.cell),
        grid_n=grid.n, layers=grid.candidate_layers(2.0), radius=2.0,
        cap_left=16, cap_right=16, max_pairs=50,
    )
    assert int(res2.count) > 50


@pytest.mark.slow
def test_join_out_of_grid_points_never_match(rng):
    """Reference semantics: points outside the grid bbox carry keys no
    neighbor set contains, so they never join — in every join variant."""
    from spatialflink_tpu.operators import (
        PointPointJoinQuery, QueryConfiguration, QueryType,
    )
    from spatialflink_tpu.models.objects import Point

    grid = UniformGrid(20, **GRID)
    conf = QueryConfiguration(QueryType.WindowBased, window_size=30, slide_step=30)
    left = [Point(obj_id="out", timestamp=100, x=-0.05, y=5.0),
            Point(obj_id="in", timestamp=200, x=0.2, y=5.0)]
    right = [Point(obj_id="r", timestamp=150, x=0.05, y=5.0)]
    for cap in (32, 256):  # bucketed path and gather path
        res = list(PointPointJoinQuery(conf, grid, cap=cap).run(
            iter(list(left)), iter(list(right)), 0.2))
        got = {(a.obj_id, b.obj_id) for r in res for a, b, _ in r.pairs}
        assert got == {("in", "r")}, (cap, got)


def _indexed_polygon_set(polys, grid, radius):
    """The pruned kernels' view of a polygon set, as the operator builds
    it: (the cell table's edge planes (num_cells + 1, 4, E, K), K)."""
    from spatialflink_tpu.operators.base import (
        pack_cell_candidates,
        pack_cell_edges,
        pack_query_geometries,
    )

    verts, ev = pack_query_geometries(polys, np.float64)
    index = pack_cell_candidates(grid, verts, ev, radius)
    return pack_cell_edges(index.table, verts, ev), index.slots


def _dense_polygon_range(xy, valid, flags, polys, radius):
    import jax
    import jax.numpy as jnp

    from spatialflink_tpu.operators.base import pack_query_geometries
    from spatialflink_tpu.ops.range import range_query_polygons_kernel

    verts, ev = pack_query_geometries(polys, np.float64)
    keep, dist = jax.jit(range_query_polygons_kernel,
                         static_argnames="approximate")(
        jnp.asarray(xy), jnp.asarray(valid), jnp.asarray(flags),
        jnp.asarray(verts), jnp.asarray(ev), radius)
    return np.asarray(keep), np.asarray(dist)


def _pruned_polygon_range(xy, valid, flags, polys, grid, radius,
                          point_chunk, budget=None):
    """(keep, dist, K[, budget overflow]) of the pruned kernel — the compact
    one with a ``budget`` — on points whose cells the host assigned."""
    import jax
    import jax.numpy as jnp

    from spatialflink_tpu.ops.range import (
        range_query_polygons_pruned_compact_kernel,
        range_query_polygons_pruned_kernel,
    )

    cell_edges, slots = _indexed_polygon_set(polys, grid, radius)
    args = (jnp.asarray(xy), jnp.asarray(valid),
            jnp.asarray(grid.assign_cells_np(xy)), jnp.asarray(flags),
            jnp.asarray(cell_edges), radius)
    if budget is None:
        out = jax.jit(range_query_polygons_pruned_kernel,
                      static_argnames=("point_chunk", "approximate"))(
            *args, point_chunk=point_chunk)
    else:
        out = jax.jit(range_query_polygons_pruned_compact_kernel,
                      static_argnames=("budget", "point_chunk"))(
            *args, budget=budget, point_chunk=point_chunk)
    keep, dist, *over = (np.asarray(o) for o in out)
    return (keep, dist, slots, *over)


def test_pruned_polygon_range_matches_dense(rng):
    """range_query_polygons_pruned_kernel must keep exactly the dense
    kernel's lanes, with equal min_dist on kept lanes: a cell's list holds
    every polygon within r of its points."""
    from spatialflink_tpu.grid import UniformGrid
    from spatialflink_tpu.utils.helper import generate_query_polygons

    grid = UniformGrid(20, 0.0, 10.0, 0.0, 10.0)
    polys = generate_query_polygons(60, 0.0, 0.0, 10.0, 10.0, grid_size=20,
                                    seed=5)
    n = 3000
    xy = rng.uniform(0, 10, (n, 2))
    valid = np.ones(n, bool)
    flags = np.ones(n, np.uint8)  # all candidate lanes: distances decide
    r = 0.4

    keep_d, dist_d = _dense_polygon_range(xy, valid, flags, polys, r)
    keep_p, dist_p, slots = _pruned_polygon_range(
        xy, valid, flags, polys, grid, r, point_chunk=512)
    assert slots == 8
    np.testing.assert_array_equal(keep_p, keep_d)
    assert 0 < keep_d.sum() < n
    np.testing.assert_allclose(dist_p[keep_d], dist_d[keep_d],
                               rtol=0, atol=0)


def test_pruned_polygon_range_crowded_spot_widens_the_table(rng):
    """Six concentric squares: every nearby point has six polygons within
    r. What used to arm the ``cand`` re-run (cand 4 < 6) is read off the
    query set at set-up — K >= 6 from the start — and the result equals the
    dense kernel's."""
    from spatialflink_tpu.grid import UniformGrid
    from spatialflink_tpu.models.objects import Polygon

    polys = []
    for i in range(6):
        s = 0.1 + 0.05 * i
        polys.append(Polygon(rings=[np.array(
            [[5 - s, 5 - s], [5 + s, 5 - s], [5 + s, 5 + s], [5 - s, 5 + s],
             [5 - s, 5 - s]])]))
    grid = UniformGrid(20, 0.0, 10.0, 0.0, 10.0)
    xy = np.concatenate([np.array([[5.05, 5.0], [9.0, 9.0]]),
                         rng.uniform(3.0, 7.0, (254, 2))])
    valid, flags = np.ones(256, bool), np.ones(256, np.uint8)
    keep_d, dist_d = _dense_polygon_range(xy, valid, flags, polys, 1.0)
    keep_p, dist_p, slots = _pruned_polygon_range(
        xy, valid, flags, polys, grid, 1.0, point_chunk=2)
    assert slots >= 6
    assert keep_d[0] and not keep_d[1]
    np.testing.assert_array_equal(keep_p, keep_d)
    np.testing.assert_allclose(dist_p[keep_d], dist_d[keep_d],
                               rtol=0, atol=0)


def test_pruned_polygon_range_lowers_with_no_ranking_and_no_box_matrix():
    """Structural guard, at the benchmark rehearsal's shapes (131,072 lanes,
    250 generator rectangles, the 100 x 100 Beijing grid, blocks of 8,192):
    the lowered ``range_polygons_pruned_fused`` ranks nothing (no top-k, no
    sort) and holds nothing of shape (point_chunk, P) — a point's candidates
    come from one gathered row of the cell table."""
    import re

    import jax
    import jax.numpy as jnp

    from spatialflink_tpu.grid import UniformGrid
    from spatialflink_tpu.ops.range import range_polygons_pruned_fused
    from spatialflink_tpu.utils.helper import generate_query_polygons

    bbox = (115.5, 39.6, 117.6, 41.1)
    grid = UniformGrid(100, bbox[0], bbox[2], bbox[1], bbox[3])
    n_polys, lanes, chunk = 250, 131_072, 8192
    polys = generate_query_polygons(n_polys, *bbox, grid_size=100, seed=1)
    cell_edges, slots = _indexed_polygon_set(polys, grid, 0.002)
    assert cell_edges.shape == (grid.num_cells + 1, 4, 4, 8) and slots == 8

    def shape(a, dtype):
        return jax.ShapeDtypeStruct(a, dtype)

    text = jax.jit(
        range_polygons_pruned_fused,
        static_argnames=("point_chunk", "approximate"),
    ).lower(
        shape((lanes, 2), jnp.float32), shape((lanes,), jnp.bool_),
        shape((lanes,), jnp.int32), shape((grid.num_cells + 1,), jnp.uint8),
        shape(cell_edges.shape, jnp.float32), 0.002, point_chunk=chunk,
    ).as_text()
    assert not re.search(r"top_?k|stablehlo\.sort|chlo\.", text, re.IGNORECASE)
    assert not re.search(rf"[<x]({chunk}x{n_polys}|{n_polys}x{chunk})x", text)
    # what took their place: a block gathers one row of 4·E·K a point
    row = cell_edges[0].size
    assert re.search(rf"gather.*tensor<{chunk}x{row}xf32>", text)


def test_pruned_compact_polygon_range_matches_dense(rng):
    """The candidate-compacted pruned kernel must keep exactly the dense
    kernel's lanes (equal dists on kept lanes) when the budget holds, with
    realistic mostly-non-candidate flags."""
    from spatialflink_tpu.grid import UniformGrid
    from spatialflink_tpu.utils.helper import generate_query_polygons

    grid = UniformGrid(20, 0.0, 10.0, 0.0, 10.0)
    polys = generate_query_polygons(50, 0.0, 0.0, 10.0, 10.0, grid_size=20,
                                    seed=6)
    n = 4000
    xy = rng.uniform(0, 10, (n, 2))
    valid = np.ones(n, bool)
    # ~10% candidate lanes, rest pruned by flags.
    flags = np.where(rng.uniform(size=n) < 0.1, 1, 0).astype(np.uint8)
    r = 0.35

    keep_d, dist_d = _dense_polygon_range(xy, valid, flags, polys, r)
    keep_c, dist_c, _slots, budget_over = _pruned_polygon_range(
        xy, valid, flags, polys, grid, r, point_chunk=256, budget=1024)
    assert int(budget_over) == 0
    np.testing.assert_array_equal(keep_c, keep_d)
    assert keep_d.any()
    np.testing.assert_allclose(dist_c[keep_d], dist_d[keep_d],
                               rtol=0, atol=0)


def test_pruned_compact_budget_overflow(rng):
    from spatialflink_tpu.grid import UniformGrid
    from spatialflink_tpu.utils.helper import generate_query_polygons

    grid = UniformGrid(20, 0.0, 10.0, 0.0, 10.0)
    polys = generate_query_polygons(10, 0.0, 0.0, 10.0, 10.0, grid_size=20,
                                    seed=8)
    n = 512
    xy = rng.uniform(0, 10, (n, 2))
    flags = np.ones(n, np.uint8)  # every lane is a candidate
    _, _, _, budget_over = _pruned_polygon_range(
        xy, np.ones(n, bool), flags, polys, grid, 0.3, point_chunk=128,
        budget=128)
    assert int(budget_over) == n - 128
