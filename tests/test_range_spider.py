"""Geofencing against many-vertex zones that crowd the centre: Spider's
polygon generator, the ragged grid index and the cell-major kernel
(``range_polygons_cell_major_fused``), the operator's choice between it and
the K-wide cell rows, and the roofline reader's cost of the deployment.

Comparisons run on seeded sets on the CPU (x64 on: float64 on both sides
unless a case asks for float32, centred as the chip's path centres)."""

import json
import os

import jax
import numpy as np
import pytest

from benchmark.readers import range_spider_roofline
from benchmark.references import range_polygons, range_polygons_grid
from benchmark.references import spider_gaussian, spider_polygons
from benchmark.references.range_polygons import Reference, brute_force
from spatialflink_tpu.grid import UniformGrid
from spatialflink_tpu.models.objects import Polygon
from spatialflink_tpu.operators import (
    PointPolygonRangeQuery,
    QueryConfiguration,
    QueryType,
)
from spatialflink_tpu.operators.base import (
    center_coords,
    flags_for_queries,
    pack_cell_candidates,
    pack_cell_edges,
    pack_entry_edges,
    pack_query_geometries,
    point_lanes,
)
from spatialflink_tpu.ops.range import (
    CELL_MAJOR_BATCH,
    CELL_MAJOR_ROW,
    CELL_MAJOR_TILE,
    CELL_MAJOR_WIDTH,
    range_polygons_cell_major_fused,
    range_polygons_pruned_fused,
)
from spatialflink_tpu.telemetry import telemetry
from spatialflink_tpu.utils.helper import generate_query_polygons

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BBOX = (115.5, 39.6, 117.6, 41.1)  # conf/geoflink-conf.yml's, as the cell's
GRID_N = 100
RADIUS = 0.002
T0_MS = 1_700_000_000_000
#: the float32 band the chip's path is held to: 4 eps32 of the bbox span
BAND32 = 4 * float(np.finfo(np.float32).eps) * (BBOX[2] - BBOX[0])


@pytest.fixture(autouse=True)
def _telemetry_off():
    telemetry.disable()
    telemetry._reset_state()
    yield
    telemetry.disable()


def _grid():
    return UniformGrid(GRID_N, BBOX[0], BBOX[2], BBOX[1], BBOX[3])


def _zones(count, seed, sigma):
    rings = spider_polygons.polygons(seed, count, BBOX, polysize=0.01,
                                     maxseg=32, sigma=sigma)
    return [Polygon(obj_id=f"zone{i}", rings=[r]) for i, r in enumerate(rings)]


def _gaussian_window(n, seed, sigma):
    rng = np.random.default_rng(seed)
    x, y = spider_gaussian.positions(rng.uniform(BBOX[0], BBOX[2], n),
                                     rng.uniform(BBOX[1], BBOX[3], n), BBOX,
                                     sigma=sigma)
    return {"ts": T0_MS + np.sort(rng.integers(0, 10_000, n)), "x": x, "y": y,
            "oid": rng.integers(0, 256, n).astype(np.int64)}


# -- the generator's laws -----------------------------------------------------


def _segments_cross(a, b, c, d):
    """Do the closed segments ab and cd properly cross or touch?"""
    def orient(p, q, r):
        return np.sign((q[0] - p[0]) * (r[1] - p[1])
                       - (q[1] - p[1]) * (r[0] - p[0]))
    return (orient(a, b, c) != orient(a, b, d)
            and orient(c, d, a) != orient(c, d, b))


def test_spider_polygons_follow_the_generators_laws():
    kw = dict(polysize=0.01, maxseg=32)
    centre, segments, angle, radius = spider_polygons.draw(11, 3000, **kw)
    assert segments.min() == 3 and segments.max() == 32
    assert len(np.unique(segments)) == 30  # every count in [3, 32] occurs
    assert len(angle) == len(radius) == segments.sum()
    assert (radius > 0).all() and (radius <= 0.005).all()
    assert radius.max() > 0.0049 and radius.min() < 0.0002  # uniform in (0, .005]
    assert (angle >= 0).all() and (angle < 2 * np.pi).all()
    first = np.cumsum(segments) - segments
    for a, k in zip(first, segments):
        assert (np.diff(angle[a:a + k]) > 0).all()  # sorted, distinct
    # N(0.5, 0.1) centres
    assert abs(centre.mean() - 0.5) < 0.01 and abs(centre.std() - 0.1) < 0.01
    rings = spider_polygons.polygons(11, 3000, BBOX, **kw)
    span = np.array([BBOX[2] - BBOX[0], BBOX[3] - BBOX[1]])
    for i in (0, 1, 2, 1500, 2999):
        ring = rings[i]
        k = segments[i]
        assert ring.shape == (k + 1, 2) and np.array_equal(ring[0], ring[-1])
        unit = (ring[:-1] - np.array(BBOX[:2])) / span
        reach = np.hypot(*(unit - centre[i]).T)
        assert np.allclose(reach, radius[first[i]:first[i] + k], atol=1e-12)
    # simple: no two edges that do not share a vertex meet
    for ring in rings[:200]:
        k = len(ring) - 1
        for i in range(k):
            for j in range(i + 2, k):
                if i == 0 and j == k - 1:
                    continue  # the closing edge meets the first at ring[0]
                assert not _segments_cross(ring[i], ring[i + 1], ring[j],
                                           ring[j + 1]), (i, j)
    # the same seed, the same set; another seed, another
    again = spider_polygons.polygons(11, 3000, BBOX, **kw)
    assert all(np.array_equal(a, b) for a, b in zip(rings, again))
    other = spider_polygons.polygons(12, 3000, BBOX, **kw)
    assert not np.array_equal(rings[0], other[0])
    with pytest.raises(ValueError):
        spider_polygons.draw(1, 10, polysize=0.01, maxseg=2)


# -- the cell's reference: range_polygons' semantics, found through buckets --

RECT = [np.array([[0.0, 0.0], [4.0, 0.0], [4.0, 3.0], [0.0, 3.0]])]
CONCAVE = [np.array([[0.0, 0.0], [6.0, 0.0], [6.0, 5.0], [3.0, 1.5],
                     [0.0, 5.0]])]  # a notch from the top down to (3, 1.5)
HOLED = [np.array([[0.0, 0.0], [8.0, 0.0], [8.0, 8.0], [0.0, 8.0],
                   [0.0, 0.0]]),
         np.array([[3.0, 3.0], [5.0, 3.0], [5.0, 5.0], [3.0, 5.0]])]


@pytest.mark.parametrize("shape", ["rectangle", "concave", "ring_with_hole",
                                   "straddling_the_grid", "degenerate_edge"])
def test_grid_reference_equals_the_plain_loop(shape):
    rng = np.random.default_rng(len(shape))
    rings = {"rectangle": RECT, "concave": CONCAVE, "ring_with_hole": HOLED,
             "straddling_the_grid": [RECT[0] - 2.5],
             "degenerate_edge": [np.array([[1.0, 1.0], [1.0, 1.0],
                                           [5.0, 2.0], [2.0, 6.0]])]}[shape]
    polys = [rings, [r + np.array([3.5, -1.0]) for r in rings]]
    x = rng.uniform(-3.0, 12.0, 800)
    y = rng.uniform(-3.0, 12.0, 800)
    # a 10 x 10 grid over [-2, 10]^2: points on both sides of its edge
    ref = range_polygons_grid.Reference(
        bbox=(-2.0, -2.0, 10.0, 10.0), grid_cells=10, polygons=polys,
        radius=0.7, tol=1e-9)
    idx, d = ref.matches(x, y)
    keep = d <= 0.7
    inside = ref.in_grid(x, y)
    want = [(i, dd) for i, dd in brute_force(x, y, polys, 0.7) if inside[i]]
    assert [i for i, _ in want] == idx[keep].tolist()
    assert np.allclose([dd for _, dd in want], d[keep], rtol=0, atol=1e-12)
    assert 0 < len(want) < inside.sum()


def test_grid_reference_answers_as_the_base_on_crowded_zones():
    zones = [[r] for r in spider_polygons.polygons(
        3, 400, BBOX, polysize=0.01, maxseg=32, sigma=0.03)]
    w = _gaussian_window(30_000, 4, 0.03)
    kw = dict(bbox=BBOX, grid_cells=GRID_N, polygons=zones, radius=RADIUS,
              tol=BAND32)
    base = range_polygons.Reference(**kw).matches(w["x"], w["y"])
    grid = range_polygons_grid.Reference(**kw).matches(w["x"], w["y"])
    assert np.array_equal(grid[0], base[0]) and len(base[0]) > 1_000
    assert np.allclose(grid[1], base[1], rtol=0, atol=1e-12)


# -- the cell-major kernel through the operator, against the reference -------


#: name -> (zones, their seed, sigma of zones and points, points, dtype)
CROWDED = {
    "float64": (300, 31, 0.03, 20_000, np.float64),
    "float32": (300, 32, 0.03, 20_000, np.float32),
}


@pytest.mark.parametrize("case", sorted(CROWDED))
def test_cell_major_run_soa_equals_the_reference(case):
    count, seed, sigma, n, dtype = CROWDED[case]
    zones = _zones(count, seed, sigma)
    window = _gaussian_window(n, seed + 100, sigma)
    op = PointPolygonRangeQuery(
        QueryConfiguration(QueryType.WindowBased, window_size=10,
                           slide_step=10), _grid())
    telemetry.enable()
    got = list(op.run_soa(iter([window]), zones, RADIUS, dtype=dtype))
    snap = telemetry.snapshot()["range"]
    telemetry.disable()
    assert op.last_range_kernel == "cell_major"
    assert snap["index_slots"] >= 32 and op._ncand == snap["index_slots"]
    verts, ev = pack_query_geometries(zones, np.float64)
    assert ev.sum(axis=1).max() >= 16  # E >= 16: many-vertex rings
    assert len(got) == 1
    _s, _e, matched, dist = got[0]
    tol = 1e-9 if dtype == np.float64 else BAND32
    ref = Reference(bbox=BBOX, grid_cells=GRID_N, radius=RADIUS, tol=tol,
                    polygons=[[np.asarray(r) for r in z.rings] for z in zones])
    bad, read = ref.check(ref.matches(window["x"], window["y"]), window,
                          matched, dist)
    assert bad == [] and read["points_wrong_outside_band"] == 0
    assert 0 < len(dist) < n and (dist <= RADIUS).all()
    # the counters: the index's device bytes and the tile plan's edge slots
    assert snap["index_bytes"] > 0 and snap["edge_slots"] > 0
    edges = pack_entry_edges(
        np.zeros(snap["index_entries"], np.int32), verts, ev, CELL_MAJOR_WIDTH)
    itemsize = np.dtype(np.float64 if jax.config.jax_enable_x64
                        and dtype == np.float64 else np.float32).itemsize
    assert snap["index_bytes"] == edges.size * itemsize + 4 * (
        GRID_N * GRID_N + 2)
    # whole steps of every edge slot
    assert snap["edge_slots"] % (
        CELL_MAJOR_TILE * CELL_MAJOR_WIDTH * edges.shape[1] // 4) == 0


# -- the kernels side by side on one set of device lanes ----------------------


def _lanes(zones, window, dtype, radius=RADIUS, index_radius=None):
    grid = _grid()
    flags = flags_for_queries(grid, radius, zones)
    verts, ev = pack_query_geometries(zones, np.float64)
    index = pack_cell_candidates(grid, verts, ev, index_radius or radius)
    centred = center_coords(grid, verts, dtype)
    xy, valid, cell, _ = point_lanes(grid, window["x"], window["y"], None,
                                     dtype)
    return xy, valid, cell, flags, index, centred, ev


def _cell_major(xy, valid, cell, flags, index, centred, ev,
                tile=CELL_MAJOR_TILE, width=CELL_MAJOR_WIDTH,
                batch=CELL_MAJOR_BATCH):
    """keep, distance and the edge slots evaluated a lane (a tile's
    share)."""
    run = jax.jit(range_polygons_cell_major_fused, static_argnames=(
        "tile", "width", "batch", "approximate"))
    edges = pack_entry_edges(index.polys, centred, ev, width)
    keep, dist, steps = run(xy, valid, cell, flags, edges, index.starts,
                            RADIUS, tile=tile, width=width, batch=batch)
    return np.asarray(keep), np.asarray(dist), int(steps) * width * (
        edges.shape[1] // 4)


def _pruned(xy, valid, cell, flags, index, centred, ev):
    run = jax.jit(range_polygons_pruned_fused,
                  static_argnames=("point_chunk", "approximate"))
    keep, dist = run(xy, valid, cell, flags,
                     pack_cell_edges(index.table, centred, ev), RADIUS)
    return np.asarray(keep), np.asarray(dist)


@pytest.mark.parametrize("dtype", [np.float32, np.float64])
def test_cell_major_equals_pruned_on_the_rectangles(dtype):
    """The 1,000-rectangle generator of ``range-polygons-1k``: the same keep
    on every lane and the same distance, bit for bit, on every kept one."""
    zones = generate_query_polygons(1000, *BBOX, grid_size=GRID_N, seed=47)
    rng = np.random.default_rng(47)
    window = {"x": rng.uniform(BBOX[0], BBOX[2], 30_000),
              "y": rng.uniform(BBOX[1], BBOX[3], 30_000)}
    lanes = _lanes(zones, window, dtype)
    keep_p, dist_p = _pruned(*lanes)
    keep_c, dist_c, _steps = _cell_major(*lanes)
    assert keep_p.sum() > 1000
    assert np.array_equal(keep_c, keep_p)
    assert np.array_equal(dist_c[keep_c], dist_p[keep_p])


def test_a_superset_of_the_lists_keeps_the_same_lanes_and_distances():
    """Wider ranges — a larger tile, a longer step, lists built for three
    times the radius — hold every point's own list and more: keep and the
    kept distances do not move, while the edge slots evaluated grow."""
    zones = _zones(300, 41, 0.03)
    window = _gaussian_window(20_000, 141, 0.03)
    xy, valid, cell, flags, index, centred, ev = _lanes(zones, window,
                                                        np.float64)
    keep, dist, edges = _cell_major(xy, valid, cell, flags, index, centred,
                                    ev, tile=512)
    assert 0 < keep.sum() < len(window["x"])
    wide = pack_cell_candidates(_grid(), *pack_query_geometries(
        zones, np.float64), 3 * RADIUS)
    assert wide.entries > index.entries
    slots = edges * 512
    for tile, width, idx in ((2048, CELL_MAJOR_WIDTH, index),
                             (512, 64, index),
                             (512, CELL_MAJOR_WIDTH, wide)):
        k2, d2, e2 = _cell_major(xy, valid, cell, flags, idx, centred, ev,
                                 tile=tile, width=width)
        assert np.array_equal(k2, keep), (tile, width)
        assert np.array_equal(d2[keep], dist[keep]), (tile, width)
        assert e2 * tile > slots
    # a tile whose cells list nothing takes no step of its own: one zone
    # lists a few cells, only the tiles whose cells reach them take one, and
    # those, ordered last, fill as few batches as they can
    one = pack_cell_candidates(_grid(), *pack_query_geometries(
        zones[:1], np.float64), RADIUS)
    _k3, _d3, e3 = _cell_major(xy, valid, cell, flags, one, centred[:1],
                               ev[:1], tile=512)
    listed = np.nonzero(np.diff(one.starts))[0]
    tiles = np.sort(np.where(valid, cell, GRID_N * GRID_N)).reshape(-1, 512)
    reach = ((tiles[:, :1] <= listed) & (listed <= tiles[:, -1:])).any(axis=1)
    # one step of every slot (the zone's E) for each tile of a batch that
    # holds a tile that reaches the zone's cells, and none for the others
    assert 0 < reach.sum() < len(tiles) / 4
    batches = -(-reach.sum() // CELL_MAJOR_BATCH)
    assert e3 == batches * CELL_MAJOR_BATCH * CELL_MAJOR_WIDTH * ev[0].sum()


@pytest.mark.parametrize("batch", [1, 5, 64])
def test_the_batch_moves_only_the_slots(batch):
    """Tiles taken one at a time, five (a batch that does not divide the
    tiles: padding tiles) or all at once: the same keep on every lane and
    the same distance on every kept one; one at a time evaluates each
    tile's own steps and no more."""
    zones = _zones(300, 43, 0.03)
    window = _gaussian_window(20_000, 143, 0.03)
    lanes = _lanes(zones, window, np.float64)
    keep, dist, edges = _cell_major(*lanes)
    k2, d2, e2 = _cell_major(*lanes, batch=batch)
    assert keep.sum() > 1000 and np.array_equal(k2, keep)
    assert np.array_equal(d2[keep], dist[keep])
    index, ev = lanes[4], lanes[6]
    cell = np.where(lanes[1], lanes[2], GRID_N * GRID_N)
    tiles = np.sort(np.asarray(cell)).reshape(-1, CELL_MAJOR_TILE)
    own = (index.starts[tiles[:, -1] + 1] - index.starts[tiles[:, 0]]
           + CELL_MAJOR_WIDTH - 1) // CELL_MAJOR_WIDTH
    step = CELL_MAJOR_WIDTH * ev.sum(axis=1).max()  # a lane's slots a step
    if batch == 1:
        assert e2 == own.sum() * step
    else:
        assert e2 >= own.sum() * step


# -- which kernel the operator picks ------------------------------------------


@pytest.mark.parametrize("case,make,kernel", [
    ("rectangles", lambda: generate_query_polygons(
        400, *BBOX, grid_size=GRID_N, seed=4), "pruned"),
    ("sparse_rectangles", lambda: generate_query_polygons(
        90, *BBOX, grid_size=GRID_N, seed=5), "pruned_compact"),
    ("dense_rectangles", lambda: generate_query_polygons(
        20_000, *BBOX, grid_size=GRID_N, seed=6), "pruned"),
    ("spider_sparse", lambda: _zones(800, 54, 0.1), "cell_major"),
    ("spider_crowded", lambda: _zones(300, 51, 0.03), "cell_major"),
    ("spider_deployment", lambda: _zones(10_000, 52, 0.1), "cell_major"),
])
def test_the_kernel_is_read_off_the_index(case, make, kernel):
    """A K-wide row of more than ``CELL_MAJOR_ROW`` edge slots (K, the
    fullest list up the bucket ladder, × E): cell-major, whatever the flags'
    occupancy — the deployment's set is at 0.28, near the 0.25 under which a
    K-wide set compacts its lanes. 20,000 rectangles list up to 28 a cell
    (K 32) but of 4 edges: 128 slots, the K-wide row; 800 Spider zones
    list up to 16 (K 16) of 32: 512, cell-major."""
    zones = make()
    op = PointPolygonRangeQuery(
        QueryConfiguration(QueryType.WindowBased, window_size=10,
                           slide_step=10), _grid())
    flags = flags_for_queries(_grid(), RADIUS, zones)
    op._window_evaluator(zones, flags, RADIUS, np.float32, None)
    assert op.last_range_kernel == kernel
    _verts, ev = pack_query_geometries(zones, np.float64)
    row = op._ncand * ev.sum(axis=1).max()
    assert (row > CELL_MAJOR_ROW) == (kernel == "cell_major")
    if case == "dense_rectangles":
        assert op._ncand == 32 and row == 128
    if case == "spider_sparse":
        assert op._ncand == 16 and row == 512
    if case == "spider_deployment":
        assert op._ncand == 128 and float((flags > 0).mean()) < 0.35


# -- the roofline reader's cost of the deployment -----------------------------


def test_the_roofline_cost_prices_the_configurations_shapes():
    with open(os.path.join(ROOT, "benchmark", "configs",
                           "range-polygons-10k-spider.json")) as f:
        config = json.load(f)
    ops, nbytes = range_spider_roofline.cost(config)
    # by hand: N = 1e6 points, E = 17.5 segments, 40 operations an edge
    n, edges = 1_000_000, 17.5
    cands = ops / (n * edges * range_spider_roofline.EDGE_OPS)
    entries = (nbytes - 18 * n) / (edges * 16)
    assert 6.0 < cands < 7.6 and 30_000 < entries < 34_000
    # against one seeded draw of the generator and the positions
    rings = spider_polygons.polygons(7, 10_000, BBOX, polysize=0.01,
                                     maxseg=32)
    lo = np.array([r.min(axis=0) for r in rings]) - RADIUS
    hi = np.array([r.max(axis=0) for r in rings]) + RADIUS
    cell = (BBOX[2] - BBOX[0]) / GRID_N
    first = np.floor((lo - np.array(BBOX[:2])) / cell)
    last = np.floor((hi - np.array(BBOX[:2])) / cell)
    drawn_entries = np.prod(last - first + 1, axis=1).sum()
    assert abs(entries / drawn_entries - 1) < 0.03
    w = _gaussian_window(100_000, 7, 0.1)
    order = np.argsort(w["x"])
    xs, ys = w["x"][order], w["y"][order]
    held = 0
    for a, b in zip(lo, hi):
        ys_in = ys[np.searchsorted(xs, a[0]):np.searchsorted(xs, b[0], "right")]
        held += ((ys_in >= a[1]) & (ys_in <= b[1])).sum()
    assert abs(cands / (held / 100_000) - 1) < 0.05
    # the mean extent of a ring along an axis, in radii, by its own draw
    _c, segments, angle, radius = spider_polygons.draw(
        8, 20_000, polysize=2.0, maxseg=32)
    u = radius * np.cos(angle)
    first = np.cumsum(segments) - segments
    width = np.maximum.reduceat(u, first) - np.minimum.reduceat(u, first)
    w1, _w2 = range_spider_roofline.mean_width(32)
    assert abs(width.mean() / w1 - 1) < 0.01


def test_the_roofline_prices_edge_tests_at_the_vector_unit():
    """The edge tests are elementwise: priced at the vector unit's rate (the
    clock the bf16 peak gives, times 8 x 128 lanes), they bind the
    deployment's window over its bytes; a device whose units are not
    listed gets no rate."""
    with open(os.path.join(ROOT, "benchmark", "harness", "peaks.json")) as f:
        peaks = json.load(f)["TPU v5 lite"]
    vpu = range_spider_roofline.vpu_ops_per_s(peaks)
    clock = peaks["bf16_flops_per_s"] / (2 * 4 * 128 * 128)
    assert 1.4e9 < clock < 1.6e9 and vpu == pytest.approx(clock * 1024)
    with open(os.path.join(ROOT, "benchmark", "configs",
                           "range-polygons-10k-spider.json")) as f:
        ops, nbytes = range_spider_roofline.cost(json.load(f))
    assert ops / vpu > 50 * nbytes / peaks["hbm_bytes_per_s"]
    assert range_spider_roofline.vpu_ops_per_s(
        dict(peaks, name="TPU vX")) is None
