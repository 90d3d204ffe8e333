"""The geofencing deployment's path at small sizes (CPU, x64 on):
``PointPolygonRangeQuery.run_soa`` against the benchmark's plain reference
through every kernel selection and both re-runs, the reference itself against
the O(N x P x E) loop on general rings, and the path's telemetry (spans
``range.assemble`` / ``range.select``, every crossing a ``d2h``,
``snapshot()["range"]``).

The x64-off twin of the operator cases (float32 on centred coordinates, the
band fixed beforehand) is ``tests/test_x64_off.py``'s ``range`` child.
"""

import numpy as np
import pytest

from benchmark.references.range_polygons import (
    Reference,
    brute_force,
    polygon_distance,
    row_keys,
)
from spatialflink_tpu.grid import UniformGrid
from spatialflink_tpu.models.objects import Polygon
from spatialflink_tpu.operators import (
    PointPolygonRangeQuery,
    QueryConfiguration,
    QueryType,
)
from spatialflink_tpu.telemetry import telemetry
from spatialflink_tpu.utils.helper import generate_query_polygons
from span_tiling import assert_parents_tile, inside, slow_consumer, x_spans

BBOX = (115.5, 39.6, 117.6, 41.1)  # conf/geoflink-conf.yml's, as the cell's
GRID_N = 100
RADIUS = 0.002
TOL = 1e-9  # x64 on: float64 on both sides, the band is rounding's
T0_MS = 1_700_000_000_000
WINDOW_MS = 10_000


@pytest.fixture(autouse=True)
def _telemetry_off():
    telemetry.disable()
    telemetry._reset_state()
    yield
    telemetry.disable()


# -- the reference against the plain loop ------------------------------------

RECT = [np.array([[0.0, 0.0], [4.0, 0.0], [4.0, 3.0], [0.0, 3.0]])]
CONCAVE = [np.array([[0.0, 0.0], [6.0, 0.0], [6.0, 5.0], [3.0, 1.5],
                     [0.0, 5.0]])]  # a notch from the top down to (3, 1.5)
HOLED = [np.array([[0.0, 0.0], [8.0, 0.0], [8.0, 8.0], [0.0, 8.0],
                   [0.0, 0.0]]),
         np.array([[3.0, 3.0], [5.0, 3.0], [5.0, 5.0], [3.0, 5.0]])]
SHAPES = {"rectangle": RECT, "concave": CONCAVE, "ring_with_hole": HOLED}


def _plain_ref(polygons, radius, tol=TOL):
    # a grid wide enough that no point is outside it and no cell guaranteed
    return Reference(bbox=(-20.0, -20.0, 20.0, 20.0), grid_cells=10,
                     polygons=polygons, radius=radius, tol=tol)


@pytest.mark.parametrize("shape", sorted(SHAPES))
def test_reference_equals_the_plain_loop(shape, rng):
    rings = SHAPES[shape]
    x = rng.uniform(-2.0, 10.0, 600)
    y = rng.uniform(-2.0, 10.0, 600)
    radius = 0.7
    want = brute_force(x, y, [rings], radius)
    idx, d = _plain_ref([rings], radius).matches(x, y)
    keep = d <= radius
    assert [i for i, _ in want] == idx[keep].tolist()
    assert np.allclose([dd for _, dd in want], d[keep], rtol=0, atol=1e-12)
    inside = sum(1 for _, dd in want if dd == 0.0)
    assert 0 < inside < len(want) < len(x)  # inside, near and far all occur


def test_reference_takes_the_nearest_of_many_polygons(rng):
    polys = [[r + np.array([dx, dy]) for r in rings]
             for rings, dx, dy in ((RECT, -5.0, -5.0), (CONCAVE, 2.0, 2.0),
                                   (HOLED, -9.0, 3.0), (RECT, 2.5, 2.5))]
    x = rng.uniform(-12.0, 12.0, 500)
    y = rng.uniform(-12.0, 12.0, 500)
    want = brute_force(x, y, polys, 0.9)
    idx, d = _plain_ref(polys, 0.9).matches(x, y)
    keep = d <= 0.9
    assert [i for i, _ in want] == idx[keep].tolist()
    assert np.allclose([dd for _, dd in want], d[keep], rtol=0, atol=1e-12)


def test_a_hole_is_outside_and_an_edge_point_is_in_the_band():
    x = np.array([4.0, 1.0, 4.0, 9.0])
    y = np.array([4.0, 1.0, 3.0 + 5e-7, 4.0])
    d = polygon_distance(x, y, HOLED)
    assert d[0] == pytest.approx(1.0)  # the hole's centre: 1 from its edge
    assert d[1] == 0.0                 # inside the ring, outside the hole
    assert d[2] == pytest.approx(5e-7)  # just inside the hole: outside
    assert d[3] == pytest.approx(1.0)
    # a point 5e-7 past r from the outline, band 1e-6: either way is right
    ref = _plain_ref([HOLED], 1.0, tol=1e-6)
    px, py = np.array([9.0 + 5e-7, 9.0 + 5e-6]), np.array([4.0, 4.0])
    win = {"ts": np.array([1, 2]), "x": px, "y": py, "oid": np.array([7, 8])}
    want = ref.matches(px, py)
    assert want[0].tolist() == [0] and ref.edge_points(want) == 1
    none = {k: v[:0] for k, v in win.items()}
    assert ref.compare(want, win, none, np.empty(0)) == []
    first = {k: v[:1] for k, v in win.items()}
    assert ref.compare(want, win, first, np.array([1.0 + 5e-7])) == []
    both = ref.compare(want, win, win, np.array([1.0, 1.0]))
    assert any("beyond the radius" in b for b in both)


@pytest.mark.parametrize("case,shift,drop,add,deviation,wrong", [
    ("exact", 0.0, False, False, 0.0, 0),
    ("shifted_within_the_limit", 4e-7, False, False, 4e-7, 0),
    ("shifted_past_it", 3e-6, False, False, 3e-6, 0),
    ("a_match_dropped", 0.0, True, False, 0.0, 1),
    ("a_far_point_added", 0.0, False, True, 0.0, 1),
])
def test_check_gives_the_readings_behind_its_verdict(case, shift, drop, add,
                                                     deviation, wrong, rng):
    x, y = rng.uniform(-2.0, 10.0, 400), rng.uniform(-2.0, 10.0, 400)
    ref = _plain_ref([HOLED], 0.7, tol=1e-6)
    win = {"ts": np.arange(400), "x": x, "y": y, "oid": np.arange(400) % 9}
    want = ref.matches(x, y)
    idx, d = (a[want[1] <= 0.7] for a in want)
    far = int(np.setdiff1d(np.arange(400), want[0])[0])
    d = d.copy()
    near = int(np.argmax(d > 0.1))  # outside the polygon and clear of r
    d[near] += shift
    if drop:
        keep = np.arange(len(idx)) != int(np.argmin(d))
        idx, d = idx[keep], d[keep]
    if add:
        idx, d = np.append(idx, far), np.append(d, 0.7)
    bad, read = ref.check(want, win, {k: v[idx] for k, v in win.items()}, d)
    assert read["max_distance_deviation"] == pytest.approx(deviation,
                                                           abs=1e-12)
    assert read["points_wrong_outside_band"] == wrong
    assert bool(bad) == (deviation > ref.tol or wrong > 0)
    assert bad == ref.compare(want, win, {k: v[idx] for k, v in win.items()},
                              d)


def test_reference_refuses_a_radius_with_a_guaranteed_layer():
    with pytest.raises(ValueError, match="guaranteed"):
        Reference(bbox=BBOX, grid_cells=GRID_N, polygons=[RECT], radius=0.06,
                  tol=TOL)


def test_row_keys_tell_rows_apart():
    a = {"ts": np.array([5, 5, 6]), "x": np.array([1.0, 1.0, 1.0]),
         "y": np.array([2.0, 2.0, 2.0]), "oid": np.array([1, 2, 1])}
    k = row_keys(a)
    assert len(set(k.tolist())) == 3
    assert row_keys({f: v[::-1] for f, v in a.items()}).tolist() \
        == k[::-1].tolist()


# -- the operator against the reference ---------------------------------------


def _grid():
    min_x, min_y, max_x, max_y = BBOX
    return UniformGrid(GRID_N, min_x, max_x, min_y, max_y)


def _stacked(n_spread, n_stacked, seed):
    """``n_spread`` generator rectangles and ``n_stacked`` more, all at one
    spot: a point there has more than 8 polygons within r, so the cells
    there list more than 8."""
    polys = generate_query_polygons(n_spread, *BBOX, grid_size=GRID_N,
                                    seed=seed)
    at = polys[0].rings[0]
    return polys + [Polygon(obj_id=f"stack{i}", rings=[at + 1e-4 * i])
                    for i in range(n_stacked)]


#: name -> (polygons, points a window, the kernel the operator must pick,
#:          the cell table's slots K (0: no table), budget re-runs over the
#:          two windows). The two stacked cases armed the ``cand`` re-run
#:          until K was read off the query set: now a wider K from set-up.
CASES = {
    "dense": (lambda: generate_query_polygons(40, *BBOX, grid_size=GRID_N,
                                              seed=3), 6_000, "dense", 0, 0),
    "pruned": (lambda: generate_query_polygons(400, *BBOX, grid_size=GRID_N,
                                               seed=4), 12_000, "pruned",
               8, 0),
    "pruned_compact": (lambda: generate_query_polygons(
        90, *BBOX, grid_size=GRID_N, seed=5), 12_000, "pruned_compact", 8, 0),
    "pruned_cand_retry": (lambda: _stacked(400, 12, seed=6), 12_000,
                          "pruned", 16, 0),
    "compact_budget_retry": (lambda: generate_query_polygons(
        90, *BBOX, grid_size=GRID_N, seed=7), 60_000, "pruned_compact", 8, 1),
    "compact_both_retries": (lambda: _stacked(90, 12, seed=8), 60_000,
                             "pruned_compact", 16, 1),
}


def _window_chunks(n, seed, windows=2, chunk=5_000):
    rng = np.random.default_rng(seed)
    total = n * windows
    ts = T0_MS + (np.arange(total, dtype=np.int64) * WINDOW_MS) // n
    arrays = {"ts": ts, "x": rng.uniform(BBOX[0], BBOX[2], total),
              "y": rng.uniform(BBOX[1], BBOX[3], total),
              "oid": rng.integers(0, 256, total).astype(np.int64)}
    chunks = [{k: v[lo:lo + chunk] for k, v in arrays.items()}
              for lo in range(0, total, chunk)]
    per_window = [{k: v[w * n:(w + 1) * n] for k, v in arrays.items()}
                  for w in range(windows)]
    return chunks, per_window


def _run(case, consume=list):
    make, n, _kernel, _c, _b = CASES[case]
    polygons = make()
    op = PointPolygonRangeQuery(
        QueryConfiguration(QueryType.WindowBased, window_size=10,
                           slide_step=10, approximate_query=False), _grid())
    chunks, per_window = _window_chunks(n, seed=len(case))
    got = consume(op.run_soa(iter(chunks), polygons, RADIUS))
    ref = Reference(bbox=BBOX, grid_cells=GRID_N,
                    polygons=[[np.asarray(r) for r in p.rings]
                              for p in polygons],
                    radius=RADIUS, tol=TOL)
    return op, got, per_window, ref


@pytest.mark.parametrize("case", sorted(CASES))
def test_run_soa_equals_the_reference(case):
    _make, n, kernel, slots, budget_retries = CASES[case]
    telemetry.enable()
    op, got, per_window, ref = _run(case)
    snap = telemetry.snapshot()["range"]
    telemetry.disable()
    assert op.last_range_kernel == kernel
    assert [(s, e) for s, e, _m, _d in got] == [
        (T0_MS + w * WINDOW_MS, T0_MS + (w + 1) * WINDOW_MS)
        for w in range(len(per_window))]
    matches = 0
    for (_s, _e, matched, dist), window in zip(got, per_window):
        want = ref.matches(window["x"], window["y"])
        assert ref.compare(want, window, matched, dist) == []
        assert 0 < len(dist) < n  # some match, most do not
        assert (dist <= RADIUS).all()
        matches += len(dist)
    # the one re-run left: paid in the first window, the budget persists
    # into the next; a crowded spot is a wider K from set-up, never a re-run
    assert (snap["cand_retries"], snap["budget_retries"]) == (
        0, budget_retries)
    assert snap["windows"] == 2 and snap["points"] == 2 * n
    assert snap["matches"] == matches and snap["lanes"] >= snap["points"]
    assert snap["cand"] == slots
    assert snap["budget"] == (
        op._cand_budget if kernel == "pruned_compact" else 0)
    assert op._ncand == (slots or 8)
    if budget_retries:  # the next power of two over what the window held
        assert op._cand_budget in (8192, 16384)
    else:
        assert op._cand_budget == 4096
    # the cell table's gauges, recorded once when the evaluator was built,
    # and the windows answered through it
    assert snap["index_windows"] == (2 if slots else 0)
    if slots:
        assert snap["index_slots"] == slots
        assert 0 < snap["index_cells"] <= GRID_N * GRID_N
        assert snap["index_cells"] <= snap["index_entries"] \
            <= snap["index_cells"] * slots
        if slots == 16:  # the twelve stacked and the one under them
            assert snap["index_entries"] >= snap["index_cells"] + 12
    else:
        assert not any(k in snap for k in (
            "index_slots", "index_entries", "index_cells"))


@pytest.mark.parametrize("case", ["pruned", "compact_both_retries"])
def test_every_crossing_is_a_leaf_and_the_spans_hold_none(case):
    _make, n, _kernel, _slots, budget_retries = CASES[case]
    telemetry.enable()
    _op, got, _per_window, _ref = _run(case)
    events = [e for e in telemetry.events if e.get("ph") == "X"]
    d2h_transfers, snap = telemetry.d2h_transfers, telemetry.snapshot()
    telemetry.disable()

    def by(name):
        return [e for e in events if e["name"] == name]

    def inside(child, parent):
        return (child["ts"] >= parent["ts"] and child["ts"] + child["dur"]
                <= parent["ts"] + parent["dur"])

    # one a window, none for a firing that gave no window
    assemble = by("range.assemble")
    select = by("range.select")
    assert len(assemble) == len(select) == len(got) == 2
    assert [e["args"]["n"] for e in assemble] == [n, n]
    assert all(e["args"]["bucket"] >= n for e in assemble)
    assert [e["args"]["matches"] for e in select] == [
        len(d) for _s, _e, _m, d in got]
    # the assembly is the producer thread's, every crossing the loop's
    loop = {e["tid"] for e in select}
    assert len(loop) == 1 and not loop & {e["tid"] for e in assemble}
    leaves = [e for e in events if e["name"] in ("h2d", "d2h")
              or e["name"].startswith("dispatch:")]
    assert leaves and {e["tid"] for e in leaves} == loop and not any(
        inside(leaf, sp) for leaf in leaves for sp in select)
    # one ship a window whatever the re-runs; one fetch a run of the program,
    # and nothing crosses unseen
    assert len(by("h2d")) == 2 + 1  # + the query set's tables, shipped once
    assert len(by("d2h")) == d2h_transfers == 2 + budget_retries
    assert snap["range"]["windows"] == 2


@pytest.mark.parametrize("case", ["pruned", "compact_budget_retry"])
def test_range_window_parent_tiles_the_window_and_no_consumer_time(case):
    """One ``range.window`` a window, from the loop's ask for it to the
    hand-back: the wait for the producer, the ship, every run of the program,
    each fetch and the selection lie inside it, the consumer's time does not,
    and the answers are the telemetry-off run's. The assembly runs on the
    producer thread: ``range.assemble`` and its four ``soa.*`` passes, once a
    window, lie in none of the loop's parents; each ``d2h`` holds its one
    ``d2h.wait``."""
    _make, n, _kernel, _slots, budget_retries = CASES[case]
    _op, plain, _pw, _ref = _run(case)
    naps = []
    telemetry.enable()
    try:
        _op, got, _pw, _ref = _run(
            case, consume=lambda results: slow_consumer(results, naps))
        events = x_spans(telemetry.events)
    finally:
        telemetry.disable()
    assert len(got) == len(plain) == len(naps) == 2
    for (s1, e1, m1, d1), (s2, e2, m2, d2) in zip(plain, got):
        assert (s1, e1) == (s2, e2) and np.array_equal(d1, d2)
        assert m1.keys() == m2.keys()
        assert all(np.array_equal(m1[k], m2[k]) for k in m1)
    parents, inner = assert_parents_tile(events, "range.window", naps)
    assert [p["args"]["n"] for p in parents] == [n, n]
    loop = parents[0]["tid"]
    # outside every parent on the loop's thread: the query set's one ship and
    # the last ask, which finds the stream at an end
    loose = [e["name"] for e in events if e["tid"] == loop
             and e["name"] != "range.window"
             and not any(inside(e, p) for p in parents)]
    assert loose == ["h2d", "range.await"]
    passes = ["soa.consolidate", "soa.center", "soa.cells", "soa.pad"]
    assemble = [e for e in events if e["name"] == "range.assemble"]
    assert len(assemble) == 2
    assert {e["tid"] for e in assemble} != {loop}
    assert len({e["tid"] for e in assemble}) == 1  # one producer thread
    for p, names, asm in zip(parents, inner, assemble):
        assert p["tid"] == loop
        assert not set(names) & {"range.assemble", *passes}
        for once in ("range.await", "h2d", "range.select"):
            assert names.count(once) == 1, (once, names)
        assert names.index("range.await") < names.index("h2d") \
            < names.index("range.select")
        mine = [e for e in events if e["name"] in passes and inside(e, asm)
                and e["tid"] == asm["tid"]]
        assert sorted(e["name"] for e in mine) == sorted(passes)
        assert all(e["args"]["n"] == n for e in mine)
        assert sum(e["dur"] for e in mine) <= asm["dur"]
    # the first window pays the budget re-run: one more dispatch and fetch
    assert inner[0].count("d2h") == 1 + budget_retries
    assert inner[1].count("d2h") == 1
    d2h = [e for e in events if e["name"] == "d2h"]
    waits = [e for e in events if e["name"] == "d2h.wait"]
    assert len(waits) == len(d2h) == 2 + budget_retries
    for wait, leaf in zip(waits, d2h):
        assert inside(wait, leaf) and wait["dur"] <= leaf["dur"]


def test_nothing_recorded_and_no_span_when_telemetry_is_off():
    before = telemetry.snapshot().get("range")
    _op, got, _per_window, _ref = _run("dense")
    assert len(got) == 2
    assert telemetry.snapshot().get("range") == before
    assert not telemetry.events


def test_knobs_are_set_where_the_operator_is_built():
    op = PointPolygonRangeQuery(
        QueryConfiguration(QueryType.WindowBased, window_size=10,
                           slide_step=10), _grid())
    assert (op._ncand, op._cand_budget, op.last_range_kernel) == (
        8, 4096, None)


# -- the grid index: a cell's list holds every polygon within r of its points --


def _triangles(rng, count, lo, hi, size):
    """``count`` triangles with corners within ``size`` of a point uniform
    over [lo, hi]²: boxes the polygon does not fill."""
    at = rng.uniform(lo, hi, (count, 1, 2))
    return [Polygon(obj_id=f"t{i}", rings=[ring]) for i, ring in
            enumerate(at + rng.uniform(-size, size, (count, 3, 2)))]


#: name -> (grid side n, grid bbox (min_x, max_x, min_y, max_y), radius,
#:          polygons from (rng), slots K the set must give)
INDEX_CASES = {
    # the generator's rectangles on the cell's own grid, its r
    "beijing_rectangles": (GRID_N, (BBOX[0], BBOX[2], BBOX[1], BBOX[3]), RADIUS,
                           lambda rng: generate_query_polygons(
                               300, *BBOX, grid_size=GRID_N, seed=11), 8),
    # r of several cells: a polygon enters a 7 x 7 block of lists and more
    "radius_of_three_cells": (20, (0.0, 10.0, 0.0, 10.0), 1.5,
                              lambda rng: _triangles(rng, 25, 0.0, 10.0, 0.4),
                              None),
    # polygons across the grid's edge and wholly outside it
    "straddling_and_outside": (16, (0.0, 8.0, 0.0, 8.0), 0.3,
                               lambda rng: _triangles(rng, 60, -3.0, 11.0, 0.9),
                               None),
    # r = 0: only the boxes' own cells and the margin
    "zero_radius": (10, (-5.0, 5.0, -5.0, 5.0), 0.0,
                    lambda rng: _triangles(rng, 40, -5.0, 5.0, 0.7), None),
    # twelve on one spot: K is read off the set, 16 from the start
    "twelve_stacked": (GRID_N, (BBOX[0], BBOX[2], BBOX[1], BBOX[3]), RADIUS,
                       lambda rng: _stacked(100, 12, seed=6), 16),
    # a grid of one cell: everything in one list
    "one_cell": (1, (0.0, 4.0, 0.0, 4.0), 0.5,
                 lambda rng: _triangles(rng, 20, 0.0, 4.0, 0.5), 32),
}


@pytest.mark.parametrize("case", sorted(INDEX_CASES))
def test_a_cells_list_holds_every_polygon_within_r_of_its_points(case, rng):
    from spatialflink_tpu.operators.base import (
        pack_cell_candidates,
        pack_query_geometries,
    )

    n, (min_x, max_x, min_y, max_y), radius, make, slots = INDEX_CASES[case]
    grid = UniformGrid(n, min_x, max_x, min_y, max_y)
    polygons = make(rng)
    verts, ev = pack_query_geometries(polygons, np.float64)
    index = pack_cell_candidates(grid, verts, ev, radius)
    p, table = len(polygons), index.table

    # shape and bookkeeping
    assert table.shape == (grid.num_cells + 1, index.slots)
    assert table.dtype == np.int32 and table.min() >= 0 and table.max() <= p
    assert index.slots >= 8 and index.slots & (index.slots - 1) == 0
    assert slots is None or index.slots == slots
    live = table < p
    assert not live[grid.num_cells].any()  # the out-of-grid row is empty
    assert index.entries == live.sum()
    assert index.cells == live.any(axis=1).sum() > 0
    assert index.slots // 2 < max(live.sum(axis=1).max(), 5)  # no rung too many
    for row in table[live.any(axis=1)][:50]:  # a polygon once a list
        assert len(set(row[row < p])) == (row < p).sum()

    # points: uniform over the grid and a margin around it, on the cells'
    # boundaries exactly, and on the boxes' grown edges
    span = max_x - min_x
    uniform = rng.uniform([min_x - 0.2 * span, min_y - 0.2 * span],
                          [max_x + 0.2 * span, max_y + 0.2 * span], (3000, 2))
    lines = min_x + grid.cell_length * rng.integers(0, n + 1, 600)
    on_x = np.stack([lines, rng.uniform(min_y, max_y, 600)], axis=1)
    on_y = np.stack([rng.uniform(min_x, max_x, 600),
                     min_y + grid.cell_length * rng.integers(0, n + 1, 600)],
                    axis=1)
    rings = [np.asarray(q.rings[0], np.float64) for q in polygons]
    corners = np.array([[f(r[:, 0]) + sx * radius, g(r[:, 1]) + sy * radius]
                        for r in rings[:150] for f, sx in ((min, -1), (max, 1))
                        for g, sy in ((min, -1), (max, 1))])
    pts = np.concatenate([uniform, on_x, on_y, corners])
    cell = grid.assign_cells_np(pts)
    tol = 1e-6 * span  # the float32 band and more
    near = held = 0
    for i, q in enumerate(polygons):
        d = polygon_distance(pts[:, 0], pts[:, 1], q.rings)
        close = (d <= radius + tol) & (cell < grid.num_cells)
        listed = (table[cell] == i).any(axis=1)
        assert listed[close].all(), (case, i, pts[close & ~listed][:3])
        near += close.sum()
        held += listed.sum()
    # it finds, and (where there is more than one cell) it prunes
    assert near > 0 and (n == 1 or held < len(pts) * p / 2)
    assert (cell == grid.num_cells).any()  # out-of-grid points were among them


def test_cell_edges_hold_each_candidates_valid_edges_and_far_slots():
    """``pack_cell_edges``: row g, slot k of the (cells + 1, 4, E, K) planes
    holds the edges of polygon ``table[g, k]`` — its valid edges first, a
    ring seam and the padding gone — and ``FAR_EDGE`` wherever no edge is:
    the tail of a shorter polygon, an empty slot, the out-of-grid row."""
    from spatialflink_tpu.operators.base import (
        FAR_EDGE,
        pack_cell_candidates,
        pack_cell_edges,
        pack_query_geometries,
    )

    grid = UniformGrid(4, 0.0, 16.0, 0.0, 16.0)
    polygons = [Polygon(obj_id="holed", rings=HOLED),  # 4 + 4 edges, a seam
                Polygon(obj_id="rect", rings=[RECT[0] + 10.0])]  # 4 edges
    verts, ev = pack_query_geometries(polygons, np.float64)
    assert ev.sum(axis=1).tolist() == [8, 4] and not ev[0].all()
    index = pack_cell_candidates(grid, verts, ev, 0.5)
    edges = pack_cell_edges(index.table, verts, ev)
    assert edges.shape == (grid.num_cells + 1, 4, 8, index.slots)
    assert edges.dtype == verts.dtype and edges.flags["C_CONTIGUOUS"]

    def segments(ring):
        ring = np.asarray(ring, np.float64)
        if not np.array_equal(ring[0], ring[-1]):
            ring = np.concatenate([ring, ring[:1]])
        return {tuple(np.concatenate([a, b])) for a, b in zip(ring, ring[1:])}

    want = [set().union(*map(segments, p.rings)) for p in polygons]
    seen = set()
    for g, row in enumerate(index.table):
        for k, i in enumerate(row):
            got = {tuple(e) for e in edges[g, :, :, k].T}
            if i == len(polygons):
                assert got == {(FAR_EDGE,) * 4}
                continue
            seen.add(int(i))
            n_real = len(want[i])
            assert {tuple(e) for e in edges[g, :, :n_real, k].T} == want[i]
            assert (edges[g, :, n_real:, k] == FAR_EDGE).all()
    assert seen == {0, 1}
    assert (edges[grid.num_cells] == FAR_EDGE).all()
