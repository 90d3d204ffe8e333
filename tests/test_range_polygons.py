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
    spot: a point there has more than 8 polygon boxes within r."""
    polys = generate_query_polygons(n_spread, *BBOX, grid_size=GRID_N,
                                    seed=seed)
    at = polys[0].rings[0]
    return polys + [Polygon(obj_id=f"stack{i}", rings=[at + 1e-4 * i])
                    for i in range(n_stacked)]


#: name -> (polygons, points a window, the kernel the operator must pick,
#:          cand re-runs, budget re-runs over the two windows)
CASES = {
    "dense": (lambda: generate_query_polygons(40, *BBOX, grid_size=GRID_N,
                                              seed=3), 6_000, "dense", 0, 0),
    "pruned": (lambda: generate_query_polygons(400, *BBOX, grid_size=GRID_N,
                                               seed=4), 12_000, "pruned",
               0, 0),
    "pruned_compact": (lambda: generate_query_polygons(
        90, *BBOX, grid_size=GRID_N, seed=5), 12_000, "pruned_compact", 0, 0),
    "pruned_cand_retry": (lambda: _stacked(400, 12, seed=6), 12_000,
                          "pruned", 1, 0),
    "compact_budget_retry": (lambda: generate_query_polygons(
        90, *BBOX, grid_size=GRID_N, seed=7), 60_000, "pruned_compact", 0, 1),
    "compact_both_retries": (lambda: _stacked(90, 12, seed=8), 60_000,
                             "pruned_compact", 1, 1),
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


def _run(case):
    make, n, _kernel, _c, _b = CASES[case]
    polygons = make()
    op = PointPolygonRangeQuery(
        QueryConfiguration(QueryType.WindowBased, window_size=10,
                           slide_step=10, approximate_query=False), _grid())
    chunks, per_window = _window_chunks(n, seed=len(case))
    got = list(op.run_soa(iter(chunks), polygons, RADIUS))
    ref = Reference(bbox=BBOX, grid_cells=GRID_N,
                    polygons=[[np.asarray(r) for r in p.rings]
                              for p in polygons],
                    radius=RADIUS, tol=TOL)
    return op, got, per_window, ref


@pytest.mark.parametrize("case", sorted(CASES))
def test_run_soa_equals_the_reference(case):
    _make, n, kernel, cand_retries, budget_retries = CASES[case]
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
    # the re-runs: paid in the first window, the knobs persist into the next
    assert (snap["cand_retries"], snap["budget_retries"]) == (
        cand_retries, budget_retries)
    assert snap["windows"] == 2 and snap["points"] == 2 * n
    assert snap["matches"] == matches and snap["lanes"] >= snap["points"]
    assert snap["cand"] == (0 if kernel == "dense" else op._ncand)
    assert snap["budget"] == (
        op._cand_budget if kernel == "pruned_compact" else 0)
    assert op._ncand == (16 if cand_retries else 8)
    if budget_retries:  # the next power of two over what the window held
        assert op._cand_budget in (8192, 16384)
    else:
        assert op._cand_budget == 4096


@pytest.mark.parametrize("case", ["pruned", "compact_both_retries"])
def test_every_crossing_is_a_leaf_and_the_spans_hold_none(case):
    _make, n, _kernel, cand_retries, budget_retries = CASES[case]
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
    leaves = [e for e in events if e["name"] in ("h2d", "d2h")
              or e["name"].startswith("dispatch:")]
    assert leaves and not any(
        inside(leaf, sp) for leaf in leaves for sp in assemble + select)
    # one ship a window whatever the re-runs; one fetch a run of the program
    # (both knobs grew in the same re-run here), and nothing crosses unseen
    reruns = max(cand_retries, budget_retries)
    assert len(by("h2d")) == 2 + 1  # + the polygon table, shipped once
    assert len(by("d2h")) == d2h_transfers == 2 + reruns
    assert snap["range"]["windows"] == 2


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
