"""The window join on a city that crowds its centre (PR 41): a key-grid cell
several capacity rungs past 128 is held by a finer bucket grid
(``JoinCapacity``: ``join_refine``), exact against the plain reference; a
uniform window still runs today's programs; what is picked only grows; points
outside the deployment's grid never join on any bucket grid; approximate mode
stays on the key grid; a window no layout of the program holds is refused by
name before a dispatch. Small twins of ``join-tdrive-2x100k-skew``
(benchmark/configs), positions from the benchmark's own mapping
(``benchmark/references/spider_gaussian.py``)."""

import numpy as np
import pytest

from benchmark.references import spider_gaussian, tjoin_tdrive
from spatialflink_tpu.grid import UniformGrid
from spatialflink_tpu.operators import (
    PointPointJoinQuery,
    QueryConfiguration,
    QueryType,
)
from spatialflink_tpu.operators import join_query
from spatialflink_tpu.operators.join_query import (
    PALLAS_ROW_LANES,
    PALLAS_TOP_RUNG,
    REFINE_RUNG,
    JoinCapacityError,
)
from spatialflink_tpu.operators.trajectory import PointPointTJoinQuery
from spatialflink_tpu.ops.compaction import max_cell_count
from spatialflink_tpu.telemetry import telemetry

from join_reference import Reference

BBOX = (115.5, 39.6, 117.6, 41.1)  # the yml's Beijing bbox
GRID_N = 30                        # key cells of 0.07 degrees
SPAN = BBOX[2] - BBOX[0]
WINDOW_MS = 5_000
# float32 on bbox-centred coordinates: the band the configuration states
TOL32 = 4 * float(np.finfo(np.float32).eps) * SPAN


def _grid():
    return UniformGrid(GRID_N, BBOX[0], BBOX[2], BBOX[1], BBOX[3])


def _side(rng, n, window=0, sigma=0.1, ids=None):
    """One side's points of one window: a uniform draw over the bbox, mapped
    as the benchmark's adapter maps the harness's stream."""
    x, y = spider_gaussian.positions(
        rng.uniform(BBOX[0], BBOX[2], n), rng.uniform(BBOX[1], BBOX[3], n),
        BBOX, sigma=sigma)
    return _chunk(rng, x, y, window, ids)


def _uniform(rng, n, window=0):
    return _chunk(rng, rng.uniform(BBOX[0], BBOX[2], n),
                  rng.uniform(BBOX[1], BBOX[3], n), window)


def _chunk(rng, x, y, window, ids=None):
    n = len(x)
    ts = window * WINDOW_MS + np.sort(rng.integers(0, WINDOW_MS, n))
    oid = np.arange(n) if ids is None else rng.integers(0, ids, n)
    return {"ts": ts.astype(np.int64), "x": x, "y": y,
            "oid": oid.astype(np.int64)}


def _operator(backend=None, approximate=False, **kw):
    conf = QueryConfiguration(QueryType.WindowBased, window_size=5,
                              slide_step=5, approximate_query=approximate)
    return PointPointJoinQuery(conf, _grid(), join_backend=backend, **kw)


def _fullest(grid, *sides):
    return max(
        max_cell_count(grid.assign_cells_np(np.stack([s["x"], s["y"]], 1)),
                       np.ones(len(s["x"]), bool), grid.num_cells)
        for s in sides)


def _held(ref, left, right, out):
    _s, _e, li, ri, dd, count, overflow = out
    want = ref.pairs(left["x"], left["y"], right["x"], right["y"])
    assert overflow == 0
    assert ref.compare(want, li, ri, dd, count, overflow,
                       len(right["x"])) == []
    return count


@pytest.fixture
def traced():
    telemetry.enable()
    yield telemetry
    telemetry.disable()


# -- the mapping --------------------------------------------------------------

def test_mapping_is_spiders_gaussian_of_the_harness_draw():
    rng = np.random.default_rng(41)
    ux = rng.uniform(BBOX[0], BBOX[2], 1_000_000)
    uy = rng.uniform(BBOX[1], BBOX[3], 1_000_000)
    x, y = spider_gaussian.positions(ux, uy, BBOX)
    sx, sy = 0.1 * (BBOX[2] - BBOX[0]), 0.1 * (BBOX[3] - BBOX[1])
    assert x.dtype == y.dtype == np.float64
    # mean 0.5 and sigma 0.1 of each span: (116.55, 40.35), 0.21 x 0.15
    assert abs(x.mean() - 116.55) < 4 * sx / 1e3
    assert abs(y.mean() - 40.35) < 4 * sy / 1e3
    assert x.std() == pytest.approx(sx, rel=5e-3)
    assert y.std() == pytest.approx(sy, rel=5e-3)
    # the axes are independent normals: 68.27 % of each inside +-1 sigma
    for v, c, s in ((x, 116.55, sx), (y, 40.35, sy)):
        assert np.mean(np.abs(v - c) <= s) == pytest.approx(0.6827, abs=2e-3)
    assert abs(np.corrcoef(x, y)[0, 1]) < 5e-3
    # a pure function of the draw: same seed, same stream, same positions
    again = spider_gaussian.positions(ux.copy(), uy.copy(), BBOX)
    assert np.array_equal(again[0], x) and np.array_equal(again[1], y)
    # a draw on the bbox's very edge is finite and far out, never NaN
    ex, ey = spider_gaussian.positions([BBOX[2]], [BBOX[1]], BBOX)
    assert np.isfinite(ex).all() and np.isfinite(ey).all()
    assert ex[0] > BBOX[2]


def test_the_mapping_imports_nothing_of_the_package():
    """``benchmark/references/spider_gaussian.py`` imports nothing of the
    package: numpy and the standard library only."""
    import ast
    import inspect

    tree = ast.parse(inspect.getsource(spider_gaussian))
    mods = {a.name.split(".")[0] for n in ast.walk(tree)
            if isinstance(n, ast.Import) for a in n.names}
    mods |= {n.module.split(".")[0] for n in ast.walk(tree)
             if isinstance(n, ast.ImportFrom)}
    assert mods <= {"numpy", "typing", "__future__"}


# -- the crowded window, held on a refined bucket grid ---------------------------

@pytest.mark.parametrize("dtype", [np.float64, np.float32],
                         ids=["float64", "float32"])
def test_crowded_window_is_exact_on_a_refined_grid_xla(dtype, traced):
    """24,000 points a side: the fullest key cell holds ~ 590, three rungs
    past 128; the pick is a 4 x finer grid at a rung within 128, before any
    dispatch, and the pair set is the reference's."""
    rng = np.random.default_rng(7)
    left, right = _side(rng, 24_000), _side(rng, 24_000)
    grid = _grid()
    fullest = _fullest(grid, left, right)
    assert fullest > 4 * REFINE_RUNG
    op = _operator("xla")
    (out,) = list(op.run_soa(iter([left]), iter([right]), 0.002, dtype=dtype))
    exact = dtype is np.float64
    ref = Reference(bbox=BBOX, grid_cells=GRID_N, radius=0.002,
                    tol=1e-12 if exact else TOL32)
    count = _held(ref, left, right, out)
    assert count > 10_000
    assert op.join_refine == 4 and op.join_cap <= REFINE_RUNG
    j = telemetry.snapshot()["join"]
    assert j["fullest_cell"] == fullest and j["refine"] == 4
    assert j["bucket_cells"] == (GRID_N * 4) ** 2
    runs = 1 + j["cap_retries"] + j["budget_retries"]
    assert j["cap_retries"] == 0  # the bound held: no layout re-run
    assert j["bucket_lanes"] == runs * (GRID_N * 4) ** 2 * 9 * op.join_cap ** 2
    assert j["cap"] == op.join_cap and j["pairs"] == count


def _small_rung(monkeypatch, rung=16):
    """The interpreter walks every bucket block: the same contract at a
    refinement rung of 16, so that a crowded window stays a second's work."""
    monkeypatch.setattr(join_query, "REFINE_RUNG", rung)


@pytest.mark.parametrize("backend", ["pallas_interpret", "xla"])
def test_crowded_window_is_exact_on_a_refined_grid_both_programs(
        backend, monkeypatch):
    _small_rung(monkeypatch)
    rng = np.random.default_rng(8)
    left, right = _side(rng, 3_000), _side(rng, 3_000)
    assert _fullest(_grid(), left, right) > 4 * 16
    op = _operator(backend, cap=16)
    (out,) = list(op.run_soa(iter([left]), iter([right]), 0.006,
                             dtype=np.float32))
    ref = Reference(bbox=BBOX, grid_cells=GRID_N, radius=0.006, tol=TOL32)
    assert _held(ref, left, right, out) > 1_500
    assert op.join_refine == 4 and op.join_cap == 16
    assert op.last_join_backend == ("xla" if backend == "xla" else "pallas")


def test_overflow_past_the_rung_refines_before_it_climbs(monkeypatch, traced):
    """The net under the pick: a bound that turns out too low costs a re-run
    one step up — a rung up to the refinement rung, then a finer grid."""
    _small_rung(monkeypatch)
    monkeypatch.setattr(join_query, "max_cell_count", lambda *a: 0)
    rng = np.random.default_rng(9)
    left, right = _side(rng, 3_000), _side(rng, 3_000)
    op = _operator("xla", cap=8)
    (out,) = list(op.run_soa(iter([left]), iter([right]), 0.006))
    ref = Reference(bbox=BBOX, grid_cells=GRID_N, radius=0.006, tol=1e-12)
    _held(ref, left, right, out)
    # 8 -> 16 on the key grid, then the grid twice finer at rung 16, twice
    assert (op.join_refine, op.join_cap) == (4, 16)
    assert telemetry.snapshot()["join"]["cap_retries"] == 3


# -- a uniform window runs today's programs ----------------------------------------

def test_uniform_window_picks_the_key_grid_and_todays_program(monkeypatch,
                                                              traced):
    rng = np.random.default_rng(10)
    left, right = _uniform(rng, 40_000), _uniform(rng, 40_000)
    grid = _grid()
    fullest = _fullest(grid, left, right)
    assert 64 < fullest <= 128
    calls = []
    program = join_query.window_join_program

    def recording(backend=None):
        fn, name = program(backend)

        def run(*args, **statics):
            calls.append((fn, statics))
            return fn(*args, **statics)

        return run, name

    monkeypatch.setattr(join_query, "window_join_program", recording)
    op = _operator("xla")
    (out,) = list(op.run_soa(iter([left]), iter([right]), 0.002,
                             dtype=np.float32))
    assert (op.join_refine, op.join_cap) == (1, 128)
    # the one jitted function of the key grid, its static arguments as ever
    fn, _ = program("xla")
    assert [c[0] for c in calls] == [fn] * len(calls)
    assert all(c[1]["grid_n"] == GRID_N and c[1]["layers"] == 1
               and c[1]["cap_left"] == c[1]["cap_right"] == 128
               for c in calls)
    assert {c[1]["max_pairs"] for c in calls} <= {262_144, op.join_budget}
    kernels = {r["kernel"] for r in telemetry.kernel_table()}
    assert "join_window_cells" not in kernels
    # and its pairs: the program called directly on the window's lanes
    from spatialflink_tpu.operators.base import device_point_args

    lanes = []
    for s in (left, right):
        xy, valid, cell, _ = device_point_args(
            grid, np.stack([s["x"], s["y"]], 1), None, np.float32)
        lanes += [xy, valid, cell]
    res = fn(*lanes, grid_n=GRID_N, layers=1, radius=0.002, cap_left=128,
             cap_right=128, max_pairs=op.join_budget)
    _s, _e, li, ri, dd, count, _o = out
    assert count == int(res.count)
    for got, want in zip((li, ri, dd),
                         (res.left_index, res.right_index, res.dist)):
        assert np.array_equal(got, np.asarray(want)[:len(got)])
    j = telemetry.snapshot()["join"]
    assert j["refine"] == 1 and j["bucket_cells"] == GRID_N ** 2
    assert j["bucket_lanes"] == len(calls) * GRID_N ** 2 * 9 * 128 ** 2


# -- what is picked only grows, and a settled run compiles nothing -----------------

def test_pick_never_shrinks_and_a_second_window_reruns_nothing(traced):
    rng = np.random.default_rng(11)
    crowded = (_side(rng, 24_000, 0), _side(rng, 24_000, 0))
    sparse = (_uniform(rng, 2_000, 1), _uniform(rng, 2_000, 1))
    again = tuple({**s, "ts": s["ts"] + 2 * WINDOW_MS} for s in crowded)
    lefts, rights = zip(crowded, sparse, again)
    op = _operator("xla")
    ref = Reference(bbox=BBOX, grid_cells=GRID_N, radius=0.002, tol=TOL32)
    picks, compiles, retries = [], [], []
    for w, out in enumerate(op.run_soa(iter(lefts), iter(rights), 0.002,
                                       dtype=np.float32)):
        _held(ref, lefts[w], rights[w], out)
        picks.append((op.join_refine, op.join_cap, op.join_budget))
        compiles.append(len(telemetry.compile_events))
        j = telemetry.snapshot()["join"]
        retries.append(j["cap_retries"] + j["budget_retries"])
    assert picks[0][0] == 4
    assert picks[1] == picks[0]  # a sparse window takes nothing back
    assert picks[2] == picks[0]
    # the same crowded window again: no re-run, no new program
    assert retries[2] == retries[1] == retries[0]
    assert compiles[2] == compiles[1]
    # a later run of the same operator at a radius the refinement would
    # cut through falls back to what that radius allows
    list(op.run_soa(iter([sparse[0]]), iter([sparse[1]]), 0.03,
                    dtype=np.float32))
    assert op.join_refine == 2 and op.join_cap == picks[0][1]


# -- key semantics: the deployment's grid decides who joins -----------------------

def test_out_of_grid_points_join_nothing_on_a_refined_grid():
    rng = np.random.default_rng(12)

    def side():
        s = _side(rng, 24_000)
        # a band of points astride the grid's left and lower borders, each
        # within the radius of many on the other side of it
        bx = rng.uniform(BBOX[0] - 0.004, BBOX[0] + 0.004, 600)
        by = rng.uniform(40.3, 40.4, 600)
        cx = rng.uniform(116.5, 116.6, 600)
        cy = rng.uniform(BBOX[1] - 0.004, BBOX[1] + 0.004, 600)
        return {**s, "x": np.concatenate([s["x"], bx, cx]),
                "y": np.concatenate([s["y"], by, cy]),
                "ts": np.concatenate([s["ts"], np.full(1200, WINDOW_MS - 1)]),
                "oid": np.arange(len(s["x"]) + 1200)}

    left, right = side(), side()
    ref = Reference(bbox=BBOX, grid_cells=GRID_N, radius=0.002, tol=TOL32)
    outside = ~ref.in_grid(left["x"], left["y"])
    assert 400 < outside.sum() < 800
    # the O(n^2) rule on the band itself: pairs astride the border exist
    near = np.hypot(left["x"][-1200:, None] - right["x"][None, -1200:],
                    left["y"][-1200:, None] - right["y"][None, -1200:])
    astride = (near <= 0.002) & (outside[-1200:, None]
                                 | ~ref.in_grid(right["x"][-1200:],
                                                right["y"][-1200:])[None, :])
    assert astride.sum() > 20
    op = _operator("xla")
    (out,) = list(op.run_soa(iter([left]), iter([right]), 0.002,
                             dtype=np.float32))
    _held(ref, left, right, out)
    assert op.join_refine == 4
    li = out[2][:out[5]]
    assert not outside[li].any()


def test_approximate_mode_stays_on_the_key_grid():
    """Approximate mode emits every candidate of the KEY grid's 3 x 3
    neighbourhood: a crowded cell climbs the ladder there, never the grid."""
    rng = np.random.default_rng(13)
    left, right = _side(rng, 700, sigma=0.02), _side(rng, 700, sigma=0.02)
    grid = _grid()
    fullest = _fullest(grid, left, right)
    assert fullest > REFINE_RUNG
    op = _operator("xla", approximate=True)
    (out,) = list(op.run_soa(iter([left]), iter([right]), 0.002))
    _s, _e, li, ri, _dd, count, overflow = out
    assert overflow == 0 and op.join_refine == 1
    assert op.join_cap == 256 >= fullest
    lc = grid.cell_xy_indices_np(np.stack([left["x"], left["y"]], 1))
    rc = grid.cell_xy_indices_np(np.stack([right["x"], right["y"]], 1))
    inside = lambda c: ((c >= 0) & (c < GRID_N)).all(axis=1)
    cand = (np.abs(lc[:, None, :] - rc[None, :, :]).max(axis=2) <= 1) \
        & inside(lc)[:, None] & inside(rc)[None, :]
    assert count == cand.sum()
    assert cand[li[:count], ri[:count]].all()


# -- the named refusal ---------------------------------------------------------

def test_window_no_layout_holds_is_refused_by_name_before_a_dispatch(traced):
    """Radius 0.05 on cells of 0.07: no refinement keeps a bucket's side at
    least the radius, and 700 points in one cell ask for rung 1,024, which
    the Pallas extraction does not exist for."""
    rng = np.random.default_rng(14)
    left, right = _side(rng, 1_500, sigma=0.01), _side(rng, 1_500, sigma=0.01)
    fullest = _fullest(_grid(), left, right)
    assert PALLAS_TOP_RUNG < fullest <= 1_024
    op = _operator("pallas_interpret")
    with pytest.raises(JoinCapacityError) as err:
        list(op.run_soa(iter([left]), iter([right]), 0.05, dtype=np.float32))
    msg = str(err.value)
    assert f"holds {fullest} points" in msg          # the fullest cell
    assert "rung 1024" in msg and f"up to rung {PALLAS_TOP_RUNG}" in msg
    assert "bucket side 0.07" in msg and "refinement 1" in msg
    assert not [e for e in telemetry.events
                if str(e.get("name", "")).startswith("dispatch:join_window")]
    assert (op.join_refine, op.join_cap) == (1, 64)  # nothing was picked
    # the XLA program has no largest rung: the same pick climbs on there
    op._open_join(0.05, pallas=False)
    op._climb(fullest)
    assert (op.join_refine, op.join_cap) == (1, 1_024)
    # and the row of buckets bounds the refinement where the rung does not
    assert PALLAS_ROW_LANES // (100 * 4) >= REFINE_RUNG


# -- the trajectory join, through the one home of the contract ---------------------

def test_trajectory_join_holds_a_crowded_window_through_the_same_contract(
        traced):
    rng = np.random.default_rng(15)
    ids = 64
    left, right = _side(rng, 24_000, ids=ids), _side(rng, 24_000, ids=ids)
    conf = QueryConfiguration(QueryType.WindowBased, window_size=5,
                              slide_step=5)
    op = PointPointTJoinQuery(conf, _grid())
    (out,) = list(op.run_soa(iter([left]), iter([right]), 0.002,
                             num_segments=ids))
    _s, _e, lo, ro, dd, count, overflow = out
    ref = tjoin_tdrive.Reference(bbox=BBOX, grid_cells=GRID_N, radius=0.002,
                                 tol=1e-9, num_ids=ids)
    want = ref.tpairs(left["x"], left["y"], left["oid"],
                      right["x"], right["y"], right["oid"])
    assert ref.compare(want, lo, ro, dd, count, overflow) == []
    assert count == len(want[0]) > 1_000
    assert op.join_refine == 4 and op.join_cap <= REFINE_RUNG
    assert telemetry.snapshot()["tjoin"]["refine"] == 4
