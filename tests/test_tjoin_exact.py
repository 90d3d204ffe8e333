"""The trajectory join at scale (PR 39): ``TJoinQuery.run_soa`` / ``run``
under the point join's capacity and budget contract (``JoinCapacity``) with
the sparse dedup (``ops/trajectory.py:traj_pair_dedup_kernel``), each window
held to the benchmark's plain float64 reference
(``benchmark/references/tjoin_tdrive.py``), which is itself held to the
O(n^2) loop here."""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from benchmark.references import tjoin_tdrive
from span_tiling import assert_parents_tile, slow_consumer, x_spans
from spatialflink_tpu.grid import UniformGrid
from spatialflink_tpu.models.objects import Point
from spatialflink_tpu.operators import (
    PointPointJoinQuery,
    QueryConfiguration,
    QueryType,
)
from spatialflink_tpu.operators import join_query
from spatialflink_tpu.operators.base import check_oid_range
from spatialflink_tpu.operators.join_query import JoinCapacity, headroom_bucket
from spatialflink_tpu.operators.trajectory import (
    PointPointTJoinQuery,
    TJoinQuery,
)
from spatialflink_tpu.ops.trajectory import (
    MAX_TRAJ_IDS,
    traj_pair_dedup_kernel,
    traj_pair_ids,
)
from spatialflink_tpu.telemetry import telemetry

BBOX = (0.0, 0.0, 10.0, 10.0)
GRID = UniformGrid(20, 0.0, 10.0, 0.0, 10.0)
W10 = QueryConfiguration(QueryType.WindowBased, window_size=10, slide_step=10)
TOL = 1e-9  # x64 on under the conftest: float64 against float64


def _side(rng, n, ids, t_max=30_000, lo=0.0, hi=10.0):
    return {"ts": np.sort(rng.integers(0, t_max, n)).astype(np.int64),
            "x": rng.uniform(lo, hi, n), "y": rng.uniform(lo, hi, n),
            "oid": rng.integers(0, ids, n).astype(np.int64)}


def _chunks(side, n_chunks=4):
    bounds = np.linspace(0, len(side["ts"]), n_chunks + 1).astype(int)
    for a, b in zip(bounds[:-1], bounds[1:]):
        yield {k: v[a:b] for k, v in side.items()}


def _in_window(side, start, end):
    keep = (side["ts"] >= start) & (side["ts"] < end)
    return {k: v[keep] for k, v in side.items()}


def _held_to_reference(op, left, right, radius, ids, conf=W10, **kw):
    """Run ``run_soa`` and hold every window to the reference; returns the
    windows as yielded."""
    ref = tjoin_tdrive.Reference(bbox=BBOX, grid_cells=GRID.n, radius=radius,
                                 tol=TOL, num_ids=ids)
    got = list(op.run_soa(_chunks(left), _chunks(right), radius,
                          num_segments=ids, **kw))
    for start, end, lo, ro, dd, count, overflow in got:
        a, b = _in_window(left, start, end), _in_window(right, start, end)
        want = ref.tpairs(a["x"], a["y"], a["oid"], b["x"], b["y"], b["oid"])
        assert ref.compare(want, lo, ro, dd, count, overflow) == []
        assert ref.edge_tpairs(want) == 0  # nothing hides in the band
        assert count == len(want[0])
    return got


# -- the reference itself -----------------------------------------------------

@pytest.mark.parametrize("seed", [0, 1, 2])
def test_reference_equals_the_double_loop(seed):
    rng = np.random.default_rng(seed)
    a, b = _side(rng, 300, 12), _side(rng, 260, 9)
    ref = tjoin_tdrive.Reference(bbox=BBOX, grid_cells=20, radius=0.6,
                                 tol=0.0, num_ids=12)
    lo, ro, dd = ref.tpairs(a["x"], a["y"], a["oid"], b["x"], b["y"], b["oid"])
    want = tjoin_tdrive.brute_force(a["x"], a["y"], a["oid"], b["x"], b["y"],
                                    b["oid"], 0.6)
    assert len(want) > 20
    assert {(int(p), int(q)): d for p, q, d in zip(lo, ro, dd)} == \
        pytest.approx(want)
    keys = lo * 12 + ro
    assert (np.diff(keys) > 0).all()  # sorted by (left, right), each once


def test_reference_names_what_is_wrong():
    ref = tjoin_tdrive.Reference(bbox=BBOX, grid_cells=20, radius=1.0,
                                 tol=1e-3, num_ids=8)
    want = (np.array([0, 1, 2]), np.array([3, 3, 5]),
            np.array([0.5, 1.0005, 0.2]))  # the middle pair: in the band
    ok = (np.array([0, 2]), np.array([3, 5]), np.array([0.5, 0.2]))
    assert ref.compare(want, *ok, 2, 0) == []
    assert ref.compare(want, *want, 3, 0) == []  # the band's pair may be there
    assert ref.edge_tpairs(want) == 1
    assert ref.max_deviation(want, [0, 2], [3, 5], [0.5004, 0.2]) == \
        pytest.approx(4e-4)
    said = lambda *a: " ".join(ref.compare(want, *a))
    assert "overflow" in said(*ok, 2, 7)
    assert "missing" in said([0], [3], [0.5], 1, 0)
    assert "twice" in said([0, 0, 2], [3, 3, 5], [0.5, 0.5, 0.2], 3, 0)
    assert "beyond" in said([0, 2, 4], [3, 5, 4], [0.5, 0.2, 0.1], 3, 0)
    assert "differ" in said([0, 2], [3, 5], [0.6, 0.2], 2, 0)
    assert "out of range" in said([0, 9], [3, 5], [0.5, 0.2], 2, 0)
    assert "arrays of" in said([0, 2, -1], [3, 5, -1], [0.5, 0.2, 0.0], 2, 0)


# -- the kernel ---------------------------------------------------------------

def _host_ids(index, oid):
    """A pair list's indices (-1 padding) as ids, on the host."""
    index = np.asarray(index)
    return np.where(index >= 0, np.asarray(oid)[np.maximum(index, 0)], -1)


def _dedup(li, ri, dd, loid, roid, ids):
    tp = jax.jit(traj_pair_dedup_kernel)(
        jnp.asarray(_host_ids(li, loid), jnp.int32),
        jnp.asarray(_host_ids(ri, roid), jnp.int32),
        jnp.asarray(dd), np.int32(ids))
    return [np.asarray(a) for a in tp]


def _pair_list(rng, lanes, pairs, n_left, n_right):
    li = np.full(lanes, -1, np.int32)
    ri = np.full(lanes, -1, np.int32)
    dd = np.full(lanes, np.inf)
    at = rng.permutation(lanes)[:pairs]  # the pairs need not lie in front
    li[at] = rng.integers(0, n_left, pairs)
    ri[at] = rng.integers(0, n_right, pairs)
    dd[at] = rng.uniform(0, 1, pairs)
    return li, ri, dd, at


@pytest.mark.parametrize("ids", [5, 40, 16_384, MAX_TRAJ_IDS])
def test_dedup_kernel_matches_a_dict(rng, ids):
    """The minimum per (left id, right id), each pair once, sorted, -1 past
    the count — also with ids whose square (2^28, 2^31 - 88,047) no table
    could be made of, up to the largest the int32 key holds."""
    li, ri, dd, at = _pair_list(rng, 4096, 3000, 500, 400)
    loid = rng.integers(0, ids, 500)
    roid = rng.integers(0, ids, 400)
    loid[0], roid[0], li[at[0]], ri[at[0]] = ids - 1, ids - 1, 0, 0
    want = {}
    for p in at:
        key = (int(loid[li[p]]), int(roid[ri[p]]))
        want[key] = min(want.get(key, np.inf), dd[p])
    lo, ro, dmin, count = _dedup(li, ri, dd, loid, roid, ids)
    assert len(lo) == len(ro) == len(dmin) == 4096  # as long as the pair list
    assert int(count) == len(want)
    got = {(int(a), int(b)): d for a, b, d in
           zip(lo[:count], ro[:count], dmin[:count])}
    assert got == want and (ids - 1, ids - 1) in got
    assert (np.diff(lo[:count].astype(np.int64) * ids + ro[:count]) > 0).all()
    assert (lo[count:] == -1).all() and (ro[count:] == -1).all()
    assert (dmin[count:] == np.finfo(dmin.dtype).max).all()


def test_dedup_kernel_when_every_lane_is_a_pair_of_its_own(rng):
    li, ri, dd, _ = _pair_list(rng, 2048, 2048, 2048, 2048)
    li, ri = rng.permutation(2048), rng.permutation(2048)
    ids = np.arange(2048)  # every point its own trajectory
    lo, ro, dmin, count = _dedup(li, ri, dd, ids, ids, 2048)
    assert int(count) == 2048
    order = np.lexsort((ri, li))
    assert np.array_equal(lo, li[order]) and np.array_equal(ro, ri[order])
    assert np.array_equal(dmin, dd[order])


def test_dedup_kernel_on_an_empty_pair_list():
    lo, ro, dmin, count = _dedup(np.full(64, -1), np.full(64, -1),
                                 np.full(64, np.inf), [3], [4], 8)
    assert int(count) == 0 and (lo == -1).all() and (ro == -1).all()


def _dedup_jaxpr(lanes):
    args = (jnp.zeros(lanes, jnp.int32), jnp.zeros(lanes, jnp.int32),
            jnp.zeros(lanes), np.int32(MAX_TRAJ_IDS))
    return jax.make_jaxpr(traj_pair_dedup_kernel)(*args)


def _eqns(jp):
    for eqn in jp.eqns:
        yield eqn
        for sub in eqn.params.values():
            if hasattr(sub, "jaxpr"):
                yield from _eqns(sub.jaxpr)


@pytest.mark.parametrize("lanes", [1024, 4096])
def test_dedup_holds_no_array_sized_by_the_ids(lanes):
    """Every value of the traced program is O(lanes): one jaxpr whatever
    the ids (they are a traced scalar), none of its arrays larger than the
    pair list."""
    jaxpr = _dedup_jaxpr(lanes)
    sizes = [int(np.prod(v.aval.shape))
             for eqn in _eqns(jaxpr.jaxpr) for v in eqn.outvars]
    assert max(sizes) <= 2 * lanes
    import inspect

    params = inspect.signature(traj_pair_dedup_kernel).parameters
    assert "num_left" not in params and "max_tpairs" not in params


def test_dedup_gathers_nothing():
    """The pair list arrives as ids: the program is two sorts and
    elementwise work, with no lane gathered (a gather a lane runs element
    by element on a v5e, 8.2 ns a row: 33 ms of a 2²¹-lane pair list)."""
    prims = {eqn.primitive.name for eqn in _eqns(_dedup_jaxpr(2048).jaxpr)}
    assert "sort" in prims  # the jaxpr is the program's, not a stub's
    assert not {"gather", "scatter", "scatter-add", "dynamic_slice"} & prims


def test_pair_ids_maps_indices_and_keeps_the_padding():
    li, ri = np.array([2, -1, 0, 1]), np.array([0, -1, 2, 2])
    loid, roid = np.array([7, 3, 5]), np.array([1, 4, 6])
    lid, rid = jax.jit(traj_pair_ids)(li, ri, loid, roid)
    assert np.array_equal(lid, [5, -1, 7, 3])
    assert np.array_equal(rid, [1, -1, 6, 6])


# -- the operator -------------------------------------------------------------

def test_both_joins_share_one_capacity_contract():
    for name in ("_open_join", "_climb", "_grow_budget", "_window_call",
                 "_join_until_held"):
        assert getattr(TJoinQuery, name) is getattr(JoinCapacity, name)
        assert getattr(PointPointJoinQuery, name) is getattr(JoinCapacity,
                                                             name)
    assert headroom_bucket(1000) == 2048 and headroom_bucket(10) == 1024
    op = PointPointTJoinQuery(W10, GRID)
    assert (op.cap, op.join_cap, op.join_refine, op.join_budget,
            op.tpair_budget) == (64, 64, 1, 0, 0)
    assert op.last_join_backend is None


@pytest.mark.parametrize("seed", [3, 4])
def test_run_soa_tumbling_windows_equal_the_reference(seed):
    rng = np.random.default_rng(seed)
    left, right = _side(rng, 2500, 40), _side(rng, 2200, 30)
    op = PointPointTJoinQuery(W10, GRID)
    got = _held_to_reference(op, left, right, 0.25, 40)
    assert [(s, e) for s, e, *_ in got] == \
        [(0, 10_000), (10_000, 20_000), (20_000, 30_000)]
    assert all(count > 50 for *_, count, _o in got)
    assert op.last_join_backend == "xla"


@pytest.mark.parametrize("cap", [64, 8], ids=["constructor_default", "cap_8"])
def test_run_soa_holds_a_cell_fuller_than_its_cap(rng, cap):
    """90 points a side in one cell against the constructor's 64 (and
    against 8): the capacity climbs to the fullest cell, nothing is yielded
    short — the parent ran at ``cap`` and yielded the overflow."""
    left, right = _side(rng, 600, 10, t_max=9_000), _side(rng, 600, 10,
                                                          t_max=9_000)
    for s in (left, right):
        s["x"][:90] = rng.uniform(5.05, 5.45, 90)
        s["y"][:90] = rng.uniform(5.05, 5.45, 90)
    op = PointPointTJoinQuery(W10, GRID) if cap == 64 else \
        PointPointTJoinQuery(W10, GRID, cap=cap)
    (_s, _e, _lo, _ro, _dd, count, overflow), = _held_to_reference(
        op, left, right, 0.2, 10)
    assert overflow == 0 and count > 0
    assert op.join_cap == 128 and op.cap == cap


def test_run_soa_grows_both_budgets_and_counts_the_reruns(rng):
    """A pair budget of 1,024 against ~6,000 point pairs: it grows and the
    window is run again, the counters say so; the trajectory-pair budget
    (what the fetch programs are compiled for) follows the count with the
    same headroom. The dedup ran once, behind the run that held."""
    left, right = _side(rng, 3000, 400, t_max=9_000), _side(rng, 3000, 400,
                                                            t_max=9_000)
    op = PointPointTJoinQuery(W10, GRID)
    telemetry.enable()
    try:
        before = dict(telemetry.snapshot().get("tjoin", {}))
        mark = len(telemetry.events)
        (_s, _e, lo, _ro, _dd, count, _o), = _held_to_reference(
            op, left, right, 0.08, 400, max_pairs=1024)
        after = telemetry.snapshot()["tjoin"]
        names = [e["name"] for e in telemetry.events[mark:]
                 if e.get("ph") == "X"]
    finally:
        telemetry.disable()
    delta = {k: after[k] - before.get(k, 0) for k in
             ("windows", "pairs", "tpairs", "cap_retries", "budget_retries")}
    assert count > 1024 and len(lo) == count
    assert delta["windows"] == 1 and delta["tpairs"] == count
    assert delta["pairs"] >= count
    assert delta["budget_retries"] >= 1
    assert after["budget"] == op.join_budget >= headroom_bucket(delta["pairs"])
    assert after["tpair_budget"] == op.tpair_budget == headroom_bucket(count)
    assert after["cap"] == op.join_cap
    assert names.count("dispatch:traj_pair_dedup_kernel") == 1
    assert "tpair_retries" not in after  # nothing there can overflow


def test_run_soa_refuses_more_ids_than_the_key_holds(rng):
    left, right = _side(rng, 50, 10, t_max=9_000), _side(rng, 50, 10,
                                                         t_max=9_000)
    with pytest.raises(ValueError, match="46340"):
        list(PointPointTJoinQuery(W10, GRID).run_soa(
            _chunks(left), _chunks(right), 0.3,
            num_segments=MAX_TRAJ_IDS + 1))


def test_run_soa_one_sided_windows_yield_nothing(rng):
    left = _side(rng, 900, 10, t_max=30_000)
    right = _in_window(_side(rng, 900, 10, t_max=30_000), 10_000, 20_000)
    got = _held_to_reference(PointPointTJoinQuery(W10, GRID), left, right,
                             0.3, 10)
    assert [(s, count > 0) for s, _e, _lo, _ro, _dd, count, _o in got] == \
        [(0, False), (10_000, True), (20_000, False)]
    assert all(len(lo) == count for _s, _e, lo, _ro, _dd, count, _o in got)


def test_run_soa_with_ids_whose_square_no_table_could_hold(rng):
    """16,384 ids a side (2^28 possible keys; the dense table was 1.07 GB)
    and a few thousand pairs, in the suite's ordinary memory."""
    left, right = _side(rng, 4000, 16_384, t_max=9_000), _side(
        rng, 4000, 16_384, t_max=9_000)
    (_s, _e, lo, ro, _dd, count, _o), = _held_to_reference(
        PointPointTJoinQuery(W10, GRID), left, right, 0.12, 16_384)
    assert count > 2000 and max(lo.max(), ro.max()) > 16_000


@pytest.mark.parametrize("bad", [10, -1], ids=["past_the_range", "negative"])
def test_run_soa_refuses_an_id_outside_its_range(rng, bad):
    """-1 marks an empty slot of the bucket planes the ids ride through: a
    negative id would join nothing, silently."""
    left, right = _side(rng, 50, 10, t_max=9_000), _side(rng, 50, 10,
                                                         t_max=9_000)
    left["oid"][3] = bad
    with pytest.raises(ValueError, match="num_segments"):
        list(PointPointTJoinQuery(W10, GRID).run_soa(
            _chunks(left), _chunks(right), 0.3, num_segments=10))


def test_check_oid_range_refuses_minus_one():
    check_oid_range(np.array([0, 9, 3]), 10)
    check_oid_range(np.array([], np.int32), 10)
    with pytest.raises(ValueError, match=r"oid -1 outside \[0, num_segments"):
        check_oid_range(np.array([4, -1, 2], np.int32), 10)
    with pytest.raises(ValueError, match="oid 10 outside"):
        check_oid_range(np.array([4, 10], np.int32), 10)


# -- the ids ride the extraction ----------------------------------------------

SKEW_BBOX = (115.5, 39.6, 117.6, 41.1)
SKEW_GRID = UniformGrid(30, 115.5, 117.6, 39.6, 41.1)  # key cells of 0.07


def _uniform_window(rng):
    left, right = _side(rng, 1500, 40, t_max=9_000), _side(rng, 1400, 30,
                                                           t_max=9_000)
    return PointPointTJoinQuery(W10, GRID, cap=16), left, right, 0.3, 40


def _crowded_window(rng, monkeypatch):
    """``tests/test_join_skew.py``'s crowded shapes: Spider-Gaussian points
    whose fullest key cell is past four times a refinement rung of 16, so
    the buckets go on a 4 x finer grid."""
    from benchmark.references import spider_gaussian

    monkeypatch.setattr(join_query, "REFINE_RUNG", 16)

    def side(n):
        x, y = spider_gaussian.positions(
            rng.uniform(SKEW_BBOX[0], SKEW_BBOX[2], n),
            rng.uniform(SKEW_BBOX[1], SKEW_BBOX[3], n), SKEW_BBOX)
        return {"ts": np.sort(rng.integers(0, 5_000, n)).astype(np.int64),
                "x": x, "y": y, "oid": rng.integers(0, 64, n).astype(np.int64)}

    conf = QueryConfiguration(QueryType.WindowBased, window_size=5,
                              slide_step=5)
    return (PointPointTJoinQuery(conf, SKEW_GRID, cap=16), side(3_000),
            side(3_000), 0.006, 64)


@pytest.mark.parametrize("window", ["uniform", "crowded_refines"])
@pytest.mark.parametrize("backend", ["xla", "pallas_interpret"])
def test_run_soa_pairs_equal_the_index_then_gather_path(rng, monkeypatch,
                                                        backend, window):
    """``run_soa`` hands its id lanes to the extraction as the payload, so
    its pairs come out as ids and the dedup gathers none. Oracle — the path
    before that, written here: every run of the extraction is repeated with
    no payload (the points' indices), the indices are mapped to ids on the
    host and deduplicated; the trajectory pairs have to come out array for
    array the same, and the raw pair lists likewise, pair order included."""
    if window == "uniform":
        op, left, right, radius, ids = _uniform_window(rng)
    else:
        op, left, right, radius, ids = _crowded_window(rng, monkeypatch)
    fn, name = join_query.window_join_program(backend)
    runs = []

    def both(*args, left_payload=None, right_payload=None, **kw):
        got = fn(*args, left_payload=left_payload,
                 right_payload=right_payload, **kw)
        runs.append((got, fn(*args, **kw), left_payload, right_payload))
        return got

    monkeypatch.setattr(join_query, "window_join_program",
                        lambda backend=None: (both, name))
    telemetry.enable()
    try:
        before = dict(telemetry.snapshot().get("tjoin", {}))
        got = []
        for out in op.run_soa(_chunks(left), _chunks(right), radius,
                              num_segments=ids, dtype=np.float32):
            got.append((out, runs[-1]))
            runs.clear()
        after = telemetry.snapshot()["tjoin"]
    finally:
        telemetry.disable()
    assert len(got) == 1 and op.last_join_backend == name
    assert op.join_refine == (4 if window == "crowded_refines" else 1)
    ((_s, _e, lo, ro, dd, count, overflow),
     (res, plain, lpay, rpay)) = got[0]
    assert lpay is not None and rpay is not None
    lid = _host_ids(plain.left_index, lpay)
    rid = _host_ids(plain.right_index, rpay)
    assert np.array_equal(res.left_index, lid)
    assert np.array_equal(res.right_index, rid)
    assert np.array_equal(res.dist, plain.dist)
    want = [np.asarray(a) for a in jax.jit(traj_pair_dedup_kernel)(
        jnp.asarray(lid, jnp.int32), jnp.asarray(rid, jnp.int32),
        plain.dist, np.int32(ids))]
    assert overflow == 0 and count == int(want[3]) > 100
    for a, b in zip((lo, ro, dd), want[:3]):
        assert np.array_equal(a, b[:count])
    assert after["id_lanes"] - before.get("id_lanes", 0) == 1
    assert after["windows"] - before.get("windows", 0) == 1


def test_object_path_keeps_indices_and_equals_the_reference(rng):
    """``run`` joins batches through ``grid_hash_join_batches``, which
    carries indices: their ids are gathered before the dedup, and each
    window's pairs are the plain reference's; no window counts as one whose
    extraction carried the ids."""
    left, right = _side(rng, 1200, 9, t_max=20_000), _side(rng, 1100, 7,
                                                           t_max=20_000)

    def points(side, tag):
        return [Point(obj_id=f"{tag}{int(o)}", timestamp=int(t), x=float(x),
                      y=float(y))
                for t, x, y, o in zip(side["ts"], side["x"], side["y"],
                                      side["oid"])]

    ref = tjoin_tdrive.Reference(bbox=BBOX, grid_cells=GRID.n, radius=0.3,
                                 tol=TOL, num_ids=16)
    telemetry.enable()
    try:
        before = telemetry.snapshot().get("tjoin", {}).get("id_lanes", 0)
        seen = 0
        for res in TJoinQuery(W10, GRID).run(
                iter(points(left, "a")), iter(points(right, "b")), 0.3):
            a = _in_window(left, res.start, res.end)
            b = _in_window(right, res.start, res.end)
            want = ref.tpairs(a["x"], a["y"], a["oid"], b["x"], b["y"],
                              b["oid"])
            got = sorted((int(p.obj_id[1:]), int(q.obj_id[1:]), d)
                         for p, q, d in res.pairs)
            lo, ro, dd = (np.array(c) for c in zip(*got)) if got else \
                (np.empty(0, int), np.empty(0, int), np.empty(0))
            assert ref.compare(want, lo, ro, dd, len(got), 0) == []
            seen += len(got)
        after = telemetry.snapshot().get("tjoin", {}).get("id_lanes", 0)
    finally:
        telemetry.disable()
    assert seen > 50 and after == before


def _trips(shift, taxis=12, fixes=60):
    """``taxis`` straight runs of ``fixes`` points 0.01 apart; the other
    side's taxi of the same number runs ``shift`` beside it."""
    taxi = np.repeat(np.arange(taxis), fixes)
    step = np.tile(np.arange(fixes), taxis)
    order = np.argsort(step, kind="stable")
    side = {"ts": (step * 100).astype(np.int64),
            "x": 0.5 + 0.7 * taxi + 0.01 * step + shift,
            "y": 0.5 + 0.7 * taxi + shift,
            "oid": (taxi * 3 + 1).astype(np.int64)}
    return {k: v[order] for k, v in side.items()}


def test_run_soa_collapses_coherent_trips_to_one_pair_each():
    """The case the cell's uniform traffic lacks: 12 taxis a side, each 60
    fixes beside its twin — hundreds of point pairs a trajectory pair."""
    left, right = _trips(0.0), _trips(0.02)
    op = PointPointTJoinQuery(W10, GRID)
    telemetry.enable()
    try:
        before = dict(telemetry.snapshot().get("tjoin", {}))
        (_s, _e, lo, ro, dd, count, _o), = _held_to_reference(
            op, left, right, 0.05, 64)
        after = telemetry.snapshot()["tjoin"]
    finally:
        telemetry.disable()
    assert count == 12 and np.array_equal(lo, ro)
    assert np.array_equal(lo, np.arange(12) * 3 + 1)
    assert dd == pytest.approx(np.full(12, 0.02))  # dx = 0 two fixes on
    assert after["pairs"] - before.get("pairs", 0) > 12 * 60 * 3


def test_object_path_equals_run_soa(rng):
    left, right = _side(rng, 1500, 9, t_max=20_000), _side(rng, 1300, 7,
                                                           t_max=20_000)
    soa = {}
    for s, e, lo, ro, dd, _c, _o in PointPointTJoinQuery(W10, GRID).run_soa(
            _chunks(left), _chunks(right), 0.3, num_segments=16):
        soa[(s, e)] = {(int(a), int(b)): float(d)
                       for a, b, d in zip(lo, ro, dd)}

    def points(side, tag):
        return [Point(obj_id=f"{tag}{int(o)}", timestamp=int(t), x=float(x),
                      y=float(y))
                for t, x, y, o in zip(side["ts"], side["x"], side["y"],
                                      side["oid"])]

    op = TJoinQuery(W10, GRID, cap=4)  # the object path climbs too
    seen = 0
    for res in op.run(iter(points(left, "a")), iter(points(right, "b")), 0.3):
        got = {(int(a.obj_id[1:]), int(b.obj_id[1:])): d
               for a, b, d in res.pairs}
        assert got == pytest.approx(soa[(res.start, res.end)])
        assert len(got) == len(res.pairs)  # each pair once
        seen += len(got)
    assert seen > 50 and op.join_cap > 4


def test_tjoin_window_parent_tiles_the_window_and_no_consumer_time(rng):
    """One ``tjoin.window`` a two-sided window, from the loop's ask for it
    to the hand-back: the wait for the producer, the id check, the ship, the
    capacity pick, the extraction, the dedup and the fetches lie inside it,
    each crossing a ``d2h`` leaf, the consumer's time outside, both sides'
    assembly on the producer's thread; a window at settled sizes crosses
    twice (four scalars, then the pairs)."""
    left, right = _side(rng, 2500, 40, t_max=19_000), _side(rng, 2500, 40,
                                                            t_max=19_000)
    op = PointPointTJoinQuery(W10, GRID)
    plain = list(PointPointTJoinQuery(W10, GRID).run_soa(
        _chunks(left), _chunks(right), 0.25, num_segments=40))
    telemetry.enable()
    try:
        mark = len(telemetry.events)
        naps = []
        got = slow_consumer(op.run_soa(_chunks(left), _chunks(right), 0.25,
                                       num_segments=40), naps)
        events = x_spans(telemetry.events[mark:])
    finally:
        telemetry.disable()
    assert len(got) == len(plain) == 2
    for a, b in zip(plain, got):  # tracing changes no result
        assert a[:2] == b[:2] and a[5:] == b[5:]
        assert all(np.array_equal(u, v) for u, v in zip(a[2:5], b[2:5]))
    parents, inner = assert_parents_tile(events, "tjoin.window", naps)
    assert [p["args"]["n"] for p in parents] == \
        [int(((left["ts"] // 10_000) == k).sum()
             + ((right["ts"] // 10_000) == k).sum()) for k in (0, 1)]
    assembly = {e["tid"] for e in events
                if e["name"] in ("join.assemble_left", "join.assemble")}
    assert len(assembly) == 1 and parents[0]["tid"] not in assembly
    for p, names in zip(parents, inner):
        assert "join.assemble_left" not in names
        for once in ("join.await", "tjoin.ids", "h2d", "join.capacity",
                     "dispatch:traj_pair_dedup_kernel"):
            assert names.count(once) == 1, (once, names)
        assert names.index("join.await") < names.index("tjoin.ids") \
            < names.index("h2d") < names.index("join.capacity")
        assert names.count("d2h") == names.count("d2h.wait")
    # the first window's dedup waits for the join to hold (three crossings),
    # the second runs behind it: the four scalars, then the pairs
    assert [names.count("d2h") for names in inner] == [3, 2]
    assert "dispatch:head_pairs" in inner[1]
