"""Round-2 SoA fast paths: TStats / TKnn / two-stream join, plus the
device-side tJoin pair dedup — each pinned bit-for-bit (or to f64 eps)
against the object path it accelerates (the host
Python loops in the trajectory operators capped throughput)."""

import numpy as np
import pytest

from spatialflink_tpu.grid import UniformGrid
from spatialflink_tpu.models.objects import Point
from spatialflink_tpu.operators import (
    PointPointJoinQuery,
    QueryConfiguration,
    QueryType,
)
from spatialflink_tpu.operators.trajectory import TJoinQuery, TKNNQuery, TStatsQuery
from spatialflink_tpu.utils.interning import Interner

GRID = UniformGrid(20, 0.0, 10.0, 0.0, 10.0)
W10 = QueryConfiguration(QueryType.WindowBased, window_size=10, slide_step=10)


def _chunks(ts, xs, ys, oids, n_chunks=4):
    bounds = np.linspace(0, len(ts), n_chunks + 1).astype(int)
    for a, b in zip(bounds[:-1], bounds[1:]):
        yield {"ts": ts[a:b], "x": xs[a:b], "y": ys[a:b], "oid": oids[a:b]}


def _stream(rng, n, n_obj=6, t_max=30_000):
    ts = np.sort(rng.integers(0, t_max, n)).astype(np.int64)
    xs = rng.uniform(0, 10, n)
    ys = rng.uniform(0, 10, n)
    oids = rng.integers(0, n_obj, n).astype(np.int32)
    return ts, xs, ys, oids


def _points(ts, xs, ys, oids):
    return [
        Point(obj_id=str(int(o)), timestamp=int(t), x=float(x), y=float(y))
        for t, x, y, o in zip(ts, xs, ys, oids)
    ]


def test_tstats_soa_matches_object_path(rng):
    ts, xs, ys, oids = _stream(rng, 3000)
    # Interner parity: the object path interns str(oid) in first-seen order;
    # feed the SoA path oids that ARE the dense ints of that interning.
    interner = Interner()
    dense = np.array([interner.intern(str(int(o))) for o in oids], np.int32)

    soa = {}
    op = TStatsQuery(W10, GRID)
    for s, e, spatial, temporal, count in op.run_soa(
        _chunks(ts, xs, ys, dense), num_segments=64
    ):
        soa[(s, e)] = (spatial, temporal, count)

    obj_op = TStatsQuery(W10, GRID)
    for res in obj_op.run(iter(_points(ts, xs, ys, oids))):
        spatial, temporal, count = soa[(res.start, res.end)]
        for oid_str, (sp, tp, ratio) in res.stats.items():
            i = interner.intern(oid_str)
            assert sp == pytest.approx(float(spatial[i]), rel=1e-12)
            assert tp == int(temporal[i])


def test_tknn_soa_matches_object_path(rng):
    ts, xs, ys, oids = _stream(rng, 2500)
    interner = Interner()
    dense = np.array([interner.intern(str(int(o))) for o in oids], np.int32)
    q = Point(x=5.0, y=5.0)
    r, k = 4.0, 4

    soa = {
        (s, e): (list(map(int, o)), [float(d) for d in dd])
        for s, e, o, dd, nv in TKNNQuery(W10, GRID).run_soa(
            _chunks(ts, xs, ys, dense), q, r, k, num_segments=64
        )
    }
    for res in TKNNQuery(W10, GRID).run(iter(_points(ts, xs, ys, oids)), q, r, k):
        got_o, got_d = soa[(res.start, res.end)]
        want = [(interner.intern(oid), d) for oid, d, _ in res.neighbors]
        assert got_o == [o for o, _ in want]
        for gd, (_, wd) in zip(got_d, want):
            assert gd == pytest.approx(wd, rel=1e-9)


@pytest.mark.slow
def test_join_soa_matches_object_path(rng):
    lts, lxs, lys, loids = _stream(rng, 2000)
    rng2 = np.random.default_rng(9)
    rts, rxs, rys, roids = _stream(rng2, 1500)
    r = 0.6

    soa_pairs = {}
    op = PointPointJoinQuery(W10, GRID)
    for s, e, li, ri, dd, count, overflow in op.run_soa(
        _chunks(lts, lxs, lys, loids), _chunks(rts, rxs, rys, roids), r
    ):
        assert overflow == 0
        # Map window-array indices back to (ts, x, y) identities.
        lsel = (lts >= s) & (lts < e)
        rsel = (rts >= s) & (rts < e)
        lt, lx_, ly_ = lts[lsel], lxs[lsel], lys[lsel]
        rt, rx_, ry_ = rts[rsel], rxs[rsel], rys[rsel]
        got = set()
        for a, b, d in zip(li, ri, dd):
            if a < 0:
                continue
            got.add((int(lt[a]), round(float(lx_[a]), 9), int(rt[b]),
                     round(float(rx_[b]), 9), round(float(d), 6)))
        soa_pairs[(s, e)] = got

    obj = PointPointJoinQuery(W10, GRID)
    left = _points(lts, lxs, lys, loids)
    right = [
        Point(obj_id=f"q{int(o)}", timestamp=int(t), x=float(x), y=float(y))
        for t, x, y, o in zip(rts, rxs, rys, roids)
    ]
    for res in obj.run(iter(left), iter(right), r):
        want = {
            (a.timestamp, round(a.x, 9), b.timestamp, round(b.x, 9),
             round(d, 6))
            for a, b, d in res.pairs
        }
        if (res.start, res.end) in soa_pairs:
            assert soa_pairs[(res.start, res.end)] == want
        else:
            assert not want


def test_tjoin_device_dedup_matches_bruteforce(rng):
    """TJoinQuery's pair set and min distances == brute force over all
    point pairs (the device segment-min dedup replaces the reference's
    dedup map AND round 1's host dict loop)."""
    lts, lxs, lys, loids = _stream(rng, 800, n_obj=5)
    rng2 = np.random.default_rng(4)
    rts, rxs, rys, roids = _stream(rng2, 700, n_obj=4)
    r = 0.8
    left = _points(lts, lxs, lys, loids)
    right = [
        Point(obj_id=f"q{int(o)}", timestamp=int(t), x=float(x), y=float(y))
        for t, x, y, o in zip(rts, rxs, rys, roids)
    ]

    results = list(TJoinQuery(W10, GRID).run(iter(left), iter(right), r))
    for res in results:
        got = {(a.obj_id, b.obj_id): d for a, b, d in res.pairs}
        # Brute force within this window.
        want = {}
        for a in left:
            if not (res.start <= a.timestamp < res.end):
                continue
            for b in right:
                if not (res.start <= b.timestamp < res.end):
                    continue
                d = float(np.hypot(a.x - b.x, a.y - b.y))
                if d <= r:
                    key = (a.obj_id, b.obj_id)
                    if key not in want or d < want[key]:
                        want[key] = d
        assert got.keys() == want.keys()
        for kk in got:
            assert got[kk] == pytest.approx(want[kk], rel=1e-9)
    assert any(res.pairs for res in results)


@pytest.mark.slow
def test_tjoin_run_soa_matches_object_path(rng):
    """run_soa's raw (left_oid, right_oid, min_dist) arrays == the object
    path's dedup'd pair set per window, through sliding windows — the
    round-2 gap: tJoin was the one trajectory operator with no SoA path."""
    conf = QueryConfiguration(QueryType.WindowBased, window_size=10,
                              slide_step=5)
    lts, lxs, lys, loids = _stream(rng, 6_000, n_obj=8)
    rng2 = np.random.default_rng(5)
    rts, rxs, rys, roids = _stream(rng2, 5_000, n_obj=6)
    r = 0.3
    left = _points(lts, lxs, lys, loids)
    right = _points(rts, rxs, rys, roids)

    obj = {}
    for res in TJoinQuery(conf, GRID, cap=256).run(iter(left), iter(right), r):
        obj[(res.start, res.end)] = {
            (a.obj_id, b.obj_id, round(d, 9)) for a, b, d in res.pairs
        }

    soa = {}
    for start, end, lo, ro, dd, count, overflow in TJoinQuery(
        conf, GRID, cap=256
    ).run_soa(
        _chunks(lts, lxs, lys, loids), _chunks(rts, rxs, rys, roids), r,
        num_segments=16,
    ):
        assert overflow == 0
        soa[(start, end)] = {
            (str(int(a)), str(int(b)), round(float(d), 9))
            for a, b, d in zip(lo, ro, dd)
        }
    # The object path skips windows where one side is empty only if BOTH
    # generators agree; compare on the union of spans with pairs.
    spans = set(obj) | set(soa)
    for span in spans:
        assert obj.get(span, set()) == soa.get(span, set()), span
    assert any(soa.values())


def test_traj_stats_sliding_matches_operator(rng):
    """Pane-decomposed tStats (10s/2s, 5x overlap) == the operator's
    per-window recompute, including start-boundary segment truncation."""
    from spatialflink_tpu.streams.panes import traj_stats_sliding

    n = 4000
    ts = np.sort(rng.integers(0, 30_000, n)).astype(np.int64)
    xy = rng.uniform(0, 10, (n, 2))
    oids = rng.integers(0, 8, n).astype(np.int64)
    conf = QueryConfiguration(QueryType.WindowBased, window_size=10, slide_step=2)

    interner = Interner()
    dense = np.array([interner.intern(str(int(o))) for o in oids], np.int64)
    res = traj_stats_sliding(ts, xy, dense, 8, 10_000, 2_000)
    by_start = {int(s): i for i, s in enumerate(res.starts)}

    pts = _points(ts, xy[:, 0], xy[:, 1], oids)
    checked = 0
    for r in TStatsQuery(conf, GRID).run(iter(pts)):
        i = by_start[r.start]
        for oid_str, (sp, tp, ratio) in r.stats.items():
            k = interner.intern(oid_str)
            assert sp == pytest.approx(float(res.spatial[i, k]), rel=1e-9)
            assert tp == int(res.temporal[i, k])
            checked += 1
    assert checked > 100


def test_traj_stats_sliding_extreme_overlap(rng):
    """The 10s/10ms reference overlap (1000 panes/window): sparse sanity —
    a single two-point trajectory counts exactly in the windows holding
    both points."""
    from spatialflink_tpu.streams.panes import traj_stats_sliding

    ts = np.array([5_000, 5_600], np.int64)
    xy = np.array([[1.0, 1.0], [4.0, 5.0]])
    res = traj_stats_sliding(ts, xy, np.zeros(2, np.int64), 1, 10_000, 10)
    has_seg = res.spatial[:, 0] > 0
    # Windows with the segment: start in (ts0 - size, ts0] → start ≤ 5000
    # and start > 5600 - 10000 → all fired windows with start ≤ 5000 that
    # still contain 5600.
    starts = res.starts[has_seg]
    assert starts.min() >= 5_600 - 10_000 + 10
    assert starts.max() == 5_000
    np.testing.assert_allclose(res.spatial[has_seg, 0], 5.0)
    # Windows containing only one endpoint: no segment.
    one_pt = (res.count[:, 0] == 1)
    assert (res.spatial[one_pt, 0] == 0).all()


def test_trange_soa_matches_object_path(rng):
    """TRange SoA fast path == object path hit sets (dense-id space)."""
    from spatialflink_tpu.models.objects import Point, Polygon
    from spatialflink_tpu.operators import (
        QueryConfiguration, QueryType, TRangeQuery,
    )

    conf = QueryConfiguration(QueryType.WindowBased, window_size=10, slide_step=5)
    n = 2500
    ts = np.sort(rng.integers(0, 30_000, n)).astype(np.int64)
    xs = rng.uniform(0, 10, n)
    ys = rng.uniform(0, 10, n)
    oids = rng.integers(0, 40, n).astype(np.int32)
    polys = [Polygon(rings=[np.array(
        [[3, 3], [4.2, 3], [4.2, 4.2], [3, 4.2], [3, 3]], float)])]

    pts = [Point(obj_id=str(o), timestamp=int(t), x=float(x), y=float(y))
           for t, x, y, o in zip(ts, xs, ys, oids)]
    op = TRangeQuery(conf, GRID)
    obj_res = {
        (r.start, r.end): sorted(int(t.obj_id) for t in r.trajectories)
        for r in op.run(iter(pts), polys)
    }
    bounds = np.linspace(0, n, 5).astype(int)
    chunks = [
        {"ts": ts[a:b], "x": xs[a:b], "y": ys[a:b], "oid": oids[a:b]}
        for a, b in zip(bounds[:-1], bounds[1:])
    ]
    soa_res = {
        (s, e): sorted(int(o) for o in hit_oids)
        for s, e, hit_oids, cnt in TRangeQuery(conf, GRID).run_soa(
            iter(chunks), polys, num_segments=64
        )
    }
    assert obj_res == soa_res and obj_res


def test_taggregate_soa_matches_object_path(rng):
    """TAggregate SoA path == object path per-cell aggregates (ALL mode
    compares dense-id keys against interner-mapped keys)."""
    from spatialflink_tpu.models.objects import Point
    from spatialflink_tpu.operators import (
        QueryConfiguration, QueryType, TAggregateQuery,
    )

    conf = QueryConfiguration(QueryType.WindowBased, window_size=10, slide_step=10)
    n = 2000
    ts = np.sort(rng.integers(0, 30_000, n)).astype(np.int64)
    xs = rng.uniform(0, 10, n)
    ys = rng.uniform(0, 10, n)
    oids = rng.integers(0, 25, n).astype(np.int32)
    pts = [Point(obj_id=str(o), timestamp=int(t), x=float(x), y=float(y))
           for t, x, y, o in zip(ts, xs, ys, oids)]

    for agg in ("SUM", "ALL"):
        obj_res = [
            (r.start, r.end, {
                c: (cnt, {str(k): v for k, v in d.items()})
                for c, (cnt, d) in r.cells.items()
            })
            for r in TAggregateQuery(conf, GRID, aggregate=agg).run(iter(pts))
        ]
        bounds = np.linspace(0, n, 4).astype(int)
        chunks = [
            {"ts": ts[a:b], "x": xs[a:b], "y": ys[a:b], "oid": oids[a:b]}
            for a, b in zip(bounds[:-1], bounds[1:])
        ]
        soa_res = [
            (r.start, r.end, {
                c: (cnt, {str(k): v for k, v in d.items()})
                for c, (cnt, d) in r.cells.items()
            })
            for r in TAggregateQuery(conf, GRID, aggregate=agg).run_soa(
                iter(chunks))
        ]
        assert obj_res == soa_res and obj_res, agg


def test_tfilter_soa_matches_object_path(rng):
    from spatialflink_tpu.models.objects import Point
    from spatialflink_tpu.operators import (
        QueryConfiguration, QueryType, TFilterQuery,
    )

    conf = QueryConfiguration(QueryType.WindowBased, window_size=10, slide_step=5)
    n = 1500
    ts = np.sort(rng.integers(0, 25_000, n)).astype(np.int64)
    xs = rng.uniform(0, 10, n)
    ys = rng.uniform(0, 10, n)
    oids = rng.integers(0, 20, n).astype(np.int32)
    pts = [Point(obj_id=str(o), timestamp=int(t), x=float(x), y=float(y))
           for t, x, y, o in zip(ts, xs, ys, oids)]
    wanted = [3, 7, 11]

    obj_res = {}
    for r in TFilterQuery(conf, GRID).run(iter(pts), [str(w) for w in wanted]):
        obj_res[(r.start, r.end)] = {
            t.obj_id: [tuple(c) for c in t.coords] for t in r.trajectories
        }
    bounds = np.linspace(0, n, 4).astype(int)
    chunks = [
        {"ts": ts[a:b], "x": xs[a:b], "y": ys[a:b], "oid": oids[a:b]}
        for a, b in zip(bounds[:-1], bounds[1:])
    ]
    soa_res = {}
    for s, e, o, t, xy, cnt in TFilterQuery(conf, GRID).run_soa(
        iter(chunks), wanted
    ):
        trajs = {}
        for oid_val in np.unique(o):
            m = o == oid_val
            trajs[str(int(oid_val))] = [tuple(c) for c in xy[m]]
        soa_res[(s, e)] = trajs
    assert obj_res == soa_res and obj_res
