"""SoA streaming path: assembler parity with the object assembler and
end-to-end operator equivalence + throughput."""

import numpy as np
import pytest

from spatialflink_tpu.grid import UniformGrid
from spatialflink_tpu.models.objects import Point
from spatialflink_tpu.operators import (
    PointPointKNNQuery,
    PointPointRangeQuery,
    QueryConfiguration,
    QueryType,
)
from spatialflink_tpu.streams.soa import SoaWindowAssembler
from spatialflink_tpu.streams.windows import SlidingEventTimeWindows, WindowAssembler

GRID = UniformGrid(20, 0.0, 10.0, 0.0, 10.0)


def _chunks(ts, xs, ys, oids, n_chunks=5):
    bounds = np.linspace(0, len(ts), n_chunks + 1).astype(int)
    for a, b in zip(bounds[:-1], bounds[1:]):
        yield {"ts": ts[a:b], "x": xs[a:b], "y": ys[a:b], "oid": oids[a:b]}


def test_soa_assembler_matches_object_assembler(rng):
    n = 3000
    ts = np.sort(rng.integers(0, 60_000, n)).astype(np.int64)
    xs = rng.uniform(0, 10, n)
    ys = rng.uniform(0, 10, n)
    oids = rng.integers(0, 9, n).astype(np.int32)

    soa = SoaWindowAssembler(10_000, 5_000)
    soa_wins = {
        (w.start, w.end): w.count
        for w in soa.stream(_chunks(ts, xs, ys, oids))
    }

    obj = WindowAssembler(
        SlidingEventTimeWindows(10_000, 5_000), timestamp_fn=lambda e: e.timestamp
    )
    pts = [Point(obj_id=str(o), timestamp=int(t), x=float(x), y=float(y))
           for t, x, y, o in zip(ts, xs, ys, oids)]
    obj_wins = {}
    for w in obj.stream(iter(pts)):
        obj_wins[(w.start, w.end)] = len(w.events)
    assert soa_wins == obj_wins


def test_soa_assembler_gap_skip():
    """A huge event-time gap must not spin over empty windows."""
    ts = np.array([0, 1000, 10**12, 10**12 + 1], np.int64)
    soa = SoaWindowAssembler(10_000, 10)
    wins = list(soa.stream([{"ts": ts, "x": np.zeros(4), "y": np.zeros(4),
                             "oid": np.zeros(4, np.int32)}]))
    spans = {(w.start, w.end): w.count for w in wins}
    total = sum(spans.values())
    # Each event is in size/slide = 1000 windows.
    assert total == 4 * 1000


@pytest.mark.parametrize("ooo_ms", [0, 3_000])
def test_take_then_fire_is_feed(rng, ooo_ms):
    """``take`` says a window is due exactly when ``feed`` would fire one
    (both go by ``_due``), gaps and late events included."""
    ts = np.sort(rng.integers(0, 90_000, 4000)).astype(np.int64)
    ts[ts > 40_000] += 35_000  # a gap of several windows
    ts += rng.integers(-2_000, 1, len(ts))  # out of order within the bound
    fed, stepped = (SoaWindowAssembler(10_000, 5_000, ooo_ms)
                    for _ in range(2))
    for a in range(0, len(ts), 97):
        chunk = {"ts": ts[a:a + 97]}
        want = fed.feed(chunk)
        got = stepped.fire() if stepped.take(chunk) else []
        assert [(w.start, w.end, w.count) for w in got] == [
            (w.start, w.end, w.count) for w in want]
        # no window is left due, whether take said yes or no
        assert not stepped._due(stepped._max_ts - ooo_ms)
    assert fed.dropped_late == stepped.dropped_late


def test_soa_point_batches_span_only_where_a_window_fired():
    from spatialflink_tpu.operators.base import soa_point_batches
    from spatialflink_tpu.telemetry import telemetry

    conf = QueryConfiguration(QueryType.WindowBased, window_size=10,
                              slide_step=10)
    ts = np.array([1_000, 2_000, 61_000, 62_000], np.int64)  # 5 empty between
    xy = np.full(len(ts), 5.0)
    chunks = [{"ts": ts[i:i + 1], "x": xy[i:i + 1], "y": xy[i:i + 1]}
              for i in range(len(ts))]
    telemetry.enable()
    try:
        none = list(soa_point_batches(GRID, [], conf, span="t.assemble"))
        wins = list(soa_point_batches(GRID, chunks, conf, span="t.assemble"))
        spans = [e for e in telemetry.events if e["name"] == "t.assemble"]
    finally:
        telemetry.disable()
    assert none == [] and [w[0].count for w in wins] == [2, 2]
    assert [e["args"]["n"] for e in spans] == [2, 2]


@pytest.mark.parametrize("slide", [10, 5], ids=["tumbling", "sliding"])
def test_soa_point_batches_hands_its_clock_on_and_names_its_passes(slide):
    """Under a span the window carries the clock reading the span opened at
    (``t0_ns``: the operator's parent span opens there too), and the four
    passes lie inside the span, once a window — ``soa.consolidate`` once a
    firing, so a window after the first of one firing has three. Telemetry
    off, or no span asked for: no reading, no event, the same arrays."""
    from spatialflink_tpu.operators.base import soa_point_batches
    from spatialflink_tpu.telemetry import telemetry

    conf = QueryConfiguration(QueryType.WindowBased, window_size=10,
                              slide_step=slide)
    ts = np.arange(0, 40_000, 250, dtype=np.int64)
    rng = np.random.default_rng(3)
    cols = {"ts": ts, "x": rng.uniform(0, 10, len(ts)),
            "y": rng.uniform(0, 10, len(ts))}
    chunks = [{k: v[i:i + 16] for k, v in cols.items()}
              for i in range(0, len(ts), 16)]
    kept = len(telemetry.events)  # an earlier test's, until the next enable
    plain = list(soa_point_batches(GRID, chunks, conf, np.float32,
                                   span="t.assemble"))
    assert len(telemetry.events) == kept
    assert all(w[0].t0_ns is None for w in plain)
    telemetry.enable()
    try:
        bare = list(soa_point_batches(GRID, chunks, conf, np.float32))
        n_bare = len(telemetry.events)
        wins = list(soa_point_batches(GRID, chunks, conf, np.float32,
                                      span="t.assemble"))
        events = telemetry.events[n_bare:]
    finally:
        telemetry.disable()
    assert all(w[0].t0_ns is None for w in bare)
    assert len(plain) == len(wins) == len(bare) >= 4
    for a, b in zip(plain, wins):
        assert (a[0].start, a[0].end) == (b[0].start, b[0].end)
        assert all(np.array_equal(u, v) for u, v in zip(a[1:4], b[1:4]))
    spans = [e for e in events if e["name"] == "t.assemble"]
    assert [e["ts"] for e in spans] == [w[0].t0_ns // 1000 for w in wins]
    firings = 0
    for sp, w in zip(spans, wins):
        mine = [e for e in events if e["name"].startswith("soa.")
                and sp["ts"] <= e["ts"]
                and e["ts"] + e["dur"] <= sp["ts"] + sp["dur"] + 2]
        names = sorted(e["name"] for e in mine)
        firings += "soa.consolidate" in names
        assert [n for n in names if n != "soa.consolidate"] == [
            "soa.cells", "soa.center", "soa.pad"]
        assert all(e["args"]["n"] == w[0].count for e in mine
                   if e["name"] != "soa.consolidate")
        assert sum(e["dur"] for e in mine) <= sp["dur"]
    # every firing's consolidation is inside its first window's span
    assert firings == len([e for e in events
                           if e["name"] == "soa.consolidate"]) >= 3


def test_soa_assembler_out_of_order_within_bound(rng):
    base = np.sort(rng.integers(0, 30_000, 500)).astype(np.int64)
    jitter = rng.integers(-1500, 1500, 500)
    ts = base + jitter  # disorder within 3s bound
    soa = SoaWindowAssembler(10_000, 5_000, ooo_ms=3_000)
    wins = list(soa.stream([{"ts": ts[i:i+50], "x": np.zeros(len(ts[i:i+50])),
                             "y": np.zeros(len(ts[i:i+50])),
                             "oid": np.zeros(len(ts[i:i+50]), np.int32)}
                            for i in range(0, 500, 50)]))
    assert soa.dropped_late == 0
    # Every event lands in exactly size/slide = 2 windows.
    assert sum(w.count for w in wins) == 2 * 500


def test_soa_range_matches_object_path(rng):
    n = 2000
    ts = np.sort(rng.integers(0, 30_000, n)).astype(np.int64)
    xs = rng.uniform(0, 10, n)
    ys = rng.uniform(0, 10, n)
    oids = rng.integers(0, 7, n).astype(np.int32)
    conf = QueryConfiguration(QueryType.WindowBased, window_size=10, slide_step=5)
    q = Point(x=5.0, y=5.0)
    r = 2.0

    soa_res = {}
    for s_, e_, matched, dists in PointPointRangeQuery(conf, GRID).run_soa(
        _chunks(ts, xs, ys, oids), [q], r
    ):
        soa_res[(s_, e_)] = len(matched["ts"])
        # Matched arrays really are the matching events: all within radius.
        assert (np.hypot(matched["x"] - 5.0, matched["y"] - 5.0) <= r + 1e-12).all()
        assert len(dists) == len(matched["ts"])
    pts = [Point(obj_id=str(o), timestamp=int(t), x=float(x), y=float(y))
           for t, x, y, o in zip(ts, xs, ys, oids)]
    obj_res = {
        (res.start, res.end): len(res.objects)
        for res in PointPointRangeQuery(conf, GRID).run(iter(pts), [q], r)
    }
    # SoA path fires only non-empty windows; object path windows always have
    # events by construction here.
    assert {k: v for k, v in soa_res.items() if v} == {
        k: v for k, v in obj_res.items() if v
    }


def test_soa_knn_matches_object_path(rng):
    n = 2000
    ts = np.sort(rng.integers(0, 30_000, n)).astype(np.int64)
    xs = rng.uniform(0, 10, n)
    ys = rng.uniform(0, 10, n)
    oids = rng.integers(0, 7, n).astype(np.int32)
    conf = QueryConfiguration(QueryType.WindowBased, window_size=10, slide_step=10)
    q = Point(x=5.0, y=5.0)
    r, k = 4.0, 5

    soa = {
        (s, e): (list(o), [float(d) for d in dd])
        for s, e, o, dd, nv in PointPointKNNQuery(conf, GRID).run_soa(
            _chunks(ts, xs, ys, oids), q, r, k, num_segments=64
        )
    }
    pts = [Point(obj_id=str(o), timestamp=int(t), x=float(x), y=float(y))
           for t, x, y, o in zip(ts, xs, ys, oids)]
    for res in PointPointKNNQuery(conf, GRID).run(iter(pts), q, r, k):
        got_oids, got_dists = soa[(res.start, res.end)]
        assert [int(o) for o in got_oids] == [int(oid) for oid, _, _ in res.neighbors]
        for gd, (_, ed, _) in zip(got_dists, res.neighbors):
            assert gd == pytest.approx(ed, rel=1e-9)


def test_soa_knn_throughput(rng):
    """Streaming SoA path must comfortably beat the 20k EPS reference target."""
    import time

    n = 1_000_000
    ts = (np.arange(n) // 100).astype(np.int64)  # 100 events/ms → 10s of data
    xs = rng.uniform(0, 10, n)
    ys = rng.uniform(0, 10, n)
    oids = (np.arange(n) % 500).astype(np.int32)
    conf = QueryConfiguration(QueryType.WindowBased, window_size=5, slide_step=5)
    q = Point(x=5.0, y=5.0)
    # Warm the jitted program for this bucket/k/num_segments so the timed
    # region measures throughput, not first-call XLA compilation.
    warm = {"ts": ts[:70000], "x": xs[:70000], "y": ys[:70000], "oid": oids[:70000]}
    list(PointPointKNNQuery(conf, GRID).run_soa(iter([warm]), q, 4.0, 50,
                                                num_segments=512))
    t0 = time.perf_counter()
    out = list(
        PointPointKNNQuery(conf, GRID).run_soa(
            _chunks(ts, xs, ys, oids, n_chunks=20), q, 4.0, 50, num_segments=512
        )
    )
    dt = time.perf_counter() - t0
    eps = n / dt
    assert out
    assert eps > 500_000, f"SoA streaming too slow: {eps:.0f} EPS"


def test_soa_assembler_ooo_before_first_event():
    """An in-bound out-of-order event earlier than the first event must not
    lose its earliest windows (seeding regression)."""
    asm = SoaWindowAssembler(10_000, 5_000, ooo_ms=3_000)
    z = lambda n: {"x": np.zeros(n), "y": np.zeros(n), "oid": np.zeros(n, np.int32)}
    fired = asm.feed({"ts": np.array([10_000], np.int64), **z(1)})
    # Watermark 7_000: nothing complete yet.
    assert fired == []
    fired = asm.feed({"ts": np.array([9_500, 20_001], np.int64), **z(2)})
    spans = {(w.start, w.end): w.count for w in fired}
    # 9_500 arrived within the bound and belongs to [0,10_000) and
    # [5_000,15_000); [0,10_000) fires complete at watermark 17_001.
    assert spans[(0, 10_000)] == 1
    assert spans[(5_000, 15_000)] == 2  # 9_500 + 10_000
    assert asm.dropped_late == 0


def test_soa_knn_panes_matches_run_soa(rng):
    """run_soa_panes (pane-digest carry) must yield identical per-window
    (oids, dists) to run_soa full recomputation on sliding windows."""
    n = 3000
    ts = np.sort(rng.integers(0, 40_000, n)).astype(np.int64)
    xs = rng.uniform(0, 10, n)
    ys = rng.uniform(0, 10, n)
    oids = rng.integers(0, 9, n).astype(np.int32)
    conf = QueryConfiguration(QueryType.WindowBased, window_size=10, slide_step=2)
    q = Point(x=5.0, y=5.0)
    r, k = 4.0, 6

    def collect(gen):
        return {
            (s, e): ([int(o) for o in oo], [round(float(d), 12) for d in dd])
            for s, e, oo, dd, nv in gen
        }

    full = collect(PointPointKNNQuery(conf, GRID).run_soa(
        _chunks(ts, xs, ys, oids), q, r, k, num_segments=64))
    pane = collect(PointPointKNNQuery(conf, GRID).run_soa_panes(
        _chunks(ts, xs, ys, oids), q, r, k, num_segments=64))
    assert full == pane


def _geoms_to_ragged_chunks(geoms, interner, n_chunks=4):
    """Objects → ragged SoA chunks via each object's own packed() chain
    (the from_ragged contract: single closed/open boundary chains)."""
    rows = []
    for g in geoms:
        pv, pe = g.packed()
        ln = int(pe.sum()) + 1  # valid chain length
        rows.append((g.timestamp, interner.intern(g.obj_id), pv[:ln]))
    bounds = np.linspace(0, len(rows), n_chunks + 1).astype(int)
    for a, b in zip(bounds[:-1], bounds[1:]):
        part = rows[a:b]
        if not part:
            continue
        yield {
            "ts": np.array([r[0] for r in part], np.int64),
            "oid": np.array([r[1] for r in part], np.int32),
            "lengths": np.array([len(r[2]) for r in part], np.int64),
            "verts": np.concatenate([r[2] for r in part]),
        }


def test_geometry_soa_range_matches_object_path(rng):
    """Ragged-SoA geometry range == object path, including bbox pruning
    and polygon containment semantics."""
    from spatialflink_tpu.models.objects import Polygon
    from spatialflink_tpu.operators import PolygonPointRangeQuery
    from spatialflink_tpu.utils.interning import Interner

    conf = QueryConfiguration(QueryType.WindowBased, window_size=10, slide_step=5)
    polys = []
    for i in range(120):
        cx, cy = rng.uniform(1, 9), rng.uniform(1, 9)
        s = rng.uniform(0.1, 0.4)
        polys.append(Polygon(
            obj_id=f"poly{i}", timestamp=int(i * 250),
            rings=[np.array([[cx - s, cy - s], [cx + s, cy - s],
                             [cx + s, cy + s], [cx - s, cy + s],
                             [cx - s, cy - s]])],
        ))
    q = Point(x=5.0, y=5.0)
    r = 1.2

    obj_op = PolygonPointRangeQuery(conf, GRID)
    obj_res = {
        (res.start, res.end): sorted(
            (p.obj_id, round(float(d), 12))
            for p, d in zip(res.objects, res.dists)
        )
        for res in obj_op.run(iter(polys), [q], r)
    }

    soa_op = PolygonPointRangeQuery(conf, GRID)
    interner = Interner()
    chunks = list(_geoms_to_ragged_chunks(polys, interner))
    soa_res = {
        (s, e): sorted(
            (interner.lookup(int(o)), round(float(d), 12))
            for o, d in zip(oids, dists)
        )
        for s, e, idx, oids, dists, cnt in soa_op.run_soa(
            iter(chunks), [q], r
        )
    }
    assert obj_res == soa_res and obj_res


def test_geometry_soa_knn_matches_object_path(rng):
    from spatialflink_tpu.models.objects import LineString
    from spatialflink_tpu.operators import LineStringPointKNNQuery
    from spatialflink_tpu.utils.interning import Interner

    conf = QueryConfiguration(QueryType.WindowBased, window_size=10, slide_step=10)
    lines = []
    for i in range(90):
        start = rng.uniform(1, 9, 2)
        pts = start + np.cumsum(rng.uniform(-0.2, 0.2, (4, 2)), axis=0)
        lines.append(LineString(
            obj_id=f"ls{i}", timestamp=int(i * 300),
            coords=np.vstack([start, pts]),
        ))
    q = Point(x=5.0, y=5.0)
    r, k = 3.0, 6

    obj_res = [
        (res.start, res.end,
         [(o, round(d, 12)) for o, d, _ in res.neighbors])
        for res in LineStringPointKNNQuery(conf, GRID).run(iter(lines), q, r, k)
    ]
    soa_op = LineStringPointKNNQuery(conf, GRID)
    interner = Interner()
    chunks = list(_geoms_to_ragged_chunks(lines, interner))
    soa_res = [
        (s, e, [(interner.lookup(int(o)), round(float(d), 12))
                for o, d in zip(oids, dists)])
        for s, e, oids, dists, nv in soa_op.run_soa(
            iter(chunks), q, r, k, num_segments=128
        )
    ]
    assert obj_res == soa_res and obj_res


def test_soa_point_polygon_range_matches_object_path(rng):
    """The generalized point-stream run_soa must equal the object path for
    a polygon query set (the Q1-style hot path)."""
    from spatialflink_tpu.models.objects import Polygon
    from spatialflink_tpu.operators import PointPolygonRangeQuery

    n = 2500
    ts = np.sort(rng.integers(0, 30_000, n)).astype(np.int64)
    xs = rng.uniform(0, 10, n)
    ys = rng.uniform(0, 10, n)
    oids = rng.integers(0, 7, n).astype(np.int32)
    conf = QueryConfiguration(QueryType.WindowBased, window_size=10, slide_step=5)
    polys = [
        Polygon(rings=[np.array([[4, 4], [6, 4], [6, 6], [4, 6], [4, 4]], float)]),
        Polygon(rings=[np.array([[1, 7], [2, 7], [2, 8.5], [1, 8.5], [1, 7]], float)]),
    ]
    r = 0.4

    soa = {
        (s, e): sorted(zip(m["ts"].tolist(), np.round(dd, 12).tolist()))
        for s, e, m, dd in PointPolygonRangeQuery(conf, GRID).run_soa(
            _chunks(ts, xs, ys, oids), polys, r
        )
    }
    pts = [Point(obj_id=str(o), timestamp=int(t), x=float(x), y=float(y))
           for t, x, y, o in zip(ts, xs, ys, oids)]
    obj = {
        (res.start, res.end): sorted(
            zip((p.timestamp for p in res.objects),
                np.round(res.dists, 12).tolist())
        )
        for res in PointPolygonRangeQuery(conf, GRID).run(iter(pts), polys, r)
    }
    assert soa == obj and soa


def test_soa_point_linestring_range_matches_object_path(rng):
    from spatialflink_tpu.models.objects import LineString
    from spatialflink_tpu.operators import PointLineStringRangeQuery

    n = 2000
    ts = np.sort(rng.integers(0, 20_000, n)).astype(np.int64)
    xs = rng.uniform(0, 10, n)
    ys = rng.uniform(0, 10, n)
    oids = rng.integers(0, 5, n).astype(np.int32)
    conf = QueryConfiguration(QueryType.WindowBased, window_size=10, slide_step=10)
    lines = [LineString(coords=np.array([[2, 2], [5, 5], [8, 3]], float))]
    r = 0.5

    soa = {
        (s, e): sorted(zip(m["ts"].tolist(), np.round(dd, 12).tolist()))
        for s, e, m, dd in PointLineStringRangeQuery(conf, GRID).run_soa(
            _chunks(ts, xs, ys, oids), lines, r
        )
    }
    pts = [Point(obj_id=str(o), timestamp=int(t), x=float(x), y=float(y))
           for t, x, y, o in zip(ts, xs, ys, oids)]
    obj = {
        (res.start, res.end): sorted(
            zip((p.timestamp for p in res.objects),
                np.round(res.dists, 12).tolist())
        )
        for res in PointLineStringRangeQuery(conf, GRID).run(iter(pts), lines, r)
    }
    assert soa == obj and soa


def test_soa_large_polygon_set_uses_pruned_path(rng):
    """run_soa with >=64 exact-mode polygons rides the pruned/compact
    evaluator (parity + the operator grows persistent budgets)."""
    from spatialflink_tpu.models.objects import Polygon
    from spatialflink_tpu.operators import PointPolygonRangeQuery

    n = 2000
    ts = np.sort(rng.integers(0, 20_000, n)).astype(np.int64)
    xs = rng.uniform(0, 10, n)
    ys = rng.uniform(0, 10, n)
    oids = rng.integers(0, 5, n).astype(np.int32)
    conf = QueryConfiguration(QueryType.WindowBased, window_size=10, slide_step=10)
    polys = []
    for i in range(70):
        cx, cy = rng.uniform(1, 3), rng.uniform(1, 3)
        polys.append(Polygon(rings=[np.array(
            [[cx - .1, cy - .1], [cx + .1, cy - .1], [cx + .1, cy + .1],
             [cx - .1, cy + .1], [cx - .1, cy - .1]])]))
    r = 0.15

    op = PointPolygonRangeQuery(conf, GRID)
    op._cand_budget = 64  # force budget growth through the SoA path
    soa = {
        (s, e): sorted(zip(m["ts"].tolist(), np.round(dd, 12).tolist()))
        for s, e, m, dd in op.run_soa(_chunks(ts, xs, ys, oids), polys, r)
    }
    pts = [Point(obj_id=str(o), timestamp=int(t), x=float(x), y=float(y))
           for t, x, y, o in zip(ts, xs, ys, oids)]
    obj = {
        (res.start, res.end): sorted(
            zip((p.timestamp for p in res.objects),
                np.round(res.dists, 12).tolist())
        )
        for res in PointPolygonRangeQuery(conf, GRID).run(iter(pts), polys, r)
    }
    assert soa == obj
    assert op._cand_budget > 64  # the growth persisted
