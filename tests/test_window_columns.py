"""The window's columnar view (streams/columns.py) against the object
walks it replaced.

Until PR 26 every SNCB node of the composed DAG walked ``win.events``
itself, one attribute at a time. Those bodies now live HERE, as the
plain reference: each node's rendered lines must equal the walk's,
byte for byte, on windows built to hit the places where a vectorised
form could silently differ (``None`` fields, timestamp ties, float
summation order, a device cut out by a predicate, mixed event kinds).
"""

import math
import os
import sys

import numpy as np
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if REPO not in sys.path:
    sys.path.insert(0, REPO)

from spatialflink_tpu import dag as dag_mod  # noqa: E402
from spatialflink_tpu import overload, qserve  # noqa: E402
from spatialflink_tpu.apps.checkin import CheckInEvent  # noqa: E402
from spatialflink_tpu.dag import (  # noqa: E402
    CheckInNode,
    DataflowDAG,
    FunctionNode,
    SNCB_BBOX,
    _toy_sncb_stream,
    build_sncb_dag,
    default_sncb_queries,
)
from spatialflink_tpu.models.objects import Point  # noqa: E402
from spatialflink_tpu.qserve import QServeCommand  # noqa: E402
from spatialflink_tpu.sncb.common import CRSUtils, GpsEvent  # noqa: E402
from spatialflink_tpu.sncb.ops import (  # noqa: E402
    TrajOut,
    TrajSpeedOut,
    VarOut,
    traj_speed,
    trajectory_wkt,
    variation,
)
from spatialflink_tpu.sncb.queries import _zone_filter  # noqa: E402
from spatialflink_tpu.streams.columns import (  # noqa: E402
    ColumnarWindowAssembler,
    PaneEvents,
    WindowColumns,
)
from spatialflink_tpu.streams.windows import (  # noqa: E402
    SlidingEventTimeWindows,
    WindowBatch,
)
from spatialflink_tpu.telemetry import telemetry  # noqa: E402
from spatialflink_tpu.utils.interning import Interner  # noqa: E402

NODES = ("q1", "q2", "q3", "q4", "q5", "staytime", "qserve")


@pytest.fixture(autouse=True)
def _clean():
    yield
    telemetry.disable()
    dag_mod.uninstall()
    qserve.uninstall()
    overload.uninstall()


# ---------------------------------------------------------------------------
# The plain reference: the per-event bodies as they stood before the view
# (dag.py and sncb/queries.py at PR 24), verbatim but for their names.


def _gps_events(win):
    return [e for e in win.events if isinstance(e, GpsEvent)]


def _by_device(events):
    groups = {}
    for e in events:
        groups.setdefault(e.device_id, []).append(e)
    return groups


def walk_q1(events, zones, backend="device"):
    return [CRSUtils.enrich(e)
            for e in _zone_filter(events, zones, keep_inside=True,
                                  backend=backend)]


def walk_q2(events, zones, start, end, var_fa_min=0.6, var_ff_max=0.5,
            backend="device"):
    kept = _zone_filter(events, zones, keep_inside=False, backend=backend)
    out = []
    for dev in sorted(groups := _by_device(kept)):
        evs = groups[dev]
        var_fa, var_ff = variation(evs)
        if var_fa > var_fa_min and var_ff <= var_ff_max:
            out.append(VarOut(dev, var_fa, var_ff, start, end, len(evs)))
    return out


def walk_q3(events, start, end):
    groups = _by_device(events)
    return [TrajOut(dev, trajectory_wkt(groups[dev]), start, end)
            for dev in sorted(groups)]


def walk_q4(events, start, end, min_lon, max_lon, min_lat, max_lat,
            t_min, t_max):
    return walk_q3(
        [e for e in events
         if min_lon <= e.lon <= max_lon and min_lat <= e.lat <= max_lat
         and t_min <= e.ts <= t_max],
        start, end,
    )


def walk_q5(events, zones, start, end, avg_threshold=50.0,
            min_threshold=20.0, backend="device"):
    fenced = _zone_filter(events, zones, keep_inside=True, backend=backend)
    out = []
    for dev in sorted(groups := _by_device(fenced)):
        wkt, avg_speed, min_speed = traj_speed(groups[dev])
        if avg_speed > avg_threshold or (
            min_speed == min_speed and min_speed > min_threshold
        ):
            out.append(TrajSpeedOut(dev, wkt, avg_speed, min_speed,
                                    start, end))
    return out


def walk_staytime(node, win):
    from spatialflink_tpu.apps.staytime import stay_time_window_soa

    evs = _gps_events(win)
    if not evs:
        return []
    grid = node.dag.grid
    ts = np.array([e.ts for e in evs], np.int64)
    oid = np.asarray(
        node.dag.interner.intern_many(e.device_id for e in evs), np.int64)
    xy = np.array([[e.lon, e.lat] for e in evs], np.float64)
    hit, dwell = stay_time_window_soa(ts, oid, xy, grid, node._kernel)
    return [(grid.cell_name(int(c)) if int(c) < grid.num_cells else "out",
             int(d)) for c, d in zip(hit, dwell)]


def walk_qserve(node, win):
    """The old node body: a fresh ``Point`` per GpsEvent, a new
    WindowBatch, the event-list entry."""
    events = []
    for e in win.events:
        if isinstance(e, QServeCommand):
            events.append(e)
        elif isinstance(e, GpsEvent):
            events.append(Point(obj_id=e.device_id, timestamp=e.ts,
                                x=e.lon, y=e.lat))
        elif isinstance(e, Point):
            events.append(e)
    return node.op.serve_window(WindowBatch(win.start, win.end, events),
                                node._kernel, dtype=node.dtype)


def walk(node, win):
    """``node``'s result for ``win`` the way the node computed it
    before the view."""
    name, evs = node.name, _gps_events(win)
    if name == "q1":
        return walk_q1(evs, node.zones)
    if name == "q2":
        return walk_q2(evs, node.zones, win.start, win.end,
                       node.var_fa_min, node.var_ff_max)
    if name == "q3":
        return walk_q3(evs, win.start, win.end)
    if name == "q4":
        return walk_q4(evs, win.start, win.end, *node.bbox, *node.t_range)
    if name == "q5":
        return walk_q5(evs, node.zones, win.start, win.end,
                       node.avg_threshold, node.min_threshold)
    if name == "staytime":
        return walk_staytime(node, win)
    return walk_qserve(node, win)


# ---------------------------------------------------------------------------
# Seeded windows


MIN_X, MAX_X, MIN_Y, MAX_Y = SNCB_BBOX
RISK = (4.354, 50.854)   # bundled high-risk zone centroid (dag._toy_sncb_stream)
FENCE = (4.404, 50.854)  # bundled Q5 fence centroid


def _boot(ts=0):
    return [QServeCommand(timestamp=ts, action="register",
                          uid=f"boot:{q.qid}", query=q)
            for q in default_sncb_queries()]


def _gps(n, seed, devices=7, none=0.0, ties=1, speed=(20.0, 110.0),
         near=(RISK, FENCE), device_of=None, shuffle_ts=False):
    """``n`` GpsEvents: a third near each of ``near``, the rest over the
    bbox; each optional field ``None`` with probability ``none``;
    ``ties`` consecutive events share a timestamp."""
    rng = np.random.default_rng(seed)
    xs = rng.uniform(MIN_X, MAX_X, n)
    ys = rng.uniform(MIN_Y, MAX_Y, n)
    for k, (cx, cy) in enumerate(near):
        xs[k::3] = cx + rng.normal(0.0, 0.004, len(xs[k::3]))
        ys[k::3] = cy + rng.normal(0.0, 0.004, len(ys[k::3]))
    ts = (np.arange(n) // ties) * 100
    if shuffle_ts:
        ts = rng.permutation(ts)
    fa, ff = rng.uniform(0.0, 1.0, n), rng.uniform(0.0, 0.4, n)
    sp = rng.uniform(*speed, n)
    gone = rng.random((3, n)) < none

    def opt(col, k, i):
        return None if gone[k, i] else float(col[i])

    return [
        GpsEvent(
            device_id=(device_of(i) if device_of else f"dev{i % devices}"),
            lon=float(xs[i]), lat=float(ys[i]), ts=int(ts[i]),
            gps_speed=opt(sp, 0, i), fa=opt(fa, 1, i), ff=opt(ff, 2, i))
        for i in range(n)
    ]


def _points(n, seed, t0=0):
    rng = np.random.default_rng(seed)
    return [Point(obj_id=f"pt{i % 3}", timestamp=t0 + 70 * i,
                  x=float(rng.uniform(MIN_X, MAX_X)),
                  y=float(rng.uniform(MIN_Y, MAX_Y))) for i in range(n)]


def _checkins(n):
    return [CheckInEvent(event_id=f"e{i}", device_id=f"r{i % 2}-in",
                         user_id=f"u{i % 3}", timestamp=90 * i)
            for i in range(n)]


def _interleave(*streams):
    out = []
    for group in zip(*streams):
        out.extend(group)
    return out


def _q4_cut():
    # dev0 sits wholly outside Q4's bbox (the middle half of the grid):
    # its trajectory must vanish from q4 and stay in q3.
    evs = _gps(240, 41, devices=4)
    for e in evs:
        if e.device_id == "dev0":
            e.lon, e.lat = MIN_X + 0.001, MIN_Y + 0.001
    return _boot() + evs


def _many_speeds():
    # ≥ 1,000 present speeds per device, every event inside the fence:
    # numpy's pairwise sum of these differs from the left-to-right sum.
    rng = np.random.default_rng(43)
    evs = _gps(2400, 43, devices=2, near=(FENCE, FENCE, FENCE),
               speed=(49.0, 51.5))
    for e in evs:
        e.lon = FENCE[0] + float(rng.normal(0.0, 0.0004))
        e.lat = FENCE[1] + float(rng.normal(0.0, 0.0004))
    return evs


CASES = {
    "all_fields": lambda: _boot() + _gps(300, 31),
    "gps_only_no_commands": lambda: _gps(300, 32),
    "all_none": lambda: _boot() + _gps(300, 33, none=1.0),
    "mixed_none": lambda: _boot() + _gps(300, 34, none=0.4),
    "ts_ties": lambda: _boot() + _gps(320, 35, devices=3, ties=16),
    "ts_out_of_order": lambda: _boot() + _gps(300, 36, shuffle_ts=True),
    "one_event_per_device": lambda: _boot() + _gps(
        64, 37, device_of=lambda i: f"solo{i:03d}"),
    "single_event": lambda: _boot() + _gps(1, 38),
    "empty": lambda: [],
    "no_gps": lambda: _boot() + _interleave(_points(30, 39), _checkins(30)),
    "mixed_kinds": lambda: _boot() + _interleave(
        _gps(120, 40), _points(120, 40), _checkins(120)),
    "q4_cuts_a_device": _q4_cut,
    "q5_many_speeds": _many_speeds,
}


def _lines(node, result, win):
    return list(node.render(result, win.start, win.end))


@pytest.fixture
def sncb_dags(tmp_path):
    """Two SNCB DAGs, one per way of computing — each with its own
    interner and qserve registry, so neither sees the other's state."""
    return (build_sncb_dag(str(tmp_path / "columns")),
            build_sncb_dag(str(tmp_path / "walk")))


@pytest.mark.parametrize("name", NODES)
@pytest.mark.parametrize("case", sorted(CASES))
def test_node_lines_equal_the_object_walk(sncb_dags, case, name):
    dag, ref = sncb_dags
    win = WindowBatch(0, 10**9, CASES[case]())
    node, ref_node = dag.node(name), ref.node(name)
    got = _lines(node, node.process(win, {}), win)
    if name in ("staytime", "qserve"):
        ref_node._kernel = node._kernel  # the old bodies made it lazily
    want = _lines(ref_node, walk(ref_node, win), win)
    assert got == want


def _fired_from_panes(events):
    """``events`` through the DAG's assembler (10 s / 5 s panes; a bound
    wide enough that the shuffled case lands whole): the windows it
    fires hold no event object, only cuts of its panes."""
    asm = ColumnarWindowAssembler(SlidingEventTimeWindows(10_000, 5_000),
                                  max_out_of_orderness_ms=60_000)
    fired = list(asm.stream(events))
    assert all(isinstance(w.events, PaneEvents) for w in fired)
    return fired


@pytest.mark.parametrize("name", NODES)
@pytest.mark.parametrize("case", sorted(CASES))
def test_node_lines_from_panes_equal_the_object_walk(sncb_dags, case, name):
    """The same seeded streams, fired from panes: every node computes
    from the concatenated columns what the walk computes from the
    window's events as a plain list."""
    dag, ref = sncb_dags
    node, ref_node = dag.node(name), ref.node(name)
    if name in ("staytime", "qserve"):
        node.process(WindowBatch(0, 1, []), {})  # make the kernel
        ref_node._kernel = node._kernel
    fired = _fired_from_panes(CASES[case]())
    assert fired or case == "empty"
    for win in fired:
        got = _lines(node, node.process(win, {}), win)
        listed = WindowBatch(win.start, win.end, list(win.events))
        assert got == _lines(ref_node, walk(ref_node, listed), listed)


def _assert_views_equal(got, want):
    for col in ("ts", "lon", "lat", "gps_speed", "fa", "ff", "is_gps", "pos",
                "oid"):
        a, b = getattr(got, col), getattr(want, col)
        assert a.dtype == b.dtype, col
        assert np.array_equal(a, b, equal_nan=a.dtype.kind == "f"), col
    assert got.ids == want.ids
    assert got.others == want.others
    assert got.interner._to_key == want.interner._to_key


@pytest.mark.parametrize("case", sorted(CASES))
def test_view_from_panes_equals_the_view_of_its_events(case):
    """``from_panes`` against ``from_events`` of the very events the
    lazy sequence hands out: all columns, ``pos``, ``ids``, ``oid``
    and the table behind it, ``gps()``, ``by_device``."""
    it_panes, it_events = Interner(), Interner()
    for win in _fired_from_panes(CASES[case]()):
        events = list(win.events)
        got = WindowColumns.from_panes(win.events, it_panes)
        want = WindowColumns.from_events(events, it_events)
        _assert_views_equal(got, want)
        _assert_views_equal(got.gps(), want.gps())
        assert got.gps().gps() is got.gps()
        assert [type(win.events[p]) for p in got.pos.tolist()] == \
            [type(events[p]) for p in want.pos.tolist()]
        rows = np.arange(len(got.gps()))
        for by_ts in (False, True):
            a = got.gps().by_device(rows, by_ts=by_ts)
            b = want.gps().by_device(rows, by_ts=by_ts)
            assert all(np.array_equal(x, y) for x, y in zip(a[:3], b[:3]))
            assert a[3] == b[3]
        assert np.array_equal(got.gps().metric_xy(), want.gps().metric_xy())


def test_cases_are_not_vacuous(sncb_dags):
    """Every node speaks in some case, the zone nodes where they must."""
    dag, _ = sncb_dags
    spoke = {n: set() for n in NODES}
    for case in sorted(CASES):
        win = WindowBatch(0, 10**9, CASES[case]())
        for n in NODES:
            node = dag.node(n)
            if _lines(node, node.process(win, {}), win):
                spoke[n].add(case)
    for n in NODES:
        assert "all_fields" in spoke[n], (n, spoke[n])
    assert "q5_many_speeds" in spoke["q5"]
    assert "mixed_none" in spoke["q2"]
    assert "no_gps" in spoke["qserve"]
    for n in ("q1", "q2", "q3", "q4", "q5", "staytime"):
        assert not spoke[n] & {"empty", "no_gps"}


def test_q5_average_is_the_left_to_right_sum(sncb_dags):
    """The case exists because the two sums differ: a pairwise sum
    would have changed a rendered digit."""
    dag, _ = sncb_dags
    win = WindowBatch(0, 10**9, _many_speeds())
    out = dag.node("q5").process(win, {})
    assert out
    differs = 0
    for o in out:
        sp = [e.gps_speed for e in win.events if e.device_id == o.device_id]
        assert len(sp) >= 1000
        assert o.avg_speed == sum(sp) / len(sp)
        differs += float(np.sum(np.array(sp))) / len(sp) != o.avg_speed
    assert differs


def test_q4_cut_device_is_in_q3_only(sncb_dags):
    dag, _ = sncb_dags
    win = WindowBatch(0, 10**9, _q4_cut())
    q3 = {o.device_id for o in dag.node("q3").process(win, {})}
    q4 = {o.device_id for o in dag.node("q4").process(win, {})}
    assert "dev0" in q3 and "dev0" not in q4 and q4


@pytest.mark.parametrize("backend", ["device", "numpy"])
def test_q1_event_list_entry_equals_the_walk(backend, sncb_dags):
    """``q1_window`` (the streaming q1_high_risk's call) is a thin
    entry over the same core: records equal the walk's, metric
    coordinates included."""
    from spatialflink_tpu.sncb.queries import q1_window

    dag, _ = sncb_dags
    evs = _gps(300, 51)
    zones = dag.node("q1").zones
    got = q1_window(evs, zones, backend=backend)
    want = walk_q1(evs, zones, backend=backend)
    assert got and [repr(g) for g in got] == [repr(w) for w in want]
    assert all(g.raw is w.raw for g, w in zip(got, want))


@pytest.mark.parametrize("name", ["q1", "q2", "q5"])
def test_fallback_twin_reads_the_same_view(sncb_dags, name):
    dag, ref = sncb_dags
    win = WindowBatch(0, 10**9, CASES["mixed_none"]())
    node, ref_node = dag.node(name), ref.node(name)
    evs = _gps_events(win)
    want = {
        "q1": lambda: walk_q1(evs, ref_node.zones, backend="numpy"),
        "q2": lambda: walk_q2(evs, ref_node.zones, win.start, win.end,
                              backend="numpy"),
        "q5": lambda: walk_q5(evs, ref_node.zones, win.start, win.end,
                              backend="numpy"),
    }[name]()
    got = node.fallback_process(win, {})
    assert _lines(node, got, win) == _lines(ref_node, want, win)
    assert got


# ---------------------------------------------------------------------------
# The view itself


def test_columns_of_a_mixed_window():
    evs = CASES["mixed_kinds"]()
    it = Interner()
    cols = WindowColumns.from_events(evs, it)
    pointlike = [(i, e) for i, e in enumerate(evs)
                 if isinstance(e, (GpsEvent, Point))]
    assert cols.pos.tolist() == [i for i, _ in pointlike]
    assert cols.others == [e for e in evs
                           if not isinstance(e, (GpsEvent, Point))]
    assert cols.is_gps.tolist() == [isinstance(e, GpsEvent)
                                    for _, e in pointlike]
    for col, dtype in ((cols.ts, np.int64), (cols.lon, np.float64),
                       (cols.lat, np.float64), (cols.oid, np.int32),
                       (cols.gps_speed, np.float64), (cols.fa, np.float64),
                       (cols.ff, np.float64), (cols.is_gps, np.bool_)):
        assert col.dtype == dtype and len(col) == len(pointlike)
    assert cols.ts.tolist() == [e.timestamp for _, e in pointlike]
    assert cols.lon.tolist() == [e.lon if isinstance(e, GpsEvent) else e.x
                                 for _, e in pointlike]
    assert it.decode(cols.oid.tolist()) == [e.obj_id for _, e in pointlike]
    gps = cols.gps()
    only = [e for e in evs if isinstance(e, GpsEvent)]
    assert [evs[p] for p in gps.pos.tolist()] == only
    assert gps.fa.tolist() == [e.fa for e in only]
    assert np.isnan(cols.fa[~cols.is_gps]).all()
    assert gps.gps() is gps
    # Metric coordinates: once, and the array the zone kernels always got.
    assert gps.metric_xy() is gps.metric_xy()
    assert np.array_equal(gps.metric_xy(), CRSUtils.enrich_batch(only))


def test_none_fields_read_nan():
    evs = _gps(50, 61, none=0.5)
    cols = WindowColumns.from_events(evs)
    for col, attr in ((cols.gps_speed, "gps_speed"), (cols.fa, "fa"),
                      (cols.ff, "ff")):
        want = [getattr(e, attr) for e in evs]
        assert [None if math.isnan(v) else v for v in col.tolist()] == want
    assert cols.gps() is cols and cols.others == []


def test_qserve_batch_equals_from_points(sncb_dags):
    """The served batch, straight from the columns, is the one
    ``PointBatch.from_points`` built from 200,000 fresh Points."""
    dag, ref = sncb_dags
    evs = CASES["mixed_kinds"]()
    cols = dag.columns(WindowBatch(0, 10, evs))
    from spatialflink_tpu.models.batch import PointBatch

    got = PointBatch.from_arrays(cols.lonlat(), cols.ts, cols.oid,
                                 dtype=np.float64).with_cells(dag.grid)
    pts = [Point(obj_id=e.device_id, timestamp=e.ts, x=e.lon, y=e.lat)
           if isinstance(e, GpsEvent) else e
           for e in evs if isinstance(e, (GpsEvent, Point))]
    op = ref.node("qserve").op
    want = op.point_batch(pts)
    for f in ("xy", "ts", "oid", "valid", "cell"):
        a, b = getattr(got, f), getattr(want, f)
        assert a.dtype == b.dtype and np.array_equal(a, b), f
    assert dag.interner._to_key == ref.interner._to_key


def test_interner_ids_across_two_windows(tmp_path):
    """Dense ids ride the unit checkpoint and qserve's rows: after two
    consecutive windows, the second bringing a device the first never
    saw, the table equals the one the per-node walks built."""
    def run(walked):
        dag = build_sncb_dag(str(tmp_path / ("walk" if walked else "cols")))
        first = _boot() + _gps(90, 71, devices=4)
        second = _gps(90, 72, devices=4)
        second[11].device_id = "latecomer"
        second[40].device_id = "latecomer"
        for k, evs in enumerate((first, second)):
            win = WindowBatch(k * 5000, k * 5000 + 10_000, evs)
            if not walked:
                dag._process_window(win)
                continue
            for name in dag.dag_nodes:
                node = dag.node(name)
                if name in ("staytime", "qserve"):
                    node.process(WindowBatch(0, 1, []), {})  # make kernel
                walk(node, win)
        return list(dag.interner._to_key)

    cols, walked = run(False), run(True)
    assert cols == walked
    assert cols.index("latecomer") > cols.index("dev3")


# ---------------------------------------------------------------------------
# One view per fired window, read by all seven nodes


def _run(dag, source):
    return list(dag.run(source))


def test_one_view_per_window_read_by_all_seven(tmp_path):
    dag = build_sncb_dag(str(tmp_path / "egress"))
    telemetry.enable()
    try:
        fired = _run(dag, _toy_sncb_stream(240)())
        spans = [e for e in telemetry.events if e.get("ph") == "X"]
    finally:
        telemetry.disable()
    assert len(fired) > 3
    walks = [e for e in spans if e["name"] == "window.dag"]
    views = [e for e in spans if e["name"] == "window.columns"]
    assert len(views) == len(walks) == len(fired)
    for w, v in zip(walks, views):  # inside the walk, before its nodes
        assert w["ts"] <= v["ts"]
        assert v["ts"] + v["dur"] <= w["ts"] + w["dur"] + 1
        assert v["args"]["gps"] <= v["args"]["events"] == w["args"]["events"]
    first_node = min(e["ts"] for e in spans if e["name"].startswith("node."))
    assert views[0]["ts"] + views[0]["dur"] <= first_node + 1
    assert dag.window_columns_built == len(fired)
    assert dag.window_columns_reads == 7 * len(fired)
    # every view came from the assembler's panes; the toy stream's
    # stragglers put some windows through the arrival-order sort
    sorted_back = sum(
        w.events.resolved().reordered for w in ColumnarWindowAssembler(
            SlidingEventTimeWindows(10_000, 5_000), 5_000,
        ).stream(_toy_sncb_stream(240)()))
    assert sorted_back > 0
    assert dag.snapshot()["window_columns"] == {
        "built": len(fired), "reads": 7 * len(fired),
        "from_panes": len(fired), "from_events": 0,
        "reordered": sorted_back}
    assert {v["args"]["source"] for v in views} == {"panes"}
    assert dag._columns is None  # alive for the walk only


def test_a_window_handed_in_as_a_list_counts_as_from_events(tmp_path):
    dag = build_sncb_dag(str(tmp_path / "egress"))
    telemetry.enable()
    try:
        dag._process_window(WindowBatch(0, 10_000, _boot() + _gps(60, 81)))
        fired = _fired_from_panes(_gps(60, 82))
        for win in fired:
            dag._process_window(win)
        views = [e for e in telemetry.events
                 if e.get("ph") == "X" and e["name"] == "window.columns"]
    finally:
        telemetry.disable()
    assert dag.snapshot()["window_columns"] == {
        "built": 1 + len(fired), "reads": 7 * (1 + len(fired)),
        "from_panes": len(fired), "from_events": 1, "reordered": 0}
    assert [v["args"]["source"] for v in views] == \
        ["events"] + ["panes"] * len(fired)


def test_a_dag_without_column_readers_builds_no_view(tmp_path):
    from spatialflink_tpu.grid import UniformGrid
    from spatialflink_tpu.operators.query_config import (
        QueryConfiguration,
        QueryType,
    )

    conf = QueryConfiguration(QueryType.WindowBased, window_size=2.0,
                              slide_step=1.0)
    nodes = [
        CheckInNode("checkin", {"r0": 2, "r1": 2}),
        FunctionNode("count", lambda win, results: len(win.events)),
    ]
    dag = DataflowDAG(conf, UniformGrid(8, 0.0, 8.0, 0.0, 8.0), nodes,
                      out_dir=str(tmp_path / "egress"))
    telemetry.enable()
    try:
        fired = _run(dag, iter(_checkins(60)))
        names = {e["name"] for e in telemetry.events}
    finally:
        telemetry.disable()
    assert fired and "window.dag" in names
    assert "window.columns" not in names
    assert dag.window_columns_built == dag.window_columns_reads == 0
    assert "window_columns" not in dag.snapshot()
    assert len(dag.interner) == 0
