"""The DAG's columnar pane assembler (streams/columns.py) against the
generic ``WindowAssembler`` it stands in for.

The generic assembler buffers event OBJECTS per window; the columnar one
buffers array elements per pane and fires a window as the concatenation
of its panes. Same streams in, and everything a node can observe must be
equal: which windows fire and when, their rows in arrival order, every
column of the view, the non-point events, the late-drop count, the hooks
at the fire site, and the interner's table after each window.
"""

import dataclasses
import gc
import math
import os
import pickle
import sys

import numpy as np
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if REPO not in sys.path:
    sys.path.insert(0, REPO)

from spatialflink_tpu import overload, slo  # noqa: E402
from spatialflink_tpu.apps.checkin import CheckInEvent  # noqa: E402
from spatialflink_tpu.checkpoint import (  # noqa: E402
    assembler_state,
    restore_assembler,
)
from spatialflink_tpu.faults import InjectedFault, faults  # noqa: E402
from spatialflink_tpu.models.objects import Point  # noqa: E402
from spatialflink_tpu.qserve import QServeCommand  # noqa: E402
from spatialflink_tpu.sncb.common import GpsEvent  # noqa: E402
from spatialflink_tpu.streams.columns import (  # noqa: E402
    ColumnarWindowAssembler,
    PaneEvents,
    WindowColumns,
)
from spatialflink_tpu.streams.windows import (  # noqa: E402
    SlidingEventTimeWindows,
    WindowAssembler,
)
from spatialflink_tpu.telemetry import telemetry  # noqa: E402
from spatialflink_tpu.utils.interning import Interner  # noqa: E402


@pytest.fixture(autouse=True)
def _clean():
    yield
    faults.disarm()
    telemetry.disable()


def _pair(size, slide, ooo):
    return (
        ColumnarWindowAssembler(SlidingEventTimeWindows(size, slide), ooo),
        WindowAssembler(SlidingEventTimeWindows(size, slide),
                        timestamp_fn=lambda e: e.timestamp,
                        max_out_of_orderness_ms=ooo),
    )


# ---------------------------------------------------------------------------
# Streams


def _gps(n, seed, step=100, devices=5, fields=lambda i: (None, None, None),
         t0=0):
    rng = np.random.default_rng(seed)
    return [
        GpsEvent(f"dev{int(rng.integers(devices))}",
                 float(rng.uniform(4.25, 4.5)), float(rng.uniform(50.75, 50.95)),
                 t0 + i * step, *fields(i))
        for i in range(n)
    ]


def _in_order():
    return _gps(400, 1)


def _out_of_order_inside_bound():
    evs = _gps(400, 2)
    rng = np.random.default_rng(2)
    for i in rng.choice(len(evs), 60, replace=False).tolist():
        evs[i].ts -= int(rng.integers(1, 4000))  # bound: 5,000
    return evs


def _late_beyond_bound():
    evs = _gps(400, 3)
    for i in range(50, 400, 37):
        evs[i].ts -= 11_000 + i  # no window of theirs is open
    for i in range(60, 400, 41):
        evs[i].ts -= 4_900       # late, but lands
    return evs


def _mixed_kinds():
    gps = _gps(150, 4, fields=lambda i: (float(i), None, 0.25))
    rng = np.random.default_rng(4)
    out = []
    for i, e in enumerate(gps):
        out.append(e)
        if i % 3 == 0:
            out.append(Point(obj_id=f"pt{i % 4}", timestamp=e.ts + 7,
                             x=float(rng.uniform(4.25, 4.5)),
                             y=float(rng.uniform(50.75, 50.95)),
                             ingestion_time=None if i % 2 else 1.5 + i))
        if i % 5 == 0:
            out.append(CheckInEvent(event_id=f"e{i}", device_id=f"r{i % 2}-in",
                                    user_id=f"u{i % 3}", timestamp=e.ts + 3))
        if i % 40 == 0:
            out.append(QServeCommand(timestamp=e.ts, action="unregister",
                                     uid=f"cmd{i}", qid=f"q{i}"))
    return out


def _mixed_kinds_out_of_order():
    evs = _mixed_kinds()
    rng = np.random.default_rng(14)
    for i in rng.choice(len(evs), 50, replace=False).tolist():
        e = evs[i]
        back = int(rng.integers(1, 4500))
        if isinstance(e, GpsEvent):
            e.ts -= back
        else:  # (commands and check-ins are frozen)
            evs[i] = dataclasses.replace(e, timestamp=e.timestamp - back)
    return evs


def _optional_fields_mid_stream():
    # every optional field absent for the first panes, then each turns up
    # (in different panes), goes again, and comes back as 0.0
    def fields(i):
        return (float(i) if 120 <= i < 200 or i > 330 else None,
                0.0 if i % 7 == 0 and i > 160 else None,
                float(-i) if 260 <= i < 262 else None)

    return _gps(400, 5, fields=fields)


def _only_others_then_points():
    cmds = [QServeCommand(timestamp=0, action="unregister", uid=f"b{i}",
                          qid=f"q{i}") for i in range(4)]
    pts = [Point(obj_id=None if i % 9 == 0 else f"p{i % 3}",
                 timestamp=200 * i, x=float(i), y=float(-i))
           for i in range(120)]
    return cmds + pts


def _jump_and_stragglers():
    evs = _gps(300, 6)
    for i, e in enumerate(evs):
        if i >= 200:
            e.ts += 60_000
            if i % 5 == 0:
                e.ts -= 3_000
    return evs


class _Tagged(GpsEvent):
    """A GpsEvent subclass: must come back as itself."""


def _kept_objects():
    evs = _gps(200, 7, fields=lambda i: (float(i), None, None))
    for i in range(10, 200, 23):
        e = evs[i]
        evs[i] = _Tagged(e.device_id, e.lon, e.lat, e.ts, e.gps_speed)
    for i in range(5, 200, 31):
        evs[i].fa = math.nan  # a NaN that is a value
    return evs


#: name → (size, slide, out-of-orderness, events)
CASES = {
    "in_order": (10_000, 5_000, 5_000, _in_order),
    "out_of_order_inside_bound": (10_000, 5_000, 5_000,
                                  _out_of_order_inside_bound),
    "late_beyond_bound": (10_000, 5_000, 5_000, _late_beyond_bound),
    "mixed_kinds": (10_000, 5_000, 5_000, _mixed_kinds),
    "mixed_kinds_out_of_order": (10_000, 5_000, 5_000,
                                 _mixed_kinds_out_of_order),
    "optional_fields_mid_stream": (10_000, 5_000, 5_000,
                                   _optional_fields_mid_stream),
    "only_others_then_points": (10_000, 5_000, 5_000,
                                _only_others_then_points),
    "jump_and_stragglers": (10_000, 5_000, 5_000, _jump_and_stragglers),
    "kept_objects": (10_000, 5_000, 5_000, _kept_objects),
    "tumbling_no_bound": (4_000, 4_000, 0, _out_of_order_inside_bound),
    "ten_panes_a_window": (10_000, 1_000, 2_000, _out_of_order_inside_bound),
    "size_not_a_multiple_of_slide": (10_000, 4_000, 3_000,
                                     _out_of_order_inside_bound),
    "gaps_between_windows": (2_000, 5_000, 1_000, _late_beyond_bound),
    "negative_timestamps": (10_000, 5_000, 5_000,
                            lambda: _gps(300, 8, t0=-17_300)),
}


def _drive(asm, events, flush=True):
    """[(index of the feed that fired it | None for flush, window)], and
    (dropped_late, watermark) after every feed."""
    fired, clock = [], []
    for i, e in enumerate(events):
        fired.extend((i, w) for w in asm.feed(e))
        clock.append((asm.dropped_late, asm.watermark))
    if flush:
        fired.extend((None, w) for w in asm.flush())
    return fired, clock


def _same_event(a, b):
    if isinstance(b, (GpsEvent, Point)) and type(b) in (GpsEvent, Point):
        # rebuilt from its row: equal field by field (a dataclass's ==),
        # of the same type, None where it was None
        return type(a) is type(b) and a == b
    return a is b  # kept as the object it was


@pytest.fixture(params=sorted(CASES))
def case(request):
    size, slide, ooo, make = CASES[request.param]
    events = make()
    col, gen = _pair(size, slide, ooo)
    return (request.param, _drive(col, events), _drive(gen, events), col, gen)


def test_fires_the_same_windows_at_the_same_feeds(case):
    _, (got, got_clock), (want, want_clock), col, gen = case
    assert [(i, w.start, w.end, len(w.events)) for i, w in got] == \
        [(i, w.start, w.end, len(w.events)) for i, w in want]
    assert got_clock == want_clock
    assert col.dropped_late == gen.dropped_late
    assert want  # not vacuous
    assert col._panes == {}  # flush leaves nothing behind


def test_window_events_come_back_in_arrival_order(case):
    _, (got, _), (want, _), _, _ = case
    for (_, g), (_, w) in zip(got, want):
        assert isinstance(g.events, PaneEvents)
        n = len(w.events)
        # by position first (built row by row), then the whole walk
        for p in (0, n // 2, n - 1, -1):
            assert _same_event(g.events[p], w.events[p])
        walked = list(g.events)
        assert len(walked) == n
        assert all(_same_event(a, b) for a, b in zip(walked, w.events))
        assert all(_same_event(a, b)
                   for a, b in zip(g.events[1:9:2], w.events[1:9:2]))
        with pytest.raises(IndexError):
            g.events[n]


def _assert_same_view(got: WindowColumns, want: WindowColumns):
    for name in ("ts", "lon", "lat", "gps_speed", "fa", "ff", "is_gps", "pos",
                 "oid"):
        a, b = getattr(got, name), getattr(want, name)
        assert a.dtype == b.dtype, name
        assert np.array_equal(a, b, equal_nan=a.dtype.kind == "f"), name
    assert got.ids == want.ids
    assert len(got.others) == len(want.others)
    assert all(a is b for a, b in zip(got.others, want.others))
    assert len(got) == len(want)


def test_every_column_equals_the_view_of_the_buffered_objects(case):
    name, (got, _), (want, _), _, _ = case
    it_got, it_want = Interner(), Interner()
    reordered = 0
    for (_, g), (_, w) in zip(got, want):
        a = WindowColumns.from_panes(g.events, it_got)
        b = WindowColumns.from_events(w.events, it_want)
        assert (a.source, b.source) == ("panes", "events")
        _assert_same_view(a, b)
        _assert_same_view(a.gps(), b.gps())
        assert np.array_equal(a.lonlat(), b.lonlat())
        # the interner's table after every window: dense ids in order of
        # first appearance over the window's rows
        assert it_got._to_key == it_want._to_key
        rows = np.arange(len(a.gps()))
        for by_ts in (False, True):
            ga, gb = a.gps().by_device(rows, by_ts), b.gps().by_device(rows, by_ts)
            assert all(np.array_equal(x, y) for x, y in zip(ga[:3], gb[:3]))
            assert ga[3] == gb[3]
        # ... and the view of the events the lazy sequence hands out
        c = WindowColumns.from_events(list(g.events), Interner())
        assert np.array_equal(c.oid, WindowColumns.from_events(
            w.events, Interner()).oid)
        _assert_same_view(
            WindowColumns.from_panes(g.events, Interner()), c)
        reordered += a.reordered
    if name in ("in_order", "negative_timestamps"):
        assert reordered == 0
    if name in ("out_of_order_inside_bound", "jump_and_stragglers",
                "ten_panes_a_window", "mixed_kinds_out_of_order"):
        assert reordered > 0  # the arrival-order sort was needed, and counted


def test_the_hooks_fire_where_the_generic_assembler_has_them(case, monkeypatch):
    name = case[0]
    size, slide, ooo, make = CASES[name]
    calls = []

    def spy(tag):
        return lambda *a, **kw: calls.append((tag, a, tuple(sorted(kw.items()))))

    monkeypatch.setattr(telemetry, "record_late_drop", spy("late"))
    monkeypatch.setattr(telemetry, "record_watermark_lag", spy("lag"))
    monkeypatch.setattr(slo, "on_window_fired", spy("slo"))
    monkeypatch.setattr(overload, "on_window_fired", spy("overload"))
    seen = []
    for asm in _pair(size, slide, ooo):
        calls.clear()
        _drive(asm, make())
        seen.append(list(calls))
    assert seen[0] == seen[1]
    assert any(tag == "overload" for tag, *_ in seen[0])


@pytest.mark.parametrize("form", ["panes", "buffers"])
def test_a_checkpoint_mid_stream_resumes_to_the_same_windows(case, form):
    """``panes``: the columnar assembler's own state, through pickle, at
    every 50th feed. ``buffers``: a checkpoint in the generic
    assembler's form restores into panes."""
    name = case[0]
    size, slide, ooo, make = CASES[name]
    events = make()
    want, want_clock = case[1] if form == "panes" else case[2]
    for cut in range(50, len(events), 50):
        first, donor = _pair(size, slide, ooo)
        if form == "buffers":
            first = donor
        head, _ = _drive(first, events[:cut], flush=False)
        blob = pickle.dumps(assembler_state(first), pickle.HIGHEST_PROTOCOL)
        second, _ = _pair(size, slide, ooo)
        restore_assembler(second, pickle.loads(blob))
        tail, clock = _drive(second, events[cut:])
        got = head + [(None if i is None else i + cut, w) for i, w in tail]
        assert [(i, w.start, w.end) for i, w in got] == \
            [(i, w.start, w.end) for i, w in want]
        assert clock == want_clock[cut:]
        for (_, g), (_, w) in zip(got, want):
            assert len(g.events) == len(w.events)
            if not isinstance(g.events, PaneEvents):
                continue  # fired by the generic donor before the cut
            a = WindowColumns.from_panes(g.events, Interner())
            b = (WindowColumns.from_panes(w.events, Interner())
                 if isinstance(w.events, PaneEvents)
                 else WindowColumns.from_events(w.events, Interner()))
            for col in ("ts", "lon", "lat", "gps_speed", "fa", "ff", "is_gps",
                        "pos", "oid"):
                assert np.array_equal(getattr(a, col), getattr(b, col),
                                      equal_nan=True), (cut, col)
            assert a.ids == b.ids
            assert [type(o) for o in a.others] == [type(o) for o in b.others]
            assert [o.timestamp for o in a.others] == \
                [o.timestamp for o in b.others]


def test_the_state_after_a_resume_equals_the_uninterrupted_one():
    size, slide, ooo, make = CASES["out_of_order_inside_bound"]
    events = make()
    whole, _ = _pair(size, slide, ooo)
    _drive(whole, events[:230], flush=False)
    first, _ = _pair(size, slide, ooo)
    _drive(first, events[:117], flush=False)
    second, _ = _pair(size, slide, ooo)
    second.restore(pickle.loads(pickle.dumps(first.state())))
    _drive(second, events[117:230], flush=False)
    assert pickle.dumps(second.state()) == pickle.dumps(whole.state())


def test_the_state_is_arrays_not_events():
    size, slide, ooo, make = CASES["in_order"]
    asm, gen = _pair(size, slide, ooo)
    events = make()
    _drive(asm, events[:300], flush=False)
    _drive(gen, events[:300], flush=False)
    blob = pickle.dumps(assembler_state(asm), pickle.HIGHEST_PROTOCOL)
    assert b"GpsEvent" not in blob
    assert b"GpsEvent" in pickle.dumps(assembler_state(gen),
                                       pickle.HIGHEST_PROTOCOL)
    buffered = sum(len(p.ts) for p in asm._panes.values())
    assert buffered > 100
    # ts 8 + lon 8 + lat 8 + code 4 bytes a row, and little else
    assert len(blob) < 28 * buffered + 2_000


def _reachable(root, kind):
    """The GC-tracked objects of ``kind`` that ``root`` leads to."""
    seen, stack, found = set(), [root], []
    while stack:
        obj = stack.pop()
        if id(obj) in seen or not gc.is_tracked(obj):
            continue
        seen.add(id(obj))
        if isinstance(obj, (type, type(sys))) or callable(obj):
            continue  # classes, modules, functions: not buffered state
        if isinstance(obj, kind):
            found.append(obj)
        stack.extend(gc.get_referents(obj))
    return found


def test_no_event_object_outlives_feed():
    """Between two feeds nothing the assembler holds is (or leads to) a
    GpsEvent or a Point — the cyclic GC has nothing of theirs to walk."""
    size, slide, ooo, _ = CASES["in_order"]
    asm, gen = _pair(size, slide, ooo)
    rng = np.random.default_rng(9)
    for i in range(500):
        e = GpsEvent(f"dev{i % 5}", float(rng.uniform(4.25, 4.5)),
                     float(rng.uniform(50.75, 50.95)), i * 100)
        before = sys.getrefcount(e)
        fired = asm.feed(e)
        assert sys.getrefcount(e) == before
        gen.feed(GpsEvent(e.device_id, e.lon, e.lat, e.ts))
        pt = Point(obj_id="p", timestamp=i * 100 + 1, x=4.3, y=50.8)
        before = sys.getrefcount(pt)
        fired += asm.feed(pt)
        assert sys.getrefcount(pt) == before
        if i % 50 == 0:
            assert _reachable(asm, (GpsEvent, Point)) == []
            assert _reachable(fired, (GpsEvent, Point)) == []
    assert len(_reachable(gen, GpsEvent)) > 100  # the probe does find them
    # what a full GC pass walks on the assembler's account: a few objects
    # a pane, not a few an event
    buffered = sum(p.count() for p in asm._panes.values())
    assert buffered > 200
    assert len(_reachable(asm, object)) < 40 * len(asm._panes) + 100 < buffered


def test_panes_stay_appendable_while_a_fired_window_is_held():
    """A fired window shares its last panes with the windows after it:
    building (and keeping) its view must not pin the panes' buffers."""
    size, slide, ooo, make = CASES["out_of_order_inside_bound"]
    asm, gen = _pair(size, slide, ooo)
    held = []
    for e in make():
        gen.feed(e)
        for w in asm.feed(e):
            held.append((w, WindowColumns.from_panes(w.events)))
    assert len(held) > 3
    # the held views did not move while their panes grew
    for w, cols in held:
        again = WindowColumns.from_panes(PaneEvents(w.events._cuts))
        assert np.array_equal(cols.ts, again.ts)
        assert np.array_equal(cols.oid, again.oid)
    assert len(asm._panes) <= (size + ooo) // math.gcd(size, slide) + 2


def test_window_feed_fault_site():
    asm, _ = _pair(10_000, 5_000, 5_000)
    events = _in_order()
    faults.arm([{"point": "window.feed", "at": 7}])
    for e in events[:6]:
        asm.feed(e)
    with pytest.raises(InjectedFault):
        asm.feed(events[6])
    # the event that met the fault was not half-appended
    assert sum(p.count() for p in asm._panes.values()) == 6
    asm.feed(events[6])
    assert sum(p.count() for p in asm._panes.values()) == 7


def test_a_changed_window_configuration_does_not_resume():
    asm, _ = _pair(10_000, 5_000, 5_000)
    _drive(asm, _in_order()[:100], flush=False)
    other, _ = _pair(9_000, 3_000, 5_000)
    with pytest.raises(ValueError, match="window configuration changed"):
        other.restore(asm.state())
