"""Windowing semantics tests: Flink-compatible assignment, watermarks,
allowed lateness, count windows."""

from dataclasses import dataclass

import pytest

from spatialflink_tpu.streams.windows import (
    CountWindows,
    SlidingEventTimeWindows,
    TumblingEventTimeWindows,
    WindowAssembler,
)


@dataclass
class Ev:
    ts: int
    name: str = ""


def test_sliding_assignment():
    w = SlidingEventTimeWindows(10_000, 5_000)
    specs = w.assign(12_000)
    spans = {(s.start, s.end) for s in specs}
    assert spans == {(10_000, 20_000), (5_000, 15_000)}


def test_tumbling_assignment():
    w = TumblingEventTimeWindows(10_000)
    (s,) = w.assign(12_000)
    assert (s.start, s.end) == (10_000, 20_000)
    (s,) = w.assign(9_999)
    assert (s.start, s.end) == (0, 10_000)


def test_assignment_negative_ts():
    w = SlidingEventTimeWindows(10_000, 5_000)
    spans = {(s.start, s.end) for s in w.assign(-3_000)}
    assert spans == {(-5_000, 5_000), (-10_000, 0)}


def test_windows_fire_on_watermark():
    asm = WindowAssembler(
        TumblingEventTimeWindows(10_000), timestamp_fn=lambda e: e.ts
    )
    fired = []
    for ts in [1000, 5000, 9999, 10001]:
        fired += asm.feed(Ev(ts))
    # The event at 10001 advances the watermark past window [0,10000).
    assert len(fired) == 1
    assert (fired[0].start, fired[0].end) == (0, 10_000)
    assert [e.ts for e in fired[0].events] == [1000, 5000, 9999]
    # Flush fires the remaining [10000,20000) window.
    rest = asm.flush()
    assert len(rest) == 1 and rest[0].start == 10_000


def test_out_of_orderness_delays_firing():
    asm = WindowAssembler(
        TumblingEventTimeWindows(10_000),
        timestamp_fn=lambda e: e.ts,
        max_out_of_orderness_ms=2_000,
    )
    fired = asm.feed(Ev(1000)) + asm.feed(Ev(10_500))
    assert fired == []  # watermark = 8_500 < 10_000
    fired = asm.feed(Ev(12_100))  # watermark = 10_100
    assert len(fired) == 1
    assert [e.ts for e in fired[0].events] == [1000]


def test_allowed_lateness_refires():
    asm = WindowAssembler(
        TumblingEventTimeWindows(10_000),
        timestamp_fn=lambda e: e.ts,
        allowed_lateness_ms=5_000,
    )
    asm.feed(Ev(1000))
    fired = asm.feed(Ev(11_000))  # fires [0,10000) with 1 event
    assert len(fired) == 1 and len(fired[0].events) == 1
    late = asm.feed(Ev(9_000))  # late but within lateness → refire
    assert len(late) == 1
    assert [e.ts for e in late[0].events] == [1000, 9_000]
    asm.feed(Ev(16_000))  # watermark 16000 >= 10000+5000 → GC
    dropped = asm.feed(Ev(8_000))  # beyond lateness → dropped
    assert dropped == [] or all(w.start != 0 for w in dropped)
    assert asm.dropped_late >= 1


def test_sliding_event_in_multiple_windows():
    asm = WindowAssembler(
        SlidingEventTimeWindows(10_000, 5_000), timestamp_fn=lambda e: e.ts
    )
    out = []
    for ts in [7_000, 12_000, 21_000]:
        out += asm.feed(Ev(ts))
    out += asm.flush()
    spans = {(w.start, w.end): [e.ts for e in w.events] for w in out}
    assert spans[(0, 10_000)] == [7_000]
    assert spans[(5_000, 15_000)] == [7_000, 12_000]
    assert spans[(10_000, 20_000)] == [12_000]
    assert (15_000, 25_000) in spans and (20_000, 30_000) in spans


def test_late_drop_counted_per_event_not_per_window():
    """Flink late-side-output semantics: one late event = one drop, even
    when it maps to several expired sliding windows; an event that still
    lands in any live window is not dropped."""
    asm = WindowAssembler(
        SlidingEventTimeWindows(10_000, 2_000), timestamp_fn=lambda e: e.ts
    )
    asm.feed(Ev(1_000))
    asm.feed(Ev(40_000))  # watermark far ahead; windows of ts=1000 expired
    asm.feed(Ev(1_500))   # late: belongs to 5 expired windows → ONE drop
    assert asm.dropped_late == 1
    # ts=33_000 has expired windows (e.g. [24000,34000)) AND live ones
    # ([26000,36000)+) — landing in a live window means NOT dropped.
    asm.feed(Ev(33_000))
    assert asm.dropped_late == 1


def test_count_windows():
    cw = CountWindows(2, 1)
    buf = []
    fired = []
    for i in range(4):
        fired += cw.feed(buf, i)
    assert fired == [[0, 1], [1, 2], [2, 3]]
    cw2 = CountWindows(2)
    buf2, fired2 = [], []
    for i in range(5):
        fired2 += cw2.feed(buf2, i)
    assert fired2 == [[0, 1], [2, 3]]
