"""The generator's schedule and the end-to-end arithmetic, on a fake clock.

    python -m pytest benchmark/checks -q        (not part of tier-1)
"""

import os
import sys

import numpy as np
import pytest

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))

from benchmark.harness import spec, traffic  # noqa: E402
from benchmark.harness.main import _stream_room  # noqa: E402

T0 = 1_700_000_000_000
STREAM = {"event_rate_eps": 1000, "ids": 10, "id_assignment": "round_robin",
          "bbox": [0.0, 0.0, 1.0, 2.0], "t0_ms": T0}


class FakeClock:
    """Time passes only when told to: ``cost`` seconds per segment handed
    over (the system's work) and whatever the feed sleeps."""

    def __init__(self):
        self.now = 100.0

    def __call__(self):
        return self.now

    def sleep(self, s):
        self.now += s


def make(mode, seconds=30.0, delay=5000, split=True, **extra):
    wn = traffic.Windows(10_000, 5_000, delay, T0)
    tr = {"mode": mode, "batch_events": 100, "warmup_results": 2,
          "rate_eps": 1000, "stream_eps": 3000, **extra}
    stream, w = traffic.build_stream(STREAM, tr, wn, 7, seconds, split)
    clock = FakeClock()
    marks = []
    feed = traffic.Feed(stream, wn, tr, w, seconds, split_at_triggers=split,
                        on_mark=lambda k, t: marks.append((k, t)),
                        clock=clock, sleep=clock.sleep)
    return feed, stream, wn, w, clock, marks


def test_same_seed_same_stream_and_triggers():
    _f, a, wn, w, *_ = make("flood")
    _f, b, *_ = make("flood")
    assert np.array_equal(a.x, b.x) and np.array_equal(a.ids, b.ids)
    assert a.ids[:12].tolist() == [0, 1, 2, 3, 4, 5, 6, 7, 8, 9, 0, 1]
    # window 0 ends 5 s after t0 and fires on the first event 5 s later
    assert wn.end(0) == T0 + 5_000 and a.trigger(wn, 0) == 10_000
    assert a.ts(10_000, 10_001)[0] == T0 + 10_000
    assert a.ts(9_999, 10_000)[0] < T0 + 10_000
    # the warm-up ends right after the second window's trigger
    assert w == a.trigger(wn, 1) + 1 == 15_001


def test_flood_hands_over_in_order_splits_at_triggers_and_stamps():
    feed, stream, wn, w, clock, marks = make("flood", seconds=20.0)
    seen = []
    for lo, hi in feed.segments():
        assert hi - lo <= 100 and lo == (seen[-1][1] if seen else 0)
        seen.append((lo, hi))
        clock.now += 0.05  # the system's work on this segment
    ends = {hi for _lo, hi in seen}
    assert {stream.trigger(wn, k) + 1 for k in range(3)} <= ends
    assert [k for k, _t in marks][:3] == [0, 1, 2]
    assert feed.t_open is not None and feed.idx_closed > w
    assert feed.t_closed >= feed.t_close == feed.t_open + 20.0
    assert feed.pulls[0][0] == w        # the window's first segment
    assert all(p[3] == 0.0 for p in feed.pulls)  # a flood never waits


def test_paced_releases_a_segment_when_its_last_event_is_due():
    feed, _s, _wn, w, clock, _m = make("paced", seconds=12.0)
    for lo, hi in feed.segments():
        if feed.t_open is not None and lo >= w:
            assert clock.now >= feed.due(hi - 1) - 1e-9  # never early
        clock.now += 0.001
    lags = [p[3] for p in feed.pulls]
    assert max(lags) < 1e-6  # a system faster than the feed never lags
    assert feed.idx_closed - w == pytest.approx(12.0 * 1000, abs=200)


def test_paced_backlog_shows_as_lag_and_late_windows():
    feed, stream, wn, w, clock, _m = make("paced", seconds=30.0)
    for lo, hi in feed.segments():
        # a system that needs 0.15 s per 100 events (the feed offers 0.1 s)
        clock.now += 0.0015 * (hi - lo)
    for k, t in _m:
        feed.result(wn.end(k), t)
    e2e = traffic.end_to_end(feed)
    lags = [p[3] for p in feed.pulls]
    assert lags[-1] > lags[len(lags) // 2] > 1.0  # it grows through the run
    assert e2e["late"] and e2e["attempted"] >= len(e2e["late"])


def test_end_to_end_arithmetic_between_results():
    feed, stream, wn, w, clock, marks = make("paced", seconds=30.0)
    for lo, hi in feed.segments():
        clock.now += 0.000005 * (hi - lo)  # faster than the feed: no queue
        if hi - 1 in {stream.trigger(wn, k) for k in range(12)}:
            clock.now += 0.75  # the window's work and its commit
    for k, t in marks:
        feed.result(wn.end(k), t)
    e2e = traffic.end_to_end(feed)
    res = sorted(feed.in_window())
    assert e2e["results"] == len(res) >= 5 and not e2e["late"]
    # 5,000 events to a slide, whatever the edges of the window
    assert e2e["events_between_results"] == 5_000 * (res[-1][0] - res[0][0])
    # a result every 5 s of the schedule: 1,000 events/s, mean and median
    assert e2e["events_per_s"] == pytest.approx(1000.0, rel=1e-3)
    assert e2e["events_per_s_mean"] == pytest.approx(1000.0, rel=1e-3)
    # trigger due -> result out: its own 5 us of ingest + 0.75 s
    assert e2e["result_latency_p50_ms"] == pytest.approx(750.005, abs=0.01)
    assert e2e["attempted"] == e2e["results"]


def test_one_stalled_slide_moves_the_mean_and_not_the_median():
    feed, stream, wn, _w, clock, marks = make("flood", seconds=60.0,
                                              stream_eps=20_000)
    triggers = {stream.trigger(wn, k): k for k in range(40)}
    for lo, hi in feed.segments():
        clock.now += 0.0005 * (hi - lo)       # 2,000 events/s
        if triggers.get(hi - 1) == 8:
            clock.now += 2.5                   # one slide stalls
    for k, t in marks:
        feed.result(wn.end(k), t)
    e2e = traffic.end_to_end(feed)
    assert e2e["results"] >= 10
    assert e2e["events_per_s"] == pytest.approx(2000.0, rel=1e-6)
    assert e2e["events_per_s_mean"] < 1950.0


def test_a_stream_that_runs_dry_is_an_error():
    feed, *_ = make("flood", seconds=20.0, stream_eps=10)
    with pytest.raises(traffic.SourceDry):
        for _ in feed.segments():
            pass


def test_pool_replays_cyclically_and_must_span_whole_slides():
    wn = traffic.Windows(10_000, 5_000, 0, T0)
    tr = {"mode": "flood", "batch_events": 100, "warmup_results": 2,
          "pool_events": 20_000}
    stream, w = traffic.build_stream(STREAM, tr, wn, 3, 10.0, False)
    assert stream.pool == 20_000 and not stream.bounded
    assert w == 10_100  # the whole batch that holds the trigger
    assert stream.ts(20_000, 20_001)[0] == T0 + 20_000  # time goes on
    # the pool does not depend on how long the run is
    longer, _w = traffic.build_stream(STREAM, tr, wn, 3, 600.0, False)
    assert np.array_equal(stream.x, longer.x) \
        and np.array_equal(stream.y, longer.y)
    with pytest.raises(ValueError):
        traffic.build_stream(STREAM, {**tr, "pool_events": 20_500}, wn, 3,
                             10.0, False)
    # a paced pool keeps its length: the schedule ends it
    paced, pw = traffic.build_stream(
        STREAM, {**tr, "mode": "paced", "rate_eps": 1000}, wn, 3, 30.0, False)
    assert paced.bounded and paced.n_total == pw + 30_000 + 10_000
    assert paced.pool == 20_000


#: what the two pooled files gave a second of run until PR 31 (rehearsal
#: sizes): the feed below is pulled past a stream of that length
OLD_REHEARSAL_STREAM_EPS = {"knn.flood": 6_000_000, "join.flood": 600_000}


@pytest.mark.parametrize("cell_name", sorted(OLD_REHEARSAL_STREAM_EPS))
def test_a_pooled_flood_never_runs_dry(cell_name):
    """The cell's own files and its adapter's own ``_chunk``, at rehearsal
    size: a system that takes no time at all pulls far past where the stream
    used to end, and every chunk is the pool's rows with time gone on."""
    cell = spec.load_cell(cell_name)
    cfg = cell.config
    stream_cfg = traffic.effective(cfg["stream"], True)
    tr = traffic.effective(cell.traffic, True)
    assert "stream_eps" not in tr and "stream_eps" not in cell.traffic
    wn = traffic.Windows(int(cfg["window_s"] * 1000),
                         int(cfg["slide_s"] * 1000),
                         int(cfg["fire_delay_ms"]), int(stream_cfg["t0_ms"]))
    seconds = 6.0
    stream, w = traffic.build_stream(stream_cfg, tr, wn, 2**31 + 17, seconds,
                                     False)
    assert not stream.bounded and stream.pool == tr["pool_events"]
    clock = FakeClock()
    feed = traffic.Feed(stream, wn, tr, w, seconds, clock=clock,
                        sleep=clock.sleep)
    ad = spec.plugin("adapters", cfg["adapter"]).Adapter(
        cfg, stream_cfg, "/nonexistent", True)
    ad.stream, ad.windows = stream, wn
    ad.ts_pool = stream.ts(0, stream.pool)
    ad.cycle_ms = stream.pool * 1000 // stream.rate_eps
    old_end = w + int(seconds * OLD_REHEARSAL_STREAM_EPS[cell_name]) \
        + 2 * wn.slide_ms * stream.rate_eps // 1000
    last_ts, n, expect_lo = T0 - 1, 0, 0
    for lo, hi in feed.segments():
        assert lo == expect_lo and hi - lo == tr["batch_events"]
        expect_lo = hi
        c = ad._chunk(lo, hi)
        assert c["ts"][0] >= last_ts and c["ts"][-1] > c["ts"][0]
        last_ts = c["ts"][-1]
        if n % 997 == 0:  # in full now and then: the whole feed is long
            assert np.array_equal(c["ts"], stream.ts(lo, hi))
            a = lo % stream.pool
            assert np.shares_memory(c["x"], stream.x)
            assert np.array_equal(c["x"], stream.x[a:a + hi - lo])
            assert np.array_equal(c["oid"], stream.ids[a:a + hi - lo])
        n += 1
        if lo > 3 * old_end:
            clock.now += seconds + 1.0  # only now does the window close
    assert feed.idx_closed > 3 * old_end > 5 * stream.pool
    room = _stream_room(stream, feed)
    assert room == {"stream_events": None}  # no share: there is no end


def test_a_bounded_flood_says_how_much_of_its_stream_the_window_took():
    feed, stream, _wn, w, clock, _m = make("flood", seconds=20.0)
    for lo, hi in feed.segments():
        clock.now += 0.0005 * (hi - lo)  # 2,000 events/s of a 3,000/s stream
    room = _stream_room(stream, feed)
    assert room["stream_events"] == stream.n_total == w + 60_000 + 10_000
    assert room["stream_used_share"] == \
        (feed.idx_closed - w) / (stream.n_total - w)
    assert 0.55 < room["stream_used_share"] < 0.60
    # a paced stream is as long as its schedule: no share to give
    feed, stream, *_ = make("paced", seconds=12.0)
    for _ in feed.segments():
        pass
    assert set(_stream_room(stream, feed)) == {"stream_events"}


@pytest.mark.parametrize("block", ["file", "rehearsal"])
def test_a_pooled_flood_file_with_stream_eps_is_refused(block, monkeypatch):
    good = spec.load_cell("knn.flood").traffic
    bad = {**good, "rehearsal": dict(good["rehearsal"])}
    (bad if block == "file" else bad["rehearsal"])["stream_eps"] = 36_000_000
    with pytest.raises(ValueError, match="stream_eps"):
        traffic.check(bad)
    # at load, with the file's name; and by the generator itself
    load_json = spec._load_json
    monkeypatch.setattr(
        spec, "_load_json", lambda path:
        bad if path.endswith("knn_backlog.json") else load_json(path))
    with pytest.raises(spec.SpecError, match="knn_backlog.json.*stream_eps"):
        spec.load_cell("knn.flood")
    wn = traffic.Windows(10_000, 5_000, 0, T0)
    with pytest.raises(ValueError, match="stream_eps"):
        traffic.build_stream(STREAM, traffic.effective(bad, block != "file"),
                             wn, 3, 10.0, False)


def test_a_flood_without_a_pool_needs_its_length():
    with pytest.raises(ValueError, match="needs stream_eps"):
        traffic.check({"mode": "flood", "batch_events": 100,
                       "warmup_results": 2})
    for name in spec.cell_names():  # every file of the benchmark passes
        traffic.check(spec.load_cell(name).traffic)
