"""The one traffic generator: a seeded stream, and the feed that hands it over.

A traffic file holds parameters only; this module turns them, the
configuration's stream shapes and ``--seed`` into

- a :class:`Stream`: events at a constant event-time rate, positions uniform
  over the configuration's bbox, ids round-robin or uniform, built in bulk
  before the window opens (a pool replayed cyclically with advancing
  timestamps where the traffic file sets ``pool_events``);
- a :class:`Feed`: the schedule. It hands the stream over in index ranges
  (*segments*), never slows because the system does, and is pulled by the
  system's own thread, so an event that is due and not yet pulled waits here:
  that wait is the backlog.

Traffic parameters (``benchmark/traffic/<name>.json``):

``mode``            ``flood`` (everything available at once) or ``paced``
``rate_eps``        paced: events per wall second, and the stream's event-time
                    rate too (a deployment at 16,000 EPS has 16,000 events to an
                    event-time second); flood streams keep the configuration's
``batch_events``    a segment's largest size: events released together
``warmup_results``  results produced at flood speed before the window opens
``pool_events``     replay a pool of this many events cyclically (optional);
                    a pooled flood has no end: the pool goes round for as
                    long as the system pulls
``stream_eps``      flood without a pool: the stream holds this many events per
                    second of ``--seconds`` (size it to >= 3x what the system
                    drains); refused beside ``pool_events`` in a flood
``rehearsal``       overrides for the toy-size rehearsal

Schedule. Event ``i`` (``i >= W``, the first event after the warm-up) is due at
``t_open + (i - W) / rate``; a segment is released when its LAST event is due,
so nothing is handed over early. A flood feed releases at once. The window
opens at the first pull after the warm-up's last result is out and closes
``--seconds`` later; the feed ends at the first pull after that. A bounded
stream that runs dry before then is an error (:class:`SourceDry`), never a
shorter window; the result's ``stream_used_share`` says how near a run came.
"""

from __future__ import annotations

import dataclasses
import math
import time
from typing import Any, Callable, Dict, Iterator, List, Optional, Tuple

import numpy as np


class SourceDry(RuntimeError):
    """The stream ended before the window did."""


@dataclasses.dataclass
class Windows:
    """Sliding event-time windows and the event that lets each one fire.

    Window ``k`` ends at ``end(k)``; it can fire once an event with a
    timestamp >= ``end(k) + fire_delay_ms`` is in (the watermark delay of a
    watermarked path, 0 on an in-order path). That event is its *trigger*.
    """

    size_ms: int
    slide_ms: int
    fire_delay_ms: int
    t0_ms: int

    def end(self, k: int) -> int:
        first_end = (self.t0_ms // self.slide_ms) * self.slide_ms + self.slide_ms
        return first_end + k * self.slide_ms

    def k_of(self, end_ms: int) -> int:
        k, rem = divmod(int(end_ms) - self.end(0), self.slide_ms)
        if rem:
            raise ValueError(f"window end {end_ms} is not on the slide grid")
        return k


@dataclasses.dataclass
class Stream:
    rate_eps: int        # event-time events per second
    t0_ms: int
    n_total: float       # events the stream holds; math.inf: a pooled flood
    x: np.ndarray        # float64, one cycle of the pool
    y: np.ndarray
    ids: np.ndarray      # int64 index into the configuration's id space

    @property
    def pool(self) -> int:
        return len(self.x)

    @property
    def bounded(self) -> bool:
        return self.n_total != math.inf

    def ts(self, lo: int, hi: int) -> np.ndarray:
        return self.t0_ms + (np.arange(lo, hi, dtype=np.int64) * 1000) \
            // self.rate_eps

    def trigger(self, windows: Windows, k: int) -> int:
        return trigger_index(self.rate_eps, windows, k)


def trigger_index(rate_eps: int, windows: Windows, k: int) -> int:
    """Index of window ``k``'s trigger in a stream whose event ``i`` carries the
    timestamp ``t0 + i * 1000 // rate``: the first one at or past
    ``end(k) + fire_delay_ms``."""
    d_ms = windows.end(k) + windows.fire_delay_ms - windows.t0_ms
    return max(0, -(-d_ms * int(rate_eps) // 1000))


def effective(params: Dict[str, Any], rehearsal: bool) -> Dict[str, Any]:
    """``params`` with its ``rehearsal`` block applied on top (toy sizes)."""
    out = {k: v for k, v in params.items() if k != "rehearsal"}
    if rehearsal:
        out.update(params.get("rehearsal", {}))
    return out


def check(params: Dict[str, Any]) -> None:
    """Refuse a traffic file whose keys contradict each other, as it stands
    and with its ``rehearsal`` block applied: a pooled flood has no length to
    give, any other flood has to give one."""
    for tr in (effective(params, False), effective(params, True)):
        if tr["mode"] != "flood":
            continue
        if tr.get("pool_events") and "stream_eps" in tr:
            raise ValueError(
                "a flood with pool_events replays its pool without an end: "
                "stream_eps means nothing there, take it out")
        if not tr.get("pool_events") and "stream_eps" not in tr:
            raise ValueError("a flood without pool_events needs stream_eps")


def warmup_end(stream_rate: int, windows: Windows,
               traffic: Dict[str, Any], split_at_triggers: bool) -> int:
    """W: the first event of the measured window. The warm-up's last trigger
    closes its segment when segments are split at triggers; else the whole
    batch that holds it belongs to the warm-up."""
    trig = trigger_index(stream_rate, windows,
                         int(traffic["warmup_results"]) - 1)
    if split_at_triggers:
        return trig + 1
    batch = int(traffic["batch_events"])
    return (trig // batch + 1) * batch


def build_stream(stream_cfg: Dict[str, Any], traffic: Dict[str, Any],
                 windows: Windows, seed: int, seconds: float,
                 split_at_triggers: bool) -> Tuple[Stream, int]:
    """The seeded stream for one run and W. Same seed, same stream."""
    check(traffic)
    paced = traffic["mode"] == "paced"
    rate = int(traffic["rate_eps"] if paced else stream_cfg["event_rate_eps"])
    w = warmup_end(rate, windows, traffic, split_at_triggers)
    pool = int(traffic.get("pool_events") or 0)
    if pool and not paced:
        n_total = math.inf
    else:
        per_s = rate if paced else int(traffic["stream_eps"])
        # Two slides of slack: the window closes at a pull, a little after
        # t_close.
        n_total = w + int(seconds * per_s) + 2 * windows.slide_ms * rate // 1000
        pool = min(pool or n_total, n_total)
    if pool < n_total and (pool * 1000) % (rate * windows.slide_ms):
        raise ValueError("pool_events must span a whole number of slides")
    rng = np.random.default_rng(seed)
    min_x, min_y, max_x, max_y = stream_cfg["bbox"]
    x = rng.uniform(min_x, max_x, pool)
    y = rng.uniform(min_y, max_y, pool)
    n_ids = int(stream_cfg["ids"])
    how = stream_cfg["id_assignment"]
    if how == "round_robin":
        ids = np.arange(pool, dtype=np.int64) % n_ids
    elif how == "uniform":
        ids = rng.integers(0, n_ids, pool).astype(np.int64)
    else:
        raise ValueError(f"unknown id_assignment {how!r}")
    return Stream(rate, windows.t0_ms, n_total, x, y, ids), w


class Feed:
    """Hands a stream over on its schedule and keeps the run's clock.

    The adapter pulls :meth:`segments` from the system's own thread and reports
    each result with :meth:`result`. ``split_at_triggers`` ends a segment right
    after every trigger and stamps the pull that follows it (``on_mark(k, t)``):
    for a driver that publishes its checkpoint between two pulls of the source,
    that pull is the first moment the result is out under its guarantee.
    """

    def __init__(self, stream: Stream, windows: Windows,
                 traffic: Dict[str, Any], w: int, seconds: float, *,
                 split_at_triggers: bool = False,
                 on_mark: Optional[Callable[[int, float], None]] = None,
                 clock: Callable[[], float] = time.perf_counter,
                 sleep: Callable[[float], None] = time.sleep):
        self.stream, self.windows = stream, windows
        self.paced = traffic["mode"] == "paced"
        self.rate = float(stream.rate_eps)
        self.batch = int(traffic["batch_events"])
        self.w = int(w)
        self.seconds = float(seconds)
        self.split = split_at_triggers
        self.on_mark = on_mark
        self.clock, self.sleep = clock, sleep
        self.on_open: List[Callable[[], None]] = []
        self.on_close: List[Callable[[], None]] = []
        self.t_open: Optional[float] = None
        self.t_close: Optional[float] = None
        self.t_closed: Optional[float] = None   # the pull that ended the feed
        self.idx_closed: Optional[int] = None
        #: per segment of the window: (first index, time the system asked for
        #: it, time it was handed over, seconds it had been released by then)
        self.pulls: List[Tuple[int, float, float, float]] = []
        #: (k, time the result was out), in and out of the window
        self.results: List[Tuple[int, float]] = []

    # -- schedule --------------------------------------------------------------

    def due(self, i: int) -> float:
        """Wall time event ``i >= W`` is due (paced feeds, window open)."""
        return self.t_open + (i - self.w) / self.rate

    def segments(self) -> Iterator[Tuple[int, int]]:
        s, clock = self.stream, self.clock
        lo, k = 0, 0
        mark = s.trigger(self.windows, 0) + 1 if self.split else None
        while True:
            now = clock()
            if mark is not None and lo == mark:
                if self.on_mark is not None:
                    self.on_mark(k, now)
                k += 1
                mark = s.trigger(self.windows, k) + 1
            if self.t_open is None and lo >= self.w:
                for hook in self.on_open:
                    hook()
                now = self.t_open = clock()
                self.t_close = now + self.seconds
            if self.t_open is not None and now >= self.t_close:
                self.t_closed, self.idx_closed = now, lo
                for hook in self.on_close:
                    hook()
                return
            if lo >= s.n_total:
                raise SourceDry(
                    f"the stream's {s.n_total} events were drained "
                    f"{self.t_close - now:.1f} s before the window closed: "
                    "size the traffic file's stream to the system")
            hi = min((lo // self.batch + 1) * self.batch, s.n_total)
            if mark is not None and lo < mark < hi:
                hi = mark
            if self.t_open is not None:
                handed, waited = now, 0.0
                if self.paced:
                    release = self.due(hi - 1)
                    if now < release:
                        self.sleep(release - now)
                        handed = clock()
                    waited = handed - release
                self.pulls.append((lo, now, handed, waited))
            yield lo, hi
            lo = hi

    # -- results ---------------------------------------------------------------

    def result(self, end_ms: int, t: Optional[float] = None) -> None:
        """Window ending at ``end_ms`` is out under the configuration's
        guarantee, at ``t`` (now, if not given)."""
        self.results.append((self.windows.k_of(end_ms),
                             self.clock() if t is None else t))

    def in_window(self) -> List[Tuple[int, float]]:
        return [(k, t) for k, t in self.results
                if self.t_open is not None and self.t_open < t <= self.t_close]


def end_to_end(feed: Feed) -> Dict[str, Any]:
    """The arithmetic of the end-to-end metrics, from the feed's clock.

    ``events_per_s``: between every two consecutive results inside the window,
    the events handed over from one to the other / the time from one to the
    other; the median of those rates. Between results, so a run neither gains
    nor loses a slide at its edges; a median, so one stalled slide on a shared
    host does not set the figure. ``events_per_s_mean`` is the same from the
    first result to the last, stalls and all.

    ``result_latency_ms``: per window, from the due time of its trigger to the
    result being out. Window length and the configured firing delay are
    semantics and stay out; queue wait, the window's work and the commit are in.

    ``late``: windows (paced) later than one slide, or due a full slide before
    the close and not out by it — so a feed cannot pass by falling behind.
    """
    s, wn = feed.stream, feed.windows
    res = sorted(feed.in_window())
    out: Dict[str, Any] = {"results": len(res), "attempted": len(res),
                           "late": [], "latency_ms": []}
    if len(res) >= 2:
        (k_a, t_a), (k_b, t_b) = res[0], res[-1]
        handed = s.trigger(wn, k_b) - s.trigger(wn, k_a)
        out["events_between_results"] = int(handed)
        out["seconds_between_results"] = t_b - t_a
        out["events_per_s_mean"] = handed / (t_b - t_a)
        out["events_per_s"] = float(np.median([
            (s.trigger(wn, k2) - s.trigger(wn, k1)) / (t2 - t1)
            for (k1, t1), (k2, t2) in zip(res, res[1:])]))
    if feed.paced and feed.t_open is not None:
        limit_ms = float(wn.slide_ms)
        seen = set()
        for k, t in res:
            trig = s.trigger(wn, k)
            if trig < feed.w:
                continue  # triggered during the warm-up: no due time
            seen.add(k)
            lat = (t - feed.due(trig)) * 1000.0
            out["latency_ms"].append(lat)
            if lat > limit_ms:
                out["late"].append(k)
        k = 0
        while True:  # due a full slide before the close, and never seen
            trig = s.trigger(wn, k)
            if trig >= s.n_total or \
                    feed.due(trig) + limit_ms / 1000.0 > feed.t_close:
                break
            if trig >= feed.w and k not in seen:
                out["late"].append(k)
                out["attempted"] += 1
            k += 1
        if out["latency_ms"]:
            out["result_latency_p50_ms"] = float(np.median(out["latency_ms"]))
            out["result_latency_max_ms"] = float(max(out["latency_ms"]))
    return out
