"""Adapter: the window join of two point streams, fed the way a deployment
feeds it.

    SoA chunks {ts, x, y, oid} of the combined stream  ->  demultiplexer
    (even events: stream A, odd: stream B)  ->  the two chunk iterators of
    PointPointJoinQuery(conf, grid).run_soa(left, right, radius)

The operator is built with no ``cap``, ``join_backend`` or ``max_pairs``:
what it picks is what is measured. This path has no driver and no sink: a
result counts when the host holds the window's pairs, i.e. when ``run_soa``
yields them, fetched. The run ends like a consumer that goes away: the feed
stops, the open window is dropped.

Every window is compared with the plain reference. A pool replayed cyclically
makes windows repeat, so the reference is computed once per distinct window of
the pool; a repeat that equals an already checked result array for array is
held to that check, any other is compared in full.
"""

from __future__ import annotations

import collections
import time
from typing import Any, Dict, Iterator, List, Optional, Tuple

import numpy as np

from benchmark.harness import spec

#: the operator's join counters and gauges, as ``telemetry.snapshot()["join"]``
#: names them; ``extras`` hands the readers their change over the window
JOIN_COUNTERS = ("pairs", "windows", "cap_retries", "budget_retries")


class Demux:
    """One feed, two chunk iterators. A side that asks with nothing queued
    pulls the next segment, keeps its half and queues the other side's: the
    feed is never pulled while the asking side still holds a chunk, so neither
    side is ever more than one segment ahead of what it was handed."""

    def __init__(self, segments: Iterator[Tuple[int, int]], chunk,
                 clock=time.perf_counter):
        self.segments, self.chunk, self.clock = segments, chunk, clock
        self.queues = (collections.deque(), collections.deque())
        self.pulled = 0           # segments taken from the feed
        self.handed = [0, 0]      # chunks handed to each side
        self.t_pulled = 0.0       # when the newest segment was handed over

    def side(self, which: int) -> Iterator[Dict[str, np.ndarray]]:
        mine = self.queues[which]
        while True:
            if not mine and not self._pull():
                return
            self.handed[which] += 1
            yield mine.popleft()

    def _pull(self) -> bool:
        seg = next(self.segments, None)
        if seg is None:
            return False
        lo, hi = seg
        self.t_pulled = self.clock()
        self.pulled += 1
        c = self.chunk(lo, hi)
        first = lo % 2  # event i is A's when i is even
        for which in (0, 1):
            start = (which - first) % 2
            self.queues[which].append({k: v[start::2] for k, v in c.items()})
        return True


class Adapter:
    #: a result is out when the operator yields it: no stamps at triggers
    split_at_triggers = False

    def __init__(self, config: Dict[str, Any], stream_cfg: Dict[str, Any],
                 workdir: str, rehearsal: bool):
        self.cfg, self.stream_cfg = config, stream_cfg
        self.grid_cells = int(
            (config.get("rehearsal", {}) if rehearsal else {}).get(
                "grid_cells", config["grid_cells"]))
        self.got: List[Tuple[int, Any, Any, Any, int, int]] = []
        self.window_spans: List[Tuple[str, float, float]] = []
        self.join_open: Optional[Dict[str, int]] = None
        self.join_close: Optional[Dict[str, int]] = None

    def prepare(self, stream, windows) -> None:
        from spatialflink_tpu.grid import UniformGrid
        from spatialflink_tpu.operators import (
            PointPointJoinQuery,
            QueryConfiguration,
            QueryType,
        )

        self.stream, self.windows = stream, windows
        min_x, min_y, max_x, max_y = self.stream_cfg["bbox"]
        grid = UniformGrid(self.grid_cells, min_x, max_x, min_y, max_y)
        conf = QueryConfiguration(
            QueryType.WindowBased, window_size=float(self.cfg["window_s"]),
            slide_step=float(self.cfg["slide_s"]),
            approximate_query=bool(self.cfg["approximate"]))
        self.op = PointPointJoinQuery(conf, grid)
        # One cycle's timestamps, built before the window: a chunk is views
        # into the pool plus, past the first cycle, one offset added.
        self.ts_pool = stream.ts(0, stream.pool)
        self.cycle_ms = stream.pool * 1000 // stream.rate_eps

    def _chunk(self, lo: int, hi: int) -> Dict[str, np.ndarray]:
        s = self.stream
        a, b = lo % s.pool, (hi - 1) % s.pool + 1
        if b <= a:
            raise ValueError("a segment may not wrap the pool: make "
                             "pool_events a multiple of batch_events")
        ts = self.ts_pool[a:b]
        if lo >= s.pool:
            ts = ts + (lo // s.pool) * self.cycle_ms
        return {"ts": ts, "x": s.x[a:b], "y": s.y[a:b], "oid": s.ids[a:b]}

    def _join_counters(self) -> Optional[Dict[str, int]]:
        """The program's join counters now (None: telemetry is off, or this
        program keeps none)."""
        from spatialflink_tpu.telemetry import telemetry

        return telemetry.snapshot().get("join") if telemetry.enabled else None

    def run(self, feed) -> None:
        clock = time.perf_counter
        feed.on_open.append(
            lambda: setattr(self, "join_open", self._join_counters()))
        feed.on_close.append(
            lambda: setattr(self, "join_close", self._join_counters()))
        self.demux = Demux(feed.segments(), self._chunk, clock)
        for start, end, li, ri, dd, count, overflow in self.op.run_soa(
                self.demux.side(0), self.demux.side(1),
                float(self.cfg["radius"])):
            if feed.t_closed is not None:
                break  # the feed has ended: this is the open window's flush
            t = clock()
            feed.result(end, t)
            t0 = self.demux.t_pulled
            self.window_spans.append(("window", t0, t - t0))
            self.got.append((end, li, ri, dd, int(count), int(overflow)))

    def health(self) -> Dict[str, Any]:
        import jax

        op = self.op
        backend = getattr(op, "last_join_backend", None)
        problems = []
        want = self.cfg["expect_join_backend"]
        if jax.default_backend() == "tpu" and backend != want:
            problems.append(
                f"the join's extraction was {backend!r}, expected {want!r} "
                "on a TPU (a program that cannot say keeps no "
                "last_join_backend)")
        return {"problems": problems, "join_backend": backend,
                "join_cap": getattr(op, "join_cap", None),
                "join_budget": getattr(op, "join_budget", None)}

    def verify(self, feed) -> Dict[str, Any]:
        ref_mod = spec.plugin("references", self.cfg["reference"])
        cfg, s, wn = self.cfg, self.stream, self.windows
        ref = ref_mod.Reference(
            bbox=self.stream_cfg["bbox"], grid_cells=self.grid_cells,
            radius=float(cfg["radius"]), tol=float(cfg["tolerance_deg"]))
        per_window = int(s.rate_eps * wn.size_ms // 1000)
        if wn.size_ms != wn.slide_ms or per_window % 2 or s.pool % per_window:
            return {"checked": 0, "wrong": {}, "problems": [
                "the adapter checks tumbling windows of an even number of "
                "events that divide the pool"]}
        #: per distinct window of the pool: the reference's pairs, and the
        #: result arrays already found right
        want: Dict[int, Any] = {}
        passed: Dict[int, Tuple[Any, Any, Any, int]] = {}
        wrong: Dict[int, List[str]] = {}
        problems = []
        edge = pairs = repeats = 0
        for i, (end, li, ri, dd, count, overflow) in enumerate(self.got):
            k = wn.k_of(end)
            if k != i:
                problems.append(f"result {i} is window {k}: a window is "
                                "missing or out of order")
                break
            pairs += count
            key = (k * per_window) % s.pool
            ok = passed.get(key)
            if ok is not None and overflow == 0 and count == ok[3] and all(
                    np.array_equal(a, b) for a, b in zip((li, ri, dd), ok)):
                repeats += 1
                continue
            if key not in want:
                lo = key  # event `lo` is even: A's
                ax, ay = s.x[lo:lo + per_window:2], s.y[lo:lo + per_window:2]
                bx = s.x[lo + 1:lo + per_window:2]
                by = s.y[lo + 1:lo + per_window:2]
                want[key] = ref.pairs(ax, ay, bx, by)
                edge += ref.edge_pairs(want[key])
            bad = ref.compare(want[key], li, ri, dd, count, overflow,
                              per_window // 2)
            if bad:
                wrong[k] = bad
            else:
                passed.setdefault(key, (li, ri, dd, count))
        return {"checked": len(self.got), "wrong": wrong,
                "problems": problems, "distinct_windows": len(want),
                "repeats_equal_to_a_checked_result": repeats,
                "pairs": pairs, "pairs_in_tolerance_band": edge,
                "demux": {"segments": self.demux.pulled,
                          "chunks_handed": list(self.demux.handed)}}

    def host_spans(self, feed, telemetry_events
                   ) -> List[Tuple[str, float, float]]:
        """``window`` = from the hand-over of the segment that closes the left
        side's window to the result on the host: the right side's queued
        chunks through its assembler (``run_soa`` takes the sides in turn),
        alignment and padding, the capacity pick, ship, extraction, the two
        fetches. The harness adds ``generate`` and calls the rest between two
        pulls ``ingest``: the left side's chunks through its assembler."""
        return list(self.window_spans)

    def extras(self) -> Dict[str, Any]:
        """``join.<counter>``: the operator's join counters over the window;
        ``join.cap`` / ``join.budget``: the gauges at its close. Empty where
        the program keeps none."""
        a, b = self.join_open, self.join_close
        if b is None:
            return {}
        a = a or {}
        out = {f"join.{k}": b.get(k, 0) - a.get(k, 0) for k in JOIN_COUNTERS}
        out.update({f"join.{k}": b[k] for k in ("cap", "budget") if k in b})
        return out
