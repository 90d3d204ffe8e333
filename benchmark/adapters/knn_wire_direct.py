"""Adapter: the headline continuous kNN as the sidecar of a windowing tier.

    finished (3, n) uint16 panes  ->
    PointPointKNNQuery.run_wire_panes(strategy="auto")

The windowing tier (the north star's Flink/JVM side, an edge gateway) has
quantised, interned and packed each 5 s slide before this process sees it. So
the pool's panes are packed before the window opens, through the library's
public producer half (``WireFormat.pack_pane``), as a producer that is ahead of
its consumer holds them; inside the window a segment of the feed is one pane
and the hand-over is the array itself: no assembler, no copy, the same array
object every cycle of the pool.

No driver and no sink: a result counts when ``run_wire_panes`` yields it,
fetched. Every window is compared with the brute-force reference (once per
distinct window of the pool), and every packed pane with the reference's own
quantisation of the same events.
"""

from __future__ import annotations

import time
from typing import Any, Dict, List, Optional, Tuple

import numpy as np

from benchmark.harness import spec

#: the operator's pane counters, as ``telemetry.snapshot()["wire"]`` names
#: them; ``extras`` hands the readers their change over the window
WIRE_COUNTERS = ("panes", "points", "lanes", "pad_lanes")


class Adapter:
    #: a result is out when the operator yields it: no stamps at triggers
    split_at_triggers = False

    def __init__(self, config: Dict[str, Any], stream_cfg: Dict[str, Any],
                 workdir: str, rehearsal: bool):
        self.cfg, self.stream_cfg = config, stream_cfg
        self.interpret = bool(rehearsal)  # Pallas interpreted off the chip
        self.got: List[Tuple[int, Any, Any, int]] = []
        self.window_spans: List[Tuple[str, float, float]] = []
        self.digest_kind = None
        self.wire_open: Optional[Dict[str, int]] = None
        self.wire_close: Optional[Dict[str, int]] = None

    def prepare(self, stream, windows) -> None:
        from spatialflink_tpu.grid import UniformGrid
        from spatialflink_tpu.models.objects import Point
        from spatialflink_tpu.operators import (
            PointPointKNNQuery,
            QueryConfiguration,
            QueryType,
        )
        from spatialflink_tpu.streams.wire import WireFormat

        self.stream, self.windows = stream, windows
        min_x, min_y, max_x, max_y = self.stream_cfg["bbox"]
        grid = UniformGrid(int(self.cfg["grid_cells"]), min_x, max_x,
                           min_y, max_y)
        self.wf = WireFormat.for_grid(grid)
        conf = QueryConfiguration(
            QueryType.WindowBased, window_size=float(self.cfg["window_s"]),
            slide_step=float(self.cfg["slide_s"]))
        self.op = PointPointKNNQuery(conf, grid)
        self.qp = Point(x=self.cfg["query_point"][0],
                        y=self.cfg["query_point"][1])
        # The producer's side, done before the window: every pane of the pool.
        self.pane = int(stream.rate_eps * windows.slide_ms // 1000)
        if stream.pool % self.pane:
            raise ValueError("pool_events must be a whole number of panes")
        self.panes = [
            self.wf.pack_pane(stream.x[lo:lo + self.pane],
                              stream.y[lo:lo + self.pane],
                              stream.ids[lo:lo + self.pane])
            for lo in range(0, stream.pool, self.pane)]

    def _pane_of(self, lo: int, hi: int) -> np.ndarray:
        if lo % self.pane or hi - lo != self.pane:
            raise ValueError(
                f"segment [{lo}, {hi}) is not a whole pane of {self.pane} "
                "events: make batch_events the pane")
        return self.panes[(lo // self.pane) % len(self.panes)]

    def _wire_counters(self) -> Optional[Dict[str, int]]:
        from spatialflink_tpu.telemetry import telemetry

        return telemetry.snapshot().get("wire") if telemetry.enabled else None

    def run(self, feed) -> None:
        clock = time.perf_counter
        feed.on_open.append(
            lambda: setattr(self, "wire_open", self._wire_counters()))
        feed.on_close.append(
            lambda: setattr(self, "wire_close", self._wire_counters()))
        handed = [0.0]

        def panes():
            for lo, hi in feed.segments():
                pane = self._pane_of(lo, hi)
                handed[0] = clock()
                yield pane

        cfg = self.cfg
        for start, end, segs, dists, nv in self.op.run_wire_panes(
                panes(), self.qp, float(cfg["radius"]), int(cfg["k"]),
                int(self.stream_cfg["ids"]), self.wf,
                start_ms=self.stream.t0_ms, strategy="auto",
                interpret=self.interpret, flush_at_end=False):
            t = clock()
            feed.result(end, t)
            self.window_spans.append(("window", handed[0], t - handed[0]))
            self.got.append((end, np.asarray(segs), np.asarray(dists),
                             int(nv)))
        self.digest_kind = self.op.last_wire_digest_kind

    def health(self) -> Dict[str, Any]:
        problems = []
        want = self.cfg["expect_digest"]
        if self.digest_kind != want:
            problems.append(
                f"wire digest ended on {self.digest_kind!r}, expected "
                f"{want!r} (a failed self-check is a kernel defect here)")
        return {"problems": problems, "wire_digest": self.digest_kind}

    def verify(self, feed) -> Dict[str, Any]:
        ref_mod = spec.plugin("references", self.cfg["reference"])
        cfg, s, wn = self.cfg, self.stream, self.windows
        ref = ref_mod.Reference(
            bbox=self.stream_cfg["bbox"], query=cfg["query_point"],
            radius=float(cfg["radius"]), k=int(cfg["k"]),
            ids=int(self.stream_cfg["ids"]))
        if not (np.array_equal(ref.scale, self.wf.scale)
                and np.array_equal(ref.origin, self.wf.origin)):
            return {"checked": 0, "wrong": {}, "problems": [
                "the program's wire format differs from the published one: "
                f"scale {self.wf.scale} origin {self.wf.origin}"]}
        xq, yq = ref.quantize(s.x, s.y)
        pane = self.pane
        problems = []
        # What was handed over, against the reference's own 6-byte records.
        for j, packed in enumerate(self.panes):
            lo = j * pane
            want = np.stack([xq[lo:lo + pane], yq[lo:lo + pane],
                             s.ids[lo:lo + pane].astype(np.uint16)])
            if not (packed.dtype == want.dtype
                    and np.array_equal(packed, want)):
                problems.append(f"packed pane {j} differs from the "
                                "reference's quantisation of its events")
        cache: Dict[Tuple[int, int], np.ndarray] = {}
        wrong: Dict[int, List[str]] = {}
        for i, (end, segs, dists, nv) in enumerate(self.got):
            k = wn.k_of(end)
            if k != i:
                problems.append(f"result {i} is window {k}: a window is "
                                "missing or out of order")
                break
            lo = max(0, (k + 1) * pane - wn.size_ms // wn.slide_ms * pane)
            hi = (k + 1) * pane
            key = (lo % s.pool, hi - lo)
            if key not in cache:
                idx = np.arange(lo, hi) % s.pool
                cache[key] = ref.minima(xq[idx], yq[idx], s.ids[idx])
            bad = ref.compare(cache[key], segs, dists, nv)
            if bad:
                wrong[k] = bad
        return {"checked": len(self.got), "wrong": wrong,
                "problems": problems, "distinct_windows": len(cache),
                "panes_checked": len(self.panes)}

    def host_spans(self, feed, telemetry_events
                   ) -> List[Tuple[str, float, float]]:
        """``window`` = from the pane handed to the operator to its result on
        the host (check, pad, ship, digest, merge, fetch). What the harness
        calls ``ingest`` is the rest between two pulls: here the look-up of
        the pane and the feed's own bookkeeping, nothing of the program's."""
        return list(self.window_spans)

    def extras(self) -> Dict[str, Any]:
        """``wire.<counter>``: the operator's pane counters over the window.
        Empty where the program keeps none."""
        a, b = self.wire_open, self.wire_close
        if b is None:
            return {}
        a = a or {}
        return {f"wire.{k}": b.get(k, 0) - a.get(k, 0) for k in WIRE_COUNTERS}
