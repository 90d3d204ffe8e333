"""Adapter: the headline continuous kNN, fed the way a deployment feeds it.

    SoA chunks {ts, x, y, oid}  ->  WirePaneAssembler.feed  ->
    PointPointKNNQuery.run_wire_panes(strategy="auto")

The chunks are views into the seeded stream (timestamps advanced for each
cycle of the pool). This path has no driver and no sink: a result counts when
the host holds it, i.e. when ``run_wire_panes`` yields it, fetched. The run ends
like a consumer that goes away — the feed stops, the open pane is dropped (a
partial pane would only compile a shape no full window uses).

Every window is compared with the brute-force reference. A pool replayed
cyclically makes windows repeat, so the reference is computed once per distinct
window of the pool and every result is held to it.
"""

from __future__ import annotations

import time
from typing import Any, Dict, List, Tuple

import numpy as np

from benchmark.harness import spec


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

    def prepare(self, stream, windows) -> None:
        from spatialflink_tpu.grid import UniformGrid
        from spatialflink_tpu.models.objects import Point
        from spatialflink_tpu.operators import (
            PointPointKNNQuery,
            QueryConfiguration,
            QueryType,
        )
        from spatialflink_tpu.streams.wire import WireFormat, WirePaneAssembler

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
        self.asm = WirePaneAssembler(self.wf, windows.slide_ms, stream.t0_ms)
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

    def run(self, feed) -> None:
        clock = time.perf_counter
        handed = [0.0]

        def panes():
            for lo, hi in feed.segments():
                for pane in self.asm.feed(self._chunk(lo, hi)):
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
        pane = int(s.rate_eps * wn.slide_ms // 1000)
        cache: Dict[Tuple[int, int], np.ndarray] = {}
        wrong: Dict[int, List[str]] = {}
        problems = []
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
                "problems": problems, "distinct_windows": len(cache)}

    def host_spans(self, feed, telemetry_events
                   ) -> List[Tuple[str, float, float]]:
        """``window`` = from the pane handed to the operator to its result on
        the host (ship, digest, merge, fetch). (The harness adds ``generate``
        and calls the rest between two pulls ``ingest``: WirePaneAssembler.feed
        — pending-array concatenation, float64 quantisation at pane close.)"""
        return list(self.window_spans)

    def extras(self) -> Dict[str, Any]:
        return {}
