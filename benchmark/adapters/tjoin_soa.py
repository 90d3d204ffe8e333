"""Adapter: the window trajectory join of two point streams, fed the way a
deployment feeds it.

    SoA chunks {ts, x, y, oid} of the combined stream  ->  demultiplexer
    (even events: stream A, odd: stream B; ``join_soa.Demux``)  ->  the two
    chunk iterators of PointPointTJoinQuery(conf, grid).run_soa(left, right,
    radius, num_segments=ids)

The operator is built with its configuration and grid only and ``run_soa`` is
given nothing but the radius and the id count: what it picks is what is
measured. This path has no driver and no sink: a result counts when the host
holds the window's trajectory pairs, i.e. when ``run_soa`` yields them,
fetched. The run ends like a consumer that goes away: the feed stops, the open
window is dropped.

Every window, warm-up included, is compared with the plain reference. A pool
replayed cyclically makes windows repeat, so the reference is computed once
per distinct window of the pool; a repeat that equals an already checked
result array for array is held to that check, any other is compared in full.
"""

from __future__ import annotations

import time
from typing import Any, Dict, List, Optional, Tuple

import numpy as np

from benchmark.adapters.join_soa import Adapter as JoinAdapter, Demux
from benchmark.harness import spec

#: the operator's counters and gauges, as ``telemetry.snapshot()["tjoin"]``
#: names them; ``extras`` hands the readers their change over the window
TJOIN_COUNTERS = ("windows", "pairs", "tpairs", "peel_passes", "cap_retries",
                  "budget_retries")
TJOIN_GAUGES = ("cap", "budget", "tpair_budget")


class Adapter:
    #: a result is out when the operator yields it: no stamps at triggers
    split_at_triggers = False

    def __init__(self, config: Dict[str, Any], stream_cfg: Dict[str, Any],
                 workdir: str, rehearsal: bool):
        self.cfg, self.stream_cfg = config, stream_cfg
        self.grid_cells = int(
            (config.get("rehearsal", {}) if rehearsal else {}).get(
                "grid_cells", config["grid_cells"]))
        #: ids lie in [0, num_segments): the stream's own count (the toy
        #: stream has fewer ids than the deployment)
        self.num_segments = int(stream_cfg["ids"])
        self.got: List[Tuple[int, Any, Any, Any, int, int]] = []
        self.window_spans: List[Tuple[str, float, float]] = []
        self.tjoin_open: Optional[Dict[str, int]] = None
        self.tjoin_close: Optional[Dict[str, int]] = None
        self.sizes_open: Optional[Dict[str, int]] = None
        self.demux: Optional[Demux] = None  # set by ``run``

    def prepare(self, stream, windows) -> None:
        from spatialflink_tpu.grid import UniformGrid
        from spatialflink_tpu.operators import QueryConfiguration, QueryType
        from spatialflink_tpu.operators.trajectory import PointPointTJoinQuery

        self.stream, self.windows = stream, windows
        min_x, min_y, max_x, max_y = self.stream_cfg["bbox"]
        grid = UniformGrid(self.grid_cells, min_x, max_x, min_y, max_y)
        conf = QueryConfiguration(
            QueryType.WindowBased, window_size=float(self.cfg["window_s"]),
            slide_step=float(self.cfg["slide_s"]),
            approximate_query=bool(self.cfg["approximate"]))
        self.op = PointPointTJoinQuery(conf, grid)
        if not hasattr(self.op, "tpair_budget"):
            # ``checked`` holds every yielded window to overflow 0 under a
            # capacity and budgets the operator grows itself: a program whose
            # trajectory join keeps none yields windows short (and makes a
            # table of ids x ids a window) and cannot run this cell.
            raise spec.SpecError(
                "this program's PointPointTJoinQuery keeps no tpair_budget "
                "(no capacity and budget contract, no sparse dedup): it "
                f"cannot run a cell of {self.cfg['name']}")
        # One cycle's timestamps, built before the window: a chunk is views
        # into the pool plus, past the first cycle, one offset added.
        self.ts_pool = stream.ts(0, stream.pool)
        self.cycle_ms = stream.pool * 1000 // stream.rate_eps

    #: a segment of the pool as views plus, past the first cycle, one offset
    _chunk = JoinAdapter._chunk

    def _tjoin_counters(self) -> Optional[Dict[str, int]]:
        """The program's trajectory-join counters now (None: telemetry is
        off, or this program keeps none)."""
        from spatialflink_tpu.telemetry import telemetry

        return telemetry.snapshot().get("tjoin") if telemetry.enabled else None

    def _sizes(self) -> Dict[str, int]:
        """The capacity rung and the two budgets the operator holds now."""
        return {k: getattr(self.op, k)
                for k in ("join_cap", "join_budget", "tpair_budget")}

    def run(self, feed) -> None:
        clock = time.perf_counter
        feed.on_open.append(
            lambda: setattr(self, "tjoin_open", self._tjoin_counters()))
        feed.on_open.append(
            lambda: setattr(self, "sizes_open", self._sizes()))
        feed.on_close.append(
            lambda: setattr(self, "tjoin_close", self._tjoin_counters()))
        self.demux = Demux(feed.segments(), self._chunk, clock)
        for start, end, lo, ro, dd, count, overflow in self.op.run_soa(
                self.demux.side(0), self.demux.side(1),
                float(self.cfg["radius"]), num_segments=self.num_segments):
            if feed.t_closed is not None:
                break  # the feed has ended: this is the open window's flush
            t = clock()
            feed.result(end, t)
            t0 = self.demux.t_pulled
            self.window_spans.append(("window", t0, t - t0))
            self.got.append((end, lo, ro, dd, int(count), int(overflow)))

    def health(self) -> Dict[str, Any]:
        import jax

        op = self.op
        backend = getattr(op, "last_join_backend", None)
        problems = []
        want = self.cfg["expect_join_backend"]
        if jax.default_backend() == "tpu" and backend != want:
            problems.append(
                f"the join's extraction was {backend!r}, expected {want!r} "
                "on a TPU")
        # A size that grew inside the window was a re-run or a new program
        # there: the warm-up has to settle all three (traced or not).
        sizes = self._sizes()
        opened = self.sizes_open
        if opened is not None and sizes != opened:
            problems.append(f"the operator's sizes grew inside the window: "
                            f"{opened} -> {sizes}")
        a, b = self.tjoin_open, self.tjoin_close
        retries = None
        if b is not None:  # telemetry is on: the re-runs, counted
            retries = sum(b.get(k, 0) - (a or {}).get(k, 0)
                          for k in ("cap_retries", "budget_retries"))
            if retries:
                problems.append(f"{retries} re-runs inside the window")
        return {"problems": problems, "join_backend": backend,
                "retries_in_window": retries, **sizes}

    def verify(self, feed) -> Dict[str, Any]:
        ref_mod = spec.plugin("references", self.cfg["reference"])
        cfg, s, wn = self.cfg, self.stream, self.windows
        ref = ref_mod.Reference(
            bbox=self.stream_cfg["bbox"], grid_cells=self.grid_cells,
            radius=float(cfg["radius"]), tol=float(cfg["tolerance_deg"]),
            num_ids=self.num_segments)
        per_window = int(s.rate_eps * wn.size_ms // 1000)
        if wn.size_ms != wn.slide_ms or per_window % 2 or s.pool % per_window:
            return {"checked": 0, "wrong": {}, "problems": [
                "the adapter checks tumbling windows of an even number of "
                "events that divide the pool"]}
        #: per distinct window of the pool: the reference's trajectory pairs,
        #: and the result arrays already found right
        want: Dict[int, Any] = {}
        passed: Dict[int, Tuple[Any, Any, Any, int]] = {}
        wrong: Dict[int, List[str]] = {}
        problems = []
        edge = tpairs = repeats = 0
        deviation = 0.0
        for i, (end, lo, ro, dd, count, overflow) in enumerate(self.got):
            k = wn.k_of(end)
            if k != i:
                problems.append(f"result {i} is window {k}: a window is "
                                "missing or out of order")
                break
            tpairs += count
            key = (k * per_window) % s.pool
            ok = passed.get(key)
            if ok is not None and overflow == 0 and count == ok[3] and all(
                    np.array_equal(a, b) for a, b in zip((lo, ro, dd), ok)):
                repeats += 1
                continue
            if key not in want:
                a = slice(key, key + per_window, 2)      # event `key` is even: A's
                b = slice(key + 1, key + per_window, 2)
                want[key] = ref.tpairs(s.x[a], s.y[a], s.ids[a],
                                       s.x[b], s.y[b], s.ids[b])
                edge += ref.edge_tpairs(want[key])
            bad = ref.compare(want[key], lo, ro, dd, count, overflow)
            if len(lo) == len(ro) == len(dd):
                deviation = max(deviation,
                                ref.max_deviation(want[key], lo, ro, dd))
            if bad:
                wrong[k] = bad
            else:
                passed.setdefault(key, (lo, ro, dd, count))
        return {"checked": len(self.got), "wrong": wrong,
                "problems": problems, "distinct_windows": len(want),
                "repeats_equal_to_a_checked_result": repeats,
                "tpairs": tpairs, "tpairs_in_tolerance_band": edge,
                "max_distance_deviation_deg": deviation,
                "demux": None if self.demux is None else {
                    "segments": self.demux.pulled,
                    "chunks_handed": list(self.demux.handed)}}

    def host_spans(self, feed, telemetry_events
                   ) -> List[Tuple[str, float, float]]:
        """``window`` = from the hand-over of the segment that closes the left
        side's window to the result on the host: the right side's queued
        chunks through its assembler (``run_soa`` takes the sides in turn),
        alignment and padding, the id check, the capacity pick, ship,
        extraction, dedup, the two fetches. The harness adds ``generate`` and
        calls the rest between two pulls ``ingest``: the left side's chunks
        through its assembler."""
        return list(self.window_spans)

    def extras(self) -> Dict[str, Any]:
        """``tjoin.<counter>``: the operator's counters over the window;
        ``tjoin.<gauge>``: the gauges at its close. Empty where the program
        keeps none."""
        a, b = self.tjoin_open, self.tjoin_close
        if b is None:
            return {}
        a = a or {}
        out = {f"tjoin.{k}": b.get(k, 0) - a.get(k, 0) for k in TJOIN_COUNTERS}
        out.update({f"tjoin.{k}": b[k] for k in TJOIN_GAUGES if k in b})
        return out
