"""Adapter: the seven-node SNCB DAG, entered as ``streaming_job.main`` enters
query option 10 with ``--checkpoint``.

    run_job(params, source, None,
            driver=WindowedDataflowDriver(checkpoint_path=..., checkpoint_every=1,
                                          sink=None),
            output_dir=...)

The source is the harness's feed: ``objID,timestamp,x,y`` text lines, built
before the window opens, each parsed by the CLI's own ``parse_csv_point`` as the
job pulls it. With ``checkpoint_every = 1`` the driver publishes the unit
checkpoint between the pull of the event that fires a window and the next pull
(``driver.py:_drive``), so the feed's stamp of that next pull is the first moment
the result is out under the configuration's guarantee — observed without
touching the program. At that pull the adapter also reads the driver's own
checkpoint count: a fire that published nothing is a problem, not a result.

The lines are rendered by worker processes started before the backend is
(``before_backend``) and collected once it is up (``prepare``): the flood's
9.5 M lines cost 2.4 us each (``repr`` of two floats and the formatting), 23 s
in one process, which would double ``setup_s``; split eight ways beside the
backend's own start they leave about 4 s to wait for (my chip run, PR 31;
PERF.md section 5). Each worker returns its rows as one newline-joined string
(one object to pickle, not a million) and the parent splits it; the lines are
those of the one-process expression, character for character.

The run ends like a consumer that goes away: the source raises past the window,
and what is committed by then is compared with the plain reference, window by
window.
"""

from __future__ import annotations

import concurrent.futures
import functools
import multiprocessing
import os
from typing import Any, Dict, List, Sequence, Tuple

import numpy as np

from benchmark.harness import spec


#: worker processes that render the stream's lines (a one-chip machine has 13
#: cores; the backend's start-up runs beside them)
RENDERERS = 8


def render_lines(names: Sequence[str], ts: np.ndarray, ids: np.ndarray,
                 xs: np.ndarray, ys: np.ndarray) -> str:
    """Rows of the stream as the ``objID,timestamp,x,y`` lines the CLI parses
    (``repr`` of the float64: the shortest text that reads back exactly),
    joined by newlines."""
    return "\n".join(
        f"{names[d]},{t},{x!r},{y!r}"
        for d, t, x, y in zip(ids.tolist(), ts.tolist(), xs.tolist(),
                              ys.tolist()))


class _EndOfRun(BaseException):
    """Raised by the source once the feed has ended: the job is dropped where
    it stands (every fired window is committed by then). A BaseException, so
    that no retry ladder of the program mistakes it for a fault."""


def _yml(cfg: Dict[str, Any], stream: Dict[str, Any]) -> str:
    min_x, min_y, max_x, max_y = stream["bbox"]
    return f"""\
clusterMode: False
inputStream1:
  topicName: "sncb"
  format: "CSV"
  dateFormat: null
  csvTsvSchemaAttr: [0, 1, 2, 3]
  gridBBox: [{min_x}, {min_y}, {max_x}, {max_y}]
  numGridCells: {cfg["grid_cells"]}
  delimiter: ","
query:
  option: {cfg["query_option"]}
window:
  type: "TIME"
  interval: {cfg["window_s"]}
  step: {cfg["slide_s"]}
"""


class Adapter:
    #: a result is out at the pull that follows its trigger
    split_at_triggers = True

    def __init__(self, config: Dict[str, Any], stream_cfg: Dict[str, Any],
                 workdir: str, rehearsal: bool):
        self.cfg, self.stream_cfg = config, stream_cfg
        self.out_dir = os.path.join(workdir, "egress")
        self.ckpt = os.path.join(workdir, "unit.ckpt")
        self.commits: List[Tuple[int, float, int, int, int]] = []
        self.health_report: Dict[str, Any] = {}
        prefix = stream_cfg["id_prefix"]
        self.names = [f"{prefix}{i}" for i in range(int(stream_cfg["ids"]))]
        self.renderers = None
        self.parts: List[concurrent.futures.Future] = []

    # -- set-up ----------------------------------------------------------------

    def before_backend(self, stream, windows) -> None:
        """Start rendering the lines, an equal run of rows to a worker.
        ``spawn``: a worker imports this module and numpy, never the backend."""
        self.renderers = concurrent.futures.ProcessPoolExecutor(
            RENDERERS, mp_context=multiprocessing.get_context("spawn"))
        edges = [stream.n_total * i // RENDERERS for i in range(RENDERERS + 1)]
        self.parts = [
            self.renderers.submit(render_lines, self.names, stream.ts(lo, hi),
                                  stream.ids[lo:hi], stream.x[lo:hi],
                                  stream.y[lo:hi])
            for lo, hi in zip(edges, edges[1:])]

    def rendered(self) -> List[str]:
        """The stream's lines, once: waits for each worker's part in turn."""
        lines: List[str] = []
        while self.parts:
            lines += self.parts.pop(0).result().split("\n")
        return lines

    def close(self) -> None:
        """The workers are gone when this returns, whatever they were at."""
        if self.renderers is not None:
            self.renderers.shutdown(wait=True, cancel_futures=True)
            self.renderers = None

    def prepare(self, stream, windows) -> None:
        from spatialflink_tpu.config import Params
        from spatialflink_tpu.driver import WindowedDataflowDriver
        from spatialflink_tpu.streams.serde import parse_csv_point

        self.stream, self.windows = stream, windows
        self.lines = self.rendered()
        self.params = Params.loads(_yml(self.cfg, self.stream_cfg))
        sc = self.params.input_stream1
        self.parse = functools.partial(
            parse_csv_point, schema=sc.csv_tsv_schema_attr,
            delimiter=sc.delimiter, date_format=sc.date_format)
        self.driver = WindowedDataflowDriver(
            checkpoint_path=self.ckpt,
            checkpoint_every=int(self.cfg["checkpoint_every"]), sink=None)

    def on_mark(self, k: int, t: float) -> None:
        """The pull after window ``k``'s trigger: its result is committed."""
        st = self.driver.stats
        size = os.path.getsize(self.ckpt) if os.path.exists(self.ckpt) else 0
        self.commits.append((k, t, int(st["checkpoints"]),
                             int(st["windows"]), size))

    # -- the run ---------------------------------------------------------------

    def run(self, feed) -> None:
        from spatialflink_tpu import dag as dag_mod
        from spatialflink_tpu import streaming_job

        lines, parse = self.lines, self.parse

        def source():
            for lo, hi in feed.segments():
                for line in lines[lo:hi]:
                    yield parse(line)
            raise _EndOfRun()

        try:
            streaming_job.run_job(self.params, source(), None,
                                  driver=self.driver, output_dir=self.out_dir)
        except _EndOfRun:
            pass
        else:
            raise RuntimeError("run_job returned before the feed ended")
        for k, t, _c, _w, _b in self.commits:
            feed.result(self.windows.end(k), t)
        dag = dag_mod.active()
        if dag is None:
            raise RuntimeError("option 10 left no DAG installed")
        self.health_report = dag.snapshot()["nodes"]
        dag_mod.uninstall()

    # -- after the window ------------------------------------------------------

    def health(self) -> Dict[str, Any]:
        problems = []
        for name, st in self.health_report.items():
            if st["backend"] != "device" or st["retries"] or st["failovers"] \
                    or st["degraded_windows"]:
                problems.append(f"node {name} left the device path: {st}")
            if st.get("breaker", {}).get("opens"):
                problems.append(f"node {name}: circuit breaker opened")
        nodes = spec.plugin("references", self.cfg["reference"]).NODES
        if sorted(self.health_report) != sorted(nodes):
            problems.append(f"nodes {sorted(self.health_report)}")
        # One fire, one publish: from one pull-after-a-trigger to the next the
        # driver has processed one more window and published one more
        # checkpoint. (Before the first, the qserve boot commands, stamped at
        # time 0, have fired two empty windows of their own.)
        for (_k, _t, c0, w0, _b), (k, _t1, c1, w1, _b1) in zip(
                self.commits, self.commits[1:]):
            if c1 - c0 != 1 or w1 - w0 != 1:
                problems.append(
                    f"window {k}: {w1 - w0} windows processed and {c1 - c0} "
                    "checkpoints published since the window before it")
                break
        if self.commits and self.commits[0][2] < 1:
            problems.append("no checkpoint published at the first result")
        return {"problems": problems,
                "nodes": {n: st["windows"] for n, st in
                          self.health_report.items()}}

    def verify(self, feed) -> Dict[str, Any]:
        """Every committed window against the plain reference."""
        ref_mod = spec.plugin("references", self.cfg["reference"])
        n = feed.idx_closed
        s = self.stream
        zone = self.cfg["zones"]["high_risk"]
        ref = ref_mod.Reference(
            s.ts(0, n), s.ids[:n], s.x[:n], s.y[:n], names=self.names,
            bbox=self.stream_cfg["bbox"], grid_n=int(self.cfg["grid_cells"]),
            queries=self.cfg["standing_queries"],
            risk_zone_file=os.path.join(spec.ROOT, zone["file"]),
            zone_buffer_m=float(zone["buffer_m"]) + float(zone["q1_radius_m"]))
        got = ref_mod.read_committed(self.out_dir)
        wn = self.windows
        expected = {(wn.end(k) - wn.size_ms, wn.end(k)): k
                    for k, *_ in self.commits}
        problems = [f"lines committed for a window that did not fire: {span}"
                    for span in sorted(set(got) - set(expected))]
        wrong: Dict[int, List[str]] = {}
        lines = {node: 0 for node in ref_mod.NODES}
        for span, k in sorted(expected.items()):
            have = got.get(span, {})
            for node, ls in have.items():
                lines[node] += len(ls)
            bad = ref.compare(ref.window(*span), have)
            if bad:
                wrong[k] = bad
        silent = [node for node in ("q1", "q3", "q4", "staytime", "qserve")
                  if not lines[node]]
        if silent:
            problems.append(f"nodes with no egress at all: {silent}")
        return {"checked": len(expected), "wrong": wrong,
                "problems": problems, "lines": lines}

    def host_spans(self, feed, telemetry_events
                   ) -> List[Tuple[str, float, float]]:
        """The host's named work, as (name, start, duration) on the feed's
        clock: ``window`` = the program's own ``window.dag`` span; ``commit`` =
        from that span's end to the next pull of the source. (The harness adds
        ``generate`` and calls the rest between two pulls ``ingest``: parse,
        GpsEvent, window assembly.)"""
        asked = np.asarray([p[1] for p in feed.pulls] + [feed.t_closed])
        spans = []
        for e in telemetry_events:
            if e["name"] != "window.dag":
                continue
            start, dur = e["ts"] * 1e-6, e["dur"] * 1e-6
            i = int(np.searchsorted(asked, start + dur, side="left"))
            if i < len(asked):
                spans.append(("window", start, dur))
                spans.append(("commit", start + dur,
                              float(asked[i]) - (start + dur)))
        return spans

    def extras(self) -> Dict[str, Any]:
        return {"checkpoint_bytes": [b for *_, b in self.commits if b]}
