"""Adapter: ``join_soa``'s window join on a city that crowds its centre.

The harness builds its one seeded stream with positions uniform over the
bbox; before the clock, ``prepare`` maps every event's draw to the
configuration's ``stream.positions`` — Spider's Gaussian
(``benchmark/references/spider_gaussian.py``, a pure function of the draw:
same seed, same stream, same positions) — and hands the stream on to the
base. Feed, demultiplexer, operator (``PointPointJoinQuery(conf, grid)``,
nothing else given), ``verify`` against ``join_tdrive`` on the mapped points
and the host spans are the base's.

The run ends as the base's does — the feed stops, the open window is dropped
— but sooner: the base lets ``run_soa`` flush the open window and throws the
result away, and that flush is a window of another lane bucket, i.e. fresh
compiles of the 400-column programs (52-63 s after the clock, outside every
metric: my chip run, PR 41, call 1). Here the feed's end reaches the operator
as an exception, so nothing is flushed.

Beside the base's counters the readers get what the operator picked to hold
a crowded cell: the counter ``join.bucket_lanes`` and the gauges
``join.fullest_cell``, ``join.refine``, ``join.bucket_cells`` — each only
where the program keeps it (a program without refinement keeps none, and the
metrics that read them then report nothing).
"""

from __future__ import annotations

from typing import Any, Dict

from benchmark.adapters import join_soa
from benchmark.harness import spec

#: what this deployment adds to ``join_soa.JOIN_COUNTERS`` and to its gauges
SKEW_COUNTERS = ("bucket_lanes",)
SKEW_GAUGES = ("fullest_cell", "refine", "bucket_cells")


class _FeedEnded(Exception):
    """The feed has handed over its last segment."""


class _EndsByRaising:
    """``feed``, whose ``segments()`` raises :class:`_FeedEnded` where the
    feed's own returns: the chunk iterators then end in that exception
    and ``run_soa`` flushes nothing."""

    def __init__(self, feed):
        self._feed = feed

    def __getattr__(self, name):
        return getattr(self._feed, name)

    def segments(self):
        yield from self._feed.segments()
        raise _FeedEnded


class Adapter(join_soa.Adapter):

    def prepare(self, stream, windows) -> None:
        how = self.stream_cfg["positions"]
        if how["distribution"] != "gaussian":
            raise ValueError(f"join_soa_skew maps to a gaussian, not to "
                             f"{how['distribution']!r}")
        stream.x, stream.y = spec.plugin("references", how["generator"]) \
            .positions(stream.x, stream.y, self.stream_cfg["bbox"],
                       mean=float(how["mean"]), sigma=float(how["sigma"]))
        super().prepare(stream, windows)

    def run(self, feed) -> None:
        try:
            super().run(_EndsByRaising(feed))
        except _FeedEnded:
            pass  # the open window is dropped, unflushed

    def health(self) -> Dict[str, Any]:
        out = super().health()
        out["join_refine"] = getattr(self.op, "join_refine", None)
        return out

    def extras(self) -> Dict[str, Any]:
        out = super().extras()
        a, b = self.join_open or {}, self.join_close
        if b is None:
            return out
        out.update({f"join.{k}": b[k] - a.get(k, 0)
                    for k in SKEW_COUNTERS if k in b})
        out.update({f"join.{k}": b[k] for k in SKEW_GAUGES if k in b})
        return out
