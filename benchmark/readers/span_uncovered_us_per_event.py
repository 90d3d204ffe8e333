"""What of the program's ``parent`` spans no other span of the same thread
covers, per event handed over (us): for every span of ``trace.spans`` named
``parent``, its ``dur`` less the union of all other spans of its ``tid`` that
start inside it (each cut at the parent's end: ``ts`` and ``dur`` are whole
microseconds, so a last child may read one past it). Children that overlap
count once (a union, not a sum); a span of another thread is not subtracted.
``None`` where the window holds no such parent: a program without the span."""

import bisect

from benchmark.harness.xplane import total, union


def read(trace, parent):
    if not trace.events:
        return None
    parents = [e for e in trace.spans if e["name"] == parent]
    if not parents:
        return None
    by_tid = {}
    for e in trace.spans:
        if e["name"] != parent:
            by_tid.setdefault(e.get("tid"), []).append(
                (e["ts"], e["ts"] + e["dur"]))
    starts = {}
    for tid, spans in by_tid.items():
        spans.sort()
        starts[tid] = [s for s, _e in spans]
    uncovered = 0.0
    for p in parents:
        lo, hi = p["ts"], p["ts"] + p["dur"]
        others = by_tid.get(p.get("tid"), [])
        at = starts.get(p.get("tid"), [])
        inside = others[bisect.bisect_left(at, lo):bisect.bisect_left(at, hi)]
        uncovered += p["dur"] - total(union((s, min(e, hi))
                                            for s, e in inside))
    return uncovered / trace.events
