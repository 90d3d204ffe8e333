"""Shared assertions on a synchronous operator loop's parent spans
(``wire.pane``, ``join.window``, ``range.window``): one a pane / window,
every other span of its thread that starts inside it ends inside it, and none
holds a moment of the consumer's time. Used by the three deployments' tests.
"""

import time

#: ``ts`` and ``dur`` are whole microseconds floored apart, so a child that
#: ends with its parent may read one past it
ROUNDING_US = 2


def x_spans(events):
    """The complete spans, less the collector's own: a generation-2 pass
    (``gc.full``) falls wherever the worker's earlier tests left the
    allocation counts, inside a parent or in the consumer's nap, and is no
    span of the loop."""
    return [e for e in events
            if e.get("ph") == "X" and e["name"] != "gc.full"]


def end(e):
    return e["ts"] + e["dur"]


def inside(child, parent):
    return (child["ts"] >= parent["ts"]
            and end(child) <= end(parent) + ROUNDING_US)


def slow_consumer(results, naps, nap_s=0.02):
    """Drain ``results`` with a nap after each: ``naps`` collects the naps'
    (start, end) in the spans' own microseconds."""
    got = []
    for r in results:
        got.append(r)
        t0 = time.perf_counter_ns() // 1000
        time.sleep(nap_s)
        naps.append((t0, time.perf_counter_ns() // 1000))
    return got


def assert_parents_tile(events, parent, naps):
    """Returns the parents, in order, and for each the names of the other
    spans of its thread inside it."""
    spans = x_spans(events)
    parents = sorted((e for e in spans if e["name"] == parent),
                     key=lambda e: e["ts"])
    assert parents, f"no {parent} span"
    inner = []
    for p, nxt in zip(parents, parents[1:] + [None]):
        if nxt is not None:
            assert end(p) <= nxt["ts"] + ROUNDING_US, "parents overlap"
        started = [e for e in spans if e is not p and e["tid"] == p["tid"]
                   and p["ts"] <= e["ts"] < end(p)]
        assert all(inside(e, p) for e in started), (
            p, [e for e in started if not inside(e, p)])
        inner.append([e["name"] for e in started])
        for lo, hi in naps:  # the consumer's time is nobody's
            assert hi <= p["ts"] + ROUNDING_US or lo >= end(p) - ROUNDING_US, \
                (p, lo, hi)
    return parents, inner
