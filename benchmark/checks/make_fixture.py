"""Cut a small recorded fixture out of a trace a chip run kept (``--keep``).

    python benchmark/checks/make_fixture.py <kept.xplane.pb> <name> <seconds>

Keeps the device events of the first ``seconds`` after the harness's ``open``
mark, moves the ``close`` mark there, and stores beside the document the
numbers a reader can work out by hand — computed here by a sweep over the
interval endpoints, not by the code under test (``xplane.union``).
"""

import gzip
import json
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.dirname(os.path.dirname(HERE)))

from benchmark.harness import xplane  # noqa: E402


def sweep_busy(events, lo, hi):
    """Seconds inside [lo, hi] covered by at least one event: +1 at a start,
    -1 at an end, time counted while the depth is above zero."""
    points = []
    for _n, s, d in events:
        s, e = max(s, lo), min(s + d, hi)
        if e > s:
            points += [(s, 1), (e, -1)]
    points.sort()
    depth, busy, last = 0, 0.0, lo
    for t, step in points:
        if depth > 0:
            busy += t - last
        depth += step
        last = t
    return busy


def main(path, name, seconds):
    doc = xplane.read(path)
    lo = xplane.mark_time(doc, "open")
    hi = lo + float(seconds)
    small = {"devices": {}, "marks": [("bench:open", lo, 0.0),
                                      ("bench:close", hi, 0.0)]}
    for dev, lines in doc["devices"].items():
        small["devices"][dev] = {
            ln: [e for e in ev if lo - 0.01 <= e[1] <= hi + 0.01]
            for ln, ev in lines.items()}
    # The host, hand-made: a "window" from 1 ms before every third program
    # execution to its end, "ingest" between them.
    host, at = [], lo
    mods = [m for d in small["devices"].values()
            for m in d[xplane.MODULES_LINE] if lo <= m[1] <= hi]
    for m in sorted(mods, key=lambda m: m[1])[::3]:
        start, end = max(m[1] - 0.001, at), m[1] + m[2]
        host += [("ingest", at, start - at), ("window", start, end - start)]
        at = end
    host.append(("ingest", at, hi - at))
    host = [h for h in host if h[2] > 0]
    ops = [e for d in small["devices"].values()
           for ln in (xplane.OPS_LINE, xplane.ASYNC_LINE) for e in d[ln]]
    per_op = {}
    for n, s, d in xplane.clip(ops, lo, hi):
        per_op[n] = per_op.get(n, 0.0) + d
    top = max(per_op.items(), key=lambda kv: kv[1])[0]
    reduced = xplane.reduce(small, lo, hi, host)
    expected = {
        "devices_used": 1, "window_s": hi - lo,
        "busy_s": sweep_busy(ops, lo, hi),
        "module_runs": len(xplane.clip(mods, lo, hi)),
        "top_op": next(n for n, _t in reduced["device_ops"]
                       if n.endswith("/" + top)),
    }
    out = os.path.join(HERE, "fixtures", name + ".json.gz")
    with gzip.open(out, "wt") as f:
        json.dump({"source": os.path.basename(path), "doc": small,
                   "host": host, "expected": expected}, f)
    print(out, os.path.getsize(out), "bytes", expected)


if __name__ == "__main__":
    main(*sys.argv[1:4])
