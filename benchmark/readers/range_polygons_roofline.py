"""The point-polygon range query's window program as a share of its roofline
(%): the least time the chip could take for one window — the larger of
operations / peak FLOP/s and bytes / peak bytes/s, both from the
configuration's shapes alone (:func:`cost`, below: this reader carries its own
cost function) — over the time the trace shows, a window, for the programs
that hold the query, whatever implements it.

    least  = max(8 N P / peak FLOP/s,
                 (13 N + 5 N + 64 P) / peak bytes/s)
    share  = results x least / seconds of the programs x 100

With N = 1,000,000 points a window and P = 1,000 polygons: 18.064 MB -> 22.1
us at 819 GB/s; 8.0e9 operations -> 40.6 us at 197e12 a second, the MXU's
bf16 peak, which nothing elementwise reaches: the share flatters nothing.

Of the two terms only the bytes are a floor of the query itself. The
operations are a bound on brute force: every point against every polygon's
box, with no index over the polygons, and priced at the MXU's peak because
``peaks.json`` carries no VPU peak to price elementwise work at. A program
that indexed the polygons could do fewer and land between 22.1 and 40.6 us a
window, which this reader would then read as over 100 %: whoever brings such
a program brings the operations term to it (or a VPU peak to ``peaks.json``)
in a benchmark PR. At the 0.014 % the share reads today (my chip run, PR 35)
it says one thing: the program is some four orders of magnitude from either
term, so the time is in how the work is done, not in how much there is.

A program holds the query when its name on the ``XLA Modules`` line starts
with one of ``programs`` (the jitted function's name:
``jit_range_polygons_pruned`` selects the pruned and the compacting program
alike). Every run of such a program counts, a re-run for a larger ``cand`` or
budget included: the share is of the time the window cost, so it cannot pass
100."""


def cost(config):
    """``(operations, bytes)`` one window of the query needs, from the
    deployment's shapes.

    Bytes: a point in as two float32 coordinates, a validity byte and an int32
    cell (13 B), a point out as a keep byte and a float32 distance (5 B), and
    the polygon table once: P polygons x 8 vertex slots x 2 float32 (64 B; the
    generator's 5-vertex rings padded to the packer's least bucket).
    Operations: without an index over the polygons every point-polygon pair
    needs the box rejection — four differences, two maxima against them, two
    against 0 (the squares, the sum and the comparison with r^2 come only
    for the few that pass): 8 a pair."""
    n = config["stream"]["event_rate_eps"] * config["window_s"]
    p = config["query_polygons"]["count"]
    return 8.0 * n * p, n * 13.0 + n * 5.0 + p * 8 * 2 * 4.0


def read(trace, programs):
    if trace.device is None or trace.peaks is None or not trace.windows:
        return None
    seconds = sum(p["seconds"] for name, p in trace.device["programs"].items()
                  if name.startswith(tuple(programs)))
    if not seconds:
        return None
    ops, nbytes = cost(trace.cell.config)
    least = max(ops / trace.peaks["bf16_flops_per_s"],
                nbytes / trace.peaks["hbm_bytes_per_s"])
    return trace.windows * least / seconds * 100.0
