"""The trajectory join's dedup as a share of its roofline (%): the least time
the chip could take to turn one window's point pairs into its trajectory
pairs — the larger of operations / peak FLOP/s and bytes / peak bytes/s, both
from the configuration's shapes alone (:func:`cost`, below: this reader
carries its own cost function) — over the time the trace shows, a window, for
the programs that hold the dedup, whatever implements it.

A program holds the dedup when its name on the ``XLA Modules`` line starts
with one of ``programs`` (the jitted function's name). Every run of such a
program counts, a re-run for a budget included: the share is of the time the
window cost, so it cannot pass 100. Nothing where the trace holds no such
program (a program without the dedup) or no device ran."""

import math


def expected(config):
    """``(point pairs, trajectory pairs)`` one window is expected to hold,
    positions and ids being uniform: pairs = n_l x n_r x pi r^2 / the bbox's
    area; they fall on ids^2 possible (left id, right id) keys uniformly, of
    which ids^2 x (1 - exp(-pairs / ids^2)) are hit at least once."""
    s = config["stream"]
    min_x, min_y, max_x, max_y = s["bbox"]
    n_side = s["event_rate_eps"] * config["window_s"] / 2.0
    area = (max_x - min_x) * (max_y - min_y)
    pairs = n_side * n_side * math.pi * config["radius"] ** 2 / area
    keys = float(s["ids"]) ** 2
    return pairs, keys * -math.expm1(-pairs / keys)


def cost(config):
    """``(operations, bytes)`` one window of the dedup needs, from the
    deployment's shapes.

    Bytes, the least the query itself moves: every point pair read once (two
    int32 ids or indices and a float32 distance, 12 B) and every trajectory
    pair written once (two int32 ids and a float32 minimum, 12 B). Operations:
    what a comparison sort of the pairs by (left id, right id, distance)
    needs at the least — log2(P!) ~ P x log2(P) comparisons of three keys, 3
    operations each. That term prices one way of grouping (sorting); a hash
    table would need O(P). It is three orders under the bytes term here, so
    the bytes bound the share either way; only the bytes term is a floor of
    the query itself."""
    pairs, tpairs = expected(config)
    return (3.0 * pairs * math.log2(max(pairs, 2.0)),
            12.0 * pairs + 12.0 * tpairs)


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
