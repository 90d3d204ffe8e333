"""The window join's extraction on Gaussian positions as a share of its
roofline (%): the least time the chip could take for one window — the larger
of operations / peak FLOP/s and bytes / peak bytes/s, both from the
configuration's shapes alone (:func:`cost`, below: this reader carries its
own cost function, ``join_roofline``'s prices uniform positions) — over the
time the trace shows, a window, for the programs that hold the extraction,
whatever implements it and on whatever grid it lays its buckets.

A program holds the extraction when its name on the ``XLA Modules`` line
starts with one of ``programs`` (``jit_join_window``: the Pallas and the XLA
program, and the program that makes a refined grid's bucket cells). Every
run of such a program counts, a re-run for a layout or a budget included:
the share is of the time the window cost, so it cannot pass 100."""

import math


def cost(config):
    """``(operations, bytes)`` one window of the join needs, from the
    deployment's shapes: n points a side, each axis N(mean, sigma x span)
    (``stream.positions``), so the density is p(x, y) with
    integral p^2 = 1 / (4 pi sx sy).

    Pairs: n^2 x pi r^2 x integral p^2 = n^2 r^2 / (4 sx sy). Bytes: both
    sides' points in (two float32 coordinates, 8 B a point) and the expected
    pairs out (two int32 indices and a float32 distance, 12 B a pair).
    Operations: a left point is compared with the right points of the 3x3
    key-grid cells around its own, 9 x n x cell area x p of them, at 8
    operations a comparison (two differences, two squares, a sum, a
    comparison with r^2, two validity tests): 8 x 9 x n^2 x cell area /
    (4 pi sx sy) — the work the key grid implies, whatever holds the
    buckets."""
    s = config["stream"]
    min_x, min_y, max_x, max_y = s["bbox"]
    sigma = float(s["positions"]["sigma"])
    sx, sy = sigma * (max_x - min_x), sigma * (max_y - min_y)
    n_side = s["event_rate_eps"] * config["window_s"] / 2.0
    pairs = n_side * n_side * config["radius"] ** 2 / (4.0 * sx * sy)
    cell = (max_x - min_x) / config["grid_cells"]
    ops = 8.0 * 9.0 * n_side * n_side * cell * cell / (4.0 * math.pi * sx * sy)
    return ops, 2 * n_side * 8.0 + pairs * 12.0


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
