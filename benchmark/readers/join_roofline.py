"""The window join's extraction as a share of its roofline (%): the least time
the chip could take for one window — the larger of operations / peak FLOP/s
and bytes / peak bytes/s, both from the configuration's shapes alone
(:func:`cost`, below: this reader carries its own cost function) — over the
time the trace shows, a window, for the programs that hold the extraction,
whatever implements it.

A program holds the extraction when its name on the ``XLA Modules`` line
starts with one of ``programs`` (the jitted function's name, e.g.
``jit_join_window``: the Pallas and the XLA program alike). Every run of such
a program counts, a re-run for a capacity or a budget included: the share is
of the time the window cost, so it cannot pass 100."""

import math


def cost(config):
    """``(operations, bytes)`` one window of the join needs, from the
    deployment's shapes.

    Bytes: both sides' points in (two float32 coordinates, 8 B a point) and
    the expected pairs out (two int32 indices and a float32 distance, 12 B a
    pair); expected pairs = n_l x n_r x pi r^2 / the bbox's area, positions
    being uniform. Operations: a left point is compared with the right points
    of its 3x3 cells, 9 x n_r / occupied cells of them, at 8 operations a
    comparison (two differences, two squares, a sum, a comparison with r^2,
    two validity tests); occupied cells = those of the n x n grid (cell side
    = the bbox's width / n) the bbox covers."""
    s = config["stream"]
    min_x, min_y, max_x, max_y = s["bbox"]
    n_side = s["event_rate_eps"] * config["window_s"] / 2.0
    area = (max_x - min_x) * (max_y - min_y)
    pairs = n_side * n_side * math.pi * config["radius"] ** 2 / area
    cell = (max_x - min_x) / config["grid_cells"]
    occupied = config["grid_cells"] * math.ceil((max_y - min_y) / cell)
    return (8.0 * n_side * 9.0 * n_side / occupied,
            2 * n_side * 8.0 + pairs * 12.0)


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
