"""The point-polygon range query on Spider's zones as a share of its roofline
(%): the least time the chip could take for one window — the larger of
operations / the vector unit's peak and bytes / peak bytes/s, both from the
configuration's shapes alone (:func:`cost`, below: this reader carries its own
cost function; ``range_polygons_roofline``'s prices 1,000 rectangles with no
index) — over the time the trace shows, a window, for the programs that hold
the query, whatever implements it.

    least  = max(N x C x E x 40 / VPU op/s,
                 (18 N + entries x E x 16) / peak bytes/s)
    share  = results x least / seconds of the programs x 100

N points a window, E the mean segments of a zone, C the expected zones whose
box grown by r holds a point, ``entries`` the expected (cell, zone) pairs of
the grid index — all from the parameters of ``stream.positions`` and
``query_polygons``, never from the run. Both terms are a floor of the query
on any index over the zones: every point has to be read and answered, every
zone's edges read once a cell its grown box touches, and every zone whose
grown box holds a point tested edge by edge.

The edge tests are elementwise float32 work: no matrix unit takes them, so
they are priced at the vector unit (:func:`vpu_ops_per_s`), not at the MXU's
bf16 peak. ``peaks.json`` carries no published vector peak; the rate here is
derived from published facts of the chip (the MXUs a core, each a square
systolic array, and the bf16 peak they make give the clock) and the vector
register's (8, 128) lanes, one operation a lane a cycle. At range_spider.flood's
shapes the operations bind, about a hundred times over the bytes.

A program holds the query when its name on the ``XLA Modules`` line starts
with one of ``programs``. Every run of such a program counts, a re-run
included: the share is of the time the window cost. It could pass 100 only
if the vector unit did more than one operation a lane a cycle and a program
came near that rate."""

import math

import numpy as np

#: operations of one (point, edge) test: the point-segment distance (two
#: differences a vector, two dot products, the division and the clamp, the
#: closest point, its difference, the squares, the sum and the root: 26),
#: the +x ray's crossing test (the span test, the intersection's abscissa,
#: the comparison: 12), and the two accumulations (the least distance, the
#: crossing count)
EDGE_OPS = 40

#: per device name (``peaks.json``'s ``name``): the MXUs of a core, the side
#: of each one's systolic array, and the 32-bit lanes of a vector register.
#: TPU v5e: Google Cloud documentation, "TPU v5e" (one TensorCore a chip, with
#: four MXUs, a vector unit and a scalar unit; the MXU a 128 x 128 array,
#: "TPU system architecture"); the (8, 128) vector register, JAX's Pallas TPU
#: documentation
VECTOR_UNITS = {"TPU v5e": {"mxus": 4, "mxu_side": 128, "lanes": 8 * 128}}


def _exceed(v):
    """P(s cos t > v) for s uniform in (0, 1], t uniform in [0, 2 pi), v in
    [0, 1]: (arccos v - v ln((1 + sqrt(1 - v^2)) / v)) / pi."""
    v = np.asarray(v, np.float64)
    with np.errstate(divide="ignore", invalid="ignore"):
        tail = np.where(v > 0, v * np.log((1.0 + np.sqrt(1.0 - v * v)) / v),
                        0.0)
    return (np.arccos(v) - tail) / math.pi


def mean_width(maxseg, points=20001):
    """The expected extent along one axis of a ring of k vertices at radii
    uniform in (0, 1] and angles uniform, k uniform in [3, ``maxseg``]:
    E[max - min] = integral over [-1, 1] of 1 - G^k - (1 - G)^k, G the
    vertex coordinate's distribution (in radii)."""
    v = np.linspace(-1.0, 1.0, points)
    g = np.where(v < 0, _exceed(-v), 1.0 - _exceed(np.abs(v)))
    ks = np.arange(3, maxseg + 1)
    per_k = [np.trapezoid(1.0 - g ** k - (1.0 - g) ** k, v) for k in ks]
    return float(np.mean(per_k)), float(np.mean(np.square(per_k)))


def cost(config):
    """``(operations, bytes)`` one window of the query needs, from the
    deployment's shapes.

    A zone's box is ``w_x x w_y``, each the extent of its vertices along the
    axis (:func:`mean_width`, times polysize / 2 of the span), grown by r on
    every side; the two axes' extents are taken as independent given the
    segment count. Points and zones' centres are each N(mean, sigma x span)
    a side, so a point lies in a given zone's grown box with chance
    area / (4 pi sx sy) (the box is small beside sigma): C = P x E[area] /
    (4 pi sx sy). A box placed at random over square cells of side c touches
    (1 + W_x / c)(1 + W_y / c) of them on average: entries = P x that.
    E = (3 + maxseg) / 2.

    Bytes: a point in as two float32 coordinates, a validity byte and an
    int32 cell, out as a keep byte and a float32 distance (18 B), and the
    index's edges once, four float32 an edge. Operations: C x E edge tests a
    point at ``EDGE_OPS`` each."""
    s, q = config["stream"], config["query_polygons"]
    min_x, min_y, max_x, max_y = s["bbox"]
    span_x, span_y = max_x - min_x, max_y - min_y
    sigma, r = float(q["sigma"]), float(q["radius"])
    if float(s["positions"]["sigma"]) != sigma:
        raise ValueError("the cost assumes points and zones of one sigma")
    n = s["event_rate_eps"] * config["window_s"]
    p, maxseg = q["count"], int(q["maxseg"])
    w1, w2 = mean_width(maxseg)  # E[w | k] and E[w^2 | k] over k, in radii
    rx, ry = q["polysize"] / 2 * span_x, q["polysize"] / 2 * span_y
    area = w2 * rx * ry + 2 * r * w1 * (rx + ry) + 4 * r * r
    cands = p * area / (4.0 * math.pi * sigma * span_x * sigma * span_y)
    c = span_x / config["grid_cells"]
    cells = 1 + (w1 * (rx + ry) + 4 * r) / c + area / (c * c)
    edges = (3 + maxseg) / 2.0
    return n * cands * edges * EDGE_OPS, n * 18.0 + p * cells * edges * 16.0


def vpu_ops_per_s(peaks):
    """The vector unit's float32 operations a second: the clock that the
    bf16 peak gives (two operations a multiply-add, every MXU cell a cycle),
    times the lanes of a vector register, one operation each a cycle. None
    for a device whose units are not listed."""
    unit = VECTOR_UNITS.get(peaks.get("name"))
    if unit is None:
        return None
    clock = peaks["bf16_flops_per_s"] / (2 * unit["mxus"]
                                         * unit["mxu_side"] ** 2)
    return clock * unit["lanes"]


def read(trace, programs):
    if trace.device is None or trace.peaks is None or not trace.windows:
        return None
    vpu = vpu_ops_per_s(trace.peaks)
    seconds = sum(p["seconds"] for name, p in trace.device["programs"].items()
                  if name.startswith(tuple(programs)))
    if not seconds or vpu is None:
        return None
    ops, nbytes = cost(trace.cell.config)
    least = max(ops / vpu, nbytes / trace.peaks["hbm_bytes_per_s"])
    return trace.windows * least / seconds * 100.0
