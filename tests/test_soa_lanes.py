"""``operators/base.py:point_lanes``: the blocked, write-once preparation of
a fired SoA window's device lanes is bit for bit the whole-window sequence
it replaced — ``np.stack`` to float64, ``center_coords``,
``UniformGrid.assign_cells_np``, four ``pad_to_bucket`` — which stays here
as the reference (all three functions still serve other callers)."""

import jax
import numpy as np
import pytest

from spatialflink_tpu.grid import UniformGrid
from spatialflink_tpu.operators import base
from spatialflink_tpu.operators.base import (
    center_coords,
    device_point_args,
    point_lanes,
)
from spatialflink_tpu.telemetry import telemetry
from spatialflink_tpu.utils.padding import next_bucket, pad_to_bucket

BLOCK = base._LANE_BLOCK
GRIDS = {
    "unit": UniformGrid(20, 0.0, 10.0, 0.0, 10.0),  # min 0: -0.0 matters
    "beijing": UniformGrid(100, 115.5, 117.6, 39.6, 41.1),
    "nyc": UniformGrid(100, -74.26, -73.70, 40.49, 40.92),
}
#: (requested dtype, jax x64): float64 with x64 on is the effective-float64
#: branch (columns written as they are); the other two centre and cast.
MODES = {
    "f32": (np.float32, True),
    "f64_x64_on": (np.float64, True),
    "f64_x64_off": (np.float64, False),
}


KEPT = base.lane_scratch()
KEPT[0].fill(np.nan)
KEPT[1].fill(True)


def reference(grid, x, y, oid, dtype):
    """The sequence ``soa_point_batches`` ran before ``point_lanes``."""
    xy64 = np.stack([np.asarray(x, np.float64), np.asarray(y, np.float64)],
                    axis=1)
    n, b = len(xy64), next_bucket(len(xy64))
    return (
        pad_to_bucket(center_coords(grid, xy64, dtype), b),
        pad_to_bucket(np.ones(n, bool), b, fill=False),
        pad_to_bucket(grid.assign_cells_np(xy64), b, fill=grid.num_cells),
        None if oid is None
        else pad_to_bucket(np.asarray(oid, np.int32), b, fill=0),
    )


def assert_same_lanes(got, want):
    assert len(got) == len(want) == 4
    for name, g, w in zip(("xy", "valid", "cell", "oid"), got, want):
        if w is None:
            assert g is None, name
            continue
        assert g.dtype == w.dtype and g.shape == w.shape, name
        assert np.array_equal(g, w, equal_nan=g.dtype.kind == "f"), name
    assert got[0].flags.c_contiguous


def check(grid, x, y, oid, mode):
    dtype, x64 = MODES[mode]
    # NaN → int64 (the reference's cast) and 1e300 → float32 (both) warn
    with jax.enable_x64(x64), np.errstate(invalid="ignore", over="ignore"):
        got = point_lanes(grid, x, y, oid, dtype)
        want = reference(grid, x, y, oid, dtype)
        assert_same_lanes(got, want)
        # on a stream's kept scratch, dirty from whatever ran before
        assert_same_lanes(point_lanes(grid, x, y, oid, dtype, KEPT), want)
    return got


def _inside(grid, rng, n=3000):
    return (rng.uniform(grid.min_x, grid.max_x, n),
            rng.uniform(grid.min_y, grid.max_y, n))


def _edges(grid, rng, n=None):
    """Every cell edge of both axes (min and max among them), and the
    floats next to each, crossed with each other in a shuffled order."""
    k = np.arange(grid.n + 1)
    ex = grid.min_x + k * grid.cell_length
    ey = grid.min_y + k * grid.cell_length
    ex = np.concatenate([ex, [grid.max_x], np.nextafter(ex, -np.inf),
                         np.nextafter(ex, np.inf)])
    ey = np.concatenate([ey, [grid.max_y], np.nextafter(ey, -np.inf),
                         np.nextafter(ey, np.inf)])
    gx, gy = np.meshgrid(ex, ey, indexing="ij")
    order = rng.permutation(gx.size)
    return gx.ravel()[order], gy.ravel()[order]


def _outside(grid, rng, n=None):
    """A ring of points off each side and each corner."""
    span = grid.max_x - grid.min_x
    xs = np.array([grid.min_x - span, grid.min_x - 1e-9,
                   (grid.min_x + grid.max_x) / 2, grid.max_x + 1e-9,
                   grid.max_x + span])
    ys = np.array([grid.min_y - span, grid.min_y - 1e-9,
                   (grid.min_y + grid.max_y) / 2, grid.max_y + 1e-9,
                   grid.max_y + span])
    gx, gy = np.meshgrid(xs, ys, indexing="ij")
    return gx.ravel(), gy.ravel()


def _negzero(grid, rng, n=None):
    return (np.array([-0.0, 0.0, -0.0, 5.0, -0.0]),
            np.array([-0.0, -0.0, 5.0, -0.0, 0.0]))


def _nonfinite(grid, rng, n=None):
    odd = np.array([np.nan, np.inf, -np.inf, 1e300, -1e300,
                    (grid.min_x + grid.max_x) / 2])
    gx, gy = np.meshgrid(odd, odd, indexing="ij")
    # the in-grid value on y's axis is x's centre: shift it into y's range
    gy = np.where(gy == odd[-1], (grid.min_y + grid.max_y) / 2, gy)
    return gx.ravel(), gy.ravel()


def _float32_columns(grid, rng, n=3000):
    x, y = _inside(grid, rng, n)
    return x.astype(np.float32), y.astype(np.float32)


def _int64_columns(grid, rng, n=3000):
    lo = int(np.floor(min(grid.min_x, grid.min_y))) - 2
    hi = int(np.ceil(max(grid.max_x, grid.max_y))) + 2
    return rng.integers(lo, hi, n), rng.integers(lo, hi, n)


def _strided_columns(grid, rng, n=3000):
    xy64 = np.stack(_inside(grid, rng, n), axis=1)
    return xy64[:, 0], xy64[:, 1]


def _reversed_columns(grid, rng, n=3000):
    x, y = _inside(grid, rng, n)
    return x[::-1], y[::-1]


INPUTS = {f.__name__[1:]: f for f in (
    _inside, _edges, _outside, _negzero, _nonfinite, _float32_columns,
    _int64_columns, _strided_columns, _reversed_columns)}


@pytest.mark.parametrize("mode", MODES)
@pytest.mark.parametrize("grid", GRIDS)
@pytest.mark.parametrize("inputs", INPUTS)
def test_point_lanes_equal_the_whole_window_sequence(inputs, grid, mode, rng):
    g = GRIDS[grid]
    x, y = INPUTS[inputs](g, rng)
    oid = rng.integers(0, 1 << 14, len(x)).astype(np.int32)
    check(g, x, y, oid, mode)


@pytest.mark.parametrize("mode", MODES)
@pytest.mark.parametrize("n", [
    0, 1, 255, 256, 257, BLOCK - 1, BLOCK, BLOCK + 1, 2 * BLOCK,
    3 * BLOCK + 7,
], ids=lambda n: f"n{n}")
def test_point_lanes_at_every_block_and_bucket_boundary(n, mode, rng):
    """One short block, a block exactly, one point into the next, a bucket
    exactly (256 and 2 blocks: no tail to fill), several blocks and a
    remainder; points in and out of the grid mixed."""
    g = GRIDS["beijing"]
    x = rng.uniform(g.min_x - 0.1, g.max_x + 0.1, n)
    y = rng.uniform(g.min_y - 0.1, g.max_y + 0.1, n)
    oid = rng.integers(0, 1 << 14, n)
    xy, valid, cell, lane = check(g, x, y, oid, mode)
    assert len(valid) == next_bucket(n) and int(valid.sum()) == n
    assert (cell[n:] == g.num_cells).all() and not xy[n:].any()
    if n in (256, 2 * BLOCK):
        assert len(valid) == n


@pytest.mark.parametrize("oid", ["none", "int32", "int64", "list"])
def test_point_lanes_oid_lane(oid, rng):
    g = GRIDS["unit"]
    x, y = _inside(g, rng, 300)
    ids = rng.integers(0, 1 << 14, len(x))
    given = {"none": None, "int32": ids.astype(np.int32), "int64": ids,
             "list": ids.tolist()}[oid]
    lane = check(g, x, y, given, "f32")[3]
    if oid == "none":
        assert lane is None
    else:
        assert lane.dtype == np.int32 and (lane[:300] == ids).all()
        assert not lane[300:].any() and lane is not given


@pytest.mark.parametrize("grid", GRIDS)
@pytest.mark.parametrize("inputs", ["edges", "nonfinite", "float32_columns",
                                    "int64_columns"])
def test_assign_cells_into_is_assign_cells_np_on_scratch(inputs, grid, rng):
    """The grid's blocked method alone, on scratch longer than the block and
    dirty from a block before."""
    g = GRIDS[grid]
    x, y = INPUTS[inputs](g, rng)
    work = np.full((2, len(x) + 5), np.nan)
    mask = np.ones(work.shape, bool)
    out = np.full(len(x), -7, np.int32)
    g.assign_cells_into(x, y, out, work, mask)
    with np.errstate(invalid="ignore"):
        want = g.assign_cells_np(np.stack(
            [np.asarray(x, np.float64), np.asarray(y, np.float64)], axis=1))
    assert out.dtype == want.dtype and np.array_equal(out, want)


@pytest.mark.parametrize("mode", MODES)
def test_device_point_args_hands_point_lanes_its_two_columns(mode, rng):
    g = GRIDS["beijing"]
    dtype, x64 = MODES[mode]
    xy64 = np.stack(_inside(g, rng, 700), axis=1)
    oid = rng.integers(0, 99, len(xy64)).astype(np.int32)
    with jax.enable_x64(x64):
        got = device_point_args(g, xy64, oid, dtype)
        assert_same_lanes(got, reference(g, xy64[:, 0], xy64[:, 1], oid,
                                         dtype))


@pytest.mark.parametrize("n", [300, 256], ids=["padded", "bucket_exactly"])
def test_two_windows_in_turn_share_no_output_buffer(n, rng):
    """The consumer may hold a window's lanes while the next is prepared
    (the join holds the left side's; sliding neighbours overlap): each call
    allocates its own, and none aliases the columns it was given."""
    g = GRIDS["unit"]
    x, y = _inside(g, rng, n)
    oid = rng.integers(0, 99, n).astype(np.int32)
    first = point_lanes(g, x, y, oid, np.float32)
    kept = [a.copy() for a in first]
    second = point_lanes(g, x[::-1].copy(), y[::-1].copy(), oid[::-1].copy(),
                         np.float32)
    want = [a.copy() for a in second]
    for a in first:
        a[...] = 1  # the consumer scribbles over the first window's lanes
    assert all(np.array_equal(a, w) for a, w in zip(second, want))
    for a in second:
        a[...] = 0
    assert all((a == 1).all() for a in first)
    # and the given columns were only read
    third = point_lanes(g, x, y, oid, np.float32)
    assert all(np.array_equal(a, k) for a, k in zip(third, kept))
    assert not any(np.shares_memory(a, c) for a in third for c in (x, y, oid))


def test_record_soa_lanes_counts_as_fed_and_nothing_when_off(rng):
    g = GRIDS["beijing"]
    sizes = [0, 5, BLOCK, 2 * BLOCK + 1]
    cols = [_inside(g, rng, n) for n in sizes]
    telemetry.enable()
    try:
        assert "soa" not in telemetry.snapshot()
        for x, y in cols:
            point_lanes(g, x, y, None, np.float32)
        on = telemetry.snapshot()["soa"]
        spans = [e["name"] for e in telemetry.events
                 if e["name"].startswith("soa.")]
    finally:
        telemetry.disable()
    assert on == {"windows": 4, "points": sum(sizes),
                  "lanes": sum(next_bucket(n) for n in sizes),
                  "blocks": 0 + 1 + 1 + 3}
    # three spans a window, however many blocks it took
    assert spans == ["soa.center", "soa.cells", "soa.pad"] * 4
    for x, y in cols:
        point_lanes(g, x, y, None, np.float32)
    assert telemetry.snapshot()["soa"] == on  # off: one attribute check
    telemetry.enable()  # a fresh run starts from nothing
    try:
        assert "soa" not in telemetry.snapshot()
    finally:
        telemetry.disable()
