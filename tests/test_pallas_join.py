"""Pallas hit-extraction join (ops/pallas_join.py) — interpret-mode parity.

On TPU the dense-bucket join compacts hits with a Pallas kernel whose cost
is proportional to the MATCH count (the XLA nonzero path pays ~9 ns/lane
over the full span²·cells·capL·capR domain). These tests run the same
kernel through the Pallas interpreter on CPU and pin it to the brute-force
cross join and to the XLA bucketed kernel: identical pair sets, counts,
distances, and overflow semantics (exact iff overflow == 0 — the contract
of join/PointPointJoinQuery.java:124-183's windowed distance filter).
"""

import numpy as np
import pytest

import jax.numpy as jnp

from spatialflink_tpu.grid import UniformGrid
from spatialflink_tpu.models.objects import Point
from spatialflink_tpu.operators import (
    PointPointJoinQuery,
    QueryConfiguration,
    QueryType,
)
from spatialflink_tpu.ops.join import join_window_bucketed
from spatialflink_tpu.ops.pallas_join import join_window_pallas

GRID_N = 8


def _cells(xy):
    ci = np.clip(np.floor(xy).astype(np.int32), 0, GRID_N - 1)
    out = (ci[:, 0] * GRID_N + ci[:, 1]).astype(np.int32)
    oob = (xy < 0).any(axis=1) | (xy >= GRID_N).any(axis=1)
    out[oob] = GRID_N * GRID_N  # out-of-grid sentinel
    return out


def _pallas(axy, av, bxy, bv, r, cap=16, layers=1, max_pairs=4096):
    return join_window_pallas(
        jnp.asarray(axy), jnp.asarray(av), jnp.asarray(_cells(axy)),
        jnp.asarray(bxy), jnp.asarray(bv), jnp.asarray(_cells(bxy)),
        grid_n=GRID_N, layers=layers, radius=np.float32(r),
        cap_left=cap, cap_right=cap, max_pairs=max_pairs, interpret=True,
    )


def _pairs(res):
    li = np.asarray(res.left_index)
    ri = np.asarray(res.right_index)
    return {(int(a), int(b)) for a, b in zip(li, ri) if a >= 0}


def _brute(axy, av, bxy, bv, r):
    d = np.sqrt(((axy[:, None, :] - bxy[None, :, :]) ** 2).sum(-1))
    keep = (d <= r) & av[:, None] & bv[None, :]
    # In-grid only: out-of-grid points never join (reference key semantics).
    ain = ~((axy < 0).any(1) | (axy >= GRID_N).any(1))
    bin_ = ~((bxy < 0).any(1) | (bxy >= GRID_N).any(1))
    keep &= ain[:, None] & bin_[None, :]
    return {(int(a), int(b)) for a, b in zip(*np.nonzero(keep))}, d


@pytest.fixture
def data():
    rng = np.random.default_rng(7)
    n, m = 260, 240
    axy = rng.uniform(-0.5, GRID_N + 0.5, (n, 2)).astype(np.float32)
    bxy = rng.uniform(-0.5, GRID_N + 0.5, (m, 2)).astype(np.float32)
    av = rng.random(n) > 0.15
    bv = rng.random(m) > 0.15
    return axy, av, bxy, bv


def test_matches_bruteforce_and_distances(data):
    axy, av, bxy, bv = data
    r = 0.7
    res = _pallas(axy, av, bxy, bv, r)
    want, d = _brute(axy, av, bxy, bv, r)
    got = _pairs(res)
    assert got == want
    assert int(res.count) == len(want)
    assert int(res.overflow) == 0
    dm = {
        (int(a), int(b)): float(x)
        for a, b, x in zip(
            np.asarray(res.left_index), np.asarray(res.right_index),
            np.asarray(res.dist),
        )
        if a >= 0
    }
    for k in got:
        assert abs(dm[k] - d[k]) < 1e-5


def test_matches_xla_bucketed(data):
    axy, av, bxy, bv = data
    r = 0.9
    res_p = _pallas(axy, av, bxy, bv, r)
    res_x = join_window_bucketed(
        jnp.asarray(axy), jnp.asarray(av), jnp.asarray(_cells(axy)),
        jnp.asarray(bxy), jnp.asarray(bv), jnp.asarray(_cells(bxy)),
        grid_n=GRID_N, layers=1, radius=np.float32(r),
        cap_left=16, cap_right=16, max_pairs=4096,
    )
    assert _pairs(res_p) == _pairs(res_x)
    assert int(res_p.count) == int(res_x.count)
    assert int(res_p.overflow) == int(res_x.overflow)


def test_two_layer_radius(data):
    axy, av, bxy, bv = data
    r = 1.6  # ceil(1.6 / 1.0) = 2 grid layers
    res = _pallas(axy, av, bxy, bv, r, layers=2, max_pairs=65536)
    want, _ = _brute(axy, av, bxy, bv, r)
    assert _pairs(res) == want
    assert int(res.count) == len(want)


def test_overflow_reported_when_cap_exceeded(data):
    axy, av, bxy, bv = data
    res = _pallas(axy, av, bxy, bv, 0.7, cap=2)
    assert int(res.overflow) > 0  # 260 pts / 64 cells >> cap 2


def test_count_exceeding_budget_reports_true_total(data):
    axy, av, bxy, bv = data
    r = 0.9
    want, _ = _brute(axy, av, bxy, bv, r)
    res = _pallas(axy, av, bxy, bv, r, max_pairs=128)
    assert len(want) > 128
    assert int(res.count) == len(want)  # retry contract: true total


def test_empty_side():
    axy = np.zeros((16, 2), np.float32)
    av = np.zeros(16, bool)
    bxy = np.full((16, 2), 4.2, np.float32)
    bv = np.ones(16, bool)
    res = _pallas(axy, av, bxy, bv, 1.0)
    assert int(res.count) == 0
    assert _pairs(res) == set()


# -- the vector peel: every pass takes the next hit of every left row of a
# block, and places the column into the 128-lane output rows ----------------

HOT_CELL, NEXT_CELL = (3, 3), (3, 4)  # the second shares an edge with the first


def _in_cell(rng, cell, n, lo=0.05, hi=0.95):
    return (np.asarray(cell) + rng.uniform(lo, hi, (n, 2))).astype(np.float32)


def _scene(seed, hot_left, hot_right, n_uniform=140):
    """Sparse uniform points (some invalid, some out of the grid) around a
    crowded cell and a half-crowded neighbour: centre blocks with many hits
    a row, edge blocks with few, and rows and blocks with none."""
    rng = np.random.default_rng(seed)
    sides = []
    for hot in (hot_left, hot_right):
        u = rng.uniform(-0.5, GRID_N + 0.5, (n_uniform, 2)).astype(np.float32)
        crowded = np.isin(_cells(u), [c[0] * GRID_N + c[1]
                                      for c in (HOT_CELL, NEXT_CELL)])
        xy = np.concatenate([u[~crowded], _in_cell(rng, HOT_CELL, hot),
                             _in_cell(rng, NEXT_CELL, hot // 2)])
        valid = rng.random(len(xy)) > 0.1
        order = rng.permutation(len(xy))
        sides += [xy[order], valid[order]]
    return tuple(sides)


def _both(scene, r, cap_left, cap_right, max_pairs):
    axy, av, bxy, bv = scene
    args = (jnp.asarray(axy), jnp.asarray(av), jnp.asarray(_cells(axy)),
            jnp.asarray(bxy), jnp.asarray(bv), jnp.asarray(_cells(bxy)))
    kw = dict(grid_n=GRID_N, layers=1, radius=np.float32(r),
              cap_left=cap_left, cap_right=cap_right, max_pairs=max_pairs)
    return (join_window_pallas(*args, **kw, interpret=True),
            join_window_bucketed(*args, **kw))


def _sorted(res):
    """The result as a sorted (left, right, dist) list, after checking its
    own shape: ``count`` slots found, then -1 / inf, no pair twice."""
    li, ri, dd = (np.asarray(a) for a in res[:3])
    n = min(int(res.count), len(li))
    assert (li[:n] >= 0).all() and (ri[:n] >= 0).all()
    assert (li[n:] == -1).all() and (ri[n:] == -1).all()
    assert np.isinf(dd[n:]).all()
    out = sorted(zip(li[:n].tolist(), ri[:n].tolist(), dd[:n].tolist()))
    assert len({p[:2] for p in out}) == n
    return out


def _assert_same(res_p, res_x):
    got, want = _sorted(res_p), _sorted(res_x)
    assert int(res_p.count) == int(res_x.count) == len(want)
    assert int(res_p.overflow) == int(res_x.overflow) == 0
    assert [p[:2] for p in got] == [p[:2] for p in want]
    np.testing.assert_allclose([p[2] for p in got], [p[2] for p in want],
                               rtol=0, atol=1e-5)
    return got


def _passes(pairs, axy, bxy):
    """Passes the peel needs for these pairs: a block is one (left cell,
    right cell), and it takes as many passes as its fullest left row has
    hits."""
    lc, rc = _cells(axy), _cells(bxy)
    rows: dict = {}
    for a, b, _ in pairs:
        rows[(lc[a], rc[b], a)] = rows.get((lc[a], rc[b], a), 0) + 1
    blocks: dict = {}
    for (ca, cb, _), n in rows.items():
        blocks[(ca, cb)] = max(blocks.get((ca, cb), 0), n)
    return sum(blocks.values())


@pytest.mark.parametrize(
    "cap_left, cap_right",
    [(16, 16), (32, 32), (64, 64), (128, 128), (256, 256),
     (16, 64), (128, 32), (256, 128),
     # a constructor's cap off the ladder: 200 left rows are placed as
     # 128 + 72
     (48, 48), (200, 200)],
    ids=lambda c: f"cap{c}",
)
def test_vector_peel_on_every_rung(cap_left, cap_right):
    scene = _scene(cap_left + cap_right, (3 * cap_left) // 4,
                   (3 * cap_right) // 4)
    res_p, res_x = _both(scene, 0.3, cap_left, cap_right, max_pairs=16384)
    got = _assert_same(res_p, res_x)
    assert len(got) > cap_left  # the crowded cell alone fills rows
    assert res_x.peel_passes is None
    passes = int(res_p.peel_passes)
    assert passes == _passes(got, scene[0], scene[2])
    assert 0 < passes < len(got)


def _full_block_scene(extra_left=5):
    """Cell (1, 1): ``extra_left`` left points around one right point (one
    pass, so the row cursor is off a multiple of 16 afterwards); cell
    (3, 3): 16 × 16 points all within r of each other — every lane of the
    block a hit, 256 > one 128-lane row."""
    rng = np.random.default_rng(5)
    axy = np.concatenate([_in_cell(rng, (1, 1), extra_left, 0.45, 0.55),
                          _in_cell(rng, (3, 3), 16, 0.4, 0.6)])
    bxy = np.concatenate([_in_cell(rng, (1, 1), 1, 0.45, 0.55),
                          _in_cell(rng, (3, 3), 16, 0.4, 0.6)])
    return (axy, np.ones(len(axy), bool), bxy, np.ones(len(bxy), bool))


def test_every_lane_a_hit_wraps_rows_inside_a_pass():
    scene = _full_block_scene()
    res_p, res_x = _both(scene, 0.5, 16, 16, max_pairs=4096)
    got = _assert_same(res_p, res_x)
    assert len(got) == 5 + 256
    # one pass for the five, then sixteen of sixteen hits each: the eighth
    # of them starts at lane 5 + 7·16 = 117 and ends in the next row
    assert int(res_p.peel_passes) == 1 + 16


def test_pass_count_is_the_fullest_rows():
    """One left point with nine right points in reach, seven with one
    each, three with none: nine passes, the first of eight hits."""
    rng = np.random.default_rng(6)
    base = np.asarray([3.5, 3.5], np.float32)
    far = _in_cell(rng, (3, 3), 7, 0.05, 0.15)  # a corner of the cell
    axy = np.concatenate([base[None], far,
                          _in_cell(rng, (3, 3), 3, 0.8, 0.9)])
    bxy = np.concatenate([
        base + rng.uniform(-0.04, 0.04, (9, 2)).astype(np.float32),
        far + np.float32(0.01),
    ])
    scene = (axy, np.ones(len(axy), bool), bxy, np.ones(len(bxy), bool))
    res_p, res_x = _both(scene, 0.06, 16, 16, max_pairs=4096)
    got = _assert_same(res_p, res_x)
    by_left = np.bincount([p[0] for p in got], minlength=len(axy))
    assert by_left[0] == 9 and by_left.max() == 9
    assert (by_left[-3:] == 0).all() and len(got) >= 9 + 7
    assert int(res_p.peel_passes) == _passes(got, axy, bxy) == 9


def test_hits_straddle_stages_and_end_in_a_partial_one(monkeypatch):
    """A stage of two rows: blocks whose hits cross a row end, a stage
    end (a DMA to HBM at the running offset) and stop in the middle of
    both."""
    from spatialflink_tpu.ops import pallas_join

    monkeypatch.setattr(pallas_join, "STAGE_ROWS", 2)
    scene = _scene(23, 48, 48)
    # 1,900 is no other test's budget: this trace, and only this one, is
    # made under the two-row stage (15 rows → 16 = 8 stages)
    res_p, res_x = _both(scene, 0.3, 64, 64, max_pairs=1900)
    got = _assert_same(res_p, res_x)
    assert len(np.asarray(res_p.dist)) == 16 * 128
    rows, lanes = divmod(len(got), 128)
    assert rows >= 4 and rows % 2 == 1 and lanes > 0, (rows, lanes)


def test_nothing_is_written_past_the_budget():
    scene = _full_block_scene()
    res_p, res_x = _both(scene, 0.5, 16, 16, max_pairs=128)
    assert int(res_p.count) == int(res_x.count) == 5 + 256  # the true total
    li, ri, dd = (np.asarray(a) for a in res_p[:3])
    assert len(li) == len(ri) == len(dd) == 128  # one row, and no more
    _, full = _both(scene, 0.5, 16, 16, max_pairs=4096)
    want = {p[:2]: p[2] for p in _sorted(full)}
    held = list(zip(li.tolist(), ri.tolist()))
    assert len(set(held)) == 128 and set(held) <= set(want)
    np.testing.assert_allclose(dd, [want[p] for p in held], atol=1e-5)
    assert int(res_p.peel_passes) == 1 + 16  # the count runs on, so do they


def test_operator_pallas_backend_matches_default():
    rng = np.random.default_rng(3)
    grid = UniformGrid(20, 0.0, 10.0, 0.0, 10.0)
    conf = QueryConfiguration(QueryType.WindowBased, window_size=10, slide_step=10)
    left = [
        Point(obj_id=f"d{i % 5}", timestamp=i * 120,
              x=float(rng.uniform(0, 10)), y=float(rng.uniform(0, 10)))
        for i in range(160)
    ]
    right = [
        Point(obj_id=f"q{i}", timestamp=i * 190,
              x=float(rng.uniform(0, 10)), y=float(rng.uniform(0, 10)))
        for i in range(120)
    ]

    def run(backend):
        op = PointPointJoinQuery(conf, grid, join_backend=backend)
        return [
            {(a.obj_id, a.timestamp, b.obj_id): d for a, b, d in res.pairs}
            for res in op.run(iter(list(left)), iter(list(right)), 0.7)
        ]

    got = run("pallas_interpret")
    want = run(None)  # XLA path (float64 on CPU)
    assert len(got) == len(want)
    for g, w in zip(got, want):
        assert g.keys() == w.keys()
        for k in g:  # Pallas computes f32; distances agree to f32 eps
            assert abs(g[k] - w[k]) < 1e-5
