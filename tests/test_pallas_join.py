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
