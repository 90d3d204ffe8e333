"""Fused Pallas wire-digest (ops/pallas_digest.py) vs the XLA digest
oracle, in interpret mode (the TPU lowering runs on the chip bench with
a runtime self-check — bench.py)."""

import numpy as np
import pytest


import jax
import jax.numpy as jnp

from spatialflink_tpu.grid import UniformGrid
from spatialflink_tpu.ops.pallas_digest import wire_digest_pallas
from spatialflink_tpu.streams.wire import WireFormat

GRID = UniformGrid(100, min_x=115.5, max_x=117.6, min_y=39.6, max_y=41.1)


@pytest.fixture
def rng():
    return np.random.default_rng(5)


def _wire(rng, n, nseg=512):
    wf = WireFormat.for_grid(GRID)
    xyq = wf.quantize(np.stack(
        [rng.uniform(115.5, 117.6, n), rng.uniform(39.6, 41.1, n)], axis=1
    ))
    oid = rng.integers(0, nseg, n).astype(np.int16)
    wire = np.concatenate([xyq, oid.view(np.uint16)[:, None]], axis=1)
    return wf, np.ascontiguousarray(wire.T)


def _oracle(wf, wire_t, q, radius, nseg):
    from spatialflink_tpu.ops.distances import point_point_distance
    from spatialflink_tpu.ops.knn import _digest_from_point_dists

    xy = wf.dequantize(jnp.asarray(wire_t[:2].T))
    dist = point_point_distance(xy, jnp.asarray(q)[None, :])
    return _digest_from_point_dists(
        dist, jnp.ones(wire_t.shape[1], bool), None,
        jnp.asarray(wire_t[2].astype(np.int32)), np.float32(radius), nseg,
        index_base=jnp.int32(0),
    )


def test_wire_digest_pallas_matches_oracle(rng):
    n, nseg, radius = 4096, 512, 0.05
    wf, wire_t = _wire(rng, n, nseg)
    q = np.asarray([116.40, 40.19], np.float32)
    dig, cnt = wire_digest_pallas(
        jnp.asarray(wire_t), jnp.asarray(q), wf.scale, wf.origin,
        np.float32(radius), num_segments=nseg, max_cand=2048,
        interpret=True,
    )
    assert int(cnt) <= 2048, "test sized to fit the candidate budget"
    ref = _oracle(wf, wire_t, q, radius, nseg)
    sa, sb = np.asarray(dig.seg_min), np.asarray(ref.seg_min)
    big = np.float32(np.finfo(np.float32).max)
    # distance rounding may differ by <= 1 ulp (FMA fusion freedom);
    # the in-radius SET must match exactly
    assert np.array_equal(sa == big, sb == big)
    both = sa != big
    assert both.sum() > 5, "degenerate: no in-radius objects"
    ulp = np.spacing(np.maximum(np.abs(sa), np.abs(sb)).astype(np.float32))
    assert np.all(np.abs(sa[both] - sb[both]) <= ulp[both])
    # representatives must agree wherever distances agree bitwise
    same = both & (sa == sb)
    ra, rb = np.asarray(dig.rep), np.asarray(ref.rep)
    assert np.array_equal(ra[same], rb[same])


def test_wire_digest_pallas_count_overflow_flagged(rng):
    n, nseg = 2048, 64
    wf, wire_t = _wire(rng, n, nseg)
    q = np.asarray([116.40, 40.19], np.float32)
    # huge radius: every point matches, far over the candidate budget
    dig, cnt = wire_digest_pallas(
        jnp.asarray(wire_t), jnp.asarray(q), wf.scale, wf.origin,
        np.float32(5.0), num_segments=nseg, max_cand=256, interpret=True,
    )
    assert int(cnt) == n  # honest count even though output truncated


def test_wire_digest_pallas_empty_radius(rng):
    n, nseg = 2048, 64
    wf, wire_t = _wire(rng, n, nseg)
    q = np.asarray([116.40, 40.19], np.float32)
    dig, cnt = wire_digest_pallas(
        jnp.asarray(wire_t), jnp.asarray(q), wf.scale, wf.origin,
        np.float32(1e-9), num_segments=nseg, max_cand=256, interpret=True,
    )
    assert int(cnt) == 0
    big = np.float32(np.finfo(np.float32).max)
    assert np.all(np.asarray(dig.seg_min) == big)


def test_wire_digest_pallas_non_divisible_n(rng):
    """The headline SLIDE (500k) is not a blk multiple — padding lanes
    must never enter the candidate set."""
    n, nseg, radius = 3000, 128, 0.08  # 3000 % 2048 != 0
    wf, wire_t = _wire(rng, n, nseg)
    q = np.asarray([116.40, 40.19], np.float32)
    dig, cnt = wire_digest_pallas(
        jnp.asarray(wire_t), jnp.asarray(q), wf.scale, wf.origin,
        np.float32(radius), num_segments=nseg, max_cand=2048,
        interpret=True,
    )
    ref = _oracle(wf, wire_t, q, radius, nseg)
    sa, sb = np.asarray(dig.seg_min), np.asarray(ref.seg_min)
    big = np.float32(np.finfo(np.float32).max)
    assert np.array_equal(sa == big, sb == big)
    assert (sa != big).sum() > 5
    # all extracted indices must point inside the real N
    rep = np.asarray(dig.rep)
    live = rep != np.iinfo(np.int32).max
    assert live.any() and int(rep[live].max()) < n
