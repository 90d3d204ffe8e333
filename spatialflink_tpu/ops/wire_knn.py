"""Wire-plane kNN pane digest — ONE program for operator and suite.

The headline deployment ingests the 6 B/pt wire (streams/wire.py) fused
straight into the kNN pane digest. This module is the single home of
the wire→digest step; bench_suite's kNN configs and
PointPointKNNQuery.run_wire_panes both call it, so the measured program
IS the shipped program.

Two interchangeable strategies (bit-compatible candidate SETS, distance
values within 1 ulp — Mosaic vs XLA FMA freedom; tests/test_wire_knn.py
pins parity):

- ``xla``: plane dequant → distances → top-``cand`` compacted segment-
  min digest (ops/knn.py:_digest_from_point_dists_compact, with its
  built-in exact overflow fallback).
- ``pallas`` (TPU): the fused select-while-dequantizing extraction
  (ops/pallas_digest.py) with an IN-PROGRAM ``lax.cond`` fallback to
  the full XLA scatter digest whenever the hit count exceeds the
  candidate budget — exact either way.

``select_wire_digest_step`` owns the self-check contract
(run one pane both ways, require exact in-radius-set equality and ≤1 ulp
distances before trusting the Pallas lowering) for any caller.

Reference seam being replaced: Deserialization.java:149-211 (text
re-parse per record) feeding KNNQuery.java:204-308 (windowAll PQ merge).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from spatialflink_tpu.ops.knn import (
    _digest_from_point_dists,
    _digest_from_point_dists_compact,
)
from spatialflink_tpu.ops.pallas_digest import (
    PALLAS_DIGEST_MAX_CAND,
    wire_digest_pallas,
)
from spatialflink_tpu.runtime import on_tpu


def wire_plane_coords(wire_s, scale, origin):
    """(3, N) u16 plane-major wire → (xf, yf, oid) device planes.

    Contiguous (N,) planes keep dequant + distance fully lane-parallel
    (the (N, 2) row-major layout tiles onto only 2 of the 128 TPU
    lanes — the plane-major lever, BASELINE.md). The f32 upcast is
    bit-exact by the wire format's m×2^e scale contract
    (streams/wire.py)."""
    xf = wire_s[0].astype(jnp.float32) * scale[0] + origin[0]
    yf = wire_s[1].astype(jnp.float32) * scale[1] + origin[1]
    # int16 oid bits travel as uint16: values < 32768 upcast bit-exact.
    oid = wire_s[2].astype(jnp.int32)
    return xf, yf, oid


def wire_digest_xla(wire_s, n_valid, query_xy, scale, origin, radius,
                    *, num_segments: int, cand: int = 8192):
    """XLA strategy: plane-major dequant + distance → compacted digest.

    ``wire_s``: (3, N) uint16; ``n_valid``: logical count (positions
    past it are bucket padding — excluded via the valid mask, so a
    variable-size pane stream reuses one compiled shape). All other
    args traced; ``num_segments``/``cand`` static. N is the caller's
    pane-capacity bucket (run_wire_panes pads through the shared
    ladder, ops/compaction.py:wire_pane_bucket — each pick lands in
    telemetry's per-bucket occupancy log), so the whole dequant →
    distance → candidate pipeline scans O(pane-rounded-up) lanes and
    the compact step's ``cand >= N`` compile-time branch already
    short-circuits small buckets straight to the scatter digest.
    """
    xf, yf, oid = wire_plane_coords(wire_s, scale, origin)
    dx = xf - query_xy[0]
    dy = yf - query_xy[1]
    dist = jnp.sqrt(dx * dx + dy * dy)
    n = wire_s.shape[1]
    valid = jnp.arange(n, dtype=jnp.int32) < n_valid
    return _digest_from_point_dists_compact(
        dist, valid, None, oid, radius, num_segments,
        index_base=jnp.int32(0), cand=cand,
    )


def wire_digest_pallas_step(wire_s, n_valid, query_xy, scale, origin,
                            radius, *, num_segments: int,
                            max_cand: int = PALLAS_DIGEST_MAX_CAND,
                            interpret: bool = False):
    """Pallas strategy: fused extraction, exact via in-program fallback.

    Delegates the extraction (consts packing included — ONE home,
    ops/pallas_digest.py) to ``wire_digest_pallas``; if the hit count
    exceeds ``max_cand`` (truncated output) a ``lax.cond`` reruns the
    pane through the full XLA scatter digest — the step is exact either
    way."""
    d_pallas, cnt = wire_digest_pallas(
        wire_s, query_xy, scale, origin, radius, num_segments,
        max_cand=max_cand, interpret=interpret, n_valid=n_valid,
    )

    def from_candidates(_):
        return d_pallas

    def full_xla(_):
        xf, yf, oid = wire_plane_coords(wire_s, scale, origin)
        dx = xf - query_xy[0]
        dy = yf - query_xy[1]
        dist = jnp.sqrt(dx * dx + dy * dy)
        n = wire_s.shape[1]
        valid = jnp.arange(n, dtype=jnp.int32) < n_valid
        return _digest_from_point_dists(
            dist, valid, None, oid, radius, num_segments,
            index_base=jnp.int32(0),
        )

    return jax.lax.cond(cnt <= max_cand, from_candidates, full_xla, None)


def make_wire_digest_step(*, num_segments: int, cand: int = 8192,
                          strategy: str = "xla",
                          max_cand: int = PALLAS_DIGEST_MAX_CAND,
                          interpret: bool = False):
    """Bind the statics; returns ``fn(wire_s, n_valid, query_xy, scale,
    origin, radius) -> KnnPaneDigest`` ready for jax.jit / lax.scan
    embedding."""
    if strategy == "xla":
        return functools.partial(
            wire_digest_xla, num_segments=num_segments, cand=cand,
        )
    if strategy == "pallas":
        return functools.partial(
            wire_digest_pallas_step, num_segments=num_segments,
            max_cand=max_cand, interpret=interpret,
        )
    raise ValueError(f"strategy must be 'xla' or 'pallas', got {strategy!r}")


def digests_agree(seg_a, rep_a, seg_b, rep_b) -> bool:
    """The self-check predicate: identical in-radius object
    SETS, distances within 1 ulp (Mosaic vs XLA FMA freedom), and
    identical representatives wherever the distances agree exactly.
    Host-side (fetches both digests)."""
    sa, sb = jax.device_get((seg_a, seg_b))  # sfcheck: ok=trace-hygiene -- host-side self-check predicate (docstring): fetching both digests IS the job
    ra, rb = jax.device_get((rep_a, rep_b))  # sfcheck: ok=trace-hygiene -- same host-side self-check fetch as above
    big = np.asarray(np.finfo(sa.dtype).max, sa.dtype)
    live_a, live_b = sa != big, sb != big
    if not np.array_equal(live_a, live_b):
        return False
    if live_a.any():
        la, lb = sa[live_a], sb[live_a]
        ulp = np.spacing(np.maximum(np.abs(la), np.abs(lb)))
        if not np.all(np.abs(la - lb) <= ulp):
            return False
        exact = live_a & (sa == sb)
        if not np.array_equal(ra[exact], rb[exact]):  # sfcheck: ok=fixed-shape -- host-side numpy predicate (docstring), never traced
            return False
    return True


def select_wire_digest_step(sample_wire, sample_n, query_xy, scale,
                            origin, radius, *, num_segments: int,
                            cand: int = 8192,
                            max_cand: int = PALLAS_DIGEST_MAX_CAND,
                            interpret: bool = False,
                            strategy: str = "auto"):
    """Pick the digest strategy under the self-check contract.

    ``auto``: on TPU (or with ``interpret=True``), run ONE sample pane
    through both strategies and adopt Pallas only if ``digests_agree``;
    a NUMERICAL disagreement says so on stderr and stays on the exact
    XLA step. A lowering or compile error is a defect in the kernel,
    not a strategy — it propagates. Returns ``(kind, step_fn)``.
    """
    import sys

    xla_step = make_wire_digest_step(
        num_segments=num_segments, cand=cand, strategy="xla",
    )
    if strategy == "xla":
        return "xla", xla_step
    if strategy == "auto" and not (interpret or on_tpu()):
        return "xla", xla_step
    pallas_step = make_wire_digest_step(
        num_segments=num_segments, strategy="pallas",
        max_cand=max_cand, interpret=interpret,
    )
    args = (sample_wire, sample_n, query_xy, jnp.asarray(scale),
            jnp.asarray(origin), jnp.asarray(radius, jnp.float32))
    d_p = jax.jit(pallas_step)(*args)
    d_x = jax.jit(xla_step)(*args)
    if digests_agree(d_p.seg_min, d_p.rep, d_x.seg_min, d_x.rep):
        return "pallas", pallas_step
    if strategy == "pallas":
        raise RuntimeError(
            "strategy='pallas' was forced but the Pallas digest "
            "disagrees with the XLA step on the sample pane"
        )
    sys.stderr.write(
        "wire-digest self-check FAILED: pallas digest disagrees with "
        "the XLA step on the sample pane — staying on XLA\n"
    )
    return "xla", xla_step
