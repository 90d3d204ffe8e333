"""Pallas TPU fused wire→candidate extraction for the kNN pane digest.

The XLA compact digest (ops/knn.py:_digest_from_point_dists_compact)
materializes either a full per-pane sort (top_k) or an ~N·per_block
one-hot tensor (blocked select) just to find the few-thousand in-radius
points of a 500k-point slide. This kernel walks the wire planes ONCE:
dequantize → distance → radius mask on the VPU, then an argmin-peel
while-loop extracts each hit in time ∝ matches (the pallas_join
extraction idiom — one-hot lane accumulate + 128-lane row flush; scalar
VMEM stores don't exist on TPU). The segment-min digest over the ≤
``max_cand`` compacted hits stays in (tested) XLA.

BASELINE.md roofline: after the r4 layout/donation levers the blocked
select's one-hot is the largest remaining term (~8M lanes/slide); this
kernel replaces it with one streaming pass (~3 MB wire read) + O(hits)
peeling — the "select-while-dequantizing" lever.

Exactness contract: ``count`` > ``max_cand`` means truncation — the
caller must fall back to the XLA digest (same retry family as the
compact path's ``cand``). Distances are the same explicit
mul-add/sqrt f32 ops as the headline step; XLA's FMA fusion may differ
by ≤1 ulp from Mosaic's, so the selector self-checks one pane against
the XLA path before trusting the kernel
(ops/wire_knn.py:select_wire_digest_step).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

# (max_cand // 128) × 128 rows of dist/oid/idx stay VMEM-resident: 12 B
# per slot, same budget math as pallas_join.
PALLAS_DIGEST_MAX_CAND = 16_384


def _extract_kernel(
    consts_ref,  # (1, 8) f32: radius, sx, ox, qx, sy, oy, qy, n_valid
    xq_ref, yq_ref, oid_ref,  # (1, BLK) i32 rows
    outd_ref, outoid_ref, outidx_ref, cnt_ref,
    sm, accd, acco, acci,
    blk: int, max_cand: int,
):
    i = pl.program_id(0)
    max_rows = max_cand // 128
    lane_iota = jax.lax.broadcasted_iota(jnp.int32, (1, 128), 1)

    @pl.when(i == 0)
    def _init():
        outd_ref[:] = jnp.full((max_rows, 128), jnp.inf, jnp.float32)
        outoid_ref[:] = jnp.zeros((max_rows, 128), jnp.int32)
        outidx_ref[:] = jnp.full((max_rows, 128), -1, jnp.int32)
        sm[0] = 0  # total hits
        sm[1] = 0  # flushed count (multiple of 128)

    radius = consts_ref[0, 0]
    sx = consts_ref[0, 1]
    ox = consts_ref[0, 2]
    qx = consts_ref[0, 3]
    sy = consts_ref[0, 4]
    oy = consts_ref[0, 5]
    qy = consts_ref[0, 6]

    xf = xq_ref[0, :].astype(jnp.float32) * sx + ox
    yf = yq_ref[0, :].astype(jnp.float32) * sy + oy
    dx = xf - qx
    dy = yf - qy
    # Same predicate as the XLA digest (sqrt THEN compare, knn.py) — a
    # d² <= r² test would classify radius-boundary points differently
    # within f32 rounding and break the set-parity self-check.
    dist = jnp.sqrt(dx * dx + dy * dy).reshape(1, blk)
    # consts slot 7 carries the logical point count (f32, exact for
    # counts < 2^24 — far above any pane size): positions >= n_valid are
    # bucket padding from a variable-size pane and can never match.
    # The wrapper always writes this slot.
    n_valid = consts_ref[0, 7]
    # Mosaic's iota is integer-only: build the index in int32, then
    # convert (exact below 2^24).
    gidx = (i * blk + jax.lax.broadcasted_iota(jnp.int32, (1, blk), 1)
            ).astype(jnp.float32)
    mask = (dist <= radius) & (gidx < n_valid)
    nhit = jnp.sum(mask.astype(jnp.int32))

    @pl.when(nhit > 0)
    def _extract():
        code_iota = jax.lax.broadcasted_iota(jnp.int32, (1, blk), 1)
        oid_row = oid_ref[0, :].reshape(1, blk)
        big = blk

        def cond(st):
            return st[1] > 0

        def body(st):
            last, remaining = st
            code = jnp.min(jnp.where(mask & (code_iota > last),
                                     code_iota, big))
            hot = code_iota == code
            dval = jnp.sum(jnp.where(hot, dist, 0.0))
            oval = jnp.sum(jnp.where(hot, oid_row, 0))
            s = sm[0]
            base = sm[1]
            lane = s - base
            lane_hot = lane_iota == lane
            accd[:] = jnp.where(lane_hot, dval.astype(jnp.float32), accd[:])
            acco[:] = jnp.where(lane_hot, oval, acco[:])
            acci[:] = jnp.where(lane_hot, i * blk + code, acci[:])
            sm[0] = s + 1

            @pl.when((lane == 127) & (base // 128 < max_rows))
            def _flush():
                row = base // 128
                outd_ref[pl.ds(row, 1), :] = accd[:]
                outoid_ref[pl.ds(row, 1), :] = acco[:]
                outidx_ref[pl.ds(row, 1), :] = acci[:]
                sm[1] = base + 128

            return (code, remaining - 1)

        jax.lax.while_loop(cond, body, (jnp.int32(-1), nhit))

    @pl.when(i == pl.num_programs(0) - 1)
    def _fin():
        cnt = sm[0]
        base = sm[1]

        @pl.when((cnt > base) & (base // 128 < max_rows))
        def _partial_flush():
            ok = lane_iota < (cnt - base)
            row = base // 128
            outd_ref[pl.ds(row, 1), :] = jnp.where(ok, accd[:], jnp.inf)
            outoid_ref[pl.ds(row, 1), :] = jnp.where(ok, acco[:], 0)
            outidx_ref[pl.ds(row, 1), :] = jnp.where(ok, acci[:], -1)

        cnt_ref[0, 0] = cnt


@functools.partial(
    jax.jit,
    static_argnames=("blk", "max_cand", "interpret"),
)
def wire_candidates_pallas(
    xq: jnp.ndarray,
    yq: jnp.ndarray,
    oid: jnp.ndarray,
    consts: jnp.ndarray,
    blk: int = 2048,
    max_cand: int = PALLAS_DIGEST_MAX_CAND,
    interpret: bool = False,
    n_valid=None,
):
    """Wire planes → compacted in-radius (dist, oid, index) + count.

    ``xq``/``yq``/``oid``: (N,) int32 (u16 wire values widened by XLA —
    Mosaic-friendly); ``consts``: (1, 8) f32 [radius, sx, ox, qx, sy,
    oy, qy, n_valid]. N is padded to a ``blk`` multiple internally
    (padding lanes sit at an astronomical distance). ``n_valid`` (traced
    scalar, default N) marks the logical point count when the caller
    bucket-padded a variable-size pane — positions past it never match;
    slot 7 of ``consts`` is overwritten with it either way. ``count`` >
    ``max_cand`` ⇒ truncated (caller falls back); indices are original
    positions, -1 padding.
    """
    n = xq.shape[0]
    if n_valid is None:
        n_valid = n
    consts = consts.at[0, 7].set(jnp.asarray(n_valid, jnp.float32))
    pad = (-n) % blk
    if pad:
        # Padding lanes carry a coordinate far outside any grid extent
        # (2^30 quantized units): dequantized distance is astronomically
        # large, so they can never pass the radius mask — the headline
        # SLIDE (500k) need not divide by blk.
        far = jnp.int32(1 << 30)
        xq = jnp.concatenate([xq, jnp.full((pad,), far, jnp.int32)])
        yq = jnp.concatenate([yq, jnp.full((pad,), far, jnp.int32)])
        oid = jnp.concatenate([oid, jnp.zeros((pad,), jnp.int32)])
        n = n + pad
    nb = n // blk
    max_rows = max_cand // 128
    grid = (nb,)
    row = lambda a: a.reshape(nb, 1, blk)
    outd, outoid, outidx, cnt = pl.pallas_call(
        functools.partial(_extract_kernel, blk=blk, max_cand=max_cand),
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, 8), lambda i: (0, 0),
                         memory_space=pltpu.SMEM),
            pl.BlockSpec((1, 1, blk), lambda i: (i, 0, 0)),
            pl.BlockSpec((1, 1, blk), lambda i: (i, 0, 0)),
            pl.BlockSpec((1, 1, blk), lambda i: (i, 0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((max_rows, 128), lambda i: (0, 0)),
            pl.BlockSpec((max_rows, 128), lambda i: (0, 0)),
            pl.BlockSpec((max_rows, 128), lambda i: (0, 0)),
            pl.BlockSpec((1, 1), lambda i: (0, 0),
                         memory_space=pltpu.SMEM),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((max_rows, 128), jnp.float32),
            jax.ShapeDtypeStruct((max_rows, 128), jnp.int32),
            jax.ShapeDtypeStruct((max_rows, 128), jnp.int32),
            jax.ShapeDtypeStruct((1, 1), jnp.int32),
        ],
        scratch_shapes=[
            pltpu.SMEM((2,), jnp.int32),
            pltpu.VMEM((1, 128), jnp.float32),
            pltpu.VMEM((1, 128), jnp.int32),
            pltpu.VMEM((1, 128), jnp.int32),
        ],
        interpret=interpret,
    )(consts, row(xq), row(yq), row(oid))
    return (
        outd.reshape(-1), outoid.reshape(-1), outidx.reshape(-1),
        cnt[0, 0],
    )


def digest_from_candidates(d, o, idx, num_segments: int):
    """Compacted (dist, oid, index) candidates → KnnPaneDigest — ONE
    home for the candidate segment-min reduction (the sentinel clamp
    and representative tie-break live here only)."""
    from spatialflink_tpu.ops.knn import KnnPaneDigest

    valid = idx >= 0
    big = jnp.asarray(jnp.finfo(jnp.float32).max, jnp.float32)
    int_big = jnp.iinfo(jnp.int32).max
    dm = jnp.where(valid, d, big)
    om = jnp.where(valid, o, 0)
    sm = jnp.minimum(
        jax.ops.segment_min(dm, om, num_segments=num_segments), big
    )
    win = valid & (dm == sm[om])
    rep = jax.ops.segment_min(
        jnp.where(win, idx, int_big), om, num_segments=num_segments
    )
    return KnnPaneDigest(sm, rep)


def wire_digest_pallas(
    wire_s: jnp.ndarray,
    query_xy: jnp.ndarray,
    scale,
    origin,
    radius,
    num_segments: int,
    max_cand: int = PALLAS_DIGEST_MAX_CAND,
    interpret: bool = False,
    n_valid=None,
):
    """(3, N) u16 wire planes → KnnPaneDigest via the fused extraction.

    Returns (digest, count): exact iff ``count <= max_cand`` — the
    caller owns the fallback (ops/wire_knn.py wraps this with the
    in-program lax.cond fallback, and its selector self-checks one
    pane before adopting the kernel)."""
    consts = jnp.asarray(
        [[radius, scale[0], origin[0], query_xy[0],
          scale[1], origin[1], query_xy[1], 0.0]], jnp.float32,
    )
    d, o, idx, cnt = wire_candidates_pallas(
        wire_s[0].astype(jnp.int32), wire_s[1].astype(jnp.int32),
        wire_s[2].astype(jnp.int32), consts,
        max_cand=max_cand, interpret=interpret, n_valid=n_valid,
    )
    return digest_from_candidates(d, o, idx, num_segments), cnt
