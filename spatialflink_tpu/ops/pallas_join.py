"""Pallas TPU grid-hash join — hit extraction in time ∝ matches.

The XLA dense-bucket join (ops.join.join_window_bucketed) evaluates the
pair predicate over span²·cells·capL·capR lanes essentially for free, but
compacting the hits with ``jnp.nonzero`` costs ~9 ns/lane on the TPU scalar
core (~2 s for a 131k×131k window at cap 48) because the cumsum+scatter
touches every lane. Real joins are sparse — ~1 M hits out of 1.5 G lanes at
two 500k-point sides — so this kernel walks the bucket planes once and
extracts each hit with an argmin-over-mask loop whose cost is proportional
to the HIT count:

  grid step = one cell row; per column and per neighbour bucket of the
  right side, one (capL, capR) pair mask is evaluated on the VPU and a
  while-loop peels off its set lanes one at a time (vector min-reduce over
  that one block, scalar store via an SMEM cursor).

The three output arrays live in HBM, not in VMEM: hits collect in a
128-lane register row, full rows in a ``STAGE_ROWS``-row VMEM stage, and a
full stage is copied out (one DMA per array) at the running offset. What
the outputs may hold is bounded by HBM; VMEM holds 3 × STAGE_ROWS × 512 B
whatever the budget.

Replaces the reference's replicate+shuffle+filter join
(join/JoinQuery.java:73-137, join/PointPointJoinQuery.java:124-183) as the
windowBased fast path on TPU. Same contract as join_window_bucketed:
results are exact iff overflow == 0; count > max_pairs means the caller
must retry with a bigger budget.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from spatialflink_tpu.ops.join import CompactJoinResult, bucketize_planes

#: Rows (of 128 pair slots) the VMEM stage holds before it is copied out to
#: HBM: 3 arrays × 512 rows × 512 B = 768 KB of VMEM at any budget.
STAGE_ROWS = 512


def _extract_kernel(
    radius_ref,
    lx_ref, ly_ref, lidx_ref,
    *rest,
    grid_n: int, layers: int, cap_left: int, cap_right: int,
    max_rows: int, stage_rows: int,
):
    span = 2 * layers + 1
    n_right = 3 * span  # rx, ry, ridx per dx
    right_refs = rest[:n_right]
    outl_ref, outr_ref, outd_ref, cnt_ref = rest[n_right:n_right + 4]
    sm, accl, accr, accd, stl, str_, std, sem = rest[n_right + 4:]
    lane_iota = jax.lax.broadcasted_iota(jnp.int32, (1, 128), 1)
    outs = ((stl, outl_ref), (str_, outr_ref), (std, outd_ref))

    i = pl.program_id(0)

    @pl.when(i == 0)
    def _init():
        sm[0] = 0  # total hit count
        sm[1] = 0  # lane of the register row the next hit takes (0..127)
        sm[2] = 0  # row of the stage the register row goes to
        sm[3] = 0  # stages copied out to HBM so far

    def copy_out():
        """The stage → rows [chunk·stage_rows, +stage_rows) of the outputs.
        Past the budget nothing is written; the count still runs on."""
        chunk = sm[3]

        @pl.when((chunk + 1) * stage_rows <= max_rows)
        def _dma():
            copies = [
                pltpu.make_async_copy(
                    st, out.at[pl.ds(chunk * stage_rows, stage_rows), :],
                    sem.at[k],
                )
                for k, (st, out) in enumerate(outs)
            ]
            for c in copies:
                c.start()
            for c in copies:
                c.wait()

        sm[3] = chunk + 1

    def stage_row():
        """The register row → the stage; a full stage → HBM."""
        srow = sm[2]
        stl[pl.ds(srow, 1), :] = accl[:]
        str_[pl.ds(srow, 1), :] = accr[:]
        std[pl.ds(srow, 1), :] = accd[:]
        sm[2] = srow + 1

        @pl.when(srow + 1 == stage_rows)
        def _full():
            copy_out()
            sm[2] = 0

    r2 = radius_ref[0, 0] * radius_ref[0, 0]
    row_any = jnp.sum((lidx_ref[0, :, :] >= 0).astype(jnp.int32)) > 0
    # Codes of one (capL, capR) block, row-major: the peel's order.
    code_iota = (
        jax.lax.broadcasted_iota(jnp.int32, (cap_left, cap_right), 0)
        * cap_right
        + jax.lax.broadcasted_iota(jnp.int32, (cap_left, cap_right), 1)
    )
    big = cap_left * cap_right

    def peel(mask, nhit, lidxv, sidx, d2):
        """Extract the ``nhit`` set lanes of one block, ascending code."""

        def cond(st):
            return st[1] > 0

        def body(st):
            # Scalar-only carry (last extracted code): Mosaic cannot
            # carry the (capL, capR) i1 mask through a while loop.
            last, remaining = st
            code = jnp.min(
                jnp.where(mask & (code_iota > last), code_iota, big)
            )
            # One-hot reduces instead of dynamic_slice (which Mosaic
            # does not lower): exactly one lane has code_iota == code.
            hot = code_iota == code
            lval = jnp.sum(jnp.where(hot, lidxv, 0))
            rval = jnp.sum(jnp.where(hot, sidx, 0))
            dval = jnp.sqrt(jnp.sum(jnp.where(hot, d2, 0.0)))
            # Scalar stores to VMEM are impossible on TPU; instead
            # accumulate into a 128-lane register row (one-hot
            # select) and hand full rows on with a vector store.
            lane = sm[1]
            lane_hot = lane_iota == lane
            accl[:] = jnp.where(lane_hot, lval, accl[:])
            accr[:] = jnp.where(lane_hot, rval, accr[:])
            accd[:] = jnp.where(lane_hot, dval.astype(jnp.float32), accd[:])
            sm[0] = sm[0] + 1
            sm[1] = lane + 1

            @pl.when(lane == 127)
            def _row_full():
                stage_row()
                sm[1] = 0

            return (code, remaining - 1)

        jax.lax.while_loop(cond, body, (jnp.int32(-1), nhit))

    @pl.when(row_any)
    def _row():
        def col_body(j, carry):
            lxv = lx_ref[0, j, :].reshape(cap_left, 1)
            lyv = ly_ref[0, j, :].reshape(cap_left, 1)
            lidxv = lidx_ref[0, j, :].reshape(cap_left, 1)
            lvalid = lidxv >= 0

            @pl.when(jnp.sum(lvalid.astype(jnp.int32)) > 0)
            def _cell():
                # One neighbour bucket at a time: a hit's cost is a pass
                # over its (capL, capR) block, not over all span² of them.
                for di in range(span):
                    rx_ref = right_refs[3 * di]
                    ry_ref = right_refs[3 * di + 1]
                    ridx_ref = right_refs[3 * di + 2]
                    for dy in range(-layers, layers + 1):
                        c = j + layers + dy  # column in the col-padded plane
                        sx = rx_ref[0, c, :].reshape(1, cap_right)
                        sy = ry_ref[0, c, :].reshape(1, cap_right)
                        sidx = ridx_ref[0, c, :].reshape(1, cap_right)
                        ddx = lxv - sx
                        ddy = lyv - sy
                        d2 = ddx * ddx + ddy * ddy
                        mask = lvalid & (sidx >= 0) & (d2 <= r2)
                        nhit = jnp.sum(mask.astype(jnp.int32))

                        @pl.when(nhit > 0)
                        def _extract():
                            peel(mask, nhit, lidxv, sidx, d2)

            return carry

        jax.lax.fori_loop(0, grid_n, col_body, 0)

    @pl.when(i == grid_n - 1)
    def _fin():
        # What is left in the register row and the stage goes out whole;
        # the caller masks every slot past the count.
        @pl.when(sm[1] > 0)
        def _partial_row():
            stage_row()

        @pl.when(sm[2] > 0)
        def _partial_stage():
            copy_out()

        cnt_ref[0, 0] = sm[0]


@functools.partial(
    jax.jit,
    static_argnames=(
        "grid_n", "layers", "cap_left", "cap_right", "max_pairs", "interpret"
    ),
)
def join_window_pallas(
    left_xy: jnp.ndarray,
    left_valid: jnp.ndarray,
    left_cells: jnp.ndarray,
    right_xy: jnp.ndarray,
    right_valid: jnp.ndarray,
    right_cells: jnp.ndarray,
    grid_n: int,
    layers: int,
    radius,
    cap_left: int,
    cap_right: int,
    max_pairs: int,
    interpret: bool = False,
) -> CompactJoinResult:
    """Dense-bucket grid join with Pallas hit extraction.

    Drop-in for ops.join.join_window_bucketed (same argument and result
    contract); float32 compute. ``interpret=True`` runs the Pallas
    interpreter for CPU testing.
    """
    f32 = jnp.float32
    max_pairs = int(max_pairs)  # sfcheck: ok=trace-hygiene -- static shape budget, a Python int at trace time (never traced)
    # Whole 128-lane rows, and whole stages of them once there are several.
    max_rows = -(-max_pairs // 128)
    stage_rows = min(STAGE_ROWS, max_rows)
    max_rows += (-max_rows) % stage_rows
    span = 2 * layers + 1
    lx, ly, lidx, l_over = bucketize_planes(
        left_xy.astype(f32), left_valid, left_cells, grid_n, cap_left
    )
    rx, ry, ridx, r_over = bucketize_planes(
        right_xy.astype(f32), right_valid, right_cells, grid_n, cap_right
    )
    # Pad the right planes by `layers` rows/cols so every neighbor access is
    # a static in-bounds slice; padding slots carry idx=-1 (never match).
    pad = ((layers, layers), (layers, layers), (0, 0))
    rxp = jnp.pad(rx, pad)
    ryp = jnp.pad(ry, pad)
    ridxp = jnp.pad(ridx, pad, constant_values=-1)

    cpad = grid_n + 2 * layers
    left_spec = lambda: pl.BlockSpec(
        (1, grid_n, cap_left), lambda i: (i, 0, 0), memory_space=pltpu.VMEM
    )
    right_specs = []
    for dx in range(-layers, layers + 1):
        for _ in range(3):
            right_specs.append(
                pl.BlockSpec(
                    (1, cpad, cap_right),
                    lambda i, d=dx: (i + layers + d, 0, 0),
                    memory_space=pltpu.VMEM,
                )
            )
    right_args = []
    for _ in range(span):
        right_args.extend([rxp, ryp, ridxp])

    kernel = functools.partial(
        _extract_kernel,
        grid_n=grid_n, layers=layers,
        cap_left=cap_left, cap_right=cap_right,
        max_rows=max_rows, stage_rows=stage_rows,
    )
    hbm = lambda: pl.BlockSpec(memory_space=pl.ANY)
    stage = lambda dtype: pltpu.VMEM((stage_rows, 128), dtype)
    outl, outr, outd, cnt = pl.pallas_call(
        kernel,
        grid=(grid_n,),
        in_specs=[
            pl.BlockSpec(memory_space=pltpu.SMEM),
            left_spec(), left_spec(), left_spec(),
            *right_specs,
        ],
        out_specs=[hbm(), hbm(), hbm(), pl.BlockSpec(memory_space=pltpu.SMEM)],
        out_shape=[
            jax.ShapeDtypeStruct((max_rows, 128), jnp.int32),
            jax.ShapeDtypeStruct((max_rows, 128), jnp.int32),
            jax.ShapeDtypeStruct((max_rows, 128), jnp.float32),
            jax.ShapeDtypeStruct((1, 1), jnp.int32),
        ],
        scratch_shapes=[
            pltpu.SMEM((4,), jnp.int32),
            pltpu.VMEM((1, 128), jnp.int32),
            pltpu.VMEM((1, 128), jnp.int32),
            pltpu.VMEM((1, 128), jnp.float32),
            stage(jnp.int32), stage(jnp.int32), stage(jnp.float32),
            pltpu.SemaphoreType.DMA((3,)),
        ],
        interpret=interpret,
    )(
        jnp.asarray(radius, f32).reshape(1, 1),
        lx, ly, lidx,
        *right_args,
    )
    # The kernel writes whole rows and stages; every slot past the count
    # (never written, or the tail of the last stage) gets the padding here.
    count = cnt[0, 0]
    found = jnp.arange(max_rows * 128, dtype=jnp.int32) < count
    return CompactJoinResult(
        jnp.where(found, outl.reshape(-1), -1),
        jnp.where(found, outr.reshape(-1), -1),
        jnp.where(found, outd.reshape(-1), jnp.inf),
        count, l_over + r_over,
    )
