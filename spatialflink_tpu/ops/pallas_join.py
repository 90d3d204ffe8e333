"""Pallas TPU grid-hash join — hit extraction in vector passes.

The XLA dense-bucket join (ops.join.join_window_bucketed) evaluates the
pair predicate over span²·cells·capL·capR lanes essentially for free, but
compacting the hits with ``jnp.nonzero`` costs ~9 ns/lane on the TPU scalar
core (~2 s for a 131k×131k window at cap 48) because the cumsum+scatter
touches every lane. Real joins are sparse — ~1 M hits out of 1.5 G lanes at
two 500k-point sides — so this kernel walks the bucket planes once and
takes the hits out of each block that has any:

  grid step = one cell row; per column and per neighbour bucket of the
  right side, one (capL, capR) pair mask is evaluated on the VPU; a block
  with hits is peeled in PASSES, each of which takes the next hit of every
  left row at once.

How a hit leaves the kernel:

1. *The pass.* ``nxt`` = the smallest right slot each left row has not
   given yet (one lane reduction → a (capL, 1) column, a vector, never a
   scalar); the row's right index and d² follow by a one-hot select and a
   lane reduction each, the left index is the row's own. A block needs as
   many passes as its fullest row has hits; the loop carries the column
   of slots last taken (int32 — Mosaic cannot carry the i1 mask) and the
   hits still in the block, one scalar a pass.
2. *The placement.* The column's valid rows take consecutive slots of the
   output stream: slot = lane cursor + exclusive prefix count down the
   column (a strictly-lower-triangular 0/1 matmul, exact on the MXU),
   ``hot[i, k] = valid[i] & (slot[i] mod 128 == k)``, and each of the
   three output rows is a select and a sublane reduction over ``hot`` —
   transpose, compact and place in one step, exactly (the MXU never sees
   an index or a distance). A pass yields at most 128 slots (columns of
   more than 128 left rows are placed 128 at a time), distinct mod 128:
   lanes from the cursor up finish the current 128-lane register row,
   lanes below the wrapped end start the next one after it is staged.
3. *The way out.* A full register row goes to a ``STAGE_ROWS``-row VMEM
   stage — d² becomes d there, 128 square roots at a time — and a full
   stage is copied out (one DMA per array) at the running offset. The
   three output arrays live in HBM: what they may hold is bounded by HBM;
   VMEM holds 3 × STAGE_ROWS × 512 B whatever the budget.

SMEM holds five scalars: the hit count (it runs on past the budget; nothing
is written there), the lane cursor, the stage row, the stages copied out
and the passes made. Count and passes leave together ((1, 2) int32);
``pairs ÷ peel_passes`` is what a pass carries — 1 by construction for a
loop that takes one hit a trip (this kernel until PR 32: four block → scalar
reductions, a scalar ``sqrt`` and four SMEM updates a hit, 0.46 µs a hit).
Measured on a v5e at two 500,000-point sides, r = 0.002° on the 100-cell
Beijing grid, cap 128 (PERF.md §6, PR 32): 78,100 passes for 997,000 pairs
= 12.7 hits a pass, 10.9 passes an occupied cell, 0.41 µs a pass; the
kernel 459 → 38 ms a window.

Pairs leave a block in pass order (row-major inside a pass), blocks in the
grid's order: deterministic, and no consumer reads an order. The pair set
and every distance are those of the one-hit loop, bit for bit.

Mosaic limits that shaped it (jax 0.9.0, libtpu 0.0.34): no i1 block through
a ``while_loop``; no ``dynamic_slice`` or gather on a vector (hence one-hot
selects); no scalar stores to VMEM (hence the register row); ``tpu.iota``
makes no float32.

What a grid step holds, and where the kernel ends: the three left and nine
right plane blocks of one row of buckets (``grid_n · cap · 4 B`` each, double-
buffered) and the (cap, cap) blocks of the mask and the peel. Compiled for a
v5e (PERF.md §6, PR 41: no chip needed): 100 columns × cap 128 | 256 | 512,
200 × 256 | 512, 400 × 128 | 256 and 800 × 128 compile; 100 × 1,024 and
100 × 2,048 do not (the cap² blocks), nor 400 × 512, 800 × 256 and 800 × 512
(204,800 lanes a row: the planes' blocks) — VMEM exhausted each time. A cell
too crowded for rung 512 is therefore never met with a larger rung: the
capacity contract (``operators/join_query.py:JoinCapacity``) lays the buckets
on a finer grid — ``grid_n`` here is the key grid's n times its refinement,
``left_cells`` / ``right_cells`` the bucket cells ``ops/join.py:
join_window_cells`` makes — keeps to ``PALLAS_TOP_RUNG`` and
``PALLAS_ROW_LANES``, and refuses by name what neither holds. The walk visits
every bucket of every row that has a left point; an empty bucket costs its
count and a branch (skipping whole empty stretches is open: PERF.md §7).

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
    accs = (accl, accr, accd)

    i = pl.program_id(0)

    @pl.when(i == 0)
    def _init():
        sm[0] = 0  # total hit count
        sm[1] = 0  # lane of the register row the next hit takes (0..127)
        sm[2] = 0  # row of the stage the register row goes to
        sm[3] = 0  # stages copied out to HBM so far
        sm[4] = 0  # peel passes so far

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
        """The register row → the stage (d² → d here, 128 at a time); a
        full stage → HBM."""
        srow = sm[2]
        stl[pl.ds(srow, 1), :] = accl[:]
        str_[pl.ds(srow, 1), :] = accr[:]
        std[pl.ds(srow, 1), :] = jnp.sqrt(accd[:])
        sm[2] = srow + 1

        @pl.when(srow + 1 == stage_rows)
        def _full():
            copy_out()
            sm[2] = 0

    r2 = radius_ref[0, 0] * radius_ref[0, 0]
    row_any = jnp.sum((lidx_ref[0, :, :] >= 0).astype(jnp.int32)) > 0
    # Lane (right slot) of every position of one (capL, capR) block.
    col_iota = jax.lax.broadcasted_iota(jnp.int32, (cap_left, cap_right), 1)
    col_f32 = col_iota.astype(jnp.float32)
    # A pass's column is placed at most 128 left rows at a time, so that it
    # wraps the 128-lane register row at most once.
    chunks = [(r0, min(r0 + 128, cap_left)) for r0 in range(0, cap_left, 128)]

    def lower_triangle(n):
        """tri[i, j] = 1 where j < i: tri @ v is v's exclusive prefix sum
        down the column (0/1 operands, counts ≤ 128: exact on the MXU)."""
        return (
            jax.lax.broadcasted_iota(jnp.int32, (n, n), 1)
            < jax.lax.broadcasted_iota(jnp.int32, (n, n), 0)
        ).astype(jnp.float32)

    tri = {n: lower_triangle(n) for n in {r1 - r0 for r0, r1 in chunks}}

    def place(valid, lcol, scol, dcol):
        """One pass's (n ≤ 128, 1) column → the output stream: the valid
        rows, in row order, take the next lanes of the register row (a
        one-hot (n, 128) placement: transpose, compact and place in one
        step); a row that fills is staged and the rest start the next one.
        Returns the number of valid rows."""
        n = valid.shape[0]
        ones = jnp.where(valid, 1.0, 0.0).astype(jnp.float32)
        before = jnp.dot(
            tri[n], jnp.broadcast_to(ones, (n, 128)),
            preferred_element_type=jnp.float32,
        ).astype(jnp.int32)
        nv = jnp.sum(valid.astype(jnp.int32))
        lane = sm[1]
        # Slots are consecutive from the cursor and at most 128: distinct
        # mod 128, so one ring row holds the tail of this register row
        # (lanes ≥ cursor) and the head of the next (lanes < end − 128).
        hot = valid & (((lane + before) & 127) == lane_iota)
        rings = [
            jnp.sum(jnp.where(hot, col, 0), axis=0, keepdims=True)
            for col in (lcol, scol, dcol)
        ]
        held = lane_iota < lane
        for acc, ring in zip(accs, rings):
            acc[:] = jnp.where(held, acc[:], ring)
        end = lane + nv
        sm[0] = sm[0] + nv
        sm[1] = end & 127

        @pl.when(end >= 128)
        def _row_full():
            stage_row()
            for acc, ring in zip(accs, rings):
                acc[:] = ring

        return nv

    def peel(mask, nhit, lidxv, sidx, d2):
        """Extract the ``nhit`` set lanes of one block: every pass takes
        the next hit (ascending right slot) of every left row at once."""

        def cond(st):
            return st[1] > 0

        def body(st):
            # Carried: the lane each row took last, as an int32 column
            # (Mosaic cannot carry the (capL, capR) i1 mask through a
            # while loop), and the hits still in the block.
            last, remaining = st
            # The minimum runs on float32 (slots are small whole numbers,
            # exact there): an int32 lane minimum is dearer on the v5e.
            nxt = jnp.min(
                jnp.where(mask & (col_iota > last), col_f32, cap_right),
                axis=1, keepdims=True,
            ).astype(jnp.int32)
            # One-hot lane reduces instead of a gather: in a row that has
            # a hit left exactly one lane has col_iota == nxt, in a row
            # that has none (nxt == cap_right) no lane has.
            hot = col_iota == nxt
            scol = jnp.sum(jnp.where(hot, sidx, 0), axis=1, keepdims=True)
            dcol = jnp.sum(jnp.where(hot, d2, 0.0), axis=1, keepdims=True)
            valid = nxt < cap_right
            taken = jnp.int32(0)
            for r0, r1 in chunks:
                cut = slice(r0, r1)
                taken = taken + place(
                    valid[cut], lidxv[cut], scol[cut], dcol[cut]
                )
            sm[4] = sm[4] + 1
            return (nxt, remaining - taken)

        last0 = jnp.full((cap_left, 1), -1, jnp.int32)
        jax.lax.while_loop(cond, body, (last0, nhit))

    @pl.when(row_any)
    def _row():
        def col_body(j, carry):
            lxv = lx_ref[0, j, :].reshape(cap_left, 1)
            lyv = ly_ref[0, j, :].reshape(cap_left, 1)
            lidxv = lidx_ref[0, j, :].reshape(cap_left, 1)
            lvalid = lidxv >= 0

            @pl.when(jnp.sum(lvalid.astype(jnp.int32)) > 0)
            def _cell():
                # One neighbour bucket at a time: a pass's cost is a sweep
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
        cnt_ref[0, 1] = sm[4]


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
    left_payload=None,
    right_payload=None,
) -> CompactJoinResult:
    """Dense-bucket grid join with Pallas hit extraction.

    Drop-in for ops.join.join_window_bucketed (same argument and result
    contract, ``left_payload`` / ``right_payload`` included: a hit emits
    the two points' third-plane values, their payload or their index);
    float32 compute. ``interpret=True`` runs the Pallas interpreter for CPU
    testing.

    Both sides' planes come from ``bucketize_planes`` — a sort and a
    window a cell, no per-point index: 1.4 ms a side at 2¹⁹ lanes beside a
    38 ms extraction, where its gathers and scatters took 24.5 (my chip
    run, PR 40).
    """
    f32 = jnp.float32
    max_pairs = int(max_pairs)  # sfcheck: ok=trace-hygiene -- static shape budget, a Python int at trace time (never traced)
    # Whole 128-lane rows, and whole stages of them once there are several.
    max_rows = -(-max_pairs // 128)
    stage_rows = min(STAGE_ROWS, max_rows)
    max_rows += (-max_rows) % stage_rows
    span = 2 * layers + 1
    lx, ly, lidx, l_over = bucketize_planes(
        left_xy.astype(f32), left_valid, left_cells, grid_n, cap_left,
        left_payload,
    )
    rx, ry, ridx, r_over = bucketize_planes(
        right_xy.astype(f32), right_valid, right_cells, grid_n, cap_right,
        right_payload,
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
            jax.ShapeDtypeStruct((1, 2), jnp.int32),
        ],
        scratch_shapes=[
            pltpu.SMEM((5,), jnp.int32),
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
        count, l_over + r_over, cnt[0, 1],
    )
