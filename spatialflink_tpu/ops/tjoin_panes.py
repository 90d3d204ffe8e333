"""Pane-carry tJoin — the extreme-overlap sliding trajectory join.

The reference's windowBased tJoin re-walks the whole window per fire
(tJoin/PointPointTJoinQuery.java:183+); at the domain's extreme-overlap
configs (10 s windows sliding every 10 ms — Q2_BrakeMonitor's window
style, ppw = 1000) that is a 1000× redundant recompute per slide, and so
is this repo's ``run_soa`` (one full-window join per fire). This module
keeps the WINDOW STATE ON DEVICE and does only O(new-pane) join work per
slide:

- **Ring-buffer bucket planes** per stream side: (cells · capW) slots of
  x/y/oid/pane-tag with a per-cell write cursor. Inserting a pane is a
  small scatter; expiry is LAZY — probes mask slots whose pane tag left
  the window, and a slot is reused (cursor ring) long after it expired.
- **Min-pane-indexed pair digests**: ``D[m % ppw, lid·K + rid]`` = min
  point-pair distance among pairs whose EARLIER point sits in pane
  ``m``. A point pair (i ≤ j) is alive for window [s, s+ppw) iff i ≥ s,
  and every contribution discovered so far has j ≤ current pane — so at
  emission time ``min over m ∈ [s, t]`` of D is exactly the window's
  per-trajectory-pair min distance (the tStats min-pane argument,
  applied to a bilinear join).
- Per slide: probe the new LEFT pane against the RIGHT window planes,
  insert the left pane, probe the new RIGHT pane against the LEFT
  planes (now containing pane t — covers new×new exactly once), insert
  the right pane, then reduce the digest ring for the window ending at
  pane t. All of it is one ``lax.scan`` step — one dispatch per BATCH
  of slides, not per slide (per-dispatch overhead, CLAUDE.md).

- **Live-slot compaction** (``cap_c > 0``, the default off-TPU): the
  ring with lazy expiry is a per-cell FIFO — points insert in pane
  order and expire in pane order — so the LIVE slots of a cell row are
  always the contiguous ``[cursor - live, cursor)`` range (mod capW).
  The carry maintains per-cell live counts (two tiny scatter-adds per
  slide: subtract the expiring pane, add the new one), and the probe
  gathers only ``cap_c`` lanes from each neighbor cell's head instead
  of the full ``capW`` ring row, masking by POSITION (lane < live)
  instead of gathering and comparing pane tags. ``cap_c`` is a static
  bucket from the host-picked capacity ladder (ops/compaction.py — the
  host reads the live counts, the device program stays fixed-shape per
  bucket, ≤6 programs per engine), and first-``pair_sel`` selection is
  the sort-free prefix-sum binary search (ops/select.py:
  first_k_prefix_indices) — together they removed the ``lax.top_k``
  full sort and the dead-slot gathers that made the XLA:CPU scan ~50×
  slower than the native engine. ``cap_c = 0``
  keeps the original full-ring row-gather probe (the TPU-preferred
  form, and the parity oracle for the compacted path).

Exactness contract (same family as the other join kernels): results
equal ``run_soa`` iff ``cap_overflow == 0`` (a live window slot was
never overwritten — grow ``capW``), ``sel_overflow == 0`` (no probe
point matched more than ``pair_sel`` window points — grow
``pair_sel``) and ``cmp_overflow == 0`` (no PROBED cell held more than
``cap_c`` live points — climb the capacity ladder; never fires when the
host planned ``cap_c`` from ops/compaction.py:max_window_cell_count).
Digest memory is ``ppw · K² · 4`` bytes (K = interned
trajectory ids per side): extreme overlap trades memory for the 1000×
work cut, sized for the domain's dozens-to-hundreds of vehicles.
"""

from __future__ import annotations

from typing import NamedTuple

import jax
import jax.numpy as jnp

import numpy as np

from spatialflink_tpu.ops.select import (
    first_k_onehot,
    first_k_prefix_indices,
    onehot_select_preferred,
)


def pane_cell_ranks(pane: "np.ndarray", cell: "np.ndarray",
                    valid: "np.ndarray" = None) -> "np.ndarray":
    """Within-(pane, cell) slot ranks, vectorized — the host half of
    ``_insert``'s ring-slot contract (a pane's same-cell points need
    distinct slots). ONE home, shared by the operator wrapper and the
    benchmark staging (drift here would silently change collision
    behavior between the product path and the measured path).

    ``valid``: rank INVALID (out-of-grid) events in their own group, not
    the cell their placeholder id aliases. ``_insert`` drops invalid
    points and advances the cursor only by the valid count, so a valid
    point whose rank counted a preceding invalid same-cell event would
    land BEYOND the cursor — outside the ``[cursor - live, cursor)``
    range the compacted probe treats as the live slots (a silent missed
    pair; the full-ring probe's tag scan was immune, which is why this
    stayed latent until the positional probe — code review)."""
    n = len(pane)
    if valid is not None:
        cell = np.where(valid, cell, -1)
    order = np.lexsort((cell, pane))
    ps, cs = pane[order], cell[order]
    newrun = np.ones(n, bool)
    if n > 1:
        newrun[1:] = (ps[1:] != ps[:-1]) | (cs[1:] != cs[:-1])
    run_id = np.cumsum(newrun) - 1
    pos = np.arange(n)
    rank = np.empty(n, np.int64)
    rank[order] = pos - pos[newrun][run_id]
    return rank


class TJoinPaneCarry(NamedTuple):
    lwx: jnp.ndarray  # (cells*capW,) left window planes
    lwy: jnp.ndarray
    lwoid: jnp.ndarray  # int32
    lwtag: jnp.ndarray  # int32 pane index, very negative = empty
    lwcur: jnp.ndarray  # (cells,) int32 ring cursor
    lwlive: jnp.ndarray  # (cells,) int32 unexpired points in the ring
    rwx: jnp.ndarray
    rwy: jnp.ndarray
    rwoid: jnp.ndarray
    rwtag: jnp.ndarray
    rwcur: jnp.ndarray
    rwlive: jnp.ndarray
    digests: jnp.ndarray  # (ppw, K*K) min-pane-indexed pair min dists
    block_digests: jnp.ndarray  # (ppw/bs, K*K) per-block mins of `digests`
    cap_overflow: jnp.ndarray  # () int32
    sel_overflow: jnp.ndarray  # () int32
    cmp_overflow: jnp.ndarray  # () int32 — probed cell live > cap_c


def block_size(ppw: int) -> int:
    """Digest-ring block length for the hierarchical window reduce: the
    divisor of ``ppw`` closest to √ppw, so the per-slide reduce cost
    bs·K² (one block recompute) + (ppw/bs)·K² (block-row min) is
    ~2√ppw·K² instead of the flat ppw·K² (16× at the 10s/10ms shape).
    ppw prime degenerates to bs=1 ≡ the flat reduce."""
    best = 1
    for d in range(1, int(ppw ** 0.5) + 1):
        if ppw % d == 0:
            best = d
    return max(best, 1)


def tjoin_pane_init(
    num_cells: int, cap_w: int, ppw: int, num_ids: int, dtype,
) -> TJoinPaneCarry:
    """Fresh carry. ``num_ids`` = interned trajectory-id bucket (shared
    by both sides); digest row m holds pairs whose earlier pane is m.
    ``block_digests`` row b is maintained as the min over digest rows
    [b·bs, (b+1)·bs) — exact at every step because min-scatters update
    both levels and the one row reset per slide triggers exactly one
    block recompute (see tjoin_pane_step)."""
    slots = num_cells * cap_w
    empty_tag = jnp.int32(-(1 << 30))
    plane_f = jnp.zeros((slots,), dtype)
    plane_i = jnp.zeros((slots,), jnp.int32)
    tags = jnp.full((slots,), empty_tag, jnp.int32)
    cur = jnp.zeros((num_cells,), jnp.int32)
    inf = jnp.asarray(jnp.inf, dtype)
    bs = block_size(ppw)
    return TJoinPaneCarry(
        plane_f, plane_f, plane_i, tags, cur, cur,
        plane_f, plane_f, plane_i, tags, cur, cur,
        jnp.full((ppw, num_ids * num_ids), inf, dtype),
        jnp.full((ppw // bs, num_ids * num_ids), inf, dtype),
        jnp.zeros((), jnp.int32), jnp.zeros((), jnp.int32),
        jnp.zeros((), jnp.int32),
    )


def _cell_counts(live, pcell, pvalid, num_cells: int, sign: int):
    """live ± per-cell count of one pane's valid points (two tiny
    scatter-adds per slide keep the FIFO live-count invariant:
    live[c] == points of cell c inside the current window)."""
    return live.at[jnp.where(pvalid, pcell, num_cells)].add(
        jnp.int32(sign), mode="drop"
    )


def _probe(wx, wy, woid, wtag, t, px, py, pxi, pyi, poid, pvalid, radius,
           swap_pair, grid_n: int, cap_w: int, layers: int, ppw: int,
           num_ids: int, pair_sel: int):
    """New-pane points × window planes → (digest flat idx, dist,
    sel_overflow). Row gathers only (span² cell rows per point, never
    element gathers); per-point first-``pair_sel`` match selection is
    backend-gated (one-hot on TPU, top_k on CPU — ops/select.py)."""
    span = 2 * layers + 1
    offs = jnp.arange(-layers, layers + 1, dtype=jnp.int32)
    nx = pxi[:, None, None] + offs[None, :, None]  # (PC, span, 1)
    ny = pyi[:, None, None] + offs[None, None, :]  # (PC, 1, span)
    in_grid = (
        (nx >= 0) & (nx < grid_n) & (ny >= 0) & (ny < grid_n)
    ).reshape(-1, span * span)
    rows = jnp.clip(nx * grid_n + ny, 0, grid_n * grid_n - 1).reshape(
        -1, span * span
    )  # (PC, span²)

    w2 = lambda a: a.reshape(grid_n * grid_n, cap_w)
    gx = w2(wx)[rows]  # (PC, span², capW) — row gathers
    gy = w2(wy)[rows]
    gtag = w2(wtag)[rows]

    d = jnp.sqrt(
        (gx - px[:, None, None]) ** 2 + (gy - py[:, None, None]) ** 2
    )
    alive = (gtag > t - ppw) & (gtag <= t)
    mask = (
        pvalid[:, None, None] & in_grid[:, :, None] & alive & (d <= radius)
    ).reshape(len(px), -1)  # (PC, C)
    dflat = d.reshape(len(px), -1)
    tflat = gtag.reshape(len(px), -1)

    if onehot_select_preferred():
        goid = w2(woid)[rows]
        oflat = goid.reshape(len(px), -1)
        hit, count, sel_over = first_k_onehot(mask, pair_sel)
        # one-hot sums select exactly one lane — bit-exact values.
        sd = jnp.sum(jnp.where(hit, dflat[:, :, None], 0), axis=1)
        so = jnp.sum(hit * oflat[:, :, None], axis=1)
        st = jnp.sum(hit * tflat[:, :, None], axis=1)
    else:
        count = jnp.sum(mask.astype(jnp.int32), axis=1)
        sel_over = jnp.sum(jnp.maximum(count - pair_sel, 0))
        _v, ci = jax.lax.top_k(mask.astype(jnp.int8), pair_sel)
        sd = jnp.take_along_axis(dflat, ci, axis=1)
        st = jnp.take_along_axis(tflat, ci, axis=1)
        # oid only matters for the ≤ pair_sel SELECTED slots — an
        # element gather through the global slot ids replaces the third
        # (PC, span², capW) row gather (25% of probe gather traffic).
        grows = jnp.take_along_axis(rows, ci // cap_w, axis=1)
        so = woid[grows * cap_w + ci % cap_w]
    svalid = (
        jnp.arange(pair_sel, dtype=jnp.int32)[None, :]
        < jnp.minimum(count, pair_sel)[:, None]
    )

    # Digest key: earlier pane = window slot's tag (window panes ≤ t).
    ring = jnp.where(st >= 0, st % ppw, (st % ppw + ppw) % ppw)
    a = poid[:, None]
    b = so
    lid = jnp.where(swap_pair, b, a)
    rid = jnp.where(swap_pair, a, b)
    flat = ring * (num_ids * num_ids) + lid * num_ids + rid
    sentinel = ppw * num_ids * num_ids  # drop lane
    flat = jnp.where(svalid, flat, sentinel)
    return flat.reshape(-1), sd.reshape(-1), sel_over


def _probe_compact(wx, wy, woid, wtag, wcur, wlive, px, py, pxi, pyi, poid,
                   pvalid, radius, swap_pair, grid_n: int, cap_w: int,
                   cap_c: int, layers: int, ppw: int, num_ids: int,
                   pair_sel: int):
    """Compacted probe: O(cap_c) live lanes per neighbor cell, not
    O(cap_w) ring slots. The live slots of a ring row are the
    contiguous FIFO range ``[cursor - live, cursor)``, so the dense
    live-slot view is pure index arithmetic — no repack scatter (an
    XLA:CPU scatter costs ~100× a gather per element), no tag gathers
    for aliveness (position < live IS the alive test; tags are gathered
    only at the ≤ pair_sel SELECTED lanes for the digest ring key), and
    first-k selection by prefix-sum binary search instead of the
    ``lax.top_k`` sort. Identical selected sets and overflow counts as
    ``_probe`` (the occupancy-sweep parity tests), plus ``cmp_over``:
    live points beyond ``cap_c`` in a PROBED cell were invisible — the
    caller must climb the capacity ladder and re-scan."""
    span = 2 * layers + 1
    offs = jnp.arange(-layers, layers + 1, dtype=jnp.int32)
    nx = pxi[:, None, None] + offs[None, :, None]  # (PC, span, 1)
    ny = pyi[:, None, None] + offs[None, None, :]  # (PC, 1, span)
    in_grid = (
        (nx >= 0) & (nx < grid_n) & (ny >= 0) & (ny < grid_n)
    ).reshape(-1, span * span)
    rows = jnp.clip(nx * grid_n + ny, 0, grid_n * grid_n - 1).reshape(
        -1, span * span
    )  # (PC, span²)
    probed = pvalid[:, None] & in_grid
    ghead = (wcur[rows] - wlive[rows]) % cap_w  # (PC, span²)
    glive = jnp.where(probed, wlive[rows], 0)
    cmp_over = jnp.sum(jnp.maximum(glive - cap_c, 0)).astype(jnp.int32)

    lane = jnp.arange(cap_c, dtype=jnp.int32)
    slot = (ghead[:, :, None] + lane[None, None, :]) % cap_w
    gidx = rows[:, :, None] * cap_w + slot  # (PC, span², cap_c)
    gx = wx[gidx]
    gy = wy[gidx]
    d = jnp.sqrt(
        (gx - px[:, None, None]) ** 2 + (gy - py[:, None, None]) ** 2
    )
    mask = (
        probed[:, :, None]
        & (lane[None, None, :] < glive[:, :, None])
        & (d <= radius)
    ).reshape(len(px), -1)  # (PC, C)
    dflat = d.reshape(len(px), -1)
    iflat = gidx.reshape(len(px), -1)

    ci, count, sel_over = first_k_prefix_indices(mask, pair_sel)
    sd = jnp.take_along_axis(dflat, ci, axis=1)
    gsel = jnp.take_along_axis(iflat, ci, axis=1)  # global slot ids
    # tag/oid only for the SELECTED slots — two (PC, pair_sel) element
    # gathers replace two (PC, span², capW) plane gathers.
    st = wtag[gsel]
    so = woid[gsel]
    svalid = (
        jnp.arange(pair_sel, dtype=jnp.int32)[None, :]
        < jnp.minimum(count, pair_sel)[:, None]
    )

    # Digest key: identical arithmetic to _probe — bit-identical flats.
    ring = jnp.where(st >= 0, st % ppw, (st % ppw + ppw) % ppw)
    a = poid[:, None]
    b = so
    lid = jnp.where(swap_pair, b, a)
    rid = jnp.where(swap_pair, a, b)
    flat = ring * (num_ids * num_ids) + lid * num_ids + rid
    sentinel = ppw * num_ids * num_ids  # drop lane
    flat = jnp.where(svalid, flat, sentinel)
    return flat.reshape(-1), sd.reshape(-1), sel_over, cmp_over


def _insert(wx, wy, woid, wtag, wcur, t, px, py, pcell, prank, poid, pvalid,
            cap_w: int, ppw: int):
    """Scatter one pane into a side's ring planes; returns the updated
    planes + the count of LIVE slots overwritten (exactness counter)."""
    cur = wcur[pcell]  # (PC,) row gather of the cursor
    slot = (cur + prank) % cap_w
    fi = jnp.where(pvalid, pcell * cap_w + slot, wx.shape[0])
    # Two loss modes feed the exactness counter: overwriting a slot whose
    # point is still inside the window, AND a single pane putting more
    # than cap_w points in one cell (ranks wrap modulo cap_w and collide
    # within this very scatter — invisible to the old-tag check).
    overwritten = (
        jnp.sum(jnp.where(
            pvalid & (wtag[jnp.clip(fi, 0, wx.shape[0] - 1)] > t - ppw),
            1, 0,
        ))
        + jnp.sum(jnp.where(pvalid & (prank >= cap_w), 1, 0))
    ).astype(jnp.int32)
    wx = wx.at[fi].set(px, mode="drop")
    wy = wy.at[fi].set(py, mode="drop")
    woid = woid.at[fi].set(poid, mode="drop")
    wtag = wtag.at[fi].set(t, mode="drop")
    wcur = wcur.at[jnp.where(pvalid, pcell, wcur.shape[0])].add(
        1, mode="drop"
    )
    return wx, wy, woid, wtag, wcur, overwritten


def tjoin_pane_step(
    carry: TJoinPaneCarry,
    xs,
    radius,
    grid_n: int,
    cap_w: int,
    layers: int,
    ppw: int,
    num_ids: int,
    pair_sel: int,
    cap_c: int = 0,
    axis_name=None,
):
    """One slide: probe/insert both sides, emit the window digest.

    ``xs`` = (t, left pane, right pane, left expiring, right expiring)
    where each pane is (x, y, xi, yi, cell, rank, oid, valid)
    fixed-capacity arrays and each expiring pane is the (cell, valid)
    pair of the pane that left the window this slide (pane ``t - ppw``
    — what keeps the per-cell live counts exact). Returns (carry',
    per-pair window min dists (K²,)). Designed as a ``lax.scan`` body
    so a whole batch of slides is ONE dispatch.

    ``cap_c`` (static): > 0 routes both probes through the compacted
    positional probe (``_probe_compact`` — gathers ``cap_c`` live lanes
    per neighbor cell); 0 keeps the full-ring row-gather probe. Same
    results whenever the overflow counters are zero.

    ``axis_name`` (inside shard_map): PROBE-parallel mesh execution —
    each shard receives its contiguous chunk of the new panes' points,
    probes it against the REPLICATED window planes (the probe's
    gathers are the step's dominant cost and divide by the
    shard count), then all-gathers the (flat idx, dist) contributions
    so every shard applies the identical digest scatter and pane insert
    (tiled all_gather restores the original point order; scatter-min is
    order-free) — the carry stays replicated and bit-identical to the
    single-device step (tests/test_parallel_operators.py). The
    expiring panes arrive replicated, so the live counts (and with
    them the compacted probe's head/alive math) are identical on every
    shard — compaction commutes with the sharding.
    """
    t, lp, rp, lxp, rxp = xs
    if axis_name is not None:
        gather = lambda a: jax.lax.all_gather(a, axis_name, tiled=True)
        lp_full = tuple(gather(f) for f in lp)
        rp_full = tuple(gather(f) for f in rp)
    else:
        gather = lambda a: a
        lp_full, rp_full = lp, rp
    num_cells = grid_n * grid_n
    # Expire pane t-ppw on both sides BEFORE any probe: the window is
    # (t-ppw, t], so its points are dead for every probe of this slide.
    llive = _cell_counts(carry.lwlive, lxp[0], lxp[1], num_cells, -1)
    rlive = _cell_counts(carry.rwlive, rxp[0], rxp[1], num_cells, -1)
    P = num_ids * num_ids
    bs = block_size(ppw)
    inf = jnp.asarray(jnp.inf, carry.digests.dtype)
    r = t % ppw
    # Ring slot r held pane t-ppw — reset before this pane's writes.
    D = jax.lax.dynamic_update_index_in_dim(
        carry.digests, jnp.full((P,), inf, carry.digests.dtype),
        r, axis=0,
    )
    # Hierarchical reduce, level 2: the reset invalidated exactly one
    # block's min — recompute it from its bs digest rows (every other
    # block's invariant carries over; the scatter-mins below update both
    # levels, so Bd[b] == min over D rows of block b at every step and
    # the window min is the bs·K² recompute + (ppw/bs)·K² block min
    # instead of the flat ppw·K².
    blk = r // bs
    Bd = jax.lax.dynamic_update_index_in_dim(
        carry.block_digests,
        jnp.min(jax.lax.dynamic_slice(
            D, (blk * bs, jnp.zeros((), blk.dtype)), (bs, P)), axis=0),
        blk, axis=0,
    )
    Bf = Bd.reshape(-1)

    def block_flat(flat):
        # digest flat idx (ring·P + pair) → block flat idx; the drop
        # sentinel ppw·P maps to (ppw/bs)·P — also out of range, drops.
        return (flat // P) // bs * P + flat % P

    # Direction A: new LEFT pane × RIGHT window (panes < t).
    if cap_c > 0:
        fa, da, sa, ca = _probe_compact(
            carry.rwx, carry.rwy, carry.rwoid, carry.rwtag, carry.rwcur,
            rlive, lp[0], lp[1], lp[2], lp[3], lp[6], lp[7], radius,
            swap_pair=jnp.asarray(False),
            grid_n=grid_n, cap_w=cap_w, cap_c=cap_c, layers=layers,
            ppw=ppw, num_ids=num_ids, pair_sel=pair_sel,
        )
    else:
        fa, da, sa = _probe(
            carry.rwx, carry.rwy, carry.rwoid, carry.rwtag, t,
            lp[0], lp[1], lp[2], lp[3], lp[6], lp[7], radius,
            swap_pair=jnp.asarray(False),
            grid_n=grid_n, cap_w=cap_w, layers=layers, ppw=ppw,
            num_ids=num_ids, pair_sel=pair_sel,
        )
        ca = jnp.zeros((), jnp.int32)
    if axis_name is not None:
        fa, da = gather(fa), gather(da)
        sa = jax.lax.psum(sa, axis_name)
        ca = jax.lax.psum(ca, axis_name)
    Df = D.reshape(-1)
    Df = Df.at[fa].min(da, mode="drop")
    Bf = Bf.at[block_flat(fa)].min(da, mode="drop")

    lwx, lwy, lwoid, lwtag, lwcur, ov_l = _insert(
        carry.lwx, carry.lwy, carry.lwoid, carry.lwtag, carry.lwcur, t,
        lp_full[0], lp_full[1], lp_full[4], lp_full[5], lp_full[6],
        lp_full[7], cap_w=cap_w, ppw=ppw,
    )
    llive = _cell_counts(llive, lp_full[4], lp_full[7], num_cells, 1)

    # Direction B: new RIGHT pane × LEFT window (panes ≤ t — includes the
    # pane just inserted, so new×new pairs are counted exactly once).
    if cap_c > 0:
        fb, db, sb, cb = _probe_compact(
            lwx, lwy, lwoid, lwtag, lwcur, llive,
            rp[0], rp[1], rp[2], rp[3], rp[6], rp[7], radius,
            swap_pair=jnp.asarray(True),
            grid_n=grid_n, cap_w=cap_w, cap_c=cap_c, layers=layers,
            ppw=ppw, num_ids=num_ids, pair_sel=pair_sel,
        )
    else:
        fb, db, sb = _probe(
            lwx, lwy, lwoid, lwtag, t,
            rp[0], rp[1], rp[2], rp[3], rp[6], rp[7], radius,
            swap_pair=jnp.asarray(True),
            grid_n=grid_n, cap_w=cap_w, layers=layers, ppw=ppw,
            num_ids=num_ids, pair_sel=pair_sel,
        )
        cb = jnp.zeros((), jnp.int32)
    if axis_name is not None:
        fb, db = gather(fb), gather(db)
        sb = jax.lax.psum(sb, axis_name)
        cb = jax.lax.psum(cb, axis_name)
    Df = Df.at[fb].min(db, mode="drop")
    Bf = Bf.at[block_flat(fb)].min(db, mode="drop")
    D = Df.reshape(ppw, P)
    Bd = Bf.reshape(ppw // bs, P)

    rwx, rwy, rwoid, rwtag, rwcur, ov_r = _insert(
        carry.rwx, carry.rwy, carry.rwoid, carry.rwtag, carry.rwcur, t,
        rp_full[0], rp_full[1], rp_full[4], rp_full[5], rp_full[6],
        rp_full[7], cap_w=cap_w, ppw=ppw,
    )
    rlive = _cell_counts(rlive, rp_full[4], rp_full[7], num_cells, 1)

    new_carry = TJoinPaneCarry(
        lwx, lwy, lwoid, lwtag, lwcur, llive,
        rwx, rwy, rwoid, rwtag, rwcur, rlive,
        D, Bd,
        (carry.cap_overflow + ov_l + ov_r).astype(jnp.int32),
        (carry.sel_overflow + sa + sb).astype(jnp.int32),
        (carry.cmp_overflow + ca + cb).astype(jnp.int32),
    )
    # Window ending at pane t: min over every live earlier-pane digest,
    # via the block level (bit-exact — min of mins).
    wmin = jnp.min(Bd, axis=0)
    return new_carry, wmin


def expired_pane_fields(cells_arr, valid_arr, ppw: int):
    """(cell, valid) of the pane EXPIRING at each slide of a batch whose
    carry started EMPTY: pane s - ppw, i.e. the same arrays shifted by
    ``ppw`` slides with nothing expiring during warmup. A caller that
    chains scans from a non-empty carry must instead slice the expiring
    panes from the earlier batch and pass them explicitly — this
    zero-fill is only correct when the scan's own slides are the whole
    ring history."""
    S = cells_arr.shape[0]
    pad = min(ppw, S)
    zc = jnp.zeros((pad,) + cells_arr.shape[1:], cells_arr.dtype)
    zv = jnp.zeros((pad,) + valid_arr.shape[1:], valid_arr.dtype)
    if S > ppw:
        return (jnp.concatenate([zc, cells_arr[:S - ppw]], axis=0),
                jnp.concatenate([zv, valid_arr[:S - ppw]], axis=0))
    return zc, zv


def tjoin_pane_scan(
    carry: TJoinPaneCarry,
    ts, lps, rps,
    radius,
    grid_n: int,
    cap_w: int,
    layers: int,
    ppw: int,
    num_ids: int,
    pair_sel: int,
    cap_c: int = 0,
    lps_expire=None,
    rps_expire=None,
    mesh=None,
):
    """Scan ``tjoin_pane_step`` over a batch of slides in ONE program.

    ``ts``: (S,) pane indices; ``lps``/``rps``: per-field (S, PC) arrays
    (x, y, xi, yi, cell, rank, oid, valid). Returns (carry',
    (S, K²) per-window pair min dists).

    ``cap_c`` (static): the bucketed live-slot probe capacity
    (ops/compaction.py ladder; 0 = full-ring probe). One compiled
    program per bucket — the host picks the rung, the device program
    stays fixed-shape.

    ``lps_expire``/``rps_expire``: (cell, valid) pairs of the pane
    expiring at each slide, (S, PC) each — required when this scan
    continues a carry whose ring already holds panes from an earlier
    scan. Default None derives them from this batch's own panes
    (``expired_pane_fields`` — correct iff the carry started empty).

    ``mesh``: probe-parallel execution over the mesh's ``data`` axis —
    pane POINTS shard (PC must divide by the axis), window/digest state
    and the expiring panes replicate, per-slide contributions
    all-gather (see tjoin_pane_step's axis_name). Bit-identical to
    single-device, compacted or not.
    """
    if lps_expire is None:
        lps_expire = expired_pane_fields(lps[4], lps[7], ppw)
    if rps_expire is None:
        rps_expire = expired_pane_fields(rps[4], rps[7], ppw)
    if mesh is None:
        def body(c, x):
            return tjoin_pane_step(
                c, x, radius, grid_n=grid_n, cap_w=cap_w, layers=layers,
                ppw=ppw, num_ids=num_ids, pair_sel=pair_sel, cap_c=cap_c,
            )

        return jax.lax.scan(body, carry, (ts, lps, rps, lps_expire,
                                          rps_expire))

    # Shim handles both the symbol's home and check_rep→check_vma.
    from jax import shard_map
    from jax.sharding import PartitionSpec as P

    ndev = int(mesh.shape["data"])
    pc = lps[0].shape[1]
    if pc % ndev:
        raise ValueError(
            f"pane capacity ({pc}) must divide by the mesh data axis "
            f"({ndev})"
        )

    def local(c, ts_, lps_, rps_, lxp_, rxp_):
        def body(cc, x):
            return tjoin_pane_step(
                cc, x, radius, grid_n=grid_n, cap_w=cap_w, layers=layers,
                ppw=ppw, num_ids=num_ids, pair_sel=pair_sel, cap_c=cap_c,
                axis_name="data",
            )

        return jax.lax.scan(body, c, (ts_, lps_, rps_, lxp_, rxp_))

    carry_spec = TJoinPaneCarry(*(P() for _ in carry))
    pane_spec = tuple(P(None, "data") for _ in lps)
    expire_spec = (P(), P())  # replicated — live counts stay identical
    fn = shard_map(
        local,
        mesh=mesh,
        in_specs=(carry_spec, P(), pane_spec, pane_spec, expire_spec,
                  expire_spec),
        out_specs=(carry_spec, P()),
        check_vma=False,
    )
    return fn(carry, ts, lps, rps, lps_expire, rps_expire)
