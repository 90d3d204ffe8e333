"""Fixed-shape live-slot compaction — the shared bucket-ladder control
plane for the pane engines.

The device pane engines keep window state in fixed-capacity structures
sized for the worst case: the tJoin ring planes hold ``cap_w`` slots per
cell (live AND expired — expiry is lazy), and the wire-kNN digest pads
every pane to a power-of-two bucket. Probing the worst-case shape is
where the XLA:CPU device scan lost ~50× to the native engine's
live-points-only loops: every ring slot was
gathered, alive or dead, and the first-``pair_sel`` match selection ran
a full ``lax.top_k`` sort over that worst-case width.

This module is the HOST half of the fix — a small ladder of
power-of-two capacities and the occupancy math that picks a bucket from
the LIVE count:

- ``capacity_ladder(cap)`` / ``pick_capacity(live, cap)``: the static
  probe capacity ``cap_c`` the device program is compiled for. Because
  the ladder is tiny (≤6 powers of two between ``CAP_LADDER_MIN`` and
  ``cap_w``), a stream sweeping any occupancy compiles at most
  ladder-many programs per engine — the recompile detector
  (telemetry.py) sees a handful of STABLE signatures, not churn.
- ``max_cell_count``: a batch's largest per-cell count — the point
  join's bucket capacity climbs the same ladder past its first rung
  (``pick_capacity(..., open_top=True)``), so a hot cell costs one more
  compiled rung and never a short join.
- ``max_window_cell_count``: exact per-cell window occupancy bound for
  a bounded stream (vectorized two-pointer over the (cell, pane)-sorted
  events), so ``run_soa_panes`` picks the bucket before the scan and
  the in-kernel ``cmp_overflow`` counter is a safety net, not a retry
  treadmill.
- ``wire_pane_bucket``: the wire-kNN pane-capacity bucket (one shared
  home for the operator and the benches), recorded per bucket in
  telemetry so occupancy drift is visible.

The DEVICE half lives in ops/tjoin_panes.py: the live slots of a ring
cell row are the contiguous ``[cursor - live, cursor)`` range (points
insert in pane order and expire in pane order — a FIFO), so the
compacted view needs no data movement at all: the probe gathers
``cap_c`` lanes starting at the per-cell head and masks by position.
Padding lanes past the live count stay masked — compaction is a
host-chosen static SHAPE, never a data-dependent one, so the
mask-don't-compact kernel invariant holds (PARITY.md "Fixed-shape
live-slot compaction").
"""

from __future__ import annotations

from typing import Tuple

import numpy as np

from spatialflink_tpu.runtime import on_tpu

#: Smallest probe capacity the ladder offers. Below this the per-point
#: gather is already trivially small; more rungs would only add compiles.
CAP_LADDER_MIN = 8

#: Wire-kNN panes bucket at this floor (the historical run_wire_panes
#: minimum — kept so existing compiled shapes and tests are unchanged).
PANE_BUCKET_MIN = 128


def capacity_ladder(cap: int, minimum: int = CAP_LADDER_MIN) -> Tuple[int, ...]:
    """Powers of two from ``minimum`` up to ``cap`` (inclusive; ``cap``
    itself is appended even when not a power of two so the full-ring
    probe is always the top rung). cap_w = 64 → (8, 16, 32, 64): 4
    buckets; cap_w = 256 → 6 buckets."""
    if cap < minimum:
        return (cap,)
    out = []
    b = minimum
    while b < cap:
        out.append(b)
        b <<= 1
    out.append(cap)
    return tuple(out)


def pick_capacity(live: int, cap: int, minimum: int = CAP_LADDER_MIN,
                  open_top: bool = False) -> int:
    """Smallest ladder rung ≥ ``live`` (the bucketed probe capacity).
    ``live`` beyond the ladder top clamps to ``cap`` — the ring capacity
    bounds live occupancy anyway (the cap_overflow retry contract) —
    unless ``open_top``: then the ladder goes on past ``cap`` in powers of
    two, for a capacity nothing bounds but what the window holds (the
    point join's per-cell buckets, whose first rung is ``cap`` itself:
    pass ``minimum=cap``).

    Under an active overload ``clamp_compaction`` rung
    (spatialflink_tpu/overload.py) the pick is FLOORED: occupancy churn
    below the clamp stops changing rungs — each fresh rung is a ~1-2 s
    XLA recompile, exactly the cost a loaded pipeline can't pay.
    Result-preserving: the rung only ever grows (padding stays masked),
    and a clamp of 0 pins the top rung (one program for the whole run).
    """
    from spatialflink_tpu import overload

    clamp = overload.compaction_clamp()
    if clamp is not None:
        live = max(live, cap) if clamp <= 0 else max(live, clamp)
    for b in capacity_ladder(cap, minimum):
        if b >= live:
            return b
    if not open_top:
        return cap
    from spatialflink_tpu.utils.padding import next_bucket

    return next_bucket(live, minimum=1)


def max_cell_count(cells, valid, num_cells: int) -> int:
    """Largest number of valid in-grid points any one cell holds — what a
    side's dense bucket capacity has to hold (one ``np.bincount``; invalid
    and out-of-grid lanes are counted into the slot past the grid)."""
    cells = np.where(valid, cells, num_cells)
    counts = np.bincount(np.minimum(cells, num_cells), minlength=num_cells + 1)
    return int(counts[:num_cells].max()) if num_cells else 0


def max_window_cell_count(pane: np.ndarray, cell: np.ndarray,
                          ppw: int) -> int:
    """Exact max, over every (cell, slide), of the number of events of
    one cell inside the window ``(t - ppw, t]`` — the live-occupancy
    bound the bucket pick needs.

    Vectorized: sort events by (cell, pane); for event i the window
    ending at its own pane holds ``i - lo + 1`` same-cell events, where
    ``lo`` is the first same-cell event with pane > pane_i - ppw
    (binary search on the composite key). The max over slides is
    attained at some event's own pane (occupancy only grows when an
    event enters), so the per-event max is the global max.
    """
    n = len(pane)
    if n == 0:
        return 0
    pane = np.asarray(pane, np.int64)  # sfcheck: ok=trace-hygiene -- HOST control plane by design (module docstring): the occupancy plan reads live counts on the host to pick the static bucket; never traced
    cell = np.asarray(cell, np.int64)  # sfcheck: ok=trace-hygiene -- same host-side occupancy plan as above
    span = int(pane.max()) + 1
    key = cell * span + pane
    order = np.argsort(key, kind="stable")
    ks = key[order]
    lo = np.searchsorted(
        ks, cell[order] * span + np.maximum(pane[order] - ppw + 1, 0)
    )
    return int((np.arange(n) - lo + 1).max())


def compact_probe_preferred() -> bool:
    """True on backends where the compacted positional probe (element
    gathers over ``cap_c`` live lanes + prefix-sum/binary-search
    selection) beats the full-ring row-gather probe. On TPU the row
    gather + one-hot select is the measured-preferred form (element
    gathers and per-lane masks are the TPU-slow ops — ops/select.py);
    everywhere else the compacted probe wins by avoiding the
    ``lax.top_k`` full sort (~45% of the XLA:CPU slide step)."""
    return not on_tpu()


def wire_pane_bucket(n: int, minimum: int = PANE_BUCKET_MIN) -> int:
    """Bucketed wire-pane capacity (power-of-two ladder above
    ``minimum``) — ONE home for run_wire_panes and the benches, with the
    pick recorded per bucket in telemetry (occupancy drift between
    panes shows up as bucket churn there, and as ≤log₂ many compiled
    digest shapes in the recompile detector)."""
    from spatialflink_tpu.telemetry import telemetry
    from spatialflink_tpu.utils.padding import next_bucket

    b = int(next_bucket(max(int(n), 1), minimum=minimum))  # sfcheck: ok=trace-hygiene -- host control plane (module docstring): pane length is a host int picking a static bucket, never a tracer
    telemetry.record_compaction("wire_pane_digest", b, int(n))  # sfcheck: ok=trace-hygiene -- same host-side bucket pick as above
    return b
