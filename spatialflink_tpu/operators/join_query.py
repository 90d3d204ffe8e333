"""Spatial-join operators — the ``spatialOperators/join/`` matrix.

``run(ordinary_stream, query_stream, radius)`` joins two streams per
window. The reference replicates each query object to all its neighbor
cells, shuffles both sides by gridID and distance-filters the equi-join
(JoinQuery.java:73-137, PointPointJoinQuery.java:124-183). Here the query
side is cell-sorted on device and each ordinary point gathers its candidate
square's bucket — a grid-hash join (ops/join.py) with zero replication.
RealTimeNaive runs the all-pairs kernel (PointPointJoinQuery.java:186-243).

Two-stream windowing: both sources are merged by event time on the host and
windows fire when the combined watermark passes (the analog of Flink's
two-input watermark min, which the reference gets from
``assignTimestampsAndWatermarks`` on both inputs,
PointPointJoinQuery.java:128-146).
"""

from __future__ import annotations

import functools
import heapq
import time
from dataclasses import dataclass
from typing import Iterable, Iterator, List, NamedTuple, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from spatialflink_tpu.models.objects import LineString, Point, Polygon, SpatialObject
from spatialflink_tpu.operators.base import (
    SpatialOperator,
    assembled_ahead,
    jitted,
    ship,
)
from spatialflink_tpu.ops.compaction import max_cell_count, pick_capacity
from spatialflink_tpu.telemetry import instrument_jit, telemetry
from spatialflink_tpu.utils.padding import next_bucket
from spatialflink_tpu.ops.join import (
    cross_join_kernel,
    geometry_geometry_join_kernel,
    geometry_geometry_join_pruned_kernel,
    head_pairs,
    join_kernel,
    join_kernel_compact,
    join_window_bucketed,
    join_window_cells,
    join_window_compact,
    pallas_join_supported,
    point_geometry_join_kernel,
    point_geometry_join_pruned_kernel,
    sort_by_cell,
)
from spatialflink_tpu.operators.query_config import QueryType


@dataclass
class JoinWindowResult:
    start: int
    end: int
    pairs: List[Tuple[SpatialObject, SpatialObject, float]]
    overflow: int
    window_count: int  # left+right events in window


def merge_by_timestamp(left: Iterable, right: Iterable):
    """Merge two timestamped streams into (tag, event), event-time order."""
    def tagged(it, tag):
        for ev in it:
            yield (ev.timestamp, tag, ev)

    for ts, tag, ev in heapq.merge(tagged(left, 0), tagged(right, 1)):
        yield tag, ev


class _TaggedEvent:
    __slots__ = ("timestamp", "tag", "event")

    def __init__(self, timestamp, tag, event):
        self.timestamp = timestamp
        self.tag = tag
        self.event = event


@functools.lru_cache(maxsize=None)
def window_join_program(backend: str | None = None):
    """``(program, name)`` of the one-window dense-bucket join — the one
    home of the backend choice for ``run_soa`` (point join and tJoin) and
    ``grid_hash_join_batches``. ``backend``: None=auto (the Pallas hit
    extraction on a TPU at any budget — its outputs live in HBM; the banded
    XLA kernel elsewhere), or 'xla' | 'pallas' | 'pallas_interpret' (tests).
    Both programs go through ``instrument_jit``; ``name`` is 'pallas' or
    'xla'."""
    if backend is None:
        backend = "pallas" if pallas_join_supported() else "xla"
    if backend == "xla":
        return jitted(
            join_window_bucketed,
            "grid_n", "layers", "cap_left", "cap_right", "max_pairs",
        ), "xla"
    if backend not in ("pallas", "pallas_interpret"):
        raise ValueError(f"unknown join backend {backend!r}")
    from spatialflink_tpu.ops.pallas_join import join_window_pallas

    fn = instrument_jit(join_window_pallas, name="join_window_pallas")
    if backend == "pallas_interpret":
        fn = functools.partial(fn, interpret=True)
    return fn, "pallas"


def grid_hash_join_batches(grid, left_batch, right_batch, radius, cap, offsets,
                           max_pairs=None, dtype=np.float64, backend=None,
                           mesh=None, filter_radius=None):
    """Run the grid-hash join kernel over two cell-assigned PointBatches.

    Shared by PointPointJoinQuery and TJoinQuery. With ``max_pairs`` set,
    pairs are compacted on device (CompactJoinResult) so only matches cross
    the host boundary — the dense mask path transfers O(N·K·cap) per
    window. ``backend``: None=auto (Pallas extraction on TPU — hit
    compaction in time ∝ matches; XLA elsewhere), or one of
    'xla' | 'pallas' | 'pallas_interpret' (tests).

    ``filter_radius`` (default = ``radius``) decouples the distance
    predicate from the candidate-cell neighborhood: approximate point
    joins pass ``inf`` so every grid candidate is emitted while the
    replication neighborhood stays that of the TRUE radius — the
    reference's "all the candidate neighbors are sent to output"
    semantics (join/PointPointJoinQuery.java:164-166)."""
    from spatialflink_tpu.operators.base import center_coords

    fr = radius if filter_radius is None else filter_radius
    if max_pairs is not None:
        layers = grid.candidate_layers(radius)
        if mesh is not None:
            # Multi-chip: left sharded over data, right replicated, pairs
            # compacted on device (parallel/sharded.py) — same
            # CompactJoinResult/retry contract as the single-device paths.
            from spatialflink_tpu.parallel.sharded import (
                sharded_join_window_compact,
            )

            left_in_grid = left_batch.valid & (left_batch.cell < grid.num_cells)
            return sharded_join_window_compact(
                mesh,
                jnp.asarray(center_coords(grid, left_batch.xy, dtype)),
                jnp.asarray(left_in_grid),
                jnp.asarray(grid.cell_xy_indices_np(left_batch.xy)),
                jnp.asarray(center_coords(grid, right_batch.xy, dtype)),
                jnp.asarray(right_batch.valid),
                jnp.asarray(right_batch.cell),
                offsets, grid_n=grid.n, radius=fr, cap=cap,
                max_pairs=max_pairs,
            )
        if backend is None and not pallas_join_supported():
            backend = "xla"
        if backend != "xla":
            # f32 explicitly: centering must run before any sub-f64 cast
            # (center_coords skips it when asked for the effective f64), and
            # the Pallas kernel computes in f32 regardless.
            fn, _ = window_join_program(backend)
            return fn(
                jnp.asarray(center_coords(grid, left_batch.xy, np.float32)),
                jnp.asarray(left_batch.valid),
                jnp.asarray(left_batch.cell),
                jnp.asarray(center_coords(grid, right_batch.xy, np.float32)),
                jnp.asarray(right_batch.valid),
                jnp.asarray(right_batch.cell),
                grid_n=grid.n, layers=layers, radius=fr,
                cap_left=cap, cap_right=cap, max_pairs=max_pairs,
            )
        span2 = (2 * layers + 1) ** 2
        lanes = grid.num_cells * cap * cap * span2
        if lanes <= 300_000_000:
            # Dense-bucket join: static shifts, no per-candidate gathers
            # — the fast path while cells×cap²×span² lanes stay few (past
            # that the gather join below costs less for a small window).
            jk, _ = window_join_program("xla")
            return jk(
                jnp.asarray(center_coords(grid, left_batch.xy, dtype)),
                jnp.asarray(left_batch.valid),
                jnp.asarray(left_batch.cell),
                jnp.asarray(center_coords(grid, right_batch.xy, dtype)),
                jnp.asarray(right_batch.valid),
                jnp.asarray(right_batch.cell),
                grid_n=grid.n, layers=layers,
                radius=fr, cap_left=cap, cap_right=cap,
                max_pairs=max_pairs,
            )
        # High per-cell capacity: gather-based join (memory O(N·span²·cap)).
        jk = jitted(join_window_compact, "grid_n", "cap", "max_pairs")
        left_in_grid = left_batch.valid & (left_batch.cell < grid.num_cells)
        return jk(
            jnp.asarray(center_coords(grid, left_batch.xy, dtype)),
            jnp.asarray(left_in_grid),
            jnp.asarray(grid.cell_xy_indices_np(left_batch.xy)),
            jnp.asarray(center_coords(grid, right_batch.xy, dtype)),
            jnp.asarray(right_batch.valid),
            jnp.asarray(right_batch.cell),
            offsets,
            grid_n=grid.n, radius=fr, cap=cap, max_pairs=max_pairs,
        )
    left_ci = grid.cell_xy_indices_np(left_batch.xy)
    # Reference semantics: out-of-grid points carry keys that never match a
    # neighbor set (HelperClass.assignGridCellID), so they never join.
    left_in_grid = left_batch.valid & (left_batch.cell < grid.num_cells)
    # Jitted, not eager: an eager sort_by_cell is three un-jitted
    # dispatches (argsort + gather + cast) per window.
    cells_sorted, order = jitted(sort_by_cell, "n_total_cells")(
        jnp.asarray(right_batch.cell), n_total_cells=grid.num_cells
    )
    args = (
        jnp.asarray(center_coords(grid, left_batch.xy, dtype)),
        jnp.asarray(left_in_grid),
        jnp.asarray(left_ci),
        jnp.asarray(center_coords(grid, right_batch.xy, dtype))[order],
        jnp.asarray(right_batch.valid)[order],
        cells_sorted, order, offsets,
    )
    if mesh is not None:
        # Multi-chip: left side sharded over the mesh's data axis, the
        # cell-sorted right side replicated (parallel/sharded.py).
        from spatialflink_tpu.parallel.sharded import sharded_join

        return sharded_join(
            mesh, *args, grid_n=grid.n, radius=fr, cap=cap
        )
    jk = jitted(join_kernel, "grid_n", "cap")
    return jk(*args, grid_n=grid.n, radius=fr, cap=cap)


def headroom_bucket(count: int) -> int:
    """The headroom policy of a device output budget (the join's pair
    budget, the trajectory join's pair budget): at least the next power of
    two of 1.25 × ``count``."""
    return next_bucket(-(-5 * count // 4), minimum=1024)


class HeldJoin(NamedTuple):
    """What ``JoinCapacity._join_until_held`` hands back: the join result
    that holds its window (overflow 0, ``count`` ≤ the budget), the re-runs
    it took, the peel passes of the result held (0 from a program that
    counts none), and — where a ``follow`` program was given — what it
    returned for that result and its scalars, fetched. ``fullest_cell``:
    the most points of one side in one cell of the key grid;
    ``bucket_lanes``: the pair lanes (buckets × span² × cap²) every run of
    the window's extraction evaluated, re-runs included."""

    res: object
    count: int
    cap_retries: int
    budget_retries: int
    peel_passes: int
    followed: object = None
    followed_scalars: Tuple[int, ...] = ()
    fullest_cell: int = 0
    bucket_lanes: int = 0


class JoinCapacityError(ValueError):
    """No bucket layout the join's program exists for holds the window:
    the bucket grid is as fine as the radius allows and its fullest bucket
    still asks for a capacity rung past the largest that compiles."""


#: Refinements of the bucket grid the contract picks from: the key grid's
#: cell side ÷ f, while that stays at least the radius.
REFINEMENTS = (1, 2, 4, 8)

#: The rung past which a crowded cell is held by a finer bucket grid before
#: a larger capacity: the lanes of one vector register. A (cap, cap) block
#: of the extraction costs cap², a refinement divides what a bucket holds by
#: four at the same lanes a point, and a rung under 128 leaves a register's
#: lanes idle.
REFINE_RUNG = 128

#: Margin a refined bucket's side keeps over the radius, so that the
#: float32 bucket index of two points within the radius of each other
#: differs by at most one (``ops/join.py:join_window_cells``).
_SIDE_MARGIN = 1.0 + 2.0 ** -10

#: The largest capacity rung the Pallas extraction exists for: its (cap, cap)
#: blocks live in VMEM, and at 1,024 they no longer fit (a v5e's compiler
#: refuses 100 × 1,024 with VMEM exhausted; 100 × 512, 200 × 512 and
#: 400 × 256 compile: PERF.md §6, PR 41).
PALLAS_TOP_RUNG = 512

#: Most lanes one row of buckets (``grid_n · refine · cap``) may hold for
#: the Pallas extraction, whose grid step keeps twelve such rows in VMEM,
#: double-buffered: 400 × 256, 800 × 128 and 200 × 512 (102,400 lanes,
#: 9.8 MB) compile for a v5e (PERF.md §6, PR 41).
PALLAS_ROW_LANES = 102_400


class JoinCapacity:
    """The capacity and pair-budget contract of a bucketed window join,
    shared by ``PointPointJoinQuery`` and ``TJoinQuery`` (the one home of
    it). It picks how the window's fullest cell is held: ``join_cap`` is
    the per-bucket capacity in use — the constructor's ``cap`` its first
    rung on the ``ops/compaction.py`` ladder — and ``join_refine`` the
    refinement f of the bucket grid (``grid.n · f`` buckets a side). Up to
    ``REFINE_RUNG`` a fuller cell climbs the ladder on the key grid, as it
    always has; past it the buckets are refined first (where the caller's
    program takes its bucket cells from the contract and the refined side
    stays at least the radius: ``_open_join``), and only at the finest
    grid does the capacity climb on. A layout the Pallas extraction does
    not exist for (a rung past ``PALLAS_TOP_RUNG``, a row of buckets past
    ``PALLAS_ROW_LANES``) is never picked, and a window no other holds is
    refused by name (``JoinCapacityError``) before a dispatch, not by the
    compiler inside the window. ``join_budget`` is the pair budget. All
    three only grow and persist across windows; a window one fails to hold
    is run again, never handed back short. Needs ``self.grid``."""

    def _init_join_capacity(self, cap: int) -> None:
        self.cap = cap
        #: The bucket capacity in use: ``cap`` is its first rung, a window
        #: whose fullest cell holds more climbs it (``_climb``); like
        #: the pair budget it only grows and persists across windows.
        self.join_cap = cap
        #: The refinement of the bucket grid in use (1: the key grid).
        self.join_refine = 1
        self.join_budget = 0  # grown pair budget, persists across windows
        #: A window has been held at the sizes in use: from then on what
        #: follows the join is dispatched behind it without waiting for
        #: its scalars (``_join_until_held``).
        self._join_settled = False
        #: 'pallas' | 'xla': the extraction ``run_soa`` last ran
        #: (``last_wire_digest_kind``'s twin); None before the first window.
        self.last_join_backend = None
        self._open_join(0.0)

    def _open_join(self, radius: float, refinable: bool = False,
                   pallas: bool = False, dtype=np.float64) -> None:
        """What the pick may use from here on: ``refinable`` — the caller
        lays its buckets on the cells the contract hands it (``run_soa``
        through ``_window_call``; never in approximate mode, whose
        candidates are the key grid's) — and ``pallas``: the program is the
        Pallas extraction, which has a largest rung and a widest row (the
        XLA programs have neither). ``dtype`` is the one the window's
        coordinates were centred for."""
        from spatialflink_tpu.operators.base import _centring

        side = self.grid.cell_length
        self._join_finest = max(
            f for f in REFINEMENTS
            if f == 1 or (refinable and side / f >= radius * _SIDE_MARGIN)
        )
        # a refinement picked for a smaller radius holds nothing here
        self.join_refine = min(self.join_refine, self._join_finest)
        self._join_pallas = pallas
        self._join_span = 2 * self.grid.candidate_layers(radius) + 1
        centring = _centring(self.grid, dtype)
        cx, cy = (0.0, 0.0) if centring is None else centring[0]
        #: the key grid's lower corner in the coordinates the kernels get
        self._join_origin = np.array(
            [self.grid.min_x - cx, self.grid.min_y - cy], np.float64)

    def _climb(self, live: int) -> None:
        """Climb ``(join_refine, join_cap)`` to what holds ``live`` points
        in one cell of the key grid. A bucket of the grid refined f times
        holds at least ``live / f²`` of them: the pick goes by that bound
        (the overflow count of the run is the net under it). From the
        refinement in use upward it takes the first whose rung stays within
        ``REFINE_RUNG``, else the finest whose rung the program exists
        for, and where there is none it refuses (``JoinCapacityError``)."""
        fits = []
        f = self.join_refine
        while f <= self._join_finest:
            rung = pick_capacity(
                -(-live // (f * f)), self.join_cap, minimum=self.join_cap,
                open_top=True,
            )
            top = min(PALLAS_TOP_RUNG, PALLAS_ROW_LANES // (self.grid.n * f))
            if not self._join_pallas or rung <= top:
                fits.append((f, rung))
                if rung <= max(REFINE_RUNG, self.join_cap):
                    break
            f *= 2
        if not fits:
            f = self._join_finest
            raise JoinCapacityError(
                f"the fullest cell of the {self.grid.n} x {self.grid.n} key "
                f"grid holds {live} points: on the finest bucket grid the "
                f"radius allows (refinement {f}, bucket side "
                f"{self.grid.cell_length / f:.6g}) a bucket needs capacity "
                f"rung {rung}, and the Pallas extraction exists up to rung "
                f"{top} there (rung {PALLAS_TOP_RUNG}, {PALLAS_ROW_LANES} "
                f"lanes a row of buckets)"
            )
        self.join_refine, self.join_cap = fits[-1]

    def _grow_budget(self, count: int) -> None:
        """Headroom policy of the pair budget: at least the next power of
        two of 1.25 × ``count``."""
        self.join_budget = max(self.join_budget, headroom_bucket(count))

    def _window_call(self, fn, left, right, radius, filter_radius=None,
                     payload=None):
        """``call(refine, cap, budget)`` for ``_join_until_held``: one run
        of the window program ``fn`` over the two shipped sides (each
        ``(xy, valid, key cells)`` on the device) with its buckets on the
        grid the contract picked — the key cells themselves at refinement
        1, today's program and arguments to the letter; at a finer one the
        cells ``join_window_cells`` makes of the coordinates (one small
        program for both sides, once a window and refinement).
        ``payload``: ``(left, right)`` device lanes every run's pairs carry
        in place of the points' indices (``bucketize_planes``); None, the
        indices."""
        (lxy, lvalid, lcell), (rxy, rvalid, rcell) = left, right
        lload, rload = (None, None) if payload is None else payload
        layers = self.grid.candidate_layers(radius)
        fr = radius if filter_radius is None else filter_radius
        cells = {1: (lcell, rcell)}

        def call(refine, cap, budget):
            if refine not in cells:
                cells[refine] = jitted(join_window_cells, "grid_n", "refine")(
                    lxy, lcell, rxy, rcell, self._join_origin,
                    refine / self.grid.cell_length,
                    grid_n=self.grid.n, refine=refine,
                )
            lc, rc = cells[refine]
            return fn(
                lxy, lvalid, lc, rxy, rvalid, rc,
                grid_n=self.grid.n * refine, layers=layers, radius=fr,
                cap_left=cap, cap_right=cap, max_pairs=budget,
                left_payload=lload, right_payload=rload,
            )

        return call

    def _join_until_held(self, lcell, lvalid, rcell, rvalid, call,
                         follow=None) -> HeldJoin:
        """``call(refine, cap, budget)`` — one bucketed join of the two
        batches whose key cells these are — until its result holds them:
        the pick first climbs to the fullest cell (one bincount a side),
        then a result that still reports overflow (the safety net under
        that pick) is run again one step up — a rung, or past
        ``REFINE_RUNG`` a refinement — and one with more pairs than the
        budget under a grown budget. The held result's overflow is 0, and
        its peel passes are fetched with its count.

        ``follow(res)`` (optional) dispatches what consumes the pairs on
        the device and returns ``(value, scalars)``; those of the held
        result come back as ``followed`` and ``followed_scalars``. Once a
        window has been held it is dispatched right behind every run of
        the join, its scalars crossing with the join's in the one fetch;
        before that (the first window, whose budget is about to grow) only
        behind the run that holds, so that no program of it is compiled
        for a pair list of a length that will not be seen again."""
        num_cells = self.grid.num_cells
        # The host side of the pick (phase span ``join.capacity``; the
        # re-runs' arithmetic below is a few integer operations).
        with telemetry.span("join.capacity"):
            fullest = max(
                max_cell_count(lcell, lvalid, num_cells),
                max_cell_count(rcell, rvalid, num_cells),
            )
            self._climb(fullest)
        cap_retries = budget_retries = lanes = 0
        while True:
            res = call(self.join_refine, self.join_cap, self.join_budget)
            lanes += (num_cells * self.join_refine ** 2 * self._join_span ** 2
                      * self.join_cap ** 2)
            scalars = (res.count, res.overflow)
            if res.peel_passes is not None:
                scalars += (res.peel_passes,)
            speculate = follow is not None and self._join_settled
            followed, extra = follow(res) if speculate else (None, ())
            fetched = [
                int(v) for v in telemetry.fetch(scalars + tuple(extra))
            ]
            count, overflow, *passes = fetched[:len(scalars)]
            if overflow > 0:
                # a bucket holds more than the pick's bound: as if the
                # fullest key cell held twice what its buckets hold now
                self._climb(2 * self.join_cap * self.join_refine ** 2)
                cap_retries += 1
            elif count > self.join_budget:
                self._grow_budget(count)
                budget_retries += 1
            else:
                tail = fetched[len(scalars):]
                if follow is not None and not speculate:
                    followed, extra = follow(res)
                    tail = [int(v) for v in telemetry.fetch(tuple(extra))]
                self._join_settled = True
                return HeldJoin(
                    res, count, cap_retries, budget_retries, sum(passes),
                    followed, tuple(tail), fullest, lanes,
                )


class PointPointJoinQuery(JoinCapacity, SpatialOperator):
    """join/PointPointJoinQuery.java (windowBased :124-183, naive :186-243).

    ``cap`` is the first rung of the per-cell point capacity; the
    dense-bucket fast path caps BOTH sides per cell, and every path runs
    under ``JoinCapacity``'s contract: a window whose fullest cell holds
    more climbs the ladder, and past rung 128 ``run_soa`` lays its buckets
    on a finer grid instead (exact mode; side at least the radius), so a
    crowded cell costs a refinement and not cap² lanes. A yielded window's
    ``overflow`` is 0: one that is not held is run again, and where no
    layout the program exists for holds it the contract raises
    ``JoinCapacityError`` with the numbers (off the TPU ``run`` /
    ``query_panes`` go on to the gather join past 3·10⁸ lanes instead).
    Out-of-grid points never join, matching the reference's key semantics.
    """

    def __init__(self, conf, grid, cap: int = 64, join_backend: str | None = None,
                 mesh=None):
        super().__init__(conf, grid, mesh=mesh)
        self._init_join_capacity(cap)
        self.join_backend = join_backend  # None=auto, 'xla', 'pallas[_interpret]'

    def _filter_radius(self, radius):
        """Distance-predicate radius: in approximate mode every grid
        candidate is emitted (the reference's "all the candidate
        neighbors are sent to output", join/PointPointJoinQuery.java:
        164-166, incl. the RealTimeNaive branch :216) — expressed as an
        infinite filter radius while the candidate neighborhood stays
        that of the true radius. Reported pair distances remain the real
        point distances (the reference emits no distance at all here)."""
        return np.inf if self.conf.approximate_query else radius

    def run(
        self,
        ordinary: Iterable[Point],
        query_stream: Iterable[Point],
        radius: float,
        dtype=np.float64,
        mesh=None,
        driver=None,
    ) -> Iterator[JoinWindowResult]:
        """Window loop lifted into the shared dataflow driver
        (spatialflink_tpu/driver.py): pass ``driver=`` to OPT INTO
        auto-checkpointing, retry-with-backoff, and device→numpy
        failover (RealTimeNaive mode — the bucketed mode's pair order is
        device compaction order, so it has no twin). Without one, a
        strict driver reproduces the old plain loop exactly — errors
        propagate immediately, nothing degrades. The driver consumes
        the timestamp-merged two-stream sequence, so resume positions
        count MERGED events (both sides must replay for a checkpointed
        run)."""
        mesh = mesh if mesh is not None else self.mesh
        merged = (
            _TaggedEvent(ev.timestamp, tag, ev)
            for tag, ev in merge_by_timestamp(ordinary, query_stream)
        )
        from spatialflink_tpu.driver import strict_driver
        from spatialflink_tpu.ops.counters import (
            count_join_candidates,
            counters as opcounters,
        )

        naive = self.conf.query_type == QueryType.RealTimeNaive
        drv = driver if driver is not None else strict_driver()
        drv.attach(self)
        process = None
        if drv.backend == "device":
            ck = jitted(cross_join_kernel)
            offsets = jnp.asarray(self.grid.neighbor_offsets(radius))

            def process(win) -> JoinWindowResult:
                left_ev = [t.event for t in win.events if t.tag == 0]
                right_ev = [t.event for t in win.events if t.tag == 1]
                if not left_ev or not right_ev:
                    return JoinWindowResult(win.start, win.end, [], 0,
                                            len(win.events))
                with telemetry.span(
                    "window.join", start=win.start, events=len(win.events)
                ):
                    lb = self.point_batch(left_ev)
                    rb = self.point_batch(right_ev)
                    if opcounters.enabled:
                        if naive:
                            cand = len(left_ev) * len(right_ev)
                        else:
                            cand = count_join_candidates(
                                self.grid, lb.cell, len(left_ev), rb.cell,
                                len(right_ev),
                                self.grid.candidate_layers(radius),
                            )
                        opcounters.record_window(len(win.events), cand,
                                                 cand)
                    if naive:
                        lv_d, rv_d = ship(lb.valid, rb.valid)
                        res = ck(
                            self.device_xy(lb, dtype), lv_d,
                            self.device_xy(rb, dtype), rv_d,
                            self._filter_radius(radius),
                        )
                        pm, ri, dd = telemetry.fetch(
                            (res.pair_mask, res.right_index, res.dist)
                        )
                        pairs = []
                        for i in np.nonzero(pm.any(axis=1))[0]:
                            for s in np.nonzero(pm[i])[0]:
                                pairs.append(
                                    (left_ev[i], right_ev[int(ri[i, s])],
                                     float(dd[i, s]))
                                )
                        overflow = int(res.overflow)
                    else:
                        # Device-compacted pairs with the persistent-
                        # budget retry contract (_compact_block): a
                        # window whose match count exceeds the budget
                        # retries once with a doubled power-of-two
                        # budget that persists across windows.
                        li, ri, dd, overflow = self._compact_block(
                            lb, rb, radius, offsets, dtype, mesh
                        )
                        pairs = [
                            (left_ev[int(a)], right_ev[int(b)], float(d))
                            for a, b, d in zip(li, ri, dd)
                        ]
                    return JoinWindowResult(
                        win.start, win.end, pairs, overflow, len(win.events)
                    )

        fallback = self._numpy_window_process(radius, dtype) if naive \
            else None
        drv.bind(self, process, fallback=fallback)
        if self.conf.query_type == QueryType.CountBased:
            from spatialflink_tpu.operators.base import count_window_batches

            yield from drv.run_windows(count_window_batches(
                merged, self.conf.count_window_size,
                self.conf.count_window_size,
            ))
        else:
            yield from drv.run(merged)

    def _numpy_window_process(self, radius, dtype):
        """Numpy twin of the RealTimeNaive cross-join path — the
        driver's failover route. Same centered/cast coordinates
        (operators/base.center_coords) and the same pair order as the
        device decode loop (ascending left index, then ascending right
        index — cross_join_kernel's slots ARE right indices), so a
        mid-stream backend switch changes no results
        (tests/test_driver.py pins parity)."""
        from spatialflink_tpu.operators.base import center_coords

        fr = self._filter_radius(radius)

        def process(win) -> JoinWindowResult:
            left_ev = [t.event for t in win.events if t.tag == 0]
            right_ev = [t.event for t in win.events if t.tag == 1]
            if not left_ev or not right_ev:
                return JoinWindowResult(win.start, win.end, [], 0,
                                        len(win.events))
            lxy = center_coords(
                self.grid,
                np.asarray([[p.x, p.y] for p in left_ev], np.float64),
                dtype,
            )
            rxy = center_coords(
                self.grid,
                np.asarray([[p.x, p.y] for p in right_ev], np.float64),
                dtype,
            )
            d = lxy[:, None, :] - rxy[None, :, :]
            dist = np.sqrt(np.sum(d * d, axis=-1))
            pm = dist <= fr
            pairs = []
            for i in np.nonzero(pm.any(axis=1))[0]:
                for s in np.nonzero(pm[i])[0]:
                    pairs.append(
                        (left_ev[int(i)], right_ev[int(s)],
                         float(dist[i, s]))
                    )
            return JoinWindowResult(win.start, win.end, pairs, 0,
                                    len(win.events))

        return process


    def _compact_block(self, lb, rb, radius, offsets, dtype, mesh):
        """One bucketed join under the persistent capacity and budget
        contract (``_join_until_held``); returns host (left_idx,
        right_idx, dist, overflow), the overflow always 0."""
        self.join_budget = max(
            self.join_budget, 1024, min(4 * lb.capacity, 262_144)
        )
        # The batches carry the key grid's cells and nothing finer; only
        # the Pallas extraction has a largest rung (off the TPU the gather
        # join takes over past the dense program's lanes).
        self._open_join(radius, pallas=mesh is None and window_join_program(
            self.join_backend)[1] == "pallas")
        res, count, *_ = self._join_until_held(
            lb.cell, lb.valid, rb.cell, rb.valid,
            lambda _refine, cap, budget: grid_hash_join_batches(
                self.grid, lb, rb, radius, cap, offsets,
                max_pairs=budget, dtype=dtype,
                backend=self.join_backend, mesh=mesh,
                filter_radius=self._filter_radius(radius),
            ),
        )
        li, ri, dd = (
            a[:count] for a in telemetry.fetch(
                (res.left_index, res.right_index, res.dist))
        )
        keep = li >= 0
        return li[keep], ri[keep], dd[keep], 0

    def query_panes(
        self,
        ordinary: Iterable[Point],
        query_stream: Iterable[Point],
        radius: float,
        dtype=np.float64,
        flush_at_end: bool = True,
    ) -> Iterator[JoinWindowResult]:
        """Incremental sliding-window join via pane-block carry.

        A window's pair set is the union over (left-pane, right-pane)
        blocks; sliding by one pane only computes the 2·(size/slide)−1
        blocks that involve the NEW pane — every other block is carried
        from previous windows (the join analog of the ListState carry,
        range/PointPointRangeQuery.java:195-296). Per-slide device work
        drops from O(window²-candidates) to O(pane·window-candidates).

        Pair multiset per window equals ``run()`` whenever
        ``overflow == 0`` (parity test); pair ORDER differs (block-major
        instead of window-compaction order). With overflow, the paths
        diverge: the per-cell ``cap`` applies per PANE here (a cell may
        exceed cap across the window yet fit per pane — pane carry then
        keeps pairs run() would drop), and the reported overflow sums the
        carried blocks' counts instead of one whole-window join's. Same
        caveats as the other pane paths: in-order streams,
        ``allowed_lateness`` rejected, size % slide == 0.
        """
        if self.conf.allowed_lateness_ms > 0:
            raise ValueError(
                "query_panes does not support allowed_lateness; use run()"
            )
        if self.conf.query_type != QueryType.WindowBased:
            raise ValueError(
                "query_panes requires WindowBased time-sliding windows"
            )
        size = self.conf.window_size_ms
        slide = self.conf.slide_step_ms
        if size % slide != 0:
            raise ValueError("query_panes requires size % slide == 0")

        merged = (
            _TaggedEvent(ev.timestamp, tag, ev)
            for tag, ev in merge_by_timestamp(ordinary, query_stream)
        )
        offsets = jnp.asarray(self.grid.neighbor_offsets(radius))
        # Operator-owned, checkpointable carry (checkpoint.py): pane event
        # lists + computed pair blocks — the join's ListState analog. One
        # logical stream pair per operator instance.
        if getattr(self, "_join_pane_carry", None) is None:
            self._join_pane_carry = {"panes": {}, "blocks": {}}
        panes: dict = self._join_pane_carry["panes"]
        blocks: dict = self._join_pane_carry["blocks"]

        for win in self._checkpointable_windows(merged, flush_at_end):
            starts = list(range(win.start, win.end, slide))
            fresh = {ps for ps in starts if ps not in panes}
            if fresh:
                # One O(window) bucketing pass for all new panes (a
                # per-pane rescan would be O(panes × window) on e.g.
                # 10s/10ms configs).
                grouped: dict = {ps: ([], []) for ps in fresh}
                for t in win.events:
                    ps = win.start + ((t.timestamp - win.start) // slide) * slide
                    if ps in grouped:
                        grouped[ps][t.tag].append(t.event)
                for ps, (left_ev, right_ev) in grouped.items():
                    panes[ps] = (
                        left_ev,
                        right_ev,
                        self.point_batch(left_ev) if left_ev else None,
                        self.point_batch(right_ev) if right_ev else None,
                    )
            for ps in [p for p in panes if p < win.start]:
                del panes[ps]
            for key in [k for k in blocks
                        if k[0] < win.start or k[1] < win.start]:
                del blocks[key]

            for p in starts:
                for q in starts:
                    if (p, q) in blocks:
                        continue
                    lev, _, lb, _ = panes[p]
                    _, rev, _, rb = panes[q]
                    if lb is None or rb is None:
                        blocks[(p, q)] = ([], 0)
                        continue
                    li, ri, dd, over = self._compact_block(
                        lb, rb, radius, offsets, dtype, None
                    )
                    blocks[(p, q)] = (
                        [(lev[int(a)], rev[int(b)], float(d))
                         for a, b, d in zip(li, ri, dd)],
                        over,
                    )

            pairs: list = []
            overflow = 0
            for p in starts:
                for q in starts:
                    bp, bo = blocks[(p, q)]
                    pairs.extend(bp)
                    overflow += bo
            yield JoinWindowResult(
                win.start, win.end, pairs, overflow, len(win.events)
            )

    def run_soa(
        self,
        left_chunks,
        right_chunks,
        radius: float,
        max_pairs: int = 262_144,
        dtype=np.float64,
    ):
        """High-rate SoA path: two chunk streams of {"ts","x","y",...}
        arrays → per-window (start, end, left_index, right_index, dist,
        count, overflow) raw compact-join arrays (indices into each side's
        window arrays; -1 / inf padding past ``count``, the arrays as long
        as the padding bucket of ``count``). Windows of the two sides
        align on their shared slide grid; a window present on only one side
        yields zero pairs. The kernels receive the assembler's pre-centered
        coordinates directly (Pallas extraction on TPU).

        Exact on every yielded window (``overflow == 0``): the bucket
        capacity — and past rung 128, in exact mode, the refinement of the
        bucket grid — comes from the window's fullest cell (``_climb``) and
        the pair budget keeps a quarter of headroom over the last count
        (``_grow_budget``, ``max_pairs`` its first value); a window either
        one fails to hold is run again, never yielded short, and one no
        layout of the program holds raises ``JoinCapacityError`` before a
        dispatch. Two fetches a window: count and overflow, then the pairs
        found (in the padding bucket of their count).

        Both sides are assembled one window ahead on a producer thread
        (``_aligned_soa_windows``): window n + 1's chunk pulls, assembly
        (``join.assemble_left``, ``join.assemble``, ``soa.*``, emitted on that
        thread) and alignment run while this loop ships, joins and fetches
        window n; the loop pulls no chunk, so a window's result goes out with
        no pull after its trigger. Everything else — the capacity contract's
        state, every JAX call, ``record_join`` and the op counters — stays on
        the loop's thread.

        With telemetry on, one parent span ``join.window`` a two-sided
        window (args ``n``: events of both sides), emitted by hand at the
        hand-back: from the moment the loop asks for the window to just
        before the yield, so it holds ``join.await`` (the wait for the
        producer: near nothing when the window was assembled beside the last
        one), ``h2d``, ``join.capacity``, ``dispatch:*`` and both ``d2h`` and
        none of the consumer's time. A one-sided window emits none."""
        from spatialflink_tpu.ops.counters import (
            count_join_candidates,
            counters as opcounters,
        )

        fn, self.last_join_backend = window_join_program(self.join_backend)
        head = jitted(head_pairs, "bucket")
        layers = self.grid.candidate_layers(radius)
        fr = self._filter_radius(radius)
        # Approximate mode emits every candidate of the KEY grid's
        # neighbourhood: its buckets stay on the key grid.
        self._open_join(
            radius, refinable=not self.conf.approximate_query,
            pallas=self.last_join_backend == "pallas", dtype=dtype,
        )
        self.join_budget = max(self.join_budget, max_pairs)
        warmed = 0  # the budget whose head programs are compiled
        for kind, wl, wr, asked_ns in _aligned_soa_windows(
            left_chunks, right_chunks,
            *_point_sides(self.grid, self.conf, dtype),
            lambda w: w[0].start, lambda w: w[0].start,
        ):
            _record_windows(wl, wr)
            if kind != "both":
                w = wl[0] if kind == "left" else wr[0]
                yield (w.start, w.end, np.empty(0, np.int32),
                       np.empty(0, np.int32), np.empty(0), 0, 0)
                continue
            win, lxy, lvalid, lcell, _ = wl
            _, rxy, rvalid, rcell, _ = wr
            if opcounters.enabled:
                cand = count_join_candidates(
                    self.grid, lcell, int(lvalid.sum()), rcell,
                    int(rvalid.sum()), layers,
                )
                opcounters.record_candidates(cand, cand)
            # Ship once, outside the retry loop (lanes are reused by every
            # re-run; counted once in bytes_h2d).
            lxy_d, lvalid_d, lcell_d, rxy_d, rvalid_d, rcell_d = ship(
                lxy, lvalid, lcell, rxy, rvalid, rcell
            )
            held = self._join_until_held(
                lcell, lvalid, rcell, rvalid,
                self._window_call(
                    fn, (lxy_d, lvalid_d, lcell_d),
                    (rxy_d, rvalid_d, rcell_d), radius, fr,
                ),
            )
            res, count = held.res, held.count
            pairs = (res.left_index, res.right_index, res.dist)
            if self.join_budget != warmed:
                # A new budget: compile the two head programs a count under
                # it asks for now, not inside a later window.
                warmed = self.join_budget
                for b in (warmed // 2, warmed):
                    head(*pairs, bucket=min(b, len(res.dist)))
            bucket = min(next_bucket(count), len(res.dist))
            li, ri, dd = telemetry.fetch(head(*pairs, bucket=bucket))
            telemetry.record_join(
                pairs=count, cap_retries=held.cap_retries,
                budget_retries=held.budget_retries, cap=self.join_cap,
                budget=self.join_budget, peel_passes=held.peel_passes,
                fullest_cell=held.fullest_cell, refine=self.join_refine,
                bucket_cells=self.grid.num_cells * self.join_refine ** 2,
                bucket_lanes=held.bucket_lanes,
            )
            self._grow_budget(count)  # headroom for the next window
            if asked_ns is not None:
                telemetry.emit_span(
                    "join.window", asked_ns,
                    time.perf_counter_ns() - asked_ns,
                    n=win.count + wr[0].count,
                )
            yield (win.start, win.end, li, ri, dd, count, 0)


def _spanned(gen, name: str):
    """``gen``, with each of its steps inside a telemetry span ``name``."""
    it = iter(gen)
    while True:
        with telemetry.span(name):
            item = next(it, None)
        if item is None:
            return
        yield item


def _point_sides(grid, conf, dtype):
    """The two point sides of the point joins' ``run_soa``, as
    ``_aligned_soa_windows`` takes them: each side's chunks →
    ``soa_point_batches``, the left side under ``join.assemble_left`` (from
    the chunk that lets its window fire), the right side under
    ``join.assemble`` (``_spanned``: every step, its chunk pulls included).
    Their op counters are the loop's (``_record_windows``)."""
    from spatialflink_tpu.operators.base import soa_point_batches

    def left(chunks):
        return soa_point_batches(grid, chunks, conf, dtype,
                                 span="join.assemble_left", counted=False)

    def right(chunks):
        return _spanned(soa_point_batches(grid, chunks, conf, dtype,
                                          counted=False), "join.assemble")

    return left, right


def _record_windows(*sides) -> None:
    """``counters.record_window`` of each point side's window handed over
    (None: no window on that side), on the loop's thread."""
    from spatialflink_tpu.ops.counters import counters

    if counters.enabled:
        for w in sides:
            if w is not None:
                counters.record_window(w[0].count, 0, 0)


def _aligned_soa_windows(left_chunks, right_chunks, windows_l, windows_r,
                         start_l, start_r):
    """Align two per-window streams on their shared slide grid, assembled one
    window ahead of the loop — the single home of the two-stream ``run_soa``
    merge loop.

    ``windows_l`` / ``windows_r`` make a side's per-window generator of its
    chunks (``soa_point_batches``, a ragged assembler's ``stream``);
    ``start_l`` / ``start_r`` read a window's start off each one's item.
    Yields ``(kind, wl, wr, asked_ns)``: ('left', wl, None) / ('right', None,
    wr) for one-sided windows, ('both', wl, wr) for aligned ones, and the
    ``perf_counter_ns`` at which the loop asked for the item (None with
    telemetry off), where the operator's parent span opens.

    Both sides' chunk pulls, their assemblers and the merge run on the
    producer thread ``join-assembly`` of ``base.assembled_ahead``, one
    window ahead of the loop; it emits the assembly spans
    (``join.assemble_left``, ``join.assemble``, ``soa.*``), and the loop's
    thread ``join.await`` around each wait for it. ``record_join_prefetch``
    counts the two-sided windows that were ready when asked for
    (``snapshot()["join"]["prefetched"]``). What a side raises is raised on
    the loop's thread at the item the merge would have raised it at;
    closing the generator stops the producer at its next chunk pull."""

    def merged(lefts, rights):
        gen_l = iter(windows_l(lefts))
        gen_r = iter(windows_r(rights))
        wl = next(gen_l, None)
        wr = next(gen_r, None)
        while wl is not None or wr is not None:
            if wr is None or (wl is not None and start_l(wl) < start_r(wr)):
                yield "left", wl, None
                wl = next(gen_l, None)
            elif wl is None or start_r(wr) < start_l(wl):
                yield "right", None, wr
                wr = next(gen_r, None)
            else:
                yield "both", wl, wr
                wl = next(gen_l, None)
                wr = next(gen_r, None)

    def ready(item):
        if item[0] == "both":
            telemetry.record_join_prefetch()

    for item, asked in assembled_ahead(
            (left_chunks, right_chunks), merged, "join-assembly",
            "join.await", ready):
        yield (*item, asked)


@functools.lru_cache(maxsize=None)
def _dummy_geometry(capacity: int):
    """Constant dummy (capacity, 2, 2) verts + (capacity, 1) edge masks
    for the approximate (bbox-only) kernel modes — the kernel never reads
    them, the shapes just have to line up. Allocated ON DEVICE once per
    capacity bucket and reused every window (lru-cached): the previous
    inline ``jnp.zeros`` pair was two eager dispatches + transfers per
    window."""
    return (
        jnp.zeros((capacity, 2, 2), np.float32),
        jnp.zeros((capacity, 1), bool),
    )


def _centered_bbox(grid, bbox: np.ndarray, dtype, pad: bool = True) -> np.ndarray:
    """Center a (N, 4) minx,miny,maxx,maxy array the way device
    coordinates are centered (operators/base.py:center_coords) so bbox
    pruning compares in the same frame as the vertex/point coords.

    With ``pad`` (the pruning call sites), sub-f64 outputs are padded
    OUTWARD by one ulp per corner: bbox corners round independently of
    the vertex coords, so a sub-ulp-shrunk expanded box could in
    principle prune a geometry exactly at the radius boundary that the
    dense kernel keeps — padding makes bbox rounding strictly
    over-inclusive (pruning is a superset filter; exactness is decided
    by the distance kernel). Approximate-mode call sites pass
    ``pad=False``: there the boxes ARE the distance operands, and
    inflating them would bias every reported bbox distance low."""
    from spatialflink_tpu.operators.base import center_coords

    mins = center_coords(grid, bbox[:, 0:2], dtype)
    maxs = center_coords(grid, bbox[:, 2:4], dtype)
    if pad and mins.dtype != np.float64:
        mins = np.nextafter(mins, -np.inf)
        maxs = np.nextafter(maxs, np.inf)
    return np.concatenate([mins, maxs], axis=1)


class _PrunedGeomJoinRetry:
    """Shared retry state for the pruned geometry joins: ``cand`` (block
    candidate width) grows on cand_overflow, ``pair_cap`` (matches per
    left item) on pair_overflow, ``max_pairs`` on count truncation; all
    persist across windows (the range/join overflow-retry idiom)."""

    _cand = 32
    _pair_cap = 8
    _geom_max_pairs = 4096

    def _pruned_block_pairs(self, call, m_cap: int):
        """call(cand, pair_cap, max_pairs) → PrunedJoinPairs; returns
        host (left_idx, right_idx, dist) with exactness guaranteed: at
        cand == m_cap the prune is a no-op, and pair_cap == cand bounds
        any item's matches. Handles both the single-device result
        (scalar count) and the sharded one (per-shard count vector;
        max_pairs is per shard)."""
        while True:
            cand = min(self._cand, m_cap)
            pair_cap = min(self._pair_cap, cand)
            res = call(cand, pair_cap, self._geom_max_pairs)
            counts = np.asarray(res.count)
            worst = int(counts.max()) if counts.ndim else int(counts)
            if worst > self._geom_max_pairs:
                self._geom_max_pairs = int(2 ** np.ceil(np.log2(worst)))
                continue
            if int(res.cand_overflow) > 0 and cand < m_cap:
                self._cand = min(self._cand * 2, m_cap)
                continue
            if int(res.pair_overflow) > 0 and pair_cap < cand:
                self._pair_cap = min(self._pair_cap * 2, m_cap)
                continue
            break
        if counts.ndim:  # sharded: -1-padded per-shard segments, no slice
            li = np.asarray(res.left_index)
            ri = np.asarray(res.right_index)
            dd = np.asarray(res.dist)
        else:
            count = int(counts)
            li = np.asarray(res.left_index)[:count]
            ri = np.asarray(res.right_index)[:count]
            dd = np.asarray(res.dist)[:count]
        keep = li >= 0
        return li[keep], ri[keep], dd[keep]


class _PointGeometryJoinQuery(SpatialOperator, _PrunedGeomJoinRetry):
    """Point stream ⋈ geometry (polygon/linestring) stream within radius.

    The reference replicates each geometry to its neighbor cells and joins
    on gridID (join/PointPolygonJoinQuery.java). Here the replication
    becomes the device-side block prune of
    ``point_geometry_join_pruned_kernel``: points cell-sorted into tiles,
    tiles bbox-tested against radius-expanded geometry bboxes, exact
    V-vertex distances only for the ≤ ``cand`` candidates per tile
    (O(N·cand·V) instead of the dense O(N·M·V)), pairs compacted on
    device. JTS semantics: 0 inside polygons. Results are exact (overflow
    retry) and identical to the dense masked evaluation (parity test).
    """

    polygonal = True
    _point_block = 256
    # Approximate semantics differ by which side is the POINT stream in
    # the reference: point-ordinary families emit ALL grid candidates
    # (join/PointPolygonJoinQuery.java:131 "all the candidate neighbors
    # are sent to output"); geometry-ordinary families (PolygonPoint /
    # LineStringPoint, which swap into this class) use the point →
    # geometry-bbox min distance (join/PolygonPointJoinQuery.java:
    # getPointPolygonBBoxMinEuclideanDistance).
    approx_emit_all = True

    def _approx_cell_space(self, cells_sorted, valid_sorted, gb, radius):
        """Kernel-space inputs for the point-ordinary approximate mode.

        The reference's candidate set is cell membership: cell(p) inside
        the geometry's bbox-cell rectangle expanded by
        ``candidate_layers(radius)`` (UniformGrid guaranteed ∪ candidate
        cells — a rectangle expanded by L layers stays a rectangle).
        Expressed for the pruned kernel's ``approx`` mode as: coords =
        (xi, yi) CELL indices, per-geometry "bbox" = the layer-expanded
        cell rectangle, radius = 0 (point-in-box ⇔
        bbox_point_min_distance == 0). Reported pair distance is 0 —
        the reference emits no distance in this mode. Out-of-grid
        points never join (key-never-matches semantics)."""
        g = self.grid
        cells = np.asarray(cells_sorted)
        xi = (cells // g.n).astype(np.float64)
        yi = (cells % g.n).astype(np.float64)
        pxy = np.stack([xi, yi], axis=1)
        pvalid = np.asarray(valid_sorted) & (cells < g.num_cells)
        L = g.candidate_layers(radius)
        bb = np.asarray(gb.bbox, np.float64)
        bx1 = np.floor((bb[:, 0] - g.min_x) / g.cell_length) - L
        by1 = np.floor((bb[:, 1] - g.min_y) / g.cell_length) - L
        bx2 = np.floor((bb[:, 2] - g.min_x) / g.cell_length) + L
        by2 = np.floor((bb[:, 3] - g.min_y) / g.cell_length) + L
        gbbox = np.stack([bx1, by1, bx2, by2], axis=1)
        return pxy, pvalid, gbbox

    def _point_side_args(self, pxy_fn, pvalid, pcell, gb, radius, dtype):
        """(args, r_call) for the pruned kernel — ONE home for the
        approximate routing, shared by run() and run_soa().

        ``pxy_fn``: zero-arg callable producing the locality-sorted
        CENTERED point coords — lazy because the emit-all mode replaces
        them with cell indices and must not pay the O(N) centering.
        In both approximate modes the kernel reads only bboxes, so dummy
        (M, 2, 2) verts/edge masks ship instead of the real boundary
        arrays (saves O(M·V) bytes per window over the link; the kernel's
        cand clamp keys on gbbox). Exact mode pads the pruning boxes
        outward one ulp (sub-f64); approximate-bbox mode does NOT — its
        boxes are the distance operands.
        """
        approx = self.conf.approximate_query
        if approx:
            geom = _dummy_geometry(gb.capacity) + (jnp.asarray(gb.valid),)
        else:
            geom = (
                self.device_verts(gb.verts, dtype),
                jnp.asarray(gb.edge_valid),
                jnp.asarray(gb.valid),
            )
        if approx and self.approx_emit_all:
            pxy_k, pvalid_k, gbbox_k = self._approx_cell_space(
                pcell, pvalid, gb, radius
            )
            return (
                (jnp.asarray(pxy_k), jnp.asarray(pvalid_k), *geom,
                 jnp.asarray(gbbox_k)),
                0.0,
            )
        return (
            (jnp.asarray(pxy_fn()), jnp.asarray(pvalid), *geom,
             jnp.asarray(_centered_bbox(self.grid, gb.bbox, dtype,
                                        pad=not approx))),
            radius,
        )

    def run(
        self,
        ordinary: Iterable[Point],
        query_stream: Iterable[Polygon | LineString],
        radius: float,
        dtype=np.float64,
        mesh=None,
    ) -> Iterator[JoinWindowResult]:
        mesh = mesh if mesh is not None else self.mesh
        merged = (
            _TaggedEvent(ev.timestamp, tag, ev)
            for tag, ev in merge_by_timestamp(ordinary, query_stream)
        )
        approx = self.conf.approximate_query
        kernel = jitted(
            point_geometry_join_pruned_kernel,
            "polygonal", "block", "cand", "max_pairs", "pair_cap", "approx",
        )
        for win in self.windows(merged):
            left_ev = [t.event for t in win.events if t.tag == 0]
            right_ev = [t.event for t in win.events if t.tag == 1]
            if not left_ev or not right_ev:
                yield JoinWindowResult(win.start, win.end, [], 0, len(win.events))
                continue
            lb = self.point_batch(left_ev)
            gb = self.geometry_batch(right_ev)
            from spatialflink_tpu.operators.base import center_coords

            # Locality sort HOST-side (numpy ~1 ms vs 13 ms device argsort
            # at 131k on v5e); kernel indices map back through ho.
            # Contiguous sharding of the sorted points preserves locality.
            ho = np.argsort(lb.cell, kind="stable")
            args, r_call = self._point_side_args(
                lambda: center_coords(self.grid, lb.xy[ho], dtype),
                lb.valid[ho], lb.cell[ho], gb, radius, dtype,
            )
            if mesh is not None:
                from spatialflink_tpu.parallel.sharded import (
                    sharded_point_geometry_join_pruned,
                )

                def call(cand, pair_cap, mp):
                    return sharded_point_geometry_join_pruned(
                        mesh, *args, r_call, polygonal=self.polygonal,
                        block=self._point_block, cand=cand, max_pairs=mp,
                        pair_cap=pair_cap, approx=approx,
                    )
            else:
                def call(cand, pair_cap, mp):
                    return kernel(
                        *args, r_call, polygonal=self.polygonal,
                        block=self._point_block, cand=cand, max_pairs=mp,
                        pair_cap=pair_cap, approx=approx,
                    )

            li, ri, dd = self._pruned_block_pairs(call, gb.capacity)
            pairs = [
                (left_ev[int(ho[int(a)])], right_ev[int(b)], float(d))
                for a, b, d in zip(li, ri, dd)
            ]
            yield JoinWindowResult(win.start, win.end, pairs, 0, len(win.events))

    def run_soa(
        self,
        point_chunks,
        geom_chunks,
        radius: float,
        dtype=np.float64,
    ):
        """Ragged-SoA fast path: point chunks {"ts","x","y","oid"} ⋈
        geometry chunks {"ts","oid","lengths","verts"[,"edge_valid"]} →
        per-window (start, end, point_idx, geom_idx, dist, count) raw
        arrays through the pruned kernel — zero per-pair Python. Windows
        align on the shared slide grid; one-sided windows yield no pairs."""
        from spatialflink_tpu.models.batch import GeometryBatch
        from spatialflink_tpu.operators.base import soa_point_batches
        from spatialflink_tpu.streams.soa import RaggedSoaWindowAssembler

        approx = self.conf.approximate_query
        kernel = jitted(
            point_geometry_join_pruned_kernel,
            "polygonal", "block", "cand", "max_pairs", "pair_cap", "approx",
        )

        def points(chunks):
            return soa_point_batches(self.grid, chunks, self.conf, dtype,
                                     counted=False)

        def geometries(chunks):
            return RaggedSoaWindowAssembler(
                self.conf.window_size_ms, self.conf.slide_step_ms,
                ooo_ms=self.conf.allowed_lateness_ms,
            ).stream(chunks)

        empty = (np.empty(0, np.int32), np.empty(0, np.int32), np.empty(0))
        for kind, wl, wr, _asked in _aligned_soa_windows(
            point_chunks, geom_chunks, points, geometries,
            lambda w: w[0].start, lambda w: w.start,
        ):
            _record_windows(wl)
            if kind == "left":
                yield (wl[0].start, wl[0].end, *empty, 0)
                continue
            if kind == "right":
                yield (wr.start, wr.end, *empty, 0)
                continue
            win, lxy, lvalid, lcell, _ = wl
            gb = GeometryBatch.from_ragged(
                wr.ts, wr.oid, wr.lengths, wr.verts,
                edge_valid_flat=wr.edge_valid, dtype=np.float64,
            )
            ho = np.argsort(lcell, kind="stable")  # host locality sort
            args, r_call = self._point_side_args(
                lambda: np.asarray(lxy)[ho], np.asarray(lvalid)[ho],
                np.asarray(lcell)[ho], gb, radius, dtype,
            )
            li, ri, dd = self._pruned_block_pairs(
                lambda cand, pair_cap, mp: kernel(
                    *args, r_call, polygonal=self.polygonal,
                    block=self._point_block, cand=cand, max_pairs=mp,
                    pair_cap=pair_cap, approx=approx,
                ),
                gb.capacity,
            )
            yield (win.start, win.end, ho[li].astype(np.int32), ri, dd,
                   len(li))


class PointPolygonJoinQuery(_PointGeometryJoinQuery):
    """join/PointPolygonJoinQuery.java."""

    polygonal = True


class PointLineStringJoinQuery(_PointGeometryJoinQuery):
    """join/PointLineStringJoinQuery.java."""

    polygonal = False


class _GeometryGeometryJoinQuery(SpatialOperator, _PrunedGeomJoinRetry):
    """Geometry ⋈ geometry within radius — JTS distance semantics including
    overlap/containment → 0.

    Runs ``geometry_geometry_join_pruned_kernel``: left geometries sorted
    by bbox-center locality into tiles, tiles bbox-tested against
    radius-expanded right bboxes, exact pair distances only for the
    ≤ ``cand`` candidates per tile (O(L·cand·V²) instead of the dense
    O(L·R·V²)), pairs compacted on device. Exact via the overflow-retry
    contract; parity-tested against the dense kernel.
    """

    left_polygonal = True
    right_polygonal = True
    _geom_block = 32

    def _window_pairs(self, kernel, la, ra, radius, dtype, mesh=None):
        """Host locality sort of the left side (quantized bbox centers) +
        pruned kernel; returns ORIGINAL-index pairs. With ``mesh``, the
        sorted left side shards contiguously over ``data`` (locality
        preserved), the right side replicates."""
        cx = (la.bbox[:, 0] + la.bbox[:, 2]) * 0.5
        cy = (la.bbox[:, 1] + la.bbox[:, 3]) * 0.5
        with np.errstate(invalid="ignore", divide="ignore"):
            vx = cx[la.valid]
            vy = cy[la.valid]
            x0, x1 = (vx.min(), vx.max()) if len(vx) else (0.0, 1.0)
            y0, y1 = (vy.min(), vy.max()) if len(vy) else (0.0, 1.0)
            qx = np.clip((cx - x0) / max(x1 - x0, 1e-30) * 1023, 0, 1023)
            qy = np.clip((cy - y0) / max(y1 - y0, 1e-30) * 1023, 0, 1023)
        key = np.where(
            la.valid,
            qy.astype(np.int64) * 1024 + qx.astype(np.int64),
            np.int64(1) << 40,
        )
        ho = np.argsort(key, kind="stable")
        approx = self.conf.approximate_query
        if approx:
            # bbox↔bbox mode reads only the bbox arrays — ship dummy
            # (N, 2, 2) verts instead of the real boundaries (saves
            # O(N·V) bytes per window over the link; cand clamp keys on
            # bbbox). pad=False: these boxes are the distance operands.
            args = _dummy_geometry(la.capacity) + (
                jnp.asarray(la.valid[ho]),
                jnp.asarray(_centered_bbox(self.grid, la.bbox[ho], dtype,
                                           pad=False)),
            ) + _dummy_geometry(ra.capacity) + (
                jnp.asarray(ra.valid),
                jnp.asarray(_centered_bbox(self.grid, ra.bbox, dtype,
                                           pad=False)),
            )
        else:
            args = (
                self.device_verts(la.verts[ho], dtype),
                jnp.asarray(la.edge_valid[ho]),
                jnp.asarray(la.valid[ho]),
                jnp.asarray(_centered_bbox(self.grid, la.bbox[ho], dtype)),
                self.device_verts(ra.verts, dtype),
                jnp.asarray(ra.edge_valid),
                jnp.asarray(ra.valid),
                jnp.asarray(_centered_bbox(self.grid, ra.bbox, dtype)),
            )
        if mesh is not None:
            from spatialflink_tpu.parallel.sharded import (
                sharded_geometry_geometry_join_pruned,
            )

            def call(cand, pair_cap, mp):
                return sharded_geometry_geometry_join_pruned(
                    mesh, *args, radius,
                    a_polygonal=self.left_polygonal,
                    b_polygonal=self.right_polygonal,
                    block=self._geom_block, cand=cand, max_pairs=mp,
                    pair_cap=pair_cap, approx=approx,
                )
        else:
            def call(cand, pair_cap, mp):
                return kernel(
                    *args, radius,
                    a_polygonal=self.left_polygonal,
                    b_polygonal=self.right_polygonal,
                    block=self._geom_block, cand=cand, max_pairs=mp,
                    pair_cap=pair_cap, approx=approx,
                )

        li, ri, dd = self._pruned_block_pairs(call, ra.capacity)
        return ho[li].astype(np.int32), ri, dd

    def run(
        self,
        ordinary: Iterable[Polygon | LineString],
        query_stream: Iterable[Polygon | LineString],
        radius: float,
        dtype=np.float64,
        mesh=None,
    ) -> Iterator[JoinWindowResult]:
        mesh = mesh if mesh is not None else self.mesh
        merged = (
            _TaggedEvent(ev.timestamp, tag, ev)
            for tag, ev in merge_by_timestamp(ordinary, query_stream)
        )
        kernel = jitted(
            geometry_geometry_join_pruned_kernel,
            "a_polygonal", "b_polygonal", "block", "cand", "max_pairs",
            "pair_cap", "approx",
        )
        for win in self.windows(merged):
            left_ev = [t.event for t in win.events if t.tag == 0]
            right_ev = [t.event for t in win.events if t.tag == 1]
            if not left_ev or not right_ev:
                yield JoinWindowResult(win.start, win.end, [], 0, len(win.events))
                continue
            la = self.geometry_batch(left_ev)
            ra = self.geometry_batch(right_ev)
            li, ri, dd = self._window_pairs(kernel, la, ra, radius, dtype,
                                            mesh=mesh)
            pairs = [
                (left_ev[int(a)], right_ev[int(b)], float(d))
                for a, b, d in zip(li, ri, dd)
            ]
            yield JoinWindowResult(win.start, win.end, pairs, 0, len(win.events))

    def run_soa(
        self,
        left_chunks,
        right_chunks,
        radius: float,
        dtype=np.float64,
    ):
        """Ragged-SoA fast path for geometry ⋈ geometry: both sides are
        ragged geometry chunk streams ({"ts","oid","lengths","verts"
        [,"edge_valid"]}); yields per-window (start, end, left_idx,
        right_idx, dist, count) raw arrays via the pruned kernel."""
        from spatialflink_tpu.models.batch import GeometryBatch
        from spatialflink_tpu.streams.soa import RaggedSoaWindowAssembler

        kernel = jitted(
            geometry_geometry_join_pruned_kernel,
            "a_polygonal", "b_polygonal", "block", "cand", "max_pairs",
            "pair_cap", "approx",
        )

        def gen(chunks):
            asm = RaggedSoaWindowAssembler(
                self.conf.window_size_ms, self.conf.slide_step_ms,
                ooo_ms=self.conf.allowed_lateness_ms,
            )
            return asm.stream(chunks)

        def batch(w):
            return GeometryBatch.from_ragged(
                w.ts, w.oid, w.lengths, w.verts,
                edge_valid_flat=w.edge_valid, dtype=np.float64,
            )

        empty = (np.empty(0, np.int32), np.empty(0, np.int32), np.empty(0))
        for kind, wl, wr, _asked in _aligned_soa_windows(
            left_chunks, right_chunks, gen, gen,
            lambda w: w.start, lambda w: w.start,
        ):
            if kind != "both":
                w = wl if kind == "left" else wr
                yield (w.start, w.end, *empty, 0)
                continue
            la, ra = batch(wl), batch(wr)
            li, ri, dd = self._window_pairs(kernel, la, ra, radius, dtype)
            yield (wl.start, wl.end, li, ri, dd, len(li))


class PolygonPointJoinQuery(_PointGeometryJoinQuery):
    """join/PolygonPointJoinQuery.java — polygon stream ⋈ point queries;
    run() takes (point_stream, polygon_stream) transposed by the caller in
    the reference; here the class swaps internally. Approximate mode is
    the bbox distance (getPointPolygonBBoxMinEuclideanDistance ≤ r), NOT
    emit-all — that semantic belongs to the point-ordinary families."""

    polygonal = True
    approx_emit_all = False

    def run(self, ordinary, query_stream, radius, dtype=np.float64,
            mesh=None):
        # Reference semantics: ordinary = polygons, query = points.
        for res in super().run(query_stream, ordinary, radius, dtype=dtype,
                               mesh=mesh):
            res.pairs = [(b, a, d) for (a, b, d) in res.pairs]
            yield res


class PolygonPolygonJoinQuery(_GeometryGeometryJoinQuery):
    """join/PolygonPolygonJoinQuery.java."""

    left_polygonal = True
    right_polygonal = True


class PolygonLineStringJoinQuery(_GeometryGeometryJoinQuery):
    """join/PolygonLineStringJoinQuery.java."""

    left_polygonal = True
    right_polygonal = False


class LineStringPointJoinQuery(PolygonPointJoinQuery):
    """join/LineStringPointJoinQuery.java."""

    polygonal = False


class LineStringPolygonJoinQuery(_GeometryGeometryJoinQuery):
    """join/LineStringPolygonJoinQuery.java."""

    left_polygonal = False
    right_polygonal = True


class LineStringLineStringJoinQuery(_GeometryGeometryJoinQuery):
    """join/LineStringLineStringJoinQuery.java."""

    left_polygonal = False
    right_polygonal = False
