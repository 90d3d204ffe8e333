"""Shared operator machinery: window planning, batching, jitted programs.

Every spatial operator follows the same shape:
  1. driver side (host, once per run): build the query's neighbor-cell flag
     table from the grid (the reference does this per query object too —
     e.g. PointPointRangeQuery.java:119-125);
  2. per window: assemble the event buffer into a padded SoA batch, ship to
     a jitted XLA program (compiled once per bucket size), decode results.

RealTime query types are executed as tumbling micro-batches
(``realtime_batch_ms``) — the batched analog of per-record evaluation.
CountBased uses count windows.
"""

from __future__ import annotations

import functools
import time
from typing import (
    Callable,
    Iterable,
    Iterator,
    List,
    NamedTuple,
    Optional,
    Sequence,
    Tuple,
)

import jax
import numpy as np

from spatialflink_tpu.grid import UniformGrid
from spatialflink_tpu.models.batch import GeometryBatch, PointBatch
from spatialflink_tpu.models.objects import LineString, Point, Polygon, SpatialObject
from spatialflink_tpu.operators.query_config import QueryConfiguration, QueryType
from spatialflink_tpu.streams.windows import (
    SlidingEventTimeWindows,
    TumblingEventTimeWindows,
    WindowAssembler,
    WindowBatch,
)
from spatialflink_tpu.faults import faults
from spatialflink_tpu.telemetry import instrument_jit, telemetry
from spatialflink_tpu.utils.interning import Interner


def window_assigner_for(conf: QueryConfiguration) -> SlidingEventTimeWindows:
    if conf.query_type in (QueryType.RealTime, QueryType.RealTimeNaive):
        return TumblingEventTimeWindows(conf.realtime_batch_ms)
    return SlidingEventTimeWindows(conf.window_size_ms, conf.slide_step_ms)


def count_window_batches(
    events: Iterable, size: int, slide: int
) -> Iterator[WindowBatch]:
    """CountBased mode: fixed-count windows over arrival order (the
    reference's QueryType.CountBased uses Flink countWindow). Window spans
    are the event-time extents of each slice."""
    from spatialflink_tpu.streams.windows import CountWindows

    cw = CountWindows(size, slide)
    buf: list = []
    for ev in events:
        for slice_ in cw.feed(buf, ev):
            yield WindowBatch(slice_[0].timestamp, slice_[-1].timestamp + 1, list(slice_))
    if buf:
        yield WindowBatch(buf[0].timestamp, buf[-1].timestamp + 1, list(buf))


class SpatialOperator:
    """Base: holds grid + config (SpatialOperator.java is an empty abstract
    base; here the base carries the real shared machinery).

    ``mesh``: optional ``jax.sharding.Mesh`` with a ``data`` axis. When set
    (or passed to ``run``), window kernels execute shard_mapped over the
    mesh — the runtime analog of the reference's default parallel execution
    (env.setParallelism, StreamingJob.java:177; conf default 15 at
    conf/geoflink-conf.yml:55). Results are bit-identical to single-device:
    elementwise kernels shard the stream axis with no collective; kNN
    pmin-reduces per-object minima over ICI (parallel/sharded.py).
    Point batches pad to power-of-two buckets (min 256), so any
    power-of-two ``data`` axis up to 256 divides them
    (``mesh_from_config`` enforces power-of-two); geometry batches raise
    their bucket floor to the data-axis size in ``geometry_batch``.
    """

    def __init__(self, conf: QueryConfiguration, grid: UniformGrid, mesh=None):
        self.conf = conf
        self.grid = grid
        self.mesh = mesh
        self.interner = Interner()

    # -- window plumbing ------------------------------------------------------

    def _assembler(self) -> WindowAssembler:
        return WindowAssembler(
            window_assigner_for(self.conf),
            timestamp_fn=lambda e: e.timestamp,
            max_out_of_orderness_ms=self.conf.allowed_lateness_ms,
            allowed_lateness_ms=self.conf.allowed_lateness_ms,
        )

    def windows(self, stream: Iterable[SpatialObject]) -> Iterator[WindowBatch]:
        if self.conf.query_type == QueryType.CountBased:
            yield from count_window_batches(
                stream, self.conf.count_window_size, self.conf.count_window_size
            )
        else:
            yield from self._assembler().stream(stream)

    def _adopt_assembler(self, asm) -> "WindowAssembler":
        """THE home of the restore-and-expose assembler protocol (also
        used by the dataflow driver, spatialflink_tpu/driver.py): consume
        a state restored by checkpoint.restore_operator before the first
        event, and expose the assembler as ``self.checkpoint_assembler``
        for checkpoint.operator_state to snapshot."""
        if getattr(self, "_restored_assembler", None):
            from spatialflink_tpu.checkpoint import restore_assembler

            restore_assembler(asm, self._restored_assembler)
            self._restored_assembler = None
        self.checkpoint_assembler = asm
        return asm

    def _adopt_soa_assembler(self, asm):
        """SoA twin of ``_adopt_assembler`` (point and ragged assemblers
        both snapshot through checkpoint.soa_assembler_state)."""
        if getattr(self, "_restored_soa_assembler", None):
            from spatialflink_tpu.checkpoint import restore_soa_assembler

            restore_soa_assembler(asm, self._restored_soa_assembler)
            self._restored_soa_assembler = None
        self.checkpoint_soa_assembler = asm
        return asm

    def _checkpointable_windows(self, stream, flush_at_end: bool = True):
        """Event-time windows with checkpoint hooks — the pane-carry
        assembler plumbing (kNN/join query_panes):

        - the assembler is exposed as ``self.checkpoint_assembler``
          (snapshotted by checkpoint.operator_state);
        - a state restored by checkpoint.restore_operator is consumed
          before the first event;
        - ``flush_at_end=False`` treats end-of-source as a KILL point
          (open windows stay buffered for the resumed run) instead of
          end-of-stream.
        """
        asm = self._adopt_assembler(self._assembler())
        for ev in stream:
            yield from asm.feed(ev)
        if flush_at_end:
            yield from asm.flush()

    def _checkpointable_soa_windows(self, asm, chunks,
                                    flush_at_end: bool = True):
        """SoA twin of ``_checkpointable_windows`` (caller supplies the
        soa.py assembler)."""
        self._adopt_soa_assembler(asm)
        for chunk in chunks:
            yield from asm.feed(chunk)
        if flush_at_end:
            yield from asm.flush()

    # -- batch building -------------------------------------------------------

    def point_batch(self, events: Sequence[Point]) -> PointBatch:
        # Batches stay float64 on the host regardless of the kernel dtype:
        # the f32 cast happens at the device boundary AFTER origin-centering
        # (see center_coords) so no precision is lost to ~116° magnitudes.
        batch = PointBatch.from_points(events, interner=self.interner, dtype=np.float64)
        return batch.with_cells(self.grid)

    def device_q(self, coords, dtype):
        """Device-ready coordinates (any (..., 2) array-like): origin-
        centered before sub-f64 casts. The one centering entry point —
        device_xy/device_verts are shape-documenting aliases. Crosses the
        link through ``_h2d`` like ``ship`` (same accounting, same leaf
        span), minus ``ship``'s chaos injection point."""
        host = center_coords(self.grid, np.asarray(coords, np.float64), dtype)
        return _h2d((host,))[0]

    def device_table(self, table: np.ndarray):
        """A query set's index table, device-ready as built (any
        coordinates in it centred and cast already): crosses the link
        through ``_h2d`` like ``device_q`` — counted, one leaf span."""
        return _h2d((table,))[0]

    def device_xy(self, batch: PointBatch, dtype):
        """Device-ready point-batch coordinates."""
        return self.device_q(batch.xy, dtype)

    def geometry_batch(
        self, events: Sequence[Polygon | LineString], mesh=None
    ) -> GeometryBatch:
        # Host storage is f64; centering/casting happens at the boundary.
        # The geometry bucket floor is 8; under a mesh the object axis must
        # divide by the data-axis size, so raise the floor to it (buckets
        # are floor·2^k, hence always divisible by the floor).
        mesh = mesh if mesh is not None else self.mesh
        bucket = None
        if mesh is not None:
            from spatialflink_tpu.utils.padding import next_bucket

            data = mesh.shape.get("data", 1)
            bucket = next_bucket(len(events), minimum=max(8, int(data)))
        return GeometryBatch.from_objects(events, interner=self.interner,
                                          dtype=np.float64, bucket=bucket)

    def device_verts(self, verts: np.ndarray, dtype):
        """Device-ready packed boundary vertices ((..., 2) arrays)."""
        return self.device_q(verts, dtype)


def query_cells_of(grid: UniformGrid, query_obj) -> List[int]:
    """Flat cells a query object overlaps (point → 1 cell; polygon/
    linestring → bbox cells, like gridIDsSet)."""
    if hasattr(query_obj, "grid_cells"):
        return list(query_obj.grid_cells(grid))
    raise TypeError(type(query_obj).__name__)


def flags_for_queries(
    grid: UniformGrid, radius: float, query_objs: Sequence
) -> np.ndarray:
    """Union flag table over all query objects (guaranteed wins)."""
    cells: List[int] = []
    for q in query_objs:
        cells.extend(query_cells_of(grid, q))
    return grid.neighbor_flags(radius, cells)


def pack_query_points(query_objs: Sequence[Point], dtype=np.float64) -> np.ndarray:
    return np.array([[q.x, q.y] for q in query_objs], dtype)


def pack_query_geometries(
    query_objs: Sequence[Polygon | LineString], dtype=np.float64
) -> Tuple[np.ndarray, np.ndarray]:
    """(Q, V, 2) verts + (Q, V-1) edge_valid, padded to a shared V."""
    from spatialflink_tpu.utils.padding import next_bucket

    vmax = max(q.num_vertices_packed() for q in query_objs)
    v = next_bucket(vmax, minimum=8)
    verts = np.zeros((len(query_objs), v, 2), dtype)
    ev = np.zeros((len(query_objs), v - 1), bool)
    for i, q in enumerate(query_objs):
        pv, pe = q.packed(pad_to=v)
        verts[i] = pv
        ev[i] = pe
    return verts, ev


class CellCandidates(NamedTuple):
    """The grid index of a polygon query set (``pack_cell_candidates``)."""

    table: np.ndarray  # (num_cells + 1, K) int32; P marks an empty slot
    entries: int  # Σ list lengths: the (cell, polygon) pairs
    cells: int  # cells with a non-empty list

    @property
    def slots(self) -> int:
        """K: the fullest cell's list, up the bucket ladder (least 8)."""
        return self.table.shape[1]


def _rank_in_group(counts: np.ndarray) -> np.ndarray:
    """0, 1, … within each run of a sequence grouped into runs of
    ``counts`` items: [2, 0, 3] → [0, 1, 0, 1, 2]."""
    return np.arange(counts.sum()) - np.repeat(
        np.cumsum(counts) - counts, counts)


def pack_cell_candidates(
    grid: UniformGrid, verts: np.ndarray, edge_valid: np.ndarray,
    radius: float,
) -> CellCandidates:
    """Cell → candidate-polygons table for a packed polygon set, built once
    per query set on the host in float64.

    Polygon ``i`` of ``verts`` (P, V, 2) / ``edge_valid`` (P, V-1) (uncentred
    coordinates, as ``pack_query_geometries`` gives them) is entered in the
    list of every grid cell its bounding box touches once grown by
    ``radius`` plus a margin of 32 float32 eps of the largest centred
    coordinate (the band in which a float32 distance on centred coordinates
    can fall on the other side of r; 4.0e-6° on the Beijing bbox). Cell
    ranges use ``UniformGrid.assign_cells_np``'s own arithmetic —
    ``floor((x - min_x) / cell_length)``, closed on both sides of the grown
    box, clipped to the grid — and that expression is monotone in x, so a
    point ``assign_cells_np`` puts in cell g can be within r (and the band)
    only of polygons in g's list. Row ``num_cells`` (out of grid) is empty;
    an empty slot holds P, one past the last polygon (``pack_cell_edges``
    keeps its far edges there). A polygon with no valid edge is in no list.
    """
    from spatialflink_tpu.utils.padding import next_bucket

    verts = np.asarray(verts, np.float64)
    ev = np.asarray(edge_valid, bool)
    p, n = len(verts), grid.n
    real = np.zeros(verts.shape[:2], bool)  # a vertex bounds a real edge
    real[:, :-1] |= ev
    real[:, 1:] |= ev
    live = np.nonzero(real.any(axis=1))[0]
    lo = np.where(real[..., None], verts, np.inf).min(axis=1)[live]
    hi = np.where(real[..., None], verts, -np.inf).max(axis=1)[live]
    origin = np.array([grid.min_x, grid.min_y])
    centre = np.array([(grid.min_x + grid.max_x) / 2.0,
                       (grid.min_y + grid.max_y) / 2.0])
    scale = max(float(np.abs(np.array([lo, hi]) - centre).max(initial=0.0)),
                float(np.abs(origin - centre).max()))
    grow = radius + 32.0 * float(np.finfo(np.float32).eps) * scale
    # floor indices as assign_cells_np takes them; clipped one past each
    # side first, so that a far-away box cannot overflow the int cast
    i0 = np.clip(np.floor((lo - grow - origin) / grid.cell_length), -1, n)
    i1 = np.clip(np.floor((hi + grow - origin) / grid.cell_length), -1, n)
    inside = ((i1 >= 0) & (i0 <= n - 1)).all(axis=1)
    live = live[inside]
    i0 = np.maximum(i0[inside], 0).astype(np.int64)
    i1 = np.minimum(i1[inside], n - 1).astype(np.int64)
    nx, ny = (i1 - i0 + 1).T
    per_poly = nx * ny
    which = np.repeat(np.arange(len(live)), per_poly)
    k = _rank_in_group(per_poly)
    cell = ((i0[which, 0] + k // ny[which]) * n
            + i0[which, 1] + k % ny[which])
    order = np.lexsort((live[which], cell))
    cell, poly = cell[order], live[which][order]
    counts = np.bincount(cell, minlength=grid.num_cells + 1)
    slots = next_bucket(int(counts.max(initial=0)), minimum=8)
    table = np.full((grid.num_cells + 1, slots), p, np.int32)
    table[cell, _rank_in_group(counts)] = poly
    return CellCandidates(table, len(cell), int(np.count_nonzero(counts)))


#: where ``pack_cell_edges`` puts both ends of an empty edge slot: a
#: degenerate segment no ray crosses, farther than any real edge, whose
#: squared distance still fits float32
FAR_EDGE = 1e18


def pack_cell_edges(table: np.ndarray, verts: np.ndarray,
                    edge_valid: np.ndarray) -> np.ndarray:
    """The cell table with the candidates' edges in place of their
    indices: (num_cells + 1, 4, E, K) endpoint planes x1, y1, x2, y2 — what
    ops/range.py's pruned kernels gather one row of per point.

    ``table`` is ``pack_cell_candidates``' (num_cells + 1, K); ``verts``
    (P, V, 2) are the packed rings as the device will see them (centred
    and cast: ``center_coords``), ``edge_valid`` (P, V-1) their edge mask.
    Each polygon's valid edges are listed first (a ring seam and padding
    are no edges), E is the longest such list, and every slot left over —
    the tail of a shorter polygon, the whole of an empty candidate slot —
    holds ``FAR_EDGE`` at both ends. The minimum and the crossing count
    over a polygon's slots are those over its valid edges, in any order.
    """
    ev = np.asarray(edge_valid, bool)
    per_poly = ev.sum(axis=1)
    which, j = np.nonzero(ev)  # row-major: a polygon's edges in ring order
    slot = _rank_in_group(per_poly)
    edges = np.full((len(verts) + 1, 4, max(int(per_poly.max(initial=0)), 1)),
                    FAR_EDGE, verts.dtype)
    edges[which, 0, slot], edges[which, 1, slot] = verts[which, j].T
    edges[which, 2, slot], edges[which, 3, slot] = verts[which, j + 1].T
    # (cells, K, 4, E) -> (cells, 4, E, K): the K slots on the minor axis
    return np.ascontiguousarray(edges[table].transpose(0, 2, 3, 1))


def _centring(grid: UniformGrid, dtype):
    """``center_coords``' rule: the (cx, cy) to subtract in float64 and the
    dtype to cast to, or None where nothing is centred — the EFFECTIVE
    device dtype is float64 (asked for, and jax x64 enabled)."""
    if np.dtype(dtype) == np.float64:
        if jax.config.jax_enable_x64:
            return None
        dtype = np.float32
    return ((grid.min_x + grid.max_x) / 2.0,
            (grid.min_y + grid.max_y) / 2.0), dtype


def center_coords(grid: UniformGrid, xy: np.ndarray, dtype) -> np.ndarray:
    """Origin-center coordinates before a float32 cast.

    Degree-scale values (~116°) have f32 ulps of ~7.6e-6°, so distances
    between nearby points lose ~meters of precision to cancellation.
    Subtracting the grid center in float64 FIRST and then casting leaves
    magnitudes of O(bbox span), where f32 ulps are ~1e-7° — radius-boundary
    decisions match the f64 reference for all practical radii. Distances
    are translation-invariant, so kernels need no other change (cell
    assignment uses the original coordinates).

    The decision keys on the EFFECTIVE device dtype: with jax x64 disabled
    (the TPU default), a float64 request still lands as f32 on device
    (jnp.asarray silently downcasts), so centering must happen then too.
    """
    centring = _centring(grid, dtype)
    if centring is None:
        return np.asarray(xy, np.float64)
    centre, out_dtype = centring
    return (np.asarray(xy, np.float64) - np.array(centre)).astype(out_dtype)


def check_oid_range(oid, num_segments: int) -> None:
    """Dense-id contract guard for the SoA fast paths: ids outside
    ``[0, num_segments)`` would be silently dropped — by the segment
    reductions, and a negative one by the trajectory join, whose bucket
    planes mark an empty slot with -1 — so fail loudly at the batch
    boundary instead."""
    if not len(oid):
        return
    lo, hi = int(np.min(oid)), int(np.max(oid))
    if lo < 0 or hi >= num_segments:
        raise ValueError(
            f"oid {lo if lo < 0 else hi} outside [0, num_segments = "
            f"{num_segments}): out-of-range ids would be silently dropped"
        )


def _link_nbytes(a) -> int:
    """Bytes ``a`` occupies once it has crossed: ``jnp.asarray`` lands a
    float64/int64 host array as its 32-bit twin while x64 is off (the TPU
    default), so the host array's own ``nbytes`` would count double."""
    if not hasattr(a, "dtype"):
        a = np.asarray(a)
    return int(a.size) * jax.dtypes.canonicalize_dtype(a.dtype).itemsize


def _h2d(arrays):
    """``jnp.asarray`` each host array (``None`` lanes pass through): THE
    host→device crossing of ``ship`` and ``device_q``. Enabled telemetry
    counts the crossing bytes (read from host metadata before the
    transfer — no extra device traffic) and times the conversions as one
    ``h2d`` leaf span; disabled, the span is the shared null span."""
    import jax.numpy as jnp

    args = {}
    if telemetry.enabled:
        live = [a for a in arrays if a is not None]
        args = {"bytes": sum(_link_nbytes(a) for a in live),
                "arrays": len(live)}
        telemetry.account_h2d(args["bytes"])
    with telemetry.span("h2d", **args):
        return tuple(None if a is None else jnp.asarray(a) for a in arrays)


def ship(*arrays):
    """``jnp.asarray`` each host array with host→device byte accounting.

    THE ship entry point for telemetry: tallies are taken here — at the
    conversion that actually crosses the host→device link — never inside batch
    builders, so ``bytes_h2d`` counts exactly the lanes a path ships
    (``None`` lanes pass through unconverted and uncounted), in the dtype
    that lands on the device (``_link_nbytes``).
    """
    if faults.armed:  # chaos injection point (faults.py)
        faults.hit("device.ship")
    return _h2d(arrays)


#: Points a block of ``point_lanes``' walk: its scratch (two float64 and
#: two bool rows a block long, 0.6 MB) stays in L2 while a million-point
#: window streams through. Flat from 8,192 to 131,072 (PERF.md §6, PR 38).
_LANE_BLOCK = 32768


def lane_scratch():
    """The block-length work rows ``point_lanes`` walks a slice over
    (float64 ``(2, block)``, bool ``(2, block)``): a stream makes them once
    and hands them to every window's call. Pages a short block never
    reaches are never touched."""
    return (np.empty((2, _LANE_BLOCK), np.float64),
            np.empty((2, _LANE_BLOCK), bool))


def point_lanes(grid: UniformGrid, x, y, oid, dtype, scratch=None):
    """One SoA point-slice → device-ready padded (xy, valid, cell, oid).

    The shared batch contract of every SoA fast path: bucket padding,
    origin-centering before sub-f64 casts (``center_coords``' rule and
    bits), invalid lanes carrying cell=grid.num_cells (the out-of-grid slot
    whose flag is always 0) — identical to
    PointBatch.from_arrays(...).with_cells(grid).

    ``x`` and ``y`` are the slice's columns (any real dtype, any stride).
    The four lanes are allocated at bucket length, fresh every call (the
    consumer and ``jnp.asarray`` may hold them), and each is written once:
    the n points go through in blocks of ``_LANE_BLOCK`` over ``scratch``
    (``lane_scratch()``: the caller's, kept from window to window; made
    here when not given), so no whole-window temporary is made. Three phase
    spans, one each a call, inside whatever span the caller has open (args
    ``n``; ``bucket`` on ``soa.pad``):

    - ``soa.center``: a block of ``x``, then of ``y``, upcast to float64,
      less the grid's centre, assigned into its column of ``xy`` — the
      assignment is the cast. With an effective float64 the columns are
      written as they are.
    - ``soa.cells``: ``UniformGrid.assign_cells_into`` a block, into
      ``cell`` (the arithmetic of ``assign_cells_np``, on the original
      coordinates).
    - ``soa.pad``: the tails past n (``xy`` 0, ``cell`` num_cells), the
      ``valid`` lane, ``oid`` cast to int32; nothing is concatenated.

    Enabled telemetry counts the call once (``record_soa_lanes``); there is
    no span, clock read or counter per block.
    """
    from spatialflink_tpu.utils.padding import next_bucket

    x, y = np.asarray(x), np.asarray(y)
    n = len(x)
    b = next_bucket(n)
    blocks = range(0, n, _LANE_BLOCK)
    work, mask = scratch or lane_scratch()
    with telemetry.span("soa.center", n=n):
        centring = _centring(grid, dtype)
        if centring is None:
            xy = np.empty((b, 2), np.float64)
            xy[:n, 0], xy[:n, 1] = x, y
        else:
            centre, out_dtype = centring
            xy = np.empty((b, 2), out_dtype)
            for lo in blocks:
                hi = min(lo + _LANE_BLOCK, n)
                for k, col in enumerate((x, y)):
                    # dtype=: float64 arithmetic whatever the column holds
                    xy[lo:hi, k] = np.subtract(
                        col[lo:hi], centre[k], out=work[0, :hi - lo],
                        dtype=np.float64)
    with telemetry.span("soa.cells", n=n):
        cell = np.empty(b, np.int32)
        for lo in blocks:
            hi = min(lo + _LANE_BLOCK, n)
            grid.assign_cells_into(x[lo:hi], y[lo:hi], cell[lo:hi], work, mask)
    # Host-side lanes only — no byte accounting here: callers ship
    # different subsets of these lanes (run_soa drops oid, the pane digest
    # path replaces valid/cell), so h2d tallies live at the actual
    # jnp.asarray ship sites (base.ship) to stay truthful.
    with telemetry.span("soa.pad", n=n, bucket=b):
        xy[n:] = 0
        cell[n:] = grid.num_cells
        valid = np.empty(b, bool)
        valid[:n], valid[n:] = True, False
        if oid is not None:
            lane = np.empty(b, np.int32)
            lane[:n], lane[n:] = oid, 0
            oid = lane
    telemetry.record_soa_lanes(n, b, len(blocks))
    return xy, valid, cell, oid


def device_point_args(grid: UniformGrid, xy64: np.ndarray, oid, dtype):
    """``point_lanes`` of the two columns of an ``(n, 2)`` point slice."""
    xy64 = np.asarray(xy64)
    return point_lanes(grid, xy64[:, 0], xy64[:, 1], oid, dtype)


def soa_point_batches(grid: UniformGrid, chunks, conf: QueryConfiguration,
                      dtype=np.float64, span: Optional[str] = None,
                      counted: bool = True):
    """SoA windows → (window, padded arrays) for the run_soa fast paths.

    Yields (win, xy, valid, cell, oid) per the point_lanes contract.
    ``span`` names a telemetry span a window around its materialisation:
    from the chunk that lets the window fire, before the assembler
    consolidates (a further window of the same firing: from its slice), to
    the padded arrays (args ``n``, ``bucket``); the chunks appended before
    are outside it, and a firing with no window has none. It holds no leaf;
    inside it lie the passes' own phase spans ``soa.consolidate`` (the
    assembler's firing) and ``point_lanes``' three: ``soa.center`` (the
    columns centred in float64 blocks, cast on assignment into ``xy``),
    ``soa.cells`` (the cells, a block at a time, into ``cell``), ``soa.pad``
    (the tails, ``valid``, ``oid``). The clock reading it opens at goes on
    with the window (``win.t0_ns``), for the operator's parent span to open
    at the same instant.

    ``counted=False`` leaves each window's ``counters.record_window`` to the
    caller: a stream materialised on a producer thread, whose consumer keeps
    the op counters (which take no lock) on its own thread.
    """
    from spatialflink_tpu.streams.soa import SoaWindowAssembler

    from spatialflink_tpu.ops.counters import counters

    asm = SoaWindowAssembler(
        conf.window_size_ms, conf.slide_step_ms,
        ooo_ms=conf.allowed_lateness_ms,
    )
    scratch = lane_scratch()

    def batch(win):
        if counted and counters.enabled:
            # Throughput meter for the SoA path (Point.java:237-253 analog);
            # candidate tallies come from the operator (it owns the flags).
            counters.record_window(win.count, 0, 0)
        return (win, *point_lanes(grid, win.arrays["x"], win.arrays["y"],
                                  win.arrays.get("oid"), dtype, scratch))

    def fired(fire):
        """One firing's windows, padded. Under ``span`` each is timed from
        the firing's start (or the hand-back of the window before) to its
        padded arrays; a firing that gives no window emits nothing."""
        if span is None or not telemetry.enabled:
            yield from map(batch, fire())
            return
        t0 = time.perf_counter_ns()
        for win in fire():
            win.t0_ns = t0
            item = batch(win)
            telemetry.emit_span(span, t0, time.perf_counter_ns() - t0,
                                n=win.count, bucket=len(item[2]))
            yield item
            t0 = time.perf_counter_ns()

    for c in chunks:
        if asm.take(c):
            yield from fired(asm.fire)
    yield from fired(asm.flush)


@functools.lru_cache(maxsize=None)
def jitted(fn: Callable, *static: str):
    """Module-level jit cache so every operator instance reuses programs.

    Wrapped with the telemetry recompile detector (telemetry.py): each
    distinct abstract-shape signature entering a kernel is one XLA compile
    (seconds, plus a device round trip), so bucket-size churn surfaces as
    recorded compile events / a RecompileWarning instead of silent
    slowness. The same wrapper feeds the per-(kernel, signature) runtime
    table behind the run ledger (calls, dispatch wall-ns, first-call
    compile-inclusive latency, lazily captured XLA cost analysis —
    tools/sfprof reports it). Free when telemetry is disabled (one
    attribute check)."""
    # instrument_jit is also the `device.dispatch` chaos injection point
    # (faults.py) — placed there, not here, so mesh window programs and
    # bench steps that skip this cache are injectable too.
    jfn = jax.jit(fn, static_argnames=static) if static else jax.jit(fn)
    return instrument_jit(jfn, name=getattr(fn, "__name__", str(fn)))


def window_program(mesh, kernel, data_idx, n_args, topk=False, reduce=False,
                   **statics):
    """Mesh-or-single dispatch for a fused window kernel.

    With a mesh: the SAME kernel shard_mapped over the ``data`` axis
    (parallel/sharded.py — topk kernels pmin-reduce per-object minima,
    reduce kernels all-reduce their segment reduction, elementwise kernels
    stay sharded). Without: the module-cached jit. Every operator's
    mesh path goes through here so a new execution mode lands in one place.
    """
    if mesh is not None:
        from spatialflink_tpu.parallel.sharded import sharded_window_kernel

        prog = sharded_window_kernel(
            mesh, kernel, data_idx, n_args, topk=topk, reduce=reduce,
            **statics,
        )
        # Mesh programs jit inside sharded.py; track their signatures under
        # a distinct label so recompiles stay visible on this path too.
        return instrument_jit(
            prog, name=f"sharded:{getattr(kernel, '__name__', kernel)}"
        )
    return functools.partial(jitted(kernel, *sorted(statics)), **statics)
