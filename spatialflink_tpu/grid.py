"""UniformGrid — the spatial index (host-side control plane).

A ground-up re-design of the reference's ``GeoFlink/spatialIndices/
UniformGrid.java``. The reference materializes neighbor cells as HashSets of
string keys per query object and tests set membership per record
(UniformGrid.java:165-222, 368-426). Here the same layer math produces a
dense uint8 **flag table** of shape (n*n+1,) once per (query, radius); the
TPU kernels gather from it per point (ops/cells.py), which replaces the
per-record hash lookups with one vectorized gather.

Layer math (kept numerically identical to the reference):
  - guaranteed layers L_g = floor(r / (cell * sqrt(2)) - 1)
    (UniformGrid.getGuaranteedNeighboringLayers, UniformGrid.java:428-439);
    -1 → no guaranteed cells, 0 → only the query cell, n → n layers.
  - candidate layers L_c = ceil(r / cell)
    (UniformGrid.getCandidateNeighboringLayers, UniformGrid.java:441-445);
    candidate set = L_c-square minus the guaranteed set
    (getCandidateNeighboringCells, UniformGrid.java:368-426).
"""

from __future__ import annotations

import math
from typing import Iterable, Sequence, Tuple

import numpy as np

FLAG_NONE = np.uint8(0)
FLAG_CANDIDATE = np.uint8(1)
FLAG_GUARANTEED = np.uint8(2)

_CELL_INDEX_STR_LENGTH = 5  # key format parity: UniformGrid.java CELLINDEXSTRLENGTH


class UniformGrid:
    """Square uniform grid over a bounding box.

    Two constructors, matching the reference:
      - ``UniformGrid.from_cell_length(cell_length, ...)`` — cell size in
        coordinate units (UniformGrid.java:47-73, incl. the square-grid bbox
        adjustment and cell-length recomputation);
      - ``UniformGrid(n_partitions, ...)`` — cell count per side
        (UniformGrid.java:75-85).
    """

    def __init__(
        self,
        num_partitions: int,
        min_x: float,
        max_x: float,
        min_y: float,
        max_y: float,
    ):
        if num_partitions < 1:
            raise ValueError("num_partitions must be >= 1")
        self.min_x = float(min_x)
        self.max_x = float(max_x)
        self.min_y = float(min_y)
        self.max_y = float(max_y)
        self.n = int(num_partitions)
        self.cell_length = (self.max_x - self.min_x) / self.n

    @classmethod
    def from_cell_length(
        cls, cell_length: float, min_x: float, max_x: float, min_y: float, max_y: float
    ) -> "UniformGrid":
        # Square-grid adjustment: stretch the shorter axis symmetrically so
        # both spans are equal (UniformGrid.adjustCoordinatesForSquareGrid,
        # UniformGrid.java:115-135).
        x_diff = max_x - min_x
        y_diff = max_y - min_y
        if x_diff > y_diff:
            pad = (x_diff - y_diff) / 2
            min_y, max_y = min_y - pad, max_y + pad
        elif y_diff > x_diff:
            pad = (y_diff - x_diff) / 2
            min_x, max_x = min_x - pad, max_x + pad
        n = max(1, math.ceil((max_x - min_x) / cell_length))
        return cls(n, min_x, max_x, min_y, max_y)

    # ---- cell id arithmetic -------------------------------------------------

    @property
    def num_cells(self) -> int:
        return self.n * self.n

    def cell_indices(self, x: float, y: float) -> Tuple[int, int]:
        """Floor indices, unclamped (HelperClass.java:104-116)."""
        xi = math.floor((x - self.min_x) / self.cell_length)
        yi = math.floor((y - self.min_y) / self.cell_length)
        return xi, yi

    def flat_cell(self, x: float, y: float) -> int:
        """Flat int id; num_cells means out-of-grid."""
        xi, yi = self.cell_indices(x, y)
        if 0 <= xi < self.n and 0 <= yi < self.n:
            return xi * self.n + yi
        return self.num_cells

    def cell_xy_indices_np(self, xy: np.ndarray) -> np.ndarray:
        """(N, 2) int32 unclamped (xi, yi) floor indices — the join kernel's
        left-side input (out-of-grid neighbors are masked device-side)."""
        xi = np.floor((xy[..., 0] - self.min_x) / self.cell_length).astype(np.int32)
        yi = np.floor((xy[..., 1] - self.min_y) / self.cell_length).astype(np.int32)
        return np.stack([xi, yi], axis=-1)

    def assign_cells_np(self, xy: np.ndarray) -> np.ndarray:
        """Vectorized host-side cell assignment, same contract as ops.assign_cells."""
        xi = np.floor((xy[..., 0] - self.min_x) / self.cell_length).astype(np.int64)
        yi = np.floor((xy[..., 1] - self.min_y) / self.cell_length).astype(np.int64)
        inside = (xi >= 0) & (xi < self.n) & (yi >= 0) & (yi < self.n)
        return np.where(inside, xi * self.n + yi, self.num_cells).astype(np.int32)

    def assign_cells_into(self, x: np.ndarray, y: np.ndarray, out: np.ndarray,
                          work: np.ndarray, mask: np.ndarray) -> None:
        """``out[:] = assign_cells_np`` of the points ``(x, y)``, on
        scratch the caller keeps: the same float64 floor arithmetic, so the
        same cells bit for bit, with no whole-array temporary.

        ``x`` and ``y`` are one block of two columns (any real dtype, any
        stride; upcast to float64 before anything else), ``out`` the int32
        lane that takes their cells, ``work`` float64 and ``mask`` bool,
        both ``(2, m)`` with ``m >= len(x)``, overwritten. The floor indices
        stay float64 (whole numbers, exact far beyond any grid), so the
        inside test is ``(fx >= 0) & (fx < n) & (fy >= 0) & (fy < n)`` in
        this form: a NaN fails every comparison and falls outside, as it
        does through ``assign_cells_np``'s int64 cast.
        """
        m = len(x)
        fx, fy = work[0, :m], work[1, :m]
        inside, t = mask[0, :m], mask[1, :m]
        # dtype= makes the ufunc compute in float64 whatever the column
        # holds (NumPy 2 would subtract a float32 column in float32).
        np.subtract(x, self.min_x, out=fx, dtype=np.float64)
        np.divide(fx, self.cell_length, out=fx)
        np.floor(fx, out=fx)
        np.subtract(y, self.min_y, out=fy, dtype=np.float64)
        np.divide(fy, self.cell_length, out=fy)
        np.floor(fy, out=fy)
        np.greater_equal(fx, 0, out=inside)
        inside &= np.less(fx, self.n, out=t)
        inside &= np.greater_equal(fy, 0, out=t)
        inside &= np.less(fy, self.n, out=t)
        with np.errstate(invalid="ignore"):  # inf − inf, of a point outside
            np.multiply(fx, self.n, out=fx)
            np.add(fx, fy, out=fx)
        np.logical_not(inside, out=inside)
        np.putmask(fx, inside, self.num_cells)
        out[...] = fx  # whole numbers below 2**31: the cast is exact

    def cell_name(self, flat: int) -> str:
        """String key parity with the reference ("xxxxxyyyyy", 5+5 digits)."""
        xi, yi = divmod(int(flat), self.n)
        w = _CELL_INDEX_STR_LENGTH
        return f"{xi:0{w}d}{yi:0{w}d}"

    def cell_from_name(self, name: str) -> int:
        w = _CELL_INDEX_STR_LENGTH
        return int(name[:w]) * self.n + int(name[w:])

    def bbox_cells(
        self, min_x: float, min_y: float, max_x: float, max_y: float
    ) -> np.ndarray:
        """All flat cells overlapped by a bbox, clipped to the grid.

        The reference's bbox→gridIDsSet assignment for Polygon/LineString
        (HelperClass.assignGridCellID(bBox,...), HelperClass.java:122-143).
        """
        x1, y1 = self.cell_indices(min_x, min_y)
        x2, y2 = self.cell_indices(max_x, max_y)
        x1, x2 = max(0, x1), min(self.n - 1, x2)
        y1, y2 = max(0, y1), min(self.n - 1, y2)
        if x1 > x2 or y1 > y2:
            return np.empty((0,), np.int32)
        xs = np.arange(x1, x2 + 1, dtype=np.int32)
        ys = np.arange(y1, y2 + 1, dtype=np.int32)
        return (xs[:, None] * self.n + ys[None, :]).reshape(-1)

    # ---- neighbor-layer math ------------------------------------------------

    def guaranteed_layers(self, radius: float) -> int:
        """floor(r / (cell*sqrt(2)) - 1); UniformGrid.java:428-439."""
        return math.floor(radius / (self.cell_length * math.sqrt(2.0)) - 1)

    def candidate_layers(self, radius: float) -> int:
        """ceil(r / cell); UniformGrid.java:441-445."""
        return math.ceil(radius / self.cell_length)

    def _square(self, xi: int, yi: int, layers: int, out: np.ndarray, flag: np.uint8):
        """Mark the (2*layers+1)^2 square around (xi, yi), grid-clipped."""
        if layers < 0:
            return
        x1, x2 = max(0, xi - layers), min(self.n - 1, xi + layers)
        y1, y2 = max(0, yi - layers), min(self.n - 1, yi + layers)
        if x1 > x2 or y1 > y2:
            return
        view = out[: self.num_cells].reshape(self.n, self.n)
        view[x1 : x2 + 1, y1 : y2 + 1] = flag

    def neighbor_flags(
        self, radius: float, query_cells: Iterable[int]
    ) -> np.ndarray:
        """Build the (num_cells+1,) uint8 flag table for a query.

        ``query_cells``: flat ids of the cells the query object overlaps (one
        cell for a point; the gridIDsSet for polygons/linestrings —
        UniformGrid.java:194-222). Guaranteed flags win over candidate
        (the sets are mutually exclusive in the reference,
        UniformGrid.java:161-164).
        """
        flags = np.zeros(self.num_cells + 1, np.uint8)
        lg = self.guaranteed_layers(radius)
        lc = self.candidate_layers(radius)
        cells = [c for c in query_cells if 0 <= c < self.num_cells]
        # Candidate square first, then overwrite with guaranteed square.
        for c in cells:
            xi, yi = divmod(int(c), self.n)
            self._square(xi, yi, lc, flags, FLAG_CANDIDATE)
        for c in cells:
            xi, yi = divmod(int(c), self.n)
            self._square(xi, yi, lg, flags, FLAG_GUARANTEED)
        flags[self.num_cells] = FLAG_NONE
        return flags

    def neighbor_cells(
        self, radius: float, query_cells: Iterable[int], guaranteed_only: bool = False
    ) -> np.ndarray:
        """Flat ids of guaranteed (∪ candidate) neighbor cells."""
        flags = self.neighbor_flags(radius, query_cells)
        if guaranteed_only:
            return np.nonzero(flags == FLAG_GUARANTEED)[0].astype(np.int32)
        return np.nonzero(flags != FLAG_NONE)[0].astype(np.int32)

    def neighbor_offsets(self, radius: float) -> np.ndarray:
        """(K, 2) int32 (dx, dy) offsets covering the candidate square.

        Static per (grid, radius): used by the bucketed join kernel to gather
        a point's neighbor-cell buckets (replaces the reference's query-
        replication flatMap, JoinQuery.java:73-90).
        """
        lc = self.candidate_layers(radius)
        r = np.arange(-lc, lc + 1, dtype=np.int32)
        dx, dy = np.meshgrid(r, r, indexing="ij")
        return np.stack([dx.reshape(-1), dy.reshape(-1)], axis=1)

    def cell_layer(self, cell_a: int, cell_b: int) -> int:
        """Chebyshev ring number of cell_b around cell_a
        (HelperClass.getCellLayerWRTQueryCell, HelperClass.java:278-296)."""
        ax, ay = divmod(int(cell_a), self.n)
        bx, by = divmod(int(cell_b), self.n)
        return max(abs(ax - bx), abs(ay - by))

    def __repr__(self) -> str:
        return (
            f"UniformGrid(n={self.n}, cell={self.cell_length:.6g}, "
            f"bbox=({self.min_x}, {self.min_y})..({self.max_x}, {self.max_y}))"
        )
