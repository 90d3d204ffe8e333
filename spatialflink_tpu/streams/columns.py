"""One fired window as columns — built once, read by every node.

The composed DAG (dag.py) hands every node the same fired window. Each
node used to walk ``win.events`` again, one attribute at a time; a
:class:`WindowColumns` is that walk done ONCE: the window's point-like
events (``GpsEvent``, ``Point``) as parallel numpy arrays in window
order, the few non-point events (``QServeCommand``, ``CheckInEvent``)
apart as the short list they are. The SNCB window cores
(sncb/queries.py), the StayTime kernel entry (apps/staytime.py) and the
qserve serving pass (qserve.py) compute from these arrays.

The view is derived state: it holds no more than the window does and is
never checkpointed.

The DAG's windows are also BUFFERED as columns
(:class:`ColumnarWindowAssembler`): one buffer per slide-aligned pane,
each event appended once as array elements, so an event object does not
outlive ``feed``. A fired window is then the concatenation of its
panes' columns (:class:`PaneEvents`, :meth:`WindowColumns.from_panes`),
the unit checkpoint pickles arrays, and the cyclic GC has no buffered
objects to walk. :meth:`WindowColumns.from_events` stays for callers
that hold a plain list (tests, the standalone qserve entry).
"""

from __future__ import annotations

import math
import operator
from array import array
from collections import Counter, deque
from collections.abc import Sequence as SequenceABC
from operator import attrgetter
from typing import Any, Dict, Hashable, List, Optional, Sequence, Tuple

import numpy as np

from spatialflink_tpu import overload, slo
from spatialflink_tpu.faults import faults
from spatialflink_tpu.models.objects import Point
from spatialflink_tpu.sncb.common import GpsEvent
from spatialflink_tpu.streams.windows import SlidingEventTimeWindows, WindowBatch
from spatialflink_tpu.telemetry import telemetry
from spatialflink_tpu.utils.crs import wgs84_to_epsg25831
from spatialflink_tpu.utils.interning import Interner

_OTHER, _GPS, _POINT = 0, 1, 2


def _kind(cls) -> int:
    if issubclass(cls, GpsEvent):
        return _GPS
    if issubclass(cls, Point):
        return _POINT
    return _OTHER


def _column(objs: Sequence, attr: str, dtype) -> np.ndarray:
    return np.fromiter(map(attrgetter(attr), objs), dtype, len(objs))


def _optional(objs: Sequence, attr: str) -> np.ndarray:
    """float64 column of a field that may be ``None``: NaN where it is."""
    vals = list(map(attrgetter(attr), objs))
    if vals.count(None) == len(vals):
        return np.full(len(vals), np.nan)
    # np.array maps None → nan for a float dtype (fromiter would raise).
    return np.array(vals, np.float64)


class WindowColumns:
    """Columns of one window's point-like events, in window order.

    Row ``i`` is ``events[pos[i]]``. ``ts`` int64; ``lon`` / ``lat``
    float64 (a ``Point``'s ``x`` / ``y``); ``gps_speed`` / ``fa`` /
    ``ff`` float64 with NaN where the event carries ``None`` (a
    ``Point`` carries none of them), so a NaN reads as "absent" — which
    is how the per-event ``variation`` treated it already; ``is_gps``
    marks the ``GpsEvent`` rows. ``oid`` (int32) interns the rows' ids
    into ``interner`` on first use, in window order — the DAG forces it
    at build time, the standalone qserve entry after its commands have
    applied, so dense ids keep the order they always had. ``others`` is
    every event that is not point-like, in window order. ``ids`` may be
    ``None`` when ``id_codes`` gives the same as ``(names, code)`` —
    row ``i``'s id is ``names[code[i]]``, ``names`` in order of first
    appearance — which is how a window built from panes carries them:
    the per-row list is then made only if something reads ``ids``.
    ``source`` says how the view was made (``"events"`` / ``"panes"``),
    ``reordered`` that its panes had to be sorted back into arrival
    order."""

    def __init__(self, events: Sequence, pos: np.ndarray,
                 ids: Optional[List[Hashable]],
                 ts: np.ndarray, lon: np.ndarray, lat: np.ndarray,
                 gps_speed: np.ndarray, fa: np.ndarray, ff: np.ndarray,
                 is_gps: np.ndarray, others: List[Any], interner: Interner,
                 id_codes: Optional[Tuple[List[Hashable], np.ndarray]] = None,
                 source: str = "events", reordered: bool = False):
        self.events = events
        self.pos = pos
        self._ids = ids
        self._id_codes = id_codes
        self.source = source
        self.reordered = reordered
        self.ts = ts
        self.lon = lon
        self.lat = lat
        self.gps_speed = gps_speed
        self.fa = fa
        self.ff = ff
        self.is_gps = is_gps
        self.others = others
        self.interner = interner
        self._oid: Optional[np.ndarray] = None
        self._gps: Optional["WindowColumns"] = None
        self._metric: Optional[np.ndarray] = None

    def __len__(self) -> int:
        return len(self.ts)

    @property
    def ids(self) -> List[Hashable]:
        """The rows' ids, one per row."""
        if self._ids is None:
            names, code = self._id_codes
            self._ids = [names[c] for c in code.tolist()]
        return self._ids

    @classmethod
    def from_panes(cls, events: "PaneEvents",
                   interner: Optional[Interner] = None) -> "WindowColumns":
        """The view of a window the columnar assembler fired: its panes'
        columns concatenated (and, where panes interleaved in arrival,
        sorted back into arrival order) — no event object is touched."""
        v = events.resolved()
        return cls(events, v.pos, None, v.ts, v.lon, v.lat, v.gps_speed,
                   v.fa, v.ff, v.is_gps, v.others,
                   interner if interner is not None else Interner(),
                   id_codes=(v.names, v.code), source="panes",
                   reordered=v.reordered)

    @classmethod
    def from_events(cls, events: Sequence,
                    interner: Optional[Interner] = None) -> "WindowColumns":
        """The one pass over a window's events. ``interner`` defaults to
        a private table (callers that only group by device need no
        shared ids)."""
        interner = interner if interner is not None else Interner()
        kinds = {t: _kind(t) for t in set(map(type, events))}
        if all(k == _GPS for k in kinds.values()):
            # The served path's window: GpsEvents only, no partition.
            return cls(events, np.arange(len(events), dtype=np.int64),
                       *cls._gps_columns(events),
                       np.ones(len(events), bool), [], interner)
        code = np.fromiter((kinds[type(e)] for e in events), np.int8,
                           len(events))
        others = [events[i] for i in np.flatnonzero(code == _OTHER).tolist()]
        pos = np.flatnonzero(code != _OTHER)
        is_gps = code[pos] == _GPS
        gps = [events[i] for i in pos[is_gps].tolist()]
        pts = [events[i] for i in pos[~is_gps].tolist()]
        n = len(pos)
        ids = np.empty(n, object)
        cols = [np.empty(n, np.int64), np.empty(n, np.float64),
                np.empty(n, np.float64)] + [np.full(n, np.nan)
                                            for _ in range(3)]
        g_ids, *g_cols = cls._gps_columns(gps)
        ids[is_gps] = g_ids
        for col, g in zip(cols, g_cols):
            col[is_gps] = g
        ids[~is_gps] = list(map(attrgetter("obj_id"), pts))
        for col, attr in zip(cols, ("timestamp", "x", "y")):
            col[~is_gps] = _column(pts, attr, col.dtype)
        return cls(events, pos, ids.tolist(), *cols, is_gps, others,
                   interner)

    @staticmethod
    def _gps_columns(gps: Sequence[GpsEvent]):
        """(ids, ts, lon, lat, gps_speed, fa, ff) of GpsEvents: one
        C-level attribute sweep per column, no per-event object made."""
        return (
            list(map(attrgetter("device_id"), gps)),
            _column(gps, "ts", np.int64),
            _column(gps, "lon", np.float64),
            _column(gps, "lat", np.float64),
            _optional(gps, "gps_speed"),
            _optional(gps, "fa"),
            _optional(gps, "ff"),
        )

    @property
    def oid(self) -> np.ndarray:
        """Dense int32 ids of the rows (interned on first use)."""
        if self._oid is None:
            if self._id_codes is not None:
                # names are in first-appearance order: interning them in
                # that order assigns what interning every row would.
                names, code = self._id_codes
                self._oid = self.interner.intern_many(names)[code]
            else:
                self._oid = self.interner.intern_many(self._ids)
        return self._oid

    def gps(self) -> "WindowColumns":
        """The ``GpsEvent`` rows only (this view itself when every row
        is one) — what Q1–Q5 and StayTime compute from."""
        if self._gps is None:
            if self.is_gps.all():
                self._gps = self
            else:
                m = self.is_gps
                if self._id_codes is not None:
                    ids, codes = None, (self._id_codes[0],
                                        self._id_codes[1][m])
                else:
                    ids, codes = [i for i, g in zip(self._ids, m.tolist())
                                  if g], None
                sub = WindowColumns(
                    self.events, self.pos[m], ids,
                    self.ts[m], self.lon[m], self.lat[m], self.gps_speed[m],
                    self.fa[m], self.ff[m], np.ones(int(m.sum()), bool),
                    self.others, self.interner, id_codes=codes,
                    source=self.source, reordered=self.reordered,
                )
                sub._oid = self.oid[m]
                sub._gps = sub
                self._gps = sub
        return self._gps

    def lonlat(self) -> np.ndarray:
        """(N, 2) float64 WGS84 coordinates."""
        return np.stack([self.lon, self.lat], axis=1)

    def metric_xy(self) -> np.ndarray:
        """(N, 2) EPSG:25831 coordinates — computed on first use and
        kept: once a window, however many zone nodes read it."""
        if self._metric is None:
            east, north = wgs84_to_epsg25831(self.lon, self.lat)
            self._metric = np.stack([east, north], axis=1)
        return self._metric

    def by_device(self, rows: np.ndarray, by_ts: bool = False):
        """Group ``rows`` (row indices, ascending) per device:
        ``(grouped, starts, ends, by_name)`` where
        ``grouped[starts[k]:ends[k]]`` are group ``k``'s rows in window
        order — or, with ``by_ts``, in timestamp order with ties in
        window order (both sorts are stable). Groups come in dense-id
        order; ``by_name`` lists ``(device_id, k)`` sorted by device id
        — the order ``sorted(groups)`` gave the result records."""
        oid = self.oid[rows]
        if by_ts:
            order = np.lexsort((self.ts[rows], oid))
        else:
            order = np.argsort(oid, kind="stable")
        uniq, starts, counts = np.unique(oid[order], return_index=True,
                                         return_counts=True)
        names = self.interner.decode(uniq.tolist())
        return (rows[order], starts, starts + counts,
                sorted(zip(names, range(len(names)))))


# ---------------------------------------------------------------------------
# Windows buffered as columns per pane


class _Pane:
    """One pane's buffered events: a row per point-like event, appended
    once, as ``array.array`` columns (untracked by the cyclic GC, one
    buffer each to pickle). ``code`` indexes ``names``, the pane's ids
    in order of first appearance. The columns after ``code`` exist only
    from the first row that needs one (``None`` = every row so far is a
    plain ``GpsEvent`` without that field): ``gps_speed`` / ``fa`` /
    ``ff`` / ``ingest`` hold NaN for ``None``, ``point`` flags the
    ``Point`` rows. ``kept`` holds the rows whose object is needed to
    give the event back as it came (a subclass, a field that IS NaN).
    ``others`` are the non-point events with their arrival index in the
    pane (rows and others counted together). ``runs`` marks arrival:
    ``(index, seq)`` says the events from ``index`` on arrived
    consecutively from assembler-wide sequence number ``seq``."""

    __slots__ = ("start", "end", "ts", "lon", "lat", "code", "codes", "names",
                 "wide", "gps_speed", "fa", "ff", "ingest", "point", "kept",
                 "others", "runs")

    #: (attribute, typecode) of every column, the optional ones last.
    COLUMNS = (("ts", "q"), ("lon", "d"), ("lat", "d"), ("code", "i"),
               ("gps_speed", "d"), ("fa", "d"), ("ff", "d"), ("ingest", "d"),
               ("point", "b"))

    def __init__(self, start: int, end: int):
        self.start, self.end = start, end
        self.ts = array("q")
        self.lon = array("d")
        self.lat = array("d")
        self.code = array("i")
        self.codes: Dict[Hashable, int] = {}
        self.names: List[Hashable] = []
        self.wide = False  # some optional column exists
        self.gps_speed = self.fa = self.ff = self.ingest = self.point = None
        self.kept: Dict[int, Any] = {}
        self.others: List[Tuple[int, Any]] = []
        self.runs: List[Tuple[int, int]] = []

    def count(self) -> int:
        return len(self.ts) + len(self.others)

    def add_row(self, event, ts: int, dev, lon, lat,
                gps_speed=None, fa=None, ff=None, ingest=None,
                is_point: bool = False, keep: bool = False) -> None:
        """Append one row (the general form; ``feed`` inlines the plain
        ``GpsEvent`` case)."""
        n = len(self.ts)
        self.ts.append(ts)
        self.lon.append(lon)
        self.lat.append(lat)
        code = self.codes.get(dev)
        if code is None:
            code = self.codes[dev] = len(self.names)
            self.names.append(dev)
        self.code.append(code)
        for attr, v in (("gps_speed", gps_speed), ("fa", fa), ("ff", ff),
                        ("ingest", ingest)):
            col = getattr(self, attr)
            if v is None:
                if col is not None:
                    col.append(math.nan)
                continue
            if col is None:
                col = array("d", [math.nan]) * n
                setattr(self, attr, col)
                self.wide = True
            col.append(v)
            if v != v:  # a NaN that is a value, not an absence
                keep = True
        if is_point and self.point is None:
            self.point = array("b", [0]) * n
            self.wide = True
        if self.point is not None:
            self.point.append(1 if is_point else 0)
        if keep:
            self.kept[n] = event

    def state(self) -> Dict[str, Any]:
        out = {a: getattr(self, a) for a, _ in self.COLUMNS}
        out.update(start=self.start, names=self.names, kept=self.kept,
                   others=self.others, runs=self.runs)
        return out

    @classmethod
    def restored(cls, state: Dict[str, Any], pane_ms: int) -> "_Pane":
        pane = cls(int(state["start"]), int(state["start"]) + pane_ms)
        for attr, code in cls.COLUMNS:
            col = state[attr]
            setattr(pane, attr, None if col is None else array(code, col))
        pane.names = list(state["names"])
        pane.codes = {k: i for i, k in enumerate(pane.names)}
        pane.wide = any(state[a] is not None for a, _ in cls.COLUMNS[4:])
        pane.kept = dict(state["kept"])
        pane.others = [tuple(o) for o in state["others"]]
        pane.runs = [tuple(r) for r in state["runs"]]
        return pane


class _Resolved:
    """A fired window's panes put together (see PaneEvents.resolved)."""

    __slots__ = ("ts", "lon", "lat", "gps_speed", "fa", "ff", "ingest",
                 "is_gps", "names", "code", "pos", "others", "other_at",
                 "kept", "reordered")


def _pane_seq(pane: _Pane, n: int) -> np.ndarray:
    """Arrival sequence numbers of the pane's first ``n`` events."""
    runs = [r for r in pane.runs if r[0] < n]
    at = np.array([r[0] for r in runs], np.int64)
    seq = np.array([r[1] for r in runs], np.int64)
    return (np.repeat(seq - at, np.diff(np.append(at, n)))
            + np.arange(n, dtype=np.int64))


class PaneEvents(SequenceABC):
    """The events of a window fired from panes, as a sequence in arrival
    order. It holds no event object: ``cuts`` names the window's panes
    and how far each had been filled when the window fired (panes are
    append-only and shared with the windows that follow). ``len`` is
    free; the columns are put together on first need
    (:meth:`resolved`); ``events[i]`` builds the ``GpsEvent`` /
    ``Point`` of that position from its row (non-point events and the
    few kept objects come back as themselves); iterating builds them
    all, once."""

    def __init__(self, cuts: List[Tuple[_Pane, int, int, int]]):
        #: (pane, rows, others, names) at the fire, in pane order
        self._cuts = cuts
        self._n = sum(rows + others for _, rows, others, _ in cuts)
        self._resolved: Optional[_Resolved] = None
        self._list: Optional[List[Any]] = None

    def __len__(self) -> int:
        return self._n

    def _in_arrival_order(self) -> bool:
        last = -1
        for pane, rows, others, _ in self._cuts:
            n = rows + others
            runs = [r for r in pane.runs if r[0] < n]
            if runs[0][1] <= last:
                return False
            last = runs[-1][1] + (n - runs[-1][0]) - 1
        return True

    def resolved(self) -> _Resolved:
        """The window's columns: the panes' concatenated; if the panes
        interleaved in arrival (an out-of-order event inside the bound),
        one sort by arrival sequence puts rows and non-point events back
        into the order a per-window buffer would have had."""
        if self._resolved is not None:
            return self._resolved
        cuts = self._cuts
        v = _Resolved()

        def column(attr, dtype, absent=None):
            parts = []
            for pane, rows, _, _ in cuts:
                col = getattr(pane, attr)
                if col is not None and rows:
                    parts.append(np.frombuffer(col, dtype, rows))
                elif rows:
                    parts.append(np.full(rows, absent, dtype))
            # concatenate copies: no view of a pane's buffer is left
            # behind (an exported array.array cannot be appended to).
            return np.concatenate(parts) if parts else np.empty(0, dtype)

        n_rows = sum(rows for _, rows, _, _ in cuts)
        v.ts = column("ts", np.int64)
        v.lon = column("lon", np.float64)
        v.lat = column("lat", np.float64)
        nan = None
        for attr in ("gps_speed", "fa", "ff", "ingest"):
            if any(getattr(p, attr) is not None for p, *_ in cuts):
                col = column(attr, np.float64, np.nan)
            else:
                if nan is None:
                    nan = np.full(n_rows, np.nan)
                col = nan
            setattr(v, attr, col)
        if any(p.point is not None for p, *_ in cuts):
            v.is_gps = column("point", np.int8, 0) == 0
        else:
            v.is_gps = np.ones(n_rows, bool)
        # pane codes → window codes, names in order of first appearance
        names: List[Hashable] = []
        index: Dict[Hashable, int] = {}
        parts = []
        for pane, rows, _, n_names in cuts:
            lut = np.empty(n_names, np.int32)
            for c, name in enumerate(pane.names[:n_names]):
                w = index.get(name)
                if w is None:
                    w = index[name] = len(names)
                    names.append(name)
                lut[c] = w
            if rows:
                parts.append(lut[np.frombuffer(pane.code, np.int32, rows)])
        code = np.concatenate(parts) if parts else np.empty(0, np.int32)
        kept: Dict[int, Any] = {}
        others: List[Any] = []
        is_row = None
        if any(n_others for _, _, n_others, _ in cuts):
            marks = []
            for pane, rows, n_others, _ in cuts:
                m = np.ones(rows + n_others, bool)
                if n_others:
                    m[[j for j, _ in pane.others[:n_others]]] = False
                marks.append(m)
                others.extend(o for _, o in pane.others[:n_others])
            is_row = np.concatenate(marks)
        base = 0
        for pane, rows, _, _ in cuts:
            for r, obj in pane.kept.items():
                if r < rows:
                    kept[base + r] = obj
            base += rows
        v.reordered = not self._in_arrival_order()
        if v.reordered:
            order = np.argsort(np.concatenate(
                [_pane_seq(p, rows + n_o) for p, rows, n_o, _ in cuts]))
            if is_row is None:
                perm = order
            else:
                row_of = np.cumsum(is_row) - 1
                other_of = np.cumsum(~is_row) - 1
                is_row = is_row[order]
                perm = row_of[order][is_row]
                others = [others[k]
                          for k in other_of[order][~is_row].tolist()]
            for attr in ("ts", "lon", "lat", "gps_speed", "fa", "ff",
                         "ingest", "is_gps"):
                setattr(v, attr, getattr(v, attr)[perm])
            code = code[perm]
            if kept:
                new_row = np.empty(n_rows, np.int64)
                new_row[perm] = np.arange(n_rows)
                kept = {int(new_row[r]): obj for r, obj in kept.items()}
            # names back into order of first appearance over the window
            uniq, first = np.unique(code, return_index=True)
            by_first = uniq[np.argsort(first)]
            rank = np.empty(len(names), np.int32)
            rank[by_first] = np.arange(len(by_first), dtype=np.int32)
            code = rank[code]
            names = [names[c] for c in by_first.tolist()]
        v.names, v.code, v.kept, v.others = names, code, kept, others
        if is_row is None:
            v.pos = np.arange(n_rows, dtype=np.int64)
            v.other_at = {}
        else:
            v.pos = np.flatnonzero(is_row)
            v.other_at = dict(zip(np.flatnonzero(~is_row).tolist(), others))
        self._resolved = v
        return v

    @staticmethod
    def _build(v: _Resolved, rows: slice) -> List[Any]:
        """The events of ``rows``, each made from its row (``None`` back
        where the column holds NaN) — or the kept object itself."""
        def opt(col):
            return [None if x != x else x for x in col[rows].tolist()]

        out = [
            GpsEvent(d, x, y, t, s, a, f) if g else Point(d, t, w, x, y)
            for d, x, y, t, s, a, f, w, g in zip(
                [v.names[c] for c in v.code[rows].tolist()],
                v.lon[rows].tolist(), v.lat[rows].tolist(),
                v.ts[rows].tolist(), opt(v.gps_speed), opt(v.fa), opt(v.ff),
                opt(v.ingest), v.is_gps[rows].tolist())
        ]
        first = rows.start or 0
        for row, obj in v.kept.items():
            if first <= row < first + len(out):
                out[row - first] = obj
        return out

    def __getitem__(self, i):
        if isinstance(i, slice):
            return [self[k] for k in range(*i.indices(self._n))]
        i = operator.index(i)
        if i < 0:
            i += self._n
        if not 0 <= i < self._n:
            raise IndexError("window event index out of range")
        if self._list is not None:
            return self._list[i]
        v = self.resolved()
        if not v.other_at:
            return self._build(v, slice(i, i + 1))[0]
        obj = v.other_at.get(i)
        if obj is not None:
            return obj
        row = int(np.searchsorted(v.pos, i))
        return self._build(v, slice(row, row + 1))[0]

    def __iter__(self):
        if self._list is None:
            v = self.resolved()
            events = self._build(v, slice(None))
            if v.other_at:
                rows, events = events, [None] * self._n
                for p, obj in zip(v.pos.tolist(), rows):
                    events[p] = obj
                for p, obj in v.other_at.items():
                    events[p] = obj
            self._list = events
        return iter(self._list)


def _arrival_order(buffers: List[List[Any]]) -> List[Any]:
    """Every event of per-window buffers once, in an order that agrees
    with each buffer's own (each is a subsequence of the one arrival
    order; events that share no window may come out either way round,
    which no window can tell)."""
    member = Counter(id(e) for buf in buffers for e in buf)
    at = [0] * len(buffers)
    heads: Dict[int, List[int]] = {}
    ready = deque()

    def expose(b):
        if at[b] < len(buffers[b]):
            e = buffers[b][at[b]]
            held = heads.setdefault(id(e), [])
            held.append(b)
            if len(held) == member[id(e)]:
                ready.append(e)

    for b in range(len(buffers)):
        expose(b)
    out = []
    while ready:
        e = ready.popleft()
        out.append(e)
        for b in heads.pop(id(e)):
            at[b] += 1
            expose(b)
    return out


class ColumnarWindowAssembler:
    """The DAG's window assembler: :class:`WindowAssembler`'s event-time
    semantics with the buffers held as columns per pane.

    Watermark = max event time − ``max_out_of_orderness_ms``; an event
    lands if a window it belongs to is still open (else it counts as
    ``dropped_late``); a window fires, in order of ``end``, when the
    watermark passes its end; no allowed-lateness refires (the DAG's
    rule); the hooks sit where the generic assembler has them. What
    differs is the storage: one :class:`_Pane` per ``gcd(size, slide)``
    of event time, each event appended ONCE as array elements, so no
    event object outlives :meth:`feed` (the few non-point events apart).
    A fired :class:`WindowBatch` carries a :class:`PaneEvents`; firing
    work happens only when the watermark crosses a window end — one
    integer compare per event otherwise."""

    STATE_VERSION = 1

    def __init__(self, windows: SlidingEventTimeWindows,
                 max_out_of_orderness_ms: int = 0):
        self.windows = windows
        self.ooo = int(max_out_of_orderness_ms)
        self._size, self._slide = windows.size, windows.slide
        self._pane_ms = math.gcd(self._size, self._slide)
        self._panes: Dict[int, _Pane] = {}
        self._max_ts: Optional[int] = None
        self.dropped_late = 0
        # The smallest window end the watermark has not reached.
        self._next_end = self._end_after(self.watermark)
        # The pane of the run of consecutive arrivals being appended,
        # where in it the run began, and the sequence number it began at.
        self._cur: Optional[_Pane] = None
        self._cur_from = 0
        self._seq = 0

    @property
    def watermark(self) -> int:
        if self._max_ts is None:
            return -(2**62)
        return self._max_ts - self.ooo

    def _end_after(self, wm: int) -> int:
        return ((wm - self._size) // self._slide + 1) * self._slide \
            + self._size

    def feed(self, event) -> List[WindowBatch]:
        """Add one event; return any windows that fire as a result."""
        if faults.armed:  # chaos injection point (faults.py)
            faults.hit("window.feed")
        cls = type(event)
        ts = int(event.ts if cls is GpsEvent else event.timestamp)
        mx = self._max_ts
        if mx is None or ts > mx:
            self._max_ts = mx = ts
        wm = mx - self.ooo
        r = ts % self._slide
        # The last window the event belongs to is the one still open
        # longest: it lands iff that one is.
        landed = r < self._size and ts - r + self._size > wm
        if not landed:
            self.dropped_late += 1
            telemetry.record_late_drop()
        # Windows the watermark has passed fire BEFORE the event is
        # appended: it belongs to none of them.
        fired = self._fire(wm) if wm >= self._next_end else []
        if landed:
            pane = self._cur
            if pane is None or not pane.start <= ts < pane.end:
                pane = self._switch(ts - ts % self._pane_ms)
            if (cls is GpsEvent and not pane.wide
                    and event.gps_speed is None and event.fa is None
                    and event.ff is None):
                # The served path's row, inlined (add_row's plain case).
                pane.ts.append(ts)
                pane.lon.append(event.lon)
                pane.lat.append(event.lat)
                dev = event.device_id
                code = pane.codes.get(dev)
                if code is None:
                    code = pane.codes[dev] = len(pane.names)
                    pane.names.append(dev)
                pane.code.append(code)
            else:
                self._append(pane, event, ts, cls)
        return fired

    def _append(self, pane: _Pane, event, ts: int, cls: type) -> None:
        kind = _kind(cls)
        if kind == _GPS:
            pane.add_row(event, ts, event.device_id, event.lon, event.lat,
                         event.gps_speed, event.fa, event.ff,
                         keep=cls is not GpsEvent)
        elif kind == _POINT:
            pane.add_row(event, ts, event.obj_id, event.x, event.y,
                         ingest=event.ingestion_time, is_point=True,
                         keep=cls is not Point)
        else:
            pane.others.append((pane.count(), event))

    def _switch(self, key: int) -> _Pane:
        """The run of arrivals moves to pane ``key``: close the one
        being appended, mark where the new one starts."""
        cur = self._cur
        if cur is not None:
            self._seq += cur.count() - self._cur_from
        pane = self._panes.get(key)
        if pane is None:
            pane = self._panes[key] = _Pane(key, key + self._pane_ms)
        n = pane.count()
        runs = pane.runs
        if not runs or runs[-1][1] + (n - runs[-1][0]) != self._seq:
            runs.append((n, self._seq))
        self._cur, self._cur_from = pane, n
        return pane

    def _batch(self, start: int, end: int) -> Optional[WindowBatch]:
        cuts = [(p, len(p.ts), len(p.others), len(p.names))
                for s, p in sorted(self._panes.items())
                if start <= s < end and p.count()]
        if not cuts:
            return None
        return WindowBatch(start, end, PaneEvents(cuts))

    def _fire(self, wm: int) -> List[WindowBatch]:
        fired = []
        ends = sorted({
            spec.end for s in self._panes for spec in self.windows.assign(s)
            if self._next_end <= spec.end <= wm
        })
        for end in ends:
            batch = self._batch(end - self._size, end)
            if batch is None:
                continue
            fired.append(batch)
            n = len(batch.events)
            # Watermark lag, SLO and overload hooks: the generic
            # assembler's fire site (streams/windows.py:_advance).
            telemetry.record_watermark_lag(wm - end)
            slo.on_window_fired(n, lag_ms=wm - end)
            overload.on_window_fired(n, lag_ms=wm - end, end=end)
        # A pane goes with the last window that holds it.
        for s in [s for s in self._panes
                  if s - s % self._slide + self._size <= wm]:
            del self._panes[s]
        self._next_end = self._end_after(wm)
        return fired

    def flush(self) -> List[WindowBatch]:
        """End of stream: fire every remaining un-fired window."""
        wm = self.watermark
        ends = sorted({
            spec.end for s in self._panes for spec in self.windows.assign(s)
            if spec.end > wm
        })
        out = [b for b in (self._batch(end - self._size, end)
                           for end in ends) if b is not None]
        self._panes.clear()
        self._cur = None
        return out

    def stream(self, source):
        """Convenience: drive a whole source through the assembler."""
        for ev in source:
            yield from self.feed(ev)
        yield from self.flush()

    # -- checkpoint ------------------------------------------------------------

    def state(self) -> Dict[str, Any]:
        """Everything buffered, as the pane buffers themselves (live:
        pickle it, or restore from it, before the next feed)."""
        cur = self._cur
        return {
            "version": self.STATE_VERSION,
            "pane_ms": self._pane_ms,
            "panes": [p.state() for _, p in sorted(self._panes.items())],
            "max_ts": self._max_ts,
            "dropped_late": self.dropped_late,
            "seq": self._seq + (cur.count() - self._cur_from
                                if cur is not None else 0),
        }

    def restore(self, state: Dict[str, Any]) -> None:
        if "panes" not in state:
            self._restore_buffers(state)
            return
        if state["version"] != self.STATE_VERSION \
                or state["pane_ms"] != self._pane_ms:
            raise ValueError(
                f"assembler state (version {state['version']}, panes of "
                f"{state['pane_ms']} ms) does not fit this assembler "
                f"(version {self.STATE_VERSION}, panes of {self._pane_ms} "
                "ms): the window configuration changed since the checkpoint"
            )
        self._panes = {}
        for pane in state["panes"]:
            pane = _Pane.restored(pane, self._pane_ms)
            self._panes[pane.start] = pane
        self._cur, self._cur_from, self._seq = None, 0, state["seq"]
        self._set_clock(state["max_ts"], state["dropped_late"])

    def _set_clock(self, max_ts: Optional[int], dropped_late: int) -> None:
        # What has fired follows from the watermark: every window it has
        # passed has, so the clock is the only fired mark there is.
        self._max_ts = max_ts
        self.dropped_late = dropped_late
        self._next_end = self._end_after(self.watermark)

    def _restore_buffers(self, state: Dict[str, Any]) -> None:
        """A checkpoint in the generic assembler's form (lists of events
        per window): every buffered event goes into its pane, in an
        order that agrees with every window's own."""
        fired = {span for span, f in state["fired"] if f}
        buffers = [list(events) for span, events in state["buffers"]
                   if span not in fired]
        self._panes = {}
        self._cur, self._cur_from, self._seq = None, 0, 0
        for event in _arrival_order(buffers):
            ts = int(event.timestamp)
            self._append(self._switch(ts - ts % self._pane_ms), event, ts,
                         type(event))
        self._set_clock(state["max_ts"], state["dropped_late"])
