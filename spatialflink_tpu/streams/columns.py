"""One fired window as columns — built once, read by every node.

The composed DAG (dag.py) hands every node the same fired window. Each
node used to walk ``win.events`` again, one attribute at a time; a
:class:`WindowColumns` is that walk done ONCE: the window's point-like
events (``GpsEvent``, ``Point``) as parallel numpy arrays in window
order, the few non-point events (``QServeCommand``, ``CheckInEvent``)
apart as the short list they are. The SNCB window cores
(sncb/queries.py), the StayTime kernel entry (apps/staytime.py) and the
qserve serving pass (qserve.py) compute from these arrays.

The view is derived state: it holds no more than the window does, is
never checkpointed, and an SoA ingest can fill the same arrays without
the nodes changing again (ROADMAP A3).
"""

from __future__ import annotations

from operator import attrgetter
from typing import Any, Hashable, List, Optional, Sequence

import numpy as np

from spatialflink_tpu.models.objects import Point
from spatialflink_tpu.sncb.common import GpsEvent
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
    every event that is not point-like, in window order."""

    def __init__(self, events: Sequence, pos: np.ndarray, ids: List[Hashable],
                 ts: np.ndarray, lon: np.ndarray, lat: np.ndarray,
                 gps_speed: np.ndarray, fa: np.ndarray, ff: np.ndarray,
                 is_gps: np.ndarray, others: List[Any], interner: Interner):
        self.events = events
        self.pos = pos
        self.ids = ids
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
            self._oid = self.interner.intern_many(self.ids)
        return self._oid

    def gps(self) -> "WindowColumns":
        """The ``GpsEvent`` rows only (this view itself when every row
        is one) — what Q1–Q5 and StayTime compute from."""
        if self._gps is None:
            if self.is_gps.all():
                self._gps = self
            else:
                m = self.is_gps
                sub = WindowColumns(
                    self.events, self.pos[m],
                    [i for i, g in zip(self.ids, m.tolist()) if g],
                    self.ts[m], self.lon[m], self.lat[m], self.gps_speed[m],
                    self.fa[m], self.ff[m], np.ones(int(m.sum()), bool),
                    self.others, self.interner,
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
