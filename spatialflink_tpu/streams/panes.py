"""Pane-decomposed sliding-window aggregation (vectorized).

The reference aggregates incrementally per record into every overlapping
window's accumulator — a 10s/10ms sliding window touches 1000 accumulators
per event (Flink AggregateFunction semantics, e.g. Q2_BrakeMonitor's
``SlidingEventTimeWindows.of(Time.seconds(10), Time.milliseconds(10))``).

Here the classic stream-slicing trick is vectorized end-to-end: events are
binned once into **panes** (one per slide step) with ``np.add.at``-style
scatter reductions, and every window aggregate is a rolling combine over
``size/slide`` consecutive panes — cumulative-sum differences for
sum/count/sumsq (O(events + panes × keys), overlap-independent), and
``sliding_window_view`` reductions for min/max (vectorized, but
O(panes × keys × overlap) arithmetic — still orders of magnitude cheaper
than per-record accumulator updates).

Requires ``size % slide == 0`` (true for every window config in the
reference: 10s/10ms, 10s/200ms, 3s/1s, 20s/2s, 45s/5s).
"""

from __future__ import annotations

from dataclasses import dataclass
from typing import Dict, Optional

import numpy as np
from numpy.lib.stride_tricks import sliding_window_view

from spatialflink_tpu.runtime import on_tpu


@dataclass
class PaneWindows:
    """Aggregates for every fired window.

    ``starts``: (W,) window start timestamps (ms). All per-key matrices are
    (W, K). A window fires iff it contains ≥1 event of any key (Flink
    semantics: windows materialize per element).
    """

    starts: np.ndarray
    count: np.ndarray  # events per (window, key)
    sums: Dict[str, np.ndarray]
    sumsqs: Dict[str, np.ndarray]
    mins: Dict[str, np.ndarray]
    maxs: Dict[str, np.ndarray]

    @property
    def ends(self) -> np.ndarray:
        return self.starts + self._size_ms

    _size_ms: int = 0


def sliding_aggregate(
    ts: np.ndarray,
    key: np.ndarray,
    num_keys: int,
    size_ms: int,
    slide_ms: int,
    sum_fields: Optional[Dict[str, np.ndarray]] = None,
    minmax_fields: Optional[Dict[str, np.ndarray]] = None,
    sumsq: bool = False,
    min_fields: Optional[Dict[str, np.ndarray]] = None,
    max_fields: Optional[Dict[str, np.ndarray]] = None,
) -> PaneWindows:
    """Aggregate a whole (bounded) stream over all sliding windows at once.

    ``ts``: (N,) event times ms; ``key``: (N,) dense int key per event
    (device id etc.); ``sum_fields``: named (N,) float arrays to sum per
    (window, key); ``minmax_fields``: tracked on both sides;
    ``min_fields``/``max_fields``: tracked on one side only (half the
    scatter + rolling work when the other side is unused).
    """
    if size_ms % slide_ms != 0:
        raise ValueError("size must be a multiple of slide for pane slicing")
    ppw = size_ms // slide_ms
    sum_fields = sum_fields or {}
    minmax_fields = minmax_fields or {}
    min_only = dict(min_fields or {})
    max_only = dict(max_fields or {})

    ts = np.asarray(ts, np.int64)
    key = np.asarray(key, np.int64)
    if len(ts) == 0:
        empty = np.zeros((0, num_keys))
        return PaneWindows(
            np.zeros(0, np.int64), empty.astype(np.int64),
            {k: empty.copy() for k in sum_fields},
            {k: empty.copy() for k in sum_fields} if sumsq else {},
            {k: empty.copy() for k in minmax_fields},
            {k: empty.copy() for k in minmax_fields},
            _size_ms=size_ms,
        )

    pane = np.floor_divide(ts, slide_ms)
    p_lo = int(pane.min())
    p_hi = int(pane.max())
    # Windows whose pane range [s, s+ppw) intersects [p_lo, p_hi]:
    # start panes from p_lo - ppw + 1 to p_hi.
    n_panes = p_hi - p_lo + 1
    n_starts = n_panes + ppw - 1
    flat = (pane - p_lo) * num_keys + key

    def scatter_sum(vals, dtype=np.float64):
        out = np.zeros(n_panes * num_keys, dtype)
        np.add.at(out, flat, vals)
        return out.reshape(n_panes, num_keys)

    pane_count = scatter_sum(np.ones(len(ts), np.int64), np.int64)
    pane_sums = {k: scatter_sum(np.asarray(v, float)) for k, v in sum_fields.items()}
    pane_sumsqs = (
        {k: scatter_sum(np.asarray(v, float) ** 2) for k, v in sum_fields.items()}
        if sumsq
        else {}
    )
    pane_mins = {}
    pane_maxs = {}
    for k, v in {**minmax_fields, **min_only}.items():
        v = np.asarray(v, float)
        mn = np.full(n_panes * num_keys, np.inf)
        np.minimum.at(mn, flat, v)
        pane_mins[k] = mn.reshape(n_panes, num_keys)
    for k, v in {**minmax_fields, **max_only}.items():
        v = np.asarray(v, float)
        mx = np.full(n_panes * num_keys, -np.inf)
        np.maximum.at(mx, flat, v)
        pane_maxs[k] = mx.reshape(n_panes, num_keys)

    # Pad ppw-1 panes on each side so every intersecting window start has a
    # full ppw-pane view.
    def pad(a, fill):
        padding = np.full((ppw - 1, num_keys), fill, a.dtype)
        return np.concatenate([padding, a, padding], axis=0)

    def rolling_sum(a):
        # Cumulative-sum difference: O(panes × keys) regardless of ppw.
        p = pad(a, 0)
        c = np.concatenate([np.zeros((1, num_keys), p.dtype), np.cumsum(p, axis=0)])
        return c[ppw:] - c[:-ppw]

    def rolling_min(a):
        return sliding_window_view(pad(a, np.inf), ppw, axis=0).min(axis=-1)

    def rolling_max(a):
        return sliding_window_view(pad(a, -np.inf), ppw, axis=0).max(axis=-1)

    w_count = rolling_sum(pane_count)
    # Keep only windows with ≥1 event (any key).
    alive = w_count.sum(axis=1) > 0
    starts = ((np.arange(n_starts) + p_lo - (ppw - 1)) * slide_ms)[alive]

    return PaneWindows(
        starts=starts.astype(np.int64),
        count=w_count[alive],
        sums={k: rolling_sum(v)[alive] for k, v in pane_sums.items()},
        sumsqs={k: rolling_sum(v)[alive] for k, v in pane_sumsqs.items()},
        mins={k: rolling_min(v)[alive] for k, v in pane_mins.items()},
        maxs={k: rolling_max(v)[alive] for k, v in pane_maxs.items()},
        _size_ms=size_ms,
    )


@dataclass
class TrajPaneWindows:
    """Per-(window, oid) trajectory stats for every fired sliding window.

    ``spatial``/``temporal``: (W, K) sums of consecutive-point distance /
    time within the window; ``count``: (W, K) points per trajectory.
    """

    starts: np.ndarray
    spatial: np.ndarray
    temporal: np.ndarray
    count: np.ndarray
    _size_ms: int = 0

    @property
    def ends(self) -> np.ndarray:
        return self.starts + self._size_ms


def _device_backend_preferred() -> bool:
    """True when the default JAX backend is a TPU — there the pane
    engine runs as one jitted program (ops/trajectory.py:
    traj_stats_pane_kernel); on CPU the native C++ single-pass engine
    wins (same gate as ops/join.pallas_join_supported)."""
    return on_tpu()


def _traj_stats_sliding_device(ts, xy, oid, num_oids, size_ms, slide_ms,
                               mesh=None):
    """Device pane engine wrapper: host (oid, ts) sort + pad, ONE jitted
    dispatch, host alive-filter. Bit-parity with the numpy path in f64
    (tests); f32 on non-x64 devices — coordinates are centred before
    the cast, and window sums are float32 prefix-sum differences, so
    the absolute error scales with a trajectory's cumulative length
    (PERF.md records the size measured on the chip). ``mesh``: shard
    trajectories (contiguous oid blocks) over the mesh's ``data`` axis
    (parallel/sharded.py:sharded_traj_stats_pane — bit-identical to
    single-device; ``num_oids`` must divide by the axis)."""
    import jax
    import jax.numpy as jnp

    from spatialflink_tpu.operators.base import jitted
    from spatialflink_tpu.ops.trajectory import traj_stats_pane_kernel
    from spatialflink_tpu.utils.padding import next_bucket

    ppw = size_ms // slide_ms
    ts = np.asarray(ts, np.int64)
    oid = np.asarray(oid, np.int64)
    xy = np.asarray(xy, np.float64)
    ts_sorted = len(ts) <= 1 or bool(np.all(ts[1:] >= ts[:-1]))
    if ts_sorted:
        order = np.argsort(oid, kind="stable")
    else:
        order = np.lexsort((ts, oid))
    t, o, p = ts[order], oid[order], xy[order]

    pane = np.floor_divide(t, slide_ms)
    p_lo = int(pane.min())
    n_panes = next_bucket(int(pane.max()) - p_lo + 1, minimum=8)
    # Rebase time HOST-side so epoch-ms values survive the int32 world
    # of a non-x64 device (raw ~1.7e12 ms would silently wrap; pane
    # arithmetic is shift-invariant). int32 covers ~24 days of stream
    # span — fail loudly beyond, don't wrap.
    t_rel = t - p_lo * slide_ms
    if len(t_rel) and int(t_rel.max()) >= np.iinfo(np.int32).max - slide_ms:
        raise ValueError(
            "stream span exceeds the device pane engine's int32 ms range "
            "(~24 days); use backend='native' or chunk the stream"
        )
    n = len(t)
    nb = next_bucket(n, minimum=8)
    pad = nb - n
    f_dtype = np.float64 if jax.config.jax_enable_x64 else np.float32
    if f_dtype is np.float32:
        # Segment lengths are differences of neighbouring coordinates:
        # degree-scale values (~116°) carry a float32 ulp of 7.6e-6°, so
        # centre on the host in float64 BEFORE the cast (lengths are
        # translation-invariant — the operators/base.py:center_coords
        # idiom). The f64 path stays bit-identical to the numpy engine.
        p = p - p.mean(axis=0)
    tp = np.concatenate([t_rel, np.full(pad, t_rel[-1], np.int64)]
                        ).astype(np.int32)
    op_ = np.concatenate([o, np.full(pad, num_oids - 1, np.int64)]
                         ).astype(np.int32)
    xp = np.concatenate([p[:, 0], np.zeros(pad)]).astype(f_dtype)
    yp = np.concatenate([p[:, 1], np.zeros(pad)]).astype(f_dtype)
    vp = np.concatenate([np.ones(n, bool), np.zeros(pad, bool)])

    if mesh is not None:
        from spatialflink_tpu.parallel.sharded import sharded_traj_stats_pane

        res = sharded_traj_stats_pane(
            mesh, tp, xp, yp, op_, vp,
            num_oids=num_oids, slide_ms=slide_ms, ppw=ppw, n_panes=n_panes,
        )
    else:
        kernel = jitted(
            traj_stats_pane_kernel, "num_oids", "slide_ms", "ppw", "n_panes",
        )
        res = kernel(
            jnp.asarray(tp), jnp.asarray(xp), jnp.asarray(yp),
            jnp.asarray(op_), jnp.asarray(vp),
            num_oids=num_oids, slide_ms=slide_ms, ppw=ppw, n_panes=n_panes,
        )
    w_d = np.asarray(res.spatial).T
    w_dt = np.asarray(res.temporal).T.astype(np.int64)  # int32-exact sums
    w_cnt = np.asarray(res.count).T
    n_starts = n_panes + ppw - 1
    alive = w_cnt.sum(axis=1) > 0
    starts = ((np.arange(n_starts) + p_lo - (ppw - 1)) * slide_ms)[alive]
    return TrajPaneWindows(
        starts=starts.astype(np.int64),
        spatial=w_d[alive],
        temporal=w_dt[alive],
        count=w_cnt[alive].astype(np.int64),
        _size_ms=size_ms,
    )


def traj_stats_sliding(
    ts: np.ndarray,
    xy: np.ndarray,
    oid: np.ndarray,
    num_oids: int,
    size_ms: int,
    slide_ms: int,
    backend: str = "auto",
    mesh=None,
) -> TrajPaneWindows:
    """Pane-decomposed sliding trajectory statistics — tStats through
    extreme-overlap windows (e.g. the reference's 10s/10ms configs) in
    O(events + panes × oids) instead of O(windows × window_size).

    Each consecutive same-trajectory segment is binned once into the pane
    of its LATER point; window sums are cumulative-sum differences over
    ``size/slide`` panes. A segment whose earlier point precedes a window's
    start must not count for that window (window semantics truncate
    trajectories at the start boundary, tStats/TStatsQuery.java:148-189's
    per-window walk), so an interval-add correction subtracts every segment
    from exactly the windows whose start boundary it crosses.

    Exactly equals TStatsQuery.run's per-window recompute (parity test).

    ``backend``: "auto" picks the DEVICE pane engine when the default
    JAX backend is a TPU (one jitted sorted-segment-sum program,
    ops/trajectory.py:traj_stats_pane_kernel) and the native C++ engine
    on CPU hosts; "device" / "native" / "numpy" force a path (the
    parity-oracle contract: all three agree bit-identically in f64).
    """
    if size_ms % slide_ms != 0:
        raise ValueError("size must be a multiple of slide for pane slicing")
    ppw = size_ms // slide_ms
    ts = np.asarray(ts, np.int64)
    oid = np.asarray(oid, np.int64)
    xy = np.asarray(xy, float)
    if len(ts) == 0:
        empty = np.zeros((0, num_oids))
        return TrajPaneWindows(
            np.zeros(0, np.int64), empty, empty.astype(np.int64),
            empty.astype(np.int64), _size_ms=size_ms,
        )

    if backend not in ("auto", "device", "numpy", "native"):
        raise ValueError(f"unknown traj_stats backend {backend!r}")
    if mesh is not None and backend in ("numpy", "native"):
        raise ValueError(
            f"mesh execution requires the device backend, not {backend!r}"
        )
    # Active overload degradation rung (overload.py): bias "auto" away
    # from the device path — the native/numpy engines below answer
    # bit-identically (parity-oracle contract), freeing the loaded
    # device. Forced backends are never overridden.
    from spatialflink_tpu import overload

    prefer_host = (backend == "auto" and mesh is None
                   and overload.pane_backend() in ("native", "numpy"))
    if mesh is not None or backend == "device" or (
            backend == "auto" and not prefer_host
            and _device_backend_preferred()):
        return _traj_stats_sliding_device(
            ts, xy, oid, num_oids, size_ms, slide_ms, mesh=mesh
        )

    ts_sorted = len(ts) <= 1 or bool(np.all(ts[1:] >= ts[:-1]))

    # Native single-pass engine (native/sfnative.cpp:sf_traj_stats):
    # counting sort + segment binning + prefix-sum windows fused per
    # trajectory, cache-resident — bit-identical to the numpy path below
    # (same float association order; parity test tests/test_native.py).
    try:
        from spatialflink_tpu import native as _native

        native_ok = _native.available() and backend != "numpy"
    except Exception:  # pragma: no cover - import/build failure
        native_ok = False
    if backend == "native" and not native_ok:
        raise RuntimeError(
            "backend='native' was forced but the native library is "
            "unavailable (build native/ with make) — refusing to "
            "silently measure the numpy path instead"
        )
    if native_ok:
        if ts_sorted:
            ts_s, xy_s, oid_s = ts, xy, oid
        else:
            order = np.argsort(ts, kind="stable")
            ts_s, xy_s, oid_s = ts[order], xy[order], oid[order]
        out = _native.traj_stats_native(
            ts_s, xy_s[:, 0], xy_s[:, 1], oid_s, num_oids, size_ms,
            slide_ms,
        )
        if out is not None:
            n_starts, w_d, w_dt, w_cnt = out
            p_lo = int(np.floor_divide(int(ts_s[0]), slide_ms))
            alive = w_cnt.sum(axis=1) > 0
            starts = (
                (np.arange(n_starts) + p_lo - (ppw - 1)) * slide_ms
            )[alive]
            return TrajPaneWindows(
                starts=starts.astype(np.int64),
                spatial=w_d[alive],
                temporal=w_dt[alive],
                count=w_cnt[alive],
                _size_ms=size_ms,
            )

    if ts_sorted:
        # Stream order is usually ts-sorted already: a stable radix sort
        # on oid alone preserves the ts order within each trajectory —
        # ~2× cheaper than the general two-key lexsort.
        order = np.argsort(oid, kind="stable")
    else:
        order = np.lexsort((ts, oid))
    t = ts[order]
    o = oid[order]
    p = xy[order]

    pane = np.floor_divide(t, slide_ms)
    p_lo = int(pane.min())
    p_hi = int(pane.max())
    n_panes = p_hi - p_lo + 1
    n_starts = n_panes + ppw - 1

    # Point counts per (pane, oid) — bincount is the fast scatter-add.
    cnt = np.bincount(
        (pane - p_lo) * num_oids + o, minlength=n_panes * num_oids
    ).astype(np.int64).reshape(n_panes, num_oids)

    # Consecutive same-trajectory segments.
    same = o[1:] == o[:-1]
    seg_d = np.hypot(p[1:, 0] - p[:-1, 0], p[1:, 1] - p[:-1, 1])[same]
    seg_dt = (t[1:] - t[:-1])[same]
    seg_oid = o[1:][same]
    seg_tprev = t[:-1][same]
    seg_pane = pane[1:][same]  # pane of the later point

    seg_flat = (seg_pane - p_lo) * num_oids + seg_oid

    def scatter(vals, dtype=float):
        if dtype is float:
            out = np.bincount(
                seg_flat, weights=vals, minlength=n_panes * num_oids
            )
        else:
            # Integer sums stay on add.at: bincount routes weights through
            # float64, which would round above 2^53 where int64 is exact.
            out = np.zeros(n_panes * num_oids, dtype)
            np.add.at(out, seg_flat, vals)
        return out.reshape(n_panes, num_oids)

    pane_d = scatter(seg_d)
    pane_dt = scatter(seg_dt, np.int64)

    # Window sums via ONE unpadded cumsum + clipped row gathers (the
    # padded-cumsum form allocates 2·(ppw−1) extra rows — ~1000 each for
    # the 10s/10ms configs).
    b = np.arange(n_starts) - (ppw - 1)  # window start pane indices
    row_hi = np.clip(b + ppw, 0, n_panes)
    row_lo = np.clip(b, 0, n_panes)

    def rolling_sum(a):
        c = np.concatenate(
            [np.zeros((1, num_oids), a.dtype), np.cumsum(a, axis=0)]
        )
        return c[row_hi] - c[row_lo]

    w_d = rolling_sum(pane_d)
    w_dt = rolling_sum(pane_dt)
    w_cnt = rolling_sum(cnt)

    # Start-boundary corrections: a segment is over-counted by every window
    # whose start lies in (t_prev, t_later] AND that still contains the
    # later point (start pane > seg_pane - ppw). Interval-add via
    # difference arrays + cumsum.
    first_b = np.maximum(seg_tprev // slide_ms + 1, seg_pane - ppw + 1)
    last_b = seg_pane
    has = first_b <= last_b
    if has.any():
        base = p_lo - (ppw - 1)  # window-start pane of start-index 0
        si0 = (first_b[has] - base).astype(np.int64)
        si1 = (last_b[has] - base).astype(np.int64) + 1

        idx = np.concatenate(
            [si0 * num_oids + seg_oid[has], si1 * num_oids + seg_oid[has]]
        )

        def interval_sub(w_mat, vals, dtype=float):
            if dtype is float:
                diff = np.bincount(
                    idx, weights=np.concatenate([vals, -vals]),
                    minlength=(n_starts + 1) * num_oids,
                )
            else:  # int64 exactness: see scatter()
                diff = np.zeros(((n_starts + 1) * num_oids,), dtype)
                np.add.at(diff, idx, np.concatenate([vals, -vals]))
            corr = np.cumsum(diff.reshape(n_starts + 1, num_oids), axis=0)
            return w_mat - corr[:n_starts]

        w_d = interval_sub(w_d, seg_d[has])
        w_dt = interval_sub(w_dt, seg_dt[has], np.int64)

    alive = w_cnt.sum(axis=1) > 0
    starts = ((np.arange(n_starts) + p_lo - (ppw - 1)) * slide_ms)[alive]
    return TrajPaneWindows(
        starts=starts.astype(np.int64),
        spatial=w_d[alive],
        temporal=w_dt[alive],
        count=w_cnt[alive],
        _size_ms=size_ms,
    )
