"""Extended benchmark suite — the five BASELINE.json configs.

This script exercises every configuration listed in
BASELINE.json's ``configs`` and prints one JSON line per config plus a
summary line. All rates are distinct-ingested-points/sec on the current
default device.

Two ratios per config:
  - ``vs_baseline``: ÷ the reference's 20,000 EPS single-node *target*
    (BenchmarkRunner.java:25-26, InstrumentedMN_Q1.java:88-89 — the repo
    publishes no measured numbers).
  - ``vs_measured_cpu``: ÷ the measured single-device CPU-backend
    throughput of the SAME fused window program on this host
    (CPU_BASELINE.json, produced by ``--cpu-baseline``). This grounds the
    multiplier in a measurement instead of a configured target.

Run: ``python bench_suite.py [--quick]``;
     ``python bench_suite.py --cpu-baseline`` regenerates CPU_BASELINE.json.
"""

from __future__ import annotations

import argparse
import json
import os
import time

import numpy as np

BASELINE_EPS = 20_000.0
CPU_BASELINE_PATH = os.path.join(os.path.dirname(__file__), "CPU_BASELINE.json")


def load_cpu_baseline(key: str = "configs") -> dict:
    try:
        with open(CPU_BASELINE_PATH) as f:
            return json.load(f).get(key, {})
    except (OSError, ValueError):
        return {}


_CPU_BASELINE = load_cpu_baseline()
_CPU_BASELINE_RESIDENT = load_cpu_baseline("configs_resident")


def _stream(n, seed=42, dtype=np.float32):
    rng = np.random.default_rng(seed)
    xy = np.stack(
        [rng.uniform(115.5, 117.6, n), rng.uniform(39.6, 41.1, n)], axis=1
    ).astype(dtype)
    oid = (rng.integers(0, 16_384, n)).astype(np.int32)
    ts = (np.arange(n, dtype=np.int64) * 1000) // 200_000  # 200k EPS event time
    return xy, oid, ts


def _result(name, n_points, seconds, extra=None, spread=None, resident=None):
    eps = n_points / seconds
    out = {
        "config": name,
        "points_per_sec": round(eps, 1),
        "vs_baseline": round(eps / BASELINE_EPS, 2),
    }
    if spread is not None:
        # Median-of-N with min/max: a single-shot rate is unusable as a
        # record (host timings on a shared machine spread widely).
        t_min, t_max = spread
        out["points_per_sec_min"] = round(n_points / t_max, 1)
        out["points_per_sec_max"] = round(n_points / t_min, 1)
    cpu = _CPU_BASELINE.get(name)
    if cpu:
        out["vs_measured_cpu"] = round(eps / cpu, 2)
    if resident is not None:
        # The silicon column: same program, inputs already in HBM, one
        # compiled scan over all windows per pass, passes chained — the
        # e2e column above includes ingest over the host↔device link;
        # this one measures the chip alone.
        pps_r, r_min, r_max = resident
        out["device_resident_points_per_sec"] = round(pps_r, 1)
        out["device_resident_min"] = round(r_min, 1)
        out["device_resident_max"] = round(r_max, 1)
        cpu_r = _CPU_BASELINE_RESIDENT.get(name)
        if cpu_r:
            out["device_resident_vs_measured_cpu"] = round(pps_r / cpu_r, 2)
    if extra:
        out.update(extra)
    from spatialflink_tpu.ablation import ablation

    taint = ablation.taint_block()
    if taint is not None:
        # Ablated (kernel-stubbed) runs are profiling artifacts: the
        # result line says so, and every downstream consumer (trend
        # ingester, diff gate, baseline writers) rejects it.
        out["tainted"] = taint
    print(json.dumps(out))
    return out


REPS = 5  # timed repetitions per config (median + min/max recorded)


def _instr(jfn, name):
    """Wrap a hand-built jit with the telemetry runtime table/recompile
    detector (deferred import: jax/spatialflink must not load before
    main() settles the --cpu-baseline backend env)."""
    from spatialflink_tpu.telemetry import instrument_jit

    return instrument_jit(jfn, name=name)


def _resident_rate(jax, body, carry0, xs, n_pts_per_pass, reps=REPS):
    """Device-resident rate of a per-window program: ``xs`` (already on
    device, leading axis = windows) is scanned by ``body`` inside ONE
    jit per pass — no transfers, no per-window dispatches (only a scan
    inside one jit amortizes the per-dispatch overhead). Passes chain
    through the carry (wrap-around stream) and the pass count is
    calibrated so a run spans ~1.5 s; per run the only sync is one
    device_get of the per-window summary outputs. Returns
    (median_pps, min_pps, max_pps, last_outs)."""
    jpass = _instr(
        jax.jit(lambda c, x: jax.lax.scan(body, c, x)),
        "resident_scan",
    )
    c, out = jpass(carry0, xs)
    jax.device_get(out)  # compile + settle
    t0 = time.perf_counter()
    c, out = jpass(carry0, xs)
    jax.device_get(out)
    t_pass = time.perf_counter() - t0
    passes = int(np.clip(np.ceil(1.5 / max(t_pass, 1e-4)), 2, 64))
    times, last = [], None
    for _ in range(reps):
        cc = carry0
        handles = []
        t0 = time.perf_counter()
        for _p in range(passes):
            cc, out = jpass(cc, xs)
            handles.append(out)
        last = jax.device_get(handles)
        times.append(time.perf_counter() - t0)
    n = passes * n_pts_per_pass
    return (
        n / float(np.median(times)), n / max(times), n / min(times),
        last[-1],
    )


def _pipelined(jax, n_win, make_arrays, dispatch, depth: int = 2,
               reps: int = REPS, reset=None):
    """Shared double-buffered dispatch loop: stage ``depth`` windows of
    host→device transfers ahead, dispatch each window's program, collect
    result handles, and materialize them ALL with one device_get.

    The full timed loop runs ``reps`` times (``reset`` re-seeds any
    carried dispatch state between reps); returns (last rep's fetched
    results, median seconds, min seconds, max seconds). The timed region
    covers every transfer, dispatch and the final fetch. ``dispatch`` may
    return None for iterations that fire no window (kNN pane warm-up)."""
    import time as _time

    ts, out = [], None
    for _ in range(reps):
        if reset is not None:
            reset()
        fired = []
        t0 = _time.perf_counter()
        staged = [make_arrays(i) for i in range(min(depth, n_win))]
        for i in range(n_win):
            if i + depth < n_win:
                staged.append(make_arrays(i + depth))
            res = dispatch(staged.pop(0))
            if res is not None:
                fired.append(res)
        out = jax.device_get(fired)
        ts.append(_time.perf_counter() - t0)
    return out, float(np.median(ts)), min(ts), max(ts)


def bench_range_window(jax, jnp, grid, quick):
    """Config 1: Point-Point range, r≈500m (0.005°), 100×100 grid, 10s
    tumbling windows. Device-side cell assignment, double-buffered
    streamed ingest, pipelined egress (hit counts fetched once at the
    end)."""
    from spatialflink_tpu.ops.cells import assign_cells, gather_cell_flags
    from spatialflink_tpu.ops.range import range_query_kernel

    n_win = 4 if quick else 10
    win_pts = 500_000
    xy, oid, ts = _stream(win_pts * n_win)
    dev = jax.devices()[0]
    q = jax.device_put(jnp.asarray(np.array([[116.40, 40.19]], np.float32)), dev)
    flags = grid.neighbor_flags(0.005, [grid.flat_cell(116.40, 40.19)])
    flags_d = jax.device_put(jnp.asarray(flags), dev)
    valid_d = jax.device_put(jnp.asarray(np.ones(win_pts, bool)), dev)

    def step(xy_w, valid, flags_table, query_xy):
        cell = assign_cells(
            xy_w, grid.min_x, grid.min_y, grid.cell_length, grid.n
        )
        keep, _ = range_query_kernel(
            xy_w, valid, gather_cell_flags(cell, flags_table), query_xy,
            np.float32(0.005),
        )
        return jnp.sum(keep)

    jstep = _instr(jax.jit(step), "range_window_step")

    def win_xy(i):
        return jax.device_put(xy[i * win_pts:(i + 1) * win_pts], dev)

    jax.device_get(jstep(win_xy(0), valid_d, flags_d, q))  # compile

    out, dt, t_min, t_max = _pipelined(
        jax, n_win, win_xy,
        lambda xy_w: jstep(xy_w, valid_d, flags_d, q),
    )
    hits = sum(int(h) for h in out)

    xs = jax.device_put(
        jnp.asarray(xy.reshape(n_win, win_pts, 2)), dev
    )
    pps_r, r_min, r_max, _ = _resident_rate(
        jax,
        lambda c, xy_w: (c, step(xy_w, valid_d, flags_d, q)),
        jnp.int32(0), xs, n_win * win_pts,
    )
    return _result("range_pp_r500m_10s_tumbling", n_win * win_pts, dt,
                   {"hits": hits}, spread=(t_min, t_max),
                   resident=(pps_r, r_min, r_max))


def bench_knn_k(jax, jnp, grid, k, quick):
    """Config 2: continuous kNN, k ∈ {10, 50, 500}, 5s/1s sliding windows.

    Measures the shipped operator program — run_wire_panes
    (operators/knn_query.py), whose wire→digest step is the ONE shared
    implementation in ops/wire_knn.py: each
    1s pane (200k points at the 200k EPS event rate) of 6 B/pt
    plane-major wire records is digested ONCE (top-k compaction on XLA;
    the fused Pallas extraction on TPU after a first-pane self-check —
    ``digest_step`` records which won), each window fire min-merges the
    5 live digests and top-ks. Every point crosses host→device exactly
    once, double-buffered so the next pane's transfer overlaps this
    window's compute. Rate = distinct ingested points / wall time,
    median of REPS runs.
    """
    from spatialflink_tpu.ops.knn import knn_merge_digest_list
    from spatialflink_tpu.ops.wire_knn import select_wire_digest_step
    from spatialflink_tpu.streams.wire import WireFormat

    ppw = 5
    pane_pts = 100_000 if quick else 200_000
    n_panes = 8 if quick else 25
    nseg = 16_384
    total = pane_pts * n_panes
    wf = WireFormat.for_grid(grid)
    xy, oid, ts = _stream(total)
    wire = np.concatenate(
        [wf.quantize(xy), oid.astype(np.int16).view(np.uint16)[:, None]],
        axis=1,
    )
    dev = jax.devices()[0]
    q = jax.device_put(jnp.asarray(np.array([116.40, 40.19], np.float32)), dev)
    scale = jax.device_put(jnp.asarray(np.asarray(wf.scale, np.float32)), dev)
    origin = jax.device_put(
        jnp.asarray(np.asarray(wf.origin, np.float32)), dev
    )
    r32 = np.float32(0.05)

    def pane_arrays(i):
        # plane-major (3, pane_pts) — the run_wire_panes/headline layout
        return jax.device_put(np.ascontiguousarray(
            wire[i * pane_pts:(i + 1) * pane_pts].T
        ), dev)

    digest_kind, digest = select_wire_digest_step(
        pane_arrays(0), pane_pts, q, scale, origin, r32,
        num_segments=nseg, cand=8_192,
    )

    def pane_step(wire_p, query_xy):
        return digest(wire_p, wire_p.shape[1], query_xy, scale, origin, r32)

    jpane = _instr(jax.jit(pane_step), "knn_pane_digest")
    jmerge = _instr(
        jax.jit(knn_merge_digest_list, static_argnames="k"),
        "knn_window_merge",
    )
    no_bases = np.zeros(ppw, np.int32)  # rep indices unread by this bench

    # Warm-up: compile both programs (synced by the device_get below,
    # ditto in the timed loop).
    d0 = jpane(pane_arrays(0), q)
    warm = jmerge(
        (d0.seg_min,) * ppw, (d0.rep,) * ppw, no_bases, k=k
    )
    jax.device_get(warm)

    # Timed region covers panes 1..n_panes-1 end to end, including their
    # host→device transfers (warm-up pane 0 is excluded from the numerator).
    digests = [(d0.seg_min, d0.rep)]

    def dispatch(wire_p):
        d = jpane(wire_p, q)
        digests.append((d.seg_min, d.rep))
        del digests[:-ppw]
        if len(digests) < ppw:
            return None  # window incomplete — no fire yet
        return jmerge(
            tuple(s for s, _ in digests),
            tuple(r for _, r in digests), no_bases, k=k,
        )

    def reset():
        digests[:] = [(d0.seg_min, d0.rep)]

    out, dt, t_min, t_max = _pipelined(
        jax, n_panes - 1, lambda i: pane_arrays(i + 1), dispatch,
        reset=reset,
    )

    # Silicon column: panes 1.. staged in HBM, digest ring carried as a
    # ppw-tuple through one scan (every step fires a window merge).
    xs = jax.device_put(
        jnp.asarray(np.ascontiguousarray(
            wire[pane_pts:pane_pts * n_panes].reshape(
                n_panes - 1, pane_pts, 3
            ).transpose(0, 2, 1)
        )), dev,
    )
    carry0 = ((d0.seg_min,) * ppw, (d0.rep,) * ppw)

    def res_body(carry, wire_p):
        segs, reps_ = carry
        d = pane_step(wire_p, q)
        segs = segs[1:] + (d.seg_min,)
        reps_ = reps_[1:] + (d.rep,)
        res = knn_merge_digest_list(segs, reps_, no_bases, k=k)
        return (segs, reps_), res.num_valid

    pps_r, r_min, r_max, last = _resident_rate(
        jax, res_body, carry0, xs, pane_pts * (n_panes - 1),
    )
    assert int(np.min(last)) > 0, "resident kNN produced empty windows"
    return _result(f"continuous_knn_k{k}_5s_sliding",
                   pane_pts * (n_panes - 1), dt,
                   {"num_valid_last": int(out[-1].num_valid),
                    "digest_step": digest_kind},
                   spread=(t_min, t_max), resident=(pps_r, r_min, r_max))


def bench_polygon_range(jax, jnp, grid, quick):
    """Config 3: Point-Polygon range with a 1k-polygon query set.

    Uses the grid-indexed pruned kernel (a point evaluates its cell's
    candidate polygons; the table is built once, below) with device-side
    cell assignment, double-buffered streamed ingest and pipelined egress
    (per-window hit counts fetched once at the end).
    """
    from spatialflink_tpu.operators.base import (
        pack_cell_candidates,
        pack_cell_edges,
        pack_query_geometries,
    )
    from spatialflink_tpu.ops.cells import assign_cells, gather_cell_flags
    from spatialflink_tpu.ops.range import range_query_polygons_pruned_kernel
    from spatialflink_tpu.utils.helper import generate_query_polygons

    n_polys = 256 if quick else 1000
    win_pts = 131_072 if quick else 262_144
    n_win = 3 if quick else 10
    polys = generate_query_polygons(
        n_polys, 115.5, 39.6, 117.6, 41.1, grid_size=100, seed=3
    )
    verts, ev = pack_query_geometries(polys, np.float64)
    index = pack_cell_candidates(grid, verts, ev, 0.002)
    dev = jax.devices()[0]
    edges_d = jax.device_put(jnp.asarray(pack_cell_edges(
        index.table, verts.astype(np.float32), ev)), dev)
    cells = []
    for p in polys:
        cells.extend(p.grid_cells(grid))
    flags = grid.neighbor_flags(0.002, cells)
    flags_d = jax.device_put(jnp.asarray(flags), dev)
    valid_d = jax.device_put(jnp.asarray(np.ones(win_pts, bool)), dev)
    xy, oid, ts = _stream(win_pts * n_win, seed=7)

    def step(xy_w, valid, flags_table, cell_edges):
        cell = assign_cells(
            xy_w, grid.min_x, grid.min_y, grid.cell_length, grid.n
        )
        keep, _ = range_query_polygons_pruned_kernel(
            xy_w, valid, cell, gather_cell_flags(cell, flags_table),
            cell_edges, np.float32(0.002),
        )
        return jnp.sum(keep)

    jstep = _instr(jax.jit(step), "polygon_range_step")

    def win_xy(i):
        return jax.device_put(xy[i * win_pts:(i + 1) * win_pts], dev)

    jax.device_get(jstep(win_xy(0), valid_d, flags_d, edges_d))  # compile

    out, dt, t_min, t_max = _pipelined(
        jax, n_win, win_xy,
        lambda xy_w: jstep(xy_w, valid_d, flags_d, edges_d),
    )
    hits = sum(int(h) for h in out)

    xs = jax.device_put(jnp.asarray(xy.reshape(n_win, win_pts, 2)), dev)
    pps_r, r_min, r_max, _ = _resident_rate(
        jax,
        lambda c, xy_w: (c, step(xy_w, valid_d, flags_d, edges_d)),
        jnp.int32(0), xs, n_win * win_pts,
    )
    return _result(f"range_point_{n_polys}polygons", n_win * win_pts, dt,
                   {"hits": hits}, spread=(t_min, t_max),
                   resident=(pps_r, r_min, r_max))


def bench_join(jax, jnp, grid, quick):
    """Config 4: spatial join of two streams, r≈200m (0.002°), grid-bucketed.

    On TPU the Pallas hit-extraction join runs (compaction cost ∝ matches);
    elsewhere the XLA dense-bucket kernel. The dispatch loop is pipelined
    lag-1 (fetch window i−1 after dispatching i) so the device round trip
    overlaps compute.
    """
    from spatialflink_tpu.ops.cells import assign_cells
    from spatialflink_tpu.ops.join import join_window_bucketed, pallas_join_supported

    win_pts = 131_072
    n_win = 3 if quick else 16  # enough windows that pipeline fill/drain
    xy_a, _, _ = _stream(win_pts * n_win, seed=1)  # overhead amortizes
    xy_b, _, _ = _stream(win_pts * n_win, seed=2)
    r = np.float32(0.002)
    layers = grid.candidate_layers(float(r))
    dev = jax.devices()[0]
    ones = jax.device_put(jnp.asarray(np.ones(win_pts, bool)), dev)
    if pallas_join_supported():
        from spatialflink_tpu.ops.pallas_join import join_window_pallas as fn
    else:
        fn = join_window_bucketed

    def step(a_xy, b_xy):
        ca = assign_cells(a_xy, grid.min_x, grid.min_y, grid.cell_length, grid.n)
        cb = assign_cells(b_xy, grid.min_x, grid.min_y, grid.cell_length, grid.n)
        return fn(
            a_xy, ones, ca, b_xy, ones, cb,
            grid_n=grid.n, layers=layers, radius=r,
            cap_left=48, cap_right=48, max_pairs=262_144,
        )

    jstep = _instr(jax.jit(step), "join_window_step")

    def win_arrays(i):
        sl = slice(i * win_pts, (i + 1) * win_pts)
        return (
            jax.device_put(xy_a[sl], dev),
            jax.device_put(xy_b[sl], dev),
        )

    a0, b0 = win_arrays(0)
    warm = jstep(a0, b0)
    jax.device_get((warm.count, warm.overflow))  # compile

    def dispatch(args):
        res = jstep(*args)
        return (res.count, res.overflow)

    stats, dt, t_min, t_max = _pipelined(jax, n_win, win_arrays, dispatch)

    xs = (
        jax.device_put(jnp.asarray(xy_a.reshape(n_win, win_pts, 2)), dev),
        jax.device_put(jnp.asarray(xy_b.reshape(n_win, win_pts, 2)), dev),
    )

    def res_body(c, x):
        res = step(x[0], x[1])
        return c, (res.count, res.overflow)

    pps_r, r_min, r_max, _ = _resident_rate(
        jax, res_body, jnp.int32(0), xs, 2 * n_win * win_pts,
    )
    return _result(
        "join_two_streams_r200m", 2 * n_win * win_pts, dt,
        {"pairs": sum(int(c) for c, _ in stats),
         "overflow": sum(int(o) for _, o in stats)},
        spread=(t_min, t_max), resident=(pps_r, r_min, r_max),
    )


def bench_knn_multi_query(jax, jnp, grid, quick):
    """Extension config: batched MULTI-query kNN — 64 query points answered
    by ONE fused program per window (ops/knn.py:knn_multi_query_kernel),
    each query pruning by its own flag table. Not a BASELINE.json config;
    recorded to show the query-set batching surface's throughput."""
    from spatialflink_tpu.ops.cells import assign_cells
    from spatialflink_tpu.ops.knn import knn_multi_query_kernel

    nq, k = 64, 10
    win_pts = 262_144
    n_win = 3 if quick else 6
    rng = np.random.default_rng(23)
    qxy = np.stack(
        [rng.uniform(115.6, 117.5, nq), rng.uniform(39.7, 41.0, nq)], axis=1
    ).astype(np.float32)
    tables = np.stack([
        grid.neighbor_flags(0.05, [grid.flat_cell(*p)]) for p in qxy
    ])
    xy, oid, ts = _stream(win_pts * n_win, seed=29)
    oid16 = oid.astype(np.int16)
    dev = jax.devices()[0]
    q_d = jax.device_put(jnp.asarray(qxy), dev)
    tables_d = jax.device_put(jnp.asarray(tables), dev)
    valid_d = jax.device_put(jnp.asarray(np.ones(win_pts, bool)), dev)

    def step(xy_w, oid16_w, valid, ftabs, queries):
        cell = assign_cells(
            xy_w, grid.min_x, grid.min_y, grid.cell_length, grid.n
        )
        return knn_multi_query_kernel(
            xy_w, valid, cell, ftabs, oid16_w.astype(jnp.int32), queries,
            np.float32(0.05), k=k, num_segments=16_384, query_block=32,
        )

    jstep = _instr(jax.jit(step), "knn_multi_query_step")

    def win_arrays(i):
        sl = slice(i * win_pts, (i + 1) * win_pts)
        return (
            jax.device_put(xy[sl], dev),
            jax.device_put(oid16[sl], dev),
        )

    xa, oa = win_arrays(0)
    jax.device_get(jstep(xa, oa, valid_d, tables_d, q_d).num_valid)

    out, dt, t_min, t_max = _pipelined(
        jax, n_win, win_arrays,
        lambda args: jstep(*args, valid_d, tables_d, q_d).num_valid,
    )

    xs = (
        jax.device_put(jnp.asarray(xy.reshape(n_win, win_pts, 2)), dev),
        jax.device_put(jnp.asarray(oid16.reshape(n_win, win_pts)), dev),
    )
    pps_r, r_min, r_max, _ = _resident_rate(
        jax,
        lambda c, x: (c, step(x[0], x[1], valid_d, tables_d, q_d).num_valid),
        jnp.int32(0), xs, n_win * win_pts,
    )
    return _result(f"knn_multi_{nq}queries_k{k}", n_win * win_pts, dt,
                   {"num_valid_min": int(min(v.min() for v in out))},
                   spread=(t_min, t_max), resident=(pps_r, r_min, r_max))


def bench_qserve(jax, jnp, grid, quick):
    """qserve config: 1024 standing queries (mixed range/kNN across
    k-rungs and radius classes) served by the bucketed registry kernels
    (ops/query_registry.py), with registration CHURN enabled — every
    window swaps 16 queries per bucket for fresh ones (same occupancy →
    same rung → zero recompiles; the ≤K-signatures contract is asserted
    in tests/test_qserve.py, this config measures its throughput).
    Rate = distinct ingested points / wall time; every point is
    evaluated against every bucket (one vmapped program per bucket per
    window), double-buffered like the other configs."""
    from spatialflink_tpu.ops.cells import assign_cells
    from spatialflink_tpu.ops.query_registry import registry_bucket_kernel
    from spatialflink_tpu.qserve import (
        StandingQuery,
        bucket_host_arrays,
        bucket_key,
    )

    nq = 256 if quick else 1024
    win_pts = 65_536 if quick else 131_072
    n_win = 3 if quick else 8
    churn = 4 if quick else 16
    nseg = 16_384
    rng = np.random.default_rng(37)

    def mk_query(i):
        kind = "range" if i % 2 == 0 else "knn"
        k = (32, 5, 10, 30)[i % 4]  # rungs 32, 8, 16, 32
        return StandingQuery(
            qid=f"q{i}", tenant=f"t{i % 97}", kind=kind,
            x=float(rng.uniform(115.6, 117.5)),
            y=float(rng.uniform(39.7, 41.0)),
            radius=float((0.002, 0.02, 0.05)[i % 3]), k=k,
        )

    queries = [mk_query(i) for i in range(nq)]
    flags_cache = {}

    def flags_of(q):
        key = (q.x, q.y, q.radius)
        if key not in flags_cache:
            flags_cache[key] = grid.neighbor_flags(
                q.radius, [grid.flat_cell(q.x, q.y)]
            )
        return flags_cache[key]

    buckets = {}
    for q in queries:
        buckets.setdefault(bucket_key(q), []).append(q)
    dev = jax.devices()[0]

    from spatialflink_tpu.ops.compaction import pick_capacity

    def stage_bucket(key, qs):
        cap = pick_capacity(len(qs), 1024, minimum=8)
        qxy, radius, qvalid, tables = bucket_host_arrays(
            grid, qs, cap, flags_of=flags_of
        )
        return {
            "k": int(key[1]), "cap": cap,
            "qxy": jax.device_put(jnp.asarray(qxy.astype(np.float32)),
                                  dev),
            "radius": jax.device_put(
                jnp.asarray(radius.astype(np.float32)), dev),
            "qvalid": jax.device_put(jnp.asarray(qvalid), dev),
            "tables": jax.device_put(jnp.asarray(tables), dev),
        }

    staged = {key: stage_bucket(key, qs) for key, qs in sorted(
        buckets.items())}
    xy, oid, ts = _stream(win_pts * n_win, seed=41)
    oid16 = oid.astype(np.int16)
    valid_d = jax.device_put(jnp.asarray(np.ones(win_pts, bool)), dev)

    def step(xy_w, oid16_w, valid, ftabs, qxy, radius, qvalid, k, cap):
        cell = assign_cells(
            xy_w, grid.min_x, grid.min_y, grid.cell_length, grid.n
        )
        res = registry_bucket_kernel(
            xy_w, valid, cell, ftabs, oid16_w.astype(jnp.int32), qxy,
            radius, qvalid, k=k, num_segments=nseg,
            query_block=min(cap, 32),
        )
        return res.num_valid, res.within

    jstep = _instr(jax.jit(step, static_argnames=("k", "cap")),
                   "qserve_bucket_step")

    def win_arrays(i):
        sl = slice(i * win_pts, (i + 1) * win_pts)
        return (
            jax.device_put(xy[sl], dev),
            jax.device_put(oid16[sl], dev),
        )

    def dispatch_all(args):
        xy_w, oid_w = args
        return [
            jstep(xy_w, oid_w, valid_d, b["tables"], b["qxy"],
                  b["radius"], b["qvalid"], k=b["k"], cap=b["cap"])
            for _key, b in sorted(staged.items())
        ]

    xa, oa = win_arrays(0)
    jax.device_get(dispatch_all((xa, oa)))  # compile every bucket

    # Churn: per timed window, swap `churn` queries per bucket for
    # fresh ones at the SAME occupancy — re-stages (re-ships) that
    # bucket's host arrays, the steady-state registration cost.
    next_id = [nq]

    def churn_buckets():
        for key in sorted(buckets):
            qs = buckets[key]
            for _ in range(min(churn, len(qs))):
                old = qs.pop(0)
                fresh = mk_query(next_id[0])
                next_id[0] += 1
                # keep the swap inside the SAME bucket: reuse the old
                # query's kind/k/radius (fresh position only)
                qs.append(StandingQuery(
                    qid=f"q{next_id[0]}", tenant=fresh.tenant,
                    kind=old.kind,
                    x=fresh.x, y=fresh.y, radius=old.radius, k=old.k,
                ))
            staged[key] = stage_bucket(key, qs)

    def dispatch(args):
        churn_buckets()
        return dispatch_all(args)

    out, dt, t_min, t_max = _pipelined(
        jax, n_win, win_arrays, dispatch,
    )
    nv_last = sum(int(np.sum(nv)) for nv, _ in out[-1])
    return _result(
        "qserve_1024q_mixed", n_win * win_pts, dt,
        {"queries": nq, "buckets": len(staged),
         "churn_per_window": churn, "num_valid_last": nv_last},
        spread=(t_min, t_max),
    )


def bench_sncb_dag(jax, jnp, grid, quick):
    """Config: the composed 7-node SNCB DAG (spatialflink_tpu/dag.py —
    Q1–Q5 + StayTime + qserve on ONE source/interner/window clock,
    exactly-once per-node egress). This is the END-TO-END pipeline
    rate: event-object windowing, zone kernels, the stay-time segment
    sum, and the bucketed qserve programs all per window, ingest and
    interning paid ONCE for all seven queries — the composition
    ROADMAP item 4 exists for. Host-dominated by design (per-event
    Python windowing), so the number grounds the DAG's ingest wall,
    not a kernel."""
    import itertools
    import tempfile

    from spatialflink_tpu import dag as dag_mod
    from spatialflink_tpu import qserve as qserve_mod
    from spatialflink_tpu.sncb.common import GpsEvent

    n_events = 3_000 if quick else 12_000
    min_x, max_x, min_y, max_y = dag_mod.SNCB_BBOX
    rng = np.random.default_rng(29)
    xs = rng.uniform(min_x, max_x, n_events)
    ys = rng.uniform(min_y, max_y, n_events)
    # Concentrate thirds near the bundled zone centroids (the dag.py
    # smoke idiom) so every node's egress is non-vacuous.
    xs[::3] = 4.354 + rng.normal(0.0, 0.004, len(xs[::3]))
    ys[::3] = 50.854 + rng.normal(0.0, 0.004, len(ys[::3]))
    xs[1::3] = 4.404 + rng.normal(0.0, 0.004, len(xs[1::3]))
    ys[1::3] = 50.854 + rng.normal(0.0, 0.004, len(ys[1::3]))
    fas = rng.uniform(0.0, 1.0, n_events)
    ffs = rng.uniform(0.0, 0.4, n_events)
    sp = rng.uniform(20.0, 110.0, n_events)

    def source():
        for i in range(n_events):
            yield GpsEvent(
                device_id=f"dev{i % 11}", lon=float(xs[i]),
                lat=float(ys[i]), ts=i * 100,
                gps_speed=float(sp[i]), fa=float(fas[i]),
                ff=float(ffs[i]),
            )

    from spatialflink_tpu.sncb.common import PolygonLoader

    zones = (  # loaded once; build_sncb_dag buffers q1's copy per rep
        PolygonLoader.load_geojson_buffered("high_risk_zones.geojson",
                                            20.0),
        PolygonLoader.load_geojson_buffered("maintenance_areas.geojson",
                                            0.0),
        PolygonLoader.load_wkt_buffered("q5_fence.wkt", 20.0),
    )
    reps = 2 if quick else 3
    times, n_results = [], 0
    for _ in range(reps):
        with tempfile.TemporaryDirectory(prefix="sft_dagbench_") as tmp:
            dag = dag_mod.build_sncb_dag(
                tmp, qserve_queries=dag_mod.default_sncb_queries(),
                zones=zones,
            )
            stream = itertools.chain(dag.qserve_boot, source())
            n_results = 0
            t0 = time.perf_counter()
            for res in dag.run(stream):
                n_results += sum(res.counts.values())
            times.append(time.perf_counter() - t0)
    dag_mod.uninstall()
    qserve_mod.uninstall()
    extra = {"nodes": len(dag.dag_nodes), "results_per_rep": n_results}
    # Per-node EPS columns from the attribution buckets (telemetry is
    # enabled by the suite's capture loop; plain runs skip the column).
    # Each node's rate is ITS events over ITS accumulated span time, so
    # the table survives the record↔ledger round trip bit-identically.
    from spatialflink_tpu.telemetry import telemetry

    rollup = telemetry.node_rollup() if telemetry.enabled else {}
    node_eps = {}
    for nname, b in rollup.items():
        span_us = float(b.get("span_us") or 0.0)
        ev = int(b.get("events") or 0)
        if nname != "(unscoped)" and span_us > 0 and ev > 0:
            node_eps[nname] = round(ev / (span_us / 1e6), 1)
    if node_eps:
        extra["node_eps"] = node_eps
    return _result(
        "sncb_dag_7node", reps * n_events, sum(times), extra,
        spread=(min(times) * reps, max(times) * reps),
    )


def bench_point_polygon_join(jax, jnp, grid, quick):
    """Polygon-STREAM join config: points ⋈ 1000 polygons per window via
    the grid-pruned block kernel (ops/join.py:
    point_geometry_join_pruned_kernel — cell-sorted point tiles, bbox
    candidate compaction, exact V-vertex distances for candidates only,
    device pair extraction). ``vs_dense`` records the measured speedup
    over the dense O(N·M·V) kernel on the same window, with a pair-count
    parity assert between the two paths (overflow 0 ⇒ exact)."""
    from spatialflink_tpu.operators.base import pack_query_geometries
    from spatialflink_tpu.ops.join import (
        point_geometry_join_kernel,
        point_geometry_join_pruned_kernel,
    )
    from spatialflink_tpu.utils.helper import generate_query_polygons

    n_polys = 256 if quick else 1000
    win_pts = 65_536 if quick else 131_072
    n_win = 3 if quick else 8
    radius = np.float32(0.002)
    polys = generate_query_polygons(
        n_polys, 115.5, 39.6, 117.6, 41.1, grid_size=100, seed=13
    )
    verts, ev = pack_query_geometries(polys, np.float32)
    # Vertex validity from the edge mask (a vertex borders >= 1 valid edge).
    vm = np.concatenate([ev, ev[:, -1:]], 1) | np.concatenate(
        [ev[:, :1], ev], 1
    )
    bbox = np.stack([
        np.where(vm, verts[:, :, 0], np.inf).min(1),
        np.where(vm, verts[:, :, 1], np.inf).min(1),
        np.where(vm, verts[:, :, 0], -np.inf).max(1),
        np.where(vm, verts[:, :, 1], -np.inf).max(1),
    ], axis=1).astype(np.float32)
    xy, _, _ = _stream(win_pts * n_win, seed=19)
    dev = jax.devices()[0]
    qv = jax.device_put(jnp.asarray(verts), dev)
    qe = jax.device_put(jnp.asarray(ev), dev)
    bbox_d = jax.device_put(jnp.asarray(bbox), dev)
    gvalid_d = jax.device_put(jnp.asarray(np.ones(len(polys), bool)), dev)
    valid_d = jax.device_put(jnp.asarray(np.ones(win_pts, bool)), dev)

    def pruned(xy_w, valid, pv, pe, pb, gval):
        # Points arrive HOST-sorted by cell (pcell=None): the device
        # argsort alone costs 13 ms at 131k on v5e — 2.5× the rest of the
        # kernel — while numpy sorts in ~1 ms overlapped with dispatch.
        res = point_geometry_join_pruned_kernel(
            xy_w, valid, pv, pe, gval, pb, radius,
            polygonal=True, block=256, cand=64, max_pairs=262_144,
            pair_cap=8,
        )
        return res.count, res.cand_overflow, res.pair_overflow

    def dense(xy_w, valid, pv, pe, gval):
        mask, _ = point_geometry_join_kernel(
            xy_w, valid, pv, pe, gval, radius, polygonal=True
        )
        return jnp.sum(mask.astype(jnp.int32))

    jpruned = _instr(jax.jit(pruned), "pp_join_pruned")
    jdense = _instr(jax.jit(dense), "pp_join_dense")

    def win_xy(i):
        sl = xy[i * win_pts:(i + 1) * win_pts]
        ho = np.argsort(grid.assign_cells_np(sl.astype(np.float64)),
                        kind="stable")
        return jax.device_put(sl[ho], dev)

    w0 = win_xy(0)
    c0, co0, po0 = jax.device_get(
        jpruned(w0, valid_d, qv, qe, bbox_d, gvalid_d)
    )
    assert int(co0) == 0, "candidate overflow: raise cand"
    assert int(po0) == 0, "per-point pair overflow: raise pair_cap"
    dense_count = int(jax.device_get(jdense(w0, valid_d, qv, qe, gvalid_d)))
    assert int(c0) == dense_count, "pruned/dense pair-count parity failed"
    # vs_dense: BOTH kernels timed device-resident on the same staged
    # window inside ONE compiled fori_loop per measurement — per-dispatch
    # overhead would swamp a millisecond-scale kernel and compress the
    # ratio toward 1. The loop
    # body perturbs the input per iteration (work-preserving) so XLA
    # cannot hoist it out as loop-invariant.
    def kernel_time(count_body):
        def make_loop(reps):
            @jax.jit
            def lp(xy_w):
                def body(i, acc):
                    pert = xy_w + (i.astype(jnp.float32)
                                   * jnp.float32(1e-9))
                    return acc + count_body(pert)
                return jax.lax.fori_loop(0, reps, body, jnp.int32(0))
            return lp

        lp8 = make_loop(8)
        jax.device_get(lp8(w0))  # compile
        t0 = time.perf_counter()
        jax.device_get(lp8(w0))
        t8 = time.perf_counter() - t0
        reps = int(np.clip(8 * np.ceil(2.0 / t8), 16, 2048))
        lpr = make_loop(reps)
        jax.device_get(lpr(w0))  # compile
        t0 = time.perf_counter()
        jax.device_get(lpr(w0))
        return (time.perf_counter() - t0) / reps

    dense_t = kernel_time(
        lambda xy_w: jnp.asarray(
            dense(xy_w, valid_d, qv, qe, gvalid_d), jnp.int32
        )
    )
    pruned_t = kernel_time(
        lambda xy_w: pruned(xy_w, valid_d, qv, qe, bbox_d, gvalid_d)[0]
    )

    out, dt, t_min, t_max = _pipelined(
        jax, n_win, win_xy,
        lambda xy_w: jpruned(xy_w, valid_d, qv, qe, bbox_d, gvalid_d),
    )
    assert sum(int(co) for _, co, _ in out) == 0, "candidate overflow: raise cand"
    assert sum(int(po) for _, _, po in out) == 0, \
        "per-point pair overflow: raise pair_cap"

    def host_win(i):
        sl = xy[i * win_pts:(i + 1) * win_pts]
        ho = np.argsort(grid.assign_cells_np(sl.astype(np.float64)),
                        kind="stable")
        return sl[ho]

    xs = jax.device_put(
        jnp.asarray(np.stack([host_win(i) for i in range(n_win)])), dev
    )
    pps_r, r_min, r_max, _ = _resident_rate(
        jax,
        lambda c, xy_w: (c, pruned(xy_w, valid_d, qv, qe, bbox_d,
                                   gvalid_d)[0]),
        jnp.int32(0), xs, n_win * win_pts,
    )
    return _result(
        f"join_point_{n_polys}polygons", n_win * win_pts, dt,
        {"pairs": sum(int(c) for c, _, _ in out),
         "vs_dense": round(dense_t / pruned_t, 2)},
        spread=(t_min, t_max), resident=(pps_r, r_min, r_max),
    )


def bench_tjoin_sliding(jax, jnp, grid, quick):
    """tJoin (trajectory join) through 10s/1s sliding windows — the
    run_soa program end to end on device: per window fire, grid-hash
    point join (dense bucket planes, roll-shift neighbor lookup) + per-
    trajectory-pair min-distance dedup (traj_pair_dedup_kernel), over a
    rolling 10-slide window whose slides stay device-resident (each point
    ships ONCE in the 6 B/pt wire format and is re-joined in 10 window
    fires). Rate = distinct ingested points (both streams) / wall time.
    """
    from spatialflink_tpu.ops.cells import assign_cells
    from spatialflink_tpu.ops.join import (
        join_window_bucketed,
        pallas_join_supported,
    )
    from spatialflink_tpu.ops.trajectory import traj_pair_dedup_kernel
    from spatialflink_tpu.streams.wire import WireFormat

    if pallas_join_supported():
        # Hit extraction in time ∝ matches — the XLA nonzero compaction
        # over the span²·cells·cap² domain costs seconds per window at
        # these shapes (the pallas_join design rationale).
        from spatialflink_tpu.ops.pallas_join import join_window_pallas as _join
    else:
        _join = join_window_bucketed

    ppw = 10  # slides per window (10s window / 1s slide)
    slide_pts = 10_240 if quick else 20_480
    n_slides = 14 if quick else 30
    n_obj = 512
    radius = np.float32(0.001)  # ≈110 m proximity
    # ~20 pts/cell avg: cap 64 holds the tail at 200k-pt windows (overflow
    # asserted 0). The Pallas extraction cost scales with matches, so the
    # budgets are sized to the ~40k pairs this radius produces.
    cap, max_pairs = 64, 65_536
    wf = WireFormat.for_grid(grid)
    dev = jax.devices()[0]
    total = slide_pts * n_slides

    def mk_wire(seed):
        r = np.random.default_rng(seed)
        xyq = wf.quantize(np.stack(
            [r.uniform(115.5, 117.6, total), r.uniform(39.6, 41.1, total)],
            axis=1,
        ))
        oid = r.integers(0, n_obj, total).astype(np.uint16)
        return np.concatenate([xyq, oid[:, None]], axis=1)

    wire_l, wire_r = mk_wire(31), mk_wire(32)
    ones = jax.device_put(jnp.asarray(np.ones(slide_pts * ppw, bool)), dev)

    def window_step_flat(lw, rw):
        lxy = wf.dequantize(lw[:, :2])
        rxy = wf.dequantize(rw[:, :2])
        lcell = assign_cells(lxy, grid.min_x, grid.min_y, grid.cell_length,
                             grid.n)
        rcell = assign_cells(rxy, grid.min_x, grid.min_y, grid.cell_length,
                             grid.n)
        # the pairs come out as ids: the extraction carries the id lanes
        res = _join(
            lxy, ones, lcell, rxy, ones, rcell,
            grid_n=grid.n, layers=grid.candidate_layers(float(radius)),
            radius=radius, cap_left=cap, cap_right=cap, max_pairs=max_pairs,
            left_payload=lw[:, 2].astype(jnp.int32),
            right_payload=rw[:, 2].astype(jnp.int32),
        )
        tp = traj_pair_dedup_kernel(
            res.left_index, res.right_index, res.dist, n_obj,
        )
        return tp.count, res.count, res.overflow

    def window_step(l_slides, r_slides):
        return window_step_flat(
            jnp.concatenate(l_slides), jnp.concatenate(r_slides)
        )

    jstep = _instr(jax.jit(window_step), "tjoin_window_step")

    def slide_pair(i):
        sl = slice(i * slide_pts, (i + 1) * slide_pts)
        return (jax.device_put(wire_l[sl], dev),
                jax.device_put(wire_r[sl], dev))

    # Pre-stage + warm the first window (outside the timed region).
    ring_l = [slide_pair(i)[0] for i in range(ppw)]
    ring_r = [slide_pair(i)[1] for i in range(ppw)]
    warm = jstep(tuple(ring_l), tuple(ring_r))
    jax.device_get(warm)

    state = {"l": list(ring_l), "r": list(ring_r)}

    def dispatch(pair):
        sl, sr = pair
        state["l"] = state["l"][1:] + [sl]
        state["r"] = state["r"][1:] + [sr]
        return jstep(tuple(state["l"]), tuple(state["r"]))

    def reset():
        state["l"], state["r"] = list(ring_l), list(ring_r)

    out, dt, t_min, t_max = _pipelined(
        jax, n_slides - ppw, lambda i: slide_pair(i + ppw), dispatch,
        reset=reset,
    )
    assert sum(int(o) for _, _, o in out) == 0, "cell cap overflow"
    assert all(int(c) <= max_pairs for _, c, _ in out), "pair budget"

    # Silicon column: slide ring carried as a (ppw, slide_pts, 3) array
    # through one scan; each step rolls in a staged slide and fires the
    # full-window join (the exact e2e program, transfers excluded).
    xs_l = jax.device_put(
        jnp.asarray(wire_l.reshape(n_slides, slide_pts, 3)[ppw:]), dev
    )
    xs_r = jax.device_put(
        jnp.asarray(wire_r.reshape(n_slides, slide_pts, 3)[ppw:]), dev
    )
    ring0 = (jnp.stack(ring_l), jnp.stack(ring_r))

    def res_body(carry, x):
        rl = jnp.concatenate([carry[0][1:], x[0][None]])
        rr = jnp.concatenate([carry[1][1:], x[1][None]])
        tpc, rc, ov = window_step_flat(rl.reshape(-1, 3), rr.reshape(-1, 3))
        return (rl, rr), (tpc, rc, ov)

    pps_r, r_min, r_max, last = _resident_rate(
        jax, res_body, ring0, (xs_l, xs_r),
        2 * slide_pts * (n_slides - ppw),
    )
    assert int(np.sum(last[2])) == 0, "resident cell cap overflow"
    return _result(
        "tjoin_10s_1s_sliding", 2 * slide_pts * (n_slides - ppw), dt,
        {"traj_pairs_last": int(out[-1][0])}, spread=(t_min, t_max),
        resident=(pps_r, r_min, r_max),
    )


def bench_tjoin_panes(jax, jnp, grid, quick):
    """tJoin at the reference's extreme-overlap window shape — 10 s
    windows sliding every 10 ms (ppw = 1000, Q2_BrakeMonitor's window
    style) — through the device pane-carry engine (ops/tjoin_panes.py):
    window state stays ON DEVICE in ring-buffer bucket planes, each
    slide is O(new pane) work, and a whole batch of slides runs as ONE
    lax.scan dispatch. Rate = distinct ingested points (both sides) /
    wall; the target is ≥1M EPS here where the
    full-window run_soa path manages ~0.4M at 100× LESS overlap.

    On a CPU host the e2e column measures the NATIVE engine
    (sf_tjoin_panes — what run_soa_panes(backend='auto') runs on CPU,
    the same device/native split as the tStats config); the device
    scan stays the resident column (what auto runs on TPU)."""
    from spatialflink_tpu.operators.base import center_coords, jitted
    from spatialflink_tpu.ops.tjoin_panes import (
        tjoin_pane_init,
        tjoin_pane_scan,
    )

    ppw = 1000
    slide_pts = 512 if quick else 1024  # per side per 10 ms pane
    S = 400 if quick else 1000  # timed slides per rep
    n_obj = 64
    # window mean pts/cell = slide_pts·ppw/cells (51 quick / 102 full);
    # the ring must hold the Poisson tail or live slots get overwritten.
    cap_w = 128 if quick else 256
    radius = np.float32(0.001)
    rng = np.random.default_rng(23)
    f32 = np.float32
    total_slides = ppw + S

    def mk_panes(seed_shift):
        n = total_slides * slide_pts
        xy = np.stack([
            rng.uniform(115.5 + seed_shift, 117.6, n),
            rng.uniform(39.6, 41.1, n),
        ], axis=1)
        cxy = center_coords(grid, xy, f32)
        xi = np.floor((xy[:, 0] - grid.min_x) / grid.cell_length)
        yi = np.floor((xy[:, 1] - grid.min_y) / grid.cell_length)
        ing = (xi >= 0) & (xi < grid.n) & (yi >= 0) & (yi < grid.n)
        cell = np.where(ing, xi * grid.n + yi, 0).astype(np.int32)
        oid = rng.integers(0, n_obj, n).astype(np.int32)
        sh = (total_slides, slide_pts)
        from spatialflink_tpu.ops.tjoin_panes import pane_cell_ranks

        pane_of = np.repeat(np.arange(total_slides), slide_pts)
        rank = pane_cell_ranks(pane_of, cell, valid=ing)
        host = (
            cxy[:, 0].astype(f32), cxy[:, 1].astype(f32),
            xi.astype(np.int32), yi.astype(np.int32), cell,
            rank.astype(np.int32), oid, ing,
        )
        dev_fields = tuple(
            jnp.asarray(a.reshape(sh)) for a in host
        )
        # native flat view: in-grid events sorted by pane
        m = ing
        nat = (
            pane_of[m].astype(np.int32), host[0][m].astype(np.float64),
            host[1][m].astype(np.float64), cell[m], oid[m],
        )
        return dev_fields, nat, (pane_of[m].astype(np.int64), cell[m])

    lp, lnat, locc = mk_panes(0.0)
    rp, rnat, rocc = mk_panes(0.0)
    ts_all = jnp.arange(total_slides, dtype=jnp.int32)
    scan = jitted(
        tjoin_pane_scan,
        "grid_n", "cap_w", "layers", "ppw", "num_ids", "pair_sel",
        "cap_c",
    )
    # Live-slot compaction: the host picks the bucketed probe capacity
    # from the exact per-cell window occupancy (ops/compaction.py); the
    # resident column measures the engine run_soa_panes(backend='auto')
    # actually ships on this platform — compacted off-TPU, full-ring on
    # TPU (the row-gather/one-hot form).
    from spatialflink_tpu.ops.compaction import (
        compact_probe_preferred,
        max_window_cell_count,
        pick_capacity,
    )

    cap_c = 0
    if compact_probe_preferred():
        occ = max(max_window_cell_count(*locc, ppw),
                  max_window_cell_count(*rocc, ppw))
        cap_c = pick_capacity(occ, cap_w)
    statics = dict(
        grid_n=grid.n, cap_w=cap_w, layers=grid.candidate_layers(float(radius)),
        ppw=ppw, num_ids=n_obj, pair_sel=16, cap_c=cap_c,
    )

    def part(fields, lo, hi):
        return tuple(f[lo:hi] for f in fields)

    # The steady scan continues the warm carry, so the panes expiring
    # during it (slides 0..S) come from the WARM batch — sliced
    # explicitly (tjoin_pane_scan's default zero-fill shift is only
    # valid when a scan's own slides are the whole ring history).
    lxp = (lp[4][:S], lp[7][:S])
    rxp = (rp[4][:S], rp[7][:S])
    carry0 = tjoin_pane_init(grid.num_cells, cap_w, ppw, n_obj, jnp.float32)
    warm, _ = scan(carry0, ts_all[:ppw], part(lp, 0, ppw), part(rp, 0, ppw),
                   radius, **statics)
    # compile the timed shape too (S ≠ ppw ⇒ distinct executable)
    wtest, wm = scan(warm, ts_all[ppw:], part(lp, ppw, total_slides),
                     part(rp, ppw, total_slides), radius,
                     lps_expire=lxp, rps_expire=rxp, **statics)
    jax.device_get((wtest.cap_overflow, wtest.sel_overflow, wm[-1]))

    times = []
    fin = None
    for _ in range(REPS):
        t0 = time.perf_counter()
        fin, wmins = scan(
            warm, ts_all[ppw:], part(lp, ppw, total_slides),
            part(rp, ppw, total_slides), radius,
            lps_expire=lxp, rps_expire=rxp, **statics,
        )
        got = jax.device_get(
            (fin.cap_overflow, fin.sel_overflow, fin.cmp_overflow,
             wmins[-1])
        )
        times.append(time.perf_counter() - t0)
    cap_over, sel_over, cmp_over, last = got
    pairs_last = int(np.isfinite(last).sum())
    assert int(cap_over) == 0, f"window ring overflow {int(cap_over)}"
    assert int(sel_over) == 0, f"pair_sel overflow {int(sel_over)}"
    assert int(cmp_over) == 0, f"live-slot bucket overflow {int(cmp_over)}"
    dt = float(np.median(times))
    n_pts = 2 * slide_pts * S
    resident = (n_pts / dt, n_pts / max(times), n_pts / min(times))
    extra = {"ppw": ppw, "traj_pairs_last": pairs_last, "engine": "device",
             "cap_c": cap_c}
    spread = (min(times), max(times))

    from spatialflink_tpu import native as _native

    if jax.devices()[0].platform == "cpu" and _native.available():
        # CPU e2e column: the native engine, steady state over every
        # slide (probe + insert + window emission each) — what
        # run_soa_panes(backend='auto') runs on this host. The device
        # scan above stays the resident column.
        nat_times = []
        for _ in range(REPS):
            t0 = time.perf_counter()
            wm = _native.tjoin_panes_native(
                *lnat, *rnat, total_slides, grid.n, statics["layers"],
                ppw, n_obj, float(radius),
            )
            nat_times.append(time.perf_counter() - t0)
        nat_pairs = int(np.isfinite(wm[-1]).sum())
        # f32 device vs f64 native radius masks may flip a borderline
        # POINT pair; a trajectory-pair count shift beyond noise means
        # a real bug (bit-tight parity lives in test_tjoin_panes.py).
        assert abs(nat_pairs - pairs_last) <= max(2, pairs_last // 100), (
            f"native/device window pair-count diverged "
            f"({nat_pairs} vs {pairs_last})"
        )
        dt = float(np.median(nat_times))
        n_pts = 2 * slide_pts * total_slides
        spread = (min(nat_times), max(nat_times))
        extra["engine"] = "native"
    return _result(
        "tjoin_panes_10s_10ms", n_pts, dt, extra, spread=spread,
        # On TPU this config is device-resident BY CONSTRUCTION (all
        # slides pre-staged, one scan dispatch per rep).
        resident=resident,
    )


def bench_tstats_pane(jax, jnp, grid, quick):
    """tStats through the reference's extreme-overlap 10s/10ms sliding
    config (Q2_BrakeMonitor-style) via pane decomposition
    (streams/panes.py:traj_stats_sliding — host-vectorized,
    O(events + panes × oids) instead of O(windows × window size))."""
    from spatialflink_tpu.streams.panes import traj_stats_sliding

    n = 300_000 if quick else 1_000_000
    rng = np.random.default_rng(17)
    ts = np.sort(rng.integers(0, 30_000, n)).astype(np.int64)
    xy = np.stack(
        [rng.uniform(115.5, 117.6, n), rng.uniform(39.6, 41.1, n)], axis=1
    )
    oid = rng.integers(0, 500, n).astype(np.int64)
    traj_stats_sliding(ts[:1000], xy[:1000], oid[:1000], 512, 10_000, 10)
    times = []
    for _ in range(REPS):
        t0 = time.perf_counter()
        res = traj_stats_sliding(ts, xy, oid, 512, 10_000, 10)
        times.append(time.perf_counter() - t0)
    dt = float(np.median(times))

    # Silicon column: the device pane engine's KERNEL on pre-staged
    # sorted/padded arrays (ops/trajectory.py:traj_stats_pane_kernel —
    # what backend='auto' runs on TPU), timed inside one calibrated
    # fori_loop (per-dispatch overhead would swamp it);
    # the loop body perturbs x so XLA can't hoist the iteration.
    import jax as _jax

    from spatialflink_tpu.ops.trajectory import traj_stats_pane_kernel
    from spatialflink_tpu.utils.padding import next_bucket as _nb

    order = np.argsort(oid, kind="stable")
    t_s, o_s, p_s = ts[order], oid[order], xy[order]
    slide = 10
    p_lo = int(t_s.min() // slide)
    n_panes = _nb(int(t_s.max() // slide) - p_lo + 1, minimum=8)
    nb = _nb(n, minimum=8)
    pad = nb - n
    f32 = np.float32
    dev = jax.devices()[0]
    tp_d = jax.device_put(jnp.asarray(np.concatenate(
        [t_s - p_lo * slide, np.full(pad, 0, np.int64)]).astype(np.int32)),
        dev)
    xp_d = jax.device_put(jnp.asarray(np.concatenate(
        [p_s[:, 0], np.zeros(pad)]).astype(f32)), dev)
    yp_d = jax.device_put(jnp.asarray(np.concatenate(
        [p_s[:, 1], np.zeros(pad)]).astype(f32)), dev)
    op_d = jax.device_put(jnp.asarray(np.concatenate(
        [o_s, np.full(pad, 511)]).astype(np.int32)), dev)
    vp_d = jax.device_put(jnp.asarray(
        np.concatenate([np.ones(n, bool), np.zeros(pad, bool)])), dev)
    statics = dict(num_oids=512, slide_ms=slide, ppw=1000, n_panes=n_panes)

    def make_loop(reps):
        @_jax.jit
        def lp(tp, xp, yp, op_, vp):
            def body(i, acc):
                pert = xp + i.astype(jnp.float32) * jnp.float32(1e-12)
                r = traj_stats_pane_kernel(tp, pert, yp, op_, vp, **statics)
                return acc + r.spatial[0, 0] + r.temporal[0, 0].astype(
                    r.spatial.dtype)
            return _jax.lax.fori_loop(0, reps, body, jnp.float32(0))
        return lp

    lp2 = make_loop(2)
    jax.device_get(lp2(tp_d, xp_d, yp_d, op_d, vp_d))
    t0 = time.perf_counter()
    jax.device_get(lp2(tp_d, xp_d, yp_d, op_d, vp_d))
    t2 = time.perf_counter() - t0
    loops = int(np.clip(2 * np.ceil(1.5 / max(t2, 1e-4)), 4, 256))
    lpr = make_loop(loops)
    jax.device_get(lpr(tp_d, xp_d, yp_d, op_d, vp_d))
    r_times = []
    for _ in range(REPS):
        t0 = time.perf_counter()
        jax.device_get(lpr(tp_d, xp_d, yp_d, op_d, vp_d))
        r_times.append(time.perf_counter() - t0)
    n_loop = loops * n
    resident = (
        n_loop / float(np.median(r_times)),
        n_loop / max(r_times), n_loop / min(r_times),
    )
    return _result(
        "tstats_pane_10s_10ms", n, dt, {"windows": int(len(res.starts))},
        spread=(min(times), max(times)), resident=resident,
    )


def bench_tknn(jax, jnp, grid, quick):
    """Config 5: trajectory kNN, per-objID grouped, k=20. Same streamed
    double-buffered dispatch model as the other configs (int16 oid wire,
    device-side cells, pipelined egress)."""
    from spatialflink_tpu.ops.cells import assign_cells
    from spatialflink_tpu.ops.knn import knn_kernel
    from spatialflink_tpu.ops.cells import gather_cell_flags

    win_pts = 262_144
    n_win = 3 if quick else 6
    xy, oid, ts = _stream(win_pts * n_win, seed=11)
    oid16 = oid.astype(np.int16)
    dev = jax.devices()[0]
    q = jax.device_put(jnp.asarray(np.array([116.40, 40.19], np.float32)), dev)
    flags = grid.neighbor_flags(0.1, [grid.flat_cell(116.40, 40.19)])
    flags_d = jax.device_put(jnp.asarray(flags), dev)
    valid_d = jax.device_put(jnp.asarray(np.ones(win_pts, bool)), dev)

    def step(xy_w, oid16_w, valid, flags_table, query_xy):
        cell = assign_cells(
            xy_w, grid.min_x, grid.min_y, grid.cell_length, grid.n
        )
        return knn_kernel(
            xy_w, valid, gather_cell_flags(cell, flags_table),
            oid16_w.astype(jnp.int32), query_xy, np.float32(0.1),
            k=20, num_segments=16_384,
        )

    jstep = _instr(jax.jit(step), "tknn_step")

    def win_arrays(i):
        sl = slice(i * win_pts, (i + 1) * win_pts)
        return (
            jax.device_put(xy[sl], dev),
            jax.device_put(oid16[sl], dev),
        )

    xa, oa = win_arrays(0)
    jax.device_get(jstep(xa, oa, valid_d, flags_d, q))  # compile

    out, dt, t_min, t_max = _pipelined(
        jax, n_win, win_arrays,
        lambda args: jstep(*args, valid_d, flags_d, q),
    )

    xs = (
        jax.device_put(jnp.asarray(xy.reshape(n_win, win_pts, 2)), dev),
        jax.device_put(jnp.asarray(oid16.reshape(n_win, win_pts)), dev),
    )
    pps_r, r_min, r_max, _ = _resident_rate(
        jax,
        lambda c, x: (c, step(x[0], x[1], valid_d, flags_d, q).num_valid),
        jnp.int32(0), xs, n_win * win_pts,
    )
    return _result("trajectory_knn_k20_per_objid", n_win * win_pts, dt,
                   {"num_valid_last": int(out[-1].num_valid)},
                   spread=(t_min, t_max), resident=(pps_r, r_min, r_max))


# -- grid-partitioned halo configs (8-device CPU mesh, subprocess) -----------

HALO_SHARDS = 8
_HALO_CONFIGS = ("range_8shard_halo", "tjoin_8shard_halo")


def _halo_child_range(quick: bool) -> dict:
    """``range_8shard_halo`` child body: the grid-partitioned range
    kernel (parallel/halo.py:sharded_range_halo) on the 8-device CPU
    mesh vs the replicated ``sharded_range_query`` on the SAME windows.
    EPS comes from the halo path; the accounted collective bytes of
    BOTH paths come from the telemetry snapshot, so the record stamps
    measured halo vs broadcast/all-gather traffic."""
    from spatialflink_tpu.grid import UniformGrid
    from spatialflink_tpu.parallel.halo import sharded_range_halo
    from spatialflink_tpu.parallel.mesh import data_mesh
    from spatialflink_tpu.parallel.partition import plan_partition
    from spatialflink_tpu.parallel.sharded import sharded_range_query
    from spatialflink_tpu.telemetry import telemetry

    grid = UniformGrid(1024, min_x=115.5, max_x=117.6, min_y=39.6,
                       max_y=41.1)
    radius = 0.002  # ≈ one cell → 1-layer halo, boundary region ≈ 1.6%
    win_pts = 8_192 if quick else 16_384
    n_win = 2 if quick else 4
    nq = 4_096
    rng = np.random.default_rng(47)
    total = win_pts * n_win
    xy = np.stack([rng.uniform(115.5, 117.6, total),
                   rng.uniform(39.6, 41.1, total)], axis=1)
    qxy = np.stack([rng.uniform(115.6, 117.5, nq),
                    rng.uniform(39.7, 41.0, nq)], axis=1)
    cell = grid.assign_cells_np(xy)
    qcell = grid.assign_cells_np(qxy)
    valid = np.ones(win_pts, bool)
    qok = np.ones(nq, bool)
    mesh = data_mesh(HALO_SHARDS)
    plan = plan_partition(grid, HALO_SHARDS, radius)

    def halo_pass():
        hits = 0
        for i in range(n_win):
            sl = slice(i * win_pts, (i + 1) * win_pts)
            keep, _ = sharded_range_halo(
                mesh, plan, xy[sl], valid, cell[sl], qxy, qcell, qok,
                radius,
            )
            hits += int(keep.sum())
        return hits

    hits = halo_pass()  # compile every rung signature outside the clock
    reps = 3
    telemetry.enable()
    times = []
    for _ in range(reps):
        t0 = time.perf_counter()
        halo_pass()
        times.append(time.perf_counter() - t0)
    snap = telemetry.snapshot()
    telemetry.disable()
    coll = snap.get("collectives") or {}
    halo_b = int(((coll.get("by_kind") or {}).get("ppermute") or {})
                 .get("bytes") or 0) // reps
    halo_state = int(coll.get("halo_state_bytes") or 0) // reps

    # The replicated path on the same windows: its accounted collective
    # is the whole-query-set broadcast (every shard receives all nq
    # queries; the halo path ships only boundary-cell query panes).
    table = grid.neighbor_flags(radius, [int(c) for c in qcell])
    telemetry.enable()
    for i in range(n_win):
        sl = slice(i * win_pts, (i + 1) * win_pts)
        keep, _ = sharded_range_query(
            mesh, xy[sl], valid, table[cell[sl]], qxy, radius,
        )
        np.asarray(keep)
    legacy = (telemetry.snapshot().get("collectives") or {})
    telemetry.disable()
    return {
        "points": n_win * win_pts,
        "times": times,
        "halo_collective_bytes": halo_b,
        "halo_state_bytes": halo_state,
        "replicated_collective_bytes": int(legacy.get("bytes") or 0),
        "extra": {"hits": hits, "queries": nq},
    }


def _halo_child_tjoin(quick: bool) -> dict:
    """``tjoin_8shard_halo`` child body: the grid-partitioned tjoin pane
    scan (parallel/halo.py:sharded_tjoin_panes_halo) vs the replicated
    ``sharded_tjoin_pane_scan`` over the SAME panes — the legacy scan
    all-gathers every pane field + contribution lanes per slide, the
    halo path ships only boundary-cell window panes."""
    import jax
    import jax.numpy as jnp

    from spatialflink_tpu.grid import UniformGrid
    from spatialflink_tpu.operators.base import center_coords
    from spatialflink_tpu.ops.tjoin_panes import (
        pane_cell_ranks,
        tjoin_pane_init,
    )
    from spatialflink_tpu.parallel.halo import sharded_tjoin_panes_halo
    from spatialflink_tpu.parallel.mesh import data_mesh
    from spatialflink_tpu.parallel.partition import plan_partition
    from spatialflink_tpu.parallel.sharded import sharded_tjoin_pane_scan
    from spatialflink_tpu.telemetry import telemetry

    grid = UniformGrid(256, min_x=115.5, max_x=117.6, min_y=39.6,
                       max_y=41.1)
    radius = 0.005
    ppw = 4
    slide_pts = 1_024 if quick else 2_048
    n_slides = 5 if quick else 8
    n_obj = 64
    total = slide_pts * n_slides

    def mk_side(seed):
        r = np.random.default_rng(seed)
        sxy = np.stack([r.uniform(115.5, 117.6, total),
                        r.uniform(39.6, 41.1, total)], axis=1)
        return sxy, grid.assign_cells_np(sxy), \
            r.integers(0, n_obj, total).astype(np.int32)

    lxy, lcell, loid = mk_side(53)
    rxy, rcell, roid = mk_side(54)
    ok = np.ones(slide_pts, bool)

    def panes_of(sxy, scell):
        return [
            (sxy[i * slide_pts:(i + 1) * slide_pts], ok,
             scell[i * slide_pts:(i + 1) * slide_pts])
            for i in range(n_slides)
        ]

    panes_l, panes_r = panes_of(lxy, lcell), panes_of(rxy, rcell)
    ts = np.arange(n_slides, dtype=np.int64) * 1000
    mesh = data_mesh(HALO_SHARDS)
    plan = plan_partition(grid, HALO_SHARDS, radius)

    def halo_pass():
        res = sharded_tjoin_panes_halo(
            mesh, plan, ts, panes_l, panes_r, radius, ppw, 65_536)
        assert sum(r[4] for r in res) == 0, "pair budget overflow"
        return sum(r[3] for r in res)

    pairs = halo_pass()  # compile every rung signature outside the clock
    reps = 3
    telemetry.enable()
    times = []
    for _ in range(reps):
        t0 = time.perf_counter()
        halo_pass()
        times.append(time.perf_counter() - t0)
    snap = telemetry.snapshot()
    telemetry.disable()
    coll = snap.get("collectives") or {}
    halo_b = int(((coll.get("by_kind") or {}).get("ppermute") or {})
                 .get("bytes") or 0) // reps
    halo_state = int(coll.get("halo_state_bytes") or 0) // reps

    # The replicated scan on the same panes (probe-parallel legacy
    # path): per slide it all-gathers both sides' 8 pane field arrays
    # plus the contribution lanes, and psums the overflow scalars.
    layers = grid.candidate_layers(radius)
    cap_w = 16

    def side_fields(sxy, scell, soid):
        cxy = center_coords(grid, sxy, np.float32)
        ci = grid.cell_xy_indices_np(sxy)
        ing = scell < grid.num_cells
        pane_of = np.repeat(np.arange(n_slides), slide_pts)
        rank = pane_cell_ranks(pane_of, scell, valid=ing)
        sh = (n_slides, slide_pts)
        host = (
            cxy[:, 0].astype(np.float32), cxy[:, 1].astype(np.float32),
            ci[:, 0], ci[:, 1],
            np.where(ing, scell, 0).astype(np.int32),
            rank.astype(np.int32), soid, ing,
        )
        return tuple(jnp.asarray(a.reshape(sh)) for a in host)

    lps = side_fields(lxy, lcell, loid)
    rps = side_fields(rxy, rcell, roid)
    telemetry.enable()
    carry0 = tjoin_pane_init(grid.num_cells, cap_w, ppw, n_obj,
                             jnp.float32)
    fin, wmins = sharded_tjoin_pane_scan(
        mesh, carry0, jnp.arange(n_slides, dtype=jnp.int32), lps, rps,
        np.float32(radius), grid_n=grid.n, cap_w=cap_w, layers=layers,
        ppw=ppw, num_ids=n_obj, pair_sel=16,
    )
    jax.device_get(wmins)
    legacy = (telemetry.snapshot().get("collectives") or {})
    telemetry.disable()
    return {
        "points": 2 * total,
        "times": times,
        "halo_collective_bytes": halo_b,
        "halo_state_bytes": halo_state,
        "replicated_collective_bytes": int(legacy.get("bytes") or 0),
        "extra": {"ppw": ppw, "traj_pairs": int(pairs)},
    }


def run_halo_child(name: str, quick: bool):
    """``--halo-child`` entry: runs inside the subprocess the parent
    config spawns with the 8-device CPU mesh env, prints ONE JSON
    record on stdout."""
    import jax

    devs = jax.devices()
    if devs[0].platform != "cpu" or len(devs) < HALO_SHARDS:
        raise SystemExit(
            f"--halo-child needs {HALO_SHARDS} CPU devices: run via the "
            "parent config (bench_halo_config pins JAX_PLATFORMS=cpu + "
            f"XLA_FLAGS=--xla_force_host_platform_device_count="
            f"{HALO_SHARDS})"
        )
    fn = {"range_8shard_halo": _halo_child_range,
          "tjoin_8shard_halo": _halo_child_tjoin}[name]
    print(json.dumps(fn(quick)))


def bench_halo_config(name: str, quick: bool):
    """Configs ``range_8shard_halo`` / ``tjoin_8shard_halo``: the
    grid-partitioned halo kernels on an 8-device CPU mesh. The 8
    virtual devices need XLA_FLAGS *before* jax initializes — which the
    suite process can't change once its own backend is up — so the
    measurement runs in a ``--halo-child`` subprocess pinned to the CPU
    backend. The child's record stamps the accounted collective bytes
    of the halo path AND the replicated legacy kernel on the same
    workload; ``halo_vs_replicated`` is the measured traffic ratio."""
    import subprocess
    import sys

    env = {
        **os.environ,
        "JAX_PLATFORMS": "cpu",
        "XLA_FLAGS":
            f"--xla_force_host_platform_device_count={HALO_SHARDS}",
    }
    env.pop("SFT_FAULT_PLAN", None)
    cmd = [sys.executable, os.path.abspath(__file__), "--halo-child",
           name]
    if quick:
        cmd.append("--quick")
    proc = subprocess.run(cmd, env=env, capture_output=True, text=True,
                          timeout=1800)
    if proc.returncode != 0:
        raise RuntimeError(
            f"halo child {name} failed (exit {proc.returncode}):\n"
            + proc.stderr[-2000:]
        )
    rec = json.loads(proc.stdout.strip().splitlines()[-1])
    times = rec["times"]
    halo_b = int(rec["halo_collective_bytes"])
    legacy_b = int(rec["replicated_collective_bytes"])
    extra = {
        "shards": HALO_SHARDS,
        "halo_collective_bytes": halo_b,
        "halo_state_bytes": int(rec["halo_state_bytes"]),
        "replicated_collective_bytes": legacy_b,
        "halo_vs_replicated":
            round(halo_b / legacy_b, 4) if legacy_b else None,
    }
    extra.update(rec.get("extra") or {})
    return _result(name, rec["points"], float(np.median(times)), extra,
                   spread=(min(times), max(times)))


def run_ablation(benches, top_n=6, ledger_dir=None):
    """The measured kernel-ablation sweep (``--ablate``;
    ``spatialflink_tpu/ablation.py``): per config, a clean baseline run
    learns the config's kernel set (heaviest-first from the telemetry
    runtime table), then the config re-runs once per kernel with that
    kernel's dispatch substituted by cached correct-aval zeros — the
    EPS delta is the kernel's MEASURED marginal cost, the empirical twin
    of the XLA cost model's flops ranking (on XLA:CPU the two disagree
    hard: scatters cost ~100× gathers).

    Every ablated run is tainted end to end (result line, ledger,
    stream) and a leg whose downstream asserts reject the zeroed
    results is recorded as unmeasurable-with-evidence, not a crash —
    an ablation that breaks the program proves the kernel is
    load-bearing, which is an answer too. Prints one
    ``ablation_table`` JSON line per config and returns the tables."""
    from spatialflink_tpu.ablation import ablation
    from spatialflink_tpu.telemetry import telemetry

    tables = []
    for name, fn in benches:
        ablation.disarm()
        telemetry.enable()
        try:
            base = fn()
            kernel_rows = telemetry.kernel_table()
        finally:
            telemetry.disable()
        base_eps = float(base["points_per_sec"])
        seen = set()
        kernels = [r["kernel"] for r in kernel_rows
                   if not (r["kernel"] in seen or seen.add(r["kernel"]))]
        rows = []
        for kernel in kernels[:top_n]:
            telemetry.enable()
            ablation.arm([kernel])
            try:
                res = fn()
                eps = float(res["points_per_sec"])
                if ledger_dir:
                    telemetry.write_ledger(
                        os.path.join(ledger_dir,
                                     f"{name}.ablate.{kernel}.json"),
                        bench=res,
                    )
                rows.append({
                    "kernel": kernel,
                    "points_per_sec": round(eps, 1),
                    "speedup_if_free": round(eps / base_eps, 3),
                    "marginal_frac": round((eps - base_eps) / base_eps,
                                           4),
                })
            except Exception as e:
                rows.append({
                    "kernel": kernel,
                    "error": f"{type(e).__name__}: {e}",
                    "note": "config rejects zeroed results — the "
                            "kernel is load-bearing; marginal cost "
                            "unmeasurable by substitution",
                })
            finally:
                telemetry.disable()
                ablation.disarm()
        table = {
            "ablation_table": name,
            "baseline_points_per_sec": round(base_eps, 1),
            "kernels": sorted(
                rows, key=lambda r: -r.get("marginal_frac", -1e9)),
            "tainted": True,
        }
        print(json.dumps(table))
        tables.append(table)
    return tables


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--quick", action="store_true")
    ap.add_argument(
        "--cpu-baseline", action="store_true",
        help="run on the single-device CPU backend and write the measured "
             "points/s of every config to CPU_BASELINE.json",
    )
    ap.add_argument(
        "--ablate", action="store_true",
        help="measured kernel-ablation sweep: per config, re-run with "
             "each kernel's dispatch substituted by cached zeros and "
             "print the marginal-EPS table (all outputs tainted — "
             "profiling only, never a record)",
    )
    ap.add_argument(
        "--ablate-top", type=int, default=6,
        help="kernels per config to ablate, heaviest steady-dispatch "
             "first (default %(default)s)",
    )
    ap.add_argument(
        "--configs", default=None,
        help="comma-separated substrings; run only configs whose name "
             "matches one (e.g. --configs knn_k50,tjoin_panes).",
    )
    ap.add_argument(
        "--halo-child", default=None, choices=_HALO_CONFIGS,
        metavar="CONFIG",
        help="internal: run one halo config's measurement body in THIS "
             "process (the parent spawns it with the 8-device CPU-mesh "
             "env, which must be set before jax initializes)",
    )
    args = ap.parse_args()
    if args.halo_child:
        run_halo_child(args.halo_child, args.quick)
        return
    if args.cpu_baseline and args.configs:
        ap.error(
            "--configs cannot combine with --cpu-baseline: the baseline "
            "file is written whole, so a filtered run would silently "
            "drop every non-matching config's entry"
        )
    if args.cpu_baseline and args.ablate:
        ap.error(
            "--ablate cannot combine with --cpu-baseline: ablated runs "
            "are tainted profiling artifacts and must never enter "
            "CPU_BASELINE.json"
        )

    if args.cpu_baseline:
        # Must happen before jax import: force the CPU backend, one device.
        os.environ["JAX_PLATFORMS"] = "cpu"
        # Don't print ratios against the file this run is about to replace.
        global _CPU_BASELINE
        _CPU_BASELINE = {}

    import jax
    import jax.numpy as jnp

    from spatialflink_tpu.ablation import ablation

    if args.cpu_baseline:
        jax.config.update("jax_platforms", "cpu")
        assert jax.devices()[0].platform == "cpu"
        if ablation.armed:
            # Fail BEFORE the hours of runs, not at the write.
            raise SystemExit(
                "--cpu-baseline refused: SFT_ABLATE is armed and "
                "ablated (tainted) numbers must never enter "
                "CPU_BASELINE.json"
            )

    from spatialflink_tpu.grid import UniformGrid

    grid = UniformGrid(100, min_x=115.5, max_x=117.6, min_y=39.6, max_y=41.1)
    all_benches = [
        ("range_pp_r500m_10s_tumbling",
         lambda: bench_range_window(jax, jnp, grid, args.quick)),
        ("continuous_knn_k10_5s_sliding",
         lambda: bench_knn_k(jax, jnp, grid, 10, args.quick)),
        ("continuous_knn_k50_5s_sliding",
         lambda: bench_knn_k(jax, jnp, grid, 50, args.quick)),
        ("continuous_knn_k500_5s_sliding",
         lambda: bench_knn_k(jax, jnp, grid, 500, args.quick)),
        ("range_point_1000polygons",
         lambda: bench_polygon_range(jax, jnp, grid, args.quick)),
        ("join_two_streams_r200m",
         lambda: bench_join(jax, jnp, grid, args.quick)),
        ("join_point_1000polygons",
         lambda: bench_point_polygon_join(jax, jnp, grid, args.quick)),
        ("tjoin_10s_1s_sliding",
         lambda: bench_tjoin_sliding(jax, jnp, grid, args.quick)),
        ("tjoin_panes_10s_10ms",
         lambda: bench_tjoin_panes(jax, jnp, grid, args.quick)),
        ("trajectory_knn_k20_per_objid",
         lambda: bench_tknn(jax, jnp, grid, args.quick)),
        ("tstats_pane_10s_10ms",
         lambda: bench_tstats_pane(jax, jnp, grid, args.quick)),
        ("knn_multi_64queries_k10",
         lambda: bench_knn_multi_query(jax, jnp, grid, args.quick)),
        ("qserve_1024q_mixed",
         lambda: bench_qserve(jax, jnp, grid, args.quick)),
        ("sncb_dag_7node",
         lambda: bench_sncb_dag(jax, jnp, grid, args.quick)),
        ("range_8shard_halo",
         lambda: bench_halo_config("range_8shard_halo", args.quick)),
        ("tjoin_8shard_halo",
         lambda: bench_halo_config("tjoin_8shard_halo", args.quick)),
    ]
    if args.configs:
        wanted = [w.strip() for w in args.configs.split(",") if w.strip()]
        all_benches = [
            (name, fn) for name, fn in all_benches
            if any(w in name for w in wanted)
        ]
        if not all_benches:
            raise SystemExit(f"--configs matched nothing: {args.configs}")
    ledger_dir = os.environ.get("SFT_LEDGER_DIR")
    if args.ablate:
        run_ablation(all_benches, top_n=args.ablate_top,
                     ledger_dir=ledger_dir)
        return
    results = []
    for name, fn in all_benches:
        if ledger_dir:
            # One run ledger per config (tools/sfprof): telemetry is
            # (re-)enabled around each config so every ledger carries
            # exactly that config's spans/kernel table/byte tallies,
            # plus the config's own result record as the bench block.
            # Each config also streams to <name>.stream.jsonl — a
            # multi-hour suite run killed mid-config keeps every
            # finished config's ledger AND a recoverable prefix of the
            # one in flight (`sfprof recover`).
            from spatialflink_tpu.telemetry import telemetry

            telemetry.enable(stream_path=os.path.join(
                ledger_dir, f"{name}.stream.jsonl"))
            res = fn()
            try:
                telemetry.write_ledger(
                    os.path.join(ledger_dir, f"{name}.json"), bench=res
                )
            except Exception as e:
                # A ledger failure (disk full, NaN in a result dict) must
                # not abort a multi-hour suite run and lose every other
                # config's result — degrade to stderr.
                import sys

                sys.stderr.write(f"ledger for {name} not written: {e!r}\n")
            finally:
                telemetry.disable()
        else:
            res = fn()
        results.append(res)
    if args.cpu_baseline:
        payload = {
            "note": (
                "Measured CPU-backend throughput of the same fused window "
                "programs (XLA:CPU), with data already in RAM (no serde/"
                "ingest). 'cores' records the host affinity at measurement "
                "time — compare against the reference's single-node "
                "parallelism-1 harness (BenchmarkRunner.java:30 "
                "setParallelism(1)); the reference publishes no measured "
                "numbers, only the 20k EPS target of "
                "BenchmarkRunner.java:25-26."
            ),
            "cores": len(os.sched_getaffinity(0)),
            "device": str(jax.devices()[0]),
            "configs": {r["config"]: r["points_per_sec"] for r in results},
            "configs_resident": {
                r["config"]: r["device_resident_points_per_sec"]
                for r in results
                if "device_resident_points_per_sec" in r
            },
        }
        with open(CPU_BASELINE_PATH, "w") as f:
            json.dump(payload, f, indent=1)
        print(json.dumps({"wrote": CPU_BASELINE_PATH}))
        return
    worst = min(r["vs_baseline"] for r in results)
    out = {
        "summary": "bench_suite", "device": str(jax.devices()[0]),
        "configs": len(results), "min_vs_baseline": worst,
    }
    ratios = [r["vs_measured_cpu"] for r in results if "vs_measured_cpu" in r]
    if ratios:
        out["min_vs_measured_cpu"] = min(ratios)
    print(json.dumps(out))


if __name__ == "__main__":
    main()
