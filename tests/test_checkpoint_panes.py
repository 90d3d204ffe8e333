"""Kill-and-resume for the incremental pane-carry pipelines (the
ListState-analog state in query_panes lived in generator locals
and could not be checkpointed). A stream is cut mid-way, the operator is
snapshotted (assembler + pane digests/blocks + interner), a FRESH operator
is restored in a "new process" (pickle round-trip through disk), and the
resumed output must equal the uninterrupted run exactly."""

import numpy as np
import pytest

from spatialflink_tpu.checkpoint import (
    load_checkpoint,
    operator_state,
    restore_operator,
    save_checkpoint,
)
from spatialflink_tpu.grid import UniformGrid
from spatialflink_tpu.models.objects import Point
from spatialflink_tpu.operators import (
    PointPointJoinQuery,
    PointPointKNNQuery,
    QueryConfiguration,
    QueryType,
)

GRID = UniformGrid(20, 0.0, 10.0, 0.0, 10.0)
CONF = QueryConfiguration(QueryType.WindowBased, window_size=10, slide_step=5)


@pytest.fixture
def rng():
    return np.random.default_rng(21)


def _pts(rng, n, prefix="d", n_obj=24, t_span=40_000):
    xy = rng.uniform(0, 10, (n, 2))
    return [
        Point(obj_id=f"{prefix}{i % n_obj}", timestamp=int(i * t_span / n),
              x=float(xy[i, 0]), y=float(xy[i, 1]))
        for i in range(n)
    ]


def _knn_key(results):
    return [
        (r.start, r.end,
         [(o, round(d, 12), ev.obj_id, ev.timestamp)
          for o, d, ev in r.neighbors])
        for r in results
    ]


def test_knn_pane_carry_kill_and_resume(rng, tmp_path):
    pts = _pts(rng, 900)
    q = Point(x=5.0, y=5.0)
    r, k = 3.0, 6
    cut = 500  # mid-stream, mid-window

    baseline = _knn_key(
        PointPointKNNQuery(CONF, GRID).query_panes(iter(pts), q, r, k)
    )

    # "Process 1": source dies after `cut` events; snapshot to disk.
    op1 = PointPointKNNQuery(CONF, GRID)
    part1 = _knn_key(
        op1.query_panes(iter(pts[:cut]), q, r, k, flush_at_end=False)
    )
    path = str(tmp_path / "knn.ckpt")
    save_checkpoint(path, op=operator_state(op1))
    del op1

    # "Process 2": fresh operator, restore, feed the remaining events.
    op2 = PointPointKNNQuery(CONF, GRID)
    restore_operator(op2, load_checkpoint(path)["op"])
    part2 = _knn_key(op2.query_panes(iter(pts[cut:]), q, r, k))

    assert part1 + part2 == baseline
    assert part1 and part2  # the cut actually split fired windows


def test_knn_pane_carry_resume_digests_survive(rng, tmp_path):
    """The resumed run must MERGE carried digests from before the kill —
    cut inside a window so its first slide's data exists only in the
    checkpoint."""
    pts = _pts(rng, 600, t_span=30_000)
    q = Point(x=5.0, y=5.0)
    op1 = PointPointKNNQuery(CONF, GRID)
    # Cut at 60%: the open window's earlier pane was digested pre-kill.
    cut = 360
    _ = _knn_key(op1.query_panes(iter(pts[:cut]), q, 3.0, 5,
                                 flush_at_end=False))
    state = operator_state(op1)
    assert any(v is not None for v in state["knn_pane_carry"].values())
    assert state["assembler"]["buffers"]  # open windows buffered


@pytest.mark.slow
def test_join_pane_carry_kill_and_resume(rng, tmp_path):
    left = _pts(rng, 500, prefix="a")
    right = _pts(np.random.default_rng(9), 400, prefix="b", n_obj=16)
    r = 0.7

    def collect(gen):
        return [
            (res.start, res.end, res.overflow,
             sorted((a.obj_id, a.timestamp, b.obj_id, b.timestamp,
                     round(d, 12)) for a, b, d in res.pairs))
            for res in gen
        ]

    baseline = collect(
        PointPointJoinQuery(CONF, GRID).query_panes(iter(left), iter(right), r)
    )

    lcut, rcut = 280, 220
    op1 = PointPointJoinQuery(CONF, GRID)
    part1 = collect(op1.query_panes(
        iter(left[:lcut]), iter(right[:rcut]), r, flush_at_end=False
    ))
    path = str(tmp_path / "join.ckpt")
    save_checkpoint(path, op=operator_state(op1))
    del op1

    op2 = PointPointJoinQuery(CONF, GRID)
    restore_operator(op2, load_checkpoint(path)["op"])
    part2 = collect(op2.query_panes(iter(left[lcut:]), iter(right[rcut:]), r))

    assert part1 + part2 == baseline
    assert part1 and part2


def test_knn_soa_pane_carry_kill_and_resume(rng, tmp_path):
    n = 4_000
    ts = np.sort(rng.integers(0, 40_000, n)).astype(np.int64)
    xs = rng.uniform(0, 10, n)
    ys = rng.uniform(0, 10, n)
    oids = rng.integers(0, 32, n).astype(np.int32)
    q = Point(x=5.0, y=5.0)
    r, k, nseg = 3.0, 6, 32

    def chunks(lo, hi, step=700):
        for a in range(lo, hi, step):
            b = min(a + step, hi)
            yield {"ts": ts[a:b], "x": xs[a:b], "y": ys[a:b],
                   "oid": oids[a:b]}

    def collect(gen):
        return [
            (s, e, list(map(int, o)), [round(float(x), 12) for x in d], nv)
            for s, e, o, d, nv in gen
        ]

    baseline = collect(PointPointKNNQuery(CONF, GRID).run_soa_panes(
        chunks(0, n), q, r, k, num_segments=nseg
    ))

    cut = 2_300
    op1 = PointPointKNNQuery(CONF, GRID)
    part1 = collect(op1.run_soa_panes(
        chunks(0, cut), q, r, k, num_segments=nseg, flush_at_end=False
    ))
    path = str(tmp_path / "soa.ckpt")
    save_checkpoint(path, op=operator_state(op1))
    del op1

    op2 = PointPointKNNQuery(CONF, GRID)
    restore_operator(op2, load_checkpoint(path)["op"])
    part2 = collect(op2.run_soa_panes(
        chunks(cut, n), q, r, k, num_segments=nseg
    ))

    assert part1 + part2 == baseline
    assert part1 and part2


def test_knn_wire_pane_carry_kill_and_resume(rng, tmp_path):
    """run_wire_panes (the wire-ingest headline path) resumes
    mid-window: the digest ring + next pane index snapshot through
    operator_state; a restored operator fed the REMAINING panes (the
    WireKafkaSource-offsets pairing) continues identically to an
    uninterrupted run."""
    from spatialflink_tpu.streams.wire import WireFormat, wire_panes

    wf = WireFormat.for_grid(GRID)
    n = 5_000
    ts = np.sort(rng.integers(0, 40_000, n)).astype(np.int64)
    xy = np.stack([rng.uniform(0, 10, n), rng.uniform(0, 10, n)], axis=1)
    wq = wf.quantize(xy)
    xyf = wf.dequantize_np(wq)
    oids = rng.integers(0, 32, n).astype(np.int32)
    q = Point(x=5.0, y=5.0)
    r, k, nseg = 3.0, 6, 32
    slide_ms = CONF.slide_step_ms

    panes = list(wire_panes(
        [{"ts": ts, "x": xyf[:, 0].astype(np.float64),
          "y": xyf[:, 1].astype(np.float64), "oid": oids}],
        wf, slide_ms, start_ms=0,
    ))

    def collect(gen):
        return [
            (s, e, list(map(int, o)), [round(float(x), 6) for x in d], nv)
            for s, e, o, d, nv in gen
        ]

    def run(op, pane_list, flush=True):
        return collect(op.run_wire_panes(
            pane_list, q, r, k, nseg, wf, start_ms=0, flush_at_end=flush,
        ))

    baseline = run(PointPointKNNQuery(CONF, GRID), panes)

    cut = len(panes) // 3
    op1 = PointPointKNNQuery(CONF, GRID)
    part1 = run(op1, panes[:cut], flush=False)
    path = str(tmp_path / "wire.ckpt")
    save_checkpoint(path, op=operator_state(op1))
    del op1

    op2 = PointPointKNNQuery(CONF, GRID)
    restore_operator(op2, load_checkpoint(path)["op"])
    part2 = run(op2, panes[cut:])

    assert part1 + part2 == baseline
    assert part1 and part2


def test_knn_wire_pane_carry_not_reentrant_leak(rng, tmp_path):
    """The index-based wire carry is consumed only right after restore:
    an ordinary SECOND call on the same operator must be a fresh run
    (identical output), not a silent time-shifted resume — and a
    checkpoint taken before ANY pane restores to a run that flushes
    nothing on an empty remainder (r5 code review)."""
    from spatialflink_tpu.streams.wire import WireFormat, wire_panes

    wf = WireFormat.for_grid(GRID)
    n = 1_500
    ts = np.sort(rng.integers(0, 20_000, n)).astype(np.int64)
    xy = np.stack([rng.uniform(0, 10, n), rng.uniform(0, 10, n)], axis=1)
    xyf = wf.dequantize_np(wf.quantize(xy))
    oids = rng.integers(0, 16, n).astype(np.int32)
    q = Point(x=5.0, y=5.0)
    panes = list(wire_panes(
        [{"ts": ts, "x": xyf[:, 0].astype(np.float64),
          "y": xyf[:, 1].astype(np.float64), "oid": oids}],
        wf, CONF.slide_step_ms, start_ms=0,
    ))

    def collect(gen):
        return [(s, e, list(map(int, o)), nv) for s, e, o, _d, nv in gen]

    op = PointPointKNNQuery(CONF, GRID)
    first = collect(op.run_wire_panes(panes, q, 3.0, 5, 16, wf))
    second = collect(op.run_wire_panes(panes, q, 3.0, 5, 16, wf))
    assert first == second

    # checkpoint before any pane → restore + empty remainder = nothing
    op1 = PointPointKNNQuery(CONF, GRID)
    none = collect(op1.run_wire_panes([], q, 3.0, 5, 16, wf,
                                      flush_at_end=False))
    assert none == []
    path = str(tmp_path / "wire0.ckpt")
    save_checkpoint(path, op=operator_state(op1))
    op2 = PointPointKNNQuery(CONF, GRID)
    restore_operator(op2, load_checkpoint(path)["op"])
    assert collect(op2.run_wire_panes([], q, 3.0, 5, 16, wf)) == []
