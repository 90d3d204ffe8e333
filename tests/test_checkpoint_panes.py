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


class _Counted:
    """A source that knows how many items have been pulled from it —
    the position a checkpoint taken at a yield pairs with."""

    def __init__(self, items):
        self.items = items
        self.pulled = 0

    def __iter__(self):
        for it in self.items:
            self.pulled += 1
            yield it


def _baseline(gen, collect, still_feeding):
    """(every window of an uninterrupted run, how many of them were
    yielded while ``still_feeding()``: before the end-of-stream flush)."""
    out, feed_yields = [], 0
    for res in gen:
        feed_yields += bool(still_feeding())
        out.extend(collect([res]))
    return out, feed_yields


def _head_until(gen, collect, cut):
    """Consume ``cut`` windows, then abandon the generator where it
    stands (the kill): nothing after that yield runs."""
    head = []
    for out in gen:
        head.extend(collect([out]))
        if len(head) == cut:
            break
    gen.close()
    return head


def _round_trip(path, op1, op2):
    save_checkpoint(path, op=operator_state(op1))
    restore_operator(op2, load_checkpoint(path)["op"])


#: The toy streams below fire this many windows while their source still
#: has items (one window an item at most, so every such yield is a
#: position a checkpoint can pair with); the windows after that are the
#: end-of-stream flush. Each test asserts its number, so a changed
#: stream cannot quietly leave yields untested.
KNN_FEED_YIELDS = 7
JOIN_FEED_YIELDS = 7
SOA_FEED_YIELDS = 7
WIRE_REAL_PANE_YIELDS = 8


@pytest.mark.parametrize(
    "cut", ["source_dies"] + list(range(1, KNN_FEED_YIELDS + 1)))
def test_knn_pane_carry_kill_and_resume(rng, tmp_path, cut):
    """``source_dies``: the source ends mid-window and the call returns
    (``flush_at_end=False``). An integer: the consumer is killed right
    after that many yielded windows and the snapshot is taken at that
    yield. Either way the windows before the cut followed by the
    resumed run's equal the uninterrupted run — none lost, none twice."""
    pts = _pts(rng, 900)
    q = Point(x=5.0, y=5.0)
    r, k = 3.0, 6

    src = _Counted(pts)
    baseline, feed_yields = _baseline(
        PointPointKNNQuery(CONF, GRID).query_panes(iter(src), q, r, k),
        _knn_key, lambda: src.pulled < len(pts))
    assert feed_yields == KNN_FEED_YIELDS

    op1 = PointPointKNNQuery(CONF, GRID)
    if cut == "source_dies":
        pos = 500  # mid-stream, mid-window
        part1 = _knn_key(
            op1.query_panes(iter(pts[:pos]), q, r, k, flush_at_end=False)
        )
    else:
        src = _Counted(pts)
        part1 = _head_until(op1.query_panes(iter(src), q, r, k),
                            _knn_key, cut)
        pos = src.pulled
    # "Process 2": fresh operator, restore, feed the remaining events.
    op2 = PointPointKNNQuery(CONF, GRID)
    _round_trip(str(tmp_path / "knn.ckpt"), op1, op2)
    part2 = _knn_key(op2.query_panes(iter(pts[pos:]), q, r, k))

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
@pytest.mark.parametrize(
    "cut", ["source_dies"] + list(range(1, JOIN_FEED_YIELDS + 1)))
def test_join_pane_carry_kill_and_resume(rng, tmp_path, cut):
    """The two-stream twin of the kNN test above; an integer cut kills
    the consumer after that many yielded windows, and the resumed run
    gets what each side had not handed over yet."""
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

    lsrc, rsrc = _Counted(left), _Counted(right)
    baseline, feed_yields = _baseline(
        PointPointJoinQuery(CONF, GRID).query_panes(
            iter(lsrc), iter(rsrc), r),
        collect,
        lambda: lsrc.pulled < len(left) or rsrc.pulled < len(right))
    assert feed_yields == JOIN_FEED_YIELDS

    op1 = PointPointJoinQuery(CONF, GRID)
    if cut == "source_dies":
        lpos, rpos = 280, 220
        part1 = collect(op1.query_panes(
            iter(left[:lpos]), iter(right[:rpos]), r, flush_at_end=False
        ))
    else:
        part1 = _head_until(
            op1.query_panes(iter(left), iter(right), r), collect, cut)
        # The merge inside query_panes reads one event ahead on the other
        # side, so the handed-over prefix is read off the snapshot: the
        # window fired on the first event that raised the assembler's
        # max timestamp to what the snapshot holds.
        merged = sorted(
            [(e.timestamp, 0) for e in left]
            + [(e.timestamp, 1) for e in right])
        max_ts = operator_state(op1)["assembler"]["max_ts"]
        fed = merged[:[ts for ts, _ in merged].index(max_ts) + 1]
        rpos = sum(tag for _, tag in fed)
        lpos = len(fed) - rpos
    op2 = PointPointJoinQuery(CONF, GRID)
    _round_trip(str(tmp_path / "join.ckpt"), op1, op2)
    part2 = collect(op2.query_panes(iter(left[lpos:]), iter(right[rpos:]), r))

    assert part1 + part2 == baseline
    assert part1 and part2


@pytest.mark.parametrize(
    "cut", ["source_dies"] + list(range(1, SOA_FEED_YIELDS + 1)))
def test_knn_soa_pane_carry_kill_and_resume(rng, tmp_path, cut):
    """run_soa_panes over SoA chunks shorter than a slide (so a chunk
    fires one window at most and every yield is a chunk boundary):
    ``source_dies`` ends the source mid-window, an integer kills the
    consumer after that many yielded windows."""
    n = 4_000
    ts = np.sort(rng.integers(0, 40_000, n)).astype(np.int64)
    xs = rng.uniform(0, 10, n)
    ys = rng.uniform(0, 10, n)
    oids = rng.integers(0, 32, n).astype(np.int32)
    q = Point(x=5.0, y=5.0)
    r, k, nseg = 3.0, 6, 32

    def chunks(lo, hi, step=350):
        return [{"ts": ts[a:min(a + step, hi)], "x": xs[a:min(a + step, hi)],
                 "y": ys[a:min(a + step, hi)],
                 "oid": oids[a:min(a + step, hi)]}
                for a in range(lo, hi, step)]

    def collect(gen):
        return [
            (s, e, list(map(int, o)), [round(float(x), 12) for x in d], nv)
            for s, e, o, d, nv in gen
        ]

    def run(op, chunk_list, flush=True):
        return op.run_soa_panes(iter(chunk_list), q, r, k,
                                num_segments=nseg, flush_at_end=flush)

    every = chunks(0, n)
    src = _Counted(every)
    baseline, feed_yields = _baseline(
        run(PointPointKNNQuery(CONF, GRID), src), collect,
        lambda: src.pulled < len(every))
    assert feed_yields == SOA_FEED_YIELDS

    op1 = PointPointKNNQuery(CONF, GRID)
    if cut == "source_dies":
        part1 = collect(run(op1, chunks(0, 2_300), flush=False))
        rest = chunks(2_300, n)
    else:
        src = _Counted(every)
        part1 = _head_until(run(op1, src), collect, cut)
        rest = every[src.pulled:]
    op2 = PointPointKNNQuery(CONF, GRID)
    _round_trip(str(tmp_path / "soa.ckpt"), op1, op2)
    part2 = collect(run(op2, rest))

    assert part1 + part2 == baseline
    assert part1 and part2


@pytest.fixture
def batch_slides(request):
    """Arm overload's ``batch_slides`` fetch-batching rung at the asked
    width (1 = no controller: the rung's own default), as
    tests/test_overload.py arms it."""
    from spatialflink_tpu import overload
    from spatialflink_tpu.overload import OverloadController, OverloadPolicy

    width = request.param
    if width > 1:
        ctrl = overload.install(OverloadController(OverloadPolicy(
            ladder=({"action": "batch_slides", "n": width},),
            degrade_cooldown=1)))
        ctrl.on_slo_evaluation(False)
    assert overload.batch_slides() == width
    yield width
    overload.uninstall()


@pytest.mark.parametrize("batch_slides", [1, 2, 3], indirect=True)
@pytest.mark.parametrize(
    "cut", ["source_dies"] + list(range(1, WIRE_REAL_PANE_YIELDS + 1)))
def test_knn_wire_pane_carry_kill_and_resume(rng, tmp_path, cut,
                                             batch_slides):
    """run_wire_panes (the wire-ingest headline path) resumes
    mid-window: the digest ring + next pane index snapshot through
    operator_state; a restored operator fed the REMAINING panes (the
    WireKafkaSource-offsets pairing) continues identically to an
    uninterrupted run. ``source_dies`` ends the source after a third of
    the panes; an integer kills the consumer after that many yielded
    windows — with a fetch batch open when ``batch_slides`` > 1 — and
    the carry's ``next_pane`` says where the source resumes: it follows
    the YIELDED windows, not the consumed panes. Cuts stop before the
    trailing flush: its synthetic panes never advance the carry, so a
    resume replays the whole flush (the call-boundary contract)."""
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
        return op.run_wire_panes(
            pane_list, q, r, k, nseg, wf, start_ms=0, flush_at_end=flush,
        )

    baseline = collect(run(PointPointKNNQuery(CONF, GRID), panes))
    ppw = CONF.window_size_ms // slide_ms
    assert len(baseline) - (ppw - 1) == WIRE_REAL_PANE_YIELDS

    op1 = PointPointKNNQuery(CONF, GRID)
    if cut == "source_dies":
        pos = len(panes) // 3
        part1 = collect(run(op1, panes[:pos], flush=False))
    else:
        part1 = _head_until(run(op1, panes), collect, cut)
        pos = int(operator_state(op1)["knn_wire_pane_carry"]["next_pane"])
    op2 = PointPointKNNQuery(CONF, GRID)
    _round_trip(str(tmp_path / "wire.ckpt"), op1, op2)
    part2 = collect(run(op2, panes[pos:]))

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
