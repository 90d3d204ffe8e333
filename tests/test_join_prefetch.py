"""The two-stream joins assemble their next window on a producer thread while
the loop runs this one (``operators/join_query.py:_aligned_soa_windows``).

Held here, on toy windows: the items and every join's results are the
synchronous merge's, bit for bit (one-sided windows and an out-of-order chunk
included); the producer is one window ahead and never more; a paced source
cannot make a result wait for the next window's chunks; a consumer that stops
leaves no thread and no further pull behind; what the producer raises is
raised in the loop with its own type, at its own item; and the spans and the
``prefetched`` counter say which thread did what.
"""

import threading
import time

import numpy as np
import pytest

from benchmark.harness import spec
from span_tiling import slow_consumer, x_spans
from spatialflink_tpu.grid import UniformGrid
from spatialflink_tpu.operators import (
    PointPointJoinQuery,
    PolygonPolygonJoinQuery,
    QueryConfiguration,
    QueryType,
)
from spatialflink_tpu.operators import join_query
from spatialflink_tpu.operators.join_query import (
    _aligned_soa_windows,
    _point_sides,
)
from spatialflink_tpu.operators.trajectory import PointPointTJoinQuery
from spatialflink_tpu.telemetry import telemetry

GRID = UniformGrid(20, 0.0, 10.0, 0.0, 10.0)
W10 = QueryConfiguration(QueryType.WindowBased, window_size=10, slide_step=10)
WINDOW_MS = 10_000
PER_WINDOW = 4  # chunks a side a window
RADIUS = 0.3
IDS = 32


def _window_chunks(rng, window: int, n: int = 400):
    """One side's ``PER_WINDOW`` chunks of one window, in time order."""
    ts = window * WINDOW_MS + np.sort(rng.integers(0, WINDOW_MS, n))
    x, y = rng.uniform(-0.2, 10.2, n), rng.uniform(-0.2, 10.2, n)
    oid = rng.integers(0, IDS, n)
    cut = np.linspace(0, n, PER_WINDOW + 1).astype(int)
    return [{"ts": ts[a:b].astype(np.int64), "x": x[a:b], "y": y[a:b],
             "oid": oid[a:b].astype(np.int64)}
            for a, b in zip(cut, cut[1:])]


def _streams(layout: str, seed: int = 3):
    """Left and right chunk lists of three windows.

    ``both``: every window on both sides; ``one_sided``: window 0 the
    right side's alone, window 2 the left's alone; ``out_of_order``: the
    left side's second chunk of window 1 ahead of its first (the assembler
    sorts it back, nothing is late)."""
    rng = np.random.default_rng(seed)
    left = [_window_chunks(rng, w) for w in range(3)]
    right = [_window_chunks(rng, w) for w in range(3)]
    if layout == "one_sided":
        left[0], right[2] = [], []
    elif layout == "out_of_order":
        left[1][0], left[1][1] = left[1][1], left[1][0]
    return ([c for w in left for c in w], [c for w in right for c in w])


def _synchronous(left_chunks, right_chunks, windows_l, windows_r, start_l,
                 start_r):
    """The merge as one loop on the consumer's thread: both sides pulled in
    turn, an item made only when it is asked for."""
    gen_l = iter(windows_l(iter(left_chunks)))
    gen_r = iter(windows_r(iter(right_chunks)))
    wl, wr = next(gen_l, None), next(gen_r, None)
    while wl is not None or wr is not None:
        if wr is None or (wl is not None and start_l(wl) < start_r(wr)):
            yield "left", wl, None, None
            wl = next(gen_l, None)
        elif wl is None or start_r(wr) < start_l(wl):
            yield "right", None, wr, None
            wr = next(gen_r, None)
        else:
            yield "both", wl, wr, None
            wl, wr = next(gen_l, None), next(gen_r, None)


def _merge(merge, left, right, dtype=np.float64):
    start = lambda w: w[0].start  # noqa: E731
    return list(merge(left, right, *_point_sides(GRID, W10, dtype), start,
                      start))


def _same_side(a, b):
    if a is None or b is None:
        return a is None and b is None
    if (a[0].start, a[0].end, a[0].count) != (b[0].start, b[0].end,
                                              b[0].count):
        return False
    return all(u.dtype == v.dtype and np.array_equal(u, v)
               for u, v in zip(a[1:], b[1:]))


def _same_results(got, want):
    assert len(got) == len(want)
    for g, w in zip(got, want):
        assert len(g) == len(w)
        for u, v in zip(g, w):
            if isinstance(v, np.ndarray):
                assert u.dtype == v.dtype and np.array_equal(u, v)
            else:
                assert u == v


def _producers():
    return [t for t in threading.enumerate() if t.name == "join-assembly"]


# --- (a) the same answers ----------------------------------------------------


@pytest.mark.parametrize("layout", ["both", "one_sided", "out_of_order"])
@pytest.mark.parametrize("dtype", [np.float64, np.float32])
def test_items_are_the_synchronous_merges_bit_for_bit(layout, dtype):
    left, right = _streams(layout)
    got = _merge(_aligned_soa_windows, left, right, dtype)
    want = _merge(_synchronous, left, right, dtype)
    assert [g[0] for g in got] == [w[0] for w in want]
    want_kinds = {"both": ["both"] * 3, "out_of_order": ["both"] * 3,
                  "one_sided": ["right", "both", "left"]}[layout]
    assert [g[0] for g in got] == want_kinds
    for g, w in zip(got, want):
        assert _same_side(g[1], w[1]) and _same_side(g[2], w[2])
    assert not _producers()


def _point_join(left, right):
    op = PointPointJoinQuery(W10, GRID, cap=16, join_backend="xla")
    return op.run_soa(iter(left), iter(right), RADIUS, max_pairs=1024)


def _trajectory_join(left, right):
    op = PointPointTJoinQuery(W10, GRID)
    return op.run_soa(iter(left), iter(right), RADIUS, num_segments=IDS)


def _square_chunks(chunks):
    """Each point chunk as a chunk of small squares around its points."""
    out = []
    for c in chunks:
        rings = [np.array([[x - .1, y - .1], [x + .1, y - .1], [x + .1, y + .1],
                           [x - .1, y + .1], [x - .1, y - .1]])
                 for x, y in zip(c["x"][::8], c["y"][::8])]
        out.append({"ts": c["ts"][::8], "oid": c["oid"][::8].astype(np.int32),
                    "lengths": np.full(len(rings), 5, np.int64),
                    "verts": np.concatenate(rings)})
    return out


def _polygon_join(left, right):
    op = PolygonPolygonJoinQuery(W10, GRID)
    return op.run_soa(iter(_square_chunks(left)), iter(_square_chunks(right)),
                      RADIUS)


JOINS = {"point": _point_join, "trajectory": _trajectory_join,
         "polygon": _polygon_join}


@pytest.mark.parametrize("layout", ["both", "one_sided", "out_of_order"])
@pytest.mark.parametrize("join", sorted(JOINS))
def test_run_soa_equals_the_synchronous_merge(join, layout, monkeypatch):
    left, right = _streams(layout)
    got = list(JOINS[join](left, right))
    with monkeypatch.context() as m:
        m.setattr(join_query, "_aligned_soa_windows", _synchronous)
        want = list(JOINS[join](left, right))
    _same_results(got, want)
    assert sum(r[5] for r in got) > 0  # something joined
    assert not _producers()


# --- the depth: one window ahead, never more ---------------------------------


class _Counted:
    """A chunk list handed out one chunk a pull, counting the pulls and the
    threads they came from."""

    def __init__(self, chunks, hold_at=None, gate=None, raise_at=None):
        self.chunks, self.pulls, self.threads = chunks, 0, set()
        self.hold_at, self.gate, self.raise_at = hold_at, gate, raise_at
        self.timed_out = False

    def __iter__(self):
        for i, c in enumerate(self.chunks):
            if i == self.hold_at and not self.gate.wait(timeout=20):
                self.timed_out = True
            if i == self.raise_at:
                raise _SourceFailed(f"chunk {i}")
            self.pulls += 1
            self.threads.add(threading.get_ident())
            yield c


class _SourceFailed(RuntimeError):
    pass


def test_the_producer_is_one_window_ahead_and_no_more():
    left, right = _streams("both")
    lsrc, rsrc = _Counted(left), _Counted(right)
    it = iter(_point_join(lsrc, rsrc))
    first = next(it)
    time.sleep(0.3)  # the producer has all the time it wants
    # window 0 fired at the first chunk of window 1; window 1 is assembled
    # beside it up to its own trigger, the first chunk of window 2
    assert first[5] > 0
    assert (lsrc.pulls, rsrc.pulls) == (2 * PER_WINDOW + 1,) * 2
    rest = list(it)
    assert len(rest) == 2 and (lsrc.pulls, rsrc.pulls) == (3 * PER_WINDOW,) * 2
    # the loop pulled no chunk: every pull came from the producer's thread
    assert threading.get_ident() not in lsrc.threads | rsrc.threads
    assert not _producers()


# --- (b) no staleness ---------------------------------------------------------


@pytest.mark.parametrize("join", ["point", "trajectory"])
def test_a_result_never_waits_for_the_next_windows_chunks(join):
    """The source holds every chunk after window 0's trigger until the test
    has window 0's result: an overlap that assembles window 1 before it
    hands window 0 over would wait here until the gate's timeout."""
    left, right = _streams("both")
    gate = threading.Event()
    lsrc = _Counted(left, hold_at=PER_WINDOW + 1, gate=gate)
    rsrc = _Counted(right, hold_at=PER_WINDOW + 1, gate=gate)
    it = iter(JOINS[join](lsrc, rsrc))
    t0 = time.perf_counter()
    first = next(it)
    waited = time.perf_counter() - t0
    assert not gate.is_set() and not lsrc.timed_out and not rsrc.timed_out
    assert waited < 15, waited
    assert first[:2] == (0, WINDOW_MS)
    gate.set()
    rest = list(it)
    assert [r[0] for r in rest] == [WINDOW_MS, 2 * WINDOW_MS]
    assert not lsrc.timed_out and not rsrc.timed_out


# --- (c) a clean close, and exceptions ----------------------------------------


@pytest.mark.parametrize("how", ["break", "close"])
def test_a_consumer_that_stops_leaves_no_thread_and_no_pull(how):
    left, right = _streams("both")
    lsrc, rsrc = _Counted(left), _Counted(right)
    if how == "break":
        for _result in _point_join(lsrc, rsrc):
            break
    else:
        results = _point_join(lsrc, rsrc)
        next(results)
        results.close()
    deadline = time.monotonic() + 1.0
    while _producers() and time.monotonic() < deadline:
        time.sleep(0.01)
    assert not _producers()
    pulls = (lsrc.pulls, rsrc.pulls)
    time.sleep(0.2)
    assert (lsrc.pulls, rsrc.pulls) == pulls  # no pull after the close
    assert max(pulls) <= 2 * PER_WINDOW + 1 < len(left)


def _missing_y(chunks, at):
    chunks = list(chunks)
    chunks[at] = {k: v for k, v in chunks[at].items() if k != "y"}
    return chunks


@pytest.mark.parametrize("fault", ["source", "assembly"])
@pytest.mark.parametrize("join", ["point", "trajectory"])
def test_what_the_producer_raises_surfaces_in_the_loop_at_its_item(
        join, fault, monkeypatch):
    """The source fails at window 1's third chunk, or a chunk without ``y``
    reaches ``point_lanes`` when window 1 fires: window 0 comes out, then the
    error, with its own type, where the synchronous merge raises it."""
    left, right = _streams("both")

    def run():
        if fault == "source":
            src = _Counted(left, raise_at=PER_WINDOW + 2)
        else:
            src = _missing_y(left, PER_WINDOW + 1)
        got = []
        with pytest.raises(_SourceFailed if fault == "source"
                           else KeyError) as err:
            for r in JOINS[join](src, right):
                got.append(r)
        return got, err.type

    got, raised = run()
    with monkeypatch.context() as m:
        m.setattr(join_query, "_aligned_soa_windows", _synchronous)
        want, want_raised = run()
    assert raised is want_raised and len(got) == len(want) == 1
    _same_results(got, want)
    assert not _producers()


# --- (d) where the time is spent, and what engaged ---------------------------


@pytest.fixture
def traced():
    telemetry.enable()
    yield telemetry
    telemetry.disable()


def test_await_on_the_loop_assembly_on_the_producer_and_prefetched(traced):
    left, right = _streams("both")
    before = traced.snapshot().get("join", {})
    got = slow_consumer(_point_join(left, right), [], nap_s=0.1)
    after = traced.snapshot()["join"]
    loop = threading.get_ident()
    spans = x_spans(traced.events)
    tids = lambda name: {e["tid"] for e in spans if e["name"] == name}  # noqa
    assert tids("join.await") == tids("join.window") == {loop}
    assembly = set()
    for name in ("join.assemble_left", "join.assemble", "soa.consolidate",
                 "soa.center", "soa.cells", "soa.pad"):
        assert tids(name) and loop not in tids(name), name
        assembly |= tids(name)
    assert len(assembly) == 1  # one producer thread
    # one wait a window, and the last ask, which finds the streams at an end
    assert len([e for e in spans if e["name"] == "join.await"]) == len(got) + 1
    windows = after["windows"] - before.get("windows", 0)
    prefetched = after["prefetched"] - before.get("prefetched", 0)
    # after a consumer's nap every window but the first is ready when asked
    # for (the first is, where the new thread got there before the ask)
    assert windows == len(got) == 3
    assert windows - 1 <= prefetched <= windows


def test_the_await_metric_reads_the_span_the_loop_emits():
    (entry,) = [m for m in spec.benchmark()["per_layer"]
                if m["name"] == "join_await_us_per_event"]
    assert entry == {"name": "join_await_us_per_event", "unit": "us",
                     "better": "lower", "source": "program_span",
                     "layer": "operators", "moves": "events_per_s",
                     "workloads": ["join.flood", "tjoin.flood",
                                   "join_skew.flood"]}
    assert spec.metric_file("join_await_us_per_event") == {
        "reader": "program_span_us_per_event",
        "args": {"names": ["join.await"]}}


def test_the_paced_join_cell_is_the_flood_cells_configuration():
    bench = spec.benchmark()
    (cell,) = [w for w in bench["workloads"] if w["name"] == "join.paced"]
    (flood,) = [w for w in bench["workloads"] if w["name"] == "join.flood"]
    assert cell["config"] == flood["config"] and cell["chips"] == 1
    (latency,) = [m for m in bench["end_to_end"]
                  if m["name"] == "result_latency_p50_ms"]
    assert "join.paced" in latency["workloads"]
