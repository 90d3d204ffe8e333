"""The range family's ``run_soa`` assembles its next window on a producer
thread while the loop runs this one (``operators/base.py:assembled_ahead``,
the producer the joins' ``_aligned_soa_windows`` runs on too).

Held here, on toy windows: the producer's items are ``soa_point_batches``'
own, bit for bit (tumbling and sliding windows, chunks out of order inside
the lateness bound, windows of one chunk); ``run_soa``'s results are the
synchronous loop's for the pruned and cell-major polygon kernels and a point
query set; the producer is one window ahead and never more; a paced source
cannot make a result wait for the next window's chunks (where a paced range
cell would see it); a consumer that stops leaves no thread and no further
pull behind; what the producer raises is raised in the loop with its own
type, at its own item; and the spans and the ``prefetched`` counter say which
thread did what.
"""

import threading
import time

import numpy as np
import pytest

from benchmark.harness import spec
from benchmark.references import spider_gaussian, spider_polygons
from span_tiling import slow_consumer, x_spans
from spatialflink_tpu.grid import UniformGrid
from spatialflink_tpu.models.objects import Point, Polygon
from spatialflink_tpu.operators import (
    PointPointRangeQuery,
    PointPolygonRangeQuery,
    QueryConfiguration,
    QueryType,
)
from spatialflink_tpu.operators import range_query
from spatialflink_tpu.operators.base import soa_point_batches
from spatialflink_tpu.telemetry import telemetry
from spatialflink_tpu.utils.helper import generate_query_polygons

BBOX = (115.5, 39.6, 117.6, 41.1)  # conf/geoflink-conf.yml's, as the cells'
GRID = UniformGrid(100, BBOX[0], BBOX[2], BBOX[1], BBOX[3])
RADIUS = 0.002
WINDOW_MS = 10_000
PER_WINDOW = 4  # chunks a window
WINDOWS = 3

TUMBLING = QueryConfiguration(QueryType.WindowBased, window_size=10,
                              slide_step=10)
#: (configuration, chunks a window, the chunk order)
LAYOUTS = {
    "tumbling": (TUMBLING, PER_WINDOW, "in_order"),
    "sliding": (QueryConfiguration(QueryType.WindowBased, window_size=10,
                                   slide_step=5), PER_WINDOW, "in_order"),
    # window 1's last chunk after window 2's first: 5 s of lateness lets the
    # watermark stay short of window 1's end, so nothing is late
    "out_of_order": (QueryConfiguration(QueryType.WindowBased,
                                        window_size=10, slide_step=10,
                                        allowed_lateness=5), PER_WINDOW,
                     "swapped"),
    "one_chunk": (TUMBLING, 1, "in_order"),
}


@pytest.fixture(autouse=True)
def _telemetry_off():
    telemetry.disable()
    telemetry._reset_state()
    yield
    telemetry.disable()


def _chunks(per_window=PER_WINDOW, order="in_order", n=1200, seed=5,
            xy=None):
    """``WINDOWS`` windows of ``n`` points, ``per_window`` chunks each, in
    time order (``swapped``: window 1's last chunk after window 2's first).
    ``xy(rng, n)`` places the points (uniform over the bbox by default)."""
    rng = np.random.default_rng(seed)
    out = []
    for w in range(WINDOWS):
        ts = w * WINDOW_MS + np.sort(rng.integers(0, WINDOW_MS, n))
        if xy is None:
            x, y = rng.uniform(BBOX[0], BBOX[2], n), rng.uniform(
                BBOX[1], BBOX[3], n)
        else:
            x, y = xy(rng, n)
        oid = rng.integers(0, 256, n)
        cut = np.linspace(0, n, per_window + 1).astype(int)
        out.append([{"ts": ts[a:b].astype(np.int64), "x": x[a:b],
                     "y": y[a:b], "oid": oid[a:b].astype(np.int64)}
                    for a, b in zip(cut, cut[1:])])
    if order == "swapped":
        out[1][-1], out[2][0] = out[2][0], out[1][-1]
    return [c for w in out for c in w]


def _synchronous(sources, items, thread, span, ready):
    """``assembled_ahead`` as one loop on the consumer's thread: an item
    made only when it is asked for."""
    for item in items(*map(iter, sources)):
        yield item, None


def _producers():
    return [t for t in threading.enumerate() if t.name == "range-assembly"]


def _point_queries():
    rng = np.random.default_rng(9)
    return [Point(obj_id=f"q{i}", x=float(x), y=float(y))
            for i, (x, y) in enumerate(zip(rng.uniform(BBOX[0], BBOX[2], 40),
                                           rng.uniform(BBOX[1], BBOX[3], 40)))]


def _point_range(chunks, conf=TUMBLING, dtype=np.float64):
    op = PointPointRangeQuery(conf, GRID)
    return op.run_soa(iter(chunks), _point_queries(), 0.05, dtype=dtype)


def _same_item(a, b):
    wa, wb = a[0], b[0]
    if (wa.start, wa.end, wa.count) != (wb.start, wb.end, wb.count):
        return False
    if wa.arrays.keys() != wb.arrays.keys() or not all(
            np.array_equal(wa.arrays[k], wb.arrays[k]) for k in wa.arrays):
        return False
    return all(u.dtype == v.dtype and np.array_equal(u, v)
               for u, v in zip(a[1:], b[1:]))


def _same_results(got, want):
    assert len(got) == len(want)
    for (s1, e1, m1, d1), (s2, e2, m2, d2) in zip(got, want):
        assert (s1, e1) == (s2, e2)
        assert d1.dtype == d2.dtype and np.array_equal(d1, d2)
        assert m1.keys() == m2.keys()
        assert all(m1[k].dtype == m2[k].dtype and np.array_equal(m1[k], m2[k])
                   for k in m1)


# --- (a) the same answers ----------------------------------------------------


@pytest.mark.parametrize("dtype", [np.float64, np.float32])
@pytest.mark.parametrize("layout", sorted(LAYOUTS))
def test_producer_items_are_soa_point_batches_bit_for_bit(layout, dtype,
                                                          monkeypatch):
    """What the operator's producer hands its loop is what
    ``soa_point_batches`` yields on the consumer's thread."""
    conf, per_window, order = LAYOUTS[layout]
    chunks = _chunks(per_window, order)
    seen = []
    real = range_query.assembled_ahead

    def spy(*args):
        for item, asked in real(*args):
            seen.append(item)
            yield item, asked

    monkeypatch.setattr(range_query, "assembled_ahead", spy)
    got = list(_point_range(chunks, conf, dtype))
    want = list(soa_point_batches(GRID, iter(chunks), conf, dtype,
                                  counted=False))
    assert len(seen) == len(want) == len(got) >= WINDOWS
    assert all(_same_item(a, b) for a, b in zip(seen, want))
    if conf.slide_step == conf.window_size:  # every point in one window
        assert sum(w[0].count for w in want) == sum(len(c["ts"])
                                                     for c in chunks)
    assert not _producers()


def _spider_zones():
    rings = spider_polygons.polygons(31, 300, BBOX, polysize=0.01, maxseg=32,
                                     sigma=0.03)
    return [Polygon(obj_id=f"zone{i}", rings=[r]) for i, r in enumerate(rings)]


def _spider_points(rng, n):
    return spider_gaussian.positions(rng.uniform(BBOX[0], BBOX[2], n),
                                     rng.uniform(BBOX[1], BBOX[3], n), BBOX,
                                     sigma=0.03)


#: kernel -> (operator, query set, chunks)
KERNELS = {
    "points": (PointPointRangeQuery, _point_queries, _chunks),
    "pruned": (PointPolygonRangeQuery,
               lambda: generate_query_polygons(400, *BBOX, grid_size=100,
                                               seed=4),
               _chunks),
    "cell_major": (PointPolygonRangeQuery, _spider_zones,
                   lambda: _chunks(xy=_spider_points)),
}


@pytest.mark.parametrize("kernel", sorted(KERNELS))
def test_run_soa_equals_the_synchronous_loop(kernel, monkeypatch):
    make_op, queries, chunks = KERNELS[kernel]
    query_set, chunks = queries(), chunks()
    radius = 0.05 if kernel == "points" else RADIUS

    def run():
        op = make_op(TUMBLING, GRID)
        return op, list(op.run_soa(iter(chunks), query_set, radius))

    op, got = run()
    with monkeypatch.context() as m:
        m.setattr(range_query, "assembled_ahead", _synchronous)
        sync_op, want = run()
    assert op.last_range_kernel == sync_op.last_range_kernel == kernel
    _same_results(got, want)
    assert len(got) == WINDOWS and sum(len(r[3]) for r in got) > 0
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
    src = _Counted(_chunks())
    it = iter(_point_range(src))
    first = next(it)
    time.sleep(0.3)  # the producer has all the time it wants
    # window 0 fired at the first chunk of window 1; window 1 is assembled
    # beside it up to its own trigger, the first chunk of window 2
    assert first[:2] == (0, WINDOW_MS)
    assert src.pulls == 2 * PER_WINDOW + 1
    rest = list(it)
    assert len(rest) == WINDOWS - 1 and src.pulls == WINDOWS * PER_WINDOW
    # the loop pulled no chunk: every pull came from the producer's thread
    assert threading.get_ident() not in src.threads
    assert not _producers()


# --- no staleness: the paced cell's guard -------------------------------------


def test_a_result_never_waits_for_the_next_windows_chunks():
    """The source holds every chunk after window 0's trigger until the test
    has window 0's result: an overlap that assembles window 1 before it
    hands window 0 over would wait here until the gate's timeout."""
    gate = threading.Event()
    src = _Counted(_chunks(), hold_at=PER_WINDOW + 1, gate=gate)
    it = iter(_point_range(src))
    t0 = time.perf_counter()
    first = next(it)
    waited = time.perf_counter() - t0
    assert not gate.is_set() and not src.timed_out
    assert waited < 15, waited
    assert first[:2] == (0, WINDOW_MS)
    gate.set()
    rest = list(it)
    assert [r[0] for r in rest] == [WINDOW_MS, 2 * WINDOW_MS]
    assert not src.timed_out


# --- a clean close, and exceptions ----------------------------------------


@pytest.mark.parametrize("how", ["break", "close"])
def test_a_consumer_that_stops_leaves_no_thread_and_no_pull(how):
    chunks = _chunks()
    src = _Counted(chunks)
    if how == "break":
        for _result in _point_range(src):
            break
    else:
        results = _point_range(src)
        next(results)
        results.close()
    deadline = time.monotonic() + 1.0
    while _producers() and time.monotonic() < deadline:
        time.sleep(0.01)
    assert not _producers()
    pulls = src.pulls
    time.sleep(0.2)
    assert src.pulls == pulls  # no pull after the close
    assert pulls <= 2 * PER_WINDOW + 1 < len(chunks)


@pytest.mark.parametrize("fault", ["source", "assembly"])
def test_what_the_producer_raises_surfaces_in_the_loop_at_its_item(
        fault, monkeypatch):
    """The source fails at window 1's third chunk, or a chunk without ``y``
    reaches ``point_lanes`` when window 1 fires: window 0 comes out, then the
    error, with its own type, where the synchronous loop raises it."""
    chunks = _chunks()

    def run():
        if fault == "source":
            src = _Counted(chunks, raise_at=PER_WINDOW + 2)
        else:
            src = list(chunks)
            src[PER_WINDOW + 1] = {k: v for k, v in src[PER_WINDOW + 1].items()
                                   if k != "y"}
        got = []
        with pytest.raises(_SourceFailed if fault == "source"
                           else KeyError) as err:
            for r in _point_range(src):
                got.append(r)
        return got, err.type

    got, raised = run()
    with monkeypatch.context() as m:
        m.setattr(range_query, "assembled_ahead", _synchronous)
        want, want_raised = run()
    assert raised is want_raised and len(got) == len(want) == 1
    _same_results(got, want)
    assert not _producers()


# --- where the time is spent, and what engaged -------------------------------


def test_await_on_the_loop_assembly_on_the_producer_and_prefetched():
    telemetry.enable()
    try:
        got = slow_consumer(_point_range(_chunks()), [], nap_s=0.1)
        snap = telemetry.snapshot()["range"]
        spans = x_spans(telemetry.events)
    finally:
        telemetry.disable()
    loop = threading.get_ident()
    tids = lambda name: {e["tid"] for e in spans if e["name"] == name}  # noqa
    assert tids("range.await") == tids("range.window") \
        == tids("range.select") == {loop}
    assembly = set()
    for name in ("range.assemble", "soa.consolidate", "soa.center",
                 "soa.cells", "soa.pad"):
        assert tids(name) and loop not in tids(name), name
        assembly |= tids(name)
    assert len(assembly) == 1  # one producer thread
    # one wait a window, and the last ask, which finds the stream at an end
    assert len([e for e in spans if e["name"] == "range.await"]) \
        == len(got) + 1
    # after a consumer's nap every window but the first is ready when asked
    # for (the first is, where the new thread got there before the ask)
    assert snap["windows"] == len(got) == WINDOWS
    assert WINDOWS - 1 <= snap["prefetched"] <= WINDOWS


def test_the_await_metric_reads_the_span_the_loop_emits():
    (entry,) = [m for m in spec.benchmark()["per_layer"]
                if m["name"] == "range_await_us_per_event"]
    assert entry == {"name": "range_await_us_per_event", "unit": "us",
                     "better": "lower", "source": "program_span",
                     "layer": "operators", "moves": "events_per_s",
                     "workloads": ["range_poly.flood", "range_spider.flood"]}
    assert spec.metric_file("range_await_us_per_event") == {
        "reader": "program_span_us_per_event",
        "args": {"names": ["range.await"]}}
