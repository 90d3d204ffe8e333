"""The window join as a deployment runs it (``PointPointJoinQuery.run_soa``
with hot cells and more pairs than the first budget): exact against the plain
reference, capacity and budget climb and persist, nothing recompiles on a
second window of the same size, the host fetches what was found, and the
path is traced through the one wrapper. Small twins of the shapes of
``join-tdrive-2x100k`` (benchmark/configs)."""

import numpy as np
import pytest

from spatialflink_tpu.grid import UniformGrid
from spatialflink_tpu.operators import (
    PointPointJoinQuery,
    QueryConfiguration,
    QueryType,
)
from spatialflink_tpu.ops import compaction
from spatialflink_tpu.telemetry import _NULL_SPAN, telemetry
from spatialflink_tpu.utils.padding import next_bucket

from join_reference import Reference, brute_force
from span_tiling import assert_parents_tile, inside, slow_consumer, x_spans

GRID_N, SPAN = 8, 8.0
BBOX = (0.0, 0.0, SPAN, SPAN)
RADIUS = 0.3
FIRST_CAP, FIRST_BUDGET = 16, 1024
HOT = 90  # points of each side in the one hot cell: past 16, 32 and 64
WINDOW_MS = 10_000
# float32 on bbox-centred coordinates: the band the configuration states
TOL32 = 4 * float(np.finfo(np.float32).eps) * SPAN


def _side(rng, window: int, n_uniform: int = 300):
    """One side's points of one window: uniform over a little more than the
    grid (so a few never join) plus a clump of ``HOT`` in cell (5, 2)."""
    x = np.concatenate([rng.uniform(-0.3, SPAN + 0.3, n_uniform),
                        rng.uniform(5.1, 5.9, HOT)])
    y = np.concatenate([rng.uniform(-0.3, SPAN + 0.3, n_uniform),
                        rng.uniform(2.1, 2.9, HOT)])
    ts = window * WINDOW_MS + np.sort(rng.integers(0, WINDOW_MS, len(x)))
    return {"ts": ts.astype(np.int64), "x": x, "y": y,
            "oid": np.arange(len(x), dtype=np.int64)}


def _streams(seed: int, windows: int = 2):
    rng = np.random.default_rng(seed)
    left = [_side(rng, w) for w in range(windows)]
    right = [_side(rng, w) for w in range(windows)]
    return left, right


def _operator(backend):
    conf = QueryConfiguration(QueryType.WindowBased, window_size=10,
                              slide_step=10)
    grid = UniformGrid(GRID_N, 0.0, SPAN, 0.0, SPAN)
    return PointPointJoinQuery(conf, grid, cap=FIRST_CAP, join_backend=backend)


def _run(op, left, right, dtype):
    return op.run_soa(iter(left), iter(right), RADIUS,
                      max_pairs=FIRST_BUDGET, dtype=dtype)


@pytest.fixture
def traced():
    telemetry.enable()
    yield telemetry
    telemetry.disable()


def test_reference_equals_the_double_loop():
    rng = np.random.default_rng(5)
    a, b = _side(rng, 0, 150), _side(rng, 0, 140)
    ref = Reference(bbox=BBOX, grid_cells=GRID_N, radius=RADIUS, tol=0.0)
    li, ri, d = ref.pairs(a["x"], a["y"], b["x"], b["y"])
    inside_a = ref.in_grid(a["x"], a["y"])
    inside_b = ref.in_grid(b["x"], b["y"])
    assert not inside_a.all() and not inside_b.all()  # some never join
    want = [(i, j, dd) for i, j, dd in
            brute_force(a["x"], a["y"], b["x"], b["y"], RADIUS)
            if inside_a[i] and inside_b[j]]
    assert len(want) > HOT  # the clump pairs up
    assert list(zip(li.tolist(), ri.tolist())) == [(i, j) for i, j, _ in want]
    np.testing.assert_allclose(d, [dd for _, _, dd in want], rtol=0, atol=1e-15)


def test_reference_reports_what_is_wrong():
    rng = np.random.default_rng(6)
    a, b = _side(rng, 0, 100), _side(rng, 0, 100)
    ref = Reference(bbox=BBOX, grid_cells=GRID_N, radius=RADIUS, tol=TOL32)
    want = ref.pairs(a["x"], a["y"], b["x"], b["y"])
    li, ri, d = (np.asarray(v) for v in want)
    n = len(li)
    n_right = len(b["x"])
    pad = lambda v, fill: np.concatenate([v, np.full(7, fill, v.dtype)])
    good = (pad(li, -1), pad(ri, -1), pad(d, np.inf), n, 0, n_right)
    assert ref.compare(want, *good) == []
    assert "short" in ref.compare(want, *good[:4], 3, n_right)[0]
    assert "missing" in ref.compare(
        want, pad(li[1:], -1), pad(ri[1:], -1), pad(d[1:], np.inf), n - 1, 0,
        n_right)[0]
    assert "twice" in ref.compare(
        want, np.r_[li, li[:1]], np.r_[ri, ri[:1]], np.r_[d, d[:1]], n + 1, 0,
        n_right)[0]
    far = int(np.argmax(np.hypot(a["x"][0] - b["x"], a["y"][0] - b["y"])))
    assert "beyond" in ref.compare(
        want, np.r_[li, 0], np.r_[ri, far], np.r_[d, 0.1], n + 1, 0,
        n_right)[0]
    assert "distances" in ref.compare(
        want, pad(li, -1), pad(ri, -1), pad(d + 1e-3, np.inf), n, 0,
        n_right)[0]


@pytest.mark.parametrize("dtype", [np.float64, np.float32],
                         ids=["float64", "float32"])
@pytest.mark.parametrize("backend", ["pallas_interpret", "xla"])
def test_exact_with_hot_cells_and_more_pairs_than_the_budget(
        backend, dtype, traced):
    left, right = _streams(seed=11)
    op = _operator(backend)
    # float64 through XLA is the reference's own arithmetic; every other
    # combination computes in float32 on centred coordinates
    exact = backend == "xla" and dtype is np.float64
    ref = Reference(bbox=BBOX, grid_cells=GRID_N, radius=RADIUS,
                    tol=1e-12 if exact else TOL32)
    compiles = []
    got = []
    for out in _run(op, left, right, dtype):
        got.append(out)
        compiles.append(len(telemetry.compile_events))
    assert [(o[0], o[1]) for o in got] == [(0, WINDOW_MS),
                                           (WINDOW_MS, 2 * WINDOW_MS)]
    for w, (_s, _e, li, ri, dd, count, overflow) in enumerate(got):
        lw, rw = left[w], right[w]
        want = ref.pairs(lw["x"], lw["y"], rw["x"], rw["y"])
        assert count > FIRST_BUDGET
        assert overflow == 0
        assert ref.compare(want, li, ri, dd, count, overflow,
                           len(rw["x"])) == []
        # only what was found crosses: the padding bucket of the count
        assert len(li) == len(ri) == len(dd) == next_bucket(count)
        assert len(li) <= op.join_budget
    # the rung and the budget climbed, and stayed
    assert op.join_cap == 128 and op.cap == FIRST_CAP
    assert op.join_budget == next_bucket(-(-5 * max(o[5] for o in got) // 4))
    assert op.last_join_backend == ("xla" if backend == "xla" else "pallas")
    # a second window of the same size: no new signature anywhere
    assert compiles[1] == compiles[0]
    j = telemetry.snapshot()["join"]
    assert j["windows"] == 2 and j["pairs"] == got[0][5] + got[1][5]
    assert j["cap_retries"] == 0  # the occupancy pick held
    assert j["budget_retries"] == 1  # the first window, once
    assert j["cap"] == 128 and j["budget"] == op.join_budget
    # the Pallas extraction counts its vector passes, each carrying the
    # next hit of every left row of a block: fewer passes than pairs; the
    # XLA program has no such loop and counts none
    if backend == "xla":
        assert j.get("peel_passes", 0) == 0
    else:
        assert 0 < j["peel_passes"] < j["pairs"]


def test_overflow_is_retried_never_yielded(monkeypatch):
    """The safety net under the occupancy pick: a capacity that turns out
    too small costs a re-run one rung up."""
    from spatialflink_tpu.operators import join_query

    monkeypatch.setattr(join_query, "max_cell_count", lambda *a: 0)
    left, right = _streams(seed=12, windows=1)
    op = _operator("xla")
    telemetry.enable()
    try:
        (_s, _e, li, ri, dd, count, overflow), = _run(op, left, right,
                                                      np.float64)
        retries = telemetry.snapshot()["join"]["cap_retries"]
    finally:
        telemetry.disable()
    assert overflow == 0 and op.join_cap == 128 and retries == 3
    ref = Reference(bbox=BBOX, grid_cells=GRID_N, radius=RADIUS, tol=1e-12)
    want = ref.pairs(left[0]["x"], left[0]["y"], right[0]["x"], right[0]["y"])
    assert ref.compare(want, li, ri, dd, count, overflow,
                       len(right[0]["x"])) == []


def test_banded_extraction_equals_one_band():
    """The XLA extraction never holds the whole grid's pair mask: bands of
    cell rows give the same pairs, in the one-band order band after band."""
    import jax.numpy as jnp

    from spatialflink_tpu.ops.join import join_window_bucketed

    rng = np.random.default_rng(13)
    a, b = _side(rng, 0), _side(rng, 0)
    grid = UniformGrid(GRID_N, 0.0, SPAN, 0.0, SPAN)

    def args(s):
        xy = np.stack([s["x"], s["y"]], axis=1)
        return (jnp.asarray(xy), jnp.ones(len(xy), bool),
                jnp.asarray(grid.assign_cells_np(xy)))

    def run(band_rows):
        res = join_window_bucketed(
            *args(a), *args(b), grid_n=GRID_N, layers=1, radius=RADIUS,
            cap_left=128, cap_right=128, max_pairs=8192, band_rows=band_rows)
        n = int(res.count)
        assert int(res.overflow) == 0 and n <= 8192
        assert (np.asarray(res.left_index)[n:] == -1).all()
        return sorted(zip(np.asarray(res.left_index)[:n].tolist(),
                          np.asarray(res.right_index)[:n].tolist(),
                          np.asarray(res.dist)[:n].tolist()))

    whole = run(GRID_N)
    assert len(whole) > FIRST_BUDGET
    for band_rows in (3, 1):  # 3 bands, the last one padded; a band a row
        banded = run(band_rows)
        assert [p[:2] for p in banded] == [p[:2] for p in whole]
        np.testing.assert_allclose([p[2] for p in banded],
                                   [p[2] for p in whole], rtol=1e-14)


def test_one_dispatch_span_a_call_and_bytes_of_what_was_found(traced):
    left, right = _streams(seed=14)
    op = _operator("pallas_interpret")
    got = list(_run(op, left, right, np.float32))
    spans = [e for e in telemetry.events if e.get("ph") == "X"]
    by = lambda name: [e for e in spans if e["name"] == name]
    j = telemetry.snapshot()["join"]
    calls = j["windows"] + j["cap_retries"] + j["budget_retries"]
    assert len(by("dispatch:join_window_pallas")) == calls == 3
    assert not by("dispatch:join_window_bucketed")
    # two fetches a call-that-held, one (count, overflow) a retry
    d2h = by("d2h")
    assert len(d2h) == calls + j["windows"]
    # int32 + int32 + float32 a pair slot, and the three int32 scalars a
    # call (count, overflow, and the pass count that rides with them)
    found = sum(12 * len(o[2]) for o in got)
    assert sum(e["args"]["bytes"] for e in d2h) == found + 12 * calls
    assert telemetry.d2h_bytes == found + 12 * calls
    assert all(len(o[2]) == next_bucket(o[5]) for o in got)
    # h2d: one ship a window, whatever the retries
    assert len(by("h2d")) == j["windows"]
    assert by("join.assemble")
    # every program of the path went through the one wrapper
    table = {}
    for r in telemetry.kernel_table():  # a row a (kernel, signature)
        table[r["kernel"]] = table.get(r["kernel"], 0) + r["calls"]
    assert table["join_window_pallas"] == calls
    assert table["head_pairs"] >= j["windows"]


def test_join_window_parent_tiles_the_window_and_no_consumer_time(traced):
    """One ``join.window`` a two-sided window, from the loop's ask for it to
    the hand-back: the wait for the producer, the capacity pick, the ship,
    every run of the program and both fetches lie inside it, the consumer's
    time does not, and the pairs are the telemetry-off run's. Both sides'
    assembly runs on the producer thread: none of it lies in the loop's
    parent."""
    left, right = _streams(seed=14)
    telemetry.disable()
    plain = list(_run(_operator("pallas_interpret"), left, right, np.float32))
    telemetry.enable()
    naps = []
    got = slow_consumer(
        _run(_operator("pallas_interpret"), left, right, np.float32), naps)
    events = x_spans(telemetry.events)
    assert len(got) == len(plain) == 2
    for a, b in zip(plain, got):
        assert a[:2] == b[:2] and a[5:] == b[5:]
        assert all(np.array_equal(u, v) for u, v in zip(a[2:5], b[2:5]))
    parents, inner = assert_parents_tile(events, "join.window", naps)
    assert [p["args"]["n"] for p in parents] == [
        len(lw["ts"]) + len(rw["ts"]) for lw, rw in zip(left, right)]
    loop = parents[0]["tid"]
    assembly = ("join.assemble_left", "join.assemble", "soa.consolidate",
                "soa.center", "soa.cells", "soa.pad")
    # each side's passes, once a window, inside its own assembly span, all
    # on one other thread
    assert {e["tid"] for e in events if e["name"] in assembly} != {loop}
    assert len({e["tid"] for e in events if e["name"] in assembly}) == 1
    for soa in assembly[2:]:
        assert sum(e["name"] == soa for e in events) == 4
    j = telemetry.snapshot()["join"]
    retries = [j["cap_retries"] + j["budget_retries"], 0]  # paid once
    for p, names, again in zip(parents, inner, retries):
        assert p["tid"] == loop
        assert not set(names) & set(assembly)
        for once in ("join.await", "join.capacity", "h2d"):
            assert names.count(once) == 1, (once, names)
        assert names.count("dispatch:join_window_pallas") == 1 + again
        assert names.count("d2h") == names.count("d2h.wait") == 2 + again
        assert names.index("join.await") < names.index("h2d") \
            < names.index("join.capacity")
    # outside every parent on the loop's thread: nothing but the last ask,
    # which finds both streams at an end
    assert [e["name"] for e in events if e["tid"] == loop
            and e["name"] != "join.window"
            and not any(inside(e, p) for p in parents)] == ["join.await"]


def test_one_sided_windows_emit_no_parent_and_stale_lefts_neither(traced):
    """A right-only window goes to the consumer while the left side's next
    window is in hand: the one-sided window emits no parent, and the
    two-sided window after it opens its parent at the loop's ask, after the
    consumer's nap, so it holds none of the consumer's time."""
    left, right = _streams(seed=16, windows=3)
    del left[0]  # window 0 is the right side's alone
    naps = []
    got = slow_consumer(_run(_operator("xla"), left, right, np.float64), naps)
    assert [o[5] > 0 for o in got] == [False, True, True]
    parents, _inner = assert_parents_tile(telemetry.events, "join.window",
                                          naps)
    assert len(parents) == 2  # windows 1 and 2; window 0 emits none


def test_null_span_when_telemetry_is_off():
    assert not telemetry.enabled
    assert telemetry.span("join.assemble") is _NULL_SPAN
    before = telemetry.snapshot().get("join")
    left, right = _streams(seed=15, windows=1)
    op = _operator("xla")
    (out,) = list(_run(op, left, right, np.float64))
    assert out[6] == 0 and out[5] > FIRST_BUDGET
    assert telemetry.snapshot().get("join") == before  # nothing recorded


def test_capacity_ladder_has_one_home():
    pick = compaction.pick_capacity
    # inside the ladder nothing changed
    assert pick(5, 64) == 8 and pick(64, 64) == 64 and pick(200, 64) == 64
    # the point join's ladder: first rung the constructor's cap, open top
    assert pick(10, 64, minimum=64, open_top=True) == 64
    assert pick(65, 64, minimum=64, open_top=True) == 128
    assert pick(129, 128, minimum=128, open_top=True) == 256
    assert pick(50, 48, minimum=48, open_top=True) == 64
    cells = np.array([3, 3, 3, 7, 64, 64, 64, 64])
    valid = np.array([1, 1, 0, 1, 1, 1, 1, 1], bool)
    assert compaction.max_cell_count(cells, valid, 64) == 2  # 64 = outside
    assert compaction.max_cell_count(cells[:0], valid[:0], 64) == 0


def test_option_5_path_climbs_instead_of_dropping():
    """``run`` / ``query_panes`` (CLI option 5) share the pick: a hot cell
    past ``cap`` used to come back as ``overflow > 0`` and a short join."""
    from spatialflink_tpu.models.objects import Point

    rng = np.random.default_rng(16)
    a, b = _side(rng, 0, 60), _side(rng, 0, 60)
    pts = lambda s, tag: [
        Point(obj_id=f"{tag}{i}", timestamp=int(t), x=float(x), y=float(y))
        for i, (t, x, y) in enumerate(zip(s["ts"], s["x"], s["y"]))]
    op = _operator(None)
    (res,) = list(op.run(iter(pts(a, "a")), iter(pts(b, "b")), RADIUS))
    assert res.overflow == 0 and op.join_cap == 128
    ref = Reference(bbox=BBOX, grid_cells=GRID_N, radius=RADIUS, tol=1e-12)
    li, ri, _d = ref.pairs(a["x"], a["y"], b["x"], b["y"])
    assert {(p.obj_id, q.obj_id) for p, q, _ in res.pairs} == {
        (f"a{i}", f"b{j}") for i, j in zip(li.tolist(), ri.tolist())}
