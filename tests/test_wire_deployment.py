"""The sidecar deployment (``knn-beijing-1m-wire``): a windowing tier outside
this process packs each slide into a (3, n) uint16 pane with the library's
public producer half and hands it straight to ``run_wire_panes``.

- ``WireFormat.pack_pane`` is byte-identical to the panes
  ``WirePaneAssembler`` emits for the same events, to the expression the
  assembler used before it called ``pack_pane``, and to the plain
  reference's own quantisation;
- producer-packed panes through ``run_wire_panes`` (XLA and interpreted
  Pallas) equal the plain reference's windows: a size off the bucket
  ladder, sizes that change from pane to pane, empty panes in a gap;
- the direct route and the SoA -> assembler route give the same windows;
- bad producer input is refused, never wrapped or dropped;
- ``wire.prepare`` once a pane, ``snapshot()["wire"]`` counting;
- the write-once ``WirePaneAssembler``: its panes equal ``pack_pane`` of the
  same rows whatever the chunking (gaps, boundaries, lists, float32, a
  buffer that grows), an emitted pane is the caller's own, a refused chunk
  leaves the assembler as it was, a snapshot (today's form and the
  ``pend_*`` form every older checkpoint has) resumes to the same panes,
  and the ``assembler_*`` counters record once a closed pane.
"""

import numpy as np
import pytest

from benchmark.references.knn_beijing import Reference
from spatialflink_tpu.grid import UniformGrid
from spatialflink_tpu.models.objects import Point
from spatialflink_tpu.operators import QueryConfiguration, QueryType
from spatialflink_tpu.operators.knn_query import PointPointKNNQuery
from spatialflink_tpu.ops.compaction import wire_pane_bucket
from spatialflink_tpu.streams.wire import WireFormat, WirePaneAssembler
from spatialflink_tpu.telemetry import telemetry
from span_tiling import assert_parents_tile, inside, slow_consumer, x_spans

BBOX = (115.5, 39.6, 117.6, 41.1)  # min_x, min_y, max_x, max_y
GRID = UniformGrid(100, BBOX[0], BBOX[2], BBOX[1], BBOX[3])
WF = WireFormat.for_grid(GRID)
QUERY = (116.14319, 40.07271)
# Wider than the deployment's 0.05 so that a few hundred points a pane put
# more than k objects inside.
RADIUS, K, IDS = 0.3, 8, 64
T0, SLIDE_MS, PPW = 1_700_000_000_000, 5_000, 2
CONF = QueryConfiguration(QueryType.WindowBased, window_size=10, slide_step=5)


def _events(rng, n, ids=IDS):
    return (rng.uniform(BBOX[0], BBOX[2], n), rng.uniform(BBOX[1], BBOX[3], n),
            rng.integers(0, ids, n).astype(np.int64))


def _reference(ids=IDS):
    return Reference(bbox=BBOX, query=QUERY, radius=RADIUS, k=K, ids=ids)


def _run(panes, strategy="xla", consume=list):
    op = PointPointKNNQuery(CONF, GRID)
    out = [(s, e, np.asarray(oo), np.asarray(dd), nv)
           for s, e, oo, dd, nv in consume(op.run_wire_panes(
               panes, Point(x=QUERY[0], y=QUERY[1]), RADIUS, K, IDS, WF,
               start_ms=T0, strategy=strategy, interpret=True))]
    assert op.last_wire_digest_kind in (strategy, None)
    return out


@pytest.mark.parametrize("n", [0, 1, 127, 128, 129, 50_000])
def test_pack_pane_is_byte_identical_to_the_assemblers_pane(n):
    rng = np.random.default_rng(1000 + n)
    x, y, oid = _events(rng, n, ids=16_384)
    ts = T0 + np.sort(rng.integers(0, SLIDE_MS, n)).astype(np.int64)
    asm = WirePaneAssembler(WF, SLIDE_MS, T0)
    # the pane closes when an event of the next slide arrives
    closer = {"ts": np.asarray([T0 + SLIDE_MS]), "x": np.asarray([116.0]),
              "y": np.asarray([40.0]), "oid": np.asarray([3])}
    emitted = asm.feed({"ts": ts, "x": x, "y": y, "oid": oid}) \
        + asm.feed(closer)
    (pane,) = emitted
    packed = WF.pack_pane(x, y, oid)
    assert packed.shape == (3, n) and packed.dtype == np.uint16
    assert packed.flags.c_contiguous and pane.flags.c_contiguous
    assert pane.dtype == packed.dtype and pane.shape == packed.shape
    assert pane.tobytes() == packed.tobytes()
    # what WirePaneAssembler._pack computed before it called pack_pane
    before = np.ascontiguousarray(np.concatenate(
        [WF.quantize(np.stack([x, y], axis=1)),
         np.asarray(oid, np.int16).view(np.uint16)[:, None]], axis=1).T)
    assert before.tobytes() == packed.tobytes()
    # and the plain reference's own 6-byte records
    xq, yq = _reference(ids=16_384).quantize(x, y)
    assert np.array_equal(packed, np.stack([xq, yq, oid.astype(np.uint16)]))


SIZES = {
    "off_the_ladder": [300, 300, 300, 300, 300],
    "changing": [300, 260, 517, 128, 1000, 129],
    "gap": [300, 280, 0, 0, 0, 310, 0, 290],
}


@pytest.mark.parametrize("strategy", ["xla", "pallas"])
@pytest.mark.parametrize("case", sorted(SIZES))
def test_producer_panes_equal_the_plain_reference(case, strategy):
    rng = np.random.default_rng(sorted(SIZES).index(case) + 77)
    sizes = SIZES[case]
    assert any(wire_pane_bucket(n) != n for n in sizes if n)
    events = [_events(rng, n) for n in sizes]
    panes = [WF.pack_pane(*ev) for ev in events]
    got = {(s, e): (oo, dd, nv) for s, e, oo, dd, nv in _run(panes, strategy)}
    ref = _reference()
    quant = [ref.quantize(x, y) + (oid,) for x, y, oid in events]
    expected = set()
    neighbours = 0
    # window j holds panes j-PPW+1 .. j; the trailing partials flush too
    for j in range(len(sizes) + PPW - 1):
        held = [quant[i] for i in range(max(0, j - PPW + 1), j + 1)
                if i < len(sizes)]
        if not sum(len(q[2]) for q in held):
            continue  # a window none of whose panes held an event
        key = (T0 + (j - PPW + 1) * SLIDE_MS, T0 + (j + 1) * SLIDE_MS)
        expected.add(key)
        assert key in got, f"window {key} did not fire"
        mins = ref.minima(*(np.concatenate(col) for col in zip(*held)))
        oo, dd, nv = got[key]
        assert ref.compare(mins, oo, dd, nv) == [], key
        neighbours += nv
    assert set(got) == expected
    assert neighbours >= K, "degenerate: nearly nothing in radius"


def test_direct_route_and_assembler_route_give_the_same_windows():
    rng = np.random.default_rng(5)
    sizes = [400, 350, 0, 420, 380]
    events = [_events(rng, n) for n in sizes]
    chunks = []
    for i, (x, y, oid) in enumerate(events):
        ts = T0 + i * SLIDE_MS + np.sort(
            rng.integers(0, SLIDE_MS, len(x))).astype(np.int64)
        for a in range(0, len(x), 150):  # a consumer's poll batches
            chunks.append({"ts": ts[a:a + 150], "x": x[a:a + 150],
                           "y": y[a:a + 150], "oid": oid[a:a + 150]})
    asm = WirePaneAssembler(WF, SLIDE_MS, T0)
    assembled = [p for ch in chunks for p in asm.feed(ch)] + asm.flush()
    direct = [WF.pack_pane(*ev) for ev in events]
    assert [p.tobytes() for p in assembled] == [p.tobytes() for p in direct]
    a, b = _run(assembled), _run(direct)
    assert len(a) == len(b) >= len(sizes)
    for (s1, e1, o1, d1, n1), (s2, e2, o2, d2, n2) in zip(a, b):
        assert (s1, e1, n1) == (s2, e2, n2)
        assert np.array_equal(o1, o2) and np.array_equal(d1, d2)


def _good_pane():
    return WF.pack_pane(*_events(np.random.default_rng(9), 200))


def _top_bit(pane):
    pane[2, 5] = 0x8000  # a negative int16 at the producer
    return pane


def _past_num_segments(pane):
    pane[2, 5] = IDS
    return pane


BAD_PANES = {
    "id_bits_at_or_over_0x8000": _top_bit,
    "id_at_num_segments": _past_num_segments,
    "row_major_n_by_3": lambda pane: np.ascontiguousarray(pane.T),
    "wrong_dtype": lambda pane: pane.view(np.int16),
}


@pytest.mark.parametrize("case", sorted(BAD_PANES))
def test_run_wire_panes_refuses_bad_producer_input(case):
    """Before the unsigned check an id with its top bit set passed (it read
    negative) and the segment reductions dropped the point without a word."""
    good = _good_pane()
    assert len(_run([good])) == PPW  # the same pane, untouched, is taken
    with pytest.raises(ValueError):
        _run([BAD_PANES[case](good.copy())])


def test_pack_pane_refuses_an_id_outside_int16():
    x, y, oid = _events(np.random.default_rng(3), 50)
    for bad in (0x8000, 40_000, -0x8001):
        oid2 = oid.copy()
        oid2[7] = bad
        with pytest.raises(ValueError, match="int16"):
            WF.pack_pane(x, y, oid2)
    with pytest.raises(ValueError, match="one length"):
        WF.pack_pane(x, y[:-1], oid)
    # the assembler quantises through the same half: it refuses too, at the
    # feed that brings the id, where it once wrapped the id onto another
    # object's segment
    asm = WirePaneAssembler(WF, SLIDE_MS, T0)
    with pytest.raises(ValueError, match="int16"):
        asm.feed({"ts": np.asarray([T0]), "x": x[:1], "y": y[:1],
                  "oid": np.asarray([40_000])})
    assert asm.flush() == []


def test_wire_prepare_span_and_wire_counters_once_a_pane():
    rng = np.random.default_rng(11)
    sizes = [300, 0, 517, 128]
    panes = [WF.pack_pane(*_events(rng, n)) for n in sizes]
    plain = _run(panes)
    telemetry.enable()
    try:
        traced = _run(panes)
        spans = [e for e in telemetry.events if e["name"] == "wire.prepare"]
        h2d = [e for e in telemetry.events if e["name"] == "h2d"]
        wire = telemetry.snapshot()["wire"]
    finally:
        telemetry.disable()
    for (s1, e1, o1, d1, n1), (s2, e2, o2, d2, n2) in zip(plain, traced):
        assert (s1, e1, n1) == (s2, e2, n2) and np.array_equal(d1, d2)
    buckets = [wire_pane_bucket(n) for n in sizes]
    assert [(e["args"]["n"], e["args"]["bucket"]) for e in spans] \
        == list(zip(sizes, buckets))
    # the leaf rule: the ship's own span stays where it is, outside prepare
    assert len(h2d) == len(panes)
    for prep, ship in zip(spans, h2d):
        assert prep["ts"] + prep["dur"] <= ship["ts"]
    assert wire == {"panes": len(sizes), "points": sum(sizes),
                    "lanes": sum(buckets),
                    "pad_lanes": sum(buckets) - sum(sizes)}


def test_wire_pane_parent_tiles_the_pane_and_no_consumer_time():
    """One ``wire.pane`` a received pane, from its receipt to just before its
    result is yielded (to the end of its body where it yields none): every
    span of the loop lies inside one, a result's two ``d2h`` with their
    ``d2h.wait`` and the ``wire.slice`` between them, and nothing of the
    consumer; the windows are the telemetry-off run's, bit for bit."""
    rng = np.random.default_rng(12)
    # pane 0 and pane 3 close windows that hold no event: they yield none
    sizes = [0, 300, 0, 0, 517, 128]
    panes = [WF.pack_pane(*_events(rng, n)) for n in sizes]
    plain = _run(panes)
    naps = []
    telemetry.enable()
    try:
        traced = _run(panes, consume=lambda r: slow_consumer(r, naps))
        events = x_spans(telemetry.events)
    finally:
        telemetry.disable()
    assert len(plain) == len(traced) == len(naps) == 5  # 4 + the last flush
    for (s1, e1, o1, d1, n1), (s2, e2, o2, d2, n2) in zip(plain, traced):
        assert (s1, e1, n1) == (s2, e2, n2)
        assert np.array_equal(o1, o2) and np.array_equal(d1, d2)
    parents, inner = assert_parents_tile(events, "wire.pane", naps)
    assert [p["args"]["n"] for p in parents] == sizes
    # outside every parent: only the trailing partial window's merge and
    # fetch, which no received pane stands behind
    loose = [e["name"] for e in events if e["name"] != "wire.pane"
             and not any(inside(e, p) for p in parents)]
    assert sorted(set(loose)) == [
        "d2h", "d2h.wait", "dispatch:knn_merge_digest_list", "wire.slice"]
    every = ["wire.prepare", "h2d", "wire.step_args", "wire.merge_args"]
    result = ["d2h", "d2h.wait", "wire.slice", "d2h", "d2h.wait"]
    for names, yields in zip(inner, [False, True, True, False, True, True]):
        kernels = [n for n in names if n.startswith("dispatch:")]
        rest = [n for n in names if not n.startswith("dispatch:")]
        assert sorted(rest) == sorted(every + (result if yields else []))
        assert len(kernels) == 1 + yields  # the digest step, then the merge
        if yields:
            i = names.index("wire.slice")
            assert names[:i].count("d2h") == names[i:].count("d2h") == 1


# -- the write-once assembler ------------------------------------------------

def _stream(sizes, seed=0):
    """Rows of consecutive panes of the given sizes, in time order: the four
    columns and ``pack_pane`` of each pane's rows."""
    rng = np.random.default_rng(seed)
    x, y, oid = _events(rng, sum(sizes), ids=16_384)
    ts = np.concatenate([
        T0 + i * SLIDE_MS + np.sort(rng.integers(0, SLIDE_MS, n))
        for i, n in enumerate(sizes)]).astype(np.int64)
    edges = np.concatenate([[0], np.cumsum(sizes)])
    packed = [WF.pack_pane(x[a:b], y[a:b], oid[a:b])
              for a, b in zip(edges[:-1], edges[1:])]
    return {"ts": ts, "x": x, "y": y, "oid": oid}, packed


def _cut(cols, a, b):
    return {key: v[a:b] for key, v in cols.items()}


def _feed_all(asm, cols, chunk, lo=0):
    n = len(cols["ts"])
    return [p for a in range(lo, n, chunk)
            for p in asm.feed(_cut(cols, a, min(a + chunk, n)))]


def _same_panes(got, want):
    assert len(got) == len(want)
    for g, w in zip(got, want):
        assert g.dtype == np.uint16 and g.shape == w.shape
        assert g.flags.c_contiguous
        assert g.tobytes() == w.tobytes()


def _state_bytes(asm):
    st = asm.state()
    return {key: (v.tobytes(), v.shape) if isinstance(v, np.ndarray) else v
            for key, v in st.items()}


CHUNKINGS = {"1": 1, "999": 999, "10000": 10_000, "one_chunk": 10 ** 9}


@pytest.mark.parametrize("chunking", sorted(CHUNKINGS))
def test_assembler_panes_equal_pack_pane_whatever_the_chunking(chunking):
    # 10,000 rows span panes; "one_chunk" is larger than every pane
    cols, packed = _stream([6_000, 4_500, 0, 5_500, 14_000], seed=21)
    asm = WirePaneAssembler(WF, SLIDE_MS, T0)
    got = _feed_all(asm, cols, CHUNKINGS[chunking]) + asm.flush()
    _same_panes(got, packed)
    assert asm.flush() == [] and asm.state()["pane"].shape == (3, 0)


def test_assembler_chunk_across_a_gap_closes_three_panes_in_order():
    cols, packed = _stream([700, 0, 0, 650], seed=22)
    asm = WirePaneAssembler(WF, SLIDE_MS, T0)
    assert asm.feed(_cut(cols, 0, 300)) == []
    got = asm.feed(_cut(cols, 300, 1_000))  # rows of panes 0 and 3
    assert [p.shape for p in got] == [(3, 700), (3, 0), (3, 0)]
    _same_panes(got + asm.feed(_cut(cols, 1_000, 1_350)) + asm.flush(),
                packed)


@pytest.mark.parametrize("last", ["just_before", "on_the_boundary"])
def test_assembler_chunk_ending_at_a_pane_boundary(last):
    cols, packed = _stream([400, 300], seed=23)
    cols["ts"][399] = T0 + SLIDE_MS - 1
    cols["ts"][400] = T0 + SLIDE_MS  # the first instant of pane 1
    asm = WirePaneAssembler(WF, SLIDE_MS, T0)
    if last == "just_before":
        assert asm.feed(_cut(cols, 0, 400)) == []  # nothing closes it yet
        got = asm.feed(_cut(cols, 400, 700))
    else:
        got = asm.feed(_cut(cols, 0, 401))  # the boundary row joins pane 1
        assert asm.state()["pane"].shape == (3, 1)
        got = got + asm.feed(_cut(cols, 401, 700))
    _same_panes(got + asm.flush(), packed)


def _as_lists(cols):
    return {key: v.tolist() for key, v in cols.items()}, T0


def _as_float32(cols):
    return dict(cols, x=cols["x"].astype(np.float32),
                y=cols["y"].astype(np.float32)), T0


def _as_int32(cols):
    # times since the stream's start, so that they fit
    return dict(cols, ts=(cols["ts"] - T0).astype(np.int32),
                oid=cols["oid"].astype(np.int32)), 0


INPUT_FORMS = {"lists": _as_lists, "float32_coordinates": _as_float32,
               "int32_ids_and_times": _as_int32}


@pytest.mark.parametrize("form", sorted(INPUT_FORMS))
def test_assembler_takes_lists_and_any_numeric_dtype(form):
    sizes = [350, 0, 420]
    cols, _ = _stream(sizes, seed=24)
    conv, start = INPUT_FORMS[form](cols)
    asm = WirePaneAssembler(WF, SLIDE_MS, start)
    got = _feed_all(asm, conv, 100) + asm.flush()
    # pack_pane of what was handed in (float32 upcasts exactly to float64)
    edges = np.concatenate([[0], np.cumsum(sizes)])
    _same_panes(got, [WF.pack_pane(conv["x"][a:b], conv["y"][a:b],
                                   conv["oid"][a:b])
                      for a, b in zip(edges[:-1], edges[1:])])


def _assembler_counters():
    return {key[len("assembler_"):]: v
            for key, v in telemetry.snapshot().get("wire", {}).items()
            if key.startswith("assembler_")}


def test_assembler_buffer_grows_for_a_pane_four_times_the_last():
    sizes = [2_000, 8_000, 8_000, 1_000]
    cols, packed = _stream(sizes, seed=25)
    bounds = np.cumsum(sizes)
    asm = WirePaneAssembler(WF, SLIDE_MS, T0)
    telemetry.enable()
    try:
        got, grows = [], []
        for a, b in zip(np.concatenate([[0], bounds[:-1]]), bounds):
            # a pane's rows in 500-row chunks; the next pane's first closes it
            got += _feed_all(asm, _cut(cols, a, b), 500)
            grows.append(_assembler_counters().get("grows", 0))
        got += asm.flush()
        final = _assembler_counters()
    finally:
        telemetry.disable()
    _same_panes(got, packed)
    # recorded when pane i closes, i.e. while pane i+1 arrives: 1,024 -> 2,048
    # for the first pane, -> 4,096 -> 8,192 for the second, then never again
    assert grows == [0, 1, 3, 3] and final["grows"] == 3
    assert final["rows"] == sum(sizes)


def test_an_emitted_pane_is_the_callers_own():
    cols, packed = _stream([900, 1_100, 1_000, 950], seed=26)
    asm = WirePaneAssembler(WF, SLIDE_MS, T0)
    (first,) = _feed_all(asm, _cut(cols, 0, 1_000), 100)
    assert first.flags.c_contiguous and first.flags.owndata
    held = first.tobytes()
    assert held == packed[0].tobytes()
    later = _feed_all(asm, cols, 100, lo=1_000) + asm.flush()
    assert len(later) == 3  # feed went on for more than two panes
    assert first.tobytes() == held
    first[:] = 0  # and the caller may write it: the assembler never reads it
    _same_panes(later, packed[1:])


def _bad_id(cols, prev_last):
    cols["oid"][450] = 40_000  # past the pane boundary at the chunk's row 300


def _before_the_open_pane(cols, prev_last):
    cols["ts"][:] -= 2 * SLIDE_MS


def _before_the_last_chunk(cols, prev_last):
    cols["ts"][0] = prev_last - 1  # still inside the open pane


def _inside_the_chunk(cols, prev_last):
    cols["ts"][450] = cols["ts"][449] - 1


def _two_lengths(cols, prev_last):
    cols["x"] = cols["x"][:-1]


REFUSED = {
    "id_outside_int16": (_bad_id, "int16"),
    "before_the_open_pane": (_before_the_open_pane, "out-of-order"),
    "before_the_previous_chunks_last": (_before_the_last_chunk,
                                        "out-of-order"),
    "inside_the_chunk": (_inside_the_chunk, "out-of-order"),
    "columns_of_two_lengths": (_two_lengths, "one length"),
}


@pytest.mark.parametrize("case", sorted(REFUSED))
def test_a_refused_chunk_leaves_the_assembler_as_it_was(case):
    """The offending chunk spans a pane boundary: an assembler that wrote
    before it checked would have closed pane 1 or kept half the chunk."""
    spoil, match = REFUSED[case]
    cols, packed = _stream([500, 400, 300], seed=27)
    asm = WirePaneAssembler(WF, SLIDE_MS, T0)
    got = _feed_all(asm, _cut(cols, 0, 600), 200)  # 100 rows into pane 1
    prev_last = int(cols["ts"][599])
    assert prev_last - 1 > T0 + SLIDE_MS
    before = _state_bytes(asm)
    bad = {key: v.copy() for key, v in _cut(cols, 600, 1_100).items()}
    spoil(bad, prev_last)
    with pytest.raises(ValueError, match=match):
        asm.feed(bad)
    assert _state_bytes(asm) == before
    got += asm.feed(_cut(cols, 600, 1_100)) + _feed_all(asm, cols, 50, 1_100)
    _same_panes(got + asm.flush(), packed)


CUTS = {"before_anything": 0, "inside_a_pane": 1_234,
        "first_row_of_a_pane": 2_001, "after_a_gap": 3_501,
        "everything_fed": 5_000}


@pytest.mark.parametrize("cut", sorted(CUTS))
def test_assembler_snapshot_resumes_to_the_uninterrupted_panes(cut):
    cols, packed = _stream([2_000, 1_500, 0, 0, 1_500], seed=28)
    at = CUTS[cut]
    asm = WirePaneAssembler(WF, SLIDE_MS, T0)
    got = _feed_all(asm, _cut(cols, 0, at), 333)
    snap = asm.state()
    assert sorted(snap) == ["cur", "last_ts", "pane", "slide_ms",
                            "wire_origin", "wire_scale"]
    assert snap["pane"].dtype == np.uint16  # 6 B a buffered point
    asm.feed(_cut(cols, at, at + 100))  # the snapshot is a copy, not a view
    resumed = WirePaneAssembler(WF, SLIDE_MS, start_ms=0)
    resumed.restore(snap)
    got += _feed_all(resumed, cols, 333, lo=at) + resumed.flush()
    _same_panes(got, packed)
    # the in-order refusal survives the restore
    again = WirePaneAssembler(WF, SLIDE_MS, start_ms=0)
    again.restore(snap)
    if at:
        with pytest.raises(ValueError, match="out-of-order"):
            again.feed({"ts": [int(cols["ts"][at - 1]) - 1], "x": [116.0],
                        "y": [40.0], "oid": [1]})


def _legacy_snapshot(cols, at, identity=True):
    """What ``state()`` wrote before the open pane was held quantised: the
    pending float64 rows and their timestamps."""
    cur = T0 + (int(cols["ts"][at - 1]) - T0) // SLIDE_MS * SLIDE_MS \
        if at else T0
    lo = int(np.searchsorted(cols["ts"], cur, "left"))
    snap = {"cur": cur,
            "pend_ts": cols["ts"][lo:at].copy(),
            "pend_xy": np.stack([cols["x"][lo:at], cols["y"][lo:at]], axis=1),
            "pend_oid": cols["oid"][lo:at].copy()}
    if identity:
        snap.update(slide_ms=SLIDE_MS,
                    wire_origin=[float(v) for v in WF.origin],
                    wire_scale=[float(v) for v in WF.scale])
    return snap, lo


@pytest.mark.parametrize("identity", [True, False],
                         ids=["with_identity_keys", "oldest_form"])
@pytest.mark.parametrize("cut", ["before_anything", "inside_a_pane",
                                 "after_a_gap"])
def test_a_pend_form_checkpoint_restores_to_the_same_panes(cut, identity):
    cols, packed = _stream([2_000, 1_500, 0, 0, 1_500], seed=29)
    at = CUTS[cut]
    snap, lo = _legacy_snapshot(cols, at, identity)
    asm = WirePaneAssembler(WF, SLIDE_MS, start_ms=0)
    asm.restore(snap)
    now = asm.state()
    want = WF.pack_pane(cols["x"][lo:at], cols["y"][lo:at],
                        cols["oid"][lo:at])
    assert now["pane"].tobytes() == want.tobytes()
    assert now["cur"] == snap["cur"]
    assert now["last_ts"] == (int(cols["ts"][at - 1]) if at > lo
                              else snap["cur"])
    got = _feed_all(asm, cols, 333, lo=at) + asm.flush()
    done = (snap["cur"] - T0) // SLIDE_MS  # panes closed before the snapshot
    _same_panes(got, packed[done:])


@pytest.mark.parametrize("form", ["pane", "pend"])
@pytest.mark.parametrize("what", ["slide_ms", "wire_format", "pane_shape"])
def test_restore_refuses_another_slide_or_wire_format(form, what):
    cols, _ = _stream([800], seed=30)
    if form == "pane":
        asm = WirePaneAssembler(WF, SLIDE_MS, T0)
        asm.feed(_cut(cols, 0, 500))
        snap = asm.state()
    else:
        snap, _ = _legacy_snapshot(cols, 500)
    if what == "slide_ms":
        other, match = WirePaneAssembler(WF, SLIDE_MS // 5, T0), "slide_ms"
    elif what == "wire_format":
        other = WirePaneAssembler(WireFormat(0.0, 20.0, 0.0, 20.0),
                                  SLIDE_MS, T0)
        match = "wire format"
    elif form == "pane":
        other, match = WirePaneAssembler(WF, SLIDE_MS, T0), "plane-major"
        snap["pane"] = np.ascontiguousarray(snap["pane"].T)  # (n, 3)
    else:
        other, match = WirePaneAssembler(WF, SLIDE_MS, T0), "one length"
        snap["pend_xy"] = snap["pend_xy"][:-1]  # a row short
    before = _state_bytes(other)
    with pytest.raises(ValueError, match=match):
        other.restore(snap)
    assert _state_bytes(other) == before


def test_assembler_counters_once_a_closed_pane(monkeypatch):
    sizes = [50_000] * 4 + [20_000]  # the last pane stays open
    cols, packed = _stream(sizes, seed=31)
    records = []
    record = telemetry.record_wire_assembler
    monkeypatch.setattr(
        telemetry, "record_wire_assembler",
        lambda *a: (records.append(a), record(*a))[1])
    # telemetry off: the hook is called once a pane and records nothing
    idle = telemetry.snapshot()
    asm = WirePaneAssembler(WF, SLIDE_MS, T0)
    _same_panes(_feed_all(asm, cols, 10_000), packed[:4])
    assert len(records) == 4 and telemetry.snapshot() == idle
    del records[:]
    asm = WirePaneAssembler(WF, SLIDE_MS, T0)
    telemetry.enable()
    try:
        first = _feed_all(asm, _cut(cols, 0, 60_000), 10_000)
        warm = _assembler_counters()
        rest = _feed_all(asm, cols, 10_000, lo=60_000)
        total = _assembler_counters()
    finally:
        telemetry.disable()
    _same_panes(first + rest, packed[:4])
    assert len(records) == 4  # one a closed pane, none a chunk
    assert sorted(total) == ["chunks", "grows", "rows", "rows_moved"]
    # rows and chunks of closed panes only: the chunk that closes a pane is
    # counted with it, its rows past the boundary with the next
    assert total["rows"] == 200_000 and total["chunks"] == 21
    assert warm["rows"] == 50_000 and warm["grows"] >= 1
    # a steady stream: every row written once and copied once, at the
    # hand-over; the buffer keeps the capacity the first pane reached
    steady = {key: total[key] - warm[key] for key in total}
    assert steady["grows"] == 0
    assert steady["rows_moved"] == steady["rows"] == 150_000
    assert total["rows_moved"] / total["rows"] < 1.5  # the first pane's growth
