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
- ``wire.prepare`` once a pane, ``snapshot()["wire"]`` counting.
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


def _run(panes, strategy="xla"):
    op = PointPointKNNQuery(CONF, GRID)
    out = [(s, e, np.asarray(oo), np.asarray(dd), nv)
           for s, e, oo, dd, nv in op.run_wire_panes(
               panes, Point(x=QUERY[0], y=QUERY[1]), RADIUS, K, IDS, WF,
               start_ms=T0, strategy=strategy, interpret=True)]
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
    # the assembler packs through the same half: it refuses too, where it
    # used to wrap the id onto another object's segment
    asm = WirePaneAssembler(WF, SLIDE_MS, T0)
    asm.feed({"ts": np.asarray([T0]), "x": x[:1], "y": y[:1],
              "oid": np.asarray([40_000])})
    with pytest.raises(ValueError, match="int16"):
        asm.flush()


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
