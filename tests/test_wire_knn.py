"""ops/wire_knn.py — the ONE wire→digest program of the shipped
operator (run_wire_panes). Pins:

- XLA wire step ≡ the operator SoA digest (knn_pane_digest_compact) on
  the dequantized coordinates (set equality, ≤1 ulp distances — FMA
  fusion freedom between differently-fused programs);
- Pallas strategy (interpret mode on CPU) ≡ XLA strategy, including the
  in-program overflow fallback (exact either way);
- bucket padding + n_valid can never leak padding points into results;
- run_wire_panes window parity with run_soa_panes, both strategies.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest


from spatialflink_tpu.grid import UniformGrid
from spatialflink_tpu.models.objects import Point
from spatialflink_tpu.operators import QueryConfiguration, QueryType
from spatialflink_tpu.operators.knn_query import PointPointKNNQuery
from spatialflink_tpu.ops.knn import knn_pane_digest_compact
from spatialflink_tpu.ops.wire_knn import (
    digests_agree,
    make_wire_digest_step,
    select_wire_digest_step,
    wire_digest_xla,
)
from spatialflink_tpu.streams.wire import WireFormat

GRID = UniformGrid(20, 0.0, 10.0, 0.0, 10.0)
WF = WireFormat.for_grid(GRID)
NSEG = 64


@pytest.fixture
def rng():
    return np.random.default_rng(7)


def _wire(rng, n, oid_hi=9):
    xy = np.stack([rng.uniform(0, 10, n), rng.uniform(0, 10, n)], axis=1)
    q = WF.quantize(xy)
    oid = rng.integers(0, oid_hi, n).astype(np.int16)
    wire = np.ascontiguousarray(
        np.concatenate([q, oid.view(np.uint16)[:, None]], axis=1).T
    )
    return wire, WF.dequantize_np(q), oid.astype(np.int32)


def _args(wire, n=None):
    return (
        jnp.asarray(wire),
        jnp.int32(wire.shape[1] if n is None else n),
        jnp.asarray(np.asarray([5.0, 5.0], np.float32)),
        jnp.asarray(np.asarray(WF.scale, np.float32)),
        jnp.asarray(np.asarray(WF.origin, np.float32)),
        jnp.float32(2.0),
    )


def test_xla_step_matches_operator_soa_digest(rng):
    wire, xyf, oid = _wire(rng, 1000)
    d_wire = jax.jit(
        make_wire_digest_step(num_segments=NSEG, cand=256)
    )(*_args(wire))
    d_soa = knn_pane_digest_compact(
        jnp.asarray(xyf), jnp.ones(1000, bool), None, None,
        jnp.asarray(oid), jnp.asarray(np.asarray([5.0, 5.0], np.float32)),
        np.float32(2.0), jnp.int32(0), num_segments=NSEG, cand=256,
    )
    assert digests_agree(d_wire.seg_min, d_wire.rep, d_soa.seg_min,
                         d_soa.rep)
    live = np.asarray(d_wire.seg_min) != np.finfo(np.float32).max
    assert live.sum() > 3, "degenerate: almost nothing in radius"


def test_pallas_interpret_matches_xla(rng):
    wire, _, _ = _wire(rng, 700)
    args = _args(wire)
    d_x = jax.jit(make_wire_digest_step(num_segments=NSEG))(*args)
    d_p = jax.jit(make_wire_digest_step(
        num_segments=NSEG, strategy="pallas", interpret=True,
    ))(*args)
    assert digests_agree(d_p.seg_min, d_p.rep, d_x.seg_min, d_x.rep)


def test_pallas_overflow_fallback_exact(rng):
    """More hits than max_cand ⇒ the lax.cond reruns the full XLA
    scatter digest in-program — results stay exact."""
    wire, _, _ = _wire(rng, 600)
    args = list(_args(wire))
    args[5] = jnp.float32(100.0)  # everything in radius: 600 hits
    d_p = jax.jit(make_wire_digest_step(
        num_segments=NSEG, strategy="pallas", interpret=True,
        max_cand=128,
    ))(*args)
    d_x = jax.jit(make_wire_digest_step(num_segments=NSEG))(*args)
    live = np.asarray(d_x.seg_min) != np.finfo(np.float32).max
    assert live.sum() == 9  # every oid present at this radius
    assert digests_agree(d_p.seg_min, d_p.rep, d_x.seg_min, d_x.rep)


@pytest.mark.parametrize("strategy", [
    "xla", "pallas",
])
def test_n_valid_padding_never_matches(rng, strategy):
    """Bucket padding (u16 zeros → the grid ORIGIN, deliberately within
    radius of an origin-adjacent query) must be masked out by n_valid."""
    n = 300
    wire, _, _ = _wire(rng, n)
    padded = np.concatenate(
        [wire, np.zeros((3, 212), np.uint16)], axis=1
    )
    step = jax.jit(make_wire_digest_step(
        num_segments=NSEG, strategy=strategy, interpret=True,
    ))
    q_origin = jnp.asarray(np.asarray([0.5, 0.5], np.float32))
    sc = jnp.asarray(np.asarray(WF.scale, np.float32))
    og = jnp.asarray(np.asarray(WF.origin, np.float32))
    r = jnp.float32(3.0)
    d_pad = step(jnp.asarray(padded), jnp.int32(n), q_origin, sc, og, r)
    d_ref = step(jnp.asarray(wire), jnp.int32(n), q_origin, sc, og, r)
    np.testing.assert_array_equal(
        np.asarray(d_pad.seg_min), np.asarray(d_ref.seg_min)
    )
    np.testing.assert_array_equal(
        np.asarray(d_pad.rep), np.asarray(d_ref.rep)
    )
    # sanity: unmasked padding WOULD have matched (origin within radius)
    d_leak = step(
        jnp.asarray(padded), jnp.int32(padded.shape[1]), q_origin, sc,
        og, r,
    )
    assert not np.array_equal(
        np.asarray(d_leak.seg_min), np.asarray(d_ref.seg_min)
    )


def test_select_auto_on_cpu_stays_xla(rng):
    wire, _, _ = _wire(rng, 256)
    args = _args(wire)
    kind, _ = select_wire_digest_step(
        *args, num_segments=NSEG, strategy="auto",
    )
    assert kind == "xla"


def test_select_forced_pallas_self_checks(rng):
    wire, _, _ = _wire(rng, 256)
    kind, step = select_wire_digest_step(
        *_args(wire), num_segments=NSEG, strategy="pallas",
        interpret=True,
    )
    assert kind == "pallas"


def _soa_chunks(ts, xyf, oid):
    return iter([{
        "ts": ts,
        "x": xyf[:, 0].astype(np.float64),
        "y": xyf[:, 1].astype(np.float64),
        "oid": oid,
    }])


@pytest.mark.parametrize("strategy", [
    "xla", "pallas",
])
def test_run_wire_panes_matches_run_soa_panes(rng, strategy):
    """The shipped wire-ingest operator path fires the same windows with
    the same neighbors as the SoA pane path on the same (dequantized)
    coordinates — variable pane sizes exercise the bucket-pad + n_valid
    seam."""
    n = 3000
    ts = np.sort(rng.integers(0, 40_000, n)).astype(np.int64)
    wire, xyf, oid = _wire(rng, n)
    conf = QueryConfiguration(QueryType.WindowBased, window_size=10,
                              slide_step=2)
    q = Point(x=5.0, y=5.0)
    r, k = 2.0, 6
    slide_ms = conf.slide_step_ms

    soa = {
        (s, e): (list(map(int, oo)), np.asarray(dd))
        for s, e, oo, dd, nv in PointPointKNNQuery(conf, GRID).run_soa_panes(
            _soa_chunks(ts, xyf, oid), q, r, k,
            num_segments=NSEG, dtype=np.float32,
        )
    }

    slides = []
    for ps in range(0, 40_000, slide_ms):
        sel = (ts >= ps) & (ts < ps + slide_ms)
        slides.append(np.ascontiguousarray(wire[:, sel]))
    op = PointPointKNNQuery(conf, GRID)
    wire_res = {
        (s, e): (list(map(int, oo)), np.asarray(dd))
        for s, e, oo, dd, nv in op.run_wire_panes(
            slides, q, r, k, NSEG, WF, start_ms=0,
            strategy=strategy, interpret=True,
        )
    }
    assert op.last_wire_digest_kind == strategy
    # Every window run_soa_panes fires — INCLUDING the leading partials
    # (negative starts) and the trailing flush — must fire identically
    # on the wire path (the code-review r5 finding: an intersection-only
    # compare would mask dropped partial windows).
    missing = set(soa) - set(wire_res)
    assert not missing, f"wire path dropped windows: {sorted(missing)}"
    assert min(soa)[0] < 0, "expected leading partial windows in the ref"
    matched_neighbors = 0
    for key in sorted(soa):
        o_s, d_s = soa[key]
        o_w, d_w = wire_res[key]
        assert o_s == o_w, f"window {key}: oids diverge"
        np.testing.assert_allclose(d_w, d_s, rtol=5e-7, atol=0)
        matched_neighbors += len(o_s)
    assert matched_neighbors > 0, "degenerate: every window empty"


def test_wire_panes_producer_feeds_run_wire_panes(rng):
    """streams/wire.py:wire_panes (the SoA→plane-major producer) must
    bin identically to hand-built slides — incl. EMPTY panes inside
    event-time gaps — so the full ingest→operator seam matches
    run_soa_panes end to end."""
    from spatialflink_tpu.streams.wire import wire_panes

    n = 2000
    ts = np.sort(rng.integers(0, 30_000, n)).astype(np.int64)
    ts[(ts >= 8_000) & (ts < 14_000)] = 7_999  # a 3-pane event gap
    ts = np.sort(ts)
    wire, xyf, oid = _wire(rng, n)
    conf = QueryConfiguration(QueryType.WindowBased, window_size=10,
                              slide_step=2)
    q, r, k = Point(x=5.0, y=5.0), 2.0, 6
    slide_ms = conf.slide_step_ms

    chunks = [
        {"ts": ts[a:b], "x": xyf[a:b, 0].astype(np.float64),
         "y": xyf[a:b, 1].astype(np.float64), "oid": oid[a:b]}
        for a, b in zip(range(0, n, 300), list(range(300, n, 300)) + [n])
    ]
    produced = list(wire_panes(chunks, WF, slide_ms, start_ms=0))
    manual = []
    for ps in range(0, int(ts[-1]) + 1, slide_ms):
        sel = (ts >= ps) & (ts < ps + slide_ms)
        manual.append(np.ascontiguousarray(wire[:, sel]))
    assert len(produced) == len(manual)
    assert any(p.shape[1] == 0 for p in produced), "gap panes missing"
    for a, b in zip(produced, manual):
        np.testing.assert_array_equal(a, b)

    soa = {
        (s, e): (list(map(int, oo)), np.asarray(dd))
        for s, e, oo, dd, nv in PointPointKNNQuery(conf, GRID).run_soa_panes(
            _soa_chunks(ts, xyf, oid), q, r, k,
            num_segments=NSEG, dtype=np.float32,
        )
    }
    got = {
        (s, e): (list(map(int, oo)), np.asarray(dd))
        for s, e, oo, dd, nv in PointPointKNNQuery(conf, GRID)
        .run_wire_panes(produced, q, r, k, NSEG, WF, start_ms=0)
    }
    # Set EQUALITY, not ⊆: windows made only of empty panes (the event
    # gap) are suppressed on the wire path exactly like the SoA
    # assembler never builds them — the r5 every-window-fires deviation
    # is resolved, not documented around.
    assert set(soa) == set(got), (
        f"extra: {sorted(set(got) - set(soa))} "
        f"missing: {sorted(set(soa) - set(got))}"
    )
    for key in soa:
        assert soa[key][0] == got[key][0]
        np.testing.assert_allclose(got[key][1], soa[key][1], rtol=5e-7,
                                   atol=0)


@pytest.mark.parametrize("strategy,interpret,kind", [
    ("xla", False, "xla"),
    ("pallas", True, "pallas"),
])
def test_wire_digest_step_goes_through_the_one_wrapper(
        rng, strategy, interpret, kind):
    """The per-pane digest program is named and instrumented: it sits in
    the kernel table as ``wire_digest_<kind>``, one ``dispatch:`` span a
    non-empty pane — and tracing it changes no result."""
    from spatialflink_tpu.telemetry import telemetry

    conf = QueryConfiguration(QueryType.WindowBased, window_size=10,
                              slide_step=5)
    panes = [_wire(rng, n)[0] for n in (300, 300, 260, 300)]

    def run():
        op = PointPointKNNQuery(conf, GRID)
        out = [
            (s, e, list(map(int, oo)), np.asarray(dd).tolist(), nv)
            for s, e, oo, dd, nv in op.run_wire_panes(
                panes, Point(x=5.0, y=5.0), 2.0, 6, NSEG, WF,
                strategy=strategy, interpret=interpret)
        ]
        assert op.last_wire_digest_kind == kind
        return out

    plain = run()
    telemetry.enable()
    try:
        traced = run()
        rows = [r for r in telemetry.kernel_table()
                if r["kernel"] == f"wire_digest_{kind}"]
        spans = [e for e in telemetry.events
                 if e["name"] == f"dispatch:wire_digest_{kind}"]
        h2d = [e for e in telemetry.events if e["name"] == "h2d"]
    finally:
        telemetry.disable()
    assert traced == plain and len(plain) >= len(panes)
    assert sum(r["calls"] for r in rows) == len(spans) == len(panes)
    # one ship a pane, 6 B a point at the pane's bucket
    assert len(h2d) == len(panes)
    assert all(e["args"]["bytes"] % 6 == 0 for e in h2d)


def test_wire_digest_program_is_named_on_the_device_trace():
    """A functools.partial has no __name__, so jax.jit names its module
    ``jit__unknown``; the wrapper's module carries the step's name (what the
    trace's ``XLA Modules`` line shows)."""
    from spatialflink_tpu.operators.knn_query import _wire_digest_program

    step = make_wire_digest_step(num_segments=NSEG, cand=256)
    wire = np.zeros((3, 64), np.uint16)
    assert "@jit__unknown" in jax.jit(step).lower(*_args(wire)).as_text()
    prog = _wire_digest_program("xla", step)
    assert "@jit_wire_digest_xla" in prog.lower(*_args(wire)).as_text()


def test_wire_panes_rejects_out_of_order():
    from spatialflink_tpu.streams.wire import wire_panes

    chunks = [
        {"ts": np.asarray([5_000], np.int64), "x": np.asarray([1.0]),
         "y": np.asarray([1.0]), "oid": np.asarray([0])},
        {"ts": np.asarray([1_000], np.int64), "x": np.asarray([1.0]),
         "y": np.asarray([1.0]), "oid": np.asarray([0])},
    ]
    with pytest.raises(ValueError, match="out-of-order"):
        list(wire_panes(chunks, WF, 2_000, start_ms=0))
    # disorder WITHIN one chunk must raise too (binary-search binning
    # would silently mis-bin; r5 code review)
    bad = [{
        "ts": np.asarray([11_000, 8_500, 12_000], np.int64),
        "x": np.asarray([1.0, 1.0, 1.0]), "y": np.asarray([1.0, 1.0, 1.0]),
        "oid": np.asarray([0, 0, 0]),
    }]
    with pytest.raises(ValueError, match="out-of-order"):
        list(wire_panes(bad, WF, 2_000, start_ms=0))


def test_run_wire_panes_rejects_bad_input():
    conf = QueryConfiguration(QueryType.WindowBased, window_size=10,
                              slide_step=2)
    op = PointPointKNNQuery(conf, GRID)
    with pytest.raises(ValueError, match="plane-major"):
        list(op.run_wire_panes(
            [np.zeros((100, 3), np.uint16)], Point(x=5.0, y=5.0),
            2.0, 5, NSEG, WF,
        ))
    with pytest.raises(ValueError, match="plane-major"):
        list(op.run_wire_panes(
            [np.zeros((3, 100), np.float32)], Point(x=5.0, y=5.0),
            2.0, 5, NSEG, WF,
        ))


def test_wire_pane_assembler_restore_rejects_mismatched_config():
    """A checkpoint from one (slide, wire-format) must not restore into
    another — pane boundaries/quantization would silently shift (r5
    code review)."""
    from spatialflink_tpu.streams.wire import WireFormat, WirePaneAssembler

    asm = WirePaneAssembler(WF, 2_000, start_ms=0)
    asm.feed({"ts": np.asarray([100], np.int64), "x": np.asarray([1.0]),
              "y": np.asarray([1.0]), "oid": np.asarray([0])})
    snap = asm.state()
    other = WirePaneAssembler(WF, 1_000, start_ms=0)
    with pytest.raises(ValueError, match="slide_ms"):
        other.restore(snap)
    wf2 = WireFormat(0.0, 20.0, 0.0, 20.0)
    other2 = WirePaneAssembler(wf2, 2_000, start_ms=0)
    with pytest.raises(ValueError, match="wire format"):
        other2.restore(snap)
    ok = WirePaneAssembler(WF, 2_000, start_ms=0)
    ok.restore(snap)
    assert ok.state()["cur"] == snap["cur"]
