"""Delta-bitpacked wire-pane codec (ops/wire_codec.py) — the round trip
must be BIT-exact for every input: the codec is allowed to change bytes
on the wire, never results. Property tests cover the regimes the design
calls out (slow random walks = the SNCB GPS regime, incompressible
uniform panes, empty/gap panes, wraparound teleports), the host/device
predictor-table lockstep, the np reference twin, the ladder-bounded
compiled-shape contract, and the Pallas extraction's self-check."""

import functools
import os
import sys

import numpy as np
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if REPO not in sys.path:
    sys.path.insert(0, REPO)

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402

from spatialflink_tpu.ops import wire_codec as wc  # noqa: E402
from spatialflink_tpu.telemetry import telemetry  # noqa: E402


@pytest.fixture
def rng():
    return np.random.default_rng(1234)


def _device_decode(enc, px, py, *, n_bucket=None):
    """One jitted decode at a bucket; returns (pane(3, nb), px2, py2)
    as numpy."""
    nb = n_bucket or max(8, enc.n)
    wb = max(wc.WORD_BUCKET_MIN, len(enc.words))
    step = jax.jit(functools.partial(
        wc.decode_wire_pane, n=nb, num_segments=len(px),
    ))
    pane, px2, py2 = step(
        jnp.asarray(wc.pad_words(enc.words, wb)), jnp.int32(enc.n),
        jnp.int32(enc.bx), jnp.int32(enc.by), jnp.int32(enc.bo),
        jnp.asarray(px), jnp.asarray(py),
    )
    return np.asarray(pane), np.asarray(px2), np.asarray(py2)


def _random_walk_panes(rng, nseg=37, n_panes=12, max_n=60, step=5,
                       teleport_at=None):
    """Pane stream in the slow-moving regime: per-oid random walk of
    ±``step`` lattice cells, optional teleport."""
    pos = rng.integers(0, 65536, (nseg, 2)).astype(np.int64)
    panes = []
    for i in range(n_panes):
        n = int(rng.integers(0, max_n))
        oids = rng.integers(0, nseg, n)
        pos[oids] = (pos[oids] + rng.integers(-step, step + 1,
                                              (n, 2))) % 65536
        if teleport_at is not None and i == teleport_at and n:
            pos[oids[0]] = rng.integers(0, 65536, 2)
        panes.append(np.stack([
            pos[oids, 0].astype(np.uint16),
            pos[oids, 1].astype(np.uint16),
            oids.astype(np.uint16),
        ]))
    return panes


class TestBitPacking:
    def test_pack_unpack_roundtrip_all_widths(self, rng):
        for b in range(17):
            n = int(rng.integers(0, 200))
            vals = rng.integers(0, 1 << b if b else 1, n).astype(
                np.uint32)
            words = wc.pack_bits(vals, b)
            assert words.dtype == np.uint32
            assert len(words) == (0 if b == 0 or n == 0
                                  else -((-n * b) // 32))
            back = wc.unpack_bits_np(words, n, b)
            assert np.array_equal(back, vals), b

    def test_device_extraction_matches_np(self, rng):
        """The jnp extraction and the np twin read identical fields at
        every (offset, width) alignment."""
        for b in (1, 3, 7, 8, 11, 16):
            n = 77
            vals = rng.integers(0, 1 << b, n).astype(np.uint32)
            words = wc.pack_bits(vals, b)
            wb = max(wc.WORD_BUCKET_MIN, len(words))
            got = jax.jit(
                lambda w, nv, bb: wc.extract_streams(
                    w, nv, bb, jnp.int32(0), jnp.int32(0), n=128
                )[0]
            )(jnp.asarray(wc.pad_words(words, wb)), jnp.int32(n),
              jnp.int32(b))
            assert np.array_equal(np.asarray(got)[:n], vals), b


class TestRoundTrip:
    def test_random_walk_bit_exact_with_predictor_lockstep(self, rng):
        """The SNCB regime: every pane decodes bit-identically AND the
        device predictor tables track the host encoder's mirror."""
        nseg = 37
        enc = wc.WirePaneEncoder(nseg)
        px = np.zeros(nseg, np.uint16)
        py = np.zeros(nseg, np.uint16)
        for pane in _random_walk_panes(rng, nseg, teleport_at=7):
            e = enc.encode(pane)
            out, px, py = _device_decode(e, px, py,
                                         n_bucket=max(8, e.n))
            assert np.array_equal(out[:, :e.n], pane)
            assert np.all(out[:, e.n:] == 0)  # padding lanes zeroed
            assert np.array_equal(px, enc.pred_x)
            assert np.array_equal(py, enc.pred_y)

    def test_slow_walk_actually_compresses(self, rng):
        """After warmup (tables populated) a ±5-step walk costs far
        fewer bits than raw — the design's reason to exist. Pane 0
        seeds every oid so later panes are pure walk (no never-seen
        full-width records)."""
        nseg = 64
        enc = wc.WirePaneEncoder(nseg)
        pos = rng.integers(0, 65536, (nseg, 2)).astype(np.int64)
        seed = np.stack([
            pos[:, 0].astype(np.uint16), pos[:, 1].astype(np.uint16),
            np.arange(nseg, dtype=np.uint16),
        ])
        enc.encode(seed)
        warm = []
        for _ in range(8):
            n = 40
            oids = rng.integers(0, nseg, n)
            pos[oids] = (pos[oids]
                         + rng.integers(-5, 6, (n, 2))) % 65536
            warm.append(enc.encode(np.stack([
                pos[oids, 0].astype(np.uint16),
                pos[oids, 1].astype(np.uint16),
                oids.astype(np.uint16),
            ])))
        for e in warm:
            assert e.coded_bytes < e.raw_bytes, (e.n, e.coded_bytes)
            # steady-state widths: zigzag(±5) needs ≤ 4 bits
            assert e.bx <= 4 and e.by <= 4, (e.bx, e.by)

    def test_incompressible_pane_worst_case_bounded(self, rng):
        """Uniform-random coords: still bit-exact, and the worst case
        is raw width + the header + word-alignment slack."""
        nseg = 512
        enc = wc.WirePaneEncoder(nseg)
        n = 300
        pane = np.stack([
            rng.integers(0, 65536, n).astype(np.uint16),
            rng.integers(0, 65536, n).astype(np.uint16),
            rng.integers(0, nseg, n).astype(np.uint16),
        ])
        e = enc.encode(pane)
        out, _, _ = _device_decode(e, np.zeros(nseg, np.uint16),
                                   np.zeros(nseg, np.uint16),
                                   n_bucket=512)
        assert np.array_equal(out[:, :n], pane)
        assert e.coded_bytes <= e.raw_bytes + wc.HEADER_BYTES + 3 * 4

    def test_empty_pane(self):
        enc = wc.WirePaneEncoder(8)
        e = enc.encode(np.zeros((3, 0), np.uint16))
        assert (e.n, e.bx, e.by, e.bo) == (0, 0, 0, 0)
        assert e.raw_bytes == 0 and e.coded_bytes == wc.HEADER_BYTES
        px = np.arange(8, dtype=np.uint16)
        py = px + 1
        out, px2, py2 = _device_decode(e, px, py, n_bucket=8)
        assert np.all(out == 0)
        # predictor tables untouched by an empty pane
        assert np.array_equal(px2, px) and np.array_equal(py2, py)

    def test_wraparound_edges_exact(self):
        """mod-2^16 deltas at the extremes: 0↔65535, ±32768 — the
        zigzag/wraparound arithmetic must be exact everywhere."""
        enc = wc.WirePaneEncoder(4)
        first = np.stack([
            np.asarray([0, 65535, 32768, 1], np.uint16),
            np.asarray([65535, 0, 1, 32768], np.uint16),
            np.asarray([0, 1, 2, 3], np.uint16),
        ])
        second = np.stack([
            np.asarray([65535, 0, 0, 32769], np.uint16),  # max deltas
            np.asarray([0, 65535, 32769, 0], np.uint16),
            np.asarray([0, 1, 2, 3], np.uint16),
        ])
        px = np.zeros(4, np.uint16)
        py = np.zeros(4, np.uint16)
        for pane in (first, second):
            e = enc.encode(pane)
            out, px, py = _device_decode(e, px, py, n_bucket=8)
            assert np.array_equal(out[:, :4], pane)

    def test_duplicate_oids_last_occurrence_wins(self):
        """A pane with one oid appearing twice: both sides must keep
        the LAST position as the next pane's predictor."""
        enc = wc.WirePaneEncoder(4)
        pane = np.stack([
            np.asarray([100, 200], np.uint16),
            np.asarray([300, 400], np.uint16),
            np.asarray([2, 2], np.uint16),
        ])
        e = enc.encode(pane)
        out, px, py = _device_decode(e, np.zeros(4, np.uint16),
                                     np.zeros(4, np.uint16), n_bucket=8)
        assert np.array_equal(out[:, :2], pane)
        assert enc.pred_x[2] == 200 and enc.pred_y[2] == 400
        assert px[2] == 200 and py[2] == 400

    def test_np_twin_matches_device(self, rng):
        nseg = 16
        enc = wc.WirePaneEncoder(nseg)
        npx = np.zeros(nseg, np.uint16)
        npy = np.zeros(nseg, np.uint16)
        dpx = npx.copy()
        dpy = npy.copy()
        for pane in _random_walk_panes(rng, nseg, n_panes=6, max_n=30):
            e = enc.encode(pane)
            d_pane, dpx, dpy = _device_decode(e, dpx, dpy,
                                              n_bucket=max(8, e.n))
            if e.n:
                n_pane, npx, npy = wc.decode_wire_pane_np(e, npx, npy)
                assert np.array_equal(n_pane, d_pane[:, :e.n])
                assert np.array_equal(npx, dpx)
                assert np.array_equal(npy, dpy)


class TestContracts:
    def test_encoder_rejects_out_of_range_oid(self):
        enc = wc.WirePaneEncoder(4)
        pane = np.stack([np.zeros(1, np.uint16), np.zeros(1, np.uint16),
                         np.asarray([7], np.uint16)])
        with pytest.raises(ValueError, match="num_segments"):
            enc.encode(pane)

    def test_encoder_rejects_bad_shape(self):
        with pytest.raises(ValueError, match="plane-major"):
            wc.WirePaneEncoder(4).encode(np.zeros((2, 5), np.uint16))

    def test_state_restore_roundtrip_and_mismatch(self, rng):
        enc = wc.WirePaneEncoder(8)
        enc.encode(np.stack([
            rng.integers(0, 65536, 5).astype(np.uint16),
            rng.integers(0, 65536, 5).astype(np.uint16),
            rng.integers(0, 8, 5).astype(np.uint16),
        ]))
        st = enc.state()
        enc2 = wc.WirePaneEncoder(8)
        enc2.restore(st)
        assert np.array_equal(enc2.pred_x, enc.pred_x)
        assert np.array_equal(enc2.pred_y, enc.pred_y)
        with pytest.raises(ValueError, match="num_segments"):
            wc.WirePaneEncoder(16).restore(st)

    def test_word_bucket_ladder_bounds_compiled_shapes(self, rng):
        """Any mix of pane compressibilities buckets into ≤rung-many
        word counts PER PANE BUCKET (the recompile-surface contract),
        with padding overhead bounded by one rung (~6% of worst case —
        a pow2 ladder could pad ~2x and ship MORE than raw)."""
        telemetry.enable()
        try:
            for nb in (256, 1024):
                worst = 3 * ((nb * 16 + 31) >> 5)
                buckets = set()
                for w in rng.integers(0, worst + 1, 300):
                    b = wc.wire_word_bucket(int(w), nb)
                    assert b >= int(w)
                    assert b - int(w) <= max(
                        wc.WORD_BUCKET_MIN,
                        -(-worst // wc.WORD_LADDER_RUNGS),
                    )
                    buckets.add(b)
                assert len(buckets) <= wc.WORD_LADDER_RUNGS + 1
            logged = telemetry.compaction_buckets("wire_codec_words")
            assert logged  # picks recorded like the pane ladder's
        finally:
            telemetry.disable()
