"""Delta-bitpacked wire-pane codec — fewer bytes on the host→device link.

The 6 B/pt wire format (streams/wire.py) already beats the reference's
~100 B/pt text serde; where the chip still idles behind the
host→device link, the bytes per point are the lever. For the
SNCB GPS regime — slow-moving objects sampled every few seconds — most
of those 6 bytes are redundant: an object's quantized position moves a
handful of lattice steps per pane. This codec makes movement cost BITS,
not lanes:

- **delta-against-previous-pane**: each record's quantized (x, y) is
  predicted by the SAME object's last position in any earlier pane (a
  per-oid predictor table, init 0); the wire carries the zigzag-encoded
  mod-2^16 delta. Wraparound arithmetic makes the round trip exact for
  EVERY input — a never-seen object or a teleport just costs full
  width.
- **bitpacked lanes**: per pane, each of the three streams (zigzag-dx,
  zigzag-dy, oid bits) is packed at the smallest bit width that holds
  its max value (0..16), LSB-first into little-endian uint32 words —
  three word-aligned streams concatenated into ONE payload array.
  Worst case (incompressible pane) is raw width plus a few header
  bytes; a stationary fleet costs ~the oid stream alone.

Decode runs ON DEVICE as a fixed-shape jitted kernel
(:func:`decode_wire_pane`): word/offset arithmetic + gathers, no
data-dependent shapes — the pane capacity and word-count buckets ride
the shared compaction ladders (``wire_pane_bucket`` /
:func:`wire_word_bucket`), so variable pane sizes reuse ≤ladder-many
compiled programs. The per-oid predictor table lives ON DEVICE between
panes (carried like the digest ring, never re-shipped); the host
encoder maintains the bit-identical mirror it needs for delta
computation. Compression can therefore NEVER change results: the
decoded (3, n) uint16 pane is bit-identical to the raw pane the
uncompressed path would have shipped (padding lanes zeroed, like the
raw path's bucket padding), and everything downstream is unchanged.

The bit extraction is jnp only: its data-dependent 1-D word gathers
do not lower through Mosaic ("Only 2D gather is supported", jax 0.9.0
on TPU v5e), so the Pallas form that once sat beside it was removed.

Host/device split (CLAUDE.md): encode is host control plane (numpy,
runs where the bytes originate); decode is compute plane (jit-safe,
fuses into the consuming pipeline's dispatch stream).
"""

from __future__ import annotations

from typing import NamedTuple, Tuple

import jax
import jax.numpy as jnp
import numpy as np

#: Fixed per-pane header cost charged to ``coded_bytes``: n (4 B) +
#: three bit widths (1 B each) + 1 B pad. The payload words are the
#: real wire traffic; the header rides the dispatch args.
HEADER_BYTES = 8

#: Floor for the payload word bucket (64 B) — keeps tiny panes from
#: minting one compiled shape per word count.
WORD_BUCKET_MIN = 16


# ---------------------------------------------------------------------------
# Host bit packing (encoder side)


def pack_bits(vals: np.ndarray, b: int) -> np.ndarray:
    """Pack ``(n,)`` unsigned values at ``b`` bits each, LSB-first, into
    little-endian uint32 words (``ceil(n*b/32)`` of them)."""
    n = int(len(vals))
    if b == 0 or n == 0:
        return np.zeros(0, np.uint32)
    v = np.asarray(vals, np.uint32)  # sfcheck: ok=trace-hygiene -- host encoder half (module docstring): packs producer-side numpy, never a tracer
    bits = ((v[:, None] >> np.arange(b, dtype=np.uint32)[None, :]) & 1)
    flat = bits.astype(np.uint8).ravel()
    words = -((-n * b) // 32)
    pad = words * 32 - flat.size
    if pad:
        flat = np.concatenate([flat, np.zeros(pad, np.uint8)])
    return np.packbits(flat, bitorder="little").view(np.dtype("<u4"))


def unpack_bits_np(words: np.ndarray, n: int, b: int) -> np.ndarray:
    """Host twin of the device extraction (tests + reference decode)."""
    if b == 0 or n == 0:
        return np.zeros(n, np.uint32)
    flat = np.unpackbits(
        np.asarray(words, np.dtype("<u4")).view(np.uint8),  # sfcheck: ok=trace-hygiene -- host reference twin of the device extraction (docstring): numpy on host words
        bitorder="little",
    )
    take = flat[: n * b].reshape(n, b).astype(np.uint32)
    return (take << np.arange(b, dtype=np.uint32)[None, :]).sum(
        axis=1, dtype=np.uint32
    )


def _zigzag16(d: np.ndarray) -> np.ndarray:
    """int16 deltas → uint16 zigzag codes (small |d| → small code)."""
    d32 = d.astype(np.int32)
    return (((d32 << 1) ^ (d32 >> 15)) & 0xFFFF).astype(np.uint16)


def _bit_width(vals: np.ndarray) -> int:
    if len(vals) == 0:
        return 0
    return int(int(np.max(vals)).bit_length())


class EncodedPane(NamedTuple):
    """One compressed wire pane: payload words + the header scalars the
    decode kernel needs. ``raw_bytes``/``coded_bytes`` feed the
    compression gauges (telemetry.account_wire)."""

    words: np.ndarray  # (W,) uint32 payload (x-, y-, oid-stream concat)
    n: int             # record count
    bx: int            # zigzag-dx bit width (0..16)
    by: int            # zigzag-dy bit width (0..16)
    bo: int            # oid bit width (0..16)
    raw_bytes: int     # 6 * n — what the uncompressed wire would ship
    coded_bytes: int   # 4 * len(words) + HEADER_BYTES


class WirePaneEncoder:
    """Host-side stateful encoder — the control-plane half.

    Mirrors the device predictor table exactly: both sides update each
    oid's entry to its LAST position in the pane, so encoder deltas and
    device reconstruction agree bit-for-bit forever. ``state()`` /
    ``restore()`` snapshot the mirror for checkpoints (the device table
    is derived state — a resume re-ships the mirror once).
    """

    def __init__(self, num_segments: int):
        self.num_segments = int(num_segments)  # sfcheck: ok=trace-hygiene -- host control plane: the encoder is constructed with a host int, never traced
        self.pred_x = np.zeros(self.num_segments, np.uint16)
        self.pred_y = np.zeros(self.num_segments, np.uint16)

    def encode(self, wire_p: np.ndarray) -> EncodedPane:
        """(3, n) uint16 plane-major pane → :class:`EncodedPane`."""
        wire_p = np.asarray(wire_p)  # sfcheck: ok=trace-hygiene -- host encoder: panes arrive as producer-side numpy (module docstring)
        if wire_p.ndim != 2 or wire_p.shape[0] != 3 \
                or wire_p.dtype != np.uint16:
            raise ValueError(
                "encode expects a (3, n) uint16 plane-major pane, got "
                f"{wire_p.dtype} {wire_p.shape}"
            )
        n = int(wire_p.shape[1])
        if n == 0:
            return EncodedPane(np.zeros(0, np.uint32), 0, 0, 0, 0, 0,
                               HEADER_BYTES)
        x, y, o = wire_p[0], wire_p[1], wire_p[2]
        if int(np.max(o)) >= self.num_segments:
            raise ValueError(
                f"oid {int(np.max(o))} >= num_segments "
                f"{self.num_segments}: the predictor table cannot index "
                "it (intern ids densely, like the wire digest)"
            )
        oi = o.astype(np.int64)
        dx = (x.astype(np.int32) - self.pred_x[oi].astype(np.int32)) \
            .astype(np.int16)
        dy = (y.astype(np.int32) - self.pred_y[oi].astype(np.int32)) \
            .astype(np.int16)
        zx, zy = _zigzag16(dx), _zigzag16(dy)
        bx, by, bo = _bit_width(zx), _bit_width(zy), _bit_width(o)
        words = np.concatenate(
            [pack_bits(zx, bx), pack_bits(zy, by), pack_bits(o, bo)]
        )
        # Duplicate oids: numpy fancy assignment keeps the LAST write,
        # matching the device update's last-occurrence segment_max.
        self.pred_x[oi] = x
        self.pred_y[oi] = y
        return EncodedPane(
            words, n, bx, by, bo,
            raw_bytes=6 * n,
            coded_bytes=4 * int(len(words)) + HEADER_BYTES,
        )

    def state(self) -> dict:
        # Copies: the live tables mutate in place on the next encode —
        # a snapshot must not change after it is taken (and a shipped
        # table must never alias them; XLA:CPU zero-copies host
        # buffers).
        return {
            "num_segments": int(self.num_segments),
            "pred_x": self.pred_x.copy(),
            "pred_y": self.pred_y.copy(),
        }

    def restore(self, state: dict) -> None:
        if int(state["num_segments"]) != self.num_segments:
            raise ValueError(
                f"codec checkpoint num_segments {state['num_segments']} "
                f"!= this encoder's {self.num_segments} — predictor "
                "tables would silently misalign"
            )
        self.pred_x = np.asarray(state["pred_x"], np.uint16).copy()
        self.pred_y = np.asarray(state["pred_y"], np.uint16).copy()


#: Rungs per pane bucket in the word ladder: padding overhead is
#: bounded by worst_case/WORD_LADDER_RUNGS (~6%), compiled shapes per
#: pane bucket by WORD_LADDER_RUNGS+1.
WORD_LADDER_RUNGS = 16


def wire_word_bucket(w: int, pane_bucket: int,
                     minimum: int = WORD_BUCKET_MIN) -> int:
    """Payload word-count bucket — the codec twin of
    ops/compaction.py:wire_pane_bucket, with the same per-bucket
    occupancy telemetry. The rung granularity derives from the pane
    bucket's WORST-CASE payload (three 16-bit streams) split into
    ``WORD_LADDER_RUNGS`` steps, so compiled decode shapes stay bounded
    per pane bucket while padding overhead stays ≤ ~1/16 — a plain
    power-of-two ladder could pad a just-over-a-rung payload by ~2x,
    which would silently cost MORE wire bytes than the raw format (the
    shipped bucket bytes are what ``account_wire`` must charge)."""
    from spatialflink_tpu.telemetry import telemetry

    worst = 3 * ((int(pane_bucket) * 16 + 31) >> 5)  # sfcheck: ok=trace-hygiene -- host control plane: the pane bucket is a host int (wire_pane_bucket's pick), never traced
    grain = max(int(minimum), -(-worst // WORD_LADDER_RUNGS))  # sfcheck: ok=trace-hygiene -- same host-side rung arithmetic as above
    b = max(int(minimum), -(-int(w) // grain) * grain)  # sfcheck: ok=trace-hygiene -- host control plane: payload word count is a host int picking a static bucket (wire_pane_bucket twin)
    telemetry.record_compaction("wire_codec_words", b, int(w))  # sfcheck: ok=trace-hygiene -- same host-side bucket pick as above
    return b


def pad_words(words: np.ndarray, bucket: int) -> np.ndarray:
    """Pad the payload to its bucket (zero words are inert: every read
    past a stream's end is masked by the extraction's width mask)."""
    if len(words) >= bucket:
        return np.asarray(words, np.uint32)  # sfcheck: ok=trace-hygiene -- host control plane: pads the encoder's numpy payload before the ship
    out = np.zeros(bucket, np.uint32)
    out[: len(words)] = words
    return out


# ---------------------------------------------------------------------------
# Device decode (jit-safe, fixed shape)


def _extract_lanes(words, word_off, idx, b):
    """Extract ``b``-bit fields ``idx`` (LSB-first stream starting at
    ``words[word_off]``) — all of ``word_off``/``b`` traced, shapes
    static. Cross-word reads mask away foreign bits: when a field fits
    in one word the second word's contribution lands at bit ≥ b and the
    width mask kills it, so reading into the NEXT stream's words is
    harmless by construction."""
    n_words = words.shape[0]
    bitpos = idx * b
    w0 = jnp.clip(word_off + (bitpos >> 5), 0, n_words - 1)
    w1 = jnp.clip(word_off + (bitpos >> 5) + 1, 0, n_words - 1)
    s = (bitpos & 31).astype(jnp.uint32)
    bu = jnp.uint32(b)
    lo = jnp.take(words, w0) >> s
    hi = jnp.where(
        s == 0,
        jnp.uint32(0),
        jnp.take(words, w1) << ((jnp.uint32(32) - s) & jnp.uint32(31)),
    )
    mask = jnp.where(
        bu == 0, jnp.uint32(0), (jnp.uint32(1) << bu) - jnp.uint32(1)
    )
    return (lo | hi) & mask


def _unzigzag(z):
    """uint32 zigzag codes → int32 deltas."""
    zi = z.astype(jnp.int32)
    return (zi >> 1) ^ -(zi & 1)


def extract_streams(words, n_valid, bx, by, bo, *, n: int):
    """The bit-twiddle half of decode: payload words → (zx, zy, o)
    uint32 lanes for ``n`` (static bucket) lanes; lanes ≥ ``n_valid``
    carry garbage the caller masks."""
    idx = jnp.arange(n, dtype=jnp.int32)
    wx = (n_valid * bx + 31) >> 5
    wy = (n_valid * by + 31) >> 5
    zx = _extract_lanes(words, jnp.int32(0), idx, bx)
    zy = _extract_lanes(words, wx, idx, by)
    o = _extract_lanes(words, wx + wy, idx, bo)
    return zx, zy, o


def decode_wire_pane(words, n_valid, bx, by, bo, pred_x, pred_y, *,
                     n: int, num_segments: int):
    """Fixed-shape device decode + predictor update — ONE dispatch.

    ``words``: (W,) uint32 bucket-padded payload; ``n_valid``/widths:
    traced scalars; ``pred_x``/``pred_y``: (num_segments,) uint16
    device-resident predictor tables. Returns ``(pane, pred_x2,
    pred_y2)`` where ``pane`` is the (3, n) uint16 plane-major pane,
    bit-identical to the raw pane the uncompressed path would ship
    (padding lanes zeroed — the raw path's bucket padding). The tables
    update to each oid's LAST position in the pane (deterministic
    last-occurrence ``segment_max``, never an unordered scatter), the
    exact rule the host encoder mirrors.
    """
    idx = jnp.arange(n, dtype=jnp.int32)
    valid = idx < n_valid
    zx, zy, o = extract_streams(words, n_valid, bx, by, bo, n=n)
    o_safe = jnp.clip(o.astype(jnp.int32), 0, num_segments - 1)
    x = (jnp.take(pred_x, o_safe).astype(jnp.int32) + _unzigzag(zx)) \
        & 0xFFFF
    y = (jnp.take(pred_y, o_safe).astype(jnp.int32) + _unzigzag(zy)) \
        & 0xFFFF
    x = jnp.where(valid, x, 0).astype(jnp.uint16)
    y = jnp.where(valid, y, 0).astype(jnp.uint16)
    ou = jnp.where(valid, o, 0).astype(jnp.uint16)
    pane = jnp.stack([x, y, ou])

    # Last-occurrence predictor update: per-segment max position, then
    # gather that position's decoded coords. Invalid lanes rank into a
    # drop segment (the out-of-grid-slot idiom).
    seg = jnp.where(valid, o_safe, num_segments)
    last = jax.ops.segment_max(
        idx, seg, num_segments=num_segments + 1
    )[:num_segments]
    has = last >= 0
    gpos = jnp.clip(last, 0, n - 1)
    px2 = jnp.where(has, jnp.take(x, gpos), pred_x).astype(jnp.uint16)
    py2 = jnp.where(has, jnp.take(y, gpos), pred_y).astype(jnp.uint16)
    return pane, px2, py2


def decode_wire_pane_np(enc: EncodedPane, pred_x: np.ndarray,
                        pred_y: np.ndarray
                        ) -> Tuple[np.ndarray, np.ndarray, np.ndarray]:
    """Host reference decode (numpy twin of :func:`decode_wire_pane`
    without padding): (3, n) pane + updated predictor copies."""
    n = enc.n
    wx = -((-n * enc.bx) // 32)
    wy = -((-n * enc.by) // 32)
    zx = unpack_bits_np(enc.words[:wx], n, enc.bx)
    zy = unpack_bits_np(enc.words[wx:wx + wy], n, enc.by)
    o = unpack_bits_np(enc.words[wx + wy:], n, enc.bo).astype(np.uint16)
    zi_x = zx.astype(np.int32)
    zi_y = zy.astype(np.int32)
    dx = (zi_x >> 1) ^ -(zi_x & 1)
    dy = (zi_y >> 1) ^ -(zi_y & 1)
    oi = o.astype(np.int64)
    x = ((pred_x[oi].astype(np.int32) + dx) & 0xFFFF).astype(np.uint16)
    y = ((pred_y[oi].astype(np.int32) + dy) & 0xFFFF).astype(np.uint16)
    px2, py2 = pred_x.copy(), pred_y.copy()
    px2[oi] = x
    py2[oi] = y
    return np.stack([x, y, o]), px2, py2
