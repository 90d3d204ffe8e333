"""Compact binary ingest wire format: grid-relative uint16 coordinates.

The reference ships stream records as text — GeoJSON/WKT/CSV produced by
Serialization.java:17-726 and re-parsed by Deserialization.java — at
~100+ bytes/point; its ingest ceiling is the 20k EPS target of
BenchmarkRunner.java:25-26. This framework's ingest ceiling is link
bandwidth into the accelerator, so the hot wire format is binary:
quantized grid-relative ``uint16`` coordinates plus an interned ``int16``
object id — **6 bytes/point** — upcast to f32 on device inside the fused
window program.

Exactness contract (tests/test_wire.py):

- ``scale`` is chosen as ``m × 2^e`` with integer ``m ≤ 255`` (8
  significand bits), the smallest such value ≥ span/65535. A quantized
  coordinate ``q ≤ 65535`` (16 bits) times ``m`` (8 bits) needs ≤ 24
  significand bits, so ``q * scale`` is EXACT in f32 and
  ``origin + q * scale`` rounds exactly once — fused (FMA) and unfused
  evaluation, numpy on host and XLA on any backend, all produce
  bit-identical f32 coordinates. Device upcast therefore adds ZERO error
  on top of quantization.
- Quantization itself is the ingest precision: one lattice step is
  span/65535-ish (Beijing extent: ~3.2e-5° ≈ 3.6 m east-west), beneath
  civilian GPS accuracy. Every consumer of the same 6-byte records —
  this framework on any backend, or a host reference implementation —
  computes on exactly the same f32 coordinates.
"""

from __future__ import annotations

import math

import numpy as np

U16_MAX = 65535


def wire_scale(span: float) -> float:
    """Smallest ``m × 2^e`` ≥ span/65535 with integer ``m`` ≤ 8 bits.

    The 8-bit significand keeps ``uint16 × scale`` exactly representable
    in f32 (16 + 8 ≤ 24 significand bits) — see module docstring.
    """
    if not span > 0:
        raise ValueError(f"span must be positive, got {span}")
    target = span / U16_MAX
    e = math.floor(math.log2(target)) - 7
    m = math.ceil(target / 2.0 ** e)
    if m > 255:  # target/2^e landed exactly on 256
        m, e = 128, e + 1
    assert 128 <= m <= 255
    return m * 2.0 ** e


class WireFormat:
    """Quantizer/dequantizer for one grid extent.

    ``quantize`` and ``pack_pane`` run host-side at the producer (the
    serde/source layer, or a windowing tier outside this process);
    ``dequantize`` is jit-safe and fuses into the consuming kernel;
    ``dequantize_np`` is the host reference the parity tests compare
    against (bit-identical by the exactness contract above).
    """

    def __init__(self, min_x: float, max_x: float, min_y: float, max_y: float):
        self.origin = np.asarray([min_x, min_y], np.float32)
        self.scale = np.asarray(
            [wire_scale(max_x - min_x), wire_scale(max_y - min_y)], np.float32
        )
        # The f32 cast is exact for the scale (m×2^e) by construction; the
        # origin rounds to f32 once, identically for every consumer.

    @classmethod
    def for_grid(cls, grid) -> "WireFormat":
        return cls(grid.min_x, grid.max_x, grid.min_y, grid.max_y)

    def quantize(self, xy) -> np.ndarray:
        """(..., 2) float coords → (..., 2) uint16 (clipped to the bbox)."""
        xy64 = np.asarray(xy, np.float64)
        q = np.floor((xy64 - self.origin.astype(np.float64))
                     / self.scale.astype(np.float64))
        return np.clip(q, 0, U16_MAX).astype(np.uint16)

    def dequantize(self, q):
        """jit-safe device upcast: (..., 2) uint16 → f32 coords."""
        import jax.numpy as jnp

        return (q.astype(jnp.float32) * jnp.asarray(self.scale)
                + jnp.asarray(self.origin))

    def dequantize_np(self, q) -> np.ndarray:
        """Host reference dequant (bit-identical to ``dequantize``)."""
        return (np.asarray(q, np.float32) * self.scale + self.origin)

    def pack_pane(self, x, y, oid) -> np.ndarray:
        """The producer half: one slide's points → one ``(3, n)`` uint16
        PLANE-MAJOR pane (rows ``x_q``, ``y_q``, the interned int16 id's
        bits), contiguous — what ``run_wire_panes`` takes.

        ``x``, ``y``: float coordinates, quantised as ``quantize`` does
        (clipped to the bbox); ``oid``: ids already interned. An id that
        does not fit the int16 the format interns into is refused, never
        wrapped: a wrapped id lands on another object's segment."""
        x = np.asarray(x, np.float64)
        y = np.asarray(y, np.float64)
        oid = np.asarray(oid)
        if not (x.ndim == y.ndim == oid.ndim == 1
                and len(x) == len(y) == len(oid)):
            raise ValueError(
                "pack_pane expects three 1-D arrays of one length, got "
                f"x {x.shape}, y {y.shape}, oid {oid.shape}"
            )
        n = len(x)
        if n:
            lo, hi = oid.min(), oid.max()
            if lo < -0x8000 or hi > 0x7FFF:
                raise ValueError(
                    f"oid {lo if lo < -0x8000 else hi} does not fit the "
                    "int16 the wire format interns ids into"
                )
        origin = self.origin.astype(np.float64)
        scale = self.scale.astype(np.float64)
        pane = np.empty((3, n), np.uint16)
        for row, v in enumerate((x, y)):
            # as ``quantize``; the store into the uint16 row is the cast
            pane[row] = np.clip(
                np.floor((v - origin[row]) / scale[row]), 0, U16_MAX
            )
        pane[2] = oid.astype(np.int16).view(np.uint16)
        return pane

    @property
    def bytes_per_point(self) -> int:
        """uint16 x + uint16 y + int16 interned oid."""
        return 6


class WirePaneAssembler:
    """Stateful SoA → (3, n) uint16 PLANE-MAJOR pane binner.

    The producer half of the wire-pane operator seam: feeds
    ``PointPointKNNQuery.run_wire_panes`` from any SoA chunk stream
    ``{"ts", "x", "y", "oid"}`` — e.g. the native CSV parser's arrays or a batched Kafka consumer.
    Pane i covers [start_ms + i·slide_ms, start_ms + (i+1)·slide_ms);
    EVERY pane in order is emitted, including empty (3, 0) panes in
    event-time gaps, so downstream window indexing stays aligned.

    In-order streams only (the pane-path contract): a pane is emitted
    once an event at/after its end arrives, so an event earlier than
    the current pane raises rather than being silently mis-binned.
    ``oid`` must already be interned into int16 range (``pack_pane``,
    which packs every pane here, refuses one that is not). ``flush()``
    emits the final, possibly partial, pane at end of stream.

    ``state()``/``restore()`` snapshot the OPEN pane's buffered events
    + position (checkpoint.py:wire_pane_assembler_state): together
    with the consumer offsets and the operator digest ring, the whole
    wire pipeline resumes
    (tests/test_kafka_wire.py::test_full_wire_pipeline_kill_and_resume).
    Snapshot ALIGNMENT: every pane ``feed()`` has returned must be
    drained downstream before snapshotting — a completed pane held
    in-flight (e.g. the second of a multi-pane burst across an
    event-time gap) lives in neither this state nor the operator's, so
    a snapshot taken mid-burst loses it. This is the pane-boundary
    barrier any checkpointing runtime imposes.
    """

    def __init__(self, wire_format: WireFormat, slide_ms: int,
                 start_ms: int):
        self._wf = wire_format
        self._slide = int(slide_ms)
        self._cur = int(start_ms)
        self._pend_ts = np.zeros(0, np.int64)
        self._pend_xy = np.zeros((0, 2), np.float64)
        self._pend_oid = np.zeros(0, np.int64)

    def _pack(self, xy, oid):
        return self._wf.pack_pane(xy[:, 0], xy[:, 1], oid)

    def feed(self, ch) -> list:
        """One SoA chunk in → the panes it completed (possibly [])."""
        ts = np.asarray(ch["ts"], np.int64)
        if len(ts) == 0:
            return []
        xy = np.stack(
            [np.asarray(ch["x"], np.float64),
             np.asarray(ch["y"], np.float64)], axis=1
        )
        oid = np.asarray(ch["oid"])
        # Full in-order check: against the open pane, against the
        # pending tail, AND within the chunk (searchsorted below is a
        # binary search — unsorted input would silently mis-bin).
        prev_last = (int(self._pend_ts[-1]) if len(self._pend_ts)
                     else self._cur)
        if int(ts[0]) < max(self._cur, prev_last) or (
                len(ts) > 1 and bool(np.any(np.diff(ts) < 0))):
            raise ValueError(
                "out-of-order event stream: wire panes require "
                "non-decreasing timestamps (the pane-path contract); "
                f"open pane starts at {self._cur} ms"
            )
        self._pend_ts = np.concatenate([self._pend_ts, ts])
        self._pend_xy = np.concatenate([self._pend_xy, xy])
        self._pend_oid = np.concatenate([self._pend_oid, oid])
        # Emit every pane strictly BEFORE the newest event's pane (the
        # in-order watermark: a later event closes all earlier panes).
        out = []
        newest = int(self._pend_ts[-1])
        while self._cur + self._slide <= newest:
            hi = int(np.searchsorted(
                self._pend_ts, self._cur + self._slide, "left"
            ))
            out.append(self._pack(self._pend_xy[:hi], self._pend_oid[:hi]))
            self._pend_ts = self._pend_ts[hi:]
            self._pend_xy = self._pend_xy[hi:]
            self._pend_oid = self._pend_oid[hi:]
            self._cur += self._slide
        return out

    def flush(self) -> list:
        """End of stream: the open pane's events as one final pane."""
        if not len(self._pend_ts):
            return []
        out = [self._pack(self._pend_xy, self._pend_oid)]
        self._pend_ts = np.zeros(0, np.int64)
        self._pend_xy = np.zeros((0, 2), np.float64)
        self._pend_oid = np.zeros(0, np.int64)
        self._cur += self._slide
        return out

    def state(self) -> dict:
        return {
            "cur": int(self._cur),
            "slide_ms": int(self._slide),
            # wire-format identity: a checkpoint quantized against one
            # grid extent must not restore into another
            "wire_origin": [float(v) for v in self._wf.origin],
            "wire_scale": [float(v) for v in self._wf.scale],
            "pend_ts": np.asarray(self._pend_ts),
            "pend_xy": np.asarray(self._pend_xy),
            "pend_oid": np.asarray(self._pend_oid),
        }

    def restore(self, state: dict) -> None:
        if int(state.get("slide_ms", self._slide)) != self._slide:
            raise ValueError(
                f"checkpoint slide_ms {state['slide_ms']} != this "
                f"assembler's {self._slide} — pane boundaries would "
                "silently shift"
            )
        want = ([float(v) for v in self._wf.origin],
                [float(v) for v in self._wf.scale])
        got = (state.get("wire_origin", want[0]),
               state.get("wire_scale", want[1]))
        if got != want:
            raise ValueError(
                "checkpoint wire format (origin/scale) does not match "
                "this assembler's grid extent"
            )
        self._cur = int(state["cur"])
        self._pend_ts = np.asarray(state["pend_ts"], np.int64)
        self._pend_xy = np.asarray(state["pend_xy"], np.float64)
        self._pend_oid = np.asarray(state["pend_oid"])


def wire_panes(chunks, wire_format: WireFormat, slide_ms: int,
               start_ms: int):
    """Generator form of ``WirePaneAssembler`` (see its docstring):
    chunks in, every completed pane out, final partial pane flushed at
    end of stream."""
    asm = WirePaneAssembler(wire_format, slide_ms, start_ms)
    for ch in chunks:
        yield from asm.feed(ch)
    yield from asm.flush()
