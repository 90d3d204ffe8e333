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

from spatialflink_tpu.telemetry import telemetry

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

    def check_oid(self, oid: np.ndarray) -> None:
        """An id that does not fit the int16 the format interns into is
        refused, never wrapped: a wrapped id lands on another object's
        segment."""
        if len(oid):
            lo, hi = oid.min(), oid.max()
            if lo < -0x8000 or hi > 0x7FFF:
                raise ValueError(
                    f"oid {lo if lo < -0x8000 else hi} does not fit the "
                    "int16 the wire format interns ids into"
                )

    def quantize_into(self, out: np.ndarray, x: np.ndarray, y: np.ndarray,
                      oid: np.ndarray) -> None:
        """The one per-row quantiser: float64 ``x``, ``y`` and checked
        ``oid`` (``check_oid``) of one length n → the ``(3, n)`` uint16
        ``out`` (rows ``x_q``, ``y_q``, the int16 id's bits). ``out`` may
        be a column slice of a larger plane-major buffer. ``pack_pane``
        and ``WirePaneAssembler`` both write through here, so a pane is
        the same bytes whichever chunks it arrived in."""
        for row, v in enumerate((x, y)):
            # as ``quantize``, in float64: clip(floor((v − origin) /
            # scale), 0, 65535), the clip as its two ufuncs; the store
            # into the uint16 row is the cast
            q = v - float(self.origin[row])
            q /= float(self.scale[row])
            np.floor(q, out=q)
            np.maximum(q, 0.0, out=q)
            np.minimum(q, float(U16_MAX), out=q)
            out[row] = q
        np.copyto(out[2].view(np.int16), oid, casting="unsafe")

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
        self.check_oid(oid)
        pane = np.empty((3, len(x)), np.uint16)
        self.quantize_into(pane, x, y, oid)
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

    WRITE-ONCE: the open pane is held as what it will be — a ``(3, cap)``
    uint16 plane-major buffer and a fill count — and each chunk is
    quantised once, straight into ``buf[:, fill:fill+n]``
    (``WireFormat.quantize_into``, the function ``pack_pane`` packs
    with, so a pane is byte-identical to ``pack_pane`` of its rows). No
    float64 row and no timestamp outlives ``feed``; only the last
    timestamp is kept, for the in-order check. ``cap`` starts small,
    doubles when a chunk does not fit and stays at what the stream's
    panes reached. A closed pane is emitted as ONE contiguous copy of
    ``buf[:, :fill]``: the caller owns it and the assembler never writes
    it again (``buf`` is wider than the pane, so a column slice of it is
    not contiguous, and a pane that aliased it would change under the
    windows that still hold it).

    In-order streams only (the pane-path contract): a pane is emitted
    once an event at/after its end arrives, so an event earlier than
    the current pane raises rather than being silently mis-binned.
    ``oid`` must already be interned into int16 range: one that is not
    is refused at the ``feed`` that brings it. A refused chunk (order,
    id, shape) leaves the assembler as it was. ``flush()`` emits the
    final, possibly partial, pane at end of stream.

    With telemetry on, every closed pane records once
    (``telemetry.record_wire_assembler`` → ``snapshot()["wire"]``) the
    chunks and rows taken in since the last record, the rows copied
    after their first write (regrowth + the emitted copy) and the
    regrowths: ``assembler_rows_moved ÷ assembler_rows`` is the copy
    amplification, 1.0 in a steady stream.

    ``state()``/``restore()`` snapshot the OPEN pane's quantised rows
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

    #: columns the open pane's buffer starts with; it doubles from here
    _MIN_CAP = 1024

    def __init__(self, wire_format: WireFormat, slide_ms: int,
                 start_ms: int):
        self._wf = wire_format
        self._slide = int(slide_ms)
        self._cur = int(start_ms)
        self._last = self._cur  # newest timestamp taken in
        self._buf = np.empty((3, self._MIN_CAP), np.uint16)
        self._fill = 0
        # what the next telemetry record carries (one a closed pane)
        self._chunks = self._rows = self._moved = self._grows = 0

    def _reserve(self, need: int) -> None:
        """Room for ``need`` columns: double until they fit, keep the
        open pane's rows."""
        cap = self._buf.shape[1]
        if need <= cap:
            return
        while cap < need:
            cap *= 2
        grown = np.empty((3, cap), np.uint16)
        grown[:, :self._fill] = self._buf[:, :self._fill]
        self._buf = grown
        self._moved += self._fill
        self._grows += 1

    def _take(self, x, y, oid) -> None:
        """Quantise checked rows into the open pane, once."""
        end = self._fill + len(x)
        self._reserve(end)
        self._wf.quantize_into(self._buf[:, self._fill:end], x, y, oid)
        self._rows += end - self._fill
        self._fill = end

    def _close(self) -> np.ndarray:
        """The open pane as the caller's own contiguous array; the next
        pane opens on the same buffer."""
        pane = self._buf[:, :self._fill].copy()
        self._moved += self._fill
        self._fill = 0
        self._cur += self._slide
        telemetry.record_wire_assembler(self._chunks, self._rows,
                                        self._moved, self._grows)
        self._chunks = self._rows = self._moved = self._grows = 0
        return pane

    def feed(self, ch) -> list:
        """One SoA chunk in → the panes it completed (possibly [])."""
        ts = np.asarray(ch["ts"], np.int64)
        if len(ts) == 0:
            return []
        x = np.asarray(ch["x"], np.float64)
        y = np.asarray(ch["y"], np.float64)
        oid = np.asarray(ch["oid"])
        # Everything that can refuse the chunk comes before the first
        # write: a refused chunk leaves the assembler as it was.
        if not (ts.ndim == x.ndim == y.ndim == oid.ndim == 1
                and len(ts) == len(x) == len(y) == len(oid)):
            raise ValueError(
                "a chunk is four 1-D columns of one length, got "
                f"ts {ts.shape}, x {x.shape}, y {y.shape}, oid {oid.shape}"
            )
        # Full in-order check: against the open pane, against the
        # previous chunk's last timestamp, AND within the chunk
        # (searchsorted below is a binary search — unsorted input would
        # silently mis-bin).
        if (int(ts[0]) < max(self._cur, self._last)
                or bool((ts[1:] < ts[:-1]).any())):
            raise ValueError(
                "out-of-order event stream: wire panes require "
                "non-decreasing timestamps (the pane-path contract); "
                f"open pane starts at {self._cur} ms"
            )
        self._wf.check_oid(oid)
        self._chunks += 1
        # Emit every pane strictly BEFORE the newest event's pane (the
        # in-order watermark: a later event closes all earlier panes).
        out = []
        newest = int(ts[-1])
        lo = 0
        while self._cur + self._slide <= newest:
            hi = int(np.searchsorted(ts, self._cur + self._slide, "left"))
            self._take(x[lo:hi], y[lo:hi], oid[lo:hi])
            out.append(self._close())
            lo = hi
        self._take(x[lo:], y[lo:], oid[lo:])
        self._last = newest
        return out

    def flush(self) -> list:
        """End of stream: the open pane's events as one final pane."""
        if not self._fill:
            return []
        return [self._close()]

    def state(self) -> dict:
        return {
            "cur": int(self._cur),
            "slide_ms": int(self._slide),
            # wire-format identity: a checkpoint quantized against one
            # grid extent must not restore into another
            "wire_origin": [float(v) for v in self._wf.origin],
            "wire_scale": [float(v) for v in self._wf.scale],
            # the open pane as it will be emitted: 6 B a buffered point
            "pane": self._buf[:, :self._fill].copy(),
            "last_ts": int(self._last),
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
        cur = int(state["cur"])
        pane = state.get("pane")
        if pane is None:
            # The form every checkpoint had before the open pane was held
            # quantised: float64 rows and their timestamps. Quantising is
            # pointwise, so the resumed pane is the same bytes.
            ts = np.asarray(state["pend_ts"], np.int64)
            xy = np.asarray(state["pend_xy"], np.float64).reshape(-1, 2)
            pane = self._wf.pack_pane(xy[:, 0], xy[:, 1], state["pend_oid"])
            last = int(ts[-1]) if len(ts) else cur
        else:
            pane = np.asarray(pane, np.uint16)
            if pane.ndim != 2 or pane.shape[0] != 3:
                raise ValueError(
                    "checkpoint pane must be plane-major (3, n) uint16, "
                    f"got {pane.shape}"
                )
            last = int(state.get("last_ts", cur))
        n = pane.shape[1]
        self._fill = 0
        self._reserve(n)
        self._buf[:, :n] = pane
        self._fill = n
        self._cur = cur
        self._last = last


def wire_panes(chunks, wire_format: WireFormat, slide_ms: int,
               start_ms: int):
    """Generator form of ``WirePaneAssembler`` (see its docstring):
    chunks in, every completed pane out, final partial pane flushed at
    end of stream."""
    asm = WirePaneAssembler(wire_format, slide_ms, start_ms)
    for ch in chunks:
        yield from asm.feed(ch)
    yield from asm.flush()
