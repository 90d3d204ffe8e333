"""Pipelined ingest runtime — the async ship/compute/fetch executor.

ROADMAP item 1's overlap half, promoted from bench.py's ad-hoc slide
double-buffering into a real runtime subsystem: a bounded-depth
pipeline that keeps the host↔device link and the chip busy at the same time by
overlapping

- **ship(N+1)** — encode (ops/wire_codec.py, when armed) + stage the
  next pane's host→device transfer (``device_put``/``jnp.asarray`` are
  async: the DMA rides the link while the host moves on),
- **compute(N)** — dispatch the current window's program (async too —
  XLA queues it behind the transfer), and
- **fetch(N−1)** — the lagged, ORDERED device→host result sync
  (``jax.device_get``), so a fetch drains windows the device already
  finished instead of stalling the stream per window.

Ordering and results are bit-identical to the synchronous path: the
same programs run in the same order, only the host's sync points move
(tests/test_pipeline.py pins byte-identical egress). Donation stays
safe by construction: a shipped buffer is handed to exactly one compute
and the executor drops its reference immediately (no use-after-donate;
sfcheck's donation-safety pass guards the lifecycle), and carry-donating
steps chain ``x = step(x)`` — the sanctioned form.

**Opt-in** via ``SFT_PIPELINE`` (inline JSON or a path, read once at
import like ``SFT_FAULT_PLAN``; ``"1"``/``"on"`` = defaults) or
:func:`install` in-process. Default-off runs take the exact synchronous
code paths of PR 10 and earlier.

**Failure containment**: ``pipeline.ship`` / ``pipeline.fetch`` are
registered fault-injection points (faults.py) with chaos-matrix
kill/resume legs; consumers publish their checkpoint carry only when a
window's result is actually yielded, so a kill mid-overlap replays the
in-flight windows instead of losing them. When the overload circuit
breaker (overload.py) reports the device path open — dead or
degraded — the executor COLLAPSES to the synchronous cadence (depth 1,
no fetch lag; ``pipeline_collapsed``/``pipeline_resumed`` instant
events, force-flushed) and re-opens when the breaker closes.
"""

from __future__ import annotations

import contextlib
import json
import os
from collections import deque
from dataclasses import dataclass
from typing import Any, Callable, Iterable, Iterator, List, Optional

from spatialflink_tpu.faults import faults
from spatialflink_tpu.telemetry import telemetry

_POLICY_KEYS = {"depth", "fetch_lag", "codec"}

CODECS = ("off", "delta")


@dataclass(frozen=True)
class PipelinePolicy:
    """Declarative pipeline configuration (strict parse — unknown keys
    raise, the fault-plan rule: a typo'd knob that silently does nothing
    is worse than none).

    - ``depth``: panes shipped but not yet computed, INCLUDING the one
      about to compute — depth d keeps d−1 panes staged beyond the
      in-flight item (≥1; 1 = no ship-ahead);
    - ``fetch_lag``: computed windows left in flight before the oldest
      is fetched (0 = fetch every window immediately — the synchronous
      cadence with the executor's bookkeeping);
    - ``codec``: ``"delta"`` arms the delta-bitpacked wire-pane codec
      (ops/wire_codec.py) on paths that ship wire panes; ``"off"``
      ships raw planes.
    """

    depth: int = 2
    fetch_lag: int = 2
    codec: str = "off"

    def __post_init__(self):
        if int(self.depth) < 1:
            raise ValueError(f"depth must be >= 1, got {self.depth}")
        if int(self.fetch_lag) < 0:
            raise ValueError(
                f"fetch_lag must be >= 0, got {self.fetch_lag}"
            )
        if self.codec not in CODECS:
            raise ValueError(
                f"unknown codec {self.codec!r} (codecs: {CODECS})"
            )

    @classmethod
    def from_dict(cls, d: dict) -> "PipelinePolicy":
        if not isinstance(d, dict):
            raise ValueError(
                f"pipeline policy must be an object, got "
                f"{type(d).__name__}"
            )
        unknown = sorted(set(d) - _POLICY_KEYS)
        if unknown:
            raise ValueError(f"pipeline policy has unknown keys {unknown}")
        return cls(**d)

    @classmethod
    def from_env(cls, spec: str) -> "PipelinePolicy":
        """``SFT_PIPELINE`` forms: ``1``/``on``/``true`` (defaults),
        inline JSON object, or a path to a JSON file."""
        text = spec.strip()
        if text.lower() in ("1", "on", "true", "yes"):
            return cls()
        if not text.startswith("{"):
            with open(text) as f:
                text = f.read()
        return cls.from_dict(json.loads(text))

    def to_dict(self) -> dict:
        return {
            "depth": int(self.depth), "fetch_lag": int(self.fetch_lag),
            "codec": self.codec,
        }


# ---------------------------------------------------------------------------
# Module policy slot (the overload.py install idiom; no __main__ here)


_policy: Optional[PipelinePolicy] = None


def install(policy: PipelinePolicy) -> PipelinePolicy:
    """Make ``policy`` the process-global pipeline policy: the pane
    engines and the dataflow driver consult :func:`policy` when no
    explicit one is passed."""
    global _policy
    _policy = policy
    return policy


def uninstall():
    global _policy
    _policy = None


def policy() -> Optional[PipelinePolicy]:
    return _policy


def arm_from_env() -> bool:
    """Arm from ``SFT_PIPELINE``; no-op when unset. Called once at
    import so pipelined chaos subprocesses arm with zero code."""
    spec = os.environ.get("SFT_PIPELINE")
    if not spec:
        return False
    install(PipelinePolicy.from_env(spec))
    return True


# ---------------------------------------------------------------------------
# The executor


def breaker_collapsed() -> bool:
    """True while the overload circuit breaker holds the device path
    open — the pipeline must not stack windows onto a dead device path."""
    from spatialflink_tpu import overload

    ctrl = overload.controller()
    if ctrl is None or ctrl.breaker is None:
        return False
    return ctrl.breaker.state == "open"


class PipelinedExecutor:
    """Generic bounded overlap over an item stream.

    Stage contracts (all host callables):

    - ``ship(item) -> staged``: encode + begin the async host→device
      transfer; may return ``None`` for items with nothing to ship
      (trailing flush panes). The executor passes ``staged`` to exactly
      ONE compute call and drops its reference — hand the buffer to a
      donating kernel freely.
    - ``compute(item, staged) -> work | None``: dispatch the window
      program; ``None`` = no window fired (gap pane). Must not sync.
    - ``fetch(works: list) -> iterable``: the ONE true-sync point —
      materialize the listed windows' results IN ORDER and return the
      values to yield. Mid-stream the list has one element; the final
      drain passes everything still in flight so the whole tail costs
      one device round trip (the flush_pending idiom).

    ``spans=True`` wraps each processed item in a ``window.pipeline``
    span with ``ship``/``compute``/``fetch`` children, so the overlap
    shows up in sfprof attribution as vanishing inter-window host gap —
    ingest rides INSIDE window spans instead of the dead time between
    them.
    """

    def __init__(self, pol: PipelinePolicy, *,
                 ship: Callable[[Any], Any],
                 compute: Callable[[Any, Any], Any],
                 fetch: Callable[[List[Any]], Iterable],
                 label: str = "pipeline",
                 spans: bool = False,
                 node: Optional[str] = None,
                 e2e_end: Optional[Callable[[Any], Any]] = None):
        self.pol = pol
        self._ship_fn = ship
        self._compute_fn = compute
        self._fetch_fn = fetch
        self.label = label
        self.spans = spans
        #: Latency-lineage hook: extracts an item's event-time window
        #: end (ms) — when set, each stage boundary feeds its own
        #: telemetry ``record_e2e`` bucket (ship/compute/fetch; the
        #: driver stamps assemble/commit around its executor). None =
        #: items are not windows (segmented scans) — no stamps.
        self._e2e_end = e2e_end
        #: Node-attribution tag for the per-item work (None inherits the
        #: caller's ambient scope — the executor runs on its thread, so
        #: a driver/DAG scope already propagates; set it for standalone
        #: pane engines with no driver above them).
        self.node = node
        self.collapsed = False

    # -- stages (fault points live here) ---------------------------------------

    def _ship(self, item):
        if faults.armed:  # chaos injection point (faults.py)
            faults.hit("pipeline.ship")
        return self._ship_fn(item)

    def _fetch(self, works: List[Any]) -> Iterable:
        if faults.armed:  # chaos injection point (faults.py)
            faults.hit("pipeline.fetch")
        return self._fetch_fn(works)

    def _stamp_e2e(self, item, stage):
        """Latency-lineage stage stamp when an ``e2e_end`` extractor is
        wired; returns the item's event-time end so the fetch stage can
        stamp without re-extracting."""
        if self._e2e_end is None or not telemetry.enabled:
            return None
        end = self._e2e_end(item)
        if end is not None:
            telemetry.record_e2e(end, stage)
        return end

    def _sync_collapse_state(self):
        want = breaker_collapsed()
        if want == self.collapsed:
            return
        self.collapsed = want
        if telemetry.enabled:
            # Literal event-name heads per branch — the contract-twin
            # pass statically diffs emit names against the sfprof
            # consumer registry (the slo.py transition idiom).
            if want:
                telemetry.record_pipeline(collapses=1)
                telemetry.emit_instant("pipeline_collapsed",
                                       label=self.label)
            else:
                telemetry.record_pipeline(resumes=1)
                telemetry.emit_instant("pipeline_resumed",
                                       label=self.label)
            telemetry.maybe_flush_stream(force=True)

    # -- the loop --------------------------------------------------------------

    def run(self, items: Iterable) -> Iterator:
        """Drive ``items`` through the three stages; yield fetch results
        in item order. The in-flight window count never exceeds
        ``fetch_lag`` and the ship-ahead never exceeds ``depth``; while
        the circuit is open both clamp to the synchronous cadence."""
        shipped: deque = deque()
        inflight: deque = deque()
        ends: deque = deque()  # event-time ends aligned with inflight
        it = iter(items)
        exhausted = False

        def refill(depth: int):
            nonlocal exhausted
            while not exhausted and len(shipped) < depth:
                try:
                    item = next(it)
                except StopIteration:
                    exhausted = True
                    break
                shipped.append((item, self._ship(item)))
                self._stamp_e2e(item, "ship")

        def maybe_span(name: str):
            return (telemetry.span(name) if self.spans
                    else contextlib.nullcontext())

        self._sync_collapse_state()
        # Prime the ship-ahead once, outside any window span (the
        # warm-up transfer); each iteration afterwards tops it up by
        # one INSIDE its window span — ingest rides the window, not
        # the gap between windows.
        refill(1 if self.collapsed else max(1, int(self.pol.depth)))
        while True:
            if not shipped:
                refill(1)  # depth-1 cadence: probe for the next item
                if not shipped:
                    break
            depth = 1 if self.collapsed else max(1, int(self.pol.depth))
            lag = 0 if self.collapsed else max(0, int(self.pol.fetch_lag))
            out: list = []
            # Scope covers the item's work only, never a yield — a
            # suspended generator must not leak its tag to the consumer.
            with telemetry.scope(self.node), \
                    maybe_span(f"window.{self.label}"):
                with maybe_span("ship"):
                    refill(depth)
                item, staged = shipped.popleft()
                with maybe_span("compute"):
                    work = self._compute_fn(item, staged)
                del staged  # the one compute owns (and may donate) it
                if work is not None:
                    inflight.append(work)
                    ends.append(self._stamp_e2e(item, "compute"))
                    if telemetry.enabled:
                        telemetry.record_pipeline(
                            windows=1,
                            **({"sync": 1} if self.collapsed
                               else {"overlapped": 1}),
                        )
                while len(inflight) > lag:
                    with maybe_span("fetch"):
                        out.extend(self._fetch([inflight.popleft()]))
                    end = ends.popleft()
                    if end is not None and telemetry.enabled:
                        telemetry.record_e2e(end, "fetch")
            yield from out
            self._sync_collapse_state()
        if inflight:  # final drain: ONE true sync for the whole tail
            with telemetry.scope(self.node):
                tail = list(self._fetch(list(inflight)))
                if telemetry.enabled:
                    for end in ends:
                        if end is not None:
                            telemetry.record_e2e(end, "fetch")
            yield from tail
            inflight.clear()
            ends.clear()


# Subprocess arming: a pipelined chaos child only needs SFT_PIPELINE in
# its env (the faults.py idiom).
arm_from_env()
