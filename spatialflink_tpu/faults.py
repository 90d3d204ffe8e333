"""Deterministic fault injection — rehearse the failure modes on demand.

The failure modes a stream processor meets in production (a device
that stops answering, a hung first dispatch, a process SIGKILLed
mid-capture) can otherwise only be reproduced by waiting for the next
outage. This module makes them a *scheduled, replayable* event: named
injection points threaded through the host/device boundary
(``operators/base.py`` ship / jitted dispatch / ``telemetry.fetch``),
the Kafka fetch and leader paths, window assembly, sink commits, and the
dataflow driver, armed by a JSON *fault plan*.

Contract (the telemetry idiom): **disarmed-free** — every injection
point costs ONE attribute check while no plan is armed::

    if faults.armed:
        faults.hit("device.ship")

Plans arm via ``SFT_FAULT_PLAN`` (inline JSON or a path to a JSON file,
read once at import so chaos *subprocesses* arm with zero code) or
``faults.arm(...)`` in-process. A plan is a list of rules::

    [{"point": "device.dispatch", "at": 3, "times": 2, "kind": "raise"}]

- ``point``: a registered injection point (arming an unknown point is an
  error — a typo'd plan that silently never fires is worse than none);
- ``at``: fire on the Nth hit of that point (1-based, default 1);
- ``times``: how many consecutive hits fire (default 1; a value larger
  than the driver's retry budget defeats retries, forcing the
  crash/failover paths);
- ``kind``: ``raise`` (InjectedFault), ``hang`` (sleep ``hang_s`` then
  raise — the bounded-timeout analog of a wedged device), ``abort``
  (``os._exit(137)`` — the SIGKILL analog: no handlers, no flush, no
  epilogue), or ``partial_write`` (sink commits only: write a byte
  prefix, then raise — a torn append).

Determinism: triggers are hit-count based, so a fixed input stream
replays the exact same fault schedule; an optional ``prob``/``seed``
pair draws per-hit from a dedicated ``random.Random(seed)`` so even
probabilistic chaos replays bit-identically. Every firing is recorded
(``faults.fired``) and — when telemetry is enabled — emitted as a
``fault_fired:<point>`` instant event and force-flushed to the ledger
stream (a fault is exactly the record that must survive the crash it
causes).

This module imports nothing at module scope beyond the stdlib, so every
layer (telemetry included) can import it without cycles.
"""

from __future__ import annotations

import json
import os
import random
import threading
import time
from dataclasses import dataclass, field
from typing import Any, Dict, List, Optional


#: Registered injection points — the chaos matrix
#: (tests/test_chaos_matrix.py) covers EVERY entry: inject → crash →
#: resume → exact egress equality. Add a point here only with a matching
#: matrix entry.
INJECTION_POINTS: Dict[str, str] = {
    "device.ship": "operators/base.py:ship — host→device batch transfer",
    "device.dispatch": "telemetry.instrument_jit — instrumented kernel "
                       "dispatch (jitted, mesh window programs, bench "
                       "steps)",
    "device.fetch": "telemetry.fetch — device→host true-sync fetch",
    "window.feed": "streams/windows.py:WindowAssembler.feed and "
                   "streams/columns.py:ColumnarWindowAssembler.feed — "
                   "per-event window assembly",
    "soa.feed": "streams/soa.py sliding assemblers — per-chunk SoA "
                "window assembly",
    "kafka.fetch": "streams/kafka.py:WireKafkaSource — per-partition "
                   "fetch round",
    "kafka.leader": "streams/kafka_wire.py:_with_leader_retry — "
                    "leader-routed request attempt",
    "sink.write": "streams/sinks.py:TransactionalFileSink.commit — "
                  "egress append (supports partial_write)",
    "driver.window": "driver.py — device-path window processing",
    "overload.admit": "overload.py:OverloadController.admit_item — "
                      "source→assembler admission decision",
    "source.stall": "driver.py:_drive — per-item source pull (the "
                    "slow-consumer / wedged-upstream hang point)",
    "qserve.register": "qserve.py:QueryRegistry.apply — standing-query "
                       "register/unregister command application (the "
                       "kill-mid-registration-churn point)",
    "dag.node": "dag.py:DataflowDAG — per-node device-path window "
                "processing (the per-node retry/failover ladder's "
                "crash point)",
    "dag.commit": "streams/sinks.py:MultiSink.commit — per-sink append "
                  "inside the atomic unit commit (`at: 2` lands BETWEEN "
                  "one sink's commit and the next — the cut the unit "
                  "checkpoint must survive)",
    "shard.exchange": "parallel/halo.py — grid-partitioned halo "
                      "exchange dispatch (boundary-cell pane ppermute; "
                      "the kill-mid-exchange point the sharded "
                      "kill/resume leg cuts at)",
}

#: Points whose callers implement the cooperative ``partial_write`` kind.
PARTIAL_WRITE_POINTS = frozenset({"sink.write"})

FAULT_KINDS = ("raise", "hang", "partial_write", "abort")

#: The exit code the ``abort`` kind dies with — 128+SIGKILL, the code a
#: real ``kill -9`` produces, so supervisors treat both identically.
ABORT_EXIT_CODE = 137


class InjectedFault(RuntimeError):
    """A deliberately injected failure (never raised by real code paths)."""

    def __init__(self, point: str, kind: str = "raise", hit: int = 0):
        super().__init__(
            f"injected fault at {point!r} (kind={kind}, hit #{hit})"
        )
        self.point = point
        self.kind = kind
        self.hit = hit


@dataclass
class FaultRule:
    """One armed fault: fires on hits ``at .. at+times-1`` of ``point``."""

    point: str
    kind: str = "raise"
    at: int = 1
    times: int = 1
    hang_s: float = 0.05
    prob: float = 1.0
    seed: int = 0
    _rng: random.Random = field(init=False, repr=False, default=None)

    def __post_init__(self):
        if self.point not in INJECTION_POINTS:
            raise ValueError(
                f"unknown injection point {self.point!r} "
                f"(registered: {sorted(INJECTION_POINTS)})"
            )
        if self.kind not in FAULT_KINDS:
            raise ValueError(
                f"unknown fault kind {self.kind!r} (kinds: {FAULT_KINDS})"
            )
        if self.kind == "partial_write" \
                and self.point not in PARTIAL_WRITE_POINTS:
            raise ValueError(
                f"kind 'partial_write' is only supported at "
                f"{sorted(PARTIAL_WRITE_POINTS)}, not {self.point!r}"
            )
        if self.at < 1 or self.times < 1:
            raise ValueError("`at` and `times` must be >= 1")
        # Dedicated, seeded stream per rule: probabilistic plans replay
        # bit-identically regardless of global RNG use elsewhere.
        self._rng = random.Random(self.seed)

    def should_fire(self, hit: int) -> bool:
        if not (self.at <= hit < self.at + self.times):
            return False
        if self.prob >= 1.0:
            return True
        return self._rng.random() < self.prob

    def to_dict(self) -> Dict[str, Any]:
        return {
            "point": self.point, "kind": self.kind, "at": self.at,
            "times": self.times, "hang_s": self.hang_s, "prob": self.prob,
            "seed": self.seed,
        }


_RULE_KEYS = {"point", "kind", "at", "times", "hang_s", "prob", "seed"}


def parse_plan(plan) -> List[FaultRule]:
    """A plan is a JSON list of rule objects (a single object is accepted
    as a 1-rule plan). Unknown keys raise — a typo'd trigger that
    silently never fires is the worst failure mode a chaos tool can
    have."""
    if isinstance(plan, dict):
        plan = [plan]
    if not isinstance(plan, list):
        raise ValueError(f"fault plan must be a list of rules, got "
                         f"{type(plan).__name__}")
    rules = []
    for i, r in enumerate(plan):
        if not isinstance(r, dict):
            raise ValueError(f"fault rule #{i} is not an object: {r!r}")
        unknown = sorted(set(r) - _RULE_KEYS)
        if unknown:
            raise ValueError(f"fault rule #{i} has unknown keys {unknown}")
        rules.append(FaultRule(**r))
    return rules


class FaultInjector:
    """Process-global injector (the ops/counters.py one-singleton idiom).

    ``armed`` is the ONLY state the disarmed hot path reads.
    """

    def __init__(self):
        self.armed = False
        self.rules: List[FaultRule] = []
        self.counts: Dict[str, int] = {}
        self.fired: List[Dict[str, Any]] = []
        self._lock = threading.Lock()

    # -- arming ----------------------------------------------------------------

    def arm(self, plan) -> "FaultInjector":
        """Arm a plan (list/dict, JSON string, or a path to a JSON file).
        Resets hit counts — arming IS the start of a chaos schedule."""
        if isinstance(plan, str):
            text = plan.strip()
            if not text.startswith(("[", "{")):
                with open(text) as f:
                    text = f.read()
            plan = json.loads(text)
        with self._lock:
            self.rules = parse_plan(plan)
            self.counts = {}
            self.fired = []
            self.armed = bool(self.rules)
        if self.armed:
            self._telemetry_instant(
                "fault_armed", plan=[r.to_dict() for r in self.rules]
            )
        return self

    def arm_from_env(self) -> bool:
        """Arm from ``SFT_FAULT_PLAN`` (inline JSON or file path); no-op
        when unset. Called once at import so chaos subprocesses arm with
        zero code."""
        spec = os.environ.get("SFT_FAULT_PLAN")
        if not spec:
            return False
        self.arm(spec)
        return True

    def disarm(self):
        with self._lock:
            self.armed = False
            self.rules = []
            self.counts = {}
            self.fired = []

    # -- the hot-path hook -----------------------------------------------------

    def hit(self, point: str) -> Optional[str]:
        """One pass through an injection point. Callers gate on
        ``faults.armed`` so the disarmed cost is one attribute check.

        Raises :class:`InjectedFault` (``raise``/``hang`` kinds), kills
        the process (``abort``), or returns ``"partial_write"`` for the
        caller to cooperate with. Returns ``None`` when nothing fires.
        """
        with self._lock:
            count = self.counts.get(point, 0) + 1
            self.counts[point] = count
            rule = None
            for r in self.rules:
                if r.point == point and r.should_fire(count):
                    rule = r
                    break
        if rule is None:
            return None
        return self._fire(rule, count)

    def _fire(self, rule: FaultRule, count: int) -> Optional[str]:
        # WHETHER a fault fires is the deterministic hit-count rule; the
        # wall timestamp below only annotates the fired-fault telemetry
        # record, and nothing downstream feeds egress/checkpoint bytes.
        rec = {"point": rule.point, "kind": rule.kind, "hit": count,
               "unix": time.time()}  # sfcheck: ok=replay-determinism -- annotation only
        with self._lock:
            self.fired.append(rec)
        self._telemetry_fired(rule.point, rule.kind, count)
        if rule.kind == "abort":
            # The SIGKILL analog: no atexit, no finally, no flush — the
            # process vanishes mid-thought like a real kill -9 / power
            # loss. Crash-consistency is exactly what this rehearses.
            os._exit(ABORT_EXIT_CODE)
        if rule.kind == "hang":
            # Hang-with-timeout: a wedged-but-bounded stall, then the
            # failure surfaces.
            time.sleep(rule.hang_s)
            raise InjectedFault(rule.point, "hang", count)
        if rule.kind == "partial_write":
            return "partial_write"
        raise InjectedFault(rule.point, "raise", count)

    # -- telemetry (lazy import: telemetry itself imports this module) ---------

    @staticmethod
    def _telemetry_instant(name: str, **args):
        try:
            from spatialflink_tpu.telemetry import telemetry
        except Exception:  # partial interpreter teardown
            return
        if telemetry.enabled:
            telemetry.emit_instant(name, **args)

    @staticmethod
    def _telemetry_fired(point: str, kind: str, count: int):
        try:
            from spatialflink_tpu.telemetry import telemetry
        except Exception:
            return
        if telemetry.enabled:
            telemetry.record_fault(point, kind=kind, hit=count)


faults = FaultInjector()

# Subprocess arming: a chaos child only needs SFT_FAULT_PLAN in its env.
faults.arm_from_env()
