"""The instant-event consumer registry — every ``ph:"i"`` event name
the observability stack emits and the sfprof surfaces understand.

``sfprof recover`` rebuilds crash stories from the ledger stream, the
smoke/chaos harnesses assert transitions, and ``health``/``recover``
summarize them — all BY NAME, so a typo'd producer name breaks crash
recovery silently (the event rides the stream, and every consumer
ignores it). This registry is the contract's consumer side:
``tools/sfcheck``'s ``contract-twin`` pass statically diffs every
``emit_instant`` site in ``spatialflink_tpu/`` against it, both ways —
an emitted name the registry lacks AND a registered name nothing emits
are findings.

Kept sfprof-side (never imported by ``spatialflink_tpu``) under the
no-cross-import twin rule: the CLI must stay importable without
configuring jax.
"""

from __future__ import annotations

from typing import Dict, List, Optional

#: Exact instant-event names.
INSTANT_EVENTS = frozenset({
    # fault injection (spatialflink_tpu/faults.py + telemetry.py)
    "fault_armed",
    # the dataflow driver's self-healing (driver.py via telemetry.py)
    "driver_retry",
    "failover",
    # host↔device link-health probe (telemetry.LinkProbe)
    "link_probe",
    # device-path circuit breaker (overload.CircuitBreaker)
    "circuit_open",
    "circuit_closed",
    "circuit_half_open",
    # overload controller transitions (overload.OverloadController)
    "overload_backpressure:engaged",
    "overload_backpressure:released",
    "overload_shedding:admission",
    "overload_shedding:lag",
    "overload_shedding:oldest",
    "overload_recovered:admission",
    "overload_recovered:lag",
    # kernel-ablation harness armed (spatialflink_tpu/ablation.py) —
    # the event that marks a capture's numbers as deliberately wrong
    "ablation_armed",
    # qserve standing-query registry (spatialflink_tpu/qserve.py):
    # registration lifecycle + per-tenant-class admission rejections
    "qserve_registered",
    "qserve_unregistered",
    "qserve_evicted",
    # flight recorder (telemetry.py): a <stream>.blackbox.json dump was
    # written — on fault fire / stream seal; `sfprof blackbox` renders
    # it and `recover` folds it into the rebuilt ledger
    "blackbox_dumped",
})

#: Literal name prefixes for parameterized events (the suffix names the
#: injection point / SLO check / ladder rung).
INSTANT_EVENT_PREFIXES = (
    "fault_fired:",
    "slo_violation:",
    "slo_recovered:",
    "overload_rung_down:",
    "overload_rung_up:",
    # per-tenant-class QoS transitions (overload.py tenant budgets;
    # the suffix names the tenant class)
    "overload_tenant_shed:",
    "overload_tenant_recovered:",
    # qserve bucket-capacity rung transitions (the suffix names the
    # (kind, k-rung, radius-class) bucket)
    "qserve_rung:",
    # composed-dataflow per-node failover (dag.py — the suffix names
    # the node; siblings keep their device path, so recovery stories
    # need the node name, not just the global `failover` event)
    "dag_node_failover:",
)

#: Display groups for the health/recover summaries.
_GROUPS = (
    ("faults", ("fault_armed", "fault_fired:")),
    ("self-healing", ("driver_retry", "failover")),
    ("circuit", ("circuit_",)),
    ("overload", ("overload_",)),
    ("dag", ("dag_node_failover:",)),
    ("qserve", ("qserve_",)),
    ("slo", ("slo_violation:", "slo_recovered:")),
    ("ablation", ("ablation_armed",)),
    ("blackbox", ("blackbox_dumped",)),
)


def classify(name: str) -> Optional[str]:
    """Display group of a known instant-event name, else None."""
    if name not in INSTANT_EVENTS \
            and not any(name.startswith(p)
                        for p in INSTANT_EVENT_PREFIXES):
        return None
    for group, heads in _GROUPS:
        if any(name == h or name.startswith(h) for h in heads):
            return group
    return None


def notable_event_counts(events: List[dict]) -> Dict[str, int]:
    """Per-group counts of registered instant events in a ledger's
    event list — the crash-story summary ``health``/``recover`` print."""
    out: Dict[str, int] = {}
    for ev in events or []:
        if ev.get("ph") != "i":
            continue
        group = classify(str(ev.get("name", "")))
        if group is not None:
            out[group] = out.get(group, 0) + 1
    return out
