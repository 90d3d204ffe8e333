"""The SFT_* environment-variable registry — one owner and hazard class
per var.

Every ``SFT_*`` variable the codebase reads is declared here;
``tools/sfcheck``'s ``env-registry`` pass fails the tree on any
unregistered ``os.environ``/``getenv`` read site, and on any registered
var nothing reads (drift cuts both ways). ``tools/ci.py`` derives its
gate-stage ambient-environment scrub from :func:`gate_scrub_vars`, so a
new armed-plan var registered here is scrubbed automatically — it can
never leak an injected fault into a healthy gate run the way an ambient
``SFT_FAULT_PLAN`` once could.

Hazard classes:

- ``armed`` — arms faults/policies or forces failures; ambient values
  SABOTAGE any run that did not set them (the chaos/overload plans, the
  bench failure-forcing test knobs). The CI gate scrubs these from
  every stage.
- ``capture`` — selects artifact outputs (ledgers, streams, traces);
  ambient values redirect captures but never change verdicts, and gate
  stages that capture set their own.
- ``tuning`` — behavior knobs with safe defaults (deadlines, smoke
  sizing, cache dirs); gate stages pin the ones they depend on.
- ``internal`` — process-internal markers set by a parent for its own
  children; never user-facing.

This module is deliberately **stdlib-only and import-free** so the CI
gate can load it by file path without importing the package (whose
``__init__`` configures jax — the sfprof no-cross-import rule).
"""

from __future__ import annotations

HAZARD_CLASSES = ("armed", "capture", "tuning", "internal")

#: name → {"owner": reading module, "hazard": class, "doc": one line}
ENV_VARS = {
    "SFT_FAULT_PLAN": {
        "owner": "spatialflink_tpu/faults.py", "hazard": "armed",
        "doc": "fault plan (inline JSON or path), armed at import",
    },
    "SFT_OVERLOAD_POLICY": {
        "owner": "spatialflink_tpu/overload.py", "hazard": "armed",
        "doc": "overload policy (inline JSON or path) the driver installs",
    },
    "SFT_QSERVE": {
        "owner": "spatialflink_tpu/qserve.py", "hazard": "armed",
        "doc": "qserve serving config (inline JSON or path): standing "
               "queries + per-tenant-class budgets; an ambient value "
               "would register ghost queries / arm QoS budgets in runs "
               "that never asked for them",
    },
    "SFT_ABLATE": {
        "owner": "spatialflink_tpu/ablation.py", "hazard": "armed",
        "doc": "kernel-ablation spec (comma list, inline JSON, or "
               "path), armed at import; substituted kernels return "
               "cached zeros, so an ambient value silently falsifies "
               "every measurement (the run is tainted, but the gate "
               "must never run tainted in the first place)",
    },
    "SFT_LEDGER_PATH": {
        "owner": "spatialflink_tpu/dag.py", "hazard": "capture",
        "doc": "run-ledger output path",
    },
    "SFT_LEDGER_STREAM": {
        "owner": "spatialflink_tpu/telemetry.py", "hazard": "capture",
        "doc": "append-only JSONL ledger stream path",
    },
    "SFT_LEDGER_STREAM_INTERVAL_S": {
        "owner": "spatialflink_tpu/telemetry.py", "hazard": "capture",
        "doc": "stream flush pacing (seconds)",
    },
    "SFT_BLACKBOX": {
        "owner": "spatialflink_tpu/telemetry.py", "hazard": "capture",
        "doc": "flight-recorder ring size (last-N window summaries + "
               "instants dumped to <stream>.blackbox.json on fault "
               "fire / stream seal; '0' disables, default 64)",
    },
    "SFT_LEDGER_DIR": {
        "owner": "bench_suite.py", "hazard": "capture",
        "doc": "per-config ledger directory for suite runs",
    },
    "SFT_DIAL_DEADLINE_S": {
        "owner": "spatialflink_tpu/driver.py", "hazard": "tuning",
        "doc": "first-device-touch deadline: when SET it bounds the "
               "driver's first device-path window (a --checkpoint "
               "resume on an unreachable device); timeout seals the "
               "ledger stream with reason dial_timeout",
    },
}


def gate_scrub_vars() -> list:
    """The vars the CI gate must remove from every stage's ambient
    environment: everything hazard-class ``armed``."""
    return sorted(n for n, meta in ENV_VARS.items()
                  if meta["hazard"] == "armed")


def _selfcheck() -> None:
    for name, meta in ENV_VARS.items():
        if meta["hazard"] not in HAZARD_CLASSES:
            raise ValueError(
                f"ENV_VARS[{name!r}]: unknown hazard class "
                f"{meta['hazard']!r} (classes: {HAZARD_CLASSES})"
            )


_selfcheck()
