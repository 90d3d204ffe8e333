"""Ledger-stream reading + crash recovery (``sfprof recover``).

A ledger STREAM is the append-only JSONL artifact ``telemetry`` writes
when ``SFT_LEDGER_STREAM`` is set — the crash-resilient inverse of the
single-document ledger. Record grammar (one JSON object per line):

    {"t": "prologue", "stream_version": 1, "ledger_version": 1,
     "created_unix": ..., "env": {...}}
    {"t": "spans",      "seq": N, "events": [...]}         (0+ per flush)
    {"t": "checkpoint", "seq": N, "unix": ..., "snapshot": {...},
     "kernels": [...]}                                      (1 per flush)
    {"t": "epilogue",   "seq": N, "unix": ..., "reason": "...",
     "bench": {...}?, "slo": {...}?}                        (seal)

``recover`` rebuilds a schema-valid ledger document from ANY prefix of
that grammar: the LAST checkpoint supplies snapshot + kernel table, the
span batches concatenate into the event list, the epilogue (when the
stream was sealed) supplies the bench record / SLO verdict and the
termination reason. A SIGKILL mid-run costs at most one flush interval
of spans and one checkpoint of gauge updates — and the recovery block
says so honestly (``truncated``, ``last_checkpoint_unix``, skipped
bytes) instead of pretending the artifact is complete.

Tolerance: a half-written line (the only corruption a kill can produce)
is dropped and counted, and it marks the truncation point — ordinary
records after it are ignored, never silently re-synchronized. The ONE
exception is the epilogue: the dial watchdog (driver.py) seals
a wedged run's stream by appending an epilogue AFTER the partial tail
(on its own line), and that termination reason must survive recovery — so past
the truncation point only ``t == "epilogue"`` records are honored.
"""

from __future__ import annotations

import json
import os
from typing import Any, Dict, List, Optional, Tuple

from tools.sfprof.ledger import LEDGER_VERSION

#: Mirror of spatialflink_tpu/telemetry.py:STREAM_VERSION — kept as a
#: literal so the CLI never imports spatialflink_tpu (whose import
#: configures jax). Bump BOTH; tests/test_ledger_stream.py cross-pins.
#: v2: checkpoints carry the per-node/collective snapshot blocks.
#: v3: checkpoints may carry the ``e2e`` latency-lineage block, and a
#: ``<stream>.blackbox.json`` flight-recorder dump may sit beside the
#: stream (``recover`` folds it in).
STREAM_VERSION = 3

#: Versions recover still accepts: the v1→v2→v3 changes are additive
#: (checkpoint snapshots grew blocks; the grammar is identical), and a
#: chip capture stranded by the r3–r5 loss mode must stay recoverable.
SUPPORTED_STREAM_VERSIONS = (1, 2, 3)

#: Snapshot skeleton for a stream killed before its first checkpoint:
#: every key ``ledger.validate`` requires, zeroed — plus an explicit
#: marker so no one mistakes it for measured state.
_EMPTY_SNAPSHOT: Dict[str, Any] = {
    "compiles": 0, "bytes_h2d": 0, "bytes_d2h": 0,
    "window_latency_p50_ms": None, "window_latency_p95_ms": None,
    "max_watermark_lag_ms": 0, "watermark_lag_p99_ms": None,
    "late_dropped": 0, "h2d_transfers": 0, "d2h_transfers": 0,
    "events": 0, "dropped_events": 0, "kernels": {}, "compaction": {},
    "synthesized": True,
}


def read_records(path: str) -> Tuple[List[dict], Dict[str, Any]]:
    """(records, tail_info): every decodable record up to the first
    undecodable line — plus, PAST that truncation point, epilogue
    records only (the external-seal case: a watchdog appends the
    termination reason after a half-written tail; see module
    docstring). ``tail_info``: ``partial_tail`` (a truncated line was
    dropped), ``skipped_lines``/``skipped_bytes`` (non-epilogue content
    at/after the truncation point)."""
    with open(path, "rb") as f:
        raw = f.read()
    lines = raw.split(b"\n")
    records: List[dict] = []
    partial = False
    skipped_lines = 0
    skipped_bytes = 0
    for i, line in enumerate(lines):
        if not line.strip():
            continue
        if partial:
            # Past the truncation point: honor sealing epilogues only;
            # anything else stays skipped (no silent re-sync).
            try:
                rec = json.loads(line)
            except ValueError:
                rec = None
            if isinstance(rec, dict) and rec.get("t") == "epilogue":
                records.append(rec)
            else:
                skipped_lines += 1
                skipped_bytes += len(line) + 1
            continue
        try:
            rec = json.loads(line)
        except ValueError:
            partial = True
            skipped_bytes += len(line) + 1
            continue
        if not isinstance(rec, dict) or "t" not in rec:
            raise ValueError(
                f"line {i + 1}: not a ledger-stream record"
            )
        records.append(rec)
    return records, {
        "partial_tail": partial,
        "skipped_lines": skipped_lines,
        "skipped_bytes": skipped_bytes,
    }


def recover(path: str) -> Tuple[Dict[str, Any], Dict[str, Any]]:
    """(ledger_doc, recovery_info) reconstructed from a (possibly
    truncated) ledger stream. Raises ``ValueError`` when the file does
    not start with a stream prologue — that is not a truncation, it is
    the wrong kind of file."""
    records, tail = read_records(path)
    if not records or records[0].get("t") != "prologue":
        raise ValueError(f"{path}: no ledger-stream prologue")
    prologue = records[0]
    ver = prologue.get("stream_version")
    if ver not in SUPPORTED_STREAM_VERSIONS:
        raise ValueError(
            f"{path}: stream_version {ver} not in supported "
            f"{SUPPORTED_STREAM_VERSIONS}"
        )

    events: List[dict] = []
    checkpoint: Optional[dict] = None
    epilogue: Optional[dict] = None
    spans_batches = 0
    checkpoints = 0
    for rec in records[1:]:
        kind = rec.get("t")
        if kind == "spans":
            spans_batches += 1
            events.extend(rec.get("events") or [])
        elif kind == "checkpoint":
            checkpoints += 1
            checkpoint = rec
        elif kind == "epilogue":
            epilogue = rec
        # Unknown record kinds are forward-compatible: skipped, counted
        # nowhere — the prologue version gate is the breaking-change lever.

    # Flight-recorder fold: a crash dump beside the stream
    # (telemetry.dump_blackbox writes <stream>.blackbox.json on fault
    # fire / seal) carries the LAST ring of instants — including any
    # emitted after the final flushed span batch, exactly the tail a
    # kill truncates. Fold ring instants NEWER than the last recovered
    # event (same perf_counter-µs timebase) into the event list; older
    # ones already ride a spans batch.
    bb_path = path + ".blackbox.json"
    bb_doc: Optional[dict] = None
    bb_folded = 0
    if os.path.exists(bb_path):
        try:
            with open(bb_path) as f:
                loaded = json.load(f)
            if isinstance(loaded, dict):
                bb_doc = loaded
        except (OSError, ValueError):
            bb_doc = None  # unreadable dump: counted below, never fatal
    if bb_doc is not None:
        last_ts = max((ev.get("ts") or 0 for ev in events
                       if isinstance(ev, dict)), default=0)
        for rec in bb_doc.get("ring") or []:
            if not isinstance(rec, dict) or rec.get("t") != "instant":
                continue
            ts = rec.get("ts") or 0
            if ts <= last_ts:
                continue
            events.append({
                "name": rec.get("name"), "cat": "telemetry",
                "ph": "i", "ts": ts, "s": "t",
                "args": rec.get("args") or {},
                "blackbox": True,  # provenance: folded, not streamed
            })
            bb_folded += 1

    sealed = epilogue is not None
    # An EXTERNAL seal (telemetry's own epilogues carry no ``sealed_by``;
    # the dial watchdogs' do) marks an attributable crash, not a complete
    # capture: the run died without its final flush, so the stream is
    # truncated even on a clean line boundary.
    external_seal = "sealed_by" in (epilogue or {})
    truncated = tail["partial_tail"] or not sealed or external_seal
    snapshot = (checkpoint or {}).get("snapshot") or dict(_EMPTY_SNAPSHOT)
    kernels = (checkpoint or {}).get("kernels") or []
    env = dict(prologue.get("env") or {})
    env.setdefault("recovered_from_stream", True)

    # External epilogues carry no seq; fall back to the checkpoint's.
    ep_seq = (epilogue or {}).get("seq")
    last_seq = ep_seq if ep_seq is not None \
        else (checkpoint or {}).get("seq", 0)
    info: Dict[str, Any] = {
        "stream_path": path,
        "stream_version": ver,
        "records": len(records),
        "spans_batches": spans_batches,
        "checkpoints": checkpoints,
        "events_recovered": len(events),
        "sealed": sealed,
        "sealed_by": (epilogue or {}).get("sealed_by", "telemetry")
        if sealed else None,
        "reason": (epilogue or {}).get("reason"),
        "truncated": truncated,
        "partial_tail": tail["partial_tail"],
        "skipped_lines": tail["skipped_lines"],
        "skipped_bytes": tail["skipped_bytes"],
        "snapshot_synthesized": checkpoint is None,
        "blackbox_folded": bb_doc is not None,
        "blackbox_path": bb_path if bb_doc is not None else None,
        "blackbox_reason": (bb_doc or {}).get("reason"),
        "blackbox_events_folded": bb_folded,
        # Per-node attribution survives reconstruction via the last
        # checkpoint's snapshot (tests pin this over a killed DAG
        # capture) — name the recovered nodes so a truncated 7-node
        # stream that lost its node blocks is visibly wrong.
        "nodes_recovered": sorted((snapshot.get("nodes") or {})),
        "collective_bytes_recovered": int(
            ((snapshot.get("collectives") or {}).get("bytes")) or 0
        ),
        "last_seq": last_seq,
        "last_checkpoint_unix": (checkpoint or {}).get("unix"),
        "loss_bound": (
            "none (sealed epilogue present)" if not truncated
            else "at most one flush interval past the last checkpoint"
        ),
    }

    doc: Dict[str, Any] = {
        "ledger_version": int(prologue.get("ledger_version",
                                           LEDGER_VERSION)),
        "created_unix": prologue.get("created_unix", 0.0),
        "env": env,
        "snapshot": snapshot,
        "kernels": kernels,
        "events": events,
        "bench": (epilogue or {}).get("bench"),
        "recovery": info,
    }
    slo = (epilogue or {}).get("slo")
    if slo is not None:
        doc["slo"] = slo
    nonfinite = (epilogue or checkpoint or {}).get("nonfinite_values")
    if nonfinite:
        doc["nonfinite_values"] = int(nonfinite)
    return doc, info
