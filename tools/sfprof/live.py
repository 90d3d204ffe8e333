"""``sfprof live`` — follow an in-flight ``SFT_LEDGER_STREAM`` capture.

The ledger stream is append-only JSONL flushed at window/phase
boundaries (``telemetry.maybe_flush_stream``), so a console can tail it
while the run is still going: per-node watermark lag and EPS from each
checkpoint's ``snapshot.dag`` / ``snapshot.nodes`` blocks, overload
shed/degrade/breaker state, and the SLO-transition /
fault-firing instant events as they land in span batches.

Reading REUSES :func:`tools.sfprof.stream.read_records` on every poll —
one copy of the truncation grammar (a half-written tail is dropped and
re-read whole on the next poll; past a genuinely undecodable line only
sealing epilogues are honored, the external-seal rule). ``live``
therefore survives mid-run truncation exactly as ``recover`` does: it
reports what the prefix says and keeps following.

Exit codes: 0 — the stream sealed (epilogue seen; any reason);
1 — ``--timeout`` expired before a seal, or ``--json`` one-shot on an
unsealed stream; 2 — unreadable / not a ledger stream.
"""

from __future__ import annotations

import json
import time
from typing import Any, Dict, List, Optional

from tools.sfprof import events as events_mod
from tools.sfprof import stream as stream_mod


def _f(v, default=0.0) -> float:
    return float(v) if isinstance(v, (int, float)) \
        and not isinstance(v, bool) else float(default)


def _i(v, default=0) -> int:
    return int(v) if isinstance(v, (int, float)) \
        and not isinstance(v, bool) else int(default)


def _e2e_straggler(e2e: Dict[str, Any]):
    """(node, p99_ms) with the worst per-node e2e "compute" stage from
    a checkpoint's ``snapshot.e2e`` block, or None — the live twin of
    ``critical.straggler_line``'s span-free fallback."""
    best = None
    for name, stages in (e2e.get("nodes") or {}).items():
        p99 = ((stages or {}).get("compute") or {}).get("p99_ms")
        if isinstance(p99, (int, float)) and not isinstance(p99, bool) \
                and (best is None or p99 > best[1]):
            best = (str(name), float(p99))
    return best


def _node_eps(rec: Dict[str, Any]) -> Optional[float]:
    """Events/s of one telemetry per-node bucket (span-time based)."""
    span_us = _f((rec or {}).get("span_us"))
    ev = _f((rec or {}).get("events"))
    if span_us > 0 and ev > 0:
        return ev / (span_us / 1e6)
    return None


def _checkpoint_lines(rec: Dict[str, Any]) -> List[str]:
    """Console lines for one checkpoint record."""
    snap = rec.get("snapshot") or {}
    out: List[str] = []
    head = (f"[ck {int(rec.get('seq') or 0)}] "
            f"events {_i(snap.get('events'))}  "
            f"lag p99 {float(_f(snap.get('watermark_lag_p99_ms'))):.1f} ms  "
            f"h2d {_i(snap.get('bytes_h2d'))} B  "
            f"d2h {_i(snap.get('bytes_d2h'))} B  "
            f"compiles {_i(snap.get('compiles'))}")
    ov = snap.get("overload") or {}
    if ov:
        br = (ov.get("breaker") or {}).get("state") or "-"
        head += (f"  shed {_i(ov.get('shed_total'))}  "
                 f"rung {_i(ov.get('rung'))}/"
                 f"{_i(ov.get('ladder_depth'))}  breaker {br}")
    coll = snap.get("collectives") or {}
    if coll:
        head += f"  collective {_i(coll.get('bytes'))} B"
    e2e = snap.get("e2e") or {}
    commit = (e2e.get("stages") or {}).get("commit") or {}
    if commit:
        head += (f"  e2e p99 "
                 f"{float(_f(commit.get('p99_ms'))):.1f} ms")
    out.append(head)
    strag = _e2e_straggler(e2e)
    if strag is not None:
        out.append(f"  straggler: {strag[0]} "
                   f"(e2e compute p99 {float(strag[1]):.1f} ms)")

    dag_nodes = (snap.get("dag") or {}).get("nodes") or {}
    acct_nodes = snap.get("nodes") or {}
    names = sorted(set(dag_nodes) | set(
        n for n in acct_nodes if n != "(unscoped)"))
    if names:
        cells = []
        for name in names:
            d = dag_nodes.get(name) or {}
            a = acct_nodes.get(name) or {}
            cell = (f"{name} lag "
                    f"{float(_f(d.get('watermark_lag_p99_ms'))):.1f}ms")
            eps = _node_eps(a)
            if eps is not None:
                cell += f" eps {float(eps):.0f}"
            if d.get("backend") and d.get("backend") != "device":
                cell += f" [{d['backend']}]"
            if _i(d.get("degraded_windows")):
                cell += f" degraded x{_i(d.get('degraded_windows'))}"
            cells.append(cell)
        out.append("  nodes: " + " | ".join(cells))
    return out


#: Instant-event groups worth a live console line (the rest are counted
#: in the final summary only — compile events alone would flood it).
_LOUD_GROUPS = frozenset({
    "slo", "faults", "overload", "circuit", "dag", "self-healing",
})


def _instant_lines(events: List[dict],
                   counts: Dict[str, int]) -> List[str]:
    """Console lines for registered instant events in one span batch
    (mutates ``counts`` — the per-group running totals)."""
    out: List[str] = []
    for ev in events or []:
        if ev.get("ph") != "i":
            continue
        name = str(ev.get("name", ""))
        group = events_mod.classify(name)
        if group is None:
            continue
        counts[group] = counts.get(group, 0) + 1
        if group in _LOUD_GROUPS:
            node = (ev.get("args") or {}).get("node")
            where = f" [node {node}]" if node else ""
            out.append(f"  ! {group}: {name}{where}")
    return out


def _summary(records: List[dict],
             counts: Dict[str, int]) -> Dict[str, Any]:
    """One JSON document describing the stream's current state."""
    prologue = records[0] if records else {}
    checkpoint = None
    epilogue = None
    for rec in records:
        if rec.get("t") == "checkpoint":
            checkpoint = rec
        elif rec.get("t") == "epilogue":
            epilogue = rec
    snap = (checkpoint or {}).get("snapshot") or {}
    strag = _e2e_straggler(snap.get("e2e") or {})
    nodes = {}
    for name, a in (snap.get("nodes") or {}).items():
        d = ((snap.get("dag") or {}).get("nodes") or {}).get(name) or {}
        nodes[name] = {
            "eps": _node_eps(a),
            "watermark_lag_p99_ms": d.get("watermark_lag_p99_ms"),
            "backend": d.get("backend"),
            "shed_events": _i((a or {}).get("shed_events")),
            "degraded_windows": _i(d.get("degraded_windows")),
        }
    return {
        "stream_version": prologue.get("stream_version"),
        "sealed": epilogue is not None,
        "reason": (epilogue or {}).get("reason"),
        "sealed_by": (epilogue or {}).get("sealed_by",
                                          "telemetry")
        if epilogue is not None else None,
        "checkpoints": sum(1 for r in records
                           if r.get("t") == "checkpoint"),
        "last_seq": _i((checkpoint or {}).get("seq")),
        "events": _i(snap.get("events")),
        "watermark_lag_p99_ms": snap.get("watermark_lag_p99_ms"),
        "nodes": nodes,
        "collectives": snap.get("collectives") or {},
        "overload": {
            "shed_total": _i((snap.get("overload") or {})
                             .get("shed_total")),
            "rung": _i((snap.get("overload") or {}).get("rung")),
            "breaker": ((snap.get("overload") or {})
                        .get("breaker") or {}).get("state"),
        },
        "e2e": snap.get("e2e"),
        "straggler": (
            {"node": strag[0], "e2e_compute_p99_ms": float(strag[1])}
            if strag is not None else None
        ),
        "instant_counts": dict(sorted(counts.items())),
    }


def _read_once(path: str) -> Optional[List[dict]]:
    """All currently decodable records (None while the file is missing
    or still empty — the writer may not have opened it yet)."""
    try:
        records, _tail = stream_mod.read_records(path)
    except OSError:
        return None
    return records or None


def follow(path: str, poll_s: float, timeout_s: Optional[float],
           json_mode: bool) -> int:
    """The live loop. See module docstring for the exit-code contract."""
    counts: Dict[str, int] = {}
    seen = 0           # records already rendered
    deadline = (time.monotonic() + timeout_s) \
        if timeout_s is not None else None

    while True:
        records = _read_once(path) or []
        if records and records[0].get("t") != "prologue":
            print(f"sfprof: {path}: no ledger-stream prologue")
            return 2

        if json_mode:
            # One-shot: summarize the current prefix and leave.
            for rec in records:
                if rec.get("t") == "spans":
                    _instant_lines(rec.get("events") or [], counts)
            doc = _summary(records, counts)
            print(json.dumps(doc, allow_nan=False))
            return 0 if doc["sealed"] else 1

        sealed = False
        for rec in records[seen:]:
            kind = rec.get("t")
            if kind == "prologue":
                env = rec.get("env") or {}
                print(f"== sfprof live: {path}")
                print(f"stream v{_i(rec.get('stream_version'))}  "
                      f"backend={env.get('backend')}  "
                      f"devices={_i(env.get('device_count'))}")
            elif kind == "spans":
                for line in _instant_lines(rec.get("events") or [],
                                           counts):
                    print(line)
            elif kind == "checkpoint":
                for line in _checkpoint_lines(rec):
                    print(line)
            elif kind == "epilogue":
                by = rec.get("sealed_by", "telemetry")
                print(f"sealed: reason={rec.get('reason')} (by {by})")
                if counts:
                    print("instant events: " + ", ".join(
                        f"{g}={int(n)}"
                        for g, n in sorted(counts.items())))
                sealed = True
        seen = len(records)
        if sealed:
            return 0
        if deadline is not None and time.monotonic() >= deadline:
            print(f"sfprof live: no seal after "
                  f"{float(timeout_s):.1f} s — giving up "
                  "(stream still unsealed)")
            return 1
        time.sleep(poll_s)


def cmd_live(args) -> int:
    return follow(args.stream, args.poll, args.timeout, args.json)


def add_parser(sub) -> None:
    """Register the ``live`` subcommand on the sfprof CLI."""
    liv = sub.add_parser(
        "live", help="follow an in-flight SFT_LEDGER_STREAM capture: "
                     "per-node lag/EPS, shed/degrade/breaker "
                     "state, SLO + fault transitions; exits 0 when the "
                     "stream seals")
    liv.add_argument("stream")
    liv.add_argument("--poll", type=float, default=0.5,
                     help="poll interval in seconds (default 0.5)")
    liv.add_argument("--timeout", type=float, default=None,
                     help="give up (exit 1) when the stream has not "
                          "sealed after this many seconds "
                          "(default: follow forever)")
    liv.add_argument("--json", action="store_true",
                     help="one-shot mode: print one JSON summary of "
                          "the stream's CURRENT state and exit "
                          "(0 sealed, 1 not)")
    liv.set_defaults(fn=cmd_live)
