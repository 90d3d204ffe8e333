"""sfprof CLI — ``report`` / ``diff [--gate]`` / ``health [--slo]`` /
``recover`` / ``live`` / ``trend [--gate]``.

Run from the repo root: ``python -m tools.sfprof <cmd> ...``. The first
three subcommands consume run ledgers (``telemetry.write_ledger``);
``report`` also accepts a raw Chrome trace (``SFT_TRACE_PATH``
JSON-lines or a ``{"traceEvents"}`` document); ``recover`` consumes a
ledger STREAM (``SFT_LEDGER_STREAM`` JSONL) and reconstructs a
gateable ledger from any truncation of it; ``health --slo <spec>``
additionally applies a declarative SLO spec (the same JSON the live
engine evaluates) to the ledger; ``trend`` ingests a whole history
(ledgers, streams, legacy ``BENCH_r*.json`` round records) into
per-config series and — with ``--gate`` — checks a new capture against
the trajectory's robust median + MAD band instead of one noisy
predecessor.

``report`` and ``health`` take ``--json`` for machine-readable verdicts
(``diff`` stays row-structured already); exit-code contracts are
identical either way. Both surface the roofline bound classification
(``tools/sfprof/roofline.py``): link/host/dispatch/compute/memory-bound
with an ``↳`` evidence chain — a diagnosis, never a gate.

Exit codes: 0 ok; 1 gated regression (``diff --gate``,
``trend --gate``), failed health/SLO verdict, or a recovered document
that fails schema validation; 2 unreadable/invalid input.
"""

from __future__ import annotations

import argparse
import json
import os
from typing import Any, Dict, List, Optional, Tuple

from tools.sfprof import attribution
from tools.sfprof import critical as critical_mod
from tools.sfprof import events as events_mod
from tools.sfprof import ledger as ledger_mod
from tools.sfprof import live as live_mod
from tools.sfprof import roofline as roofline_mod
from tools.sfprof import slo as slo_mod
from tools.sfprof import stream as stream_mod
from tools.sfprof import trend as trend_mod

# -- shared helpers -----------------------------------------------------------


def _flatten_numeric(value: Any, prefix: str, out: Dict[str, float]):
    if isinstance(value, bool):
        return
    if isinstance(value, (int, float)):
        out[prefix] = value
    elif isinstance(value, dict):
        for k, v in value.items():
            _flatten_numeric(v, f"{prefix}.{k}" if prefix else str(k), out)


def _metrics(doc: Dict[str, Any]) -> Dict[str, float]:
    """Comparable numeric metrics of one ledger, dotted-key flattened."""
    out: Dict[str, float] = {}
    snap = doc.get("snapshot") or {}
    for key in ("compiles", "bytes_h2d", "bytes_d2h",
                "window_latency_p50_ms", "window_latency_p95_ms",
                "max_watermark_lag_ms", "watermark_lag_p99_ms",
                "late_dropped", "dropped_events"):
        v = snap.get(key)
        if isinstance(v, (int, float)) and not isinstance(v, bool):
            out[f"snapshot.{key}"] = v
    _flatten_numeric(doc.get("bench") or {}, "bench", out)
    return out


def _ms(us) -> float:
    return float(us) / 1000.0


#: Collective kind → transfer class. ``halo`` kinds move only
#: boundary-cell panes (the grid-partitioned ppermute exchange);
#: ``gather`` kinds replicate whole operands across the mesh;
#: ``reduce`` kinds move reduction trees.
_COLLECTIVE_CLASSES = (
    ("halo", ("ppermute", "pshuffle")),
    ("gather", ("all_gather", "broadcast", "all_to_all")),
    ("reduce", ("psum", "pmin", "pmax", "pmean", "psum_scatter")),
)


def collective_split(coll: Dict[str, Any]) -> Optional[Dict[str, Any]]:
    """Bucket the snapshot ``collectives`` gauges by transfer class
    (halo vs gather vs reduce — see ``_COLLECTIVE_CLASSES``), plus the
    replication ratio: total collective bytes over the boundary-state
    bytes the halo wrappers declared via
    ``telemetry.account_halo_state``. A ratio near the halo pad factor
    means the mesh moved essentially only boundary state; an
    all-gather path pushes it orders of magnitude above that."""
    if not coll:
        return None
    by_kind = coll.get("by_kind") or {}
    by_class: Dict[str, Dict[str, Any]] = {}
    assigned = set()
    for cls, kinds in _COLLECTIVE_CLASSES:
        b = c = 0
        members = []
        for k in kinds:
            row = by_kind.get(k) or {}
            if row.get("calls"):
                assigned.add(k)
                b += int(row.get("bytes") or 0)
                c += int(row.get("calls") or 0)
                members.append(k)
        if c:
            by_class[cls] = {"bytes": b, "calls": c, "kinds": members}
    other_b = other_c = 0
    other_members = []
    for k, row in by_kind.items():
        if k in assigned:
            continue
        row = row or {}
        if row.get("calls"):
            other_b += int(row.get("bytes") or 0)
            other_c += int(row.get("calls") or 0)
            other_members.append(k)
    if other_c:
        by_class["other"] = {"bytes": other_b, "calls": other_c,
                             "kinds": sorted(other_members)}
    if not by_class:
        return None
    out: Dict[str, Any] = {"by_class": by_class}
    halo_state = coll.get("halo_state_bytes")
    total = int(coll.get("bytes") or 0)
    if isinstance(halo_state, (int, float)) and not isinstance(
            halo_state, bool) and halo_state > 0:
        out["halo_state_bytes"] = int(halo_state)
        out["replication_ratio"] = total / float(halo_state)
    return out


# -- report -------------------------------------------------------------------


def cmd_report(args) -> int:
    try:
        doc, events = ledger_mod.load_any(args.path)
    except (OSError, ValueError) as e:
        print(f"sfprof: cannot read {args.path}: {e}")
        return 2
    bound = roofline_mod.classify(
        doc, events, peak_flops=args.peak_flops, peak_bw=args.peak_bw)
    if args.json:
        return _report_json(args, doc, events, bound)
    print(f"== sfprof report: {args.path}")
    if doc is not None:
        env = doc.get("env") or {}
        print(
            "ledger v{v}  backend={b}  jax={j}  devices={d}".format(
                v=int(doc.get("ledger_version", 0)),
                b=env.get("backend"), j=env.get("jax"),
                d=int(env.get("device_count", 0)),
            )
        )

    windows, ops = attribution.attribute_windows(events)
    print("\n-- phase attribution per operator "
          "(unattributed residue always reported) --")
    if not ops:
        print("no window.* spans in the event stream")
    for name, agg in sorted(ops.items()):
        total_us = agg["dur_us"]
        frac = ((total_us - agg["unattributed_us"]) / total_us
                if total_us else 1.0)
        print(f"{name}: {int(agg['windows'])} windows, "
              f"total {float(_ms(total_us)):.3f} ms, "
              f"attributed {float(100.0 * frac):.1f}%")
        rows = sorted(agg["phases"].items(), key=lambda kv: -kv[1])
        rows.append(("unattributed", agg["unattributed_us"]))
        for phase, us in rows:
            pct = 100.0 * us / total_us if total_us else 0.0
            print(f"    {phase:<18} {float(pct):6.1f}%  "
                  f"{float(_ms(us)):10.3f} ms")

    node_spans = attribution.attribute_nodes(events)
    snap_nodes: Dict[str, Any] = {}
    if doc is not None:
        snap_nodes = (doc.get("snapshot") or {}).get("nodes") or {}
    if node_spans or snap_nodes:
        _print_node_table(node_spans, snap_nodes,
                          (doc or {}).get("snapshot") or {})

    if doc is not None:
        kernels = doc.get("kernels") or []
        print(f"\n-- top {int(args.top)} kernels by steady dispatch time "
              "(first call = compile, shown separately) --")
        for row in kernels[:args.top]:
            cost = row.get("cost") or {}
            flops = cost.get("flops") or 0.0
            bytes_acc = cost.get("bytes_accessed") or 0.0
            steady = row.get(
                "steady_ns",
                max(row["dispatch_ns"] - row["first_call_ns"], 0),
            )
            print(f"{row['kernel']:<28} calls={int(row['calls']):<6} "
                  f"steady={float(steady / 1e6):10.3f} ms  "
                  f"first={float(row['first_call_ns'] / 1e6):10.3f} ms  "
                  f"flops={float(flops):.3g} "
                  f"bytes={float(bytes_acc):.3g}")
            if cost.get("error"):
                print(f"    cost unavailable: {cost['error']}")

        snap = doc.get("snapshot") or {}
        churn = sorted(((snap.get("kernels") or {}).items()),
                       key=lambda kv: -kv[1])
        print(f"\n-- top {int(args.top)} kernels by distinct compiled "
              "signatures --")
        for kernel, n in churn[:args.top]:
            print(f"{kernel:<28} {int(n)} signatures")

        by_flops = sorted(
            (r for r in kernels
             if (r.get("cost") or {}).get("flops") is not None),
            key=lambda r: -r["cost"]["flops"],
        )
        print(f"\n-- top {int(args.top)} kernels by flops per dispatch --")
        for row in by_flops[:args.top]:
            print(f"{row['kernel']:<28} "
                  f"flops={float(row['cost']['flops']):.3g}  "
                  f"bytes="
                  f"{float(row['cost'].get('bytes_accessed', 0.0)):.3g}  "
                  f"peak_mem={int(row['cost'].get('peak_memory_bytes', 0))}")

        n_win = len(windows)
        if n_win:
            # Honest label: byte totals cover the WHOLE run (warm-up,
            # throughput loops, staging), while only the latency-probe
            # windows carry spans — so this is run-total ÷ traced
            # windows, an upper bound on true per-window traffic. These
            # are WIRE bytes — what actually crossed the link.
            print("\n-- device-boundary wire bytes "
                  "(run totals ÷ traced windows) --")
            print(f"h2d {float(snap.get('bytes_h2d', 0) / n_win):.1f} "
                  f"B/traced-win  "
                  f"d2h {float(snap.get('bytes_d2h', 0) / n_win):.1f} "
                  f"B/traced-win  over {int(n_win)} traced windows "
                  f"(run totals: h2d {int(snap.get('bytes_h2d', 0))} B, "
                  f"d2h {int(snap.get('bytes_d2h', 0))} B)")
            _print_link_utilization(snap, events)
        # Per-tenant-class QoS, next to the device-boundary numbers
        # (the health CLI prints the same rows as notes).
        tenants = (snap.get("overload") or {}).get("tenants") or {}
        if tenants:
            print("\n-- per-tenant-class QoS (overload tenant budgets) --")
            for cls, rec in sorted(tenants.items()):
                rec = rec or {}
                print(f"{cls:<16} queries_live="
                      f"{int(rec.get('queries_live') or 0):<6} "
                      f"queries_shed="
                      f"{int(rec.get('queries_shed') or 0):<6} "
                      f"results_shed="
                      f"{int(rec.get('results_shed') or 0):<8} "
                      f"degraded_windows="
                      f"{int(rec.get('degraded_windows') or 0)}")
        qs = snap.get("qserve") or {}
        if qs:
            print(f"qserve registry: {int(qs.get('registered') or 0)} "
                  f"standing queries in {len(qs.get('buckets') or {})} "
                  f"bucket(s), "
                  f"{int(qs.get('recompiles') or 0)} compiled bucket "
                  f"signatures (ladder-bounded), "
                  f"{int(qs.get('evicted_total') or 0)} evicted")
        coll = snap.get("collectives") or {}
        if coll:
            kinds = ", ".join(
                f"{k}={int((v or {}).get('bytes') or 0)}B"
                f"/{int((v or {}).get('calls') or 0)} call(s)"
                for k, v in sorted((coll.get("by_kind") or {}).items())
            ) or "-"
            print("\n-- mesh collectives "
                  "(trace-time logical bytes, host-side estimate) --")
            print(f"{int(coll.get('calls') or 0)} collective call(s), "
                  f"{int(coll.get('bytes') or 0)} B moved  [{kinds}]")
            axes = coll.get("by_axis") or {}
            if axes:
                print("    by axis: " + ", ".join(
                    f"{ax}={int(b or 0)}B" for ax, b in sorted(axes.items())
                ))
            split = collective_split(coll)
            if split:
                print("    by class: " + ", ".join(
                    f"{cls}={int(row['bytes'])}B/{int(row['calls'])} "
                    f"call(s) [{'+'.join(row['kinds'])}]"
                    for cls, row in sorted(split["by_class"].items())
                ))
                rr = split.get("replication_ratio")
                if rr is not None:
                    print(f"    replication ratio "
                          f"{float(rr):.2f}x (collective bytes / "
                          "boundary-state bytes)")
                    print(f"      ↳ {int(coll.get('bytes') or 0)} B "
                          "moved by collectives over "
                          f"{int(split['halo_state_bytes'])} B of live "
                          "boundary-pane state the halo wrappers "
                          "declared (telemetry.account_halo_state)")
        if snap.get("dropped_events"):
            print(f"\nWARNING: {int(snap['dropped_events'])} trace events "
                  "dropped (buffer cap) — attribution above is partial")

    gaps = attribution.host_gaps(events)
    print(f"\n-- host gaps between window spans (top {int(args.top)}) --")
    if not gaps:
        print("none detected")
    for g in gaps[:args.top]:
        print(f"{float(_ms(g['gap_us'])):10.3f} ms  after {g['after']} "
              f"→ before {g['before']}")

    # One-line straggler verdict (critical.py has the full path walk).
    sline = critical_mod.straggler_line(doc, events)
    if sline is not None:
        print(f"\n{sline}")

    _print_roofline(bound)
    return 0


def _print_node_table(node_spans: Dict[str, dict],
                      snap_nodes: Dict[str, Any],
                      snap: Dict[str, Any]):
    """Per-node attribution table (the PR 16 ``node.*`` convention):
    span-derived windows/EPS/phase split merged with the snapshot
    ``nodes`` conservation counters. Node totals sum EXACTLY to the
    untagged globals — the ``(unscoped)`` bucket is the remainder, so
    the sum line next to the global makes drift visible at a glance."""
    print("\n-- per-node attribution "
          "(node totals sum to the untagged globals) --")
    names = sorted(set(node_spans) | set(snap_nodes))
    for name in names:
        sp = node_spans.get(name) or {}
        sn = snap_nodes.get(name) or {}
        windows = int(sp.get("windows") or sn.get("windows") or 0)
        eps = sp.get("eps")
        eps_s = f"{float(eps):.0f} ev/s" if eps else "-"
        print(f"{name}: {windows} windows, "
              f"total {float(_ms(sp.get('dur_us') or 0)):.3f} ms, "
              f"eps {eps_s}")
        rows = sorted((sp.get("phases") or {}).items(),
                      key=lambda kv: -kv[1])
        if sp.get("unattributed_us"):
            rows.append(("unattributed", sp["unattributed_us"]))
        total_us = sp.get("dur_us") or 0
        for phase, us in rows:
            pct = 100.0 * us / total_us if total_us else 0.0
            print(f"    {phase:<18} {float(pct):6.1f}%  "
                  f"{float(_ms(us)):10.3f} ms")
        if sn:
            print(f"    h2d {int(sn.get('h2d_bytes') or 0)} B  "
                  f"d2h {int(sn.get('d2h_bytes') or 0)} B  "
                  f"dispatch "
                  f"{float((sn.get('dispatch_ns') or 0) / 1e6):.3f} ms  "
                  f"compiles {int(sn.get('compiles') or 0)}  "
                  f"sheds {int(sn.get('shed_events') or 0)}  "
                  f"collective {int(sn.get('collective_bytes') or 0)} B")
    if snap_nodes and snap:
        # Conservation receipt: bucket sums vs the global counters.
        for label, bucket_key, snap_key in (
            ("h2d", "h2d_bytes", "bytes_h2d"),
            ("d2h", "d2h_bytes", "bytes_d2h"),
            ("compiles", "compiles", "compiles"),
        ):
            total = sum(int((r or {}).get(bucket_key) or 0)
                        for r in snap_nodes.values())
            want = int(snap.get(snap_key) or 0)
            mark = "ok" if total == want else "MISMATCH"
            print(f"conservation {label}: node-sum {int(total)} "
                  f"vs global {int(want)} [{mark}]")


def _print_roofline(bound: Dict[str, Any]):
    """The bound verdict with its sfcheck-style ``↳`` evidence chain."""
    dom = "" if bound.get("dominant") else " (weak dominance)"
    print(f"\n-- roofline bound classification --")
    print(f"verdict: {bound['verdict']}{dom}")
    for line in bound.get("evidence") or []:
        print(f"  ↳ {line}")
    per_op = bound.get("per_operator") or {}
    for name, row in sorted(per_op.items()):
        ph = row["phases_us"]
        print(f"  {name}: {row['verdict']}  "
              f"(transfer {float(_ms(ph['transfer'])):.3f} ms, "
              f"compute {float(_ms(ph['compute'])):.3f} ms, "
              f"host {float(_ms(ph['host'])):.3f} ms)")
    per_node = bound.get("per_node") or {}
    if per_node:
        print("  per node:")
        for name, row in sorted(per_node.items()):
            ph = row["phases_us"]
            print(f"    {name}: {row['verdict']}  "
                  f"(transfer {float(_ms(ph['transfer'])):.3f} ms, "
                  f"compute {float(_ms(ph['compute'])):.3f} ms, "
                  f"host {float(_ms(ph['host'])):.3f} ms)")


def _report_json(args, doc, events, bound) -> int:
    """Machine-readable report: same signals the human text renders,
    as one JSON document on stdout (exit code unchanged)."""
    windows, ops = attribution.attribute_windows(events)
    gaps = attribution.host_gaps(events)
    node_spans = attribution.attribute_nodes(events)
    out: Dict[str, Any] = {
        "path": args.path,
        "ledger": None,
        "attribution": {
            "windows": len(windows),
            "operators": {
                name: {
                    "windows": int(agg["windows"]),
                    "dur_us": int(agg["dur_us"]),
                    "unattributed_us": int(agg["unattributed_us"]),
                    "phases_us": dict(agg["phases"]),
                }
                for name, agg in sorted(ops.items())
            },
            "nodes": node_spans,
        },
        "host_gaps": gaps[:args.top],
        "roofline": bound,
    }
    if doc is not None:
        snap = doc.get("snapshot") or {}
        # Per-node conservation counters + collective gauges, lifted to
        # the top level (they also ride ledger.snapshot) so machine
        # consumers need not know the snapshot layout.
        if snap.get("nodes"):
            out["nodes"] = snap["nodes"]
        if snap.get("collectives"):
            out["collectives"] = snap["collectives"]
            split = collective_split(snap["collectives"])
            if split:
                out["collective_split"] = split
        out["ledger"] = {
            "ledger_version": int(doc.get("ledger_version", 0)),
            "env": doc.get("env") or {},
            "snapshot": snap,
            "bench": doc.get("bench"),
        }
        out["kernels"] = (doc.get("kernels") or [])[:args.top]
        if snap.get("e2e"):
            out["e2e"] = snap["e2e"]
    out["straggler"] = critical_mod.straggler_line(doc, events)
    print(json.dumps(out, allow_nan=False))
    return 0


def _print_link_utilization(snap: Dict[str, Any], events: List[dict]):
    """Effective link utilization against the MEASURED LinkProbe
    bandwidth gauge — never a folklore constant:
    transferred bytes over the traced span vs what the probe says this
    run's link could actually move. Both sides are honest run-wide
    aggregates (the span includes compute time), so this is a floor on
    utilization."""
    lp = snap.get("link_probe") or {}
    bw = lp.get("roundtrip_mbps_p50")
    spans = complete_spans_ts_range(events)
    if not isinstance(bw, (int, float)) or not bw or spans is None:
        return
    span_s = spans / 1e6
    if span_s <= 0:
        return
    total = float(snap.get("bytes_h2d", 0)) + float(snap.get("bytes_d2h", 0))
    mbps = total / 1e6 / span_s
    print(f"link utilization: {float(mbps):.2f} MB/s transferred over "
          f"the {float(span_s):.2f} s traced span = "
          f"{float(100.0 * mbps / bw):.1f}% of the probed "
          f"{float(bw):.1f} MB/s round-trip bandwidth (p50 gauge)")


def complete_spans_ts_range(events: List[dict]) -> Optional[float]:
    """µs between the first event start and the last event end (None
    when nothing is timestamped). Shared with the roofline classifier
    via ``attribution.span_range_us`` — ONE traced-wall definition."""
    return attribution.span_range_us(events)


# -- diff / gate --------------------------------------------------------------

#: higher-is-better throughput metrics (substring match on the leaf key).
_EPS_LEAVES = ("per_sec",)
#: lower-is-better duration metrics.
_LAT_LEAVES = ("latency", "lag_ms")
#: counters where ANY increase over the baseline ledger is a regression.
_ZERO_TOL_LEAVES = ("dropped", "overflow")


def _kind(name: str) -> str:
    parts = name.split(".")
    if "link_probe" in parts or "slo" in parts:
        # Link-health gauges measure the LINK, not the code under
        # test: they annotate verdicts (see cmd_diff) and must never
        # gate — a degraded link is context, not a regression. SLO
        # blocks are verdict metadata (spec thresholds, counts), gated
        # by `health --slo`, not by metric bands.
        return "info"
    leaf = name.rsplit(".", 1)[-1]
    if leaf == "value" or any(s in leaf for s in _EPS_LEAVES):
        return "eps"
    if any(s in leaf for s in _LAT_LEAVES):
        return "latency"
    if leaf == "compiles":
        return "compiles"
    if any(s in leaf for s in _ZERO_TOL_LEAVES):
        return "zero_tol"
    return "info"


def compare(a_doc: Dict, b_doc: Dict, eps_tol: float,
            lat_tol: float) -> List[dict]:
    """Per-metric rows {name, a, b, band, verdict} comparing ledger B
    (candidate) against ledger A (reference).

    Tolerance bands per metric class: EPS throughput regresses when B
    falls more than ``eps_tol`` (fraction) below A — wide until per-cell
    spreads are measured on the chip; latency when B exceeds A by more
    than ``lat_tol`` (fraction) plus a 1 ms absolute floor; ``compiles``
    when B > 2·A + 8 (ladder growth is legitimate, churn is not);
    dropped/overflow counters on ANY increase."""
    rows: List[dict] = []
    a_m, b_m = _metrics(a_doc), _metrics(b_doc)
    for name in sorted(set(a_m) | set(b_m)):
        a, b = a_m.get(name), b_m.get(name)
        kind = _kind(name)
        if b is None:
            # A gateable metric the candidate LOST is a stronger failure
            # than a bad value (broken telemetry / truncated bench block)
            # — the gate must not pass on silence.
            rows.append({"name": name, "a": a, "b": b,
                         "band": "must exist in B",
                         "verdict": ("regression" if kind != "info"
                                     else "info")})
            continue
        if a is None:
            rows.append({"name": name, "a": a, "b": b,
                         "band": "new in B", "verdict": "info"})
            continue
        verdict, band = "info", ""
        if kind == "eps":
            band = f"B >= A*(1-{float(eps_tol):g})"
            if a > 0:
                verdict = "regression" if b < a * (1 - eps_tol) else "ok"
        elif kind == "latency":
            band = f"B <= A*(1+{float(lat_tol):g}) + 1ms"
            verdict = ("regression"
                       if b > a * (1 + lat_tol) + 1.0 else "ok")
        elif kind == "compiles":
            band = "B <= 2*A + 8"
            verdict = "regression" if b > 2 * a + 8 else "ok"
        elif kind == "zero_tol":
            band = "B <= A"
            verdict = "regression" if b > a else "ok"
        rows.append({"name": name, "a": a, "b": b, "band": band,
                     "verdict": verdict})
    return rows


def _fmt_num(v) -> str:
    if v is None:
        return "-"
    return f"{float(v):.6g}"


def _link_annotation(a_doc: Dict, b_doc: Dict) -> Optional[str]:
    """Link-health context line for a diff: when BOTH ledgers carry
    link-probe gauges and the round-trip bandwidth moved by >30%, say so
    — the bands themselves stay exactly as configured (annotate, never
    widen), but the reader learns whether an e2e EPS delta is the code
    or the link."""
    a_lp = (a_doc.get("snapshot") or {}).get("link_probe") or {}
    b_lp = (b_doc.get("snapshot") or {}).get("link_probe") or {}
    a_bw = a_lp.get("roundtrip_mbps_p50")
    b_bw = b_lp.get("roundtrip_mbps_p50")
    if not isinstance(a_bw, (int, float)) \
            or not isinstance(b_bw, (int, float)) or not a_bw:
        return None
    ratio = b_bw / a_bw
    if 0.7 <= ratio <= 1.3:
        return (f"link: comparable links "
                f"(A {float(a_bw):.1f} MB/s rt, B {float(b_bw):.1f} "
                f"MB/s rt) — deltas above reflect the code")
    direction = "DEGRADED" if ratio < 1 else "improved"
    return (f"link: B's link {direction} {float(ratio):.2f}x vs A "
            f"(A {float(a_bw):.1f} MB/s rt, B {float(b_bw):.1f} MB/s rt)"
            " — e2e EPS/latency deltas may reflect link health, not"
            " code; device-resident metrics are unaffected")


def cmd_diff(args) -> int:
    try:
        a_doc = ledger_mod.load(args.a)
        b_doc = ledger_mod.load(args.b)
    except (OSError, ValueError) as e:
        print(f"sfprof: cannot read ledger: {e}")
        return 2
    rows = compare(a_doc, b_doc, args.eps_tol, args.lat_tol)
    regressions = [r for r in rows if r["verdict"] == "regression"]
    print(f"== sfprof diff: A={args.a}  B={args.b}")
    note = _link_annotation(a_doc, b_doc)
    if note:
        print(note)
    for r in rows:
        if r["verdict"] == "info" and not args.verbose:
            continue
        a, b = r["a"], r["b"]
        delta = ""
        if isinstance(a, (int, float)) and isinstance(b, (int, float)) \
                and a:
            delta = f"{float(100.0 * (b - a) / a):+8.1f}%"
        print(f"{r['verdict']:<11} {r['name']:<46} "
              f"A={_fmt_num(a):<12} B={_fmt_num(b):<12} {delta:<9} "
              f"[{r['band']}]")
    print(f"{len(rows)} metrics compared, "
          f"{len(regressions)} regression(s)")
    if regressions and args.gate:
        return 1
    return 0


# -- health -------------------------------------------------------------------

# ONE overflow-scanner for both the unconditional health scan and the
# --slo budget check — two copies of the "every *overflow* counter"
# substring contract would drift.
_find_overflows = slo_mod.find_overflows


def cmd_health(args) -> int:
    try:
        doc = ledger_mod.load(args.ledger)
    except (OSError, ValueError) as e:
        print(f"sfprof: cannot read {args.ledger}: {e}")
        return 2
    problems = ledger_mod.validate(doc)
    if problems:
        if args.json:
            print(json.dumps({
                "ledger": args.ledger, "schema_problems": problems,
                "checks": [], "failed": len(problems),
            }, allow_nan=False))
            return 1
        print(f"== sfprof health: {args.ledger}")
        for p in problems:
            print(f"FAIL schema: {p}")
        return 1
    snap = doc.get("snapshot") or {}
    churn = max((snap.get("kernels") or {}).values(), default=0)
    checks = [
        ("recompile_churn_max_signatures", churn,
         f"<= {int(args.recompile_threshold)}",
         churn <= args.recompile_threshold),
        ("dropped_trace_events", snap.get("dropped_events", 0), "== 0",
         not snap.get("dropped_events")),
        ("late_dropped", snap.get("late_dropped", 0), "== 0",
         not snap.get("late_dropped")),
        ("max_watermark_lag_ms", snap.get("max_watermark_lag_ms", 0),
         f"<= {int(args.max_lag_ms)}",
         (snap.get("max_watermark_lag_ms") or 0) <= args.max_lag_ms),
    ]
    overflows: List[Tuple[str, float]] = []
    _find_overflows(doc.get("bench") or {}, "bench", overflows)
    _find_overflows(snap.get("compaction") or {}, "snapshot.compaction",
                    overflows)
    for path, v in overflows:
        checks.append((path, v, "== 0", not v))
    if args.slo:
        try:
            spec = slo_mod.load_spec(args.slo)
        except (OSError, ValueError) as e:
            print(f"sfprof: cannot read SLO spec {args.slo}: {e}")
            return 2
        checks.extend(slo_mod.evaluate(spec, doc))
    failed = sum(0 if ok else 1 for _n, _v, _b, ok in checks)
    bound = roofline_mod.classify(doc, doc.get("events") or [])
    sline = critical_mod.straggler_line(doc, doc.get("events") or [])
    if args.json:
        print(json.dumps({
            "ledger": args.ledger,
            "schema_problems": [],
            "checks": [
                {"name": name, "value": value, "band": band,
                 "ok": bool(ok)}
                for name, value, band, ok in checks
            ],
            "failed": failed,
            "roofline": bound,
            "notes": {
                "driver": snap.get("driver") or {},
                "overload": snap.get("overload") or {},
                # per-tenant-class QoS counters, surfaced at top level
                # too (they also ride notes.overload.tenants)
                "tenants": (snap.get("overload") or {}).get("tenants")
                or {},
                "qserve": snap.get("qserve") or {},
                "faults": snap.get("faults") or {},
                "dag": snap.get("dag") or {},
                "nodes": snap.get("nodes") or {},
                "collectives": snap.get("collectives") or {},
                "collective_split": collective_split(
                    snap.get("collectives") or {}),
                "instant_events": events_mod.notable_event_counts(
                    doc.get("events") or []),
                "e2e": snap.get("e2e") or {},
                "straggler": sline,
            },
        }, allow_nan=False))
        return 1 if failed else 0
    print(f"== sfprof health: {args.ledger}")
    for name, value, band, ok in checks:
        print(f"{'ok  ' if ok else 'FAIL'} {name:<34} "
              f"{_fmt_num(value):<12} [{band}]")
    # Bound verdict (roofline.py): a diagnosis line, never a check —
    # health's exit code stays a pure threshold contract.
    dom = "" if bound.get("dominant") else " (weak dominance)"
    print(f"bound: {bound['verdict']}{dom}")
    for line in bound.get("evidence") or []:
        print(f"  ↳ {line}")
    if sline is not None:
        print(f"note {sline}")
    commit = ((snap.get("e2e") or {}).get("stages") or {}).get("commit")
    if commit:
        print(f"note e2e commit latency: "
              f"p50 {float(commit.get('p50_ms') or 0):.1f} ms  "
              f"p99 {float(commit.get('p99_ms') or 0):.1f} ms over "
              f"{int(commit.get('count') or 0)} committed window(s)")
    # Self-healing visibility (informational — a run that SURVIVED on
    # retries/fallback is degraded, not failed; budget it via an --slo
    # spec's retry_budget/failover_budget to make it gate):
    drv = snap.get("driver") or {}
    if drv.get("retries") or drv.get("failovers"):
        print(f"note driver self-healing: "
              f"retries={int(drv.get('retries') or 0)} "
              f"failovers={int(drv.get('failovers') or 0)}")
    # Overload visibility (informational, like self-healing — budget it
    # via an --slo spec's shed_budget/degraded_window_budget to gate):
    ov = snap.get("overload") or {}
    if ov.get("shed_total") or ov.get("degraded_windows") \
            or ov.get("rung_transitions") or ov.get("backpressure_engaged"):
        shed = ", ".join(
            f"{k}={int((v or {}).get('events', 0))}"
            for k, v in sorted((ov.get("shed") or {}).items())
        ) or "none"
        print(f"note overload sheds: total={int(ov.get('shed_total') or 0)}"
              f" ({shed}); backpressure engaged "
              f"{int(ov.get('backpressure_engaged') or 0)}x")
        print(f"note overload degradation: rung={int(ov.get('rung') or 0)}"
              f"/{int(ov.get('ladder_depth') or 0)} after "
              f"{int(ov.get('rung_transitions') or 0)} transitions; "
              f"degraded_windows={int(ov.get('degraded_windows') or 0)}")
        br = ov.get("breaker") or {}
        if br:
            print(f"note overload circuit: state={br.get('state')} "
                  f"opens={int(br.get('opens') or 0)} "
                  f"probes={int(br.get('probes') or 0)}")
    # Per-tenant-class QoS (qserve's scoping of the overload budgets;
    # informational like the overload notes — budget it via an --slo
    # spec's tenant_budgets to gate). SLO verdicts for a class surface
    # in the check rows above as slo:tenant_*_budget:<class>.
    for cls, rec in sorted((ov.get("tenants") or {}).items()):
        rec = rec or {}
        print(f"note tenant QoS [{cls}]: "
              f"queries_live={int(rec.get('queries_live') or 0)} "
              f"queries_shed={int(rec.get('queries_shed') or 0)} "
              f"results_shed={int(rec.get('results_shed') or 0)} "
              f"degraded_windows="
              f"{int(rec.get('degraded_windows') or 0)}")
    # qserve registry visibility (the snapshot()["qserve"] block).
    qs = snap.get("qserve") or {}
    if qs:
        print(f"note qserve: registered={int(qs.get('registered') or 0)} "
              f"(+{int(qs.get('registered_total') or 0)} total, "
              f"-{int(qs.get('unregistered_total') or 0)} unregistered, "
              f"{int(qs.get('evicted_total') or 0)} evicted) "
              f"buckets={len(qs.get('buckets') or {})} "
              f"recompiles={int(qs.get('recompiles') or 0)}")
    # Worst-offender per-node lines (informational): the DAG provider's
    # watermark-lag p99 names the node dragging the frontier, and the
    # telemetry per-node buckets name the slowest node per event —
    # budget either via an --slo spec's node_budgets to make it gate.
    dag_nodes = (snap.get("dag") or {}).get("nodes") or {}
    if dag_nodes:
        worst_name, worst_rec = max(
            dag_nodes.items(),
            key=lambda kv: float(
                (kv[1] or {}).get("watermark_lag_p99_ms") or 0),
        )
        print(f"note worst-node watermark lag: {worst_name} "
              f"p99={float((worst_rec or {}).get('watermark_lag_p99_ms') or 0):.1f} ms "
              f"(backend={(worst_rec or {}).get('backend')}, "
              f"retries={int((worst_rec or {}).get('retries') or 0)}, "
              f"failovers={int((worst_rec or {}).get('failovers') or 0)})")
    node_eps = []
    for nname, rec in (snap.get("nodes") or {}).items():
        rec = rec or {}
        span_us = float(rec.get("span_us") or 0)
        ev = float(rec.get("events") or 0)
        if span_us > 0 and ev > 0:
            node_eps.append((nname, ev / (span_us / 1e6)))
    if node_eps:
        slow_name, slow_eps = min(node_eps, key=lambda kv: kv[1])
        print(f"note worst-node EPS: {slow_name} at "
              f"{float(slow_eps):.0f} ev/s "
              f"({len(node_eps)} attributed node(s))")
    coll = snap.get("collectives") or {}
    if coll:
        print(f"note mesh collectives: {int(coll.get('calls') or 0)} "
              f"call(s), {int(coll.get('bytes') or 0)} B "
              "(trace-time logical estimate)")
        split = collective_split(coll)
        if split:
            print("note collective classes: " + ", ".join(
                f"{cls}={int(row['bytes'])}B/{int(row['calls'])} "
                f"call(s) [{'+'.join(row['kinds'])}]"
                for cls, row in sorted(split["by_class"].items())))
            rr = split.get("replication_ratio")
            if rr is not None:
                print(f"note replication ratio: {float(rr):.2f}x "
                      "(collective bytes / boundary-state bytes)")
                print(f"  ↳ {int(coll.get('bytes') or 0)} B moved by "
                      "collectives over "
                      f"{int(split['halo_state_bytes'])} B of live "
                      "boundary-pane state the halo wrappers declared "
                      "(telemetry.account_halo_state)")
    if snap.get("faults"):
        fired = ", ".join(f"{k}×{int(v)}"
                          for k, v in sorted(snap["faults"].items()))
        print(f"note injected faults fired (chaos run): {fired}")
    # Registered instant events (tools/sfprof/events.py — the consumer
    # side of the emit-name contract sfcheck's contract-twin pass pins):
    notable = events_mod.notable_event_counts(doc.get("events") or [])
    if notable:
        print("note instant events: "
              + ", ".join(f"{g}={int(n)}"
                          for g, n in sorted(notable.items())))
    print(f"{len(checks)} checks, {int(failed)} failed")
    return 1 if failed else 0


# -- recover ------------------------------------------------------------------


def cmd_recover(args) -> int:
    try:
        doc, info = stream_mod.recover(args.stream)
    except (OSError, ValueError) as e:
        print(f"sfprof: cannot recover {args.stream}: {e}")
        return 2
    out_path = args.out or args.stream + ".recovered.json"
    with open(out_path, "w") as f:
        json.dump(doc, f, allow_nan=False)
        f.write("\n")
    print(f"== sfprof recover: {args.stream} -> {out_path}")
    print(f"records={int(info['records'])} "
          f"checkpoints={int(info['checkpoints'])} "
          f"span_batches={int(info['spans_batches'])} "
          f"events={int(info['events_recovered'])}")
    if info["sealed"]:
        print(f"sealed: yes (reason: {info['reason']})")
    else:
        print("sealed: NO — stream ends without an epilogue "
              "(crash/SIGKILL)")
    if info["truncated"]:
        ck = info["last_checkpoint_unix"]
        where = (f"last checkpoint at unix {float(ck):.3f} "
                 f"(seq {int(info['last_seq'])})"
                 if ck is not None else "BEFORE the first checkpoint")
        print(f"truncated: yes — {where}; loss bound: "
              f"{info['loss_bound']}")
        if info["partial_tail"]:
            print(f"dropped a half-written tail line "
                  f"({int(info['skipped_bytes'])} bytes, "
                  f"{int(info['skipped_lines'])} later lines)")
    if info.get("nodes_recovered"):
        print("per-node attribution recovered: "
              + ", ".join(info["nodes_recovered"])
              + f" (collective bytes "
              f"{int(info.get('collective_bytes_recovered') or 0)})")
    if info.get("blackbox_folded"):
        print(f"blackbox dump folded: {info['blackbox_path']}")
        print(f"  ↳ dump reason: {info['blackbox_reason']}; "
              f"{int(info.get('blackbox_events_folded') or 0)} ring "
              "instant(s) newer than the last flushed span batch "
              "folded into the event list")
    # The crash story, by registered event name (events.py): what the
    # recovered run was doing when it died — sheds, circuit flips,
    # fault firings — without grepping the stream by hand.
    notable = events_mod.notable_event_counts(doc.get("events") or [])
    if notable:
        print("recovered instant events: "
              + ", ".join(f"{g}={int(n)}"
                          for g, n in sorted(notable.items())))
    problems = ledger_mod.validate(doc)
    for p in problems:
        print(f"FAIL schema: {p}")
    print(f"recovered ledger {'INVALID' if problems else 'valid'} "
          f"({len(problems)} schema problems)")
    return 1 if problems else 0


# -- blackbox -----------------------------------------------------------------


def cmd_blackbox(args) -> int:
    """Render a ``<stream>.blackbox.json`` flight-recorder dump: the
    dump reason, the counter gauges at death, the e2e block when
    present, and the last-N ring of window summaries + instants —
    newest last, timestamped relative to the dump's final entry."""
    try:
        with open(args.dump) as f:
            bb = json.load(f)
    except (OSError, ValueError) as e:
        print(f"sfprof: cannot read {args.dump}: {e}")
        return 2
    if not isinstance(bb, dict) or "blackbox_version" not in bb:
        print(f"sfprof: {args.dump}: not a blackbox dump "
              "(no blackbox_version)")
        return 2
    if args.json:
        print(json.dumps(bb, allow_nan=False))
        return 0
    print(f"== sfprof blackbox: {args.dump}")
    print(f"blackbox v{int(bb.get('blackbox_version') or 0)}  "
          f"reason: {bb.get('reason')}  "
          f"unix {float(bb.get('unix') or 0):.3f}")
    if bb.get("stream"):
        print(f"stream: {bb['stream']}")
    counters = bb.get("counters") or {}
    if counters:
        # fault_fires is a per-point dict, not a scalar — sum it for
        # the one-line view (the full map survives in --json).
        print("counters at dump: " + "  ".join(
            f"{k}={_fmt_num(sum(v.values()) if isinstance(v, dict) else v)}"
            for k, v in sorted(counters.items())))
    commit = ((bb.get("e2e") or {}).get("stages") or {}).get("commit")
    if commit:
        print(f"e2e commit latency: "
              f"p50 {float(commit.get('p50_ms') or 0):.1f} ms  "
              f"p99 {float(commit.get('p99_ms') or 0):.1f} ms over "
              f"{int(commit.get('count') or 0)} committed window(s)")
    ring = [r for r in (bb.get("ring") or []) if isinstance(r, dict)]
    print(f"ring: last {len(ring)} record(s), newest last")
    last_ts = max((float(r.get("ts") or 0) for r in ring), default=0.0)
    for rec in ring:
        rel_s = (last_ts - float(rec.get("ts") or 0)) / 1e6
        args_s = json.dumps(rec.get("args") or {}, sort_keys=True)
        if len(args_s) > 100:
            args_s = args_s[:97] + "..."
        if rec.get("t") == "window":
            print(f"  -{float(rel_s):9.3f}s  window  "
                  f"{rec.get('name')}  "
                  f"{float(float(rec.get('dur_us') or 0) / 1e3):.3f} ms"
                  f"  {args_s}")
        else:
            print(f"  -{float(rel_s):9.3f}s  instant "
                  f"{rec.get('name')}  {args_s}")
    return 0


# -- trend --------------------------------------------------------------------


def _key_str(key: tuple) -> str:
    return " ".join(f"{f}={v}" for f, v in
                    zip(trend_mod.SERIES_KEY_FIELDS, key))


def cmd_trend(args) -> int:
    points, skipped = trend_mod.ingest_paths(args.history)
    series = trend_mod.build_series(points)
    if args.config:
        series = {k: v for k, v in series.items()
                  if args.config in str(k[0])}

    out: Dict[str, Any] = {
        "series": [], "skipped": skipped, "gate": None,
    }
    for key, pts in sorted(series.items(), key=lambda kv: kv[0]):
        values = [p["value"] for p in pts]
        stats = trend_mod.robust_stats(values)
        row = {
            "key": dict(zip(trend_mod.SERIES_KEY_FIELDS, key)),
            "n": stats["n"],
            "median": stats["median"],
            "mad": stats["mad"],
            "floor": trend_mod.gate_floor(stats, args.mad_k,
                                          args.eps_tol),
            "latest": pts[-1]["value"],
            "sources": [p["source"] for p in pts],
        }
        res = [p["resident"] for p in pts if p["resident"] is not None]
        if res:
            rstats = trend_mod.robust_stats(res)
            row["resident_median"] = rstats["median"]
            row["resident_n"] = rstats["n"]
        out["series"].append(row)

    rc = 0
    if args.gate:
        out["gate"], rc = _gate_against_trend(args, series)
    if args.json:
        print(json.dumps(out, allow_nan=False))
        return rc

    print(f"== sfprof trend: {len(points)} point(s) in "
          f"{len(series)} series, {len(skipped)} record(s) skipped")
    for row in out["series"]:
        print(f"{_key_str(tuple(row['key'].values()))}: "
              f"n={int(row['n'])} median={float(row['median']):.1f} "
              f"MAD={float(row['mad']):.1f} "
              f"floor={float(row['floor']):.1f} "
              f"latest={float(row['latest']):.1f}")
    if skipped:
        # Each skipped history record is evidence, not just a count —
        # a trend built over silently-dropped captures reads as "the
        # whole trajectory" when it is not.
        print(f"skipped {len(skipped)} record(s):")
        for s in skipped:
            print(f"  ↳ {s['source']}: {s['reason']}")
    g = out["gate"]
    if g:
        print(f"== trend gate: {g['candidate']}")
        if g.get("reject"):
            print(f"REJECT: {g['reject']}")
        for chk in g.get("checks") or []:
            print(f"{'ok  ' if chk['ok'] else 'FAIL'} "
                  f"{chk['metric']:<28} "
                  f"value={float(chk['value']):.1f} [{chk['band']}]")
        if g.get("note"):
            print(f"note: {g['note']}")
        print(f"gate verdict: {'PASS' if rc == 0 else 'FAIL'}")
    return rc


def _gate_against_trend(args, series) -> Tuple[Dict[str, Any], int]:
    """(gate block, exit code) for the ``--gate`` candidate against its
    series. A candidate with no matching history passes with a loud
    note unless ``--require-history`` (the CI mode — a missing fixture
    must fail, not silently wave everything through)."""
    gate: Dict[str, Any] = {"candidate": args.gate, "checks": []}
    try:
        doc, kind = trend_mod.load_candidate(args.gate)
    except (OSError, ValueError) as e:
        gate["reject"] = f"cannot read candidate: {e}"
        return gate, 2
    pt, reason = trend_mod.point_of(doc, kind, args.gate)
    if pt is None:
        gate["reject"] = f"candidate carries no gateable EPS: {reason}"
        return gate, 1
    gate["key"] = dict(zip(trend_mod.SERIES_KEY_FIELDS,
                           trend_mod.series_key(pt)))
    pts = series.get(trend_mod.series_key(pt)) or []
    # Never gate a capture against itself: the candidate file may sit
    # in the history dir, and the same run may ALSO appear under
    # another path — its sibling stream's recovery, a copied ledger —
    # carrying the identical bench record.
    # Exclude by path and by exact (value, resident) identity; a
    # distinct run tying both rounded values is rare and could only
    # make the gate stricter by one sample.
    cand = os.path.abspath(args.gate)

    def _own(p) -> bool:
        return (os.path.abspath(p["source"]) == cand
                or (p["value"] == pt["value"]
                    and p["resident"] == pt["resident"]))

    others = [p for p in pts if not _own(p)]
    history = [p["value"] for p in others]
    # Stats need >= 1 point: --min-history 0 still means "gate only
    # with actual history", never an empty-series crash.
    min_hist = max(int(args.min_history), 1)
    if len(history) < min_hist:
        note = (f"insufficient history for this key: {len(history)} "
                f"point(s) < --min-history {int(min_hist)}")
        gate["note"] = note
        return gate, (1 if args.require_history else 0)
    rc = 0
    chk = trend_mod.gate_metric(history, pt["value"], args.mad_k,
                                args.eps_tol)
    chk["metric"] = "points_per_sec"
    gate["checks"].append(chk)
    rc = rc or (0 if chk["ok"] else 1)
    res_hist = [p["resident"] for p in others
                if p["resident"] is not None]
    if pt["resident"] is not None and len(res_hist) >= min_hist:
        chk = trend_mod.gate_metric(res_hist, pt["resident"],
                                    args.mad_k, args.eps_tol)
        chk["metric"] = "device_resident_points_per_sec"
        gate["checks"].append(chk)
        rc = rc or (0 if chk["ok"] else 1)
    return gate, rc


# -- entry --------------------------------------------------------------------


def build_parser() -> argparse.ArgumentParser:
    ap = argparse.ArgumentParser(
        prog="python -m tools.sfprof",
        description=__doc__,
        formatter_class=argparse.RawDescriptionHelpFormatter,
    )
    sub = ap.add_subparsers(dest="cmd", required=True)

    rep = sub.add_parser(
        "report", help="phase attribution, top kernels, bytes/window, "
                       "host gaps, roofline bound verdict from a "
                       "ledger or Chrome trace")
    rep.add_argument("path")
    rep.add_argument("--top", type=int, default=10)
    rep.add_argument("--json", action="store_true",
                     help="one machine-readable JSON document instead "
                          "of human text (same exit code)")
    rep.add_argument("--peak-flops", type=float, default=None,
                     help="override the roofline machine model's "
                          "sustained flop/s")
    rep.add_argument("--peak-bw", type=float, default=None,
                     help="override the roofline machine model's "
                          "memory bandwidth (B/s)")
    rep.set_defaults(fn=cmd_report)

    dif = sub.add_parser(
        "diff", help="per-metric deltas A→B with tolerance bands; "
                     "--gate exits 1 on regression")
    dif.add_argument("a")
    dif.add_argument("b")
    dif.add_argument("--gate", action="store_true")
    dif.add_argument("--eps-tol", type=float, default=0.5,
                     help="allowed fractional EPS drop (default 0.5 — "
                          "until per-cell spreads are measured)")
    dif.add_argument("--lat-tol", type=float, default=1.0,
                     help="allowed fractional latency growth "
                          "(default 1.0 = 2x)")
    dif.add_argument("--verbose", action="store_true",
                     help="also print informational rows")
    dif.set_defaults(fn=cmd_diff)

    hea = sub.add_parser(
        "health", help="threshold verdicts: recompile churn, overflows, "
                       "late drops, watermark lag, dropped events; "
                       "--slo applies a declarative spec")
    hea.add_argument("ledger")
    hea.add_argument("--recompile-threshold", type=int, default=8)
    hea.add_argument("--max-lag-ms", type=int, default=10_000)
    hea.add_argument("--slo", default=None, metavar="SPEC_JSON",
                     help="SLO spec (the same JSON the live engine "
                          "evaluates: watermark-lag p99 ceiling, EPS "
                          "floor, late-drop/overflow budgets, recompile "
                          "ceiling)")
    hea.add_argument("--json", action="store_true",
                     help="one machine-readable JSON document (checks, "
                          "roofline verdict, notes) instead of "
                          "human text (same exit code)")
    hea.set_defaults(fn=cmd_health)

    rec = sub.add_parser(
        "recover", help="reconstruct a gateable ledger from a (possibly "
                        "truncated) SFT_LEDGER_STREAM JSONL stream")
    rec.add_argument("stream")
    rec.add_argument("-o", "--out", default=None,
                     help="output ledger path (default: "
                          "<stream>.recovered.json)")
    rec.set_defaults(fn=cmd_recover)

    critical_mod.add_parser(sub)

    bbx = sub.add_parser(
        "blackbox", help="render a <stream>.blackbox.json flight-"
                         "recorder dump: reason, counters at death, "
                         "last-N window summaries + instants")
    bbx.add_argument("dump")
    bbx.add_argument("--json", action="store_true",
                     help="print the dump document as one JSON line "
                          "(validated; same exit code)")
    bbx.set_defaults(fn=cmd_blackbox)

    live_mod.add_parser(sub)

    trd = sub.add_parser(
        "trend", help="per-config time series over a whole capture "
                      "history (ledgers, streams, legacy BENCH_r*.json "
                      "round records); --gate checks a new "
                      "capture against the robust median + MAD band")
    trd.add_argument("history", nargs="+",
                     help="history files and/or directories (dirs: "
                          "every .json/.jsonl inside, sorted)")
    trd.add_argument("--gate", default=None, metavar="NEW_LEDGER",
                     help="candidate capture to gate against its "
                          "series; exit 1 outside the band")
    trd.add_argument("--config", default=None,
                     help="only series whose config name contains this "
                          "substring")
    trd.add_argument("--mad-k", type=float,
                     default=trend_mod.DEFAULT_MAD_K,
                     help="MAD band width in robust sigmas "
                          "(default %(default)s)")
    trd.add_argument("--eps-tol", type=float,
                     default=trend_mod.DEFAULT_EPS_TOL,
                     help="relative floor: regression also requires "
                          "value < median*(1-eps_tol) "
                          "(default %(default)s)")
    trd.add_argument("--min-history", type=int,
                     default=trend_mod.DEFAULT_MIN_HISTORY,
                     help="points required before the gate engages "
                          "(default %(default)s)")
    trd.add_argument("--require-history", action="store_true",
                     help="fail (exit 1) when the candidate's series "
                          "has fewer than --min-history points — the "
                          "CI mode: a missing fixture must not wave "
                          "captures through")
    trd.add_argument("--json", action="store_true",
                     help="one machine-readable JSON document (series, "
                          "skipped evidence, gate verdict)")
    trd.set_defaults(fn=cmd_trend)
    return ap


def main(argv: Optional[List[str]] = None) -> int:
    args = build_parser().parse_args(argv)
    try:
        return args.fn(args)
    except BrokenPipeError:
        # `sfprof report | head` closing the pipe early is not an error;
        # detach stdout so the interpreter's exit flush stays quiet.
        import sys

        os.dup2(os.open(os.devnull, os.O_WRONLY), sys.stdout.fileno())
        return 0
