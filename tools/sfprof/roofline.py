"""Roofline bound-classification: WHY is this config slow?

The suite's evidence says different configs are bound by completely
different resources (ingest over the host↔device link on dense
configs, the kernel itself on tjoin), but until now the ledger only *reported* signals; the
reader had to do the attribution by hand. This module turns one run
ledger into a verdict with an sfcheck-style evidence chain:

- **link-bound** — device-boundary bytes ÷ the MEASURED LinkProbe p50
  bandwidth explain the traced wall;
- **host-bound** — inter-window host gaps plus the unattributed residue
  inside window spans dominate (assembly, serde, GC);
- **dispatch-bound** — kernel steady dispatch time dominates, but the
  machine-model device-work estimate covers less than half of it: the
  wall is per-dispatch overhead, so
  batching dispatches — not faster kernels — is the lever;
- **compute-bound / memory-bound** — dispatch time dominates AND the
  XLA cost model accounts for it; the flops-vs-bytes roofline picks the
  side (arithmetic intensity against the machine balance point).

Everything here is derived from signals the ledger already carries
(``telemetry.capture_costs`` flops/bytes, ``instrument_jit`` steady
wall-ns, LinkProbe gauges, span attribution) —
no new instrumentation, no jax import (the sfprof no-cross-import
rule). The machine models are order-of-magnitude ridge estimates per
backend, overridable via ``--peak-flops``/``--peak-bw``; they gate
nothing — the classifier is a diagnosis surface (``report``/``health``
print it, ``--json`` carries it), never a regression gate.
"""

from __future__ import annotations

from typing import Any, Dict, List, Optional

from tools.sfprof import attribution

#: Verdict vocabulary (fixed — tests pin it; dashboards key on it).
BOUND_KINDS = (
    "link-bound", "host-bound", "dispatch-bound", "compute-bound",
    "memory-bound", "inconclusive",
)

#: Order-of-magnitude machine models per backend family: sustained
#: flop/s and memory bandwidth (B/s) a dispatch-dominated run could
#: plausibly achieve. Deliberately coarse — they only split dispatch
#: time into {overhead, compute, memory} shares for the verdict; they
#: never enter a gate band.
MACHINE_MODELS: Dict[str, Dict[str, float]] = {
    "cpu": {"peak_flops": 5.0e10, "peak_bw": 2.0e10},
    # v5e-class chip (HBM bw dominates for the mask-don't-compact
    # kernels here).
    "tpu": {"peak_flops": 2.0e14, "peak_bw": 8.0e11},
}

#: A component must explain at least this fraction of the traced wall
#: for the verdict to be called DOMINANT; below it the verdict still
#: names the largest component but the evidence says so ("weak").
DOMINANCE_FRAC = 0.4

#: Machine-model share of dispatch time below which dispatch time is
#: per-dispatch overhead, not device work.
OVERHEAD_FRAC = 0.5


def _machine_model(backend: Optional[str], peak_flops: Optional[float],
                   peak_bw: Optional[float]) -> Dict[str, float]:
    b = str(backend or "").lower()
    family = "tpu" if "tpu" in b else "cpu"
    model = dict(MACHINE_MODELS[family])
    model["family"] = family
    if peak_flops:
        model["peak_flops"] = float(peak_flops)
    if peak_bw:
        model["peak_bw"] = float(peak_bw)
    return model


def _kernel_signals(kernels: List[dict], model: Dict[str, float]):
    """(dispatch_us, est_compute_us, est_memory_us, est_device_us,
    costed_flops, costed_bytes, calls) over the steady-state
    dispatches. ``est_device_us`` is the roofline device-time estimate:
    per kernel, max(flops-time, bytes-time) — the resource the kernel
    actually waits on — summed over its steady calls.

    First calls are excluded on BOTH sides (steady_ns already excludes
    the compile-inclusive first call, so the cost-model estimate pairs
    each kernel's ``calls - 1`` steady dispatches with its per-dispatch
    flops/bytes)."""
    dispatch_us = 0.0
    est_compute_us = 0.0
    est_memory_us = 0.0
    est_device_us = 0.0
    flops_total = 0.0
    bytes_total = 0.0
    calls_total = 0
    for row in kernels or []:
        calls = int(row.get("calls") or 0)
        steady = row.get("steady_ns")
        if steady is None:
            steady = max(
                int(row.get("dispatch_ns") or 0)
                - int(row.get("first_call_ns") or 0), 0)
        dispatch_us += float(steady) / 1e3
        n_steady = max(calls - 1, 0)
        calls_total += n_steady
        cost = row.get("cost") or {}
        flops = cost.get("flops")
        nbytes = cost.get("bytes_accessed")
        per_compute = 0.0
        per_memory = 0.0
        if isinstance(flops, (int, float)):
            flops_total += float(flops) * n_steady
            per_compute = float(flops) / model["peak_flops"] * 1e6
            est_compute_us += per_compute * n_steady
        if isinstance(nbytes, (int, float)):
            bytes_total += float(nbytes) * n_steady
            per_memory = float(nbytes) / model["peak_bw"] * 1e6
            est_memory_us += per_memory * n_steady
        est_device_us += max(per_compute, per_memory) * n_steady
    return (dispatch_us, est_compute_us, est_memory_us, est_device_us,
            flops_total, bytes_total, calls_total)


def _pct(part: float, whole: float) -> float:
    return 100.0 * part / whole if whole else 0.0


def classify(doc: Optional[Dict[str, Any]], events: List[dict],
             peak_flops: Optional[float] = None,
             peak_bw: Optional[float] = None) -> Dict[str, Any]:
    """One run ledger (+ its events) → a bound verdict with evidence.

    Returns a JSON-safe block::

        {"verdict", "dominant": bool, "wall_us",
         "components": {"link_us"|None, "host_us", "dispatch_us",
                        "overhead_us", "est_compute_us", "est_memory_us"},
         "fractions": {"link"|None, "host", "dispatch"},
         "machine_model": {...}, "evidence": [str, ...],
         "per_operator": {op: {"verdict", "phases_us": {...}}}}

    ``verdict`` is always one of :data:`BOUND_KINDS`; ``inconclusive``
    only when the event stream carries no timestamped spans at all.
    """
    snap = (doc or {}).get("snapshot") or {}
    kernels = (doc or {}).get("kernels") or []
    env = (doc or {}).get("env") or {}
    model = _machine_model(env.get("backend"), peak_flops, peak_bw)
    evidence: List[str] = []

    wall_us = attribution.span_range_us(events)
    if not wall_us:
        return {
            "verdict": "inconclusive", "dominant": False,
            "wall_us": None, "components": {}, "fractions": {},
            "machine_model": model,
            "evidence": ["no timestamped spans in the event stream — "
                         "re-run with telemetry enabled to classify"],
            "per_operator": {},
        }
    wall_ms = wall_us / 1e3

    # -- link: measured boundary bytes ÷ the probed bandwidth ---------------
    lp = snap.get("link_probe") or {}
    bw = lp.get("roundtrip_mbps_p50")
    total_bytes = (float(snap.get("bytes_h2d") or 0)
                   + float(snap.get("bytes_d2h") or 0))
    link_us: Optional[float] = None
    if isinstance(bw, (int, float)) and bw > 0:
        # bytes ÷ (MB/s · 1e6 B/MB) s → µs: numerically bytes/bw.
        link_us = total_bytes / float(bw)
        evidence.append(
            f"link: {int(total_bytes)} B across the device boundary ÷ "
            f"probe p50 {float(bw):.1f} MB/s ≈ "
            f"{float(link_us / 1e3):.2f} ms = "
            f"{float(_pct(link_us, wall_us)):.1f}% of the "
            f"{float(wall_ms):.2f} ms traced span"
        )
    else:
        evidence.append(
            "link: no LinkProbe bandwidth gauge in this ledger — link "
            "share unknown"
        )

    # -- host: inter-window gaps + unattributed residue ---------------------
    _windows, ops = attribution.attribute_windows(events)
    gaps = attribution.host_gaps(events)
    gap_us = float(sum(g["gap_us"] for g in gaps))
    resid_us = float(sum(a["unattributed_us"] for a in ops.values()))
    host_us = gap_us + resid_us
    evidence.append(
        f"host: {float(gap_us / 1e3):.2f} ms inter-window gaps + "
        f"{float(resid_us / 1e3):.2f} ms unattributed window residue = "
        f"{float(_pct(host_us, wall_us)):.1f}% of wall"
    )

    # -- collectives: trace-time mesh traffic (parallel/ wrappers) ----------
    coll = snap.get("collectives") or {}
    collective_bytes = float(coll.get("bytes") or 0)
    if collective_bytes:
        kinds = ", ".join(
            f"{k}: {int((v or {}).get('bytes') or 0)} B"
            for k, v in sorted((coll.get("by_kind") or {}).items())
        )
        evidence.append(
            f"collectives: {int(collective_bytes)} logical B across "
            f"{int(coll.get('calls') or 0)} mesh collective(s) "
            f"({kinds}) — the all-gather/halo baseline scale-out must "
            "beat (trace-time estimate, not wire measurement)"
        )

    # -- dispatch: steady kernel time, split by the machine model -----------
    (dispatch_us, est_compute_us, est_memory_us, est_device_us,
     flops_total, bytes_total, calls_total) = _kernel_signals(
        kernels, model)
    overhead_us = max(dispatch_us - est_device_us, 0.0)
    evidence.append(
        f"dispatch: {float(dispatch_us / 1e3):.2f} ms steady kernel "
        f"dispatch across {len(kernels)} kernel(s) / "
        f"{int(calls_total)} steady call(s) = "
        f"{float(_pct(dispatch_us, wall_us)):.1f}% of wall"
    )

    fractions: Dict[str, Optional[float]] = {
        "link": (link_us / wall_us) if link_us is not None else None,
        "host": host_us / wall_us,
        "dispatch": dispatch_us / wall_us,
    }
    candidates = {k: v for k, v in fractions.items() if v is not None}
    winner = max(candidates, key=lambda k: candidates[k])
    dominant = candidates[winner] >= DOMINANCE_FRAC

    # -- e2e lineage: per-stage deltas sharpen link vs dispatch -------------
    # The v3 snapshot's e2e stage buckets are CUMULATIVE lifecycle
    # latencies (assemble ⊆ ship ⊆ compute ⊆ fetch), so count-weighted
    # mean DELTAS split a window's life into transfer (ship + fetch
    # hops) vs device work (compute) — an independent clock on the same
    # question the span fractions answer, used as evidence always and
    # as the tiebreak when link and dispatch are within 10% of wall.
    def _stage_mean(stage_name: str) -> Optional[float]:
        st = ((snap.get("e2e") or {}).get("stages") or {}) \
            .get(stage_name) or {}
        s, n = st.get("sum_ms"), st.get("count")
        if isinstance(s, (int, float)) and isinstance(n, (int, float)) \
                and n:
            return float(s) / float(n)
        return None

    mean_asm = _stage_mean("assemble")
    mean_ship = _stage_mean("ship")
    mean_comp = _stage_mean("compute")
    mean_fetch = _stage_mean("fetch")
    if mean_ship is not None and mean_comp is not None:
        transfer_ms = max(mean_ship - (mean_asm or 0.0), 0.0)
        if mean_fetch is not None:
            transfer_ms += max(mean_fetch - mean_comp, 0.0)
        device_ms = max(mean_comp - mean_ship, 0.0)
        evidence.append(
            f"e2e lineage: mean per-window stage deltas — transfer "
            f"(ship+fetch hops) ≈ {float(transfer_ms):.2f} ms vs "
            f"device (compute) ≈ {float(device_ms):.2f} ms "
            "(cumulative stage buckets, count-weighted means)"
        )
        if ("link" in candidates and "dispatch" in candidates
                and winner in ("link", "dispatch")
                and abs(candidates["link"]
                        - candidates["dispatch"]) < 0.1
                and transfer_ms != device_ms):
            lean = "link" if transfer_ms > device_ms else "dispatch"
            if lean != winner:
                evidence.append(
                    f"e2e lineage: link and dispatch within 10% of "
                    f"wall — the lineage split breaks the tie toward "
                    f"{lean}"
                )
                winner = lean
                dominant = candidates[winner] >= DOMINANCE_FRAC

    if winner == "link":
        verdict = "link-bound"
    elif winner == "host":
        verdict = "host-bound"
    else:
        # Split dispatch time with the machine model.
        if est_device_us <= 0:
            verdict = "dispatch-bound"
            evidence.append(
                "dispatch: no kernel cost data (capture_costs never "
                "ran?) — cannot split device work from overhead; "
                "classifying the dispatch wall as per-dispatch overhead"
            )
        elif overhead_us >= OVERHEAD_FRAC * dispatch_us:
            verdict = "dispatch-bound"
            evidence.append(
                f"dispatch: machine-model device work ≈ "
                f"{float(est_device_us / 1e3):.2f} ms "
                f"({model['family']} model: "
                f"{float(model['peak_flops']):.1e} flop/s, "
                f"{float(model['peak_bw']):.1e} B/s) leaves "
                f"{float(overhead_us / 1e3):.2f} ms "
                f"({float(_pct(overhead_us, dispatch_us)):.0f}% of "
                "dispatch) as per-dispatch overhead → batch dispatches, "
                "don't optimize kernels"
            )
        else:
            intensity = (flops_total / bytes_total) if bytes_total else None
            balance = model["peak_flops"] / model["peak_bw"]
            if intensity is not None and intensity < balance:
                verdict = "memory-bound"
            else:
                verdict = "compute-bound"
            ai = float(intensity) if intensity is not None else 0.0
            evidence.append(
                f"dispatch: arithmetic intensity "
                f"{float(flops_total):.3g} flop / "
                f"{float(bytes_total):.3g} B ≈ "
                f"{float(ai):.2f}"
                f" flop/B vs machine balance {float(balance):.1f} "
                f"flop/B → {verdict}"
            )
    if not dominant:
        evidence.append(
            f"weak dominance: largest component ({winner}) explains "
            f"only {float(100.0 * candidates[winner]):.1f}% of wall "
            f"(< {float(100.0 * DOMINANCE_FRAC):.0f}%) — verdict is "
            "the best available signal, not a clear wall"
        )

    per_operator = _per_operator(ops)
    per_node = _per_node(attribution.attribute_nodes(events),
                         snap.get("nodes") or {})
    return {
        "verdict": verdict,
        "dominant": bool(dominant),
        "wall_us": float(wall_us),
        "components": {
            "link_us": (float(link_us) if link_us is not None else None),
            "host_us": float(host_us),
            "dispatch_us": float(dispatch_us),
            "overhead_us": float(overhead_us),
            "est_compute_us": float(est_compute_us),
            "est_memory_us": float(est_memory_us),
            "collective_bytes": float(collective_bytes),
        },
        "fractions": {
            k: (float(v) if v is not None else None)
            for k, v in fractions.items()
        },
        "machine_model": model,
        "evidence": evidence,
        "per_operator": per_operator,
        "per_node": per_node,
    }


#: Phase names that are boundary transfers in the PR 1 span convention,
#: and the link's own leaf spans (``operators/base.py:_h2d``,
#: ``telemetry.fetch``). Only top-level children are summed
#: (attribution.py), so a leaf nested in its phase span is not counted
#: again; one that a path emits outside any phase span (a DAG node's
#: ``ship``, the pane paths' bare ``telemetry.fetch``) still reads as link.
_LINK_PHASES = ("ship", "fetch", "h2d", "d2h")


def _per_operator(ops: Dict[str, dict]) -> Dict[str, dict]:
    """Phase-level verdict per ``window.*`` operator: which of
    {transfer, compute, host} dominates ITS OWN window time. Coarser
    than the run verdict (phase spans cannot split compute from memory)
    but localizes the wall to an operator."""
    out: Dict[str, dict] = {}
    for name, agg in sorted(ops.items()):
        phases = agg.get("phases") or {}
        link = float(sum(us for p, us in phases.items()
                         if any(p == lp or p.startswith(lp + ".")
                                for lp in _LINK_PHASES)))
        host = float(agg.get("unattributed_us") or 0)
        compute = float(sum(us for p, us in phases.items())) - link
        total = float(agg.get("dur_us") or 0)
        shares = {"link-bound": link, "dispatch-bound": compute,
                  "host-bound": host}
        verdict = max(shares, key=lambda k: shares[k]) \
            if total > 0 else "inconclusive"
        out[name] = {
            "verdict": verdict,
            "phases_us": {"transfer": link, "compute": compute,
                          "host": host, "total": total},
        }
    return out


def _per_node(nodes: Dict[str, dict],
              snap_nodes: Dict[str, dict]) -> Dict[str, dict]:
    """Per-DAG-node bound verdict: the :func:`_per_operator` phase split
    over each node's ``node.*`` container spans, refined with the
    snapshot ``nodes`` block's exact byte/dispatch counters (a node with
    heavy h2d/d2h traffic but thin ship/fetch spans — e.g. panes shipped
    by the shared source — still shows its boundary bytes). A link-bound
    q3 next to a compute-bound qserve is exactly the verdict split the
    chip-capture campaign needs."""
    out: Dict[str, dict] = {}
    for name, agg in sorted(nodes.items()):
        phases = agg.get("phases") or {}
        link = float(sum(us for p, us in phases.items()
                         if any(p == lp or p.startswith(lp + ".")
                                for lp in _LINK_PHASES)))
        host = float(agg.get("unattributed_us") or 0)
        compute = float(sum(us for p, us in phases.items())) - link
        total = float(agg.get("dur_us") or 0)
        shares = {"link-bound": link, "dispatch-bound": compute,
                  "host-bound": host}
        verdict = max(shares, key=lambda k: shares[k]) \
            if total > 0 and max(shares.values()) > 0 else "inconclusive"
        counters = snap_nodes.get(name) or {}
        row = {
            "verdict": verdict,
            "windows": int(agg.get("windows") or 0),
            "events": int(agg.get("events") or 0),
            "eps": agg.get("eps"),
            "phases_us": {"transfer": link, "compute": compute,
                          "host": host, "total": total},
            "bytes_h2d": int(counters.get("h2d_bytes") or 0),
            "bytes_d2h": int(counters.get("d2h_bytes") or 0),
            "dispatch_ns": int(counters.get("dispatch_ns") or 0),
            "compiles": int(counters.get("compiles") or 0),
            "collective_bytes": int(
                counters.get("collective_bytes") or 0),
        }
        out[name] = row
    return out
