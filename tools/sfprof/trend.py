"""Longitudinal trend store: gate a capture against its TRAJECTORY.

The pairwise ``sfprof diff`` gate is nearly blind when run-to-run
variance is wide: one noisy predecessor hides any regression smaller
than its band. This module ingests the WHOLE history — run ledgers,
ledger streams (recovered in-memory), the legacy ``BENCH_r*.json``
round records (``{n, cmd, rc, tail, parsed}``),
and bare bench-record JSON — into one per-config time series, then
gates a new capture against the series' robust center:

    regression  ⇔  value < min(median − k·1.4826·MAD,
                               median·(1 − eps_tol))

Both legs must agree: the MAD band adapts to the series' real scatter
(a noisy trajectory widens its own band), while the relative
floor keeps a zero-variance toy series from flagging ordinary noise.
Only the DOWNSIDE gates — faster is never a regression.

Series are keyed by (config, device class, smoke, pipeline arming,
codec arming) so toy smoke runs never mix with chip captures and a
pipelined capture lands against pipelined history. Commit/device/time
ride each point as attributes for the report, not the key.

History hygiene is skip-with-counted-evidence, never a crash: an rc≠0
round record (a failed capture), an unparseable tail, a
zero-value error record, or a ``tainted`` ablation capture is skipped
WITH its reason in the output — silence is how bad history poisons a
gate. A tainted CANDIDATE is hard-rejected (exit 1): an ablated run
must never enter the record as a real number.

Stdlib-only, no jax import (the sfprof no-cross-import rule).
"""

from __future__ import annotations

import json
import os
import statistics
from typing import Any, Dict, List, Optional, Tuple

from tools.sfprof import ledger as ledger_mod
from tools.sfprof import stream as stream_mod

#: The series key, in order (also the ``--json`` key row order).
SERIES_KEY_FIELDS = ("config", "device_class", "smoke", "pipeline",
                     "codec")

#: Gate defaults — shared with the CLI's argparse defaults.
DEFAULT_MAD_K = 4.0
DEFAULT_EPS_TOL = 0.5
DEFAULT_MIN_HISTORY = 3

#: 1.4826 · MAD estimates one standard deviation for normal scatter.
MAD_SIGMA = 1.4826


def device_class(device: Any) -> str:
    """Stable device family: 'cpu' / 'tpu' / first token. Keys must not
    depend on host-specific device strings ('TFRT_CPU_0' vs 'cpu:0')."""
    d = str(device or "").lower()
    if not d:
        return "unknown"
    if "cpu" in d:
        return "cpu"
    if "tpu" in d:
        return "tpu"
    return d.split()[0].split(":")[0]


def _finite_pos(v: Any) -> bool:
    """A usable EPS sample: numeric, finite, > 0. NaN/Inf can ride a
    hand-edited or legacy record (json.loads accepts them) and would
    otherwise poison the median or crash the strict ``--json`` dump."""
    if not isinstance(v, (int, float)) or isinstance(v, bool):
        return False
    return v > 0 and v != float("inf")


def taint_of(doc_or_rec: Dict[str, Any]) -> Optional[dict]:
    """The taint block of a ledger/record, wherever it rides (top level,
    snapshot checkpoint — the stream-recovery path — or bench block)."""
    for block in (doc_or_rec,
                  doc_or_rec.get("snapshot") or {},
                  doc_or_rec.get("bench") or {}):
        t = block.get("tainted")
        if isinstance(t, dict):
            return t
    return None


def point_from_bench(bench: Dict[str, Any], source: str,
                     created_unix: Optional[float] = None,
                     commit: Optional[str] = None,
                     device: Any = None) -> Tuple[Optional[dict],
                                                  Optional[str]]:
    """(point, skip_reason) from one bench record dict."""
    if not isinstance(bench, dict):
        return None, "bench block is not an object"
    config = bench.get("config") or bench.get("metric")
    if not config:
        return None, "record names no config/metric"
    value = bench.get("points_per_sec")
    if not _finite_pos(value):
        value = bench.get("value")
    if not _finite_pos(value):
        return None, "zero/absent EPS (outage or error record)"
    t = bench.get("tainted")
    if isinstance(t, dict):
        return None, f"tainted: {t.get('kind', '?')}"
    pipe = bench.get("pipeline") or {}
    resident = bench.get("device_resident_points_per_sec")
    if not _finite_pos(resident):
        resident = None
    return {
        "config": str(config),
        "device_class": device_class(device or bench.get("device")),
        "device": str(device or bench.get("device") or ""),
        "smoke": bool(bench.get("smoke")),
        "pipeline": bool(pipe.get("armed")),
        "codec": str(pipe.get("armed_codec") or ""),
        "value": float(value),
        "resident": (float(resident) if resident is not None else None),
        "created_unix": (float(created_unix)
                         if created_unix is not None else None),
        "commit": commit,
        "source": source,
    }, None


def point_from_ledger(doc: Dict[str, Any], source: str) \
        -> Tuple[Optional[dict], Optional[str]]:
    t = taint_of(doc)
    if t is not None:
        return None, f"tainted: {t.get('kind', '?')}"
    env = doc.get("env") or {}
    device = (env.get("devices") or [None])[0] or env.get("backend")
    return point_from_bench(
        doc.get("bench") or {}, source,
        created_unix=doc.get("created_unix"), device=device,
    )


def point_from_supervisor(rec: Dict[str, Any], source: str) \
        -> Tuple[Optional[dict], Optional[str]]:
    """Normalize one legacy BENCH_r*-style round record."""
    rc = rec.get("rc")
    if rc not in (0, None):
        return None, f"supervisor rc={rc} (failed capture)"
    parsed = rec.get("parsed")
    if not isinstance(parsed, dict):
        # Fall back to the last JSON line of the captured tail — the
        # ONE-line driver contract means it is the record when present.
        parsed = None
        for line in reversed(str(rec.get("tail") or "").splitlines()):
            line = line.strip()
            if line.startswith("{"):
                try:
                    parsed = json.loads(line)
                except ValueError:
                    parsed = None
                break
        if not isinstance(parsed, dict):
            return None, "no parseable record in parsed/tail"
    return point_from_bench(parsed, source)


def load_candidate(path: str) -> Tuple[Dict[str, Any], str]:
    """(document, kind) for one history file or gate candidate. Raises
    OSError/ValueError on unreadable input (the CLI's exit-2 surface)."""
    try:
        with open(path) as f:
            doc = json.load(f)
    except ValueError:
        # Multi-line non-document: a ledger STREAM — recover in memory.
        doc, _info = stream_mod.recover(path)
        return doc, "stream"
    if not isinstance(doc, dict):
        raise ValueError(f"{path}: not a JSON object")
    if ledger_mod.is_ledger(doc):
        return doc, "ledger"
    if "rc" in doc and ("parsed" in doc or "tail" in doc):
        return doc, "supervisor"
    if "config" in doc or "metric" in doc:
        return doc, "bench"
    raise ValueError(f"{path}: unrecognized record shape")


def point_of(doc: Dict[str, Any], kind: str, source: str) \
        -> Tuple[Optional[dict], Optional[str]]:
    if kind in ("ledger", "stream"):
        return point_from_ledger(doc, source)
    if kind == "supervisor":
        return point_from_supervisor(doc, source)
    return point_from_bench(doc, source)


def expand_paths(paths: List[str]) -> List[str]:
    """Files named directly plus the JSON/JSONL files of any named
    directory (one level, sorted — the SFT_LEDGER_DIR layout)."""
    out: List[str] = []
    for p in paths:
        if os.path.isdir(p):
            for name in sorted(os.listdir(p)):
                if name.endswith((".json", ".jsonl")):
                    out.append(os.path.join(p, name))
        else:
            out.append(p)
    return out


def ingest_paths(paths: List[str]) -> Tuple[List[dict], List[dict]]:
    """(points, skipped) over every history file; skipped entries carry
    ``{"source", "reason"}`` — counted evidence, never a crash."""
    points: List[dict] = []
    skipped: List[dict] = []
    for path in expand_paths(paths):
        try:
            doc, kind = load_candidate(path)
        except (OSError, ValueError) as e:
            skipped.append({"source": path, "reason": str(e)})
            continue
        pt, reason = point_of(doc, kind, path)
        if pt is None:
            skipped.append({"source": path, "reason": reason})
        else:
            points.append(pt)
    return points, skipped


def series_key(point: Dict[str, Any]) -> Tuple:
    return tuple(point[f] for f in SERIES_KEY_FIELDS)


def build_series(points: List[dict]) -> Dict[Tuple, List[dict]]:
    """Points grouped by series key, time-ordered, with ONE entry per
    capture: a run captured as both a ledger and its sibling stream
    (the SFT_LEDGER_DIR layout writes ``<cfg>.json`` AND
    ``<cfg>.stream.jsonl``, whose recovery carries the identical bench
    record) must count once — twin artifacts would otherwise shrink the
    MAD and let a candidate be gated partly against itself. Dedup key:
    (series key, value, resident) — two genuinely distinct runs landing
    on the exact same rounded EPS pair collapse too, which moves a
    robust median by at most one sample."""
    out: Dict[Tuple, List[dict]] = {}
    seen: set = set()
    for pt in points:
        key = series_key(pt)
        dedup = (key, pt["value"], pt["resident"])
        if dedup in seen:
            continue
        seen.add(dedup)
        out.setdefault(key, []).append(pt)
    for pts in out.values():
        pts.sort(key=lambda p: (p["created_unix"] is None,
                                p["created_unix"] or 0.0, p["source"]))
    return out


def robust_stats(values: List[float]) -> Dict[str, float]:
    med = statistics.median(values)
    mad = statistics.median([abs(v - med) for v in values])
    return {"n": len(values), "median": med, "mad": mad}


def gate_floor(stats: Dict[str, float], mad_k: float,
               eps_tol: float) -> float:
    """The regression floor: BOTH the MAD band and the relative floor
    must be violated, so the floor is the LOWER of the two."""
    lo_mad = stats["median"] - mad_k * MAD_SIGMA * stats["mad"]
    lo_rel = stats["median"] * (1.0 - eps_tol)
    return min(lo_mad, lo_rel)


def gate_metric(history: List[float], value: float, mad_k: float,
                eps_tol: float) -> Dict[str, Any]:
    stats = robust_stats(history)
    lo = gate_floor(stats, mad_k, eps_tol)
    return {
        "value": float(value),
        "floor": float(lo),
        "median": float(stats["median"]),
        "mad": float(stats["mad"]),
        "n": int(stats["n"]),
        "band": (f">= min(median - {float(mad_k):g}*{MAD_SIGMA}*MAD, "
                 f"median*(1-{float(eps_tol):g})) = {float(lo):.1f}"),
        "ok": bool(value >= lo),
    }
