"""From a profiler trace to numbers: busy time, time per device operation,
idle gaps named by what the host was doing.

Two stages, so that the arithmetic can be checked without a chip:

1. :func:`read` turns an ``.xplane.pb`` (``jax.profiler.ProfileData``) into a
   small plain document — per device plane the events of its ``XLA Ops``,
   ``Async XLA Ops`` and ``XLA Modules`` lines, and from the host planes the harness's own
   ``bench:*`` annotations. Times are seconds on the trace's clock.
2. Everything else here works on that document and on plain intervals.
   ``checks/test_xplane.py`` holds it to a document recorded on a TPU v5e
   (``checks/fixtures/``) and to hand-made intervals.

Definitions (on-chip-measurement guide, section 4): busy = the union of the
intervals in which an operation, synchronous or asynchronous, ran on the
device, inside the window; idle share = 1 - busy / window; a program's device
time = the sum of its executions on the ``XLA Modules`` line.
"""

from __future__ import annotations

import bisect
import glob
import os
import re
from typing import Any, Dict, Iterable, List, Optional, Sequence, Tuple

Interval = Tuple[float, float]            # (start_s, end_s)
Event = Tuple[str, float, float]          # (name, start_s, duration_s)

DEVICE_PLANE = re.compile(r"^/device:TPU:\d+$")
#: A TPU plane's lines (seen on a v5e, jax 0.9.0): the operations of the
#: programs, their asynchronous halves (copy-start, slice-start: DMA that runs
#: beside them), and one event per program execution. Transfers to and from
#: the host are on no device line.
OPS_LINE, ASYNC_LINE, MODULES_LINE = "XLA Ops", "Async XLA Ops", "XLA Modules"
MARK_PREFIX = "bench:"


def newest_xplane(trace_dir: str) -> Optional[str]:
    found = glob.glob(os.path.join(trace_dir, "plugins", "profile", "*",
                                   "*.xplane.pb"))
    return max(found, key=os.path.getmtime) if found else None


def read(path: str) -> Dict[str, Any]:
    """Stage 1: the plain document of one ``.xplane.pb``."""
    from jax.profiler import ProfileData

    data = ProfileData.from_file(path)
    doc: Dict[str, Any] = {"devices": {}, "marks": []}
    for plane in data.planes:
        lines = list(plane.lines)
        if DEVICE_PLANE.match(plane.name):
            dev = doc["devices"].setdefault(
                plane.name, {OPS_LINE: [], ASYNC_LINE: [], MODULES_LINE: []})
            for ln in lines:
                if ln.name in dev:
                    dev[ln.name] += [
                        (op_name(ev.name), ev.start_ns * 1e-9,
                         ev.duration_ns * 1e-9) for ev in ln.events]
        elif plane.name.startswith("/host:"):
            for ln in lines:
                doc["marks"] += [
                    (ev.name, ev.start_ns * 1e-9, ev.duration_ns * 1e-9)
                    for ev in ln.events if ev.name.startswith(MARK_PREFIX)]
    for dev in doc["devices"].values():
        for events in dev.values():
            events.sort(key=lambda e: e[1])
    doc["marks"].sort(key=lambda e: e[1])
    return doc


def mark_time(doc: Dict[str, Any], name: str) -> Optional[float]:
    """Start of the first ``bench:<name>`` annotation, on the trace's clock."""
    for ev_name, start, _dur in doc["marks"]:
        if ev_name == MARK_PREFIX + name:
            return start
    return None


# -- intervals -----------------------------------------------------------------


def clip(events: Iterable[Event], lo: float, hi: float) -> List[Event]:
    """The part of every event that lies inside [lo, hi]."""
    out = []
    for name, start, dur in events:
        s, e = max(start, lo), min(start + dur, hi)
        if e > s:
            out.append((name, s, e - s))
    return out


def union(intervals: Iterable[Interval]) -> List[Interval]:
    merged: List[Interval] = []
    for s, e in sorted(intervals):
        if merged and s <= merged[-1][1]:
            if e > merged[-1][1]:
                merged[-1] = (merged[-1][0], e)
        else:
            merged.append((s, e))
    return merged


def busy_intervals(events: Iterable[Event]) -> List[Interval]:
    return union((s, s + d) for _n, s, d in events)


def total(intervals: Iterable[Interval]) -> float:
    return sum(e - s for s, e in intervals)


def gaps(busy: Sequence[Interval], lo: float, hi: float) -> List[Interval]:
    """The complement of ``busy`` (merged, sorted) inside [lo, hi]."""
    out, at = [], lo
    for s, e in busy:
        if s > at:
            out.append((at, min(s, hi)))
        at = max(at, e)
        if at >= hi:
            break
    if at < hi:
        out.append((at, hi))
    return out


def _ranked(acc: Dict[str, float], top: int) -> List[List[Any]]:
    ranked = sorted(acc.items(), key=lambda kv: (-kv[1], kv[0]))
    return [[n, t] for n, t in ranked[:top]]


def by_name(events: Iterable[Event], top: int = 10) -> List[List[Any]]:
    """[[name, seconds], ...] summed per name, largest first."""
    acc: Dict[str, float] = {}
    for name, _s, dur in events:
        acc[name] = acc.get(name, 0.0) + dur
    return _ranked(acc, top)


def op_name(name: str) -> str:
    """``%fusion.5 = s32[16384]{...} fusion(...)`` -> ``fusion.5``: on a TPU an
    operation's event carries its whole HLO text."""
    return name.split(" = ", 1)[0].lstrip("%")


def program_of(name: str) -> str:
    """``jit_step(1234567890)`` -> ``jit_step``: the fingerprint a module's
    event carries changes with every compile."""
    return re.sub(r"\(\d+\)$", "", name)


def label_ops(ops: Sequence[Event], modules: Sequence[Event]) -> List[Event]:
    """Each operation named ``<program>/<op>`` by the module execution that
    contains its start (``?`` when none does). Both sorted by start."""
    starts = [m[1] for m in modules]
    out = []
    for name, start, dur in ops:
        i = bisect.bisect_right(starts, start) - 1
        prog = "?"
        if i >= 0 and start < modules[i][1] + modules[i][2]:
            prog = program_of(modules[i][0])
        out.append((f"{prog}/{name}", start, dur))
    return out


def attribute(idle: Sequence[Interval], host: Sequence[Event],
              other: str = "other", top: int = 10) -> List[List[Any]]:
    """Idle seconds by what the host was doing: every gap is cut by the host's
    spans (which must not overlap each other); what no span covers is
    ``other``. [[name, seconds], ...], largest first."""
    spans = sorted(host, key=lambda e: e[1])
    starts = [s[1] for s in spans]
    acc: Dict[str, float] = {}
    for g_lo, g_hi in idle:
        covered = 0.0
        i = max(bisect.bisect_right(starts, g_lo) - 1, 0)
        while i < len(spans) and spans[i][1] < g_hi:
            name, s, d = spans[i]
            part = min(s + d, g_hi) - max(s, g_lo)
            if part > 0:
                acc[name] = acc.get(name, 0.0) + part
                covered += part
            i += 1
        rest = (g_hi - g_lo) - covered
        if rest > 0:
            acc[other] = acc.get(other, 0.0) + rest
    return _ranked(acc, top)


# -- the reduction the harness prints ------------------------------------------


def reduce(doc: Dict[str, Any], lo: float, hi: float,
           host: Sequence[Event] = ()) -> Dict[str, Any]:
    """Busy seconds (averaged over the device planes that ran anything),
    the traced window's length, the operations that took most time, the idle
    seconds by host activity, and the per-program executions — all inside
    [lo, hi] on the trace's clock. ``host`` spans are on the same clock."""
    def all_ops(dev):
        return sorted(dev[OPS_LINE] + dev.get(ASYNC_LINE, []),
                      key=lambda e: e[1])

    used = {name: dev for name, dev in doc["devices"].items()
            if clip(all_ops(dev), lo, hi)}
    out: Dict[str, Any] = {"window_s": hi - lo, "devices_used": len(used),
                           "busy_s": 0.0, "device_ops": [], "idle_gaps": [],
                           "programs": {}, "module_runs": 0}
    if not used:
        return out
    labelled: List[Event] = []
    idle: List[Interval] = []
    for dev in used.values():
        ops = clip(all_ops(dev), lo, hi)
        busy = busy_intervals(ops)
        out["busy_s"] += total(busy) / len(used)
        idle += gaps(busy, lo, hi)
        mods = clip(dev[MODULES_LINE], lo, hi)
        named = label_ops(ops, dev[MODULES_LINE])
        labelled += named
        out["module_runs"] += len(mods)
        for name, _s, dur in mods:
            prog = out["programs"].setdefault(
                program_of(name), {"runs": 0, "seconds": 0.0, "ops": []})
            prog["runs"] += 1
            prog["seconds"] += dur
        for label in {n for n, _s, _d in named}:
            prog_name, _, op = label.partition("/")
            if prog_name in out["programs"]:
                out["programs"][prog_name]["ops"].append(op)
    for prog in out["programs"].values():
        prog["ops"] = sorted(set(prog["ops"]))
    out["device_ops"] = by_name(labelled)
    scale = 1.0 / len(used)
    out["idle_gaps"] = [[n, t * scale]
                        for n, t in attribute(idle, clip(host, lo, hi))]
    return out


def summarize(path: str, top: int = 8) -> Dict[str, Any]:
    """Every plane and line of a trace with its event count and most frequent
    names — for looking at a new trace by hand before trusting :func:`read`."""
    from jax.profiler import ProfileData

    out = {}
    for plane in ProfileData.from_file(path).planes:
        lines = {}
        for ln in plane.lines:
            counts: Dict[str, int] = {}
            n = 0
            for ev in ln.events:
                n += 1
                counts[ev.name] = counts.get(ev.name, 0) + 1
            common = sorted(counts.items(), key=lambda kv: -kv[1])[:top]
            lines[ln.name] = {"events": n, "common": common}
        out[plane.name] = lines
    return out
