"""One run of one cell: set up, warm up, measure for ``--seconds``, verify,
print the line.

Phases (everything before the window opens is ``setup_s``):

1. read the cell from data (``spec``), build the seeded stream in bulk and
   let the adapter start what needs no backend (``adapter.before_backend``:
   worker processes that encode the stream while the backend comes up);
2. start the backend, refuse the wrong platform, and let the adapter collect
   the encoded stream and build the system (``adapter.prepare``);
3. ``adapter.run(feed)``: the feed hands the warm-up over at flood speed until
   the warm-up's last result is out, opens the window, keeps to its schedule
   for ``--seconds`` and ends; nothing may compile inside the window;
4. outside the window: compare every result with the plain reference
   (``adapter.verify``), read the health counts, and in a traced run reduce
   the profiler's trace and call the per-layer metrics' readers;
5. print details on earlier lines and the contract's object on the last.
"""

from __future__ import annotations

import argparse
import bisect
import ctypes
import dataclasses
import json
import os
import shutil
import subprocess
import sys
import time
from typing import Any, Dict, List, Optional, Sequence, Tuple

from benchmark.harness import spec, traffic, xplane

Span = Tuple[str, float, float]  # (name, start, duration) on the feed's clock


@dataclasses.dataclass
class Trace:
    """What a per-layer metric's reader may read (``--trace 1``)."""

    cell: spec.Cell
    feed: traffic.Feed
    events: int                 # handed over between the open and the closing pull
    windows: int                # results out inside the window
    host: List[Span]            # generate / ingest / window / commit, in the window
    spans: List[Dict[str, Any]]  # the program's telemetry spans in the window
    counters: Dict[str, float]  # the program's counters, over the window
    device: Optional[Dict[str, Any]]  # xplane.reduce(); None: no device ran
    peaks: Optional[Dict[str, Any]]
    memory_peak_bytes: Optional[int]
    extras: Dict[str, Any]


def pin_allocator() -> str:
    """Take the chance out of glibc's allocator: never ``mmap`` a block, never
    trim the heap (what ``MALLOC_MMAP_THRESHOLD_`` / ``MALLOC_TRIM_THRESHOLD_``
    = 2^30 set from outside; setting either also switches off glibc's adapting
    of both to the sizes a process frees).

    Why: on the chip machine (a VM without transparent huge pages) a fresh
    8 MB array costs 8.3 ms of page faults where a copy into memory the
    process already holds costs 0.31 ms, and with the adaptive default it is
    the history of frees that decides which of the two a numpy temporary
    gets. ``knn.flood`` read 5.0-8.7 M events/s from run to run that way, 11.4-11.8 M
    pinned like this, 1.15-1.42 M with every block mapped afresh (my chip runs,
    PR 22; PERF.md section 6). A deployment sets the same two variables."""
    try:
        mallopt = ctypes.CDLL(None).mallopt
    except (OSError, AttributeError):
        return "libc has no mallopt: allocator left as it is"
    m_trim_threshold, m_mmap_threshold = -1, -3  # <malloc.h>
    ok = mallopt(m_mmap_threshold, 1 << 30) and mallopt(m_trim_threshold,
                                                        1 << 30)
    return ("mmap_threshold = trim_threshold = 2^30" if ok
            else "mallopt refused: allocator left as it is")


def _say(**detail) -> None:
    print(json.dumps({"detail": detail}), flush=True)


def _fail(msg: str, code: int = 2) -> int:
    sys.stderr.write(f"benchmark: {msg}\n")
    return code


def host_timeline(feed: traffic.Feed, named: Sequence[Span]) -> List[Span]:
    """The host's time between the open and the closing pull, every second of
    it named: ``generate`` (the system waited for a release), the adapter's
    ``named`` spans, and ``ingest`` for the rest of each stretch between a
    hand-over and the next pull."""
    out = [s for s in named if s[2] > 0]
    taken = xplane.busy_intervals(out)
    starts = [s for s, _e in taken]
    asked_next = [p[1] for p in feed.pulls[1:]] + [feed.t_closed]
    for (_lo, asked, handed, _w), nxt in zip(feed.pulls, asked_next):
        if handed > asked:
            out.append(("generate", asked, handed - asked))
        # a named span lies inside one stretch between a hand-over and a pull
        mine = taken[bisect.bisect_left(starts, handed):
                     bisect.bisect_left(starts, nxt)]
        out += [("ingest", s, e - s) for s, e in xplane.gaps(mine, handed, nxt)]
    return sorted(out, key=lambda s: s[1])


def _counters(telemetry) -> Dict[str, float]:
    return {
        "h2d_bytes": telemetry.h2d_bytes,
        "h2d_transfers": telemetry.h2d_transfers,
        "d2h_bytes": telemetry.d2h_bytes,
        "d2h_transfers": telemetry.d2h_transfers,
        "kernel_calls": sum(r["calls"] for r in telemetry.kernel_table()),
    }


def _memory_peak(devices) -> Optional[int]:
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use") for d in devices]
    peaks = [p for p in peaks if p is not None]
    return int(max(peaks)) if peaks else None


def _rehearse_all(args) -> int:
    """Every cell at toy size, each in a process of its own (this one never
    touches JAX)."""
    rc = 0
    for name in spec.cell_names():
        for trace in (0, 1):
            cmd = [sys.executable, os.path.join(spec.BENCH_DIR, "run.py"),
                   "--workload", name, "--seed", str(args.seed), "--seconds",
                   str(args.seconds), "--trace", str(trace), "--rehearsal"]
            p = subprocess.run(cmd, cwd=spec.ROOT, capture_output=True,
                               text=True, timeout=1200)
            last = p.stdout.strip().splitlines()[-1] if p.stdout.strip() else ""
            print(f"{name} trace={trace} rc={p.returncode} {last[:400]}",
                  flush=True)
            if p.returncode:
                sys.stderr.write(p.stderr[-4000:])
                rc = 1
    return rc


def main(argv: Sequence[str], t_start: float) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--rehearsal", action="store_true",
                    help="toy size on the CPU backend; prints no timing "
                         "(the driver never gives this flag)")
    ap.add_argument("--keep", default=None, metavar="DIR",
                    help="traced runs: copy the .xplane.pb and a summary of "
                         "its planes to DIR (the driver never gives this flag)")
    args = ap.parse_args(argv)
    if args.workload == "all":
        if not args.rehearsal:
            return _fail("--workload all is for --rehearsal only")
        return _rehearse_all(args)
    allocator = pin_allocator()
    if args.rehearsal:
        os.environ["JAX_PLATFORMS"] = "cpu"
    # The program keeps its compile cache where this says (runtime.py honours
    # the variable): a fixed path inside the checkout.
    os.environ.setdefault("JAX_COMPILATION_CACHE_DIR",
                          os.path.join(spec.ROOT, ".jax_cache"))
    cell = spec.load_cell(args.workload)
    cfg = cell.config
    stream_cfg = traffic.effective(cfg["stream"], args.rehearsal)
    tr = traffic.effective(cell.traffic, args.rehearsal)
    windows = traffic.Windows(
        size_ms=int(cfg["window_s"] * 1000), slide_ms=int(cfg["slide_s"] * 1000),
        fire_delay_ms=int(cfg["fire_delay_ms"]), t0_ms=int(stream_cfg["t0_ms"]))
    workdir = os.path.join(spec.ROOT, ".bench_work", cell.name)
    adapter = spec.plugin("adapters", cfg["adapter"]).Adapter(
        cfg, stream_cfg, workdir, args.rehearsal)
    stream, w = traffic.build_stream(stream_cfg, tr, windows, args.seed,
                                     args.seconds, adapter.split_at_triggers)
    # An adapter may have host work that needs no backend (encoding the
    # stream in worker processes): it starts here, goes on while the backend
    # comes up and is collected in ``prepare``; ``close`` ends what a run
    # that stops before then has left of it.
    if hasattr(adapter, "before_backend"):
        adapter.before_backend(stream, windows)
    stages = {"stream_s": time.perf_counter() - t_start}
    try:
        import jax

        devices = jax.devices()
        stages["backend_s"] = time.perf_counter() - t_start - stages["stream_s"]
        platform, kind = devices[0].platform, devices[0].device_kind
        if platform != "tpu" and not args.rehearsal:
            return _fail(f"JAX found platform {platform!r}, not 'tpu': a cell "
                         "counts only on the chip (--rehearsal is the CPU "
                         "mode)")
        if len(devices) < cell.chips:
            return _fail(f"cell {cell.name} needs {cell.chips} chips, JAX "
                         f"found {len(devices)}")
        with open(os.path.join(spec.BENCH_DIR, "harness", "peaks.json")) as f:
            peaks = json.load(f).get(kind)
        if peaks is None and not args.rehearsal:
            return _fail(f"device kind {kind!r} is not in harness/peaks.json")

        from benchmark.harness.compile_clock import CompileClock

        compile_clock = CompileClock()
        shutil.rmtree(workdir, ignore_errors=True)
        os.makedirs(workdir)
        feed = traffic.Feed(
            stream, windows, tr, w, args.seconds,
            split_at_triggers=adapter.split_at_triggers,
            on_mark=getattr(adapter, "on_mark", None))
        adapter.prepare(stream, windows)
    finally:
        if hasattr(adapter, "close"):
            adapter.close()
    stages["system_s"] = time.perf_counter() - t_start - sum(stages.values())

    telemetry = None
    snaps: Dict[str, Any] = {}
    trace_dir = os.path.join(workdir, "profile")
    if args.trace:
        from spatialflink_tpu.telemetry import telemetry

        telemetry.enable()
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.host_tracer_level = 1

    def opened():
        if args.trace:
            jax.profiler.start_trace(trace_dir, profiler_options=opts)
            snaps["counters_open"] = _counters(telemetry)
            snaps["pc_open"] = time.perf_counter()
            with jax.profiler.TraceAnnotation(xplane.MARK_PREFIX + "open"):
                pass
        snaps["compile_open"] = compile_clock.mark()
        snaps["setup_s"] = time.perf_counter() - t_start

    def closed():
        snaps["compiled"] = compile_clock.since(snaps["compile_open"])
        if args.trace:
            snaps["pc_close"] = time.perf_counter()
            with jax.profiler.TraceAnnotation(xplane.MARK_PREFIX + "close"):
                pass
            snaps["counters_close"] = _counters(telemetry)

    feed.on_open.append(opened)
    feed.on_close.append(closed)
    try:
        adapter.run(feed)
    finally:
        if args.trace and "pc_open" in snaps:
            t_stop = time.perf_counter()
            jax.profiler.stop_trace()
            snaps["stop_trace_s"] = time.perf_counter() - t_stop
    if feed.t_closed is None:
        return _fail("the run ended before the window closed", 1)
    t_after = time.perf_counter()

    # -- outside the window ----------------------------------------------------
    e2e = traffic.end_to_end(feed)
    check = adapter.verify(feed)
    health = adapter.health()
    t_verified = time.perf_counter()
    compiled = snaps["compiled"]
    in_window = {k for k, _t in feed.in_window()}
    problems = list(check.get("problems", [])) + list(health["problems"])
    if compiled["programs"]:
        problems.append(f"{compiled['programs']} programs compiled or loaded "
                        "inside the window")
    wrong = check["wrong"]
    failed = len({k for k in wrong if k in in_window} | set(e2e["late"]))
    correct = not wrong and not problems
    values: Dict[str, Any] = {"setup_s": snaps["setup_s"], **e2e}
    waited = [p[3] for p in feed.pulls]
    _say(cell=cell.name, seed=args.seed, seconds=args.seconds,
         rehearsal=args.rehearsal, platform=platform, device_kind=kind,
         allocator=allocator, warmup_events=w,
         handed_in_window=feed.idx_closed - w, **_stream_room(stream, feed),
         fire_delay_ms=windows.fire_delay_ms, window_ms=windows.size_ms,
         slide_ms=windows.slide_ms, results_total=len(feed.results),
         results_in_window=e2e["results"], late=e2e["late"],
         compiled_in_window=compiled["programs"],
         compiled_total=compile_clock.programs,
         cache_misses_total=compile_clock.cache_misses,
         checked=check["checked"],
         wrong={str(k): v[:3] for k, v in list(wrong.items())[:5]},
         problems=problems[:10], health=health,
         verify={k: v for k, v in check.items()
                 if k not in ("wrong", "problems", "checked")})
    if not args.rehearsal:  # timings: a chip run's only
        _say(timings=True, latency_ms=e2e["latency_ms"],
             events_per_s=e2e.get("events_per_s"),
             events_per_s_mean=e2e.get("events_per_s_mean"),
             events_between_results=e2e.get("events_between_results"),
             seconds_between_results=e2e.get("seconds_between_results"),
             result_times_s=[t - feed.t_open for _k, t in
                             sorted(feed.in_window())],
             source_lag_ms_after_first_result=_lag_after(feed, 0),
             source_lag_ms_after_last_result=_lag_after(feed, -1),
             source_lag_ms_max=max(waited) * 1000.0
             if feed.paced and waited else None,
             compile_s_total=compile_clock.seconds,
             setup_s=snaps["setup_s"], setup_stages={
                 **stages, "warmup_s": snaps["setup_s"] - sum(stages.values())},
             window_s=feed.t_closed - feed.t_open,
             after_window_s=t_after - feed.t_closed,
             verify_s=t_verified - t_after)

    device = {"platform": platform, "kind": kind, "count": len(devices),
              "memory_peak_bytes": _memory_peak(devices)}
    line: Dict[str, Any] = {"correct": correct, "attempted": e2e["attempted"],
                            "failed": failed}
    metrics: Dict[str, Any] = {}
    if not args.trace:
        for m in cell.end_to_end:
            if values.get(m["name"]) is None:
                return _fail(f"cell {cell.name} produced no {m['name']} "
                             f"({e2e['results']} results in the window)", 1)
            metrics[m["name"]] = {"value": values[m["name"]], "unit": m["unit"]}
    else:
        trace = _reduce_trace(cell, feed, e2e, adapter, telemetry, snaps,
                              trace_dir, peaks, device, args)
        if trace.device is None and not args.rehearsal:
            return _fail("no operation ran on the device inside the traced "
                         "window", 1)
        if trace.device is not None:
            device["busy_s"] = trace.device["busy_s"]
            device["window_s"] = trace.device["window_s"]
            line["breakdown"] = {"device_ops": trace.device["device_ops"],
                                 "idle_gaps": trace.device["idle_gaps"]}
        for m in cell.per_layer:
            mf = spec.metric_file(m["name"])
            value = spec.plugin("readers", mf["reader"]).read(
                trace, **mf.get("args", {}))
            if value is not None:
                metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    if args.rehearsal:
        # A CPU run gives counts and correctness; no number of it may stand
        # under a metric's name.
        for m in metrics.values():
            m["value"] = None
        for key in ("busy_s", "window_s"):
            if key in device:
                device[key] = None
        line.pop("breakdown", None)
        line["rehearsal"] = True
    line["metrics"] = metrics
    line["device"] = device
    shutil.rmtree(workdir, ignore_errors=True)
    print(json.dumps(line), flush=True)
    return 0


def _stream_room(stream: traffic.Stream, feed: traffic.Feed) -> Dict[str, Any]:
    """How long the stream was and, for a bounded flood, the share of what it
    held past the warm-up that the window took: at 1 the run ends in
    ``SourceDry``. A pooled flood has no end and a paced stream is as long as
    its schedule, so neither has a share to give."""
    if not stream.bounded:
        return {"stream_events": None}
    room = {"stream_events": stream.n_total}
    if not feed.paced:
        room["stream_used_share"] = \
            (feed.idx_closed - feed.w) / (stream.n_total - feed.w)
    return room


def _lag_after(feed: traffic.Feed, which: int) -> Optional[float]:
    """Paced: how long the first segment handed over after the first / last
    result of the window had been released (ms) — the backlog then."""
    res = sorted(t for _k, t in feed.in_window())
    if not (feed.paced and res):
        return None
    for _lo, asked, _handed, waited in feed.pulls:
        if asked >= res[which]:
            return waited * 1000.0
    return None


def _reduce_trace(cell, feed, e2e, adapter, telemetry, snaps, trace_dir,
                  peaks, device, args) -> Trace:
    lo, hi = feed.t_open, feed.t_closed
    spans = [e for e in telemetry.events
             if e.get("ph") == "X" and lo <= e["ts"] * 1e-6 <= hi]
    host = xplane.clip(host_timeline(feed, adapter.host_spans(feed, spans)),
                       lo, hi)
    c0, c1 = snaps["counters_open"], snaps["counters_close"]
    reduced = None
    path = xplane.newest_xplane(trace_dir)
    if path is not None:
        doc = xplane.read(path)
        tr_open = xplane.mark_time(doc, "open")
        tr_close = xplane.mark_time(doc, "close")
        if tr_open is None or tr_close is None:
            raise RuntimeError("the trace lacks the harness's open/close marks")
        # The feed's clock -> the trace's clock, through the two marks.
        rate = (tr_close - tr_open) / (snaps["pc_close"] - snaps["pc_open"])
        on_trace = [(n, tr_open + (s - snaps["pc_open"]) * rate, d * rate)
                    for n, s, d in host]
        reduced = xplane.reduce(doc, tr_open, tr_close, on_trace)
        if args.keep:
            os.makedirs(args.keep, exist_ok=True)
            stem = os.path.join(args.keep, f"{cell.name}.seed{args.seed}")
            with open(stem + ".summary.json", "w") as f:
                json.dump({"summary": xplane.summarize(path),
                           "reduced": reduced}, f)
            shutil.copy(path, stem + ".xplane.pb")
        if not reduced["devices_used"]:
            reduced = None  # a trace in which nothing ran on a device
    by_name: Dict[str, float] = {}
    for n, _s, d in host:
        by_name[n] = by_name.get(n, 0.0) + d
    if not args.rehearsal:
        _say(traced=True, stop_trace_s=snaps.get("stop_trace_s"),
             xplane_bytes=os.path.getsize(path) if path else None,
             host_seconds=by_name,
             program_spans=xplane.by_name(
                 (e["name"], 0.0, e["dur"] * 1e-6) for e in spans),
             counters={k: c1[k] - c0[k] for k in c0},
             device=None if reduced is None else {
                 "programs": {n: {"runs": p["runs"], "seconds": p["seconds"]}
                              for n, p in reduced["programs"].items()},
                 **{k: reduced[k] for k in ("busy_s", "window_s",
                                            "devices_used", "module_runs")}})
    return Trace(
        cell=cell, feed=feed, events=feed.idx_closed - feed.w,
        windows=e2e["results"], host=host, spans=spans,
        counters={k: c1[k] - c0[k] for k in c0}, device=reduced, peaks=peaks,
        memory_peak_bytes=device["memory_peak_bytes"],
        extras=adapter.extras())
