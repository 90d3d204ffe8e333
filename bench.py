"""Headline benchmark — continuous kNN (k=50) over 1M-point sliding windows.

The BASELINE.md north-star metric: points/sec/chip + p50 window latency on
continuous kNN, k=50, 1M-point windows, Beijing-extent stream, vs the
single-node CPU reference. The reference publishes no numbers; its own
benchmark harness is configured for a 20,000 events/sec single-node target
(BenchmarkRunner.java:25-26, InstrumentedMN_Q1.java:88-89), so
``vs_baseline`` = measured points/sec/chip ÷ 20,000.

The measured program is the pane-carry sliding-window pipeline in its
TPU-first form (ops/knn.py):

  6 B/pt wire record (uint16 grid-relative coords + int16 interned oid,
  streams/wire.py — device upcast bit-exact) → top-``cand``-compacted
  pane digest (``knn_pane_digest_compact``: radius-masked distances →
  lax.top_k → tiny segment-min scatters; automatic exact fallback) →
  window merge + top-50. One transfer and ONE dispatch per slide.

TWO throughputs in the single JSON line:

- ``value`` (points/s, e2e): host slide → wire transfer → digest+merge →
  pipelined result fetch — bounded by the host↔device link at 6 B/pt
  when the chip outruns it.
- ``device_resident_points_per_sec``: same wire records staged in HBM
  once, same digest+merge per window inside one compiled scan per pass,
  passes chained through the carried digest, EVERY window's full top-50
  result kept live and fetched. The chip's own sustained rate on the
  flagship kernel — compare against the measured XLA:CPU in-RAM figure
  (CPU_BASELINE.json, regenerated with this same program).

Prints ONE JSON line: {"metric", "value", "unit", "vs_baseline", ...}.
"""

from __future__ import annotations

import functools
import json
import sys
import time

import numpy as np


WINDOW = 1_000_000
SLIDE = WINDOW // 2
N_WINDOWS = 20
K = 50
NUM_SEGMENTS = 16_384  # distinct objIDs
RADIUS = 0.05
CAND = 8_192  # top-k compaction width (exact fallback above this)
BASELINE_EPS = 20_000.0


def build_headline_step(jnp, wf, slide=SLIDE, k=K, nseg=NUM_SEGMENTS,
                        radius=RADIUS, cand=CAND, pallas=False):
    """The headline program, shared verbatim with the CPU-baseline run
    (bench_suite.bench_headline_knn_1m) AND the shipped operator path
    (operators/knn_query.py:run_wire_panes): one slide of packed wire
    records + the carried digest → (new digest, window KnnResult).

    The wire→digest step itself lives in ops/wire_knn.py — ONE program
    for operator, bench, and suite (the measured and shipped programs
    must never diverge). This wrapper adds only the
    2-pane window merge and bakes the statics.

    ``wire_s``: (3, slide) uint16 PLANE-MAJOR rows — x_q, y_q, oid (int16
    bits). Returns a raw fn for jax.jit / lax.scan embedding.

    ``pallas=True`` (TPU): the fused Pallas extraction with the
    IN-PROGRAM ``lax.cond`` overflow fallback — exact either way;
    main() self-checks one slide against the XLA step before trusting
    the lowering (ops/wire_knn.py:digests_agree).
    """
    from spatialflink_tpu.ops.knn import knn_merge_digest_list
    from spatialflink_tpu.ops.wire_knn import make_wire_digest_step

    bases = np.asarray([0, slide], np.int32)
    scale = jnp.asarray(np.asarray(wf.scale, np.float32))
    origin = jnp.asarray(np.asarray(wf.origin, np.float32))
    r32 = np.float32(radius)
    digest = make_wire_digest_step(
        num_segments=nseg, cand=cand,
        strategy="pallas" if pallas else "xla",
    )

    def step(seg_prev, rep_prev, wire_s, query_xy):
        d = digest(wire_s, wire_s.shape[1], query_xy, scale, origin, r32)
        res = knn_merge_digest_list(
            (seg_prev, d.seg_min), (rep_prev, d.rep), bases, k=k
        )
        return d.seg_min, d.rep, res

    return step


_ERROR_RECORD = {
    "metric": "continuous_knn_k50_1M_window_points_per_sec_per_chip",
    "value": 0,
    "unit": "points/s",
    "vs_baseline": 0,
}


def _seal_stream_raw(reason: str, sealed_by: str = "watchdog") -> None:
    """Failure-path ledger-stream seal WITHOUT telemetry/jax.

    Telemetry owns the stream, but on the dial-timeout path (main's
    watchdog) jax may be wedged in an uninterruptible C call, so the
    seal must not go through it. The stream is plain JSONL, so the
    sealing epilogue can be appended directly, turning an abandoned
    stream into an attributable artifact (``sfprof recover`` reports the
    termination reason instead of guessing). Skips cleanly when no
    stream was configured/created or telemetry already sealed it."""
    import os
    import time

    path = os.environ.get("SFT_LEDGER_STREAM")
    if not path or not os.path.exists(path):
        return
    try:
        # Tail big enough to hold any single record (epilogues carry the
        # bench record + SLO verdict; checkpoints the kernel table) — a
        # 4 KiB peek once started MID-epilogue and double-sealed.
        with open(path, "rb") as f:
            f.seek(0, os.SEEK_END)
            size = f.tell()
            f.seek(max(size - (4 << 20), 0))
            tail = f.read()
        # Walk complete tail lines newest-first; the first one that
        # parses tells us whether the stream is already sealed (the
        # chunk boundary may cut the oldest line — parse failures there
        # are expected and skipped).
        for line in reversed(tail.splitlines()):
            if not line.strip():
                continue
            try:
                rec = json.loads(line)
            except ValueError:
                continue  # half-written tail / chunk-boundary fragment
            if isinstance(rec, dict) and rec.get("t") == "epilogue":
                return  # already sealed
            break  # newest parseable record is not an epilogue: seal
        with open(path, "ab") as f:
            lead = b"" if tail.endswith(b"\n") or not tail else b"\n"
            # The leading newline terminates a half-written last line so
            # the epilogue starts on its own line (recover scans past
            # the corrupt fragment and still honors this seal).
            f.write(lead + json.dumps({
                "t": "epilogue", "unix": time.time(),
                "reason": str(reason), "sealed_by": str(sealed_by),
            }).encode() + b"\n")
    except OSError as e:  # pragma: no cover - fs trouble is non-fatal
        sys.stderr.write(f"ledger stream not sealed: {e}\n")


def main() -> None:
    global WINDOW, SLIDE, N_WINDOWS, NUM_SEGMENTS, RADIUS, CAND
    import os as _os
    import threading

    # Dial watchdogs: a chip held by another process (or a wedged
    # runtime) can hang EITHER backend initialisation OR the first real
    # device op after a seemingly healthy init, both inside C calls no
    # exception leaves. TWO bounded phases, each under
    # SFT_DIAL_DEADLINE_S (default 180 s): phase 1 covers import jax →
    # device discovery; phase 2 re-arms just before the warm-up step
    # and covers the first ship + compile + fetch. Host-side work in
    # between — stream generation, packing — is deliberately OUTSIDE
    # both windows: it cannot hang on the device and must not eat the
    # budget. On timeout the watchdog seals the ledger stream with
    # reason ``dial_timeout`` (plain JSONL append — jax is wedged,
    # telemetry must not be asked to flush through it), prints the
    # honest one-line record (value 0), and exits 3.
    _dial_deadline = float(_os.environ.get("SFT_DIAL_DEADLINE_S", "180"))

    def _arm_dial_watchdog(label: str) -> threading.Event:
        ok = threading.Event()

        def _watchdog():
            if not ok.wait(_dial_deadline):
                if ok.is_set():  # lost the race at the boundary
                    return
                _seal_stream_raw("dial_timeout")
                print(json.dumps({
                    **_ERROR_RECORD,
                    "error": f"device unreachable ({label} hang "
                             f"> {float(_dial_deadline):.0f} s; "
                             "SFT_DIAL_DEADLINE_S)",
                }))
                sys.stdout.flush()
                _os._exit(3)

        threading.Thread(target=_watchdog, daemon=True).start()
        return ok

    _init_ok = _arm_dial_watchdog("backend initialisation")

    import jax
    import jax.numpy as jnp

    from spatialflink_tpu.grid import UniformGrid
    from spatialflink_tpu.streams.wire import WireFormat

    from __graft_entry__ import BEIJING_GRID_ARGS, QUERY_POINT

    from spatialflink_tpu.runtime import on_tpu

    dev = jax.devices()[0]
    _init_ok.set()  # phase 1 done: the backend answered. Device
    # DISCOVERY succeeding does not prove the device can move bytes —
    # phase 2 below re-arms around the first real device op.

    smoke = bool(_os.environ.get("SFT_BENCH_SMOKE"))
    if smoke:
        # Contract-test preset (tests/test_bench_contract.py): the SAME
        # program at toy sizes — window stays 2× slide, density/radius
        # chosen so every window still fills its top-50 — runnable on
        # XLA:CPU in seconds, and marked ``smoke`` in its record.
        WINDOW, SLIDE, N_WINDOWS = 4_096, 2_048, 8
        NUM_SEGMENTS, RADIUS, CAND = 512, 0.5, 256
    elif not on_tpu():
        # The headline is a chip measurement: a missing TPU is an error,
        # never a CPU number under the chip metric's name.
        print(json.dumps({
            **_ERROR_RECORD,
            "error": f"no TPU: JAX's default backend is "
                     f"{jax.default_backend()!r} ({dev})",
        }))
        sys.exit(3)

    from spatialflink_tpu.telemetry import (
        LinkProbe,
        instrument_jit,
        telemetry,
    )

    # Runtime telemetry rides the measured run: recompile detection on the
    # jitted steps, host→device bytes at the staging device_puts,
    # device→host bytes + true-sync timing at the fetches the loops
    # already do (zero extra round trips), window latency from the
    # latency-probe spans. Summary lands in the JSON line's "telemetry"
    # block; SFT_TRACE_PATH additionally captures a Chrome-trace file;
    # SFT_LEDGER_STREAM makes the capture incrementally durable (JSONL
    # checkpoints at window/phase boundaries — a SIGKILL mid-run loses at
    # most one flush interval; `sfprof recover` rebuilds the ledger).
    telemetry.enable(
        trace_path=_os.environ.get("SFT_TRACE_PATH"),
        stream_path=_os.environ.get("SFT_LEDGER_STREAM"),
    )

    # Live SLO gating (SFT_SLO_SPEC=<spec.json>): the declarative spec is
    # evaluated incrementally as probe windows fire; violations become
    # slo_violation:* events in the trace/stream and the verdict block
    # rides the record + ledger. `sfprof health --slo` applies the SAME
    # spec post-hoc.
    slo_engine = None
    _spec_path = _os.environ.get("SFT_SLO_SPEC")
    if _spec_path:
        from spatialflink_tpu import slo as slo_mod

        slo_engine = slo_mod.install(
            slo_mod.SloEngine(slo_mod.SloSpec.from_file(_spec_path))
        )

    # Overload control (SFT_OVERLOAD_POLICY=<inline JSON | policy.json>):
    # installs the process-global controller so chip captures get the
    # degradation ladder (SLO violations step it down), the counters
    # ride snapshot()["overload"] into the record/ledger/stream, and a
    # shed_budget/degraded_window_budget spec can gate the run.
    overload_ctrl = None
    _ov_spec = _os.environ.get("SFT_OVERLOAD_POLICY")
    if _ov_spec:
        from spatialflink_tpu import overload as overload_mod

        overload_ctrl = overload_mod.install(
            overload_mod.OverloadController(
                overload_mod.OverloadPolicy.from_env(_ov_spec)
            )
        )

    grid = UniformGrid(**BEIJING_GRID_ARGS)
    wf = WireFormat.for_grid(grid)
    q = np.asarray(QUERY_POINT, np.float32)

    # Synthetic Beijing stream packed in the 6 B/pt wire format: one
    # contiguous (n, 3) uint16 record stream (quantized coords ~3.2e-5°
    # lattice ≈ 3.6 m — beneath GPS accuracy, upcast bit-exact per
    # tests/test_wire.py; int16 interned oid). ONE transfer per slide.
    rng = np.random.default_rng(42)
    total = SLIDE * (N_WINDOWS - 1) + WINDOW
    xyq = wf.quantize(np.stack(
        [rng.uniform(115.5, 117.6, total), rng.uniform(39.6, 41.1, total)],
        axis=1,
    ))
    oid16 = (rng.integers(0, NUM_SEGMENTS, total)).astype(np.int16)
    wire = np.concatenate([xyq, oid16.view(np.uint16)[:, None]], axis=1)

    step = build_headline_step(jnp, wf, slide=SLIDE, nseg=NUM_SEGMENTS,
                               radius=RADIUS, cand=CAND)
    jstep = instrument_jit(jax.jit(step), name="headline_step")
    # Throughput loops donate the carried digest buffers: without
    # donation every dispatch materializes fresh (nseg,) seg/rep outputs
    # and the runtime schedules carry copies (~230 ms per 100 steps in
    # the round-3 profiler trace, BASELINE.md). Donated inputs are dead
    # after the call, so resets re-copy seg0/rep0 device-side.
    jstep_d = instrument_jit(
        jax.jit(step, donate_argnums=(0, 1)), name="headline_step_donated"
    )
    jcopy = jax.jit(lambda a: a.copy())
    q_d = jax.device_put(jnp.asarray(q), dev)
    big = np.float32(np.finfo(np.float32).max)
    empty_seg = jax.device_put(
        jnp.full((NUM_SEGMENTS,), big, jnp.float32), dev
    )
    empty_rep = jax.device_put(
        jnp.full((NUM_SEGMENTS,), np.iinfo(np.int32).max, jnp.int32), dev
    )

    def slide_wire(i):
        # plane-major (3, SLIDE) — see build_headline_step's layout note
        host = np.ascontiguousarray(wire[i * SLIDE:(i + 1) * SLIDE].T)
        telemetry.account_h2d(host.nbytes)
        return jax.device_put(host, dev)

    # Phase 2: the first device op (ship + compile + fetch) under its
    # own fresh dial deadline — host data generation above is excluded,
    # it cannot hang on the device.
    _first_op_ok = _arm_dial_watchdog("first device op")
    _dial_hang = _os.environ.get("SFT_BENCH_DIAL_HANG")
    if _dial_hang:
        # Contract-test hook: simulate the first device op hanging
        # (device discovery succeeded, bytes don't move) so the dial
        # watchdog's seal/record path can be pinned without a device
        # (tests/test_bench_contract.py).
        time.sleep(float(_dial_hang))

    # Warm-up (compile) + slide-0 digest (its ingest precedes window 0).
    seg0, rep0, warm = jstep(empty_seg, empty_rep, slide_wire(0), q_d)
    jax.device_get(warm.num_valid)  # sync: the result is on the host
    _first_op_ok.set()  # bytes moved to the device and back — disarmed

    # Link-health probe: tiny fixed-shape round trips at PHASE BOUNDARIES
    # only (never inside a window span), so "chip slow" and "link
    # degraded" are distinguishable in the record — the gauges land in
    # the telemetry snapshot and the JSON line's "link_probe" block, and
    # `sfprof diff` annotates (never widens) its bands with them.
    probe = None
    if not _os.environ.get("SFT_NO_LINK_PROBE"):
        probe = LinkProbe(dev)
        probe.sample()
    # Phase boundary: warm-up done — checkpoint the ledger stream now so
    # a crash during the throughput loops already has a recoverable
    # prefix (the SIGKILL chaos test kills right after this point).
    telemetry.maybe_flush_stream(force=True)

    import contextlib
    import os as _os

    # Fused Pallas digest selection (TPU only): self-check one slide
    # against the XLA step — the in-radius SET must match exactly,
    # distances within 1 ulp (Mosaic vs XLA FMA freedom) — then the
    # throughput loops run the fused step (exactness is in-program via
    # its lax.cond fallback). A numerical disagreement stays on the
    # exact XLA step and says so; a lowering or compile error is a
    # kernel defect and propagates.
    step_kind = "xla"
    if on_tpu() and not _os.environ.get("SFT_NO_PALLAS_DIGEST"):
        from spatialflink_tpu.ops.wire_knn import digests_agree

        pstep = build_headline_step(jnp, wf, slide=SLIDE,
                                    nseg=NUM_SEGMENTS, radius=RADIUS,
                                    cand=CAND, pallas=True)
        jp = instrument_jit(jax.jit(pstep), name="headline_step_pallas")
        s_p, r_p, res_p = jp(empty_seg, empty_rep, slide_wire(0), q_d)
        if digests_agree(s_p, r_p, seg0, rep0):
            step = pstep
            jstep = jp
            jstep_d = instrument_jit(
                jax.jit(pstep, donate_argnums=(0, 1)),
                name="headline_step_pallas_donated",
            )
            seg0, rep0 = s_p, r_p  # slide-0 digest from the same step
            step_kind = "pallas"
        else:
            sys.stderr.write(
                "pallas digest self-check FAILED against the XLA step "
                "on slide 0 — staying on XLA\n")

    # Kernel-level tracing hook (the SURVEY §5 "jax.profiler traces"
    # analog of the reference's Flink metric operators): set
    # SFT_PROFILE_DIR=<dir> to capture an XLA/runtime trace of the
    # measured loop (view with tensorboard or xprof).

    profile_dir = _os.environ.get("SFT_PROFILE_DIR")
    trace_ctx = (
        jax.profiler.trace(profile_dir)
        if profile_dir
        else contextlib.nullcontext()
    )

    # Throughput loop: fully pipelined through the shared ingest
    # executor (spatialflink_tpu/pipeline.py — the promoted form of the
    # hand-rolled slide double-buffering this loop used to carry): one
    # transfer + one dispatch per slide, window results collected as
    # in-flight handles and materialized once at the end-of-run drain
    # (a per-window fetch would drain the pipeline every slide —
    # fetch_lag=N_WINDOWS keeps every fetch in the single final drain).
    # depth counts the in-compute item (pipeline.py), so depth=3
    # reproduces the old loop's cadence exactly: TWO slides staged
    # beyond the one being computed. The loop runs 5 times and the
    # MEDIAN rate is reported.
    from spatialflink_tpu.pipeline import PipelinedExecutor, PipelinePolicy
    from spatialflink_tpu import pipeline as pipeline_mod

    throughput_pol = PipelinePolicy(depth=3, fetch_lag=N_WINDOWS)

    def timed_run():
        # Re-seed from slide 0's digest outside the timed region:
        # carrying the previous run's final slide into window 0 would
        # merge non-adjacent panes. Copies, not aliases — jstep_d
        # donates its carry inputs (the executor hands each shipped
        # slide to exactly one compute, so donation never aliases an
        # in-flight transfer).
        st = {"sp": jcopy(seg0), "rp": jcopy(rep0)}

        def compute(w, wire_d):
            st["sp"], st["rp"], res = jstep_d(st["sp"], st["rp"],
                                              wire_d, q_d)
            return res.num_valid

        ex = PipelinedExecutor(
            throughput_pol, ship=slide_wire, compute=compute,
            fetch=telemetry.fetch, label="headline", node="headline",
        )
        t0 = time.perf_counter()
        results = [int(v) for v in ex.run(range(1, N_WINDOWS + 1))]
        return time.perf_counter() - t0, results

    if slo_engine is not None:
        # Start the engine's EPS clock NOW: the first real feed happens
        # after run 1 completes, and crediting run 1's points without
        # run 1's elapsed time would inflate live EPS ~25% (an
        # eps_floor gate that under-gates is worse than none).
        slo_engine.observe_window(0)
    with trace_ctx:
        runs = []
        for _ in range(5):
            runs.append(timed_run())
            # Between timed runs = a phase boundary: probe the link and
            # feed the SLO engine the windows that just fired (outside
            # the timed region — the engine's counters are host-cheap
            # but the EPS floor must see real points).
            if probe is not None:
                probe.sample()
            if slo_engine is not None:
                for _ in range(N_WINDOWS):
                    slo_engine.observe_window(SLIDE, lag_ms=0.0)
    telemetry.maybe_flush_stream(force=True)
    t_total = float(np.median([t for t, _ in runs]))
    results = runs[-1][1]

    # Latency probe: window-close → answer-on-host, measured synchronously
    # on pre-staged slides (in a live stream the slide's events finished
    # transferring during the window interval; what remains at window
    # close is digest + merge + result fetch).
    latencies = []
    sp, rp = seg0, rep0
    # Fresh trace-flush budget: the throughput loop above may have pushed
    # the buffered writer near its FLUSH_EVERY boundary, and the probe's
    # ~4 emits per window must never trip a synchronous disk flush inside
    # the timed region.
    telemetry.flush_trace()
    for w in range(5):
        wire_s = slide_wire(w + 1)
        jax.device_get(wire_s[:1])  # staged before window close
        t0 = time.perf_counter()
        # window.* span → FixedBucketLatency → telemetry p50/p95. The
        # timed region holds dispatch + the true-sync device_get (the
        # probe's own fetch), wrapped in compute/fetch child spans so
        # the run ledger attributes the probe's phases (tools/sfprof):
        # their buffered span emits cost ~µs against ms-scale windows.
        # The heavier telemetry
        # work — d2h accounting (a counter-event trace write) and the
        # window span-exit write — happens after the clock stops, and
        # OUTSIDE the window span so it lands in the inter-window host
        # gap, not in the window's unattributed residue.
        with telemetry.span("window.headline", window=w):
            with telemetry.span("compute"):
                sp, rp, res = jstep(sp, rp, wire_s, q_d)
            with telemetry.span("fetch"):
                nv = jax.device_get(res.num_valid)
                latencies.append(time.perf_counter() - t0)
        telemetry.account_d2h(np.asarray(nv).nbytes)
        if slo_engine is not None:
            # Outside the window span, after the clock stopped: the
            # bench's synthetic stream is in order, so lag is 0 — the
            # engine still sees every probe window for its EPS/budget
            # checks.
            slo_engine.observe_window(SLIDE, lag_ms=0.0)
    if probe is not None:
        probe.sample()  # phase boundary: latency probe done
    telemetry.maybe_flush_stream(force=True)

    # ---- Overlap proof: the pipelined ingest runtime, span-visible. ----
    # The latency probe above is the SYNCHRONOUS cadence: ship lands
    # BETWEEN window.headline spans, so ingest is attributed host gap.
    # This probe runs the same windows through the executor with spans
    # on (window.pipeline) and the delta-bitpacked codec on the wire:
    # ship rides INSIDE the window spans and pane bytes shrink, so the
    # run ledger itself proves the overlap (sfprof host-gap detection —
    # the SFT_BENCH_SMOKE contract asserts pipelined gaps < sync gaps)
    # and carries the compression gauges (record: wire_bytes vs
    # raw_bytes). Results must stay exact: every probe window still
    # fills its top-50.
    from spatialflink_tpu.ops import wire_codec as wc

    overlap_pol = PipelinePolicy(depth=2, fetch_lag=2, codec="delta")
    n_probe = min(6, N_WINDOWS)
    codec_enc = wc.WirePaneEncoder(NUM_SEGMENTS)
    codec_dec = {
        # COPIES: XLA:CPU zero-copy-aliases host buffers, and the
        # encoder mutates its tables in place per pane (see
        # run_wire_panes' pipelined branch for the full note).
        "px": jax.device_put(codec_enc.pred_x.copy(), dev),
        "py": jax.device_put(codec_enc.pred_y.copy(), dev),
    }
    # ONE jit instance: the pane capacity (SLIDE) is static, the word
    # bucket just retraces — at most ladder-many compiled shapes. The
    # predictor tables are NOT donated (the multi-executable px chain
    # corrupts under XLA:CPU donation — see run_wire_panes'
    # decode_step note; retraced word buckets = multiple executables
    # here too).
    jdecode = instrument_jit(
        jax.jit(functools.partial(
            wc.decode_wire_pane, n=SLIDE, num_segments=NUM_SEGMENTS,
        )),
        name="wire_pane_decode",
    )
    pst = {"sp": jcopy(seg0), "rp": jcopy(rep0)}

    def probe_ship(w):
        host = np.ascontiguousarray(wire[w * SLIDE:(w + 1) * SLIDE].T)
        enc = codec_enc.encode(host)
        wb = wc.wire_word_bucket(len(enc.words), SLIDE)
        # Charge the padded bucket — what actually ships (h2d agrees).
        telemetry.account_wire(enc.raw_bytes, 4 * wb + wc.HEADER_BYTES)
        words = wc.pad_words(enc.words, wb)
        telemetry.account_h2d(words.nbytes)
        return (jax.device_put(words, dev), enc)

    def probe_compute(w, staged):
        words_d, enc = staged
        pane_d, codec_dec["px"], codec_dec["py"] = jdecode(
            words_d, jnp.int32(enc.n), jnp.int32(enc.bx),
            jnp.int32(enc.by), jnp.int32(enc.bo),
            codec_dec["px"], codec_dec["py"],
        )
        pst["sp"], pst["rp"], res = jstep_d(pst["sp"], pst["rp"],
                                            pane_d, q_d)
        return res.num_valid

    overlap_ex = PipelinedExecutor(
        overlap_pol, ship=probe_ship, compute=probe_compute,
        fetch=telemetry.fetch, label="pipeline", spans=True,
        node="headline",
    )
    pipeline_results = [
        int(v) for v in overlap_ex.run(range(1, n_probe + 1))
    ]
    assert all(v == K for v in pipeline_results), \
        f"pipelined kNN underfilled: {pipeline_results[:3]}"
    if probe is not None:
        probe.sample()  # phase boundary: overlap probe done
    telemetry.maybe_flush_stream(force=True)

    # ---- Device-resident throughput: ingest off the critical path. ----
    # Slides 1..N stay staged in HBM (60 MB of wire records); one
    # compiled scan digests every slide, merges every window, and keeps
    # each window's FULL top-50 result live (dist/segment/index/num_valid
    # all fetched — nothing is dead code). Passes chain through the
    # carried digest (a wrap-around continuous stream); one fetch at the
    # end is the only sync. This is the silicon number comparable to the
    # measured XLA:CPU in-RAM baseline.
    wire_all_host = np.ascontiguousarray(
        wire[SLIDE:].reshape(N_WINDOWS, SLIDE, 3).transpose(0, 2, 1)
    )
    telemetry.account_h2d(wire_all_host.nbytes)
    wire_all = jax.device_put(wire_all_host, dev)

    def resident_pass(seg_prev, rep_prev, wire_r):
        def body(carry, wire_s):
            sp, rp, res = step(carry[0], carry[1], wire_s, q_d)
            return (sp, rp), tuple(res)
        carry, outs = jax.lax.scan(body, (seg_prev, rep_prev), wire_r)
        return carry[0], carry[1], outs

    jresident = instrument_jit(
        jax.jit(resident_pass, donate_argnums=(0, 1)), name="resident_pass"
    )

    # Compile + force staging, then calibrate the pass count so a timed
    # run spans ~2 s (amortizes the final fetch's round trip).
    s, r, outs = jresident(jcopy(seg0), jcopy(rep0), wire_all)
    jax.device_get(outs[-1])
    t0 = time.perf_counter()
    s, r, outs = jresident(jcopy(seg0), jcopy(rep0), wire_all)
    fetched = jax.device_get(outs)
    t_pass = time.perf_counter() - t0
    resident_results = [int(v) for v in fetched[-1]]
    passes = int(np.clip(np.ceil(2.0 / max(t_pass, 1e-4)), 2, 64))

    def resident_run():
        sp, rp = jcopy(seg0), jcopy(rep0)
        handles = []
        t0 = time.perf_counter()
        for _ in range(passes):
            sp, rp, outs = jresident(sp, rp, wire_all)
            handles.append(outs)
        all_out = telemetry.fetch(handles)  # the only true sync
        return time.perf_counter() - t0, all_out

    res_runs = [resident_run() for _ in range(5)]
    if probe is not None:
        probe.sample()  # phase boundary: resident loops done
    telemetry.maybe_flush_stream(force=True)
    t_res = float(np.median([t for t, _ in res_runs]))
    resident_pps = passes * N_WINDOWS * SLIDE / t_res
    for _, all_out in res_runs[-1:]:
        for outs in all_out:
            assert all(int(v) == K for v in outs[-1]), "resident underfill"

    # Ingest rate: distinct stream points consumed per second (each point
    # is ingested once, digested once, and evaluated in 2 overlapping
    # windows via the digest merge). The timed region ingests slides
    # 1..N_WINDOWS (slide 0 precedes window 0). Comparable to the
    # reference's 20k events/sec target; window-evaluations/sec would
    # double-count the 50% overlap.
    distinct_points = SLIDE * N_WINDOWS
    points_per_sec = distinct_points / t_total
    p50_ms = float(np.percentile(latencies, 50) * 1000)
    assert all(v == K for v in results), f"kNN underfilled: {results[:3]}"
    assert all(v == K for v in resident_results), \
        f"resident kNN underfilled: {resident_results[:3]}"

    out = {
        "metric": "continuous_knn_k50_1M_window_points_per_sec_per_chip",
        "value": round(points_per_sec, 1),
        "unit": "points/s",
        "vs_baseline": round(points_per_sec / BASELINE_EPS, 2),
        "p50_window_latency_ms": round(p50_ms, 3),
        "device": str(dev),
        "windows": N_WINDOWS,
        "k": K,
        "wire_bytes_per_point": wf.bytes_per_point,
        "digest_step": step_kind,
        "device_resident_points_per_sec": round(resident_pps, 1),
        "device_resident_passes": passes,
        "device_resident_vs_baseline": round(resident_pps / BASELINE_EPS, 2),
        # Runtime-telemetry summary (telemetry.py): XLA compile count from
        # the recompile detector, device-boundary bytes both ways, window
        # latency p50/p95 from the probe spans, watermark gauges (0 here —
        # the bench's synthetic stream is in order by construction).
        "telemetry": telemetry.summary(),
    }
    # Per-node attribution table (telemetry.node_rollup — the pipelined
    # executors above run under node "headline"): rides the record AND
    # the ledger snapshot; the smoke contract below asserts the two are
    # identical (record↔ledger round trip).
    _nodes = telemetry.node_rollup()
    if _nodes:
        out["telemetry"]["nodes"] = _nodes
    # Pipelined-ingest proof block: the executor's counters (overlapped
    # vs collapsed windows, drains) + whether SFT_PIPELINE armed the
    # OPERATOR paths too (the throughput loop and overlap probe always
    # run through the executor). wire_bytes/raw_bytes are the overlap
    # probe's codec gauges: post-codec bytes actually shipped for wire
    # panes vs what the raw 6 B/pt format would have cost — the
    # uniform-random bench stream bounds the ratio near 1 + the oid
    # width win; the SNCB random-walk regime is where it pays
    # (tests/test_wire_codec.py).
    _armed_pol = pipeline_mod.policy()
    out["pipeline"] = {
        "armed": _armed_pol is not None,
        # The armed policy's codec is part of the capture's identity:
        # the trend store keys series by (pipeline, codec) arming so a
        # codec-on capture never gates against codec-off history.
        "armed_codec": _armed_pol.codec if _armed_pol is not None
        else None,
        "probe_policy": overlap_pol.to_dict(),
        "counters": telemetry.pipeline_counters(),
    }
    wg = telemetry.wire_codec_gauges()
    if wg:
        out["raw_bytes"] = wg["raw_bytes"]
        out["wire_bytes"] = wg["coded_bytes"]
        if wg["ratio"]:
            out["wire_compression_ratio"] = round(wg["ratio"], 4)
    # Measured link health at the record's phase boundaries: lets the
    # reader (and sfprof diff) separate "link degraded" from "chip
    # slow".
    link = telemetry.link_gauges()
    if link:
        out["link_probe"] = link
    if slo_engine is not None:
        out["slo"] = slo_engine.verdict()
    if overload_ctrl is not None:
        out["overload"] = overload_ctrl.snapshot()
    if smoke:
        out["smoke"] = True
    # Ablation taint (SFT_ABLATE armed at import, ablation.py): the
    # record itself says it is a profiling artifact, so the trend
    # ingester / diff gate reject it even when only the one-line record
    # (not the ledger) survives.
    from spatialflink_tpu.ablation import ablation as _ablation

    _taint = _ablation.taint_block()
    if _taint is not None:
        out["tainted"] = _taint
    # Measured CPU-backend throughput of the same fused program on this
    # host (bench_suite.py --cpu-baseline) — the measured counterpart to
    # the reference's configured 20k EPS target.
    try:
        from bench_suite import load_cpu_baseline

        cpu = load_cpu_baseline().get("continuous_knn_k50_1M_window")
        if cpu:
            out["vs_measured_cpu"] = round(points_per_sec / cpu, 2)
            out["device_resident_vs_measured_cpu"] = round(
                resident_pps / cpu, 2
            )
            # The CPU figure is the SAME program (build_headline_step) on
            # XLA:CPU with the wire records already in RAM (no ingest):
            # the honest comparator for device_resident_points_per_sec.
            # See BASELINE.md.
            out["measured_cpu_is"] = "same-program XLA:CPU in-RAM"
    except Exception:
        pass
    print(json.dumps(out))
    ledger_path = _os.environ.get("SFT_LEDGER_PATH")
    if ledger_path:
        # Run ledger (tools/sfprof): full telemetry state + this record
        # in one schema-versioned document. Written AFTER the contract
        # line is on stdout (flushed): the lazy cost capture re-pays one
        # AOT compile per signature, and a caller's time limit could
        # kill the run mid-capture — the record must already be out. A
        # ledger failure degrades to stderr.
        sys.stdout.flush()
        try:
            telemetry.write_ledger(ledger_path, bench=out)
        except Exception as e:
            sys.stderr.write(f"ledger not written: {e!r}\n")
        else:
            if smoke:
                # Contract: the per-node table printed in the record is
                # byte-for-byte the one the ledger snapshot carries —
                # nothing between the print and the ledger write may
                # touch a node bucket (cost capture is node-blind).
                with open(ledger_path) as f:
                    _doc = json.load(f)
                _rec = out["telemetry"].get("nodes") or {}
                _led = (_doc.get("snapshot") or {}).get("nodes") or {}
                if json.dumps(_rec, sort_keys=True) != json.dumps(
                        _led, sort_keys=True):
                    raise SystemExit(
                        "bench smoke: per-node table diverged between "
                        f"record ({sorted(_rec)}) and ledger "
                        f"({sorted(_led)})"
                    )
                if not _rec:
                    raise SystemExit(
                        "bench smoke: no per-node attribution in the "
                        "record (the headline executors should scope "
                        "node='headline')"
                    )
    # A run with only a stream (no SFT_LEDGER_PATH) still seals cleanly;
    # no-op when write_ledger above already sealed it.
    telemetry.seal_stream("complete", bench=out)


if __name__ == "__main__":
    sys.exit(main())
