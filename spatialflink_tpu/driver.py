"""Self-healing windowed-dataflow driver — ONE shared run loop.

ROADMAP item 5's named refactor: every operator used to own its run
loop (``for win in self.windows(stream): ...``), which made failure
recovery ad-hoc per operator and left nothing in charge of checkpoints
or degradation. This module lifts the loop into a single driver that
owns:

- **window iteration** over the operator's event-time assembler (object
  windows via ``_assembler()`` or SoA windows via a supplied assembler
  factory), with the checkpoint hooks ``_checkpointable_windows``
  pioneered wired in by construction;
- **auto-checkpoint cadence**: every ``checkpoint_every`` fired windows,
  the transactional sink's staged records are durably appended FIRST,
  then the operator/assembler/ingest snapshot and the sink's committed
  marker publish atomically as ONE checkpoint (checkpoint.py's framed
  format) — the exactly-once egress protocol
  (streams/sinks.py:TransactionalFileSink);
- **bounded retry-with-backoff** on transient device/ingest errors
  (``RetryPolicy``), each retry visible as a ``driver_retry`` telemetry
  instant event;
- **graceful degradation**: when retries exhaust and a ``fallback``
  window processor exists (the numpy/native route that
  ``traj_stats_sliding``/``panes.py`` already expose for the pane
  engines, and the numpy twins the range/tstats/knn operators provide),
  the driver fails over for the rest of the run — emitting a
  ``failover`` instant event and counting in ``snapshot()["driver"]``
  so `sfprof health` and the SLO engine
  (``failover_budget``/``retry_budget``) can budget it. Results must be
  identical across the switch (tests/test_driver.py asserts parity);
- **overload control** (``overload=`` — an
  :class:`spatialflink_tpu.overload.OverloadController`): bounded
  admission with backpressure/shedding on every pulled item, the
  device-path circuit breaker (whole windows to the twin while open, a
  half-open probe re-dials on a bounded schedule — the temporary
  generalization of the permanent failover above), and overload state
  published with each checkpoint so a resume replays the exact shed
  schedule. ``None`` (the default) changes nothing.

Resume contract: the driver records ``events_consumed`` in each
checkpoint; on resume with a REPLAYABLE source (file/collection — the
same record sequence again) it skips that many events and continues
mid-window from the restored assembler state. Kafka sources position by
checkpointed offsets instead (``skip_on_resume=False`` +
``extra_state`` carrying ``kafka_source_state``). Either way the
concatenated egress of kill → resume is byte-identical to an
uninterrupted run (tests/test_chaos_matrix.py, one crash per registered
injection point).

``python -m spatialflink_tpu.driver --chaos-smoke`` is the self-test:
a toy pipeline run clean, then killed by an armed ``abort`` fault
(``os._exit(137)``, the SIGKILL analog) and resumed, asserting exact
egress equality — tools/ci runs it as the chaos smoke stage.
"""

from __future__ import annotations

import contextlib
import itertools
import time
from dataclasses import dataclass
from typing import Any, Callable, Dict, Iterable, Iterator, Optional

from spatialflink_tpu.checkpoint import (
    CheckpointCorruptError,
    load_checkpoint,
    operator_state,
    restore_operator,
    save_checkpoint,
)
from spatialflink_tpu.faults import faults
from spatialflink_tpu.telemetry import telemetry


@dataclass(frozen=True)
class RetryPolicy:
    """Bounded retry-with-backoff for a failed window processor.

    ``max_retries`` EXTRA attempts after the first failure; backoff
    sleeps ``backoff_s * multiplier**attempt`` between them. Retries are
    for transient device/ingest errors (a link blip, a leader change);
    a deterministic error simply exhausts the budget fast and moves on
    to failover or the crash path.

    ``sleep`` is the injectable clock hook: ``None`` (production) means
    ``time.sleep``; tests inject a recorder so the backoff SCHEDULE is
    pinned deterministically without burning wall-clock seconds or
    monkeypatching the module's ``time`` (tests/test_driver.py).
    """

    max_retries: int = 2
    backoff_s: float = 0.05
    multiplier: float = 2.0
    sleep: Optional[Callable[[float], None]] = None

    def do_sleep(self, seconds: float) -> None:
        (self.sleep if self.sleep is not None else time.sleep)(seconds)


#: Test seam for the dial watchdog's process kill (a real timeout must
#: ``os._exit`` — jax may be wedged in an unkillable C call, so neither
#: exceptions nor atexit can be trusted to run).
def _dial_timeout_exit(code: int) -> None:
    import os

    os._exit(code)  # pragma: no cover - replaced by tests


DIAL_TIMEOUT_EXIT_CODE = 3  # the dial-failure exit code


def _seal_stream_dial_timeout(label: str) -> None:
    """Seal an armed ledger stream with reason ``dial_timeout``,
    WITHOUT ever blocking the watchdog. Normal wedge (the device): the
    hung thread is stuck inside a device call and does NOT hold
    telemetry's lock, so the seal goes through telemetry's own writer
    (appending around its buffered handle would be silently overwritten
    by the handle's next write). Host-side wedge (e.g. a dead
    filesystem mid-flush, lock held): the lock acquire is BOUNDED, and
    on timeout the epilogue appends directly to the stream file — it
    may interleave with the stuck writer's buffer, but an attributable
    tail beats an unbounded wait; the watchdog's exit must never block
    on a lock. Best-effort either way: a dying process must exit,
    sealed or not."""
    import json
    import os
    import time as _time

    got = telemetry._lock.acquire(timeout=2.0)
    try:
        if got:
            telemetry.seal_stream("dial_timeout")  # sfcheck: ok=lock-discipline -- deliberate same-RLock re-entrancy: the BOUNDED acquire above proves this watchdog thread can take telemetry's RLock without wedging, and seal_stream re-enters it on the same thread; holding it across the seal keeps the sealed-check + epilogue write atomic against a concurrently recovering writer
            return
        path = telemetry.stream_path
        if not path or not os.path.exists(path) \
                or getattr(telemetry, "_stream_sealed", False):
            return
        with open(path, "a") as f:
            f.write(json.dumps({
                "t": "epilogue", "unix": _time.time(),
                "reason": "dial_timeout",
                "sealed_by": "driver-watchdog", "label": label,
            }) + "\n")
            f.flush()
            os.fsync(f.fileno())
    except Exception:  # the seal is best-effort on a dying process
        pass
    finally:
        if got:
            telemetry._lock.release()


def resolve_dial_deadline_s(explicit=None) -> float:
    """The driver's dial budget: an explicit construction value wins,
    else ``SFT_DIAL_DEADLINE_S`` when SET (the bench convention; its
    180 s default stays bench-owned — an un-set env disables the driver
    watchdog so unit tests never race a global timer), else disabled."""
    import os

    if explicit is not None:
        return float(explicit)
    spec = os.environ.get("SFT_DIAL_DEADLINE_S")
    return float(spec) if spec else 0.0


def strict_driver() -> "WindowedDataflowDriver":
    """The driver the operators construct when the caller passes none:
    NO retries, NO failover, no checkpoint — byte-for-byte the old plain
    loop, including its error semantics (a device-path exception
    propagates immediately; nothing silently completes on the numpy
    twin). Self-healing is an OPT-IN: pass a configured
    :class:`WindowedDataflowDriver` to ``run(..., driver=...)``."""
    return WindowedDataflowDriver(
        retry=RetryPolicy(max_retries=0), failover=False,
    )


class WindowedDataflowDriver:
    """The shared run loop. Typical construction::

        driver = WindowedDataflowDriver(
            checkpoint_path="ckpt.bin", checkpoint_every=4, sink=txn_sink
        )
        for res in op.run(stream, ..., driver=driver):  # operator binds
            for line in render(res):
                txn_sink.stage(line)   # staged records commit with the
                                       # NEXT checkpoint, exactly once

    Operators bind themselves with :meth:`bind` (run() does it). When a
    caller passes no driver, the operators construct
    :func:`strict_driver` — no retries, no failover, no checkpoint —
    so routing every operator through here changes neither results nor
    error semantics; constructing a :class:`WindowedDataflowDriver`
    yourself IS the opt-in to self-healing (retries default to 2,
    failover to on).
    """

    def __init__(self, *, checkpoint_path: Optional[str] = None,
                 checkpoint_every: int = 1,
                 sink=None,
                 retry: Optional[RetryPolicy] = None,
                 extra_state: Optional[Callable[[], Dict[str, Any]]] = None,
                 skip_on_resume: bool = True,
                 flush_at_end: bool = True,
                 failover: bool = True,
                 overload=None,
                 source_pausable: Optional[bool] = None,
                 dial_deadline_s: Optional[float] = None):
        self.checkpoint_path = checkpoint_path
        self.checkpoint_every = max(1, int(checkpoint_every))
        self.sink = sink
        self.retry = retry if retry is not None else RetryPolicy()
        self.extra_state = extra_state
        self.skip_on_resume = skip_on_resume
        self.flush_at_end = flush_at_end
        #: ``failover=False`` is strict mode: retries still apply but a
        #: dead device path CRASHES (for resume) instead of degrading —
        #: what a parity-critical capture wants, and what the chaos
        #: matrix uses to force crash semantics at every point.
        self.failover = failover
        #: Optional :class:`spatialflink_tpu.overload.OverloadController`
        #: — bounded admission (shed/backpressure) on every item this
        #: driver pulls, the device-path circuit breaker in
        #: ``_process_window``, and overload state published with each
        #: checkpoint (so a resumed run replays the exact shed
        #: schedule). ``None`` (the default, incl. ``strict_driver``)
        #: changes nothing.
        self.overload = overload
        #: Whether the source can absorb backpressure (data safe at the
        #: source). ``None`` defaults to ``skip_on_resume`` — replayable
        #: sources pause, non-replayable ones shed.
        self.source_pausable = (bool(skip_on_resume)
                                if source_pausable is None
                                else bool(source_pausable))
        #: Bounded first device touch (the bench dial-deadline semantics
        #: brought to the driver): the FIRST device-path window process
        #: after construction or resume runs under a watchdog — a
        #: ``--checkpoint`` resume on an unreachable device dies in bounded time
        #: with the ledger stream sealed ``dial_timeout`` instead of
        #: hanging forever. Explicit value wins; else SFT_DIAL_DEADLINE_S
        #: when set; else disabled (see :func:`resolve_dial_deadline_s`).
        self.dial_deadline_s = resolve_dial_deadline_s(dial_deadline_s)
        self._dialed = False
        self.op = None
        self._node_label: Optional[str] = None  # set by bind()
        self.process: Optional[Callable] = None
        self.fallback: Optional[Callable] = None
        self.backend = "device"
        self.loaded_checkpoint: Optional[Dict[str, Any]] = None
        self.stats = {
            "windows": 0, "events": 0, "retries": 0, "failovers": 0,
            "checkpoints": 0, "resumed": False,
        }
        self._since_ckpt = 0
        self._consumed = 0
        self._skip = 0
        # Window ends finished since the last commit — the latency-
        # lineage "commit" stage stamps them when the sink/checkpoint
        # actually publishes (the only moment a result is durably OURS).
        # Only populated while a sink or checkpoint exists: a driverless
        # yield has no commit concept, and an unbounded list here would
        # leak on sinkless runs.
        self._pending_commit: list = []

    # -- binding / resume ------------------------------------------------------

    def attach(self, op) -> "WindowedDataflowDriver":
        """Attach the operator and load + restore an existing checkpoint
        (operator state, assembler, egress marker, resume position,
        backend). Callable BEFORE any device staging: operators consult
        ``self.backend`` afterwards and skip building the device path
        when the restored run had already failed over — a resume on a
        dead device path must not touch it during setup."""
        if self.op is not op:
            self.op = op
            self._load()
        return self

    def bind(self, op, process: Optional[Callable],
             fallback: Optional[Callable] = None
             ) -> "WindowedDataflowDriver":
        """Attach (if :meth:`attach` hasn't already) and set the
        per-window processors. ``process`` is the device path (may be
        None when the restored backend is the fallback and the caller
        skipped building it); ``fallback`` the numpy/native route used
        after device-path failover."""
        self.attach(op)
        # Node-attribution label for everything this driver processes:
        # the operator names itself via `telemetry_node` (the DAG says
        # "dag"); else its class name. Inner scopes (the DAG's per-node
        # walk) override it — innermost wins.
        self._node_label = (getattr(op, "telemetry_node", None)
                            or type(op).__name__)
        self.process = process
        self.fallback = fallback if self.failover else None
        if self.backend == "fallback" and self.fallback is None:
            raise ValueError(
                f"checkpoint {self.checkpoint_path!r} was taken after a "
                "failover to the fallback backend, but this driver has "
                "no fallback bound (failover=False, or the operator "
                "provides none) — resume with a failover-enabled driver "
                "on a fallback-capable operator, or delete the "
                "checkpoint to recompute from the source"
            )
        if self.backend == "device" and self.process is None:
            raise ValueError("bind() needs a device process while "
                             "backend == 'device'")
        return self

    def _load(self) -> None:
        import os

        if not (self.checkpoint_path and os.path.exists(self.checkpoint_path)):
            # Fresh run: the sink's truncate-and-restart is DEFERRED to
            # the moment the loop actually starts — a misconfigured
            # driver that gets rejected before running must not have
            # wiped a previous run's committed egress on the way.
            self._sink_fresh = True
            return
        ck = load_checkpoint(self.checkpoint_path)
        restore_operator(self.op, ck["op"])
        drv = ck.get("driver", {})
        if self.skip_on_resume:
            self._skip = int(drv.get("events_consumed", 0))
        self._consumed = int(drv.get("events_consumed", 0))
        self.stats["windows"] = int(drv.get("windows", 0))
        self.backend = drv.get("backend", "device")
        if self.sink is not None and hasattr(self.sink, "restore"):
            if "egress" in ck:
                self.sink.restore(ck["egress"])
            else:
                self.sink.reset()
        if self.overload is not None and "overload" in ck:
            # Shed decisions are a function of controller state + the
            # stream — restoring the state replays the exact shed
            # schedule of an uninterrupted run past the skip point.
            self.overload.restore(ck["overload"])
        self.stats["resumed"] = True
        self.loaded_checkpoint = ck

    # -- the loop --------------------------------------------------------------

    def run(self, source: Iterable) -> Iterator:
        """Drive ``source`` through the operator's event-time assembler;
        yield one result per fired window. Checkpoints at window
        boundaries between events; a crash anywhere resumes from the
        last published checkpoint."""
        asm = self.op._adopt_assembler(self.op._assembler())
        yield from self._drive(source, asm.feed,
                               asm.flush if self.flush_at_end else None)

    def run_soa(self, chunks: Iterable, asm) -> Iterator:
        """SoA twin of :meth:`run`: ``chunks`` feed the supplied soa.py
        sliding assembler (point or ragged); consumed positions count
        chunks. The assembler snapshots through the operator's
        ``checkpoint_soa_assembler`` hook."""
        self.op._adopt_soa_assembler(asm)
        yield from self._drive(chunks, asm.feed,
                               asm.flush if self.flush_at_end else None)

    def run_windows(self, windows: Iterable) -> Iterator:
        """Pre-built window batches (count windows etc.): retry/failover
        still apply, but there is no event-position to checkpoint — a
        configured ``checkpoint_path`` is rejected rather than silently
        unsafe."""
        if self.checkpoint_path:
            raise ValueError(
                "run_windows cannot checkpoint (no event-stream position "
                "to record) — use run()/run_soa() for resumable pipelines"
            )
        self._reset_fresh_sink()
        with self._installed_controller():
            for win in windows:
                yield self._process_window(win)
            self._commit_sink_only()

    def _reset_fresh_sink(self) -> None:
        if getattr(self, "_sink_fresh", False):
            self._sink_fresh = False
            if self.sink is not None and hasattr(self.sink, "reset"):
                self.sink.reset()

    def run_precomputed(self, windows: Iterable) -> Iterator:
        """Deterministically re-computable window batches (the pane-scan
        engines, e.g. ``TJoinQuery.run_soa_panes``): the checkpointed
        position counts WINDOWS, and a resume — after the caller re-runs
        the upstream recompute over the replayed bounded stream — skips
        the already-committed prefix. Retry/failover apply per window
        like everywhere else. Admission control does NOT apply —
        these items are fired WINDOWS, not ingest; shedding one would
        silently drop results rather than load."""
        yield from self._drive(windows, lambda w: [w], None, admit=False)

    @contextlib.contextmanager
    def _installed_controller(self):
        """The driver's controller becomes the process-global one for
        the run (the fire-site hooks and rung-effect getters read the
        module slot). A controller installed BEFORE the run (e.g.
        bench's SFT_OVERLOAD_POLICY global) is restored when the loop
        ends; otherwise the driver's stays installed — the ledger
        seal and the post-run SLO verdict read the module slot, and
        uninstalling to None would turn the run's real shed counters
        into a silence-fails budget violation (tests clean the slot
        via overload.uninstall())."""
        from spatialflink_tpu import overload as overload_mod

        prev = overload_mod.controller()
        if self.overload is not None and prev is not self.overload:
            overload_mod.install(self.overload)
        try:
            yield
        finally:
            if (self.overload is not None and prev is not None
                    and prev is not self.overload):
                overload_mod.install(prev)

    def _drive(self, source, feed, flush, admit: bool = True) -> Iterator:
        self._reset_fresh_sink()
        with self._installed_controller():
            # A source may declare its own backpressure capability
            # (WireKafkaSource.pausable — a consumer absorbs pressure by
            # not fetching; a socket cannot); the driver's setting is
            # the fallback.
            pausable = getattr(source, "pausable", None)
            if pausable is None:
                pausable = self.source_pausable
            it = iter(source)
            if self._skip:
                # Resume: the first `events_consumed` records are already
                # reflected in the restored assembler/operator state.
                next(itertools.islice(it, self._skip - 1, self._skip), None)
                self._skip = 0
            for item in it:
                if faults.armed:  # chaos injection point (faults.py)
                    faults.hit("source.stall")
                self._consumed += 1
                self.stats["events"] += 1
                if admit and self.overload is not None and not \
                        self.overload.admit_item(item, pausable=pausable):
                    # Shed: the item never reaches the assembler, but it
                    # still counts as consumed — resume determinism (the
                    # same stream prefix sheds the same items).
                    self.stats["shed"] = self.stats.get("shed", 0) + 1
                    continue
                fired = feed(item)
                for win in fired:
                    yield self._process_window(win)
                if fired and self._since_ckpt >= self.checkpoint_every:
                    self._commit()
            if flush is not None:
                for win in flush():
                    yield self._process_window(win)
            self._commit(final=True)

    # -- bounded first device touch (the dial watchdog) ------------------------

    @contextlib.contextmanager
    def _dial_guard(self, device_path: bool):
        """Arm a bounded watchdog around the run's FIRST device-path
        window process — the first real device touch a driver (or a
        ``--checkpoint`` resume) makes. On deadline: seal any armed
        ledger stream with reason ``dial_timeout`` (bounded-lock seal —
        :func:`_seal_stream_dial_timeout` never blocks the watchdog)
        and kill the process with the dial exit code; a wedged
        device call cannot be un-wedged from Python, only reported and
        abandoned. Disarmed (no deadline / already dialed / fallback
        path) cost: one attribute check."""
        import threading

        if not device_path or self._dialed or self.dial_deadline_s <= 0:
            yield
            return
        ok = threading.Event()
        deadline = float(self.dial_deadline_s)

        def _watchdog():
            if not ok.wait(deadline):
                if ok.is_set():  # lost the race at the boundary
                    return
                _seal_stream_dial_timeout("driver first device window")
                import sys

                print(
                    "driver: first device window hung > "
                    f"{float(deadline):.0f} s (SFT_DIAL_DEADLINE_S) — "
                    "device unreachable; ledger stream sealed "
                    "dial_timeout", file=sys.stderr,
                )
                sys.stderr.flush()
                _dial_timeout_exit(DIAL_TIMEOUT_EXIT_CODE)

        t = threading.Thread(target=_watchdog, daemon=True)
        t.start()
        try:
            yield
            self._dialed = True
        finally:
            ok.set()

    # -- per-window processing (retry → failover → crash) ----------------------

    def _process_window(self, win):
        if telemetry.enabled:
            # Latency lineage, stage "assemble": the window just fired
            # at the source clock — its event-time staleness starts the
            # per-window lineage every later stage extends.
            end = getattr(win, "end", None)
            if end is not None:
                telemetry.record_e2e(end, "assemble",
                                     node=self._node_label)
        # Operator-level node attribution: everything in the retry →
        # failover ladder (device bytes, compiles, kernel rows, fault
        # hits) tags the bound operator's label. The DAG's per-node
        # scopes nest inside and win (innermost-wins).
        with telemetry.scope(self._node_label):
            return self._process_window_inner(win)

    def _process_window_inner(self, win):
        ctrl = self.overload
        breaker = ctrl.breaker if ctrl is not None else None
        # The circuit breaker generalizes the permanent failover below:
        # with one configured (and a fallback bound), whole windows route
        # to the twin while the circuit is open — no per-window
        # retry/timeout — and a half-open probe re-dials the device path
        # on a bounded schedule. Without one, PR 8 semantics unchanged.
        use_breaker = (breaker is not None and self.backend == "device"
                       and self.fallback is not None)
        single_attempt = False
        if use_breaker:
            route = breaker.route()
            if route == "fallback":
                return self._finish_window(self.fallback(win),
                                           degraded=True, win=win)
            single_attempt = route == "probe"
        policy = self.retry
        attempt = 0
        delay = policy.backoff_s
        proc = self.process if self.backend == "device" else self.fallback
        while True:
            try:
                with self._dial_guard(proc is self.process):
                    if self.backend == "device" and proc is self.process \
                            and faults.armed:
                        faults.hit("driver.window")  # chaos injection pt
                    result = proc(win)
                if use_breaker and proc is self.process:
                    breaker.record_success()
                break
            except (KeyboardInterrupt, SystemExit):
                raise
            except CheckpointCorruptError:
                raise  # never retry integrity failures
            except Exception as e:
                if not getattr(proc, "idempotent", True):
                    # A stateful processor (e.g. the realtime TStats
                    # ValueState walk) may have half-applied the window:
                    # re-running would double-count. Crash-and-resume is
                    # the only safe recovery for it.
                    raise
                start = getattr(win, "start", 0)
                if not single_attempt and attempt < policy.max_retries:
                    attempt += 1
                    self.stats["retries"] += 1
                    telemetry.record_driver_retry(start, attempt, repr(e))
                    policy.do_sleep(delay)
                    delay *= policy.multiplier
                    continue
                if use_breaker and proc is self.process:
                    # Breaker mode: count the failed window (opening the
                    # circuit at the configured threshold) and run THIS
                    # window on the twin — no permanent backend switch,
                    # the next probe may win the device path back.
                    breaker.record_failure(start, repr(e))
                    return self._finish_window(self.fallback(win),
                                               degraded=True, win=win)
                if self.backend == "device" and self.fallback is not None:
                    # Graceful degradation: the device path is gone (a
                    # dead device outlives any retry budget) — switch to
                    # the numpy/native route for the REST of the run.
                    self.backend = "fallback"
                    self.stats["failovers"] += 1
                    telemetry.record_driver_failover(start, repr(e))
                    proc = self.fallback
                    attempt = 0
                    delay = policy.backoff_s
                    continue
                raise
        return self._finish_window(result,
                                   degraded=self.backend != "device",
                                   win=win)

    def _finish_window(self, result, degraded: bool = False, win=None):
        self.stats["windows"] += 1
        self._since_ckpt += 1
        if degraded and self.overload is not None:
            # A window answered by a non-device path is a DEGRADED
            # window — the SLO ``degraded_window_budget`` counts these.
            self.overload.count_degraded_window()
        if telemetry.enabled and win is not None:
            end = getattr(win, "end", None)
            if end is not None:
                # Stage "compute": the window's result is materialized
                # host-side (the processor returned).
                telemetry.record_e2e(end, "compute",
                                     node=self._node_label)
                if self.sink is not None or \
                        self.checkpoint_path is not None:
                    self._pending_commit.append(end)
        return result

    # -- checkpoint commit -----------------------------------------------------

    def _commit(self, final: bool = False) -> None:
        """The exactly-once commit point (between source events):
        1. staged egress appends durably (fsync) — marker advances;
        2. operator + assembler + driver position + that marker publish
           atomically as one checkpoint.
        A crash between 1 and 2 leaves a tail past the OLD marker, which
        restore() truncates — so resumed egress never gaps or dups."""
        if self.checkpoint_path is None:
            if final:
                self._commit_sink_only()
            return
        # One ``commit`` span a published checkpoint, its parts as
        # children: ``commit.egress`` (the sinks' fsync'd appends),
        # ``commit.state`` (gathering the components), and inside
        # save_checkpoint ``checkpoint.pickle`` / ``checkpoint.write``.
        with telemetry.span("commit"):
            egress = None
            with telemetry.span("commit.egress"):
                if self.sink is not None and hasattr(self.sink, "commit"):
                    egress = self.sink.commit()
            with telemetry.span("commit.state"):
                components: Dict[str, Any] = {
                    "op": operator_state(self.op),
                    "driver": {
                        "events_consumed": self._consumed,
                        "windows": self.stats["windows"],
                        "backend": self.backend,
                    },
                }
                if egress is not None:
                    components["egress"] = egress
                if self.overload is not None:
                    components["overload"] = self.overload.state()
                if self.extra_state is not None:
                    components.update(self.extra_state())
            save_checkpoint(self.checkpoint_path, **components)
        self.stats["checkpoints"] += 1
        self._since_ckpt = 0
        self._stamp_committed()

    def _commit_sink_only(self) -> None:
        if self.sink is not None and hasattr(self.sink, "commit") \
                and getattr(self.sink, "pending", 0):
            self.sink.commit()
        self._stamp_committed()

    def _stamp_committed(self) -> None:
        """Latency lineage, stage "commit": every window finished since
        the last commit is now durably published (egress appended and/or
        checkpoint framed) — the stamp that answers "how stale is a
        COMMITTED result?". Closes each window's open lineage entry."""
        if not self._pending_commit:
            return
        if telemetry.enabled:
            for end in self._pending_commit:
                telemetry.record_e2e(end, "commit",
                                     node=self._node_label)
        self._pending_commit = []


# ---------------------------------------------------------------------------
# Chaos smoke: the kill/resume round trip tools/ci runs on every commit.


def _toy_pipeline(n_events: int = 120):
    """A tiny deterministic range-query pipeline over a synthetic point
    stream: the chaos harness shared by the CLI smoke below and
    tests/test_chaos_matrix.py. Returns (grid, conf, source_factory,
    query_point) — callers assemble to taste."""
    import numpy as np

    from spatialflink_tpu.grid import UniformGrid
    from spatialflink_tpu.models.objects import Point
    from spatialflink_tpu.operators.query_config import (
        QueryConfiguration,
        QueryType,
    )

    grid = UniformGrid(8, 0.0, 8.0, 0.0, 8.0)
    conf = QueryConfiguration(QueryType.WindowBased, window_size=2.0,
                              slide_step=1.0)
    rng = np.random.default_rng(7)
    xs = rng.uniform(0.0, 8.0, n_events)
    ys = rng.uniform(0.0, 8.0, n_events)

    def source():
        for i in range(n_events):
            yield Point(obj_id=f"o{i % 13}", timestamp=100 * i,
                        x=float(xs[i]), y=float(ys[i]))

    query = Point(obj_id="q", x=4.0, y=4.0)
    return grid, conf, source, query


def render_range_result(res) -> Iterator[str]:
    """The streaming_job option-1 egress line format."""
    for p, d in zip(res.objects, res.dists):
        yield (f"{res.start},{res.end},{p.obj_id},{float(p.x)!r},"
               f"{float(p.y)!r},{float(d)!r}")


def run_chaos_child(workdir: str) -> int:
    """One (possibly fault-armed) pipeline run: range query → exactly-
    once CSV egress + checkpoint under ``workdir``. Resumes
    automatically when the checkpoint exists. Faults arm via
    SFT_FAULT_PLAN (read at import by faults.py)."""
    import os

    from spatialflink_tpu.operators.range_query import PointPointRangeQuery
    from spatialflink_tpu.streams.sinks import TransactionalFileSink

    # A stream-armed chaos child records its capture (the dag.py chaos
    # idiom): the abort leg's kill then leaves both a recoverable stream
    # AND a <stream>.blackbox.json flight-recorder dump — what
    # chaos_smoke() asserts below.
    stream = os.environ.get("SFT_LEDGER_STREAM")
    if stream:
        telemetry.enable(stream_path=stream)
    grid, conf, source, query = _toy_pipeline()
    sink = TransactionalFileSink(os.path.join(workdir, "egress.csv"))
    driver = WindowedDataflowDriver(
        checkpoint_path=os.path.join(workdir, "ckpt.bin"),
        checkpoint_every=2, sink=sink,
        retry=RetryPolicy(max_retries=1, backoff_s=0.0),
        failover=False,  # chaos wants crash-and-resume, not degradation
    )
    op = PointPointRangeQuery(conf, grid)
    n = 0
    for res in op.run(source(), [query], 1.5, driver=driver):
        for line in render_range_result(res):
            sink.stage(line)
            n += 1
    if stream:
        telemetry.seal_stream("complete")
    return n


def run_chaos_sharded_child(workdir: str) -> int:
    """One (possibly fault-armed) GRID-PARTITIONED pipeline run on the
    8-device CPU mesh: ``run_partitioned`` (parallel/halo.py halo
    exchange) → exactly-once CSV egress + checkpoint, with the partition
    plan riding the framed unit publish. The ``shard.exchange`` chaos
    point fires once per window inside the halo wrapper, so an armed
    abort kills the process mid-exchange; a resume must re-dispatch onto
    the checkpointed placement and converge byte-identically
    (tests/test_chaos_matrix.py).

    Needs 8 CPU devices (the parent sets
    ``XLA_FLAGS=--xla_force_host_platform_device_count=8``)."""
    import os

    import jax
    import numpy as np

    from spatialflink_tpu.grid import UniformGrid
    from spatialflink_tpu.models.objects import Point
    from spatialflink_tpu.operators.query_config import (
        QueryConfiguration,
        QueryType,
    )
    from spatialflink_tpu.operators.range_query import PointPointRangeQuery
    from spatialflink_tpu.parallel.mesh import data_mesh
    from spatialflink_tpu.streams.sinks import TransactionalFileSink

    if len(jax.devices()) < 8:
        raise RuntimeError(
            "chaos-sharded-child needs 8 devices — set "
            "XLA_FLAGS=--xla_force_host_platform_device_count=8 "
            "(and JAX_PLATFORMS=cpu) in the child env"
        )
    # Finer grid than _toy_pipeline's 8×8: every one of the 8 shards
    # must span at least the halo width in flat cells
    # (parallel/partition.py's single-hop contract), which the toy grid
    # cannot give at any useful radius.
    grid = UniformGrid(128, 0.0, 8.0, 0.0, 8.0)
    conf = QueryConfiguration(QueryType.WindowBased, window_size=2.0,
                              slide_step=1.0)
    n_events = 160
    rng = np.random.default_rng(7)
    xs = rng.uniform(0.0, 8.0, n_events)
    ys = rng.uniform(0.0, 8.0, n_events)

    def source():
        for i in range(n_events):
            yield Point(obj_id=f"o{i % 13}", timestamp=100 * i,
                        x=float(xs[i]), y=float(ys[i]))

    queries = [Point(obj_id="q0", x=4.0, y=4.0),
               Point(obj_id="q1", x=1.0, y=6.5)]
    sink = TransactionalFileSink(os.path.join(workdir, "egress.csv"))
    driver = WindowedDataflowDriver(
        checkpoint_path=os.path.join(workdir, "ckpt.bin"),
        checkpoint_every=2, sink=sink,
        retry=RetryPolicy(max_retries=1, backoff_s=0.0),
        failover=False,  # chaos wants crash-and-resume, not degradation
    )
    op = PointPointRangeQuery(conf, grid)
    mesh = data_mesh(8)
    n = 0
    for res in op.run_partitioned(source(), queries, 0.9, mesh,
                                  driver=driver):
        for line in render_range_result(res):
            sink.stage(line)
            n += 1
    return n


def chaos_smoke() -> int:
    """Clean run vs (killed-by-abort-fault → resumed) run: egress must be
    byte-identical. Exit 0 on equality. Each leg is a fresh subprocess —
    the abort kind ``os._exit``\\ s, and crash-consistency only means
    anything across process boundaries."""
    import json
    import os
    import subprocess
    import sys
    import tempfile

    env_base = dict(os.environ)
    env_base.pop("SFT_FAULT_PLAN", None)
    # Ambient capture paths would point every leg's stream at ONE file
    # (the kill leg arms its own below).
    env_base.pop("SFT_LEDGER_STREAM", None)
    env_base.pop("SFT_LEDGER_PATH", None)
    # The smoke is a CPU run (it must never take the chip from a
    # measurement in flight) — force CPU like every CPU-only path does
    # (tools/ci._cpu_env, tests/conftest.py).
    env_base["JAX_PLATFORMS"] = "cpu"

    def child(workdir, plan=None, stream=None):
        env = dict(env_base)
        if plan is not None:
            env["SFT_FAULT_PLAN"] = json.dumps(plan)
        if stream is not None:
            env["SFT_LEDGER_STREAM"] = stream
        return subprocess.run(
            [sys.executable, "-m", "spatialflink_tpu.driver",
             "--chaos-child", workdir],
            env=env, capture_output=True, text=True, timeout=600,
        )

    with tempfile.TemporaryDirectory(prefix="sft_chaos_") as tmp:
        clean_dir = os.path.join(tmp, "clean")
        chaos_dir = os.path.join(tmp, "chaos")
        os.makedirs(clean_dir)
        os.makedirs(chaos_dir)
        p = child(clean_dir)
        if p.returncode != 0:
            print("chaos-smoke: clean run failed\n" + p.stderr[-2000:])
            return 1
        # Kill -9 analog mid-run: the abort fault fires on the 2nd sink
        # commit — after durable state exists, before the run completes.
        # The kill leg streams its capture so the abort leaves a flight-
        # recorder dump beside it (record_fault dumps BEFORE os._exit).
        stream = os.path.join(chaos_dir, "stream.jsonl")
        p = child(chaos_dir,
                  plan=[{"point": "sink.write", "kind": "abort", "at": 2}],
                  stream=stream)
        if p.returncode != 137:
            print(f"chaos-smoke: expected the armed child to die with "
                  f"exit 137, got {p.returncode}\n" + p.stderr[-2000:])
            return 1
        bb_path = stream + ".blackbox.json"
        if not os.path.exists(bb_path):
            print("chaos-smoke: the killed child left no flight-recorder "
                  f"dump at {bb_path}")
            return 1
        try:
            with open(bb_path) as f:
                bb = json.load(f)
        except ValueError as e:
            print(f"chaos-smoke: blackbox dump unparseable: {e!r}")
            return 1
        if bb.get("blackbox_version") != 1 \
                or not str(bb.get("reason", "")).startswith("fault:") \
                or not bb.get("ring"):
            print("chaos-smoke: blackbox dump malformed "
                  f"(version={bb.get('blackbox_version')!r}, "
                  f"reason={bb.get('reason')!r}, "
                  f"ring entries={len(bb.get('ring') or [])})")
            return 1
        p = child(chaos_dir)  # resume from the published checkpoint
        if p.returncode != 0:
            print("chaos-smoke: resume run failed\n" + p.stderr[-2000:])
            return 1
        with open(os.path.join(clean_dir, "egress.csv"), "rb") as f:
            clean = f.read()
        with open(os.path.join(chaos_dir, "egress.csv"), "rb") as f:
            recovered = f.read()
        if clean != recovered:
            print(f"chaos-smoke: egress mismatch after kill/resume "
                  f"(clean {len(clean)} B, recovered {len(recovered)} B)")
            return 1
        if not clean:
            print("chaos-smoke: clean egress is empty (vacuous pass)")
            return 1
    print("chaos-smoke: kill/resume egress byte-identical — OK")
    return 0


def main(argv=None) -> int:
    import argparse

    ap = argparse.ArgumentParser(
        prog="python -m spatialflink_tpu.driver",
        description="windowed-dataflow driver chaos self-test",
    )
    ap.add_argument("--chaos-smoke", action="store_true",
                    help="run the kill/resume egress-equality smoke")
    ap.add_argument("--chaos-child", metavar="DIR", default=None,
                    help="internal: one pipeline run rooted at DIR")
    ap.add_argument("--chaos-sharded-child", metavar="DIR", default=None,
                    help="internal: one grid-partitioned (8-shard halo) "
                         "pipeline run rooted at DIR")
    args = ap.parse_args(argv)
    if args.chaos_child:
        n = run_chaos_child(args.chaos_child)
        print(f"chaos-child: {n} records staged")
        return 0
    if args.chaos_sharded_child:
        n = run_chaos_sharded_child(args.chaos_sharded_child)
        print(f"chaos-sharded-child: {n} records staged")
        return 0
    if args.chaos_smoke:
        return chaos_smoke()
    ap.error("pass --chaos-smoke (or internal --chaos-child)")
    return 2


if __name__ == "__main__":
    import sys

    sys.exit(main())
