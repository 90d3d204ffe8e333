"""The self-healing windowed-dataflow driver (spatialflink_tpu/driver.py):
plain-loop equivalence, retry-with-backoff, device→numpy failover parity
(+ telemetry/ledger visibility), checkpoint/resume, and the exactly-once
egress protocol against the transactional sink."""

import json
import os
import sys

import numpy as np
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if REPO not in sys.path:
    sys.path.insert(0, REPO)

from spatialflink_tpu.checkpoint import (  # noqa: E402
    CheckpointCorruptError,
    load_checkpoint,
)
from spatialflink_tpu.driver import (  # noqa: E402
    RetryPolicy,
    WindowedDataflowDriver,
    _toy_pipeline,
    render_range_result,
)
from spatialflink_tpu.faults import InjectedFault, faults  # noqa: E402
from spatialflink_tpu.operators.range_query import (  # noqa: E402
    PointPointRangeQuery,
)
from spatialflink_tpu.operators.trajectory import TStatsQuery  # noqa: E402
from spatialflink_tpu.streams.sinks import (  # noqa: E402
    TransactionalFileSink,
)
from spatialflink_tpu.telemetry import telemetry  # noqa: E402


@pytest.fixture(autouse=True)
def _clean():
    yield
    faults.disarm()
    telemetry.disable()


def _run_range(driver=None, radius=1.5, n_events=120):
    grid, conf, source, query = _toy_pipeline(n_events=n_events)
    op = PointPointRangeQuery(conf, grid)
    return list(op.run(source(), [query], radius, driver=driver)), op


def _range_pipeline(workdir, *, fault_plan=None, checkpoint_every=2,
                    retry=None, n_events=120):
    """One (possibly fault-armed) checkpointed pipeline leg; returns the
    driver (crashes propagate to the caller)."""
    grid, conf, source, query = _toy_pipeline(n_events=n_events)
    sink = TransactionalFileSink(os.path.join(workdir, "egress.csv"))
    driver = WindowedDataflowDriver(
        checkpoint_path=os.path.join(workdir, "ckpt.bin"),
        checkpoint_every=checkpoint_every, sink=sink,
        retry=retry or RetryPolicy(max_retries=1, backoff_s=0.0),
        failover=False,
    )
    op = PointPointRangeQuery(conf, grid)
    if fault_plan:
        faults.arm(fault_plan)
    try:
        for res in op.run(source(), [query], 1.5, driver=driver):
            for line in render_range_result(res):
                sink.stage(line)
    finally:
        faults.disarm()
    return driver


class TestPlainLoopEquivalence:
    def test_default_driver_matches_direct_iteration(self):
        """Routing run() through a default driver is the old plain loop:
        same windows, same objects, same dists, bit for bit."""
        base, _ = _run_range()
        driven, _ = _run_range(driver=WindowedDataflowDriver())
        assert len(base) == len(driven) > 0
        for a, b in zip(base, driven):
            assert (a.start, a.end, a.window_count) == \
                   (b.start, b.end, b.window_count)
            assert [p.obj_id for p in a.objects] == \
                   [p.obj_id for p in b.objects]
            np.testing.assert_array_equal(a.dists, b.dists)

    def test_tstats_through_default_driver(self):
        grid, conf, source, _ = _toy_pipeline()
        base = list(TStatsQuery(conf, grid).run(source()))
        driven = list(TStatsQuery(conf, grid).run(
            source(), driver=WindowedDataflowDriver()))
        assert len(base) == len(driven) > 0
        for a, b in zip(base, driven):
            assert a.stats == b.stats

    def test_no_driver_keeps_old_error_semantics(self):
        """Without an explicit driver, operators construct the STRICT
        driver: a device-path failure propagates immediately — no
        silent retry, no silent completion on the numpy twin (which
        would report host-path results as device results)."""
        faults.arm([{"point": "driver.window", "at": 1, "times": 1}])
        with pytest.raises(InjectedFault):
            _run_range()  # one transient fault; a retry WOULD recover
        assert faults.counts.get("driver.window") == 1  # single attempt

    def test_realtime_tstats_is_never_retried(self):
        """The realtime ValueState walk mutates per-oid running state —
        a half-applied window must not re-run (double counting). Even a
        retry-configured driver crashes instead."""
        from spatialflink_tpu.operators.query_config import (
            QueryConfiguration,
            QueryType,
        )

        grid, _, source, _ = _toy_pipeline()
        conf = QueryConfiguration(QueryType.RealTime)
        faults.arm([{"point": "driver.window", "at": 2, "times": 1}])
        drv = WindowedDataflowDriver(
            retry=RetryPolicy(max_retries=5, backoff_s=0.0))
        with pytest.raises(InjectedFault):
            list(TStatsQuery(conf, grid).run(source(), driver=drv))
        assert drv.stats["retries"] == 0
        assert drv.stats["failovers"] == 0


class TestMigratedOperators:
    """ISSUE 9: KnnQuery.run / JoinQuery.run / TJoinQuery.run_soa_panes
    route through the driver — default-strict semantics pinned (single
    attempt, errors propagate) plus failover parity for the new numpy
    twins."""

    def _knn(self, driver=None):
        grid, conf, source, query = _toy_pipeline()
        from spatialflink_tpu.operators.knn_query import PointPointKNNQuery

        op = PointPointKNNQuery(conf, grid)
        return list(op.run(source(), query, 2.5, 3, driver=driver))

    def _join(self, driver=None, naive=False):
        from spatialflink_tpu.operators.join_query import (
            PointPointJoinQuery,
        )
        from spatialflink_tpu.operators.query_config import (
            QueryConfiguration,
            QueryType,
        )

        grid, conf, source, _ = _toy_pipeline()
        if naive:
            # Micro-batches wide enough that each holds BOTH sides of
            # the interleaved stream (events are 100 ms apart).
            conf = QueryConfiguration(QueryType.RealTimeNaive,
                                      realtime_batch_ms=2000)
        op = PointPointJoinQuery(conf, grid)
        left = [e for i, e in enumerate(source()) if i % 2 == 0]
        right = [e for i, e in enumerate(source()) if i % 2 == 1]
        return list(op.run(iter(left), iter(right), 1.5, driver=driver))

    def test_knn_no_driver_is_single_attempt(self):
        faults.arm([{"point": "driver.window", "at": 1, "times": 1}])
        with pytest.raises(InjectedFault):
            self._knn()  # one transient fault; a retry WOULD recover
        assert faults.counts.get("driver.window") == 1

    def test_join_no_driver_is_single_attempt(self):
        faults.arm([{"point": "driver.window", "at": 1, "times": 1}])
        with pytest.raises(InjectedFault):
            self._join()
        assert faults.counts.get("driver.window") == 1

    def test_knn_failover_parity(self):
        base = self._knn()
        faults.arm([{"point": "driver.window", "at": 2, "times": 10_000}])
        drv = WindowedDataflowDriver(
            retry=RetryPolicy(max_retries=0, backoff_s=0.0))
        driven = self._knn(driver=drv)
        faults.disarm()
        assert drv.backend == "fallback"
        assert len(driven) == len(base) > 4
        assert any(r.neighbors for r in base), "degenerate: no neighbors"
        for a, b in zip(base, driven):
            assert (a.start, a.end) == (b.start, b.end)
            # Same ordered (objID, representative) winners; distances
            # agree to float ulps (FMA fusion freedom).
            assert [(oid, ev.obj_id) for oid, _, ev in a.neighbors] == \
                   [(oid, ev.obj_id) for oid, _, ev in b.neighbors]
            np.testing.assert_allclose(
                [d for _, d, _ in a.neighbors],
                [d for _, d, _ in b.neighbors], rtol=3e-7)

    def test_join_naive_failover_parity(self):
        base = self._join(naive=True)
        faults.arm([{"point": "driver.window", "at": 1, "times": 10_000}])
        drv = WindowedDataflowDriver(
            retry=RetryPolicy(max_retries=0, backoff_s=0.0))
        driven = self._join(driver=drv, naive=True)
        faults.disarm()
        assert drv.backend == "fallback"
        assert len(driven) == len(base) > 0
        assert any(r.pairs for r in base), "degenerate: no pairs"
        for a, b in zip(base, driven):
            assert [(x.obj_id, y.obj_id) for x, y, _ in a.pairs] == \
                   [(x.obj_id, y.obj_id) for x, y, _ in b.pairs]
            np.testing.assert_allclose(
                [d for _, _, d in a.pairs], [d for _, _, d in b.pairs],
                rtol=3e-7)

    def test_join_bucketed_has_no_twin_and_stays_strict(self):
        """The window-based grid-hash mode's pair order is device
        compaction order — no twin exists, so even a failover-enabled
        driver crashes when the device path dies (honest, not silent)."""
        faults.arm([{"point": "driver.window", "at": 1, "times": 10_000}])
        drv = WindowedDataflowDriver(
            retry=RetryPolicy(max_retries=0, backoff_s=0.0))
        with pytest.raises(InjectedFault):
            self._join(driver=drv)
        assert drv.stats["failovers"] == 0

    def test_tjoin_panes_checkpoint_resume_byte_identical(self, tmp_path):
        """run_soa_panes through run_precomputed: the position counts
        fired windows; a resume re-runs the deterministic scan and
        skips the committed prefix. Reuses the chaos-matrix tjoin
        harness at the driver.window point (the matrix leg itself
        exercises source.stall)."""
        from test_chaos_matrix import chaos_tjoin_panes

        chaos_tjoin_panes(tmp_path, "driver.window", at=5)


class TestRetry:
    def test_transient_fault_is_retried_and_recovers(self):
        """One injected failure + one retry budget → the run completes
        with identical results and a driver_retry event."""
        telemetry.enable()
        base, _ = _run_range()
        faults.arm([{"point": "driver.window", "at": 3, "times": 1}])
        drv = WindowedDataflowDriver(
            retry=RetryPolicy(max_retries=2, backoff_s=0.0))
        driven, _ = _run_range(driver=drv)
        assert drv.stats["retries"] == 1
        assert drv.stats["failovers"] == 0
        assert drv.backend == "device"
        assert len(driven) == len(base)
        for a, b in zip(base, driven):
            np.testing.assert_array_equal(a.dists, b.dists)
        assert telemetry.snapshot()["driver"]["retries"] == 1
        assert "driver_retry" in [e["name"] for e in telemetry.events]

    def test_exhausted_retries_raise_in_strict_mode(self):
        faults.arm([{"point": "driver.window", "at": 1, "times": 99}])
        drv = WindowedDataflowDriver(
            retry=RetryPolicy(max_retries=1, backoff_s=0.0),
            failover=False)
        with pytest.raises(InjectedFault):
            _run_range(driver=drv)
        assert drv.stats["retries"] == 1

    def test_backoff_schedule_pinned_via_sleep_hook(self):
        """RetryPolicy.sleep is the injectable clock: the full backoff
        schedule is pinned deterministically with ZERO wall-clock
        sleeping and no module monkeypatching (the production default —
        sleep=None → time.sleep — is untouched)."""
        sleeps = []
        faults.arm([{"point": "driver.window", "at": 1, "times": 3}])
        drv = WindowedDataflowDriver(
            retry=RetryPolicy(max_retries=3, backoff_s=0.1,
                              multiplier=3.0, sleep=sleeps.append))
        _run_range(driver=drv)
        assert sleeps == [0.1, pytest.approx(0.3), pytest.approx(0.9)]
        assert drv.stats["retries"] == 3

    def test_sleep_hook_default_is_time_sleep(self, monkeypatch):
        import spatialflink_tpu.driver as driver_mod

        called = []
        monkeypatch.setattr(driver_mod.time, "sleep", called.append)
        RetryPolicy().do_sleep(0.07)
        assert called == [0.07]


class TestFailoverParity:
    """ISSUE acceptance: device→fallback switch mid-stream changes no
    results and is visible as telemetry events consumable by `sfprof
    health` / the SLO engine."""

    def test_range_failover_set_parity_and_visibility(self, tmp_path):
        telemetry.enable()
        base, _ = _run_range()
        # Device path dies permanently at window 3 → numpy fallback.
        faults.arm([{"point": "driver.window", "at": 3, "times": 10_000}])
        drv = WindowedDataflowDriver(
            retry=RetryPolicy(max_retries=1, backoff_s=0.0))
        driven, _ = _run_range(driver=drv)
        faults.disarm()
        assert drv.backend == "fallback"
        assert drv.stats["failovers"] == 1
        assert len(driven) == len(base) > 4
        for a, b in zip(base, driven):
            assert (a.start, a.end) == (b.start, b.end)
            # Bit/set parity: the KEPT SET is identical; distances agree
            # to float ulps (XLA may fuse x²+y² with FMA, numpy cannot).
            assert [p.obj_id for p in a.objects] == \
                   [p.obj_id for p in b.objects]
            np.testing.assert_allclose(a.dists, b.dists, rtol=3e-7)

        # Telemetry: failover event + snapshot counter...
        snap = telemetry.snapshot()
        assert snap["driver"]["failovers"] == 1
        assert "failover" in [e["name"] for e in telemetry.events]
        # ...and it reaches a LEDGER health/SLO consumers can read.
        ledger = tmp_path / "ledger.json"
        telemetry.write_ledger(str(ledger), capture_costs=False)
        doc = json.loads(ledger.read_text())
        assert doc["snapshot"]["driver"]["failovers"] == 1

        from tools.sfprof import slo as sfslo

        rows = sfslo.evaluate({"failover_budget": 0}, doc)
        assert rows == [("slo:failover_budget", 1.0, "<= 0", False)]
        rows = sfslo.evaluate({"failover_budget": 1}, doc)
        assert rows[0][3] is True

    def test_tstats_failover_parity(self):
        grid, conf, source, _ = _toy_pipeline()
        base = list(TStatsQuery(conf, grid).run(source()))
        faults.arm([{"point": "driver.window", "at": 1, "times": 10_000}])
        drv = WindowedDataflowDriver(
            retry=RetryPolicy(max_retries=0, backoff_s=0.0))
        driven = list(TStatsQuery(conf, grid).run(source(), driver=drv))
        faults.disarm()
        assert drv.backend == "fallback"
        assert len(driven) == len(base) > 4
        for a, b in zip(base, driven):
            assert set(a.stats) == set(b.stats)
            for oid in a.stats:
                np.testing.assert_allclose(
                    a.stats[oid][0], b.stats[oid][0], rtol=1e-6)
                assert a.stats[oid][1] == b.stats[oid][1]  # exact ms

    def test_live_slo_engine_budgets_failover(self):
        from spatialflink_tpu import slo

        telemetry.enable()
        engine = slo.SloEngine(slo.SloSpec(failover_budget=0,
                                           retry_budget=0,
                                           eval_interval_s=0.0))
        try:
            faults.arm(
                [{"point": "driver.window", "at": 2, "times": 10_000}])
            drv = WindowedDataflowDriver(
                retry=RetryPolicy(max_retries=1, backoff_s=0.0))
            _run_range(driver=drv)
            rows = {r["check"]: r["ok"] for r in engine.evaluate()}
            assert rows["failover_budget"] is False
            assert rows["retry_budget"] is False
        finally:
            slo.uninstall()


class TestFailoverResume:
    """A checkpoint taken AFTER failover records backend="fallback" —
    resuming it must neither dial the (dead) device path during setup
    nor crash into a None fallback."""

    def _failover_checkpoint(self, tmp_path):
        grid, conf, source, query = _toy_pipeline()
        ck = str(tmp_path / "ck.bin")
        drv = WindowedDataflowDriver(
            checkpoint_path=ck, checkpoint_every=1,
            retry=RetryPolicy(max_retries=0, backoff_s=0.0))
        faults.arm([{"point": "driver.window", "at": 1,
                     "times": 10_000}])
        op = PointPointRangeQuery(conf, grid)
        base = list(op.run(source(), [query], 1.5, driver=drv))
        faults.disarm()
        assert drv.backend == "fallback" and base
        return grid, conf, source, query, ck

    def test_resume_after_failover_skips_device_setup(self, tmp_path,
                                                      monkeypatch):
        grid, conf, source, query, ck = self._failover_checkpoint(tmp_path)
        # Resume on a "dead device": ANY device staging during setup
        # would hang a real resume — simulate by making the evaluator
        # builder (the setup's device-touching step) explode.
        def boom(*a, **k):
            raise AssertionError("resume dialed the dead device path")

        monkeypatch.setattr(PointPointRangeQuery, "_window_evaluator",
                            boom)
        drv2 = WindowedDataflowDriver(
            checkpoint_path=ck,
            retry=RetryPolicy(max_retries=0, backoff_s=0.0))
        op2 = PointPointRangeQuery(conf, grid)
        list(op2.run(source(), [query], 1.5, driver=drv2))
        assert drv2.stats["resumed"] is True
        assert drv2.backend == "fallback"

    def test_resume_fallback_checkpoint_without_fallback_is_loud(
            self, tmp_path):
        grid, conf, source, query, ck = self._failover_checkpoint(tmp_path)
        drv2 = WindowedDataflowDriver(checkpoint_path=ck, failover=False)
        op2 = PointPointRangeQuery(conf, grid)
        with pytest.raises(ValueError, match="failover"):
            list(op2.run(source(), [query], 1.5, driver=drv2))


class TestCheckpointResume:
    def test_crash_resume_egress_byte_identical(self, tmp_path):
        clean = tmp_path / "clean"
        chaos = tmp_path / "chaos"
        clean.mkdir()
        chaos.mkdir()
        _range_pipeline(str(clean))
        want = (clean / "egress.csv").read_bytes()
        assert want
        with pytest.raises(InjectedFault):
            _range_pipeline(
                str(chaos),
                fault_plan=[{"point": "driver.window", "at": 7,
                             "times": 10_000}],
            )
        partial = (chaos / "egress.csv").read_bytes()
        assert partial != want  # the crash really interrupted egress
        drv = _range_pipeline(str(chaos))
        assert drv.stats["resumed"] is True
        assert (chaos / "egress.csv").read_bytes() == want

    def test_resume_skips_consumed_prefix_exactly(self, tmp_path):
        """events_consumed in the checkpoint + the restored assembler
        must hand the resumed run the exact remaining suffix — no window
        fires twice, none is skipped."""
        d = tmp_path / "p"
        d.mkdir()
        with pytest.raises(InjectedFault):
            _range_pipeline(
                str(d),
                fault_plan=[{"point": "window.feed", "at": 70,
                             "times": 10_000}],
            )
        ck = load_checkpoint(str(d / "ckpt.bin"))
        consumed = ck["driver"]["events_consumed"]
        assert 0 < consumed < 120
        drv = _range_pipeline(str(d))
        # the resumed leg consumes exactly the remaining suffix — the
        # full stream is seen once across both legs
        assert drv.stats["events"] == 120 - consumed

    def test_checkpoint_carries_egress_marker_and_backend(self, tmp_path):
        d = tmp_path / "p"
        d.mkdir()
        _range_pipeline(str(d))
        ck = load_checkpoint(str(d / "ckpt.bin"))
        assert ck["egress"]["bytes"] == \
            os.path.getsize(str(d / "egress.csv"))
        assert ck["driver"]["backend"] == "device"
        assert ck["driver"]["events_consumed"] == 120

    def test_corrupt_checkpoint_fails_loudly_on_resume(self, tmp_path):
        d = tmp_path / "p"
        d.mkdir()
        _range_pipeline(str(d))
        path = str(d / "ckpt.bin")
        data = open(path, "rb").read()
        with open(path, "wb") as f:
            f.write(data[:-5])
        with pytest.raises(CheckpointCorruptError, match="truncated"):
            _range_pipeline(str(d))

    def test_commit_span_holds_its_four_parts_in_order(self, tmp_path):
        """Every published checkpoint is one ``commit`` span; egress
        append, state gathering, pickle and the durable write lie
        inside it, in that order, and account for it."""
        d = tmp_path / "p"
        d.mkdir()
        telemetry.enable()
        drv = _range_pipeline(str(d))
        spans = [e for e in telemetry.events if e.get("ph") == "X"]
        commits = [e for e in spans if e["name"] == "commit"]
        assert len(commits) == drv.stats["checkpoints"] > 1
        parts = ("commit.egress", "commit.state", "checkpoint.pickle",
                 "checkpoint.write")
        for c in commits:
            lo, hi = c["ts"], c["ts"] + c["dur"]
            inside = [e for e in spans if e["name"] in parts
                      and lo - 1 <= e["ts"] and e["ts"] + e["dur"] <= hi + 1]
            assert [e["name"] for e in inside] == list(parts)
            for a, b in zip(inside, inside[1:]):
                assert a["ts"] + a["dur"] <= b["ts"] + 1
        # one of each part per commit: nothing strays outside a commit
        for name in parts:
            assert sum(e["name"] == name for e in spans) == len(commits)

    def test_checkpoint_write_span_carries_the_files_size(self, tmp_path):
        d = tmp_path / "p"
        d.mkdir()
        telemetry.enable()
        _range_pipeline(str(d))
        writes = [e for e in telemetry.events
                  if e["name"] == "checkpoint.write"]
        assert writes[-1]["args"]["bytes"] == \
            os.path.getsize(str(d / "ckpt.bin"))
        assert all(type(e["args"]["bytes"]) is int for e in writes)

    def test_commit_without_a_checkpoint_path_emits_no_commit_span(self):
        telemetry.enable()
        _run_range(driver=WindowedDataflowDriver())
        assert not [e for e in telemetry.events
                    if e["name"].startswith(("commit", "checkpoint."))]

    def test_checkpoints_identical_with_telemetry_on_and_off(self, tmp_path):
        off, on = tmp_path / "off", tmp_path / "on"
        off.mkdir()
        on.mkdir()
        _range_pipeline(str(off))
        telemetry.enable()
        _range_pipeline(str(on))
        telemetry.disable()
        assert (on / "egress.csv").read_bytes() == \
            (off / "egress.csv").read_bytes()
        a, b = (load_checkpoint(str(x / "ckpt.bin")) for x in (off, on))
        assert a["driver"] == b["driver"] and a["egress"] == b["egress"]

    def test_run_windows_rejects_checkpointing(self):
        drv = WindowedDataflowDriver(checkpoint_path="x.bin")
        drv.op = object()
        drv.process = lambda w: w
        with pytest.raises(ValueError, match="run_windows"):
            list(drv.run_windows(iter([])))


class TestDialDeadline:
    """The driver's bounded first device touch (the bench dial-deadline
    semantics): a --checkpoint resume on an unreachable device must die in
    bounded time with the ledger stream sealed ``dial_timeout``, never
    hang forever."""

    def test_resolution_order(self, monkeypatch):
        from spatialflink_tpu.driver import resolve_dial_deadline_s

        monkeypatch.delenv("SFT_DIAL_DEADLINE_S", raising=False)
        assert resolve_dial_deadline_s() == 0.0  # unset env → disabled
        monkeypatch.setenv("SFT_DIAL_DEADLINE_S", "7.5")
        assert resolve_dial_deadline_s() == 7.5
        assert resolve_dial_deadline_s(2.0) == 2.0  # explicit wins

    def test_first_window_hang_fires_watchdog_and_seals(
            self, tmp_path, monkeypatch):
        import time as _time

        import spatialflink_tpu.driver as driver_mod

        fired = []
        monkeypatch.setattr(driver_mod, "_dial_timeout_exit",
                            fired.append)
        stream = tmp_path / "run.stream.jsonl"
        telemetry.enable(stream_path=str(stream),
                         stream_flush_interval_s=0.0)
        grid, conf, source, query = _toy_pipeline()
        op = PointPointRangeQuery(conf, grid)
        drv = WindowedDataflowDriver(dial_deadline_s=0.05)

        def slow_first(win):
            _time.sleep(0.4)  # the wedge: > deadline on window 1 only
            return win

        drv.bind(op, slow_first)
        out = list(drv.run(source()))
        assert out  # the recorder exit hook let the run complete
        assert fired == [driver_mod.DIAL_TIMEOUT_EXIT_CODE]
        telemetry.disable()
        recs = [json.loads(ln)
                for ln in stream.read_text().splitlines()]
        sealed = [r for r in recs if r.get("t") == "epilogue"]
        # The watchdog's seal wins; disable() cannot double-seal.
        assert [r["reason"] for r in sealed] == ["dial_timeout"]

    def test_fast_first_window_never_fires(self, monkeypatch):
        import spatialflink_tpu.driver as driver_mod

        fired = []
        monkeypatch.setattr(driver_mod, "_dial_timeout_exit",
                            fired.append)
        grid, conf, source, query = _toy_pipeline()
        op = PointPointRangeQuery(conf, grid)
        drv = WindowedDataflowDriver(dial_deadline_s=5.0)
        drv.bind(op, lambda win: win)
        out = list(drv.run(source()))
        assert out and fired == []
        assert drv._dialed is True  # later windows never re-arm


class TestTransactionalSink:
    def test_partial_write_is_repaired_on_restore(self, tmp_path):
        """A torn (fsync'd!) half-append dies mid-commit; restore from
        the checkpointed marker truncates it and the replay regenerates
        the records — no gap, no dup."""
        path = str(tmp_path / "out.csv")
        s = TransactionalFileSink(path)
        s.reset()
        s.stage("one")
        marker = s.commit()
        s.stage("two")
        s.stage("three")
        faults.arm([{"point": "sink.write", "kind": "partial_write"}])
        with pytest.raises(InjectedFault):
            s.commit()
        faults.disarm()
        torn = open(path, "rb").read()
        assert torn != b"one\n"  # bytes really landed past the marker
        s2 = TransactionalFileSink(path)
        s2.restore(marker)
        assert open(path, "rb").read() == b"one\n"
        s2.stage("two")
        s2.stage("three")
        s2.commit()
        assert open(path, "rb").read() == b"one\ntwo\nthree\n"

    def test_restore_missing_committed_bytes_is_corrupt(self, tmp_path):
        path = str(tmp_path / "out.csv")
        s = TransactionalFileSink(path)
        s.reset()
        s.stage("a" * 100)
        marker = s.commit()
        with open(path, "wb") as f:
            f.write(b"a" * 10)  # committed egress lost out-of-band
        with pytest.raises(CheckpointCorruptError, match="out-of-band"):
            TransactionalFileSink(path).restore(marker)

    def test_exception_path_never_publishes_staged_records(self, tmp_path):
        path = str(tmp_path / "out.csv")
        with pytest.raises(RuntimeError, match="boom"):
            with TransactionalFileSink(path) as s:
                s.reset()
                s.stage("doomed")
                raise RuntimeError("boom")
        assert open(path, "rb").read() == b""

    def test_header_counts_into_committed_bytes(self, tmp_path):
        path = str(tmp_path / "out.csv")
        s = TransactionalFileSink(path, header="h1,h2")
        s.reset()
        s.stage("1,2")
        marker = s.commit()
        assert open(path).read() == "h1,h2\n1,2\n"
        s2 = TransactionalFileSink(path, header="h1,h2")
        s2.restore(marker)
        assert open(path).read() == "h1,h2\n1,2\n"


class TestRejectedConfigPreservesEgress:
    def test_rejected_run_windows_does_not_wipe_prior_egress(self, tmp_path):
        """A driver rejected before running (run_windows + checkpoint is
        invalid) must not have truncated a previous run's committed
        egress during attach/load."""
        path = str(tmp_path / "out.csv")
        prior = TransactionalFileSink(path)
        prior.reset()
        prior.stage("precious")
        prior.commit()

        grid, conf, source, query = _toy_pipeline()
        sink = TransactionalFileSink(path)
        drv = WindowedDataflowDriver(
            checkpoint_path=str(tmp_path / "ck.bin"), sink=sink)
        drv.bind(PointPointRangeQuery(conf, grid), lambda w: w)
        with pytest.raises(ValueError, match="run_windows"):
            list(drv.run_windows(iter([])))
        assert open(path, "rb").read() == b"precious\n"
