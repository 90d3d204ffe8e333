"""Overload control (spatialflink_tpu/overload.py): bounded admission
(backpressure vs counted shedding), watermark-aware late/oldest-first
shedding, the SLO-driven degradation ladder and its rung effects, the
device-path circuit breaker, checkpointed shed determinism, and the
live/post-hoc SLO budget twins (shed_budget / degraded_window_budget).
"""

import json
import os
import sys

import numpy as np
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if REPO not in sys.path:
    sys.path.insert(0, REPO)

from spatialflink_tpu import overload, slo  # noqa: E402
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
from spatialflink_tpu.overload import (  # noqa: E402
    OverloadController,
    OverloadPolicy,
)
from spatialflink_tpu.streams.sinks import (  # noqa: E402
    TransactionalFileSink,
)
from spatialflink_tpu.telemetry import telemetry  # noqa: E402


@pytest.fixture(autouse=True)
def _clean():
    yield
    slo.uninstall()
    overload.uninstall()
    faults.disarm()
    telemetry.disable()


class _Ev:
    def __init__(self, ts):
        self.timestamp = int(ts)


def _event_names():
    return [e["name"] for e in telemetry.events]


# ---------------------------------------------------------------------------
# Policy parsing


class TestPolicy:
    def test_strict_parse_rejects_unknown_keys(self):
        with pytest.raises(ValueError, match="unknown overload policy"):
            OverloadPolicy.from_dict({"max_bufferd_events": 8})

    def test_strict_parse_rejects_unknown_rung_action(self):
        with pytest.raises(ValueError, match="unknown action"):
            OverloadPolicy(ladder=[{"action": "turbo"}])

    def test_strict_parse_rejects_unknown_rung_key(self):
        with pytest.raises(ValueError, match="unknown keys"):
            OverloadPolicy(ladder=[{"action": "batch_slides", "N": 4}])

    def test_dict_roundtrip_and_env_forms(self, tmp_path):
        p = OverloadPolicy(max_buffered_events=8, lag_shed_ceiling_ms=500,
                           ladder=[{"action": "clamp_compaction"}])
        assert OverloadPolicy.from_dict(p.to_dict()) == p
        assert OverloadPolicy.from_env(json.dumps(p.to_dict())) == p
        f = tmp_path / "policy.json"
        f.write_text(json.dumps(p.to_dict()))
        assert OverloadPolicy.from_env(str(f)) == p

    def test_version_mismatch_raises(self):
        with pytest.raises(ValueError, match="overload_version"):
            OverloadPolicy.from_dict({"overload_version": 99})

    def test_strict_parse_rejects_bad_rung_values(self):
        """Value typos must fail at SFT_OVERLOAD_POLICY load, not become
        a silent no-op rung (pane_backend targeting nothing) or a
        mid-overload crash at the first step-down (non-int cap/n)
        (r9 code review)."""
        with pytest.raises(ValueError, match="unknown target"):
            OverloadPolicy(ladder=[{"action": "pane_backend",
                                    "to": "devise"}])
        with pytest.raises(ValueError, match="cap must be"):
            OverloadPolicy(ladder=[{"action": "clamp_compaction",
                                    "cap": "top"}])
        with pytest.raises(ValueError, match="cap must be"):
            OverloadPolicy(ladder=[{"action": "clamp_compaction",
                                    "cap": -1}])
        with pytest.raises(ValueError, match="n must be"):
            OverloadPolicy(ladder=[{"action": "batch_slides",
                                    "n": "four"}])
        with pytest.raises(ValueError, match="n must be"):
            OverloadPolicy(ladder=[{"action": "batch_slides", "n": 0}])


# ---------------------------------------------------------------------------
# Bounded admission


class TestAdmission:
    def test_non_pausable_sheds_beyond_budget(self):
        telemetry.enable()
        c = OverloadController(OverloadPolicy(max_buffered_events=3,
                                              admission_window_ms=10_000))
        verdicts = [c.admit_item(_Ev(t), pausable=False)
                    for t in range(0, 80, 10)]
        assert verdicts[:3] == [True] * 3
        assert verdicts[3:] == [False] * 5
        snap = c.snapshot()
        assert snap["shed"]["admission"]["events"] == 5
        assert snap["shed_total"] == 5
        # Transition, not spam: ONE shedding event for the burst.
        assert _event_names().count("overload_shedding:admission") == 1

    def test_pausable_backpressures_instead_of_shedding(self):
        telemetry.enable()
        c = OverloadController(OverloadPolicy(max_buffered_events=3))
        assert all(c.admit_item(_Ev(t), pausable=True)
                   for t in range(0, 80, 10))
        snap = c.snapshot()
        assert snap["shed_total"] == 0
        assert snap["backpressure_engaged"] == 1
        assert "overload_backpressure:engaged" in _event_names()
        # A fired window drains the burst and releases the signal.
        c.on_window_fired(3, lag_ms=0.0, end=1000)
        assert "overload_backpressure:released" in _event_names()

    def test_event_time_horizon_resets_the_burst(self):
        """Shed events never advance the watermark, so the burst budget
        must reset on EVENT TIME — otherwise one blown budget starves
        the stream forever."""
        c = OverloadController(OverloadPolicy(max_buffered_events=2,
                                              admission_window_ms=1000))
        assert c.admit_item(_Ev(0), pausable=False)
        assert c.admit_item(_Ev(10), pausable=False)
        assert not c.admit_item(_Ev(20), pausable=False)
        # Past the horizon: a new burst interval, admission resumes.
        assert c.admit_item(_Ev(2000), pausable=False)
        assert c.snapshot()["shed_total"] == 1

    def test_chunks_measured_by_arrays_and_bytes(self):
        c = OverloadController(OverloadPolicy(
            max_buffered_bytes=100, admission_window_ms=10_000))
        chunk = {"ts": np.arange(4, dtype=np.int64),
                 "x": np.zeros(4), "y": np.zeros(4)}
        assert c.admit_item(chunk, pausable=False)  # 96 B admitted
        assert not c.admit_item(chunk, pausable=False)  # would be 192 B
        shed = c.snapshot()["shed"]["admission"]
        assert shed["events"] == 4 and shed["bytes"] > 0


# ---------------------------------------------------------------------------
# Watermark-aware shedding


def _lag_controller(**kw):
    kw.setdefault("lag_shed_ceiling_ms", 1000)
    kw.setdefault("lag_recover_ms", 100)
    kw.setdefault("shed_oldest_after_windows", 2)
    return OverloadController(OverloadPolicy(**kw))


class TestLagShedding:
    def test_lag_ceiling_enters_shed_mode_late_first(self):
        telemetry.enable()
        c = _lag_controller()
        c.admit_item(_Ev(5000), pausable=False)  # stream head
        c.on_window_fired(5, lag_ms=4000.0, end=1000)  # way over ceiling
        assert c.snapshot()["shedding"] is True
        assert "overload_shedding:lag" in _event_names()
        # Late-first: an out-of-order straggler sheds...
        assert not c.admit_item(_Ev(1200), pausable=False)
        assert c.snapshot()["shed"]["late"]["events"] == 1
        # ...the stream head does not.
        assert c.admit_item(_Ev(6000), pausable=False)

    def test_escalates_to_oldest_then_recovers(self):
        telemetry.enable()
        c = _lag_controller()
        c.admit_item(_Ev(5000), pausable=False)
        c.on_window_fired(5, lag_ms=4000.0, end=1000)  # enter
        c.on_window_fired(5, lag_ms=4000.0, end=2000)  # still behind 1
        c.on_window_fired(5, lag_ms=4000.0, end=3000)  # still behind 2 → escalate
        assert "overload_shedding:oldest" in _event_names()
        # Oldest-first: an in-order event destined only for the
        # already-behind windows sheds too.
        assert not c.admit_item(_Ev(2500), pausable=False)
        assert c.snapshot()["shed"]["oldest"]["events"] == 1
        # Recovery below the floor exits BOTH modes, transition event.
        c.on_window_fired(5, lag_ms=50.0, end=4000)
        assert c.snapshot()["shedding"] is False
        assert "overload_recovered:lag" in _event_names()
        assert c.admit_item(_Ev(3500), pausable=False)

    def test_shed_schedule_is_deterministic(self):
        """Same stream → same sheds, run to run (the chaos matrix's
        byte-identical-resume premise)."""
        def run_once():
            c = _lag_controller(max_buffered_events=4,
                                admission_window_ms=500)
            rng = np.random.default_rng(3)
            for i in range(300):
                ts = int(rng.integers(0, 20_000))
                c.admit_item(_Ev(ts), pausable=False)
                if i % 7 == 0:
                    c.on_window_fired(3, lag_ms=float(ts % 3000),
                                      end=ts - (ts % 1000))
            return c.snapshot()["shed"]

        assert run_once() == run_once()


# ---------------------------------------------------------------------------
# Degradation ladder


LADDER = (
    {"action": "clamp_compaction", "cap": 32},
    {"action": "batch_slides", "n": 3},
    {"action": "pane_backend", "to": "native"},
)


class TestLadder:
    def test_steps_down_apply_cumulative_effects(self):
        telemetry.enable()
        c = overload.install(OverloadController(OverloadPolicy(
            ladder=LADDER, degrade_cooldown=1, recover_after=2)))
        assert (overload.compaction_clamp(), overload.batch_slides(),
                overload.pane_backend()) == (None, 1, None)
        c.on_slo_evaluation(False)
        assert overload.compaction_clamp() == 32
        c.on_slo_evaluation(False)
        assert overload.batch_slides() == 3
        c.on_slo_evaluation(False)
        assert overload.pane_backend() == "native"
        assert c.rung == 3
        names = _event_names()
        assert "overload_rung_down:clamp_compaction" in names
        assert "overload_rung_down:batch_slides" in names
        assert "overload_rung_down:pane_backend" in names

    def test_sustained_recovery_steps_back_up(self):
        telemetry.enable()
        c = overload.install(OverloadController(OverloadPolicy(
            ladder=LADDER, degrade_cooldown=1, recover_after=2)))
        c.on_slo_evaluation(False)
        c.on_slo_evaluation(False)
        assert c.rung == 2
        for _ in range(4):  # 2 healthy windows per rung
            c.on_window_fired(5, lag_ms=0.0, end=1000)
        assert c.rung == 0
        names = _event_names()
        assert "overload_rung_up:batch_slides" in names
        assert "overload_rung_up:clamp_compaction" in names
        assert (overload.compaction_clamp(), overload.batch_slides(),
                overload.pane_backend()) == (None, 1, None)

    def test_midband_lag_is_neutral_for_the_ladder(self):
        """recover < lag ≤ ceiling without shed mode steps the ladder
        NEITHER down (the documented triggers are shed / backpressure /
        SLO violations only) nor up (not recovered — the healthy streak
        breaks) (r9 code review)."""
        telemetry.enable()
        ctrl = overload.install(OverloadController(OverloadPolicy(
            lag_shed_ceiling_ms=5_000, lag_recover_ms=2_500,
            ladder=({"action": "batch_slides", "n": 2},),
            degrade_cooldown=1, recover_after=2)))
        for _ in range(6):
            ctrl.on_window_fired(1, lag_ms=3_000.0)
        assert ctrl.rung == 0  # sustained mid-band lag never steps down
        ctrl.on_slo_evaluation(False)
        assert ctrl.rung == 1
        for _ in range(4):  # mid-band windows don't count as recovery…
            ctrl.on_window_fired(1, lag_ms=3_000.0)
        assert ctrl.rung == 1
        for _ in range(2):  # …sustained lag ≤ recover does
            ctrl.on_window_fired(1, lag_ms=1_000.0)
        assert ctrl.rung == 0

    def test_sustained_admission_shedding_holds_the_rung_down(self):
        """A fired window amid ongoing admission sheds is NOT a healthy
        observation: the ladder must not step back up (un-clamping
        compaction, re-starting recompile churn) while every cycle is
        still shedding. Backpressure engaged during the cycle counts
        the same way — the fire-site check reads the cycle's state
        captured BEFORE the per-fire resets (r9 code review)."""
        ctrl = overload.install(OverloadController(OverloadPolicy(
            max_buffered_events=2, admission_window_ms=10_000,
            ladder=({"action": "clamp_compaction", "cap": 0},),
            degrade_cooldown=1, recover_after=3)))
        ctrl.on_slo_evaluation(False)  # length-1 ladder: rung 1 is the floor
        assert ctrl.rung == 1
        for cycle in range(6):  # sustained burst: 5 events per fire
            for i in range(5):
                ctrl.admit_item(_Ev(cycle * 100 + i), pausable=False)
            ctrl.on_window_fired(5, lag_ms=0.0, end=cycle * 100)
            assert ctrl.rung == 1, f"rung stepped up mid-shed @ {cycle}"
        assert ctrl.shed_total > 0
        # Same contract for a pausable source: backpressure engaged
        # during the cycle breaks the healthy streak at the fire.
        ctrl2 = overload.install(OverloadController(OverloadPolicy(
            max_buffered_events=2, admission_window_ms=10_000,
            ladder=({"action": "clamp_compaction", "cap": 0},),
            degrade_cooldown=1, recover_after=3)))
        ctrl2.on_slo_evaluation(False)
        assert ctrl2.rung == 1
        for cycle in range(6):
            for i in range(5):
                ctrl2.admit_item(_Ev(cycle * 100 + i), pausable=True)
            ctrl2.on_window_fired(5, lag_ms=0.0, end=cycle * 100)
            assert ctrl2.rung == 1, f"rung stepped up mid-bp @ {cycle}"
        # Once the burst ends, sustained clean fires DO recover.
        for cycle in range(6, 9):
            ctrl2.on_window_fired(1, lag_ms=0.0, end=cycle * 100)
        assert ctrl2.rung == 0

    def test_live_slo_violation_drives_the_ladder(self):
        """The wiring contract: SloEngine.evaluate → overload hook."""
        telemetry.enable()
        ctrl = overload.install(OverloadController(OverloadPolicy(
            ladder=LADDER, degrade_cooldown=1)))
        eng = slo.install(slo.SloEngine(slo.SloSpec(
            late_drop_budget=0, eval_interval_s=0.0)))
        telemetry.record_late_drop(3)  # bust the budget
        eng.evaluate()
        assert ctrl.rung == 1

    def test_pick_capacity_honors_the_clamp(self):
        from spatialflink_tpu.ops.compaction import pick_capacity

        assert pick_capacity(3, 64) == 8  # ladder floor, unclamped
        overload.install(OverloadController(OverloadPolicy(
            ladder=({"action": "clamp_compaction", "cap": 32},),
            degrade_cooldown=1))).on_slo_evaluation(False)
        assert pick_capacity(3, 64) == 32  # floored at the clamp rung
        assert pick_capacity(60, 64) == 64  # exactness still wins
        overload.uninstall()
        overload.install(OverloadController(OverloadPolicy(
            ladder=({"action": "clamp_compaction", "cap": 0},),
            degrade_cooldown=1))).on_slo_evaluation(False)
        assert pick_capacity(3, 64) == 64  # cap 0 = pin the top rung

    def test_traj_stats_auto_backend_biased_host(self):
        """An active pane_backend rung routes backend="auto" away from
        the device engine — and the three engines answer identically,
        so this is pure routing, not results."""
        from spatialflink_tpu.streams import panes

        ctrl = overload.install(OverloadController(OverloadPolicy(
            ladder=({"action": "pane_backend", "to": "native"},),
            degrade_cooldown=1)))
        ctrl.on_slo_evaluation(False)
        ts = np.arange(0, 4000, 100, dtype=np.int64)
        xy = np.stack([np.linspace(0, 1, len(ts)),
                       np.zeros(len(ts))], axis=1)
        oid = (np.arange(len(ts)) % 3).astype(np.int64)
        a = panes.traj_stats_sliding(ts, xy, oid, 3, 1000, 500,
                                     backend="auto")
        b = panes.traj_stats_sliding(ts, xy, oid, 3, 1000, 500,
                                     backend="numpy")
        np.testing.assert_array_equal(a.starts, b.starts)
        np.testing.assert_allclose(a.spatial, b.spatial)


# ---------------------------------------------------------------------------
# Circuit breaker


def _run_range(driver=None, n_events=120):
    grid, conf, source, query = _toy_pipeline(n_events=n_events)
    op = PointPointRangeQuery(conf, grid)
    return list(op.run(source(), [query], 1.5, driver=driver))


class TestCircuitBreaker:
    def test_open_fallback_probe_close_round_trip(self):
        telemetry.enable()
        base = _run_range()
        # Device path fails for exactly 2 windows → the circuit opens;
        # while open, windows route to the twin with NO device attempt;
        # the 3rd fallback window half-opens for a probe, which succeeds
        # and closes the circuit — the device path comes BACK (unlike
        # permanent failover).
        ctrl = OverloadController(OverloadPolicy(
            breaker_failures=2, breaker_probe_every=3))
        drv = WindowedDataflowDriver(
            retry=RetryPolicy(max_retries=0, backoff_s=0.0), overload=ctrl)
        faults.arm([{"point": "driver.window", "at": 1, "times": 2}])
        driven = _run_range(driver=drv)
        faults.disarm()
        br = ctrl.breaker
        assert br.state == "closed"
        assert br.opens == 1 and br.probes == 1
        assert drv.backend == "device"  # never permanently failed over
        assert drv.stats["failovers"] == 0
        # windows 1-2 (device failures) + 3-4 (circuit open) = degraded
        assert ctrl.degraded_windows == 4
        names = _event_names()
        assert "circuit_open" in names
        assert "circuit_half_open" in names
        assert "circuit_closed" in names
        # Result parity across every route (device / twin / probe).
        assert len(driven) == len(base) > 5
        for a, b in zip(base, driven):
            assert [p.obj_id for p in a.objects] == \
                   [p.obj_id for p in b.objects]
            np.testing.assert_allclose(a.dists, b.dists, rtol=3e-7)

    def test_probe_failure_reopens(self):
        ctrl = OverloadController(OverloadPolicy(
            breaker_failures=1, breaker_probe_every=2))
        drv = WindowedDataflowDriver(
            retry=RetryPolicy(max_retries=0, backoff_s=0.0), overload=ctrl)
        # Every device attempt fails: open stays open through probes.
        faults.arm([{"point": "driver.window", "at": 1, "times": 10_000}])
        driven = _run_range(driver=drv)
        faults.disarm()
        assert ctrl.breaker.state == "open"
        assert ctrl.breaker.probes >= 2
        assert len(driven) > 5  # the twin carried the whole run

    def test_link_degraded_ratio_opens_preemptively(self):
        telemetry.enable()
        ctrl = OverloadController(OverloadPolicy(
            breaker_failures=9, breaker_link_ratio=0.5))
        # p50 100 MB/s → last 10 MB/s: ratio 0.1 < 0.5.
        for mbps in (100.0, 100.0, 100.0, 10.0):
            telemetry.record_link_sample(1.0, mbps, 1 << 18)
        assert ctrl.breaker.route() == "fallback"
        assert ctrl.breaker.state == "open"
        assert "circuit_open" in _event_names()

    def test_probe_close_not_reopened_by_stale_link_gauges(self):
        """A probe-success close sticks until a FRESHER LinkProbe sample
        arrives: probes only run at bench phase boundaries, so re-reading
        the same degraded sample would flap the circuit
        open→probe→closed→open forever (r9 code review)."""
        telemetry.enable()
        ctrl = OverloadController(OverloadPolicy(
            breaker_link_ratio=0.5, breaker_probe_every=1))
        for mbps in (100.0, 100.0, 100.0, 10.0):
            telemetry.record_link_sample(1.0, mbps, 1 << 18)
        br = ctrl.breaker
        assert br.route() == "fallback" and br.state == "open"
        assert br.route() == "probe"  # half-open re-dial
        br.record_success()  # the device path provably works again
        assert br.state == "closed"
        # The SAME stale degraded sample must not re-open the circuit.
        assert br.route() == "device"
        assert br.state == "closed" and br.opens == 1
        # A fresh degraded sample re-arms the ratio check.
        telemetry.record_link_sample(1.0, 5.0, 1 << 18)
        assert br.route() == "fallback"
        assert br.opens == 2

    def test_link_only_policy_ignores_failure_counts(self):
        """breaker_failures=0 disables count-based opening even when a
        link-ratio-only policy instantiates the breaker (the documented
        '0 disables' contract) (r9 code review)."""
        ctrl = OverloadController(OverloadPolicy(breaker_link_ratio=0.5))
        br = ctrl.breaker
        assert br is not None
        for _ in range(5):
            br.record_failure(window_start=0, error="boom")
        assert br.state == "closed"
        assert br.opens == 0

    def test_without_breaker_permanent_failover_is_preserved(self):
        ctrl = OverloadController(OverloadPolicy())  # no breaker config
        assert ctrl.breaker is None
        drv = WindowedDataflowDriver(
            retry=RetryPolicy(max_retries=0, backoff_s=0.0), overload=ctrl)
        faults.arm([{"point": "driver.window", "at": 1, "times": 10_000}])
        driven = _run_range(driver=drv)
        faults.disarm()
        assert drv.backend == "fallback"  # PR 8 semantics unchanged
        assert drv.stats["failovers"] == 1
        assert ctrl.degraded_windows == len(driven)


# ---------------------------------------------------------------------------
# Driver integration: admission + checkpointed shed determinism


def _shedding_pipeline(workdir, fault_plan=None):
    """Range pipeline under a tiny admission budget over a NON-pausable
    source: sheds are part of the committed stream position."""
    grid, conf, source, query = _toy_pipeline()
    sink = TransactionalFileSink(os.path.join(workdir, "egress.csv"))
    # The toy stream runs 10 events per 1000 ms of event time: a budget
    # of 3 per 500 ms horizon sheds ~2 of every 5 — a sustained burst.
    ctrl = OverloadController(OverloadPolicy(max_buffered_events=3,
                                             admission_window_ms=500))
    driver = WindowedDataflowDriver(
        checkpoint_path=os.path.join(workdir, "ckpt.bin"),
        checkpoint_every=1, sink=sink,
        retry=RetryPolicy(max_retries=1, backoff_s=0.0), failover=False,
        overload=ctrl, source_pausable=False,
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
    return driver, ctrl


class TestDriverIntegration:
    def test_no_budget_controller_changes_nothing(self):
        base = _run_range()
        ctrl = OverloadController(OverloadPolicy())
        driven = _run_range(driver=WindowedDataflowDriver(overload=ctrl))
        assert ctrl.shed_total == 0
        assert len(driven) == len(base)
        for a, b in zip(base, driven):
            assert [p.obj_id for p in a.objects] == \
                   [p.obj_id for p in b.objects]
            np.testing.assert_array_equal(a.dists, b.dists)

    def test_sheds_count_consumed_and_survive_kill_mid_shed(self, tmp_path):
        """The acceptance round trip in-process: a burst run sheds
        deterministically, dies mid-shed, and resumes to byte-identical
        committed egress with the SAME total shed schedule."""
        clean = tmp_path / "clean"
        chaos = tmp_path / "chaos"
        clean.mkdir()
        chaos.mkdir()
        drv, ctrl = _shedding_pipeline(str(clean))
        want = (clean / "egress.csv").read_bytes()
        clean_sheds = ctrl.snapshot()["shed"]
        assert want and ctrl.shed_total > 0, "vacuous: nothing shed"
        assert drv.stats["shed"] == ctrl.shed_total
        # Kill while the admission path is actively shedding.
        with pytest.raises(InjectedFault):
            _shedding_pipeline(str(chaos), fault_plan=[
                {"point": "overload.admit", "at": 40, "times": 10_000},
            ])
        drv2, ctrl2 = _shedding_pipeline(str(chaos))  # resume
        assert drv2.stats["resumed"] is True
        assert (chaos / "egress.csv").read_bytes() == want
        assert ctrl2.snapshot()["shed"] == clean_sheds

    def test_overload_state_rides_the_checkpoint(self, tmp_path):
        drv, ctrl = _shedding_pipeline(str(tmp_path))
        from spatialflink_tpu.checkpoint import load_checkpoint

        ck = load_checkpoint(str(tmp_path / "ckpt.bin"))
        assert ck["overload"]["shed"] == ctrl.snapshot()["shed"]

    def test_driver_restores_a_preinstalled_controller(self):
        """A controller installed BEFORE the run (bench's
        SFT_OVERLOAD_POLICY global) is restored when the driver's loop
        ends — the ledger seal must read the global slot, not a stale
        driver-owned controller (r9 code review)."""
        global_ctrl = overload.install(OverloadController(OverloadPolicy()))
        drv_ctrl = OverloadController(OverloadPolicy())
        _run_range(driver=WindowedDataflowDriver(overload=drv_ctrl))
        assert overload.controller() is global_ctrl

    def test_run_windows_installs_the_controller_too(self):
        """Count-window runs (run_windows — no event stream) must
        install the driver's controller like _drive does: a breaker
        counting degraded windows there otherwise stays invisible to
        the SLO budgets (silence-fails a configured
        degraded_window_budget) and the rung getters (r9 code review)."""
        drv_ctrl = OverloadController(OverloadPolicy())
        drv = WindowedDataflowDriver(overload=drv_ctrl)
        drv.op = object()
        drv.process = lambda w: w
        seen = []
        for _ in drv.run_windows(iter([1, 2])):
            seen.append(overload.controller())
        assert seen == [drv_ctrl, drv_ctrl]
        assert overload.controller() is drv_ctrl  # empty slot: stays

    def test_driver_controller_stays_installed_without_a_prior_one(self):
        """With an empty slot, the driver's controller stays installed
        after the run: uninstalling to None would turn the run's real
        shed counters into a silence-fails budget violation at the
        ledger-seal SLO verdict."""
        assert overload.controller() is None
        drv_ctrl = OverloadController(OverloadPolicy())
        _run_range(driver=WindowedDataflowDriver(overload=drv_ctrl))
        assert overload.controller() is drv_ctrl


# ---------------------------------------------------------------------------
# SLO budgets: live engine + post-hoc twin


class TestSloBudgets:
    def test_live_shed_budget_reads_the_controller(self):
        telemetry.enable()
        ctrl = overload.install(OverloadController(OverloadPolicy(
            max_buffered_events=1, admission_window_ms=10_000)))
        for t in range(5):
            ctrl.admit_item(_Ev(t), pausable=False)
        eng = slo.SloEngine(slo.SloSpec(shed_budget=2,
                                        degraded_window_budget=0))
        rows = {r["check"]: r for r in eng.evaluate()}
        assert rows["shed_budget"]["ok"] is False
        assert rows["shed_budget"]["value"] == 4
        assert rows["degraded_window_budget"]["ok"] is True

    def test_live_budget_fails_on_silence(self):
        """A spec naming shed_budget with NO controller installed must
        violate — the gate cannot pass on silence."""
        telemetry.enable()
        eng = slo.SloEngine(slo.SloSpec(shed_budget=1000))
        rows = {r["check"]: r for r in eng.evaluate()}
        assert rows["shed_budget"]["ok"] is False
        assert rows["shed_budget"]["value"] is None

    def test_posthoc_twin_reads_the_ledger_block(self, tmp_path):
        telemetry.enable()
        ctrl = overload.install(OverloadController(OverloadPolicy(
            max_buffered_events=1, admission_window_ms=10_000)))
        for t in range(4):
            ctrl.admit_item(_Ev(t), pausable=False)
        ctrl.count_degraded_window()
        ledger = tmp_path / "ledger.json"
        telemetry.write_ledger(str(ledger), capture_costs=False)
        doc = json.loads(ledger.read_text())
        assert doc["snapshot"]["overload"]["shed_total"] == 3

        from tools.sfprof import slo as sfslo

        rows = sfslo.evaluate(
            {"shed_budget": 2, "degraded_window_budget": 0}, doc)
        assert rows == [
            ("slo:shed_budget", 3.0, "<= 2", False),
            ("slo:degraded_window_budget", 1.0, "<= 0", False),
        ]
        rows = sfslo.evaluate(
            {"shed_budget": 10, "degraded_window_budget": 5}, doc)
        assert all(r[3] for r in rows)

    def test_posthoc_twin_fails_on_silence(self):
        from tools.sfprof import slo as sfslo

        rows = sfslo.evaluate({"shed_budget": 1000},
                              {"snapshot": {}, "bench": {}})
        assert rows == [("slo:shed_budget", None, "<= 1000", False)]

    def test_spec_twin_field_sets_stay_in_sync(self):
        import dataclasses

        from tools.sfprof import slo as sfslo

        live = {f.name for f in dataclasses.fields(slo.SloSpec)}
        assert {"shed_budget", "degraded_window_budget"} <= live
        assert live == set(sfslo.SPEC_KEYS)


# ---------------------------------------------------------------------------
# sfprof health visibility


class TestHealthCli:
    def test_health_prints_overload_notes(self, tmp_path, capsys):
        telemetry.enable()
        ctrl = overload.install(OverloadController(OverloadPolicy(
            max_buffered_events=1, admission_window_ms=10_000,
            breaker_failures=2)))
        for t in range(4):
            ctrl.admit_item(_Ev(t), pausable=False)
        ctrl.count_degraded_window()
        ledger = tmp_path / "ledger.json"
        telemetry.write_ledger(str(ledger), capture_costs=False)

        from tools.sfprof.cli import main as sfprof_main

        assert sfprof_main(["health", str(ledger)]) == 0
        out = capsys.readouterr().out
        assert "note overload sheds: total=3 (admission=3)" in out
        assert "note overload degradation: rung=0" in out
        assert "note overload circuit: state=closed" in out

    def test_health_prints_backpressure_only_runs(self, tmp_path, capsys):
        """A run that only engaged backpressure (no sheds, no rungs, no
        degraded windows) still surfaces its overload note — the
        engaged count is the signal the line exists to report (r9 code
        review)."""
        telemetry.enable()
        ctrl = overload.install(OverloadController(OverloadPolicy(
            max_buffered_events=1, admission_window_ms=10_000)))
        for t in range(4):
            ctrl.admit_item(_Ev(t), pausable=True)  # pause, don't shed
        assert ctrl.shed_total == 0
        assert ctrl.backpressure_engaged > 0
        ledger = tmp_path / "ledger.json"
        telemetry.write_ledger(str(ledger), capture_costs=False)

        from tools.sfprof.cli import main as sfprof_main

        assert sfprof_main(["health", str(ledger)]) == 0
        out = capsys.readouterr().out
        assert (f"backpressure engaged "
                f"{int(ctrl.backpressure_engaged)}x") in out


# ---------------------------------------------------------------------------
# run_wire_panes batch_slides rung: batched fetches, identical results


def _wire_pane_setup():
    from spatialflink_tpu.grid import UniformGrid
    from spatialflink_tpu.models.objects import Point
    from spatialflink_tpu.operators import (
        QueryConfiguration,
        QueryType,
    )
    from spatialflink_tpu.operators.knn_query import PointPointKNNQuery
    from spatialflink_tpu.streams.wire import WireFormat

    grid = UniformGrid(20, 0.0, 10.0, 0.0, 10.0)
    wf = WireFormat.for_grid(grid)
    rng = np.random.default_rng(5)
    panes = []
    for _ in range(9):
        n = int(rng.integers(5, 40))
        xy = np.stack([rng.uniform(0, 10, n),
                       rng.uniform(0, 10, n)], axis=1)
        q = wf.quantize(xy)
        oid = rng.integers(0, 9, n).astype(np.int16)
        panes.append(np.ascontiguousarray(np.concatenate(
            [q, oid.view(np.uint16)[:, None]], axis=1).T))
    conf = QueryConfiguration(QueryType.WindowBased, window_size=3.0,
                              slide_step=1.0)
    qp = Point(obj_id="q", x=5.0, y=5.0)

    def make_op():
        return PointPointKNNQuery(conf, grid)

    def collect(gen):
        return [
            (s, e, list(map(int, segs)), [float(d) for d in dists], nv)
            for s, e, segs, dists, nv in gen
        ]

    return make_op, collect, panes, qp, wf


def _batching_controller():
    ctrl = overload.install(OverloadController(OverloadPolicy(
        ladder=({"action": "batch_slides", "n": 3},),
        degrade_cooldown=1)))
    ctrl.on_slo_evaluation(False)
    assert overload.batch_slides() == 3
    return ctrl


class TestBatchSlides:
    def test_wire_pane_results_identical_under_batching(self):
        make_op, collect, panes, qp, wf = _wire_pane_setup()

        def run():
            return collect(make_op().run_wire_panes(
                panes, qp, 2.0, 5, 16, wf))

        base = run()
        _batching_controller()
        assert run() == base

    def test_wire_pane_parents_close_before_a_batch_is_yielded(self):
        """Telemetry on under the rung: still one ``wire.pane`` a received
        pane, closed before the batch's first yield, so the consumer's time
        between the batch's results is in none."""
        from span_tiling import assert_parents_tile, slow_consumer
        from spatialflink_tpu.telemetry import telemetry

        make_op, collect, panes, qp, wf = _wire_pane_setup()
        base = collect(make_op().run_wire_panes(panes, qp, 2.0, 5, 16, wf))
        _batching_controller()
        naps = []
        telemetry.enable()
        try:
            got = collect(slow_consumer(make_op().run_wire_panes(
                panes, qp, 2.0, 5, 16, wf), naps, nap_s=0.005))
            events = list(telemetry.events)
        finally:
            telemetry.disable()
        assert got == base and len(naps) == len(base) == len(panes) + 2
        parents, inner = assert_parents_tile(events, "wire.pane", naps)
        assert len(parents) == len(panes)
        # one fetch a batch of three windows, inside the third pane's parent
        assert [names.count("d2h") for names in inner] == [0, 0, 1] * 3

    def test_mid_batch_checkpoint_never_loses_pending_windows(
            self, tmp_path):
        """A checkpoint taken at a yield while a batch_slides batch is
        open pairs with the last YIELDED window, not the last consumed
        pane: the pending (batched-but-unyielded) windows recompute on
        resume from the carry — never silently lost (r9 code review)."""
        from spatialflink_tpu.checkpoint import (
            load_checkpoint,
            operator_state,
            restore_operator,
            save_checkpoint,
        )

        make_op, collect, panes, qp, wf = _wire_pane_setup()
        base = collect(make_op().run_wire_panes(panes, qp, 2.0, 5, 16, wf))

        _batching_controller()
        op1 = make_op()
        gen = op1.run_wire_panes(panes, qp, 2.0, 5, 16, wf)
        head = []
        for tup in gen:
            head.append(tup)
            if len(head) == 2:  # suspended mid-flush — the batch is open
                break
        gen.close()
        state = operator_state(op1)
        cut = int(state["knn_wire_pane_carry"]["next_pane"])
        # Three panes were consumed (the width-3 batch filled at pane
        # 2) but only panes 0-1's windows were yielded — the carry must
        # lag at 2, not jump to 3 past the pending window.
        assert cut == 2
        path = str(tmp_path / "wire.ckpt")
        save_checkpoint(path, op=state)

        op2 = make_op()
        restore_operator(op2, load_checkpoint(path)["op"])
        rest = collect(op2.run_wire_panes(panes[cut:], qp, 2.0, 5, 16, wf))
        assert collect(iter(head)) + rest == base


# ---------------------------------------------------------------------------
# The per-commit smoke


def test_overload_smoke_round_trip():
    """The tools/ci stage, in-process: burst → shed → degrade → recover
    → every transition sealed in the stream, exit 0."""
    assert overload.smoke() == 0
