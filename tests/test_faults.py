"""Deterministic fault injection (spatialflink_tpu/faults.py): plan
parsing, trigger determinism, kinds, the disarmed-free contract, and
telemetry visibility of armed/fired faults."""

import json
import os
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if REPO not in sys.path:
    sys.path.insert(0, REPO)

from spatialflink_tpu.faults import (  # noqa: E402
    ABORT_EXIT_CODE,
    FaultInjector,
    FaultRule,
    InjectedFault,
    INJECTION_POINTS,
    faults,
    parse_plan,
)
from spatialflink_tpu.telemetry import telemetry  # noqa: E402


@pytest.fixture(autouse=True)
def _disarm():
    yield
    faults.disarm()
    telemetry.disable()


class TestPlanParsing:
    def test_unknown_point_raises(self):
        with pytest.raises(ValueError, match="unknown injection point"):
            parse_plan([{"point": "device.shipp"}])

    def test_unknown_kind_raises(self):
        with pytest.raises(ValueError, match="unknown fault kind"):
            parse_plan([{"point": "device.ship", "kind": "explode"}])

    def test_unknown_key_raises(self):
        with pytest.raises(ValueError, match="unknown keys"):
            parse_plan([{"point": "device.ship", "when": 3}])

    def test_partial_write_only_on_sink(self):
        with pytest.raises(ValueError, match="partial_write"):
            parse_plan([{"point": "device.ship", "kind": "partial_write"}])
        assert parse_plan(
            [{"point": "sink.write", "kind": "partial_write"}]
        )[0].kind == "partial_write"

    def test_single_object_is_one_rule_plan(self):
        assert len(parse_plan({"point": "window.feed"})) == 1

    def test_arm_accepts_inline_json_and_file(self, tmp_path):
        inj = FaultInjector()
        inj.arm('[{"point": "window.feed", "at": 2}]')
        assert inj.armed and inj.rules[0].at == 2
        p = tmp_path / "plan.json"
        p.write_text(json.dumps([{"point": "soa.feed", "times": 3}]))
        inj.arm(str(p))
        assert inj.rules[0].point == "soa.feed"
        assert inj.rules[0].times == 3

    def test_registry_names_every_threaded_point(self):
        # The chaos matrix iterates this registry — keep it exact.
        assert set(INJECTION_POINTS) == {
            "device.ship", "device.dispatch", "device.fetch",
            "window.feed", "soa.feed", "kafka.fetch", "kafka.leader",
            "sink.write", "driver.window",
            "overload.admit", "source.stall",
            "qserve.register", "dag.node", "dag.commit", "shard.exchange",
        }


class TestTriggers:
    def test_fires_at_exact_hit_count(self):
        inj = FaultInjector()
        inj.arm([{"point": "window.feed", "at": 3, "times": 2}])
        assert inj.hit("window.feed") is None
        assert inj.hit("window.feed") is None
        for expect_hit in (3, 4):
            with pytest.raises(InjectedFault) as ei:
                inj.hit("window.feed")
            assert ei.value.hit == expect_hit
        assert inj.hit("window.feed") is None  # budget spent
        assert len(inj.fired) == 2

    def test_points_count_independently(self):
        inj = FaultInjector()
        inj.arm([{"point": "device.ship", "at": 2}])
        assert inj.hit("device.fetch") is None
        assert inj.hit("device.ship") is None
        with pytest.raises(InjectedFault):
            inj.hit("device.ship")

    def test_seeded_prob_replays_identically(self):
        def firing_pattern():
            inj = FaultInjector()
            inj.arm([{"point": "window.feed", "at": 1, "times": 50,
                      "prob": 0.5, "seed": 42}])
            out = []
            for _ in range(50):
                try:
                    inj.hit("window.feed")
                    out.append(False)
                except InjectedFault:
                    out.append(True)
            return out

        a, b = firing_pattern(), firing_pattern()
        assert a == b
        assert any(a) and not all(a)  # the draw actually varies

    def test_hang_kind_sleeps_then_raises(self):
        import time

        inj = FaultInjector()
        inj.arm([{"point": "device.fetch", "kind": "hang",
                  "hang_s": 0.05}])
        t0 = time.monotonic()
        with pytest.raises(InjectedFault) as ei:
            inj.hit("device.fetch")
        assert time.monotonic() - t0 >= 0.05
        assert ei.value.kind == "hang"

    def test_disarm_clears_state(self):
        inj = FaultInjector()
        inj.arm([{"point": "window.feed"}])
        inj.disarm()
        assert not inj.armed and not inj.rules
        assert inj.hit("window.feed") is None  # inert once disarmed


class TestDisarmedFree:
    def test_module_singleton_starts_disarmed(self):
        # SFT_FAULT_PLAN is unset in the test env: the import-time arm
        # must leave the injector inert (the bench-smoke contract run
        # depends on this).
        assert faults.armed is False

    def test_disarmed_hot_paths_do_not_touch_the_injector(self):
        """With no plan, the threaded code paths never call hit() — the
        guard is `if faults.armed` — so counts stay empty even after
        real windows/ships run."""
        from spatialflink_tpu.driver import _toy_pipeline
        from spatialflink_tpu.operators.range_query import (
            PointPointRangeQuery,
        )

        grid, conf, source, query = _toy_pipeline(n_events=40)
        op = PointPointRangeQuery(conf, grid)
        assert list(op.run(source(), [query], 1.5))
        assert faults.counts == {}
        assert faults.fired == []


class TestTelemetryVisibility:
    def test_fired_fault_lands_in_snapshot_and_events(self):
        telemetry.enable()
        inj = faults
        inj.arm([{"point": "window.feed", "at": 1}])
        with pytest.raises(InjectedFault):
            inj.hit("window.feed")
        snap = telemetry.snapshot()
        assert snap["faults"] == {"window.feed": 1}
        names = [e["name"] for e in telemetry.events]
        assert "fault_armed" in names
        assert "fault_fired:window.feed" in names

    def test_plan_armed_before_enable_still_records_fault_armed(self):
        """The SFT_FAULT_PLAN path arms at import — BEFORE any
        telemetry.enable(). The armed schedule must still reach the
        trace/stream, or a recovered chaos artifact couldn't say what
        was armed (only what fired)."""
        faults.arm([{"point": "soa.feed", "at": 3}])
        telemetry.enable()
        armed = [e for e in telemetry.events if e["name"] == "fault_armed"]
        assert len(armed) == 1
        assert armed[0]["args"]["plan"][0]["point"] == "soa.feed"

    def test_no_faults_block_when_nothing_fired(self):
        telemetry.enable()
        assert "faults" not in telemetry.snapshot()
        # the driver block is ALWAYS present (gate on zero, not absence)
        assert telemetry.snapshot()["driver"] == {
            "retries": 0, "failovers": 0,
        }


class TestDispatchPointCoverage:
    def test_device_dispatch_lives_in_instrument_jit(self):
        """The point must fire for EVERY instrumented dispatch — the
        mesh window programs and bench steps skip operators/base.jitted,
        so the hook lives in telemetry.instrument_jit (a plan arming
        device.dispatch on a mesh run must not silently never fire)."""
        from spatialflink_tpu.telemetry import instrument_jit

        calls = []
        f = instrument_jit(lambda x: calls.append(x) or x, name="probe")
        faults.arm([{"point": "device.dispatch", "at": 2}])
        assert f(1) == 1
        with pytest.raises(InjectedFault):
            f(2)
        assert calls == [1]  # the faulted dispatch never ran the kernel


class TestEnvArming:
    def test_subprocess_arms_from_env_and_abort_kind_kills(self):
        """SFT_FAULT_PLAN in the environment arms at import; the abort
        kind dies with the SIGKILL-analog exit code, skipping every
        handler."""
        code = (
            "from spatialflink_tpu.faults import faults\n"
            "assert faults.armed\n"
            "import atexit; atexit.register("
            "lambda: print('HANDLER RAN'))\n"
            "faults.hit('window.feed')\n"
            "print('UNREACHABLE')\n"
        )
        p = subprocess.run(
            [sys.executable, "-c", code],
            env={**os.environ,
                 "SFT_FAULT_PLAN":
                     '[{"point": "window.feed", "kind": "abort"}]'},
            capture_output=True, text=True, timeout=120,
        )
        assert p.returncode == ABORT_EXIT_CODE, p.stderr
        assert "UNREACHABLE" not in p.stdout
        assert "HANDLER RAN" not in p.stdout

    def test_rule_validation_happens_at_arm_time(self):
        with pytest.raises(ValueError):
            FaultRule(point="nope")
