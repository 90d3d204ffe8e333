"""Tier-1 tools/ci: the pre-commit gate's stage plan, fail-fast
behavior, and environment hygiene (CPU only, no armed plans). The
stages themselves (sfcheck / pytest / the smokes) have their own
suites — here we pin the orchestration only."""

import os
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if REPO not in sys.path:
    sys.path.insert(0, REPO)

from tools import ci  # noqa: E402


def test_dry_run_lists_all_stages(capsys):
    assert ci.main(["--dry-run"]) == 0
    out = capsys.readouterr().out
    assert "[sfcheck]" in out
    assert "[pytest-quick]" in out
    assert "[chaos-smoke]" in out
    plain = out.replace(sys.executable, "py")
    assert len(out.strip().splitlines()) == 5
    # The kill/resume chaos round trip rides every commit.
    assert "spatialflink_tpu.driver --chaos-smoke" in plain
    # And the burst/shed/degrade/recover overload round trip.
    assert "[overload-smoke]" in out
    assert "spatialflink_tpu.overload --smoke" in plain
    # And the composed-DAG kill-between-sink-commits round trip.
    assert "[dag-smoke]" in out
    assert "spatialflink_tpu.dag --smoke" in plain


def test_skip_flags_trim_stages(capsys):
    assert ci.main(["--dry-run", "--skip-tests"]) == 0
    out = capsys.readouterr().out
    assert "[sfcheck]" in out
    assert "pytest" not in out
    # --skip-tests does NOT drop the chaos/overload/dag smokes; only
    # their own flags do.
    assert "[chaos-smoke]" in out
    assert "[overload-smoke]" in out
    assert "[dag-smoke]" in out
    assert ci.main(["--dry-run", "--skip-tests", "--skip-chaos",
                    "--skip-overload", "--skip-dag"]) == 0
    out = capsys.readouterr().out
    assert "chaos" not in out and "overload" not in out
    assert "dag" not in out


def test_changed_flag_passes_through(capsys):
    assert ci.main(["--dry-run", "--changed"]) == 0
    assert "--changed" in capsys.readouterr().out


def test_github_actions_switches_sfcheck_format(monkeypatch):
    """Under Actions the sfcheck stage emits ::error annotations; locally
    it stays human. Exit codes are format-invariant, so the gate verdict
    is identical either way."""
    def sfcheck_argv():
        (cmd,) = [c for name, c in ci.stages(
            False, True, skip_chaos=True, skip_overload=True,
            skip_dag=True) if name == "sfcheck"]
        return cmd

    monkeypatch.delenv("GITHUB_ACTIONS", raising=False)
    assert "--format=github" not in sfcheck_argv()
    monkeypatch.setenv("GITHUB_ACTIONS", "true")
    assert "--format=github" in sfcheck_argv()


def test_fail_fast_propagates_stage_exit(monkeypatch):
    calls = []

    class P:
        def __init__(self, rc):
            self.returncode = rc

    def fake_run(cmd, cwd=None, env=None):
        calls.append(cmd)
        return P(7 if "pytest" in " ".join(cmd) else 0)

    monkeypatch.setattr(ci.subprocess, "run", fake_run)
    assert ci.main([]) == 7
    joined = [" ".join(c) for c in calls]
    assert any("tools.sfcheck" in c for c in joined)
    assert any("pytest" in c for c in joined)
    # fail-fast: no later stage ran
    assert not any("--chaos-smoke" in c or "--smoke" in c for c in joined)


def test_all_green_runs_every_stage(monkeypatch):
    calls = []
    envs = []

    class P:
        returncode = 0

    def fake_run(cmd, cwd=None, env=None):
        calls.append(" ".join(cmd))
        envs.append(env)
        return P()

    # Seed EVERY hazard-class-`armed` registry var ambient: the scrub
    # is derived from spatialflink_tpu/envvars.py, so all of them —
    # not just the historical FAULT_PLAN/OVERLOAD_POLICY pair — must
    # vanish from every stage env.
    armed = ci._envvars_registry().gate_scrub_vars()
    assert "SFT_FAULT_PLAN" in armed and "SFT_OVERLOAD_POLICY" in armed
    for var in armed:
        monkeypatch.setenv(var, "ambient-sabotage")
    monkeypatch.setattr(ci.subprocess, "run", fake_run)
    assert ci.main([]) == 0
    assert len(calls) == 5
    assert any("spatialflink_tpu.driver --chaos-smoke" in c for c in calls)
    assert any("spatialflink_tpu.overload --smoke" in c for c in calls)
    assert any("spatialflink_tpu.dag --smoke" in c for c in calls)
    # every stage env is a CPU run AND free of any ambient fault plan
    # (an armed abort plan would kill healthy stages like a real kill -9)
    assert all(e["JAX_PLATFORMS"] == "cpu" for e in envs)
    assert all("SFT_FAULT_PLAN" not in e for e in envs)
    # the derived scrub: no armed var survives into ANY stage
    assert all(v not in e for e in envs for v in armed)
