"""chip_smoke.py on CPU: as a script it must refuse to run; its legs —
called directly with toy sizes and the expected kernel form passed as an
argument (the TPU-only check lives in ``run()``) — must agree with their
numpy references and notice a node that left the device path."""

import json
import os
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

import chip_smoke  # noqa: E402


def test_script_refuses_cpu_and_names_the_platform():
    p = subprocess.run(
        [sys.executable, os.path.join(REPO, "chip_smoke.py")],
        env={**os.environ, "JAX_PLATFORMS": "cpu"},
        capture_output=True, text=True, timeout=120,
    )
    assert p.returncode == 2
    assert "'cpu'" in p.stderr and "refuses" in p.stderr
    assert '"ok"' not in p.stdout  # no result line, ever


def test_script_fails_without_the_package(tmp_path):
    """In a directory that holds chip_smoke.py and nothing else of the
    repo it must fail (import error), not print a result."""
    lone = tmp_path / "chip_smoke.py"
    lone.write_text(open(os.path.join(REPO, "chip_smoke.py")).read())
    env = {k: v for k, v in os.environ.items() if k != "PYTHONPATH"}
    p = subprocess.run(
        [sys.executable, str(lone)], cwd=tmp_path,
        env={**env, "JAX_PLATFORMS": "cpu"},
        capture_output=True, text=True, timeout=120,
    )
    assert p.returncode != 0
    assert '"ok"' not in p.stdout


def test_leg_a_toy_matches_reference_with_clean_health(tmp_path):
    rep = chip_smoke.leg_a(str(tmp_path), seed=3, eps=400, duration_s=20)
    assert rep["events"] == 8000 and rep["full_windows"] == 3
    assert set(rep["nodes"]) == {"q1", "q2", "q3", "q4", "q5", "staytime",
                                 "qserve"}
    for st in rep["nodes"].values():
        assert st["backend"] == "device"
        assert st["retries"] == st["failovers"] == 0
        assert st["degraded_windows"] == 0
    # Non-vacuous: the nodes the CSV schema can feed all produced egress.
    for node in ("q1", "q3", "q4", "staytime", "qserve"):
        assert rep["lines"][node] > 0, node


def test_leg_a_fails_when_a_node_fails_over(tmp_path, monkeypatch):
    """Three injected failures at ``dag.node`` exhaust q1's retry budget
    and push it onto its numpy twin — the run still exits 0 and the
    egress is still right, so only the health counts can tell."""
    from spatialflink_tpu.faults import faults

    monkeypatch.setenv("SFT_FAULT_PLAN", json.dumps(
        [{"point": "dag.node", "at": 1, "times": 3}]))
    assert faults.arm_from_env()
    try:
        with pytest.raises(chip_smoke.SmokeFailure,
                           match="left the device path"):
            chip_smoke.leg_a(str(tmp_path), seed=3, eps=400, duration_s=10)
    finally:
        faults.disarm()


def test_leg_b_toy_matches_reference():
    rep = chip_smoke.leg_b(seed=3, window_points=5000, slide_points=2500,
                           n_windows=4, num_segments=256,
                           expect_digest="xla")
    assert rep["full_windows"] == 4 and rep["windows"] == 6
    assert rep["wire_digest"] == "xla"  # CPU: the Pallas form is TPU-only


def test_leg_b_notices_a_kernel_off_its_expected_form():
    with pytest.raises(chip_smoke.SmokeFailure, match="wire digest"):
        chip_smoke.leg_b(seed=3, window_points=5000, slide_points=2500,
                         n_windows=4, num_segments=256,
                         expect_digest="pallas")


def test_leg_c_toy_matches_reference():
    rep = chip_smoke.leg_c(seed=3, points=2000)
    assert rep["events"] == 4000 and rep["pairs"] > 0


def test_reference_catches_a_wrong_answer(tmp_path):
    """The comparison is not a rubber stamp: one altered sink line fails."""
    chip_smoke.leg_a(str(tmp_path), seed=3, eps=400, duration_s=10)
    # leg_a leaves its inputs and egress under tmp_path: re-check them
    # against the reference with one qserve distance nudged.
    out_dir = os.path.join(str(tmp_path), "egress")
    path = os.path.join(out_dir, "qserve.csv")
    lines = open(path).read().splitlines()
    head, dist = lines[0].rsplit(",", 1)
    lines[0] = f"{head},{float(dist) + 1e-3!r}"
    with open(path, "w") as f:
        f.write("\n".join(lines) + "\n")
    events = chip_smoke.write_sncb_inputs(
        str(tmp_path), 3, 400, 10, chip_smoke.SNCB_DEVICES, 100)[2]
    with pytest.raises(chip_smoke.SmokeFailure, match="qserve"):
        chip_smoke.verify_sncb(out_dir, *events, grid_n=100)
