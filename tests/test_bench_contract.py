"""bench.py JSON-contract tests, without a device.

The driver runs ``python bench.py`` once and records the single stdout
JSON line. bench.py is ONE process that touches the device once; these
tests pin what it does when it cannot, and the record↔ledger contract
of the toy-size ``SFT_BENCH_SMOKE`` run:

- a full-size run that finds no TPU exits 3 with ``value`` 0 and an
  ``error`` — never a CPU number under the chip metric's name;
- a device op that hangs is bounded by ``SFT_DIAL_DEADLINE_S``: one
  failure record, exit 3, the ledger stream sealed ``dial_timeout``;
- the smoke run's record, trace, ledger and stream agree
  (``@pytest.mark.slow``).
"""

import json
import os
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BENCH = os.path.join(REPO, "bench.py")

sys.path.insert(0, REPO)  # tools.sfprof


def _run(extra_env, timeout=120):
    env = {**os.environ, "JAX_PLATFORMS": "cpu", **extra_env}
    p = subprocess.run(
        [sys.executable, BENCH], env=env, capture_output=True, text=True,
        timeout=timeout,
    )
    lines = [ln for ln in p.stdout.strip().splitlines() if ln]
    return p, lines


def test_full_size_run_without_tpu_is_an_error():
    """No fallback: the headline is a chip measurement, so a CPU
    backend yields the failure record and a non-zero exit — in one
    process, in seconds."""
    p, lines = _run({})
    assert p.returncode == 3
    assert len(lines) == 1, f"driver contract: ONE line, got {lines}"
    rec = json.loads(lines[0])
    assert rec["value"] == 0 and rec["vs_baseline"] == 0
    assert "no TPU" in rec["error"] and "cpu" in rec["error"]


class TestSmokeRun:
    """The REAL measured program at toy sizes on XLA:CPU
    (``SFT_BENCH_SMOKE``): the record↔ledger↔stream contract."""

    @pytest.mark.slow
    def test_sigkill_chaos_recovers_gateable_ledger(self, tmp_path):
        """The acceptance chaos test: a real bench-smoke run streaming
        with interval 0, SIGKILLed mid-run (no handler can save it),
        must recover into a schema-valid ledger that passes `sfprof
        health`, reporting the truncation honestly."""
        import time

        stream = tmp_path / "chaos_stream.jsonl"
        env = {
            **os.environ,
            "SFT_BENCH_SMOKE": "1",
            "SFT_LEDGER_STREAM": str(stream),
            "SFT_LEDGER_STREAM_INTERVAL_S": "0",
            "JAX_PLATFORMS": "cpu",
        }
        p = subprocess.Popen(
            [sys.executable, BENCH], env=env, stdout=subprocess.PIPE,
            stderr=subprocess.PIPE, text=True,
        )

        def n_checkpoints():
            try:
                return stream.read_text().count('"t": "checkpoint"')
            except OSError:
                return 0

        # Wait for ≥2 durable checkpoints (warm-up boundary + first
        # post-run flush), then SIGKILL while the rest of the run —
        # latency probe, resident passes, ledger write — is still ahead.
        deadline = time.time() + 480
        while time.time() < deadline and n_checkpoints() < 2:
            if p.poll() is not None:
                pytest.fail(
                    "bench exited before the kill: rc="
                    f"{p.returncode}\n{p.stderr.read()[-4000:]}"
                )
            time.sleep(0.25)
        assert n_checkpoints() >= 2, "no checkpoints within the deadline"
        p.kill()  # SIGKILL: no handler, no seal, no epilogue
        p.wait(timeout=60)

        from tools.sfprof import ledger as ledger_mod
        from tools.sfprof import stream as stream_mod
        from tools.sfprof.cli import main as sfprof_main

        doc, info = stream_mod.recover(str(stream))
        assert ledger_mod.validate(doc) == [], ledger_mod.validate(doc)
        assert info["sealed"] is False  # honest: the run never completed
        assert info["truncated"] is True
        assert "one flush interval" in info["loss_bound"]
        assert doc["bench"] is None  # no fabricated record
        # The recovered snapshot carries real measured state.
        assert doc["snapshot"]["compiles"] >= 1
        assert doc["snapshot"]["bytes_h2d"] > 0
        # CLI round trip: recover exit 0, recovered ledger passes the
        # post-bench health gate.
        out = tmp_path / "recovered.json"
        assert sfprof_main(["recover", str(stream), "-o", str(out)]) == 0
        assert sfprof_main(["health", str(out)]) == 0


    @pytest.mark.slow
    def test_smoke_run_emits_telemetry_summary(self, tmp_path):
        """SFT_BENCH_SMOKE runs the REAL measured program at toy sizes on
        XLA:CPU: still exactly ONE JSON line, now with the telemetry
        summary, and the Chrome-trace side channel loads as valid JSON.
        SFT_LEDGER_PATH additionally captures the run ledger, which must
        validate against the sfprof schema, attribute the probe's
        window spans, carry CPU cost analysis, and survive the
        ``sfprof diff --gate`` round trip (self-diff 0, injected
        regression nonzero)."""
        trace = tmp_path / "bench_trace.jsonl"
        ledger = tmp_path / "bench_ledger.json"
        env = {
            **os.environ,
            "SFT_BENCH_SMOKE": "1",
            "SFT_TRACE_PATH": str(trace),
            "SFT_LEDGER_PATH": str(ledger),
            "JAX_PLATFORMS": "cpu",
        }
        p = subprocess.run(
            [sys.executable, BENCH], env=env, capture_output=True,
            text=True, timeout=540,
        )
        assert p.returncode == 0, p.stderr[-4000:]
        lines = [ln for ln in p.stdout.strip().splitlines() if ln]
        assert len(lines) == 1, f"driver contract: ONE line, got {lines}"
        rec = json.loads(lines[0])
        assert rec["smoke"] is True
        assert rec["value"] > 0
        tel = rec["telemetry"]
        assert tel["compiles"] >= 1  # headline step compiled at least once
        assert tel["bytes_h2d"] > 0
        assert tel["bytes_d2h"] > 0
        assert tel["window_latency_p50_ms"] is not None
        assert tel["window_latency_p95_ms"] >= tel["window_latency_p50_ms"]
        assert tel["max_watermark_lag_ms"] == 0  # in-order synthetic stream
        # The run's trace file is a loadable Chrome-trace document.
        from spatialflink_tpu.telemetry import load_trace

        doc = load_trace(str(trace))
        assert doc["traceEvents"], "trace captured no events"
        json.dumps(doc)
        names = {e["name"] for e in doc["traceEvents"]}
        assert "window.headline" in names
        assert any(n.startswith("compile:") for n in names)
        # Counter-event symmetry: BOTH transfer directions render as
        # Perfetto counter tracks.
        counters = {e["name"] for e in doc["traceEvents"]
                    if e.get("ph") == "C"}
        assert {"h2d_bytes", "d2h_bytes"} <= counters

        # ---- run ledger: schema, attribution, costs, gate. ----
        from tools.sfprof import ledger as ledger_mod
        from tools.sfprof.attribution import attribute_windows
        from tools.sfprof.cli import main as sfprof_main

        led = ledger_mod.load(str(ledger))
        assert ledger_mod.validate(led) == [], ledger_mod.validate(led)
        # The bench block is the SAME record the driver line carried.
        assert led["bench"]["value"] == rec["value"]
        assert led["bench"]["smoke"] is True
        # Per-kernel flops/bytes from XLA cost analysis on CPU.
        costed = [r for r in led["kernels"]
                  if r["cost"] and r["cost"].get("flops")]
        assert costed, led["kernels"]
        assert {"headline_step", "headline_step_donated"} <= {
            r["kernel"] for r in led["kernels"]
        }
        # Every window.* span is ≥90% attributed to its phase children
        # OR the residue is reported explicitly — either way no silently
        # missing time: phases + unattributed == the window's dur,
        # exactly. (At toy smoke sizes the windows are sub-ms, so span-
        # machinery µs can push the residue past 10% — the explicit
        # residue is the contract, the 90% is what real window sizes
        # deliver.)
        windows, ops = attribute_windows(led["events"])
        assert windows, "ledger carried no window spans"
        for w in windows:
            assert (sum(w["phases"].values()) + w["unattributed_us"]
                    == w["dur_us"])
            assert (w["attributed_frac"] >= 0.9
                    or w["unattributed_us"] > 0)
        agg = ops["window.headline"]
        assert {"compute", "fetch"} <= set(agg["phases"])
        attributed = sum(agg["phases"].values())
        assert attributed + agg["unattributed_us"] == agg["dur_us"]
        # The probe's dispatch+fetch dominate even at toy sizes.
        assert attributed / agg["dur_us"] >= 0.5

        # ---- pipelined ingest proof (ISSUE 11). The overlap probe's
        # window.pipeline spans carry their ingest INSIDE the spans
        # (the executor ships pane N+1 while window N computes), so
        # the attributed inter-window host gap must SHRINK vs the
        # synchronous latency probe's window.headline spans on the
        # same toy run — sfprof's host-gap detector is the proof
        # metric. The codec gauges must ride record + ledger.
        import statistics

        from tools.sfprof.attribution import host_gaps

        counters = rec["pipeline"]["counters"]
        assert counters["overlapped"] > 0
        assert counters.get("collapses", 0) == 0
        # Codec-arming identity rides the record (the trend store keys
        # series by it): unarmed smoke run → armed False, codec None.
        assert rec["pipeline"]["armed"] is False
        assert rec["pipeline"]["armed_codec"] is None
        assert 0 < rec["wire_bytes"] <= rec["raw_bytes"]
        assert led["snapshot"]["wire_codec"]["coded_bytes"] \
            == rec["wire_bytes"]
        assert led["snapshot"]["wire_codec"]["raw_bytes"] \
            == rec["raw_bytes"]
        gaps = host_gaps(led["events"])

        def median_gap(name):
            vals = [g["gap_us"] for g in gaps
                    if g["after"] == name and g["before"] == name]
            assert len(vals) >= 2, (name, gaps)
            return float(statistics.median(vals))

        assert median_gap("window.pipeline") \
            < median_gap("window.headline")
        # ship is ATTRIBUTED inside the pipelined window spans (it is
        # dead inter-window time on the sync path).
        assert "ship" in ops["window.pipeline"]["phases"]
        assert "ship" not in ops["window.headline"]["phases"]

        # report renders; self-diff gates clean; an injected EPS
        # regression (beyond the ±50% tolerance band) gates nonzero.
        assert sfprof_main(["report", str(ledger)]) == 0
        assert sfprof_main(["diff", str(ledger), str(ledger),
                            "--gate"]) == 0
        bad = json.loads(json.dumps(led))
        bad["bench"]["value"] = led["bench"]["value"] / 10.0
        bad_path = tmp_path / "bench_ledger_regressed.json"
        bad_path.write_text(json.dumps(bad))
        assert sfprof_main(["diff", str(ledger), str(bad_path),
                            "--gate"]) != 0
        # The post-bench health check (CLAUDE.md) passes on a clean run.
        assert sfprof_main(["health", str(ledger)]) == 0


class TestDialDeadline:
    """A hang at the first device op is bounded by SFT_DIAL_DEADLINE_S:
    the run prints the one-line failure record AND seals the ledger
    stream with reason ``dial_timeout`` instead of hanging forever."""

    def test_dial_timeout_prints_record_and_seals_stream(self, tmp_path):
        stream = tmp_path / "dial_stream.jsonl"
        env = {
            **os.environ,
            "SFT_BENCH_SMOKE": "1",
            "SFT_LEDGER_STREAM": str(stream),
            "SFT_DIAL_DEADLINE_S": "8",
            # Simulated wedged device: device discovery succeeds,
            # the first device op never completes.
            "SFT_BENCH_DIAL_HANG": "300",
            "JAX_PLATFORMS": "cpu",
        }
        p = subprocess.run(
            [sys.executable, BENCH], env=env, capture_output=True,
            text=True, timeout=100,
        )
        assert p.returncode == 3
        lines = [ln for ln in p.stdout.strip().splitlines()
                 if ln.startswith("{")]
        assert len(lines) == 1
        rec = json.loads(lines[0])
        assert rec["value"] == 0
        assert "SFT_DIAL_DEADLINE_S" in rec["error"]
        # The stream is sealed with the dial_timeout reason, so `sfprof
        # recover` attributes the loss instead of guessing.
        from tools.sfprof import stream as stream_mod

        doc, info = stream_mod.recover(str(stream))
        assert info["sealed"] is True
        assert info["reason"] == "dial_timeout"

    def test_healthy_smoke_run_unaffected_by_deadline(self, tmp_path):
        """With no hang, the watchdog disarms at the warm-up fetch and a
        tight-but-sane deadline changes nothing (the acceptance
        criterion: the SFT_BENCH_SMOKE contract run is unchanged)."""
        p, lines = _run(
            {"SFT_BENCH_SMOKE": "1", "SFT_DIAL_DEADLINE_S": "90"},
            timeout=300,
        )
        assert p.returncode == 0, p.stderr[-2000:]
        rec = json.loads(lines[-1])
        assert rec["smoke"] is True and rec["value"] > 0
