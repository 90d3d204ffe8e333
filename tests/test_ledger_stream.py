"""Ledger-stream tests: the JSONL segment grammar telemetry writes under
``SFT_LEDGER_STREAM`` (prologue / span batches / checkpoints / sealing
epilogue), the disable()-seals contract, non-finite sanitization on the
stream path, and ``sfprof recover`` rebuilding a schema-valid ledger
from complete AND truncated streams."""

import json

import jax
import jax.numpy as jnp
import pytest

from spatialflink_tpu.telemetry import (
    LEDGER_VERSION,
    STREAM_VERSION,
    instrument_jit,
    telemetry,
)
from tools.sfprof import ledger as ledger_mod
from tools.sfprof import stream as stream_mod
from tools.sfprof.cli import main as sfprof_main


@pytest.fixture(autouse=True)
def _telemetry_off():
    cap = telemetry.max_events
    yield
    telemetry.max_events = cap
    telemetry.enable()
    telemetry.disable()


def _run_stream(tmp_path, name="s.jsonl", windows=3, seal="ledger"):
    """A small instrumented run writing a stream; returns its path.
    ``seal``: "ledger" (write_ledger seals with reason complete),
    "disable" (disable() seals), or None (leave unsealed/open)."""
    path = str(tmp_path / name)
    telemetry.enable(stream_path=path, stream_flush_interval_s=0.0)
    f = instrument_jit(jax.jit(lambda x: x * 2), name="double")
    for w in range(windows):
        with telemetry.span("window.demo", window=w):
            f(jnp.ones((8,), jnp.float32))
    if seal == "ledger":
        telemetry.write_ledger(str(tmp_path / (name + ".ledger.json")),
                               bench={"value": 10.0})
        telemetry.disable()
    elif seal == "disable":
        telemetry.disable()
    return path


def _records(path):
    with open(path) as f:
        return [json.loads(ln) for ln in f if ln.strip()]


# -- stream grammar -----------------------------------------------------------


def test_stream_version_constants_in_sync():
    """Writer (telemetry) and recoverer (tools/sfprof) deliberately
    don't import each other — the cross-pin, same as LEDGER_VERSION."""
    assert stream_mod.STREAM_VERSION == STREAM_VERSION


def test_stream_grammar_prologue_segments_epilogue(tmp_path):
    path = _run_stream(tmp_path)
    recs = _records(path)
    assert recs[0]["t"] == "prologue"
    assert recs[0]["stream_version"] == STREAM_VERSION
    assert recs[0]["ledger_version"] == LEDGER_VERSION
    assert recs[0]["created_unix"] > 0
    kinds = [r["t"] for r in recs]
    assert kinds[-1] == "epilogue"
    assert "checkpoint" in kinds and "spans" in kinds
    # Window-boundary flush with interval 0: one checkpoint per window,
    # each preceded (same seq) by its span batch.
    cks = [r for r in recs if r["t"] == "checkpoint"]
    assert len(cks) >= 3
    assert [c["seq"] for c in cks] == sorted(c["seq"] for c in cks)
    for c in cks:
        assert set(c["snapshot"]) >= {"compiles", "bytes_h2d",
                                      "late_dropped", "kernels"}
    # Every emitted event appears in exactly one span batch, in order.
    streamed = [e for r in recs if r["t"] == "spans"
                for e in r["events"]]
    assert [e["name"] for e in streamed
            if e["name"].startswith("window.")] == ["window.demo"] * 3
    ep = recs[-1]
    assert ep["reason"] == "complete"
    assert ep["bench"]["value"] == 10.0


def test_flush_interval_paces_checkpoints(tmp_path):
    path = str(tmp_path / "paced.jsonl")
    telemetry.enable(stream_path=path, stream_flush_interval_s=3600.0)
    for w in range(10):
        with telemetry.span("window.demo", window=w):
            pass
    telemetry.disable()
    # Only the seal flushed: one checkpoint, one span batch, all events.
    recs = _records(path)
    assert sum(r["t"] == "checkpoint" for r in recs) == 1
    batches = [r for r in recs if r["t"] == "spans"]
    assert len(batches) == 1 and len(batches[0]["events"]) == 10


def test_disable_seals_stream_and_flushes_trace(tmp_path):
    """Satellite regression: a mid-run disable() must seal BOTH sinks —
    the stream gets its epilogue (reason: disabled) and the trace file
    keeps every buffered event even though FLUSH_EVERY was never hit."""
    trace = tmp_path / "t.jsonl"
    stream = tmp_path / "s.jsonl"
    telemetry.enable(trace_path=str(trace), stream_path=str(stream),
                     stream_flush_interval_s=3600.0)
    n = 5  # far below FLUSH_EVERY: only disable() can flush these
    assert n < telemetry.FLUSH_EVERY
    for w in range(n):
        with telemetry.span("window.demo", window=w):
            pass
    telemetry.disable()
    recs = _records(str(stream))
    assert recs[-1]["t"] == "epilogue"
    assert recs[-1]["reason"] == "disabled"
    spans = [ln for ln in trace.read_text().splitlines()
             if '"window.demo"' in ln]
    assert len(spans) == n
    # And the sealed stream recovers into a valid ledger.
    doc, info = stream_mod.recover(str(stream))
    assert ledger_mod.validate(doc) == []
    assert info["sealed"] and info["reason"] == "disabled"


def test_stream_sanitizes_nonfinite_values(tmp_path):
    path = str(tmp_path / "nan.jsonl")
    telemetry.enable(stream_path=path, stream_flush_interval_s=0.0)
    with telemetry.span("window.demo", bad=float("nan")):
        pass
    telemetry.disable()
    recs = _records(path)  # json.loads would choke on a bare NaN token
    ep = recs[-1]
    assert ep["nonfinite_values"] >= 1
    doc, _ = stream_mod.recover(path)
    assert ledger_mod.validate(doc) == []
    assert doc["nonfinite_values"] >= 1


# -- recovery -----------------------------------------------------------------


def test_recover_complete_stream_matches_ledger(tmp_path):
    stream = _run_stream(tmp_path)
    ledger_path = stream + ".ledger.json"
    doc, info = stream_mod.recover(stream)
    assert ledger_mod.validate(doc) == []
    assert info["sealed"] is True and info["truncated"] is False
    assert info["loss_bound"].startswith("none")
    ledger = ledger_mod.load(ledger_path)
    # The stream's final checkpoint carries the same gauge state the
    # one-shot ledger recorded (written before costs were captured, so
    # compare the snapshot, not the kernel cost blocks).
    for key in ("compiles", "bytes_h2d", "bytes_d2h", "late_dropped"):
        assert doc["snapshot"][key] == ledger["snapshot"][key]
    assert doc["bench"]["value"] == ledger["bench"]["value"]
    win_names = [e["name"] for e in doc["events"]
                 if e["name"].startswith("window.")]
    assert win_names == [e["name"] for e in ledger["events"]
                         if e["name"].startswith("window.")]


def test_recover_truncated_stream_loses_at_most_one_interval(tmp_path):
    """Simulated SIGKILL: cut the stream mid-final-line, no epilogue.
    Recovery must yield a schema-valid ledger holding everything up to
    the last complete checkpoint and say so honestly."""
    full = _run_stream(tmp_path, windows=4, seal=None)
    telemetry.maybe_flush_stream(force=True)
    raw = open(full, "rb").read()
    telemetry.disable()
    trunc = tmp_path / "trunc.jsonl"
    trunc.write_bytes(raw[: len(raw) - 25])  # half-written tail line
    doc, info = stream_mod.recover(str(trunc))
    assert ledger_mod.validate(doc) == []
    assert info["sealed"] is False
    assert info["truncated"] is True and info["partial_tail"] is True
    assert "one flush interval" in info["loss_bound"]
    assert doc["bench"] is None  # no epilogue — no bench record to fake
    assert doc["recovery"]["checkpoints"] >= 3
    # Events survive up to the truncation point: at least the windows
    # before the last complete flush.
    wins = [e for e in doc["events"]
            if e["name"].startswith("window.")]
    assert len(wins) >= 3


def test_recover_stream_killed_before_first_checkpoint(tmp_path):
    path = tmp_path / "young.jsonl"
    telemetry.enable(stream_path=str(path), stream_flush_interval_s=3600)
    with telemetry.span("window.demo"):
        pass
    raw = open(path, "rb").read()  # prologue only: nothing flushed yet
    telemetry.disable()
    young = tmp_path / "young_cut.jsonl"
    young.write_bytes(raw)
    doc, info = stream_mod.recover(str(young))
    assert ledger_mod.validate(doc) == []
    assert info["snapshot_synthesized"] is True
    assert doc["snapshot"]["synthesized"] is True
    assert info["checkpoints"] == 0 and info["sealed"] is False


def test_recover_honors_epilogue_past_partial_tail(tmp_path):
    """The external-seal shape: valid records, a half-written line,
    then an epilogue appended on its own line. The epilogue's reason
    must survive; any OTHER record past the corruption stays skipped
    (no silent re-synchronization)."""
    full = _run_stream(tmp_path, windows=2, seal=None)
    telemetry.maybe_flush_stream(force=True)
    raw = open(full, "rb").read()
    telemetry.disable()
    cut = tmp_path / "sealed_after_cut.jsonl"
    cut.write_bytes(
        raw[: len(raw) - 20]  # half-written tail, no newline
        + b"\n" + json.dumps({"t": "spans", "seq": 9, "events": [
            {"name": "window.fake", "ph": "X", "ts": 0, "dur": 1,
             "pid": 1, "tid": 1}]}).encode() + b"\n"  # must NOT re-sync
        + json.dumps({"t": "epilogue", "unix": 9.0,
                      "reason": "dial_timeout",
                      "sealed_by": "watchdog"}).encode() + b"\n"
    )
    doc, info = stream_mod.recover(str(cut))
    assert ledger_mod.validate(doc) == []
    assert info["sealed"] is True
    assert info["sealed_by"] == "watchdog"
    assert info["reason"] == "dial_timeout"
    assert info["partial_tail"] is True and info["truncated"] is True
    assert info["skipped_lines"] == 1  # the post-corruption spans batch
    assert all(e["name"] != "window.fake" for e in doc["events"])


def test_external_seal_on_clean_boundary_still_truncated(tmp_path):
    """A watchdog epilogue on a clean line boundary (run wedged
    BETWEEN flushes) attributes the crash but must not masquerade as a
    complete capture: truncated stays True, child seals stay not."""
    full = _run_stream(tmp_path, windows=2, seal=None)
    telemetry.maybe_flush_stream(force=True)
    raw = open(full, "rb").read()
    telemetry.disable()
    crashed = tmp_path / "crashed.jsonl"
    crashed.write_bytes(raw + json.dumps(
        {"t": "epilogue", "unix": 9.0, "reason": "dial_timeout",
         "sealed_by": "driver-watchdog"}).encode() + b"\n")
    _, info = stream_mod.recover(str(crashed))
    assert info["sealed"] is True and info["truncated"] is True
    assert info["sealed_by"] == "driver-watchdog"
    assert "one flush interval" in info["loss_bound"]
    # A CHILD seal ("complete"/"disabled") is the complete-capture case.
    complete = _run_stream(tmp_path, name="done.jsonl", seal="disable")
    _, info = stream_mod.recover(complete)
    assert info["sealed_by"] == "telemetry"
    assert info["truncated"] is False


def test_recover_rejects_non_stream_files(tmp_path):
    not_stream = tmp_path / "x.json"
    not_stream.write_text('{"hello": 1}\n')
    with pytest.raises(ValueError, match="record|prologue"):
        stream_mod.recover(str(not_stream))
    assert sfprof_main(["recover", str(not_stream)]) == 2
    assert sfprof_main(["recover", str(tmp_path / "absent.jsonl")]) == 2


# -- CLI ----------------------------------------------------------------------


def test_recover_cli_roundtrips_into_health(tmp_path, capsys):
    stream = _run_stream(tmp_path)
    out = tmp_path / "recovered.json"
    assert sfprof_main(["recover", stream, "-o", str(out)]) == 0
    printed = capsys.readouterr().out
    assert "sealed: yes" in printed and "valid" in printed
    assert "np." not in printed  # egress stays numpy-repr-free
    # The recovered document passes the post-bench health gate.
    assert sfprof_main(["health", str(out)]) == 0
    # And sfprof report renders it like any ledger.
    assert sfprof_main(["report", str(out)]) == 0


def test_recover_cli_reports_truncation_honestly(tmp_path, capsys):
    full = _run_stream(tmp_path, windows=3, seal=None)
    telemetry.maybe_flush_stream(force=True)
    raw = open(full, "rb").read()
    telemetry.disable()
    trunc = tmp_path / "cut.jsonl"
    trunc.write_bytes(raw[: len(raw) - 10])
    out = tmp_path / "rec.json"
    assert sfprof_main(["recover", str(trunc), "-o", str(out)]) == 0
    printed = capsys.readouterr().out
    assert "sealed: NO" in printed
    assert "truncated: yes" in printed
    assert "half-written tail" in printed
    assert sfprof_main(["health", str(out)]) == 0
