"""Kernel-ablation harness (spatialflink_tpu/ablation.py): the
substituted dispatch (learning call → cached correct-aval zeros), the
taint contract across snapshot/ledger/stream/record, the gate and
baseline-writer rejections, SFT_ABLATE arming, and the bench_suite
--ablate marginal-cost sweep."""

import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from spatialflink_tpu.ablation import _parse_spec, ablation
from spatialflink_tpu.telemetry import instrument_jit, telemetry
from tools.sfprof import ledger as ledger_mod
from tools.sfprof import stream as stream_mod
from tools.sfprof import trend as trend_mod
from tools.sfprof.cli import main as sfprof_main


@pytest.fixture(autouse=True)
def _clean_singletons():
    """Both process-global singletons reset and disarmed around every
    test (the test_sfprof fixture, plus ablation)."""
    yield
    ablation.disarm()
    ablation.reset_counters()
    telemetry.enable()
    telemetry.disable()


# -- the substituted dispatch -------------------------------------------------


def test_learning_call_then_cached_zeros():
    telemetry.enable()
    f = instrument_jit(jax.jit(lambda x: x * 2 + 1), name="twice")
    x = jnp.ones((8,), jnp.float32)
    assert float(np.asarray(f(x))[0]) == 3.0
    ablation.arm(["twice"])
    # First armed call per signature is the REAL kernel (learning).
    assert float(np.asarray(f(x))[0]) == 3.0
    # Then cached zeros with the exact avals.
    out = f(x)
    assert out.shape == (8,) and out.dtype == jnp.float32
    assert float(np.asarray(out).sum()) == 0.0
    t = ablation.taint_block()
    assert t["kind"] == "ablation"
    assert t["kernels"] == ["twice"]
    assert t["learning_calls"] == {"twice": 1}
    assert t["substituted_calls"] == {"twice": 1}


def test_new_signature_relearns():
    telemetry.enable()
    f = instrument_jit(jax.jit(lambda x: x + 1), name="bump")
    ablation.arm(["bump"])
    assert float(np.asarray(f(jnp.ones((4,))))[0]) == 2.0  # learn (4,)
    assert float(np.asarray(f(jnp.ones((4,))))[0]) == 0.0  # zeros
    # A new abstract shape learns again before substituting.
    assert float(np.asarray(f(jnp.ones((6,))))[0]) == 2.0
    assert float(np.asarray(f(jnp.ones((6,))))[0]) == 0.0


def test_pytree_outputs_and_fresh_buffers():
    """NamedTuple outputs mirror structurally, and each substituted
    call returns FRESH buffers — a downstream donate_argnums consumer
    must never invalidate the cache."""
    from typing import NamedTuple

    class Out(NamedTuple):
        a: object
        b: object

    telemetry.enable()
    f = instrument_jit(jax.jit(lambda x: Out(x * 2, (x.sum(),))),
                       name="nt")
    consume = jax.jit(lambda a: a + 1, donate_argnums=(0,))
    x = jnp.ones((16,), jnp.float32)
    ablation.arm(["nt"])
    f(x)  # learning
    o1 = f(x)
    assert isinstance(o1, Out)
    assert float(np.asarray(o1.b[0])) == 0.0
    consume(o1.a)  # donate the substituted buffer
    o2 = f(x)  # the cache must still be alive
    assert float(np.asarray(o2.a).sum()) == 0.0


def test_unablated_kernels_unaffected_and_disarm_restores():
    telemetry.enable()
    f = instrument_jit(jax.jit(lambda x: x * 2), name="keep")
    g = instrument_jit(jax.jit(lambda x: x * 3), name="cut")
    x = jnp.ones((4,), jnp.float32)
    ablation.arm(["cut"])
    g(x)  # learning
    assert float(np.asarray(g(x))[0]) == 0.0
    assert float(np.asarray(f(x))[0]) == 2.0  # untouched
    ablation.disarm()
    assert float(np.asarray(g(x))[0]) == 3.0  # real again
    # Disarmed cost path: the runtime table kept recording "keep".
    assert any(r["kernel"] == "keep" for r in telemetry.kernel_table())


def test_works_with_telemetry_disabled():
    # Substitution is a profiling tool but must not NEED a capture.
    f = instrument_jit(jax.jit(lambda x: x + 5), name="solo")
    x = jnp.ones((4,), jnp.float32)
    ablation.arm(["solo"])
    f(x)
    assert float(np.asarray(f(x))[0]) == 0.0


# -- the taint contract -------------------------------------------------------


def test_taint_rides_snapshot_ledger_and_record(tmp_path):
    telemetry.enable()
    f = instrument_jit(jax.jit(lambda x: x * 2), name="tk")
    x = jnp.ones((4,), jnp.float32)
    ablation.arm(["tk"])
    f(x)
    f(x)
    assert telemetry.snapshot()["tainted"]["kind"] == "ablation"
    path = telemetry.write_ledger(
        str(tmp_path / "t.json"),
        bench={"config": "c", "points_per_sec": 1.0, "value": 1.0})
    doc = ledger_mod.load(path)
    assert doc["tainted"]["kernels"] == ["tk"]
    assert doc["snapshot"]["tainted"]["kind"] == "ablation"
    assert ledger_mod.validate(doc) == []  # taint is schema-legal


def test_taint_scope_resets_with_a_fresh_capture(tmp_path):
    telemetry.enable()
    f = instrument_jit(jax.jit(lambda x: x * 2), name="tk2")
    ablation.arm(["tk2"])
    f(jnp.ones((4,)))
    f(jnp.ones((4,)))
    ablation.disarm()
    # Disarmed but substitutions happened THIS capture: still tainted.
    assert telemetry.snapshot()["tainted"] is not None
    # A fresh capture with ablation disarmed starts clean.
    telemetry.enable()
    assert "tainted" not in telemetry.snapshot()
    path = telemetry.write_ledger(str(tmp_path / "clean.json"))
    assert "tainted" not in ledger_mod.load(path)


def test_taint_survives_stream_recovery(tmp_path):
    stream = str(tmp_path / "s.jsonl")
    telemetry.enable(stream_path=stream)
    f = instrument_jit(jax.jit(lambda x: x * 2), name="sk")
    ablation.arm(["sk"])
    f(jnp.ones((4,)))
    f(jnp.ones((4,)))
    telemetry.maybe_flush_stream(force=True)
    telemetry.disable()  # seals
    doc, _info = stream_mod.recover(stream)
    assert trend_mod.taint_of(doc)["kind"] == "ablation"
    # And the recovered document is still rejected by the trend gate.
    p = tmp_path / "recovered.json"
    p.write_text(json.dumps(doc))
    hist = tmp_path / "hist"
    hist.mkdir()
    for i, v in enumerate((1.0, 2.0, 3.0)):
        (hist / f"r{i}.json").write_text(json.dumps(
            {"metric": "c", "value": v, "device": "cpu",
             "smoke": False}))
    assert sfprof_main(["trend", str(hist), "--gate", str(p)]) == 1


def test_ablation_armed_event_registered_and_counted():
    from tools.sfprof import events as events_mod

    telemetry.enable()
    ablation.arm(["whatever"])
    telemetry.disable()
    evs = [e for e in telemetry.events if e.get("ph") == "i"]
    names = [e["name"] for e in evs]
    assert "ablation_armed" in names
    counts = events_mod.notable_event_counts(evs)
    assert counts.get("ablation") == 1
    # arm-before-enable (the SFT_ABLATE import-time order): enable
    # re-emits the marker, the fault_armed idiom.
    telemetry.enable()
    telemetry.disable()
    assert any(e["name"] == "ablation_armed" for e in telemetry.events)


# -- gates and baseline writers reject taint ----------------------------------


def _tainted_ledger(tmp_path, name="tainted.json"):
    telemetry.enable()
    f = instrument_jit(jax.jit(lambda x: x * 2), name="gk")
    ablation.arm(["gk"])
    f(jnp.ones((4,)))
    f(jnp.ones((4,)))
    path = telemetry.write_ledger(
        str(tmp_path / name),
        bench={"config": "c", "points_per_sec": 9e9, "value": 9e9})
    telemetry.disable()
    ablation.disarm()
    return path


def test_diff_gate_rejects_tainted_ledger(tmp_path, capsys):
    bad = _tainted_ledger(tmp_path)
    telemetry.enable()
    good = telemetry.write_ledger(
        str(tmp_path / "good.json"),
        bench={"config": "c", "points_per_sec": 1.0, "value": 1.0})
    telemetry.disable()
    # Tainted candidate: rejected with the explicit reason, exit 1.
    assert sfprof_main(["diff", good, bad, "--gate"]) == 1
    out = capsys.readouterr().out
    assert "REJECT" in out and "tainted" in out and "ablation" in out
    # Tainted REFERENCE is equally unusable.
    assert sfprof_main(["diff", bad, good, "--gate"]) == 1
    # Un-gated diff: loud refusal to compare, informational exit.
    assert sfprof_main(["diff", good, bad]) == 0
    assert "REJECT" in capsys.readouterr().out


def test_cpu_baseline_refuses_armed_ablation(monkeypatch, capsys):
    import bench_suite

    monkeypatch.setenv("SFT_ABLATE", "some_kernel")
    monkeypatch.setattr("sys.argv", ["bench_suite.py", "--cpu-baseline"])
    from spatialflink_tpu.ablation import maybe_arm_from_env

    maybe_arm_from_env()
    with pytest.raises(SystemExit) as exc:
        bench_suite.main()
    assert "CPU_BASELINE" in str(exc.value)
    ablation.disarm()


# -- SFT_ABLATE parsing -------------------------------------------------------


def test_parse_spec_shapes(tmp_path):
    assert _parse_spec("a,b , c") == ["a", "b", "c"]
    assert _parse_spec('["x", "y"]') == ["x", "y"]
    assert _parse_spec('{"kernels": ["z"]}') == ["z"]
    p = tmp_path / "spec.json"
    p.write_text('{"kernels": ["from_file"]}')
    assert _parse_spec(str(p)) == ["from_file"]
    assert _parse_spec("") == []
    with pytest.raises(ValueError):
        _parse_spec('{"kernels": "notalist"}')


def test_maybe_arm_from_env(monkeypatch):
    from spatialflink_tpu.ablation import maybe_arm_from_env

    monkeypatch.setenv("SFT_ABLATE", "k1,k2")
    maybe_arm_from_env()
    assert ablation.armed and ablation.kernels == {"k1", "k2"}
    ablation.disarm()
    monkeypatch.setenv("SFT_ABLATE", "   ")
    with pytest.raises(ValueError):
        maybe_arm_from_env()


# -- the bench_suite --ablate sweep -------------------------------------------


def test_run_ablation_measures_marginal_cost(tmp_path, capsys):
    import bench_suite

    jheavy = instrument_jit(jax.jit(lambda x: (x * 2).sum()),
                            name="heavy_k")
    jlight = instrument_jit(jax.jit(lambda x: x + 1), name="light_k")

    def stub_bench():
        x = jnp.ones((64,), jnp.float32)
        for _ in range(4):
            jheavy(x)
            jlight(x)
        return {"config": "stub", "points_per_sec": 1000.0,
                "value": 1000.0}

    tables = bench_suite.run_ablation(
        [("stub", stub_bench)], top_n=2, ledger_dir=str(tmp_path))
    (table,) = tables
    assert table["ablation_table"] == "stub"
    assert table["tainted"] is True
    assert table["baseline_points_per_sec"] == 1000.0
    kernels = {r["kernel"] for r in table["kernels"]}
    assert kernels == {"heavy_k", "light_k"}
    for row in table["kernels"]:
        assert "marginal_frac" in row and "speedup_if_free" in row
    out = capsys.readouterr().out
    assert '"ablation_table": "stub"' in out
    # Every per-kernel ledger is tainted and self-diff-rejected.
    for k in ("heavy_k", "light_k"):
        ledger = str(tmp_path / f"stub.ablate.{k}.json")
        doc = ledger_mod.load(ledger)
        assert doc["tainted"]["kernels"] == [k]
        assert sfprof_main(["diff", ledger, ledger, "--gate"]) == 1
    # The sweep leaves the process disarmed and the NEXT capture clean.
    assert not ablation.armed
    telemetry.enable()
    assert "tainted" not in telemetry.snapshot()


def test_run_ablation_records_load_bearing_kernels_as_evidence(tmp_path):
    """A config whose asserts reject zeroed results yields an
    error-with-evidence row, never a crashed sweep."""
    import bench_suite

    jcount = instrument_jit(jax.jit(lambda x: x.sum()), name="count_k")

    def strict_bench():
        # Two calls: the armed leg's first is the real learning call,
        # the second returns zeros and trips the underfill assert.
        for _ in range(2):
            out = float(np.asarray(jcount(jnp.ones((8,), jnp.float32))))
            assert out > 0, "underfilled"
        return {"config": "strict", "points_per_sec": 10.0,
                "value": 10.0}

    (table,) = bench_suite.run_ablation(
        [("strict", strict_bench)], top_n=1)
    (row,) = table["kernels"]
    assert row["kernel"] == "count_k"
    assert "AssertionError" in row["error"]
    assert "load-bearing" in row["note"]
