"""Pipelined ingest runtime (spatialflink_tpu/pipeline.py) — policy
parsing, the bounded executor's ordering/lag/drain contracts, the
circuit-breaker collapse, and the BIT-IDENTICAL parity of every
integrated path: run_wire_panes (codec on and off), the tjoin segmented
scan, and the driver's split-protocol window processing. The pipeline
may move sync points; it may never move results."""

import json
import os
import sys

import numpy as np
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if REPO not in sys.path:
    sys.path.insert(0, REPO)

from spatialflink_tpu import overload  # noqa: E402
from spatialflink_tpu import pipeline  # noqa: E402
from spatialflink_tpu.faults import InjectedFault, faults  # noqa: E402
from spatialflink_tpu.grid import UniformGrid  # noqa: E402
from spatialflink_tpu.models.objects import Point  # noqa: E402
from spatialflink_tpu.operators.query_config import (  # noqa: E402
    QueryConfiguration,
    QueryType,
)
from spatialflink_tpu.telemetry import telemetry  # noqa: E402


@pytest.fixture(autouse=True)
def _clean():
    yield
    pipeline.uninstall()
    overload.uninstall()
    faults.disarm()
    telemetry.disable()


# ---------------------------------------------------------------------------
# Policy


class TestPolicy:
    def test_defaults(self):
        pol = pipeline.PipelinePolicy()
        assert (pol.depth, pol.fetch_lag, pol.codec) == (2, 2, "off")

    def test_strict_parse_rejects_unknown_keys(self):
        with pytest.raises(ValueError, match="unknown keys"):
            pipeline.PipelinePolicy.from_dict({"depht": 3})

    @pytest.mark.parametrize("bad", [
        {"depth": 0}, {"fetch_lag": -1}, {"codec": "lz4"},
    ])
    def test_invalid_values_raise(self, bad):
        with pytest.raises(ValueError):
            pipeline.PipelinePolicy(**bad)

    def test_from_env_forms(self, tmp_path):
        assert pipeline.PipelinePolicy.from_env("1").depth == 2
        assert pipeline.PipelinePolicy.from_env("on").codec == "off"
        pol = pipeline.PipelinePolicy.from_env(
            '{"depth": 4, "codec": "delta"}'
        )
        assert (pol.depth, pol.codec) == (4, "delta")
        p = tmp_path / "pol.json"
        p.write_text(json.dumps({"fetch_lag": 7}))
        assert pipeline.PipelinePolicy.from_env(str(p)).fetch_lag == 7

    def test_arm_from_env(self, monkeypatch):
        monkeypatch.delenv("SFT_PIPELINE", raising=False)
        assert pipeline.arm_from_env() is False
        monkeypatch.setenv("SFT_PIPELINE", '{"depth": 3}')
        assert pipeline.arm_from_env() is True
        assert pipeline.policy().depth == 3

    def test_install_uninstall(self):
        pol = pipeline.install(pipeline.PipelinePolicy())
        assert pipeline.policy() is pol
        pipeline.uninstall()
        assert pipeline.policy() is None


# ---------------------------------------------------------------------------
# Executor (fake stages — no device)


def _tracing_executor(pol, log, n_items=8, gap_every=None):
    def ship(i):
        log.append(("ship", i))
        return f"staged{i}"

    def compute(i, staged):
        assert staged == f"staged{i}"
        log.append(("compute", i))
        if gap_every and i % gap_every == 0:
            return None
        return i

    def fetch(works):
        log.append(("fetch", tuple(works)))
        return [w * 10 for w in works]

    ex = pipeline.PipelinedExecutor(pol, ship=ship, compute=compute,
                                    fetch=fetch)
    return ex, list(range(n_items))


class TestExecutor:
    def test_order_and_overlap_shape(self):
        log = []
        ex, items = _tracing_executor(
            pipeline.PipelinePolicy(depth=2, fetch_lag=2), log)
        out = list(ex.run(items))
        assert out == [i * 10 for i in range(8)]  # ordered results
        # ship ahead: item i+1's ship precedes item i's compute
        assert log.index(("ship", 1)) < log.index(("compute", 0))
        # lag: item 0's fetch happens only after item 2's compute
        first_fetch = next(k for k, e in enumerate(log)
                           if e[0] == "fetch")
        assert log[first_fetch] == ("fetch", (0,))
        assert log.index(("compute", 2)) < first_fetch
        # final drain is ONE batched fetch of the whole tail
        assert log[-1] == ("fetch", (6, 7))

    def test_ship_ahead_never_exceeds_depth(self):
        log = []
        ex, items = _tracing_executor(
            pipeline.PipelinePolicy(depth=3, fetch_lag=1), log)
        list(ex.run(items))
        computed = shipped = 0
        for e in log:
            if e[0] == "ship":
                shipped += 1
            elif e[0] == "compute":
                computed += 1
            assert shipped - computed <= 3

    def test_depth1_lag0_is_synchronous_cadence(self):
        log = []
        ex, items = _tracing_executor(
            pipeline.PipelinePolicy(depth=1, fetch_lag=0), log)
        out = list(ex.run(items))
        assert out == [i * 10 for i in range(8)]
        # strict ship→compute→fetch per item, no overlap
        per_item = [("ship", 0), ("compute", 0), ("fetch", (0,))]
        assert log[:3] == per_item

    def test_gap_items_yield_nothing(self):
        log = []
        ex, items = _tracing_executor(
            pipeline.PipelinePolicy(depth=2, fetch_lag=2), log,
            gap_every=2)
        out = list(ex.run(items))
        assert out == [10, 30, 50, 70]  # odd items only

    def test_empty_stream(self):
        log = []
        ex, _ = _tracing_executor(pipeline.PipelinePolicy(), log)
        assert list(ex.run([])) == []
        assert log == []

    def test_fault_points_fire(self):
        log = []
        ex, items = _tracing_executor(pipeline.PipelinePolicy(), log)
        faults.arm([{"point": "pipeline.ship", "at": 3,
                     "times": 10_000}])
        with pytest.raises(InjectedFault):
            list(ex.run(items))
        faults.arm([{"point": "pipeline.fetch", "at": 1,
                     "times": 10_000}])
        log2 = []
        ex2, items2 = _tracing_executor(pipeline.PipelinePolicy(), log2)
        with pytest.raises(InjectedFault):
            list(ex2.run(items2))

    def test_breaker_collapse_and_resume(self):
        """An OPEN overload circuit collapses the executor to the
        synchronous cadence (no stacking onto a dead device path), emits the
        transition events, and re-opens when the breaker closes."""
        pol = overload.OverloadPolicy(breaker_failures=1)
        ctrl = overload.install(
            overload.OverloadController(pol))
        telemetry.enable()
        ctrl.breaker.record_failure(0, "boom")  # → open
        assert ctrl.breaker.state == "open"
        log = []
        ex, items = _tracing_executor(
            pipeline.PipelinePolicy(depth=3, fetch_lag=3), log,
            n_items=4)
        out = list(ex.run(items))
        assert out == [0, 10, 20, 30]
        # collapsed: every item fetched before the next computes
        assert log[2] == ("fetch", (0,))
        snap = telemetry.snapshot()["pipeline"]
        assert snap["collapses"] == 1
        assert snap["sync"] == 4
        names = [e["name"] for e in telemetry.events]
        assert "pipeline_collapsed" in names
        # breaker closes mid-stream → executor resumes overlapping
        log2 = []
        ex2, items2 = _tracing_executor(
            pipeline.PipelinePolicy(depth=2, fetch_lag=2), log2,
            n_items=6)

        def fetch_and_heal(works):
            if ctrl.breaker.state != "closed":
                ctrl.breaker.state = "closed"
            log2.append(("fetch", tuple(works)))
            return [w * 10 for w in works]

        ex2._fetch_fn = fetch_and_heal
        ctrl.breaker.state = "open"
        out2 = list(ex2.run(items2))
        assert out2 == [i * 10 for i in range(6)]
        assert "pipeline_resumed" in [e["name"] for e in
                                      telemetry.events]


# ---------------------------------------------------------------------------
# run_wire_panes parity (the headline operator path)


GRID = UniformGrid(10, 0.0, 10.0, 0.0, 10.0)
CONF = QueryConfiguration(QueryType.WindowBased, window_size=4.0,
                          slide_step=1.0)


def _wire_fixture(rng, n=3000, with_gap=True):
    from spatialflink_tpu.streams.wire import WireFormat, wire_panes

    wf = WireFormat.for_grid(GRID)
    ts = np.sort(rng.integers(0, 30_000, n)).astype(np.int64)
    if with_gap:  # event-time gap → gap windows + multi-pane bursts
        ts[ts > 12_000] += 9_000
        ts = np.sort(ts)
    xy = np.stack([rng.uniform(0, 10, n), rng.uniform(0, 10, n)],
                  axis=1)
    xyf = wf.dequantize_np(wf.quantize(xy))
    # num_segments 64 sits ABOVE XLA:CPU's host-buffer zero-copy
    # aliasing threshold (~128 B): the codec's predictor tables MUST be
    # shipped as copies or the encoder's in-place updates corrupt the
    # device table — a 32-segment fixture would mask that (found live).
    oids = rng.integers(0, 64, n).astype(np.int32)
    panes = list(wire_panes(
        [{"ts": ts, "x": xyf[:, 0].astype(np.float64),
          "y": xyf[:, 1].astype(np.float64), "oid": oids}],
        wf, CONF.slide_step_ms, start_ms=0,
    ))
    return wf, panes


def _collect_wire(op, panes, wf, flush=True):
    return [
        (s, e, list(map(int, o)), [round(float(x), 9) for x in d], nv)
        for s, e, o, d, nv in op.run_wire_panes(
            panes, Point(x=5.0, y=5.0), 3.0, 6, 64, wf, start_ms=0,
            flush_at_end=flush,
        )
    ]


@pytest.fixture
def rng():
    return np.random.default_rng(77)


class TestRunWirePanesPipelined:
    @pytest.mark.parametrize("polkw", [
        {},
        {"codec": "delta"},
        {"depth": 4, "fetch_lag": 3, "codec": "delta"},
        {"depth": 1, "fetch_lag": 0},
    ])
    def test_bit_identical_to_sync(self, rng, polkw):
        from spatialflink_tpu.operators.knn_query import (
            PointPointKNNQuery,
        )

        wf, panes = _wire_fixture(rng)
        pipeline.uninstall()
        base = _collect_wire(PointPointKNNQuery(CONF, GRID), panes, wf)
        assert base, "vacuous parity fixture"
        pipeline.install(pipeline.PipelinePolicy(**polkw))
        got = _collect_wire(PointPointKNNQuery(CONF, GRID), panes, wf)
        assert got == base

    def test_kill_and_resume_mid_overlap(self, rng, tmp_path):
        """The carry publishes per YIELDED window: a checkpoint cut
        anywhere mid-stream resumes to the exact baseline — codec
        predictor state deliberately restarts (results can't change,
        only compression continuity)."""
        from spatialflink_tpu.checkpoint import (
            load_checkpoint,
            operator_state,
            restore_operator,
            save_checkpoint,
        )
        from spatialflink_tpu.operators.knn_query import (
            PointPointKNNQuery,
        )

        wf, panes = _wire_fixture(rng)
        pipeline.uninstall()
        base = _collect_wire(PointPointKNNQuery(CONF, GRID), panes, wf)
        pipeline.install(pipeline.PipelinePolicy(codec="delta",
                                                 depth=3, fetch_lag=2))
        cut = len(panes) // 3
        op1 = PointPointKNNQuery(CONF, GRID)
        part1 = _collect_wire(op1, panes[:cut], wf, flush=False)
        path = str(tmp_path / "wire.ckpt")
        save_checkpoint(path, op=operator_state(op1))
        op2 = PointPointKNNQuery(CONF, GRID)
        restore_operator(op2, load_checkpoint(path)["op"])
        part2 = _collect_wire(op2, panes[cut:], wf)
        assert part1 + part2 == base
        assert part1 and part2

    def test_checkpoint_cut_at_every_yield_loses_nothing(self, rng):
        """Per-YIELD carry contract: snapshot the operator after EACH
        yielded window of a pipelined run and resume from that
        snapshot's pane position — the stitched output must equal the
        baseline at EVERY cut. A fetch batch that published its last
        window's carry before yielding its first would skip the batch
        siblings on resume (lost egress — the bug this pins)."""
        from spatialflink_tpu.checkpoint import (
            load_checkpoint,
            operator_state,
            restore_operator,
            save_checkpoint,
        )
        from spatialflink_tpu.operators.knn_query import (
            PointPointKNNQuery,
        )

        wf, panes = _wire_fixture(rng, n=1500)
        pipeline.uninstall()
        base = _collect_wire(PointPointKNNQuery(CONF, GRID), panes, wf)
        # fetch_lag 3 → the final drain fetches a multi-window batch.
        # Cuts stop BEFORE the trailing flush: synthetic flush panes
        # never advance the carry (by design, sync path identical), so
        # a checkpoint cut mid-flush replays the whole flush — the
        # documented call-boundary contract, not a pipeline property.
        ppw = CONF.window_size_ms // CONF.slide_step_ms
        last_cut = len(base) - ppw
        cuts = sorted(set(
            list(range(1, 7)) + list(range(7, last_cut, 5))
            + [last_cut]
        ))
        pipeline.install(pipeline.PipelinePolicy(depth=2, fetch_lag=3,
                                                 codec="delta"))
        for cut in cuts:
            op1 = PointPointKNNQuery(CONF, GRID)
            gen = op1.run_wire_panes(panes, Point(x=5.0, y=5.0), 3.0,
                                     6, 64, wf, start_ms=0)
            head = []
            for out in gen:
                head.append((out[0], out[1], list(map(int, out[2])),
                             [round(float(x), 9) for x in out[3]],
                             out[4]))
                if len(head) == cut:
                    break
            gen.close()  # the kill: generator abandoned mid-batch
            next_pane = int(op1._wire_pane_carry["next_pane"])
            st = operator_state(op1)
            op2 = PointPointKNNQuery(CONF, GRID)
            import tempfile

            with tempfile.NamedTemporaryFile(suffix=".ckpt") as f:
                save_checkpoint(f.name, op=st)
                restore_operator(op2, load_checkpoint(f.name)["op"])
            tail = _collect_wire(op2, panes[next_pane:], wf)
            assert head + tail == base, f"cut after window {cut}"

    def test_codec_gauges_and_counters_recorded(self, rng):
        from spatialflink_tpu.operators.knn_query import (
            PointPointKNNQuery,
        )

        wf, panes = _wire_fixture(rng, with_gap=False)
        telemetry.enable()
        pipeline.install(pipeline.PipelinePolicy(codec="delta"))
        _collect_wire(PointPointKNNQuery(CONF, GRID), panes, wf)
        snap = telemetry.snapshot()
        wcg = snap["wire_codec"]
        assert wcg["panes"] > 0
        assert wcg["raw_bytes"] > 0
        assert wcg["coded_bytes"] > 0
        assert snap["pipeline"]["windows"] > 0
        assert snap["pipeline"]["overlapped"] > 0
        # the decode kernel rides the compiled-shape ladder
        assert telemetry.distinct_shapes("wire_pane_decode") <= 8


# ---------------------------------------------------------------------------
# tjoin segmented scan parity


class TestTJoinSegmentedScan:
    def _chunks(self, side, n_chunks=10, per=8):
        rng = np.random.default_rng(21 + side)
        out = []
        for c in range(n_chunks):
            base = c * per
            out.append({
                "ts": np.arange(base, base + per, dtype=np.int64) * 250,
                "x": rng.uniform(0.0, 8.0, per),
                "y": rng.uniform(0.0, 8.0, per),
                "oid": (np.arange(base, base + per) % 5).astype(
                    np.int32),
            })
        return out

    def _collect(self):
        from spatialflink_tpu.operators.trajectory import TJoinQuery

        grid = UniformGrid(8, 0.0, 8.0, 0.0, 8.0)
        conf = QueryConfiguration(QueryType.WindowBased,
                                  window_size=2.0, slide_step=0.5)
        op = TJoinQuery(conf, grid)
        return [
            (s, e, list(map(int, lo)), list(map(int, ro)),
             [float(x) for x in dd], c, o)
            for s, e, lo, ro, dd, c, o in op.run_soa_panes(
                self._chunks(0), self._chunks(1), 1.5, 5,
                backend="device",
            )
        ]

    @pytest.mark.parametrize("polkw", [
        {}, {"depth": 4, "fetch_lag": 3}, {"depth": 1, "fetch_lag": 0},
    ])
    def test_segmented_scan_bit_identical(self, polkw):
        """Chained-carry segments (with explicit expiring panes) must
        reproduce the monolithic scan exactly — the expiring-pane slice
        is the part a naive split gets wrong (stale pairs leak into
        late windows)."""
        pipeline.uninstall()
        base = self._collect()
        assert base
        pipeline.install(pipeline.PipelinePolicy(**polkw))
        got = self._collect()
        assert got == base


# ---------------------------------------------------------------------------
# driver integration (split protocol)


def _run_range_driver(workdir, pol, fault_plan=None):
    from spatialflink_tpu.driver import (
        RetryPolicy,
        WindowedDataflowDriver,
        _toy_pipeline,
        render_range_result,
    )
    from spatialflink_tpu.operators.range_query import (
        PointPointRangeQuery,
    )
    from spatialflink_tpu.streams.sinks import TransactionalFileSink

    grid, conf, source, query = _toy_pipeline()
    sink = TransactionalFileSink(os.path.join(workdir, "egress.csv"))
    drv = WindowedDataflowDriver(
        checkpoint_path=os.path.join(workdir, "ckpt.bin"),
        checkpoint_every=2, sink=sink,
        retry=RetryPolicy(max_retries=1, backoff_s=0.0),
        failover=False, pipeline=pol,
    )
    op = PointPointRangeQuery(conf, grid)
    if fault_plan:
        faults.arm(fault_plan)
    try:
        for res in op.run(source(), [query], 1.5, driver=drv):
            for line in render_range_result(res):
                sink.stage(line)
    finally:
        faults.disarm()
    return drv


class TestDriverPipelined:
    def test_egress_byte_identical(self, tmp_path):
        a = tmp_path / "a"
        b = tmp_path / "b"
        a.mkdir()
        b.mkdir()
        _run_range_driver(str(a), None)
        _run_range_driver(
            str(b), pipeline.PipelinePolicy(depth=2, fetch_lag=3))
        wa = (a / "egress.csv").read_bytes()
        assert wa
        assert (b / "egress.csv").read_bytes() == wa

    def test_module_policy_applies_without_explicit_arg(self, tmp_path):
        a = tmp_path / "a"
        b = tmp_path / "b"
        a.mkdir()
        b.mkdir()
        _run_range_driver(str(a), None)
        telemetry.enable()
        pipeline.install(pipeline.PipelinePolicy(fetch_lag=4))
        _run_range_driver(str(b), None)
        counters = telemetry.pipeline_counters()
        telemetry.disable()
        assert counters.get("overlapped", 0) > 0
        assert (b / "egress.csv").read_bytes() == \
            (a / "egress.csv").read_bytes()

    def test_transient_pipeline_fault_contained(self, tmp_path):
        """A raise-kind fault at pipeline.ship/fetch degrades that
        window to the synchronous retry ladder — the run completes
        with byte-identical egress (containment; the crash legs are
        the chaos matrix's abort-kind subprocesses)."""
        a = tmp_path / "a"
        b = tmp_path / "b"
        c = tmp_path / "c"
        for d in (a, b, c):
            d.mkdir()
        _run_range_driver(str(a), None)
        pol = pipeline.PipelinePolicy(depth=2, fetch_lag=2)
        _run_range_driver(str(b), pol, fault_plan=[
            {"point": "pipeline.ship", "at": 3, "times": 2},
        ])
        _run_range_driver(str(c), pol, fault_plan=[
            {"point": "pipeline.fetch", "at": 2, "times": 1},
        ])
        want = (a / "egress.csv").read_bytes()
        assert want
        assert (b / "egress.csv").read_bytes() == want
        assert (c / "egress.csv").read_bytes() == want

    def test_breaker_collapse_instrumented(self, tmp_path):
        """An open circuit during a pipelined driver run must leave the
        same observable trail as the executor's collapse: the
        pipeline_collapsed instant, the collapses counter, and sync
        window counts — a device-path death mid-overlap may not be
        invisible in the ledger."""
        telemetry.enable()
        pol = overload.OverloadPolicy(breaker_failures=1)
        ctrl = overload.install(overload.OverloadController(pol))
        ctrl.breaker.record_failure(0, "boom")
        assert ctrl.breaker.state == "open"
        d = tmp_path / "d"
        d.mkdir()
        _run_range_driver(
            str(d), pipeline.PipelinePolicy(depth=2, fetch_lag=2))
        counters = telemetry.pipeline_counters()
        assert counters.get("collapses") == 1
        assert counters.get("sync", 0) > 0
        assert counters.get("overlapped", 0) == 0
        names = [e["name"] for e in telemetry.events]
        assert "pipeline_collapsed" in names
        assert (d / "egress.csv").read_bytes()  # run still completed

    def test_failover_mid_flight_keeps_order_and_degraded_honest(self):
        """A fetch failure that exhausts retries and fails over while
        LATER windows sit in flight must (a) drain those windows before
        any post-failover window is yielded — committed egress order
        identical to the synchronous failover run — and (b) not charge
        device-answered in-flight windows as degraded."""
        from spatialflink_tpu.driver import (
            RetryPolicy,
            WindowedDataflowDriver,
            _toy_pipeline,
        )
        from spatialflink_tpu.operators.range_query import (
            PointPointRangeQuery,
        )

        grid, conf, source, _query = _toy_pipeline()

        def build(pol, ctrl):
            op = PointPointRangeQuery(conf, grid)
            drv = WindowedDataflowDriver(
                failover=True,
                retry=RetryPolicy(max_retries=0, backoff_s=0.0),
                pipeline=pol, overload=ctrl,
            )
            drv.attach(op)
            state = {"n": 0}

            def process(win):
                # The device path dies at the 3rd window (sync AND
                # fetch forms) — retries exhaust, failover flips the
                # backend while in-flight windows remain.
                if win.start == poison["start"]:
                    raise RuntimeError("device died")
                return ("dev", win.start, win.end, len(win.events))

            def pipeline_compute(win):
                state["n"] += 1
                return win

            def pipeline_fetch(win):
                return process(win)

            process.pipeline_compute = pipeline_compute
            process.pipeline_fetch = pipeline_fetch

            def fallback(win):
                return ("fb", win.start, win.end, len(win.events))

            drv.bind(op, process, fallback=fallback)
            return op, drv

        # Find the 3rd fired window's start with a throwaway run.
        poison = {"start": None}
        op0 = PointPointRangeQuery(conf, grid)
        starts = [w.start for w in op0.windows(source())]
        poison["start"] = starts[2]

        ctrl_sync = overload.OverloadController(overload.OverloadPolicy())
        op, drv = build(None, ctrl_sync)
        sync_out = list(drv.run(source()))
        overload.uninstall()
        assert ("fb", poison["start"]) == sync_out[2][:2]

        ctrl_pipe = overload.OverloadController(overload.OverloadPolicy())
        op, drv = build(
            pipeline.PipelinePolicy(depth=2, fetch_lag=2), ctrl_pipe)
        pipe_out = list(drv.run(source()))
        overload.uninstall()
        assert pipe_out == sync_out  # ordered, identical routing
        # Degraded accounting: only the genuinely fallback-answered
        # windows count — identical to the synchronous run's tally.
        assert ctrl_pipe.snapshot()["degraded_windows"] == \
            ctrl_sync.snapshot()["degraded_windows"]

    def test_no_split_protocol_means_sync(self, tmp_path):
        """A process without pipeline_compute/fetch attributes runs the
        exact synchronous loop even with a policy armed."""
        from spatialflink_tpu.driver import (
            WindowedDataflowDriver,
        )
        from spatialflink_tpu.operators.trajectory import TStatsQuery
        from spatialflink_tpu.streams.soa import SoaWindowAssembler

        grid = UniformGrid(8, 0.0, 8.0, 0.0, 8.0)
        conf = QueryConfiguration(QueryType.WindowBased,
                                  window_size=2.0, slide_step=1.0)
        op = TStatsQuery(conf, grid)
        telemetry.enable()
        pipeline.install(pipeline.PipelinePolicy())
        drv = WindowedDataflowDriver(failover=False)

        def process(win):
            return (win.start, win.count)

        drv.bind(op, process)

        def chunks():
            rng = np.random.default_rng(3)
            for i in range(6):
                yield {
                    "ts": np.arange(i * 5, i * 5 + 5,
                                    dtype=np.int64) * 200,
                    "x": rng.uniform(0, 8, 5),
                    "y": rng.uniform(0, 8, 5),
                    "oid": np.zeros(5, np.int32),
                }

        asm = SoaWindowAssembler(conf.window_size_ms,
                                 conf.slide_step_ms)
        out = list(drv.run_soa(chunks(), asm))
        assert out
        assert telemetry.pipeline_counters() == {}


# ---------------------------------------------------------------------------
# sfprof surfaces


class TestSfprofSurfaces:
    def test_health_notes_pipeline_counters(self, tmp_path, capsys):
        telemetry.enable()
        telemetry.record_pipeline(windows=5, overlapped=4, sync=1,
                                  drains=2, collapses=1)
        ledger = tmp_path / "ledger.json"
        telemetry.write_ledger(str(ledger))
        telemetry.disable()
        from tools.sfprof.cli import main as sfprof_main

        assert sfprof_main(["health", str(ledger)]) == 0
        out = capsys.readouterr().out
        assert "note pipeline:" in out
        assert "STALLED" in out

    def test_events_registry_covers_pipeline_transitions(self):
        from tools.sfprof import events as ev

        assert ev.classify("pipeline_collapsed") == "pipeline"
        assert ev.classify("pipeline_resumed") == "pipeline"

    def test_report_prints_codec_and_link_utilization(self, tmp_path,
                                                      capsys):
        import time as _time

        telemetry.enable()
        telemetry.account_wire(6000, 2400)
        telemetry.record_link_sample(0.5, 25.0, 262144)
        telemetry.account_h2d(1_000_000)
        with telemetry.span("window.x"):
            _time.sleep(0.01)
        ledger = tmp_path / "ledger.json"
        telemetry.write_ledger(str(ledger))
        telemetry.disable()
        from tools.sfprof.cli import main as sfprof_main

        assert sfprof_main(["report", str(ledger)]) == 0
        out = capsys.readouterr().out
        assert "wire bytes, post-codec" in out
        assert "wire codec: 1 panes" in out
        assert "link utilization:" in out
        assert "MB/s round-trip bandwidth" in out
