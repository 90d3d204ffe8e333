"""The composed dataflow DAG (spatialflink_tpu/dag.py): topology,
per-node retry/failover/breaker independence, the atomic unit
checkpoint (multi-sink exactly-once), per-node SLO budgets (live +
sfprof twin), telemetry surfaces, and the streaming_job option-10
wiring."""

import json
import os
import sys

import numpy as np
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if REPO not in sys.path:
    sys.path.insert(0, REPO)

from spatialflink_tpu import dag as dag_mod  # noqa: E402
from spatialflink_tpu import overload, qserve  # noqa: E402
from spatialflink_tpu.checkpoint import (  # noqa: E402
    CheckpointCorruptError,
    load_checkpoint,
)
from spatialflink_tpu.dag import (  # noqa: E402
    DataflowDAG,
    FunctionNode,
    StayTimeNode,
    build_sncb_dag,
    _toy_sncb_stream,
)
from spatialflink_tpu.driver import (  # noqa: E402
    RetryPolicy,
    WindowedDataflowDriver,
)
from spatialflink_tpu.faults import InjectedFault, faults  # noqa: E402
from spatialflink_tpu.grid import UniformGrid  # noqa: E402
from spatialflink_tpu.models.objects import Point  # noqa: E402
from spatialflink_tpu.operators.query_config import (  # noqa: E402
    QueryConfiguration,
    QueryType,
)
from spatialflink_tpu.streams.sinks import (  # noqa: E402
    MultiSink,
    TransactionalFileSink,
)
from spatialflink_tpu.telemetry import telemetry  # noqa: E402


@pytest.fixture(autouse=True)
def _clean():
    yield
    faults.disarm()
    telemetry.disable()
    dag_mod.uninstall()
    qserve.uninstall()
    overload.uninstall()


# ---------------------------------------------------------------------------
# Toy two-node function DAG (fast unit harness)


def _toy_conf():
    return QueryConfiguration(QueryType.WindowBased, window_size=2.0,
                              slide_step=1.0)


def _toy_points(n=60):
    rng = np.random.default_rng(5)
    xs = rng.uniform(0.0, 8.0, n)
    ys = rng.uniform(0.0, 8.0, n)
    return [
        Point(obj_id=f"o{i % 5}", timestamp=100 * i,
              x=float(xs[i]), y=float(ys[i]))
        for i in range(n)
    ]


def _count_node(name, fail_windows=(), fallback=True, upstream=None):
    """A node counting window events; optionally raising on the given
    window starts (device path only)."""

    def fn(win, results):
        if win.start in fail_windows:
            raise RuntimeError(f"boom@{win.start}")
        return ("device", len(win.events))

    def fb(win, results):
        return ("fallback", len(win.events))

    def render(result, start, end):
        yield f"{start},{end},{result[1]}"

    return FunctionNode(name, fn, fallback=fb if fallback else None,
                        render_fn=render, upstream=upstream)


def _toy_dag(tmp_path, nodes, **driver_kw):
    grid = UniformGrid(8, 0.0, 8.0, 0.0, 8.0)
    dag = DataflowDAG(_toy_conf(), grid, nodes,
                      out_dir=str(tmp_path / "egress"),
                      retry=RetryPolicy(max_retries=1, backoff_s=0.0,
                                        sleep=lambda s: None))
    return dag


class TestTopology:
    def test_upstream_orders_nodes_and_passes_results(self, tmp_path):
        seen = {}

        def up_fn(win, results):
            return len(win.events)

        def down_fn(win, results):
            seen[win.start] = results["up"]
            return results["up"] * 2

        up = FunctionNode("up", up_fn)
        down = FunctionNode("down", down_fn, upstream="up")
        # Constructed downstream-first: topo sort must still run `up`
        # before `down` every window.
        dag = _toy_dag(tmp_path, [down, up])
        assert dag.dag_nodes == ("up", "down")
        out = list(dag.run(iter(_toy_points())))
        assert out and seen
        for res in out:
            assert res.counts["up"] >= 1

    def test_cycle_and_unknown_upstream_are_loud(self, tmp_path):
        a = FunctionNode("a", lambda w, r: 1, upstream="b")
        b = FunctionNode("b", lambda w, r: 1, upstream="a")
        with pytest.raises(ValueError, match="cycle"):
            _toy_dag(tmp_path, [a, b])
        c = FunctionNode("c", lambda w, r: 1, upstream="ghost")
        with pytest.raises(ValueError, match="unknown upstream"):
            _toy_dag(tmp_path, [c])

    def test_duplicate_names_rejected(self, tmp_path):
        with pytest.raises(ValueError, match="duplicate"):
            _toy_dag(tmp_path, [FunctionNode("x", lambda w, r: 1),
                                FunctionNode("x", lambda w, r: 2)])


class TestPerNodeSelfHealing:
    def test_failover_is_node_local(self, tmp_path):
        """One node's device path dies permanently → that node (and
        ONLY that node) runs its twin for the rest of the run; the
        sibling stays on device, results keep flowing on both sinks."""
        telemetry.enable()
        sick = _count_node("sick", fail_windows=range(-10**9, 10**9))
        healthy = _count_node("healthy")
        dag = _toy_dag(tmp_path, [sick, healthy])
        out = list(dag.run(iter(_toy_points())))
        assert len(out) > 3
        snap = dag.snapshot()
        assert snap["nodes"]["sick"]["backend"] == "fallback"
        assert snap["nodes"]["sick"]["failovers"] == 1
        assert snap["nodes"]["sick"]["degraded_windows"] == len(out)
        assert snap["nodes"]["healthy"]["backend"] == "device"
        assert snap["nodes"]["healthy"]["degraded_windows"] == 0
        # Retries preceded the failover (per-node ladder).
        assert snap["nodes"]["sick"]["retries"] == 1
        names = [e["name"] for e in telemetry.events]
        assert "dag_node_failover:sick" in names
        # Both sinks carry every window.
        sick_lines = (tmp_path / "egress" / "sick.csv").read_bytes()
        ok_lines = (tmp_path / "egress" / "healthy.csv").read_bytes()
        assert sick_lines.count(b"\n") == ok_lines.count(b"\n") > 0

    def test_transient_fault_is_retried_node_locally(self, tmp_path):
        sick = _count_node("sick", fail_windows=())
        calls = {"n": 0}
        real = sick._fn

        def flaky(win, results):
            calls["n"] += 1
            if calls["n"] == 3:
                raise RuntimeError("transient")
            return real(win, results)

        sick._fn = flaky
        dag = _toy_dag(tmp_path, [sick])
        out = list(dag.run(iter(_toy_points())))
        assert len(out) > 3
        snap = dag.snapshot()
        assert snap["nodes"]["sick"]["retries"] == 1
        assert snap["nodes"]["sick"]["failovers"] == 0
        assert snap["nodes"]["sick"]["backend"] == "device"

    def test_no_fallback_node_crashes_the_run(self, tmp_path):
        sick = _count_node("sick", fail_windows=range(-10**9, 10**9),
                           fallback=False)
        dag = _toy_dag(tmp_path, [sick])
        with pytest.raises(RuntimeError, match="boom"):
            list(dag.run(iter(_toy_points())))

    def test_stateful_node_is_never_retried(self, tmp_path):
        hits = {"n": 0}

        def stateful(win, results):
            hits["n"] += 1
            raise RuntimeError("half-applied")

        node = FunctionNode("state", stateful, idempotent=False)
        dag = _toy_dag(tmp_path, [node])
        with pytest.raises(RuntimeError, match="half-applied"):
            list(dag.run(iter(_toy_points())))
        assert hits["n"] == 1  # single attempt: no retry, no twin

    def test_driver_never_rerruns_the_node_walk(self, tmp_path):
        """The DAG's window process is marked non-idempotent: a
        driver-level retry would re-stage lines of nodes that already
        completed. The driver must crash instead."""
        sick = _count_node("sick", fail_windows=range(-10**9, 10**9),
                           fallback=False)
        dag = _toy_dag(tmp_path, [sick])
        drv = WindowedDataflowDriver(
            retry=RetryPolicy(max_retries=5, backoff_s=0.0))
        with pytest.raises(RuntimeError, match="boom"):
            list(dag.run(iter(_toy_points()), driver=drv))
        assert drv.stats["retries"] == 0

    def test_breaker_is_per_node(self, tmp_path):
        """With a breaker-configured overload policy, each
        fallback-capable node gets its OWN circuit: the sick node's
        circuit opens (windows route to its twin with no retry) while
        the healthy sibling's stays closed."""
        sick = _count_node("sick", fail_windows=range(-10**9, 10**9))
        healthy = _count_node("healthy")
        dag = _toy_dag(tmp_path, [sick, healthy])
        ctrl = overload.OverloadController(overload.OverloadPolicy(
            breaker_failures=2, breaker_probe_every=1000,
        ))
        drv = WindowedDataflowDriver(
            retry=RetryPolicy(max_retries=0, backoff_s=0.0),
            overload=ctrl,
        )
        out = list(dag.run(iter(_toy_points()), driver=drv))
        assert len(out) > 4
        snap = dag.snapshot()
        assert snap["nodes"]["sick"]["breaker"]["state"] == "open"
        assert snap["nodes"]["sick"]["backend"] == "device"  # no perm.
        assert snap["nodes"]["healthy"]["breaker"]["state"] == "closed"
        assert snap["nodes"]["sick"]["degraded_windows"] == len(out)


# ---------------------------------------------------------------------------
# The atomic unit checkpoint (multi-sink exactly-once)


def _run_sncb_leg(workdir, fault_plan=None, n_events=150):
    dag = build_sncb_dag(
        os.path.join(workdir, "egress"),
        qserve_queries=None,
        retry=RetryPolicy(max_retries=1, backoff_s=0.0),
    )
    driver = WindowedDataflowDriver(
        checkpoint_path=os.path.join(workdir, "ckpt.bin"),
        checkpoint_every=2, sink=None,
        retry=RetryPolicy(max_retries=1, backoff_s=0.0),
        failover=False,
    )
    source = _toy_sncb_stream(n_events)
    if fault_plan:
        faults.arm(fault_plan)
    try:
        for _ in dag.run(source(), driver=driver):
            pass
    finally:
        faults.disarm()
        qserve.uninstall()
        dag_mod.uninstall()
    return driver, dag


SNCB_SINKS = ("q1", "q2", "q3", "q4", "q5", "staytime", "qserve")


def _sink_bytes(workdir):
    out = {}
    for name in SNCB_SINKS:
        with open(os.path.join(workdir, "egress", f"{name}.csv"),
                  "rb") as f:
            out[name] = f.read()
    return out


@pytest.fixture(scope="module")
def sncb_clean(tmp_path_factory):
    """One clean 7-node run shared by the kill/resume legs below."""
    d = tmp_path_factory.mktemp("dag_clean")
    _run_sncb_leg(str(d))
    want = _sink_bytes(str(d))
    assert sum(len(v) for v in want.values()) > 0
    assert all(len(v) > 0 for v in want.values()), {
        k: len(v) for k, v in want.items()}
    return want


class TestUnitCheckpoint:
    @pytest.mark.parametrize("plan", [
        # Between-sink-commits cut: the SECOND unit commit's 2nd
        # sub-append (7 sinks per commit → hit 9), so the crash lands
        # after one sink's bytes of commit #2 are durable, before the
        # next sink's — with commit #1's checkpoint to resume from.
        [{"point": "dag.commit", "at": 9, "times": 10_000}],
        # Mid-node-walk kill (some nodes already staged this window).
        [{"point": "dag.node", "at": 25, "times": 10_000}],
        # Kill mid-registration-churn INSIDE the composed DAG (applies
        # re-hit per window; hit 11 lands on a mid-stream churn
        # command, past the first checkpoint).
        [{"point": "qserve.register", "at": 11, "times": 10_000}],
    ])
    def test_kill_anywhere_resumes_every_sink_exactly(
            self, tmp_path, sncb_clean, plan):
        with pytest.raises(InjectedFault):
            _run_sncb_leg(str(tmp_path), fault_plan=plan)
        drv, dag = _run_sncb_leg(str(tmp_path))  # resume
        assert drv.stats["resumed"] is True
        assert _sink_bytes(str(tmp_path)) == sncb_clean

    def test_unit_checkpoint_carries_all_components(self, tmp_path):
        _run_sncb_leg(str(tmp_path))
        ck = load_checkpoint(os.path.join(str(tmp_path), "ckpt.bin"))
        assert set(ck["egress"]["sinks"]) == set(SNCB_SINKS)
        nodes = ck["op"]["dag"]["nodes"]
        assert set(nodes) == set(SNCB_SINKS)
        # qserve's registry state rides as the node's substate, and the
        # markers match the files on disk (the atomic pair).
        assert "substate" in nodes["qserve"]
        assert nodes["qserve"]["substate"]["queries"]
        for name, marker in ck["egress"]["sinks"].items():
            path = os.path.join(str(tmp_path), "egress", f"{name}.csv")
            assert marker["bytes"] == os.path.getsize(path)
        assert "interner" in ck["op"] and "assembler" in ck["op"]

    def test_one_intern_home(self, tmp_path):
        _, dag = _run_sncb_leg(str(tmp_path))
        interned = set(dag.interner._to_key)
        assert "dev0" in interned            # device ids
        assert {"r0", "ta"} <= interned      # qserve qids + tenants

    def test_resume_fallback_backend_without_twin_is_loud(self,
                                                          tmp_path):
        """A checkpoint taken after a node failed over records
        backend="fallback"; resuming it into a DAG whose node lost its
        twin must fail AT RESTORE (the driver.bind rule per node) —
        never mid-window-walk with earlier nodes' egress staged."""
        sick = _count_node("sick", fail_windows=range(-10**9, 10**9))
        dag = _toy_dag(tmp_path, [sick])
        ck = str(tmp_path / "ck.bin")
        drv = WindowedDataflowDriver(checkpoint_path=ck, sink=None,
                                     checkpoint_every=1)
        list(dag.run(iter(_toy_points()), driver=drv))
        assert dag.snapshot()["nodes"]["sick"]["backend"] == "fallback"
        dag_mod.uninstall()
        twin_less = DataflowDAG(
            _toy_conf(), UniformGrid(8, 0.0, 8.0, 0.0, 8.0),
            [_count_node("sick", fallback=False)],
            out_dir=str(tmp_path / "egress2"))
        drv2 = WindowedDataflowDriver(checkpoint_path=ck, sink=None)
        with pytest.raises(ValueError, match="fallback"):
            list(twin_less.run(iter(_toy_points()), driver=drv2))

    def test_resume_with_missing_node_is_loud(self, tmp_path):
        _run_sncb_leg(str(tmp_path))
        grid = UniformGrid(8, 0.0, 8.0, 0.0, 8.0)
        small = DataflowDAG(_toy_conf(), grid,
                            [FunctionNode("q1", lambda w, r: 1)],
                            out_dir=str(tmp_path / "other"))
        drv = WindowedDataflowDriver(
            checkpoint_path=os.path.join(str(tmp_path), "ckpt.bin"),
            sink=None,
        )
        with pytest.raises(ValueError, match="unknown DAG node"):
            list(small.run(iter([]), driver=drv))


class TestMultiSink:
    def _pair(self, tmp_path):
        return MultiSink({
            "a": TransactionalFileSink(str(tmp_path / "a.csv")),
            "b": TransactionalFileSink(str(tmp_path / "b.csv")),
        })

    def test_torn_tail_on_a_newer_marker_on_b(self, tmp_path):
        """The satellite case: a crash between sub-commits leaves sink
        A with a tail past the checkpointed marker while B never
        committed — restore must truncate A, keep B, and the replay
        regenerates both."""
        ms = self._pair(tmp_path)
        ms.reset()
        ms.stage("a", "a1")
        ms.stage("b", "b1")
        marker = ms.commit()  # the checkpointed unit marker
        ms.stage("a", "a2")
        ms.stage("b", "b2")
        # Crash between A's commit and B's: dag.commit fires per
        # sub-append, and arming resets hit counts — hit 2 is B's side
        # of the commit below (A's append already durable).
        faults.arm([{"point": "dag.commit", "at": 2, "times": 10_000}])
        with pytest.raises(InjectedFault):
            ms.commit()
        faults.disarm()
        assert (tmp_path / "a.csv").read_bytes() == b"a1\na2\n"  # torn
        assert (tmp_path / "b.csv").read_bytes() == b"b1\n"
        ms2 = self._pair(tmp_path)
        ms2.restore(marker)
        assert (tmp_path / "a.csv").read_bytes() == b"a1\n"  # truncated
        assert (tmp_path / "b.csv").read_bytes() == b"b1\n"  # kept
        ms2.stage("a", "a2")
        ms2.stage("b", "b2")
        ms2.commit()
        assert (tmp_path / "a.csv").read_bytes() == b"a1\na2\n"
        assert (tmp_path / "b.csv").read_bytes() == b"b1\nb2\n"

    def test_marker_ahead_of_file_is_loud(self, tmp_path):
        """A sink file SHORTER than its checkpointed marker (committed
        egress lost out-of-band, or a marker from a future checkpoint
        generation) must raise, naming the file."""
        ms = self._pair(tmp_path)
        ms.reset()
        ms.stage("a", "a1" * 50)
        ms.stage("b", "b1")
        marker = ms.commit()
        (tmp_path / "a.csv").write_bytes(b"short")
        with pytest.raises(CheckpointCorruptError, match="out-of-band"):
            self._pair(tmp_path).restore(marker)

    def test_unknown_sink_in_restore_resets_fresh(self, tmp_path):
        ms = self._pair(tmp_path)
        ms.reset()
        ms.stage("a", "a1")
        marker = ms.commit()
        ms3 = MultiSink({
            "a": TransactionalFileSink(str(tmp_path / "a.csv")),
            "b": TransactionalFileSink(str(tmp_path / "b.csv")),
            "c": TransactionalFileSink(str(tmp_path / "c.csv")),
        })
        ms3.restore(marker)  # c has no marker → fresh reset
        assert (tmp_path / "c.csv").read_bytes() == b""


# ---------------------------------------------------------------------------
# Node parity (device vs numpy twin)


class TestNodeParity:
    def test_staytime_device_matches_host_walk(self, tmp_path):
        node = StayTimeNode("st")
        dag = build_sncb_dag(str(tmp_path / "egress"))
        node.bind(dag)
        from spatialflink_tpu.streams.windows import WindowBatch

        src = _toy_sncb_stream(90)
        evs = [e for e in src()
               if getattr(e, "device_id", None) is not None]
        win = WindowBatch(0, 40_000, evs)
        dev = node.process(win, {})
        host = node.fallback_process(win, {})
        assert sorted(dev) == sorted(host)
        assert dev  # non-vacuous

    def test_zone_nodes_device_matches_numpy(self, tmp_path):
        dag = build_sncb_dag(str(tmp_path / "egress"))
        from spatialflink_tpu.streams.windows import WindowBatch

        src = _toy_sncb_stream(90)
        evs = [e for e in src()
               if getattr(e, "device_id", None) is not None]
        win = WindowBatch(0, 40_000, evs)
        for name in ("q1", "q2", "q5"):
            node = dag.node(name)
            dev = node.process(win, {})
            twin = node.fallback_process(win, {})
            assert len(dev) > 0, name
            assert [repr(d) for d in dev] == [repr(t) for t in twin], name


# ---------------------------------------------------------------------------
# Windows buffered as columns per pane (streams/columns.py): the DAG's
# assembler holds arrays, the object path (the generic WindowAssembler +
# WindowColumns.from_events) is the reference.


def _pane_stream(n=420, out_of_order=False):
    """Boot commands, then ``n`` full-schema GpsEvents at a 100 ms
    cadence near the bundled zones (every node has egress); with
    ``out_of_order`` a sixth of them arrive up to 4.5 s late — inside
    the 5 s bound, so they land, in panes older than the one being
    filled."""
    from spatialflink_tpu.dag import default_sncb_queries
    from spatialflink_tpu.qserve import QServeCommand
    from spatialflink_tpu.sncb.common import GpsEvent

    rng = np.random.default_rng(77)
    out = [QServeCommand(timestamp=0, action="register",
                         uid=f"boot:{q.qid}", query=q)
           for q in default_sncb_queries()]
    for i in range(n):
        cx, cy = ((4.354, 50.854), (4.404, 50.854),
                  (4.375, 50.85))[i % 3]
        ts = i * 100
        if out_of_order and i % 6 == 0:
            ts -= int(rng.integers(1, 4_500))
        out.append(GpsEvent(
            device_id=f"dev{i % 7}",
            lon=cx + float(rng.normal(0.0, 0.004)),
            lat=cy + float(rng.normal(0.0, 0.004)), ts=max(ts, 0),
            gps_speed=float(rng.uniform(20.0, 110.0)),
            fa=float(rng.uniform(0.0, 1.0)),
            ff=None if i % 11 == 0 else float(rng.uniform(0.0, 0.4))))
    return out


def _pane_leg(workdir, events, objects=False, plan=None):
    """One 7-node run with a unit checkpoint at every window;
    ``objects`` swaps in the generic assembler (the object path)."""
    from spatialflink_tpu.streams.windows import (
        SlidingEventTimeWindows,
        WindowAssembler,
    )

    dag = build_sncb_dag(os.path.join(workdir, "egress"),
                         retry=RetryPolicy(max_retries=1, backoff_s=0.0))
    if objects:
        conf = dag.conf
        dag._assembler = lambda: WindowAssembler(
            SlidingEventTimeWindows(conf.window_size_ms,
                                    conf.slide_step_ms),
            timestamp_fn=lambda e: e.timestamp,
            max_out_of_orderness_ms=conf.allowed_lateness_ms)
    driver = WindowedDataflowDriver(
        checkpoint_path=os.path.join(workdir, "ckpt.bin"),
        checkpoint_every=1, sink=None,
        retry=RetryPolicy(max_retries=1, backoff_s=0.0), failover=False)
    if plan:
        faults.arm(plan)
    try:
        for _ in dag.run(iter(events), driver=driver):
            pass
    finally:
        faults.disarm()
        qserve.uninstall()
        dag_mod.uninstall()
    return driver, dag


@pytest.fixture(scope="module", params=["in_order", "out_of_order"])
def pane_clean(request, tmp_path_factory):
    """(events, the object path's committed bytes) per stream."""
    events = _pane_stream(out_of_order=request.param == "out_of_order")
    d = tmp_path_factory.mktemp("pane_objects")
    _, dag = _pane_leg(str(d), events, objects=True)
    want = _sink_bytes(str(d))
    assert all(len(v) > 0 for v in want.values()), {
        k: len(v) for k, v in want.items()}
    counts = dag.snapshot()["window_columns"]
    assert counts["from_events"] == counts["built"] > 3
    assert counts["from_panes"] == 0
    return request.param, events, want


class TestPaneBuffers:
    def test_sinks_byte_identical_to_the_object_path(self, tmp_path,
                                                     pane_clean):
        order, events, want = pane_clean
        _, dag = _pane_leg(str(tmp_path), events)
        assert _sink_bytes(str(tmp_path)) == want
        counts = dag.snapshot()["window_columns"]
        assert counts["from_panes"] == counts["built"] > 3
        assert counts["from_events"] == 0
        assert (counts["reordered"] > 0) == (order == "out_of_order")

    @pytest.mark.parametrize("plan", [
        [{"point": "window.feed", "at": 260, "times": 10_000}],
        [{"point": "dag.commit", "at": 17, "times": 10_000}],
        [{"point": "dag.node", "at": 25, "times": 10_000}],
    ], ids=lambda p: p[0]["point"])
    def test_kill_and_resume_from_the_pane_state(self, tmp_path,
                                                 pane_clean, plan):
        _, events, want = pane_clean
        with pytest.raises(InjectedFault):
            _pane_leg(str(tmp_path), events, plan=plan)
        ck = os.path.join(str(tmp_path), "ckpt.bin")
        asm = load_checkpoint(ck)["op"]["assembler"]
        assert asm["panes"] and "buffers" not in asm
        with open(ck, "rb") as f:
            assert b"GpsEvent" not in f.read()  # arrays, not objects
        drv, _ = _pane_leg(str(tmp_path), events)  # resume
        assert drv.stats["resumed"] is True
        assert _sink_bytes(str(tmp_path)) == want

    def test_a_checkpoint_in_the_object_form_restores(self, tmp_path,
                                                      pane_clean):
        """The parent's checkpoint (``"buffers"``: lists of events per
        window) resumes into panes, byte-identically."""
        _, events, want = pane_clean
        with pytest.raises(InjectedFault):
            _pane_leg(str(tmp_path), events, objects=True, plan=[
                {"point": "window.feed", "at": 260, "times": 10_000}])
        ck = os.path.join(str(tmp_path), "ckpt.bin")
        asm = load_checkpoint(ck)["op"]["assembler"]
        assert sum(len(evs) for _, evs in asm["buffers"]) > 50
        drv, dag = _pane_leg(str(tmp_path), events)  # columnar resume
        assert drv.stats["resumed"] is True
        assert _sink_bytes(str(tmp_path)) == want
        counts = dag.snapshot()["window_columns"]
        assert counts["from_panes"] == counts["built"] > 0
        assert "panes" in load_checkpoint(ck)["op"]["assembler"]

    def test_lazy_events_serve_q1_and_a_fallback_twin(self, tmp_path,
                                                      pane_clean):
        """``win.events`` holds no objects, yet q1 hands back the raw
        event of each hit and StayTime's host twin walks the window
        after a forced failover — as on the object path."""
        _, events, _ = pane_clean
        # first window's 6th device attempt = staytime; + its one retry
        plan = [{"point": "dag.node", "at": 6, "times": 2}]
        (tmp_path / "objects").mkdir()
        (tmp_path / "panes").mkdir()
        _, ref = _pane_leg(str(tmp_path / "objects"), events,
                           objects=True, plan=plan)
        _, dag = _pane_leg(str(tmp_path / "panes"), events, plan=plan)
        for d in (ref, dag):
            nodes = d.snapshot()["nodes"]
            assert nodes["staytime"]["backend"] == "fallback"
            assert nodes["staytime"]["failovers"] == 1
            assert all(st["backend"] == "device"
                       for n, st in nodes.items() if n != "staytime")
        got = _sink_bytes(str(tmp_path / "panes"))
        assert got == _sink_bytes(str(tmp_path / "objects"))
        assert got["q1"] and got["staytime"]


# ---------------------------------------------------------------------------
# Leaf spans inside the node walk (h2d / dispatch:<kernel> / d2h)


LEAVES = ("h2d", "d2h")


def _is_leaf(e):
    return e["name"] in LEAVES or e["name"].startswith("dispatch:")


@pytest.fixture(scope="module")
def sncb_traced(tmp_path_factory):
    """One 7-node run with telemetry on: (sink bytes, events, kernel
    table, snapshot) — taken before any other test resets the singleton."""
    d = tmp_path_factory.mktemp("dag_traced")
    telemetry.enable()
    try:
        _run_sncb_leg(str(d))
        got = (_sink_bytes(str(d)), list(telemetry.events),
               telemetry.kernel_table(), telemetry.snapshot())
    finally:
        telemetry.disable()
    return got


class TestLeafSpans:
    def test_every_leaf_inside_the_walk_names_its_node(self, sncb_traced):
        _, events, _, _ = sncb_traced
        spans = [e for e in events if e.get("ph") == "X"]
        walks = [e for e in spans if e["name"] == "window.dag"]
        assert walks
        seen = set()
        for w in walks:
            lo, hi = w["ts"], w["ts"] + w["dur"]
            for e in spans:
                if _is_leaf(e) and lo <= e["ts"] and \
                        e["ts"] + e["dur"] <= hi + 1:
                    assert e["args"].get("node") in SNCB_SINKS, e
                    seen.add((e["args"]["node"], e["name"].split(":")[0]))
        # the zone nodes, StayTime and qserve: every node with a device
        # path crosses the link through the choke points
        for node in ("q1", "q2", "q5", "staytime", "qserve"):
            for kind in ("h2d", "dispatch", "d2h"):
                assert (node, kind) in seen, (node, kind)

    def test_zone_kernel_is_in_the_kernel_table(self, sncb_traced):
        _, events, table, _ = sncb_traced
        rows = [r for r in table if r["kernel"] == "_zone_hit_kernel"]
        assert {r["node"] for r in rows} == {"q1", "q2", "q5"}
        spans = [e for e in events
                 if e["name"] == "dispatch:_zone_hit_kernel"]
        assert sum(r["calls"] for r in rows) == len(spans) > 0
        # every program the walk dispatched went through the one wrapper
        assert sum(r["calls"] for r in table) == sum(
            e["name"].startswith("dispatch:") for e in events)

    def test_zone_ships_are_counted(self, sncb_traced):
        _, events, _, snap = sncb_traced
        h2d = [e for e in events if e["name"] == "h2d"]
        assert snap["h2d_transfers"] == len(h2d)
        assert snap["bytes_h2d"] == sum(e["args"]["bytes"] for e in h2d)
        for node in ("q1", "q2", "q5", "staytime"):
            mine = [e for e in h2d if e["args"].get("node") == node]
            assert mine and all(e["args"]["arrays"] == 4 for e in mine)
            assert snap["nodes"][node]["h2d_transfers"] == len(mine)
            assert snap["nodes"][node]["d2h_transfers"] == len(mine)

    def test_committed_lines_identical_with_telemetry_on(
            self, sncb_traced, sncb_clean):
        assert sncb_traced[0] == sncb_clean


# ---------------------------------------------------------------------------
# CheckIn node (stateful: occupancy + per-user last-event carry)


def _checkin_events(n=40):
    from spatialflink_tpu.apps.checkin import CheckInEvent

    rooms = ("r1", "r2")
    evs = []
    for i in range(n):
        room = rooms[i % 2]
        # Every 7th event repeats the user's previous direction — the
        # missing-opposite-event synthesis path.
        direction = "in" if (i // 2) % 2 == 0 or i % 7 == 0 else "out"
        evs.append(CheckInEvent(
            event_id=f"e{i}", device_id=f"{room}-{direction}",
            user_id=f"u{i % 3}", timestamp=100 * i,
        ))
    return evs


class TestCheckInNode:
    def _dag(self, tmp_path, sub):
        from spatialflink_tpu.dag import CheckInNode

        grid = UniformGrid(8, 0.0, 8.0, 0.0, 8.0)
        node = CheckInNode("checkin", {"r1": 10, "r2": 5})
        return DataflowDAG(_toy_conf(), grid, [node],
                           out_dir=str(tmp_path / sub)), node

    def test_matches_unwindowed_host_walk(self, tmp_path):
        """Each event is processed ONCE (the new-pane filter under the
        sliding clock), so the DAG's occupancy stream equals the
        standalone check_in_query over the same ordered events."""
        from spatialflink_tpu.apps.checkin import check_in_query

        evs = _checkin_events()
        want = [(room, cap, occ)
                for room, cap, occ, _t in check_in_query(
                    iter(evs), {"r1": 10, "r2": 5})]
        dag, node = self._dag(tmp_path, "egress")
        rows = []
        for res in dag.run(iter(evs)):
            pass
        got = [ln.split(",")[2:]
               for ln in (tmp_path / "egress" / "checkin.csv")
               .read_text().splitlines()]
        assert [(r, int(c), int(o)) for r, c, o in got] == \
            [(r, c, o) for r, c, o in want]

    def test_kill_resumes_occupancy_exactly(self, tmp_path):
        evs = _checkin_events()

        def leg(sub, plan=None):
            dag, node = self._dag(tmp_path, sub)
            drv = WindowedDataflowDriver(
                checkpoint_path=str(tmp_path / f"{sub}.ckpt"),
                checkpoint_every=2, sink=None, failover=False,
                retry=RetryPolicy(max_retries=1, backoff_s=0.0),
            )
            if plan:
                faults.arm(plan)
            try:
                for _ in dag.run(iter(evs), driver=drv):
                    pass
            finally:
                faults.disarm()
            return drv

        leg("clean")
        want = (tmp_path / "clean" / "checkin.csv").read_bytes()
        assert want
        with pytest.raises(InjectedFault):
            # dag.node raises mid-walk; the STATEFUL node takes no
            # retry and no twin — crash-and-resume only.
            leg("chaos", plan=[{"point": "dag.node", "at": 4,
                                "times": 10_000}])
        drv = leg("chaos")
        assert drv.stats["resumed"] is True
        assert (tmp_path / "chaos" / "checkin.csv").read_bytes() == want


# ---------------------------------------------------------------------------
# Per-node SLO budgets (live + sfprof twin) and telemetry surfaces


class TestNodeSlo:
    def test_live_node_budgets(self, tmp_path):
        from spatialflink_tpu import slo

        telemetry.enable()
        sick = _count_node("sick", fail_windows=range(-10**9, 10**9))
        ok = _count_node("ok")
        dag = _toy_dag(tmp_path, [sick, ok])
        engine = slo.install(slo.SloEngine(slo.SloSpec(
            eval_interval_s=0.0,
            node_budgets={
                "sick": {"failover_budget": 0},
                "ok": {"failover_budget": 0,
                       "degraded_window_budget": 0},
                "ghost": {"retry_budget": 1},
            },
        )))
        try:
            list(dag.run(iter(_toy_points())))
            rows = {r["check"]: r["ok"] for r in engine.evaluate()}
            assert rows["node_failover_budget:sick"] is False
            assert rows["node_failover_budget:ok"] is True
            assert rows["node_degraded_window_budget:ok"] is True
            # Unknown node: the budget is unanswerable — silence fails.
            assert rows["node_retry_budget:ghost"] is False
        finally:
            slo.uninstall()

    def test_live_node_budgets_without_dag_fail_on_silence(self):
        from spatialflink_tpu import slo

        engine = slo.SloEngine(slo.SloSpec(
            eval_interval_s=0.0,
            node_budgets={"q1": {"watermark_lag_p99_ms": 10_000}},
        ))
        rows = {r["check"]: r["ok"] for r in engine.evaluate()}
        assert rows["node_watermark_lag_p99_ms:q1"] is False

    def test_node_budget_validation_is_strict(self):
        from spatialflink_tpu import slo

        with pytest.raises(ValueError, match="node_budgets"):
            slo.SloSpec(node_budgets={"q1": {"typo_budget": 1}})

    def test_ledger_and_sfprof_twin(self, tmp_path):
        telemetry.enable()
        sick = _count_node("sick", fail_windows=range(-10**9, 10**9))
        dag = _toy_dag(tmp_path, [sick])
        list(dag.run(iter(_toy_points())))
        ledger = tmp_path / "ledger.json"
        telemetry.write_ledger(str(ledger), capture_costs=False)
        doc = json.loads(ledger.read_text())
        nodes = doc["snapshot"]["dag"]["nodes"]
        assert nodes["sick"]["backend"] == "fallback"
        assert nodes["sick"]["failovers"] == 1

        from tools.sfprof import slo as sfslo

        rows = {name: ok for name, _v, _b, ok in sfslo.evaluate(
            {"node_budgets": {
                "sick": {"failover_budget": 0,
                         "watermark_lag_p99_ms": 10_000_000},
                "ghost": {"failover_budget": 0},
            }}, doc)}
        assert rows["slo:node_failover_budget:sick"] is False
        assert rows["slo:node_watermark_lag_p99_ms:sick"] is True
        assert rows["slo:node_failover_budget:ghost"] is False
        # No dag block at all → every node budget fails on silence.
        rows = sfslo.evaluate(
            {"node_budgets": {"sick": {"failover_budget": 0}}},
            {"snapshot": {}})
        assert rows == [("slo:node_failover_budget:sick", None,
                         "<= 0", False)]


# ---------------------------------------------------------------------------
# streaming_job option 10


def _write_conf(tmp_path, option=10):
    conf = tmp_path / "conf.yml"
    conf.write_text(f"""
inputStream1:
  topicName: t
  format: CSV
  csvTsvSchemaAttr: [0, 1, 2, 3]
  gridBBox: [4.25, 50.75, 4.50, 50.95]
  numGridCells: 20
  delimiter: ","
query:
  option: {option}
  radius: 0.05
  k: 3
  queryPoints:
    - [4.37, 50.85]
window:
  type: "TIME"
  interval: 10
  step: 5
""")
    return conf


def _write_csv(tmp_path, n=120):
    rows = []
    for i in range(n):
        x = 4.354 if i % 3 == 0 else (4.404 if i % 3 == 1 else 4.30)
        y = 50.854 if i % 3 != 2 else 50.80
        rows.append(f"dev{i % 4},{i * 400},{x},{y}")
    csv = tmp_path / "in.csv"
    csv.write_text("\n".join(rows))
    return csv


class TestStreamingJobOption10:
    def test_option10_checkpointed_run(self, tmp_path):
        from spatialflink_tpu.streaming_job import main

        conf = _write_conf(tmp_path)
        csv = _write_csv(tmp_path)
        out = tmp_path / "out"
        rc = main(["--config", str(conf), "--source", f"csv:{csv}",
                   "--output", str(out),
                   "--checkpoint", str(tmp_path / "ck.bin")])
        assert rc == 0
        for name in SNCB_SINKS:
            assert (out / f"{name}.csv").exists()
        assert (out / "q1.csv").read_bytes()
        assert (out / "qserve.csv").read_bytes()
        ck = load_checkpoint(str(tmp_path / "ck.bin"))
        assert set(ck["egress"]["sinks"]) == set(SNCB_SINKS)

    def test_option10_needs_output_dir(self, tmp_path):
        from spatialflink_tpu.streaming_job import main

        conf = _write_conf(tmp_path)
        csv = _write_csv(tmp_path)
        with pytest.raises(SystemExit, match="directory"):
            main(["--config", str(conf), "--source", f"csv:{csv}"])
