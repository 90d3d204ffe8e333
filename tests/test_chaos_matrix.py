"""The chaos matrix (ISSUE 8 acceptance): for EVERY registered fault
injection point, inject → crash → resume → the concatenated exactly-once
egress is byte-identical to an uninterrupted run — no gap, no duplicate,
at the sink and not just the source.

Crash semantics: an armed fault rule with ``times`` larger than the
driver's retry budget defeats retries and propagates out of the pipeline
with no cleanup — from the checkpoint/egress protocol's point of view,
the same abandonment as a ``kill -9`` (nothing commits, nothing
flushes). The real-process SIGKILL analog (``abort`` kind,
``os._exit(137)``) is pinned by the slow subprocess test below and runs
on every commit as tools/ci's chaos-smoke stage.

Seven pipeline harnesses cover the fifteen points:

- range-query driver pipeline (collection source): device.ship,
  device.dispatch, device.fetch, window.feed, driver.window, sink.write,
  and — with an admission controller attached — overload.admit;
- SoA driver pipeline (chunked source → run_soa): soa.feed;
- qserve standing-query pipeline (Points + registration commands →
  QServeOperator, registry state checkpointed): qserve.register —
  killed mid-registration-churn, resumed egress byte-identical;
- Kafka driver pipeline (FakeBroker ingest, offsets checkpointed):
  kafka.fetch, kafka.leader;
- tJoin pane-engine pipeline (bounded SoA chunks → run_soa_panes →
  driver.run_precomputed): source.stall — the scan recomputes
  deterministically on resume and the driver skips the committed
  window prefix;
- composed SNCB DAG subprocess (7 nodes, 7 transactional sinks, one
  atomic unit checkpoint, SFT_OVERLOAD_POLICY armed):
  dag.commit — killed BETWEEN two sink commits of a unit commit —
  and dag.node (mid-node-walk), plus a qserve.register leg inside the
  DAG; every sink must converge byte-identically on resume;
- grid-partitioned range driver subprocess (8-device CPU mesh):
  shard.exchange — killed mid-halo-exchange, the resumed child restores
  the checkpointed partition plan.
"""

import json
import os
import subprocess
import sys

import numpy as np
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if REPO not in sys.path:
    sys.path.insert(0, REPO)

from spatialflink_tpu.checkpoint import load_checkpoint  # noqa: E402
from spatialflink_tpu.driver import (  # noqa: E402
    RetryPolicy,
    WindowedDataflowDriver,
    _toy_pipeline,
    render_range_result,
)
from spatialflink_tpu.faults import (  # noqa: E402
    ABORT_EXIT_CODE,
    INJECTION_POINTS,
    InjectedFault,
    faults,
)
from spatialflink_tpu.operators.range_query import (  # noqa: E402
    PointPointRangeQuery,
)
from spatialflink_tpu import overload  # noqa: E402
from spatialflink_tpu.operators.trajectory import TStatsQuery  # noqa: E402
from spatialflink_tpu.streams.sinks import (  # noqa: E402
    TransactionalFileSink,
)
from spatialflink_tpu.telemetry import telemetry  # noqa: E402


@pytest.fixture(autouse=True)
def _disarm():
    yield
    faults.disarm()
    telemetry.disable()
    # The overload.admit leg's driver deliberately leaves its controller
    # in the module slot when no prior one was installed (the
    # ledger-seal contract) — clean it so later tests in the process
    # don't inherit a crashed leg's stale controller.
    overload.uninstall()
    from spatialflink_tpu import qserve

    qserve.uninstall()


RETRY = RetryPolicy(max_retries=1, backoff_s=0.0)


# ---------------------------------------------------------------------------
# Harness 1: range-query pipeline (collection source)


def run_range_leg(workdir, fault_plan=None, with_overload=False):
    grid, conf, source, query = _toy_pipeline()
    sink = TransactionalFileSink(os.path.join(workdir, "egress.csv"))
    ctrl = None
    if with_overload:
        # Admission controller with no budgets: nothing sheds, but
        # every event passes through admit_item — the overload.admit
        # injection point's hit stream.
        from spatialflink_tpu import overload

        ctrl = overload.OverloadController(overload.OverloadPolicy())
    driver = WindowedDataflowDriver(
        checkpoint_path=os.path.join(workdir, "ckpt.bin"),
        checkpoint_every=2, sink=sink, retry=RETRY, failover=False,
        overload=ctrl,
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


def chaos_range(tmp_path, point, kind="raise", at=5, with_overload=False):
    clean = tmp_path / "clean"
    chaos = tmp_path / "chaos"
    clean.mkdir()
    chaos.mkdir()
    run_range_leg(str(clean), with_overload=with_overload)
    want = (clean / "egress.csv").read_bytes()
    assert want, "vacuous matrix entry: clean egress is empty"
    with pytest.raises(InjectedFault):
        run_range_leg(str(chaos), fault_plan=[
            {"point": point, "kind": kind, "at": at, "times": 10_000},
        ], with_overload=with_overload)
    drv = run_range_leg(str(chaos), with_overload=with_overload)  # resume
    assert drv.stats["resumed"] is True
    assert (chaos / "egress.csv").read_bytes() == want


# ---------------------------------------------------------------------------
# Harness 2: SoA pipeline (chunked source → driver.run_soa)


def _soa_chunks(n_chunks=12, per=10):
    rng = np.random.default_rng(11)
    for c in range(n_chunks):
        base = c * per
        yield {
            "ts": np.arange(base, base + per, dtype=np.int64) * 100,
            "x": rng.uniform(0.0, 8.0, per),
            "y": rng.uniform(0.0, 8.0, per),
            "oid": (np.arange(base, base + per) % 7).astype(np.int32),
        }


def run_soa_leg(workdir, fault_plan=None):
    from spatialflink_tpu.streams.soa import SoaWindowAssembler

    grid, conf, _, _ = _toy_pipeline()
    op = TStatsQuery(conf, grid)
    sink = TransactionalFileSink(os.path.join(workdir, "egress.csv"))
    driver = WindowedDataflowDriver(
        checkpoint_path=os.path.join(workdir, "ckpt.bin"),
        checkpoint_every=1, sink=sink, retry=RETRY, failover=False,
    )

    def process(win):
        # Host-only per-window reduction: the matrix entry exercises the
        # soa.feed crash/resume machinery, not a device kernel.
        return (win.start, win.end, win.count,
                float(np.sum(win.arrays["x"])))

    driver.bind(op, process)
    if fault_plan:
        faults.arm(fault_plan)
    try:
        asm = SoaWindowAssembler(conf.window_size_ms, conf.slide_step_ms)
        for start, end, count, sx in driver.run_soa(_soa_chunks(), asm):
            sink.stage(f"{start},{end},{count},{float(sx)!r}")
    finally:
        faults.disarm()
    return driver


def chaos_soa(tmp_path, point, kind="raise", at=6):
    clean = tmp_path / "clean"
    chaos = tmp_path / "chaos"
    clean.mkdir()
    chaos.mkdir()
    run_soa_leg(str(clean))
    want = (clean / "egress.csv").read_bytes()
    assert want
    with pytest.raises(InjectedFault):
        run_soa_leg(str(chaos), fault_plan=[
            {"point": point, "kind": kind, "at": at, "times": 10_000},
        ])
    drv = run_soa_leg(str(chaos))
    assert drv.stats["resumed"] is True
    assert (chaos / "egress.csv").read_bytes() == want


# ---------------------------------------------------------------------------
# Harness 2b: tJoin pane-engine pipeline (run_soa_panes →
# driver.run_precomputed). The device scan happens up front; the driver
# owns WINDOW emission, so the checkpointed position counts windows and
# a resume re-runs the (deterministic) scan and skips the committed
# prefix. source.stall fires on the driver's per-window pull.


def _tjoin_chunks(side, n_chunks=10, per=8):
    rng = np.random.default_rng(21 + side)
    out = []
    for c in range(n_chunks):
        base = c * per
        out.append({
            "ts": np.arange(base, base + per, dtype=np.int64) * 250,
            "x": rng.uniform(0.0, 8.0, per),
            "y": rng.uniform(0.0, 8.0, per),
            "oid": (np.arange(base, base + per) % 5).astype(np.int32),
        })
    return out


def run_tjoin_panes_leg(workdir, fault_plan=None):
    from spatialflink_tpu.operators.trajectory import TJoinQuery

    grid, conf, _, _ = _toy_pipeline()
    op = TJoinQuery(conf, grid)
    sink = TransactionalFileSink(os.path.join(workdir, "egress.csv"))
    driver = WindowedDataflowDriver(
        checkpoint_path=os.path.join(workdir, "ckpt.bin"),
        checkpoint_every=1, sink=sink, retry=RETRY, failover=False,
    )
    if fault_plan:
        faults.arm(fault_plan)
    try:
        for s, e, lo, ro, dd, cnt, over in op.run_soa_panes(
            _tjoin_chunks(0), _tjoin_chunks(1), 1.5, 5, driver=driver,
        ):
            for a, b, d in zip(lo, ro, dd):
                sink.stage(f"{s},{e},{int(a)},{int(b)},{float(d)!r}")
    finally:
        faults.disarm()
    return driver


def chaos_tjoin_panes(tmp_path, point, kind="raise", at=4):
    clean = tmp_path / "clean"
    chaos = tmp_path / "chaos"
    clean.mkdir()
    chaos.mkdir()
    run_tjoin_panes_leg(str(clean))
    want = (clean / "egress.csv").read_bytes()
    assert want, "vacuous matrix entry: clean egress is empty"
    with pytest.raises(InjectedFault):
        run_tjoin_panes_leg(str(chaos), fault_plan=[
            {"point": point, "kind": kind, "at": at, "times": 10_000},
        ])
    drv = run_tjoin_panes_leg(str(chaos))  # resume: re-scan, skip prefix
    assert drv.stats["resumed"] is True
    assert (chaos / "egress.csv").read_bytes() == want


# ---------------------------------------------------------------------------
# Harness 2c: qserve standing-query pipeline (Points + registration
# commands on one stream → QServeOperator). The qserve.register point
# fires inside QueryRegistry.apply — mid-registration-churn — and the
# resumed run must re-apply the replayed commands exactly once (the
# applied-uid set) and converge to byte-identical per-tenant egress.


def run_qserve_leg(workdir, fault_plan=None):
    from spatialflink_tpu import qserve

    grid, conf, source, _ = _toy_pipeline()
    op = qserve.QServeOperator(conf, grid)
    sink = TransactionalFileSink(os.path.join(workdir, "egress.csv"))
    driver = WindowedDataflowDriver(
        checkpoint_path=os.path.join(workdir, "ckpt.bin"),
        checkpoint_every=2, sink=sink, retry=RETRY, failover=False,
    )

    def mk(i, kind, x, y, r, k=5, tenant="t0"):
        return qserve.QServeCommand(
            timestamp=0, action="register", uid=f"c{i}",
            query=qserve.StandingQuery(
                qid=f"q{i}", tenant=tenant, kind=kind, x=x, y=y,
                radius=r, k=k,
            ),
        )

    def stream():
        # Boot registrations, then data, then MID-STREAM churn: an
        # unregister + two registers landing around the 6-8 s windows —
        # after several checkpoints, so the crash legs resume mid-churn.
        churn = [
            qserve.QServeCommand(timestamp=6005, action="unregister",
                                 uid="c10", qid="q1"),
            qserve.QServeCommand(timestamp=7005, action="register",
                                 uid="c11", query=qserve.StandingQuery(
                                     qid="q11", tenant="t1", kind="knn",
                                     x=3.0, y=3.0, radius=2.0, k=5)),
            qserve.QServeCommand(timestamp=8005, action="register",
                                 uid="c12", query=qserve.StandingQuery(
                                     qid="q12", tenant="t1", kind="range",
                                     x=5.0, y=5.0, radius=1.8, k=8)),
        ]
        boot = [mk(0, "range", 4.0, 4.0, 1.5),
                mk(1, "knn", 2.0, 6.0, 2.5),
                mk(2, "knn", 6.0, 2.0, 2.5, tenant="t1")]
        pending = sorted(churn, key=lambda c: c.timestamp)
        yield from boot
        for ev in source():
            while pending and pending[0].timestamp <= ev.timestamp:
                yield pending.pop(0)
            yield ev
        yield from pending

    if fault_plan:
        faults.arm(fault_plan)
    try:
        for res in op.run(stream(), driver=driver):
            for line in res.lines():
                sink.stage(line)
    finally:
        faults.disarm()
        qserve.uninstall()
    return driver


def chaos_qserve(tmp_path, point, kind="raise", at=7):
    clean = tmp_path / "clean"
    chaos = tmp_path / "chaos"
    clean.mkdir()
    chaos.mkdir()
    run_qserve_leg(str(clean))
    want = (clean / "egress.csv").read_bytes()
    assert want, "vacuous matrix entry: clean egress is empty"
    with pytest.raises(InjectedFault):
        # at=7: the 3 boot registrations hit twice (two sliding windows
        # contain ts=0 — duplicate applies still count a hit), so hit 7
        # is the FIRST mid-stream churn command (~6 s), after several
        # checkpoints exist to resume from.
        run_qserve_leg(str(chaos), fault_plan=[
            {"point": point, "kind": kind, "at": at, "times": 10_000},
        ])
    drv = run_qserve_leg(str(chaos))  # resume mid-churn
    assert drv.stats["resumed"] is True
    assert (chaos / "egress.csv").read_bytes() == want


# ---------------------------------------------------------------------------
# Harness 3: Kafka pipeline (FakeBroker ingest, offsets checkpointed)


N_KAFKA = 30


def _fill_topic(broker, topic):
    from spatialflink_tpu.streams.kafka_wire import KafkaWireClient

    client = KafkaWireClient(f"127.0.0.1:{broker.port}")
    msgs = []
    rng = np.random.default_rng(3)
    for i in range(N_KAFKA):
        line = (f"o{i % 5},{i * 100},{rng.uniform(0, 8):.4f},"
                f"{rng.uniform(0, 8):.4f}")
        msgs.append((line.encode(), None, i * 100))
    client.produce(topic, 0, msgs)
    client.close()


def run_kafka_leg(workdir, broker, topic, n_events, *, flush_at_end,
                  fault_plan=None):
    import itertools

    from spatialflink_tpu.checkpoint import kafka_source_state
    from spatialflink_tpu.models.objects import Point
    from spatialflink_tpu.streams.kafka import WireKafkaSource

    def parse(line):
        oid, ts, x, y = line.split(",")
        return Point(obj_id=oid, timestamp=int(ts), x=float(x),
                     y=float(y))

    ckpt = os.path.join(workdir, "ckpt.bin")
    start_offsets = None
    consumed = 0
    if os.path.exists(ckpt):
        ck = load_checkpoint(ckpt)
        start_offsets = ck["kafka"]["offsets"]
        consumed = ck["driver"]["events_consumed"]
    src = WireKafkaSource(topic, f"127.0.0.1:{broker.port}", parse,
                          start_offsets=start_offsets)
    grid, conf, _, query = _toy_pipeline()
    sink = TransactionalFileSink(os.path.join(workdir, "egress.csv"))
    driver = WindowedDataflowDriver(
        checkpoint_path=ckpt, checkpoint_every=1, sink=sink, retry=RETRY,
        failover=False, skip_on_resume=False, flush_at_end=flush_at_end,
        extra_state=lambda: {"kafka": kafka_source_state(src)},
    )
    op = PointPointRangeQuery(conf, grid)
    if fault_plan:
        faults.arm(fault_plan)
    try:
        stream = itertools.islice(iter(src), max(n_events - consumed, 0))
        for res in op.run(stream, [query], 1.5, driver=driver):
            for line in render_range_result(res):
                sink.stage(line)
    finally:
        faults.disarm()
        src.close()
    return driver


def chaos_kafka(tmp_path, point, kind="raise"):
    """Mid-stream ingest crash: leg 1 consumes half the topic and
    checkpoints (end-of-source treated as a kill point, open windows
    stay buffered); leg 2 resumes from the checkpointed offsets and dies
    on its first fetch/leader attempt; leg 3 resumes and finishes. The
    stitched egress must equal one uninterrupted run."""
    test_kafka_wire = pytest.importorskip("test_kafka_wire")
    broker = test_kafka_wire.FakeBroker()
    try:
        _fill_topic(broker, "chaos-clean")
        _fill_topic(broker, "chaos-crash")
        clean = tmp_path / "clean"
        chaos = tmp_path / "chaos"
        clean.mkdir()
        chaos.mkdir()
        run_kafka_leg(str(clean), broker, "chaos-clean", N_KAFKA,
                      flush_at_end=True)
        want = (clean / "egress.csv").read_bytes()
        assert want
        run_kafka_leg(str(chaos), broker, "chaos-crash", N_KAFKA // 2,
                      flush_at_end=False)
        with pytest.raises(InjectedFault):
            run_kafka_leg(str(chaos), broker, "chaos-crash", N_KAFKA,
                          flush_at_end=True, fault_plan=[
                              {"point": point, "kind": kind, "at": 1,
                               "times": 10_000},
                          ])
        drv = run_kafka_leg(str(chaos), broker, "chaos-crash", N_KAFKA,
                            flush_at_end=True)
        assert drv.stats["resumed"] is True
        assert (chaos / "egress.csv").read_bytes() == want
    finally:
        broker.close()


# ---------------------------------------------------------------------------
# Harness 6: the composed SNCB DAG (subprocess, armed overload
# policy). Seven nodes, seven transactional sinks, ONE unit
# checkpoint: the abort kind kills the child at the named point —
# including BETWEEN two sink commits of a unit commit (dag.commit at 9
# = the second unit commit's 2nd sub-append) — and the resumed child
# must converge every sink to the clean child's bytes.


def chaos_dag(tmp_path, point, at):
    from spatialflink_tpu.dag import SMOKE_OVERLOAD_POLICY

    env_base = {**os.environ, "JAX_PLATFORMS": "cpu"}
    env_base.pop("SFT_FAULT_PLAN", None)
    # Armed overload: the shed schedule CHANGES egress and must replay
    # exactly across the kill.
    env_base["SFT_OVERLOAD_POLICY"] = json.dumps(SMOKE_OVERLOAD_POLICY)

    def child(workdir, plan=None):
        env = dict(env_base)
        if plan:
            env["SFT_FAULT_PLAN"] = json.dumps(plan)
        return subprocess.run(
            [sys.executable, "-m", "spatialflink_tpu.dag",
             "--chaos-child", str(workdir)],
            env=env, capture_output=True, text=True, timeout=600,
            cwd=REPO,
        )

    clean = tmp_path / "clean"
    chaos = tmp_path / "chaos"
    clean.mkdir()
    chaos.mkdir()
    p = child(clean)
    assert p.returncode == 0, p.stderr[-2000:]

    def sinks(d):
        out = {}
        for f in sorted((d / "egress").iterdir()):
            out[f.name] = f.read_bytes()
        return out

    want = sinks(clean)
    assert len(want) == 7 and all(want.values()), {
        k: len(v) for k, v in want.items()}
    p = child(chaos, plan=[{"point": point, "kind": "abort", "at": at}])
    assert p.returncode == ABORT_EXIT_CODE, (p.returncode,
                                             p.stderr[-2000:])
    p = child(chaos)  # resume from the unit checkpoint
    assert p.returncode == 0, p.stderr[-2000:]
    assert sinks(chaos) == want


def test_dag_qserve_register_kill_under_armed_policies(tmp_path):
    """The acceptance's fourth cut: kill -9 at qserve.register INSIDE
    the composed DAG (mid-registration-churn of the qserve node), same
    armed overload env, every sink byte-identical after resume."""
    chaos_dag(tmp_path, "qserve.register", at=11)


# ---------------------------------------------------------------------------
# The wedged (not killed) device: a hang past any patience on the first
# device window is bounded by the driver's dial watchdog
# (SFT_DIAL_DEADLINE_S) — the watchdog seals and kills the child with
# the dial exit code, and a resumed child still converges byte-exactly.


def test_hang_wedge_is_bounded_by_dial_deadline(tmp_path):
    """A hang far past any retry patience on the FIRST device ship:
    the driver's dial watchdog (SFT_DIAL_DEADLINE_S) must kill the
    child with the dial exit code in bounded time — not ride out
    the wedge — and a fresh child must still converge to the clean
    bytes."""
    env_base = {**os.environ, "JAX_PLATFORMS": "cpu"}
    env_base.pop("SFT_FAULT_PLAN", None)

    def child(workdir, plan=None, deadline=None):
        env = dict(env_base)
        env.pop("SFT_DIAL_DEADLINE_S", None)
        if deadline is not None:
            env["SFT_DIAL_DEADLINE_S"] = str(deadline)
        if plan:
            env["SFT_FAULT_PLAN"] = json.dumps(plan)
        return subprocess.run(
            [sys.executable, "-m", "spatialflink_tpu.driver",
             "--chaos-child", str(workdir)],
            env=env, capture_output=True, text=True, timeout=600,
            cwd=REPO,
        )

    clean = tmp_path / "clean"
    chaos = tmp_path / "chaos"
    clean.mkdir()
    chaos.mkdir()
    assert child(clean).returncode == 0
    want = (clean / "egress.csv").read_bytes()
    assert want
    p = child(chaos, deadline="0.3", plan=[
        {"point": "device.ship", "kind": "hang", "hang_s": 60,
         "at": 1},
    ])
    from spatialflink_tpu.driver import DIAL_TIMEOUT_EXIT_CODE

    assert p.returncode == DIAL_TIMEOUT_EXIT_CODE, (p.returncode,
                                                    p.stderr[-2000:])
    assert "dial_timeout" in p.stderr or "SFT_DIAL_DEADLINE_S" \
        in p.stderr
    p = child(chaos)  # recover: fresh run, no wedge
    assert p.returncode == 0, p.stderr[-2000:]
    assert (chaos / "egress.csv").read_bytes() == want


# ---------------------------------------------------------------------------
# Harness 7: the grid-partitioned pipeline (subprocess, 8-device CPU
# mesh). run_partitioned dispatches through parallel/halo.py, whose
# shard.exchange point fires once per window right before the boundary-
# pane ppermute — the abort kind is kill -9 mid-exchange. The resumed
# child restores the CHECKPOINTED partition plan (checkpoint.py
# validates the shard count) and must converge byte-identically. The
# virtual-device count must be in the env BEFORE jax initializes, hence
# the subprocess harness.


def chaos_sharded(tmp_path, point):
    env_base = {**os.environ, "JAX_PLATFORMS": "cpu",
                "XLA_FLAGS": "--xla_force_host_platform_device_count=8"}
    env_base.pop("SFT_FAULT_PLAN", None)

    def child(workdir, plan=None):
        env = dict(env_base)
        if plan:
            env["SFT_FAULT_PLAN"] = json.dumps(plan)
        return subprocess.run(
            [sys.executable, "-m", "spatialflink_tpu.driver",
             "--chaos-sharded-child", str(workdir)],
            env=env, capture_output=True, text=True, timeout=600,
            cwd=REPO,
        )

    clean = tmp_path / "clean"
    chaos = tmp_path / "chaos"
    clean.mkdir()
    chaos.mkdir()
    p = child(clean)
    assert p.returncode == 0, p.stderr[-2000:]
    want = (clean / "egress.csv").read_bytes()
    assert want, "vacuous matrix entry: clean egress is empty"
    p = child(chaos, plan=[{"point": point, "kind": "abort", "at": 5}])
    assert p.returncode == ABORT_EXIT_CODE, (p.returncode,
                                             p.stderr[-2000:])
    p = child(chaos)  # resume onto the checkpointed placement
    assert p.returncode == 0, p.stderr[-2000:]
    assert (chaos / "egress.csv").read_bytes() == want


# ---------------------------------------------------------------------------
# The matrix


MATRIX = {
    "device.ship": lambda tp: chaos_range(tp, "device.ship"),
    "device.dispatch": lambda tp: chaos_range(tp, "device.dispatch"),
    "device.fetch": lambda tp: chaos_range(tp, "device.fetch"),
    "window.feed": lambda tp: chaos_range(tp, "window.feed", at=60),
    "driver.window": lambda tp: chaos_range(tp, "driver.window"),
    "sink.write": lambda tp: chaos_range(tp, "sink.write",
                                         kind="partial_write", at=3),
    "soa.feed": lambda tp: chaos_soa(tp, "soa.feed"),
    "kafka.fetch": lambda tp: chaos_kafka(tp, "kafka.fetch"),
    "kafka.leader": lambda tp: chaos_kafka(tp, "kafka.leader"),
    # admit fires once per EVENT (like window.feed) — trigger late
    # enough that a checkpoint exists to resume from.
    "overload.admit": lambda tp: chaos_range(tp, "overload.admit", at=60,
                                             with_overload=True),
    "source.stall": lambda tp: chaos_tjoin_panes(tp, "source.stall"),
    "qserve.register": lambda tp: chaos_qserve(tp, "qserve.register"),
    # kill -9 mid-halo-exchange on the grid-partitioned path; resume
    # restores the checkpointed partition plan (8-device subprocess).
    "shard.exchange": lambda tp: chaos_sharded(tp, "shard.exchange"),
    # The 7-node SNCB DAG under an armed overload policy:
    # at=9 is the SECOND unit commit's 2nd sub-append — the between-
    # sink-commits cut the atomic unit checkpoint exists to close.
    "dag.commit": lambda tp: chaos_dag(tp, "dag.commit", at=9),
    "dag.node": lambda tp: chaos_dag(tp, "dag.node", at=25),
}


def test_matrix_covers_every_registered_point():
    """Registering an injection point without a matrix entry is a
    finding: the registry IS the coverage contract."""
    assert set(MATRIX) == set(INJECTION_POINTS)


@pytest.mark.parametrize("point", sorted(INJECTION_POINTS))
def test_inject_crash_resume_egress_exact(tmp_path, point):
    MATRIX[point](tmp_path)


def test_hang_kind_also_resumes_exactly(tmp_path):
    """The hang-with-timeout kind (a wedged device call): the stall
    bounds out, the run dies, and resume is still exact."""
    chaos_range(tmp_path, "device.dispatch", kind="hang")


def test_double_crash_then_resume(tmp_path):
    """Two consecutive crashes (the r3–r5 outages came in bursts) still
    converge to the exact clean egress."""
    clean = tmp_path / "clean"
    chaos = tmp_path / "chaos"
    clean.mkdir()
    chaos.mkdir()
    run_range_leg(str(clean))
    want = (clean / "egress.csv").read_bytes()
    for at in (4, 8):
        with pytest.raises(InjectedFault):
            run_range_leg(str(chaos), fault_plan=[
                {"point": "driver.window", "at": at, "times": 10_000},
            ])
    run_range_leg(str(chaos))
    assert (chaos / "egress.csv").read_bytes() == want


@pytest.mark.slow
def test_sigkill_analog_subprocess_round_trip(tmp_path):
    """The real-process leg: an armed ``abort`` fault ``os._exit(137)``s
    the child mid-commit (no handlers, no flush — kill -9 semantics),
    and a resumed child converges to the clean child's bytes. The same
    round trip runs on every commit as tools/ci's chaos-smoke stage."""
    env_base = {**os.environ, "JAX_PLATFORMS": "cpu"}
    env_base.pop("SFT_FAULT_PLAN", None)

    def child(workdir, plan=None):
        env = dict(env_base)
        if plan:
            env["SFT_FAULT_PLAN"] = json.dumps(plan)
        return subprocess.run(
            [sys.executable, "-m", "spatialflink_tpu.driver",
             "--chaos-child", workdir],
            env=env, capture_output=True, text=True, timeout=600,
            cwd=REPO,
        )

    clean = tmp_path / "clean"
    chaos = tmp_path / "chaos"
    clean.mkdir()
    chaos.mkdir()
    assert child(str(clean)).returncode == 0
    p = child(str(chaos),
              plan=[{"point": "sink.write", "kind": "abort", "at": 2}])
    assert p.returncode == ABORT_EXIT_CODE, p.stderr[-2000:]
    assert child(str(chaos)).returncode == 0
    want = (clean / "egress.csv").read_bytes()
    assert want
    assert (chaos / "egress.csv").read_bytes() == want
