"""Checkpoint / resume for stateful streaming operators.

The reference is state-backend-ready but never enables checkpointing
(SURVEY.md §5: ListState/MapState/ValueState exist, no
``enableCheckpointing`` call anywhere). Here operator state is explicit
host data, so snapshots are trivial: component states are plain dicts and
``save_checkpoint``/``load_checkpoint`` persist them as one pickle file
with an atomic publish. Checkpoints are trusted local state (pickle — do
not load files from untrusted sources).

Snapshottable components:
  - WindowAssembler: open window buffers, fired flags, max event-time,
    late-drop count; the DAG's ColumnarWindowAssembler
    (streams/columns.py): its pane buffers — arrays, id tables, the few
    non-point objects, arrival marks — with the same clock fields (a
    checkpoint in the generic form still restores into it);
  - SoA sliding assemblers (streams/soa.py): buffered chunks + watermark
    state machine;
  - TAggregateQuery: the per-(cell, objID) min/max timestamp MapState;
  - TStatsQuery: per-objID running spatial/temporal state;
  - kNN pane-digest carry (query_panes / run_soa_panes / run_wire_panes'
    digest ring + next-pane index) and join pane-block carry
    (query_panes) — the incremental sliding-window
    state, the ListState-carry analog of
    range/PointPointRangeQuery.java:234-246. Device digests are pulled
    to numpy at snapshot time; a resumed operator continues the stream
    mid-window with identical output (tests/test_checkpoint_panes.py —
    pass ``flush_at_end=False`` so a killed source doesn't flush open
    windows);
  - qserve QueryRegistry (qserve.py): the standing-query set, applied-
    command uids, and QoS counters — kill mid-registration-churn
    resumes to byte-identical per-tenant egress (chaos matrix,
    ``qserve.register``);
  - DataflowDAG (dag.py): every node's backend/counters/substate as one
    ``dag`` component — published atomically with the shared assembler,
    interner, source position, and the MultiSink marker map (the atomic
    unit checkpoint of the composed SNCB pipeline);
  - PartitionPlan (parallel/partition.py): the grid-partitioned
    placement map — per-shard contiguous flat-cell bounds + halo width —
    published with the operator state it placed so a resume re-dispatches
    onto the SAME placement (restore validates the shard count);
  - Interner: the objID vocabulary (so dense ids stay stable on resume);
  - WireKafkaSource: per-partition consumed offsets (kafka_source_state)
    — Flink's checkpointed Kafka-consumer role, so kill-and-resume
    covers INGEST as well as operator state.
"""

from __future__ import annotations

import os
import pickle
from typing import Any, Dict

import numpy as np

from spatialflink_tpu.streams.windows import WindowAssembler, WindowSpec
from spatialflink_tpu.telemetry import telemetry
from spatialflink_tpu.utils.interning import Interner


def assembler_state(asm) -> Dict[str, Any]:
    if not isinstance(asm, WindowAssembler):
        return asm.state()  # ColumnarWindowAssembler: its pane buffers
    return {
        "buffers": [
            ((spec.start, spec.end), events)
            for spec, events in asm._buffers.items()
        ],
        "fired": [
            ((spec.start, spec.end), fired) for spec, fired in asm._fired.items()
        ],
        "max_ts": asm._max_ts,
        "dropped_late": asm.dropped_late,
    }


def restore_assembler(asm, state: Dict[str, Any]) -> None:
    if not isinstance(asm, WindowAssembler):
        asm.restore(state)  # either form (streams/columns.py)
        return
    asm._buffers = {
        WindowSpec(s, e): list(events) for (s, e), events in state["buffers"]
    }
    asm._fired = {WindowSpec(s, e): f for (s, e), f in state["fired"]}
    asm._max_ts = state["max_ts"]
    asm.dropped_late = state["dropped_late"]


def soa_assembler_state(asm) -> Dict[str, Any]:
    """Snapshot a streams/soa.py sliding assembler — the point assembler
    (payload in ``_chunks``) or the ragged-geometry one (payload in
    ``_rows``/``_verts``/``_edges``)."""
    out: Dict[str, Any] = {
        "max_ts": asm._max_ts,
        "next_start": asm._next_start,
        "dropped_late": asm.dropped_late,
    }
    if hasattr(asm, "_chunks"):  # SoaWindowAssembler
        out["chunks"] = [
            {k: np.asarray(v) for k, v in c.items()} for c in asm._chunks
        ]
    else:  # RaggedSoaWindowAssembler
        out["rows"] = [dict(r) for r in asm._rows]
        out["verts"] = list(asm._verts)
        out["edges"] = None if asm._edges is None else list(asm._edges)
        out["edge_mode"] = asm._edge_mode
    return out


def restore_soa_assembler(asm, state: Dict[str, Any]) -> None:
    asm._max_ts = state["max_ts"]
    asm._next_start = state["next_start"]
    asm.dropped_late = state["dropped_late"]
    if "chunks" in state:
        asm._chunks = [dict(c) for c in state["chunks"]]
    else:
        asm._rows = [dict(r) for r in state["rows"]]
        asm._verts = list(state["verts"])
        asm._edges = None if state["edges"] is None else list(state["edges"])
        asm._edge_mode = state["edge_mode"]


def interner_state(interner: Interner) -> Dict[str, Any]:
    return {"table": list(interner._to_key)}


def restore_interner(interner: Interner, state: Dict[str, Any]) -> None:
    interner._to_key = list(state["table"])
    interner._to_int = {k: i for i, k in enumerate(interner._to_key)}


def operator_state(op) -> Dict[str, Any]:
    """Snapshot the known stateful fields of an operator instance.

    Pane-carry digests live on device during the run; they're pulled to
    numpy here (a checkpoint is a host/disk artifact by definition)."""
    out: Dict[str, Any] = {"interner": interner_state(op.interner)}
    if hasattr(op, "_skeys"):  # TAggregateQuery MapState (sorted arrays)
        out["agg_state"] = {
            "keys": op._skeys.copy(),
            "min": op._smin.copy(),
            "max": op._smax.copy(),
        }
    if hasattr(op, "_running"):  # TStatsQuery ValueState
        out["running"] = dict(op._running)
    if getattr(op, "checkpoint_assembler", None) is not None:
        out["assembler"] = assembler_state(op.checkpoint_assembler)
    if getattr(op, "checkpoint_soa_assembler", None) is not None:
        out["soa_assembler"] = soa_assembler_state(op.checkpoint_soa_assembler)
    pane = getattr(op, "_pane_carry", None)
    if pane is not None:  # kNN query_panes digests
        out["knn_pane_carry"] = {
            ps: None if v is None else
            (int(v[0]), np.asarray(v[1]), np.asarray(v[2]), list(v[3]))
            for ps, v in pane.items()
        }
    soa_pane = getattr(op, "_pane_carry_soa", None)
    if soa_pane is not None:  # kNN run_soa_panes digests
        out["knn_pane_carry_soa"] = {
            ps: None if v is None else (np.asarray(v[0]), np.asarray(v[1]))
            for ps, v in soa_pane.items()
        }
    wire_pane = getattr(op, "_wire_pane_carry", None)
    if wire_pane is not None:  # kNN run_wire_panes digest ring
        out["knn_wire_pane_carry"] = {
            "next_pane": int(wire_pane["next_pane"]),
            "digests": [
                (np.asarray(s), np.asarray(r))
                for s, r in wire_pane["digests"]
            ],
            # per-pane event counts — gap-window suppression state
            "counts": [int(c) for c in wire_pane.get(
                "counts", [1] * len(wire_pane["digests"])
            )],
        }
    pplan = getattr(op, "partition_plan", None)
    if pplan is not None:  # grid-partitioned placement (parallel/partition.py)
        # The per-shard partition map rides the SAME framed-CRC unit
        # publish as the operator state it placed — resume validates the
        # shard count against the restoring mesh before any dispatch.
        out["partition"] = pplan.to_dict()
    qreg = getattr(op, "qserve_registry", None)
    if qreg is not None:  # qserve standing-query registry (qserve.py)
        out["qserve"] = qreg.state()
    if getattr(op, "dag_nodes", None) is not None:
        # Composed dataflow (dag.py): every node's backend + counters +
        # substate (qserve registry, checkin occupancy, …) snapshot as
        # ONE component — the atomic-unit-checkpoint half that pairs
        # with the MultiSink marker map in the same publish.
        out["dag"] = op.dag_state()
    jcarry = getattr(op, "_join_pane_carry", None)
    if jcarry is not None:  # join query_panes pane events + pair blocks
        out["join_pane_carry"] = {
            "panes": {
                ps: (list(v[0]), list(v[1]))
                for ps, v in jcarry["panes"].items()
            },
            "blocks": {
                key: (list(pairs), over)
                for key, (pairs, over) in jcarry["blocks"].items()
            },
        }
    return out


def restore_operator(op, state: Dict[str, Any]) -> None:
    restore_interner(op.interner, state["interner"])
    if "agg_state" in state and hasattr(op, "_skeys"):
        agg = state["agg_state"]
        if "keys" not in agg:
            # Round-1 checkpoint format: {(cell, oid_str): (min, max)}.
            # Convert to the sorted cell<<32|interned-oid key arrays (the
            # interner is already restored above, so interning an oid seen
            # at snapshot time returns its original dense id).
            rows = sorted(
                ((int(c) << 32) | op.interner.intern(o), int(mn), int(mx))
                for (c, o), (mn, mx) in agg.items()
            )
            agg = {
                "keys": [r[0] for r in rows],
                "min": [r[1] for r in rows],
                "max": [r[2] for r in rows],
            }
        op._skeys = np.asarray(agg["keys"], np.int64)
        op._smin = np.asarray(agg["min"], np.int64)
        op._smax = np.asarray(agg["max"], np.int64)
    if "running" in state and hasattr(op, "_running"):
        op._running = dict(state["running"])
    if "assembler" in state:
        op._restored_assembler = state["assembler"]
    if "soa_assembler" in state:
        op._restored_soa_assembler = state["soa_assembler"]
    if "knn_pane_carry" in state:
        op._pane_carry = {
            ps: None if v is None else (v[0], v[1], v[2], list(v[3]))
            for ps, v in state["knn_pane_carry"].items()
        }
    if "knn_pane_carry_soa" in state:
        op._pane_carry_soa = {
            ps: None if v is None else (v[0], v[1])
            for ps, v in state["knn_pane_carry_soa"].items()
        }
    if "knn_wire_pane_carry" in state:
        op._wire_pane_carry = {
            "next_pane": int(state["knn_wire_pane_carry"]["next_pane"]),
            "digests": [
                (s, r) for s, r in state["knn_wire_pane_carry"]["digests"]
            ],
            "counts": [int(c) for c in state["knn_wire_pane_carry"].get(
                "counts",
                [1] * len(state["knn_wire_pane_carry"]["digests"]),
            )],
        }
        # Consumed by the NEXT run_wire_panes call only — the
        # index-based carry must never leak into an ordinary fresh run.
        op._wire_pane_restored = True
    if "dag" in state and getattr(op, "dag_nodes", None) is not None:
        # Restored BEFORE the assembler state is consumed (dag.py's
        # _adopt_assembler) so resumed nodes see their backend/substate
        # before the first replayed window fires.
        op.restore_dag(state["dag"])
    if "partition" in state:  # pre-halo checkpoints carry no plan
        # Lazy import: partition.py is jax-free numpy, so restoring a
        # plan never touches the device runtime.
        from spatialflink_tpu.parallel.partition import PartitionPlan

        plan = PartitionPlan.from_dict(state["partition"])
        current = getattr(op, "partition_plan", None)
        if current is not None and current.n_shards != plan.n_shards:
            raise ValueError(
                f"checkpoint partition plan is for {plan.n_shards} "
                f"shard(s) but the resuming operator is configured for "
                f"{current.n_shards} — re-plan and re-checkpoint "
                f"instead of resuming across a shard-count change"
            )
        op.partition_plan = plan
    if "qserve" in state and getattr(op, "qserve_registry", None) \
            is not None:
        # Flag tables are derived (rebuilt from the grid inside
        # restore); the interner restored above keeps tenant/qid ids
        # stable — one intern home.
        op.qserve_registry.restore(state["qserve"])
    if "join_pane_carry" in state:
        # Pane batches are derived data — rebuild through the operator's
        # own batcher (the interner restored above keeps ids stable).
        op._join_pane_carry = {
            "panes": {
                ps: (
                    list(lev), list(rev),
                    op.point_batch(lev) if lev else None,
                    op.point_batch(rev) if rev else None,
                )
                for ps, (lev, rev) in state["join_pane_carry"]["panes"].items()
            },
            "blocks": {
                key: (list(pairs), over)
                for key, (pairs, over)
                in state["join_pane_carry"]["blocks"].items()
            },
        }


def wire_pane_assembler_state(asm) -> Dict[str, Any]:
    """Snapshot a streams/wire.py:WirePaneAssembler — the open pane's
    buffered events + position (slide/wire-format identity included;
    restore refuses a mismatched config). With the consumer offsets and
    the operator's wire digest ring, the full wire pipeline resumes —
    snapshots must be taken with all completed panes drained (the
    pane-boundary alignment note on the class)."""
    return asm.state()


def restore_wire_pane_assembler(asm, state: Dict[str, Any]) -> None:
    asm.restore(state)


def kafka_source_state(src) -> Dict[str, Any]:
    """Snapshot a streams/kafka.py:WireKafkaSource — the checkpointed
    consumer-offsets role of Flink's Kafka consumer
    (StreamingJob.java:255). Pass the saved mapping back as
    ``WireKafkaSource(start_offsets=...)`` on resume; combined with the
    operator/assembler state above, kill-and-resume replays the topic
    with no gap and no duplicate."""
    return {
        "topic": src.topic,
        "offsets": {int(p): int(o) for p, o in src.offsets.items()},
    }


def restore_kafka_source_offsets(state: Dict[str, Any],
                                 topic: str) -> Dict[int, int]:
    """Validate + extract ``start_offsets`` for a resumed source."""
    if state["topic"] != topic:
        raise ValueError(
            f"checkpoint is for topic {state['topic']!r}, not {topic!r}"
        )
    return dict(state["offsets"])


#: Framed-checkpoint magic. Format (big-endian):
#: ``MAGIC(8) | version u32 | crc32 u32 | payload_len u64 | payload`` —
#: the payload is the pickled component dict. The header turns the two
#: silent corruption modes a raw pickle has (truncation → EOFError deep
#: inside the unpickler; bit rot → an arbitrary exception or, worse,
#: garbage state) into explicit :class:`CheckpointCorruptError`\ s naming
#: the path and what was expected.
CHECKPOINT_MAGIC = b"SFTCKPT\x01"
CHECKPOINT_VERSION = 2


class CheckpointCorruptError(RuntimeError):
    """A checkpoint file failed its integrity check (magic / version /
    length / CRC / unpickle). Carries the path and what was expected so
    the operator sees an actionable error, never a raw pickle traceback.
    """

    def __init__(self, path: str, expected: str, found: str = ""):
        msg = f"corrupt checkpoint {path!r}: expected {expected}"
        if found:
            msg += f", found {found}"
        super().__init__(msg)
        self.path = path


def save_checkpoint(path: str, **components) -> None:
    """Persist named component states, e.g.
    ``save_checkpoint(p, assembler=assembler_state(asm), op=operator_state(o))``.

    Durable publish: framed payload (magic + version + CRC32 + length)
    written to a sibling temp file, fsync'd, then atomically renamed over
    ``path`` — a crash at ANY instant leaves either the old checkpoint or
    the new one, never a torn file. The containing directory is fsync'd
    too so the rename itself survives power loss.
    """
    import struct
    import zlib

    dirname = os.path.dirname(os.path.abspath(path))
    os.makedirs(dirname, exist_ok=True)
    with telemetry.span("checkpoint.pickle"):
        payload = pickle.dumps(components, protocol=pickle.HIGHEST_PROTOCOL)
    tmp = path + ".tmp"
    framed = len(CHECKPOINT_MAGIC) + struct.calcsize(">IIQ") + len(payload)
    with telemetry.span("checkpoint.write", bytes=framed):
        with open(tmp, "wb") as f:
            f.write(CHECKPOINT_MAGIC)
            f.write(struct.pack(">IIQ", CHECKPOINT_VERSION,
                                zlib.crc32(payload) & 0xFFFFFFFF,
                                len(payload)))
            f.write(payload)
            f.flush()
            os.fsync(f.fileno())
        os.replace(tmp, path)  # atomic publish
        try:
            dfd = os.open(dirname, os.O_RDONLY)
            try:
                os.fsync(dfd)
            finally:
                os.close(dfd)
        except OSError:  # pragma: no cover - platform without dir fsync
            pass


def load_checkpoint(path: str) -> Dict[str, Any]:
    """Load + verify a checkpoint.

    Framed (v2+) files are validated magic → version → length → CRC →
    unpickle, each failure raising :class:`CheckpointCorruptError` with
    the path and the expectation that failed. Round-1 checkpoints (raw
    pickle, no header) still load — restore code already handles their
    in-payload format drift — but their corruption is wrapped into the
    same error type instead of surfacing as a pickle traceback.
    """
    import struct
    import zlib

    with open(path, "rb") as f:
        data = f.read()
    if not data.startswith(CHECKPOINT_MAGIC):
        if data[:1] == b"\x80":  # legacy raw-pickle checkpoint (pre-v2)
            try:
                legacy = pickle.loads(data)
            except Exception as e:
                raise CheckpointCorruptError(
                    path, "a loadable legacy (headerless) checkpoint",
                    f"unpickling failed: {e!r}",
                ) from e
            if not isinstance(legacy, dict):
                raise CheckpointCorruptError(
                    path, "a component dict",
                    type(legacy).__name__,
                )
            return legacy
        raise CheckpointCorruptError(
            path, f"magic {CHECKPOINT_MAGIC!r}",
            f"{data[:8]!r} ({len(data)} bytes)",
        )
    header = data[len(CHECKPOINT_MAGIC):len(CHECKPOINT_MAGIC) + 16]
    if len(header) < 16:
        raise CheckpointCorruptError(
            path, "a 16-byte header after the magic",
            f"{len(header)} bytes (truncated)",
        )
    version, crc, length = struct.unpack(">IIQ", header)
    if version > CHECKPOINT_VERSION:
        raise CheckpointCorruptError(
            path,
            f"checkpoint version <= {CHECKPOINT_VERSION} (this build)",
            f"version {version} — written by a newer build; upgrade or "
            "re-checkpoint",
        )
    payload = data[len(CHECKPOINT_MAGIC) + 16:]
    if len(payload) != length:
        raise CheckpointCorruptError(
            path, f"{length} payload bytes",
            f"{len(payload)} (truncated or trailing garbage)",
        )
    if zlib.crc32(payload) & 0xFFFFFFFF != crc:
        raise CheckpointCorruptError(
            path, f"payload CRC32 {crc:#010x}",
            f"{zlib.crc32(payload) & 0xFFFFFFFF:#010x} (bit rot or a "
            "partial overwrite)",
        )
    try:
        return pickle.loads(payload)
    except Exception as e:  # CRC passed but unpickle failed: version skew
        raise CheckpointCorruptError(
            path, "a loadable pickle payload",
            f"unpickling failed: {e!r}",
        ) from e
