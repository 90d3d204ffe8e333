"""Multi-chip sharded query kernels via ``shard_map``.

Sharding layout (the scaling-book recipe: pick a mesh, annotate shardings,
let XLA insert collectives):

  - **range**: points sharded over ``data``; optionally queries sharded
    over ``query`` with a psum-OR across the query axis. Fully local
    compute, no collective in the 1-D case — the analog of the reference's
    keyBy(gridID) partitioning minus the shuffle.
  - **kNN**: points sharded over ``data``; each shard computes its local
    per-object segment-min, then a ``pmin`` collective over ``data``
    reduces object minima across shards and the (replicated) top-k runs on
    the reduced table. This replaces the reference's single-subtask
    windowAll merge bottleneck (KNNQuery.java:204-308) with one ICI
    all-reduce.
  - **join**: left side sharded over ``data``, cell-sorted right side
    replicated (broadcast once per window) — each shard joins its left
    slice; pair outputs stay sharded.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax import shard_map
from jax.sharding import Mesh, PartitionSpec as P

from spatialflink_tpu.ops.distances import point_point_distance
from spatialflink_tpu.ops.join import JoinResult, join_kernel
from spatialflink_tpu.ops.knn import KnnResult
from spatialflink_tpu.ops.range import _emit_mask
from spatialflink_tpu.parallel.mesh import payload_nbytes
from spatialflink_tpu.telemetry import telemetry


def _itemsize(dtype) -> int:
    return int(np.dtype(dtype).itemsize)


def mesh_from_config(shape):
    """Build the runtime mesh from the config's ``deviceMesh`` list
    (config.py: Params.device_mesh — the ``parallelism`` analog of
    conf/geoflink-conf.yml:55). 1-D → ("data",); 2-D → ("data", "query").
    A product of 1 means single-device: returns None.

    The data axis must be a power of two: window batches are padded to
    power-of-two buckets (utils/padding.py), so only power-of-two axes
    divide every batch.
    """
    import numpy as np

    from spatialflink_tpu.parallel.mesh import make_mesh

    shape = [int(s) for s in shape]
    total = int(np.prod(shape)) if shape else 1
    if total <= 1:
        return None
    if shape[0] & (shape[0] - 1):
        raise ValueError(
            f"deviceMesh data axis must be a power of two (window batches "
            f"are padded to power-of-two buckets); got {shape[0]}"
        )
    names = ("data",) if len(shape) == 1 else ("data", "query")
    return make_mesh(tuple(shape), names[: len(shape)])


@functools.lru_cache(maxsize=None)
def _cached_sharded_window(mesh, kernel, data_idx, n_args, statics, topk,
                           reduce=False):
    skw = dict(statics)
    in_specs = tuple(
        P("data") if i in data_idx else P() for i in range(n_args)
    )
    if topk:
        def local(*args):
            base = jax.lax.axis_index("data") * args[data_idx[0]].shape[0]
            return kernel(*args, axis_name="data", index_base=base, **skw)

        out_specs = KnnResult(P(), P(), P(), P())
    elif reduce:
        # Segment-reduction kernels (e.g. tRange's per-trajectory hit
        # flags): the kernel's axis_name hook all-reduces its per-shard
        # segment reduction; the output is replicated.
        def local(*args):
            return kernel(*args, axis_name="data", **skw)

        out_specs = P()
    else:
        def local(*args):
            return kernel(*args, **skw)

        out_specs = (P("data"), P("data"))
    fn = shard_map(
        local, mesh=mesh, in_specs=in_specs, out_specs=out_specs,
        check_vma=False,
    )
    return jax.jit(fn)


def sharded_window_kernel(mesh, kernel, data_idx, n_args, topk=False,
                          reduce=False, **statics):
    """jit + shard_map a fused window kernel over a mesh's ``data`` axis.

    This is how the operator layer executes on a mesh: the SAME fused
    per-window program the single-device path jits is shard_mapped with the
    stream-axis arguments (positions ``data_idx``) split over ``data`` and
    everything else replicated — the moral equivalent of the reference's
    keyBy partitioning (StreamingJob.java:177, parallelism default 15 at
    conf/geoflink-conf.yml:55) without the shuffle.

    ``topk=False``: elementwise kernels, outputs (keep, dist) stay sharded.
    ``topk=True``: kNN kernels — the kernel's ``axis_name``/``index_base``
    hooks pmin-reduce per-object minima across shards (one ICI all-reduce
    replacing the reference's single-subtask windowAll merge,
    KNNQuery.java:204-308); outputs are replicated.

    Wrappers are cached per (mesh, kernel, statics) so repeated windows
    reuse the compiled program.
    """
    fn = _cached_sharded_window(
        mesh, kernel, tuple(data_idx), n_args,
        tuple(sorted(statics.items())), topk, reduce,
    )
    return _AccountedProgram(fn, tuple(data_idx), topk, reduce,
                             dict(statics))


class _AccountedProgram:
    """Accounts the generic mesh program's collective footprint at call
    time (host-side, from the concrete args' static shapes), then calls
    the cached jitted program. Attribute access forwards to the jit
    object so ``instrument_jit``'s lower()/cost hooks keep working.

    topk → the kernel's axis_name hook pmin-reduces its per-object
    minima + representative tables ((num_segments,) each); reduce → a
    psum of the replicated segment reduction; elementwise → no explicit
    collective, so the replicated operands' broadcast is the traffic.
    """

    __slots__ = ("_fn", "_data_idx", "_topk", "_reduce", "_statics")

    def __init__(self, fn, data_idx, topk, reduce, statics):
        self._fn = fn
        self._data_idx = frozenset(data_idx)
        self._topk = topk
        self._reduce = reduce
        self._statics = statics

    def __call__(self, *args, **kwargs):
        if telemetry.enabled:
            rep = payload_nbytes(*(
                a for i, a in enumerate(args) if i not in self._data_idx
            ))
            if self._topk or self._reduce:
                nseg = int(self._statics.get("num_segments", 0))
                ref = (args[min(self._data_idx)]
                       if self._data_idx and args else None)
                elem = (_itemsize(ref.dtype)
                        if ref is not None and hasattr(ref, "dtype") else 8)
                table = 2 * nseg * elem if nseg else max(rep, elem)
                telemetry.account_collective(
                    "pmin" if self._topk else "psum", table, axis="data"
                )
            if rep:
                telemetry.account_collective("broadcast", rep, axis="data")
        return self._fn(*args, **kwargs)

    def __getattr__(self, attr):
        return getattr(self._fn, attr)


def sharded_range_query(
    mesh: Mesh,
    xy: jnp.ndarray,
    valid: jnp.ndarray,
    flags: jnp.ndarray,
    query_xy: jnp.ndarray,
    radius,
    approximate: bool = False,
):
    """Data-parallel range query. ``xy``/``valid``/``flags`` shard over
    ``data``; the query set is replicated. Returns (keep, min_dist) sharded
    like the inputs."""
    # Fully local compute: the replicated query set's broadcast is the
    # only cross-chip traffic.
    telemetry.account_collective(
        "broadcast", payload_nbytes(query_xy), axis="data"
    )

    def local(xy_l, valid_l, flags_l, q):
        d = point_point_distance(xy_l[:, None, :], q[None, :, :])
        min_dist = jnp.min(d, axis=1)
        return _emit_mask(valid_l, flags_l, min_dist, radius, approximate), min_dist

    fn = shard_map(
        local,
        mesh=mesh,
        in_specs=(P("data"), P("data"), P("data"), P()),
        out_specs=(P("data"), P("data")),
    )
    return fn(xy, valid, flags, query_xy)


def sharded_range_query_2d(
    mesh: Mesh,
    xy: jnp.ndarray,
    valid: jnp.ndarray,
    flags: jnp.ndarray,
    query_xy: jnp.ndarray,
    radius,
    approximate: bool = False,
):
    """2-D sharded range query: points over ``data``, query set over
    ``query``. Each (data, query) tile evaluates its query slice; a psum-OR
    over the ``query`` axis merges per-slice hits — the collective pattern
    for large query sets (e.g. 1k query polygons sharded across chips).
    Returns (keep sharded over data, min_dist sharded over data)."""
    # pmin of each data tile's per-point min-dist vector across the
    # query axis (one lane per point).
    telemetry.account_collective(
        "pmin", int(xy.shape[0]) * _itemsize(xy.dtype), axis="query"
    )

    def local(xy_l, valid_l, flags_l, q_l):
        d = point_point_distance(xy_l[:, None, :], q_l[None, :, :])
        local_min = jnp.min(d, axis=1)
        # Min distance across the query shards (ICI all-reduce on "query").
        min_dist = jax.lax.pmin(local_min, axis_name="query")
        keep = _emit_mask(valid_l, flags_l, min_dist, radius, approximate)
        return keep, min_dist

    fn = shard_map(
        local,
        mesh=mesh,
        in_specs=(P("data"), P("data"), P("data"), P("query")),
        out_specs=(P("data"), P("data")),
        check_vma=False,
    )
    return fn(xy, valid, flags, query_xy)


def sharded_knn(
    mesh: Mesh,
    xy: jnp.ndarray,
    valid: jnp.ndarray,
    flags: jnp.ndarray,
    oid: jnp.ndarray,
    query_xy: jnp.ndarray,
    radius,
    k: int,
    num_segments: int,
) -> KnnResult:
    """Multi-chip kNN: local segment-min per shard → pmin over ``data`` →
    replicated top-k. Object ids are global dense ints (host interning),
    so the (num_segments,) minima table is the only cross-chip traffic —
    one psum-sized all-reduce instead of the reference's windowAll
    re-shuffle of every candidate."""
    # Two (num_segments,) pmin tables (minima + packed representatives)
    # plus the replicated query point's broadcast.
    telemetry.account_collective(
        "pmin", 2 * int(num_segments) * _itemsize(xy.dtype), axis="data"
    )
    telemetry.account_collective(
        "broadcast", payload_nbytes(query_xy), axis="data"
    )

    from spatialflink_tpu.ops.knn import _topk_from_point_dists

    def local(xy_l, valid_l, flags_l, oid_l, q):
        dist = point_point_distance(xy_l, q[None, :])
        # Same top-k core as the single-chip kernel, with the per-object
        # minima/representatives pmin-reduced over the data axis and local
        # indices offset to global ones.
        base = jax.lax.axis_index("data") * xy_l.shape[0]
        return _topk_from_point_dists(
            dist, valid_l, flags_l, oid_l, radius, k, num_segments,
            axis_name="data", index_base=base,
        )

    fn = shard_map(
        local,
        mesh=mesh,
        in_specs=(P("data"), P("data"), P("data"), P("data"), P()),
        out_specs=KnnResult(P(), P(), P(), P()),
        check_vma=False,
    )
    return fn(xy, valid, flags, oid, query_xy)


@functools.lru_cache(maxsize=None)
def _cached_knn_multi(mesh, k, num_segments, query_sharded):
    from spatialflink_tpu.ops.cells import gather_cell_flags
    from spatialflink_tpu.ops.knn import _topk_from_point_dists

    def local(xy_l, valid_l, cell_l, ft_l, oid_l, q_l, radius):
        base = jax.lax.axis_index("data") * xy_l.shape[0]

        def one(q_xy, ftab):
            dist = point_point_distance(xy_l, q_xy[None, :])
            return _topk_from_point_dists(
                dist, valid_l, gather_cell_flags(cell_l, ftab), oid_l,
                radius, k, num_segments,
                axis_name="data", index_base=base,
            )

        # Same query blocking as knn_multi_query_kernel: vmap only
        # ``block`` query lanes at a time under lax.map so peak memory is
        # O(block × N_local), not O(Q_local × N_local).
        q_total = q_l.shape[0]
        block = next(b for b in (32, 16, 8, 4, 2, 1) if q_total % b == 0)

        def blk(args):
            q_b, f_b = args
            return jax.vmap(one)(q_b, f_b)

        res = jax.lax.map(
            blk,
            (
                q_l.reshape(-1, block, 2),
                ft_l.reshape(q_total // block, block, -1),
            ),
        )
        return KnnResult(*[x.reshape((q_total,) + x.shape[2:]) for x in res])

    qspec = P("query") if query_sharded else P()
    fn = shard_map(
        local,
        mesh=mesh,
        in_specs=(
            P("data"), P("data"), P("data"), qspec, P("data"), qspec, P(),
        ),
        out_specs=KnnResult(qspec, qspec, qspec, qspec),
        check_vma=False,
    )
    return jax.jit(fn)


def sharded_knn_multi(
    mesh: Mesh,
    xy: jnp.ndarray,
    valid: jnp.ndarray,
    cell: jnp.ndarray,
    flags_tables: jnp.ndarray,
    oid: jnp.ndarray,
    query_xy: jnp.ndarray,
    radius,
    k: int,
    num_segments: int,
) -> KnnResult:
    """Sharded MULTI-query kNN: points over ``data``; with a 2-D mesh the
    query batch (and its per-query flag tables) additionally shards over
    ``query``. Each (data[, query]) tile answers its query slice against
    its point shard; per-object minima pmin-reduce over ``data`` (batched
    collective under vmap — one ICI all-reduce per query lane), and the
    (Q, k) results stay sharded over ``query`` (replicated on 1-D
    meshes). The scale-out form of ops/knn.py:knn_multi_query_kernel for
    query sets too large for one chip's flag-table memory. On a 2-D mesh
    Q must divide the query-axis size."""
    query_sharded = "query" in mesh.shape
    # One batched pmin per query lane (two (num_segments,) tables each);
    # on 1-D meshes the query batch + flag tables replicate (broadcast).
    lanes = int(query_xy.shape[0])
    telemetry.account_collective(
        "pmin", 2 * lanes * int(num_segments) * _itemsize(xy.dtype),
        axis="data", calls=lanes,
    )
    if not query_sharded:
        telemetry.account_collective(
            "broadcast", payload_nbytes(query_xy, flags_tables),
            axis="data",
        )
    fn = _cached_knn_multi(mesh, k, num_segments, query_sharded)
    return fn(xy, valid, cell, flags_tables, oid, query_xy, radius)


@functools.lru_cache(maxsize=None)
def _cached_registry_bucket(mesh, k, num_segments):
    from spatialflink_tpu.ops.query_registry import (
        RegistryBucketResult,
        registry_bucket_query,
    )

    def local(xy_l, valid_l, cell_l, ft, oid_l, q, r, qok):
        base = jax.lax.axis_index("data") * xy_l.shape[0]

        def one(q_xy, ftab, rad, ok):
            return registry_bucket_query(
                xy_l, valid_l, cell_l, ftab, oid_l, q_xy, rad, ok,
                k=k, num_segments=num_segments,
                axis_name="data", index_base=base,
            )

        # Same query blocking as the single-device bucket kernel: vmap
        # only ``block`` query lanes at a time under lax.map so peak
        # memory is O(block × N_local).
        q_total = q.shape[0]
        block = next(b for b in (32, 16, 8, 4, 2, 1) if q_total % b == 0)

        def blk(args):
            q_b, f_b, r_b, ok_b = args
            return jax.vmap(one)(q_b, f_b, r_b, ok_b)

        res = jax.lax.map(
            blk,
            (
                q.reshape(-1, block, 2),
                ft.reshape(q_total // block, block, -1),
                r.reshape(-1, block),
                qok.reshape(-1, block),
            ),
        )
        return RegistryBucketResult(
            *[x.reshape((q_total,) + x.shape[2:]) for x in res]
        )

    fn = shard_map(
        local,
        mesh=mesh,
        in_specs=(
            P("data"), P("data"), P("data"), P(), P("data"), P(), P(), P(),
        ),
        out_specs=RegistryBucketResult(P(), P(), P(), P(), P()),
        check_vma=False,
    )
    return jax.jit(fn)


def sharded_registry_bucket(
    mesh: Mesh,
    xy: jnp.ndarray,
    valid: jnp.ndarray,
    cell: jnp.ndarray,
    flags_tables: jnp.ndarray,
    oid: jnp.ndarray,
    query_xy: jnp.ndarray,
    radius: jnp.ndarray,
    query_valid: jnp.ndarray,
    k: int,
    num_segments: int,
):
    """Sharded standing-query bucket (qserve): points over ``data``, the
    query bucket (coords, per-query radii, flag tables, validity lanes)
    replicated. Per-object minima pmin-reduce over ``data`` inside
    ``ops/query_registry.py:registry_bucket_query`` — the same one-ICI-
    all-reduce shape as ``sharded_knn_multi`` — and the ``within``
    exactness counter is computed on the REDUCED table, so results
    (top-k rows, counts, overflow) are bit-identical to the
    single-device ``registry_bucket_kernel`` (CPU-mesh parity pinned in
    tests/test_qserve.py)."""
    # Same batched-pmin shape as sharded_knn_multi; the whole standing
    # bucket (coords, radii, flag tables, validity) replicates.
    lanes = int(query_xy.shape[0])
    telemetry.account_collective(
        "pmin", 2 * lanes * int(num_segments) * _itemsize(xy.dtype),
        axis="data", calls=lanes,
    )
    telemetry.account_collective(
        "broadcast",
        payload_nbytes(query_xy, radius, flags_tables, query_valid),
        axis="data",
    )
    fn = _cached_registry_bucket(mesh, k, num_segments)
    return fn(xy, valid, cell, flags_tables, oid, query_xy, radius,
              query_valid)


def sharded_traj_stats(
    mesh: Mesh,
    xy: jnp.ndarray,
    ts: jnp.ndarray,
    oid: jnp.ndarray,
    valid: jnp.ndarray,
    num_segments: int,
):
    """Sequence-parallel trajectory statistics with halo exchange.

    The long-trajectory analog of sequence/context parallelism: the
    (oid, ts)-sorted point sequence is sharded over ``data``; each shard
    computes consecutive-point contributions locally, and the one pair that
    straddles each shard boundary is recovered by passing every shard's
    *last* point to its right neighbor via ``lax.ppermute`` (a ring halo
    exchange over ICI). Per-object partials are then psum'd. Exactly equals
    the single-device ops.trajectory.traj_stats_kernel.
    """
    from spatialflink_tpu.ops.distances import point_point_distance

    # Ring halo (every shard ships its last xy/ts/oid/valid row) plus
    # three (num_segments,) psum tables (spatial, temporal, count).
    ndev = int(mesh.shape["data"])
    halo = ndev * (2 * _itemsize(xy.dtype) + _itemsize(ts.dtype)
                   + _itemsize(oid.dtype) + 1)
    telemetry.account_collective("ppermute", halo, axis="data", calls=4)
    telemetry.account_collective(
        "psum", int(num_segments) * (2 * _itemsize(xy.dtype) + 4),
        axis="data", calls=3,
    )

    def local(xy_l, ts_l, oid_l, valid_l):
        # The ppermute ring needs a STATIC shard count; read it from the
        # mesh.
        n_shards = int(mesh.shape["data"])
        # Ring halo: receive the previous shard's last (xy, ts, oid, valid).
        perm = [(i, (i + 1) % n_shards) for i in range(n_shards)]
        prev_xy = jax.lax.ppermute(xy_l[-1], "data", perm)
        prev_ts = jax.lax.ppermute(ts_l[-1], "data", perm)
        prev_oid = jax.lax.ppermute(oid_l[-1], "data", perm)
        prev_valid = jax.lax.ppermute(valid_l[-1], "data", perm)
        # Shard 0 has no predecessor: mask its halo pair.
        first = jax.lax.axis_index("data") == 0
        prev_valid = prev_valid & ~first

        xy_ext = jnp.concatenate([prev_xy[None, :], xy_l], axis=0)
        ts_ext = jnp.concatenate([prev_ts[None], ts_l], axis=0)
        oid_ext = jnp.concatenate([prev_oid[None], oid_l], axis=0)
        valid_ext = jnp.concatenate([prev_valid[None], valid_l], axis=0)

        same_traj = (oid_ext[1:] == oid_ext[:-1]) & valid_ext[1:] & valid_ext[:-1]
        seg_d = point_point_distance(xy_ext[1:], xy_ext[:-1])
        seg_t = (ts_ext[1:] - ts_ext[:-1]).astype(seg_d.dtype)
        spatial = jax.ops.segment_sum(
            jnp.where(same_traj, seg_d, 0), oid_l, num_segments=num_segments
        )
        temporal = jax.ops.segment_sum(
            jnp.where(same_traj, seg_t, 0), oid_l, num_segments=num_segments
        )
        count = jax.ops.segment_sum(
            valid_l.astype(jnp.int32), oid_l, num_segments=num_segments
        )
        spatial = jax.lax.psum(spatial, "data")
        temporal = jax.lax.psum(temporal, "data")
        count = jax.lax.psum(count, "data")
        speed = jnp.where(
            temporal > 0, spatial / jnp.where(temporal > 0, temporal, 1), 0.0
        )
        return spatial, temporal, count, speed

    fn = shard_map(
        local,
        mesh=mesh,
        in_specs=(P("data"), P("data"), P("data"), P("data")),
        out_specs=(P(), P(), P(), P()),
        check_vma=False,
    )
    return fn(xy, ts, oid, valid)


@functools.lru_cache(maxsize=None)
def _cached_sharded_join_compact(mesh, grid_n, cap, max_pairs):
    n_shards = int(mesh.shape["data"])
    local_budget = max_pairs // n_shards

    def fn(left_xy, left_valid, left_ci,
           right_xy, right_valid, right_cells, offsets, radius):
        # Cell-sort the right side INSIDE the jitted program (an eager
        # argsort per window would pay a dispatch round trip — CLAUDE.md
        # hot-path rule).
        order = jnp.argsort(right_cells).astype(jnp.int32)

        def local(lxy, lvalid, lci, rxy, rvalid, rcells, rorder, offs, r):
            res = join_kernel(
                lxy, lvalid, lci, rxy, rvalid, rcells, rorder, offs,
                grid_n=grid_n, radius=r, cap=cap,
            )
            # Compact PER SHARD: jnp.nonzero over a sharded value hangs the
            # SPMD partitioner (cross-shard cumsum), so each shard extracts
            # its own hits into a local budget of max_pairs / n_shards.
            n_loc, kc = res.pair_mask.shape
            flat = res.pair_mask.reshape(-1)
            (hit,) = jnp.nonzero(flat, size=local_budget, fill_value=-1)
            found = hit >= 0
            hit_c = jnp.maximum(hit, 0)
            base = jax.lax.axis_index("data") * n_loc
            left_idx = jnp.where(
                found, (hit_c // kc).astype(jnp.int32) + base, -1
            )
            right_idx = jnp.where(
                found, res.right_index.reshape(-1)[hit_c], -1
            )
            dist = jnp.where(found, res.dist.reshape(-1)[hit_c], jnp.inf)
            local_count = jnp.sum(flat.astype(jnp.int32))
            total = jax.lax.psum(local_count, "data")
            max_local = jax.lax.pmax(local_count, "data")
            over = jax.lax.psum(res.overflow, "data")
            return left_idx, right_idx, dist, total, max_local, over

        left_idx, right_idx, dist, total, max_local, over = shard_map(
            local,
            mesh=mesh,
            in_specs=(
                P("data"), P("data"), P("data"),
                P(), P(), P(), P(), P(), P(),
            ),
            out_specs=(P("data"), P("data"), P("data"), P(), P(), P()),
            check_vma=False,
        )(
            left_xy, left_valid, left_ci,
            right_xy[order], right_valid[order], right_cells[order], order,
            offsets, radius,
        )
        # Shard outputs concatenate with per-shard padding tails; compact
        # valid pairs to the front so the caller's [:count] slice works.
        perm = jnp.argsort(left_idx < 0, stable=True)
        # A shard whose hits exceeded its local budget dropped pairs even
        # if the global total fits; inflating the reported count past
        # max_pairs makes the caller's retry-with-doubled-budget kick in.
        count = jnp.maximum(total, max_local * n_shards)
        from spatialflink_tpu.ops.join import CompactJoinResult

        return CompactJoinResult(
            left_idx[perm], right_idx[perm], dist[perm], count, over
        )

    return jax.jit(fn)


def sharded_join_window_compact(
    mesh: Mesh,
    left_xy, left_valid, left_cell_xy_idx,
    right_xy, right_valid, right_cells,
    neighbor_offsets, grid_n: int, radius, cap: int, max_pairs: int,
):
    """Multi-chip grid-hash join for the operator layer: left side sharded
    over ``data``, right side replicated, pairs compacted per shard on
    device (O(max_pairs) egress, same CompactJoinResult/retry contract as
    the single-device compact and Pallas paths). One cached jitted program
    per (mesh, grid_n, cap, max_pairs); ``max_pairs`` is rounded up to a
    multiple of the data-axis size."""
    n_shards = int(mesh.shape["data"])
    max_pairs = int(max_pairs) + (-int(max_pairs)) % n_shards
    # Replicated right side broadcast once per window; the compaction
    # protocol all-reduces three int32 scalars (total, max_local, over).
    telemetry.account_collective(
        "broadcast",
        payload_nbytes(right_xy, right_valid, right_cells,
                       neighbor_offsets),
        axis="data",
    )
    telemetry.account_collective("psum", 8, axis="data", calls=2)
    telemetry.account_collective("pmax", 4, axis="data")
    return _cached_sharded_join_compact(mesh, grid_n, cap, max_pairs)(
        left_xy, left_valid, left_cell_xy_idx,
        right_xy, right_valid, right_cells, neighbor_offsets, radius,
    )


def sharded_join(
    mesh: Mesh,
    left_xy: jnp.ndarray,
    left_valid: jnp.ndarray,
    left_cell_xy_idx: jnp.ndarray,
    right_xy_sorted: jnp.ndarray,
    right_valid_sorted: jnp.ndarray,
    right_cells_sorted: jnp.ndarray,
    right_order: jnp.ndarray,
    neighbor_offsets: jnp.ndarray,
    grid_n: int,
    radius,
    cap: int,
) -> JoinResult:
    """Grid-hash join with the left side sharded over ``data`` and the
    (smaller) cell-sorted right side replicated."""
    # Replicated right-side broadcast + the overflow-scalar psum.
    telemetry.account_collective(
        "broadcast",
        payload_nbytes(right_xy_sorted, right_valid_sorted,
                       right_cells_sorted, right_order, neighbor_offsets),
        axis="data",
    )
    telemetry.account_collective("psum", 4, axis="data")

    def local(lxy, lvalid, lci, rxy, rvalid, rcells, rorder, offs):
        res = join_kernel(
            lxy, lvalid, lci, rxy, rvalid, rcells, rorder, offs,
            grid_n=grid_n, radius=radius, cap=cap,
        )
        # Per-shard overflow counts differ; psum them so the scalar output
        # is replicated (its out_spec is P()).
        return res._replace(overflow=jax.lax.psum(res.overflow, "data"))

    fn = shard_map(
        local,
        mesh=mesh,
        in_specs=(
            P("data"), P("data"), P("data"), P(), P(), P(), P(), P(),
        ),
        out_specs=JoinResult(P("data"), P("data"), P("data"), P()),
        check_vma=False,
    )
    return fn(
        left_xy, left_valid, left_cell_xy_idx,
        right_xy_sorted, right_valid_sorted, right_cells_sorted, right_order,
        neighbor_offsets,
    )


@functools.lru_cache(maxsize=None)
def _cached_sharded_pg_join(mesh: Mesh, polygonal: bool, block: int,
                            cand: int, max_pairs: int, pair_cap: int,
                            approx: bool = False):
    from spatialflink_tpu.ops.join import (
        PrunedJoinPairs,
        point_geometry_join_pruned_kernel,
    )

    def local(pxy, pvalid, gverts, gev, gvalid, gbbox, radius):
        res = point_geometry_join_pruned_kernel(
            pxy, pvalid, gverts, gev, gvalid, gbbox, radius,
            polygonal=polygonal, block=block, cand=cand,
            max_pairs=max_pairs, pair_cap=pair_cap, approx=approx,
        )
        base = jax.lax.axis_index("data") * pxy.shape[0]
        left = jnp.where(res.left_index >= 0, res.left_index + base, -1)
        return PrunedJoinPairs(
            left, res.right_index, res.dist,
            res.count[None],  # (1,) per shard → (n_shards,) stacked
            jax.lax.psum(res.cand_overflow, "data"),
            jax.lax.psum(res.pair_overflow, "data"),
        )

    return jax.jit(shard_map(
        local,
        mesh=mesh,
        in_specs=(P("data"), P("data"), P(), P(), P(), P(), P()),
        out_specs=PrunedJoinPairs(
            P("data"), P("data"), P("data"), P("data"), P(), P()
        ),
        check_vma=False,
    ))


def sharded_point_geometry_join_pruned(
    mesh: Mesh,
    pxy, pvalid, gverts, gev, gvalid, gbbox, radius,
    polygonal: bool, block: int, cand: int, max_pairs: int,
    pair_cap: int = 8, approx: bool = False,
):
    """Multi-chip grid-pruned point ⋈ geometry join: the (host-locality-
    sorted) point side shards over ``data``, the geometry batch
    replicates; each shard runs point_geometry_join_pruned_kernel on its
    contiguous slice (sorted order is preserved by contiguous sharding,
    so tile locality survives) and compacts its own pairs.

    ``left_index`` entries are global input positions; ``count`` comes
    back as a per-shard (n_shards,) vector (``max_pairs`` is PER SHARD —
    a shard truncates when its own count exceeds it); both overflow
    counters are psum-replicated. Bit-parity with single-device up to
    pair order (tests/test_join_pruned.py)."""
    # Replicated geometry batch broadcast + two overflow-scalar psums.
    telemetry.account_collective(
        "broadcast", payload_nbytes(gverts, gev, gvalid, gbbox),
        axis="data",
    )
    telemetry.account_collective("psum", 8, axis="data", calls=2)
    return _cached_sharded_pg_join(
        mesh, polygonal, block, cand, max_pairs, pair_cap, approx
    )(pxy, pvalid, gverts, gev, gvalid, gbbox, radius)


@functools.lru_cache(maxsize=None)
def _cached_sharded_gg_join(mesh: Mesh, a_polygonal: bool, b_polygonal: bool,
                            block: int, cand: int, max_pairs: int,
                            pair_cap: int, approx: bool = False):
    from spatialflink_tpu.ops.join import (
        PrunedJoinPairs,
        geometry_geometry_join_pruned_kernel,
    )

    def local(averts, aev, avalid, abbox, bverts, bev, bvalid, bbox, radius):
        res = geometry_geometry_join_pruned_kernel(
            averts, aev, avalid, abbox, bverts, bev, bvalid, bbox, radius,
            a_polygonal=a_polygonal, b_polygonal=b_polygonal,
            block=block, cand=cand, max_pairs=max_pairs, pair_cap=pair_cap,
            approx=approx,
        )
        base = jax.lax.axis_index("data") * averts.shape[0]
        left = jnp.where(res.left_index >= 0, res.left_index + base, -1)
        return PrunedJoinPairs(
            left, res.right_index, res.dist, res.count[None],
            jax.lax.psum(res.cand_overflow, "data"),
            jax.lax.psum(res.pair_overflow, "data"),
        )

    return jax.jit(shard_map(
        local,
        mesh=mesh,
        in_specs=(
            P("data"), P("data"), P("data"), P("data"),
            P(), P(), P(), P(), P(),
        ),
        out_specs=PrunedJoinPairs(
            P("data"), P("data"), P("data"), P("data"), P(), P()
        ),
        check_vma=False,
    ))


@functools.lru_cache(maxsize=None)
def _cached_sharded_tstats_pane(mesh: Mesh, kb: int, slide_ms: int,
                                ppw: int, n_panes: int):
    from spatialflink_tpu.ops.trajectory import (
        TrajPaneStats,
        traj_stats_pane_kernel,
    )

    def local(tp, xp, yp, op_, vp):
        # (1, nmax) point slice in, (kb, n_starts) oid-block rows out —
        # P("data") on the output concatenates the blocks into the
        # global (num_oids, n_starts) tables.
        base = jax.lax.axis_index("data") * kb
        return traj_stats_pane_kernel(
            tp[0], xp[0], yp[0], (op_[0] - base).astype(jnp.int32), vp[0],
            num_oids=kb, slide_ms=slide_ms, ppw=ppw, n_panes=n_panes,
        )

    return jax.jit(shard_map(
        local,
        mesh=mesh,
        in_specs=(P("data"), P("data"), P("data"), P("data"), P("data")),
        out_specs=TrajPaneStats(P("data"), P("data"), P("data")),
        check_vma=False,
    ))


def sharded_traj_stats_pane(
    mesh: Mesh,
    ts_rel: "np.ndarray",
    x: "np.ndarray",
    y: "np.ndarray",
    oid: "np.ndarray",
    valid: "np.ndarray",
    num_oids: int,
    slide_ms: int,
    ppw: int,
    n_panes: int,
):
    """Trajectory-parallel device tStats panes — the mesh execution of
    ``ops/trajectory.py:traj_stats_pane_kernel``.

    Sharding axis: TRAJECTORIES, not points. Every per-pane quantity in
    the kernel (segment sums, cumsum windows, boundary corrections) is
    per-oid independent, so contiguous oid BLOCKS shard over ``data``
    with zero collectives and the per-oid rows come back bit-identical
    to the single-device kernel (x64 parity:
    tests/test_parallel_operators.py) — the trajectory analog of the
    reference's keyBy(objID) partitioning (tStats pipelines key by
    trajectory id; SURVEY §2.2).

    Inputs are the single-device kernel's HOST arrays, sorted by
    (oid, ts) with padding at the end (``valid`` False). The host half
    here re-partitions them into per-shard contiguous slices (sorted
    order makes each oid block a contiguous slice) padded to a common
    bucket. ``num_oids`` must divide by the mesh's ``data`` axis."""
    # Deliberately NO account_collective here: this is the documented
    # zero-collective kernel (per-oid blocks are fully independent), and
    # the mesh parity test asserts its accounted bytes are exactly zero.
    from spatialflink_tpu.utils.padding import next_bucket

    ndev = int(mesh.shape["data"])
    if num_oids % ndev:
        raise ValueError(
            f"num_oids ({num_oids}) must divide by the data axis ({ndev})"
        )
    kb = num_oids // ndev
    tp = np.asarray(ts_rel)
    xp = np.asarray(x)
    yp = np.asarray(y)
    op_ = np.asarray(oid)
    vp = np.asarray(valid)
    live = vp.astype(bool)
    shard_of = op_[live] // kb
    counts = np.bincount(shard_of, minlength=ndev)
    nmax = next_bucket(max(int(counts.max()), 1), minimum=8)
    sh = (ndev, nmax)
    t2 = np.zeros(sh, tp.dtype)
    x2 = np.zeros(sh, xp.dtype)
    y2 = np.zeros(sh, yp.dtype)
    o2 = np.zeros(sh, op_.dtype)
    v2 = np.zeros(sh, bool)
    tl, xl, yl, ol = tp[live], xp[live], yp[live], op_[live]
    start = 0
    for s in range(ndev):
        c = int(counts[s])
        sl = slice(start, start + c)  # oid-sorted ⇒ contiguous block
        t2[s, :c] = tl[sl]
        x2[s, :c] = xl[sl]
        y2[s, :c] = yl[sl]
        o2[s, :c] = ol[sl]
        v2[s, :c] = True
        o2[s, c:] = (s + 1) * kb - 1  # local padding stays in-shard
        start += c
    fn = _cached_sharded_tstats_pane(mesh, kb, slide_ms, ppw, n_panes)
    return fn(
        jnp.asarray(t2), jnp.asarray(x2), jnp.asarray(y2),
        jnp.asarray(o2), jnp.asarray(v2),
    )


def sharded_geometry_geometry_join_pruned(
    mesh: Mesh,
    averts, aev, avalid, abbox, bverts, bev, bvalid, bbbox, radius,
    a_polygonal: bool, b_polygonal: bool,
    block: int, cand: int, max_pairs: int, pair_cap: int = 8,
    approx: bool = False,
):
    """Multi-chip grid-pruned geometry ⋈ geometry join — left side (host-
    locality-sorted) sharded over ``data``, right side replicated; same
    contracts as sharded_point_geometry_join_pruned."""
    # Replicated right geometry batch broadcast + two overflow psums.
    telemetry.account_collective(
        "broadcast", payload_nbytes(bverts, bev, bvalid, bbbox),
        axis="data",
    )
    telemetry.account_collective("psum", 8, axis="data", calls=2)
    return _cached_sharded_gg_join(
        mesh, a_polygonal, b_polygonal, block, cand, max_pairs, pair_cap,
        approx,
    )(averts, aev, avalid, abbox, bverts, bev, bvalid, bbbox, radius)


@functools.lru_cache(maxsize=None)
def _cached_tjoin_pane_scan(mesh, grid_n, cap_w, layers, ppw, num_ids,
                            pair_sel, cap_c):
    from spatialflink_tpu.ops.tjoin_panes import tjoin_pane_scan
    from spatialflink_tpu.telemetry import instrument_jit

    def fn(carry, ts, lps, rps, radius, lps_expire, rps_expire):
        return tjoin_pane_scan(
            carry, ts, lps, rps, radius, grid_n=grid_n, cap_w=cap_w,
            layers=layers, ppw=ppw, num_ids=num_ids, pair_sel=pair_sel,
            cap_c=cap_c, lps_expire=lps_expire, rps_expire=rps_expire,
            mesh=mesh,
        )

    # Same recompile-detector label convention as window_program's mesh
    # path, so bucket churn on the pane scan stays visible.
    return instrument_jit(jax.jit(fn), name="sharded:tjoin_pane_scan")


def sharded_tjoin_pane_scan(
    mesh: Mesh,
    carry,
    ts,
    lps,
    rps,
    radius,
    lps_expire=None,
    rps_expire=None,
    *,
    grid_n: int,
    cap_w: int,
    layers: int,
    ppw: int,
    num_ids: int,
    pair_sel: int,
    cap_c: int = 0,
):
    """Accounted mesh entry for ``ops/tjoin_panes.tjoin_pane_scan``.

    Probe-parallel: pane POINTS shard over ``data``; per slide each
    shard probes its chunk against the replicated window planes, then
    the 8 pane field arrays of BOTH sides and the (flat idx, dist)
    contribution pairs of both probe directions all-gather so every
    shard applies the identical digest scatter, and the 4 overflow
    scalars psum (tjoin_pane_step's axis_name hooks). Bit-identical to
    the single-device scan (tests/test_parallel_operators.py).

    The collective footprint is computed HERE, host-side from static
    shapes, per scan invocation — the ``telemetry.account_collective``
    feeder contract (PARITY.md "Observability"): per slide, both panes'
    fields (x, y at the field dtype; xi/yi/cell/rank/oid int32; valid
    bool) plus ``2·PC·pair_sel`` gathered contribution lanes, and four
    int32 psums.
    """
    n_slides = int(ts.shape[0])
    pc = int(lps[0].shape[1])
    fb = _itemsize(lps[0].dtype)
    per_side = pc * (2 * fb + 5 * 4 + 1)
    contrib = 2 * pc * pair_sel * (4 + fb)
    telemetry.account_collective(
        "all_gather", n_slides * (2 * per_side + contrib), axis="data",
        calls=n_slides * 20,
    )
    telemetry.account_collective(
        "psum", n_slides * 16, axis="data", calls=n_slides * 4,
    )
    fn = _cached_tjoin_pane_scan(
        mesh, grid_n, cap_w, layers, ppw, num_ids, pair_sel, cap_c,
    )
    return fn(carry, ts, lps, rps, radius, lps_expire, rps_expire)
