"""Grid-partitioned mesh kernels — halo exchange replaces all-gather.

Execution shape (parallel/partition.py has the placement math): window
rows partition by owning shard (contiguous flat-cell ranges), and each
kernel ships ONLY the boundary-cell pane lanes to the two adjacent
shards via ``lax.ppermute`` — two open-chain permutations (no ring
wraparound; edge shards receive ppermute's zero fill, which is an
all-invalid pane) instead of replicating total window state::

    perm_right = [(i, i+1)]  — my RIGHT-boundary pane → right neighbor
    perm_left  = [(i, i-1)]  — my LEFT-boundary pane  → left neighbor

Every wrapper here

- is a public ``(mesh, plan, …)`` kernel with a bit-identical
  single-device counterpart in ``ops/halo.py`` (8-device CPU-mesh
  parity pinned in tests/test_partition.py);
- feeds ``telemetry.account_collective`` from STATIC pane shapes and
  ``telemetry.account_halo_state`` with the unpadded boundary-row bytes
  (the replication-ratio denominator in ``sfprof report``);
- passes the ``shard.exchange`` chaos point before dispatch (the
  kill-mid-exchange leg in tests/test_chaos_matrix.py);
- records per-shard watermarks when given event times (the cross-shard
  watermark gauges + merged min-watermark in telemetry).

Host in, host out: wrappers take numpy arrays, partition on the host
(control plane), dispatch ONE cached jitted shard_map program, fetch,
and scatter results back to original row order — so callers never see
the placement.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax import shard_map
from jax.sharding import Mesh, PartitionSpec as P

from spatialflink_tpu.faults import faults
from spatialflink_tpu.ops.halo import (
    join_partitioned_kernel,
    range_partitioned_kernel,
    registry_bucket_partitioned_kernel,
)
from spatialflink_tpu.parallel.mesh import payload_nbytes
from spatialflink_tpu.parallel.partition import (
    PartitionPlan,
    gather_rows,
    scatter_rows,
    shard_layout,
)
from spatialflink_tpu.telemetry import instrument_jit, telemetry

__all__ = [
    "sharded_range_halo",
    "sharded_join_halo",
    "sharded_tjoin_panes_halo",
    "sharded_registry_bucket_halo",
]


def _perms(n_shards: int):
    """Open-chain halo permutations (static per mesh)."""
    perm_r = tuple((i, i + 1) for i in range(n_shards - 1))
    perm_l = tuple((i, i - 1) for i in range(1, n_shards))
    return perm_r, perm_l


def _exchange(fields, perm):
    """ppermute each pane field; uncovered shards (chain ends) receive
    ppermute's zero fill — an all-invalid pane, no masking needed."""
    return tuple(jax.lax.ppermute(f, "data", list(perm)) for f in fields)


def _check_plan(mesh: Mesh, plan: PartitionPlan):
    n_shards = int(mesh.shape["data"])
    if n_shards != plan.n_shards:
        raise ValueError(
            f"partition plan is for {plan.n_shards} shard(s) but the "
            f"mesh data axis has {n_shards}"
        )
    return n_shards


# Each wrapper below accounts its own exchange INLINE (never via a
# helper): the collective-accounting pass seeds coverage at the
# function that calls account_collective, so the accounting must live
# in the same function whose call graph reaches the ppermute sites.
# The ppermute payload is the padded pane stacks (static metadata); the
# halo-state bytes are the unpadded boundary rows — the state the
# exchange exists to move (replication-ratio denominator).


def _record_shard_watermarks(plan: PartitionPlan, cells, valid, ts):
    """Per-shard watermark gauges from one window's event times (host
    side, telemetry only)."""
    if not telemetry.enabled or ts is None:
        return
    live = np.asarray(valid, bool)
    if not live.any():
        return
    t = np.asarray(ts)[live]
    sh = plan.shard_of(np.asarray(cells)[live])
    for s in np.unique(sh):
        telemetry.record_shard_watermark(int(s), int(t[sh == s].max()))


# -- range -------------------------------------------------------------------


@functools.lru_cache(maxsize=None)
def _cached_range_halo(mesh, grid_n, layers, guaranteed, approximate):
    n_shards = int(mesh.shape["data"])
    perm_r, perm_l = _perms(n_shards)

    def local(pxy, pok, pcell, qxy, qok, qcell, lqxy, lqok, lqcell,
              rqxy, rqok, rqcell, radius):
        if n_shards > 1:
            # Boundary QUERIES halo: my left neighbor's right pane and
            # my right neighbor's left pane probe my own points.
            flxy, flok, flcell = _exchange(
                (rqxy[0], rqok[0], rqcell[0]), perm_r)
            frxy, frok, frcell = _exchange(
                (lqxy[0], lqok[0], lqcell[0]), perm_l)
            q_xy = jnp.concatenate([qxy[0], flxy, frxy], axis=0)
            q_ok = jnp.concatenate([qok[0], flok, frok], axis=0)
            q_cell = jnp.concatenate([qcell[0], flcell, frcell], axis=0)
        else:
            q_xy, q_ok, q_cell = qxy[0], qok[0], qcell[0]
        keep, dist = range_partitioned_kernel(
            pxy[0], pok[0], pcell[0], q_xy, q_cell, q_ok, radius,
            grid_n=grid_n, layers=layers, guaranteed=guaranteed,
            approximate=approximate,
        )
        return keep[None], dist[None]

    fn = shard_map(
        local,
        mesh=mesh,
        in_specs=(P("data"),) * 12 + (P(),),
        out_specs=(P("data"), P("data")),
        check_vma=False,
    )
    return instrument_jit(jax.jit(fn), name="sharded:range_halo")


def sharded_range_halo(
    mesh: Mesh,
    plan: PartitionPlan,
    xy: np.ndarray,
    valid: np.ndarray,
    cell: np.ndarray,
    query_xy: np.ndarray,
    query_cell: np.ndarray,
    query_valid: np.ndarray,
    radius,
    approximate: bool = False,
    ts=None,
):
    """Grid-partitioned range query: points AND queries partition by
    cell; only boundary-cell QUERY panes halo-exchange (the query side
    is what the replicated path broadcasts whole). Bit-identical to
    ``ops/halo.py:range_partitioned_kernel`` on the full arrays.
    Returns numpy (keep, dist) in original row order."""
    n_shards = _check_plan(mesh, plan)
    xy = np.asarray(xy)
    n = xy.shape[0]
    lp = shard_layout(plan, cell, valid)
    lq = shard_layout(plan, query_cell, query_valid)
    sentinel = plan.num_cells

    def pane(index_map, src_xy, src_cell):
        return (
            gather_rows(index_map, src_xy, 0.0),
            index_map >= 0,
            gather_rows(index_map, src_cell, sentinel).astype(np.int32),
        )

    pxy, pok, pcell = pane(lp.own, xy, cell)
    qxy, qok, qcell = pane(lq.own, query_xy, query_cell)
    lqxy, lqok, lqcell = pane(lq.left, query_xy, query_cell)
    rqxy, rqok, rqcell = pane(lq.right, query_xy, query_cell)
    if faults.armed:
        faults.hit("shard.exchange")
    if n_shards > 1:
        panes = (lqxy, lqok, lqcell, rqxy, rqok, rqcell)
        telemetry.account_collective(
            "ppermute", payload_nbytes(*panes), axis="data",
            calls=len(panes),
        )
        row_bytes = 2 * xy.dtype.itemsize + 4 + 1
        telemetry.account_halo_state(lq.live_boundary_rows * row_bytes)
    _record_shard_watermarks(plan, cell, valid, ts)
    fn = _cached_range_halo(mesh, plan.grid_n, plan.layers,
                            plan.guaranteed, approximate)
    keep2, dist2 = fn(
        jnp.asarray(pxy), jnp.asarray(pok), jnp.asarray(pcell),
        jnp.asarray(qxy), jnp.asarray(qok), jnp.asarray(qcell),
        jnp.asarray(lqxy), jnp.asarray(lqok), jnp.asarray(lqcell),
        jnp.asarray(rqxy), jnp.asarray(rqok), jnp.asarray(rqcell),
        radius,
    )
    dist2 = np.asarray(dist2)
    # Unassigned rows take the kernel's no-active-pair fill — the RESULT
    # dtype's max (the program may run f32 when x64 is off).
    big = np.finfo(dist2.dtype).max
    keep = scatter_rows(lp.own, np.asarray(keep2), n, False)
    dist = scatter_rows(lp.own, dist2, n, big)
    return keep, dist


# -- join --------------------------------------------------------------------


@functools.lru_cache(maxsize=None)
def _cached_join_halo(mesh, grid_n, layers, budget):
    n_shards = int(mesh.shape["data"])
    perm_r, perm_l = _perms(n_shards)

    def local(lxy, lok, lcell, lgid, rxy, rok, rcell, rgid,
              blxy, blok, blcell, blgid, brxy, brok, brcell, brgid,
              radius):
        if n_shards > 1:
            flxy, flok, flcell, flgid = _exchange(
                (brxy[0], brok[0], brcell[0], brgid[0]), perm_r)
            frxy, frok, frcell, frgid = _exchange(
                (blxy[0], blok[0], blcell[0], blgid[0]), perm_l)
            r_xy = jnp.concatenate([rxy[0], flxy, frxy], axis=0)
            r_ok = jnp.concatenate([rok[0], flok, frok], axis=0)
            r_cell = jnp.concatenate([rcell[0], flcell, frcell], axis=0)
            r_gid = jnp.concatenate([rgid[0], flgid, frgid], axis=0)
        else:
            r_xy, r_ok, r_cell, r_gid = rxy[0], rok[0], rcell[0], rgid[0]
        li, ri, dist, count, over = join_partitioned_kernel(
            lxy[0], lok[0], lcell[0], r_xy, r_ok, r_cell, radius,
            grid_n=grid_n, layers=layers, budget=budget,
        )
        found_l = li >= 0
        found_r = ri >= 0
        lg = jnp.where(found_l, lgid[0][jnp.maximum(li, 0)], -1)
        rg = jnp.where(found_r, r_gid[jnp.maximum(ri, 0)], -1)
        return lg[None], rg[None], dist[None], count[None], over[None]

    fn = shard_map(
        local,
        mesh=mesh,
        in_specs=(P("data"),) * 16 + (P(),),
        out_specs=(P("data"),) * 5,
        check_vma=False,
    )
    return instrument_jit(jax.jit(fn), name="sharded:join_halo")


def sharded_join_halo(
    mesh: Mesh,
    plan: PartitionPlan,
    left_xy: np.ndarray,
    left_valid: np.ndarray,
    left_cell: np.ndarray,
    right_xy: np.ndarray,
    right_valid: np.ndarray,
    right_cell: np.ndarray,
    radius,
    max_pairs: int,
    ts=None,
):
    """Grid-partitioned point ⋈ point join: both sides partition by
    cell; only the RIGHT side's boundary-cell panes halo-exchange (the
    side the replicated join broadcasts whole), with global row ids
    riding the panes. Returns numpy (left_idx, right_idx, dist) of the
    found pairs canonically sorted by (left, right) — the same order the
    single-device ``ops/halo.py:join_partitioned_kernel`` pairs sort to
    — plus (count, overflow) totals."""
    n_shards = _check_plan(mesh, plan)
    left_xy = np.asarray(left_xy)
    right_xy = np.asarray(right_xy)
    ll = shard_layout(plan, left_cell, left_valid)
    lr = shard_layout(plan, right_cell, right_valid)
    sentinel = plan.num_cells

    def pane(index_map, src_xy, src_cell):
        return (
            gather_rows(index_map, src_xy, 0.0),
            index_map >= 0,
            gather_rows(index_map, src_cell, sentinel).astype(np.int32),
            index_map.astype(np.int32),  # global row id (−1 padding)
        )

    lp = pane(ll.own, left_xy, left_cell)
    rp = pane(lr.own, right_xy, right_cell)
    blp = pane(lr.left, right_xy, right_cell)
    brp = pane(lr.right, right_xy, right_cell)
    if faults.armed:
        faults.hit("shard.exchange")
    if n_shards > 1:
        telemetry.account_collective(
            "ppermute", payload_nbytes(*(blp + brp)), axis="data",
            calls=len(blp + brp),
        )
        row_bytes = 2 * right_xy.dtype.itemsize + 4 + 1 + 4
        telemetry.account_halo_state(lr.live_boundary_rows * row_bytes)
    _record_shard_watermarks(plan, left_cell, left_valid, ts)
    budget = int(max_pairs)
    fn = _cached_join_halo(mesh, plan.grid_n, plan.layers, budget)
    out = fn(*(jnp.asarray(a) for a in lp + rp + blp + brp),
             radius)
    lg, rg, dist, count, over = (np.asarray(o) for o in out)
    found = lg.reshape(-1) >= 0
    li = lg.reshape(-1)[found]
    ri = rg.reshape(-1)[found]
    dv = dist.reshape(-1)[found]
    order = np.lexsort((ri, li))
    return (
        li[order], ri[order], dv[order],
        int(count.sum()), int(over.sum()),
    )


def sharded_tjoin_panes_halo(
    mesh: Mesh,
    plan: PartitionPlan,
    ts,
    left_panes,
    right_panes,
    radius,
    ppw: int,
    max_pairs: int,
):
    """Grid-partitioned tjoin pane scan: per slide, the sliding window
    (last ``ppw`` panes per side) joins via :func:`sharded_join_halo` —
    boundary panes halo-exchange instead of the replicated scan's
    all-gather of every pane field. ``left_panes``/``right_panes`` are
    sequences of ``(xy, valid, cell)`` host pane arrays, ``ts`` the
    per-slide window-end times (feeds the per-shard watermark gauges).
    Returns the per-slide list of ``sharded_join_halo`` results."""
    ts = np.asarray(ts)
    results = []
    for i in range(ts.shape[0]):
        lo = max(0, i - int(ppw) + 1)
        lxy, lok, lcell = (
            np.concatenate([p[j] for p in left_panes[lo: i + 1]], axis=0)
            for j in range(3)
        )
        rxy, rok, rcell = (
            np.concatenate([p[j] for p in right_panes[lo: i + 1]], axis=0)
            for j in range(3)
        )
        slide_ts = np.full(lcell.shape[0], int(ts[i]), np.int64)
        results.append(sharded_join_halo(
            mesh, plan, lxy, lok, lcell, rxy, rok, rcell, radius,
            max_pairs, ts=slide_ts,
        ))
    return results


# -- registry bucket (qserve) ------------------------------------------------


@functools.lru_cache(maxsize=None)
def _cached_registry_halo(mesh, grid_n, layers, k, num_segments):
    n_shards = int(mesh.shape["data"])
    perm_r, perm_l = _perms(n_shards)

    def local(pxy, pok, pcell, poid, bl_xy, bl_ok, bl_cell, bl_oid,
              br_xy, br_ok, br_cell, br_oid, qxy, qok, qcell, rad):
        if n_shards > 1:
            flxy, flok, flcell, floid = _exchange(
                (br_xy[0], br_ok[0], br_cell[0], br_oid[0]), perm_r)
            frxy, frok, frcell, froid = _exchange(
                (bl_xy[0], bl_ok[0], bl_cell[0], bl_oid[0]), perm_l)
            p_xy = jnp.concatenate([pxy[0], flxy, frxy], axis=0)
            p_ok = jnp.concatenate([pok[0], flok, frok], axis=0)
            p_cell = jnp.concatenate([pcell[0], flcell, frcell], axis=0)
            p_oid = jnp.concatenate([poid[0], floid, froid], axis=0)
        else:
            p_xy, p_ok, p_cell, p_oid = pxy[0], pok[0], pcell[0], poid[0]
        dist, segment, num_valid, within = \
            registry_bucket_partitioned_kernel(
                p_xy, p_ok, p_cell, p_oid, qxy[0], qcell[0], rad[0],
                qok[0], grid_n=grid_n, layers=layers, k=k,
                num_segments=num_segments,
            )
        return dist[None], segment[None], num_valid[None], within[None]

    fn = shard_map(
        local,
        mesh=mesh,
        in_specs=(P("data"),) * 16,
        out_specs=(P("data"),) * 4,
        check_vma=False,
    )
    return instrument_jit(jax.jit(fn), name="sharded:registry_halo")


def sharded_registry_bucket_halo(
    mesh: Mesh,
    plan: PartitionPlan,
    xy: np.ndarray,
    valid: np.ndarray,
    cell: np.ndarray,
    oid: np.ndarray,
    query_xy: np.ndarray,
    query_cell: np.ndarray,
    radius: np.ndarray,
    query_valid: np.ndarray,
    k: int,
    num_segments: int,
):
    """Grid-partitioned standing-query bucket (qserve): QUERIES partition
    by cell (per-query output ownership), and the POINT side's
    boundary-cell panes halo-exchange so each query is answered entirely
    on its owner shard — replacing the replicated bucket's broadcast of
    the whole standing bucket + per-query flag tables AND its per-lane
    pmin reduction. ``plan`` must be built for the bucket's radius-class
    ceiling (qserve's radius-class bucketing gives one static halo width
    per bucket). Bit-identical to
    ``ops/halo.py:registry_bucket_partitioned_kernel`` on the full
    arrays; returns numpy (dist (Q, k), segment (Q, k), num_valid (Q,),
    within (Q,)) in original query order."""
    n_shards = _check_plan(mesh, plan)
    xy = np.asarray(xy)
    q = np.asarray(query_xy).shape[0]
    lp = shard_layout(plan, cell, valid)
    lq = shard_layout(plan, query_cell, query_valid)
    sentinel = plan.num_cells

    def ppane(index_map):
        return (
            gather_rows(index_map, xy, 0.0),
            index_map >= 0,
            gather_rows(index_map, cell, sentinel).astype(np.int32),
            gather_rows(index_map, oid, 0).astype(np.int32),
        )

    pp = ppane(lp.own)
    blp = ppane(lp.left)
    brp = ppane(lp.right)
    qp = (
        gather_rows(lq.own, query_xy, 0.0),
        lq.own >= 0,
        gather_rows(lq.own, query_cell, sentinel).astype(np.int32),
        gather_rows(lq.own, radius, 0.0),
    )
    if faults.armed:
        faults.hit("shard.exchange")
    if n_shards > 1:
        telemetry.account_collective(
            "ppermute", payload_nbytes(*(blp + brp)), axis="data",
            calls=len(blp + brp),
        )
        row_bytes = 2 * xy.dtype.itemsize + 4 + 1 + 4
        telemetry.account_halo_state(lp.live_boundary_rows * row_bytes)
    fn = _cached_registry_halo(mesh, plan.grid_n, plan.layers, int(k),
                               int(num_segments))
    dist2, seg2, nv2, win2 = fn(
        *(jnp.asarray(a) for a in pp + blp + brp),
        jnp.asarray(qp[0]), jnp.asarray(qp[1]), jnp.asarray(qp[2]),
        jnp.asarray(qp[3]),
    )
    dist2 = np.asarray(dist2)
    big = np.finfo(dist2.dtype).max
    return (
        scatter_rows(lq.own, dist2, q, big),
        scatter_rows(lq.own, np.asarray(seg2), q, -1),
        scatter_rows(lq.own, np.asarray(nv2), q, 0),
        scatter_rows(lq.own, np.asarray(win2), q, 0),
    )
