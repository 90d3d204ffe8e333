"""End-to-end operator execution on the 8-device CPU mesh.

The sharded kernels alone are not enough: operators must run on a
mesh. These tests drive the OPERATOR layer (windows → batches →
shard_mapped kernels → decoded results) with ``mesh=`` and require results
identical to the single-device run — the framework analog of the
reference's parallelism default (StreamingJob.java:177,
conf/geoflink-conf.yml:55) with semantics unchanged.

Shapes are ≥100k points for the point-stream paths so shard boundaries,
bucket padding, and the pmin/top-k collectives are exercised at realistic
sizes, not toys.
"""

import numpy as np
import pytest
import jax
from jax.sharding import Mesh

from spatialflink_tpu.grid import UniformGrid
from spatialflink_tpu.models.objects import LineString, Point, Polygon
from spatialflink_tpu.operators import (
    PointPointJoinQuery,
    PointPointKNNQuery,
    PointPointRangeQuery,
    PolygonPointKNNQuery,
    PolygonPointRangeQuery,
    QueryConfiguration,
    QueryType,
)
from spatialflink_tpu.operators.trajectory import TStatsQuery

GRID = UniformGrid(20, 0.0, 10.0, 0.0, 10.0)
W = QueryConfiguration(QueryType.WindowBased, window_size=10, slide_step=10)


@pytest.fixture(scope="module")
def mesh():
    devs = np.array(jax.devices())
    assert devs.size == 8, "conftest must provide 8 virtual CPU devices"
    return Mesh(devs.reshape(8), ("data",))


def _points(rng, n, n_obj=512, t_span=10_000):
    xy = rng.uniform(0, 10, (n, 2))
    return [
        Point(obj_id=f"d{i % n_obj}", timestamp=int(i * t_span / n),
              x=float(xy[i, 0]), y=float(xy[i, 1]))
        for i in range(n)
    ]


def test_range_operator_mesh_matches_single(rng, mesh):
    pts = _points(rng, 120_000)
    q = Point(x=5.0, y=5.0)
    r = 0.5

    def run(m):
        return [
            (res.start, res.end,
             [(o.obj_id, o.timestamp) for o in res.objects],
             res.dists.tolist())
            for res in PointPointRangeQuery(W, GRID).run(
                iter(list(pts)), [q], r, mesh=m)
        ]

    single = run(None)
    sharded = run(mesh)
    assert sharded == single
    assert sum(len(s[2]) for s in single) > 0


def test_knn_operator_mesh_bit_matches_single(rng, mesh):
    pts = _points(rng, 120_000)
    q = Point(x=5.0, y=5.0)

    def run(m):
        op = PointPointKNNQuery(W, GRID, mesh=m)  # mesh via constructor
        return [
            (res.start, res.end,
             [(oid, d, obj.obj_id, obj.timestamp)
              for oid, d, obj in res.neighbors])
            for res in op.run(iter(list(pts)), q, 2.0, 50)
        ]

    single = run(None)
    sharded = run(mesh)
    assert sharded == single  # bit-identical incl. tie-breaks
    assert all(len(w[2]) == 50 for w in single)


@pytest.mark.slow
def test_join_operator_mesh_matches_single(rng, mesh):
    # Finer grid so neither side exceeds the per-cell cap (overflow 0 →
    # both the compact single-device path and the dense sharded path are
    # exact and must agree).
    grid_j = UniformGrid(64, 0.0, 10.0, 0.0, 10.0)
    left = _points(rng, 100_000)
    rxy = np.random.default_rng(5).uniform(0, 10, (4_000, 2))
    right = [
        Point(obj_id=f"q{i}", timestamp=int(i * 10_000 / 4_000),
              x=float(rxy[i, 0]), y=float(rxy[i, 1]))
        for i in range(4_000)
    ]
    r = 0.05

    def run(m):
        out = []
        for res in PointPointJoinQuery(W, grid_j, mesh=m).run(
            iter(list(left)), iter(list(right)), r
        ):
            assert res.overflow == 0
            out.append((
                res.start, res.end,
                sorted((a.obj_id, a.timestamp, b.obj_id, round(d, 12))
                       for a, b, d in res.pairs),
            ))
        return out

    single = run(None)
    sharded = run(mesh)
    # Same pair sets; the compact (single) and dense-sharded paths emit in
    # different orders, hence the sort.
    assert len(sharded) == len(single)
    for s, g in zip(single, sharded):
        assert s[0] == g[0] and s[1] == g[1]
        assert s[2] == g[2]
    assert sum(len(s[2]) for s in single) > 100


def test_tstats_operator_mesh_matches_single(rng, mesh):
    pts = _points(rng, 100_000, n_obj=256)

    def run(m):
        return [
            (res.start, res.end, res.stats)
            for res in TStatsQuery(W, GRID, mesh=m).run(iter(list(pts)))
        ]

    single = run(None)
    sharded = run(mesh)
    assert len(sharded) == len(single)
    for s, g in zip(single, sharded):
        assert s[0] == g[0] and s[1] == g[1]
        assert s[2].keys() == g[2].keys()
        for k in s[2]:
            np.testing.assert_allclose(g[2][k], s[2][k], rtol=1e-12)


def test_streaming_job_device_mesh_config(tmp_path, mesh):
    """yml deviceMesh: [8] → run_job executes on the mesh, output identical
    to single-device (the config seam for conf/geoflink-conf.yml:55)."""
    from spatialflink_tpu.streaming_job import main

    def run(device_mesh):
        conf = tmp_path / f"conf{device_mesh}.yml"
        conf.write_text(f"""
inputStream1:
  topicName: t
  format: CSV
  csvTsvSchemaAttr: [0, 1, 2, 3]
  gridBBox: [0.0, 0.0, 10.0, 10.0]
  numGridCells: 20
  delimiter: ","
query:
  option: 1
  radius: 2.0
  k: 3
  queryPoints:
    - [5.0, 5.0]
window:
  type: "TIME"
  interval: 10
  step: 10
deviceMesh: [{device_mesh}]
""")
        csv = tmp_path / "in.csv"
        rng2 = np.random.default_rng(9)
        rows = [
            f"dev{i % 5},{i * 300},{rng2.uniform(0, 10)},{rng2.uniform(0, 10)}"
            for i in range(500)
        ]
        csv.write_text("\n".join(rows))
        out = tmp_path / f"out{device_mesh}.csv"
        rc = main(["--config", str(conf), "--source", f"csv:{csv}",
                   "--output", str(out)])
        assert rc == 0
        return out.read_text()

    assert run(8) == run(1)


def test_geometry_stream_operators_mesh(rng, mesh):
    """Geometry-stream range + kNN on the mesh (object-axis sharding)."""
    polys = []
    for i in range(500):
        cx, cy = rng.uniform(1, 9), rng.uniform(1, 9)
        s = 0.25
        polys.append(Polygon(
            obj_id=f"z{i}", timestamp=i * 20,
            rings=[np.array([[cx - s, cy - s], [cx + s, cy - s],
                             [cx + s, cy + s], [cx - s, cy + s],
                             [cx - s, cy - s]])],
        ))
    q = Point(x=5.0, y=5.0)

    def run_range(m):
        return [
            (res.start, res.end,
             sorted((o.obj_id, round(d, 12))
                    for o, d in zip(res.objects, res.dists)))
            for res in PolygonPointRangeQuery(W, GRID).run(
                iter(list(polys)), [q], 1.5, mesh=m)
        ]

    assert run_range(mesh) == run_range(None)

    def run_knn(m):
        return [
            (res.start, res.end,
             [(oid, d, obj.obj_id) for oid, d, obj in res.neighbors])
            for res in PolygonPointKNNQuery(W, GRID).run(
                iter(list(polys)), q, 5.0, 10, mesh=m)
        ]

    assert run_knn(mesh) == run_knn(None)


def test_trange_operator_mesh_matches_single(rng, mesh):
    from spatialflink_tpu.operators import TRangeQuery

    pts = _points(rng, 100_000, n_obj=256)
    polys = [
        Polygon(rings=[np.array([[3, 3], [4.5, 3], [4.5, 4.5], [3, 4.5],
                                 [3, 3]], float)]),
        Polygon(rings=[np.array([[6, 6], [8, 6], [8, 8], [6, 8],
                                 [6, 6]], float)]),
    ]

    def run(m):
        return [
            (res.start, res.end,
             sorted(t.obj_id for t in res.trajectories))
            for res in TRangeQuery(W, GRID).run(iter(pts), polys, mesh=m)
        ]

    assert run(None) == run(mesh)


def test_tknn_operator_mesh_matches_single(rng, mesh):
    from spatialflink_tpu.operators import TKNNQuery

    pts = _points(rng, 100_000, n_obj=256)
    q = Point(x=5.0, y=5.0)

    def run(m):
        return [
            (res.start, res.end,
             [(o, round(d, 12)) for o, d, _ in res.neighbors])
            for res in TKNNQuery(W, GRID).run(iter(pts), q, 2.0, 7, mesh=m)
        ]

    assert run(None) == run(mesh)


def test_taggregate_operator_mesh_matches_single(rng, mesh):
    from spatialflink_tpu.operators import TAggregateQuery

    pts = _points(rng, 100_000, n_obj=128)

    def run(m):
        out = []
        for res in TAggregateQuery(W, GRID, aggregate="SUM").run(
            iter(pts), mesh=m
        ):
            out.append((res.start, res.end, sorted(res.cells.items())))
        return out

    assert run(None) == run(mesh)


@pytest.mark.slow
def test_tjoin_operator_mesh_matches_single(rng, mesh):
    from spatialflink_tpu.operators import TJoinQuery

    left = _points(rng, 60_000, n_obj=64)
    right = [
        Point(obj_id=f"q{i % 48}", timestamp=int(i * 10_000 / 40_000),
              x=float(rng.uniform(0, 10)), y=float(rng.uniform(0, 10)))
        for i in range(40_000)
    ]

    def run(m):
        return [
            (res.start, res.end,
             sorted((a.obj_id, b.obj_id, round(d, 12))
                    for a, b, d in res.pairs))
            # cap=256 > the ~150 points/cell of this density: the cap/
            # overflow contract (per-shard caps) only guarantees parity
            # when no cell overflows.
            for res in TJoinQuery(W, GRID, cap=256, mesh=m).run(
                iter(left), iter(right), 0.05
            )
        ]

    assert run(None) == run(mesh)


def test_run_multi_mesh_matches_single(rng, mesh):
    """run_multi on a 1-D data mesh (replicated queries) must produce the
    same per-query winner lists as single-device (distances to 1 ulp)."""
    pts = _points(rng, 80_000, n_obj=256)
    queries = [Point(x=2.0, y=2.0), Point(x=5.0, y=5.0), Point(x=8.0, y=7.0)]

    def run(m):
        return [
            (res.start, res.end,
             [[(o, round(d, 12)) for o, d, _ in r.neighbors]
              for r in res.results])
            for res in PointPointKNNQuery(W, GRID).run_multi(
                iter(pts), queries, 1.5, 6, mesh=m
            )
        ]

    assert run(None) == run(mesh)


def test_tstats_pane_engine_mesh_bit_matches_single(rng, mesh):
    """The device tStats pane engine on the 8-device
    mesh (trajectory-parallel oid blocks,
    parallel/sharded.py:sharded_traj_stats_pane) must be BIT-identical
    to the single-device kernel at x64 — not the dryrun's f32
    tolerance. Driven through the product path
    (streams/panes.py:traj_stats_sliding(mesh=))."""
    from spatialflink_tpu.streams.panes import traj_stats_sliding

    n, n_obj = 60_000, 64  # 8 oids per shard
    ts = np.sort(rng.integers(0, 30_000, n)).astype(np.int64)
    xy = rng.uniform(0, 10, (n, 2))
    oid = rng.integers(0, n_obj, n).astype(np.int64)

    single = traj_stats_sliding(ts, xy, oid, n_obj, 10_000, 100,
                                backend="device")
    meshed = traj_stats_sliding(ts, xy, oid, n_obj, 10_000, 100,
                                backend="device", mesh=mesh)
    np.testing.assert_array_equal(single.starts, meshed.starts)
    np.testing.assert_array_equal(single.spatial, meshed.spatial)
    np.testing.assert_array_equal(single.temporal, meshed.temporal)
    np.testing.assert_array_equal(single.count, meshed.count)
    assert single.spatial.any(), "degenerate: no spatial sums"
    # ... and the device result matches the host oracle at the engine's
    # documented tolerance (segment_sum associates float adds in a
    # different order than bincount — test_panes.py pins 1e-12 relative;
    # ints exact).
    host = traj_stats_sliding(ts, xy, oid, n_obj, 10_000, 100,
                              backend="numpy")
    np.testing.assert_array_equal(host.count, meshed.count)
    np.testing.assert_array_equal(host.temporal, meshed.temporal)
    assert np.allclose(host.spatial, meshed.spatial, rtol=1e-12,
                       atol=5e-12)


def test_tstats_pane_mesh_rejects_bad_config(rng, mesh):
    from spatialflink_tpu.streams.panes import traj_stats_sliding

    ts = np.arange(100, dtype=np.int64)
    xy = np.zeros((100, 2))
    oid = np.zeros(100, np.int64)
    with pytest.raises(ValueError, match="divide"):
        traj_stats_sliding(ts, xy, oid, 12, 1_000, 100,
                           backend="device", mesh=mesh)
    with pytest.raises(ValueError, match="device backend"):
        traj_stats_sliding(ts, xy, oid, 16, 1_000, 100,
                           backend="numpy", mesh=mesh)


def test_tjoin_pane_engine_mesh_bit_matches_single(rng, mesh):
    """The pane-carry tJoin engine on the
    8-device mesh (probe-parallel points, replicated window/digest
    state, all-gathered contributions — ops/tjoin_panes.py) must be
    BIT-identical to single-device at x64, through the operator path."""
    from spatialflink_tpu.operators.trajectory import TJoinQuery

    conf = QueryConfiguration(QueryType.WindowBased, window_size=1,
                              slide_step=0.1)
    n, n_obj = 4_000, 16

    def mk(shift):
        ts = np.sort(rng.integers(0, 4_000, n)).astype(np.int64)
        return {
            "ts": ts,
            "x": rng.uniform(2 + shift, 8 + shift, n),
            "y": rng.uniform(2, 8, n),
            "oid": rng.integers(0, n_obj, n).astype(np.int32),
        }

    left, right = mk(0.0), mk(0.2)

    def run(m, **kw):
        return [
            (s, e, list(map(int, lo)), list(map(int, ro)),
             [float(d) for d in dd], c, ov)
            for s, e, lo, ro, dd, c, ov in TJoinQuery(conf, GRID).run_soa_panes(
                iter([dict(left)]), iter([dict(right)]), 0.4,
                num_segments=n_obj, mesh=m, backend="device", **kw,
            )  # backend forced: auto would route the mesh-less run to
        ]  # the NATIVE engine (1e-12, not bit, vs the device scan)

    single = run(None)
    meshed = run(mesh)
    assert single == meshed  # exact — incl. every float distance bit
    assert sum(len(r[2]) for r in single) > 0, "degenerate: no pairs"
    # Compaction commutes with sharding: the live-slot compacted scan
    # (auto bucket — the default above on CPU) under the mesh must also
    # bit-match the FULL-RING probe single-device — replicated live
    # counts + positional heads shard-invariantly reproduce the legacy
    # candidate sets.
    full_ring_single = run(None, cap_c=0)
    assert full_ring_single == meshed
