"""Coverage for operator classes and stream paths not exercised elsewhere:
geometry-stream kNN, linestring range variants, socket source, CLI options."""

import socket
import threading

import numpy as np
import pytest

from spatialflink_tpu.grid import UniformGrid
from spatialflink_tpu.models.objects import LineString, Point, Polygon
from spatialflink_tpu.operators import (
    LineStringLineStringRangeQuery,
    LineStringPointKNNQuery,
    PointLineStringRangeQuery,
    PolygonPointKNNQuery,
    QueryConfiguration,
    QueryType,
)
from spatialflink_tpu.streams.serde import parse_csv_point
from spatialflink_tpu.streams.sources import socket_source

GRID = UniformGrid(20, 0.0, 10.0, 0.0, 10.0)
W30 = QueryConfiguration(QueryType.WindowBased, window_size=30, slide_step=30)


def _squares(rng, n, size=0.4):
    out = []
    for i in range(n):
        cx, cy = rng.uniform(1, 9), rng.uniform(1, 9)
        out.append(Polygon(
            obj_id=f"poly{i}", timestamp=i * 100,
            rings=[np.array([[cx - size, cy - size], [cx + size, cy - size],
                             [cx + size, cy + size], [cx - size, cy + size],
                             [cx - size, cy - size]])],
        ))
    return out


def _jts_point_poly_dist(q, ring):
    """JTS point.distance(polygon): 0 inside, else min edge distance."""
    verts = np.vstack([ring])
    seg_min = np.inf
    for a, b in zip(verts[:-1], verts[1:]):
        ab = b - a
        t = np.clip(np.dot(q - a, ab) / np.dot(ab, ab), 0, 1)
        seg_min = min(seg_min, float(np.linalg.norm(a + t * ab - q)))
    # Even-odd point-in-polygon.
    inside = False
    for a, b in zip(verts[:-1], verts[1:]):
        if (a[1] > q[1]) != (b[1] > q[1]):
            xcross = a[0] + (q[1] - a[1]) / (b[1] - a[1]) * (b[0] - a[0])
            if q[0] < xcross:
                inside = not inside
    return 0.0 if inside else seg_min


def test_polygon_stream_knn(rng):
    """PolygonPointKNNQuery: JTS getDistance semantics (0 inside)."""
    polys = _squares(rng, 30)
    q = Point(x=5.0, y=5.0)
    results = list(PolygonPointKNNQuery(W30, GRID).run(iter(polys), q, 6.0, 5))
    assert results
    res = results[0]
    assert 1 <= len(res.neighbors) <= 5
    dists = [d for _, d, _ in res.neighbors]
    assert dists == sorted(dists)
    for oid, d, obj in res.neighbors:
        assert d == pytest.approx(
            _jts_point_poly_dist(np.array([5.0, 5.0]), obj.rings[0]), abs=1e-9
        )


def test_polygon_stream_knn_containment_is_zero(rng):
    """A polygon containing the query point ranks first with distance 0
    (JTS point.distance(polygon) == 0 inside — DistanceFunctions.java:15-54
    via getDistance)."""
    polys = _squares(rng, 10, size=0.3)
    polys.append(Polygon(
        obj_id="around", timestamp=0,
        rings=[np.array([[4.0, 4.0], [6.0, 4.0], [6.0, 6.0],
                         [4.0, 6.0], [4.0, 4.0]])],
    ))
    q = Point(x=5.0, y=5.0)
    results = list(PolygonPointKNNQuery(W30, GRID).run(iter(polys), q, 6.0, 3))
    top = results[0].neighbors[0]
    assert top[0] == "around"
    assert top[1] == 0.0


def test_linestring_stream_knn(rng):
    lines = [
        LineString(obj_id=f"ls{i}", timestamp=i * 100,
                   coords=np.array([[i * 0.3, 0.0], [i * 0.3, 10.0]]))
        for i in range(20)
    ]
    q = Point(x=5.0, y=5.0)
    results = list(LineStringPointKNNQuery(W30, GRID).run(iter(lines), q, 8.0, 3))
    res = results[0]
    # Vertical lines at x = 0.3i; nearest to x=5 are i=17 (x=5.1), i=16 (4.8)...
    got = [oid for oid, _, _ in res.neighbors]
    dists = {oid: abs(0.3 * i - 5.0) for i, oid in enumerate(f"ls{i}" for i in range(20))}
    expect = sorted(dists, key=dists.get)[:3]
    assert got == expect


def test_point_linestring_range(rng):
    pts = [Point(obj_id=f"p{i}", timestamp=i * 100,
                 x=float(rng.uniform(0, 10)), y=float(rng.uniform(0, 10)))
           for i in range(300)]
    ls = LineString(coords=np.array([[0.0, 5.0], [10.0, 5.0]]))  # horizontal
    results = list(PointLineStringRangeQuery(W30, GRID).run(iter(pts), [ls], 0.5))
    got = {p.obj_id for r in results for p in r.objects}
    expect = {p.obj_id for p in pts if abs(p.y - 5.0) <= 0.5}
    assert got == expect


def test_linestring_linestring_range(rng):
    lines = [
        LineString(obj_id=f"ls{i}", timestamp=i * 100,
                   coords=np.array([[1.0 + i * 0.5, 1.0], [1.0 + i * 0.5, 2.0]]))
        for i in range(10)
    ]
    q = LineString(coords=np.array([[3.0, 0.0], [3.0, 9.0]]))
    results = list(
        LineStringLineStringRangeQuery(W30, GRID).run(iter(lines), [q], 0.6)
    )
    got = {l.obj_id for r in results for l in r.objects}
    # Lines at x = 1 + 0.5i within 0.6 of x=3: i in {3, 4, 5, 6, 7} →
    # x ∈ {2.5, 3.0, 3.5} within; 2.5 and 3.5 are at exactly 0.5 ≤ 0.6.
    expect = {f"ls{i}" for i in range(10) if abs(1.0 + 0.5 * i - 3.0) <= 0.6}
    assert got == expect


def test_socket_source_loopback():
    """socket_source against a live loopback server."""
    server = socket.socket(socket.AF_INET, socket.SOCK_STREAM)
    server.bind(("127.0.0.1", 0))
    server.listen(1)
    port = server.getsockname()[1]

    def serve():
        conn, _ = server.accept()
        conn.sendall(b"a,100,1.0,2.0\nGARBAGE\nb,200,3.0,4.0\n")
        conn.close()

    t = threading.Thread(target=serve, daemon=True)
    t.start()
    pts = list(socket_source("127.0.0.1", port,
                             lambda ln: parse_csv_point(ln, schema=[0, 1, 2, 3])))
    t.join(timeout=5)
    server.close()
    assert [(p.obj_id, p.x) for p in pts] == [("a", 1.0), ("b", 3.0)]


@pytest.mark.slow
def test_streaming_job_remaining_options(tmp_path):
    """CLI options 2 (realtime range), 5 (join), 7 (tAggregate),
    8 (multi-query kNN)."""
    from spatialflink_tpu.streaming_job import main

    base = """
inputStream1:
  topicName: t
  format: CSV
  csvTsvSchemaAttr: [0, 1, 2, 3]
  gridBBox: [0.0, 0.0, 10.0, 10.0]
  numGridCells: 20
  delimiter: ","
query:
  option: {opt}
  radius: 3.0
  k: 2
  aggregateFunction: "SUM"
  queryPoints:
    - [5.0, 5.0]
    - [4.5, 5.2]
window:
  type: "TIME"
  interval: 10
  step: 10
"""
    csv = tmp_path / "in.csv"
    # Option 5 splits the stream into halves; keep both halves in the same
    # time range (each half internally sorted) so join windows overlap.
    csv.write_text("\n".join(
        f"dev{i%3},{(i % 40) * 250},{4 + 0.02*(i % 40)},{5 + 0.01*(i % 40)}"
        for i in range(80)
    ))
    for opt in (2, 5, 7, 8):
        conf = tmp_path / f"c{opt}.yml"
        conf.write_text(base.format(opt=opt))
        out = tmp_path / f"o{opt}.csv"
        rc = main(["--config", str(conf), "--source", f"csv:{csv}",
                   "--output", str(out)])
        assert rc == 0
        assert out.read_text().strip(), f"option {opt} produced no output"


@pytest.mark.slow
def test_streaming_job_incremental_flag_matches_full(tmp_path):
    """query.incremental: true routes options 1/3/5 through the carry
    paths; CLI output must equal the full-recompute run line for line
    (order-insensitive for the join's block-major ordering)."""
    from spatialflink_tpu.streaming_job import main

    base = """
inputStream1:
  topicName: t
  format: CSV
  csvTsvSchemaAttr: [0, 1, 2, 3]
  gridBBox: [0.0, 0.0, 10.0, 10.0]
  numGridCells: 20
  delimiter: ","
query:
  option: {opt}
  radius: 3.0
  k: 4
  incremental: {inc}
  aggregateFunction: "SUM"
  queryPoints:
    - [5.0, 5.0]
window:
  type: "TIME"
  interval: 10
  step: 5
"""
    csv = tmp_path / "in.csv"
    csv.write_text("\n".join(
        f"dev{i%5},{i * 120},{3 + 0.05*(i % 60)},{4 + 0.03*(i % 60)}"
        for i in range(160)
    ))
    for opt in (1, 3, 5):
        outs = {}
        for inc in ("false", "true"):
            conf = tmp_path / f"c{opt}_{inc}.yml"
            conf.write_text(base.format(opt=opt, inc=inc))
            out = tmp_path / f"o{opt}_{inc}.csv"
            rc = main(["--config", str(conf), "--source", f"csv:{csv}",
                       "--output", str(out)])
            assert rc == 0
            outs[inc] = sorted(out.read_text().strip().splitlines())
        assert outs["false"] == outs["true"], f"option {opt}"
        assert outs["true"], f"option {opt} produced no output"
