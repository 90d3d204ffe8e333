"""Point-in-polygon and point↔polygon distance kernels.

The reference delegates polygon predicates to JTS
(``point.distance(polygon)`` — DistanceFunctions.java:33-36 — returns 0 for
interior points, else the min boundary distance; containment via
PreparedGeometry in the SNCB layer, CRSUtils.java:19-56). Here polygons are
packed once on the host into padded edge arrays and both predicates are
single fused XLA ops over a point batch.

Packed polygon layout (see ``pack_rings``):
  - ``verts``: (V, 2) vertex array; rings are laid out back to back, each
    ring closed (first vertex repeated last).
  - ``edge_valid``: (V-1,) bool — True for real ring edges, False for the
    seam between consecutive rings and for padding.
Holes need no special casing: even-odd crossing counting over all rings
(exterior + holes) is the standard ray-cast containment with holes.
"""

from __future__ import annotations

from typing import Sequence, Tuple

import numpy as np
import jax.numpy as jnp

from spatialflink_tpu.ops.distances import point_polyline_distance


def pack_rings(
    rings: Sequence[np.ndarray], pad_to: int | None = None, dtype=np.float64
) -> Tuple[np.ndarray, np.ndarray]:
    """Pack polygon rings (or polyline parts) into (verts, edge_valid).

    Each ring is an (Ri, 2) array; rings are closed here if not already.
    Padding vertices repeat the last real vertex with ``edge_valid`` False,
    so padded shapes never change results.
    """
    closed = []
    for r in rings:
        r = np.asarray(r, dtype=dtype)
        if r.ndim != 2 or r.shape[1] != 2:
            raise ValueError("each ring must be (R, 2)")
        if not np.array_equal(r[0], r[-1]):
            r = np.concatenate([r, r[:1]], axis=0)
        closed.append(r)
    verts = np.concatenate(closed, axis=0)
    edge_valid = np.ones(len(verts) - 1, bool)
    # Invalidate seam edges between consecutive rings.
    pos = 0
    for r in closed[:-1]:
        pos += len(r)
        edge_valid[pos - 1] = False
    if pad_to is not None:
        if pad_to < len(verts):
            raise ValueError(f"pad_to={pad_to} < {len(verts)} vertices")
        pad = pad_to - len(verts)
        if pad:
            verts = np.concatenate([verts, np.repeat(verts[-1:], pad, axis=0)])
            edge_valid = np.concatenate([edge_valid, np.zeros(pad, bool)])
    return verts, edge_valid


def pack_polyline(
    parts: Sequence[np.ndarray], pad_to: int | None = None, dtype=np.float64
) -> Tuple[np.ndarray, np.ndarray]:
    """Pack open polyline part(s) into (verts, edge_valid) — no closing."""
    parts = [np.asarray(p, dtype=dtype) for p in parts]
    verts = np.concatenate(parts, axis=0)
    edge_valid = np.ones(len(verts) - 1, bool)
    pos = 0
    for p in parts[:-1]:
        pos += len(p)
        edge_valid[pos - 1] = False
    if pad_to is not None:
        if pad_to < len(verts):
            raise ValueError(f"pad_to={pad_to} < {len(verts)} vertices")
        pad = pad_to - len(verts)
        if pad:
            verts = np.concatenate([verts, np.repeat(verts[-1:], pad, axis=0)])
            edge_valid = np.concatenate([edge_valid, np.zeros(pad, bool)])
    return verts, edge_valid


def ray_crosses(x, y, x1, y1, x2, y2) -> jnp.ndarray:
    """Does the +x ray from (x, y) cross the edge (x1, y1)–(x2, y2)?
    Coordinate planes of any broadcastable shapes. The half-open vertical
    span test avoids double-counting shared vertices; a horizontal edge
    (and a degenerate one) is never crossed."""
    spans = (y1 > y) != (y2 > y)
    dy = y2 - y1
    t = jnp.where(dy != 0, (y - y1) / jnp.where(dy != 0, dy, 1), 0.0)
    x_int = x1 + t * (x2 - x1)
    return spans & (x < x_int)


def points_in_polygon(
    p: jnp.ndarray, verts: jnp.ndarray, edge_valid: jnp.ndarray
) -> jnp.ndarray:
    """Even-odd ray-cast containment for a batch of points.

    ``p``: (N, 2) → (N,) bool. Counts crossings of a +x ray against every
    valid edge of every ring; an odd count means inside (holes subtract
    naturally). Points exactly on a boundary edge may land either way, same
    as JTS's non-boundary-inclusive ``contains``.
    """
    x, y = p[:, 0:1], p[:, 1:2]  # (N, 1)
    x1, y1 = verts[:-1, 0][None, :], verts[:-1, 1][None, :]  # (1, E)
    x2, y2 = verts[1:, 0][None, :], verts[1:, 1][None, :]
    crossings = ray_crosses(x, y, x1, y1, x2, y2) & edge_valid[None, :]
    return jnp.sum(crossings.astype(jnp.int32), axis=1) % 2 == 1


def point_polygon_distance(
    p: jnp.ndarray, verts: jnp.ndarray, edge_valid: jnp.ndarray
) -> jnp.ndarray:
    """JTS-compatible point→polygon distance: 0 inside, else min edge dist.

    Batched replacement for ``point.distance(polygon)``
    (DistanceFunctions.java:33-36) — the hot op of PointPolygonRangeQuery's
    window loop (range/PointPolygonRangeQuery.java:37-101).
    """
    inside = points_in_polygon(p, verts, edge_valid)
    d = point_polyline_distance(p, verts, edge_valid)
    return jnp.where(inside, jnp.zeros((), d.dtype), d)


def signed_area(ring: np.ndarray) -> float:
    """Shoelace signed area of a host-side ring (CCW positive)."""
    r = np.asarray(ring, np.float64)  # sfcheck: ok=trace-hygiene -- host-side geometry prep (docstring); rings are concrete numpy, never traced
    x, y = r[:, 0], r[:, 1]
    return 0.5 * float(np.sum(x * np.roll(y, -1) - np.roll(x, -1) * y))
