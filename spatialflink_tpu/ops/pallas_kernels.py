"""Hand-written Pallas TPU kernels for the hot geometry ops.

``point_polyline_min_dist_pallas`` computes the min distance from a block
of points to every edge of a packed polyline/polygon boundary — the inner
loop of polygon range queries and geofence filters. Points stream through
(64, 128) VMEM tiles; edge endpoints are SMEM scalars consumed by a
``fori_loop`` with a running minimum, so no (N, E) intermediate exists.

Status: numerically identical to ops.distances.point_polyline_distance
(≤1e-6 f32) and functional on the real chip, but NOT the default — XLA's
own fusion of the broadcast+reduce form already keeps this op compute-bound
on v5e, and the scalar-edge loop underutilizes the VPU. The kernel is kept
as the template for ops XLA cannot fuse (candidates for later rounds: the
grid-hash join gather and multi-boundary batched containment). Measure
before switching defaults.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

_LANES = 128
_ROWS = 64
_BLOCK = _LANES * _ROWS  # points per grid step, one (64, 128) f32 tile


def _min_dist_kernel(ex1_ref, ey1_ref, ex2_ref, ey2_ref, evalid_ref,
                     px_ref, py_ref, out_ref):
    """One (8, 128) block of points vs all edges; edges are SMEM scalars
    streamed through a fori_loop with a running minimum — no (N, E)
    intermediate ever exists."""
    px = px_ref[:]
    py = py_ref[:]
    n_edges = ex1_ref.shape[0]

    def body(e, acc):
        x1 = ex1_ref[e]
        y1 = ey1_ref[e]
        x2 = ex2_ref[e]
        y2 = ey2_ref[e]
        ok = evalid_ref[e]
        ax = px - x1
        ay = py - y1
        cx = x2 - x1
        cy = y2 - y1
        len_sq = cx * cx + cy * cy
        dot = ax * cx + ay * cy
        # Degenerate segment → clamp to endpoint 1 (param < 0 path).
        param = jnp.where(len_sq > 0, dot / jnp.where(len_sq > 0, len_sq, 1.0), -1.0)
        t = jnp.clip(param, 0.0, 1.0)
        dx = px - (x1 + t * cx)
        dy = py - (y1 + t * cy)
        d2 = dx * dx + dy * dy
        d2 = jnp.where(ok > 0, d2, jnp.float32(np.inf))
        return jnp.minimum(acc, d2)

    min_d2 = jax.lax.fori_loop(
        0, n_edges, body, jnp.full(px.shape, np.inf, jnp.float32)
    )
    out_ref[:] = jnp.sqrt(min_d2)


@functools.partial(jax.jit, static_argnames=("interpret",))
def _run_pallas(px, py, ex1, ey1, ex2, ey2, evalid, interpret=False):
    n_rows = px.shape[0]  # (n_rows, 128)
    grid = (n_rows // _ROWS,)
    block2d = lambda: pl.BlockSpec((_ROWS, _LANES), lambda i: (i, 0),
                                   memory_space=pltpu.VMEM)
    smem = lambda: pl.BlockSpec(memory_space=pltpu.SMEM)
    return pl.pallas_call(
        _min_dist_kernel,
        out_shape=jax.ShapeDtypeStruct((n_rows, _LANES), jnp.float32),
        grid=grid,
        in_specs=[smem(), smem(), smem(), smem(), smem(), block2d(), block2d()],
        out_specs=block2d(),
        interpret=interpret,
    )(ex1, ey1, ex2, ey2, evalid, px, py)


def point_polyline_min_dist_pallas(
    xy: jnp.ndarray,
    verts: jnp.ndarray,
    edge_valid: jnp.ndarray,
    interpret: bool = False,
) -> jnp.ndarray:
    """(N,) min distance from each point to the packed boundary's edges.

    Drop-in float32 equivalent of ops.distances.point_polyline_distance for
    a single boundary. ``interpret=True`` runs the Pallas interpreter (CPU
    testing).
    """
    n = xy.shape[0]
    pad = (-n) % _BLOCK
    px = jnp.pad(xy[:, 0].astype(jnp.float32), (0, pad)).reshape(-1, _LANES)
    py = jnp.pad(xy[:, 1].astype(jnp.float32), (0, pad)).reshape(-1, _LANES)
    ex1 = verts[:-1, 0].astype(jnp.float32)
    ey1 = verts[:-1, 1].astype(jnp.float32)
    ex2 = verts[1:, 0].astype(jnp.float32)
    ey2 = verts[1:, 1].astype(jnp.float32)
    ev = edge_valid.astype(jnp.int32)
    out = _run_pallas(px, py, ex1, ey1, ex2, ey2, ev, interpret=interpret)
    return out.reshape(-1)[:n]
