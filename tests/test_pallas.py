"""Pallas kernel parity (interpreter mode on the CPU test platform)."""

import jax.numpy as jnp
import numpy as np

from spatialflink_tpu.ops.distances import point_polyline_distance
from spatialflink_tpu.ops.pallas_kernels import point_polyline_min_dist_pallas
from spatialflink_tpu.ops.polygon import pack_rings


def test_pallas_min_dist_matches_xla(rng):
    ring = rng.uniform(0, 10, (37, 2))
    verts, ev = pack_rings([ring], pad_to=64)
    pts = rng.uniform(-2, 12, (3000, 2)).astype(np.float32)
    ref = np.asarray(
        point_polyline_distance(
            jnp.asarray(pts), jnp.asarray(verts.astype(np.float32)), jnp.asarray(ev)
        )
    )
    got = np.asarray(
        point_polyline_min_dist_pallas(
            jnp.asarray(pts), jnp.asarray(verts), jnp.asarray(ev), interpret=True
        )
    )
    np.testing.assert_allclose(got, ref, atol=2e-6)


def test_pallas_min_dist_multi_ring_seams(rng):
    rings = [rng.uniform(0, 5, (9, 2)), rng.uniform(5, 10, (7, 2))]
    verts, ev = pack_rings(rings, pad_to=32)
    pts = rng.uniform(0, 10, (500, 2)).astype(np.float32)
    ref = np.asarray(
        point_polyline_distance(
            jnp.asarray(pts), jnp.asarray(verts.astype(np.float32)), jnp.asarray(ev)
        )
    )
    got = np.asarray(
        point_polyline_min_dist_pallas(
            jnp.asarray(pts), jnp.asarray(verts), jnp.asarray(ev), interpret=True
        )
    )
    np.testing.assert_allclose(got, ref, atol=2e-6)
