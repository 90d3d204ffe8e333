"""Two-process jax.distributed dryrun — proof the DCN seam runs.

The reference scales out to a 15-task Flink cluster
(conf/geoflink-conf.yml:55); this framework's scale-out is
``jax.distributed`` + a global mesh (parallel/multihost.py). This module
DEMONSTRATES that seam end to end on CPU, no second host required:

- ``run_dryrun()`` spawns ``num_processes`` child interpreters on this
  machine, each with ``local_devices`` virtual CPU devices;
- every child joins the job through ``initialize_distributed`` (the
  exact production entry point), builds ONE global mesh spanning all
  processes' devices, and runs a real package kernel —
  ``parallel/sharded.py:sharded_knn`` — over a globally-sharded point
  batch (cross-process pmin/psum ride the gloo CPU collectives standing
  in for DCN);
- each child asserts the distributed result matches the single-device
  ``ops/knn.py:knn_kernel`` on its full local copy, then prints an OK
  line the parent verifies.

Run: ``python -m spatialflink_tpu.parallel.multihost_dryrun``
Test: tests/test_multihost.py (slow marker — spawns 2 jax processes).
"""

from __future__ import annotations

import os
import subprocess
import sys

OK_TAG = "MULTIHOST_DRYRUN_OK"


def child_main(process_id: int, port: int, num_processes: int,
               local_devices: int) -> None:
    # JAX_PLATFORMS/XLA_FLAGS are set by run_dryrun in the SPAWNING env:
    # ``python -m`` imports the package (which configures jax) before
    # this function runs, so in-process env edits would come too late.
    from spatialflink_tpu.parallel.multihost import initialize_distributed

    joined = initialize_distributed(
        f"127.0.0.1:{port}", num_processes, process_id
    )
    assert joined, "initialize_distributed returned False for a 2-proc job"

    import jax
    import jax.numpy as jnp
    import numpy as np

    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    n_global = num_processes * local_devices
    assert len(jax.devices()) == n_global, jax.devices()
    assert jax.process_index() == process_id

    from spatialflink_tpu.grid import UniformGrid
    from spatialflink_tpu.ops.cells import gather_cell_flags
    from spatialflink_tpu.ops.knn import knn_kernel
    from spatialflink_tpu.parallel.sharded import sharded_knn

    grid = UniformGrid(20, 0.0, 10.0, 0.0, 10.0)
    rng = np.random.default_rng(5)  # identical stream on every process
    n, nseg, k, radius = 4096, 64, 8, np.float64(3.0)
    xy = rng.uniform(0, 10, (n, 2))
    oid = rng.integers(0, nseg, n).astype(np.int32)
    cell = grid.assign_cells_np(xy)
    flags = gather_cell_flags(
        jnp.asarray(cell),
        jnp.asarray(grid.neighbor_flags(float(radius),
                                        [grid.flat_cell(5.0, 5.0)])),
    )
    q = np.asarray([5.0, 5.0])

    mesh = Mesh(np.asarray(jax.devices()).reshape(n_global), ("data",))
    sh = NamedSharding(mesh, P("data"))

    def gput(a, sharding):
        return jax.make_array_from_callback(
            a.shape, sharding, lambda idx: a[idx]
        )

    res = sharded_knn(
        mesh,
        gput(xy, sh),
        gput(np.ones(n, bool), sh),
        gput(np.asarray(flags), sh),
        gput(oid, sh),
        gput(q, NamedSharding(mesh, P())),
        radius, k=k, num_segments=nseg,
    )

    ref = knn_kernel(
        jnp.asarray(xy), jnp.ones(n, bool), flags, jnp.asarray(oid),
        jnp.asarray(q), radius, k=k, num_segments=nseg,
    )

    def fetch(x):
        return np.asarray(jax.device_get(x.addressable_data(0)))

    nv = int(fetch(res.num_valid))
    assert nv == int(jax.device_get(ref.num_valid)), (
        nv, int(jax.device_get(ref.num_valid)))
    assert nv == k, f"degenerate dryrun: top-k underfilled ({nv})"
    np.testing.assert_array_equal(
        fetch(res.segment)[:nv], np.asarray(ref.segment)[:nv]
    )
    np.testing.assert_array_equal(
        fetch(res.dist)[:nv], np.asarray(ref.dist)[:nv]
    )
    np.testing.assert_array_equal(
        fetch(res.index)[:nv], np.asarray(ref.index)[:nv]
    )
    print(f"{OK_TAG} pid={process_id} devices={n_global} "
          f"procs={num_processes} k={nv}", flush=True)


def run_dryrun(num_processes: int = 2, local_devices: int = 2,
               timeout: float = 240.0, port: int = 0) -> str:
    """Spawn the children, wait, and return their combined stdout.

    Raises RuntimeError (with both children's output) unless every
    child printed its OK line and exited 0."""
    import socket

    if port == 0:
        s = socket.socket()
        s.bind(("127.0.0.1", 0))
        port = s.getsockname()[1]
        s.close()
    env = {**os.environ}
    env["JAX_PLATFORMS"] = "cpu"
    flags = [f for f in env.get("XLA_FLAGS", "").split()
             if "xla_force_host_platform_device_count" not in f]
    env["XLA_FLAGS"] = " ".join(
        flags + [f"--xla_force_host_platform_device_count={local_devices}"]
    )
    env["PYTHONPATH"] = (
        os.path.dirname(os.path.dirname(os.path.dirname(
            os.path.abspath(__file__))))
        + os.pathsep + env.get("PYTHONPATH", "")
    )
    procs = [
        subprocess.Popen(
            [sys.executable, "-m",
             "spatialflink_tpu.parallel.multihost_dryrun",
             "--child", str(pid), str(port), str(num_processes),
             str(local_devices)],
            env=env, stdout=subprocess.PIPE, stderr=subprocess.STDOUT,
            text=True,
        )
        for pid in range(num_processes)
    ]
    outs = []
    try:
        for p in procs:
            out, _ = p.communicate(timeout=timeout)
            outs.append(out)
    except subprocess.TimeoutExpired:
        # Kill AND drain every child: the hung child's partial output is
        # the diagnostic (e.g. which side of the coordinator barrier it
        # reached), and un-reaped children would leak zombies + pipes.
        drained = []
        for p in procs:
            p.kill()
            try:
                out, _ = p.communicate(timeout=10)
            except Exception:
                out = "<unreadable>"
            drained.append(f"[child rc={p.returncode}]\n{out}")
        raise RuntimeError(
            "multihost dryrun timed out\n" + "\n".join(drained)
        )
    combined = "\n".join(outs)
    rcs = [p.returncode for p in procs]
    if any(rcs) or combined.count(OK_TAG) != num_processes:
        raise RuntimeError(
            f"multihost dryrun failed (rcs={rcs}):\n{combined}"
        )
    return combined


def main(argv=None) -> int:
    argv = list(sys.argv[1:] if argv is None else argv)
    if argv[:1] == ["--child"]:
        child_main(int(argv[1]), int(argv[2]), int(argv[3]), int(argv[4]))
        return 0
    out = run_dryrun()
    sys.stdout.write(out)
    return 0


if __name__ == "__main__":
    sys.exit(main())
