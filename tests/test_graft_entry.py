"""Driver-contract tests: entry() compiles and dryrun_multichip(8) runs on
the virtual CPU mesh."""

import os
import sys

import jax
import numpy as np
import pytest

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def test_entry_compiles_and_runs():
    import __graft_entry__

    fn, args = __graft_entry__.entry()
    out = jax.jit(fn)(*args)
    # Sync by fetching (sfcheck sync-discipline).
    out = jax.device_get(out)
    assert int(out.num_valid) == 50
    d = np.asarray(out.dist[: int(out.num_valid)])
    assert (np.diff(d) >= 0).all()  # ascending


@pytest.mark.slow
def test_dryrun_multichip_8():
    import __graft_entry__

    __graft_entry__.dryrun_multichip(8)
