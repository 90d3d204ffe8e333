"""Test config: force CPU with 8 virtual devices (multi-chip sharding tests)
and float64 (parity with the reference's JTS double math).

Must run before the first ``import jax`` anywhere in the test session.
"""

import os

# Force CPU: the tests never take the chip.
os.environ["JAX_PLATFORMS"] = "cpu"
_flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in _flags:
    os.environ["XLA_FLAGS"] = (
        _flags + " --xla_force_host_platform_device_count=8"
    ).strip()

import jax  # noqa: E402

# Belt and braces: some pytest plugin may import jax before this conftest
# runs, in which case the env var above is read too late.
jax.config.update("jax_platforms", "cpu")
jax.config.update("jax_enable_x64", True)
# The persistent compile cache (repeat test runs are cheap) is placed by
# the package's one rule — spatialflink_tpu/runtime.py — at import.
import spatialflink_tpu  # noqa: E402,F401

import numpy as np  # noqa: E402
import pytest  # noqa: E402


# The sfcheck fixture corpus contains deliberate violations AND mini
# test repos (meshparity_*/tests/test_*.py) that only import relative to
# their own project root — never collect them as real tests.
collect_ignore_glob = ["fixtures/*"]


@pytest.fixture
def rng():
    return np.random.default_rng(42)
