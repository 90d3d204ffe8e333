"""Every violation class suppressed by a pragma — must yield ZERO findings
from every pass (force-checked by tests/test_sfcheck.py).
"""

import time

import jax
import jax.numpy as jnp

PAD = jnp.zeros((8,))  # sfcheck: ok=hotpath -- fixture: deliberate import-time dispatch
LUT = jnp.full(
    (16,),
    0.0,
)  # sfcheck: ok -- fixture: pragma on the LAST line of a multi-line call spans the whole node


def host_helper(x, scale):
    t0 = time.time()  # hotpath: ok (legacy pragma still honored)
    s = float(scale)  # sfcheck: ok=trace-hygiene -- fixture: host-side scalar by contract
    idx = jnp.nonzero(x)  # sfcheck: ok=fixed-shape,trace-hygiene -- fixture: multi-pass pragma list
    jax.block_until_ready(x)  # sfcheck: ok=sync-discipline -- fixture: CPU-only path
    return f"t={t0:.3f} s={s:.1f}", idx  # sfcheck: ok=fstring-numpy -- fixture: known Python floats
