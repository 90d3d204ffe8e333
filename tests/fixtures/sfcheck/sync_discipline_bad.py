"""sync-discipline true positives: function and method spellings."""

import jax
from jax import block_until_ready as bur


def timed_step(fn, x):
    out = fn(x)
    jax.block_until_ready(out)     # unaccounted sync
    out.block_until_ready()        # method form, same
    bur(out)                       # aliased import
    return out
