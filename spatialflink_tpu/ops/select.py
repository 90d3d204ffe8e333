"""Sort-free first-k selection — the shared TPU selection primitive and
the per-backend strategy gate.

``lax.top_k`` lowers to a full sort on TPU; when only set-MEMBERSHIP
matters (the consumer's reduction is order-independent, e.g. min), the
first k set bits per row can be selected with a prefix-sum one-hot —
pure VPU compare/select/reduce, measured ~10× faster than top_k at the
shapes the kernels use. On XLA:CPU the relation inverts (the vectorized
sort wins; the one-hot tensor measured ~9× slower on the kNN headline),
so every consumer gates on ``onehot_select_preferred()``:

- ops/join.py:_block_candidates (candidate geometries per tile),
- ops/join.py:_compact_pairs (matches per left item),
- ops/knn.py compact-digest candidate select.

``first_k_prefix_indices`` is the third strategy — index extraction via
prefix sum + batched binary search, no sort and no one-hot tensor. It
is the CPU form of the compacted tJoin pane probe
(ops/tjoin_panes.py:_probe_compact), where ``lax.top_k`` over the
span²·cap candidate width was ~45% of the whole slide step.

The top_k alternative stays at each call site rather than behind one
index-returning API: the TPU consumers reduce the one-hot tensor
directly (sums — no gathers, which are the TPU-slow op this module
exists to avoid), while the CPU consumers gather by the top_k indices.
Both strategies select the identical set (ascending position, ties by
index) — parity-tested per consumer.
"""

from __future__ import annotations

import jax.numpy as jnp

from spatialflink_tpu.runtime import on_tpu


def onehot_select_preferred() -> bool:
    """True on the backend where the prefix-sum one-hot select beats
    top_k (TPU) — the ONE gate every consumer shares."""
    return on_tpu()


def first_k_prefix_indices(mask: jnp.ndarray, k: int):
    """First-``k`` set bits along the LAST axis as INDICES, sort-free.

    Returns ``(ci, count, overflow)``: ``ci`` is (..., k) int32 — slot
    ``s`` holds the lane index of the (s+1)-th set bit (clipped in-range
    garbage past the per-row count; mask with ``count`` downstream),
    ``count``/``overflow`` as in ``first_k_onehot``. Selects the
    IDENTICAL set as ``lax.top_k`` over the int8 mask (ascending lane
    order, complete iff overflow == 0) without the full per-row sort
    top_k lowers to on CPU (~45% of the tJoin pane slide step at the
    10s/10ms bench shape) and without the (..., C, k) one-hot tensor:
    one prefix sum plus a ⌈log₂ C⌉-step batched binary search over it
    (the prefix is nondecreasing, so ``ci[s]`` is the first lane where
    ``prefix ≥ s+1`` — k·log C tiny gathers instead of a C-wide sort).
    """
    prefix = jnp.cumsum(mask.astype(jnp.int32), axis=-1)
    count = prefix[..., -1]
    overflow = jnp.sum(jnp.maximum(count - k, 0))
    C = mask.shape[-1]
    target = jnp.arange(1, k + 1, dtype=jnp.int32)
    target = jnp.broadcast_to(target, count.shape + (k,))
    lo = jnp.zeros(count.shape + (k,), jnp.int32)
    hi = jnp.full(count.shape + (k,), C, jnp.int32)
    # The search interval is [0, C] — C+1 distinct answers, so
    # ⌈log₂(C+1)⌉ = C.bit_length() halvings (NOT (C-1).bit_length(),
    # which is one short exactly when C is a power of two).
    steps = max(int(C).bit_length(), 1)
    for _ in range(steps):  # static trip count — fully unrolled, no sort
        mid = (lo + hi) // 2
        v = jnp.take_along_axis(prefix, jnp.clip(mid, 0, C - 1), axis=-1)
        go = v < target
        lo = jnp.where(go, mid + 1, lo)
        hi = jnp.where(go, hi, mid)
    return jnp.clip(lo, 0, C - 1), count, overflow


def first_k_onehot(mask: jnp.ndarray, k: int):
    """Select the first ``k`` set bits along the LAST axis, ascending.

    Returns ``(hit, count, overflow)``: ``hit`` is a (..., C, k) one-hot
    bool tensor (slot ``s`` marks the (s+1)-th set bit of the row —
    consumers reduce it against index or value tensors; a one-hot sum
    selects exactly one term, so value selection is bit-exact),
    ``count`` the (...,) per-row set-bit totals, and ``overflow`` the
    scalar total of set bits beyond ``k`` (the callers' retry contract:
    selection is complete iff 0).
    """
    prefix = jnp.cumsum(mask.astype(jnp.int32), axis=-1)
    count = prefix[..., -1]
    slots = jnp.arange(k, dtype=jnp.int32)
    hit = mask[..., None] & (prefix[..., None] == slots + 1)
    overflow = jnp.sum(jnp.maximum(count - k, 0))
    return hit, count, overflow
