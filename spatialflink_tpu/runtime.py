"""Runtime configuration for the JAX backend: where compiled programs
are cached, and the ONE way to ask whether the default backend is a TPU.

Compile cache rule (this module is its only home — nothing else in the
tree may set ``jax_compilation_cache_dir``):

- ``JAX_COMPILATION_CACHE_DIR`` set → JAX reads it itself and the
  program sets no directory in code, so the cache can be placed from
  outside (a chip machine that carries a cache between calls, a CI
  volume);
- unset → ``<repo>/.jax_cache`` (git-ignored). A fixed path inside the
  checkout: the directory is part of the cache key, so a home/temp/pid
  path would never hit from a fresh copy of the tree.
"""

from __future__ import annotations

import os

REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
DEFAULT_CACHE_DIR = os.path.join(REPO_ROOT, ".jax_cache")


def configure_jax_cache() -> None:
    import jax

    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        jax.config.update("jax_compilation_cache_dir", DEFAULT_CACHE_DIR)
    # Every program is worth keeping: the per-window kernels compile in
    # well under JAX's default 1 s threshold but there are hundreds.
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)


def on_tpu() -> bool:
    """True iff JAX's default backend is a TPU — the single platform
    question every backend-dependent code path asks (Pallas vs XLA
    join, one-hot vs top_k select, device vs native pane engine, …).

    Never guesses: a backend that fails to initialise raises here
    instead of silently selecting the CPU code path."""
    import jax

    return jax.default_backend() == "tpu"


configure_jax_cache()
