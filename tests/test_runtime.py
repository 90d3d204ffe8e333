"""spatialflink_tpu/runtime.py: where the compile cache goes, the one
way to ask "are we on a TPU", and the native library's build-or-absent
rule."""

import os
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

_PRINT_CACHE_DIR = (
    "import spatialflink_tpu, jax; "
    "print(jax.config.jax_compilation_cache_dir)"
)


def _cache_dir_after_import(env_overrides):
    env = {k: v for k, v in os.environ.items()
           if k != "JAX_COMPILATION_CACHE_DIR"}
    env.update({"JAX_PLATFORMS": "cpu", **env_overrides})
    p = subprocess.run(
        [sys.executable, "-c", _PRINT_CACHE_DIR], cwd=REPO, env=env,
        capture_output=True, text=True, timeout=120,
    )
    assert p.returncode == 0, p.stderr[-2000:]
    return p.stdout.strip().splitlines()[-1]


def test_cache_dir_placed_from_outside_is_left_alone(tmp_path):
    want = str(tmp_path / "outside_cache")
    assert _cache_dir_after_import(
        {"JAX_COMPILATION_CACHE_DIR": want}) == want


def test_cache_dir_defaults_to_a_fixed_path_in_the_checkout():
    assert _cache_dir_after_import({}) == os.path.join(REPO, ".jax_cache")


def test_only_runtime_sets_the_cache_dir():
    """One rule, one place: no other file may update the cache dir."""
    hits = []
    for root, dirs, files in os.walk(REPO):
        dirs[:] = [d for d in dirs if not d.startswith(".")
                   and d not in ("scratch_chip", "chiprun_out",
                                 "__pycache__")]
        for name in files:
            if name.endswith(".py"):
                path = os.path.join(root, name)
                with open(path) as f:
                    if ("update(" + '"jax_compilation_cache_dir"') in f.read():
                        hits.append(os.path.relpath(path, REPO))
    assert hits == [os.path.join("spatialflink_tpu", "runtime.py")]


def test_on_tpu_reads_the_default_backend(monkeypatch):
    import jax

    from spatialflink_tpu import runtime

    assert runtime.on_tpu() is False  # the tests run on CPU
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    assert runtime.on_tpu() is True


def test_backend_init_error_propagates_through_every_selector(monkeypatch):
    """A backend that fails to initialise must never select the CPU
    code path silently."""
    import jax

    from spatialflink_tpu import runtime
    from spatialflink_tpu.ops.compaction import compact_probe_preferred
    from spatialflink_tpu.ops.join import pallas_join_supported
    from spatialflink_tpu.ops.select import onehot_select_preferred
    from spatialflink_tpu.streams.panes import _device_backend_preferred

    def boom():
        raise RuntimeError("Unable to initialize backend 'tpu'")

    monkeypatch.setattr(jax, "default_backend", boom)
    for ask in (runtime.on_tpu, pallas_join_supported,
                onehot_select_preferred, compact_probe_preferred,
                _device_backend_preferred):
        with pytest.raises(RuntimeError, match="Unable to initialize"):
            ask()


def test_native_is_unavailable_when_make_fails(monkeypatch):
    """A failed build never loads an old binary lying next to the
    source — even one that would load fine."""
    from spatialflink_tpu import native

    native.available()  # builds the .so where a toolchain exists
    monkeypatch.setattr(native, "_lib", None)
    monkeypatch.setattr(native, "_build_failed", False)
    monkeypatch.setattr(native, "_abi_mismatch", False)
    with open(native._LIB_PATH, "ab"):  # a (possibly stale) .so exists
        pass
    created = os.path.getsize(native._LIB_PATH) == 0

    def failing_make(*a, **k):
        raise subprocess.CalledProcessError(2, "make")

    monkeypatch.setattr(native.subprocess, "run", failing_make)
    try:
        assert native.ensure_built() is False
        assert native.available() is False
    finally:
        if created:
            os.remove(native._LIB_PATH)
