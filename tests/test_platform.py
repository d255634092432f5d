"""configure_platform: flag construction in-process, full behaviour in
a subprocess (XLA flags only apply before JAX initializes, and pytest's
main process has long since initialized)."""

import os
import subprocess
import sys

import pytest

from repro.config import (GPU_OVERLAP_FLAGS, _merge_xla_flags,
                          configure_platform, enable_compilation_cache)


def test_merge_replaces_same_name_and_keeps_others(monkeypatch):
    monkeypatch.setenv("XLA_FLAGS",
                       "--xla_cpu_enable_fast_math=false "
                       "--xla_force_host_platform_device_count=2")
    merged = _merge_xla_flags(
        ("--xla_force_host_platform_device_count=8",)).split()
    assert "--xla_force_host_platform_device_count=8" in merged
    assert "--xla_force_host_platform_device_count=2" not in merged
    assert "--xla_cpu_enable_fast_math=false" in merged


def test_merge_is_idempotent(monkeypatch):
    monkeypatch.setenv("XLA_FLAGS", "")
    _merge_xla_flags(GPU_OVERLAP_FLAGS)
    once = os.environ["XLA_FLAGS"]
    _merge_xla_flags(GPU_OVERLAP_FLAGS)
    assert os.environ["XLA_FLAGS"] == once


def test_after_init_warns_and_returns_false(monkeypatch):
    # pytest's process has run jax computations: the call must refuse
    # politely, not crash, and must not touch the environment.
    import jax
    jax.numpy.zeros(())  # ensure a backend exists
    monkeypatch.setenv("XLA_FLAGS", "--sentinel=1")
    with pytest.warns(RuntimeWarning, match="after JAX initialized"):
        applied = configure_platform(host_devices=4)
    assert applied is False
    assert os.environ["XLA_FLAGS"] == "--sentinel=1"


def test_host_devices_validation():
    with pytest.raises(ValueError, match="host_devices"):
        configure_platform(host_devices=0)


def test_configure_platform_subprocess():
    """Acceptance: a fresh process gets 16 emulated CPU devices, a mesh
    over them, idempotent flag merging, and the warn-after-init
    contract (tests/_platform_check.py)."""
    env = {k: v for k, v in os.environ.items() if k != "XLA_FLAGS"}
    out = subprocess.run(
        [sys.executable, "tests/_platform_check.py", "16"],
        capture_output=True, text=True, timeout=300, env=env)
    assert out.returncode == 0, out.stdout + out.stderr
    assert "OK 16" in out.stdout


@pytest.fixture
def cache_dir_restored():
    import jax
    before = jax.config.jax_compilation_cache_dir
    yield jax
    jax.config.update("jax_compilation_cache_dir", before)


def test_compilation_cache_honours_env(monkeypatch, cache_dir_restored,
                                       tmp_path):
    jax = cache_dir_restored
    before = jax.config.jax_compilation_cache_dir
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    assert enable_compilation_cache() == str(tmp_path)
    assert jax.config.jax_compilation_cache_dir == before  # set no other


def test_compilation_cache_defaults_to_checkout(monkeypatch,
                                                cache_dir_restored):
    from pathlib import Path
    jax = cache_dir_restored
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    root = Path(__file__).resolve().parents[1]
    want = str(root / ".jax_cache")
    assert enable_compilation_cache() == want
    assert jax.config.jax_compilation_cache_dir == want
    ignored = (root / ".gitignore").read_text().split()
    assert ".jax_cache/" in ignored, ".jax_cache must be git-ignored"
