"""Launcher helpers: ``--devices`` selection and compile-cache placement."""

import os

import pytest

import jax

from repro.launch import CHECKOUT, launch_devices, use_compile_cache


def test_launch_devices_takes_exactly_the_first_n():
    visible = jax.devices()
    assert launch_devices(0) == visible          # 0: every visible device
    assert launch_devices(1) == visible[:1]
    assert launch_devices(len(visible)) == visible


def test_launch_devices_refuses_more_than_visible():
    n = len(jax.devices()) + 1
    with pytest.raises(SystemExit, match=f"--devices {n}: only"):
        launch_devices(n)


@pytest.fixture
def cache_dir_restored(monkeypatch):
    prev = jax.config.jax_compilation_cache_dir
    yield monkeypatch
    jax.config.update("jax_compilation_cache_dir", prev)


def test_compile_cache_defaults_to_fixed_checkout_path(cache_dir_restored):
    cache_dir_restored.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    path = use_compile_cache()
    assert path == os.path.join(CHECKOUT, ".jax_cache")
    assert jax.config.jax_compilation_cache_dir == path
    # the same path on every call: no pid, time or temp name in it
    assert use_compile_cache() == path


def test_compile_cache_respects_environment(cache_dir_restored, tmp_path):
    jax.config.update("jax_compilation_cache_dir", None)
    cache_dir_restored.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    assert use_compile_cache() == str(tmp_path)
    # the helper sets no directory of its own when the variable is set
    assert jax.config.jax_compilation_cache_dir is None
