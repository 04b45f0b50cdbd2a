"""Start-up hooks in repro.launch._env: where the compilation cache goes and
how --host-devices reaches XLA_FLAGS."""
import os

import jax
import pytest

from repro.launch import _env


@pytest.fixture
def cache_dir_config():
    """Put jax_compilation_cache_dir back as it was: the tests run with the
    persistent cache off, and nothing here compiles while it is changed."""
    before = jax.config.jax_compilation_cache_dir
    yield
    jax.config.update("jax_compilation_cache_dir", before)


def test_compile_cache_env_var_wins(monkeypatch, cache_dir_config):
    jax.config.update("jax_compilation_cache_dir", None)
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/elsewhere/cache")
    _env.use_compile_cache()
    assert jax.config.jax_compilation_cache_dir is None


def test_compile_cache_defaults_to_fixed_checkout_path(
    monkeypatch, cache_dir_config
):
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    _env.use_compile_cache()
    assert jax.config.jax_compilation_cache_dir == str(_env.COMPILE_CACHE_DIR)
    root = _env.COMPILE_CACHE_DIR.parent
    assert (root / "pyproject.toml").exists()
    ignored = (root / ".gitignore").read_text().split()
    assert f"{_env.COMPILE_CACHE_DIR.name}/" in ignored


@pytest.mark.parametrize(
    "argv, added",
    [
        (["x", "--host-devices", "4"], " --xla_force_host_platform_device_count=4"),
        (["x", "--host-devices=2"], " --xla_force_host_platform_device_count=2"),
        (["x", "--host-devices", "0"], ""),
        (["x"], ""),
    ],
)
def test_host_devices_flag(monkeypatch, argv, added):
    monkeypatch.setenv("XLA_FLAGS", "--base")
    _env.apply_host_devices(argv)
    assert os.environ["XLA_FLAGS"] == "--base" + added
