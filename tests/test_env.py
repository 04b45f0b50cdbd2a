"""Start-up hooks in repro.launch._env: where the compilation cache goes and
how --host-devices reaches XLA_FLAGS."""
import os
import re

import jax
import pytest

from repro.launch import _env


@pytest.fixture
def cache_dir_config():
    """Put jax_compilation_cache_dir (and whether the cache keys on op
    metadata) back as it was: the tests run with the persistent cache off,
    and nothing here compiles while it is changed."""
    before = jax.config.jax_compilation_cache_dir
    keyed = jax.config.jax_compilation_cache_include_metadata_in_key
    paths = jax.config.jax_hlo_source_file_canonicalization_regex
    yield
    jax.config.update("jax_compilation_cache_dir", before)
    jax.config.update("jax_compilation_cache_include_metadata_in_key", keyed)
    jax.config.update("jax_hlo_source_file_canonicalization_regex", paths)


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


@pytest.mark.parametrize("env_dir", [None, "/elsewhere/cache"])
def test_compile_cache_keys_on_op_metadata(monkeypatch, cache_dir_config, env_dir):
    # the named scopes are op metadata: a cached executable of a program
    # with other scopes must not stand in for this one
    if env_dir is None:
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    else:
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", env_dir)
    jax.config.update("jax_compilation_cache_include_metadata_in_key", False)
    _env.use_compile_cache()
    assert jax.config.jax_compilation_cache_include_metadata_in_key
    # ... with source paths relative to the checkout
    root = str(_env.COMPILE_CACHE_DIR.parent)
    pattern = jax.config.jax_hlo_source_file_canonicalization_regex
    assert re.sub(pattern, "", f"{root}/src/repro/core/bulk.py") == "src/repro/core/bulk.py"


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
