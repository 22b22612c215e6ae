"""Launcher plumbing: compile-cache placement and one chip per shard."""
from __future__ import annotations

import jax
import pytest


@pytest.fixture
def cache_dir_config():
    was = jax.config.jax_compilation_cache_dir
    yield
    jax.config.update("jax_compilation_cache_dir", was)


def test_compile_cache_defaults_to_the_checkout(monkeypatch, cache_dir_config):
    from repro.launch import compile_cache

    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    path = compile_cache.configure_compile_cache()
    assert path == str(compile_cache.DEFAULT_CACHE_DIR)
    assert path.endswith("/.jax-cache")
    assert (compile_cache.DEFAULT_CACHE_DIR.parent / "chip_smoke.py").exists()
    assert jax.config.jax_compilation_cache_dir == path


def test_compile_cache_leaves_the_environment_setting(
    monkeypatch, tmp_path, cache_dir_config
):
    from repro.launch import compile_cache

    before = jax.config.jax_compilation_cache_dir
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    assert compile_cache.configure_compile_cache() == str(tmp_path)
    assert jax.config.jax_compilation_cache_dir == before


@pytest.mark.parametrize("num_shards", [1, 2, 4])
def test_shard_mesh_needs_one_device_per_shard(num_shards):
    """The serve launcher builds a ("shard",) mesh only when the shard
    count equals the visible device count; otherwise shards run as the
    host loop (None)."""
    from repro.launch.serve import shard_mesh

    mesh = shard_mesh(num_shards)
    if num_shards > 1 and num_shards == len(jax.devices()):
        assert mesh.axis_names == ("shard",) and mesh.devices.size == num_shards
    else:
        assert mesh is None
