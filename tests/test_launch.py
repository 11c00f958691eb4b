"""Launcher plumbing: mesh parsing, the local mesh, the compile cache."""
import os

import jax
import pytest

from repro.launch import compile_cache
from repro.launch.mesh import make_local_mesh
from repro.launch.serve import parse_mesh

REPO = os.path.join(os.path.dirname(os.path.abspath(__file__)), "..")


def test_parse_mesh():
    assert parse_mesh(None) == (1, 1)
    assert parse_mesh("2x2") == (2, 2)
    assert parse_mesh("1X4") == (1, 4)
    with pytest.raises(ValueError, match="DPxTP"):
        parse_mesh("4")


def test_local_mesh_uses_devices_present():
    """The serving mesh defaults to 1x1 over the devices present, and
    builds on any given device."""
    mesh = make_local_mesh()
    assert dict(mesh.shape) == {"data": 1, "model": 1}
    assert list(mesh.devices.flat) == [jax.devices()[0]]
    last = jax.devices()[-1]
    assert list(make_local_mesh(devices=[last]).devices.flat) == [last]
    with pytest.raises(RuntimeError, match="need"):
        make_local_mesh((len(jax.devices()) + 1, 1))


def test_compile_cache_honours_env_var(monkeypatch):
    before = jax.config.jax_compilation_cache_dir
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/nonexistent/cache")
    assert compile_cache.enable_compile_cache() is None
    assert jax.config.jax_compilation_cache_dir == before


def test_compile_cache_dir_is_fixed_in_checkout():
    """Without the env var the cache goes to one fixed, git-ignored path
    in the checkout (never a temporary name, pid or time)."""
    assert (os.path.normpath(compile_cache.CACHE_DIR)
            == os.path.normpath(os.path.join(REPO, ".jax_cache")))
    with open(os.path.join(REPO, ".gitignore")) as fh:
        assert ".jax_cache/" in fh.read().split()
