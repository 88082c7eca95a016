"""Entry-point plumbing: the serving launcher's function form and the
persistent compilation cache's location."""
import os

import jax
import pytest

from repro import compile_cache
from repro.launch.serve import serve


def test_serve_answers_every_request():
    rep = serve("deit-b", replicas=2, requests=10)
    assert rep.answered == 10
    assert all(isinstance(r, int) for r in rep.results)
    assert rep.stats["admitted"] == 10
    assert rep.devices == [str(jax.devices()[0])] * 2


@pytest.fixture
def cache_config():
    was = jax.config.jax_compilation_cache_dir
    was_meta = jax.config.jax_compilation_cache_include_metadata_in_key
    yield
    jax.config.update("jax_compilation_cache_dir", was)
    jax.config.update("jax_compilation_cache_include_metadata_in_key",
                      was_meta)


def test_compile_cache_in_checkout(monkeypatch, cache_config):
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    path = compile_cache.use_compile_cache()
    assert path == compile_cache.CACHE_DIR
    assert jax.config.jax_compilation_cache_dir == path
    root = os.path.dirname(path)
    assert os.path.isfile(os.path.join(root, "pyproject.toml"))
    with open(os.path.join(root, ".gitignore")) as f:
        assert ".jax_cache/" in f.read().split()


def test_compile_cache_env_wins(monkeypatch, cache_config, tmp_path):
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    before = jax.config.jax_compilation_cache_dir
    assert compile_cache.use_compile_cache() == str(tmp_path)
    assert jax.config.jax_compilation_cache_dir == before


@pytest.mark.parametrize("env", [False, True])
def test_compile_cache_keys_include_op_metadata(monkeypatch, cache_config,
                                                tmp_path, env):
    # the named scopes a trace reports live in the op metadata: an entry
    # keyed without it would hand back an executable with stale scopes
    if env:
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    else:
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    jax.config.update("jax_compilation_cache_include_metadata_in_key", False)
    compile_cache.use_compile_cache()
    assert jax.config.jax_compilation_cache_include_metadata_in_key
