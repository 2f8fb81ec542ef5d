"""The entry points' JAX compilation cache: $JAX_COMPILATION_CACHE_DIR
when set, else a fixed directory in the checkout."""
from pathlib import Path

import jax
import pytest

from repro.launch import compile_cache


@pytest.fixture
def restore_config():
    keys = ("jax_compilation_cache_dir",
            "jax_persistent_cache_min_compile_time_secs")
    saved = {k: getattr(jax.config, k) for k in keys}
    yield
    for k, v in saved.items():
        jax.config.update(k, v)


@pytest.mark.parametrize("env", [None, "/some/cache/dir"])
def test_cache_dir(monkeypatch, restore_config, env):
    if env is None:
        monkeypatch.delenv(compile_cache.ENV, raising=False)
        want = str(Path(compile_cache.__file__).resolve().parents[3]
                   / ".jax_cache")
    else:
        monkeypatch.setenv(compile_cache.ENV, env)
        want = env
    assert compile_cache.enable_compile_cache() == want
    assert jax.config.jax_compilation_cache_dir == want
