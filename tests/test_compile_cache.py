"""``repro.compile_cache.enable``: ``$JAX_COMPILATION_CACHE_DIR`` wins when
set; otherwise the fixed ``<repo>/.jax_cache``.  JAX's config is recorded,
not changed, so no test sets a cache directory."""
from __future__ import annotations

import jax
import pytest

from repro import compile_cache


@pytest.fixture
def updates(monkeypatch):
    calls: dict = {}
    monkeypatch.setattr(jax.config, "update",
                        lambda name, value: calls.__setitem__(name, value))
    return calls


def test_repo_cache_without_the_env_var(monkeypatch, updates, tmp_path):
    monkeypatch.delenv(compile_cache.ENV_VAR, raising=False)
    assert compile_cache.enable(tmp_path) == tmp_path / ".jax_cache"
    assert updates["jax_compilation_cache_dir"] == str(tmp_path / ".jax_cache")
    assert updates["jax_persistent_cache_min_compile_time_secs"] == 0.0


def test_env_var_wins(monkeypatch, updates, tmp_path):
    monkeypatch.setenv(compile_cache.ENV_VAR, str(tmp_path / "shared"))
    assert compile_cache.enable(tmp_path) == tmp_path / "shared"
    assert "jax_compilation_cache_dir" not in updates
