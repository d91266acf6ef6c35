"""Compile-cache resolution (utils/jax_cache): one placeable directory.

``JAX_COMPILATION_CACHE_DIR`` set → JAX's own handling is all there is
(the code sets no directory); unset → the fixed in-checkout path; a
directory that cannot be created raises instead of running uncached.
"""

from pathlib import Path

import jax
import pytest

from sonata_tpu.utils import jax_cache


@pytest.fixture
def config_updates(monkeypatch):
    """Record ``jax.config.update`` calls instead of applying them (the
    suite's own cache configuration must survive the test)."""
    calls = []
    monkeypatch.setattr(jax.config, "update",
                        lambda name, value: calls.append((name, value)))
    return calls


def test_env_set_means_code_sets_no_directory(tmp_path, monkeypatch,
                                              config_updates):
    placed = tmp_path / "placed"
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(placed))
    assert jax_cache.enable_persistent_compile_cache() == str(placed)
    assert "jax_compilation_cache_dir" not in dict(config_updates)
    assert not placed.exists()  # creating it is JAX's business too
    assert jax_cache.aot_cache_dir() == str(placed / "aot")


def test_env_unset_means_fixed_in_checkout_path(tmp_path, monkeypatch,
                                                config_updates):
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    repo = Path(jax_cache.__file__).resolve().parents[2]
    assert jax_cache.CHECKOUT_CACHE_DIR == str(repo / ".jax_cache")
    # redirect the constant so the test creates nothing in the checkout
    fixed = tmp_path / ".jax_cache"
    monkeypatch.setattr(jax_cache, "CHECKOUT_CACHE_DIR", str(fixed))
    assert jax_cache.enable_persistent_compile_cache(0.5) == str(fixed)
    assert fixed.is_dir()
    assert dict(config_updates) == {
        "jax_compilation_cache_dir": str(fixed),
        "jax_persistent_cache_min_compile_time_secs": 0.5}


def test_uncreatable_directory_raises(tmp_path, monkeypatch,
                                      config_updates):
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    blocker = tmp_path / "a_file"
    blocker.write_text("not a directory")
    monkeypatch.setattr(jax_cache, "CHECKOUT_CACHE_DIR",
                        str(blocker / ".jax_cache"))
    with pytest.raises(OSError):
        jax_cache.enable_persistent_compile_cache()
    assert config_updates == []
    monkeypatch.delenv("SONATA_AOT_CACHE", raising=False)
    with pytest.raises(OSError):
        jax_cache.aot_cache_dir()
