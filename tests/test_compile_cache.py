"""utils/compile_cache: the directory is placed from outside."""

import os

import pytest

from ceph_tpu.utils import compile_cache


@pytest.fixture
def config_updates(monkeypatch):
    """Record jax.config.update calls without applying the directory
    ones (the process's real cache setting stays as it was)."""
    import jax
    calls = []
    real = jax.config.update

    def update(name, value):
        calls.append((name, value))
        if name != "jax_compilation_cache_dir":
            real(name, value)

    monkeypatch.setattr(jax.config, "update", update)
    monkeypatch.delenv("CEPH_TPU_COMPILE_CACHE", raising=False)
    compile_cache._reset_for_tests()
    yield calls
    compile_cache._reset_for_tests()


def test_env_dir_is_used_and_no_directory_is_set_in_code(
        config_updates, monkeypatch, tmp_path):
    env_dir = str(tmp_path / "from_env")
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", env_dir)
    assert compile_cache.enable() == env_dir
    assert compile_cache.enabled_dir() == env_dir
    assert not [c for c in config_updates
                if c[0] == "jax_compilation_cache_dir"]
    # the floors are still dropped: they are not a directory
    assert ("jax_persistent_cache_min_compile_time_secs", 0.0) \
        in config_updates
    # the ledger follows the cache
    compile_cache.note_compile("sig_a", 1.5)
    assert os.path.exists(os.path.join(env_dir,
                                       compile_cache.LEDGER_NAME))
    assert compile_cache.ledger()["sig_a"]["cold_s"] == 1.5


def test_unset_env_means_the_fixed_path_in_the_checkout(
        config_updates, monkeypatch):
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    fixed = os.path.join(root, ".jax_compile_cache")
    assert compile_cache.default_dir() == fixed
    assert compile_cache.enable() == fixed
    assert ("jax_compilation_cache_dir", fixed) in config_updates


def test_second_process_on_the_same_dir_counts_a_hit(
        config_updates, monkeypatch, tmp_path):
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    compile_cache.enable()
    assert compile_cache.note_compile("sig_b", 2.0) is False
    compile_cache._reset_for_tests()          # "a new process"
    compile_cache.enable()
    assert compile_cache.note_compile("sig_b", 0.25) is True
    ent = compile_cache.ledger()["sig_b"]
    assert ent["cold_s"] == 2.0 and ent["warm_s"] == 0.25
    assert ent["hits"] == 1
