"""hostcache: cache dir placed from outside, device signatures, enable()."""

import os

import jax
import pytest

from oversim_tpu import hostcache

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
FIXED = os.path.join(REPO, ".jax_cache")


def test_cache_dir_is_the_variable_when_set(monkeypatch, tmp_path):
    # placed from outside: $JAX_COMPILATION_CACHE_DIR wins, verbatim
    want = str(tmp_path / "outside")
    monkeypatch.setenv(hostcache.CACHE_ENV, want)
    assert hostcache.cache_dir() == want


def test_cache_dir_unset_is_one_fixed_path_in_the_checkout(monkeypatch,
                                                           tmp_path):
    monkeypatch.delenv(hostcache.CACHE_ENV, raising=False)
    a = hostcache.cache_dir()
    # the path is part of the cache's key: it must not depend on the
    # working directory, the pid or the time — two calls agree
    monkeypatch.chdir(tmp_path)
    assert hostcache.cache_dir() == a == FIXED
    assert not a.startswith("/tmp")


def test_cache_dir_empty_variable_counts_as_unset(monkeypatch):
    monkeypatch.setenv(hostcache.CACHE_ENV, "")
    assert hostcache.cache_dir() == FIXED


def test_aot_store_root_follows_cache_dir(monkeypatch, tmp_path):
    from oversim_tpu.aot import store
    monkeypatch.delenv("OVERSIM_AOT_DIR", raising=False)
    monkeypatch.setenv(hostcache.CACHE_ENV, str(tmp_path / "outside"))
    assert store.default_root() == str(tmp_path / "outside" / "aot")
    monkeypatch.delenv(hostcache.CACHE_ENV)
    assert store.default_root() == os.path.join(FIXED, "aot")


def test_device_signature_names_the_visible_set():
    sig = hostcache.device_signature()
    # conftest: CPU backend with 8 virtual devices
    assert sig.startswith("cpu:")
    assert sig.endswith(":x8")


@pytest.mark.parametrize("env_set", [True, False],
                         ids=["variable-set", "variable-unset"])
def test_enable_persistent_points_cache_at_cache_dir(monkeypatch, tmp_path,
                                                     env_set):
    want = str(tmp_path / "outside") if env_set else FIXED
    if env_set:
        monkeypatch.setenv(hostcache.CACHE_ENV, want)
    else:
        monkeypatch.delenv(hostcache.CACHE_ENV, raising=False)
    prev_dir = jax.config.jax_compilation_cache_dir
    try:
        assert hostcache.enable(persistent=True) == want
        # that directory and no other is set in code
        assert jax.config.jax_compilation_cache_dir == want
        # enable() must NOT flip the cache enable flag back on — the
        # suite runs with it disabled (XLA-CPU serialize segfault,
        # conftest note) and only sets the directory
        assert jax.config.jax_enable_compilation_cache is False
        assert jax.config.jax_enable_x64 is True
    finally:
        jax.config.update("jax_compilation_cache_dir", prev_dir)


def test_enable_non_persistent_disables_cache():
    assert hostcache.enable(persistent=False) is None
    assert jax.config.jax_enable_compilation_cache is False
