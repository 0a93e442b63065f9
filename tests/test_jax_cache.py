"""The persistent compilation cache helper of the device entry points."""
import pathlib

import jax

from repro import jax_cache

ENV = "JAX_COMPILATION_CACHE_DIR"


def test_environment_dir_is_used_and_nothing_set(monkeypatch, tmp_path):
    calls = []
    monkeypatch.setattr(jax.config, "update", lambda *a: calls.append(a))
    monkeypatch.setenv(ENV, str(tmp_path))
    assert jax_cache.enable_compile_cache() == str(tmp_path)
    assert calls == []


def test_default_dir_is_one_fixed_path_in_the_checkout(monkeypatch):
    calls = []
    monkeypatch.setattr(jax.config, "update", lambda *a: calls.append(a))
    monkeypatch.delenv(ENV, raising=False)
    want = str(pathlib.Path(__file__).resolve().parents[1] / ".jax_cache")
    assert jax_cache.enable_compile_cache() == want
    assert jax_cache.enable_compile_cache() == want
    assert calls == [("jax_compilation_cache_dir", want)] * 2
