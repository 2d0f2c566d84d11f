"""The persistent compilation cache lives where a rerun finds it again."""
from pathlib import Path

import jax

from repro.launch import compile_cache

ROOT = Path(__file__).resolve().parent.parent


def _record_updates(monkeypatch):
    seen = []
    monkeypatch.setattr(jax.config, "update", lambda *a: seen.append(a))
    return seen


def test_env_dir_is_left_to_jax(monkeypatch, tmp_path):
    monkeypatch.setenv(compile_cache.CACHE_ENV, str(tmp_path))
    seen = _record_updates(monkeypatch)
    assert compile_cache.enable_compile_cache() == str(tmp_path)
    assert seen == []


def test_default_dir_is_fixed_inside_the_repo(monkeypatch):
    monkeypatch.delenv(compile_cache.CACHE_ENV, raising=False)
    seen = _record_updates(monkeypatch)
    path = compile_cache.enable_compile_cache()
    assert path == str(ROOT / ".jax_cache")
    assert compile_cache.enable_compile_cache() == path
    assert seen == [("jax_compilation_cache_dir", path)] * 2
