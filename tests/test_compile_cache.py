"""The persistent compilation cache: the env var wins, else a fixed path."""
import jax
import pytest

from repro.launch import compile_cache as CC


@pytest.fixture
def restore_cache_dir():
    was = jax.config.jax_compilation_cache_dir
    yield
    jax.config.update("jax_compilation_cache_dir", was)


def test_env_var_is_left_to_jax(monkeypatch, tmp_path, restore_cache_dir):
    monkeypatch.setenv(CC.ENV_VAR, str(tmp_path))
    jax.config.update("jax_compilation_cache_dir", "/sentinel")
    assert CC.enable_compile_cache() == str(tmp_path)
    assert jax.config.jax_compilation_cache_dir == "/sentinel"


def test_default_is_the_fixed_repo_path(monkeypatch, restore_cache_dir):
    monkeypatch.delenv(CC.ENV_VAR, raising=False)
    d = CC.enable_compile_cache()
    assert d == str(CC.REPO_CACHE_DIR)
    assert CC.REPO_CACHE_DIR.name == ".jax_cache"
    assert (CC.REPO_CACHE_DIR.parent / "chip_smoke.py").is_file()
    assert jax.config.jax_compilation_cache_dir == d
