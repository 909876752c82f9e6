"""Where the drivers put JAX's persistent compile cache (never turned on
here: tests only read the chosen path)."""
import jax

from repro.launch import compile_cache as cc


def test_env_var_wins_and_nothing_is_set(monkeypatch, tmp_path):
    monkeypatch.setenv(cc.ENV_VAR, str(tmp_path))
    before = jax.config.jax_compilation_cache_dir
    assert cc.compile_cache_dir() == str(tmp_path)
    assert cc.use_compile_cache() == str(tmp_path)
    assert jax.config.jax_compilation_cache_dir == before


def test_default_is_fixed_under_repo_root(monkeypatch):
    monkeypatch.delenv(cc.ENV_VAR, raising=False)
    path = cc.compile_cache_dir()
    assert path == str(cc.REPO_ROOT / ".jax_cache")
    assert (cc.REPO_ROOT / "pyproject.toml").is_file()
    assert cc.compile_cache_dir() == path         # no pid, time or tmp name
