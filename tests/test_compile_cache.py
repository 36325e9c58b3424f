"""The persistent compilation cache is placed from outside: the
environment's directory when set, else a fixed path in the checkout."""
import jax

from repro.launch import compile_cache


def _restore(old):
    jax.config.update("jax_compilation_cache_dir", old)


def test_env_dir_wins_and_nothing_else_is_set(monkeypatch, tmp_path):
    old = jax.config.jax_compilation_cache_dir
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    try:
        jax.config.update("jax_compilation_cache_dir", None)
        assert compile_cache.enable_compile_cache() == str(tmp_path)
        # JAX reads the variable itself; the helper sets no directory.
        assert jax.config.jax_compilation_cache_dir is None
    finally:
        _restore(old)


def test_default_is_fixed_path_in_checkout(monkeypatch):
    old = jax.config.jax_compilation_cache_dir
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    try:
        path = compile_cache.enable_compile_cache()
        assert path == str(compile_cache.DEFAULT_DIR)
        assert jax.config.jax_compilation_cache_dir == path
        # the checkout root: the directory holding src/ and tests/
        root = compile_cache.DEFAULT_DIR.parent
        assert (root / "src" / "repro").is_dir()
        assert (root / "tests").is_dir()
        assert compile_cache.DEFAULT_DIR.name == ".jax_cache"
        # the same path on every call: no pid, time or temp directory
        assert compile_cache.enable_compile_cache() == path
    finally:
        _restore(old)
