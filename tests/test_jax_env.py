"""Process settings: where ``repro.config.jax_env`` puts the compile cache,
and the scoped float64 the jax fleet engine runs under."""
import jax
import jax.numpy as jnp
import pytest

from repro.config import jax_env


@pytest.fixture
def restore_cache_dir():
    was = jax.config.jax_compilation_cache_dir
    yield
    jax.config.update("jax_compilation_cache_dir", was)


def test_compile_cache_uses_env_dir(monkeypatch, restore_cache_dir, tmp_path):
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    jax.config.update("jax_compilation_cache_dir", str(tmp_path))
    assert jax_env.use_compile_cache() == str(tmp_path)
    # the directory JAX read from the environment is left alone
    assert jax.config.jax_compilation_cache_dir == str(tmp_path)


def test_compile_cache_fixed_in_checkout(monkeypatch, restore_cache_dir):
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    first = jax_env.use_compile_cache()
    assert jax_env.use_compile_cache() == first
    assert jax.config.jax_compilation_cache_dir == first
    checkout = jax_env.Path(jax_env.__file__).resolve().parents[3]
    assert jax_env.Path(first) == checkout / ".jax_cache"
    gitignore = (checkout / ".gitignore").read_text().split()
    assert ".jax_cache/" in gitignore


def test_x64_scope_is_scoped(monkeypatch):
    """The jax fleet engine's entry points compute in float64 and leave the
    process in jax's default float32."""
    from repro.fleet import jax_engine
    seen = []

    def probe(*args):
        seen.append(jnp.zeros(1).dtype)

    monkeypatch.setattr(jax_engine._JaxFleetEngine, "_play", probe)
    monkeypatch.setattr(jax_engine, "_sweep", probe)
    assert jnp.zeros(1).dtype == jnp.float32
    engine = object.__new__(jax_engine._JaxFleetEngine)
    engine.play([1.0])
    jax_engine.sweep([], [object()], [1.0])
    assert seen == [jnp.float64, jnp.float64]
    assert jnp.zeros(1).dtype == jnp.float32
