"""Every benchmark test leaves the environment and JAX's settings as it
found them: the harness points the persistent compile cache at the
checkout and caches every program, which is right for its own process
and must not reach the repository's other tests or their subprocesses."""
import jax
import pytest


@pytest.fixture(autouse=True)
def _cache_settings_restored(monkeypatch, tmp_path):
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path / "jax_cache"))
    monkeypatch.setenv("TPU_LOG_DIR", "disabled")
    keep = jax.config.jax_persistent_cache_min_compile_time_secs
    yield
    jax.config.update("jax_persistent_cache_min_compile_time_secs", keep)
