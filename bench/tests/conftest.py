import jax
import pytest

_CACHE_OPTIONS = (
    "jax_compilation_cache_dir",
    "jax_persistent_cache_min_compile_time_secs",
    "jax_persistent_cache_min_entry_size_bytes",
)


@pytest.fixture(autouse=True)
def _restore_compile_cache():
    """A harness run turns JAX's persistent compilation cache on inside its
    (temporary) root; put the process's settings back after each test."""
    saved = {k: getattr(jax.config, k) for k in _CACHE_OPTIONS}
    yield
    for k, v in saved.items():
        jax.config.update(k, v)
    from jax.experimental.compilation_cache import compilation_cache

    compilation_cache.reset_cache()
