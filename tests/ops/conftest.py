"""What several files of tests/ops ask for by name (the fixtures that steer
the backend decision itself are `tests/conftest.py`'s)."""

import jax
import pytest
from jax.experimental.compilation_cache import compilation_cache


@pytest.fixture
def compiled_for_tpu(as_on_tpu):
    """For a compile for a described chip: kernels lower through Mosaic
    (not the interpreter), and the persistent cache stays out of it: an
    executable for an unattached device is written but can never be read
    back, and warns on the way."""
    before = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", before)
    compilation_cache.reset_cache()
