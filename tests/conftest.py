"""Test harness: run everything on a virtual 8-device CPU mesh.

Mirrors the reference's multi-process single-machine simulation strategy
(/root/reference/tests/conftest.py:347-474, which fakes multi-node with
CUDA_VISIBLE_DEVICES pinning): here a single process gets 8 virtual XLA CPU
devices via --xla_force_host_platform_device_count, and multi-host scenarios
are expressed as sub-meshes of those devices.

This must run before any other module imports jax and triggers backend init.

The suite runs WITHOUT JAX's persistent compilation cache, whatever the
environment says: on the CPU backend a warm entry of a multi-device program
aborts the process (utils/compile_cache.py has the finding), and one abort
costs every test after it.
"""

import os

os.environ["XLA_FLAGS"] = (
    os.environ.get("XLA_FLAGS", "") + " --xla_force_host_platform_device_count=8"
)

import jax

jax.config.update("jax_platforms", "cpu")


from oobleck_tpu.execution.pipeline import PROGRAMS
from oobleck_tpu.utils.compile_cache import ensure_persistent_cache

assert ensure_persistent_cache() is None  # CPU backend: switched off

import numpy as np
import pytest


# Compile-bound modules that sort late in the alphabet. Collected in place,
# they are the last thing a pytest-xdist run hands out, and one worker
# finishes them alone while the others idle (minutes, against a fixed wall
# budget); a serial run is cut inside tests/execution/ either way. So they
# run right after the engine tests, and the many fast tests fill the tail.
_HEAVY_LATE = ("tests/models/", "tests/serve/", "tests/test_ops.py",
               "tests/test_overlap.py", "tests/test_smoke.py",
               "tests/test_train_spmd.py")


def pytest_configure(config):
    """Under pytest-xdist, keep a module on one worker unless the caller
    chose a --dist mode: `-n N` alone means `--dist load`, which deals a
    module's tests out to every worker — and each then builds the module's
    shared engine fixture and compiles its programs for itself (jit caches
    are per process, and the persistent cache is off on the CPU)."""
    chosen = any(a.startswith("--dist") or a == "-d"
                 for a in config.invocation_params.args)
    if getattr(config.option, "dist", "no") == "load" and not chosen:
        config.option.dist = "loadfile"


def pytest_collection_modifyitems(items):
    def rank(item):
        path = item.nodeid.split("::", 1)[0]
        if path.startswith(_HEAVY_LATE):
            return "tests/execution/test_reconfigure.py~"
        return path

    items.sort(key=rank)  # stable: order inside a module is kept


@pytest.fixture(scope="module", autouse=True)
def _clear_jax_caches():
    """Drop compiled-executable caches between test modules: the full suite
    compiles hundreds of programs over 8 virtual devices and can exhaust
    host memory in a single process otherwise. The process's table of
    jitted programs goes with them, so engines share programs within a
    module (which `--dist loadfile` keeps on one worker) and no further."""
    yield
    PROGRAMS.clear()
    jax.clear_caches()


@pytest.fixture(scope="module", autouse=True)
def _empty_flight_ring():
    """Every test module starts from an empty flight ring. The ring is
    process-global and bounded (256 events); tests count the events an
    action added (`events()[n0:]`, `len(...) == before + 1`), which reads
    nothing new once the modules a worker ran earlier have filled it."""
    from oobleck_tpu.utils import metrics

    metrics.flight_recorder().clear()
    yield


@pytest.fixture(scope="session")
def devices8():
    devs = jax.devices()
    assert len(devs) >= 8, f"expected 8 virtual CPU devices, got {len(devs)}"
    return devs[:8]


@pytest.fixture(scope="session")
def rng():
    return jax.random.PRNGKey(42)


@pytest.fixture
def np_rng():
    return np.random.default_rng(42)
