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

Every test process, and a child that keeps its environment, builds XLA:CPU
programs at LLVM level 1, not the default: the models here are tiny, so the
optimiser buys seconds of run time and costs a sixth of the suite in compile
(PR 61, seven dear files in one hour, 378 tests passing each time, junit s:
default 807, level 1 663, level 0 538; PERF.md section 7 has the whole runs).
Level 0 waits for tests/benchmarks/test_bench_nemotron_h.py's `two_blocks`
(ROADMAP.md D24). It says nothing of a chip: the TPU compiler never sees it.
"""

import os

# Appended, so they win over a caller's; in the environment, so a child that
# keeps it builds as the tests do (the sacrificial children of tests/ckpt/,
# tests/serve/ and tests/elastic/). The `-m slow` worlds set XLA_FLAGS anew
# and tests/benchmarks/test_bench_run.py's children drop it: the default level.
XLA_FLAGS = ("--xla_force_host_platform_device_count=8",
             "--xla_backend_optimization_level=1")
os.environ["XLA_FLAGS"] = " ".join([os.environ.get("XLA_FLAGS", ""), *XLA_FLAGS])

import jax

jax.config.update("jax_platforms", "cpu")
# One-device programs run inline, on the caller's thread. Dispatched
# asynchronously, one compiled program's calls gave one of two outputs an ulp
# apart, the rare one in 2 % of calls beside busy processes, and a test that
# holds two calls to bits was red in one run of eight (PR 61, 29 of 240 runs
# of tests/benchmarks/test_bench_deepseek_v3.py's first 44 tests; 0 of 120 so).
jax.config.update("jax_cpu_enable_async_dispatch", False)


from oobleck_tpu.execution.pipeline import PROGRAMS
from oobleck_tpu.ops import attention, kernel, paged_attention
from oobleck_tpu.utils.compile_cache import ensure_persistent_cache

assert ensure_persistent_cache() is None  # CPU backend: switched off

import numpy as np
import pytest


# The files over 100 s of test time, dearest first, then the alphabet: under
# `--dist loadfile` a file is one worker's from start to end, and a 300 s file
# handed out when the others have 100 s left ends the run 200 s late. From the
# junit file of the driver's command on PR 61's tree (2026-10-04 09:51 UTC,
# six workers on 8 cores, 3,095 passed in 1,133 s, 6,663 s of test time), s in
# the tuple's order: 276 251 209 209 209 203 201 197 195 188 188 187 185 181
# 173 157 154 154 153 149 147 138 138 135 125 125 119 110 108 102 101. Stale
# when a file here has halved or one outside has passed 150 s.
DEAREST_FIRST = (
    "tests/ops/test_tpu_compile_routed.py",
    "tests/benchmarks/test_bench_qwen3_next.py",
    "tests/models/test_qwen3_next.py", "tests/ops/test_routed_experts.py",
    "tests/benchmarks/test_bench_phi4flash.py", "tests/test_overlap.py",
    "tests/test_ops.py", "tests/models/test_lfm2.py",
    "tests/execution/test_engine_families.py",
    "tests/benchmarks/test_bench_hostloss.py",
    "tests/models/test_smallthinker.py", "tests/ops/test_remat_cells_c.py",
    "tests/serve/test_speculative.py", "tests/models/test_families.py",
    "tests/benchmarks/test_bench_nemotron_h.py",
    "tests/benchmarks/test_bench_smallthinker.py",
    "tests/ops/test_remat_cells_b.py", "tests/test_train_spmd.py",
    "tests/models/test_phi4flash.py", "tests/execution/test_exec_args.py",
    "tests/execution/test_engine_reconfig.py", "tests/ops/test_tpu_compile.py",
    "tests/ops/test_remat_cells_a.py", "tests/models/test_nemotron_h.py",
    "tests/models/test_deepseek_v3.py", "tests/serve/test_paged_parity.py",
    "tests/execution/test_programs.py",
    "tests/execution/test_pipeline_mpmd.py", "tests/ops/test_gdn.py",
    "tests/execution/test_precompile.py",
    "tests/execution/test_kernel_grad_sums.py")


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
    # xdist >= 3.7 otherwise hands files out by their NUMBER of tests, most
    # first: a file of three 50 s compiles would go last of all.
    config.option.loadscopereorder = False


def pytest_collection_modifyitems(items):
    place = {path: i for i, path in enumerate(DEAREST_FIRST)}

    def rank(item):
        path = item.nodeid.split("::", 1)[0]
        return place.get(path, len(place)), path  # then the alphabet

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


@pytest.fixture(scope="module", autouse=True)
def _empty_metrics_registry():
    """Every test module starts from an empty metrics registry, as it
    starts from an empty flight ring. The registry is process-global and a
    family's series outlive the test that made them: a reader that takes
    every series of a family (`benchmarks/readers/moe_gmm_roofline_pct.py`:
    pairs a token by routed layer) read a THIRD layer, left by whichever
    routed model the worker had run before, in one whole run of two
    (`test_bench_deepseek_v3.py::test_runner_control_flow_on_the_cpu`,
    PR 63), by how `--dist loadfile` happened to deal the files out."""
    from oobleck_tpu.utils import metrics

    metrics.registry().clear()
    yield


@pytest.fixture
def as_on_tpu(monkeypatch):
    """Every kernel module takes its kernels' path, compiled, as on a TPU:
    for a trace nothing runs of, or a compile for a described chip. ONE
    name decides for all of them (`ops/kernel.on_tpu`). What a process
    keeps of the decision (the "auto" choices, resolved once; the stage
    programs, whose key leaves the backend out) is forgotten before and
    after: no test is handed, or hands on, a choice made under the other
    answer."""
    def forget():
        for choice in (attention.select_attention_impl,
                       paged_attention._select_paged_impl,
                       paged_attention._select_paged_verify_impl):
            choice.cache_clear()
        PROGRAMS.clear()

    monkeypatch.setattr(kernel, "on_tpu", lambda: True)
    forget()
    yield
    forget()


@pytest.fixture
def kernels_interpreted(as_on_tpu, monkeypatch):
    """The kernels' path as on a TPU, in Pallas's interpreter: the kernels'
    arithmetic, on the CPU."""
    monkeypatch.setattr(kernel, "interpret", lambda: True)


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
