"""The persistent-compilation-cache rule (utils/compile_cache.py):
`JAX_COMPILATION_CACHE_DIR` verbatim where set, `<checkout>/.jax_cache`
otherwise and never a temporary path; off on the CPU backend; JAX's own
hit/write events counted; and corrupt-entry scrubbing (a killed writer
leaves a truncated entry)."""

import os
import subprocess
import sys
import tempfile
import zlib
from pathlib import Path

import jax
import pytest

from oobleck_tpu.utils import compile_cache, metrics
from oobleck_tpu.utils.compile_cache import (
    ENV_DIR,
    ensure_persistent_cache,
    persistent_cache_dir,
    scrub_persistent_cache,
)

REPO = Path(__file__).resolve().parents[1]


def test_env_dir_is_taken_verbatim(monkeypatch, tmp_path):
    monkeypatch.setenv(ENV_DIR, str(tmp_path / "custom"))
    assert persistent_cache_dir() == str(tmp_path / "custom")
    # no creation, no chmod: the operator owns that directory
    assert not (tmp_path / "custom").exists()


def test_default_dir_is_the_checkout_never_a_temp_path(monkeypatch):
    monkeypatch.delenv(ENV_DIR, raising=False)
    d = persistent_cache_dir()
    assert d == str(REPO / ".jax_cache")
    assert not d.startswith(tempfile.gettempdir() + os.sep)
    assert ".jax_cache/" in (REPO / ".gitignore").read_text().splitlines()
    src = Path(compile_cache.__file__).read_text()
    assert "gettempdir" not in src and "getuser" not in src


def test_two_processes_resolve_the_same_dir(tmp_path):
    """Different cwd, different pid, same answer — what lets one process
    read what another wrote."""
    env = {k: v for k, v in os.environ.items() if k != ENV_DIR}
    env["PYTHONPATH"] = str(REPO)
    code = ("import os\n"
            "from oobleck_tpu.utils.compile_cache import persistent_cache_dir\n"
            "print(os.getpid(), persistent_cache_dir())")
    outs = []
    for cwd in (tmp_path, REPO / "tests"):
        out = subprocess.run([sys.executable, "-c", code], cwd=cwd, env=env,
                             check=True, capture_output=True, text=True,
                             timeout=60).stdout.split()
        outs.append(out)
    (pid_a, dir_a), (pid_b, dir_b) = outs
    assert pid_a != pid_b
    assert dir_a == dir_b == str(REPO / ".jax_cache")


@pytest.mark.parametrize("env_set", [False, True])
def test_cpu_backend_switches_the_cache_off(monkeypatch, tmp_path, env_set):
    """Whoever set the directory: a CPU world must not read a warm entry."""
    if env_set:
        monkeypatch.setenv(ENV_DIR, str(tmp_path))
    else:
        monkeypatch.delenv(ENV_DIR, raising=False)
    assert jax.default_backend() == "cpu"
    dir_before = jax.config.jax_compilation_cache_dir
    assert ensure_persistent_cache() is None
    assert jax.config.jax_enable_compilation_cache is False
    assert jax.config.jax_compilation_cache_dir == dir_before


@pytest.mark.parametrize("env_set", [False, True])
def test_accelerator_process_follows_the_rule(monkeypatch, tmp_path, env_set):
    """Env var set: no code sets a directory. Unset: the checkout's. Either
    way JAX's hit and write events land in the metrics registry."""
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    monkeypatch.setattr(compile_cache, "_listening", False)
    monkeypatch.setattr(compile_cache, "scrub_persistent_cache",
                        lambda d: 0)
    listeners, timers = [], []
    monkeypatch.setattr(jax.monitoring, "register_event_listener",
                        listeners.append)
    monkeypatch.setattr(jax.monitoring,
                        "register_event_duration_secs_listener",
                        timers.append)
    updates = {}
    monkeypatch.setattr(jax.config, "update", updates.__setitem__)
    if env_set:
        monkeypatch.setenv(ENV_DIR, str(tmp_path))
        want = str(tmp_path)
    else:
        monkeypatch.delenv(ENV_DIR, raising=False)
        want = str(REPO / ".jax_cache")

    assert ensure_persistent_cache() == want
    assert ensure_persistent_cache() == want  # idempotent
    assert updates == ({} if env_set else {"jax_compilation_cache_dir": want})
    assert len(listeners) == 1 and len(timers) == 1

    ctr = metrics.registry().counter("oobleck_compile_cache_events_total")
    read0 = ctr.value(event="entry_read")
    wrote0 = ctr.value(event="entry_written")
    listeners[0]("/jax/compilation_cache/cache_hits")
    listeners[0]("/jax/compilation_cache/cache_misses")
    listeners[0]("/jax/compilation_cache/cache_misses")
    listeners[0]("/jax/some/other/event")
    assert ctr.value(event="entry_read") - read0 == 1
    assert ctr.value(event="entry_written") - wrote0 == 2

    secs = metrics.registry().counter("oobleck_compile_seconds_total")
    before = secs.value()
    timers[0]("/jax/core/compile/backend_compile_duration", 1.5, fun_name="f")
    timers[0]("/jax/core/compile/jaxpr_trace_duration", 9.0, fun_name="f")
    assert secs.value() - before == 1.5


def test_scrub_evicts_truncated_entry(tmp_path):
    """Regression for the PR 2 failure mode: a deliberately truncated
    compressed entry (what a killed writer leaves) must be deleted; valid
    and unvalidatable entries must survive."""
    good = zlib.compress(b"serialized executable " * 64)
    (tmp_path / "jit_good-k1-cache").write_bytes(good)
    truncated = zlib.compress(b"poisoned payload " * 256)[:23]
    (tmp_path / "jit_truncated-k2-cache").write_bytes(truncated)
    # Unknown format: not provably corrupt -> must be left alone.
    (tmp_path / "jit_unknown-k3-cache").write_bytes(b"\x00\x01not-compressed")
    # Empty entry: a crash mid-write -> corrupt.
    (tmp_path / "jit_empty-k4-cache").write_bytes(b"")

    assert scrub_persistent_cache(str(tmp_path), force=True) == 2
    assert (tmp_path / "jit_good-k1-cache").read_bytes() == good
    assert (tmp_path / "jit_unknown-k3-cache").exists()
    assert not (tmp_path / "jit_truncated-k2-cache").exists()
    assert not (tmp_path / "jit_empty-k4-cache").exists()


def test_scrub_leaves_jax_bookkeeping_alone(tmp_path):
    """Found on the chip (PR 21): with a size cap JAX keeps an 8-byte
    `-atime` clock beside each entry. Its first byte is arbitrary — one in
    256 looks like a zlib header — and deleting it makes every later cache
    write in the process fail. Only `-cache` files are entries."""
    atime = tmp_path / "jit_step-k1-atime"
    atime.write_bytes(b"\x78" + b"\x00" * 7)        # "corrupt zlib", if asked
    (tmp_path / "not_an_entry").write_bytes(b"")      # "empty entry", if asked
    assert scrub_persistent_cache(str(tmp_path), force=True) == 0
    assert atime.exists() and (tmp_path / "not_an_entry").exists()
    assert compile_cache.cache_entries(str(tmp_path)) == 0


def test_scrub_is_incremental_via_stamp(tmp_path):
    """Entries older than the stamp are skipped; new corruption is still
    caught by the next scrub."""
    (tmp_path / "jit_old-k1-cache").write_bytes(zlib.compress(b"x" * 100))
    assert scrub_persistent_cache(str(tmp_path), force=True) == 0
    assert (tmp_path / ".oobleck_scrub_stamp").exists()
    bad = zlib.compress(b"poisoned " * 128)[:17]
    (tmp_path / "jit_new-k2-cache").write_bytes(bad)
    os.utime(tmp_path / "jit_new-k2-cache")  # strictly newer than the stamp
    assert scrub_persistent_cache(str(tmp_path)) == 1
    assert not (tmp_path / "jit_new-k2-cache").exists()
    assert (tmp_path / "jit_old-k1-cache").exists()


def test_scrub_missing_dir_is_noop(tmp_path):
    assert scrub_persistent_cache(str(tmp_path / "nope")) == 0
