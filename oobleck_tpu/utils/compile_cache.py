"""One rule for JAX's persistent compilation cache.

  * `JAX_COMPILATION_CACHE_DIR` set: JAX reads it itself and that is the
    whole mechanism. No code here, or anywhere in the repo, sets another
    directory.
  * unset: the cache is `<checkout>/.jax_cache` (git-ignored). The path is
    fixed beside the code because it is what the next process has to find
    again: it never depends on a temporary directory, a user, a pid, the
    host CPU or the clock.

Every process that owns an accelerator — the worker, the serve engine,
chip_smoke.py's children — calls `ensure_persistent_cache()`
before it compiles.

On the CPU backend the cache is switched OFF instead. With this jax/jaxlib
a warm XLA:CPU entry of a multi-device program (the 8-virtual-device fused
step's `init_fn`/`step_fn`) aborts the process inside the first loss
readback — not every time (10 of 20 warm runs of tests/test_smoke.py -k
fused; 0 of 10 cold or cache-off runs), with intact entries written by the
same machine. An abort takes a whole pytest process with it, so a CPU
world must not read such an entry at all, whoever set the directory.

On a TPU one kind of executable must not come back from the cache either,
and `compile_unpersisted` compiles it so that no entry is left: a program
with a collective over a SUBSET of the process's chips (PR 63: the
gradient sum between two pipelines' chips, `execution/engine.
dp_sum_program`). Compiled in the process it runs; where two such programs
(chips 0, 2 and 1, 3 of a v5e 2x2; 0, 1 and 2, 3 the same) were READ from
the cache, the first run halted the chip ("Core halted unexpectedly ...
schecklt: Invalid logical z: enhanced-barrier-parent-phase-1", then "The
program continuator has halted unexpectedly" at the next readback) in
every one of five warm processes, where one such program alone (0, 2) and
a program over all four chips read back sound (jax / jaxlib 0.9.0,
libtpu 0.0.34; `chiprun_out/t63c`, `t63d`).
"""

from __future__ import annotations

import logging
import os
import zlib
from pathlib import Path

logger = logging.getLogger("oobleck.compile_cache")

ENV_DIR = "JAX_COMPILATION_CACHE_DIR"
# For the environment of a CPU child process (multi-process test worlds,
# the multichip dry run): they compile before any oobleck code can switch
# the cache off for them.
CPU_WORLD_ENV = {"JAX_ENABLE_COMPILATION_CACHE": "false"}

# Compressed-entry magics: jax's compilation cache compresses serialized
# executables with zstandard when importable, zlib otherwise.
_ZSTD_MAGIC = b"\x28\xb5\x2f\xfd"
_SCRUB_STAMP = ".oobleck_scrub_stamp"
# JAX names an executable `<module>-<key>-cache`; beside it, where the cache
# has a size cap, sits `<module>-<key>-atime`, eight raw bytes of clock that
# belong to JAX's eviction and are nobody's to validate.
_ENTRY_SUFFIX = "-cache"

_JAX_EVENTS = {
    "/jax/compilation_cache/cache_hits": "entry_read",
    "/jax/compilation_cache/cache_misses": "entry_written",
}
_JAX_COMPILE_SECONDS = "/jax/core/compile/backend_compile_duration"
_listening = False


def cache_event(event: str, n: int = 1) -> None:
    """Count one persistent-cache event in the metrics registry.
    `entry_read` / `entry_written` are JAX's own hit and write events;
    enabled/disabled come from ensure_persistent_cache, hit/miss from the
    recovery precompiler's walk."""
    if n <= 0:
        return
    from oobleck_tpu.utils import metrics

    metrics.registry().counter(
        "oobleck_compile_cache_events_total",
        "Persistent compile-cache events by kind").inc(n, event=event)


def _on_jax_event(event: str, **_) -> None:
    if event in _JAX_EVENTS:
        cache_event(_JAX_EVENTS[event])


def _on_jax_duration(event: str, seconds: float, **_) -> None:
    if event == _JAX_COMPILE_SECONDS:
        from oobleck_tpu.utils import metrics

        metrics.registry().counter(
            "oobleck_compile_seconds_total",
            "Seconds spent obtaining XLA executables (backend compiles and "
            "persistent-cache reads)").inc(seconds)


def persistent_cache_dir() -> str:
    """`JAX_COMPILATION_CACHE_DIR` verbatim, else `<checkout>/.jax_cache`."""
    return os.environ.get(ENV_DIR) or str(
        Path(__file__).resolve().parents[2] / ".jax_cache")


def cache_entries(d: str | None = None) -> int:
    """Executables persisted under `d` (bookkeeping files excluded)."""
    try:
        return sum(1 for n in os.listdir(d or persistent_cache_dir())
                   if n.endswith(_ENTRY_SUFFIX))
    except OSError:
        return 0


def _entry_corrupt(path: str) -> bool:
    """True when a cache entry is PROVABLY corrupt: empty, or a truncated/
    damaged compressed stream. Entries in a format we cannot validate
    (zstd without the zstandard module, or an unrecognized header) are
    left alone — eviction must never eat a valid entry."""
    try:
        with open(path, "rb") as f:
            blob = f.read()
    except OSError:
        return False
    if not blob:
        return True  # a crash mid-write left an empty entry
    if blob[:4] == _ZSTD_MAGIC:
        try:
            import zstandard
        except ImportError:
            return False
        try:
            dec = zstandard.ZstdDecompressor().decompressobj()
            for i in range(0, len(blob), 1 << 20):
                dec.decompress(blob[i:i + (1 << 20)])
            return False
        except zstandard.ZstdError:
            return True
    if blob[0] != 0x78:  # zlib header byte
        return False
    try:
        dec = zlib.decompressobj()
        for i in range(0, len(blob), 1 << 20):
            dec.decompress(blob[i:i + (1 << 20)])
        # A truncated stream decompresses without error but never reaches
        # EOF — the exact state a killed writer leaves behind, and the one
        # that wedges deserialization at use time.
        return not dec.eof
    except zlib.error:
        return True


def scrub_persistent_cache(d: str | None = None, *, force: bool = False) -> int:
    """Detect and evict corrupt persistent-cache entries.

    JAX writes an entry in place, so a worker killed mid-write — which this
    system does on purpose — leaves a truncated one. JAX then fails to read
    it on every later start (a warning and a recompile) and never replaces
    it. So every entry newer than the last scrub is validated at startup
    and deleted on failure; JAX recompiles and rewrites it. Returns the
    number evicted.

    Incremental via a stamp file so repeated startups only pay for new
    entries; `force=True` rescans everything."""
    d = d if d is not None else persistent_cache_dir()
    if d is None or not os.path.isdir(d):
        return 0
    stamp = os.path.join(d, _SCRUB_STAMP)
    last = 0.0
    if not force:
        try:
            last = os.stat(stamp).st_mtime
        except OSError:
            pass
    evicted = 0
    for name in os.listdir(d):
        if not name.endswith(_ENTRY_SUFFIX):
            continue
        path = os.path.join(d, name)
        try:
            st = os.stat(path)
        except OSError:
            continue
        if not os.path.isfile(path) or (not force and st.st_mtime < last):
            continue
        if _entry_corrupt(path):
            try:
                os.unlink(path)
            except OSError:
                continue
            evicted += 1
            logger.warning(
                "evicted corrupt persistent-cache entry %s (%d B): "
                "deserialization would fail or hang; it will recompile",
                name, st.st_size)
    try:
        with open(stamp, "w") as f:
            f.write("scrub marker; entries older than this mtime are validated\n")
    except OSError:
        pass
    cache_event("evicted_corrupt", evicted)
    return evicted


def ensure_persistent_cache() -> str | None:
    """Apply the module's rule to this process; idempotent. Returns the
    directory in use, or None on the CPU backend, where the cache is off.

    Touches the backend (`jax.default_backend()`): call it from the process
    that owns the chip, after `jax.distributed.initialize` where there is
    one."""
    global _listening
    import jax

    if jax.default_backend() == "cpu":
        if jax.config.jax_enable_compilation_cache:
            jax.config.update("jax_enable_compilation_cache", False)
            cache_event("disabled")
        return None
    d = persistent_cache_dir()
    if not _listening:
        # First enable in this process: validate entries written since the
        # last scrub before anything deserializes them.
        _listening = True
        scrub_persistent_cache(d)
        if not os.environ.get(ENV_DIR):
            jax.config.update("jax_compilation_cache_dir", d)
        jax.monitoring.register_event_listener(_on_jax_event)
        jax.monitoring.register_event_duration_secs_listener(_on_jax_duration)
        cache_event("enabled")
    return d


def compile_unpersisted(jitted, *operands):
    """`jitted.lower(*operands).compile()`, leaving no entry in the
    persistent cache, so that no later process reads the executable back
    (the module's docstring says which executables, and why). JAX writes an
    entry only where the compile took `jax_persistent_cache_min_compile_
    time_secs` at the least: that is raised for the length of this compile.
    The flag is the process's: call it where other compiles are held off
    (`utils/background.device_work`), as every caller does. The caller
    keeps the executable for the life of the process."""
    import jax

    name = "jax_persistent_cache_min_compile_time_secs"
    before = getattr(jax.config, name)
    jax.config.update(name, float("inf"))
    try:
        return jitted.lower(*operands).compile()
    finally:
        jax.config.update(name, before)
