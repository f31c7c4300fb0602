"""Step timing.

Capability match for the reference's measure_time decorator around
deepspeed's SynchronizedWallClockTimer (/root/reference/oobleck/utils/
timer.py:8-21): wall-clock accumulation per named region, reported by the
engine every 10 steps. No deepspeed here — a plain monotonic-clock
accumulator; device-side sync is the caller's readback (see
profiler._sync).

Thread-safe: the step path mutates the accumulators from the training
thread while ``sync_timers()`` reads them from logging/metrics paths (and
the live-mirror writer runs off-thread), so every access goes through one
module lock and readers get copies. Each observation is also routed into
the metrics registry (``oobleck_timer_seconds{region=...}``) so timer
regions appear in /metrics alongside the engine gauges.
"""

from __future__ import annotations

import functools
import threading
import time
from collections import defaultdict
from dataclasses import dataclass

from oobleck_tpu.utils import metrics


@dataclass
class TimerStats:
    count: int = 0
    total_s: float = 0.0
    last_s: float = 0.0

    @property
    def mean_s(self) -> float:
        return self.total_s / self.count if self.count else 0.0

    def __repr__(self) -> str:
        return (f"TimerStats(n={self.count}, last={self.last_s*1e3:.1f}ms, "
                f"mean={self.mean_s*1e3:.1f}ms)")

    def copy(self) -> "TimerStats":
        return TimerStats(self.count, self.total_s, self.last_s)


_lock = threading.Lock()
_timers: dict[str, TimerStats] = defaultdict(TimerStats)


def _histogram() -> metrics.Histogram:
    return metrics.registry().histogram(
        "oobleck_timer_seconds", "Wall time of named engine regions")


def record(name: str, seconds: float) -> None:
    """Record one observation for region `name`."""
    with _lock:
        st = _timers[name]
        st.count += 1
        st.total_s += seconds
        st.last_s = seconds
    _histogram().observe(seconds, region=name)


def measure_time(name: str):
    """Decorator: accumulate wall time of each call under `name`."""

    def deco(fn):
        @functools.wraps(fn)
        def wrapper(*args, **kwargs):
            t0 = time.perf_counter()
            try:
                return fn(*args, **kwargs)
            finally:
                record(name, time.perf_counter() - t0)
        return wrapper

    return deco


def sync_timers() -> dict[str, TimerStats]:
    """Copies, not live references: a caller iterating the result must not
    race the step thread's in-place mutation."""
    with _lock:
        return {name: st.copy() for name, st in _timers.items()}


def reset_timers() -> None:
    with _lock:
        _timers.clear()
