"""Per-step per-host telemetry: the continuous fleet-health sample stream.

The metrics plane (utils/metrics.py) aggregates; the span plane
(obs/spans.py) explains single incidents. What neither provides is a
CONTINUOUS per-host signal the master can compare across the fleet — the
stream that makes a host that is alive-but-slow (a gray failure: thermal
throttling, a dying NIC, a noisy neighbor) visible *before* its heartbeat
deadline ever fires. This module is that stream's host-local half.

Design constraints, in order:

1.  **Zero host syncs.** Every value recorded here is a host-side float
    the caller already had (``time.perf_counter`` deltas, queue depths,
    shape metadata). Nothing in this module may read back a device value
    — it is covered by oobleck-lint's OBL002/OBL003 fence rules exactly
    like the step loop it instruments, so a readback cannot sneak in.
2.  **Bounded, allocation-light.** Samples land in a preallocated ring
    (a deque of tuples); recording is an append and nothing else.
3.  **Digest, not firehose.** The wire carries a compact windowed digest
    (piggybacked on the agent's existing heartbeat as one extra JSON
    key — legacy masters ignore it), never raw samples.

Knobs:
    OOBLECK_TELEMETRY=0            disable sampling entirely
    OOBLECK_TELEMETRY_CAPACITY     ring size in samples (default 512)
    OOBLECK_TELEMETRY_WINDOW       samples per digest (default 32)
"""

from __future__ import annotations

import collections
import os
import threading

ENV_TELEMETRY = "OOBLECK_TELEMETRY"
ENV_CAPACITY = "OOBLECK_TELEMETRY_CAPACITY"
ENV_WINDOW = "OOBLECK_TELEMETRY_WINDOW"

DEFAULT_CAPACITY = 512
DEFAULT_WINDOW = 32

# Digest schema version: receivers skip digests they do not understand
# (the same skip-with-warning posture as incident SCHEMA_VERSION).
DIGEST_VERSION = 1

# Sample tuple layout (kept positional: a tuple append is the cheapest
# thing CPython can do per step, and the digest is the only reader).
_STEP, _STEP_S, _COMPUTE_S, _COMM_S, _DATA_WAIT_S, _CKPT_S, _LIVE_BYTES = \
    range(7)


def _env_int(name: str, default: int) -> int:
    raw = os.environ.get(name, "")
    try:
        return int(raw) if raw else default
    except ValueError:
        return default


class TelemetryRing:
    """Bounded per-process sample ring + windowed digest builder.

    ``record_step`` is the hot-path entry point: pure-python tuple append
    under a lock that is uncontended in steady state (the digest reader
    runs on the publish cadence, every ~10 steps). Everything heavier —
    sorting for percentiles, dict building — happens in ``digest()``,
    off the per-step path.
    """

    def __init__(self, capacity: int | None = None,
                 window: int | None = None):
        self.enabled = os.environ.get(ENV_TELEMETRY, "1") != "0"
        if capacity is None:
            capacity = _env_int(ENV_CAPACITY, DEFAULT_CAPACITY)
        if window is None:
            window = _env_int(ENV_WINDOW, DEFAULT_WINDOW)
        self.window = max(window, 1)
        self._lock = threading.Lock()
        self._ring: collections.deque = collections.deque(
            maxlen=max(capacity, 1))

    # -- hot path ----------------------------------------------------------- #

    def record_step(self, step: int, step_s: float, *,
                    compute_s: float = 0.0, comm_s: float = 0.0,
                    data_wait_s: float = 0.0, ckpt_s: float = 0.0,
                    live_bytes: int = 0) -> None:
        """Append one step's host-side timings. All arguments are plain
        host floats the caller already measured — never device values."""
        if not self.enabled:
            return
        with self._lock:
            self._ring.append((step, step_s, compute_s, comm_s,
                               data_wait_s, ckpt_s, live_bytes))

    # -- digest (publish cadence, not per-step) ----------------------------- #

    def digest(self) -> dict | None:
        """Compact summary of the last ``window`` samples, or None when
        nothing was recorded. Short keys: the digest rides every
        heartbeat, so its wire weight is paid ~6x/minute per host."""
        with self._lock:
            tail = list(self._ring)[-self.window:]
        if not tail:
            return None
        n = len(tail)
        steps = sorted(s[_STEP_S] for s in tail)
        return {
            "v": DIGEST_VERSION,
            "n": n,
            "step": tail[-1][_STEP],
            "step_s": round(sum(steps) / n, 6),
            "step_p50_s": round(steps[n // 2], 6),
            "step_max_s": round(steps[-1], 6),
            "compute_s": round(sum(s[_COMPUTE_S] for s in tail) / n, 6),
            "comm_s": round(sum(s[_COMM_S] for s in tail) / n, 6),
            "data_wait_s": round(sum(s[_DATA_WAIT_S] for s in tail) / n, 6),
            "ckpt_s": round(sum(s[_CKPT_S] for s in tail), 6),
            "live_bytes": tail[-1][_LIVE_BYTES],
        }

    def samples(self) -> list[tuple]:
        with self._lock:
            return list(self._ring)

    def __len__(self) -> int:
        with self._lock:
            return len(self._ring)


def digest_ok(d) -> bool:
    """Whether a wire-received digest is one this reader understands —
    the legacy-tolerance gate: absent (old agent) and future-versioned
    digests are both skipped, never errors."""
    return (isinstance(d, dict) and d.get("v") == DIGEST_VERSION
            and isinstance(d.get("step_s"), (int, float)))


_instance: TelemetryRing | None = None


def telemetry() -> TelemetryRing:
    """Process-global ring, built from the env knobs on first use."""
    global _instance
    if _instance is None:
        _instance = TelemetryRing()
    return _instance


def reset(capacity: int | None = None,
          window: int | None = None) -> TelemetryRing:
    """Re-build the global ring (tests monkeypatch the env then call
    this)."""
    global _instance
    _instance = TelemetryRing(capacity, window)
    return _instance
