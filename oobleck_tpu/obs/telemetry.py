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

A sample is one tuple a step. Positions 0-6 are (step, step_s, compute_s,
comm_s, data_wait_s, ckpt_s, live_bytes); since PR 41 it goes on, at its
END only, with what the host did in THAT step: ``between_s`` (wall from
the previous step's end to this step's start), ``phases`` (host seconds in
the order of ``PHASES``, from the train thread's
``obs/spans.StepAccumulator``) and the allocator's headroom at the step's
end (``hbm_in_use``, ``hbm_limit``, ``hbm_largest_free``; bytes of the
fullest local device, None where the platform reports none). A step's
self time is ``step_s`` less the sum of its phases.

BESIDE the samples, not in them (a sample keeps its 12 positions), the ring
keeps where each step ROUTED: ``record_load(step, {layer: (rows a held
expert..., tiles in use, the tile's rows)})``, a step's routed layers'
loads summed over its microbatches, which rode out of the pipeline's
backward programs beside the loss (``execution/pipeline.py``) and were
read where the loss was. They arrive here as host integers.
``loads()`` / ``last_load()`` read them back; ``record_load`` also sets the
five registry names an operator of a routed model watches (``load_stats``).

``StepWatchdog`` is the one reader that looks at a step while it is still
open: a daemon thread per ``train()`` call that records what the train
thread is inside when a step has lasted twice the recent median.

Knobs:
    OOBLECK_TELEMETRY=0            disable sampling entirely (the stage
                                   programs are then built without the
                                   loads' output and nothing is read)
    OOBLECK_TELEMETRY_CAPACITY     ring size in samples (default 512)
    OOBLECK_TELEMETRY_WINDOW       samples per digest (default 32)
"""

from __future__ import annotations

import collections
import logging
import os
import statistics
import sys
import threading
import time
from oobleck_tpu.utils import metrics

logger = logging.getLogger("oobleck.telemetry")

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
(_STEP, _STEP_S, _COMPUTE_S, _COMM_S, _DATA_WAIT_S, _CKPT_S, _LIVE_BYTES,
 BETWEEN_S, PHASES_AT, HBM_IN_USE, HBM_LIMIT, HBM_LARGEST_FREE) = range(12)
SAMPLE_LEN = HBM_LARGEST_FREE + 1

# The regions of a step whose host seconds a sample keeps, in the order of
# its `phases` tuple (readers import this, never a position). On the fused
# path `engine.fused_step` is the dispatch and takes its place.
PHASES = ("engine.staging", "pipeline.dispatch", "dp.allreduce",
          "engine.optimizer", "engine.loss_readback")
FUSED_DISPATCH = "engine.fused_step"
_DISPATCH = PHASES.index("pipeline.dispatch")
_READBACK = PHASES.index("engine.loss_readback")


def phases_of(seconds: dict) -> tuple:
    """A closed step's region seconds (`StepAccumulator.seconds`) as the
    sample's `phases` tuple."""
    out = [seconds.get(name, 0.0) for name in PHASES]
    out[_DISPATCH] += seconds.get(FUSED_DISPATCH, 0.0)
    return tuple(out)


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
        self._loads: collections.deque = collections.deque(
            maxlen=max(capacity, 1))

    # -- hot path ----------------------------------------------------------- #

    def record_step(self, step: int, step_s: float, *,
                    compute_s: float = 0.0, comm_s: float = 0.0,
                    data_wait_s: float = 0.0, ckpt_s: float = 0.0,
                    live_bytes: int = 0, between_s: float = 0.0,
                    phases: tuple = (0.0,) * len(PHASES),
                    hbm: tuple = (None, None, None)) -> tuple | None:
        """Append one step's host-side timings; returns the sample. All
        arguments are plain host numbers the caller already measured —
        never device values. `hbm` is (in_use, limit, largest_free)."""
        if not self.enabled:
            return None
        sample = (step, step_s, compute_s, comm_s, data_wait_s, ckpt_s,
                  live_bytes, between_s, tuple(phases), *hbm)
        with self._lock:
            self._ring.append(sample)
        return sample

    def record_load(self, step: int, load: dict) -> None:
        """Where step `step` routed: {layer: (rows that held a pair of each
        held expert..., row tiles in use, the rows of a tile)}, summed over
        the step's microbatches; host integers the caller has read already.
        Counts the step's rows under the two counters and sets the three
        gauges from the ring's window (`load_stats`)."""
        if not self.enabled or not load:
            return
        with self._lock:
            self._loads.append((step, load))
        reg = metrics.registry()
        rows = reg.counter(
            "oobleck_moe_step_rows_total",
            "Rows of the routed experts' buffers that held a (token, slot) "
            "pair in training steps, by routed block")
        walked = reg.counter(
            "oobleck_moe_step_tile_rows_total",
            "Rows the grouped kernels walked in training steps, row tiles "
            "in use x the rows of a tile, by routed block")
        for layer, (*held, tiles, tile) in load.items():
            rows.inc(sum(held), layer=layer)
            walked.inc(tiles * tile, layer=layer)
        stats = load_stats(self.load_window())
        reg.gauge(
            "oobleck_moe_tile_fill_pct",
            "100 x rows that held a pair / rows the grouped kernels walked, "
            "all routed blocks, over the telemetry window").set(
                stats["fill_pct"])
        reg.gauge(
            "oobleck_moe_load_skew",
            "Most loaded held expert's rows over the mean rows a held "
            "expert, the worst routed block's, over the telemetry "
            "window").set(stats["skew"])
        reg.gauge(
            "oobleck_moe_step_rows_spread_pct",
            "100 x (max - min) / median of a step's rows that held a pair, "
            "all routed blocks, over the telemetry window").set(
                stats["rows_spread_pct"])

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
        last = tail[-1]
        free_frac = None
        if last[HBM_LIMIT] and last[HBM_IN_USE] is not None:
            free_frac = round(1.0 - last[HBM_IN_USE] / last[HBM_LIMIT], 6)
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
            "live_bytes": last[_LIVE_BYTES],
            # Why a straggler is slow (obs/fleet.py flags on step_max_s):
            # held while dispatching, held on the device, or between steps.
            "dispatch_s": round(
                sum(s[PHASES_AT][_DISPATCH] for s in tail) / n, 6),
            "readback_s": round(
                sum(s[PHASES_AT][_READBACK] for s in tail) / n, 6),
            "between_s": round(sum(s[BETWEEN_S] for s in tail) / n, 6),
            "hbm_free_frac": free_frac,
        }

    def samples(self) -> list[tuple]:
        with self._lock:
            return list(self._ring)

    def last(self) -> tuple | None:
        with self._lock:
            return self._ring[-1] if self._ring else None

    def loads(self) -> list[tuple]:
        """(step, load) of every step whose load was recorded, oldest
        first (`record_load`)."""
        with self._lock:
            return list(self._loads)

    def last_load(self) -> tuple | None:
        with self._lock:
            return self._loads[-1] if self._loads else None

    def load_window(self) -> list[tuple]:
        """The newest `window` entries of `loads()`."""
        with self._lock:
            size = len(self._loads)
            return [self._loads[i]
                    for i in range(max(size - self.window, 0), size)]

    def recent_step_s(self, n: int) -> list[float]:
        """`step_s` of the newest `n` samples, oldest first."""
        with self._lock:
            size = len(self._ring)
            return [self._ring[i][_STEP_S]
                    for i in range(max(size - n, 0), size)]

    def __len__(self) -> int:
        with self._lock:
            return len(self._ring)


def load_stats(loads: list) -> dict:
    """What a window of (step, load) entries says, by plain arithmetic on a
    few dozen integers a step: `fill_pct`, 100 x rows that held a pair over
    the rows the grouped kernels walked (tiles in use x the tile's rows),
    all layers; `skew`, the worst layer's (most loaded held expert's rows /
    mean rows a held expert), a layer's experts summed over the window;
    `rows_spread_pct`, 100 x (max - min) / median of a step's rows over
    all layers."""
    rows = walked = 0
    by_expert: dict[tuple, list[int]] = {}
    by_step = []
    for _, load in loads:
        step_rows = 0
        for layer, (*held, tiles, tile) in load.items():
            step_rows += sum(held)
            walked += tiles * tile
            # By the experts held too: a window can span a pipeline built
            # anew over another share (and the tests' several models).
            seen = by_expert.setdefault((layer, len(held)), [0] * len(held))
            for e, n in enumerate(held):
                seen[e] += n
        rows += step_rows
        by_step.append(step_rows)
    median = statistics.median(by_step) if by_step else 0
    return {
        "fill_pct": 100.0 * rows / walked if walked else 0.0,
        "skew": max((max(seen) * len(seen) / sum(seen)
                     for seen in by_expert.values() if sum(seen)),
                    default=0.0),
        "rows_spread_pct": (100.0 * (max(by_step) - min(by_step)) / median
                            if median else 0.0),
    }


def load_totals(entry: tuple | None) -> dict | None:
    """A `last_load()` entry by layer, as the `step_stall` event carries
    it: {"step", "rows": {layer: rows that held a pair}, "tile_rows":
    {layer: rows the grouped kernels walked}}."""
    if entry is None:
        return None
    step, load = entry
    return {"step": step,
            "rows": {layer: sum(v[:-2]) for layer, v in load.items()},
            "tile_rows": {layer: v[-2] * v[-1] for layer, v in load.items()}}


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


# ---------------------------------------------------------------------------
# a step that overstays


def sample_fields(sample: tuple) -> dict:
    """What a sample holds beyond the digest's means, by name: the form
    the `step_stall*` flight events and the log line carry."""
    return {
        "step": sample[_STEP], "step_s": round(sample[_STEP_S], 6),
        "between_s": round(sample[BETWEEN_S], 6),
        "phases": {name: round(sec, 6)
                   for name, sec in zip(PHASES, sample[PHASES_AT])},
        "hbm_in_use": sample[HBM_IN_USE], "hbm_limit": sample[HBM_LIMIT],
        "hbm_largest_free": sample[HBM_LARGEST_FREE],
    }


def thread_frames(limit: int, train_ident: int | None = None) -> dict:
    """The innermost `limit` frames of every thread of the process, each
    `dir/file.py:line:function`, innermost first; the thread `train_ident`
    is listed as `train`."""
    names = {t.ident: t.name for t in threading.enumerate()}
    out = {}
    for ident, frame in sys._current_frames().items():
        rows = []
        while frame is not None and len(rows) < limit:
            code = frame.f_code
            where = "/".join(code.co_filename.split(os.sep)[-2:])
            rows.append(f"{where}:{frame.f_lineno}:{code.co_name}")
            frame = frame.f_back
        name = ("train" if ident == train_ident
                else f"{names.get(ident, 'thread')}-{ident}")
        out[name] = rows
    return out


class StepWatchdog:
    """Looks at a training step WHILE it is open, and says what the train
    thread is inside when the step has lasted `OVERSTAY` x the median
    `step_s` of the ring's newest `MEDIAN_OVER` samples.

    One daemon thread per `train()` call. The train thread publishes the
    open step as ONE attribute (`open`: a (start, step) pair or None), so
    the step path takes no lock and the reader never pairs one step's start
    with another's number. The first step after `start()` is never
    published: it follows set-up, a reconfiguration or a grow, and may
    compile. Under `ARMED_AT` samples there is no median and nothing fires
    (the median and not an average: a warm-up's compiling step would sit in
    an average for ten steps). No knob: steps repeat to 0.5 %, and the
    shortest stall on record is 3 x its step.

    Fires once a step: flight event `step_stall` (step, seconds open, the
    median, the innermost region open on the train thread, every thread's
    innermost frames, the memory sample of the last step's end), the same
    as one warning line, `oobleck_step_stalls_total{phase}`, and a profiler
    window over the rest of the step where `open_trace` grants one. The
    train thread adds `step_stall_end` with the whole sample once the step
    is over. It reads no device value and never calls into the runtime
    itself: a second thread entering the allocator while the first is held
    there is not something to find out in production."""

    OVERSTAY = 2.0
    MEDIAN_OVER = 8
    ARMED_AT = 3
    FRAMES = 12
    IDLE_S = 0.25

    def __init__(self, ring: TelemetryRing, accumulator, *, open_trace=None,
                 clock=time.monotonic, wait=None):
        self._ring = ring
        self._acc = accumulator
        self._open_trace = open_trace      # (step) -> bool, or None
        self._clock = clock
        self._stop = threading.Event()
        self._wait = wait or self._stop.wait    # (seconds) -> stopping?
        self._thread: threading.Thread | None = None
        self._first = True
        self.open: tuple | None = None     # written by the train thread
        self.fired_step = -1               # written by the watchdog's
        self._m_stalls = metrics.registry().counter(
            "oobleck_step_stalls_total", "Training steps that stayed open "
            "twice the recent median, by the region open on the train "
            "thread when the watchdog looked")

    # -- the train thread ---------------------------------------------------- #

    def step_opens(self, step: int) -> None:
        if self._first:
            self._first = False
            return
        self.open = (self._clock(), step)

    def step_closes(self) -> None:
        self.open = None

    def step_recorded(self, sample: tuple | None) -> None:
        """The closed step's sample is in the ring: if the watchdog fired
        in that step, say how it ended."""
        if sample is not None and sample[_STEP] == self.fired_step:
            fields = sample_fields(sample)
            metrics.flight_recorder().record("step_stall_end", **fields)
            logger.warning("step_stall_end %s", fields)

    # -- the watchdog's thread ----------------------------------------------- #

    def start(self) -> "StepWatchdog":
        self._thread = threading.Thread(
            target=self._run, name="oobleck-step-watchdog", daemon=True)
        self._thread.start()
        return self

    def stop(self) -> None:
        self._stop.set()
        thread, self._thread = self._thread, None
        if thread is not None:
            thread.join(timeout=30.0)
            if thread.is_alive():
                logger.warning("step watchdog did not stop within 30 s")

    def _run(self) -> None:
        while not self._stop.is_set():
            if self._wait(self.check()):
                return

    def check(self) -> float:
        """One look at the open step; returns the seconds until the next:
        the time left until the step now open would be overdue, else a
        quarter of the median."""
        recent = self._ring.recent_step_s(self.MEDIAN_OVER)
        if len(recent) < self.ARMED_AT:
            return self.IDLE_S
        median = statistics.median(recent)
        idle = min(max(median / 4, 0.001), self.IDLE_S)
        opened = self.open
        if opened is None or opened[1] == self.fired_step:
            return idle
        since, step = opened
        open_s = self._clock() - since
        left = self.OVERSTAY * median - open_s
        if left > 0:
            return max(left, 0.001)
        self.fired_step = step
        self._fire(step, open_s, median)
        return idle

    def _fire(self, step: int, open_s: float, median: float) -> None:
        phase = self._acc.innermost() or "none"
        frames = thread_frames(self.FRAMES, self._acc.owner)
        last = self._ring.last()
        before = sample_fields(last) if last is not None else None
        event = dict(step=step, open_s=round(open_s, 3),
                     median_s=round(median, 6), phase=phase, frames=frames,
                     last_sample=before,
                     last_load=load_totals(self._ring.last_load()))
        metrics.flight_recorder().record("step_stall", **event)
        self._m_stalls.inc(phase=phase)
        # The same on one line: a run with no sink set shows it on stderr.
        logger.warning("step_stall %s", event)
        if self._open_trace is not None:
            self._open_trace(step)
