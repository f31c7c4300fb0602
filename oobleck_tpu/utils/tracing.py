"""Runtime tracing.

An on-demand jax.profiler trace of a window of training steps (the
TensorBoard wiring the reference lists as a dep but never uses, SURVEY §5).
The named regions that show in it (`engine.step`, `pipeline.dispatch`, ...)
are `obs/spans.region` calls, and the kernels, programs and model parts on
the device plane carry stable names of their own (README, "Reading a
trace").

Enable with OOBLECK_TRACE_DIR=/path — the engine writes a
perfetto-compatible trace for steps
[OOBLECK_TRACE_START, OOBLECK_TRACE_START + OOBLECK_TRACE_STEPS). Set
OOBLECK_TRACE_EVERY=<n> to re-arm the window every n steps for long runs
(window k covers [START + k*EVERY, START + k*EVERY + STEPS)).

With OOBLECK_TRACE_DIR set, the step watchdog (obs/telemetry.py) also gets
a window for a step that overstays: `open_stall_window(step)` starts a
trace into <dir>/stall-<step> from the watchdog's thread unless a session
is open, and the train thread closes it at that step's end
(`close_stall_window`). The rest of the stall is then a profiler trace with
the runtime's own threads and the device plane in it.

Lifecycle: the engine owns one StepTracer per train() and calls close()
from its finally AND from reconfigure() — a mid-window failure or topology
change must not leave a jax.profiler trace open (start_trace raises on
double-start, and an unclosed trace loses its buffered data).
"""

from __future__ import annotations

import logging
import os

import jax

logger = logging.getLogger("oobleck.tracing")


def _env_int(name: str, default: int) -> int:
    raw = os.environ.get(name, "")
    try:
        return int(raw) if raw else default
    except ValueError:
        logger.warning("ignoring malformed %s=%r", name, raw)
        return default


class StepTracer:
    """Traces windows of training steps to OOBLECK_TRACE_DIR."""

    def __init__(self):
        self.trace_dir = os.environ.get("OOBLECK_TRACE_DIR")
        self.start = _env_int("OOBLECK_TRACE_START", 3)
        self.steps = _env_int("OOBLECK_TRACE_STEPS", 3)
        # 0 = one window (legacy behavior); n > 0 re-arms every n steps.
        self.every = _env_int("OOBLECK_TRACE_EVERY", 0)
        self._active = False
        self._done = False  # one-shot mode: window consumed (or closed)
        # Set by the watchdog's thread once its start_trace has returned,
        # read and cleared by the train thread at a step's end. A step that
        # ends while the session is still starting is closed one step late.
        self.stall_open = False

    def _window_start(self, step: int) -> int:
        if self.every > 0 and step >= self.start:
            k = (step - self.start) // self.every
            return self.start + k * self.every
        return self.start

    def on_step(self, step: int) -> None:
        if not self.trace_dir or self.steps <= 0:
            return
        ws = self._window_start(step)
        in_window = ws <= step < ws + self.steps
        if self._active:
            if not in_window:
                self._stop()
            else:
                return
        if self._done and self.every <= 0:
            return
        if in_window:
            try:
                jax.profiler.start_trace(self.trace_dir)
            except RuntimeError as e:
                # Another component holds a trace open; skip this window
                # rather than kill training.
                logger.warning("trace window skipped: %s", e)
                self._done = True
                return
            self._active = True

    def open_stall_window(self, step: int) -> bool:
        """A window over the rest of a step that overstays; from the
        watchdog's thread. Skipped without a trace directory and while a
        session is open, this tracer's or anyone's (`start_trace` raising
        is the skip `on_step` has)."""
        if not self.trace_dir or self._active or self.stall_open:
            return False
        path = os.path.join(self.trace_dir, f"stall-{step}")
        try:
            jax.profiler.start_trace(path)
        except RuntimeError as e:
            logger.warning("stall window for step %d skipped: %s", step, e)
            return False
        self.stall_open = True
        logger.warning("stall window open: tracing the rest of step %d "
                       "into %s", step, path)
        return True

    def close_stall_window(self) -> None:
        if self.stall_open:
            self.stall_open = False
            self._stop_session()

    @staticmethod
    def _stop_session() -> None:
        try:
            jax.profiler.stop_trace()
        except RuntimeError as e:
            logger.warning("stop_trace failed: %s", e)

    def _stop(self) -> None:
        self._stop_session()
        self._active = False
        if self.every <= 0:
            self._done = True

    def close(self) -> None:
        """Idempotent: stop an open window (engine shutdown/reconfigure).
        One-shot mode stays closed; periodic mode re-arms at the next
        window boundary."""
        self.close_stall_window()
        if self._active:
            self._stop()
        if self.every <= 0:
            self._done = True
