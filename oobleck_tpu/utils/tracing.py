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

    def _stop(self) -> None:
        try:
            jax.profiler.stop_trace()
        except RuntimeError as e:
            logger.warning("stop_trace failed: %s", e)
        self._active = False
        if self.every <= 0:
            self._done = True

    def close(self) -> None:
        """Idempotent: stop an open window (engine shutdown/reconfigure).
        One-shot mode stays closed; periodic mode re-arms at the next
        window boundary."""
        if self._active:
            self._stop()
        if self.every <= 0:
            self._done = True
