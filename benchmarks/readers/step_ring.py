"""What the host did in each step of the measured window, from the
program's telemetry ring (`oobleck_tpu/obs/telemetry.py`), read in the
benchmark's process as `counter_value` reads the registry.

Since PR 41 the ring's sample of a step holds, after the seven fields it
had: `between_s` (wall from the previous step's end to this step's start),
`phases` (host seconds of the step's regions, in the order of the
program's `PHASES`) and the allocator's counters at the step's end
(`hbm_in_use`, `hbm_limit`, `hbm_largest_free`). The window's steps are
the ring's newest `trace_detail.window_steps(data)` samples; joined to a
trace by order, the k-th of them is the k-th `engine.step` event of the
traced window. `stat`:

* `excess_dispatch_ms`, `excess_readback_ms`, `excess_rest_ms`: in the
  window's SLOWEST step, the seconds of `pipeline.dispatch`, of
  `engine.loss_readback`, and of the rest of `step_s` (staging,
  all-reduce, optimizer enqueue, the step's self time), each less the
  window's median of that same part. The three add up to the slowest step
  less the median step, up to the spread of the medians (exactly where
  the steps differ in one part). Medians of each part and not the median
  step's parts: where the host sits in the allocator, dispatch and
  readback trade places from step to step, and that reads 0, not +-20.
* `between_ms`: mean `between_s`, the host time the rate pays outside
  `step_s` (bookkeeping, the stager's handshake, the fence).
* `slow_steps`: steps over 1.25 x the window's median `step_s`.
* `hbm_headroom_min_pct`: least `(hbm_limit - hbm_in_use) / hbm_limit`
  over the window's step ends, in %.

A test hands a ring over as `data["step_ring"]` (a list of samples). A
program without the fields (the parent of the PR that added them), a ring
with fewer samples than the window has steps, or a platform that reports
no memory: nothing to read.
"""

import statistics

from benchmarks import trace_detail


def window_samples(data: dict) -> tuple[list, object] | None:
    """(the window's samples, the program's telemetry module), or None."""
    steps = trace_detail.window_steps(data)
    if not steps:
        return None
    from oobleck_tpu.obs import telemetry

    if not hasattr(telemetry, "PHASES"):
        return None
    ring = data.get("step_ring")
    if ring is None:
        ring = telemetry.telemetry().samples()
    window = list(ring)[-steps:]
    if len(window) < steps or any(
            len(s) < telemetry.SAMPLE_LEN for s in window):
        return None
    return window, telemetry


def read(data: dict, *, stat: str) -> float | None:
    found = window_samples(data)
    if found is None:
        return None
    window, t = found
    step_s = [s[1] for s in window]
    if stat == "between_ms":
        return statistics.fmean(s[t.BETWEEN_S] for s in window) * 1e3
    if stat == "slow_steps":
        limit = 1.25 * statistics.median(step_s)
        return float(sum(1 for s in step_s if s > limit))
    if stat == "hbm_headroom_min_pct":
        free = [(s[t.HBM_LIMIT] - s[t.HBM_IN_USE]) / s[t.HBM_LIMIT]
                for s in window
                if s[t.HBM_LIMIT] and s[t.HBM_IN_USE] is not None]
        return min(free) * 100.0 if free else None
    at_dispatch = t.PHASES.index("pipeline.dispatch")
    at_readback = t.PHASES.index("engine.loss_readback")
    dispatch = [s[t.PHASES_AT][at_dispatch] for s in window]
    readback = [s[t.PHASES_AT][at_readback] for s in window]
    part = {
        "excess_dispatch_ms": dispatch,
        "excess_readback_ms": readback,
        "excess_rest_ms": [s - d - r for s, d, r
                           in zip(step_s, dispatch, readback)],
    }[stat]
    slowest = max(range(len(window)), key=step_s.__getitem__)
    return (part[slowest] - statistics.median(part)) * 1e3
