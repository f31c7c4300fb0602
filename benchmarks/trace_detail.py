"""What `trace_reduce.reduce` drops from a traced run's profile, for the
readers that need it: each device operation's stats, the device plane's
module line, and the program's own spans on the host plane.

`trace_reduce.load_xplane` keeps names, starts and durations. Two kinds of
per-layer metric need more, and read it here:

* which XLA module (`jit_bwd`, `jit_grad_zero`, `jit_optimizer_update`;
  `jit_fwd` where a pipeline has more than one stage) an operation ran in: the operation's `hlo_module`
  stat where the event carries one (the CPU's do), else the module-line
  event that contains it in time (a TPU's operations carry their device
  offset and duration and nothing else: no module, and no JAX name stack,
  so no metric can read `jax.named_scope` from this trace);
* what the host was doing: `oobleck_tpu.obs.spans.region` /`span` open a
  `jax.profiler.TraceAnnotation`, so the program's spans (`engine.step`,
  `engine.loss_readback`, ...) are events of the host plane on the device
  operations' clock. They are told from runtime threads' events and Python
  frames by their form, `<layer>.<what>` in lower case.

`for_data(data)` is what a reader calls. It returns the detail of the cell's
last traced run (`<checkout>/.jax_cache/benchmarks/trace/<cell>/`, found
through `data["cell"]["name"]`), opened ONCE per process however many
readers ask (memoised), or `data["trace_detail"]` where a test hands over a
recorded cut, or None where there is neither: a reader then has nothing to
read. The shape, the same for a profile and for a recorded cut:

    {"ops":     [[short name, start_ns, duration_ns, {stat: value}], ...]
                 operations that ran on the first device, in time order
     "modules": [[module name, start_ns, duration_ns], ...]  its module line
     "host":    {span name: [[start_ns, duration_ns], ...]}  in time order}

(`ops_by_module` adds `by_module`, its partition of `ops`, on first use.)

Only the first device (by plane name) is kept: it is the one
`trace_reduce.reduce` attributes idle gaps on.
"""

from __future__ import annotations

import functools
import re
from pathlib import Path

from benchmarks import trace_reduce

TRACE_ROOT = (Path(__file__).resolve().parent.parent
              / ".jax_cache" / "benchmarks" / "trace")
MODULES_LINE = "XLA Modules"
MODULE_STAT = "hlo_module"
# The program's spans: `engine.step`, `pipeline.flush_sends`, `dp.allreduce`.
# (`dot.16`, an operation on a CPU's host plane, is not one: a component
# starts with a letter.)
SPAN_NAME = re.compile(r"^[a-z][a-z0-9_]*(\.[a-z][a-z0-9_]*)+$")
_MODULE_SUFFIX = re.compile(r"\(\d+\)$")


def module_name(event_name: str) -> str:
    """`jit_bwd(1234567890)` -> `jit_bwd`: the number is the program's id,
    different in every process."""
    return _MODULE_SUFFIX.sub("", event_name.strip())


def from_profile(path: str) -> dict | None:
    from jax.profiler import ProfileData

    data = ProfileData.from_file(path)
    device = None
    host: dict[str, list] = {}
    for plane in data.planes:
        if plane.name.startswith("/device:"):
            if "TPU" not in plane.name and "GPU" not in plane.name:
                continue
            if device is None or plane.name < device.name:
                device = plane
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for ev in line.events:
                    if ev.duration_ns > 0 and SPAN_NAME.match(ev.name):
                        host.setdefault(ev.name, []).append(
                            [float(ev.start_ns), float(ev.duration_ns)])
    if device is None:
        return None
    lines = list(device.lines)
    op_lines = [ln for ln in lines if ln.name == trace_reduce.OPS_LINE] or lines
    ops = [[trace_reduce.short_name(ev.name), float(ev.start_ns),
            float(ev.duration_ns), dict(ev.stats)]
           for ln in op_lines for ev in ln.events]
    modules = [[module_name(ev.name), float(ev.start_ns),
                float(ev.duration_ns)]
               for ln in lines if ln.name == MODULES_LINE for ev in ln.events]
    ops.sort(key=lambda e: e[1])
    modules.sort(key=lambda e: e[1])
    for spans in host.values():
        spans.sort()
    return {"ops": ops, "modules": modules, "host": host}


@functools.lru_cache(maxsize=4)
def _of_cell(cell_name: str) -> dict | None:
    try:
        path = trace_reduce.find_xplane(str(TRACE_ROOT / cell_name))
    except FileNotFoundError:
        return None
    return from_profile(path)


def for_data(data: dict) -> dict | None:
    if data.get("trace_detail") is not None:
        return data["trace_detail"]
    cell = (data.get("cell") or {}).get("name")
    return _of_cell(cell) if cell else None


def window_steps(data: dict) -> int | None:
    """Training steps of the measured window, from what the runner hands
    over (as `kernel_roofline_pct` counts microbatches)."""
    t = data.get("train")
    traffic = (data.get("cell") or {}).get("traffic")
    if not t or not traffic:
        return None
    per_step = traffic["global_batch"] // traffic["microbatch_size"]
    steps = t["microbatches_run"] // per_step
    return steps if steps > 0 else None


def ops_by_module(detail: dict) -> dict[str, list]:
    """`{module: [[name, start_ns, duration_ns], ...]}`: each operation
    under the module it ran in, by its own `hlo_module` stat, else by the
    module-line event containing its start (both lists are in time order).
    Operations under no module are left out. Computed once per detail."""
    if "by_module" not in detail:
        out: dict[str, list] = {}
        modules = detail["modules"]
        i = 0
        for name, start, dur, stats in detail["ops"]:
            module = stats.get(MODULE_STAT)
            if module:
                module = module_name(str(module))
            elif modules:
                while i + 1 < len(modules) and modules[i + 1][1] <= start:
                    i += 1
                m_name, m_start, m_dur = modules[i]
                if m_start <= start < m_start + m_dur:
                    module = m_name
            if module:
                out.setdefault(module, []).append([name, start, dur])
        detail["by_module"] = out
    return detail["by_module"]


def first_device_gaps(detail: dict) -> list[tuple[float, float]]:
    """Idle gaps of the first device, as `trace_reduce.attribute_gaps` cuts
    them: between consecutive busy intervals, `MIN_GAP_NS` or longer."""
    intervals = trace_reduce.busy_intervals(
        [[n, s, d] for n, s, d, _ in detail["ops"]])
    return [(a_end, b_start) for (_, a_end), (b_start, _)
            in zip(intervals, intervals[1:])
            if b_start - a_end >= trace_reduce.MIN_GAP_NS]
