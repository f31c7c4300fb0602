"""Profiler trace -> device busy/idle, time by operation, idle gaps by host span.

`load_xplane(path)` reads a `.xplane.pb` with nothing but JAX and returns a
plain dictionary (the same shape as the small recorded trace kept with the
tests, so the reduction is tested without a chip):

    {"devices": {"/device:TPU:0": [[name, start_ns, duration_ns], ...]},
     "host": [[name, start_ns, duration_ns], ...]}

`devices` holds the operations that RAN on each device (the plane's
"XLA Ops" line where it has one, else every line of the plane); `host`
holds the host threads' events, `jax.profiler.TraceAnnotation`s among them,
on the same clock.

`reduce(trace, window_s)` gives

    busy_s      seconds in which some operation ran, the union of the
                intervals, averaged over the devices in the trace
    window_s    as passed in: the length of the traced window
    device_ops  [[name, seconds], ...] operations by total time, summed
                over devices, most first
    time_by_name  {name: [seconds, calls]}, all of them, for the readers
    idle_gaps   [[host span, seconds], ...] idle time of the first device,
                by the innermost host event covering each gap's middle
"""

from __future__ import annotations

import glob
import heapq
import os
import re

OPS_LINE = "XLA Ops"
NO_HOST_SPAN = "(no host span)"
# Gaps shorter than this are launch latency, not waiting: not attributed.
MIN_GAP_NS = 20_000


_HLO = re.compile(r"^(%[\w.\-]+) = (\(?[a-z0-9]+\[[^\]]*\])")
_OPCODE = re.compile(r"[\])}] ([a-z][a-z\-]*)\(")
_TARGET = re.compile(r'custom_call_target="([^"]+)"')
_PARAM = re.compile(r"%params_tuple_(\w+?)\.\d+")


def short_name(name: str) -> str:
    """An operation's event name is its whole HLO line (hundreds of
    characters of operands and layouts). Keep what tells operations apart:
    the result's name and first shape, the opcode, a custom call's target
    (a Pallas kernel is `tpu_custom_call`), and the parameters it reads."""
    m = _HLO.match(name)
    if not m:
        return name[:120]
    out = f"{m.group(1)} {m.group(2).lstrip('(')}"
    op = _OPCODE.search(name)
    if op:
        out += f" {op.group(1)}"
    target = _TARGET.search(name)
    if target:
        out += f":{target.group(1)}"
    params = list(dict.fromkeys(_PARAM.findall(name)))[:2]
    if params:
        out += " <" + ",".join(params) + ">"
    return out[:120]


def find_xplane(trace_dir: str) -> str:
    found = sorted(glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return found[-1]


def load_xplane(path: str) -> dict:
    from jax.profiler import ProfileData

    data = ProfileData.from_file(path)
    devices: dict[str, list] = {}
    host: list = []
    for plane in data.planes:
        lines = list(plane.lines)
        if plane.name.startswith("/device:"):
            if "TPU" not in plane.name and "GPU" not in plane.name:
                continue
            ops = [ln for ln in lines if ln.name == OPS_LINE] or lines
            devices[plane.name] = [
                [short_name(ev.name), float(ev.start_ns),
                 float(ev.duration_ns)]
                for ln in ops for ev in ln.events]
        elif plane.name.startswith("/host:"):
            host.extend(
                [ev.name, float(ev.start_ns), float(ev.duration_ns)]
                for ln in lines for ev in ln.events if ev.duration_ns > 0)
    return {"devices": devices, "host": host}


def busy_intervals(events: list) -> list[tuple[float, float]]:
    """Union of [start, start + duration) over events, sorted, merged."""
    spans = sorted((s, s + d) for _, s, d in events if d > 0)
    out: list[tuple[float, float]] = []
    for s, e in spans:
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1] = (out[-1][0], e)
        else:
            out.append((s, e))
    return out


def time_by_name(events: list) -> dict[str, list]:
    """[seconds, calls] per operation name. Nested events (a while loop
    and its body) would double count; `busy_intervals` is what gives busy
    time."""
    out: dict[str, list] = {}
    for name, _, d in events:
        cell = out.setdefault(name, [0.0, 0])
        cell[0] += d / 1e9
        cell[1] += 1
    return out


def attribute_gaps(intervals: list[tuple[float, float]], host: list
                   ) -> dict[str, float]:
    """Idle seconds between consecutive busy intervals, by the shortest
    host event that covers the gap's middle."""
    gaps = [(a_end, b_start) for (_, a_end), (b_start, _)
            in zip(intervals, intervals[1:]) if b_start - a_end >= MIN_GAP_NS]
    events = sorted(host, key=lambda e: e[1])
    active: list[tuple[float, float, str]] = []   # (end, duration, name)
    out: dict[str, float] = {}
    i = 0
    for g0, g1 in gaps:                            # already in time order
        mid = (g0 + g1) / 2
        while i < len(events) and events[i][1] <= mid:
            name, s, d = events[i]
            heapq.heappush(active, (s + d, d, name))
            i += 1
        while active and active[0][0] < mid:
            heapq.heappop(active)
        name = min(active, key=lambda a: a[1])[2] if active else NO_HOST_SPAN
        out[name] = out.get(name, 0.0) + (g1 - g0) / 1e9
    return out


def _top(table: dict[str, float], n: int = 10) -> list:
    return [[k, v] for k, v in
            sorted(table.items(), key=lambda kv: -kv[1])[:n]]


def reduce(trace: dict, window_s: float) -> dict:
    devices = trace["devices"]
    if not devices:
        raise ValueError("the trace holds no device plane")
    busy, by_name = [], {}
    first_intervals = None
    for name in sorted(devices):
        events = devices[name]
        intervals = busy_intervals(events)
        if first_intervals is None:
            first_intervals = intervals
        busy.append(sum(e - s for s, e in intervals) / 1e9)
        for k, (secs, calls) in time_by_name(events).items():
            cell = by_name.setdefault(k, [0.0, 0])
            cell[0] += secs
            cell[1] += calls
    gaps = attribute_gaps(first_intervals or [], trace.get("host", []))
    return {"busy_s": sum(busy) / len(busy), "window_s": float(window_s),
            "n_devices": len(busy), "time_by_name": by_name,
            "device_ops": _top({k: v[0] for k, v in by_name.items()}),
            "idle_gaps": _top(gaps)}
