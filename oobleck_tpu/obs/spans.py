"""Dependency-free span recorder with cross-process trace propagation.

The metrics plane (utils/metrics.py) answers *that* recovery took 21 s;
this module answers *where the time went*. A span is one named wall-clock
interval carrying ``trace_id`` / ``span_id`` / ``parent_id`` plus free-form
attributes. Finished spans land in a bounded ring (the flight-recorder
idiom) and can be dumped to ``OOBLECK_METRICS_DIR/spans-{role}-{pid}-{seq}
.jsonl`` or exported as Chrome-trace/Perfetto JSON (``to_chrome_trace``).

Trace context crosses processes by riding the elastic control-plane verbs
as one extra JSON key (``inject``/``extract`` — legacy peers parse fine,
payload dicts merge arbitrary keys) and crosses threads inside a process
via an explicit "ambient" context (``set_ambient``): the engine pins the
incident's trace around ``reconfigure()`` so spans recorded anywhere in
the recovery path (degrade apply, plan materialization, recovery marks)
stitch into one timeline without threading a context object through every
call signature.

Timestamps are wall-clock epoch seconds, same rationale as
utils/recovery.py: the chain crosses master/agent/worker processes, and
processes on one machine share a clock (TPU pods have NTP-class sync).

Two calls, two jobs:

* ``span(name)`` -- the incident recorder above: wall clock, ids that cross
  processes, a record in the ring. For what happens once per incident or
  once per process (reconfigure, restore, set-up).
* ``region(name)`` -- a hot-path region, many times a step: nothing but a
  ``jax.profiler.TraceAnnotation`` (the region is an event of the host
  plane of the profiler's own trace, on the device operations' clock) and
  one observation of ``oobleck_span_seconds{span=name}``. No ids, no wall
  clock, no record. Where the calling thread owns a ``StepAccumulator``
  (the engine's train thread does), the region's seconds are also added
  to the current step's, by name, and its name lies on the accumulator's
  stack while it is open: what ``obs/telemetry.py`` writes into each
  step's sample, and what its watchdog reads of a step that overstays.

``span()`` opens the same annotation, so an incident's spans also lie in a
device trace taken across it. This module is the only place in the package
that constructs a ``TraceAnnotation``. Constructing one initialises no JAX
backend: the master and the agent call ``span()`` too.
"""

from __future__ import annotations

import collections
import contextlib
import json
import logging
import os
import threading
import time
import uuid

import jax

from oobleck_tpu.utils import metrics

logger = logging.getLogger("oobleck.obs")

ENV_SPAN_CAPACITY = "OOBLECK_SPAN_CAPACITY"
# Payload key the elastic verbs carry trace context under. Receivers that
# predate the key ignore it (length-prefixed JSON merges arbitrary keys).
TRACE_KEY = "trace"


def new_trace_id() -> str:
    return uuid.uuid4().hex[:16]


def new_span_id() -> str:
    return uuid.uuid4().hex[:16]


class SpanRecorder:
    """Thread-safe bounded ring of finished spans (FlightRecorder idiom:
    always recording, cheap enough to leave on, dumped on demand)."""

    def __init__(self, capacity: int | None = None):
        if capacity is None:
            raw = os.environ.get(ENV_SPAN_CAPACITY, "")
            try:
                capacity = int(raw) if raw else 1024
            except ValueError:
                logger.warning("obs: malformed %s=%r ignored",
                               ENV_SPAN_CAPACITY, raw)
                capacity = 1024
        self._lock = threading.Lock()
        self._ring: collections.deque = collections.deque(
            maxlen=max(capacity, 1))
        self._seq = 0

    def record(self, name: str, t0: float, t1: float, *,
               trace_id: str | None = None, span_id: str | None = None,
               parent_id: str | None = None, **attrs) -> dict:
        """Append one finished span; returns the stored record."""
        span = {
            "name": name,
            "t0": t0,
            "t1": t1,
            "trace_id": trace_id or new_trace_id(),
            "span_id": span_id or new_span_id(),
            "parent_id": parent_id,
            "role": metrics.get_role(),
            "pid": os.getpid(),
            "tid": threading.get_native_id(),
        }
        if attrs:
            span["attrs"] = attrs
        with self._lock:
            self._ring.append(span)
        return span

    def spans(self) -> list[dict]:
        with self._lock:
            return list(self._ring)

    def for_trace(self, trace_id: str) -> list[dict]:
        return [s for s in self.spans() if s.get("trace_id") == trace_id]

    def dump(self, reason: str) -> str | None:
        """Write the whole ring to OOBLECK_METRICS_DIR/spans-{role}-{pid}-
        {seq}.jsonl; None when the sink is disabled."""
        d = metrics.metrics_dir()
        if d is None:
            return None
        with self._lock:
            spans = list(self._ring)
            self._seq += 1
            seq = self._seq
        path = os.path.join(
            d, f"spans-{metrics.get_role()}-{os.getpid()}-{seq}.jsonl")
        try:
            with open(path, "w") as f:
                f.write(json.dumps({"t": time.time(), "event": "dump",
                                    "reason": reason,
                                    "role": metrics.get_role()}) + "\n")
                for span in spans:
                    f.write(json.dumps(span) + "\n")
        except OSError as e:
            logger.warning("obs: cannot write span dump %s: %s", path, e)
            return None
        return path


_recorder = SpanRecorder()


def span_recorder() -> SpanRecorder:
    return _recorder


# ---------------------------------------------------------------------------
# context: thread-local span stack + process-wide ambient trace


class _ThreadState(threading.local):
    # Class-level defaults: a thread that never set one reads None without
    # the AttributeError a bare `threading.local` raises inside `getattr`
    # (0.5 us a region on every thread that owns no accumulator).
    stack: list | None = None                 # open span() frames
    step: "StepAccumulator | None" = None     # see StepAccumulator.install


_tls = _ThreadState()
_ambient_lock = threading.Lock()
_ambient: dict | None = None


def set_ambient(ctx: dict | None) -> None:
    """Pin a process-wide trace context ({"trace_id", "span_id"}) used when
    no thread-local span is open — how an incident's trace reaches spans
    recorded from other threads/modules during recovery."""
    global _ambient
    with _ambient_lock:
        _ambient = dict(ctx) if ctx else None


def ambient() -> dict | None:
    with _ambient_lock:
        return dict(_ambient) if _ambient else None


def current() -> dict | None:
    """The innermost open span's context, else the ambient one."""
    stack = _tls.stack
    if stack:
        return dict(stack[-1])
    return ambient()


@contextlib.contextmanager
def span(name: str, *, trace_id: str | None = None,
         parent_id: str | None = None, recorder: SpanRecorder | None = None,
         **attrs):
    """Record one span around a code region. Nested spans parent onto the
    enclosing one; the outermost parents onto the ambient context (if any).
    Yields the span's context dict ({"trace_id", "span_id"}) so callers can
    inject it into outbound messages."""
    ctx = current()
    if trace_id is None and ctx:
        trace_id = ctx.get("trace_id")
    if parent_id is None and ctx:
        parent_id = ctx.get("span_id")
    frame = {"trace_id": trace_id or new_trace_id(), "span_id": new_span_id()}
    stack = _tls.stack
    if stack is None:
        stack = _tls.stack = []
    stack.append(frame)
    t0 = time.time()
    try:
        with jax.profiler.TraceAnnotation(name):
            yield frame
    finally:
        stack.pop()
        (recorder or _recorder).record(
            name, t0, time.time(), trace_id=frame["trace_id"],
            span_id=frame["span_id"], parent_id=parent_id, **attrs)


SPAN_SECONDS = "oobleck_span_seconds"
# name -> bound observe() of its series, for the registry generation they
# were bound in (tests clear the registry between cases).
_observers: dict = {}
_observers_generation = -1


def _observer(name: str):
    global _observers_generation
    reg = metrics.registry()
    if reg.generation != _observers_generation:
        _observers.clear()
        _observers_generation = reg.generation
    observe = _observers.get(name)
    if observe is None:
        # The literal, not SPAN_SECONDS: the registry generator reads it.
        observe = _observers[name] = reg.histogram(
            "oobleck_span_seconds", "Host seconds of named hot-path "
            "regions (obs/spans.region)").bind(span=name)
    return observe


class StepAccumulator:
    """Host seconds of the regions ONE thread closed since `begin()`, by
    name, and the names of the regions it has open now, outermost first.

    `install()` hands it to the calling thread; regions entered on any
    other thread (the stager's worker, checkpoint writers) never see it.
    Only the owner writes. Another thread may read `innermost()`: a list's
    last element is one bytecode under the interpreter lock, so neither
    side takes a lock."""

    __slots__ = ("owner", "seconds", "stack")

    def __init__(self) -> None:
        self.owner: int | None = None
        self.seconds: dict[str, float] = {}
        self.stack: list[str] = []

    def install(self) -> None:
        self.owner = threading.get_ident()
        _tls.step = self

    def uninstall(self) -> None:
        if _tls.step is self:
            _tls.step = None

    def begin(self) -> None:
        """A step starts: forget the seconds of what closed before it."""
        self.seconds.clear()

    def innermost(self) -> str | None:
        try:
            return self.stack[-1]
        except IndexError:
            return None


class region:
    """A named hot-path region: `with region("engine.staging"): ...`.

    While a profiler trace runs, the region is an event named `name` on the
    calling thread's line of the trace's host plane; always, its host
    seconds go to the histogram `oobleck_span_seconds{span=name}`. Nests,
    and closes on an exception. Reads no device value: what it times is
    what the host did, which on an asynchronous device is the enqueue
    unless the region itself blocks. On a thread that owns a
    `StepAccumulator` the seconds also go to the current step's."""

    __slots__ = ("_name", "_annotation", "_t0", "_step")

    def __init__(self, name: str):
        self._name = name
        self._annotation = jax.profiler.TraceAnnotation(name)

    def __enter__(self) -> None:
        step = self._step = _tls.step
        if step is not None:
            step.stack.append(self._name)
        self._annotation.__enter__()
        self._t0 = time.perf_counter()

    def __exit__(self, exc_type, exc, tb) -> None:
        seconds = time.perf_counter() - self._t0
        self._annotation.__exit__(exc_type, exc, tb)
        _observer(self._name)(seconds)
        step = self._step
        if step is not None:
            step.stack.pop()
            step.seconds[self._name] = (
                step.seconds.get(self._name, 0.0) + seconds)


def event(name: str, t: float | None = None, **attrs) -> dict:
    """Record a zero-duration span (a point event) on the current trace."""
    ctx = current()
    t = time.time() if t is None else t
    return _recorder.record(
        name, t, t,
        trace_id=ctx.get("trace_id") if ctx else None,
        parent_id=ctx.get("span_id") if ctx else None, **attrs)


# ---------------------------------------------------------------------------
# wire propagation


def inject(ctx: dict | None = None) -> dict:
    """Trace context for an outbound message payload: {"trace_id",
    "span_id"}. Uses (and creates, if absent) the current context."""
    ctx = ctx or current()
    if not ctx:
        ctx = {"trace_id": new_trace_id(), "span_id": new_span_id()}
    return {"trace_id": ctx["trace_id"], "span_id": ctx.get("span_id")}


def extract(msg: dict | None) -> dict | None:
    """Trace context from an inbound message, or None. Tolerates anything:
    legacy peers send no TRACE_KEY, future peers may extend it."""
    if not isinstance(msg, dict):
        return None
    ctx = msg.get(TRACE_KEY)
    if not isinstance(ctx, dict) or not isinstance(ctx.get("trace_id"), str):
        return None
    return ctx


# ---------------------------------------------------------------------------
# Chrome-trace / Perfetto export


def to_chrome_trace(spans: list[dict], *, extra_events: list[dict] | None = None,
                    metadata: dict | None = None) -> dict:
    """Render spans as a Chrome-trace JSON object (complete "X" events,
    microsecond timestamps) loadable in Perfetto / chrome://tracing.

    Each distinct (role, pid) becomes one trace process with a
    ``process_name`` metadata event; ``tid`` passes through so spans from
    different threads land in different lanes."""
    events: list[dict] = []
    procs: dict[tuple, int] = {}
    for s in spans:
        key = (s.get("role", "proc"), s.get("pid", 0))
        if key not in procs:
            pid = len(procs) + 1
            procs[key] = pid
            events.append({
                "name": "process_name", "ph": "M", "pid": pid, "tid": 0,
                "args": {"name": f"{key[0]}-{key[1]}"},
            })
    for s in spans:
        pid = procs[(s.get("role", "proc"), s.get("pid", 0))]
        t0, t1 = float(s["t0"]), float(s["t1"])
        args = {
            "trace_id": s.get("trace_id"),
            "span_id": s.get("span_id"),
            "parent_id": s.get("parent_id"),
        }
        args.update(s.get("attrs") or {})
        events.append({
            "name": s["name"], "ph": "X", "cat": "span",
            "ts": round(t0 * 1e6, 3),
            "dur": round(max(t1 - t0, 0.0) * 1e6, 3),
            "pid": pid, "tid": int(s.get("tid", 0)),
            "args": args,
        })
    events.extend(extra_events or [])
    out = {"traceEvents": events, "displayTimeUnit": "ms"}
    if metadata:
        out["otherData"] = metadata
    return out


def write_chrome_trace(path: str, spans: list[dict], **kwargs) -> str:
    """Atomic (tmp + rename) Chrome-trace file write."""
    trace = to_chrome_trace(spans, **kwargs)
    tmp = f"{path}.tmp-{os.getpid()}"
    with open(tmp, "w") as f:
        json.dump(trace, f)
        f.flush()
        os.fsync(f.fileno())
    os.replace(tmp, path)
    return path
