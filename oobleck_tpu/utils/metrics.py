"""Dependency-free cluster metrics plane.

One registry per process holds counters, gauges, and fixed-bucket
histograms (all label-aware, all thread-safe). Two sinks:

- Prometheus text exposition (``render_prometheus``), served cluster-wide
  by the master's ``MetricsHTTPServer`` (/metrics and /status);
- an append-only JSONL file under ``OOBLECK_METRICS_DIR``
  (``dump_jsonl``).

Snapshots are plain JSON dicts so they travel over the elastic protocol
(worker -> agent mp pipe -> master TCP METRICS push) and merge on the
master with ``host``/``role`` labels attached.

The module also hosts the control-plane flight recorder: a bounded ring
of recent events (registrations, heartbeats, reconfigurations, chaos
injections) that is dumped to ``OOBLECK_METRICS_DIR/flight-*.jsonl``
when a failure is detected or a recovery deadline is breached, turning
every chaos-test failure into a self-contained postmortem artifact.
"""

from __future__ import annotations

import bisect
import collections
import json
import logging
import os
import threading
import time
from http.server import BaseHTTPRequestHandler, ThreadingHTTPServer

logger = logging.getLogger(__name__)

ENV_METRICS_DIR = "OOBLECK_METRICS_DIR"
ENV_METRICS_PORT = "OOBLECK_METRICS_PORT"
ENV_FLIGHT_CAPACITY = "OOBLECK_FLIGHT_CAPACITY"
ENV_STRICT_REGISTRY = "OOBLECK_STRICT_REGISTRY"


def _strict_registry_check(kind: str, name: str) -> None:
    """Debug/test-run schema enforcement: with OOBLECK_STRICT_REGISTRY=1,
    a metric family or flight-event kind missing from the generated
    ``obs/registry.py`` raises instead of minting a silent, never-read
    parallel series (the OBL005 invariant, enforced at runtime for names
    lint cannot see). Off by default: tests record ad-hoc event kinds.
    Fail-open on import problems — the registry module is generated, and
    a half-built checkout must not take the metrics plane down."""
    if os.environ.get(ENV_STRICT_REGISTRY, "") not in ("1", "true", "yes"):
        return
    try:
        # Deferred import: obs -> metrics at module load, never the
        # reverse (registry is leaf, but the package __init__ is not).
        from oobleck_tpu.obs import registry
        allowed = (registry.METRIC_FAMILIES if kind == "metric"
                   else registry.FLIGHT_EVENT_KINDS)
    except (ImportError, AttributeError):
        return
    if name not in allowed:
        raise ValueError(
            f"{kind} name {name!r} is not in obs/registry.py — a typo "
            f"would emit a series nothing reads; regenerate with "
            f"`make gen-registry` if the name is intentional")

# Step/region wall times: sub-millisecond CPU smoke runs up to multi-second
# real steps.
DEFAULT_BUCKETS = (0.001, 0.0025, 0.005, 0.01, 0.025, 0.05, 0.1, 0.25,
                   0.5, 1.0, 2.5, 5.0, 10.0, 30.0, 60.0)
# Recovery latencies: the interesting range is seconds to minutes (the
# RECOVERY_DEADLINE budget in chaos tests is tens of seconds).
RECOVERY_BUCKETS = (0.1, 0.25, 0.5, 1.0, 2.5, 5.0, 10.0, 15.0, 30.0,
                    60.0, 120.0, 300.0, 600.0)
# Checkpoint train-loop stalls: the async writer's enqueue is tens of
# microseconds (reference capture, no device_get), the sync baseline is
# the full write — the histogram must resolve both ends to evidence the
# "<25% of synchronous stall" acceptance bar (oobleck_tpu/ckpt).
CKPT_STALL_BUCKETS = (0.0001, 0.00025, 0.0005, 0.001, 0.0025, 0.005,
                      0.01, 0.025, 0.05, 0.1, 0.25, 0.5, 1.0, 2.5, 5.0,
                      10.0, 30.0, 60.0)
# Serving latencies (TTFT, per-token decode, hot-reload pause): per-token
# times are sub-millisecond-to-tens-of-ms on warm caches, TTFT includes a
# prefill (up to seconds when it triggers a compile), and the reload-pause
# claim ("well below one checkpoint restore") needs sub-millisecond
# resolution at the bottom end.
SERVE_LATENCY_BUCKETS = (0.0001, 0.00025, 0.0005, 0.001, 0.0025, 0.005,
                         0.01, 0.025, 0.05, 0.1, 0.25, 0.5, 1.0, 2.5,
                         5.0, 10.0, 30.0)


def _label_key(labels: dict[str, str]) -> tuple[tuple[str, str], ...]:
    return tuple(sorted((str(k), str(v)) for k, v in labels.items()))


def _format_labels(pairs) -> str:
    if not pairs:
        return ""
    body = ",".join('%s="%s"' % (k, str(v).replace("\\", "\\\\")
                                 .replace('"', '\\"').replace("\n", "\\n"))
                    for k, v in pairs)
    return "{" + body + "}"


class _Metric:
    """Base for one named metric family; children keyed by label values."""

    kind = "untyped"

    def __init__(self, name: str, help_text: str):
        self.name = name
        self.help = help_text
        self._lock = threading.Lock()
        self._children: dict[tuple, object] = {}

    def _child(self, labels: dict[str, str], factory):
        key = _label_key(labels)
        with self._lock:
            child = self._children.get(key)
            if child is None:
                child = factory()
                self._children[key] = child
            return child

    def series(self) -> list[dict]:
        raise NotImplementedError


class Counter(_Metric):
    kind = "counter"

    def inc(self, amount: float = 1.0, **labels) -> None:
        cell = self._child(labels, lambda: [0.0])
        with self._lock:
            cell[0] += amount

    def value(self, **labels) -> float:
        cell = self._child(labels, lambda: [0.0])
        with self._lock:
            return cell[0]

    def series(self) -> list[dict]:
        with self._lock:
            return [{"labels": dict(key), "value": cell[0]}
                    for key, cell in self._children.items()]


class Gauge(_Metric):
    kind = "gauge"

    def set(self, value: float, **labels) -> None:
        cell = self._child(labels, lambda: [0.0])
        with self._lock:
            cell[0] = float(value)

    def inc(self, amount: float = 1.0, **labels) -> None:
        cell = self._child(labels, lambda: [0.0])
        with self._lock:
            cell[0] += amount

    def value(self, **labels) -> float:
        cell = self._child(labels, lambda: [0.0])
        with self._lock:
            return cell[0]

    def series(self) -> list[dict]:
        with self._lock:
            return [{"labels": dict(key), "value": cell[0]}
                    for key, cell in self._children.items()]


class _HistCell:
    __slots__ = ("counts", "sum", "count")

    def __init__(self, n_buckets: int):
        self.counts = [0] * n_buckets  # per-bucket (non-cumulative)
        self.sum = 0.0
        self.count = 0


class Histogram(_Metric):
    kind = "histogram"

    def __init__(self, name: str, help_text: str, buckets=DEFAULT_BUCKETS):
        super().__init__(name, help_text)
        self.buckets = tuple(sorted(float(b) for b in buckets))

    def observe(self, value: float, **labels) -> None:
        self.bind(**labels)(float(value))

    def bind(self, **labels):
        """`observe(value)` for one fixed label set: the series is looked
        up once, here, so a hot path that keeps the result pays for the
        bucket search and the lock only (locals, not attributes: it is
        called a dozen times a training step)."""
        cell = self._child(labels, lambda: _HistCell(len(self.buckets)))
        lock, buckets, counts = self._lock, self.buckets, cell.counts

        def observe(value: float) -> None:
            i = bisect.bisect_left(buckets, value)
            with lock:
                if i < len(counts):
                    counts[i] += 1
                cell.sum += value
                cell.count += 1

        return observe

    def series(self) -> list[dict]:
        with self._lock:
            return [{"labels": dict(key), "buckets": list(self.buckets),
                     "counts": list(cell.counts), "sum": cell.sum,
                     "count": cell.count}
                    for key, cell in self._children.items()]


class Registry:
    """Thread-safe collection of metric families, keyed by name."""

    def __init__(self):
        self._lock = threading.Lock()
        self._metrics: dict[str, _Metric] = {}
        # Bumped by clear(): whoever keeps a bound series (Histogram.bind)
        # across calls compares it and binds again after a clear.
        self.generation = 0

    def _get(self, name: str, cls, help_text: str, **kwargs) -> _Metric:
        with self._lock:
            metric = self._metrics.get(name)
            if metric is None:
                _strict_registry_check("metric", name)
                metric = cls(name, help_text, **kwargs)
                self._metrics[name] = metric
            elif not isinstance(metric, cls):
                raise ValueError(
                    f"metric {name!r} already registered as {metric.kind}")
            return metric

    def counter(self, name: str, help_text: str = "") -> Counter:
        return self._get(name, Counter, help_text)

    def gauge(self, name: str, help_text: str = "") -> Gauge:
        return self._get(name, Gauge, help_text)

    def histogram(self, name: str, help_text: str = "",
                  buckets=DEFAULT_BUCKETS) -> Histogram:
        return self._get(name, Histogram, help_text, buckets=buckets)

    def snapshot(self) -> dict:
        """JSON-serializable view: ships over the wire and into JSONL."""
        with self._lock:
            metrics = list(self._metrics.values())
        return {
            "t": time.time(),
            "metrics": [{"name": m.name, "type": m.kind, "help": m.help,
                         "series": m.series()} for m in metrics],
        }

    def clear(self) -> None:
        with self._lock:
            self._metrics.clear()
            self.generation += 1


def render_prometheus(snapshots: list[dict],
                      extra_labels: list[dict[str, str]] | None = None,
                      ) -> str:
    """Render one or more registry snapshots as Prometheus text.

    ``extra_labels[i]`` (e.g. {"host": ..., "role": ...}) is attached to
    every series of ``snapshots[i]`` so the master can expose a merged
    cluster-wide view without name collisions.
    """
    families: dict[str, dict] = {}
    for i, snap in enumerate(snapshots):
        extra = (extra_labels or [{}] * len(snapshots))[i] or {}
        for metric in snap.get("metrics", []):
            fam = families.setdefault(
                metric["name"],
                {"type": metric["type"], "help": metric.get("help", ""),
                 "series": []})
            for s in metric.get("series", []):
                merged = dict(extra)
                merged.update(s.get("labels", {}))
                families[metric["name"]]["series"].append(
                    {**s, "labels": merged})
            fam["type"] = metric["type"]

    lines: list[str] = []
    for name in sorted(families):
        fam = families[name]
        if fam["help"]:
            lines.append(f"# HELP {name} {fam['help']}")
        lines.append(f"# TYPE {name} {fam['type']}")
        for s in fam["series"]:
            pairs = _label_key(s.get("labels", {}))
            if fam["type"] == "histogram":
                cumulative = 0
                for upper, cnt in zip(s["buckets"], s["counts"]):
                    cumulative += cnt
                    bucket_pairs = pairs + (("le", repr(float(upper))),)
                    lines.append("%s_bucket%s %d" % (
                        name, _format_labels(bucket_pairs), cumulative))
                inf_pairs = pairs + (("le", "+Inf"),)
                lines.append("%s_bucket%s %d" % (
                    name, _format_labels(inf_pairs), s["count"]))
                lines.append("%s_sum%s %g" % (
                    name, _format_labels(pairs), s["sum"]))
                lines.append("%s_count%s %d" % (
                    name, _format_labels(pairs), s["count"]))
            else:
                lines.append("%s%s %g" % (
                    name, _format_labels(pairs), s["value"]))
    return "\n".join(lines) + "\n"


def histogram_percentile(series: dict, q: float) -> float | None:
    """Estimate the q-th percentile (0..1) from one histogram series dict
    (as found in a snapshot) by linear interpolation within the bucket."""
    count = series.get("count", 0)
    if not count:
        return None
    target = q * count
    cumulative = 0
    lower = 0.0
    for upper, cnt in zip(series["buckets"], series["counts"]):
        if cumulative + cnt >= target:
            if cnt == 0:
                return float(upper)
            frac = (target - cumulative) / cnt
            return lower + (float(upper) - lower) * frac
        cumulative += cnt
        lower = float(upper)
    # Beyond the last finite bucket: best effort from the running mean.
    return max(lower, series["sum"] / count)


# ---------------------------------------------------------------------------
# process-global registry / role / sinks


_registry = Registry()
_role = "proc"
_role_lock = threading.Lock()


def registry() -> Registry:
    return _registry


def set_role(role: str) -> None:
    """Tag this process (master/agent/worker) for sink file names."""
    global _role
    with _role_lock:
        _role = role


def get_role() -> str:
    with _role_lock:
        return _role


def metrics_dir() -> str | None:
    d = os.environ.get(ENV_METRICS_DIR)
    if not d:
        return None
    try:
        os.makedirs(d, exist_ok=True)
    except OSError as e:
        logger.warning("metrics: cannot create %s: %s", d, e)
        return None
    return d


def dump_jsonl(snapshot: dict | None = None) -> str | None:
    """Append one snapshot line to OOBLECK_METRICS_DIR/metrics-{role}-{pid}
    .jsonl. Returns the path, or None when the sink is disabled."""
    d = metrics_dir()
    if d is None:
        return None
    if snapshot is None:
        snapshot = _registry.snapshot()
    snapshot = dict(snapshot)
    snapshot.setdefault("role", get_role())
    path = os.path.join(d, f"metrics-{get_role()}-{os.getpid()}.jsonl")
    try:
        with open(path, "a") as f:
            f.write(json.dumps(snapshot) + "\n")
    except OSError as e:
        logger.warning("metrics: cannot append to %s: %s", path, e)
        return None
    return path


def read_jsonl_dir(d: str) -> list[dict]:
    """Load every snapshot line from metrics-*.jsonl under ``d``, tagging
    each with its source file (``_file``) — counters/histograms are
    per-process cumulative, so consumers aggregate the LAST snapshot per
    file. Malformed lines are skipped (a SIGKILLed writer can leave a torn
    tail)."""
    snapshots: list[dict] = []
    try:
        names = sorted(os.listdir(d))
    except OSError:
        return snapshots
    for name in names:
        if not (name.startswith("metrics-") and name.endswith(".jsonl")):
            continue
        try:
            with open(os.path.join(d, name)) as f:
                for line in f:
                    line = line.strip()
                    if not line:
                        continue
                    try:
                        snap = json.loads(line)
                    except ValueError:
                        continue
                    if isinstance(snap, dict):
                        snap["_file"] = name
                        snapshots.append(snap)
        except OSError:
            continue
    return snapshots


def latest_per_file(snapshots: list[dict]) -> list[dict]:
    """The last snapshot of each source file (see read_jsonl_dir)."""
    by_file: dict[str, dict] = {}
    for snap in snapshots:
        by_file[snap.get("_file", "")] = snap
    return list(by_file.values())


def find_series(snapshots: list[dict], name: str) -> list[dict]:
    """All series dicts of metric `name` across snapshots."""
    out = []
    for snap in snapshots:
        for m in snap.get("metrics", []):
            if m.get("name") == name:
                out.extend(m.get("series", []))
    return out


def merge_histogram_series(series: list[dict]) -> dict | None:
    """Sum histogram series (same bucket layout) into one, for cluster-wide
    percentiles; None when empty or bucket layouts disagree."""
    merged: dict | None = None
    for s in series:
        if "buckets" not in s:
            continue
        if merged is None:
            merged = {"buckets": list(s["buckets"]),
                      "counts": list(s["counts"]),
                      "sum": s["sum"], "count": s["count"]}
        elif merged["buckets"] == list(s["buckets"]):
            merged["counts"] = [a + b for a, b
                                in zip(merged["counts"], s["counts"])]
            merged["sum"] += s["sum"]
            merged["count"] += s["count"]
    return merged


# ---------------------------------------------------------------------------
# flight recorder


class FlightRecorder:
    """Bounded ring of recent control-plane events. ``dump()`` writes the
    whole ring to OOBLECK_METRICS_DIR/flight-{role}-{pid}-{seq}.jsonl."""

    def __init__(self, capacity: int | None = None):
        if capacity is None:
            raw = os.environ.get(ENV_FLIGHT_CAPACITY, "")
            try:
                capacity = int(raw) if raw else 256
            except ValueError:
                logger.warning("metrics: malformed %s=%r ignored",
                               ENV_FLIGHT_CAPACITY, raw)
                capacity = 256
        self._lock = threading.Lock()
        self._ring: collections.deque = collections.deque(
            maxlen=max(capacity, 1))
        self._seq = 0

    def record(self, event: str, **fields) -> None:
        _strict_registry_check("flight event", event)
        entry = {"t": time.time(), "event": event}
        entry.update(fields)
        with self._lock:
            self._ring.append(entry)

    def events(self) -> list[dict]:
        with self._lock:
            return list(self._ring)

    def clear(self) -> None:
        with self._lock:
            self._ring.clear()

    def dump(self, reason: str) -> str | None:
        d = metrics_dir()
        if d is None:
            return None
        with self._lock:
            events = list(self._ring)
            self._seq += 1
            seq = self._seq
        path = os.path.join(
            d, f"flight-{get_role()}-{os.getpid()}-{seq}.jsonl")
        try:
            with open(path, "w") as f:
                f.write(json.dumps({"t": time.time(), "event": "dump",
                                    "reason": reason,
                                    "role": get_role()}) + "\n")
                for entry in events:
                    f.write(json.dumps(entry) + "\n")
        except OSError as e:
            logger.warning("metrics: cannot write flight dump %s: %s",
                           path, e)
            return None
        logger.info("flight recorder dumped %d events to %s (%s)",
                    len(events), path, reason)
        return path


_flight = FlightRecorder()


def flight_recorder() -> FlightRecorder:
    return _flight


# ---------------------------------------------------------------------------
# HTTP endpoint (master)


class MetricsHTTPServer:
    """Stdlib ThreadingHTTPServer serving /metrics (Prometheus text from
    ``metrics_fn``) and /status (JSON from ``status_fn``) on a daemon
    thread. Port 0 binds an ephemeral port; read ``.port`` after start."""

    def __init__(self, metrics_fn, status_fn, port: int = 0,
                 host: str = "0.0.0.0"):
        self._metrics_fn = metrics_fn
        self._status_fn = status_fn
        outer = self

        class Handler(BaseHTTPRequestHandler):
            def log_message(self, fmt, *args):  # keep test logs quiet
                logger.debug("metrics http: " + fmt, *args)

            def do_GET(self):
                try:
                    if self.path.split("?")[0] == "/metrics":
                        body = outer._metrics_fn().encode()
                        ctype = "text/plain; version=0.0.4; charset=utf-8"
                    elif self.path.split("?")[0] == "/status":
                        body = json.dumps(outer._status_fn()).encode()
                        ctype = "application/json"
                    else:
                        self.send_error(404)
                        return
                except Exception:  # noqa: BLE001 — endpoint must never take the master down
                    logger.exception("metrics http handler failed")
                    self.send_error(500)
                    return
                self.send_response(200)
                self.send_header("Content-Type", ctype)
                self.send_header("Content-Length", str(len(body)))
                self.end_headers()
                self.wfile.write(body)

        self._server = ThreadingHTTPServer((host, port), Handler)
        self._server.daemon_threads = True
        self.port = self._server.server_address[1]
        self._thread = threading.Thread(
            target=self._server.serve_forever, name="oobleck-metrics-http",
            daemon=True)

    def start(self) -> "MetricsHTTPServer":
        self._thread.start()
        return self

    def close(self) -> None:
        self._server.shutdown()
        self._server.server_close()
