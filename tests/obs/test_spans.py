"""Span recorder core (oobleck_tpu/obs/spans): ring bounds, nesting /
ambient-context stitching, wire propagation (inject/extract with legacy
peers), and the Chrome-trace export contract Perfetto actually loads."""

import json
import threading

from oobleck_tpu.obs import spans


def test_ring_is_bounded_and_thread_safe():
    rec = spans.SpanRecorder(capacity=8)
    def worker(k):
        for i in range(50):
            rec.record(f"w{k}.{i}", 0.0, 1.0)
    threads = [threading.Thread(target=worker, args=(k,)) for k in range(4)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    got = rec.spans()
    assert len(got) == 8  # 200 recorded, only the newest 8 retained
    assert all(s["span_id"] and s["trace_id"] for s in got)


def test_capacity_env_parsing(monkeypatch):
    monkeypatch.setenv(spans.ENV_SPAN_CAPACITY, "3")
    assert spans.SpanRecorder()._ring.maxlen == 3
    monkeypatch.setenv(spans.ENV_SPAN_CAPACITY, "banana")
    assert spans.SpanRecorder()._ring.maxlen == 1024  # malformed -> default
    monkeypatch.setenv(spans.ENV_SPAN_CAPACITY, "0")
    assert spans.SpanRecorder()._ring.maxlen == 1  # floor, never unbounded


def test_nested_spans_share_trace_and_parent():
    rec = spans.SpanRecorder(capacity=16)
    with spans.span("outer", recorder=rec) as outer:
        with spans.span("inner", recorder=rec) as inner:
            assert inner["trace_id"] == outer["trace_id"]
    inner_s, outer_s = rec.spans()  # inner closes (and records) first
    assert inner_s["name"] == "inner" and outer_s["name"] == "outer"
    assert inner_s["parent_id"] == outer_s["span_id"]
    assert inner_s["trace_id"] == outer_s["trace_id"]
    assert outer_s["parent_id"] is None
    assert outer_s["t1"] >= outer_s["t0"]


def test_ambient_context_stitches_unrelated_spans():
    """The engine pins the incident trace as ambient around reconfigure();
    spans opened anywhere in the process during that window must join it."""
    rec = spans.SpanRecorder(capacity=16)
    tid = spans.new_trace_id()
    spans.set_ambient({"trace_id": tid, "span_id": "rootspan"})
    try:
        with spans.span("somewhere.deep", recorder=rec):
            pass
        ev = spans.event("a.point.mark")
    finally:
        spans.set_ambient(None)
    s = rec.spans()[0]
    assert s["trace_id"] == tid and s["parent_id"] == "rootspan"
    assert ev["trace_id"] == tid
    assert ev["t0"] == ev["t1"]  # point event
    # ambient cleared: a fresh span mints its own trace again
    with spans.span("after", recorder=rec):
        pass
    assert rec.spans()[-1]["trace_id"] != tid


def test_for_trace_filters():
    rec = spans.SpanRecorder(capacity=16)
    a = rec.record("a", 0.0, 1.0)
    rec.record("b", 0.0, 1.0)
    assert [s["name"] for s in rec.for_trace(a["trace_id"])] == ["a"]


# ------------------------------------------------------------------ #
# wire propagation: the TRACE_KEY payload riding the elastic verbs


def test_inject_extract_roundtrip():
    with spans.span("sender") as ctx:
        msg = {"kind": "reconfigure", "lost_ip": "10.0.0.2"}
        msg[spans.TRACE_KEY] = spans.inject()
    got = spans.extract(msg)
    assert got == {"trace_id": ctx["trace_id"], "span_id": ctx["span_id"]}


def test_extract_tolerates_legacy_and_malformed_peers():
    # a legacy peer sends no trace key at all
    assert spans.extract({"kind": "reconfigure", "lost_ip": "x"}) is None
    assert spans.extract(None) is None
    assert spans.extract("not a dict") is None
    # future/hostile shapes must not raise, only decline
    assert spans.extract({spans.TRACE_KEY: "oops"}) is None
    assert spans.extract({spans.TRACE_KEY: {"trace_id": 7}}) is None
    # extra context keys pass through untouched (forward compat)
    ctx = {"trace_id": "abc", "detected_at": 1.5, "cause": "chaos"}
    assert spans.extract({spans.TRACE_KEY: ctx}) == ctx


def test_inject_without_context_mints_fresh_ids():
    ctx = spans.inject()
    assert isinstance(ctx["trace_id"], str) and len(ctx["trace_id"]) == 16


# ------------------------------------------------------------------ #
# Chrome-trace export


def test_chrome_trace_shape_and_process_lanes():
    rec = spans.SpanRecorder(capacity=16)
    rec.record("step", 10.0, 10.5, foo="bar")
    rec.record("other", 10.2, 10.3)
    trace = spans.to_chrome_trace(rec.spans(), metadata={"src": "test"})
    assert trace["displayTimeUnit"] == "ms"
    assert trace["otherData"] == {"src": "test"}
    xs = [e for e in trace["traceEvents"] if e["ph"] == "X"]
    ms = [e for e in trace["traceEvents"] if e["ph"] == "M"]
    assert len(xs) == 2
    # one process lane per (role, pid), named for Perfetto's sidebar
    assert [m["name"] for m in ms] == ["process_name"]
    for e in xs:
        assert e["ts"] >= 0 and e["dur"] >= 0  # complete events, never open
        assert isinstance(e["args"]["trace_id"], str)
    assert xs[0]["dur"] == 0.5e6  # seconds -> microseconds
    assert xs[0]["args"]["foo"] == "bar"
    json.dumps(trace)  # and the whole thing is JSON-serializable


def test_write_chrome_trace_is_loadable(tmp_path):
    rec = spans.SpanRecorder(capacity=4)
    rec.record("a", 1.0, 2.0)
    path = str(tmp_path / "trace.json")
    assert spans.write_chrome_trace(path, rec.spans()) == path
    with open(path) as f:
        loaded = json.load(f)
    assert {e["ph"] for e in loaded["traceEvents"]} == {"M", "X"}
    assert not list(tmp_path.glob("*.tmp-*"))  # atomic: no droppings


def test_dump_writes_jsonl_with_header(tmp_path, monkeypatch):
    from oobleck_tpu.utils import metrics

    monkeypatch.setenv(metrics.ENV_METRICS_DIR, str(tmp_path))
    rec = spans.SpanRecorder(capacity=4)
    rec.record("x", 0.0, 1.0)
    path = rec.dump("test_reason")
    assert path is not None
    lines = [json.loads(line) for line in open(path)]
    assert lines[0]["event"] == "dump" and lines[0]["reason"] == "test_reason"
    assert [s["name"] for s in lines[1:]] == ["x"]


def test_dump_disabled_without_sink(monkeypatch):
    from oobleck_tpu.utils import metrics

    monkeypatch.delenv(metrics.ENV_METRICS_DIR, raising=False)
    assert spans.SpanRecorder(capacity=4).dump("r") is None


# ------------------------------------------------------------------ #
# region(): the hot-path call, and the annotation both calls open


class _Annotations:
    """Stand-in for jax.profiler.TraceAnnotation that records opens and
    closes in order (the real one writes to the profiler's trace)."""

    def __init__(self):
        self.log = []

    def __call__(self, name):
        log = self.log

        class Annotation:
            def __enter__(self):
                log.append(("open", name))

            def __exit__(self, *exc):
                log.append(("close", name))

        return Annotation()


def _span_series(name):
    from oobleck_tpu.utils import metrics

    hist = metrics.registry().histogram(spans.SPAN_SECONDS)
    return [s for s in hist.series() if s["labels"] == {"span": name}]


def test_region_nests_and_annotates(monkeypatch):
    fake = _Annotations()
    monkeypatch.setattr(spans.jax.profiler, "TraceAnnotation", fake)
    with spans.region("t.outer"):
        with spans.region("t.inner"):
            pass
    assert fake.log == [("open", "t.outer"), ("open", "t.inner"),
                        ("close", "t.inner"), ("close", "t.outer")]


def test_region_survives_an_exception(monkeypatch):
    fake = _Annotations()
    monkeypatch.setattr(spans.jax.profiler, "TraceAnnotation", fake)
    before = sum(s["count"] for s in _span_series("t.raises"))
    try:
        with spans.region("t.raises"):
            raise KeyError("x")
    except KeyError:
        pass
    else:
        raise AssertionError("region() swallowed the exception")
    assert fake.log == [("open", "t.raises"), ("close", "t.raises")]
    assert sum(s["count"] for s in _span_series("t.raises")) == before + 1


def test_region_observes_span_seconds():
    import time

    before = _span_series("t.observed")
    n0 = before[0]["count"] if before else 0
    s0 = before[0]["sum"] if before else 0.0
    with spans.region("t.observed"):
        time.sleep(0.01)
    (after,) = _span_series("t.observed")
    assert after["count"] == n0 + 1
    assert 0.01 <= after["sum"] - s0 < 1.0


def test_region_binds_again_after_the_registry_is_cleared():
    from oobleck_tpu.utils import metrics

    with spans.region("t.cleared"):
        pass
    metrics.registry().clear()
    with spans.region("t.cleared"):
        pass
    (series,) = _span_series("t.cleared")
    assert series["count"] == 1


def test_region_allocates_no_ids_and_records_nothing(monkeypatch):
    def no_ids():
        raise AssertionError("region() asked for an id")

    monkeypatch.setattr(spans.uuid, "uuid4", no_ids)
    monkeypatch.setattr(spans.time, "time", no_ids)
    ring = len(spans.span_recorder().spans())
    with spans.region("t.no_ids"):
        pass
    assert len(spans.span_recorder().spans()) == ring
    assert spans.current() is None  # no frame on the span stack either


def test_region_costs_microseconds_with_no_trace_running():
    import time

    n = 20_000
    for _ in range(1000):
        with spans.region("t.cost"):
            pass
    t0 = time.perf_counter()
    for _ in range(n):
        with spans.region("t.cost"):
            pass
    per_call = (time.perf_counter() - t0) / n
    # ~2 us on an idle host; the limit only catches a region() that grew
    # an id, a lock convoy or a dict per call, not a busy CI machine.
    assert per_call < 50e-6, per_call


def test_span_opens_an_annotation_too(monkeypatch):
    fake = _Annotations()
    monkeypatch.setattr(spans.jax.profiler, "TraceAnnotation", fake)
    rec = spans.SpanRecorder(capacity=4)
    with spans.span("t.incident", recorder=rec):
        with spans.region("t.within"):
            pass
    assert fake.log == [("open", "t.incident"), ("open", "t.within"),
                        ("close", "t.within"), ("close", "t.incident")]
    assert [s["name"] for s in rec.spans()] == ["t.incident"]


def test_span_as_a_decorator_records_each_call():
    rec = spans.SpanRecorder(capacity=4)

    @spans.span("t.decorated", recorder=rec)
    def work(x):
        return x + 1

    assert work(1) == 2 and work(2) == 3
    assert [s["name"] for s in rec.spans()] == ["t.decorated"] * 2
    assert rec.spans()[0]["span_id"] != rec.spans()[1]["span_id"]
