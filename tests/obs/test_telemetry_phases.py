"""A step's host phases reach the telemetry ring: `obs/spans.region` adds
its seconds to the calling thread's `StepAccumulator` and keeps the open
regions on its stack; a sample grows at its end only; the digest says why
a straggler is slow and a legacy reader still takes the old form."""

import threading

import pytest

from oobleck_tpu.obs import spans, telemetry
from oobleck_tpu.obs.telemetry import PHASES, TelemetryRing


@pytest.fixture
def acc():
    a = spans.StepAccumulator()
    a.install()
    yield a
    a.uninstall()


class _Ticks:
    """`time.perf_counter` for obs/spans: one second a call."""

    def __init__(self):
        self.t = 0.0

    def perf_counter(self):
        self.t += 1.0
        return self.t


def test_region_sequence_gives_phases_and_open_stack(acc, monkeypatch):
    monkeypatch.setattr(spans, "time", _Ticks())
    seen = []
    with spans.region("engine.step"):
        with spans.region("engine.staging"):
            seen.append(list(acc.stack))
        with spans.region("pipeline.dispatch"):
            with spans.region("pipeline.flush_sends"):
                seen.append(acc.innermost())
        with spans.region("engine.staging"):
            pass
        seen.append(acc.innermost())
    assert seen == [["engine.step", "engine.staging"],
                    "pipeline.flush_sends", "engine.step"]
    assert acc.stack == [] and acc.innermost() is None
    # Enter and exit read the clock once each, one second apart; a region
    # that closed twice holds the sum.
    assert acc.seconds == {
        "engine.staging": 2.0, "pipeline.flush_sends": 1.0,
        "pipeline.dispatch": 3.0, "engine.step": 9.0}
    assert telemetry.phases_of(acc.seconds) == (2.0, 3.0, 0.0, 0.0, 0.0)
    acc.begin()
    assert acc.seconds == {}


def test_fused_step_takes_the_dispatch_place():
    phases = telemetry.phases_of({"engine.fused_step": 0.5,
                                  "engine.loss_readback": 0.25})
    assert dict(zip(PHASES, phases)) == {
        "engine.staging": 0.0, "pipeline.dispatch": 0.5, "dp.allreduce": 0.0,
        "engine.optimizer": 0.0, "engine.loss_readback": 0.25}


def test_regions_on_a_second_thread_feed_nothing(acc):
    def other():
        with spans.region("engine.staging"):
            pass

    t = threading.Thread(target=other)
    t.start()
    t.join(timeout=30)
    assert not t.is_alive()
    assert acc.seconds == {} and acc.stack == []
    with spans.region("engine.staging"):
        pass
    assert list(acc.seconds) == ["engine.staging"]


def test_a_region_open_across_install_and_uninstall_is_left_alone():
    a = spans.StepAccumulator()
    before = spans.region("engine.bookkeeping")
    before.__enter__()
    a.install()
    try:
        before.__exit__(None, None, None)       # opened before: not counted
        inside = spans.region("engine.staging")
        inside.__enter__()
    finally:
        a.uninstall()
    inside.__exit__(None, None, None)           # closes what it opened
    assert a.stack == [] and list(a.seconds) == ["engine.staging"]


def test_sample_grows_at_its_end_only():
    ring = TelemetryRing(capacity=4, window=4)
    ring.enabled = True
    sample = ring.record_step(
        7, 0.9, compute_s=0.1, comm_s=0.2, data_wait_s=0.3, ckpt_s=0.4,
        live_bytes=5, between_s=0.01, phases=(0.1, 0.2, 0.0, 0.05, 0.5),
        hbm=(90, 100, 4))
    assert ring.samples() == [sample] and ring.last() == sample
    assert sample[:7] == (7, 0.9, 0.1, 0.2, 0.3, 0.4, 5)
    _, step_s, compute_s, *_ = sample           # how the older tests unpack
    assert (step_s, compute_s) == (0.9, 0.1)
    assert len(sample) == telemetry.SAMPLE_LEN
    assert sample[telemetry.BETWEEN_S] == 0.01
    assert sample[telemetry.PHASES_AT] == (0.1, 0.2, 0.0, 0.05, 0.5)
    assert sample[telemetry.HBM_IN_USE:] == (90, 100, 4)
    assert telemetry.sample_fields(sample)["phases"]["engine.loss_readback"] \
        == 0.5
    # What the callers that predate the fields pass still makes a whole
    # sample.
    old = ring.record_step(8, 1.0)
    assert old[7:] == (0.0, (0.0,) * len(PHASES), None, None, None)
    assert ring.recent_step_s(8) == [0.9, 1.0]
    assert ring.recent_step_s(1) == [1.0]


def test_digest_says_why_and_a_legacy_digest_still_passes():
    ring = TelemetryRing(capacity=8, window=2)
    ring.enabled = True
    ring.record_step(1, 9.0, phases=(9.0,) * 5, between_s=9.0,
                     hbm=(1, 100, 1))                 # outside the window
    ring.record_step(2, 1.0, phases=(0.0, 0.2, 0.0, 0.0, 0.6),
                     between_s=0.02, hbm=(50, 100, 30))
    ring.record_step(3, 1.0, phases=(0.0, 0.4, 0.0, 0.0, 0.2),
                     between_s=0.04, hbm=(80, 100, 10))
    d = ring.digest()
    assert d["dispatch_s"] == pytest.approx(0.3)
    assert d["readback_s"] == pytest.approx(0.4)
    assert d["between_s"] == pytest.approx(0.03)
    assert d["hbm_free_frac"] == pytest.approx(0.2)
    assert telemetry.digest_ok(d)
    legacy = {k: v for k, v in d.items() if k not in
              ("dispatch_s", "readback_s", "between_s", "hbm_free_frac")}
    assert d["v"] == telemetry.DIGEST_VERSION == 1
    assert telemetry.digest_ok(legacy)
    # A platform that reports no memory: the key is there and says so.
    ring.record_step(4, 1.0)
    assert ring.digest()["hbm_free_frac"] is None


def test_fleet_snapshot_reads_a_straggler_with_its_cause():
    from oobleck_tpu.obs.fleet import FleetTracker

    ring = TelemetryRing(capacity=8, window=4)
    ring.enabled = True
    ring.record_step(5, 2.5, phases=(0.0, 2.3, 0.0, 0.0, 0.1),
                     between_s=0.01, hbm=(99, 100, 0))
    tracker = FleetTracker(ratio=1.5, z=3.0, persist=1)
    tracker.ingest("10.0.0.1", ring.digest())
    tracker.ingest("10.0.0.2", {"v": 1, "step": 5, "step_s": 1.0})  # legacy
    hosts = tracker.snapshot()["hosts"]
    assert hosts["10.0.0.1"]["cause"] == {
        "dispatch_s": 2.3, "readback_s": 0.1, "between_s": 0.01,
        "hbm_free_frac": 0.01}
    assert hosts["10.0.0.2"]["cause"] == {}
