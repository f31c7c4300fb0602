"""A step that overstays names itself: the watchdog (obs/telemetry.py)
looks at the step while it is open and records the region and the frame
the train thread is held in. The clock and the wait are the test's, so no
case sleeps and none compares two wall clocks."""

import threading

import jax
import pytest

from oobleck_tpu.execution import engine as engine_mod
from oobleck_tpu.obs import spans, telemetry
from oobleck_tpu.obs.telemetry import StepWatchdog, TelemetryRing
from oobleck_tpu.utils import metrics, tracing
from tests.execution.test_engine import cache_env, make_engine  # noqa: F401

THREAD = "oobleck-step-watchdog"


def _events(kind):
    return [e for e in metrics.flight_recorder().events()
            if e["event"] == kind]


def _stalls(phase):
    return metrics.registry().counter(
        "oobleck_step_stalls_total").value(phase=phase)


def _watchdog_threads():
    return [t for t in threading.enumerate() if t.name == THREAD]


class _Clock:
    def __init__(self):
        self.t = 100.0

    def __call__(self):
        return self.t


def _ring(*step_seconds):
    ring = TelemetryRing(capacity=16, window=8)
    ring.enabled = True
    for i, s in enumerate(step_seconds):
        ring.record_step(i + 1, s, hbm=(90 + i, 100, 5))
    return ring


def _watchdog(ring, clock, **kwargs):
    """A watchdog past its first step, looked through by hand: no thread."""
    acc = spans.StepAccumulator()
    acc.owner = threading.get_ident()
    wd = StepWatchdog(ring, acc, clock=clock, **kwargs)
    wd.step_opens(0)                # the first step is never published
    assert wd.open is None
    return wd, acc


@pytest.fixture(autouse=True)
def _clean_flight():
    metrics.flight_recorder().clear()
    yield


def test_not_armed_under_three_samples():
    clock = _Clock()
    wd, _ = _watchdog(_ring(1.0, 1.0), clock)
    wd.step_opens(3)
    clock.t += 1000.0
    assert wd.check() == StepWatchdog.IDLE_S
    assert not _events("step_stall") and wd.fired_step == -1


def test_fires_once_at_twice_the_median_and_not_before():
    clock = _Clock()
    # The median and not a mean: a compiling first step does not move it.
    wd, acc = _watchdog(_ring(30.0, 1.0, 1.0, 1.0, 1.0), clock)
    before = _stalls("pipeline.dispatch")
    wd.step_opens(6)
    acc.stack[:] = ["engine.step", "pipeline.dispatch"]
    clock.t += 1.5
    assert wd.check() == pytest.approx(0.5)     # the time left, to the tick
    assert not _events("step_stall")
    clock.t += 0.5
    assert wd.check() == pytest.approx(0.25)    # a quarter of the median
    clock.t += 50.0
    wd.check()                                  # still open: once a step
    (event,) = _events("step_stall")
    assert event["step"] == 6 and event["phase"] == "pipeline.dispatch"
    assert event["open_s"] == pytest.approx(2.0)
    assert event["median_s"] == pytest.approx(1.0)
    assert event["last_sample"]["hbm_in_use"] == 94
    assert event["last_sample"]["hbm_largest_free"] == 5
    # This thread stands in for the train thread: its frames are listed
    # under that name, innermost first, a dozen at most.
    frames = event["frames"]["train"]
    assert 0 < len(frames) <= StepWatchdog.FRAMES
    assert frames[0].endswith(":thread_frames")
    assert any(f.endswith(
        ":test_fires_once_at_twice_the_median_and_not_before")
        for f in frames)
    assert all(len(f.split(":")) == 3 for f in frames)
    assert _stalls("pipeline.dispatch") - before == 1
    # The step ends: its whole sample follows, once; a later step's does not.
    wd.step_closes()
    wd.step_recorded(wd._ring.record_step(
        6, 52.0, between_s=0.01, phases=(0.0, 51.0, 0.0, 0.1, 0.5),
        hbm=(99, 100, 0)))
    wd.step_recorded(wd._ring.record_step(7, 1.0))
    (end,) = _events("step_stall_end")
    assert end["step"] == 6 and end["step_s"] == 52.0
    assert end["phases"]["pipeline.dispatch"] == 51.0
    assert end["between_s"] == 0.01 and end["hbm_largest_free"] == 0


def test_a_step_under_the_threshold_fires_nothing():
    clock = _Clock()
    wd, _ = _watchdog(_ring(1.0, 1.0, 1.0), clock)
    for step in range(4, 9):
        wd.step_opens(step)
        clock.t += 1.9
        assert wd.check() == pytest.approx(0.1)
        wd.step_closes()
        assert wd.check() == pytest.approx(0.25)
    assert not _events("step_stall") and not _events("step_stall_end")


def test_stall_window_needs_a_directory_and_skips_an_open_session(
        monkeypatch, tmp_path):
    started = []

    def start_trace(path):
        started.append(path)
        raise RuntimeError("Only one profile may be run at a time.")

    monkeypatch.setattr(tracing.jax.profiler, "start_trace", start_trace)
    monkeypatch.setattr(tracing.jax.profiler, "stop_trace",
                        lambda: started.append("stop"))
    monkeypatch.delenv("OOBLECK_TRACE_DIR", raising=False)
    assert tracing.StepTracer().open_stall_window(5) is False
    assert started == []                    # no directory: no session
    monkeypatch.setenv("OOBLECK_TRACE_DIR", str(tmp_path))
    tracer = tracing.StepTracer()
    # A session is open (the benchmark's traced run): a skip, as on_step's.
    assert tracer.open_stall_window(5) is False
    assert started == [str(tmp_path / "stall-5")]
    assert tracer.stall_open is False
    tracer.close()
    assert started == [str(tmp_path / "stall-5")]       # nothing to stop
    # The watchdog fires all the same, and asks once.
    clock = _Clock()
    wd, _ = _watchdog(_ring(1.0, 1.0, 1.0), clock,
                      open_trace=tracer.open_stall_window)
    wd.step_opens(4)
    clock.t += 3.0
    wd.check()
    assert len(_events("step_stall")) == 1
    assert started == [str(tmp_path / "stall-5"), str(tmp_path / "stall-4")]
    # Granted, the window is the train thread's to close, once.
    monkeypatch.setattr(tracing.jax.profiler, "start_trace", started.append)
    assert tracer.open_stall_window(9) is True and tracer.stall_open
    assert tracer.open_stall_window(9) is False
    tracer.close_stall_window()
    tracer.close_stall_window()
    assert started[-2:] == [str(tmp_path / "stall-9"), "stop"]


class _HeldRun:
    """The watchdog's clock and wait for a live engine. Time stands still
    while steps run, so none looks long; once the train thread is held,
    every wait lets exactly the time pass that the watchdog meant to wait,
    and the first wait after `step_stall` lets the step go."""

    def __init__(self):
        self.t = 0.0
        self.holding = threading.Event()
        self.release = threading.Event()
        self.watchdogs = []

    def make(self, ring, acc, **kwargs):
        wd = StepWatchdog(ring, acc, clock=lambda: self.t, wait=self.wait,
                          **kwargs)
        self.watchdogs.append(wd)
        return wd

    def wait(self, timeout):
        if self.holding.is_set() and not self.release.is_set():
            self.t += timeout
            if _events("step_stall"):
                self.release.set()
            return False
        return self.watchdogs[-1]._stop.wait(timeout)


@pytest.fixture(scope="module")
def engine(cache_env):  # noqa: F811
    eng = make_engine(num_hosts=1, steps=5, devices=jax.devices()[:1],
                      microbatch=1, global_mb=2)
    eng.initialize_distributed()
    eng.instantiate_pipelines(eng.args.job.global_num_microbatch)
    return eng


def test_a_held_step_names_its_region_and_frame_and_leaves_no_thread(
        engine, monkeypatch):
    monkeypatch.delenv("OOBLECK_TRACE_DIR", raising=False)
    sessions = []
    monkeypatch.setattr(tracing.jax.profiler, "start_trace", sessions.append)
    run = _HeldRun()
    monkeypatch.setattr(engine_mod.obs_telemetry, "StepWatchdog", run.make)
    telemetry.reset()
    host_sync = engine_mod._host_sync
    calls = []

    def held_readback(value):
        calls.append(value)
        if len(calls) == 4:         # three samples in the ring: armed
            with spans.region("engine.loss_readback"):
                run.holding.set()
                assert run.release.wait(timeout=120)
        return host_sync(value)

    monkeypatch.setattr(engine_mod, "_host_sync", held_readback)
    before = _stalls("engine.loss_readback")
    engine.train()
    assert engine.step == 5 and run.release.is_set()
    (stall,) = _events("step_stall")
    assert stall["step"] == 4 and stall["phase"] == "engine.loss_readback"
    assert stall["median_s"] > 0
    assert stall["open_s"] == pytest.approx(2 * stall["median_s"], abs=2e-3)
    train = stall["frames"]["train"]
    assert any(f.endswith(":held_readback") for f in train)
    assert any(f.endswith(":_train_step") for f in train)
    assert any(f.startswith("execution/engine.py:") for f in train)
    assert THREAD in " ".join(stall["frames"])      # every thread is listed
    assert stall["last_sample"]["step"] == 3
    (end,) = _events("step_stall_end")
    assert end["step"] == 4
    assert set(end["phases"]) == set(telemetry.PHASES)
    assert _stalls("engine.loss_readback") - before == 1
    # One watchdog for the call, gone with it; and with no trace directory
    # it started no profiler session.
    assert len(run.watchdogs) == 1
    assert not _watchdog_threads() and engine._watchdog is None
    assert sessions == []


def test_no_thread_is_left_when_train_raises(engine, monkeypatch):
    seen = []

    def failing(value):
        seen.append(_watchdog_threads())
        raise RuntimeError("readback failed")

    monkeypatch.setattr(engine_mod, "_host_sync", failing)
    engine.args.job.steps = engine.step + 2
    with pytest.raises(RuntimeError, match="readback failed"):
        engine.train()
    assert len(seen[0]) == 1                # it was there during the step
    assert not _watchdog_threads() and engine._watchdog is None
    assert spans._tls.step is None
