"""Worker half of the straggler story on a live engine: a chaos
slow_host directive inflates the measured step wall-clock, the telemetry
ring samples every step, the published metrics snapshot carries the
heartbeat digest + goodput ledger the agent relays upward, and a
committed incident's goodput_cost is exactly the ledger's attribution
for its trace. Small engine (1 host, 2 devices) — the control-plane half
lives in tests/elastic/test_fleet_wire.py."""

import os
import time

import pytest

from oobleck_tpu.execution import engine as engine_mod
from oobleck_tpu.obs import telemetry as telemetry_mod
from oobleck_tpu.obs.goodput import BUCKETS
from oobleck_tpu.obs.incident import IncidentBuilder
from oobleck_tpu.obs.telemetry import digest_ok
from oobleck_tpu.utils import chaos as chaos_mod
from oobleck_tpu.utils import metrics

from tests.execution.test_engine import cache_env, make_engine  # noqa: F401


FACTOR = 3.0


class _RecordingClock:
    """The engine module's `time`, with every sleep the engine asks for
    written down (and still slept): the gray-failure injection is the
    engine's only sleep, one per slowed step."""

    def __init__(self):
        self.slept = []

    def __getattr__(self, name):
        return getattr(time, name)

    def sleep(self, seconds):
        self.slept.append(seconds)
        time.sleep(seconds)


class _Pipe:
    """Stand-in agent pipe: captures what the worker would relay."""

    def __init__(self):
        self.sent = []

    def send(self, msg):
        self.sent.append(msg)


@pytest.fixture(scope="module")
def slow_engine(cache_env, devices8):  # noqa: F811
    """ONE engine through train() shared by the module (compiling an
    engine per test would blow the per-module budget): 10.0.0.0 goes 3x
    slow after step 0 (the @1 delay leaves step 0 as the in-run
    baseline). No metrics dir: nothing lands on disk."""
    old_dir = os.environ.pop(metrics.ENV_METRICS_DIR, None)
    clock = _RecordingClock()
    telemetry_mod.reset()
    eng = make_engine(num_hosts=1, steps=6, devices=devices8[:2],
                      microbatch=2, global_mb=4, agent_ip="10.0.0.0")
    eng.initialize_distributed()
    eng.instantiate_pipelines(eng.args.job.global_num_microbatch)
    # Pay the compile before anything is timed (each call advances
    # eng.step, so the loop below runs the remaining 4 steps: 3..6).
    for _ in range(2):
        eng._train_step()
    engine_mod.time = clock
    try:
        chaos_mod.reset(f"slow_host=10.0.0.0:{FACTOR:g}@1")
        eng.train()
    finally:
        chaos_mod.reset("")
        engine_mod.time = time
    eng.injected_sleeps = clock.slept
    yield eng
    if old_dir is not None:
        os.environ[metrics.ENV_METRICS_DIR] = old_dir


def test_gray_failure_is_visible_in_the_telemetry_ring(slow_engine):
    samples = telemetry_mod.telemetry().samples()
    assert [s[0] for s in samples] == [3, 4, 5, 6]  # one per step, in order
    assert samples[0][1] > 0
    # Step 0 of the run is the baseline and the next three ran under the
    # 3x gray failure: the engine slept (factor - 1) x each of those steps'
    # own measure and reports factor x it. So the sample is the recorded
    # sleep scaled by factor / (factor - 1), to the float, and at least
    # factor x the compute the pipelines timed inside that step. No two
    # wall-clock readings are compared.
    slept = slow_engine.injected_sleeps
    assert len(slept) == len(samples) - 1
    for (_, step_s, compute_s, *_), sleep_s in zip(samples[1:], slept):
        assert sleep_s > 0
        assert step_s == pytest.approx(sleep_s * FACTOR / (FACTOR - 1.0))
        assert step_s >= FACTOR * compute_s > 0
    # The injection itself was flight-recorded exactly once (activation
    # is one-shot even though the rule keeps matching).
    # (The recorder is process-wide: other modules' slow_host injections
    # against other hosts may sit in the same ring.)
    slow = [e for e in metrics.flight_recorder().events()
            if e["event"] == "chaos_injection"
            and e.get("action") == "slow_host" and e["ip"] == "10.0.0.0"]
    assert len(slow) == 1
    assert slow[0]["factor"] == pytest.approx(FACTOR)


def test_published_snapshot_carries_digest_and_ledger(slow_engine):
    pipe = _Pipe()
    slow_engine.agent_pipe = pipe
    slow_engine._publish_metrics()
    snap = pipe.sent[-1]["snapshot"]
    # The digest the agent piggybacks on its heartbeats: wire-valid, and
    # its windowed mean agrees with the raw samples it summarizes.
    d = snap["telemetry"]
    assert digest_ok(d)
    samples = telemetry_mod.telemetry().samples()
    assert d["n"] == len(samples) == 4
    assert d["step"] == 6
    assert d["step_s"] == pytest.approx(
        sum(s[1] for s in samples) / len(samples), rel=1e-3)
    assert d["step_max_s"] >= d["step_p50_s"]
    assert d["live_bytes"] > 0
    # The goodput ledger partitions the engine's whole wall-clock.
    g = snap["goodput"]
    assert set(g["buckets"]) == set(BUCKETS)
    assert g["steps"] == 4
    assert g["buckets"]["step"] > 0
    assert sum(g["buckets"].values()) == pytest.approx(g["wall_s"])
    assert 0 < g["goodput_fraction"] <= 1.0
    # ...and the same fraction is on the scrapeable gauge (stamped at the
    # last step, so marginally ahead of a snapshot whose wall kept
    # growing).
    gauge = metrics.registry().gauge("oobleck_goodput_fraction", "")
    assert gauge.value() >= g["goodput_fraction"]
    assert gauge.value() == pytest.approx(g["goodput_fraction"], rel=0.05)


def test_committed_incident_carries_ledger_attribution(slow_engine):
    eng = slow_engine
    inc = IncidentBuilder("10.0.0.0", cause="slowdown")
    inc.mark("detect", time.time() - 4.0)  # commit marks first_step = now
    eng._incident = inc
    recovery_before = eng._ledger.snapshot()["buckets"]["recovery"]

    eng._commit_incident()

    # The detect -> first_step window was charged to the incident's trace
    # in the ledger, and the incident record carries the same numbers.
    cost = eng._ledger.incident_cost(inc.trace_id)
    assert cost is not None
    assert cost["lost_s"] == pytest.approx(4.0, abs=0.5)
    assert cost["cause"] == "slowdown"
    assert inc.goodput_cost == cost
    assert inc.build()["goodput_cost"] == cost
    after = eng._ledger.snapshot()
    assert after["buckets"]["recovery"] == pytest.approx(
        recovery_before + cost["lost_s"])
    assert after["incidents"][inc.trace_id]["lost_s"] == cost["lost_s"]
    # The one-shot digest is staged and rides the next metrics push.
    pipe = _Pipe()
    eng.agent_pipe = pipe
    eng._publish_metrics()
    assert pipe.sent[-1]["snapshot"]["incident"]["trace_id"] == inc.trace_id
    assert eng._incident_record is None  # consumed by the relay
