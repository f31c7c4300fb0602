"""What the host does in a training step has a name, once: every span of
the step path (`oobleck_tpu.obs.spans.region`) opens in the order the work
happens, the readback lies inside the step, bookkeeping lies between
steps, and set-up leaves its spans in the program's ring."""

import jax

from oobleck_tpu.execution import engine as engine_mod
from oobleck_tpu.obs import spans, telemetry
from tests.execution.test_engine import cache_env, make_engine  # noqa: F401

STEP = ["engine.step", "engine.staging", "pipeline.dispatch", "dp.allreduce",
        "engine.optimizer", "engine.loss_readback"]


class _Recording:
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


def test_two_steps_record_every_span_in_order(cache_env, monkeypatch):  # noqa: F811
    ring0 = len(spans.span_recorder().spans())
    eng = make_engine(num_hosts=1, steps=2, devices=jax.devices()[:1],
                      microbatch=1, global_mb=2)
    eng.initialize_distributed()
    eng.instantiate_pipelines(eng.args.job.global_num_microbatch)
    setup = spans.span_recorder().spans()[ring0:]
    by_name = {s["name"]: s for s in setup}
    assert [s["name"] for s in setup] == [
        "engine.profile", "engine.build", "engine.plan", "engine.instantiate"]
    assert by_name["engine.profile"]["parent_id"] == \
        by_name["engine.build"]["span_id"]

    fake = _Recording()
    monkeypatch.setattr(spans.jax.profiler, "TraceAnnotation", fake)
    syncs0 = engine_mod.host_sync_counter.count
    ring = telemetry.reset()
    eng.train()
    assert eng.step == 2
    # Each step left one sample, and in it the host seconds of the step's
    # own regions, in the module's order: the regions are disjoint and lie
    # inside the step, so they sum to no more than it.
    assert telemetry.PHASES == tuple(STEP[1:])
    first, second = ring.samples()
    assert [first[0], second[0]] == [1, 2]
    for sample in (first, second):
        assert len(sample) == telemetry.SAMPLE_LEN
        phases = sample[telemetry.PHASES_AT]
        assert len(phases) == len(telemetry.PHASES)
        assert all(p >= 0.0 for p in phases)
        by_name = dict(zip(telemetry.PHASES, phases))
        assert by_name["pipeline.dispatch"] > 0.0
        assert by_name["engine.loss_readback"] > 0.0
        assert sum(phases) <= sample[1]
        assert sample[telemetry.BETWEEN_S] > 0.0
        # The CPU reports no memory statistics.
        assert sample[telemetry.HBM_IN_USE:] == (None, None, None)
    # The train thread's accumulator went with train(), and its watchdog.
    assert spans._tls.step is None and eng._watchdog is None
    opens = [name for what, name in fake.log if what == "open"]
    assert opens == STEP + ["engine.bookkeeping"] + STEP + [
        "engine.bookkeeping"]
    # Properly nested on the one training thread, so the profiler's host
    # line shows them as a tree.
    stack = []
    inside_step = set()
    for what, name in fake.log:
        if what == "open":
            if stack and stack[0] == "engine.step":
                inside_step.add(name)
            stack.append(name)
        else:
            assert stack.pop() == name
    assert not stack
    assert inside_step == set(STEP[1:])          # bookkeeping lies outside
    assert engine_mod.host_sync_counter.count - syncs0 == 2
    # ... and each region's host seconds are in the one histogram.
    from oobleck_tpu.utils import metrics

    series = {s["labels"]["span"]: s["count"] for s in
              metrics.registry().histogram(spans.SPAN_SECONDS).series()}
    for name in STEP + ["engine.bookkeeping"]:
        assert series[name] >= 2, name
