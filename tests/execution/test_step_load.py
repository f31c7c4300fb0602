"""A training step says where it routed: the routed layers' loads
(`ops/moe.load_of`: each held expert's rows, the row tiles in use) ride out
of the pipeline's backward programs beside the loss, are read where the loss
is, and land in the telemetry ring beside the samples and under five
registry names.

One routed engine a module (`lfm2-moe-tiny` in float32, one stage on one
device, two microbatches of two short sequences) and the programs it
compiled (`PROGRAMS`): the cases that need a step share both. The engine's
layer profile is not measured (constants stand in for the rows: one host
of one chip has one plan), so nothing compiles but what a step runs.
"""

import logging

import jax
import numpy as np
import pytest

from oobleck_tpu.config import (
    DistributedArguments,
    ExecutionArguments,
    JobArguments,
    ModelArguments,
    OobleckArguments,
)
from oobleck_tpu.execution import engine as engine_mod
from oobleck_tpu.execution.pipeline import PROGRAMS, PipelineInstance
from oobleck_tpu.models import build_model
from oobleck_tpu.models.routed import routing_probe
from oobleck_tpu.obs import telemetry
from oobleck_tpu.planning import profiler
from oobleck_tpu.utils import metrics
from tests.execution.test_pipeline_mpmd import make_template

NAME = "lfm2-moe-tiny"
# Experts 2..5 of 8 held, as one chip of an expert-parallel pair holds them.
# One dense block and two routed ones between the embedding and the head.
SHARE = {"num_experts_held": 4, "expert_offset": 2, "vocab_rows_held": 128,
         "num_layers": 3}
MB, SEQ, NUM_MB = 2, 32, 2
HELD = SHARE["num_experts_held"]
# float32: a top-k flips on a near-tie of two scores, and two programs of
# the same arithmetic (the probe's one forward, a stage's backward) round
# bfloat16 hidden states apart often enough to move a row in a few hundred.
F32 = dict(precision="float32")


@pytest.fixture(scope="module")
def ring():
    """The process's ring, on and empty, whatever an earlier module of
    this worker left; the next module gets a fresh one too."""
    yield telemetry.reset()
    telemetry.reset()


@pytest.fixture
def ring_off(monkeypatch):
    """A ring built with the switch off in the process's place, for one
    test; the module's ring, with what it holds, comes back after."""
    monkeypatch.setenv(telemetry.ENV_TELEMETRY, "0")
    off = telemetry.TelemetryRing()
    assert not off.enabled
    monkeypatch.setattr(telemetry, "_instance", off)
    return off


@pytest.fixture(scope="module")
def engine(ring, tmp_path_factory):
    """The engine after `train()` over its two steps (losses read every
    step), and what that cost at the funnel."""
    with pytest.MonkeyPatch.context() as mp:
        mp.setenv("OOBLECK_TPU_CACHE",
                  str(tmp_path_factory.mktemp("untimed_profiles")))
        mp.setattr(profiler, "profile_execution_layers", _unmeasured)
        eng = _engine()
    syncs = engine_mod.host_sync_counter.count
    eng.train()
    eng.trained_syncs = engine_mod.host_sync_counter.count - syncs
    return eng


def _unmeasured(model, microbatch_size, seq_len=None) -> list[dict]:
    return [{"forward": 1.0, "backward": 2.0, "mem_required": [1 << 20, 1 << 16]}
            for _ in range(model.num_pipeline_layers)]


def _engine():
    args = OobleckArguments(
        dist=DistributedArguments(node_ips=["10.0.0.0"]),
        job=JobArguments(microbatch_size=MB, global_microbatch_size=MB * NUM_MB,
                         steps=2, learning_rate=1e-3, warmup_steps=1,
                         seq_len=SEQ),
        model=ModelArguments(model_name=NAME, dataset_path="synthetic",
                             model_args=dict(SHARE)),
        execution=ExecutionArguments(**F32),
    )
    eng = engine_mod.OobleckEngine(args, devices=jax.devices()[:1])
    eng.initialize_distributed()
    eng.instantiate_pipelines(args.job.global_num_microbatch)
    return eng


def _model(name=NAME, remat=True, **model_args):
    return build_model(name, model_args,
                       execution=ExecutionArguments(remat=remat, **F32))


def _pipeline(model, *, splits=None, devices=None):
    splits = splits or [(0, model.num_pipeline_layers)]
    return PipelineInstance(
        pipeline_id=0, template=make_template(splits, [1] * len(splits)),
        ranks=list(range(len(splits))), model=model,
        devices=devices or jax.devices()[:len(splits)],
        num_microbatches=NUM_MB, total_num_microbatches=NUM_MB,
        microbatch_size=MB, seq_len=SEQ)


def _batch(model, seed=0):
    return np.random.default_rng(seed).integers(
        0, model.config.data_vocab_size, size=(NUM_MB, MB, SEQ),
        dtype=np.int32)


def _probed(model, params, batch) -> dict:
    """{layer: rows of each held expert}: `routing_probe`'s choices for the
    step's sequences, counted on the host."""
    c = model.config
    chosen = routing_probe(model, jax.device_get(params),
                           batch.reshape(-1, SEQ))
    out = {}
    for block, picks in zip(model.routed_blocks, chosen):
        local = picks - c.expert_offset
        out[str(block)] = tuple(np.bincount(
            local[(local >= 0) & (local < HELD)], minlength=HELD).tolist())
    return out


def _bwd_avals(pipe):
    """(stage 0's one chunk's `bwd`, its four operands as avals): the
    parent's operands, `(params, acc, x, tokens)`."""
    st = pipe.stages[0]
    params = tuple(
        jax.tree.map(lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype),
                     pipe.params[li]) for li in st.chunks[0])
    tokens = {"input_ids": jax.ShapeDtypeStruct((MB, SEQ), np.int32)}
    return st.bwd[0], (params, params, None, tokens)


# --------------------------------------------------------------------- #
# (a) the step's load is the probes' sum, exactly


STAGES = {"one_stage_remat": (True, [(0, 5)]),
          "two_stages_no_remat": (False, [(0, 3), (3, 5)])}


@pytest.mark.parametrize("case", sorted(STAGES))
def test_a_step_s_load_is_the_sum_of_the_probe_s_counts(engine, devices8,
                                                        case):
    """Over M microbatches, integers, exactly. With the layers' checkpoint
    on (the engine's own programs, from the table): the load is the primal
    call's, and the recompute adds nothing to it. And off, on two stages
    with routed layers on both: each chunk's `bwd` hands its own layers'
    loads out, no `fwd` does, so a microbatch is counted once."""
    remat, splits = STAGES[case]
    model = _model(remat=remat, **SHARE)
    pipe = _pipeline(model, splits=splits, devices=devices8[:len(splits)])
    if remat:
        assert model.config == engine.model.config
        assert pipe.stages[0].bwd[0] is engine.pipelines[0].stages[0].bwd[0]
        assert [st.load_layers for st in pipe.stages] == [[(2, 3)]]
    else:
        assert [st.load_layers for st in pipe.stages] == [[(2,)], [(3,)]]
    batch = _batch(model, seed=3)
    assert np.isfinite(float(pipe.train_step(batch)))
    assert sorted(layers for layers, _ in pipe.load) == sorted(
        st.load_layers[0] for st in pipe.stages)
    got = engine_mod.StepLoad([pipe]).read()
    want = _probed(model, [pipe.params[li] for li in range(5)], batch)
    assert {k: v[:HELD] for k, v in got.items()} == want
    assert sum(map(sum, want.values())) > 0
    tile = pipe.load_info[2][1]
    for *held, tiles, tile_rows in got.values():
        assert tile_rows == tile
        # Every held expert has one tile at least, a microbatch.
        assert tiles >= max(sum(-(-n // tile) for n in held), NUM_MB * HELD)
    if not remat:
        # The first stage's forward program: the carry and nothing else.
        st = pipe.stages[0]
        out = jax.eval_shape(
            st.fwd[0], tuple(pipe.params[li] for li in st.chunks[0]), None,
            {"input_ids": batch[0]})
        assert isinstance(out, jax.ShapeDtypeStruct)


# --------------------------------------------------------------------- #
# (b), (c) the switch


def test_with_the_ring_off_bwd_has_the_parent_s_outputs(engine, request):
    live = engine.pipelines[0]
    on_key = live.stage_program_key(live.stages[0], 0)
    on_bwd, avals = _bwd_avals(live)
    on = jax.eval_shape(on_bwd, *avals)
    assert len(on) == 4 and on[3].shape == (2, HELD + 1)
    assert on[3].dtype == np.int32

    off_ring = request.getfixturevalue("ring_off")
    model = _model(**SHARE)
    pipe = _pipeline(model)
    assert pipe.stages[0].load_layers == [()]
    assert pipe.stage_program_key(pipe.stages[0], 0) != on_key
    off_bwd, avals = _bwd_avals(pipe)
    assert off_bwd is not on_bwd
    off = jax.eval_shape(off_bwd, *avals)
    # (loss, new sum, dx): what the parent's returns, and of the same
    # trees as the first three with the ring on.
    assert len(off) == 3
    assert jax.tree.structure(off) == jax.tree.structure(on[:3])
    assert pipe.load is None
    step_load = engine_mod.StepLoad([pipe])
    assert not step_load
    syncs = engine_mod.host_sync_counter.count
    engine._record_load(7, step_load)
    off_ring.record_load(7, {"1": (1, 2, 3, 4, 4, 32)})
    assert engine_mod.host_sync_counter.count == syncs
    assert off_ring.loads() == [] and off_ring.last_load() is None


def test_a_dense_model_s_bwd_text_ignores_the_switch(ring, request):
    """`gpt3-2.7b`'s case at gpt2-tiny's size: no routed layer, so the
    lowered `jit_bwd` is one text whatever the ring says, three outputs."""
    def lowered():
        pipe = _pipeline(_model("gpt2-tiny", num_layers=1))
        assert pipe.load_info == {} and pipe.stages[0].load_layers == [()]
        bwd, avals = _bwd_avals(pipe)
        assert len(jax.eval_shape(bwd, *avals)) == 3
        return pipe.stage_program_key(pipe.stages[0], 0), bwd.lower(
            *avals).as_text()

    on_key, on = lowered()
    PROGRAMS.pop(on_key)
    request.getfixturevalue("ring_off")
    off_key, off = lowered()
    assert off_key == on_key and off == on
    assert "jit_bwd" in on


# --------------------------------------------------------------------- #
# (d), (e) the engine: where the load is read


def test_a_step_counts_one_read_more_and_a_sample_keeps_its_length(engine):
    """Two steps of `train()`: a loss and a load a step through the funnel
    (all chunks' loads in ONE transfer), a load beside each sample, and the
    sample what it was: 12 positions."""
    ring = telemetry.telemetry()
    assert engine.trained_syncs == 2 * 2
    samples = [s for s in ring.samples() if s[0] in (1, 2)]
    assert len(samples) == 2
    assert telemetry.SAMPLE_LEN == 12
    assert all(len(s) == telemetry.SAMPLE_LEN for s in samples)
    loads = [entry for entry in ring.loads() if entry[0] in (1, 2)]
    assert [step for step, _ in loads] == [1, 2]
    for _, load in loads:
        assert sorted(load) == ["1", "2"]
        assert all(len(v) == HELD + 2 for v in load.values())
    rows = metrics.registry().counter("oobleck_moe_step_rows_total")
    assert rows.value(layer="1") >= sum(
        sum(load["1"][:HELD]) for _, load in loads)


def test_a_deferring_step_reads_nothing_and_the_loads_come_with_the_losses(
        engine, monkeypatch):
    ring = telemetry.telemetry()
    monkeypatch.setattr(engine.args.execution, "loss_readback_every", 4)
    seen = len(ring.loads())
    syncs = engine_mod.host_sync_counter.count
    pending = [(engine.step + 1 + i, engine._train_step()) for i in range(2)]
    assert all(isinstance(p, engine_mod.DeferredLoss) for _, p in pending)
    assert engine_mod.host_sync_counter.count == syncs
    assert len(ring.loads()) == seen
    engine._pending_losses.extend(pending)
    engine._drain_pending_losses()
    # One loss and one load a step, when the losses are read.
    assert engine_mod.host_sync_counter.count == syncs + 2 * 2
    assert [step for step, _ in ring.loads()[seen:]] == [
        step for step, _ in pending]
    assert [step for step, _ in engine.loss_history[-2:]] == [
        step for step, _ in pending]


# --------------------------------------------------------------------- #
# (f) the five names, by hand

# Two steps of two layers of two held experts, tiles of 8 rows.
#   step 1: layer "a" rows (6, 2) in 2 tiles, layer "b" rows (4, 4) in 2
#   step 2: layer "a" rows (9, 3) in 3 tiles, layer "b" rows (8, 0) in 2
HAND = [(1, {"a": (6, 2, 2, 8), "b": (4, 4, 2, 8)}),
        (2, {"a": (9, 3, 3, 8), "b": (8, 0, 2, 8)})]
BY_HAND = {
    # rows 8 + 8 + 12 + 8 = 36 over (2 + 2 + 3 + 2) * 8 = 72 rows walked
    "oobleck_moe_tile_fill_pct": 50.0,
    # "a": (15, 5): 15 / 10 = 1.5; "b": (12, 4): 12 / 8 = 1.5 -> 1.5
    "oobleck_moe_load_skew": 1.5,
    # a step's rows: 16, 20: (20 - 16) / 18
    "oobleck_moe_step_rows_spread_pct": 100.0 * 4 / 18,
    "oobleck_moe_step_rows_total": {"a": 20, "b": 16},
    "oobleck_moe_step_tile_rows_total": {"a": 40, "b": 32},
}


@pytest.fixture(scope="module")
def by_hand():
    """A ring of its own fed HAND; (what the counters read before, the
    ring)."""
    reg = metrics.registry()
    before = {
        name: {layer: reg.counter(name).value(layer=layer) for layer in want}
        for name, want in BY_HAND.items() if isinstance(want, dict)}
    own = telemetry.TelemetryRing(capacity=4, window=2)
    own.enabled = True
    for step, load in HAND:
        own.record_load(step, load)
    return before, own


@pytest.mark.parametrize("name", sorted(BY_HAND))
def test_the_five_names_read_what_a_hand_made_load_says(by_hand, name):
    before, _ = by_hand
    reg, want = metrics.registry(), BY_HAND[name]
    if isinstance(want, dict):
        counter = reg.counter(name)
        assert {layer: counter.value(layer=layer) - before[name][layer]
                for layer in want} == want
    else:
        assert reg.gauge(name).value() == pytest.approx(want, rel=1e-12)


def test_the_window_is_the_ring_s_and_an_older_step_leaves_it(by_hand):
    _, own = by_hand
    assert own.loads() == HAND and own.last_load() == HAND[-1]
    assert own.load_window() == HAND
    assert telemetry.load_stats(HAND[1:]) == {
        "fill_pct": 100.0 * 20 / 40, "skew": 2.0, "rows_spread_pct": 0.0}
    assert telemetry.load_stats([]) == {
        "fill_pct": 0.0, "skew": 0.0, "rows_spread_pct": 0.0}
    narrow = telemetry.TelemetryRing(capacity=4, window=1)
    narrow.enabled = True
    for step, load in HAND:
        narrow.record_load(step, load)
    assert narrow.load_window() == HAND[1:]
    assert metrics.registry().gauge(
        "oobleck_moe_load_skew").value() == pytest.approx(2.0)


def test_the_log_line_and_the_stall_event_carry_the_load(by_hand, caplog):
    _, own = by_hand
    assert engine_mod._load_summary(own) == " | moe fill 50.0% skew 1.50"
    assert engine_mod._load_summary(
        telemetry.TelemetryRing(capacity=2, window=2)) == ""
    assert telemetry.load_totals(None) is None
    totals = {"step": 2, "rows": {"a": 12, "b": 8},
              "tile_rows": {"a": 24, "b": 16}}
    assert telemetry.load_totals(own.last_load()) == totals

    class Accumulator:
        owner = None

        def innermost(self):
            return "pipeline.dispatch"

    n0 = len(metrics.flight_recorder().events())
    dog = telemetry.StepWatchdog(own, Accumulator())
    with caplog.at_level(logging.WARNING, logger="oobleck.telemetry"):
        dog._fire(3, 2.0, 0.5)
    (event,) = [e for e in metrics.flight_recorder().events()[n0:]
                if e["event"] == "step_stall"]
    assert event["last_load"] == totals
    assert event["last_sample"] is None


def test_a_window_that_spans_another_share_of_the_experts_still_adds_up():
    """A pipeline built anew over another share holds another number of
    experts under the same layer's label, and the ring's window can hold
    both (so can one process of the tests, model after model: a whole run
    of PR 56 failed there): each share's experts are summed among their
    own."""
    two = (7, {"1": (3, 1, 2, 8)})              # 2 held: 3 + 1 of 2 x 8
    four = (8, {"1": (1, 1, 1, 5, 4, 8)})       # 4 held: 8 of 4 x 8
    assert telemetry.load_stats([two, four]) == {
        "fill_pct": 100.0 * 12 / 48, "skew": 5 * 4 / 8,
        "rows_spread_pct": 100.0 * 4 / 6}
