"""The process's ONE table of jitted programs (`execution/pipeline.PROGRAMS`).

What it is for: an engine, a reconfiguration or a precompiler that builds
what the process built before finds the programs there and compiles
nothing. What could go wrong with it: a key that lacks something a
program's trace reads hands out a WRONG program silently. So:

  (a) a second engine of equal arguments adds no key and compiles no stage,
      update or sync program, and trains to the first one's losses;
  (b) pipelines that differ in ONE input of a stage program's trace, a case
      for each kind `PipelineInstance.stage_program_key` lists, share no
      program, and each gives the loss and the gradients it gives alone;
  (c) the data-parallel engine a reconfiguration builds anew compiles no
      program of a signature the process holds;
  (d) two optimizers share an update exactly when they were built from equal
      arguments.
"""

import jax
import numpy as np
import pytest

from oobleck_tpu.config import ExecutionArguments
from oobleck_tpu.execution.pipeline import (
    PROGRAMS,
    PipelineInstance,
    optimizer_update_program,
)
from oobleck_tpu.models import build_model
from oobleck_tpu.parallel.train import make_optimizer
from tests.execution.test_engine import cache_env, make_engine  # noqa: F401
from tests.execution.test_pipeline_mpmd import make_template
from tests.execution.test_precompile import (
    STAGE_PROGRAMS,
    _CompileCounter,
    _stage_keys,
)

UPDATE_AND_SYNC = ("jit(optimizer_update)", "jit(pack_flat)",
                   "jit(unpack_add)", "jit(unpack)")


# --------------------------------------------------------------------- #
# (a), (c): two engines of equal arguments, each through a host's loss


def _life(engine, counter):
    """Two steps, a host lost, two steps. What each half compiled (backend
    compiles by program name) and added to the table, and its losses."""
    halves = []
    for lose in (None, "10.0.0.2"):
        keys, _ = set(PROGRAMS), counter.take()
        if lose is None:
            engine.initialize_distributed()
            engine.instantiate_pipelines(
                engine.args.job.global_num_microbatch)
        else:
            engine.reconfigure(lose)
        losses = [engine._train_step() for _ in range(2)]
        halves.append({
            "losses": losses,
            "new_keys": set(PROGRAMS) - keys,
            "compiled": counter.take(*STAGE_PROGRAMS, *UPDATE_AND_SYNC),
            "transfers": engine.dp_engine.last_transfer_count,
        })
    return halves


@pytest.fixture(scope="module")
def twins(cache_env, devices8):  # noqa: F811
    """(first engine's halves, second engine's halves): equal arguments,
    one process, the table empty before the first."""
    PROGRAMS.clear()
    counter = _CompileCounter()
    jax.monitoring.register_event_duration_secs_listener(counter)
    try:
        return [_life(make_engine(num_hosts=4, steps=8, devices=devices8),
                      counter) for _ in range(2)]
    finally:
        counter.on = False


def test_a_second_engine_compiles_no_program_the_process_holds(twins):
    (first, _), (second, _) = twins
    assert first["new_keys"] and _stage_keys() >= {
        k for k in first["new_keys"] if isinstance(k[0], type)}
    assert {"jit(bwd)", "jit(optimizer_update)"} <= set(first["compiled"])
    assert second["new_keys"] == set()
    assert second["compiled"] == []
    assert second["losses"] == first["losses"]


def test_a_reconfiguration_s_data_parallel_engine_compiles_nothing_held(twins):
    (_, first), (_, second) = twins
    # Each recovery built a data-parallel engine anew, and its sync programs
    # ran: the second's is another new object over equal pipelines.
    assert first["transfers"] > 0 and second["transfers"] > 0
    assert second["new_keys"] == set()
    assert second["compiled"] == []
    # A recovery's losses take one of two values a rounding apart, engine by
    # engine, and did before the table (the parent's twins do the same).
    assert second["losses"] == pytest.approx(first["losses"], rel=1e-6)


# --------------------------------------------------------------------- #
# (b): one input of the trace apart

MB, SEQ, NUM_MB = 2, 32, 2
# One block between the embedding and the head, on one stage of one device:
# what is compared is the table, and a small program says as much of it.
BASE = dict(name="gpt2-tiny", args={"num_layers": 1}, execution={},
            splits=[(0, 3)], chips=[1], first_device=0, mb=MB, seq=SEQ,
            total=NUM_MB, tp=1, sp=1, fsdp=-1)
# Each case is BASE with one thing changed, named after the entry of
# `stage_program_key`'s list it changes.
ONE_APART = {
    "the model's class": dict(name="llama-tiny"),
    "the model's config, a width": dict(
        args={"num_layers": 1, "num_heads": 2}),
    "the model's config, remat": dict(execution={"remat": False}),
    "the model's config, the precision": dict(
        execution={"precision": "float32"}),
    "the chunk's layers": dict(splits=[(0, 1), (1, 3)], chips=[1, 1]),
    "tp": dict(chips=[2], tp=2),
    "sp": dict(chips=[2], sp=2),
    "use_fsdp": dict(chips=[2]),
    "the mesh, by its devices": dict(first_device=2),
    "the mesh, by its shape": dict(chips=[2], fsdp=1),
    "microbatch_size": dict(mb=4),
    "seq_len": dict(seq=16),
    "total_num_microbatches": dict(total=4),
}


def _pipeline(devices, *, name, args, execution, splits, chips, first_device,
              mb, seq, total, tp, sp, fsdp):
    model = build_model(name, dict(args),
                        execution=ExecutionArguments(**execution))
    n = sum(chips)
    return PipelineInstance(
        pipeline_id=0, template=make_template(splits, chips),
        ranks=list(range(n)), model=model,
        devices=devices[first_device:first_device + n],
        num_microbatches=NUM_MB, total_num_microbatches=total,
        microbatch_size=mb, seq_len=seq, tensor_parallel=tp,
        sequence_parallel=sp, fsdp=fsdp)


def _train(pipe):
    """A step's loss and gradients (summed, a number a leaf), and the
    programs that gave them."""
    batch = np.random.default_rng(0).integers(
        0, pipe.model.config.vocab_size,
        size=(NUM_MB, pipe.microbatch_size, pipe.seq_len), dtype=np.int32)
    loss = float(pipe.train_step(batch))
    grads = [float(g.astype(np.float32).sum())
             for g in jax.tree.leaves(jax.device_get(pipe.grads))]
    return [loss, *grads], [f for st in pipe.stages for f in st.fwd + st.bwd]


@pytest.fixture(scope="module")
def base(devices8):
    PROGRAMS.clear()
    losses, programs = _train(_pipeline(devices8, **BASE))
    return losses, programs, dict(PROGRAMS)


@pytest.mark.parametrize("what", sorted(ONE_APART))
def test_one_input_of_the_trace_apart_shares_no_stage_program(
        base, devices8, what):
    base_losses, base_programs, base_table = base
    case = {**BASE, **ONE_APART[what]}
    try:
        # Alone: the table holds nothing when the pipeline is built.
        PROGRAMS.clear()
        alone, _ = _train(_pipeline(devices8, **case))
        alone_keys = _stage_keys()
        # Beside the base's programs.
        PROGRAMS.clear()
        PROGRAMS.update(base_table)
        beside, programs = _train(_pipeline(devices8, **case))
        assert _stage_keys() - set(base_table) == alone_keys
        assert len(alone_keys) == len(case["splits"])
        assert not {id(f) for f in programs} & {id(f) for f in base_programs}
        assert beside == alone
        # And the base still finds its own, and they still are its own.
        again, same = _train(_pipeline(devices8, **BASE))
        assert [id(f) for f in same] == [id(f) for f in base_programs]
        assert again == base_losses
    finally:
        PROGRAMS.clear()
        PROGRAMS.update(base_table)


def test_every_kind_of_input_the_key_lists_has_a_case(devices8):
    """The fields of a stage program's key, against ONE_APART: a field added
    to the key comes with a case here. The marks of the sums a kernel takes
    (the key's last two fields) are `test_kernel_grad_sums.py`'s: a model
    that marks nothing, the same kernels, another program. The layers whose
    load `bwd` hands out (the field before them) are `test_step_load.py`'s:
    the telemetry ring off, the same model, another program. The chunk's
    walk (the field after its layers) is `test_looped_pipeline.py`'s: the
    same layers of the same looped model, folded or visited, another
    program."""
    pipe = _pipeline(devices8, **{**BASE, **ONE_APART["the chunk's layers"]})
    key = pipe.stage_program_key(pipe.stages[0], 0)
    model = pipe.model
    assert key[:-3] == (
        type(model), model.config, (0,), (0,), pipe.stages[0].mesh, 1, 1,
        False, MB, SEQ, NUM_MB)
    assert key[-3:] == ((), (), jax.tree.structure((None,)))
    # config x3, mesh x2; the walk's case is test_looped_pipeline.py's.
    assert len(ONE_APART) >= len(key[:-3]) + 3 - 1


# --------------------------------------------------------------------- #
# (d): the optimizer's update, by what the optimizer was built from

DEFAULTS = dict(learning_rate=1e-3, warmup_steps=2, weight_decay=0.01,
                max_grad_norm=1.0, frozen=())


@pytest.mark.parametrize("other", [
    dict(learning_rate=1e-2), dict(warmup_steps=5), dict(weight_decay=0.0),
    dict(max_grad_norm=0.5), dict(frozen=("w",)),
], ids=lambda d: next(iter(d)))
def test_optimizers_share_an_update_only_when_built_from_equal_arguments(
        other):
    one, twin = make_optimizer(**DEFAULTS), make_optimizer(**DEFAULTS)
    assert one is not twin
    update = optimizer_update_program(one)
    assert optimizer_update_program(twin) is update
    differs = make_optimizer(**{**DEFAULTS, **other})
    other_update = optimizer_update_program(differs)
    assert other_update is not update
    assert not any(k[0] == "optimizer_update" and not isinstance(k[1], tuple)
                   for k in PROGRAMS)  # by value: no object, no id

    p = {"w": jax.numpy.full((4,), 2.0), "b": jax.numpy.ones((4,))}
    g = {"w": jax.numpy.full((4,), 3.0), "b": jax.numpy.full((4,), -1.0)}
    got, _ = update(g, one.init(p), p)
    twin_got, _ = optimizer_update_program(twin)(g, twin.init(p), p)
    other_got, _ = other_update(g, differs.init(p), p)
    for name in p:
        np.testing.assert_array_equal(got[name], twin_got[name])
    # (Adam's first step does not see a gradient's scale, so not its clip.)
    assert "max_grad_norm" in other or any(
        not np.array_equal(got[name], other_got[name]) for name in p)


# --------------------------------------------------------------------- #
# (e): the gradient sum's collective after a recovery, warmed by the walk


@pytest.mark.parametrize("hosts,pipelines_after", [
    (3, 2),     # three one-host pipelines, one lost: the two that are left
    (4, 3),     # four, one lost: re-instantiated as three
])
def test_a_re_plan_s_first_gradient_sum_compiles_nothing(
        cache_env, devices8, hosts, pipelines_after):  # noqa: F811
    """After `start_recovery_precompile(wait=True)` a recovery onto
    congruent pipelines finds its collective's EXECUTABLE in the table,
    under a key the first layout's did not have (other owners, another
    mesh): the recovery and the first `do_allreduce` after it add no such
    key and compile no `dp_sum`. (The table, not the persistent cache, is
    what carries it: `engine.dp_sum_program`.)"""
    PROGRAMS.clear()
    sums = lambda: {k for k in PROGRAMS if k[0] == "dp_sum"}
    meshes = lambda keys: {k[1][0].mesh for k in keys}
    counter = _CompileCounter()
    jax.monitoring.register_event_duration_secs_listener(counter)
    try:
        engine = make_engine(num_hosts=hosts, steps=8,
                             devices=devices8[:hosts])
        engine.initialize_distributed()
        engine.instantiate_pipelines(engine.args.job.global_num_microbatch)
        engine._train_step()
        first_layout = sums()
        assert len(first_layout) == len(
            engine.dp_engine.collective_groups) > 0
        assert len(counter.take("jit(dp_sum)")) == len(first_layout)

        pc = engine.start_recovery_precompile(wait=True)
        assert pc.stats["errors"] == 0, pc.stats
        warmed = sums()
        assert warmed > first_layout
        # The walk compiled the other layouts' sums, and not the live one's.
        assert len(counter.take("jit(dp_sum)")) == len(warmed - first_layout)

        engine.reconfigure("10.0.0.1")
        engine._precompiler.wait()  # re-armed, for the NEXT loss's layouts
        counter.take()
        groups = engine.dp_engine.collective_groups
        assert len(engine.pipelines) == pipelines_after
        assert groups and not engine.dp_engine.anchor_layers
        assert all(len(g.owners) == pipelines_after for g in groups)
        after_recovery = sums()
        loss = engine._train_step()
        assert np.isfinite(loss)
        assert engine.dp_engine.last_transfer_count == len(groups)
        assert counter.take("jit(dp_sum)") == []
        assert sums() == after_recovery
        mine = {g.mesh for g in groups}
        assert mine <= meshes(warmed) and not mine & meshes(first_layout)
    finally:
        counter.on = False
