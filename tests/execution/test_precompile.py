"""Bounded-time recovery: the RecoveryPrecompiler must make reconfigure()
planning-free AND compile-free. The predicted-plan walk puts its jitted
stage programs into the process's one table of programs
(`execution/pipeline.PROGRAMS`) under the keys `stage_program_key` gives, so
the post-failure instantiation finds every stage there instead of
cold-compiling it (the 480 s MoE recovery hang this PR retires). The table
is the process's, not an engine's: the second half of this module holds it
to that."""

import numpy as np
import pytest

from oobleck_tpu.execution.pipeline import PROGRAMS
from tests.execution.test_engine import cache_env, make_engine  # noqa: F401


def _stage_keys():
    # A stage program's key starts with the model's class; every other
    # program's with its kind, a string.
    return {k for k in PROGRAMS if isinstance(k[0], type)}


def test_precompile_makes_reconfigure_compile_free(cache_env, devices8):
    """Start the precompiler, let it finish, kill a host: reconfigure must
    add ZERO new stage keys to the table of programs — every stage
    program of the recovery plan was already built — and training resumes
    finite. This is the tentpole acceptance gate in miniature."""
    engine = make_engine(num_hosts=4, steps=10, devices=devices8)
    engine.initialize_distributed()
    engine.instantiate_pipelines(engine.args.job.global_num_microbatch)
    loss_before = engine._train_step()

    pc = engine.start_recovery_precompile(wait=True)
    assert pc is not None and not pc.running
    assert pc.stats["plans"] >= 1          # live plan + n-1 (+ n-2) worlds
    assert pc.stats["stages_compiled"] > 0
    assert pc.stats["errors"] == 0, pc.stats
    keys_before = _stage_keys()
    assert keys_before

    engine.reconfigure("10.0.0.2")

    assert _stage_keys() == keys_before, (
        "reconfigure compiled stage programs the precompiler should have "
        "already built"
    )
    # the precompiler re-arms for the NEXT failure after each recovery
    assert engine._precompiler is not None and engine._precompiler is not pc
    engine._precompiler.wait()

    losses = [engine._train_step() for _ in range(3)]
    assert all(np.isfinite(l) for l in losses), losses
    assert losses[-1] < loss_before


def test_predict_replan_is_pure(cache_env, devices8):
    """predict_replan must not mutate the engine: same host algebra and
    template re-match reconfigure() runs, but read-only — the precompiler
    calls it from a background thread while training steps run."""
    engine = make_engine(num_hosts=4, steps=3, devices=devices8)
    engine.initialize_distributed()
    engine.instantiate_pipelines(engine.args.job.global_num_microbatch)
    hosts_before = list(engine.host_ips)
    ranks_before = [list(p.ranks) for p in engine.pipelines]

    plan, assignment, idle = engine.predict_replan({2})

    assert engine.host_ips == hosts_before
    assert [list(p.ranks) for p in engine.pipelines] == ranks_before
    used = sorted({h for g in assignment for h in g})
    assert 2 not in used
    assert set(used) <= {0, 1, 3}
    assert plan.total_num_microbatches == engine.plan.total_num_microbatches


def test_precompile_env_disable(cache_env, devices8, monkeypatch):
    """OOBLECK_PRECOMPILE=0 must turn the feature off without touching the
    config file (ops escape hatch)."""
    engine = make_engine(num_hosts=2, steps=3, devices=devices8[:4])
    engine.initialize_distributed()
    engine.instantiate_pipelines(engine.args.job.global_num_microbatch)
    monkeypatch.setenv("OOBLECK_PRECOMPILE", "0")
    assert engine.start_recovery_precompile() is None
    monkeypatch.setenv("OOBLECK_PRECOMPILE", "not-an-int")
    # malformed override: warn and fall back to the config value (2)
    pc = engine.start_recovery_precompile()
    assert pc is not None
    pc.wait()
    assert pc.stats["errors"] == 0, pc.stats


# `jit(optimizer_update)` is warmed best-effort, once per aval whatever the
# stage's devices, and is not held to this: the stage programs are, and with
# them each chunk's gradient-sum fill, which `bwd`'s donated operand needs
# before the step's first microbatch.
STAGE_PROGRAMS = ("jit(fwd)", "jit(bwd)", "jit(eval_fwd)", "jit(grad_zero)")


class _CompileCounter:
    """Backend compiles by jitted function name, from JAX's own duration
    event (the one utils/compile_cache.py feeds its seconds counter from)."""

    def __init__(self):
        self.names: list[str] = []
        self.on = True

    def __call__(self, event, _seconds, fun_name=None, **_):
        if self.on and event == "/jax/core/compile/backend_compile_duration":
            self.names.append(fun_name)

    def take(self, *wanted) -> list[str]:
        got = [n for n in self.names if n in wanted]
        self.names.clear()
        return got


@pytest.fixture
def compile_counter():
    import jax

    counter = _CompileCounter()
    jax.monitoring.register_event_duration_secs_listener(counter)
    yield counter
    counter.on = False  # jax keeps listeners for the process's lifetime


@pytest.mark.parametrize("model_name,has_eval_program", [
    ("gpt2-tiny", False),   # eval runs the last stage's forward-only program
    ("bert-tiny", True),    # eval runs the program that returns the metric
])
def test_precompiled_stage_programs_are_the_ones_the_step_runs(
        cache_env, devices8, compile_counter, model_name, has_eval_program):
    """The walk over the LIVE pipelines compiles every program a step runs,
    each chunk's `bwd` with the running gradient sum among its operands
    (the last stage's gives three outputs) and the fill that starts the
    sum: the step after it compiles no stage program. The last stage's
    forward-only program is compiled ahead only where eval_step runs it."""
    from oobleck_tpu.execution.precompile import RecoveryPrecompiler

    engine = make_engine(num_hosts=2, steps=3, devices=devices8[:4],
                         microbatch=2, global_mb=8, model_name=model_name)
    engine.initialize_distributed()
    engine.instantiate_pipelines(engine.args.job.global_num_microbatch)
    chunks, last_chunks = set(), set()
    for pipe in engine.pipelines:
        for st in pipe.stages:
            for c, layers in enumerate(st.chunks):
                key = (layers, tuple(st.ranks))
                chunks.add(key)
                if layers[-1] == engine.model.num_pipeline_layers - 1:
                    last_chunks.add(key)
                    assert (st.efwd[c] is not None) == has_eval_program

    pc = RecoveryPrecompiler(engine)
    compile_counter.take()
    for pipe in engine.pipelines:
        pc._aot_pipeline(pipe)
    assert pc.stats["errors"] == 0, pc.stats
    # Two programs a chunk: fwd + bwd, and on the last virtual stage bwd +
    # whichever forward eval_step runs (it was three with an eval program).
    assert pc.stats["stages_compiled"] == 2 * len(chunks)
    walked = compile_counter.take(*STAGE_PROGRAMS)
    assert walked.count("jit(bwd)") == len(chunks)
    # One fill a chunk is lowered and compiled too (an aux program, beside
    # the optimizer updates). `grad_zero` is one function for every chunk,
    # so JAX may answer a fill from an equal one an earlier engine of this
    # process compiled: the backend count is at most the chunks.
    assert pc.stats["aux_compiled"] >= len(chunks)
    assert walked.count("jit(grad_zero)") <= len(chunks)
    assert walked.count("jit(eval_fwd)") == (
        len(last_chunks) if has_eval_program else 0)
    assert walked.count("jit(fwd)") == len(chunks) - (
        len(last_chunks) if has_eval_program else 0)

    loss = engine._train_step()
    assert np.isfinite(loss)
    assert compile_counter.take(*STAGE_PROGRAMS) == []
    assert np.isfinite(engine.evaluate(num_batches=1))
    assert compile_counter.take(*STAGE_PROGRAMS) == []
