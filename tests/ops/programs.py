"""What the tests of tests/ops share when they look INTO a program: the
Pallas calls of a jaxpr, a checkpoint that keeps values by the names it is
given, and a benchmark cell's one-stage pipeline built for described (not
attached) devices."""

import functools
import json
from pathlib import Path

import jax

CONFIGS = Path(__file__).resolve().parents[2] / "benchmarks" / "configs"


def all_eqns(jaxpr):
    """Every equation of a jaxpr, those of its sub-jaxprs (a scan's body,
    a checkpoint's, a custom rule's call) included."""
    for eqn in jaxpr.eqns:
        yield eqn
        for param in eqn.params.values():
            for sub in param if isinstance(param, (list, tuple)) else [param]:
                inner = sub if hasattr(sub, "eqns") else getattr(
                    sub, "jaxpr", None)
                if hasattr(inner, "eqns"):
                    yield from all_eqns(inner)


def kernel_calls(fn, *args):
    """The `pallas_call` equations of `fn`'s jaxpr at `args`, in the
    program's order. Traced only: nothing runs."""
    return [e for e in all_eqns(jax.make_jaxpr(fn)(*args).jaxpr)
            if e.primitive.name == "pallas_call"]


def checkpoint_keeping(*names):
    """`jax.checkpoint` with the policy that keeps values by `names` and
    nothing else: what `ops/remat.checkpoint_layer` is for its own list."""
    return functools.partial(
        jax.checkpoint,
        policy=jax.checkpoint_policies.save_only_these_names(*names))


def pallas_calls(jaxpr, found=None):
    """(name, operand shapes after the prefetched tables) of every
    `pallas_call` equation, sub-jaxprs included."""
    found = [] if found is None else found
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "pallas_call":
            found.append((eqn.params["name"],
                          [v.aval.shape for v in eqn.invars[3:]]))
            continue
        for param in eqn.params.values():
            for sub in param if isinstance(param, (list, tuple)) else [param]:
                # A checkpoint's body is a Jaxpr, a pjit's a ClosedJaxpr.
                inner = sub if hasattr(sub, "eqns") else getattr(
                    sub, "jaxpr", None)
                if inner is not None:
                    pallas_calls(inner, found)
    return found


def cell_stage(config_name, devices, *, microbatch, seq, num_microbatches=8):
    """The one stage (first and last) of the pipeline a benchmark cell
    trains, from `benchmarks/configs/<config_name>.json`, with abstract
    parameters and one abstract microbatch: `st.bwd[0]` lowers on
    (params, params, None, batch), the loss's value-and-gradient with the
    running sum donated. Returns (stage, params, batch)."""
    from oobleck_tpu.config import ExecutionArguments
    from oobleck_tpu.execution.pipeline import PipelineInstance
    from oobleck_tpu.execution.precompile import _sds as sds
    from oobleck_tpu.models import build_model
    from oobleck_tpu.planning.templates import PipelineTemplate, StageSpec

    config = json.loads((CONFIGS / f"{config_name}.json").read_text())
    model = build_model(
        config["model_name"], dict(config["model_args"]),
        execution=ExecutionArguments(**config["execution"]))
    n = model.num_pipeline_layers
    template = PipelineTemplate(
        (StageSpec(tuple(range(n)), 1, 1.0, 3.0, 1000),), 10.0, n, 1, 1)
    pipe = PipelineInstance(
        pipeline_id=0, template=template, ranks=[0], model=model,
        devices=list(devices), num_microbatches=num_microbatches,
        total_num_microbatches=num_microbatches, microbatch_size=microbatch,
        seq_len=seq, materialize_params=False)
    st = pipe.stages[0]
    params = tuple(
        jax.tree.map(sds, jax.eval_shape(
            lambda r, _li=li: model.init_layer(r, _li), jax.random.PRNGKey(0)),
            st.param_shardings[li])
        for li in st.chunks[0])
    batch = {k: sds(v, st.batch_sharding)
             for k, v in model.sample_batch(microbatch, seq).items()}
    return st, params, batch
