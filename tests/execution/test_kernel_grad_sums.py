"""The routed experts' running gradient sums inside the dW kernel, through a
pipeline stage (`execution/pipeline._accumulate`, `ops/moe.GradSum`).

A routed one-stage pipeline on the CPU with the expert kernels in Pallas's
interpreter: the model marks the held experts' matrices, the stage's
backward hands their sums down and takes the kernels' `sum + dW` as the new
sum. Against the same kernels with the plain `acc + grads` (the model
marking nothing), over two microbatches, and counted by
`oobleck_pipeline_grad_accumulations_total{where="moe_tgmm"}`. A stage
whose mesh splits the microbatch over two devices, and a run off the
kernels' path, keep the plain add and count nothing.
"""

import jax
import numpy as np
import pytest

from oobleck_tpu.execution.pipeline import PipelineInstance
from oobleck_tpu.models import build_model
from oobleck_tpu.planning.templates import PipelineTemplate, StageSpec
from oobleck_tpu.utils import metrics

MB, SEQ, NUM_MB = 2, 32, 2
# model -> leaves its one stage sums in the kernel: three routed blocks of
# SwiGLU experts (w1, w3, w2), two of experts without a gate (w1, w2).
ROUTED = {"lfm2-moe-tiny": 3 * 3, "nemotron-h-tiny": 2 * 2}


def _train_step(name, *, chips=1, marks=True):
    """(stage, its gradients after one step of NUM_MB microbatches, what
    the step counted under where="moe_tgmm")."""
    model = build_model(name, {"remat": True})
    if not marks:
        model.sums_in_kernel = lambda index, params: None
    n = model.num_pipeline_layers
    template = PipelineTemplate(
        (StageSpec(tuple(range(n)), chips, 1.0, 3.0, 1000),), 10.0, n, 1, 1)
    pipe = PipelineInstance(
        pipeline_id=0, template=template, ranks=list(range(chips)),
        model=model, devices=jax.devices(), num_microbatches=NUM_MB,
        total_num_microbatches=NUM_MB, microbatch_size=MB, seq_len=SEQ)
    batch = np.random.default_rng(0).integers(
        0, model.config.data_vocab_size, size=(NUM_MB, MB, SEQ),
        dtype=np.int32)
    counter = metrics.registry().counter(
        "oobleck_pipeline_grad_accumulations_total")
    before = counter.value(where="moe_tgmm")
    assert np.isfinite(float(pipe.train_step(batch)))
    grads = [np.asarray(g) for g in jax.tree.leaves(pipe.grads)]
    return pipe.stages[0], grads, counter.value(where="moe_tgmm") - before


@pytest.mark.parametrize("name", sorted(ROUTED))
def test_sums_taken_in_the_kernel_equal_the_plain_add(kernels_interpreted, name):
    st, got, counted = _train_step(name)
    assert st.kernel_sums == [ROUTED[name]]
    assert counted == ROUTED[name] * NUM_MB
    plain_st, want, plain_counted = _train_step(name, marks=False)
    assert plain_st.kernel_sums == [0] and plain_counted == 0
    # The second microbatch's sum is (g1 + g2) in one case and g1, then
    # + g2, in the other: float32 rounding of one addition apart.
    for g, w in zip(got, want):
        np.testing.assert_allclose(g, w, rtol=1e-5,
                                   atol=1e-6 * max(np.abs(w).max(), 1e-30))
    assert any(np.abs(w).max() > 0 for w in want)


def test_a_stage_that_splits_the_microbatch_keeps_the_plain_add(kernels_interpreted):
    st, _, counted = _train_step("lfm2-moe-tiny", chips=2)
    assert st.use_fsdp and st.mesh.size == 2      # gradients reduce over it
    assert st.kernel_sums == [0] and counted == 0


def test_off_the_kernels_path_the_plain_add_stays():
    st, _, counted = _train_step("nemotron-h-tiny")
    assert st.kernel_sums == [0] and counted == 0
