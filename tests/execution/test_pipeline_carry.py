"""A carry that is a TREE layers add to, across a stage edge: the
Phi-4-mini-flash family's `(hidden, m, k, v)` (`models/phi4flash.py`).

A two-stage pipeline on two CPU devices, cut between the keys' and values'
source and the first Gated Memory Unit, so the whole tree crosses the edge
forward and its cotangent tree comes back, against one stage: the same loss
and gradients. Then the parts a cross-process edge is made of, on the same
tree (`activation_avals`, `ProcessComm.send`'s packed layout), and the
planner's rows for it.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from oobleck_tpu.execution.pipeline import PipelineInstance
from oobleck_tpu.models import build_model
from oobleck_tpu.parallel.cross_host import TypedFlatLayout, activation_avals
from tests.execution.test_pipeline_mpmd import make_template

MB, SEQ, NUM_MB = 1, 32, 2
# phi4flash-tiny: [embed, mamba, swa, mamba, swa, mamba_source, full_source,
# gmu, cross, head]; the carry is (hidden, m, k, v) after layer 6.
CUT = 7


@pytest.fixture(scope="module")
def model():
    return build_model("phi4flash-tiny", {"dtype": jnp.float32})


@pytest.fixture(scope="module")
def batch(model):
    rng = np.random.default_rng(0)
    return rng.integers(0, model.config.vocab_size,
                        size=(NUM_MB, MB, SEQ), dtype=np.int32)


def _pipeline(model, batch, splits, devices):
    template = make_template(splits, [1] * len(splits))
    pipe = PipelineInstance(
        pipeline_id=0, template=template,
        ranks=list(range(template.num_chips)), model=model, devices=devices,
        num_microbatches=NUM_MB, total_num_microbatches=NUM_MB,
        microbatch_size=MB, seq_len=SEQ)
    return pipe, float(pipe.train_step(batch))


@pytest.fixture(scope="module")
def one_and_two(model, batch, devices8):
    n = model.num_pipeline_layers
    return (_pipeline(model, batch, [(0, n)], devices8),
            _pipeline(model, batch, [(0, CUT), (CUT, n)], devices8))


def test_the_loss_of_two_stages_is_one_stages(one_and_two):
    (_, one), (_, two) = one_and_two
    assert np.isfinite(one) and two == pytest.approx(one, rel=1e-6)


@pytest.mark.parametrize("layer", range(10))
def test_every_gradient_of_two_stages_is_one_stages(one_and_two, layer):
    """The layers before the cut get their gradients from the cotangent
    tree that came back over the edge: `m`'s into the memory's source,
    `k, v`'s into theirs, all three from BOTH cross-decoder layers."""
    (one, _), (two, _) = one_and_two
    for a, b in zip(jax.tree.leaves(two.grads[layer]),
                    jax.tree.leaves(one.grads[layer])):
        scale = max(float(jnp.max(jnp.abs(b))), 1e-6)
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   atol=2e-5 * scale, rtol=2e-4)


def test_the_edge_ships_the_whole_tree_and_the_planner_is_charged_it(model):
    """`_edge_aval` is `activation_avals`' entry: a tree. What a
    cross-process edge does with it (`ProcessComm.send`: pack on the
    sender, unpack on the receiver) gives the tree back."""
    c = model.config
    avals = activation_avals(model, MB, SEQ)
    shape = lambda width: (MB, SEQ, width)
    kv = c.num_kv_heads * c.head_dim
    assert [jax.tree.map(lambda a: a.shape, a) for a in avals[4:8]] == [
        shape(c.hidden_size),
        (shape(c.hidden_size), shape(c.d_inner)),
        (shape(c.hidden_size), shape(c.d_inner), shape(kv), shape(kv)),
        (shape(c.hidden_size), shape(c.d_inner), shape(kv), shape(kv))]
    edge = avals[CUT - 1]
    layout = TypedFlatLayout({0: edge})
    values = tuple(jnp.full(a.shape, i + 1.0, a.dtype)
                   for i, a in enumerate(edge))
    back = layout.unpack(layout.pack_leaves(0, list(values)), 0)
    assert jax.tree.structure(back) == jax.tree.structure(edge)
    for a, b in zip(back, values):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    assert layout.wire_bytes == 4 * MB * SEQ * (
        c.hidden_size + c.d_inner + 2 * kv)


def test_the_planner_s_rows_charge_a_cross_decoder_layer_its_carry(model):
    """`mem_required[1]` of a layer is the bytes of what it hands on: the
    four leaves for `full_source`, `gmu` and `cross`, one kind timed once;
    and the engine's gauge is the largest of them."""
    from oobleck_tpu.planning.profiler import profile_execution_layers

    c = model.config
    rows = profile_execution_layers(model, MB, SEQ)
    names = [model.layer_name(i) for i in range(model.num_pipeline_layers)]
    assert names == ["embed", "mamba_0", "swa_1", "mamba_2", "swa_3",
                     "mamba_source_4", "full_source_5", "gmu_6", "cross_7",
                     "head"]
    hidden = 4 * MB * SEQ * c.hidden_size
    carry = 4 * MB * SEQ * (c.hidden_size + c.d_inner
                            + 2 * c.num_kv_heads * c.head_dim)
    by_name = dict(zip(names, (r["mem_required"][1] for r in rows)))
    assert by_name["mamba_2"] == by_name["swa_3"] == hidden
    assert by_name["mamba_source_4"] == hidden + 4 * MB * SEQ * c.d_inner
    assert (by_name["full_source_5"] == by_name["gmu_6"]
            == by_name["cross_7"] == carry)
    assert rows[1] == rows[3] and rows[2] == rows[4]       # timed once
    assert all(r["forward"] > 0 and r["backward"] > 0 for r in rows)
