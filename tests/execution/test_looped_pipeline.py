"""A model that goes through a range of its layers several times over one
set of weights (`models/base.py`: the contract; `models/ouro.py`), in the
MPMD pipeline: ouro-tiny (2 blocks, 3 passes) on one stage, where the
visits are folded into the stage's one program; on two stages CUT INSIDE
the repeated range, where every stage holds three chunks that are the same
layers and the carry goes round the stages three times; and with the fold
switched off, the whole range visited behind a stage that holds the
embedding alone. Each against the plain reference
(`benchmarks/reference/ouro.py`): the loss and every gradient leaf. Then
what holds whatever the route: a layer's gradient sum is zero-filled once a
step and takes every visit's addition, the key holds the walk, the
precompiler's walk compiles such a pipeline's programs, a re-cut pipeline
takes the old parameters, and what the constructor refuses. And what the
fold IS: the passes are the trips of ONE `lax.scan` over the range's layers
(the body once in the program however many passes), a walk that repeats
nothing traces no loop, and a repeated layer's load is the sum over the
trips."""

from dataclasses import dataclass

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks.reference import ouro as ref
from oobleck_tpu.execution import pipeline
from oobleck_tpu.execution.pipeline import PROGRAMS, PipelineInstance
from oobleck_tpu.models import build_model
from oobleck_tpu.utils import metrics
from tests.execution.test_pipeline_mpmd import make_template
from tests.models.test_ouro import SEED, as_list, ref_config

MB, SEQ, NUM_MB = 1, 32, 2
FOLDED, CUT_INSIDE = "folded", "cut inside"
SPLITS = {FOLDED: [(0, 4)], CUT_INSIDE: [(0, 2), (2, 4)]}


@pytest.fixture(scope="module")
def model():
    return build_model("ouro-tiny", {"dtype": jnp.float32})


@pytest.fixture(scope="module")
def batch():
    return np.random.default_rng(0).integers(
        0, 256, size=(NUM_MB, MB, SEQ), dtype=np.int32)


@pytest.fixture(scope="module")
def seeded(model):
    return ref.init_params(SEED, ref_config(model.config))


def build(model, devices, splits, *, params=None, fold=True, **kw):
    template = make_template(splits, [1] * len(splits))
    pipeline.FOLD_WHOLE_RANGE = fold
    try:
        return PipelineInstance(
            pipeline_id=0, template=template,
            ranks=list(range(template.num_chips)), model=model,
            devices=devices, microbatch_size=MB, seq_len=SEQ,
            **{"num_microbatches": NUM_MB, "total_num_microbatches": NUM_MB,
               "params": params, **kw})
    finally:
        pipeline.FOLD_WHOLE_RANGE = True


def counter(name, **labels):
    return metrics.registry().counter(name).value(**labels)


@pytest.fixture(scope="module")
def trained(model, batch, seeded, devices8):
    """{route: (pipeline, its step's loss, what the step counted)}, every
    pipeline on the seed's weights; and the reference's loss and gradients
    of the same two microbatches."""
    PROGRAMS.clear()
    out = {}
    for route, splits in SPLITS.items():
        pipe = build(model, devices8, splits,
                     params=dict(enumerate(as_list(seeded))))
        fills = counter("oobleck_pipeline_grad_accumulations_total",
                        where="zero_fill")
        visits = [counter("oobleck_pipeline_stage_visits_total",
                          stage=str(s)) for s in range(2)]
        with jax.default_matmul_precision("highest"):
            loss = float(pipe.train_step(batch))
        out[route] = (pipe, loss, {
            "zero_fills": counter("oobleck_pipeline_grad_accumulations_total",
                                  where="zero_fill") - fills,
            "visits": [counter("oobleck_pipeline_stage_visits_total",
                               stage=str(s)) - v
                       for s, v in enumerate(visits)]})
    rc = ref_config(model.config)
    both = jax.jit(lambda p, t: ref.loss_and_grads(p, t, rc))
    with jax.default_matmul_precision("highest"):
        per_mb = [both(seeded, jnp.asarray(mb)) for mb in batch]
    loss = float(np.mean([float(l) for (l, _), _ in per_mb]))
    grads = jax.tree.map(lambda *g: sum(g) / NUM_MB,
                         *(g for _, g in per_mb))
    yield out, loss, as_list(grads)
    PROGRAMS.clear()


def test_chunks_and_walks(trained):
    pipes, _, _ = trained
    folded, cut = (pipes[r][0] for r in SPLITS)
    assert folded.virtual_stages == 1
    assert folded.stages[0].chunks == ((0, 1, 2, 3),)
    assert folded.stages[0].walks == ((0, 1, 2, 1, 2, 1, 2, 3),)
    # The cut falls between the two blocks: each stage visits its block
    # three times, the embedding in front on the first visit alone, the
    # head behind on the last alone.
    assert cut.virtual_stages == 3
    assert [st.chunks for st in cut.stages] == [
        ((0, 1), (1,), (1,)), ((2,), (2,), (2, 3))]
    assert [st.walks for st in cut.stages] == [st.chunks for st in cut.stages]
    assert [st.layer_ids for st in cut.stages] == [(0, 1), (2, 3)]
    # A stage's visits between the first and the last are ONE program.
    assert cut.stages[0].bwd[1] is cut.stages[0].bwd[2]
    assert cut.stages[1].bwd[0] is cut.stages[1].bwd[1]


@pytest.mark.parametrize("route", SPLITS)
def test_the_loss_is_the_reference_s(trained, route):
    pipes, loss_ref, _ = trained
    assert pipes[route][1] == pytest.approx(loss_ref, rel=2e-6)


@pytest.mark.parametrize("layer", range(4))
@pytest.mark.parametrize("route", SPLITS)
def test_every_gradient_leaf_is_the_reference_s(trained, route, layer):
    """Every parameter is held once and its sum has taken all three
    visits' additions, whichever way the visits are scheduled."""
    pipes, _, grads_ref = trained
    got = jax.tree_util.tree_leaves_with_path(pipes[route][0].grads[layer])
    for (path, a), b in zip(got, jax.tree.leaves(grads_ref[layer])):
        scale = max(float(jnp.max(jnp.abs(b))), 1e-8)
        np.testing.assert_allclose(
            np.asarray(a), np.asarray(b), atol=3e-5 * scale, rtol=3e-4,
            err_msg=jax.tree_util.keystr(path))


def test_a_shared_weight_s_gradient_is_the_sum_over_its_uses(
        trained, model, batch, seeded):
    """R untied copies of the reference's blocks, every copy holding the
    shared values: the program's gradient of a block is the SUM of the
    copies' gradients, leaf by leaf, and no one copy's alone."""
    pipes, _, _ = trained
    rc = ref_config(model.config)
    untied = dict(seeded, blocks=[seeded["blocks"]] * rc.num_passes)
    grad = jax.jit(jax.grad(
        lambda p, t: ref.loss(p, t, rc, untied=True)[0]))
    with jax.default_matmul_precision("highest"):
        per_mb = [grad(untied, jnp.asarray(mb))["blocks"] for mb in batch]
    by_copy = jax.tree.map(lambda *g: sum(g) / NUM_MB, *per_mb)
    summed = jax.tree.map(lambda *g: sum(g), *by_copy)
    for route in SPLITS:
        for b in range(rc.num_layers):
            for a, want, first in zip(
                    jax.tree.leaves(pipes[route][0].grads[1 + b]),
                    jax.tree.leaves(summed[b]),
                    jax.tree.leaves(by_copy[0][b])):
                scale = max(float(jnp.max(jnp.abs(want))), 1e-8)
                np.testing.assert_allclose(np.asarray(a), np.asarray(want),
                                           atol=3e-5 * scale, rtol=3e-4)
                assert not np.allclose(np.asarray(a), np.asarray(first),
                                       atol=3e-5 * scale, rtol=3e-4)


@pytest.mark.parametrize("route,fills,visits", [
    (FOLDED, 1, [NUM_MB, 0]),
    # The stage's first backward of a step fills its sums; the embedding,
    # which only the first chunk names, is filled when that chunk's first
    # backward comes, once.
    (CUT_INSIDE, 3, [3 * NUM_MB, 3 * NUM_MB]),
])
def test_a_sum_is_filled_once_a_step_and_a_stage_counts_its_visits(
        trained, route, fills, visits):
    counted = trained[0][route][2]
    assert counted == {"zero_fills": fills, "visits": visits}


def test_the_key_holds_the_walk(model, devices8):
    """The same layers of the same model on the same device, folded or
    visited: another walk, another program."""
    splits = [(0, 1), (1, 4)]
    folded = build(model, devices8, splits)
    visited = build(model, devices8, splits, fold=False)
    a, b = folded.stages[1], visited.stages[1]
    assert a.chunks[0] == b.chunks[2] == (1, 2, 3) and a.mesh == b.mesh
    assert a.walks[0] == (1, 2) * 3 + (3,) and b.walks[2] == (1, 2, 3)
    key_a, key_b = (folded.stage_program_key(a, 0),
                    visited.stage_program_key(b, 2))
    assert key_a != key_b and key_a[:3] == key_b[:3]
    assert a.bwd[0] is not b.bwd[2]
    # A stage in front of the range has nothing to apply after the first
    # visit: the carry that enters the range's first layer is then the
    # range's last layer's.
    assert visited.stages[0].chunks == ((0,), (), ())
    assert visited.stages[1].chunks == ((1, 2), (1, 2), (1, 2, 3))
    assert [visited.input_edge_layer(1, c) for c in range(3)] == [0, 2, 2]
    assert [visited.input_edge_layer(0, c) for c in (1, 2)] == [2, 2]


def test_stages_without_a_repeated_layer_pass_the_carry_through(
        trained, model, batch, devices8):
    pipes, loss_ref, _ = trained
    pipe = build(model, devices8, [(0, 1), (1, 4)], fold=False,
                 params=dict(pipes[FOLDED][0].params))
    with jax.default_matmul_precision("highest"):
        assert float(pipe.train_step(batch)) == pytest.approx(
            loss_ref, rel=2e-6)
        assert float(pipe.eval_step(batch)) == pytest.approx(
            loss_ref, rel=2e-6)
    for a, b in zip(jax.tree.leaves(pipe.grads[2]),
                    jax.tree.leaves(pipes[FOLDED][0].grads[2])):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   atol=1e-6, rtol=3e-4)


def test_a_re_cut_pipeline_takes_the_old_parameters(
        trained, model, batch, devices8):
    """A recovery re-cuts the list: two stages' parameters into one stage
    (folded again), the same loss; `eval_step` reads the last exit."""
    pipes, loss_ref, _ = trained
    recut = build(model, devices8, SPLITS[FOLDED],
                  params=dict(pipes[CUT_INSIDE][0].params))
    with jax.default_matmul_precision("highest"):
        assert float(recut.eval_step(batch)) == pytest.approx(
            loss_ref, rel=2e-6)
        assert float(pipes[CUT_INSIDE][0].eval_step(batch)) == pytest.approx(
            loss_ref, rel=2e-6)
    correct, count = recut.last_eval_metrics
    assert count == NUM_MB * MB * (SEQ - 1) and 0 <= correct <= count
    assert pipes[CUT_INSIDE][0].last_eval_metrics == (correct, count)


def test_the_precompiler_s_walk_compiles_a_cut_inside_the_range(
        model, devices8):
    from oobleck_tpu.execution.precompile import RecoveryPrecompiler

    pipe = build(model, devices8, SPLITS[CUT_INSIDE],
                 materialize_params=False)
    walk = RecoveryPrecompiler(None)
    walk._aot_opt_update = lambda *_: None      # the engine's, and none here
    walk._aot_pipeline(pipe)
    assert walk.stats["errors"] == 0
    # Two programs a stage: with the embedding or the head, and without.
    assert len(walk._done_keys) == 4 and walk.stats["stages_cached"] == 2


def test_what_the_constructor_refuses(model, devices8):
    with pytest.raises(ValueError, match=r"layers 1\.\.2 3 times"):
        build(model, devices8, SPLITS[CUT_INSIDE], virtual_stages=2)
    # The visits are scheduled as the interleaved schedule's chunks.
    with pytest.raises(ValueError, match="multiple of num_stages"):
        build(model, devices8, SPLITS[CUT_INSIDE], num_microbatches=3,
              total_num_microbatches=3)


def test_the_loop_s_counters_say_what_a_microbatch_goes_through(
        model, devices8):
    before = [counter("oobleck_loop_block_visits_total"),
              counter("oobleck_loop_exits_total")]
    # How the visits are made: the folded walk's three passes are trips of
    # one loop in the program, a stage's visits are none.
    for splits, fold, scanned in (([(0, 4)], True, 3),
                                  ([(0, 2), (2, 4)], True, 0),
                                  ([(0, 4)], False, 0)):
        trips = counter("oobleck_loop_scanned_passes_total")
        build(model, devices8, splits, fold=fold)
        assert counter("oobleck_loop_scanned_passes_total") - trips == scanned
    assert counter("oobleck_loop_block_visits_total") - before[0] == 3 * 6
    assert counter("oobleck_loop_exits_total") - before[1] == 3 * 3
    # A model that repeats nothing counts nothing.
    plain = build_model("gpt2-tiny", {})
    n = plain.num_pipeline_layers
    template = make_template([(0, n)], [1])
    PipelineInstance(
        pipeline_id=0, template=template, ranks=[0], model=plain,
        devices=devices8, num_microbatches=2, total_num_microbatches=2,
        microbatch_size=MB, seq_len=SEQ, materialize_params=False)
    assert counter("oobleck_loop_block_visits_total") - before[0] == 3 * 6


# ---- the fold is one loop ------------------------------------------------


def equations(jaxpr, name, *, in_loop=False):
    """[(equation, inside a `scan` body?)] of the primitive `name`, through
    every sub-jaxpr (a checkpoint's, a call's, a loop's body)."""
    out = []
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == name:
            out.append((eqn, in_loop))
        inner = in_loop or eqn.primitive.name == "scan"
        for sub in jax.core.jaxprs_in_params(eqn.params):
            out.extend(equations(sub, name, in_loop=inner))
    return out


def bwd_jaxpr(pipe):
    """The jaxpr of a one-stage pipeline's `jit_bwd` (first and last)."""
    st = pipe.stages[0]
    params = tuple(pipe.params[li] for li in st.chunks[0])
    tokens = {"input_ids": jnp.zeros((MB, SEQ), jnp.int32)}
    return jax.make_jaxpr(st.bwd[0])(params, params, None, tokens).jaxpr


def block_products(jaxpr):
    """(outside a loop, inside one): the products of the repeated blocks
    (the model's scope `loop_blocks`) in `jaxpr`."""
    where = [inside for eqn, inside in equations(jaxpr, "dot_general")
             if "loop_blocks" in str(eqn.source_info.name_stack)]
    return where.count(False), where.count(True)


@pytest.fixture(scope="module")
def folded_jaxprs(devices8):
    """passes -> the folded `jit_bwd`'s jaxpr, ouro-tiny's two blocks."""
    return {passes: bwd_jaxpr(build(
        build_model("ouro-tiny", {"dtype": jnp.float32,
                                  "num_passes": passes}),
        devices8, SPLITS[FOLDED])) for passes in (1, 2, 3)}


def the_fold_s(jaxpr):
    """The `scan`s of `jaxpr`: ouro-tiny's layers bring none themselves."""
    return equations(jaxpr, "scan")


def test_the_folded_program_holds_the_range_s_body_once(folded_jaxprs):
    """Forward and backward are a `scan` each, `num_passes` long; every
    product of a block is in their bodies, as many at three passes as at
    two; outside them only the exits grow with the passes."""
    inside = {}
    for passes in (2, 3):
        scans = the_fold_s(folded_jaxprs[passes])
        assert [(eqn.params["length"], nested) for eqn, nested in scans] == [
            (passes, False)] * 2
        outside, inside[passes] = block_products(folded_jaxprs[passes])
        assert outside == 0
    assert inside[2] == inside[3] > 0
    # The same blocks in a Python loop, once through: as many products.
    assert block_products(folded_jaxprs[1]) == (inside[3], 0)


def test_the_running_sums_ride_the_loop_s_carry(folded_jaxprs, model):
    """The backward loop carries, beside the pipeline's carry, a tree
    shaped as the repeated blocks' parameters that starts from the running
    gradient sum and takes every trip's gradient (`_route`): the forward
    loop hands the same tree on untouched, which a closed-over weight's
    own accumulators (filled with zeros, in the backward loop alone) would
    not show. (What the chip's compiler makes of it, 1.2 GB of
    temporaries less at `ouro-2.6b`'s size, is in `PERF.md` §6, PR 65: the
    cell-sized compile takes 44-58 s here, too long for a test.)"""
    (forward, _), (backward, _) = the_fold_s(folded_jaxprs[3])
    leaves = sum(len(jax.tree.leaves(jax.eval_shape(
        lambda r: model.init_layer(r, li), jax.random.PRNGKey(0))))
        for li in model.repeated_layers)
    assert forward.params["num_carry"] == 3 + leaves   # h, exits, gates
    assert backward.params["num_carry"] >= 3 + leaves


def test_the_fold_on_a_stage_of_two_chips_sums_what_one_chip_sums(
        model, seeded, devices8):
    """Under the partitioner (a stage of two chips that split the
    microbatch) the sums that ride the loop take the chips' reduced
    gradients as one chip's do: the arrays are global ones."""
    batch = np.random.default_rng(1).integers(
        0, 256, size=(NUM_MB, 2, SEQ), dtype=np.int32)
    grads = []
    for chips in (1, 2):
        template = make_template(SPLITS[FOLDED], [chips])
        pipe = PipelineInstance(
            pipeline_id=0, template=template, ranks=list(range(chips)),
            model=model, devices=devices8, num_microbatches=NUM_MB,
            total_num_microbatches=NUM_MB, microbatch_size=2, seq_len=SEQ,
            params=dict(enumerate(as_list(seeded))))
        assert pipe.stages[0].use_fsdp == (chips == 2)
        pipe.train_step(batch)
        grads.append(jax.tree.map(np.asarray, pipe.grads))
    for a, b in zip(*map(jax.tree.leaves, grads)):
        np.testing.assert_allclose(a, b, rtol=3e-4, atol=1e-6)


@pytest.mark.parametrize("name", ["ouro-tiny one pass", "gpt2-tiny"])
def test_a_walk_that_repeats_nothing_traces_no_loop(
        name, folded_jaxprs, devices8):
    """One pass, or a model that says nothing of passes: the Python loop
    over the layers, the program every other model has."""
    if name == "gpt2-tiny":
        plain = build_model(name, {})
        n = plain.num_pipeline_layers
        jaxpr = bwd_jaxpr(build(plain, devices8, [(0, n)]))
    else:
        jaxpr = folded_jaxprs[1]
    assert the_fold_s(jaxpr) == []
    assert equations(jaxpr, "while") == []


@dataclass(frozen=True)
class CountingConfig:
    remat: bool = True
    width: int = 8


class Counting:
    """A layer list on the contract alone: [embed, a, b, head], `a` and
    `b` gone through three times, `b` handing out a load that follows the
    carry (two numbers: the carry's positive entries, and one a visit)."""

    config = CountingConfig()
    num_pipeline_layers = 4
    repeated_layers = range(1, 3)
    num_passes = 3

    def layer_name(self, index):
        return ("embed", "a", "b", "head")[index]

    def init_layer(self, rng, index):
        w = self.config.width
        return {"w": jax.random.normal(jax.random.fold_in(rng, index),
                                       (w, w)) / w ** 0.5}

    def load_layers(self, num_tokens):
        return {2: ("b", 1)}

    def apply_layer(self, index, params, carry, batch, return_load=False):
        if index == 0:
            carry = jax.nn.one_hot(batch["input_ids"] % self.config.width,
                                   self.config.width)
        out = jnp.tanh(carry @ params["w"]) + (carry if index else 0.0)
        if return_load:
            return out, jnp.stack([jnp.sum(out > 0), 1]).astype(jnp.int32)
        return out

    def loss_from_logits(self, logits, batch):
        return jnp.mean(logits ** 2)

    def sample_batch(self, batch_size, seq_len):
        return {"input_ids": jnp.zeros((batch_size, seq_len), jnp.int32)}


def test_a_repeated_layer_s_load_is_the_sum_over_the_trips(batch, devices8):
    model = Counting()
    PROGRAMS.clear()
    try:
        pipe = build(model, devices8, [(0, 4)])
        st = pipe.stages[0]
        assert st.walks == ((0, 1, 2, 1, 2, 1, 2, 3),)
        assert st.load_layers == [(2,)]
        pipe.train_step(batch)
        ((layers, load),) = pipe.load
    finally:
        PROGRAMS.clear()
    # The same applications one after another, no program around them.
    want = 0
    for mb in batch:
        carry = None
        for li in st.walks[0][:-1]:
            carry = model.apply_layer(li, pipe.params[li], carry,
                                      {"input_ids": mb}, return_load=li == 2)
            if li == 2:
                carry, visit = carry
                want = want + visit
    assert layers == (2,) and load.shape == (1, 2)
    np.testing.assert_array_equal(np.asarray(load[0]), np.asarray(want))
    assert int(want[1]) == NUM_MB * 3 and 0 < int(want[0])
