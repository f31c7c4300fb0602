"""The gradient sum between pipelines, by path (`engine.DataParallelEngine`).

Owners that hold a layer on congruent stages (meshes of one shape, every
leaf at one spec) sum it in ONE collective over their chips
(`CollectiveGroup`, `dp_sum_program`); any other owners keep the anchor
path. Held here, on the suite's CPU devices:

  * two and three congruent owners, on one-chip stages and on two-chip
    stages whose leaves are sharded, take the collective: every owner gets
    the same bits, they are the anchor path's (exactly for two owners, to
    float32 rounding for three), each owner's arrays are on its own
    `param_shardings`, and the owners' own gradients are still there;
  * owners on different mesh shapes take the anchor path, as before;
  * a one-pipeline engine dispatches nothing;
  * `oobleck_dp_sync_layer_sums_total{path}` counts a shared layer a step
    under the path that summed it.
"""

import jax
import numpy as np
import pytest

from oobleck_tpu.execution import engine as engine_mod
from oobleck_tpu.execution.engine import CollectiveGroup, DataParallelEngine
from oobleck_tpu.execution.pipeline import PipelineInstance
from oobleck_tpu.models import build_model
from oobleck_tpu.parallel.cross_host import layer_avals
from oobleck_tpu.utils import metrics
from tests.execution.test_pipeline_mpmd import make_template

MB, SEQ, NUM_MB = 2, 32, 2
# gpt2-tiny with one block: layers 0 (embedding), 1 (block), 2 (head).
TWO_STAGES = dict(splits=[(0, 2), (2, 3)], chips=[1, 1])
ONE_WIDE_STAGE = dict(splits=[(0, 3)], chips=[2])
ONE_STAGE = dict(splits=[(0, 3)], chips=[1])


@pytest.fixture(scope="module")
def model():
    return build_model("gpt2-tiny", {"num_layers": 1})


def _pipelines(model, devices, shapes):
    """One pipeline a shape, on the next chips of `devices`, each after a
    step on a batch of its own."""
    pipes, first = [], 0
    for i, shape in enumerate(shapes):
        n = sum(shape["chips"])
        pipe = PipelineInstance(
            pipeline_id=i,
            template=make_template(shape["splits"], shape["chips"]),
            ranks=list(range(n)), model=model,
            devices=devices[first:first + n], num_microbatches=NUM_MB,
            total_num_microbatches=NUM_MB * len(shapes),
            microbatch_size=MB, seq_len=SEQ)
        first += n
        pipe.train_step(np.random.default_rng(i).integers(
            0, model.config.vocab_size, size=(NUM_MB, MB, SEQ),
            dtype=np.int32))
        pipes.append(pipe)
    return pipes


def _by_anchor(pipes):
    """What the anchor path gives for the same pipelines."""
    dp = DataParallelEngine(pipes)
    dp.anchor_layers = sorted(
        li for li, owners in dp.owners.items() if len(owners) > 1)
    dp.collective_groups = []
    return dp.do_allreduce()


def _leaves(tree):
    return [np.asarray(l) for l in jax.tree.leaves(tree)]


def _layer_sums(path):
    return metrics.registry().counter(
        "oobleck_dp_sync_layer_sums_total").value(path=path)


CONGRUENT = {
    "two owners, one-chip stages": [TWO_STAGES] * 2,
    "three owners, one-chip stages": [TWO_STAGES] * 3,
    "two owners, leaves sharded over two chips": [ONE_WIDE_STAGE] * 2,
    "three owners, leaves sharded over two chips": [ONE_WIDE_STAGE] * 3,
}


@pytest.mark.parametrize("case", sorted(CONGRUENT))
def test_congruent_owners_sum_in_one_collective(model, devices8, case):
    pipes = _pipelines(model, devices8, CONGRUENT[case])
    n = len(pipes)
    own = [{li: _leaves(p.grads[li]) for li in p.grads} for p in pipes]
    dp = DataParallelEngine(pipes)
    stages = len(CONGRUENT[case][0]["chips"])
    assert not dp.anchor_layers
    assert sorted(g.layers for g in dp.collective_groups) == [
        tuple(range(a, b)) for a, b in CONGRUENT[case][0]["splits"]]
    assert all(g.mesh.axis_names[0] == engine_mod.DP_AXIS
               and g.mesh.devices.shape[0] == n
               for g in dp.collective_groups)

    before = _layer_sums("collective"), _layer_sums("anchor")
    synced = dp.do_allreduce()
    assert dp.last_transfer_count == stages       # one program a group
    assert (_layer_sums("collective"), _layer_sums("anchor")) == (
        before[0] + 3, before[1])

    anchored = _by_anchor(pipes)
    for li in range(3):
        first = _leaves(synced[0][li])
        want = [np.sum(ls, axis=0) for ls in zip(*(o[li] for o in own))]
        # A sum of gradients of different batches is no owner's own.
        assert any(not np.allclose(f, o) for f, o in zip(first, own[0][li]))
        for pipe in pipes:
            got = synced[pipe.pipeline_id][li]
            for g, f, w, a in zip(_leaves(got), first, want,
                                  _leaves(anchored[pipe.pipeline_id][li])):
                np.testing.assert_array_equal(g, f)     # replicas agree
                np.testing.assert_allclose(g, w, rtol=1e-5, atol=1e-7)
                if n == 2:
                    np.testing.assert_array_equal(g, a)
                else:
                    np.testing.assert_allclose(g, a, rtol=1e-6, atol=1e-8)
            st = pipe.stages[pipe.stage_of_layer(li)]
            assert ([l.sharding for l in jax.tree.leaves(got)]
                    == jax.tree.leaves(st.param_shardings[li]))
            # Nothing was donated: the owner's own gradients read as before.
            for mine, was in zip(_leaves(pipe.grads[li]),
                                 own[pipe.pipeline_id][li]):
                np.testing.assert_array_equal(mine, was)


def test_sharded_leaves_go_in_and_come_back_as_they_lie(model, devices8):
    """On two-chip stages some leaf is sharded, the whole array's spec then
    names `dp` and the stage's axis together on dimension 0, and a chip's
    shard of the whole has the shape of its shard of its owner's leaf. The
    precompiler's shapes (`layer_avals`) give the program the step finds."""
    pipes = _pipelines(model, devices8, [ONE_WIDE_STAGE] * 2)
    (group,) = DataParallelEngine(pipes).collective_groups
    avals = layer_avals(model)
    shardings, shapes, program = group.program(
        jax.tree.leaves([avals[li] for li in group.layers]))
    sharded = [sh for sh in shardings if sh.spec[0] not in ("dp", ("dp",))]
    assert sharded and all(sh.spec[0] == ("dp", "fsdp") for sh in sharded)
    grads = jax.tree.leaves([pipes[0].grads[li] for li in group.layers])
    for sh, shape, g in zip(shardings, shapes, grads):
        assert shape == (2 * g.shape[0], *g.shape[1:])
        assert sh.shard_shape(shape) == g.sharding.shard_shape(g.shape)
    (again,) = DataParallelEngine(pipes).collective_groups
    assert again.program(grads)[2] is program


def test_owners_on_different_mesh_shapes_take_the_anchor_path(
        model, devices8):
    pipes = _pipelines(model, devices8, [ONE_WIDE_STAGE, TWO_STAGES])
    own = [{li: _leaves(p.grads[li]) for li in p.grads} for p in pipes]
    assert not CollectiveGroup.congruent(
        [pipes[0].stages[0], pipes[1].stages[0]], (0, 1))
    dp = DataParallelEngine(pipes)
    assert dp.collective_groups == [] and dp.anchor_layers == [0, 1, 2]
    before = _layer_sums("collective"), _layer_sums("anchor")
    synced = dp.do_allreduce()
    assert 0 < dp.last_transfer_count <= 2      # a batched put a phase
    assert (_layer_sums("collective"), _layer_sums("anchor")) == (
        before[0], before[1] + 3)
    for li in range(3):
        want = [a + b for a, b in zip(own[0][li], own[1][li])]
        for pipe in pipes:
            got = synced[pipe.pipeline_id][li]
            for g, w in zip(_leaves(got), want):
                np.testing.assert_allclose(g, w, rtol=1e-5, atol=1e-7)
            st = pipe.stages[pipe.stage_of_layer(li)]
            assert all(l.sharding.is_equivalent_to(sh, l.ndim) for l, sh in zip(
                jax.tree.leaves(got), jax.tree.leaves(st.param_shardings[li])))


def test_one_chip_twice_is_not_congruent(model, devices8):
    """Two pipelines on the SAME chips (what no plan makes) have no mesh a
    collective could run over."""
    pipes = _pipelines(model, devices8, [ONE_STAGE])
    stage = pipes[0].stages[0]
    assert CollectiveGroup.congruent([stage], (0, 1, 2))
    assert not CollectiveGroup.congruent([stage, stage], (0, 1, 2))


def test_a_one_pipeline_engine_dispatches_nothing(model, devices8):
    (pipe,) = _pipelines(model, devices8, [TWO_STAGES])
    dp = DataParallelEngine([pipe])
    assert dp.collective_groups == [] and dp.anchor_layers == []
    before = _layer_sums("collective"), _layer_sums("anchor")
    with jax.log_compiles(False), jax.transfer_guard("disallow"):
        synced = dp.do_allreduce()
    assert dp.last_transfer_count == 0
    assert (_layer_sums("collective"), _layer_sums("anchor")) == before
    assert synced == {0: pipe.grads}
    for li in pipe.grads:       # the very arrays, passed through
        assert all(a is b for a, b in zip(jax.tree.leaves(synced[0][li]),
                                          jax.tree.leaves(pipe.grads[li])))


@pytest.mark.parametrize("spec,ndim,want", [
    ((), 0, ("dp",)),
    ((), 2, (("dp",),)),
    ((None, "tensor"), 2, (("dp",), "tensor")),
    (("fsdp",), 2, (("dp", "fsdp"),)),
    ((("fsdp", "tensor"), None), 2, (("dp", "fsdp", "tensor"), None)),
], ids=str)
def test_a_leaf_s_spec_on_the_group_s_mesh(spec, ndim, want):
    P = jax.sharding.PartitionSpec
    assert engine_mod.dp_spec(P(*spec), ndim) == P(*want)


def test_a_leaf_of_no_dimension_is_summed_too(devices8):
    """No layer of the suite's models has a scalar parameter; one would go
    through as a vector of the owners, one element a chip."""
    from types import SimpleNamespace

    from jax.sharding import Mesh, NamedSharding
    from jax.sharding import PartitionSpec as P

    stages, owners = [], []
    for i in range(3):
        mesh = Mesh(np.array(devices8[2 * i:2 * i + 2]).reshape(2, 1, 1),
                    ("fsdp", "seq", "tensor"))
        shardings = {"scale": NamedSharding(mesh, P()),
                     "w": NamedSharding(mesh, P("fsdp", None))}
        stages.append(SimpleNamespace(
            mesh=mesh, param_shardings={7: shardings},
            param_pspecs={7: {"scale": P(), "w": P("fsdp", None)}}))
        owners.append(SimpleNamespace(grads={7: jax.device_put(
            {"scale": np.float32(i + 1.5),
             "w": np.full((4, 3), i + 1, np.float32)}, shardings)}))
    assert CollectiveGroup.congruent(stages, (7,))
    group = CollectiveGroup((7,), owners, stages)
    for (tree,), stage in zip(group.summed_grads(), stages):
        assert tree["scale"].shape == () and float(tree["scale"]) == 7.5
        np.testing.assert_array_equal(np.asarray(tree["w"]),
                                      np.full((4, 3), 6, np.float32))
        assert {k: v.sharding for k, v in tree.items()} == (
            stage.param_shardings[7])
