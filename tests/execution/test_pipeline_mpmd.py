"""MPMD pipeline tests on the virtual 8-device CPU mesh, mirroring the
reference's pipeline coverage (/root/reference/tests/execution/
test_pipeline.py:20-400): per-stage execution, p2p choreography, full train
for several stage counts, FSDP+PP combo — plus equivalence against the
single-device fused loss."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from oobleck_tpu.execution.pipeline import PipelineInstance
from oobleck_tpu.execution.schedule import Op, all_instructions, stage_instructions
from oobleck_tpu.models import build_model
from oobleck_tpu.planning.templates import LayerProfile, StageSpec, PipelineTemplate

MB, SEQ, NUM_MB = 4, 32, 4


def make_template(layer_splits: list[tuple[int, int]], chips: list[int],
                  chips_per_host: int = 1) -> PipelineTemplate:
    """Hand-built template, like the reference conftest's
    get_dummy_pipeline_template (tests/conftest.py:144-213)."""
    stages = tuple(
        StageSpec(tuple(range(a, b)), c, 1.0, 3.0, 1000)
        for (a, b), c in zip(layer_splits, chips)
    )
    total = layer_splits[-1][1]
    return PipelineTemplate(stages, 10.0, total, len(stages), chips_per_host)


@pytest.fixture(scope="module")
def model():
    return build_model("gpt2-tiny")  # 4 blocks -> 6 pipeline layers


@pytest.fixture(scope="module")
def batch(model):
    rng = np.random.default_rng(0)
    return rng.integers(0, model.config.vocab_size,
                        size=(NUM_MB, MB, SEQ), dtype=np.int32)


def reference_loss_and_grads(model, batch):
    """Single-device fused loss over the same microbatches."""
    params = model.init_params(jax.random.PRNGKey(42))

    def loss_fn(params):
        tokens = jnp.asarray(batch.reshape(-1, SEQ))
        return model.loss(params, {"input_ids": tokens})

    return jax.value_and_grad(loss_fn)(params)


# --------------------------------------------------------------------- #
# schedule


def test_schedule_1f1b_shape():
    ins = stage_instructions(0, 4, 8)
    fwd = [i for i in ins if i.op == Op.FORWARD]
    bwd = [i for i in ins if i.op == Op.BACKWARD]
    assert len(fwd) == len(bwd) == 8
    # stage 0 warms up S-1 forwards before its first backward
    first_b = next(n for n, i in enumerate(ins) if i.op == Op.BACKWARD)
    fwd_before = sum(1 for i in ins[:first_b] if i.op == Op.FORWARD)
    assert fwd_before == 4  # warmup(3) + 1 steady forward


def test_schedule_last_stage_alternates():
    ins = [i.op for i in stage_instructions(3, 4, 4)
           if i.op in (Op.FORWARD, Op.BACKWARD)]
    assert ins == [Op.FORWARD, Op.BACKWARD] * 4


# --------------------------------------------------------------------- #
# pipeline execution


def _run_pipeline(model, batch, template, devices, num_mb=NUM_MB):
    pipe = PipelineInstance(
        pipeline_id=0, template=template, ranks=list(range(template.num_chips)),
        model=model, devices=devices, num_microbatches=num_mb,
        total_num_microbatches=num_mb, microbatch_size=MB, seq_len=SEQ,
    )
    loss = pipe.train_step(batch)
    return pipe, float(loss)


@pytest.mark.parametrize("splits,chips", [
    ([(0, 6)], [1]),                       # single stage
    ([(0, 3), (3, 6)], [1, 1]),            # 2 stages
    ([(0, 2), (2, 4), (4, 6)], [1, 1, 1]),  # 3 stages
    ([(0, 1), (1, 3), (3, 5), (5, 6)], [1, 1, 1, 1]),  # 4 incl. bare embed
])
def test_pipeline_loss_matches_fused(model, batch, devices8, splits, chips):
    expected, _ = reference_loss_and_grads(model, batch)
    template = make_template(splits, chips)
    _, loss = _run_pipeline(model, batch, template, devices8)
    assert loss == pytest.approx(float(expected), rel=2e-2)


def test_pipeline_grads_match_fused(model, batch, devices8):
    """Gradients through the 1F1B interpreter must match autodiff through
    the fused program (per-layer, scaled by 1/num_mb)."""
    expected_loss, expected_grads = reference_loss_and_grads(model, batch)
    template = make_template([(0, 3), (3, 6)], [1, 1])
    pipe, _ = _run_pipeline(model, batch, template, devices8)
    # layer 1 = block_0: compare against fused blocks[0]
    got = pipe.grads[1]
    want = jax.tree.map(lambda x: x[0], expected_grads["blocks"])
    for k in ("ln1", "attn", "mlp"):
        g = jax.tree.leaves(got[k])
        w = jax.tree.leaves(want[k])
        for a, b in zip(g, w):
            np.testing.assert_allclose(
                np.asarray(a, np.float32), np.asarray(b, np.float32),
                rtol=5e-2, atol=5e-3,
            )


def test_pipeline_fsdp_stage(model, batch, devices8):
    """A stage spanning 4 chips shards params and batch (FSDP+PP combo)."""
    template = make_template([(0, 3), (3, 6)], [4, 4], chips_per_host=4)
    expected, _ = reference_loss_and_grads(model, batch)
    pipe, loss = _run_pipeline(model, batch, template, devices8)
    assert loss == pytest.approx(float(expected), rel=2e-2)
    # params of a 4-chip stage are actually sharded over 4 devices
    wqkv = pipe.params[1]["attn"]["wqkv"]
    assert len(wqkv.sharding.device_set) == 4


def test_pipeline_seq_parallel_stage(model, batch, devices8):
    """Sequence parallelism INSIDE elastic MPMD stages (round-4 weak #5:
    'elastic and long-context are mutually exclusive'): a 2-stage pipeline
    whose stages are 2-chip (fsdp=1, seq=2, tensor=1) meshes runs ring/
    Ulysses attention over the stage-local `seq` axis and must match both
    the sp=1 pipeline and the fused single-device loss."""
    template = make_template([(0, 3), (3, 6)], [2, 2], chips_per_host=2)
    expected, _ = reference_loss_and_grads(model, batch)

    sp_pipe = PipelineInstance(
        pipeline_id=0, template=template,
        ranks=list(range(template.num_chips)), model=model,
        devices=devices8, num_microbatches=NUM_MB,
        total_num_microbatches=NUM_MB, microbatch_size=MB, seq_len=SEQ,
        sequence_parallel=2,
    )
    for st in sp_pipe.stages:
        assert dict(st.mesh.shape)["seq"] == 2
        assert st.ctx is not None and st.ctx.seq == "seq"
    sp_loss = float(sp_pipe.train_step(batch))

    base_pipe, base_loss = _run_pipeline(
        model, batch, make_template([(0, 3), (3, 6)], [1, 1]), devices8
    )
    assert sp_loss == pytest.approx(base_loss, rel=1e-2)
    assert sp_loss == pytest.approx(float(expected), rel=2e-2)
    # Gradients agree layerwise with the sp=1 interpreter (params are
    # replicated over `seq`; reductions fall out of the shard_map AD).
    got = sp_pipe.grads[1]
    want = base_pipe.grads[1]
    for a, b in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
        np.testing.assert_allclose(
            np.asarray(a, np.float32), np.asarray(b, np.float32),
            rtol=5e-2, atol=5e-3,
        )


def test_optimizer_step_changes_params(model, batch, devices8):
    from oobleck_tpu.parallel.train import make_optimizer

    template = make_template([(0, 3), (3, 6)], [1, 1])
    pipe, _ = _run_pipeline(model, batch, template, devices8)
    opt = make_optimizer(learning_rate=1e-2, warmup_steps=1)
    state = pipe.init_opt_state(opt)
    before = np.asarray(pipe.params[1]["attn"]["wqkv"]).copy()
    # The step consumes the dict it is given: the new state is in it.
    assert pipe.apply_updates(opt, state, pipe.grads) is state
    after = np.asarray(pipe.params[1]["attn"]["wqkv"])
    assert not np.allclose(before, after)


# --------------------------------------------------------------------- #
# interleaved schedule parity


def _make_pipe(model, devices, template, v, num_mb=NUM_MB, params=None):
    return PipelineInstance(
        pipeline_id=0, template=template,
        ranks=list(range(template.num_chips)), model=model, devices=devices,
        num_microbatches=num_mb, total_num_microbatches=num_mb,
        microbatch_size=MB, seq_len=SEQ, virtual_stages=v, params=params,
    )


def test_interleaved_matches_fused_and_splits_chunks(model, batch, devices8):
    """virtual_stages=2 on 2 stages: each stage runs two layer chunks whose
    concatenation in virtual-stage order is the full layer range, and the
    loss still matches the single-device fused program."""
    expected, _ = reference_loss_and_grads(model, batch)
    template = make_template([(0, 3), (3, 6)], [1, 1])
    pipe = _make_pipe(model, devices8, template, v=2)
    assert pipe.virtual_stages == 2
    for st in pipe.stages:
        assert len(st.chunks) == 2
    # vs order = chunk*S + stage must tile the layers contiguously
    vs_chunks = sorted(
        ((c * 2 + st.stage_index, list(chunk))
         for st in pipe.stages for c, chunk in enumerate(st.chunks))
    )
    flat = [li for _, chunk in vs_chunks for li in chunk]
    assert flat == list(range(model.num_pipeline_layers))
    loss = float(pipe.train_step(batch))
    assert loss == pytest.approx(float(expected), rel=2e-2)


def test_interleaved_loss_trajectory_matches_1f1b(model, batch, devices8):
    """The interleaved schedule reorders compute but must not change the
    math: loss trajectories over 3 optimizer steps agree with 1F1B down to
    float reassociation noise (chunked backward sums grads in a different
    order), and so do the first-step layer grads."""
    from oobleck_tpu.parallel.train import make_optimizer

    template = make_template([(0, 3), (3, 6)], [1, 1])

    def run(v):
        pipe = _make_pipe(model, devices8, template, v)
        opt = make_optimizer(learning_rate=1e-2, warmup_steps=1)
        state = pipe.init_opt_state(opt)
        losses, first_grads = [], None
        for _ in range(3):
            losses.append(float(pipe.train_step(batch)))
            if first_grads is None:
                first_grads = jax.tree.map(np.asarray, pipe.grads)
            state = pipe.apply_updates(opt, state, pipe.grads)
        return losses, first_grads

    base_losses, base_grads = run(1)
    int_losses, int_grads = run(2)
    np.testing.assert_allclose(int_losses, base_losses, rtol=1e-3, atol=1e-4)
    assert int_losses[-1] < int_losses[0]
    # Per-leaf relative L2 error: element-wise tolerances are dominated by
    # cancellation noise on near-zero entries; the norm criterion still
    # fails loudly (O(1) error) if the chunked backward computed the wrong
    # gradient. The extra chunk-boundary edges round activations at the
    # transfer dtype, so the bound matches the 5e-2 the fused-vs-pipeline
    # grad comparison above already accepts.
    for li in base_grads:
        for a, b in zip(jax.tree.leaves(int_grads[li]),
                        jax.tree.leaves(base_grads[li])):
            a = np.asarray(a, np.float32)
            b = np.asarray(b, np.float32)
            denom = max(float(np.linalg.norm(b)), 1e-8)
            rel = float(np.linalg.norm(a - b)) / denom
            assert rel < 5e-2, f"layer {li}: grad rel-L2 error {rel:.2e}"


@pytest.mark.parametrize("S,M", [(1, 1), (1, 4), (2, 2), (2, 6), (3, 4),
                                 (4, 4), (4, 8), (5, 7)])
def test_canonical_order_is_dependency_valid(S, M):
    """Multi-host deadlock-freedom rests on canonical_order being a valid
    total order of the 1F1B streams: every process executes it verbatim, so
    it must (a) contain every instruction exactly once, (b) respect FIFO
    order within each stage stream, and (c) place every SEND before the
    dependent compute and every producer before its SEND."""
    from oobleck_tpu.execution.pipeline import canonical_order
    from oobleck_tpu.execution.schedule import Op, all_instructions

    order = canonical_order(S, M)
    streams = all_instructions(S, M)
    assert len(order) == sum(len(s) for s in streams)

    # (b) per-stream FIFO
    from collections import Counter

    counts = Counter((ins.op, ins.stage, ins.microbatch) for ins in order)
    assert all(c == 1 for c in counts.values())
    for stream in streams:
        idxs = [order.index(ins) for ins in stream]
        assert idxs == sorted(idxs), "stream order violated"

    # (c) dataflow: replay the order and assert each op's inputs exist.
    acts, gacts, fwd_done, bwd_done = set(), set(), set(), set()
    for ins in order:
        key = (ins.stage, ins.microbatch)
        if ins.op == Op.FORWARD:
            if ins.stage > 0:
                assert key in acts, f"FORWARD before activation: {ins}"
            fwd_done.add(key)
        elif ins.op == Op.SEND_ACTIVATION:
            assert key in fwd_done, f"SEND before FORWARD: {ins}"
            acts.add((ins.stage + 1, ins.microbatch))
        elif ins.op == Op.BACKWARD:
            assert key in fwd_done
            if ins.stage < S - 1:
                assert key in gacts, f"BACKWARD before grad arrived: {ins}"
            bwd_done.add(key)
        elif ins.op == Op.SEND_GRAD:
            assert key in bwd_done, f"SEND_GRAD before BACKWARD: {ins}"
            gacts.add((ins.stage - 1, ins.microbatch))
    assert len(fwd_done) == S * M and len(bwd_done) == S * M


@pytest.mark.parametrize("S,M,v", [(2, 4, 2), (2, 4, 3), (3, 6, 2),
                                   (4, 4, 2)])
def test_canonical_order_interleaved_dependency_valid(S, M, v):
    """Same deadlock-freedom contract for the interleaved streams, keyed by
    virtual stage vs = chunk*S + stage: sends land before the dependent
    compute, producers before their sends, every unit exactly once."""
    from collections import Counter

    from oobleck_tpu.execution.pipeline import canonical_order
    from oobleck_tpu.execution.schedule import (
        send_activation_dest,
        send_grad_dest,
    )

    order = canonical_order(S, M, v)
    streams = all_instructions(S, M, v)
    assert len(order) == sum(len(s) for s in streams)
    counts = Counter((i.op, i.stage, i.microbatch, i.chunk) for i in order)
    assert all(c == 1 for c in counts.values())
    for stream in streams:
        idxs = [order.index(ins) for ins in stream]
        assert idxs == sorted(idxs), "stream order violated"

    acts, gacts, fwd_done, bwd_done = set(), set(), set(), set()
    for ins in order:
        vs = ins.chunk * S + ins.stage
        key = (vs, ins.microbatch)
        if ins.op == Op.FORWARD:
            if vs > 0:
                assert key in acts, f"FORWARD before activation: {ins}"
            fwd_done.add(key)
        elif ins.op == Op.SEND_ACTIVATION:
            assert key in fwd_done, f"SEND before FORWARD: {ins}"
            ds, dc = send_activation_dest(ins.stage, ins.chunk, S)
            acts.add((dc * S + ds, ins.microbatch))
        elif ins.op == Op.BACKWARD:
            assert key in fwd_done
            if vs < S * v - 1:
                assert key in gacts, f"BACKWARD before grad arrived: {ins}"
            bwd_done.add(key)
        elif ins.op == Op.SEND_GRAD:
            assert key in bwd_done, f"SEND_GRAD before BACKWARD: {ins}"
            ds, dc = send_grad_dest(ins.stage, ins.chunk, S)
            gacts.add((dc * S + ds, ins.microbatch))
    assert len(fwd_done) == S * v * M and len(bwd_done) == S * v * M


# --------------------------------------------------------------------- #
# the last virtual stage's forward is folded into its backward


FOLD_CASES = {
    # name: (layer splits, chips per stage, virtual stages)
    "S1-generic": ([(0, 6)], [1], 1),
    "S2-generic": ([(0, 3), (3, 6)], [1, 1], 1),
    "S2v2-generic": ([(0, 3), (3, 6)], [1, 1], 2),
    # Two chips a stage: fsdp shards the microbatch, so the stage programs
    # are the manual-collective shard_map flavour (st.ctx is not None).
    "S1-manual": ([(0, 6)], [2], 1),
    "S2-manual": ([(0, 3), (3, 6)], [2, 2], 1),
    "S2v2-manual": ([(0, 3), (3, 6)], [2, 2], 2),
}


def _dispatches():
    from oobleck_tpu.utils import metrics

    c = metrics.registry().counter("oobleck_pipeline_forward_dispatches_total")
    return {mode: c.value(mode=mode) for mode in ("run", "folded")}


@pytest.fixture(scope="module", params=list(FOLD_CASES))
def folded_step(request, model, batch, devices8):
    """One train_step of the case's pipeline with a counting wrapper on the
    last virtual stage's forward program, then one eval_step."""
    splits, chips, v = FOLD_CASES[request.param]
    template = make_template(splits, chips, chips_per_host=chips[0])
    pipe = _make_pipe(model, devices8, template, v)
    manual = request.param.endswith("manual")
    assert all((st.ctx is not None) == manual for st in pipe.stages)
    last_st, last_c = pipe.stages[-1], v - 1
    assert last_st.chunks[last_c][-1] == model.num_pipeline_layers - 1
    calls = {"n": 0}
    real_fwd = last_st.fwd[last_c]

    def counting_fwd(*args):
        calls["n"] += 1
        return real_fwd(*args)

    last_st.fwd[last_c] = counting_fwd
    before = _dispatches()
    loss = float(pipe.train_step(batch))
    after = _dispatches()
    fwd_calls_in_train = calls["n"]
    eval_loss = float(pipe.eval_step(batch))
    return {
        "pipe": pipe, "S": len(splits), "v": v, "loss": loss,
        "grads": jax.tree.map(np.asarray, pipe.grads),
        "op_times": dict(pipe.last_op_times),
        "dispatched": {k: after[k] - before[k] for k in after},
        "fwd_calls_in_train": fwd_calls_in_train,
        "fwd_calls_in_eval": calls["n"] - fwd_calls_in_train,
        "eval_loss": eval_loss,
    }


@pytest.fixture(scope="module")
def fused(model, batch):
    loss, grads = reference_loss_and_grads(model, batch)
    return float(loss), jax.tree.map(np.asarray, grads)


def test_folded_loss_matches_value_and_grad(folded_step, fused):
    assert folded_step["loss"] == pytest.approx(fused[0], rel=2e-2)


def test_folded_every_grad_leaf_matches_value_and_grad(folded_step, fused,
                                                       model):
    """Every layer's every leaf against jax.value_and_grad of the whole
    model on the same microbatches (relative L2, the bound the file's
    interleaved parity test uses)."""
    _, want = fused
    n = model.num_pipeline_layers
    got = folded_step["grads"]
    assert sorted(got) == list(range(n))
    for li in range(n):
        if li == 0:
            ref = want["embed"]
        elif li == n - 1:
            ref = want["head"]
        else:
            ref = jax.tree.map(lambda x, _i=li - 1: x[_i], want["blocks"])
        g_leaves, g_def = jax.tree.flatten(got[li])
        r_leaves, r_def = jax.tree.flatten(ref)
        assert g_def == r_def
        for a, b in zip(g_leaves, r_leaves):
            a = np.asarray(a, np.float32)
            b = np.asarray(b, np.float32)
            rel = float(np.linalg.norm(a - b)) / max(
                float(np.linalg.norm(b)), 1e-8)
            assert rel < 5e-2, f"layer {li}: grad rel-L2 error {rel:.2e}"


def test_last_stage_forward_never_runs_in_train_step(folded_step):
    assert folded_step["fwd_calls_in_train"] == 0
    # gpt2-tiny has no accuracy metric, so eval runs the forward-only
    # program of the last stage: once a microbatch.
    assert folded_step["fwd_calls_in_eval"] == NUM_MB


def test_forward_dispatch_counter(folded_step):
    S, v = folded_step["S"], folded_step["v"]
    assert folded_step["dispatched"] == {
        "folded": NUM_MB, "run": NUM_MB * (S * v - 1)}


def test_eval_step_loss_unchanged_by_fold(folded_step, fused):
    """eval_step runs the forward-only programs on the parameters the
    train_step left untouched: the same mean loss, from programs that
    share nothing with the folded backward."""
    assert folded_step["eval_loss"] == pytest.approx(
        folded_step["loss"], rel=1e-5)
    assert folded_step["eval_loss"] == pytest.approx(fused[0], rel=2e-2)


def test_last_op_times_keeps_f_and_b_for_every_chunk(folded_step):
    pipe, times = folded_step["pipe"], folded_step["op_times"]
    S, v = folded_step["S"], folded_step["v"]
    for st in pipe.stages:
        for c in range(len(st.chunks)):
            for kind in ("f", "b"):
                total, n = times[(st.stage_index, c, kind)]
                assert n == NUM_MB and total >= 0.0
    # The folded forward dispatched nothing, and says so.
    assert times[(S - 1, v - 1, "f")][0] == 0.0
    assert times[(S - 1, v - 1, "b")][0] > 0.0
    # The readers of these keys still get a bubble out of them.
    from oobleck_tpu.execution.schedule import Op, simulate_bubble

    def dur(inst):
        kind = "f" if inst.op is Op.FORWARD else "b"
        total, n = times[(inst.stage, inst.chunk, kind)]
        return total / n

    assert 0.0 <= simulate_bubble(S, NUM_MB, v, dur) < 1.0


# --------------------------------------------------------------------- #
# microbatch gradients accumulate inside the backward program


ACC_CASES = {
    # name: (layer splits, chips per stage, virtual stages)
    "S1": ([(0, 6)], [1], 1),
    "S2": ([(0, 3), (3, 6)], [1, 1], 1),
    "S2v2": ([(0, 3), (3, 6)], [1, 1], 2),
    # fsdp over two chips a stage: the sums are sharded like the parameters
    # and the gradients leave a shard_map before the add.
    "S2-manual": ([(0, 3), (3, 6)], [2, 2], 1),
}


def _accumulations():
    from oobleck_tpu.utils import metrics

    c = metrics.registry().counter("oobleck_pipeline_grad_accumulations_total")
    return {w: c.value(where=w) for w in ("backward", "zero_fill")}


@pytest.fixture(scope="module", params=list(ACC_CASES))
def accumulated(request, model, batch, devices8):
    """Two train_steps on the same batch and parameters. In the first,
    every BACKWARD's program also runs on a fresh zero sum, which gives that
    microbatch's own gradients (0 + g) from the very program under test."""
    import warnings

    splits, chips, v = ACC_CASES[request.param]
    template = make_template(splits, chips, chips_per_host=chips[0])
    pipe = _make_pipe(model, devices8, template, v)
    own: dict[tuple[int, ...], list] = {}   # chunk layers -> per microbatch

    def recording(st, c):
        real, layers = st.bwd[c], st.chunks[c]

        def bwd(params, acc, *rest):
            alone = real(params, st.zero[c](params), *rest)
            own.setdefault(layers, []).append(
                jax.tree.map(np.asarray, alone[-2]))
            return real(params, acc, *rest)

        return bwd

    real_bwds = {}
    for st in pipe.stages:
        for c in range(len(st.chunks)):
            real_bwds[(st.stage_index, c)] = st.bwd[c]
            st.bwd[c] = recording(st, c)
    before = _accumulations()
    with warnings.catch_warnings(record=True) as caught:
        warnings.simplefilter("always")
        pipe.train_step(batch)
    after_first = _accumulations()
    for st in pipe.stages:
        for c in range(len(st.chunks)):
            st.bwd[c] = real_bwds[(st.stage_index, c)]
    kept = pipe.grads          # held as the DP engine holds it
    first = jax.tree.map(np.asarray, kept)
    pipe.train_step(batch)
    after_second = _accumulations()
    return {
        "pipe": pipe, "chunks": len(splits) * v, "own": own, "first": first,
        "second": jax.tree.map(np.asarray, pipe.grads),
        "kept_after_second": jax.tree.map(np.asarray, kept),
        "counted": [{w: b[w] - a[w] for w in a} for a, b in (
            (before, after_first), (after_first, after_second))],
        "warnings": [str(w.message) for w in caught],
    }


def _close(a, b):
    # Float32 sums of the same numbers in the same order: the only room is
    # the compiler's (an add fused into a product may round once, not twice).
    np.testing.assert_allclose(a, b, rtol=2e-6, atol=1e-9)


def test_grads_are_the_ordered_sum_of_each_microbatchs_own(accumulated):
    own, got = accumulated["own"], accumulated["first"]
    assert sorted(li for layers in own for li in layers) == sorted(got)
    for layers, per_mb in own.items():
        assert len(per_mb) == NUM_MB
        total = per_mb[0]
        for g in per_mb[1:]:
            total = jax.tree.map(np.add, total, g)
        for li, want in zip(layers, total):
            jax.tree.map(_close, got[li], want)


def test_grads_match_the_separate_add_program(accumulated):
    """What the step gave before the sum moved into `bwd`: the first
    microbatch's gradients as they are, then one jitted tree add a
    microbatch."""
    separate_add = jax.jit(lambda a, b: jax.tree.map(jnp.add, a, b))
    got = accumulated["first"]
    for layers, per_mb in accumulated["own"].items():
        total = per_mb[0]
        for g in per_mb[1:]:
            total = separate_add(total, g)
        for li, want in zip(layers, total):
            jax.tree.map(_close, got[li], jax.tree.map(np.asarray, want))


def test_second_step_holds_only_its_own_gradients(accumulated):
    """Same batch, same parameters: the second step's sums start from
    zeros again, and what a reader kept of the first step is not touched
    (only a step's own running sum is ever donated)."""
    first, second = accumulated["first"], accumulated["second"]
    assert sorted(first) == sorted(second)
    for li in first:
        jax.tree.map(np.testing.assert_array_equal, second[li], first[li])
        jax.tree.map(np.testing.assert_array_equal,
                     accumulated["kept_after_second"][li], first[li])
    assert any(float(np.abs(leaf).max()) > 0
               for li in first for leaf in jax.tree.leaves(first[li]))


def test_grad_accumulation_counter(accumulated):
    """A step: every BACKWARD adds into its chunk's sum, and each chunk's
    sum is filled with zeros once. (The recording first step ran no extra
    instruction: its second program call is the test's own.)"""
    chunks = accumulated["chunks"]
    assert accumulated["counted"] == [
        {"backward": NUM_MB * chunks, "zero_fill": chunks}] * 2


def test_donated_sum_is_taken(accumulated):
    """The compiler reports a donated operand it could not alias to an
    output; the sum's tree, dtypes and shardings are the output's."""
    assert not [w for w in accumulated["warnings"] if "donat" in w.lower()]
    pipe = accumulated["pipe"]
    for st in pipe.stages:
        for layers in st.chunks:
            for li in layers:
                want = jax.tree.leaves(
                    st.param_shardings[li],
                    is_leaf=lambda x: hasattr(x, "mesh"))
                got = [g.sharding for g in jax.tree.leaves(pipe.grads[li])]
                assert all(g.is_equivalent_to(w, np.ndim(leaf)) for g, w, leaf
                           in zip(got, want, jax.tree.leaves(pipe.grads[li])))
