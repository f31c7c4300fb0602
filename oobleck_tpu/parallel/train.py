"""The fused SPMD train step: pipeline + tensor + fsdp + data parallelism in
one jitted program built from three full-manual shard_map phases.

TPU-native replacement for the reference's hot loop
(/root/reference/oobleck/execution/pipeline.py:458-487 — a Python interpreter
dispatching per-instruction NCCL ops): here the whole schedule is *compiled*.

  Phase A  embed: vocab-parallel lookup, microbatches sharded over `stage`
           (every device embeds a distinct slice — no redundant work).
  Phase B  pipeline: circular collective-permute schedule over `stage` —
           each tick, stage 0 ingests a microbatch, every stage applies its
           block slice (Megatron-TP + fsdp gathers inside), `lax.ppermute`
           shifts activations to the next stage. XLA differentiates through
           the permute, so the backward pipeline comes from `jax.grad`, with
           a checkpoint of each tick (`ops.checkpoint_layer`: the tick's
           input and flash's O and LSE are kept) standing in for 1F1B's
           memory discipline.
  Phase C  head/loss: vocab-parallel cross-entropy, microbatches again
           sharded over `stage` so the lm-head matmul uses all devices.

Design rules learned the hard way (enforced throughout):
  * every mesh axis is manual — no GSPMD/auto axes inside shard_map;
  * collectives are issued unconditionally and identically on all devices —
    never inside a `lax.cond` on a device-varying predicate (XLA matches
    collectives by program position; divergence deadlocks the rendezvous);
  * gradient cross-device reductions are not hand-written on the DEFAULT
    path: they fall out of the shard_map in_spec transposes (replicated
    input -> psum of cotangents, all_gather -> psum_scatter), which is
    exactly the DP/fsdp/TP grad sync the reference builds NCCL process-group
    grids for (engine.py:363-412).
  * the OVERLAP path (build_train_step(..., overlap=OverlapConfig(enabled=
    True))) inverts that last rule: the whole step is ONE check_rep=False
    shard_map with value_and_grad INSIDE and the grad sync written out —
    bucketed ppermute rings over the data axis, psums over the other
    non-spec axes, Megatron f / identity-backward g inside the model
    (ShardCtx.explicit_bwd) for the tensor axis — so collectives can be
    bucketed, interleaved, and latency-hidden behind compute. See
    parallel/overlap.py.
"""

from __future__ import annotations

from dataclasses import replace as _dc_replace
from typing import Any, NamedTuple

import jax
import jax.numpy as jnp
import numpy as np
import optax
from jax import lax
from jax.sharding import NamedSharding, PartitionSpec as P

from oobleck_tpu.models.gpt import ShardCtx
from oobleck_tpu.ops import checkpoint_layer
from oobleck_tpu.parallel import overlap as ovl
from oobleck_tpu.parallel.collectives import pvary_to
from oobleck_tpu.parallel.mesh import (
    ALL_AXES,
    AXIS_DATA,
    AXIS_FSDP,
    AXIS_SEQ,
    AXIS_STAGE,
    AXIS_TENSOR,
)


class TrainState(NamedTuple):
    params: Any
    opt_state: Any
    step: jax.Array


class StepMetrics(NamedTuple):
    loss: jax.Array
    grad_norm: jax.Array


class Optimizer(NamedTuple):
    """An `optax.GradientTransformation` that says what it was built from:
    `make_optimizer`'s arguments, all its closures read, and so the key of
    its compiled update (`execution/pipeline.optimizer_update_program`)."""

    init: Any
    update: Any
    built_from: tuple


def freeze_leaves(inner: optax.GradientTransformation,
                  names: tuple[str, ...]) -> optax.GradientTransformation:
    """`inner`, with every leaf whose dict key is in `names` left out: its
    update is zero (no step, no weight decay, nothing in the clipped norm)
    and the state `inner` keeps for it is EMPTY (zero-size arrays where the
    leaf's mirrors would be). The state's tree still mirrors the
    parameters', so `optax.tree_map_params` and the engine's placement and
    checkpoint code need no case for it, which `optax.masked` does."""
    names = frozenset(names)

    def hide(tree):
        return jax.tree_util.tree_map_with_path(
            lambda path, x: (jnp.zeros((0,), x.dtype)
                             if getattr(path[-1], "key", None) in names
                             else x), tree)

    def init(params):
        return inner.init(hide(params))

    def update(grads, state, params=None):
        updates, state = inner.update(
            hide(grads), state, None if params is None else hide(params))
        updates = jax.tree.map(
            lambda u, g: jnp.zeros_like(g) if u.shape != g.shape else u,
            updates, grads)
        return updates, state

    return optax.GradientTransformation(init, update)


def make_optimizer(
    *,
    learning_rate: float = 1e-4,
    warmup_steps: int = 10,
    weight_decay: float = 0.01,
    max_grad_norm: float = 1.0,
    frozen: tuple[str, ...] = (),
) -> Optimizer:
    """AdamW + linear-warmup LR + global-norm clipping.

    Matches the reference's optimizer stack (fused AdamW + WarmupLR,
    /root/reference/oobleck/execution/pipeline.py:117-127) with clipping
    added (reference leaves grads unclipped). `frozen` names parameter
    leaves that are never trained (a model's `frozen_param_names`).
    """
    def schedule(step):
        return learning_rate * jnp.minimum(1.0, (step + 1) / max(warmup_steps, 1))

    optimizer = optax.chain(
        optax.clip_by_global_norm(max_grad_norm),
        optax.adamw(schedule, b1=0.9, b2=0.999, weight_decay=weight_decay),
    )
    if frozen:
        optimizer = freeze_leaves(optimizer, tuple(frozen))
    return Optimizer(*optimizer, built_from=(
        learning_rate, warmup_steps, weight_decay, max_grad_norm,
        tuple(sorted(frozen))))


def state_partition_specs(model, optimizer) -> TrainState:
    """PartitionSpec pytree for the full TrainState (params + opt mirrors)."""
    param_specs = model.param_specs(stacked=True)
    params_shape = jax.eval_shape(lambda: model.init_params(jax.random.PRNGKey(0)))
    opt_shape = jax.eval_shape(optimizer.init, params_shape)
    opt_specs = optax.tree_map_params(
        optimizer,
        lambda _leaf, spec: spec,
        opt_shape,
        param_specs,
        transform_non_params=lambda _leaf: P(),
        is_leaf=lambda x: isinstance(x, P),
    )
    return TrainState(params=param_specs, opt_state=opt_specs, step=P())


def _to_shardings(mesh, spec_tree):
    return jax.tree.map(
        lambda spec: NamedSharding(mesh, spec),
        spec_tree,
        is_leaf=lambda x: isinstance(x, P),
    )


def shift_targets(tokens_mb: np.ndarray) -> np.ndarray:
    """Next-token targets for [num_mb, mb, seq] tokens, shifted on the host.

    The shift must see the GLOBAL sequence (targets[t] = token[t+1] crosses
    seq-shard boundaries), so it happens here on the unsharded numpy batch
    rather than inside the jitted step — see the note in loss_fn."""
    return np.concatenate(
        [tokens_mb[:, :, 1:], np.zeros_like(tokens_mb[:, :, :1])], axis=-1
    )


def count_params(model) -> int:
    """Total parameter count via eval_shape (no device allocation)."""
    shapes = jax.eval_shape(lambda: model.init_params(jax.random.PRNGKey(0)))
    return sum(int(np.prod(l.shape)) for l in jax.tree.leaves(shapes))


def estimate_flops_per_token(n_params: int, seq_len: int, *,
                             num_layers: int = 0,
                             hidden_size: int = 0) -> float:
    """Training FLOPs per token: 6N for the matmuls (fwd+bwd) plus the
    causal-attention term. Read by the engine's per-step MFU gauge. Of a
    model that repeats layers `n_params` is its APPLIED parameters
    (`models/base.applied_param_count`: a repeated layer's and the exits'
    once a pass) and `num_layers` its attention layers times its passes:
    6 x what it holds would read such a model low by the passes."""
    return 6.0 * n_params + 6.0 * (num_layers * hidden_size * seq_len)


# Peak dense bf16 FLOP/s per chip, keyed by `jax.Device.device_kind`
# (Google Cloud TPU documentation, per-generation system architecture pages:
# v4 275, v5e 197, v5p 459, v6e 918 TFLOP/s).
PEAK_BF16_FLOPS = {
    "TPU v4": 275e12,
    "TPU v5 lite": 197e12,
    "TPU v5e": 197e12,
    "TPU v5": 459e12,
    "TPU v5p": 459e12,
    "TPU v6 lite": 918e12,
    "TPU v6e": 918e12,
}


def peak_flops(device_kind: str) -> float:
    """Peak bf16 FLOP/s of one chip of this kind. A kind that is not in the
    table is an error, not a default: an MFU against a guessed peak is a
    wrong number. Callers on a backend with no such peak (CPU) do not ask."""
    try:
        return PEAK_BF16_FLOPS[device_kind]
    except KeyError:
        raise KeyError(
            f"no peak FLOP/s known for device kind {device_kind!r}; add it "
            "to PEAK_BF16_FLOPS with its source") from None


def mfu_estimate(tokens_per_sec: float, flops_per_token: float,
                 n_chips: int, peak_flops_per_chip: float | None
                 ) -> float | None:
    """Model FLOPs utilization from the planner's FLOPs model: achieved
    training FLOP/s over the fleet's peak. One definition shared by the
    engine's per-step gauge and the goodput ledger. None when peak is
    unknown (CPU) or the inputs are degenerate."""
    if (peak_flops_per_chip is None or peak_flops_per_chip <= 0
            or n_chips <= 0 or tokens_per_sec <= 0):
        return None
    return (flops_per_token * tokens_per_sec) / (
        n_chips * peak_flops_per_chip)


def _overlap_loss_and_grads(model, mesh, specs, ctx: ShardCtx, cfg,
                            *, num_mb: int, remat: bool):
    """Overlap-mode core: ONE check_rep=False shard_map over every mesh axis
    computing (loss, synced grads) with value_and_grad INSIDE.

    Boundary collectives that the three-phase default path gets from its
    in/out specs are written out: an all_gather over `stage` reconstructs
    the stage-replicated activation block after the stage-sharded embed, a
    psum over `stage` broadcasts the last stage's pipeline outputs (zeros
    elsewhere — each stage then slices its own head chunk, so the psum
    transpose correctly accumulates every stage's head cotangent), and the
    per-leaf grad sync goes through overlap.sync_grads (bucketed ppermute
    rings over data; psums over the other non-spec axes; tensor completed
    by the model's explicit_bwd f/g — see the regime note in collectives.py).
    """
    S = mesh.shape[AXIS_STAGE]
    axis_sizes = dict(mesh.shape)
    ctx_u = _dc_replace(ctx, explicit_bwd=True)
    ctx_nofsdp = _dc_replace(ctx_u, fsdp=None)
    tok_stage = P(AXIS_STAGE, (AXIS_DATA, AXIS_FSDP), AXIS_SEQ)
    chunk = num_mb // S
    block_specs_1 = ovl.unstacked_specs(specs["blocks"])
    prefetch = cfg.prefetch_fsdp and axis_sizes[AXIS_FSDP] > 1
    db_sends = cfg.double_buffer_sends and S > 1
    lead = 2 * (S - 1) if db_sends else S - 1
    n_ticks = num_mb + lead
    perm = [(i, (i + 1) % S) for i in range(S)]

    def body(params, tokens_loc, targets_loc):
        stage_idx = lax.axis_index(AXIS_STAGE)
        is_first = stage_idx == 0
        is_last = stage_idx == S - 1
        mb_local, seq_local = tokens_loc.shape[1], tokens_loc.shape[2]
        seq_global = seq_local * axis_sizes[AXIS_SEQ]
        valid = num_mb * (mb_local * axis_sizes[AXIS_DATA]
                          * axis_sizes[AXIS_FSDP]) * (seq_global - 1)
        # Local shard of the next-token mask (last global position invalid).
        pos = lax.axis_index(AXIS_SEQ) * seq_local + jnp.arange(seq_local)
        mask_loc = jnp.broadcast_to(
            (pos < seq_global - 1).astype(jnp.float32), tokens_loc.shape)

        def apply_stage(blocks_local, h):
            if prefetch:
                return ovl.prefetched_block_scan(
                    lambda bp, hh: model.apply_block(bp, hh, ctx_nofsdp),
                    lambda bp: ovl.fsdp_gather_block(
                        bp, block_specs_1, AXIS_FSDP),
                    blocks_local, h, model.config.num_layers // S)

            def bodyb(h, bp):
                return model.apply_block(bp, h, ctx_u), None

            h, _ = lax.scan(bodyb, h, blocks_local)
            return h

        def local_loss(params):
            x_loc = model.embed(params["embed"], tokens_loc, ctx_u)
            x = (lax.all_gather(x_loc, AXIS_STAGE, axis=0, tiled=True)
                 if S > 1 else x_loc)
            blocks_local = params["blocks"]

            def tick_plain(carry, t):
                state, outputs = carry
                inp = lax.dynamic_index_in_dim(
                    x, jnp.minimum(t, num_mb - 1), 0, keepdims=False)
                cur = jnp.where(is_first, inp, state)
                out = apply_stage(blocks_local, cur)
                out_idx = t - lead
                upd = lax.dynamic_update_index_in_dim(
                    outputs, out, jnp.maximum(out_idx, 0), 0)
                outputs = jnp.where(is_last & (out_idx >= 0), upd, outputs)
                state = lax.ppermute(out, AXIS_STAGE, perm)
                return (state, outputs), None

            def tick_db(carry, t):
                # The ppermute issued at tick t is consumed at tick t+2:
                # microbatch m reaches stage s at tick m + 2s, and the send
                # of m rides under the compute of m+1 (one extra in-flight
                # buffer, S-1 extra warmup ticks).
                ready, in_flight, outputs = carry
                inp = lax.dynamic_index_in_dim(
                    x, jnp.minimum(t, num_mb - 1), 0, keepdims=False)
                cur = jnp.where(is_first, inp, ready)
                out = apply_stage(blocks_local, cur)
                out_idx = t - lead
                upd = lax.dynamic_update_index_in_dim(
                    outputs, out, jnp.maximum(out_idx, 0), 0)
                outputs = jnp.where(is_last & (out_idx >= 0), upd, outputs)
                return (in_flight, lax.ppermute(out, AXIS_STAGE, perm),
                        outputs), None

            tick_fn = tick_db if db_sends else tick_plain
            tick = checkpoint_layer(tick_fn) if remat else tick_fn
            zero = jnp.zeros_like(x[0])
            init = ((zero, zero, jnp.zeros_like(x)) if db_sends
                    else (zero, jnp.zeros_like(x)))
            carry, _ = lax.scan(tick, init, jnp.arange(n_ticks))
            outputs = carry[-1]
            ys = lax.psum(outputs, AXIS_STAGE) if S > 1 else outputs
            ys_chunk = lax.dynamic_slice_in_dim(
                ys, stage_idx * chunk, chunk, axis=0)
            loss_sum = model.head_loss_shifted(
                params["head"], ys_chunk, targets_loc, mask_loc, ctx_u)
            return loss_sum / valid

        loss_local, grads = jax.value_and_grad(local_loss)(params)
        grads = ovl.sync_grads(
            grads, specs, axis_sizes,
            data_impl=cfg.grad_sync, bucket_bytes=cfg.bucket_bytes)
        loss = lax.psum(
            loss_local, (AXIS_STAGE, AXIS_DATA, AXIS_FSDP, AXIS_SEQ))
        return loss, grads

    return jax.shard_map(
        body, mesh=mesh, in_specs=(specs, tok_stage, tok_stage),
        out_specs=(P(), specs), axis_names=set(ALL_AXES), check_vma=False,
    )


def build_train_step(model, mesh, *, num_microbatches: int, optimizer=None,
                     remat: bool | None = None,
                     overlap: "ovl.OverlapConfig | None" = None):
    """Build (init_fn, step_fn) for the fused SPMD path.

    init_fn(rng) -> TrainState, sharded over `mesh`.
    step_fn(state, tokens) -> (TrainState, StepMetrics); tokens [batch, seq]
    with batch = num_microbatches * microbatch_size (microbatch split is
    internal). Fully jit-compiled, state donated.

    overlap: an enabled OverlapConfig switches grad computation to the
    explicit-collective overlap path (see _overlap_loss_and_grads);
    None/disabled keeps the default three-phase path unchanged.
    """
    if optimizer is None:
        optimizer = make_optimizer()
    if remat is None:
        remat = model.config.remat
    S = mesh.shape[AXIS_STAGE]
    tp = mesh.shape[AXIS_TENSOR]
    sp = mesh.shape[AXIS_SEQ]
    num_mb = num_microbatches
    if model.config.num_layers % S != 0:
        raise ValueError(
            f"num_layers={model.config.num_layers} not divisible by stage={S}"
        )
    if model.config.num_heads % tp != 0:
        raise ValueError(
            f"num_heads={model.config.num_heads} not divisible by tensor={tp}"
        )
    if num_mb % S != 0:
        raise ValueError(
            f"num_microbatches={num_mb} not divisible by stage={S}: the embed "
            "and head phases shard microbatches over the stage axis"
        )
    ctx = ShardCtx(tensor=AXIS_TENSOR, fsdp=AXIS_FSDP,
                   seq=AXIS_SEQ if sp > 1 else None)
    specs = model.param_specs(stacked=True)
    batch_shards = mesh.shape[AXIS_DATA] * mesh.shape[AXIS_FSDP]

    # Batch layouts: microbatch index over `stage` (phases A/C) or replicated
    # (phase B input); sample dim over (data, fsdp) and sequence dim over
    # `seq` (ring attention) everywhere.
    tok_stage = P(AXIS_STAGE, (AXIS_DATA, AXIS_FSDP), AXIS_SEQ)
    x_stage = P(AXIS_STAGE, (AXIS_DATA, AXIS_FSDP), AXIS_SEQ, None)
    x_repl = P(None, (AXIS_DATA, AXIS_FSDP), AXIS_SEQ, None)

    def embed_fn(embed_params, tokens_loc):
        return model.embed(embed_params, tokens_loc, ctx)

    def pipeline_fn(blocks_local, x):
        """Circular pipeline over the stage axis. x: [num_mb, mb, seq, E]
        (stage-replicated); returns [1, num_mb, mb, seq, E] whose global
        stage-stacked form is sliced at S-1 by the caller."""
        stage_idx = lax.axis_index(AXIS_STAGE)
        is_first = stage_idx == 0
        is_last = stage_idx == S - 1
        perm = [(i, (i + 1) % S) for i in range(S)]

        def apply_stage(h):
            def body(h, bp):
                return model.apply_block(bp, h, ctx), None

            h, _ = lax.scan(body, h, blocks_local)
            return h

        def tick_fn(carry, t):
            state, outputs = carry
            inp = lax.dynamic_index_in_dim(
                x, jnp.minimum(t, num_mb - 1), 0, keepdims=False
            )
            cur = jnp.where(is_first, inp, state)
            out = apply_stage(cur)
            out_idx = t - (S - 1)
            upd = lax.dynamic_update_index_in_dim(
                outputs, out, jnp.maximum(out_idx, 0), 0
            )
            outputs = jnp.where(is_last & (out_idx >= 0), upd, outputs)
            state = lax.ppermute(out, AXIS_STAGE, perm)
            return (state, outputs), None

        tick = checkpoint_layer(tick_fn) if remat else tick_fn
        vary = (AXIS_DATA, AXIS_FSDP, AXIS_STAGE)
        state0 = pvary_to(jnp.zeros_like(x[0]), vary)
        outputs0 = pvary_to(jnp.zeros_like(x), vary)
        (_, outputs), _ = lax.scan(
            tick, (state0, outputs0), jnp.arange(num_mb + S - 1)
        )
        return outputs[None]

    def head_fn(head_params, ys_loc, targets_loc, mask_loc):
        # Pre-shifted targets: the next-token shift crosses seq-shard
        # boundaries, so the caller shifts globally (see wrapped_step).
        loss_sum = model.head_loss_shifted(
            head_params, ys_loc, targets_loc, mask_loc, ctx
        )
        return lax.psum(loss_sum, (AXIS_STAGE, AXIS_DATA, AXIS_FSDP, AXIS_SEQ))

    embed_sm = jax.shard_map(
        embed_fn, mesh=mesh, in_specs=(specs["embed"], tok_stage),
        out_specs=x_stage, axis_names=set(ALL_AXES),
    )
    pipe_sm = jax.shard_map(
        pipeline_fn, mesh=mesh, in_specs=(specs["blocks"], x_repl),
        out_specs=P(AXIS_STAGE, None, (AXIS_DATA, AXIS_FSDP), AXIS_SEQ, None),
        axis_names=set(ALL_AXES),
    )
    head_sm = jax.shard_map(
        head_fn, mesh=mesh,
        in_specs=(specs["head"], x_stage, tok_stage, tok_stage),
        out_specs=P(), axis_names=set(ALL_AXES),
    )

    def loss_fn(params, tokens_mb, targets_mb):
        # targets_mb is the globally next-token-shifted copy of tokens_mb,
        # computed on the HOST (see _shift_targets).  Computing the shift
        # inside jit looks equivalent — tokens are still logically global —
        # but when the shifted array then feeds a shard_map in_spec that
        # shards the sequence dim, the GSPMD partitioner on older jax
        # (0.4.x) shifts each seq shard locally without the cross-shard
        # halo exchange, silently corrupting the target at every shard
        # boundary.  The host shift is equally global and version-proof.
        seq = tokens_mb.shape[2]
        mask_mb = jnp.broadcast_to(
            (jnp.arange(seq) < seq - 1).astype(jnp.float32), tokens_mb.shape
        )
        x = embed_sm(params["embed"], tokens_mb)
        ys = pipe_sm(params["blocks"], x)[S - 1]
        loss_sum = head_sm(params["head"], ys, targets_mb, mask_mb)
        valid = num_mb * tokens_mb.shape[1] * (seq - 1)
        return loss_sum / valid

    overlap = overlap if (overlap is not None and overlap.enabled) else None
    if overlap is not None:
        ovl_sm = _overlap_loss_and_grads(
            model, mesh, specs, ctx, overlap, num_mb=num_mb, remat=remat)

        def loss_and_grads(params, tokens_mb, targets_mb):
            return ovl_sm(params, tokens_mb, targets_mb)
    else:
        def loss_and_grads(params, tokens_mb, targets_mb):
            return jax.value_and_grad(loss_fn)(params, tokens_mb, targets_mb)

    def step_fn(state: TrainState, tokens_mb, targets_mb):
        loss, grads = loss_and_grads(state.params, tokens_mb, targets_mb)
        updates, new_opt = optimizer.update(grads, state.opt_state, state.params)
        new_params = optax.apply_updates(state.params, updates)
        metrics = StepMetrics(loss=loss, grad_norm=optax.global_norm(grads))
        return TrainState(new_params, new_opt, state.step + 1), metrics

    def init_fn(rng):
        params = model.init_params(rng)
        return TrainState(params, optimizer.init(params), jnp.zeros((), jnp.int32))

    state_specs = state_partition_specs(model, optimizer)
    state_shardings = _to_shardings(mesh, state_specs)
    token_sharding = NamedSharding(mesh, P(None, (AXIS_DATA, AXIS_FSDP), AXIS_SEQ))

    jit_init = jax.jit(init_fn, out_shardings=state_shardings)
    jit_step = jax.jit(
        step_fn,
        in_shardings=(state_shardings, token_sharding, token_sharding),
        out_shardings=(state_shardings, None),
        donate_argnums=(0,),
    )

    def _global_arrays(*host_arrays):
        if jax.process_count() > 1:
            # Multi-process SPMD: every host computes the same global batch
            # (same dataset + sampler seed); build the global array from the
            # host-local copy — numpy inputs cannot carry non-trivial
            # shardings across processes.
            return tuple(
                jax.make_array_from_callback(
                    a.shape, token_sharding, lambda idx, a=a: a[idx]
                )
                for a in host_arrays
            )
        return host_arrays

    def prepare_tokens(tokens):
        """Everything wrapped_step does before dispatching the compiled
        program: reshape, host-side target shift, globalize, and (single
        process) an async device_put onto the token sharding. Safe to run
        on a background thread (the DeviceStager), so by the time the
        train loop calls the step the inputs are already in flight to the
        devices."""
        tokens = np.asarray(tokens)  # oobleck: allow[OBL002] -- input is host memory already
        b, seq = tokens.shape
        assert b % num_mb == 0, f"batch {b} not divisible by {num_mb} microbatches"
        assert seq % sp == 0, f"seq {seq} not divisible by seq-parallel {sp}"
        tokens_mb = tokens.reshape(num_mb, b // num_mb, seq)
        tokens_mb, targets_mb = _global_arrays(tokens_mb,
                                               shift_targets(tokens_mb))
        if jax.process_count() == 1:
            # numpy inputs would otherwise be copied host->device inside
            # the jit dispatch; device_put here starts the transfer early
            # and does not block on its completion.
            tokens_mb, targets_mb = jax.device_put(
                [tokens_mb, targets_mb], [token_sharding, token_sharding]
            )
        return tokens_mb, targets_mb

    def wrapped_step(state, tokens=None, prepared=None):
        if prepared is None:
            prepared = prepare_tokens(tokens)
        tokens_mb, targets_mb = prepared
        return jit_step(state, tokens_mb, targets_mb)

    wrapped_step.jitted = jit_step
    wrapped_step.loss_fn = loss_fn
    wrapped_step.globalize = _global_arrays
    wrapped_step.prepare = prepare_tokens
    wrapped_step.state_shardings = state_shardings
    wrapped_step.token_sharding = token_sharding
    wrapped_step.overlap = overlap
    # (loss, grads) probe for parity tests — the same core the step uses,
    # without the optimizer update or donation.
    wrapped_step.loss_and_grads = jax.jit(
        loss_and_grads,
        in_shardings=(state_shardings.params, token_sharding, token_sharding),
    )
    return jit_init, wrapped_step
