"""Manual-mode collective helpers for full-manual shard_map programs.

The fused train step runs with *every* mesh axis manual (scaling-book style):
tensor parallelism, fsdp parameter gathering, and the Megatron f/g conjugate
pair are written out explicitly here instead of relying on GSPMD propagation.

TPU-native replacement for the reference's NCCL primitive usage
(/root/reference/oobleck/execution/layer.py:127-217 — manual FSDP
all_gather/reduce-scatter hooks; engine.py:404-412 — DP allreduce): the same
operations expressed as XLA collectives over mesh axes.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import PartitionSpec as P


# NOTE on Megatron `f` (identity forward / psum backward): the default fused
# path takes value_and_grad OUTSIDE shard_map, where the in/out-spec transposes
# insert the backward psum at each replicated->varying boundary themselves —
# an explicit custom_vjp psum there DOUBLE-counts the cotangent (verified
# numerically: grads off by ~2x with it, exact without), so that path writes
# only the forward reduction `g`. The OVERLAP path (parallel/overlap.py) is the
# opposite regime: value_and_grad runs INSIDE one check_rep=False shard_map, no
# spec transposes run, and the transpose of a bare lax.psum is psum (cotangents
# of axis-invariant values get multiplied by the axis size — measured 2e+01
# grad error). There every forward tensor-psum must be `psum_idbwd` and every
# replicated->column-parallel entry needs an explicit `megatron_f`; the
# `identity_bwd` flags below switch the shared building blocks between the two
# regimes.


def psum_idbwd(x, axis: str):
    """psum forward, identity backward (the stop_gradient trick).

    For explicit-backward bodies (grad taken inside shard_map) where the
    cotangent is already axis-invariant and a real psum transpose would
    multiply it by the axis size.
    """
    return x + lax.stop_gradient(lax.psum(x, axis) - x)


def megatron_f(x, axis: str):
    """Megatron `f`: identity forward, psum-over-`axis` backward.

    Placed at each replicated->column-parallel entry in explicit-backward
    bodies: each tensor rank's backward produces only its own partial input
    cotangent, and `f` sums them into the full one.
    """

    @jax.custom_vjp
    def f(v):
        return v

    def fwd(v):
        return v, None

    def bwd(_, g):
        return (lax.psum(g, axis),)

    f.defvjp(fwd, bwd)
    return f(x)


def reduce_from_tp(x, axis: str, *, identity_bwd: bool = False):
    """Megatron `g`: psum forward (row-parallel output), identity backward.

    identity_bwd=True makes the identity backward explicit (overlap path);
    False relies on the shard_map spec transpose (default path).
    """
    if identity_bwd:
        return psum_idbwd(x, axis)
    return lax.psum(x, axis)


def unshard_fsdp(param: jax.Array, axis: str, dim: int) -> jax.Array:
    """All-gather an fsdp-sharded parameter along `dim` for use.

    The AD transpose of all_gather is psum_scatter, so gradients come back
    already reduced *and* sharded — the ZeRO-3 reduce-scatter for free
    (cf. reference layer.py:213-217 doing this by hand with NCCL).
    """
    return lax.all_gather(param, axis, axis=dim, tiled=True)


def vocab_parallel_logits_loss(
    local_logits: jax.Array,
    targets: jax.Array,
    vocab_offset: jax.Array | int,
    tensor_axis: str | None,
    *,
    identity_bwd: bool = False,
) -> jax.Array:
    """Cross-entropy over vocab-sharded logits without materializing the full
    vocab dimension on any device (Megatron-style three-psum construction).

    local_logits: [..., seq, V_local] f32, this rank's vocab shard.
    targets:      [..., seq] global token ids.
    Returns per-position loss [..., seq].
    """
    local_logits = local_logits.astype(jnp.float32)
    vlocal = local_logits.shape[-1]
    # max for stability
    local_max = jnp.max(local_logits, axis=-1)
    if tensor_axis is not None:
        gmax = lax.pmax(lax.stop_gradient(local_max), tensor_axis)
    else:
        gmax = local_max
    # The max shift is for stability only; its gradient contribution cancels.
    gmax = lax.stop_gradient(gmax)
    shifted = local_logits - gmax[..., None]
    sumexp = jnp.sum(jnp.exp(shifted), axis=-1)
    # gold logit: only the owning rank contributes
    local_ids = targets - vocab_offset
    in_range = (local_ids >= 0) & (local_ids < vlocal)
    safe_ids = jnp.clip(local_ids, 0, vlocal - 1)
    gold = jnp.take_along_axis(shifted, safe_ids[..., None], axis=-1)[..., 0]
    gold = jnp.where(in_range, gold, 0.0)
    if tensor_axis is not None:
        reduce = psum_idbwd if identity_bwd else lax.psum
        sumexp = reduce(sumexp, tensor_axis)
        gold = reduce(gold, tensor_axis)
    return jnp.log(sumexp) - gold


def vocab_parallel_embed(
    wte_local: jax.Array,
    tokens: jax.Array,
    vocab_offset: jax.Array | int,
    tensor_axis: str | None,
    *,
    identity_bwd: bool = False,
) -> jax.Array:
    """Embedding lookup over a vocab-sharded table: masked local gather + psum.

    identity_bwd: the residual-stream cotangent arriving here in explicit-
    backward bodies is already tensor-summed (every downstream tensor-parallel
    branch is guarded by a `megatron_f`), so the psum's backward must be
    identity — each rank scatters the full row cotangent into only the rows
    its shard owns.
    """
    vlocal = wte_local.shape[0]
    local_ids = tokens - vocab_offset
    in_range = (local_ids >= 0) & (local_ids < vlocal)
    safe_ids = jnp.clip(local_ids, 0, vlocal - 1)
    out = wte_local[safe_ids]
    out = jnp.where(in_range[..., None], out, 0.0)
    if tensor_axis is not None:
        out = psum_idbwd(out, tensor_axis) if identity_bwd else lax.psum(out, tensor_axis)
    return out


def pvary_to(x, axes: tuple[str, ...]):
    """pcast `x` to be varying over exactly the axes in `axes` it isn't yet.

    lax.cond requires both branches to have identical varying-manual-axes
    types; this normalizes a branch output (or pytree) to a superset target.
    """
    def one(v):
        have = jax.typeof(v).vma
        missing = tuple(a for a in axes if a not in have)
        return lax.pcast(v, missing, to="varying") if missing else v

    return jax.tree.map(one, x)
