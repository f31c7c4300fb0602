"""Blockwise (flash) attention — Pallas TPU kernels, forward AND backward.

The reference has no custom kernels (all GPU compute goes through torch
modules); on TPU the attention inner loop is the one op worth hand-writing:
the naive path materializes the [S, S] score matrix in HBM, while these
kernels stream K/V blocks through VMEM with the online-softmax recurrence,
keeping HBM traffic linear in S in BOTH directions:

  forward:  online softmax, emits O and the row logsumexp (LSE, stored
            lane-broadcast [BH, S, 128] following the layout the TPU memory
            system wants for per-row scalars).
  backward: standard two-pass recompute —
              dq kernel   grid (BH, q_blocks, kv_blocks), kv innermost,
                          accumulates dq for one q block across kv blocks;
              dk/dv kernel grid (BH, kv_blocks, q_blocks), q innermost,
                          accumulates dk/dv for one kv block across q blocks.
            Each recomputes p = exp(s - lse) from the saved LSE — no [S, S]
            residual ever touches HBM.

Supports an additive attention bias ([H, S, S] — ALiBi for the Bloom family)
and bidirectional (non-causal) attention for encoder models. The bias is
treated as a constant (stop_gradient): for ALiBi it is position-only, so the
zero cotangent is exact; learned biases must use the XLA path.

Layout notes: head dim is padded to the 128-lane width and sequence to the
block size outside the kernels; zero padding is exact (padded q rows are
sliced off, padded k columns are causally masked or explicitly masked in the
non-causal case, and padded dO rows are zero so they contribute nothing to
dk/dv).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e9

BLOCK_Q = 128
BLOCK_K = 128
LANE = 128


def _block_relevant(qi, ki, causal: bool):
    """Whether kv block ki overlaps the causal support of q block qi."""
    if not causal:
        return True
    return ki * BLOCK_K <= qi * BLOCK_Q + (BLOCK_Q - 1)


def _scores(q, k, qi, ki, scale, bias_ref, slope_ref, *, causal: bool,
            kv_len: int):
    """[Bq, Bk] masked, scaled, biased f32 logits for one (q, kv) block pair.

    Operands stay in their native dtype (bf16 in production) so the MXU runs
    at full rate; only the accumulator is f32. ALiBi arrives as a per-head
    SLOPE scalar (slope_ref) and the bias block is generated in-kernel from
    the position iotas — no [H, S, S] bias buffer ever exists in HBM, the
    long-context memory hazard a materialized bias reintroduces.
    """
    s = jax.lax.dot_general(
        q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
    ) * scale
    if bias_ref is not None:
        s = s + bias_ref[0].astype(jnp.float32)
    q_pos = qi * BLOCK_Q + jax.lax.broadcasted_iota(jnp.int32, s.shape, 0)
    k_pos = ki * BLOCK_K + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
    if slope_ref is not None:
        # Identical to alibi_bias_from_slopes: -slope * (q - k) causal,
        # -slope * |q - k| bidirectional (the signed form would reward
        # future keys in the encoder case).
        dist = (q_pos - k_pos).astype(jnp.float32)
        if not causal:
            dist = jnp.abs(dist)
        s = s - slope_ref[0, 0, 0] * dist
    if causal:
        s = jnp.where(q_pos >= k_pos, s, NEG_INF)
    else:
        # Padded kv columns are not causally masked in the encoder form —
        # mask them explicitly so softmax never sees them.
        s = jnp.where(k_pos < kv_len, s, NEG_INF)
    return s


def _fwd_kernel(*refs, scale: float, blocks_k: int, causal: bool,
                has_bias: bool, has_slopes: bool, kv_len: int,
                emit_lse: bool):
    refs = list(refs)
    bias_ref = slope_ref = lse_ref = None
    q_ref, k_ref, v_ref = refs[:3]
    del refs[:3]
    if has_bias:
        bias_ref = refs.pop(0)
    if has_slopes:
        slope_ref = refs.pop(0)
    o_ref = refs.pop(0)
    if emit_lse:
        lse_ref = refs.pop(0)
    acc_ref, m_ref, l_ref = refs
    qi = pl.program_id(1)
    ki = pl.program_id(2)

    @pl.when(ki == 0)
    def _():
        acc_ref[:] = jnp.zeros_like(acc_ref)
        m_ref[:] = jnp.full_like(m_ref, NEG_INF)
        l_ref[:] = jnp.zeros_like(l_ref)

    # Fully-masked blocks contribute exactly zero — predicate away the MXU
    # work (the actual cost), ~halving causal FLOPs.
    @pl.when(_block_relevant(qi, ki, causal))
    def _():
        q = q_ref[0]                               # [Bq, D] native dtype
        k = k_ref[0]                               # [Bk, D]
        v = v_ref[0]                               # [Bk, D]
        s = _scores(q, k, qi, ki, scale, bias_ref, slope_ref,
                    causal=causal, kv_len=kv_len)

        m_prev = m_ref[:, :1]                      # [Bq, 1]
        m_cur = jnp.max(s, axis=-1, keepdims=True)
        m_new = jnp.maximum(m_prev, m_cur)
        p = jnp.exp(s - m_new)                     # [Bq, Bk] f32
        correction = jnp.exp(m_prev - m_new)       # [Bq, 1]

        l_new = l_ref[:, :1] * correction + jnp.sum(p, axis=-1, keepdims=True)
        acc_ref[:] = acc_ref[:] * correction + jax.lax.dot_general(
            p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        m_ref[:] = jnp.broadcast_to(m_new, m_ref.shape)
        l_ref[:] = jnp.broadcast_to(l_new, l_ref.shape)

    @pl.when(ki == blocks_k - 1)
    def _():
        # Padded-out rows can have l == 0; guard the divide/log.
        l = jnp.maximum(l_ref[:, :1], 1e-30)
        o_ref[0] = (acc_ref[:] / l).astype(o_ref.dtype)
        if emit_lse:
            lse_ref[0] = jnp.broadcast_to(m_ref[:, :1] + jnp.log(l),
                                          lse_ref.shape[1:])


def _dq_kernel(*refs, scale: float, blocks_k: int, causal: bool,
               has_bias: bool, has_slopes: bool, kv_len: int):
    refs = list(refs)
    q_ref, k_ref, v_ref, o_ref, do_ref, lse_ref = refs[:6]
    del refs[:6]
    bias_ref = refs.pop(0) if has_bias else None
    slope_ref = refs.pop(0) if has_slopes else None
    dq_ref, dq_acc = refs
    qi = pl.program_id(1)
    ki = pl.program_id(2)

    @pl.when(ki == 0)
    def _():
        dq_acc[:] = jnp.zeros_like(dq_acc)

    @pl.when(_block_relevant(qi, ki, causal))
    def _():
        q, k, v = q_ref[0], k_ref[0], v_ref[0]     # native dtype (MXU-rate dots)
        do, o = do_ref[0], o_ref[0]
        s = _scores(q, k, qi, ki, scale, bias_ref, slope_ref,
                    causal=causal, kv_len=kv_len)
        p = jnp.exp(s - lse_ref[0][:, :1])         # [Bq, Bk] f32
        dp = jax.lax.dot_general(                  # dO @ V^T  [Bq, Bk]
            do, v, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
        )
        delta = jnp.sum(do.astype(jnp.float32) * o.astype(jnp.float32),
                        axis=-1, keepdims=True)    # [Bq, 1]
        ds = p * (dp - delta)                      # dlogits  [Bq, Bk] f32
        dq_acc[:] = dq_acc[:] + jax.lax.dot_general(
            ds.astype(k.dtype), k, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        ) * scale

    @pl.when(ki == blocks_k - 1)
    def _():
        dq_ref[0] = dq_acc[:].astype(dq_ref.dtype)


def _dkv_kernel(*refs, scale: float, blocks_q: int, causal: bool,
                has_bias: bool, has_slopes: bool, kv_len: int):
    refs = list(refs)
    q_ref, k_ref, v_ref, o_ref, do_ref, lse_ref = refs[:6]
    del refs[:6]
    bias_ref = refs.pop(0) if has_bias else None
    slope_ref = refs.pop(0) if has_slopes else None
    dk_ref, dv_ref, dk_acc, dv_acc = refs
    ki = pl.program_id(1)   # kv block is the OUTER sequential axis here
    qi = pl.program_id(2)

    @pl.when(qi == 0)
    def _():
        dk_acc[:] = jnp.zeros_like(dk_acc)
        dv_acc[:] = jnp.zeros_like(dv_acc)

    @pl.when(_block_relevant(qi, ki, causal))
    def _():
        q, k, v = q_ref[0], k_ref[0], v_ref[0]     # native dtype (MXU-rate dots)
        do, o = do_ref[0], o_ref[0]
        s = _scores(q, k, qi, ki, scale, bias_ref, slope_ref,
                    causal=causal, kv_len=kv_len)
        p = jnp.exp(s - lse_ref[0][:, :1])         # [Bq, Bk] f32
        dv_acc[:] = dv_acc[:] + jax.lax.dot_general(   # P^T @ dO  [Bk, D]
            p.astype(do.dtype), do, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        dp = jax.lax.dot_general(
            do, v, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
        )
        delta = jnp.sum(do.astype(jnp.float32) * o.astype(jnp.float32),
                        axis=-1, keepdims=True)
        ds = p * (dp - delta)
        dk_acc[:] = dk_acc[:] + jax.lax.dot_general(   # dS^T @ Q  [Bk, D]
            ds.astype(q.dtype), q, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        ) * scale

    @pl.when(qi == blocks_q - 1)
    def _():
        dk_ref[0] = dk_acc[:].astype(dk_ref.dtype)
        dv_ref[0] = dv_acc[:].astype(dv_ref.dtype)


def _pad_inputs(q, k, v, bias):
    """Pad head dim to the lane width and seq to the block size."""
    b, h, s_len, d = q.shape
    d_pad = (LANE - d % LANE) % LANE
    s_pad = (BLOCK_Q - s_len % BLOCK_Q) % BLOCK_Q
    if d_pad or s_pad:
        pad = ((0, 0), (0, 0), (0, s_pad), (0, d_pad))
        q, k, v = (jnp.pad(x, pad) for x in (q, k, v))
        if bias is not None:
            bias = jnp.pad(bias, ((0, 0), (0, s_pad), (0, s_pad)))
    bh = b * h
    sp, dp = q.shape[2], q.shape[3]
    q, k, v = (x.reshape(bh, sp, dp) for x in (q, k, v))
    return q, k, v, bias, (b, h, s_len, d, bh, sp, dp)


def _canon_bias(bias, h, s_len):
    """Canonicalize a broadcastable bias to [H, S, S] (ALiBi form)."""
    if bias is None:
        return None
    bias = jnp.asarray(bias)
    if bias.ndim == 4:
        if bias.shape[0] != 1:
            raise ValueError(
                "flash kernel supports batch-independent bias only "
                f"(got shape {bias.shape}); use the XLA path")
        bias = bias[0]
    return jnp.broadcast_to(bias, (h, s_len, s_len))


def _interpret() -> bool:
    # Interpreter mode off-TPU: tests validate kernel math on the CPU mesh.
    from oobleck_tpu.ops import attention

    return not attention._pallas_ok()


def _out_struct(shape, dtype, *operands) -> jax.ShapeDtypeStruct:
    """A `pallas_call` out_shape entry varying over every mesh axis any
    operand varies over: inside a `check_vma=True` shard_map (the fused
    step's three phases, the MPMD stage programs) Pallas refuses an output
    whose varying-manual-axes it would have to guess."""
    vma = frozenset().union(*(jax.typeof(x).vma for x in operands))
    return jax.ShapeDtypeStruct(shape, dtype, vma=vma)


def _bias_specs(has_bias: bool, h: int, outer_is_q: bool):
    if not has_bias:
        return []
    if outer_is_q:
        index = lambda b_, qi, ki: (b_ % h, qi, ki)
    else:
        index = lambda b_, ki, qi: (b_ % h, qi, ki)
    return [pl.BlockSpec((1, BLOCK_Q, BLOCK_K), index)]


def _slope_specs(has_slopes: bool, h: int):
    # One f32 scalar per head, shaped [H, 1, 1]; the grid's batch*head axis
    # indexes its head row (same map under both backward grids — the block
    # index ignores qi/ki).
    if not has_slopes:
        return []
    return [pl.BlockSpec((1, 1, 1), lambda b_, i, j: (b_ % h, 0, 0))]


def _flash_forward(q, k, v, bias, slopes, scale: float, causal: bool,
                   emit_lse: bool = True):
    bias = _canon_bias(bias, q.shape[1], q.shape[2])
    q, k, v, bias, (b, h, s_len, d, bh, sp, dp) = _pad_inputs(q, k, v, bias)
    blocks_q = sp // BLOCK_Q
    blocks_k = sp // BLOCK_K
    has_bias = bias is not None
    has_slopes = slopes is not None
    if has_slopes:
        slopes = jnp.asarray(slopes, jnp.float32).reshape(h, 1, 1)

    kernel = functools.partial(
        _fwd_kernel, scale=scale, blocks_k=blocks_k, causal=causal,
        has_bias=has_bias, has_slopes=has_slopes, kv_len=s_len,
        emit_lse=emit_lse)
    qkv_specs = [
        pl.BlockSpec((1, BLOCK_Q, dp), lambda b_, qi, ki: (b_, qi, 0)),
        pl.BlockSpec((1, BLOCK_K, dp), lambda b_, qi, ki: (b_, ki, 0)),
        pl.BlockSpec((1, BLOCK_K, dp), lambda b_, qi, ki: (b_, ki, 0)),
    ]
    o_spec = pl.BlockSpec((1, BLOCK_Q, dp), lambda b_, qi, ki: (b_, qi, 0))
    operands = ([q, k, v] + ([bias] if has_bias else [])
                + ([slopes] if has_slopes else []))
    o_shape = _out_struct((bh, sp, dp), q.dtype, *operands)
    if emit_lse:
        # The LSE residual is only needed when a backward pass will run;
        # forward-only (eval) calls skip the extra [BH, S, 128] HBM write.
        out_shape = (o_shape,
                     _out_struct((bh, sp, LANE), jnp.float32, *operands))
        out_specs = (o_spec, pl.BlockSpec((1, BLOCK_Q, LANE),
                                          lambda b_, qi, ki: (b_, qi, 0)))
    else:
        out_shape, out_specs = o_shape, o_spec
    result = pl.pallas_call(
        kernel,
        out_shape=out_shape,
        grid=(bh, blocks_q, blocks_k),
        in_specs=(qkv_specs + _bias_specs(has_bias, h, outer_is_q=True)
                  + _slope_specs(has_slopes, h)),
        out_specs=out_specs,
        scratch_shapes=[
            pltpu.VMEM((BLOCK_Q, dp), jnp.float32),
            pltpu.VMEM((BLOCK_Q, LANE), jnp.float32),
            pltpu.VMEM((BLOCK_Q, LANE), jnp.float32),
        ],
        interpret=_interpret(),
        name="flash_fwd",
    )(*operands)

    out, lse = result if emit_lse else (result, None)
    out = out.reshape(b, h, sp, dp)[:, :, :s_len, :d]
    return out, lse


def _flash_backward(q, k, v, bias, slopes, out, lse, g, scale: float,
                    causal: bool):
    bias = _canon_bias(bias, q.shape[1], q.shape[2])
    dtype_in = (q.dtype, k.dtype, v.dtype)
    qp, kp, vp, bias, (b, h, s_len, d, bh, sp, dp) = _pad_inputs(q, k, v, bias)
    # Pad O / dO the same way (their padded rows are zero, so padded-row
    # contributions to dk/dv vanish and padded delta rows are zero).
    op, gp, *_ = _pad_inputs(out, g, g, None)[:2]
    blocks_q = sp // BLOCK_Q
    blocks_k = sp // BLOCK_K
    has_bias = bias is not None
    has_slopes = slopes is not None
    if has_slopes:
        slopes = jnp.asarray(slopes, jnp.float32).reshape(h, 1, 1)
    interpret = _interpret()

    common = ([qp, kp, vp, op, gp, lse] + ([bias] if has_bias else [])
              + ([slopes] if has_slopes else []))
    grad_shape = _out_struct((bh, sp, dp), jnp.float32, *common)

    def qspec(inner_kv: bool):
        # index maps for (q-like, kv-like, lse) inputs under the two grids
        if inner_kv:  # grid (bh, qi, ki)
            qix = lambda b_, qi, ki: (b_, qi, 0)
            kix = lambda b_, qi, ki: (b_, ki, 0)
        else:         # grid (bh, ki, qi)
            qix = lambda b_, ki, qi: (b_, qi, 0)
            kix = lambda b_, ki, qi: (b_, ki, 0)
        return [
            pl.BlockSpec((1, BLOCK_Q, dp), qix),     # q
            pl.BlockSpec((1, BLOCK_K, dp), kix),     # k
            pl.BlockSpec((1, BLOCK_K, dp), kix),     # v
            pl.BlockSpec((1, BLOCK_Q, dp), qix),     # o
            pl.BlockSpec((1, BLOCK_Q, dp), qix),     # do
            pl.BlockSpec((1, BLOCK_Q, LANE), qix),   # lse
        ]

    dq = pl.pallas_call(
        functools.partial(_dq_kernel, scale=scale, blocks_k=blocks_k,
                          causal=causal, has_bias=has_bias,
                          has_slopes=has_slopes, kv_len=s_len),
        out_shape=grad_shape,
        grid=(bh, blocks_q, blocks_k),
        in_specs=(qspec(inner_kv=True)
                  + _bias_specs(has_bias, h, outer_is_q=True)
                  + _slope_specs(has_slopes, h)),
        out_specs=pl.BlockSpec((1, BLOCK_Q, dp), lambda b_, qi, ki: (b_, qi, 0)),
        scratch_shapes=[pltpu.VMEM((BLOCK_Q, dp), jnp.float32)],
        interpret=interpret,
        name="flash_bwd_dq",
    )(*common)

    dk, dv = pl.pallas_call(
        functools.partial(_dkv_kernel, scale=scale, blocks_q=blocks_q,
                          causal=causal, has_bias=has_bias,
                          has_slopes=has_slopes, kv_len=s_len),
        out_shape=(grad_shape, grad_shape),
        grid=(bh, blocks_k, blocks_q),
        in_specs=(qspec(inner_kv=False)
                  + _bias_specs(has_bias, h, outer_is_q=False)
                  + _slope_specs(has_slopes, h)),
        out_specs=(
            pl.BlockSpec((1, BLOCK_K, dp), lambda b_, ki, qi: (b_, ki, 0)),
            pl.BlockSpec((1, BLOCK_K, dp), lambda b_, ki, qi: (b_, ki, 0)),
        ),
        scratch_shapes=[
            pltpu.VMEM((BLOCK_K, dp), jnp.float32),
            pltpu.VMEM((BLOCK_K, dp), jnp.float32),
        ],
        interpret=interpret,
        name="flash_bwd_dkv",
    )(*common)

    def unpad(x, dt):
        return x.reshape(b, h, sp, dp)[:, :, :s_len, :d].astype(dt)

    return unpad(dq, dtype_in[0]), unpad(dk, dtype_in[1]), unpad(dv, dtype_in[2])


@functools.partial(jax.custom_vjp, nondiff_argnums=(5, 6))
def _flash(q, k, v, bias, slopes, scale, causal):
    out, _ = _flash_forward(q, k, v, bias, slopes, scale, causal,
                            emit_lse=False)
    return out


def _flash_fwd(q, k, v, bias, slopes, scale, causal):
    out, lse = _flash_forward(q, k, v, bias, slopes, scale, causal)
    return out, (q, k, v, bias, slopes, out, lse)


def _flash_bwd(scale, causal, res, g):
    q, k, v, bias, slopes, out, lse = res
    dq, dk, dv = _flash_backward(q, k, v, bias, slopes, out, lse, g, scale,
                                 causal)
    # Bias/slopes are constants (ALiBi): position-only, so the zero
    # cotangent is exact. Learned biases must use the XLA path
    # (attention.py routes them).
    dbias = None if bias is None else jnp.zeros_like(bias)
    dslopes = None if slopes is None else jnp.zeros_like(slopes)
    return dq, dk, dv, dbias, dslopes


_flash.defvjp(_flash_fwd, _flash_bwd)


def flash_attention(q: jax.Array, k: jax.Array, v: jax.Array, *,
                    scale: float | None = None,
                    bias: jax.Array | None = None,
                    alibi_slopes: jax.Array | None = None,
                    causal: bool = True) -> jax.Array:
    """Flash attention. [B, H, S, D] -> [B, H, S, D].

    `bias` is an additive [H, S, S] (or broadcastable) logit bias, treated as
    a constant under differentiation (exact for ALiBi). Prefer
    `alibi_slopes` ([H] f32) for ALiBi: the bias block is generated
    IN-KERNEL from the slopes and position iotas, so no O(H S^2) bias
    buffer exists in HBM at any sequence length. `causal=False` gives the
    bidirectional encoder form.
    """
    if scale is None:
        scale = q.shape[-1] ** -0.5
    if q.shape[-2] != k.shape[-2]:
        raise ValueError(
            "flash kernel is self-attention only (seq_q == seq_k); "
            "use the XLA path for cross-attention")
    if bias is not None and alibi_slopes is not None:
        raise ValueError("pass bias OR alibi_slopes, not both")
    if bias is not None:
        bias = jax.lax.stop_gradient(bias)
    if alibi_slopes is not None:
        if alibi_slopes.shape != (q.shape[1],):
            raise ValueError(
                f"alibi_slopes must be [H]={q.shape[1]}, got "
                f"{alibi_slopes.shape}")
        alibi_slopes = jax.lax.stop_gradient(alibi_slopes)
    return _flash(q, k, v, bias, alibi_slopes, scale, causal)
