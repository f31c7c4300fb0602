"""Blockwise (flash) attention — Pallas TPU kernels, forward AND backward.

The reference has no custom kernels (all GPU compute goes through torch
modules); on TPU the attention inner loop is the one op worth hand-writing:
the naive path materializes the [S, S] score matrix in HBM, while these
kernels stream K/V blocks through VMEM with the online-softmax recurrence,
keeping HBM traffic linear in S in BOTH directions:

  forward:  online softmax, emits O and the row logsumexp (LSE, stored
            lane-broadcast [BH, S, 128] following the layout the TPU memory
            system wants for per-row scalars).
  backward: ONE kernel in kv-major order. A block pair recomputes
            p = exp(s - lse) from the saved LSE and dS from it once, and
            the pair's five products follow: dv and dk accumulate for one kv
            block across the q blocks of its column; dq of the WHOLE head
            accumulates in VMEM ([S, D] f32, a q block's rows added to once
            a column) and is written when the head's steps end. No [S, S]
            residual and no unreduced dq ever touches HBM. The call asks
            for the VMEM its shape needs (`vmem_limit_bytes`: the resident
            dq beside the default scoped limit); a head whose dq would not
            fit (`MAX_RESIDENT_DQ`: 65536 positions of 128 in bf16) is
            refused by that shape test, where the call is traced.

Geometry. Both kernels run on a grid of (batch * head, step), one step
per (q block, kv block) PAIR THE MASK LEAVES ANYTHING OF: `_live_pairs`
lists them once per shape (three small int32 tables, prefetched to SMEM,
which the block index maps read), so a pair the causal mask empties costs
neither a grid step nor a K/V fetch. The steps of one accumulation
(a q block's row for the forward, a kv block's column for dk/dv) adjoin;
the tables' flag says which step opens and which closes it. Block sizes
come from `choose_tiles`, a pure function of the sequence length: 512 x 512
where the sequence allows (a step then carries about a microsecond of MXU
work; the fixed 128 x 128 of before carried 0.04 us under 0.4 us of step
overhead), 128 x 128 for a 128-token prompt. There is no option for them.

A sliding window (`window`: query i sees key j iff 0 <= i - j < window,
causal calls only) is the same two kernels over a shorter table: `_live_pairs`
also drops the pairs that lie wholly behind the window, so the band's
pairs alone cost a step, and `_scores` masks the window's edge in the
select it makes on every live pair anyway. At 16384 positions and
512 x 512 blocks the causal table has 528 steps a head and a window of
4096 leaves 252 of them (108 of 136 at 8192). Such calls go out under
`WINDOW`'s names: a reader of `%flash_fwd.` counts a causal half.

Supports an additive attention bias ([H, S, S] — ALiBi for the Bloom family,
blocked by the same tile) and bidirectional (non-causal) attention for
encoder models. The bias is treated as a constant (stop_gradient): for ALiBi
it is position-only, so the zero cotangent is exact; learned biases must use
the XLA path.

Layout notes: head dim and sequence are padded to the 128-lane width outside
the kernels, and the blocks divide the padded sequence, so padding never
reaches a whole 128-row block. Zero padding is exact (padded q rows are
sliced off, padded k columns are causally masked or explicitly masked in the
non-causal case, and padded dO rows are zero so they contribute nothing to
dk/dv). Gradients leave the kernels in the operands' dtype, rounded once
from the f32 accumulators.
"""

from __future__ import annotations

import functools
from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.ad_checkpoint import checkpoint_name
from jax.experimental import pallas as pl

from oobleck_tpu.ops import kernel
from oobleck_tpu.ops.kernel import LANE, SCOPED_VMEM

NEG_INF = -1e9

# The widest block of queries or keys a grid step holds, and the most
# [Bq, Bk] logits of one block pair. 512 x 512 x 128 is about a microsecond
# of MXU work: enough for a step to cost its matmuls and not its
# bookkeeping (PERF.md section 6, PR 28).
MAX_BLOCK = 512
MAX_PAIR = MAX_BLOCK * MAX_BLOCK

# Bits of a grid step's flag: the step opens, closes an accumulation.
FIRST, LAST = 1, 2

# One step's blocks and a pair's [Bq, Bk] f32 temporaries fit in
# `SCOPED_VMEM` at every tile `choose_tiles` returns, so the forward states
# no limit; the backward asks for that much beside the head's dq it keeps
# resident.
# The most a head's resident dq may take: half a v5e's 128 MiB of VMEM.
MAX_RESIDENT_DQ = 64 << 20


class KernelNames(NamedTuple):
    """What a device trace calls the two kernels of one attention (a TPU
    trace names a kernel by its `pallas_call`'s `name=` alone)."""
    fwd: str
    bwd: str


PLAIN = KernelNames("flash_fwd", "flash_bwd_dqkv")
# Latent attention's calls (`latent_flash_attention`): the same kernels at
# scores wider than the values, under names of their own, so that a reader
# of `%flash_fwd.` never counts them at one width.
LATENT = KernelNames("flash_mla_fwd", "flash_mla_bwd_dqkv")
# Calls with a sliding window: the same kernels over the band's pairs.
WINDOW = KernelNames("flash_swa_fwd", "flash_swa_bwd_dqkv")
# Differential attention's calls (`differential_flash_attention`): two a
# layer, each a softmax of 64-wide scores (padded to the lane) over values
# twice as wide; the band's under the second pair.
DIFF = KernelNames("flash_diff_fwd", "flash_diff_bwd_dqkv")
DIFF_WINDOW = KernelNames("flash_diff_swa_fwd", "flash_diff_swa_bwd_dqkv")


# The forward rule's names for the kernel's two outputs, O and the row
# logsumexp: the residuals that only a second kernel call could give back.
RESIDUAL_NAMES = ("flash_out", "flash_lse")


class Tiles(NamedTuple):
    seq: int       # the sequence as the kernels see it: padded to the lane
    block_q: int   # rows of Q (and O, dO, LSE) per block
    block_k: int   # rows of K and V per block


@functools.cache
def choose_tiles(seq_len: int) -> Tiles:
    """The kernels' geometry: a pure function of the call's shape, the
    same on every start. The sequence is padded to the lane width and no
    further, and both blocks divide it, so a short prompt stays one
    128-row block and 640 rows stay 640. Among the divisors: the largest
    block of queries up to MAX_BLOCK, then the largest block of keys that
    keeps a pair within MAX_PAIR."""
    seq = -(-seq_len // LANE) * LANE
    n = seq // LANE
    divisors = [LANE * m for m in range(1, n + 1) if n % m == 0]
    block_q = max(d for d in divisors if d <= MAX_BLOCK)
    block_k = max(d for d in divisors if d * block_q <= MAX_PAIR)
    return Tiles(seq, block_q, block_k)


@functools.cache
def _live_pairs(t: Tiles, causal: bool, q_major: bool,
                window: int | None = None):
    """The grid's second axis: one step per (q block, kv block) pair that
    the mask leaves anything of, and no step for the others. Returns
    three int32 tables indexed by step: the q block, the kv block, and the
    step's flag. `q_major` orders the steps of one q block together
    (the forward: FIRST and LAST bracket a q block's accumulation);
    otherwise those of one kv block (the backward: dk/dv). Built once per
    shape, from Python ints."""
    def live(qi: int, ki: int) -> bool:
        if not causal:
            return True
        # the block's first key is at or before its last query
        if ki * t.block_k >= (qi + 1) * t.block_q:
            return False
        # ... and its last key inside the window of the block's first query
        return (window is None
                or qi * t.block_q - ((ki + 1) * t.block_k - 1) < window)

    pairs = [(qi, ki)
             for qi in range(t.seq // t.block_q)
             for ki in range(t.seq // t.block_k) if live(qi, ki)]
    major = (lambda p: p[0]) if q_major else (lambda p: p[1])
    pairs.sort(key=lambda p: p if q_major else p[::-1])
    flags = []
    for i, p in enumerate(pairs):
        first = i == 0 or major(pairs[i - 1]) != major(p)
        last = i == len(pairs) - 1 or major(pairs[i + 1]) != major(p)
        flags.append((FIRST if first else 0) | (LAST if last else 0))
    table = lambda xs: np.asarray(xs, np.int32)
    return (table([p[0] for p in pairs]), table([p[1] for p in pairs]),
            table(flags))


def _scores(q, k, qi, ki, t: Tiles, scale, bias_ref, slope_ref, *,
            causal: bool, kv_len: int, window: int | None = None):
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
        s = s + bias_ref[...].astype(jnp.float32)
    padded = kv_len < t.seq
    if slope_ref is None and not causal and not padded:
        return s
    q_pos = qi * t.block_q + jax.lax.broadcasted_iota(jnp.int32, s.shape, 0)
    k_pos = ki * t.block_k + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
    if slope_ref is not None:
        # Identical to alibi_bias_from_slopes: -slope * (q - k) causal,
        # -slope * |q - k| bidirectional (the signed form would reward
        # future keys in the encoder case).
        dist = (q_pos - k_pos).astype(jnp.float32)
        if not causal:
            dist = jnp.abs(dist)
        s = s - slope_ref[...] * dist               # [1, 1] * [Bq, Bk]
    # Every live pair is masked, not only those that cross the mask's edge:
    # the compare and select hide under the exponentials (PERF.md, PR 28).
    if causal:
        seen = q_pos >= k_pos
        if window is not None:
            # A row whose keys of this pair all lie behind the window meets
            # its diagonal pair later in the same accumulation (a row's
            # steps ascend in k), which rescales what this one added to
            # nothing.
            seen = seen & (q_pos - k_pos < window)
        s = jnp.where(seen, s, NEG_INF)
    elif padded:
        # Padded kv columns are not causally masked in the encoder form —
        # mask them explicitly so softmax never sees them.
        s = jnp.where(k_pos < kv_len, s, NEG_INF)
    return s


def _fwd_kernel(qi, ki, flag, *refs, scale: float, tiles: Tiles,
                causal: bool, has_bias: bool, has_slopes: bool, kv_len: int,
                window: int | None, emit_lse: bool):
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

    @pl.when(flag & FIRST != 0)
    def _():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    q = q_ref[...]                                 # [Bq, D] native dtype
    k = k_ref[...]                                 # [Bk, D]
    v = v_ref[...]                                 # [Bk, D]
    s = _scores(q, k, qi, ki, tiles, scale, bias_ref, slope_ref,
                causal=causal, kv_len=kv_len, window=window)

    # m and l stay lane-broadcast [Bq, 128] from scratch to scratch: a row's
    # scalar meets the [Bq, Bk] logits and the [Bq, D] accumulator by tiling
    # whole vregs. Taken as [Bq, 1] columns they cost the forward as much
    # again in lane broadcasts (0.95 against 0.50 ms a call, PERF.md, PR 28).
    m_prev = m_ref[...]
    m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
    p = jnp.exp(s - jnp.tile(m_new, (1, s.shape[1] // LANE)))   # [Bq, Bk] f32
    correction = jnp.exp(m_prev - m_new)           # [Bq, 128]
    l_ref[...] = l_ref[...] * correction + jnp.sum(p, axis=-1, keepdims=True)
    m_ref[...] = m_new
    acc_ref[...] = (
        acc_ref[...] * jnp.tile(correction, (1, acc_ref.shape[1] // LANE))
        + jax.lax.dot_general(
            p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32))

    @pl.when(flag & LAST != 0)
    def _():
        # Padded-out rows can have l == 0; guard the divide/log.
        l = jnp.maximum(l_ref[:, :1], 1e-30)
        o_ref[...] = (acc_ref[...] / l).astype(o_ref.dtype)
        if emit_lse:
            lse_ref[...] = jnp.broadcast_to(m_ref[:, :1] + jnp.log(l),
                                            lse_ref.shape)


def _bwd_kernel(qi, ki, flag, *refs, scale: float, tiles: Tiles,
                causal: bool, has_bias: bool, has_slopes: bool, kv_len: int,
                window: int | None):
    """dq, dk and dv of one block pair, in kv-major order: P and dS are
    computed once and feed all three. dk and dv accumulate over a kv
    block's column, whose steps adjoin. A q block comes back once a column,
    so dq of the WHOLE head accumulates in `dq_acc` ([S, D] f32, resident
    for the head's steps) and leaves at the head's last step. A q block
    meets its kv blocks in ascending order, as a q-major pass would."""
    refs = list(refs)
    q_ref, k_ref, v_ref, o_ref, do_ref, lse_ref = refs[:6]
    del refs[:6]
    bias_ref = refs.pop(0) if has_bias else None
    slope_ref = refs.pop(0) if has_slopes else None
    dq_ref, dk_ref, dv_ref, dq_acc, dk_acc, dv_acc = refs
    block_q = tiles.block_q

    def q_rows(block):
        return pl.ds(pl.multiple_of(block * block_q, block_q), block_q)

    def each_q_block(fn):
        # A loop, not one [S, D] expression: the kernel's size must not
        # follow the sequence (2048 vregs at 16384 x 128).
        def step(block, carry):
            fn(q_rows(block))
            return carry
        jax.lax.fori_loop(0, tiles.seq // block_q, step, 0)

    # The first column opens the head, the last closes it: both exist under
    # every mask (the diagonal pair is always live).
    @pl.when((flag & FIRST != 0) & (ki == 0))
    def _():
        def zero(rows):
            dq_acc[rows, :] = jnp.zeros((block_q, dq_acc.shape[1]),
                                        jnp.float32)
        each_q_block(zero)

    @pl.when(flag & FIRST != 0)
    def _():
        dk_acc[...] = jnp.zeros_like(dk_acc)
        dv_acc[...] = jnp.zeros_like(dv_acc)

    q, k, v = q_ref[...], k_ref[...], v_ref[...]   # native dtype (MXU-rate dots)
    do, o = do_ref[...], o_ref[...]
    s = _scores(q, k, qi, ki, tiles, scale, bias_ref, slope_ref,
                causal=causal, kv_len=kv_len, window=window)
    # The LSE block is lane-broadcast [Bq, 128]: tiled, not re-broadcast.
    p = jnp.exp(s - jnp.tile(lse_ref[...], (1, s.shape[1] // LANE)))  # [Bq, Bk]
    dv_acc[...] = dv_acc[...] + jax.lax.dot_general(   # P^T @ dO  [Bk, D]
        p.astype(do.dtype), do, (((0,), (0,)), ((), ())),
        preferred_element_type=jnp.float32,
    )
    dp = jax.lax.dot_general(                      # dO @ V^T  [Bq, Bk]
        do, v, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
    )
    delta = jnp.sum(do.astype(jnp.float32) * o.astype(jnp.float32),
                    axis=-1, keepdims=True)        # [Bq, 1]
    ds = (p * (dp - delta)).astype(q.dtype)        # dlogits  [Bq, Bk]
    dk_acc[...] = dk_acc[...] + jax.lax.dot_general(   # dS^T @ Q  [Bk, D]
        ds, q, (((0,), (0,)), ((), ())), preferred_element_type=jnp.float32,
    )
    rows = q_rows(qi)
    dq_acc[rows, :] = dq_acc[rows, :] + jax.lax.dot_general(  # dS @ K [Bq, D]
        ds, k, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32,
    )

    @pl.when(flag & LAST != 0)
    def _():
        dk_ref[...] = (dk_acc[...] * scale).astype(dk_ref.dtype)
        dv_ref[...] = dv_acc[...].astype(dv_ref.dtype)

    @pl.when((flag & LAST != 0) & (ki == tiles.seq // tiles.block_k - 1))
    def _():
        def write(rows):
            dq_ref[rows, :] = (dq_acc[rows, :] * scale).astype(dq_ref.dtype)
        each_q_block(write)


def _pad_inputs(q, k, v, bias):
    """Pad head dims and sequence to the lane width. `v` (and with it O and
    dO) has its own width: where it is narrower than q and k (latent
    attention: 192-wide scores over 128-wide values) it is not padded to
    theirs."""
    b, h, s_len, d = q.shape
    dv = v.shape[-1]
    s_pad = (LANE - s_len % LANE) % LANE

    def pad(x):
        d_pad = (LANE - x.shape[-1] % LANE) % LANE
        if d_pad or s_pad:
            x = jnp.pad(x, ((0, 0), (0, 0), (0, s_pad), (0, d_pad)))
        return x.reshape(b * h, x.shape[2], x.shape[3])

    q, k, v = pad(q), pad(k), pad(v)
    if bias is not None and s_pad:
        bias = jnp.pad(bias, ((0, 0), (0, s_pad), (0, s_pad)))
    return q, k, v, bias, (b, h, s_len, d, dv, b * h, q.shape[1], q.shape[2],
                           v.shape[2])


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


def _call(body, name: str, pairs, operands, *, in_specs, out_shape,
          out_specs, scratch, vmem_limit_bytes: int | None = None, **statics):
    """One `pallas_call` over the live pairs: grid (batch * head, step);
    each step looks its pair up in the prefetched tables of `_live_pairs`
    and runs `body(qi, ki, flag, *refs, **statics)` on it."""
    def pair(step, q_of, k_of, flag_of, *refs):
        body(q_of[step], k_of[step], flag_of[step], *refs, **statics)

    return kernel.sequential_call(
        pair, name, operands, in_specs, out_shape, out_specs,
        grid=(operands[0].shape[0], len(pairs[0])), scratch=scratch,
        prefetch=pairs, vmem_limit_bytes=vmem_limit_bytes,
        count=functools.partial(_count_call, name, len(pairs[0]),
                                statics["window"]))


# Block index maps: the grid is (batch * head, step) and the step's q and
# kv block come from the prefetched tables of `_live_pairs`.
def _q_rows(rows: int, width: int):
    return pl.BlockSpec((None, rows, width),
                        lambda b_, s, q_of, k_of, flag_of: (b_, q_of[s], 0))


def _k_rows(rows: int, width: int):
    return pl.BlockSpec((None, rows, width),
                        lambda b_, s, q_of, k_of, flag_of: (b_, k_of[s], 0))


def _head_rows(rows: int, width: int):
    # A head's whole [S, width]: the block does not move along the steps.
    return pl.BlockSpec((None, rows, width),
                        lambda b_, s, q_of, k_of, flag_of: (b_, 0, 0))


def _bias_specs(has_bias: bool, h: int, t: Tiles):
    if not has_bias:
        return []
    return [pl.BlockSpec(
        (None, t.block_q, t.block_k),
        lambda b_, s, q_of, k_of, flag_of: (b_ % h, q_of[s], k_of[s]))]


def _slope_specs(has_slopes: bool, h: int):
    # One f32 scalar per head, shaped [H, 1, 1]; the grid's batch*head axis
    # indexes its head row.
    if not has_slopes:
        return []
    return [pl.BlockSpec((None, 1, 1),
                         lambda b_, s, q_of, k_of, flag_of: (b_ % h, 0, 0))]


def _flash_forward(q, k, v, bias, slopes, scale: float, causal: bool,
                   names: KernelNames, window: int | None = None,
                   emit_lse: bool = True):
    bias = _canon_bias(bias, q.shape[1], q.shape[2])
    q, k, v, bias, (b, h, s_len, _, dv, bh, sp, dp, dvp) = _pad_inputs(
        q, k, v, bias)
    has_bias = bias is not None
    has_slopes = slopes is not None
    t = choose_tiles(s_len)
    if has_slopes:
        slopes = jnp.asarray(slopes, jnp.float32).reshape(h, 1, 1)

    operands = ([q, k, v] + ([bias] if has_bias else [])
                + ([slopes] if has_slopes else []))
    o_shape = kernel.out_struct((bh, sp, dvp), q.dtype, *operands)
    o_spec = _q_rows(t.block_q, dvp)
    if emit_lse:
        # The LSE residual is only needed when a backward pass will run;
        # forward-only (eval) calls skip the extra [BH, S, 128] HBM write.
        out_shape = (o_shape, kernel.out_struct((bh, sp, LANE), jnp.float32,
                                                *operands))
        out_specs = (o_spec, _q_rows(t.block_q, LANE))
    else:
        out_shape, out_specs = o_shape, o_spec
    result = _call(
        _fwd_kernel, names.fwd,
        _live_pairs(t, causal, q_major=True, window=window), operands,
        in_specs=([_q_rows(t.block_q, dp), _k_rows(t.block_k, dp),
                   _k_rows(t.block_k, dvp)]
                  + _bias_specs(has_bias, h, t) + _slope_specs(has_slopes, h)),
        out_shape=out_shape, out_specs=out_specs,
        scratch=[(t.block_q, dvp), (t.block_q, LANE), (t.block_q, LANE)],
        scale=scale, tiles=t, causal=causal, has_bias=has_bias,
        has_slopes=has_slopes, kv_len=s_len, window=window,
        emit_lse=emit_lse)

    out, lse = result if emit_lse else (result, None)
    out = out.reshape(b, h, sp, dvp)[:, :, :s_len, :dv]
    return out, lse


def _flash_backward(q, k, v, bias, slopes, out, lse, g, scale: float,
                    causal: bool, names: KernelNames,
                    window: int | None = None):
    bias = _canon_bias(bias, q.shape[1], q.shape[2])
    qp, kp, vp, bias, (b, h, s_len, d, dv, bh, sp, dp, dvp) = _pad_inputs(
        q, k, v, bias)
    # Pad O / dO the same way (their padded rows are zero, so padded-row
    # contributions to dk/dv vanish and padded delta rows are zero).
    op, gp, *_ = _pad_inputs(out, g, g, None)[:2]
    has_bias = bias is not None
    has_slopes = slopes is not None
    t = choose_tiles(s_len)
    if has_slopes:
        slopes = jnp.asarray(slopes, jnp.float32).reshape(h, 1, 1)

    operands = ([qp, kp, vp, op, gp, lse] + ([bias] if has_bias else [])
                + ([slopes] if has_slopes else []))
    # dq of one head stays in VMEM while the head's steps run: the f32
    # accumulator and the two buffers of the output block it is rounded to.
    resident = sp * dp * (4 + 2 * q.dtype.itemsize)
    if resident > MAX_RESIDENT_DQ:
        raise ValueError(
            f"the flash backward keeps a head's dq [{sp}, {dp}] in VMEM: "
            f"{resident} bytes are over {MAX_RESIDENT_DQ}; split the "
            "sequence (ring attention) or use the XLA path")
    # Gradients leave in the operands' dtype: one rounding from the f32
    # accumulator, here and not in a cast after the kernel.
    grad_shape = lambda x, width: kernel.out_struct(
        (bh, sp, width), x.dtype, *operands)
    dq, dk, dv_ = _call(
        _bwd_kernel, names.bwd,
        _live_pairs(t, causal, q_major=False, window=window), operands,
        in_specs=([_q_rows(t.block_q, dp), _k_rows(t.block_k, dp),
                   _k_rows(t.block_k, dvp), _q_rows(t.block_q, dvp),
                   _q_rows(t.block_q, dvp), _q_rows(t.block_q, LANE)]
                  + _bias_specs(has_bias, h, t) + _slope_specs(has_slopes, h)),
        out_shape=(grad_shape(q, dp), grad_shape(k, dp), grad_shape(v, dvp)),
        out_specs=(_head_rows(sp, dp), _k_rows(t.block_k, dp),
                   _k_rows(t.block_k, dvp)),
        scratch=[(sp, dp), (t.block_k, dp), (t.block_k, dvp)],
        vmem_limit_bytes=resident + SCOPED_VMEM,
        scale=scale, tiles=t, causal=causal, has_bias=has_bias,
        has_slopes=has_slopes, kv_len=s_len, window=window)

    def unpad(x, width):
        return x.reshape(b, h, sp, x.shape[-1])[:, :, :s_len, :width]

    return unpad(dq, d), unpad(dk, d), unpad(dv_, dv)


@functools.partial(jax.custom_vjp, nondiff_argnums=(5, 6, 7, 8))
def _flash(q, k, v, bias, slopes, scale, causal, names, window):
    out, _ = _flash_forward(q, k, v, bias, slopes, scale, causal, names,
                            window, emit_lse=False)
    return out


def _flash_fwd(q, k, v, bias, slopes, scale, causal, names, window):
    out, lse = _flash_forward(q, k, v, bias, slopes, scale, causal, names,
                              window)
    # All that the kernel wrote goes by a name, so that a layer's checkpoint
    # (`checkpoint_layer`) keeps it and the recomputed forward holds no
    # kernel call. q, k, v are not named: they come back from the layer's
    # input by cheap XLA, at three times O's bytes. LSE stays in the layout
    # the backward kernels read.
    out = checkpoint_name(out, RESIDUAL_NAMES[0])
    lse = checkpoint_name(lse, RESIDUAL_NAMES[1])
    _count_named_residuals(names.fwd)
    return out, (q, k, v, bias, slopes, out, lse)


def _flash_bwd(scale, causal, names, window, res, g):
    q, k, v, bias, slopes, out, lse = res
    dq, dk, dv = _flash_backward(q, k, v, bias, slopes, out, lse, g, scale,
                                 causal, names, window)
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
                    causal: bool = True,
                    window: int | None = None) -> jax.Array:
    """Flash attention. [B, H, S, D] -> [B, H, S, D]. Whose keys and values
    they are is the caller's business (this layer's, or another layer's at
    the same positions: a cross-decoder's shared keys and values); what the
    kernels need is queries and keys of one length under one causal mask.

    `bias` is an additive [H, S, S] (or broadcastable) logit bias, treated as
    a constant under differentiation (exact for ALiBi). Prefer
    `alibi_slopes` ([H] f32) for ALiBi: the bias block is generated
    IN-KERNEL from the slopes and position iotas, so no O(H S^2) bias
    buffer exists in HBM at any sequence length. `causal=False` gives the
    bidirectional encoder form. `window` (causal calls only): query i sees
    key j iff 0 <= i - j < window, itself and the `window - 1` keys before
    it; the kernels visit the band's block pairs alone, under `WINDOW`'s
    names.
    """
    if window is not None and (not causal or window < 1):
        raise ValueError(
            f"a sliding window is a causal call's and at least 1 "
            f"(causal={causal}, window={window})")
    if scale is None:
        scale = q.shape[-1] ** -0.5
    if q.shape[-2] != k.shape[-2]:
        raise ValueError(
            "the flash kernels need queries and keys of ONE length under "
            f"one causal mask (got {q.shape[-2]} and {k.shape[-2]}); keys "
            "and values from another layer at the same positions are fine, "
            "a shorter or longer memory takes the XLA path")
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
    return _flash(q, k, v, bias, alibi_slopes, scale, causal,
                  PLAIN if window is None else WINDOW,
                  None if window is None else int(window))


def latent_flash_attention(q_nope: jax.Array, q_rope: jax.Array,
                           k_nope: jax.Array, k_rope: jax.Array,
                           v: jax.Array, *,
                           scale: float | None = None) -> jax.Array:
    """Causal latent attention's core (DeepSeek's MLA, as trained).

    q_nope, k_nope [B, H, S, Dn]: the part of a head's query and key that
    carries no position; q_rope [B, H, S, Dr], rotated; k_rope [B, S, Dr]:
    ONE rotated key a position, shared by all H heads; v [B, H, S, Dv].
    Scores are (q_nope . k_nope + q_rope . k_rope) * scale, scale
    1 / sqrt(Dn + Dr) by default; returns softmax x v, [B, H, S, Dv].

    One path: the plain kernels at scores wider than the values (192
    against 128; `_pad_inputs` pads each to the lane width by itself),
    under `LATENT`'s names. The shared key is broadcast over the heads
    outside the kernels (`attention.latent_qk`), so its gradient is the sum
    over the heads of what the backward kernel gives each."""
    from oobleck_tpu.ops.attention import latent_qk

    q, k = latent_qk(q_nope, q_rope, k_nope, k_rope)
    if scale is None:
        scale = q.shape[-1] ** -0.5
    _count_latent_calls()
    return _flash(q, k, v, None, None, scale, True, LATENT, None)


def differential_flash_attention(q1: jax.Array, k1: jax.Array,
                                 q2: jax.Array, k2: jax.Array, v: jax.Array,
                                 *, scale: float | None = None,
                                 window: int | None = None):
    """Differential attention's two softmaxes, causal:
    (softmax(q1 k1^T) v, softmax(q2 k2^T) v). q1, k1, q2, k2 [B, H, S, D];
    v [B, H, S, 2 D], the pair's two value heads side by side; scale
    1 / sqrt(D) by default; `window` as `flash_attention`'s.

    Two calls of the plain kernels (`_pad_inputs` pads the 64-wide queries
    and keys to the lane by itself, the values are a lane already), under
    `DIFF`'s names (`DIFF_WINDOW`'s with a window): a reader of
    `%flash_fwd.` counts one width and a causal half. The difference, its
    norm and `lambda` are the caller's, outside."""
    if scale is None:
        scale = q1.shape[-1] ** -0.5
    names = DIFF if window is None else DIFF_WINDOW
    window = None if window is None else int(window)
    return tuple(_flash(q, k, v, None, None, scale, True, names, window)
                 for q, k in ((q1, k1), (q2, k2)))


def _count_named_residuals(kernel: str) -> None:
    """`oobleck_flash_residuals_named_total{kernel}`: once a forward rule
    traced (not once a step), by the forward kernel whose O and LSE it
    named."""
    from oobleck_tpu.utils import metrics

    metrics.registry().counter(
        "oobleck_flash_residuals_named_total",
        "Flash forward rules traced with O and LSE named for the layer's "
        "checkpoint, by kernel").inc(kernel=kernel)


def _count_call(kernel: str, steps: int, window: int | None) -> None:
    """Where a kernel is built into a traced program (not once a step):
    `oobleck_flash_live_pairs{kernel}`, the grid steps a head of the last
    such call, `oobleck_flash_window_calls_total{kernel}` where the call
    has a window and `oobleck_flash_diff_calls_total{kernel}` where it is
    one of differential attention's."""
    from oobleck_tpu.utils import metrics

    reg = metrics.registry()
    reg.gauge(
        "oobleck_flash_live_pairs",
        "Grid steps a head (live block pairs) of the last flash kernel "
        "built into a traced program, by kernel").set(steps, kernel=kernel)
    if window is not None:
        reg.counter(
            "oobleck_flash_window_calls_total",
            "Flash kernels with a sliding window built into traced "
            "programs, by kernel").inc(kernel=kernel)
    if kernel in DIFF + DIFF_WINDOW:
        reg.counter(
            "oobleck_flash_diff_calls_total",
            "Flash kernels of differential attention built into traced "
            "programs, by kernel").inc(kernel=kernel)


def _count_latent_calls() -> None:
    """`oobleck_flash_mla_calls_total{kernel}`: counted where the kernels
    are built, once a kernel of every program traced (not once a step)."""
    from oobleck_tpu.utils import metrics

    built = metrics.registry().counter(
        "oobleck_flash_mla_calls_total",
        "Latent-attention kernels built into traced programs, by kernel")
    for name in LATENT:
        built.inc(kernel=name)
