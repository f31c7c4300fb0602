"""Attention behind one functional interface.

`select_attention_impl` resolves six names:

  - "xla":     einsum + masked softmax (`_xla_causal_attention`); XLA fuses
               this well and it is the reference for correctness tests.
  - "pallas":  the blockwise flash kernels (`ops/flash.py`).
  - "ring":    ring attention over a sequence-parallel mesh axis
               (`ops/ring_attention.py`) for long-context training.
  - "ulysses": the all-to-all layout exists only under a sequence-parallel
               axis (`ops/ulysses.py`, which models call there); without
               one, the "auto" choice.
  - "auto":    flash on a TPU (`kernel.on_tpu`), XLA elsewhere.
  - "paged":   ragged paged decode (`ops/paged_attention.py`), with ANOTHER
               signature: page pools and block tables, not [B, H, S, D].

All but "paged" take [batch, heads, seq, head_dim] Q/K/V and return the
same shape. `causal_attention` dispatches on top (which calls the flash
kernels take: `flash_ok`); `latent_attention` and `differential_attention`
are the latent (MLA) and differential forms over the same two paths.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from oobleck_tpu.ops import kernel

NEG_INF = -1e9  # large-but-finite: jnp.finfo(bf16).min overflows under softmax subtraction


def _xla_causal_attention(
    q: jax.Array, k: jax.Array, v: jax.Array, *, scale: float | None = None,
    bias: jax.Array | None = None, causal: bool = True,
    window: int | None = None
) -> jax.Array:
    """Masked-softmax attention. [B, H, S, D] -> [B, H, S, D].

    `bias` ([H, Sq, Sk] or broadcastable) supports ALiBi (Bloom family);
    `causal=False` gives the bidirectional encoder form (BERT/ViT);
    `window` (causal only): query i sees key j iff 0 <= i - j < window."""
    if window is not None and not causal:
        raise ValueError("a sliding window is a causal call's")
    *_, seq_q, head_dim = q.shape
    seq_k = k.shape[-2]
    if scale is None:
        scale = head_dim**-0.5
    logits = jnp.einsum("bhqd,bhkd->bhqk", q, k) * scale
    if bias is not None:
        logits = logits + bias.astype(logits.dtype)
    if causal:
        # Supports seq_q != seq_k (ring attention partial blocks).
        q_pos = jnp.arange(seq_q)[:, None] + (seq_k - seq_q)
        k_pos = jnp.arange(seq_k)[None, :]
        seen = q_pos >= k_pos
        if window is not None:
            seen = seen & (q_pos - k_pos < window)
        logits = jnp.where(seen, logits, NEG_INF)
    # Softmax in f32 for stability regardless of compute dtype.
    probs = jax.nn.softmax(logits.astype(jnp.float32), axis=-1).astype(q.dtype)
    return jnp.einsum("bhqk,bhkd->bhqd", probs, v)


def alibi_slopes(num_heads: int) -> jax.Array:
    """ALiBi per-head slopes (Bloom): geometric sequence from 2^(-8/n)."""
    import math

    def pow2_slopes(n):
        start = 2.0 ** (-(2.0 ** -(math.log2(n) - 3)))
        return [start * (start ** i) for i in range(n)]

    if math.log2(num_heads).is_integer():
        s = pow2_slopes(num_heads)
    else:
        closest = 2 ** int(math.floor(math.log2(num_heads)))
        s = pow2_slopes(closest)
        extra = pow2_slopes(2 * closest)[0::2][: num_heads - closest]
        s = s + extra
    return jnp.asarray(s, jnp.float32)


def alibi_bias_from_slopes(slopes: jax.Array, seq_q: int, seq_k: int,
                           causal: bool = True) -> jax.Array:
    """[h, Sq, Sk] ALiBi bias for the GIVEN slopes only — callers holding a
    head slice (TP rank, Ulysses shard) materialize h=H_local rows instead
    of all H (the O(H S^2) buffer is the long-context memory hazard).

    Causal form: -slope * (q - k), the original ALiBi decoder penalty
    (future keys are masked anyway, so the sign of the k > q half never
    matters). Bidirectional (`causal=False`): -slope * |q - k| — the
    symmetric "nonsym" variant of the ALiBi encoder ablations. The signed
    form would REWARD attending to future keys (positive bias growing with
    k - q), which is never the intent."""
    q_pos = jnp.arange(seq_q)[:, None] + (seq_k - seq_q)
    k_pos = jnp.arange(seq_k)[None, :]
    dist = (q_pos - k_pos).astype(jnp.float32)
    if not causal:
        dist = jnp.abs(dist)
    return -slopes[:, None, None] * dist[None]


def alibi_bias(num_heads: int, seq_q: int, seq_k: int,
               causal: bool = True) -> jax.Array:
    """[H, Sq, Sk] ALiBi bias: -slope * (q - k) causal, -slope * |q - k|
    bidirectional."""
    return alibi_bias_from_slopes(alibi_slopes(num_heads), seq_q, seq_k,
                                  causal=causal)


# -- KV-cache decode path (serving) ------------------------------------- #

def cache_write(cache: jax.Array, new: jax.Array, pos: jax.Array) -> jax.Array:
    """Write one token's K or V into a slot cache at per-slot positions.

    cache [B, H, S, D]; new [B, H, D]; pos [B] int32 (each batch slot in a
    continuous batch sits at its own sequence position). Returns the updated
    cache; safe to donate — every write is a dynamic_update_slice."""
    def one(c, n, p):
        return jax.lax.dynamic_update_slice(
            c, n[:, None, :].astype(c.dtype), (0, p, 0))

    return jax.vmap(one)(cache, new, pos)


def decode_attention(
    q: jax.Array, k_cache: jax.Array, v_cache: jax.Array, pos: jax.Array, *,
    scale: float | None = None, alibi_slopes: jax.Array | None = None,
) -> jax.Array:
    """Single-token attention against a preallocated KV cache.

    q [B, Hq, D]; k_cache/v_cache [B, Hkv, S, D]; pos [B] is each slot's
    current position — keys at indices <= pos are live, later indices hold
    stale/garbage bytes from freed slots and are masked. Grouped-query
    caches (Hkv < Hq) fold query heads into [Hkv, G] groups against the
    unrepeated cache instead of materializing repeated K/V per step.
    Returns [B, Hq, D]."""
    b, hq, d = q.shape
    hkv, s = k_cache.shape[1], k_cache.shape[2]
    if scale is None:
        scale = d**-0.5
    g = hq // hkv
    qg = q.reshape(b, hkv, g, d)
    logits = jnp.einsum("bkgd,bksd->bkgs", qg, k_cache) * scale
    k_idx = jnp.arange(s)
    if alibi_slopes is not None:
        dist = (pos[:, None] - k_idx[None, :]).astype(jnp.float32)  # [B, S]
        slopes = alibi_slopes.reshape(hkv, g)
        logits = logits - slopes[None, :, :, None] * dist[:, None, None, :]
    live = k_idx[None, :] <= pos[:, None]                           # [B, S]
    logits = jnp.where(live[:, None, None, :], logits, NEG_INF)
    probs = jax.nn.softmax(logits.astype(jnp.float32), axis=-1).astype(q.dtype)
    return jnp.einsum("bkgs,bksd->bkgd", probs, v_cache).reshape(b, hq, d)


@functools.cache
def select_attention_impl(impl: str = "auto"):
    """Resolve an attention implementation name to a callable.

    "auto" picks pallas flash on TPU when the kernel supports the platform,
    otherwise the XLA path. Resolution is deferred so importing this module
    never triggers backend init.
    """
    if impl == "xla":
        return _xla_causal_attention
    if impl == "pallas":
        from oobleck_tpu.ops.flash import flash_attention

        return flash_attention
    if impl == "ring":
        from oobleck_tpu.ops.ring_attention import ring_attention

        return ring_attention
    if impl == "paged":
        # Ragged paged decode over block tables (serving hot path). The
        # callable has the paged signature (pools + block tables), not the
        # [B, H, S, D] one; it dispatches pallas/xla internally by backend.
        from oobleck_tpu.ops.paged_attention import paged_decode_attention

        return paged_decode_attention
    if impl == "ulysses":
        # The Ulysses all-to-all layout only exists under a sequence-
        # parallel mesh axis (models call ops.ulysses directly there);
        # without one it degenerates to the "auto" single-device choice —
        # flash on TPU, NOT the HBM-quadratic XLA path.
        return select_attention_impl("auto")
    if impl == "auto":
        # On TPU the Pallas flash kernel (fwd + bwd) is the default — it
        # keeps HBM traffic linear in S where the XLA path materializes
        # [S, S] logits. Elsewhere (CPU mesh tests) the kernel would run in
        # interpreter mode, so the fused XLA path is faster. Never silently
        # swallow an ImportError here — a masked fallback hides real bugs.
        if kernel.on_tpu():
            from oobleck_tpu.ops.flash import flash_attention

            return flash_attention
        return _xla_causal_attention
    raise ValueError(f"unknown attention impl: {impl!r}")


def causal_attention(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    *,
    impl: str = "auto",
    scale: float | None = None,
    bias: jax.Array | None = None,
    alibi_slopes: jax.Array | None = None,
    causal: bool = True,
    constant_bias: bool = False,
    window: int | None = None,
) -> jax.Array:
    """Dispatching attention entry point.

    `constant_bias=True` asserts the bias carries no gradient (ALiBi and
    other position-only biases) — required for the flash kernel, whose VJP
    treats the bias as a constant. Learned/batch-dependent biases and
    queries and keys of different lengths always take the XLA path (keys
    and values from another layer at the SAME positions do not: the flash
    kernels need one length under one causal mask and no more).

    Prefer `alibi_slopes` ([H] f32) over a materialized ALiBi `bias`: the
    flash kernel generates the bias block in-kernel from the slopes, so no
    O(H S^2) buffer exists in HBM at any S; non-flash fallbacks
    materialize it from the slopes only where unavoidable.

    `window`: a sliding window over a causal call (query i sees key j iff
    0 <= i - j < window), on the flash kernels and the XLA path. Ring and
    Ulysses have no windowed form and a non-causal call has no window:
    each raises, none ignores it.
    """
    if bias is not None and alibi_slopes is not None:
        raise ValueError("pass bias OR alibi_slopes, not both")
    if window is not None and (not causal or impl in ("ring", "ulysses")):
        raise ValueError(
            f"a sliding window needs a causal call on the flash or XLA "
            f"path (causal={causal}, impl={impl!r})")
    fn = select_attention_impl(impl)
    from oobleck_tpu.ops.ring_attention import ring_attention

    def slope_bias():
        # Non-flash fallback: materialize from slopes (constant, exact).
        return alibi_bias_from_slopes(alibi_slopes, q.shape[-2], k.shape[-2],
                                      causal=causal)

    if fn is ring_attention:
        # Ring handles unbiased causal self-attention only; anything else
        # falls back to XLA (single-device call — the sequence-parallel path
        # reaches ring_attention directly with its own checks).
        if bias is None and alibi_slopes is None and causal:
            return fn(q, k, v, scale=scale)
        if alibi_slopes is not None:
            bias = slope_bias()
        return _xla_causal_attention(q, k, v, scale=scale, bias=bias,
                                     causal=causal)
    flash_ok = (
        q.shape[-2] == k.shape[-2]
        and (bias is None
             or (constant_bias and (bias.ndim < 4 or bias.shape[0] == 1)))
    )
    if fn is _xla_causal_attention or not flash_ok:
        if alibi_slopes is not None:
            bias = slope_bias()
        return _xla_causal_attention(q, k, v, scale=scale, bias=bias,
                                     causal=causal, window=window)
    return fn(q, k, v, scale=scale, bias=bias, alibi_slopes=alibi_slopes,
              causal=causal, window=window)


def latent_qk(q_nope, q_rope, k_nope, k_rope):
    """Latent attention's scores as ONE width: q = [q_nope | q_rope],
    k = [k_nope | k_rope], the one rotary key a position ([B, S, Dr])
    broadcast over the heads; its gradient is then the sum over them."""
    b, h, s_len, _ = q_nope.shape
    if k_rope.shape != (b, s_len, q_rope.shape[-1]):
        raise ValueError(
            f"k_rope must be [B, S, Dr] = {(b, s_len, q_rope.shape[-1])}, "
            f"one key a position for all heads; got {k_rope.shape}")
    q = jnp.concatenate([q_nope, q_rope], axis=-1)
    k = jnp.concatenate(
        [k_nope, jnp.broadcast_to(k_rope[:, None], q_rope.shape)], axis=-1)
    return q, k


def latent_attention(
    q_nope: jax.Array,
    q_rope: jax.Array,
    k_nope: jax.Array,
    k_rope: jax.Array,
    v: jax.Array,
    *,
    impl: str = "auto",
    scale: float | None = None,
) -> jax.Array:
    """Causal latent attention (DeepSeek's MLA, the form trained): scores
    over [q_nope | q_rope] . [k_nope | k_rope], `k_rope` [B, S, Dr] ONE
    rotated key a position shared by all heads, values of their own width.
    [B, H, S, Dn] / [B, H, S, Dr] / [B, H, S, Dv] -> [B, H, S, Dv].

    The flash kernels on a TPU ("auto") or where asked for ("pallas": the
    interpreter elsewhere); otherwise the XLA reference on the same
    operands. Ring and Ulysses have no latent form and take the
    single-device choice."""
    if impl in ("ring", "ulysses"):
        impl = "auto"
    if impl == "pallas" or (impl == "auto" and kernel.on_tpu()):
        from oobleck_tpu.ops.flash import latent_flash_attention

        return latent_flash_attention(q_nope, q_rope, k_nope, k_rope, v,
                                      scale=scale)
    if impl not in ("auto", "xla"):
        raise ValueError(f"unknown attention impl: {impl!r}")
    q, k = latent_qk(q_nope, q_rope, k_nope, k_rope)
    return _xla_causal_attention(q, k, v, scale=scale)


def differential_attention(
    q1: jax.Array,
    k1: jax.Array,
    q2: jax.Array,
    k2: jax.Array,
    v: jax.Array,
    *,
    impl: str = "auto",
    scale: float | None = None,
    window: int | None = None,
):
    """Differential attention's two causal softmaxes over ONE set of
    values: (softmax(q1 k1^T) v, softmax(q2 k2^T) v), q and k [B, H, S, D],
    v [B, H, S, 2 D]; `window` as `causal_attention`'s. What is done with
    the two (the difference, `lambda`, the norm) is the model's.

    The flash kernels on a TPU ("auto") or where asked for ("pallas": the
    interpreter elsewhere), under names of their own; otherwise the XLA
    reference twice. Ring and Ulysses have no such form and take the
    single-device choice."""
    if impl in ("ring", "ulysses"):
        impl = "auto"
    if impl == "pallas" or (impl == "auto" and kernel.on_tpu()):
        from oobleck_tpu.ops.flash import differential_flash_attention

        return differential_flash_attention(q1, k1, q2, k2, v, scale=scale,
                                            window=window)
    if impl not in ("auto", "xla"):
        raise ValueError(f"unknown attention impl: {impl!r}")
    return tuple(_xla_causal_attention(q, k, v, scale=scale, window=window)
                 for q, k in ((q1, k1), (q2, k2)))
