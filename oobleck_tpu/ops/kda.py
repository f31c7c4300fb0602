"""Kimi Delta Attention's recurrence: the delta rule with a decay that is a
VECTOR a head, in chunks.

Per head, with a decay `exp(g_t)` a key CHANNEL (`g_t` [dk], never
positive) and a scalar write strength `beta_t` a position, a state `S`
[dk, dv] in float32:

    S' = Diag(exp(g_t)) S_{t-1}                         S_{-1} = 0
    u_t = beta_t (v_t - S'^T k_t)
    S_t = S' + k_t u_t^T
    o_t = S_t^T q_t

`ops/gdn.py`'s rule with `g_t` one number a head is the special case of
equal channels (`tests/ops/test_kda.py` holds the two together). With `cum`
the running sum of `g` INSIDE a chunk of `Q` positions, a vector a position,
and for rows `a` (the keys, then the queries) the DECAYED PRODUCT

    M(a)_ij = sum_c a_ic k_jc exp(cum_ic - cum_jc)            i >= j

the chunk is `ops/gdn.py`'s, with `cum` a vector:

  system  A = strict_lower(diag(beta) M(K)),  T = (I + A)^-1 diag(beta)
          W = T (K * e^cum),  U = T V
  inter   a chunk that starts at state S:   V' = U - W S
          S_next = Diag(e^{cum_Q}) S + (K * e^{cum_Q - cum})^T V'
  out     O = (Q * e^cum) S + lower(M(Q)) V'

`M` is no product of a matrix and a mask: the decay between two positions
differs by channel. Written out it is [Q, Q, dk] a head and chunk (4.3 GB a
layer at 64 chunks of 64 over 32 heads of 128), and factored about one
point, `(a * e^cum)(k * e^-cum)^T`, it is a quotient of exponentials that
overflows once a channel decays by e^-89 inside a chunk. `decayed_products`
takes it in LEVELS, each factored about points of its own that lie BETWEEN
the rows and the columns it pairs, so that both factors' arguments are
never positive. Two positions i > j differ in a highest bit: at the level of
blocks of 2h positions (h = Q/2, Q/4, .. 1) `i` lies in the later half of a
block and `j` in the earlier half of the SAME block, and with `b` the
running sum at the earlier half's LAST position

    M(a)_ij = (a_i * e^{cum_i - b}) . (k_j * e^{b - cum_j})

So a level scales every position once (`e^{cum - b}` in a later half,
`e^{b - cum}` in an earlier one), takes ONE [Q, dk] x [dk, Q] product a
head and chunk and keeps the level's pairs by a constant mask; log2 Q
levels and the plain diagonal `a_i . k_i` make `M`. A level costs what
`K * e^cum` costs; nothing of size [Q, Q, dk] a head exists anywhere.

The inverse is `ops/gdn.unit_lower_inverse` (the series and its gradient
rule, imported), under a forward rule of this module's own that NAMES it
(`RESIDUAL_NAMES`) so that a layer's checkpoint
(`ops/remat.checkpoint_layer`) keeps it. Everything else is plain
`jax.numpy` on every backend: batched `einsum`s and a `lax.scan` across the
chunks, differentiated by JAX. (Kernels for what follows the inverse, as
`gdn_fwd` / `gdn_bwd`, are not here yet: PERF.md §7.)

Held to what `ops/gdn.py` is held to: `g`, `cum`, every `exp`, the inverse
and the state in float32 (the inverse's products at `Precision.HIGHEST`);
no `exp` of a positive argument and no quotient of exponentials, forward or
backward; the other products' operands in `v`'s dtype with float32
accumulation; a length that is no multiple of `Q` padded with `g = 0,
beta = 0` rows, which move no state, and cut off again.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.ad_checkpoint import checkpoint_name

from oobleck_tpu.ops import kernel
from oobleck_tpu.ops.gdn import _inverse_bwd, unit_lower_inverse

# The forward rule's name for what only more product time could give back:
# the inverse (the decayed products and `a` come back by cheap XLA).
RESIDUAL_NAMES = ("kda_inverse",)


def _count(chunks: int, layer: str | None) -> None:
    """`oobleck_kda_scans_total`: counted where the rule is built, once a
    call of every program traced (not once a step), and
    `oobleck_kda_chunks{layer}`, the chunks a sequence of the last traced
    call."""
    from oobleck_tpu.utils import metrics

    reg = metrics.registry()
    reg.counter(
        "oobleck_kda_scans_total",
        "Chunked Kimi delta rules built into traced programs").inc()
    reg.gauge(
        "oobleck_kda_chunks",
        "Chunks a sequence of the LAST traced Kimi delta rule was cut "
        "into, by layer").set(chunks, layer=str(layer))


@jax.custom_vjp
@jax.named_scope("kda_inverse")
def _inverse(a: jax.Array) -> jax.Array:
    return unit_lower_inverse(a)


def _inverse_fwd(a):
    """`oobleck_kda_residuals_named_total`: once a forward rule traced (not
    once a step), with the inverse named."""
    from oobleck_tpu.utils import metrics

    inverse = checkpoint_name(_inverse(a), RESIDUAL_NAMES[0])
    metrics.registry().counter(
        "oobleck_kda_residuals_named_total",
        "Forward rules of the Kimi delta rule's inverse traced with the "
        "inverse named for the layer's checkpoint").inc()
    return inverse, inverse


_inverse.defvjp(_inverse_fwd, jax.named_scope("kda_inverse")(_inverse_bwd))


def _levels(qn: int):
    """A chunk's pairs of positions i > j by the HIGHEST bit in which i and
    j differ: at the level of blocks of 2h positions (h = qn / 2 .. 1), i
    lies in the later half of a block and j in the earlier half of the same
    block. Yields (h, the [qn, qn] mask of the level's pairs)."""
    p = np.arange(qn)
    half = qn // 2
    while half:
        late = (p % (2 * half)) >= half
        same = (p[:, None] // (2 * half)) == (p[None, :] // (2 * half))
        yield half, late[:, None] & ~late[None, :] & same
        half //= 2


def decayed_products(q: jax.Array, k: jax.Array, cum: jax.Array
                     ) -> tuple[jax.Array, jax.Array]:
    """`(M(k), M(q))`, `M(a)_ij = sum_c a_ic k_jc exp(cum_ic - cum_jc)` for
    i >= j and zero above the diagonal, of the keys and the queries
    [..., Q, dk] against the keys under the running sums `cum` [..., Q, dk]
    (float32, never increasing along Q; Q a power of two). Each [..., Q, Q]
    float32; q and k enter the products in `k`'s dtype.

    A level scales every position ONCE, by `e^{cum - b}` in a block's later
    half and `e^{b - cum}` in its earlier half, `b` the running sum at the
    earlier half's last position: whichever half a position is in, the
    argument is never positive. The level's pairs are the later rows
    against the earlier columns of the same block; the product of the
    scaled chunk (its keys over its queries, [2 Q, dk]) with its scaled
    keys holds them, and the level's mask drops the rest (finite: no factor
    is above one in magnitude)."""
    f32 = jnp.float32
    dtype = k.dtype
    qn, dk = k.shape[-2:]
    assert qn & (qn - 1) == 0, f"a chunk of {qn} positions"
    product = lambda rows, cols: jnp.einsum(
        "...ic,...jc->...ij", rows, cols, preferred_element_type=f32)
    both = lambda t: jnp.concatenate([t, t])               # [2 Q, Q]
    out = both(jnp.eye(qn, dtype=f32)) * product(
        jnp.concatenate([k, q], axis=-2), k)
    for half, pairs in _levels(qn):
        blocks = cum.reshape(*cum.shape[:-2], qn // (2 * half), 2, half, dk)
        about = blocks[..., :1, half - 1:, :]          # [..., n, 1, 1, dk]
        scale = jnp.exp(jnp.concatenate(
            [about - blocks[..., :1, :, :], blocks[..., 1:, :, :] - about],
            axis=-3).reshape(cum.shape))
        keys = (k.astype(f32) * scale).astype(dtype)
        rows = jnp.concatenate(
            [keys, (q.astype(f32) * scale).astype(dtype)], axis=-2)
        out = out + both(jnp.asarray(pairs, f32)) * product(rows, keys)
    return out[..., :qn, :], out[..., qn:, :]


@jax.named_scope("kda")
def kimi_delta_rule(q: jax.Array, k: jax.Array, v: jax.Array, g: jax.Array,
                    beta: jax.Array, *, chunk: int,
                    layer: str | None = None) -> jax.Array:
    """q, k [B, S, H, dk] (as the rule reads them: the caller normalises
    and scales); v [B, S, H, dv]; g [B, S, H, dk], the log of the decay a
    channel, never positive; beta [B, S, H]. Returns o [B, S, H, dv] in v's
    dtype."""
    f32 = jnp.float32
    bsz, seq, heads, dv = v.shape
    dk = k.shape[-1]
    nc = -(-seq // chunk)
    _count(nc, layer)
    pad = nc * chunk - seq
    if pad:
        rows = lambda t: jnp.pad(t, ((0, 0), (0, pad)) + ((0, 0),) * (t.ndim - 2))
        q, k, v, g, beta = rows(q), rows(k), rows(v), rows(g), rows(beta)
    dtype = v.dtype
    # Heads before positions: the [Q, Q] blocks are the minor dimensions.
    per_head = lambda t: jnp.swapaxes(
        t.reshape(bsz, nc, chunk, heads, t.shape[-1]), 2, 3)
    qh, kh, vh = (per_head(t.astype(dtype)) for t in (q, k, v))
    beta_h = jnp.swapaxes(
        beta.astype(f32).reshape(bsz, nc, chunk, heads), 2, 3)  # [B, nc, H, Q]
    cum = kernel.running_sums(per_head(g.astype(f32)), axis=-2)
    i = jnp.arange(chunk)

    # system: every position's write against the writes before it.
    kk, qk = decayed_products(qh, kh, cum)
    a = jnp.where(i[:, None] > i[None, :], beta_h[..., :, None] * kk, 0.0)
    t = (_inverse(a) * beta_h[..., None, :]).astype(dtype)
    since = jnp.exp(cum)                # the decay since the chunk's start
    total = cum[..., -1:, :]                               # [B, nc, H, 1, dk]
    w = jnp.einsum("bzhij,bzhjd->bzhid", t,
                   (kh.astype(f32) * since).astype(dtype),
                   preferred_element_type=f32).astype(dtype)
    u = jnp.einsum("bzhij,bzhjv->bzhiv", t, vh,
                   preferred_element_type=f32).astype(dtype)
    k_to_end = (kh.astype(f32) * jnp.exp(total - cum)).astype(dtype)

    # inter: the state at every chunk's start, and the chunk's writes.
    def step(state, chunk_in):
        w_c, u_c, k_c, whole = chunk_in
        wrote = (u_c.astype(f32) - jnp.einsum(
            "bhid,bhdv->bhiv", w_c, state.astype(dtype),
            preferred_element_type=f32)).astype(dtype)
        after = whole[..., None] * state + jnp.einsum(
            "bhjd,bhjv->bhdv", k_c, wrote, preferred_element_type=f32)
        return after, (state.astype(dtype), wrote)

    by_chunk = lambda x: jnp.moveaxis(x, 1, 0)
    _, (starts, wrote) = lax.scan(
        step, jnp.zeros((bsz, heads, dk, dv), f32),
        tuple(by_chunk(x) for x in (w, u, k_to_end,
                                    jnp.exp(total[..., 0, :]))))
    starts, wrote = by_chunk(starts), by_chunk(wrote)

    # out: what the state at the chunk's start gives, and the chunk's own
    # writes up to and including the position's.
    o = jnp.einsum("bzhij,bzhjv->bzhiv", qk.astype(dtype), wrote,
                   preferred_element_type=f32)
    o = o + jnp.einsum("bzhid,bzhdv->bzhiv",
                       (qh.astype(f32) * since).astype(dtype), starts,
                       preferred_element_type=f32)
    return jnp.swapaxes(o, 2, 3).reshape(v.shape).astype(dtype)[:, :seq]
