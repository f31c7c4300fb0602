"""The gated delta rule (Gated DeltaNet's recurrence), in chunks.

Per value head, with a scalar decay `exp(g_t)` and a scalar write strength
`beta_t` a position, a state `S` [dk, dv] in float32:

    S' = exp(g_t) S_{t-1}                               S_{-1} = 0
    u_t = beta_t (v_t - S'^T k_t)
    S_t = S' + k_t u_t^T
    o_t = S_t^T q_t

Every position READS the state it is about to write (`S'^T k_t`), which is
what `ops/ssd.py`'s recurrence does not do; inside a chunk of `Q` positions
that dependence is a unit lower-triangular system. With `cum` the running
sum of `g` INSIDE a chunk and `D_ij = exp(cum_i - cum_j)` for i >= j:

  system  A = strict_lower(diag(beta) (K K^T * D)),  T = (I + A)^-1 diag(beta)
          W = T (K * e^cum),  U = T V
  inter   a chunk that starts at state S:   V' = U - W S  (the chunk's u_t)
          S_next = e^{cum_Q} S + (K * e^{cum_Q - cum})^T V'
  out     O = (Q * e^cum) S + lower(Q K^T * D) V'

`(I + A)^-1`: `A` is strictly lower triangular, so `N = -A` is nilpotent
(`N^Q = 0`) and the inverse is the finite series `sum_n N^n`, taken as the
product `(I + N)(I + N^2)(I + N^4)...` of `log2 Q` factors: batched [Q, Q]
matrix products the compiler knows, and no triangular solve walked row by
row. Plain `jax.numpy`: `einsum`s, gradients by JAX's differentiation of
them (under the layer's checkpoint like every other layer), but for the
inverse, whose gradient is written down (`-X^T dX X^T`: two products a head
and chunk where the series' own would be eighteen) and whose forward rule
NAMES it (`RESIDUAL_NAMES`), so that a layer's checkpoint
(`ops/remat.checkpoint_layer`) keeps it and the recomputed forward holds no
series: the one value of the rule that only `2 (log2 Q - 1)` more float32
products could give back. No Pallas kernel: `D`, `A` and `T` are written
out, [Q, Q] float32 blocks a head and chunk, and the traffic that costs is
what a kernel for this rule would save.

Held to what `ops/ssd.py` is held to: `g`, `cum`, every `exp`, the inverse
and the state in float32 (the inverse's products at `Precision.HIGHEST`);
`D` from the DIFFERENCE of running sums (never a quotient of exponentials:
a chunk that decays by e^-20 has no inf and no nan in it, forward or
backward), and `e^cum`, `e^{cum_Q - cum}` of arguments that are never
positive; the other products' operands in `v`'s dtype with float32
accumulation; a length that is no multiple of `Q` padded with `g = 0,
beta = 0` rows, which move no state, and cut off again; the `G` key heads
read by their `H / G` value heads through an `einsum` index, never copied.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax
from jax.ad_checkpoint import checkpoint_name

# The forward rule's name for the inverse it returns: the residual that
# only the series' products could give back (`decay`, `kk` and `a` come
# back by cheap XLA; `W` and `U` are two products of the kept value).
RESIDUAL_NAMES = ("gdn_inverse",)


def _count(chunks: int, layer: str | None) -> None:
    """`oobleck_gdn_scans_total`: counted where the rule is built, once a
    call of every program traced (not once a step), and
    `oobleck_gdn_chunks{layer}`, the chunks a sequence of the last traced
    call."""
    from oobleck_tpu.utils import metrics

    reg = metrics.registry()
    reg.counter(
        "oobleck_gdn_scans_total",
        "Chunked gated delta rules built into traced programs").inc()
    reg.gauge(
        "oobleck_gdn_chunks",
        "Chunks a sequence of the LAST traced gated delta rule was cut "
        "into, by layer").set(chunks, layer=str(layer))


def _count_named_residuals() -> None:
    """`oobleck_gdn_residuals_named_total`: once a forward rule of the
    inverse traced (not once a step), with the inverse named."""
    from oobleck_tpu.utils import metrics

    metrics.registry().counter(
        "oobleck_gdn_residuals_named_total",
        "Forward rules of the delta rule's inverse traced with the inverse "
        "named for the layer's checkpoint").inc()


def _dot(x: jax.Array, y: jax.Array) -> jax.Array:
    return jnp.matmul(x, y, precision=lax.Precision.HIGHEST)


@jax.custom_vjp
@jax.named_scope("gdn_inverse")
def unit_lower_inverse(a: jax.Array) -> jax.Array:
    """(I + a)^-1 for `a` [..., Q, Q] strictly lower triangular, float32:
    with n = -a, (I + n)(I + n^2)(I + n^4)... until the power is zero.
    Its gradient is the inverse's own, `-X^T dX X^T` with `X` the result
    (two products, where differentiating the 2 log2 Q products of the
    series costs twice as many again and keeps every power)."""
    q = a.shape[-1]
    power = -a
    inverse = jnp.eye(q, dtype=a.dtype) + power
    reach = 2                       # `inverse` holds the series below n^reach
    while reach < q:
        power = _dot(power, power)
        inverse = inverse + _dot(inverse, power)
        reach *= 2
    return inverse


def _inverse_fwd(a):
    inverse = checkpoint_name(unit_lower_inverse(a), RESIDUAL_NAMES[0])
    _count_named_residuals()
    return inverse, inverse


@jax.named_scope("gdn_inverse")
def _inverse_bwd(inverse, d_inverse):
    transposed = jnp.swapaxes(inverse, -1, -2)
    return (-_dot(_dot(transposed, d_inverse), transposed),)


unit_lower_inverse.defvjp(_inverse_fwd, _inverse_bwd)


@jax.named_scope("gdn")
def gated_delta_rule(q: jax.Array, k: jax.Array, v: jax.Array, g: jax.Array,
                     beta: jax.Array, *, chunk: int,
                     layer: str | None = None) -> jax.Array:
    """q, k [B, S, G, dk] (as the rule reads them: the caller normalises
    and scales); v [B, S, H, dv] with G dividing H (value head h reads key
    head h // (H / G)); g [B, S, H], the log of the decay, never positive;
    beta [B, S, H]. Returns o [B, S, H, dv] in v's dtype."""
    f32 = jnp.float32
    bsz, seq, heads, dv = v.shape
    groups, dk = k.shape[2], k.shape[3]
    assert heads % groups == 0, (heads, groups)
    r = heads // groups
    nc = -(-seq // chunk)
    _count(nc, layer)
    pad = nc * chunk - seq
    if pad:
        rows = lambda t: jnp.pad(t, ((0, 0), (0, pad)) + ((0, 0),) * (t.ndim - 2))
        q, k, v, g, beta = rows(q), rows(k), rows(v), rows(g), rows(beta)
    dtype = v.dtype
    qg = q.astype(dtype).reshape(bsz, nc, chunk, groups, dk)
    kg = k.astype(dtype).reshape(bsz, nc, chunk, groups, dk)
    vg = v.reshape(bsz, nc, chunk, groups, r, dv)
    # Heads before positions: the [Q, Q] blocks are the minor dimensions.
    per_head = lambda t: jnp.moveaxis(
        t.astype(f32).reshape(bsz, nc, chunk, groups, r), 2, -1)
    per_row = lambda t: jnp.moveaxis(t, -1, 2)             # [B, nc, Q, G, R]
    beta_h = per_head(beta)                                # [B, nc, G, R, Q]
    cum = jnp.cumsum(per_head(g), axis=-1)
    total = cum[..., -1]                                   # [B, nc, G, R]
    from_start = jnp.exp(cum)           # the decay since the chunk's start
    i = jnp.arange(chunk)
    decay = jnp.exp(jnp.where(i[:, None] >= i[None, :],
                              cum[..., :, None] - cum[..., None, :],
                              -jnp.inf))                   # [B, nc, G, R, Q, Q]

    # system: every position's write against the writes before it.
    kk = jnp.einsum("bzigd,bzjgd->bzgij", kg, kg, preferred_element_type=f32)
    a = jnp.where(i[:, None] > i[None, :],
                  beta_h[..., :, None] * kk[:, :, :, None] * decay, 0.0)
    t = unit_lower_inverse(a) * beta_h[..., None, :]
    w = jnp.einsum("bzgrij,bzjgd->bzgrid",
                   (t * from_start[..., None, :]).astype(dtype), kg,
                   preferred_element_type=f32).astype(dtype)
    u = jnp.einsum("bzgrij,bzjgrd->bzgrid", t.astype(dtype), vg,
                   preferred_element_type=f32).astype(dtype)

    # inter: the state at every chunk's start, and the chunk's writes.
    to_end = jnp.exp(total[..., None] - cum)               # [B, nc, G, R, Q]

    def step(state, chunk_in):
        w_c, u_c, k_c, to_end_c, total_c = chunk_in
        wrote = u_c.astype(f32) - jnp.einsum(
            "bgrid,bgrdv->bgriv", w_c, state.astype(dtype),
            preferred_element_type=f32)
        after = jnp.exp(total_c)[..., None, None] * state + jnp.einsum(
            "bjgd,bgrjv->bgrdv", k_c,
            (wrote * to_end_c[..., None]).astype(dtype),
            preferred_element_type=f32)
        return after, (state, wrote.astype(dtype))

    by_chunk = lambda x: jnp.moveaxis(x, 1, 0)
    _, (starts, wrote) = lax.scan(
        step, jnp.zeros((bsz, groups, r, dk, dv), f32),
        tuple(by_chunk(x) for x in (w, u, kg, to_end, total)))
    starts = by_chunk(starts)                          # [B, nc, G, R, dk, dv]
    wrote = by_chunk(wrote)                            # [B, nc, G, R, Q, dv]

    # out: what the state at the chunk's start gives, and the chunk's own
    # writes up to and including the position's.
    qk = jnp.einsum("bzigd,bzjgd->bzgij", qg, kg, preferred_element_type=f32)
    o = jnp.einsum("bzgrij,bzgrjv->bzigrv",
                   (qk[:, :, :, None] * decay).astype(dtype), wrote,
                   preferred_element_type=f32)
    o = o + per_row(from_start)[..., None] * jnp.einsum(
        "bzigd,bzgrdv->bzigrv", qg, starts.astype(dtype),
        preferred_element_type=f32)
    return o.reshape(bsz, nc * chunk, heads, dv)[:, :seq].astype(dtype)
