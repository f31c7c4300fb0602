"""The Mamba-2 recurrence (state-space duality), in chunks.

Per head, with a scalar decay a position, a state `H` [P, N] in float32:

    H_t = exp(dt_t A) H_{t-1} + dt_t x_t (x) B_t        H_{-1} = 0
    y_t = H_t C_t + D x_t

The sequence is not walked step by step. With chunks of `Q` positions,
`a_t = dt_t A` and `cum` the running sum of `a` INSIDE a chunk:

  intra  inside a chunk      Y_diag = ((C B^T) * L)(dt * x),
                             L_ij = exp(cum_i - cum_j) for i >= j, else 0
  state  a chunk's own part  S_c = ((exp(cum_Q - cum) * dt) * x)^T B
  inter  across the chunks   H_{c+1} = exp(cum_Q of chunk c) H_c + S_c
         and back in         Y_off = exp(cum) * (C H_c^T)

and `y = Y_diag + Y_off + D x`. Plain `jax.numpy`: batched `einsum`s that
XLA compiles, gradients by JAX's differentiation of them (under the layer's
checkpoint like every other layer). No Pallas kernel: `L` is written out, a
[Q, Q] float32 block a head and chunk, and the traffic that costs is what a
kernel for this scan would save.

Held to: `a`, `cum`, every `exp` and the state in float32; `L` from the
DIFFERENCE of running sums (never a quotient of exponentials: a chunk that
decays by e^-20 has no inf and no nan in it, forward or backward); the
products' operands in `x`'s dtype with float32 accumulation; a length that
is no multiple of `Q` padded with `dt = 0` rows, which move no state, and
cut off again; the `G` groups of `B` and `C` read by their heads through an
`einsum` index, never copied `H / G` times.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax

def _count(chunks: int, layer: str | None) -> None:
    """`oobleck_ssd_scans_total`: counted where the scan is built, once a
    scan of every program traced (not once a step; its three parts, intra,
    state and inter, are built together and are not told apart), and
    `oobleck_ssd_chunks{layer}`, the chunks a sequence of the last traced
    call."""
    from oobleck_tpu.utils import metrics

    reg = metrics.registry()
    reg.counter(
        "oobleck_ssd_scans_total",
        "Chunked state-space scans built into traced programs").inc()
    reg.gauge(
        "oobleck_ssd_chunks",
        "Chunks a sequence of the LAST traced state-space scan was cut "
        "into, by layer").set(chunks, layer=str(layer))


@jax.named_scope("ssd")
def ssd_scan(x: jax.Array, dt: jax.Array, a_neg: jax.Array, b: jax.Array,
             c: jax.Array, d_skip: jax.Array, *, chunk: int,
             layer: str | None = None) -> jax.Array:
    """x [B, S, H, P]; dt [B, S, H] (after its softplus); a_neg [H] (A, a
    negative scalar a head); b, c [B, S, G, N] with G dividing H (head h
    reads group h // (H / G)); d_skip [H]. Returns y [B, S, H, P] in x's
    dtype."""
    f32 = jnp.float32
    bsz, seq, heads, p = x.shape
    groups, n = b.shape[2], b.shape[3]
    assert heads % groups == 0, (heads, groups)
    r = heads // groups
    nc = -(-seq // chunk)
    _count(nc, layer)
    pad = nc * chunk - seq
    if pad:
        rows = lambda t: jnp.pad(t, ((0, 0), (0, pad)) + ((0, 0),) * (t.ndim - 2))
        x, dt, b, c = rows(x), rows(dt), rows(b), rows(c)
    dtype = x.dtype
    dt = dt.astype(f32)
    xg = x.reshape(bsz, nc, chunk, groups, r, p)
    bg = b.reshape(bsz, nc, chunk, groups, n)
    cg = c.reshape(bsz, nc, chunk, groups, n)
    dtg = dt.reshape(bsz, nc, chunk, groups, r)
    # Heads before positions: the [Q, Q] blocks are the minor dimensions.
    per_head = lambda t: jnp.moveaxis(t, 2, -1)            # [B, nc, G, R, Q]
    per_row = lambda t: jnp.moveaxis(t, -1, 2)             # [B, nc, Q, G, R]
    cum = jnp.cumsum(
        per_head(dtg) * a_neg.astype(f32).reshape(groups, r, 1), axis=-1)
    total = cum[..., -1]                                   # [B, nc, G, R]
    x_dt = (xg.astype(f32) * dtg[..., None]).astype(dtype)

    # intra: the chunk's own positions, through L.
    i = jnp.arange(chunk)
    decay = jnp.exp(jnp.where(i[:, None] >= i[None, :],
                              cum[..., :, None] - cum[..., None, :],
                              -jnp.inf))                   # [B, nc, G, R, Q, Q]
    cb = jnp.einsum("bzign,bzjgn->bzgij", cg, bg, preferred_element_type=f32)
    y = jnp.einsum("bzgrij,bzjgrp->bzigrp",
                   (cb[:, :, :, None] * decay).astype(dtype), x_dt,
                   preferred_element_type=f32)

    # state: what a chunk adds to the state, decayed to the chunk's end.
    to_end = per_row(jnp.exp(total[..., None] - cum))      # [B, nc, Q, G, R]
    added = jnp.einsum(
        "bzjgrp,bzjgn->bzgrpn",
        (xg.astype(f32) * (to_end * dtg)[..., None]).astype(dtype), bg,
        preferred_element_type=f32)

    # inter: the state at every chunk's start, then its part of y.
    def step(state, chunk_in):
        decay_c, added_c = chunk_in
        return decay_c[..., None, None] * state + added_c, state

    _, starts = lax.scan(
        step, jnp.zeros((bsz, groups, r, p, n), f32),
        (jnp.moveaxis(jnp.exp(total), 1, 0), jnp.moveaxis(added, 1, 0)))
    starts = jnp.moveaxis(starts, 0, 1)                    # [B, nc, G, R, P, N]
    y = y + per_row(jnp.exp(cum))[..., None] * jnp.einsum(
        "bzign,bzgrpn->bzigrp", cg, starts.astype(dtype),
        preferred_element_type=f32)

    y = y + xg.astype(f32) * d_skip.astype(f32).reshape(groups, r, 1)
    return y.reshape(bsz, nc * chunk, heads, p)[:, :seq].astype(dtype)
