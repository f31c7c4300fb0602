"""Ring attention: causal attention over a sequence-parallel mesh axis.

The reference has no long-context support at all (SURVEY §5 "Long-context /
sequence parallelism: absent") — this is green-field TPU capability: the
sequence dim is sharded over a mesh axis, K/V shards rotate around the ring
with `lax.ppermute` while each device folds every block into its local
queries' online-softmax state. HBM per device stays O(S/n · D) and the
permutes overlap with the block compute on ICI.

Must run inside a full-manual shard_map with `axis_name` manual. Causality is
handled by global position offsets: block (q_shard i, kv origin j) applies a
full/partial/empty mask depending on i vs j.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax

NEG_INF = -1e9


def ring_attention(q: jax.Array, k: jax.Array, v: jax.Array, *,
                   axis_name: str = "sp", scale: float | None = None,
                   remat: bool = True) -> jax.Array:
    """Causal attention with seq sharded over `axis_name`.

    q, k, v: [B, H, S_local, D] — this device's sequence shard.
    Returns [B, H, S_local, D], the attention output for the local queries
    over the *global* (causal-visible) sequence.
    """
    if scale is None:
        scale = q.shape[-1] ** -0.5
    n = lax.axis_size(axis_name)
    idx = lax.axis_index(axis_name)
    s_local = q.shape[2]
    qf = q.astype(jnp.float32)

    def block(qf, k, v, kv_rank):
        """Unnormalized local attention of qf against one K/V shard."""
        s = jnp.einsum("bhqd,bhkd->bhqk", qf, k.astype(jnp.float32)) * scale
        q_pos = idx * s_local + jax.lax.broadcasted_iota(jnp.int32, s.shape, 2)
        k_pos = kv_rank * s_local + jax.lax.broadcasted_iota(jnp.int32, s.shape, 3)
        s = jnp.where(q_pos >= k_pos, s, NEG_INF)
        m = jnp.max(s, axis=-1, keepdims=True)                     # [B,H,Ql,1]
        # Fully-masked rows (future blocks) produce m = NEG_INF; clamp so
        # exp() stays finite and their contribution is exactly zero.
        m = jnp.maximum(m, -1e30)
        p = jnp.exp(s - m)
        p = jnp.where(q_pos >= k_pos, p, 0.0)
        l = jnp.sum(p, axis=-1, keepdims=True)
        o = jnp.einsum("bhqk,bhkd->bhqd", p, v.astype(jnp.float32))
        return o, m, l

    if remat:
        block = jax.checkpoint(block)

    perm = [(i, (i + 1) % n) for i in range(n)]

    def step(carry, _):
        k, v, acc, m, l, rot = carry
        kv_rank = (idx - rot) % n
        o_b, m_b, l_b = block(qf, k, v, kv_rank)
        m_new = jnp.maximum(m, m_b)
        c_old = jnp.exp(m - m_new)
        c_new = jnp.exp(m_b - m_new)
        acc = acc * c_old + o_b * c_new
        l = l * c_old + l_b * c_new
        k = lax.ppermute(k, axis_name, perm)
        v = lax.ppermute(v, axis_name, perm)
        return (k, v, acc, m_new, l, rot + 1), None

    from oobleck_tpu.parallel.collectives import pvary_to

    # Carry init must match the compute's varying-axes type: everything q
    # varies over, plus the ring axis itself.
    vary = tuple(getattr(qf.aval, "vma", ()) or ()) + (axis_name,)
    acc0 = pvary_to(jnp.zeros(qf.shape, jnp.float32), vary)
    m0 = pvary_to(jnp.full((*qf.shape[:3], 1), -1e30, jnp.float32), vary)
    l0 = pvary_to(jnp.zeros((*qf.shape[:3], 1), jnp.float32), vary)
    (_, _, acc, _, l, _), _ = lax.scan(
        step, (k, v, acc0, m0, l0, jnp.int32(0)), None, length=n
    )
    return (acc / jnp.maximum(l, 1e-30)).astype(q.dtype)
