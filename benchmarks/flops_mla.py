"""The yardstick's arithmetic for latent attention (MLA, as trained): what
the ALGORITHM needs at scores `d_nope + d_rope` wide and values `d_v` wide.

Per head and sequence, over the causal half of the [S, S] pairs:

  forward    Q K^T at d_nope + d_rope, P V at d_v: S^2 (d_qk + d_v)
             operations (2 a multiply-add, halved by the mask)
  backward   dQ and dK at d_qk, dV and dP at d_v: S^2 (2 d_qk + 2 d_v)
             (the recomputed Q K^T is the kernel's own cost, not counted)

Bytes, each operand once at the operands' 2 bytes: q (and dq) at d_qk a
head; k (and dk) at d_nope a head plus the rotary key ONCE a position, not
once a head (all heads share it); v, o (and do, dv) at d_v. The same work
whatever implements it: a kernel that pads 192 to 256, broadcasts the
shared key over the heads or visits whole tiles across the diagonal reads
a smaller share, and none can pass 100 %.
"""

from __future__ import annotations


def _rows(batch: int, heads: int, seq: int, d_nope: int, d_rope: int,
          d_v: int) -> tuple[float, float, float]:
    """Elements of (q, k, v) of one call."""
    q = float(batch) * heads * seq * (d_nope + d_rope)
    k = float(batch) * seq * (heads * d_nope + d_rope)
    v = float(batch) * heads * seq * d_v
    return q, k, v


def latent_attention_fwd(batch: int, heads: int, seq: int, d_nope: int,
                         d_rope: int, d_v: int,
                         dtype_bytes: int = 2) -> tuple[float, float]:
    """Read q, k, v, write o."""
    ops = float(seq) * seq * (d_nope + d_rope + d_v) * batch * heads
    q, k, v = _rows(batch, heads, seq, d_nope, d_rope, d_v)
    return ops, (q + k + 2 * v) * dtype_bytes


def latent_attention_bwd(batch: int, heads: int, seq: int, d_nope: int,
                         d_rope: int, d_v: int,
                         dtype_bytes: int = 2) -> tuple[float, float]:
    """Read q, k, v, o, do; write dq, dk, dv."""
    ops = float(seq) * seq * 2 * (d_nope + d_rope + d_v) * batch * heads
    q, k, v = _rows(batch, heads, seq, d_nope, d_rope, d_v)
    return ops, (2 * q + 2 * k + 4 * v) * dtype_bytes
