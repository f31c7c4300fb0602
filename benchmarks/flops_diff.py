"""The yardstick's arithmetic for differential attention's two softmaxes
over ONE set of values (`oobleck_tpu/ops/flash.differential_flash_attention`):
what the ALGORITHM needs for one layer's call over `batch` sequences of
`seq` positions and `pairs` paired heads, queries and keys `head_dim` wide,
values `2 head_dim` wide, causal.

Per pair and sequence the causal half holds S^2 / 2 (query, key) pairs, and
each of the two softmaxes r = 1, 2 needs

  forward    q_r k_r^T   2 d a pair;   P_r v   2 (2 d) a pair
  backward   dV, dP      2 (2 d) a pair each;  dQ, dK   2 d a pair each
             (the recomputed q_r k_r^T is the kernel's own cost)

so forward 2 x 6 d and backward 2 x 12 d operations a (query, key) pair.

Bytes, each operand once at 2 bytes, at the `pairs` heads the kernels are
handed (a grouped-query model repeats keys and values outside them): forward
read q1, q2, k1, k2 (d each) and v (2 d) ONCE, write a1, a2 (2 d each): 10 d
a position; backward read those and da1, da2, write dq1, dq2, dk1, dk2 and
dv: 20 d a position.

The same work whatever implements it: two calls of a one-softmax kernel
that pad d = 64 to the 128 lanes issue twice the score products counted
here and read v twice; both are the implementation's cost, and neither can
pass 100 %.
"""

from __future__ import annotations


def diff_attention_fwd(batch: int, pairs: int, seq: int, head_dim: int,
                       dtype_bytes: int = 2) -> tuple[float, float]:
    ops = 2 * 6.0 * head_dim * (seq * seq / 2.0) * batch * pairs
    nbytes = 10.0 * head_dim * batch * pairs * seq * dtype_bytes
    return ops, nbytes


def diff_attention_bwd(batch: int, pairs: int, seq: int, head_dim: int,
                       dtype_bytes: int = 2) -> tuple[float, float]:
    ops = 2 * 12.0 * head_dim * (seq * seq / 2.0) * batch * pairs
    nbytes = 20.0 * head_dim * batch * pairs * seq * dtype_bytes
    return ops, nbytes
