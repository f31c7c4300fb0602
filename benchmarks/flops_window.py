"""The yardstick's arithmetic for causal attention under a sliding window:
what the ALGORITHM needs where query i sees key j iff 0 <= i - j < window.

Per head and sequence the band holds `S W - W (W - 1) / 2` (query, key)
pairs for `S >= W` (the first W queries see 1, 2, ... W keys, every later
one W); a window the sequence never reaches leaves the causal half with
its diagonal, `S (S + 1) / 2`. At 16384 positions and a window of 4096:
58,722,304 pairs of the causal 134.2 M (43.7 %).

  forward    Q K^T and P V over the band: 2 products of 2 d a pair
  backward   dV, dP, dQ, dK: 4 products of 2 d a pair (the recomputed
             Q K^T is the kernel's own cost, not counted)

Bytes as `flops.causal_attention_*` count them: every operand once at the
operands' 2 bytes (forward q, k, v, o; backward q, k, v, o, do, dq, dk,
dv), at the heads the kernel is handed. The same work whatever implements
it: a kernel that visits whole 512 x 512 blocks across the band's two
edges, or every causal block under a mask, reads a smaller share, and none
can pass 100 %.
"""

from __future__ import annotations


def band_pairs(seq: int, window: int) -> float:
    """(query, key) pairs a head and sequence under the window."""
    if seq >= window:
        return float(seq) * window - window * (window - 1) / 2.0
    return seq * (seq + 1) / 2.0


def window_attention_fwd(batch: int, heads: int, seq: int, head_dim: int,
                         window: int, dtype_bytes: int = 2
                         ) -> tuple[float, float]:
    """Read q, k, v, write o."""
    ops = 2 * 2.0 * band_pairs(seq, window) * head_dim * batch * heads
    nbytes = 4.0 * batch * heads * seq * head_dim * dtype_bytes
    return ops, nbytes


def window_attention_bwd(batch: int, heads: int, seq: int, head_dim: int,
                         window: int, dtype_bytes: int = 2
                         ) -> tuple[float, float]:
    """Read q, k, v, o, do; write dq, dk, dv."""
    ops = 4 * 2.0 * band_pairs(seq, window) * head_dim * batch * heads
    nbytes = 8.0 * batch * heads * seq * head_dim * dtype_bytes
    return ops, nbytes
