"""The yardstick's arithmetic for the Mamba-2 chunked scan (state-space
duality): what the ALGORITHM needs for one call over `batch` sequences of
`seq` positions in chunks of `chunk`, `heads` heads of `head_dim` in
`groups` groups that share B and C, a state of `state` a head and column.

With Q the chunk, N the state, W = (heads / groups) x head_dim the columns
of a group, every product below is counted once a (sequence, group, chunk),
2 operations a multiply-add:

  forward    C B^T            2 Q Q N   (the group's, shared by its heads)
             M x~             2 Q Q W   (2 Q Q head_dim a head)
             C H^T, B^T x~    2 Q N W each
  backward   C B^T, dS B, dS^T C                            2 Q Q N each
             M x~ again (y is not read back), M^T dY,
             dY x~^T                                        2 Q Q W each
             C H^T, B dH^T, (dY e^cum) H, (x~ e^..) dH,
             C^T (dY e^cum)                                 2 Q N W each

Bytes, each operand once: x, y (and dY, dx) at `dtype_bytes` a (position,
head, column); B, C (and dB, dC) at `dtype_bytes` a (position, group,
state); the state at every chunk's START, float32, written by the forward
and read by the backward (a training step keeps it: that is the algorithm
where the sequence is walked in chunks once each way); dt and its running
sum, float32 a (position, head), read by both, and the two sums a position
the backward hands back for them.

The same work whatever implements it: a kernel whose two heads of 64 both
multiply a whole 128-lane tile issues twice the Q Q W products counted
here, one that reads the running sums in two layouts reads them twice;
both are the kernel's cost, and neither can pass 100 %.
"""

from __future__ import annotations

STATE_BYTES = 4
SCALAR_BYTES = 4


def _sizes(batch: int, seq: int, heads: int, head_dim: int, groups: int,
           state: int, chunk: int) -> tuple[float, float, float, float]:
    """(steps, columns of a group, elements of x, elements of B)."""
    steps = float(batch) * groups * -(-seq // chunk)
    width = heads // groups * head_dim
    return (steps, width, float(batch) * seq * heads * head_dim,
            float(batch) * seq * groups * state)


def _states(steps: float, state: int, width: float) -> float:
    return steps * state * width * STATE_BYTES


def scan_fwd(batch: int, seq: int, heads: int, head_dim: int, groups: int,
             state: int, chunk: int, dtype_bytes: int = 2
             ) -> tuple[float, float]:
    """Read x, B, C, dt and its running sum; write y and the states."""
    steps, width, x, b = _sizes(batch, seq, heads, head_dim, groups, state,
                                chunk)
    ops = steps * 2.0 * chunk * (chunk * state + chunk * width
                                 + 2 * state * width)
    nbytes = ((2 * x + 2 * b) * dtype_bytes + _states(steps, state, width)
              + 2 * SCALAR_BYTES * float(batch) * seq * heads)
    return ops, nbytes


def scan_bwd(batch: int, seq: int, heads: int, head_dim: int, groups: int,
             state: int, chunk: int, dtype_bytes: int = 2
             ) -> tuple[float, float]:
    """Read x, dY, B, C, the states, dt and its running sum; write dx, dB,
    dC and a position's two sums."""
    steps, width, x, b = _sizes(batch, seq, heads, head_dim, groups, state,
                                chunk)
    ops = steps * 2.0 * chunk * (3 * chunk * state + 3 * chunk * width
                                 + 5 * state * width)
    nbytes = ((3 * x + 4 * b) * dtype_bytes + _states(steps, state, width)
              + 4 * SCALAR_BYTES * float(batch) * seq * heads)
    return ops, nbytes
