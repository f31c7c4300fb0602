"""The yardstick's arithmetic for the Mamba-1 recurrence (the selective
scan, `oobleck_tpu/ops/sscan.py`): what the ALGORITHM needs for one call
over `batch` sequences of `seq` positions, `channels` channels of `state`
states each, walked in chunks of `chunk`.

    h_t[c, n] = exp(dt_t[c] A[c, n]) h_{t-1}[c, n] + dt_t[c] B_t[n] x_t[c]
    y_t[c]    = sum_n C_t[n] h_t[c, n] + D[c] x_t[c]

Nothing here is a matrix product; an operation is one multiply, add or
exponential, counted once:

  forward    a (position, channel, state): dt A, its exp, the decay times
             h, B times dt x, their sum, C times h, the sum over the
             states: 7. A (position, channel): dt x, D x, the sum: 3.
  backward   a (position, channel, state): C dy and its sum into dh; dy h
             and its sum over the channels (dC); dh times dt x and its sum
             (dB); dh B and its sum over the states (d of dt x); dt A, its
             exp, dh times the decay, times h_{t-1}; that times A and its
             sum over the states (d dt), times dt and its sum over the
             positions (dA): 16. A (position, channel): dx from d (dt x)
             and D dy, d dt from d (dt x) x, dD from dy x: 7. The states
             made again inside a chunk are the implementation's own cost
             and are not counted.

Bytes, each operand once at the dtypes a call is handed: x and y (dy, dx)
at `x_bytes`, dt (d dt) float32, a (position, channel); B, C (dB, dC)
float32 a (position, state); the state at every chunk's START, float32,
written by the forward and read by the backward (a training step keeps it:
that is the algorithm where the sequence is walked in chunks once each
way); A (dA) and D (dD) once.

The same work whatever implements it: a kernel that writes a channel tile's
part of dB and dC for the tiles to be summed outside moves more bytes than
counted here; that is the kernel's cost, and it cannot pass 100 %. The
operations are held against the chip's bf16 matrix peak, which the vector
units cannot reach: a call of this scan is bound by its bytes on that
count, and that is the bound it is read against.
"""

from __future__ import annotations

F32 = 4


def _sizes(batch: int, seq: int, channels: int, state: int, chunk: int
           ) -> tuple[float, float, float]:
    """(elements of x, elements of B, bytes of the chunk-start states)."""
    chunks = -(-seq // chunk)
    return (float(batch) * seq * channels, float(batch) * seq * state,
            float(batch) * chunks * channels * state * F32)


def scan_fwd(batch: int, seq: int, channels: int, state: int, chunk: int,
             x_bytes: int = 2) -> tuple[float, float]:
    """Read x, dt, B, C, A, D; write y and the states."""
    x, b, starts = _sizes(batch, seq, channels, state, chunk)
    ops = x * (7.0 * state + 3.0)
    nbytes = (x * (2 * x_bytes + F32) + 2 * b * F32 + starts
              + channels * (state + 1) * F32)
    return ops, nbytes


def scan_bwd(batch: int, seq: int, channels: int, state: int, chunk: int,
             x_bytes: int = 2) -> tuple[float, float]:
    """Read x, dt, dy, B, C, A, D and the states; write dx, d dt, dB, dC,
    dA, dD."""
    x, b, starts = _sizes(batch, seq, channels, state, chunk)
    ops = x * (16.0 * state + 7.0)
    nbytes = (x * (3 * x_bytes + 2 * F32) + 4 * b * F32 + starts
              + 2 * channels * (state + 1) * F32)
    return ops, nbytes
