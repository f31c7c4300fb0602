"""The yardstick's arithmetic for routed experts WITHOUT a gate: what the
ALGORITHM needs.

A routed layer of experts `W2 relu(W1 x)^2` over `rows` (token, slot) pairs
held on this chip, experts of width `hidden` x `inter`, `experts` of them
held. Training needs six grouped products per layer and microbatch, where
a SwiGLU layer (`flops_moe.py`) needs nine:

  forward    rows x W1              [rows, hidden] x [hidden, inter]
             act  x W2              [rows, inter]  x [inter, hidden]
  backward   dX of each of the two (the same shapes, W transposed)
             dW of each of the two (rows^T x rows -> [experts, ., .])

Each is `flops_moe.grouped_product`: 2 * rows * hidden * inter operations,
its row operand and row result (for dW its two row operands) once and
every held expert's matrix once a call, at the operands' 2 bytes, at the
PUBLISHED width (1856 for Nemotron-3-Nano: not the 1920 a call may pad it
to, which is the kernel's cost). Products recomputed under remat are not
counted.
"""

from __future__ import annotations

from benchmarks import flops
from benchmarks.flops_moe import grouped_product

PRODUCTS_FORWARD = 2
PRODUCTS_BACKWARD = 4


def routed_layer_train_seconds(rows: float, hidden: int, inter: int,
                               experts: int, device_kind: str) -> float:
    """The least time one chip could take for the six products of one
    routed layer over `rows` pairs: each product the larger of operations
    over peak and bytes over bandwidth."""
    ops, nbytes = grouped_product(rows, hidden, inter, experts)
    one, _ = flops.roofline_seconds(ops, nbytes, device_kind)
    return (PRODUCTS_FORWARD + PRODUCTS_BACKWARD) * one
