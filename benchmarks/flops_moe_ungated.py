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
its row operand and row result once and every held expert's matrix once a
call, at the operands' 2 bytes; a dW product `flops_moe.grouped_product_dw`:
its two row operands and the float32 running sum of the gradient read and
written (PR 42); all at the PUBLISHED width (1856 for Nemotron-3-Nano: not
the 1920 a call may pad it to, which is the kernel's cost). Products
recomputed under remat are not counted.
"""

from __future__ import annotations

from benchmarks.flops_moe import layer_train_seconds

PRODUCTS_FORWARD = 2
PRODUCTS_BACKWARD = 4
PRODUCTS_DW = 2                  # of the four backward products


def routed_layer_train_seconds(rows: float, hidden: int, inter: int,
                               experts: int, device_kind: str) -> float:
    """The six products of one routed layer without a gate, two of them
    dW."""
    return layer_train_seconds(rows, hidden, inter, experts, device_kind,
                               PRODUCTS_FORWARD + PRODUCTS_BACKWARD,
                               PRODUCTS_DW)
