"""The yardstick's arithmetic for routed experts: what the ALGORITHM needs.

A routed SwiGLU layer over `rows` (token, slot) pairs held on this chip,
experts of width `hidden` x `inter`, `experts` of them held. Training
needs nine grouped products per layer and microbatch:

  forward    rows x W1, rows x W3   [rows, hidden] x [hidden, inter]
             act  x W2              [rows, inter]  x [inter, hidden]
  backward   dX of each of the three (the same shapes, W transposed)
             dW of each of the three (rows^T x rows -> [experts, ., .])

Each is 2 * rows * hidden * inter operations. Each moves, at least, its
row operand and its row result (or, for dW, its two row operands) once and
every held expert's matrix once a call, at the operands' 2 bytes: rows
are not padded to tiles here, nor experts' matrices counted at the 4 bytes
the program stores them in -- both are the kernels' cost, not the
model's. Products recomputed under remat are not counted.
"""

from __future__ import annotations

from benchmarks import flops

PRODUCTS_FORWARD = 3
PRODUCTS_BACKWARD = 6


def grouped_product(rows: float, hidden: int, inter: int, experts: int,
                    dtype_bytes: int = 2) -> tuple[float, float]:
    """(operations, bytes) of ONE of the nine products."""
    ops = 2.0 * rows * hidden * inter
    nbytes = (rows * (hidden + inter) + float(experts) * hidden * inter
              ) * dtype_bytes
    return ops, nbytes


def routed_layer_train_seconds(rows: float, hidden: int, inter: int,
                               experts: int, device_kind: str) -> float:
    """The least time one chip could take for the nine products of one
    routed layer over `rows` pairs: each product the larger of operations
    over peak and bytes over bandwidth."""
    ops, nbytes = grouped_product(rows, hidden, inter, experts)
    one, _ = flops.roofline_seconds(ops, nbytes, device_kind)
    return (PRODUCTS_FORWARD + PRODUCTS_BACKWARD) * one
