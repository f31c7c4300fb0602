"""The yardstick's arithmetic for routed experts: what the ALGORITHM needs.

A routed SwiGLU layer over `rows` (token, slot) pairs held on this chip,
experts of width `hidden` x `inter`, `experts` of them held. Training
needs nine grouped products per layer and microbatch:

  forward    rows x W1, rows x W3   [rows, hidden] x [hidden, inter]
             act  x W2              [rows, inter]  x [inter, hidden]
  backward   dX of each of the three (the same shapes, W transposed)
             dW of each of the three (rows^T x rows -> [experts, ., .])

Each is 2 * rows * hidden * inter operations. A forward or dX product
moves, at least, its row operand and its row result once and every held
expert's matrix once a call, at the operands' 2 bytes: rows are not padded
to tiles here, nor experts' matrices counted at the 4 bytes the program
stores them in -- both are the kernels' cost, not the model's. A dW
product moves its two row operands at 2 bytes and, where its matrices
would stand, the float32 RUNNING SUM of the gradient over the step's
microbatches, read and written once a call (`SUM_BYTES` = 8 an element of
`[experts, hidden, inter]`): gradient accumulation is the algorithm's, and
since PR 42 the sum is the dW kernel's third operand and its result, so its
time holds that traffic. Products recomputed under remat are not counted.
"""

from __future__ import annotations

from benchmarks import flops

PRODUCTS_FORWARD = 3
PRODUCTS_BACKWARD = 6
PRODUCTS_DW = 3                  # of the six backward products
SUM_BYTES = 8                    # a float32 read and a float32 written


def grouped_product(rows: float, hidden: int, inter: int, experts: int,
                    dtype_bytes: int = 2) -> tuple[float, float]:
    """(operations, bytes) of ONE forward or dX product."""
    ops = 2.0 * rows * hidden * inter
    nbytes = (rows * (hidden + inter) + float(experts) * hidden * inter
              ) * dtype_bytes
    return ops, nbytes


def grouped_product_dw(rows: float, hidden: int, inter: int, experts: int,
                       dtype_bytes: int = 2) -> tuple[float, float]:
    """(operations, bytes) of ONE dW product: its two row operands, and the
    float32 running sum it adds to."""
    ops, _ = grouped_product(rows, hidden, inter, experts)
    nbytes = (rows * (hidden + inter) * dtype_bytes
              + float(experts) * hidden * inter * SUM_BYTES)
    return ops, nbytes


def layer_train_seconds(rows: float, hidden: int, inter: int, experts: int,
                        device_kind: str, products: int, dw: int) -> float:
    """The least time one chip could take for `products` grouped products
    over `rows` pairs, `dw` of them dW: each product the larger of
    operations over peak and bytes over bandwidth."""
    one, _ = flops.roofline_seconds(
        *grouped_product(rows, hidden, inter, experts), device_kind)
    one_dw, _ = flops.roofline_seconds(
        *grouped_product_dw(rows, hidden, inter, experts), device_kind)
    return (products - dw) * one + dw * one_dw


def routed_layer_train_seconds(rows: float, hidden: int, inter: int,
                               experts: int, device_kind: str) -> float:
    """The nine products of one routed SwiGLU layer, three of them dW."""
    return layer_train_seconds(rows, hidden, inter, experts, device_kind,
                               PRODUCTS_FORWARD + PRODUCTS_BACKWARD,
                               PRODUCTS_DW)
