"""The yardstick's arithmetic: peaks, model FLOPs, kernel ops and bytes.

Copied from `oobleck_tpu/parallel/train.py` (`estimate_flops_per_token`,
`PEAK_BF16_FLOPS`, `mfu_estimate`) so that a later change to the program
cannot move what its speed is measured against; `tests/benchmarks` checks
that the two still agree. Nothing here imports the program.
"""

from __future__ import annotations

# Published peaks of ONE chip, keyed by `jax.Device.device_kind`.
# Source: Google Cloud TPU documentation, per-generation system
# architecture pages ("TPU v5e": 197 TFLOP/s bf16, 16 GB HBM2e at 819 GB/s;
# v4 275 TFLOP/s, 1228 GB/s; v5p 459 TFLOP/s, 2765 GB/s; v6e 918 TFLOP/s,
# 1640 GB/s).
PEAKS = {
    "TPU v4": {"bf16_flops": 275e12, "hbm_bytes_per_s": 1228e9},
    "TPU v5 lite": {"bf16_flops": 197e12, "hbm_bytes_per_s": 819e9},
    "TPU v5e": {"bf16_flops": 197e12, "hbm_bytes_per_s": 819e9},
    "TPU v5": {"bf16_flops": 459e12, "hbm_bytes_per_s": 2765e9},
    "TPU v5p": {"bf16_flops": 459e12, "hbm_bytes_per_s": 2765e9},
    "TPU v6 lite": {"bf16_flops": 918e12, "hbm_bytes_per_s": 1640e9},
    "TPU v6e": {"bf16_flops": 918e12, "hbm_bytes_per_s": 1640e9},
}


def peaks(device_kind: str) -> dict:
    """Peaks of one chip of this kind. An unknown kind is an error, not a
    default: a share of a guessed peak is a wrong number."""
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise KeyError(
            f"no peaks known for device kind {device_kind!r}; add it to "
            "benchmarks/flops.py::PEAKS with its source") from None


def train_flops_per_token(n_params: int, seq_len: int, *, num_layers: int,
                          hidden_size: int) -> float:
    """FLOPs the forward and backward passes REQUIRE per trained token:
    6N for the matmuls plus the causal-attention term (2 S E per layer
    forward, halved by causality, times 3 for fwd + bwd). Operations
    recomputed under remat are not counted."""
    return 6.0 * n_params + 6.0 * (num_layers * hidden_size * seq_len)


def mfu(tokens_per_s: float, flops_per_token: float, n_chips: int,
        device_kind: str) -> float:
    """Required FLOP/s over the chips' bf16 peak (a fraction, not %)."""
    return flops_per_token * tokens_per_s / (
        n_chips * peaks(device_kind)["bf16_flops"])


def roofline_seconds(ops: float, nbytes: float, device_kind: str
                     ) -> tuple[float, str]:
    """The least time one chip could take for `ops` bf16 operations over
    `nbytes` of HBM traffic, and which of the two bounds it."""
    p = peaks(device_kind)
    t_ops = ops / p["bf16_flops"]
    t_bytes = nbytes / p["hbm_bytes_per_s"]
    return (t_ops, "compute") if t_ops >= t_bytes else (t_bytes, "memory")


# -- kernels ------------------------------------------------------------ #
# Ops and bytes the ALGORITHM needs per call, at the published head_dim
# (80 for gpt3-2.7b) -- not at the 128 lanes a kernel may pad it to: the
# padding is the kernel's cost, not the model's.

def causal_attention_fwd(batch: int, heads: int, seq: int, head_dim: int,
                         dtype_bytes: int = 2) -> tuple[float, float]:
    """Q K^T and P V over the causal half: 2 matmuls of 2 S^2 d each,
    halved. Bytes: read q, k, v, write o, once."""
    ops = 2 * (2.0 * seq * seq * head_dim) / 2 * batch * heads
    nbytes = 4.0 * batch * heads * seq * head_dim * dtype_bytes
    return ops, nbytes


def causal_attention_bwd(batch: int, heads: int, seq: int, head_dim: int,
                         dtype_bytes: int = 2) -> tuple[float, float]:
    """dV, dP, dQ, dK: the 4 matmuls the gradient needs (the recomputed
    Q K^T is the kernel's own cost and is not counted), causal half.
    Bytes: read q, k, v, o, do; write dq, dk, dv."""
    ops = 4 * (2.0 * seq * seq * head_dim) / 2 * batch * heads
    nbytes = 8.0 * batch * heads * seq * head_dim * dtype_bytes
    return ops, nbytes

