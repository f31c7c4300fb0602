"""Model FLOP/s utilization of the traced run's window for a LOOPED model,
in % of the chip's bf16 peak: `mfu_pct.py`'s twin over
`flops_looped.py`'s count (passes x the repeated blocks' matrices + exits x
the head's, causal attention a block visit; no recompute, no lookup), over
all the window's time: the share of the whole step.

A configuration that states no passes (`total_ut_steps`), or a runner that
hands no `train`: nothing to read.
"""

from benchmarks import flops, flops_looped

KEYS = ("total_ut_steps", "num_hidden_layers", "hidden_size",
        "num_attention_heads", "num_key_value_heads", "head_dim",
        "intermediate_size", "vocab_size")


def read(data: dict) -> float | None:
    t, config = data.get("train"), data.get("config") or {}
    if not t or any(k not in config for k in KEYS):
        return None
    per_token = flops_looped.from_config(config, t["seq_len"])
    # tokens_per_s is already per chip.
    return 100.0 * flops.mfu(t["tokens_per_s"], per_token, 1,
                             data["device"]["kind"])
