"""Model FLOP/s utilization of the traced run's window, in % of the chips'
bf16 peak: required operations only (6N + causal attention, recompute not
counted; `benchmarks/flops.py`), over all the window's time."""

from benchmarks import flops


def read(data: dict) -> float | None:
    t = data.get("train")
    if not t:
        return None
    per_token = flops.train_flops_per_token(
        t["n_params"], t["seq_len"], num_layers=t["num_layers"],
        hidden_size=t["hidden_size"])
    # tokens_per_s is already per chip.
    return 100.0 * flops.mfu(t["tokens_per_s"], per_token, 1,
                             data["device"]["kind"])
