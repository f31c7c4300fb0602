"""Differential attention's flash kernels' share of their roofline over the
traced window, in %.

`window_roofline_pct.py`'s twin for two softmaxes over one set of values:
the least time the chip could take for what the traced window's
microbatches REQUIRE (per microbatch and layer of differential attention
without a window one call of `needed`, a function of
`benchmarks/flops_diff.py`: the paired heads are half the configuration's
`num_attention_heads`, the width its `head_dim`, the layers held the
`full_source` and `cross` entries of its `layer_kinds`; the sequence
length, the microbatch and the microbatches run the runner's `train`) over
ALL the device time in operations whose name holds `match`
(`%flash_diff_fwd.`, `%flash_diff_bwd_dqkv.`: both softmaxes' calls).
Padding the 64-wide queries and keys to the lane and reading the values
twice are in the time and not in the need. A program without those kernels
or a configuration without those keys or layers: nothing to read.
"""

from benchmarks import flops, flops_diff

GEOMETRY = ("num_attention_heads", "head_dim", "layer_kinds")


def read(data: dict, *, match: str, needed: str) -> float | None:
    trace, t, config = data.get("trace"), data.get("train"), data.get("config")
    if not trace or not t or not config or any(k not in config
                                               for k in GEOMETRY):
        return None
    layers = sum(k in ("full_source", "cross") for k in config["layer_kinds"])
    spent = sum(secs for name, (secs, _) in trace["time_by_name"].items()
                if match in name)
    if spent <= 0 or not layers:
        return None
    ops, nbytes = getattr(flops_diff, needed)(
        t["microbatch_size"], config["num_attention_heads"] // 2,
        t["seq_len"], config["head_dim"])
    least, _ = flops.roofline_seconds(ops, nbytes, data["device"]["kind"])
    return 100.0 * least * t["microbatches_run"] * layers / spent
