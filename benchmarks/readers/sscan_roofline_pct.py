"""One of the selective scan's kernels' share of its roofline over the
traced window, in %.

`ssd_roofline_pct.py`'s twin for the Mamba-1 recurrence: the least time the
chip could take for the scans the window's microbatches REQUIRE (per
microbatch and Mamba-1 layer one call of `needed`, a function of
`benchmarks/flops_sscan.py`: the larger of operations over peak FLOP/s and
bytes over peak bytes/s; channels are the configuration's `hidden_size`
times `mamba_expand`, states and chunk its `mamba_d_state` and
`mamba_chunk`, the layers held the `mamba` and `mamba_source` entries of its
`layer_kinds`; the sequence length, the microbatch and the microbatches run
the runner's `train`) over ALL the device time in operations whose name
holds `match` (`%sscan_fwd.`, `%sscan_bwd.`). The layer's checkpoint keeps
what the forward kernel wrote, so a step calls each once a layer and
microbatch; a program that called one twice would read half. A
configuration without those keys or without such a layer, or a trace
without the kernel: nothing to read.
"""

from benchmarks import flops, flops_sscan

GEOMETRY = ("hidden_size", "mamba_expand", "mamba_d_state", "mamba_chunk",
            "layer_kinds")


def read(data: dict, *, match: str, needed: str) -> float | None:
    trace, t, config = data.get("trace"), data.get("train"), data.get("config")
    if not trace or not t or not config or any(k not in config
                                               for k in GEOMETRY):
        return None
    layers = sum(k in ("mamba", "mamba_source") for k in config["layer_kinds"])
    spent = sum(secs for name, (secs, _) in trace["time_by_name"].items()
                if match in name)
    if spent <= 0 or not layers:
        return None
    ops, nbytes = getattr(flops_sscan, needed)(
        t["microbatch_size"], t["seq_len"],
        config["hidden_size"] * config["mamba_expand"],
        config["mamba_d_state"], config["mamba_chunk"])
    least, _ = flops.roofline_seconds(ops, nbytes, data["device"]["kind"])
    return 100.0 * least * t["microbatches_run"] * layers / spent
