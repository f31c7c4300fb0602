"""One of the state-space scan's kernels' share of its roofline over the
traced window, in %.

`mla_roofline_pct.py`'s twin for the Mamba-2 chunked scan: the least time
the chip could take for the scans the window's microbatches REQUIRE (per
microbatch and `M` layer one call of `needed`, a function of
`benchmarks/flops_ssd.py`: the larger of operations over peak FLOP/s and
bytes over peak bytes/s; heads, head width, groups, state and chunk are the
configuration's `mamba_num_heads`, `mamba_head_dim`, `n_groups`,
`ssm_state_size` and `chunk_size`, the `M` layers held the `M`s of its
`hybrid_override_pattern`; the sequence length, the microbatch and the
microbatches run the runner's `train`) over ALL the device time in
operations whose name holds `match` (`%ssd_fwd.`, `%ssd_bwd.`). The layer's
checkpoint keeps what the forward kernel wrote, so a step calls each once a
layer and microbatch; a program that called one twice would read half. A
configuration without those keys or without an `M` layer, or a trace
without the kernel: nothing to read.
"""

from benchmarks import flops, flops_ssd

GEOMETRY = ("mamba_num_heads", "mamba_head_dim", "n_groups",
            "ssm_state_size", "chunk_size")


def read(data: dict, *, match: str, needed: str) -> float | None:
    trace, t, config = data.get("trace"), data.get("train"), data.get("config")
    if not trace or not t or not config or any(k not in config
                                               for k in GEOMETRY):
        return None
    layers = str(config.get("hybrid_override_pattern", "")).count("M")
    spent = sum(secs for name, (secs, _) in trace["time_by_name"].items()
                if match in name)
    if spent <= 0 or not layers:
        return None
    ops, nbytes = getattr(flops_ssd, needed)(
        t["microbatch_size"], t["seq_len"], *(config[k] for k in GEOMETRY))
    least, _ = flops.roofline_seconds(ops, nbytes, data["device"]["kind"])
    return 100.0 * least * t["microbatches_run"] * layers / spent
