"""The latent-attention kernels' share of their roofline over the traced
window, in %.

`kernel_roofline_pct.py`'s twin for latent attention (MLA): the least time
the chip could take for the attention the window's microbatches REQUIRE
(per microbatch and layer one call of each function in `needed`, from
`benchmarks/flops_mla.py`: scores `qk_nope_head_dim + qk_rope_head_dim`
wide, values `v_head_dim` wide, the shared rotary key once a position;
the widths are the configuration's) over ALL the device time in operations
whose name holds `match` (`%flash_mla_fwd.`, `%flash_mla_bwd_`), recomputed
forwards included in the time and not in the need. A program without those
kernels, or a configuration without those widths: nothing to read.
"""

from benchmarks import flops, flops_mla

WIDTHS = ("qk_nope_head_dim", "qk_rope_head_dim", "v_head_dim")


def read(data: dict, *, match: str, needed: list) -> float | None:
    trace, t, config = data.get("trace"), data.get("train"), data.get("config")
    if not trace or not t or not config or any(w not in config
                                               for w in WIDTHS):
        return None
    spent = sum(secs for name, (secs, _) in trace["time_by_name"].items()
                if match in name)
    if spent <= 0:
        return None
    least = 0.0
    for fn in needed:
        ops, nbytes = getattr(flops_mla, fn)(
            t["microbatch_size"], t["num_heads"], t["seq_len"],
            *(config[w] for w in WIDTHS))
        least += flops.roofline_seconds(ops, nbytes,
                                        data["device"]["kind"])[0]
    return 100.0 * least * t["microbatches_run"] * t["num_layers"] / spent
