"""The windowed flash kernels' share of their roofline over the traced
window, in %.

`flash_geometry_roofline_pct.py`'s twin for attention under a sliding
window: the least time the chip could take for the band's pairs the traced
window's microbatches REQUIRE (per microbatch and WINDOWED layer one call
of each function in `needed`, from `benchmarks/flops_window.py`: heads, head
width and the window are the configuration's `num_attention_heads`,
`head_dim` and `sliding_window_size`; the sequence length, the microbatch,
the microbatches run and the windowed layers held, `window_layers`, the
runner's `train`) over ALL the device time in operations whose name holds
`match` (`%flash_swa_fwd.`, `%flash_swa_bwd_`), recomputed forwards
included in the time and not in the need. Keys and values are counted at
the heads the kernel is handed (a grouped-query model repeats them outside
it). A program without those kernels, a runner that names no windowed
layers, a configuration without a window: nothing to read.
"""

from benchmarks import flops, flops_window

GEOMETRY = ("num_attention_heads", "head_dim", "sliding_window_size")


def read(data: dict, *, match: str, needed: list) -> float | None:
    trace, t, config = data.get("trace"), data.get("train"), data.get("config")
    if not trace or not t or not t.get("window_layers") or not config or any(
            k not in config for k in GEOMETRY):
        return None
    spent = sum(secs for name, (secs, _) in trace["time_by_name"].items()
                if match in name)
    if spent <= 0:
        return None
    heads, head_dim, window = (config[k] for k in GEOMETRY)
    least = 0.0
    for fn in needed:
        ops, nbytes = getattr(flops_window, fn)(
            t["microbatch_size"], heads, t["seq_len"], head_dim, window)
        least += flops.roofline_seconds(ops, nbytes,
                                        data["device"]["kind"])[0]
    return 100.0 * least * t["microbatches_run"] * t["window_layers"] / spent
