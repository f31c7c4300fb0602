"""The plain flash kernels' share of their roofline over the traced window,
in %, at a geometry the CONFIGURATION states.

`kernel_roofline_pct.py`'s twin for a model whose attention heads are no
`hidden_size // num_heads` (Nemotron-3-Nano: 32 heads of 128 beside a
hidden size of 2688): heads and head width are the configuration's
`num_attention_heads` and `head_dim`, the sequence length, the microbatch,
the microbatches run and the ATTENTION layers held (`num_layers`) the
runner's `train`. Per microbatch and attention layer one call of each
function in `needed` (`benchmarks/flops.py`), over ALL the device time in
operations whose name holds `match`, recomputed forwards included in the
time and not in the need. Keys and values are counted at the heads the
kernel is handed: a grouped-query model repeats them up to the query heads
OUTSIDE the kernel, so the kernel reads `num_attention_heads` of each. A
configuration without those two keys, or a trace without those kernels:
nothing to read.
"""

from benchmarks import flops

GEOMETRY = ("num_attention_heads", "head_dim")


def read(data: dict, *, match: str, needed: list) -> float | None:
    trace, t, config = data.get("trace"), data.get("train"), data.get("config")
    if not trace or not t or not config or any(k not in config
                                               for k in GEOMETRY):
        return None
    spent = sum(secs for name, (secs, _) in trace["time_by_name"].items()
                if match in name)
    if spent <= 0:
        return None
    heads, head_dim = (config[k] for k in GEOMETRY)
    least = 0.0
    for fn in needed:
        ops, nbytes = getattr(flops, fn)(
            t["microbatch_size"], heads, t["seq_len"], head_dim)
        least += flops.roofline_seconds(ops, nbytes,
                                        data["device"]["kind"])[0]
    return 100.0 * least * t["microbatches_run"] * t["num_layers"] / spent
