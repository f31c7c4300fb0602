"""The grouped-matmul kernels' share of their roofline over the traced
window, in %, for experts WITHOUT a gate.

`moe_gmm_roofline_pct.py`'s twin: the least time the chip could take for
the SIX grouped products (`benchmarks/flops_moe_ungated.py`: two forward,
four backward) of every routed layer and microbatch of the window, over ALL
the device time in operations whose name holds one of `match` (`%moe_gmm.`,
`%moe_tgmm.`), recomputed forward products included in the time and not in
the need. The rows a microbatch routes to the experts held here come from
the program's own counters, as there. A configuration whose experts have a
gate (`mlp_hidden_act` other than `relu2`), a program without those
counters, or a trace without those kernels: nothing to read.
"""

from benchmarks import flops_moe_ungated
from benchmarks.readers.moe_gmm_roofline_pct import _pairs_per_token_by_layer


def read(data: dict, *, match: list) -> float | None:
    trace, t, config = data.get("trace"), data.get("train"), data.get("config")
    if (not trace or not t or not config
            or config.get("mlp_hidden_act") != "relu2"):
        return None
    spent = sum(secs for name, (secs, _) in trace["time_by_name"].items()
                if any(m in name for m in match))
    per_layer = _pairs_per_token_by_layer()
    if spent <= 0 or not per_layer:
        return None
    tokens = t["microbatch_size"] * t["seq_len"]
    least = sum(flops_moe_ungated.routed_layer_train_seconds(
        share * tokens, config["hidden_size"],
        config["moe_intermediate_size"], config["num_experts_held"],
        data["device"]["kind"]) for share in per_layer)
    return 100.0 * least * t["microbatches_run"] / spent
