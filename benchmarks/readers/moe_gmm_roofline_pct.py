"""The grouped-matmul kernels' share of their roofline over the traced
window, in %.

The least time the chip could take for the nine grouped products
(`benchmarks/flops_moe.py`) of every routed layer and microbatch of the
window, over ALL the device time in operations whose name holds one of
`match` (`%moe_gmm.`, `%moe_tgmm.`: the kernels' `pallas_call` names),
recomputed forward products included in the time and not in the need; a
dW product's bytes hold the float32 running sum `moe_tgmm` reads and writes
(PR 42).

The rows a microbatch routes to the experts held here are not a constant
of the cell: they come from the program's own counters, which its routing
probe fills (`oobleck_moe_routed_pairs_total{layer}` over
`oobleck_moe_probed_tokens_total`: pairs a token, per routed layer). A
program without those counters, or a trace without those kernels: nothing
to read.
"""

from benchmarks import flops_moe


def _pairs_per_token_by_layer() -> list[float]:
    try:
        from oobleck_tpu.utils import metrics
    except ImportError:
        return []
    pairs, probed = [], 0.0
    for metric in metrics.registry().snapshot()["metrics"]:
        if metric["name"] == "oobleck_moe_routed_pairs_total":
            pairs = [s["value"] for s in metric["series"]]
        elif metric["name"] == "oobleck_moe_probed_tokens_total":
            probed = sum(s["value"] for s in metric["series"])
    return [p / probed for p in pairs] if probed > 0 else []


def read(data: dict, *, match: list) -> float | None:
    trace, t, config = data.get("trace"), data.get("train"), data.get("config")
    if not trace or not t or not config:
        return None
    spent = sum(secs for name, (secs, _) in trace["time_by_name"].items()
                if any(m in name for m in match))
    per_layer = _pairs_per_token_by_layer()
    if spent <= 0 or not per_layer:
        return None
    tokens = t["microbatch_size"] * t["seq_len"]
    least = sum(flops_moe.routed_layer_train_seconds(
        share * tokens, config["hidden_size"],
        config["moe_intermediate_size"], config["num_experts_held"],
        data["device"]["kind"]) for share in per_layer)
    return 100.0 * least * t["microbatches_run"] / spent
