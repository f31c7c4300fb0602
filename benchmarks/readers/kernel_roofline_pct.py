"""The Pallas kernels' share of their roofline over the traced window, in %.

The least time the chip could take for the attention the window's
microbatches REQUIRE (per microbatch and layer one call of each function
in `needed`, from `benchmarks/flops.py`: the larger of operations over peak
FLOP/s and bytes over peak bytes/s, at the model's published head size)
over ALL the time the device trace shows in operations whose name holds
`match`. Nothing in the program names its kernels (`jax.named_scope` is
never called), so the forward, dq and dk/dv kernels cannot be told apart by
a stable name; what is stable is that a Pallas kernel is a custom call with
the target `tpu_custom_call`, and in the training step flash attention is
the only one. Forward calls repeated under remat add to the time, not to
what is required. No event that matches: nothing to read, no number.
"""

from benchmarks import flops


def read(data: dict, *, match: str, needed: list) -> float | None:
    trace, t = data.get("trace"), data.get("train")
    if not trace or not t:
        return None
    spent = sum(secs for name, (secs, _) in trace["time_by_name"].items()
                if match in name)
    if spent <= 0:
        return None
    head_dim = t["hidden_size"] // t["num_heads"]
    least = 0.0
    for fn in needed:
        ops, nbytes = getattr(flops, fn)(
            t["microbatch_size"], t["num_heads"], t["seq_len"], head_dim)
        least += flops.roofline_seconds(ops, nbytes,
                                        data["device"]["kind"])[0]
    return 100.0 * least * t["microbatches_run"] * t["num_layers"] / spent
