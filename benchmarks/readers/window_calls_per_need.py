"""Calls of a windowed-attention kernel in the traced window over the calls
the window's work REQUIRES: one per microbatch and WINDOWED layer
(`kernel_calls_per_need.py` divides by the runner's `num_layers`, which in
a model of two kinds of attention counts the full-attention layers alone;
this one divides by `window_layers`). 1.0 is a kernel run once where it is
needed; the forward kernel under remat reads 2.0 where the layer's
checkpoint did not keep its outputs. No such operation, or a runner that
names no windowed layers: nothing to read.
"""


def read(data: dict, *, match: str) -> float | None:
    trace, t = data.get("trace"), data.get("train")
    if not trace or not t or not t.get("window_layers"):
        return None
    calls = sum(n for name, (_, n) in trace["time_by_name"].items()
                if match in name)
    needed = t["microbatches_run"] * t["window_layers"]
    if calls <= 0 or needed <= 0:
        return None
    return calls / needed
