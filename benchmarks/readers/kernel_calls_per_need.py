"""Calls of a kernel in the traced window over the calls the window's work
REQUIRES: one per microbatch and layer. 1.0 is a kernel run once where it
is needed; the forward flash kernel under remat reads more, because the
backward program runs it again (`match` is the kernel's `pallas_call` name
as the operation's name carries it, e.g. `%flash_fwd.`). No operation that
matches: nothing to read.
"""


def read(data: dict, *, match: str) -> float | None:
    trace, t = data.get("trace"), data.get("train")
    if not trace or not t:
        return None
    calls = sum(n for name, (_, n) in trace["time_by_name"].items()
                if match in name)
    needed = t["microbatches_run"] * t["num_layers"]
    if calls <= 0 or needed <= 0:
        return None
    return calls / needed
