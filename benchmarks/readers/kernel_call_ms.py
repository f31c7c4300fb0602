"""Mean device time of one call of a kernel over the traced window, in ms:
all the time in operations whose name holds `match` (the kernel's
`pallas_call` name, e.g. `%flash_bwd_dqkv.`) over their number. No operation
that matches: nothing to read.
"""


def read(data: dict, *, match: str) -> float | None:
    trace = data.get("trace")
    if not trace:
        return None
    spent = calls = 0
    for name, (secs, n) in trace["time_by_name"].items():
        if match in name:
            spent += secs
            calls += n
    if calls <= 0:
        return None
    return spent / calls * 1e3
