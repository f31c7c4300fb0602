"""The largest idle share among the chips of a traced part, in %.

`trace_reduce.reduce` gives the busy seconds averaged over the chips, which
is what the driver's idle share wants; a pipeline's bubble and a stage that
waits for its neighbour show on the chip that idles most. The runner hands
over each chip's busy seconds (`trace_reduce.busy_intervals` over the
chip's own operations) and the length of the part they were taken in
(`data["device_busy"] = {"window_s": s, "busy_s": {plane name: s}}`); this
is `max(1 - busy / window)`. No such table, one chip only, or a part of no
length: nothing to read (one chip's idle share is the driver's own).
"""


def read(data: dict) -> float | None:
    table = data.get("device_busy") or {}
    window, busy = table.get("window_s"), table.get("busy_s") or {}
    if not window or window <= 0 or len(busy) < 2:
        return None
    return 100.0 * max(1.0 - b / window for b in busy.values())
