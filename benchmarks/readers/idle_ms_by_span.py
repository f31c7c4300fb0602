"""Idle time of the first device per training step, inside or outside one
of the program's spans, in ms.

`trace_reduce.attribute_gaps` names each idle gap after the innermost host
event over its middle, which is always a runtime thread's
(`DeferredTpuAllocator::Allocate`): true, and no help in saying where in a
step the device waited. Here the same gaps (between consecutive busy
intervals of the first device, `MIN_GAP_NS` or longer) are split by whether
their middle lies inside an occurrence of `span`: `engine.step` with
`inside` true is the device waiting while the host dispatches a step, with
`inside` false the device waiting between steps (bookkeeping, input
handshake). The two add up to all the idle time `trace_reduce` attributes.
A trace without the span: nothing to read.
"""

from benchmarks import trace_detail


def read(data: dict, *, span: str, inside: bool) -> float | None:
    detail = trace_detail.for_data(data)
    steps = trace_detail.window_steps(data)
    if not detail or not steps:
        return None
    spans = detail["host"].get(span)
    if not spans:
        return None
    total = 0.0
    i = 0
    for g0, g1 in trace_detail.first_device_gaps(detail):   # in time order
        mid = (g0 + g1) / 2
        while i < len(spans) and spans[i][0] + spans[i][1] < mid:
            i += 1
        covered = i < len(spans) and spans[i][0] <= mid
        if covered == bool(inside):
            total += g1 - g0
    return total / 1e6 / steps
