"""A statistic of one of the program's spans over the traced window, in ms.

The program's hot-path regions (`oobleck_tpu.obs.spans.region`) are events
of the host plane of the profiler's trace, one per occurrence, so a median
and a maximum can be read where the program's histograms keep a sum, a
count and coarse buckets only. `stat` is `p50`, `max` or `mean`. With
`minus`, each occurrence's duration is first reduced by the durations of
the `minus` spans that start inside it (`engine.step` less
`engine.loss_readback`: the host time a step needs before it blocks on the
device). No such span in the trace: nothing to read.
"""

import statistics

from benchmarks import trace_detail


def read(data: dict, *, span: str, stat: str,
         minus: str | None = None) -> float | None:
    detail = trace_detail.for_data(data)
    if not detail:
        return None
    spans = detail["host"].get(span)
    if not spans:
        return None
    durations = [d for _, d in spans]
    if minus is not None:
        inner = detail["host"].get(minus)
        if not inner:
            return None
        i = 0
        for k, (start, dur) in enumerate(spans):
            while i < len(inner) and inner[i][0] < start:
                i += 1
            while i < len(inner) and inner[i][0] < start + dur:
                durations[k] -= inner[i][1]
                i += 1
    value = {"p50": statistics.median, "max": max,
             "mean": statistics.fmean}[stat](durations)
    return value / 1e6
