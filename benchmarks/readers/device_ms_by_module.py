"""Device busy time inside one named XLA module per training step, in ms.

Every jitted program of the training path is a named function, so its
module has a name of ours on the device plane: `jit_bwd`, `jit_grad_zero`,
`jit_optimizer_update` (and `jit_fwd` where a pipeline has more than one
stage: the last virtual stage's `jit_bwd` is the loss's value-and-gradient
since PR 30; no `jit_grad_add` since PR 33, the sum is `jit_bwd`'s own). The
operations of the first device
that ran in `module` (their `hlo_module` stat, else containment in the
module line; `benchmarks/trace_detail.py`) are united into busy intervals,
and their total is divided by the window's steps. A trace with no such
module (the name is new, or the program is not part of the cell): nothing
to read.
"""

from benchmarks import trace_detail, trace_reduce


def read(data: dict, *, module: str) -> float | None:
    detail = trace_detail.for_data(data)
    steps = trace_detail.window_steps(data)
    if not detail or not steps:
        return None
    mine = trace_detail.ops_by_module(detail).get(module)
    if not mine:
        return None
    busy_ns = sum(e - s for s, e in trace_reduce.busy_intervals(mine))
    return busy_ns / 1e6 / steps
