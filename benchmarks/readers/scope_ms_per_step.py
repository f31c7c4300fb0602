"""Device busy time, per training step in ms, of the operations of one XLA
module that were built under one `jax.named_scope`.

A TPU trace's events carry an operation's HLO name (`%fusion.123`) and no
JAX name stack (`benchmarks/trace_detail.py`), so a scope cannot be read
from the trace alone. The compiled program has both: every instruction of
its text names the scope it was built under (`metadata={op_name=
"jit(bwd)/.../mamba/ssd/dot_general"}`; a fusion carries its root's). A
runner that wants its model's parts timed hands that table over as
`data["scopes"] = {module: {"%fusion.123": op_name, ...}}`, made by
`scopes_of_text` from the text of the very executable the window ran
(`runners/train_nemotron_h.py::backward_scopes`), and this reader unites
the module's operations whose `op_name` has `scope` as one of its path
components (`.../ssd/...`, `jvp(mamba)/...`) into busy intervals. A `while`
and the operations of its body are both events of the trace; the union
counts their time once.

Nothing to read, and no error: a runner that hands no table over (every
other cell; the parent of the PR that brought this), a trace without the
module, no operation under the scope.
"""

import re

from benchmarks import trace_detail, trace_reduce

_INSTRUCTION = re.compile(
    r'^\s*(?:ROOT )?(%[\w.\-]+) = [^\n]*?metadata=\{[^}\n]*?op_name="([^"]*)"',
    re.M)


def scopes_of_text(hlo_text: str) -> dict[str, str]:
    """`{"%name": op_name}` of every instruction of a compiled module's
    text that says where it was built (names are unique in a module)."""
    return {name if name.startswith("%") else "%" + name: op_name
            for name, op_name in _INSTRUCTION.findall(hlo_text)}


def read(data: dict, *, module: str | None = None,
         scope: str | None = None) -> float | None:
    table = (data.get("scopes") or {}).get(module)
    detail = trace_detail.for_data(data)
    steps = trace_detail.window_steps(data)
    if not table or not detail or not steps:
        return None
    part = re.compile(rf"(?:^|[/(]){re.escape(scope)}(?:[/)]|$)")
    mine = [op for op in trace_detail.ops_by_module(detail).get(module, [])
            if part.search(table.get(op[0].split(" ", 1)[0], ""))]
    if not mine:
        return None
    busy_ns = sum(e - s for s, e in trace_reduce.busy_intervals(mine))
    return busy_ns / 1e6 / steps
