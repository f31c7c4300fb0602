"""How far the rows routed to the experts held here moved through the
window, in %: the largest over the routed layers of
|rows after the window / rows before it - 1| x 100.

Both readings are of ONE sequence, the one `correct` is checked on, through
the program's own routing probe, which sets the gauge
`oobleck_moe_held_rows{layer}`: once on the seed's weights before the
warm-up, once on the trained weights after the window has closed (outside
`setup_s` and the rate). The runner hands both on (`held_rows`:
{"before": {layer: rows}, "after": {layer: rows}}). A step's time follows
the row tiles the held experts fill, so a cell whose routing moves reads
differently with every seed and from step to step (PERF.md section 6,
PR 34: 4,215 -> 7 rows within twelve steps at a learning rate of 1.6e-4).
A runner that hands nothing on, or a program without the gauge: nothing to
read.
"""


def read(data: dict) -> float | None:
    rows = data.get("held_rows") or {}
    before, after = rows.get("before") or {}, rows.get("after") or {}
    layers = [l for l in before if l in after and before[l] > 0]
    if not layers:
        return None
    return max(abs(after[l] / before[l] - 1.0) for l in layers) * 100.0
