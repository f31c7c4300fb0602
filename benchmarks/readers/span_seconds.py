"""Seconds the process spent in some of the program's once-per-process
spans, summed: `engine.build` (the engine's constructor, the layer profile
inside it), `engine.plan`, `engine.instantiate`. They are recorded by
`oobleck_tpu.obs.spans.span` into the program's span ring before any trace
starts, and read from that ring in the benchmark's process. A program
without such spans: nothing to read.
"""


def read(data: dict, *, spans: list) -> float | None:
    if not (data.get("cell") or {}).get("name"):
        return None
    from oobleck_tpu.obs.spans import span_recorder

    wanted = set(spans)
    found = [s["t1"] - s["t0"] for s in span_recorder().spans()
             if s["name"] in wanted]
    return sum(found) if found else None
