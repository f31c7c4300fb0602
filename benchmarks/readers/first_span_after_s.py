"""Seconds of the first occurrence of one of the program's spans that
starts after another span has ended, in a traced run whose runner hands
over the part of the profile from an incident on
(`data["trace_detail_recovery"]`: host spans by name, `[start_ns,
duration_ns]` in time order, as `trace_detail` keeps them). With `span`
`engine.step` and `after` `engine.reconfigure`: the first training step on
the layout a recovery left, whatever it compiles, reads from the cache or
places for the first time included. No such part, no `after` span in it, or
no `span` after it: nothing to read.
"""


def read(data: dict, *, span: str, after: str) -> float | None:
    host = (data.get("trace_detail_recovery") or {}).get("host") or {}
    first = host.get(after)
    if not first:
        return None
    ended = first[0][0] + first[0][1]
    for start, duration in host.get(span, []):
        if start >= ended:
            return duration / 1e9
    return None
