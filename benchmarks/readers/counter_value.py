"""The value of one of the program's counters in the benchmark's process at
the end of the run (`oobleck_compile_seconds_total`: the seconds JAX spent
getting executables, compiled or read from the persistent cache; nothing
compiles inside the window, which the `compile_cache` observation of every
run shows, so all of it is set-up). A counter the program does not have or
never touched: nothing to read.
"""


def read(data: dict, *, counter: str) -> float | None:
    if not (data.get("cell") or {}).get("name"):
        return None
    from oobleck_tpu.utils import metrics

    for metric in metrics.registry().snapshot()["metrics"]:
        if metric["name"] == counter and metric["series"]:
            return sum(s["value"] for s in metric["series"])
    return None
