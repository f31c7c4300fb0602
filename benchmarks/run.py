"""One cell of the benchmark, once, in a new process.

    python benchmarks/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Looks the cell up by name (`workloads/<cell>.json`), its configuration
(`configs/<config>.json`), its runner (`runners/<kind>.py`) and, in a traced
run, every per-layer metric the manifest gives the cell
(`layer_metrics/<metric>.json`, each naming a reader under `readers/`).
This file holds no list of cells, configurations or metrics; which metrics
a cell reports is what `BENCHMARK.json` says, and nothing else says it.
README.md beside this file describes the files.

Every line printed before the last is an observation that names the device
it was taken on. The last line of stdout is the one JSON object the
benchmark's contract fixes. Without a TPU, with fewer chips than the cell
asks for, or beside nothing else of the repo, the exit code is not 0 and
no result is printed.
"""

from __future__ import annotations

import time

T_PROCESS_START = time.monotonic()

import argparse  # noqa: E402
import importlib  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

HERE = Path(__file__).resolve().parent
ROOT = HERE.parent
# Everything the benchmark or the program caches goes here: a fixed path
# inside the checkout (the path is part of the compile cache's key).
CACHE = ROOT / ".jax_cache" / "benchmarks"

EXIT_NO_DEVICE = 3
EXIT_INCOMPLETE = 4


def load_json(path: Path) -> dict:
    with open(path) as f:
        return json.load(f)


class Context:
    """What a runner gets: the cell, its configuration, the seed, the
    window, the device, and the few services the harness owns."""

    def __init__(self, cell: dict, config: dict, seed: int, seconds: float,
                 trace: bool, device: dict):
        self.cell, self.config = cell, config
        self.seed, self.seconds, self.trace = seed, seconds, trace
        self.device = device
        self.setup_s: float | None = None
        self.trace_dir = CACHE / "trace" / cell["name"]
        self.traced_window_s: float | None = None
        self._trace_t0: float | None = None
        self.cache_at_window: dict | None = None

    def say(self, what: str, **fields) -> None:
        """One observation line; always names the device."""
        print(json.dumps({"observation": what, **fields,
                          "platform": self.device["platform"],
                          "device_kind": self.device["kind"],
                          "device_count": self.device["count"]}),
              flush=True)

    def say_memory(self, stage: str) -> None:
        """Device memory of the fullest chip at a named point of set-up."""
        import jax

        stats = max((d.memory_stats() or {} for d in jax.devices()),
                    key=lambda m: m.get("bytes_in_use", 0))
        self.say("memory", stage=stage,
                 bytes_in_use=stats.get("bytes_in_use"),
                 peak_bytes_in_use=stats.get("peak_bytes_in_use"),
                 largest_free_block_bytes=stats.get(
                     "largest_free_block_bytes"),
                 bytes_limit=stats.get("bytes_limit"))

    def window_starts(self) -> None:
        """Called by the runner at the first measured step or request:
        everything before it is set-up."""
        self.setup_s = time.monotonic() - T_PROCESS_START
        self.cache_at_window = cache_counts()

    def start_trace(self) -> None:
        if not self.trace or self._trace_t0 is not None:
            return
        import jax

        shutil.rmtree(self.trace_dir, ignore_errors=True)
        jax.profiler.start_trace(str(self.trace_dir))
        self._trace_t0 = time.perf_counter()

    def stop_trace(self) -> None:
        if self._trace_t0 is None or self.traced_window_s is not None:
            return
        import jax

        self.traced_window_s = time.perf_counter() - self._trace_t0
        jax.profiler.stop_trace()


def cache_counts() -> dict:
    """This process's reads of and writes to the persistent compile cache
    and the seconds it spent getting executables (JAX's own events,
    counted by oobleck_tpu/utils/compile_cache.py)."""
    from oobleck_tpu.utils import metrics

    reg = metrics.registry()
    events = reg.counter("oobleck_compile_cache_events_total")
    return {"entries_read": int(events.value(event="entry_read")),
            "entries_written": int(events.value(event="entry_written")),
            "compile_s": reg.counter("oobleck_compile_seconds_total").value()}


def device_record(chips: int) -> dict:
    import jax

    devs = jax.devices()
    if devs[0].platform != "tpu":
        raise SystemExit(_refuse(
            f"JAX reports platform {devs[0].platform!r}; the benchmark "
            "measures on a TPU only"))
    if len(devs) < chips:
        raise SystemExit(_refuse(
            f"the cell needs {chips} chip(s), JAX reports {len(devs)}"))
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs)}


def _refuse(why: str) -> int:
    print(f"benchmarks/run.py: {why}", file=sys.stderr)
    return EXIT_NO_DEVICE


def memory_peak_bytes() -> int:
    import jax

    peaks = []
    for d in jax.devices():
        stats = d.memory_stats() or {}
        peaks.append(int(stats.get("peak_bytes_in_use", 0)))
    return max(peaks)


def cell_metrics(manifest: dict, section: str, cell: str,
                 reported: set[str] | None = None) -> list[dict]:
    """The manifest's metrics of one section that this cell reports: those
    that list it under `workloads`, and those with no such key (for a
    per-layer metric: if its `moves` is something this cell reports)."""
    out = []
    for m in manifest[section]:
        if "workloads" in m:
            if cell in m["workloads"]:
                out.append(m)
        elif reported is None or m.get("moves") in reported:
            out.append(m)
    return out


def read_layer_metrics(ctx: Context, manifest: dict, e2e_names: set[str],
                       data: dict) -> dict:
    """Every per-layer metric the manifest gives this cell, each through
    the reader its own file (`layer_metrics/<metric>.json`) names. Which
    cells report a metric is the manifest's to say and nobody else's. A
    reader that finds nothing to read returns None and the metric is left
    out of the line."""
    out = {}
    for m in cell_metrics(manifest, "per_layer", ctx.cell["name"], e2e_names):
        path = HERE / "layer_metrics" / f"{m['name']}.json"
        if not path.exists():
            raise SystemExit(
                f"BENCHMARK.json names the per-layer metric {m['name']} "
                f"but there is no {path.relative_to(ROOT)}")
        spec = load_json(path)
        reader = importlib.import_module(
            f"benchmarks.readers.{spec['reader']}")
        value = reader.read(data, **spec.get("args", {}))
        if value is not None:
            out[m["name"]] = {"value": float(value), "unit": m["unit"]}
    return out


def set_cache_environment() -> None:
    """One compile cache, at a fixed path in the checkout, whatever the
    machine's environment says; to be called before anything imports JAX.
    The program reads the same variable
    (oobleck_tpu/utils/compile_cache.py). The layer profiles the planner
    caches (OOBLECK_TPU_CACHE) live beside it."""
    (CACHE / "xla").mkdir(parents=True, exist_ok=True)
    os.environ["JAX_COMPILATION_CACHE_DIR"] = str(CACHE / "xla")
    os.environ["JAX_COMPILATION_CACHE_MAX_SIZE"] = "-1"
    os.environ["JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS"] = "0"
    os.environ["JAX_PERSISTENT_CACHE_MIN_ENTRY_SIZE_BYTES"] = "-1"
    os.environ["OOBLECK_TPU_CACHE"] = str(CACHE / "profiles")
    os.environ.setdefault("TPU_LOG_DIR", "disabled")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ns = ap.parse_args(argv)

    set_cache_environment()
    sys.path.insert(0, str(ROOT))
    # Beside nothing else of the repo there is no system to measure.
    import oobleck_tpu  # noqa: F401

    manifest = load_json(ROOT / "BENCHMARK.json")
    cell = load_json(HERE / "workloads" / f"{ns.workload}.json")
    config = load_json(HERE / "configs" / f"{cell['config']}.json")

    device = device_record(int(cell["chips"]))
    # This process owns the chip: apply the program's one cache rule
    # (scrub, JAX's cache events counted) before anything compiles.
    from oobleck_tpu.utils.compile_cache import ensure_persistent_cache

    ensure_persistent_cache()
    ctx = Context(cell, config, ns.seed, ns.seconds, bool(ns.trace), device)
    runner = importlib.import_module(f"benchmarks.runners.{cell['kind']}")
    result = runner.run(ctx)
    ctx.stop_trace()
    at_end, at_window = cache_counts(), ctx.cache_at_window or {}
    ctx.say("compile_cache", **at_end, in_window={
        k: at_end[k] - at_window.get(k, 0) for k in at_end})

    if ctx.setup_s is None:
        raise SystemExit("the runner never marked the window's start")
    for c in result["checks"]:
        ctx.say("correct", **c)
    correct = all(c["ok"] for c in result["checks"]) and bool(result["checks"])

    e2e = dict(result["end_to_end"], setup_s=ctx.setup_s)
    wanted = cell_metrics(manifest, "end_to_end", cell["name"])
    missing = [m["name"] for m in wanted if e2e.get(m["name"]) is None]
    if missing:
        print(f"benchmarks/run.py: no value for {missing}", file=sys.stderr)
        return EXIT_INCOMPLETE
    line = {"correct": correct, "attempted": result["attempted"],
            "failed": result["failed"]}
    dev = dict(device, memory_peak_bytes=memory_peak_bytes())
    if not ctx.trace:
        line["metrics"] = {m["name"]: {"value": float(e2e[m["name"]]),
                                       "unit": m["unit"]} for m in wanted}
    else:
        from benchmarks import trace_reduce

        reduced = trace_reduce.reduce(
            trace_reduce.load_xplane(
                trace_reduce.find_xplane(str(ctx.trace_dir))),
            ctx.traced_window_s)
        data = dict(result.get("layer_data", {}), trace=reduced,
                    end_to_end=e2e, device=device, cell=cell, config=config)
        line["metrics"] = read_layer_metrics(
            ctx, manifest, {m["name"] for m in wanted}, data)
        dev.update(busy_s=reduced["busy_s"], window_s=reduced["window_s"])
        line["breakdown"] = {"device_ops": reduced["device_ops"],
                             "idle_gaps": reduced["idle_gaps"]}
        line["end_to_end_traced"] = {k: float(v) for k, v in e2e.items()}
    line["device"] = dev
    # Each number `correct` was decided on, beside its limit: the line's
    # last key and, after the line, the last lines of standard error.
    line["compared"] = {c["check"]: {"value": c["value"], "limit": c["limit"]}
                        for c in result["checks"]}
    print(json.dumps(line), flush=True)
    for c in result["checks"]:
        print(f"compared {c['check']}: {c['value']} limit {c['limit']}",
              file=sys.stderr, flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
