"""The readers that open the trace themselves, on a recorded cut of a
traced run of `gpt3-2.7b.steady` on a TPU v5 lite (PR 26): one whole step,
the device's operations with the stats they carried, its module line, and
the host plane with the program's `engine.*` spans on the same clock."""

import importlib
import json
from pathlib import Path

import pytest

from benchmarks import trace_detail, trace_reduce

CUT = json.loads((Path(__file__).parent / "data"
                  / "trace_steady_named_v5e.json").read_text())
LAYER_METRICS = Path(trace_detail.__file__).parent / "layer_metrics"


def _data() -> dict:
    (device, events), = CUT["devices"].items()
    ops = [[name, s, d, dict(CUT["op_stats"])] for name, s, d in events]
    detail = {"ops": ops, "modules": CUT["modules"], "host": CUT["spans"]}
    return {"trace": trace_reduce.reduce(CUT, CUT["window_ns"] / 1e9),
            "trace_detail": detail, "train": dict(CUT["train"]),
            "cell": {"traffic": CUT["traffic"]},
            "device": {"kind": CUT["device_kind"]}}


@pytest.fixture(scope="module")
def data():
    return _data()


# What the cut's program (PR 26's) ran and today's does not: two backward
# kernels a flash call (one since PR 47), a forward program in a one-stage
# pipeline (PR 30), a program for the gradients' sum (PR 33). The metrics
# that read them went in PR 57; the accepted readers still read the cut.
GONE = {
    "kernel_call_ms:flash_bwd_dq": ("kernel_call_ms",
                                    {"match": "%flash_bwd_dq."}),
    "kernel_call_ms:flash_bwd_dkv": ("kernel_call_ms",
                                     {"match": "%flash_bwd_dkv."}),
    "device_ms_by_module:jit_fwd": ("device_ms_by_module",
                                    {"module": "jit_fwd"}),
    "device_ms_by_module:jit_grad_add": ("device_ms_by_module",
                                         {"module": "jit_grad_add"})}


def _read(metric: str, data: dict):
    """Through the metric's own file (reader and args), or for a name of
    `GONE` through the reader and args it gives."""
    if metric in GONE:
        name, args = GONE[metric]
    else:
        spec = json.loads((LAYER_METRICS / f"{metric}.json").read_text())
        name, args = spec["reader"], spec.get("args", {})
    reader = importlib.import_module(f"benchmarks.readers.{name}")
    return reader.read(data, **args)


ON_THE_CUT = {
    "flash_fwd_roofline": 1.02091,           # forward kernel, recomputes in the time
    "flash_bwd_roofline": 2.94444,           # dq + dk/dv
    "flash_fwd_calls_per_need": 3.0,         # once in fwd, twice in bwd
    "kernel_call_ms:flash_bwd_dq": 2.82851,
    "kernel_call_ms:flash_bwd_dkv": 4.39286,
    # The cut is one HOST step plus 30 ms: the device works through a
    # little more than one step's programs in it.
    "device_ms_by_module:jit_fwd": 251.634,
    "device_ms_per_step.bwd": 883.068,
    "device_ms_by_module:jit_grad_add": 71.7324,
    "device_ms_per_step.optimizer": 39.1587,
    "step_ms_p50.train": 1213.767,
    "step_ms_max.train": 1213.767,
    "host_dispatch_ms.train": 1213.309,      # the host blocks while it dispatches
    "idle_ms_per_step.in_step": 1.315423,
    "idle_ms_per_step.between_steps": 0.021764,
}


@pytest.mark.parametrize("metric", sorted(ON_THE_CUT))
def test_metric_on_the_recorded_cut(data, metric):
    assert _read(metric, data) == pytest.approx(ON_THE_CUT[metric], rel=1e-4)


# A program without the name (the parent of the PR that brought it), a
# trace without the span, or no trace: nothing to read, and no error.
RENAMED = {"%flash_fwd.": "%checkpoint.", "%flash_bwd_dq.": "%jvp__.",
           "%flash_bwd_dkv.": "%jvp__."}


def _without_names(data: dict) -> dict:
    def old(name):
        for new, stand_in in RENAMED.items():
            name = name.replace(new, stand_in)
        return name

    detail = data["trace_detail"]
    trace = dict(data["trace"], time_by_name={
        old(k): v for k, v in data["trace"]["time_by_name"].items()})
    return dict(data, trace=trace, trace_detail={
        "ops": detail["ops"], "host": {},
        "modules": [[m.replace("jit_grad_add", "jit__lambda")
                      .replace("jit_optimizer_update", "jit_upd"), s, d]
                    for m, s, d in detail["modules"]]})


@pytest.mark.parametrize("metric", sorted(
    set(ON_THE_CUT) - {"device_ms_by_module:jit_fwd",
                       "device_ms_per_step.bwd"}))
def test_metric_is_left_out_where_the_program_lacks_the_name(data, metric):
    assert _read(metric, _without_names(data)) is None


def test_the_one_backward_kernels_metric_finds_nothing_on_the_cut(data):
    """`flash_bwd_ms` reads `%flash_bwd_dqkv.`, which PR 26's program did
    not have: nothing, and not the two older kernels' time."""
    assert _read("flash_bwd_ms", data) is None
    by_name = dict(data["trace"]["time_by_name"])
    by_name["%flash_bwd_dqkv.3 bf16[128,1024,128] custom-call"] = [0.5, 200]
    by_name["%flash_swa_bwd_dqkv.1 bf16[28,16384,128] custom-call"] = [9., 9]
    assert _read("flash_bwd_ms", dict(data, trace=dict(
        data["trace"], time_by_name=by_name))) == pytest.approx(2.5)


@pytest.mark.parametrize("metric", sorted(ON_THE_CUT))
def test_metric_is_left_out_without_a_trace(metric):
    assert _read(metric, {}) is None
    assert _read(metric, {"cell": {"name": "no.such.cell", "traffic":
                                   CUT["traffic"]},
                          "train": dict(CUT["train"])}) is None


def test_idle_split_adds_up_to_what_trace_reduce_attributes(data):
    """The two idle metrics split exactly the gaps `attribute_gaps` sums
    over all its names (runtime threads' names, on this cut)."""
    total = sum(seconds for _, seconds in data["trace"]["idle_gaps"])
    split = (_read("idle_ms_per_step.in_step", data)
             + _read("idle_ms_per_step.between_steps", data))
    assert split * CUT["steps"] / 1e3 == pytest.approx(total, rel=1e-9)
    names = {name for name, _ in data["trace"]["idle_gaps"]}
    assert not any(n.startswith("engine.") for n in names)


def test_kernels_and_programs_go_by_their_own_names(data):
    by_name = data["trace"]["time_by_name"]
    custom = {k.split(".")[0] for k in by_name if "tpu_custom_call" in k}
    assert custom == {"%flash_fwd", "%flash_bwd_dq", "%flash_bwd_dkv"}
    modules = {m for m, _, _ in data["trace_detail"]["modules"]}
    assert {"jit_fwd", "jit_bwd", "jit_grad_add",
            "jit_optimizer_update"} <= modules
    assert not any("lambda" in m for m in modules)


def test_every_operation_falls_under_a_module(data):
    detail = data["trace_detail"]
    by_module = trace_detail.ops_by_module(detail)
    placed = sum(len(v) for v in by_module.values())
    assert placed >= 0.999 * len(detail["ops"])
    # An operation's own stat wins over containment (the CPU's events).
    own = {"ops": [["a", 5, 1, {"hlo_module": "jit_other(7)"}]],
           "modules": [["jit_fwd", 0, 10]], "host": {}}
    assert list(trace_detail.ops_by_module(own)) == ["jit_other"]


def test_spans_are_told_from_runtime_events_by_their_form():
    ok = ["engine.step", "pipeline.flush_sends", "dp.allreduce",
          "degrade.plan.apply"]
    not_spans = ["DeferredTpuAllocator::Allocate", "$engine.py:12 train",
                 "AllocateBufferAwait", "engine", "Engine.step", "a.b c",
                 "dot.16", "copy_bitcast_fusion.3"]
    assert all(trace_detail.SPAN_NAME.match(n) for n in ok)
    assert not any(trace_detail.SPAN_NAME.match(n) for n in not_spans)
    assert trace_detail.module_name("jit_bwd(12188948436616315486)") == "jit_bwd"


def test_from_profile_reads_a_real_profile_once_per_cell(tmp_path, monkeypatch):
    """A CPU profile has no device plane: `from_profile` says so with
    None, and `for_data` asks once per cell however many readers call."""
    import jax
    import jax.numpy as jnp

    from oobleck_tpu.obs import spans

    trace_dir = tmp_path / "a.cell"
    jax.profiler.start_trace(str(trace_dir))
    with spans.region("engine.step"):
        jnp.ones(8).sum().block_until_ready()
    jax.profiler.stop_trace()
    assert trace_detail.from_profile(
        trace_reduce.find_xplane(str(trace_dir))) is None

    calls = []
    monkeypatch.setattr(trace_detail, "TRACE_ROOT", tmp_path)
    monkeypatch.setattr(trace_detail, "from_profile",
                        lambda path: calls.append(path) or {"ops": []})
    trace_detail._of_cell.cache_clear()
    data = {"cell": {"name": "a.cell"}}
    assert trace_detail.for_data(data) is trace_detail.for_data(data)
    assert len(calls) == 1
    trace_detail._of_cell.cache_clear()


SETUP_SPANS = ["engine.build", "engine.plan", "engine.instantiate"]


def test_span_seconds_reads_the_programs_span_ring():
    from benchmarks.readers import span_seconds
    from oobleck_tpu.obs import spans

    data = {"cell": {"name": "x.y"}}
    assert span_seconds.read(data, spans=["t.never_recorded"]) is None
    spans.span_recorder().record("t.setup_a", 10.0, 12.5)
    spans.span_recorder().record("t.setup_b", 20.0, 20.5)
    assert span_seconds.read(
        data, spans=["t.setup_a", "t.setup_b"]) == pytest.approx(3.0)
    assert span_seconds.read({}, spans=["t.setup_a"]) is None
    spec = json.loads((LAYER_METRICS / "setup_engine_build_s.json").read_text())
    assert spec["args"]["spans"] == SETUP_SPANS


def test_counter_value_reads_the_programs_registry():
    from benchmarks.readers import counter_value
    from oobleck_tpu.utils import metrics

    data = {"cell": {"name": "x.y"}}
    assert counter_value.read(data, counter="oobleck_no_such_total") is None
    metrics.registry().counter("oobleck_compile_seconds_total").inc(1.5)
    got = counter_value.read(data, counter="oobleck_compile_seconds_total")
    assert got is not None and got >= 1.5
    assert counter_value.read({}, counter="oobleck_compile_seconds_total") is None
