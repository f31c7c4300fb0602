"""The host-loss cell's own pieces: its three numbers' arithmetic (two rates
end to end, the recovery's seconds per layer)
on step samples made by hand, the split of a traced run's profile at the
loss and the three new readers on it, MEMBERSHIP of the cell and its
metrics in the manifest (never a list's end or its whole), and the runner's
and the control's flow rehearsed on four CPU devices at gpt2-tiny sizes
(control flow only, never a number): the loss goes in through the public
request and is applied at a step boundary, the steps are split by layout,
`correct` is true on the program as it is and false with a batch lost in
the recovery, the gradient exchange left out, half of a step's rows left
out or a state returned unchanged; the reference in the program's place one
precision down, or with a fault, comes out not correct too."""

import json
from pathlib import Path
from types import SimpleNamespace

import numpy as np
import pytest

from benchmarks import control_hostloss as control
from benchmarks.readers import (
    device_idle_max_pct,
    first_span_after_s,
    runner_value,
)
from benchmarks.reference import train_steps as plain
from benchmarks.runners import train_hostloss as runner

ROOT = Path(__file__).resolve().parents[2]
BENCH = ROOT / "benchmarks"
MANIFEST = json.loads((ROOT / "BENCHMARK.json").read_text())
CELL = json.loads(
    (BENCH / "workloads" / "gpt3-2.7b.hostloss.json").read_text())
TOKENS = 64 * 1024


# --------------------------------------------------------------------- #
# the three numbers, from samples made by hand                           #
# --------------------------------------------------------------------- #

def _sample(step, step_s, between_s):
    return (step, step_s, 0.0, 0.0, 0.0, 0.0, 0, between_s, (0.0,) * 5,
            None, None, None)


def _window():
    """Steps 3..6 on four chips (0.5 s each, 0.01 s between; the first
    step's `between_s` is the wait from `train()`'s start and is not the
    window's), the loss applied after step 6 (1.2 s between, a first step
    of 0.9 s), then steps 8..10 of 1.0 s on three."""
    return ([_sample(3, 0.5, 0.3)]
            + [_sample(s, 0.5, 0.01) for s in (4, 5, 6)]
            + [_sample(7, 0.9, 1.2)]
            + [_sample(s, 1.0, 0.02) for s in (8, 9, 10)])


def test_window_metrics_by_hand():
    w = runner.window_metrics(_window(), 6, TOKENS, 4, 3)
    assert (w["steps_before"], w["steps_after"]) == (4, 4)
    # 4 steps over 4 x 0.5 + 3 x 0.01 s, per chip of four.
    assert w["before_loss_tokens_per_s"] == pytest.approx(
        4 * TOKENS / 2.03 / 4)
    # End of step 6 to end of step 7, and nothing else.
    assert w["recovery_s"] == pytest.approx(1.2 + 0.9)
    assert w["recovery_between_s"] == pytest.approx(1.2)
    # Steps 8..10 over 3 x 1.02 s, per SURVIVING chip.
    assert w["after_loss_tokens_per_s"] == pytest.approx(
        3 * TOKENS / 3.06 / 3)


def test_window_metrics_one_step_more_on_either_side_moves_no_rate():
    """A blend would move by a step's share; these do not move at all."""
    base = runner.window_metrics(_window(), 6, TOKENS, 4, 3)
    longer = _window()
    longer.insert(4, _sample(6.5, 0.5, 0.01))       # one more before
    longer.append(_sample(11, 1.0, 0.02))           # one more after
    more = runner.window_metrics(longer, 6.5, TOKENS, 4, 3)
    assert more["before_loss_tokens_per_s"] == pytest.approx(
        5 * TOKENS / 2.54 / 4)
    assert more["after_loss_tokens_per_s"] == pytest.approx(
        base["after_loss_tokens_per_s"])
    assert more["recovery_s"] == pytest.approx(base["recovery_s"])


def test_window_metrics_without_a_loss_or_a_later_step():
    w = runner.window_metrics(_window()[:4], 6, TOKENS, 4, 4)
    assert w["recovery_s"] is None and w["after_loss_tokens_per_s"] is None
    assert w["before_loss_tokens_per_s"] is not None
    w = runner.window_metrics(_window()[:5], 6, TOKENS, 4, 3)
    assert w["recovery_s"] == pytest.approx(2.1)
    assert w["after_loss_tokens_per_s"] is None


@pytest.mark.parametrize("numbers,wrong", [
    ([3, 4, 5, 6, 7], 0), ([3, 4, 6, 7], 1), ([3, 4, 4, 5], 1),
    ([4, 5], 1), ([3, 5, 5, 6], 2), ([], 0)])
def test_a_skipped_or_repeated_step_number_counts_as_failed(numbers, wrong):
    assert runner.step_numbers_failed(numbers, 3) == wrong


# --------------------------------------------------------------------- #
# a traced run's profile, split at the loss; the three new readers       #
# --------------------------------------------------------------------- #

def _profile():
    """Times in ns. The profile opens at 0 inside some step (a span that
    was open then leaves no event; its operations do, here `%z`); three
    whole steps on the first layout start at 0.2 s; `engine.reconfigure`
    runs 3.0-3.5 s; the first later step runs 3.6-4.6 s."""
    s = 1e9
    steps = [[0.2 * s, 0.7 * s], [1.0 * s, 0.9 * s], [2.0 * s, 0.9 * s],
             [3.6 * s, 1.0 * s], [4.7 * s, 1.0 * s]]
    detail = {
        "ops": [["%z", 0.05 * s, 0.1 * s, {}],
                ["%a", 0.3 * s, 0.1 * s, {}], ["%f", 1.1 * s, 0.2 * s, {}],
                ["%b", 1.4 * s, 0.4 * s, {}], ["%f", 2.1 * s, 0.2 * s, {}],
                ["%b", 2.4 * s, 0.4 * s, {}], ["%b", 3.7 * s, 0.8 * s, {}]],
        "modules": [["jit_fwd", 1.1 * s, 0.2 * s], ["jit_bwd", 1.4 * s, 0.4 * s],
                    ["jit_fwd", 2.1 * s, 0.2 * s], ["jit_bwd", 2.4 * s, 0.4 * s],
                    ["jit_bwd", 3.7 * s, 0.8 * s]],
        "host": {"engine.step": steps,
                 "engine.reconfigure": [[3.0 * s, 0.5 * s]],
                 "dp.allreduce": [[0.8 * s, 0.05 * s], [1.8 * s, 0.02 * s],
                                  [2.8 * s, 0.04 * s], [4.5 * s, 0.01 * s]]},
    }
    devices = {
        "/device:TPU:0": [[n, t, d] for n, t, d, _ in detail["ops"]],
        # Busy 0.7 s of the 2.8 s between the first step's start and the loss.
        "/device:TPU:1": [["%x", 0.1 * s, 0.05 * s], ["%x", 0.5 * s, 0.2 * s],
                          ["%x", 1.5 * s, 0.25 * s], ["%x", 2.5 * s, 0.25 * s],
                          ["%x", 3.2 * s, 0.1 * s]],
    }
    return detail, devices


def test_split_at_loss_hands_whole_steps_of_the_first_layout():
    detail, devices = _profile()
    view = runner.split_at_loss(detail, devices)
    assert view["traced_steps_before"] == 3
    four = view["trace_detail"]
    assert [o[1] / 1e9 for o in four["ops"]] == [0.3, 1.1, 1.4, 2.1, 2.4]
    assert len(four["host"]["engine.step"]) == 3
    assert len(four["host"]["dp.allreduce"]) == 3
    assert "engine.reconfigure" not in {
        k for k, v in four["host"].items() if v}
    assert view["device_busy"]["window_s"] == pytest.approx(2.8)
    assert view["device_busy"]["busy_s"] == {
        "/device:TPU:0": pytest.approx(1.3),
        "/device:TPU:1": pytest.approx(0.7)}
    assert [s[0] / 1e9 for s in
            view["trace_detail_recovery"]["host"]["engine.step"]] == [3.6, 4.7]
    # A profile that never saw the loss, or no whole step before it.
    del detail["host"]["engine.reconfigure"]
    assert runner.split_at_loss(detail, devices) == {}
    detail["host"]["engine.reconfigure"] = [[0.5e9, 0.1e9]]
    assert runner.split_at_loss(detail, devices) == {}


def _read(metric, data):
    import importlib

    spec = json.loads((BENCH / "layer_metrics" / f"{metric}.json").read_text())
    reader = importlib.import_module(f"benchmarks.readers.{spec['reader']}")
    return reader.read(data, **spec.get("args", {}))


def test_the_cells_metrics_on_the_split_profile():
    detail, devices = _profile()
    per_step = 16
    data = dict(runner.split_at_loss(detail, devices),
                cell={"name": "x.hostloss",
                      "traffic": {"global_batch": 64, "microbatch_size": 4}},
                train={"microbatches_run": 3 * per_step},
                window_counters={"oobleck_compile_seconds_total": 0.0},
                setup_seconds={"engine_build_s": 1.5, "executables_s": 2.5,
                               "precompile_wait_s": 0.25},
                end_to_end={"recovery_s": 1.125, "setup_s": 30.0})
    assert _read("device_ms_per_step.fwd", data) == pytest.approx(400.0 / 3)
    assert _read("stage_idle_pct.max", data) == pytest.approx(75.0)
    assert _read("dp_sync_ms.train", data) == pytest.approx(110.0 / 3)
    assert _read("recovery_first_step_s.hostloss", data) == pytest.approx(1.0)
    assert _read("recovery_compile_s.hostloss", data) == 0.0   # a reading
    assert _read("recovery_s.hostloss", data) == 1.125
    # engine.step spans of 0.7, 0.9, 0.9 s, no read-back span inside.
    assert _read("host_dispatch_ms.hostloss", dict(data, trace_detail=dict(
        data["trace_detail"], host=dict(
            data["trace_detail"]["host"],
            **{"engine.loss_readback": [[1.5e9, 0.3e9]]})))) == pytest.approx(
                (700.0 + 600.0 + 900.0) / 3)
    assert _read("setup_engine_build_s.hostloss", data) == 1.5
    assert _read("setup_executables_s.hostloss", data) == 2.5
    assert _read("setup_precompile_wait_s.hostloss", data) == 0.25


@pytest.mark.parametrize("metric", [
    "recovery_s.hostloss",
    "reconfigure_s.hostloss", "recovery_first_step_s.hostloss",
    "recovery_compile_s.hostloss", "device_ms_per_step.fwd",
    "stage_idle_pct.max", "dp_sync_ms.train", "host_dispatch_ms.hostloss",
    "setup_precompile_wait_s.hostloss", "setup_engine_build_s.hostloss",
    "setup_executables_s.hostloss"])
def test_nothing_to_read(metric):
    assert _read(metric, {}) is None


def test_new_readers_where_a_part_is_missing():
    assert first_span_after_s.read(
        {"trace_detail_recovery": {"host": {"engine.step": [[1.0, 2.0]]}}},
        span="engine.step", after="engine.reconfigure") is None
    assert first_span_after_s.read(
        {"trace_detail_recovery": {"host": {
            "engine.reconfigure": [[5.0, 2.0]],
            "engine.step": [[1.0, 2.0], [6.0, 2.0]]}}},
        span="engine.step", after="engine.reconfigure") is None
    assert runner_value.read(
        {"window_counters": {"a": 1.5}}, table="window_counters",
        key="b") is None
    assert runner_value.read(
        {"window_counters": {"a": 1.5}}, table="setup_seconds",
        key="a") is None
    assert runner_value.read(
        {"window_counters": {"a": 1.5}}, table="window_counters",
        key="a") == 1.5
    assert device_idle_max_pct.read(
        {"device_busy": {"window_s": 2.0, "busy_s": {"one": 1.0}}}) is None
    assert device_idle_max_pct.read(
        {"device_busy": {"window_s": 0.0, "busy_s": {"a": 0, "b": 0}}}) is None


# --------------------------------------------------------------------- #
# the manifest, by membership                                            #
# --------------------------------------------------------------------- #

NEW_METRICS = {
    # `recovery_s` is per layer: its runs spread over half of the largest
    # bound an end-to-end metric may have (PERF.md 2), so what moved it
    # names the rate after the loss, the cell's end-to-end metric nearest.
    "recovery_s.hostloss": ("runner_value", "after_loss_tokens_per_s"),
    "reconfigure_s.hostloss": ("span_seconds", "after_loss_tokens_per_s"),
    "recovery_first_step_s.hostloss": ("first_span_after_s",
                                       "after_loss_tokens_per_s"),
    "recovery_compile_s.hostloss": ("runner_value",
                                    "after_loss_tokens_per_s"),
    "device_ms_per_step.fwd": ("device_ms_by_module",
                               "before_loss_tokens_per_s"),
    "stage_idle_pct.max": ("device_idle_max_pct", "before_loss_tokens_per_s"),
    "dp_sync_ms.train": ("span_stat_ms", "before_loss_tokens_per_s"),
    "host_dispatch_ms.hostloss": ("span_stat_ms", "before_loss_tokens_per_s"),
    "setup_precompile_wait_s.hostloss": ("runner_value", "setup_s"),
    "setup_engine_build_s.hostloss": ("runner_value", "setup_s"),
    "setup_executables_s.hostloss": ("runner_value", "setup_s"),
}
# The accepted per-layer metrics move `train_tokens_per_s` or `setup_s`.
# The cell does not report the first (README-hostloss.md: its four-chip
# rate has a name and a bound of its own), and the two that move `setup_s`
# read the process at the run's END, so none names the cell.
THIS_CELLS_TOO = []
_MOVES = "moves train_tokens_per_s, which this cell does not report; "
_CUT = _MOVES + ("the runner hands the profile cut to the first layout's "
                 "whole steps, so the reader would be right on it")
_CHIP_0 = _MOVES + ("reads the first device's plane, one stage of one "
                    "pipeline; the four chips differ")
_NO_TABLE = _MOVES + "the runner hands no such table (no metric here reads it)"
# Accepted metrics this cell does NOT report, each with why.
NOT_THIS_CELLS = {
    "host_dispatch_ms.train": _CUT + ": host_dispatch_ms.hostloss is that "
                              "reading under a metric this cell reports",
    "device_ms_per_step.bwd": _CHIP_0,
    "device_ms_per_step.optimizer": _CHIP_0,
    "device_ms_per_step.grad_zero": _CHIP_0,
    "idle_ms_per_step.in_step": _CHIP_0 + " (stage_idle_pct.max reads each)",
    "idle_ms_per_step.between_steps": _CHIP_0,
    "idle_ms_per_step.in_dispatch": _CHIP_0,
    "idle_ms_per_step.in_readback": _CHIP_0,
    "flash_roofline": _CHIP_0 + ", and its need is one chip's microbatches",
    "flash_fwd_roofline": _CHIP_0,
    "flash_bwd_roofline": _CHIP_0,
    "flash_fwd_calls_per_need": _CHIP_0,
    "flash_bwd_ms": _CHIP_0,
    "dispatch_stall_ms.train": _MOVES + "a histogram's gain over the whole "
                               "window, steps of BOTH layouts in one mean",
    "input_wait_ms.train": _MOVES + "as dispatch_stall_ms.train",
    "step_ms.train": _MOVES + "as dispatch_stall_ms.train",
    "step_ms_p50.train": _CUT,
    "step_ms_max.train": _CUT,
    "step_excess_ms.dispatch": _NO_TABLE,
    "step_excess_ms.readback": _NO_TABLE,
    "step_excess_ms.rest": _NO_TABLE,
    "between_steps_ms.train": _NO_TABLE,
    "slow_steps.train": _NO_TABLE,
    "hbm_headroom_min_pct.train": _NO_TABLE + "; the runner prints each "
                                  "layout's fullest chip",
    "mfu_pct.train": _NO_TABLE + "; its 6 N is the whole model's a chip, "
                     "here a chip holds one stage of it",
    "setup_engine_build_s": "sums the process's build spans at the run's "
                            "end, the re-plan inside the window too: "
                            "setup_engine_build_s.hostloss stops at the "
                            "window's first step",
    "setup_executables_s": "the counter at the run's end, the window's and "
                           "the reference's seconds too: "
                           "setup_executables_s.hostloss stops at the "
                           "window's first step",
}


@pytest.mark.parametrize(
    "metric", sorted(NEW_METRICS) + THIS_CELLS_TOO + sorted(NOT_THIS_CELLS))
def test_which_metrics_name_the_cell(metric):
    (entry,) = [m for m in MANIFEST["per_layer"] if m["name"] == metric]
    if metric in NOT_THIS_CELLS:
        assert CELL["name"] not in entry["workloads"], NOT_THIS_CELLS[metric]
        return
    assert CELL["name"] in entry["workloads"]
    if metric in NEW_METRICS:
        reader, moves = NEW_METRICS[metric]
        spec = json.loads(
            (BENCH / "layer_metrics" / f"{metric}.json").read_text())
        assert (spec["reader"], entry["moves"]) == (reader, moves)


def test_the_cell_and_its_end_to_end_metrics_are_in_the_manifest():
    (cell,) = [c for c in MANIFEST["workloads"] if c["name"] == CELL["name"]]
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        "gpt3-2.7b", "hostloss", 4)
    assert cell["why"] == CELL["why"]
    e2e = {m["name"]: m for m in MANIFEST["end_to_end"]}
    assert CELL["name"] not in e2e["train_tokens_per_s"]["workloads"]
    assert "workloads" not in e2e["setup_s"]            # every cell's
    assert "recovery_s" not in e2e                      # per layer
    for name, better in (("before_loss_tokens_per_s", "higher"),
                         ("after_loss_tokens_per_s", "higher")):
        assert CELL["name"] in e2e[name]["workloads"]
        assert (e2e[name]["source"], e2e[name]["better"]) == (
            "host_clock", better)
    # What this cell reports in a traced run is what the lists above say.
    named = {m["name"] for m in MANIFEST["per_layer"]
             if CELL["name"] in m.get("workloads", [])}
    assert set(NEW_METRICS) | set(THIS_CELLS_TOO) <= named
    assert not named & set(NOT_THIS_CELLS)
    # The six accepted cells are where they were.
    assert [w["name"] for w in MANIFEST["workloads"]][:6] == [
        "gpt3-2.7b.steady", "lfm2-24b-a2b.steady", "moonlight-16b-a3b.steady",
        "nemotron-3-nano-30b-a3b.steady", "qwen3-next-80b-a3b.steady",
        "smallthinker-21b-a3b.steady"]


def test_the_cell_is_as_the_issue_states_it():
    assert CELL["chips"] == 4 and CELL["kind"] == "train_hostloss"
    assert CELL["execution"] == {"engine_path": "mpmd", "num_stages": 2}
    assert CELL["traffic"] == {
        "seq_len": 1024, "microbatch_size": 4, "global_batch": 64,
        "warmup_steps": 2, "learning_rate": 1.6e-4, "lr_warmup_steps": 10,
        "lose_host": "10.0.0.1", "lose_at_window_share": 0.4,
        # What the job's defaults are, stated for the plain reference.
        "optimizer": {"b1": 0.9, "b2": 0.999, "eps": 1e-8,
                      "weight_decay": 0.01, "clip_norm": 1.0,
                      "clip_norm_over": "pipeline_layer"},
        "corpus": {"rows": 8192, "held_out_share": 0.02, "order_seed": 0},
        "reference_rows_per_block": 1}
    assert set(CELL["correct"]) == {
        "grad_rel_err", "grad_rel_err_after_loss", "step_loss_rel_err",
        "first_grad_rel_err", "first_grad_norm_gap", "param_change_norm_gap",
        "next_rows_out_of_place", "ring_clock_gap"}
    assert CELL["correct"]["next_rows_out_of_place"] == 0
    assert CELL["correct"]["ring_clock_gap"] == 0.01
    # A state returned unchanged reads 1.
    assert CELL["correct"]["param_change_norm_gap"] < 1 / 3
    assert (CELL["correct"]["grad_rel_err_after_loss"]
            == CELL["correct"]["grad_rel_err"])
    steady = json.loads(
        (BENCH / "workloads" / "gpt3-2.7b.steady.json").read_text())
    assert CELL["correct"]["grad_rel_err"] == steady["correct"]["grad_rel_err"]


# --------------------------------------------------------------------- #
# the runner and the control, rehearsed on four CPU devices              #
# --------------------------------------------------------------------- #

TINY = {"name": "tiny", "model_name": "gpt2-tiny", "model_args": {},
        "vocab_size": 256, "max_position_embeddings": 128, "hidden_size": 64,
        "num_heads": 4, "head_dim": 16, "intermediate_size": 256,
        "num_layers": 4, "execution": {"precision": "bfloat16", "remat": True}}
TINY_CELL = dict(
    # The loss early in a window long enough that, with every worker of a
    # test run busy, three steps still follow it.
    CELL, traffic=dict(CELL["traffic"], seq_len=128, microbatch_size=2,
                       global_batch=32, lose_at_window_share=0.25,
                       reference_rows_per_block=4),
    # Limits for THIS size. The program reads 0.007-0.010 / 0.006-0.008 /
    # 0.008-0.012 / 4e-6 (first_grad_rel_err, first_grad_norm_gap,
    # param_change_norm_gap, step_loss_rel_err); the reference in float8
    # 0.042-0.046 / 0.014-0.016 / 0.030-0.033 / 1.1e-5-1.6e-5; half of the
    # batch left out 0.8-1.1 / 0.5-0.8 / 0.14-0.15 / 6e-4-7e-4; the
    # exchange left out 0.59-0.75 / 0.39-0.46 / 0.14-0.15 / 6e-4-7e-4.
    correct=dict(CELL["correct"], grad_rel_err=0.05,
                 grad_rel_err_after_loss=0.05, step_loss_rel_err=1e-4,
                 first_grad_rel_err=0.02, first_grad_norm_gap=0.1,
                 param_change_norm_gap=0.06))
SEED = 2**31 + 11


class _Ctx(SimpleNamespace):
    """What `benchmarks/run.py` hands a runner, less the look for a chip."""

    def say(self, what, **fields):
        self.said.append((what, fields))

    def say_memory(self, stage):
        pass

    def window_starts(self):
        from benchmarks.run import cache_counts

        self.window_started = True
        self.cache_at_window = cache_counts()

    def start_trace(self):
        pass

    def stop_trace(self):
        pass


def _ctx(seconds=6.0):
    return _Ctx(cell=TINY_CELL, config=TINY, seed=SEED, seconds=seconds,
                trace=False, said=[], window_started=False)


def _rehearse(monkeypatch, after_recovery=None, seconds=6.0, trace=False):
    """One run of the cell on four CPU devices. `after_recovery(engine)`
    is called when the engine has applied the loss: the place to break the
    timed path underneath the runner."""
    import jax

    from oobleck_tpu.execution.engine import OobleckEngine

    calls = {"requested": [], "applied": []}
    request = OobleckEngine.request_reconfiguration
    apply = OobleckEngine._do_reconfigure

    def requested(self, lost_ip, trace=None, decision=None):
        calls["requested"].append((lost_ip, trace, decision, self.step))
        return request(self, lost_ip, trace=trace, decision=decision)

    def applied(self, lost_ip, decision=None, **kw):
        calls["applied"].append((lost_ip, decision, self.step))
        apply(self, lost_ip, decision=decision, **kw)
        if after_recovery is not None:
            after_recovery(self)

    monkeypatch.setattr(OobleckEngine, "request_reconfiguration", requested)
    monkeypatch.setattr(OobleckEngine, "_do_reconfigure", applied)
    devices = jax.devices
    # The conftest's process has eight; the cell's machine has four.
    monkeypatch.setattr(jax, "devices",
                        lambda *a: devices(*a) if a else devices()[:4])
    ctx = _ctx(seconds)
    ctx.trace = trace
    return ctx, runner.run(ctx), calls


@pytest.fixture(scope="module")
def rehearsal():
    with pytest.MonkeyPatch.context() as mp:
        yield _rehearse(mp)


def _said(ctx, what):
    return [fields for name, fields in ctx.said if name == what]


def test_the_loss_goes_in_by_the_public_request_and_lands_on_a_boundary(
        rehearsal):
    ctx, result, calls = rehearsal
    (req,) = calls["requested"]
    assert req[:3] == ("10.0.0.1", None, None)      # no decision handed in
    (app,) = calls["applied"]
    assert app[:2] == ("10.0.0.1", None)
    (recovery,) = _said(ctx, "recovery")
    # Applied between two steps: after the last step the engine had
    # completed, which is the step the arm's event names.
    assert recovery["applied_after_step"] == app[2] >= req[3]
    assert recovery["arm"] in ("reroute", "reinstantiate", "restore")
    assert len(recovery["engine_recovery_times"]) == 1
    assert ctx.window_started


def test_the_steps_are_split_by_layout_and_recovery_s_spans_one_gap(
        rehearsal):
    ctx, result, calls = rehearsal
    (w,) = _said(ctx, "hostloss_window")
    loss_step = calls["applied"][0][2]
    rows = w["steps_as_number_seconds_between_layout"]
    assert [r[0] for r in rows] == list(range(rows[0][0], rows[-1][0] + 1))
    assert [r[3] for r in rows] == (
        ["first"] * w["steps_before"] + ["second"] * w["steps_after"])
    assert rows[w["steps_before"] - 1][0] == loss_step
    assert w["steps_before"] >= 1 and w["steps_after"] >= 3
    first_after = rows[w["steps_before"]]
    assert w["recovery_s"] == pytest.approx(
        first_after[1] + first_after[2], abs=2e-4)
    e2e = result["end_to_end"]
    assert e2e["recovery_s"] == w["recovery_s"] > 0
    assert e2e["before_loss_tokens_per_s"] > 0
    assert e2e["after_loss_tokens_per_s"] > 0
    before, after = _said(ctx, "layout")
    assert before["when"] == "window_start" and not before["idle_hosts"]
    assert len(before["hosts"]) == 4 and len(after["hosts"]) == 3
    assert "10.0.0.1" not in after["hosts"]
    assert all("10.0.0.1" not in hosts for p in after["pipelines"]
               for hosts in p["stage_hosts"])
    assert sum(p["microbatches"] for p in after["pipelines"]) == 16


def test_the_program_as_it_is_reads_correct(rehearsal):
    ctx, result, _ = rehearsal
    assert result["failed"] == 0 and result["attempted"] >= 4
    checks = {c["check"]: c for c in result["checks"]}
    assert set(checks) == set(TINY_CELL["correct"])
    assert all(c["ok"] for c in checks.values()), checks
    # The ring's samples are held to the runner's own clock of the window.
    (w,) = _said(ctx, "hostloss_window")
    assert checks["ring_clock_gap"]["value"] == pytest.approx(
        abs(w["ring_covers_s"] - w["elapsed_s"]) / w["elapsed_s"])
    # Both checks of one sequence's gradient, before the window on the
    # first layout and after it on the layout the recovery left.
    first, second = _said(ctx, "train_check")
    assert (first["when"], second["when"]) == ("window_start", "window_end")
    assert checks["grad_rel_err"]["value"] == first["grad_rel_err"]
    assert checks["grad_rel_err_after_loss"]["value"] == second["grad_rel_err"]
    (followed,) = _said(ctx, "first_steps_against_reference")
    assert [row[0] for row in followed["losses_program_reference"]] == [1, 2]
    # Every leaf of both pipelines, the stacked projections apart.
    assert followed["leaves_compared"] == 69
    assert followed["leaves_moved"] < followed["leaves_compared"]
    data = result["layer_data"]
    assert set(data) == {"window_counters", "setup_seconds"}   # no trace
    assert data["window_counters"] == {
        "oobleck_compile_seconds_total": pytest.approx(0.0, abs=60.0)}
    assert set(data["setup_seconds"]) == {
        "engine_build_s", "executables_s", "precompile_wait_s"}
    assert all(v >= 0 for v in data["setup_seconds"].values())
    assert data["setup_seconds"]["engine_build_s"] > 0


def _lose_a_batch(monkeypatch):
    """The recovery drops the batch the stagers hold."""
    def after_recovery(engine):
        for loader in engine.dataloaders:
            loader.advance()

    return after_recovery


def _leave_the_exchange_out(monkeypatch):
    """Each pipeline steps on its own gradients: no sum between the chips
    that hold a layer."""
    from oobleck_tpu.execution.engine import DataParallelEngine

    monkeypatch.setattr(
        DataParallelEngine, "do_allreduce",
        lambda self: {p.pipeline_id: dict(p.grads) for p in self.pipelines})


def _leave_half_of_the_batch_out(monkeypatch):
    """Every pipeline steps on the first pipeline's rows alone, the mean
    taken over them (each microbatch's gradient is weighed by the whole
    step's count, so a half's sum is doubled)."""
    import jax

    from oobleck_tpu.execution.engine import DataParallelEngine

    def first_half(self):
        if len(self.pipelines) == 1:
            return {self.pipelines[0].pipeline_id: dict(self.pipelines[0].grads)}
        mean = jax.tree.map(lambda g: 2.0 * g, dict(self.pipelines[0].grads))
        return {p.pipeline_id: jax.device_put(mean, jax.tree.map(
            lambda g: g.sharding, dict(p.grads))) for p in self.pipelines}

    monkeypatch.setattr(DataParallelEngine, "do_allreduce", first_half)


def _return_the_state_unchanged(monkeypatch):
    """The optimizer's step changes nothing."""
    from oobleck_tpu.execution.pipeline import PipelineInstance

    monkeypatch.setattr(
        PipelineInstance, "apply_updates",
        lambda self, optimizer, opt_state, grads: opt_state)


@pytest.mark.parametrize("fault,fails", [
    (_lose_a_batch, {"next_rows_out_of_place"}),
    (_leave_the_exchange_out, {"first_grad_rel_err", "first_grad_norm_gap",
                               "param_change_norm_gap"}),
    (_leave_half_of_the_batch_out, {"first_grad_rel_err",
                                    "first_grad_norm_gap",
                                    "param_change_norm_gap"}),
    (_return_the_state_unchanged, {"param_change_norm_gap"})])
def test_a_broken_timed_path_reads_not_correct(fault, fails, monkeypatch):
    traced = fault is _lose_a_batch
    if traced:
        # One of these runs as a traced run, the profile made by hand.
        monkeypatch.setattr(runner, "traced_view",
                            lambda ctx: runner.split_at_loss(*_profile()))
    # A window long enough for the loss to land; no rate is read here.
    ctx, result, _ = _rehearse(monkeypatch, after_recovery=fault(monkeypatch),
                               seconds=3.0, trace=traced)
    if traced:
        data = dict(result["layer_data"], cell=TINY_CELL)
        assert set(data) == {
            "window_counters", "setup_seconds", "trace_detail",
            "trace_detail_recovery", "device_busy", "train", "cell"}
        assert data["train"] == {"microbatches_run": 3 * 16}
        assert _read("device_ms_per_step.fwd", data) == pytest.approx(400 / 3)
        assert _read("host_dispatch_ms.hostloss", data) is None  # no read-back
        (said,) = _said(ctx, "traced_first_layout")
        assert said["steps"] == 3 and said["window_s"] == pytest.approx(2.8)
    failed = {c["check"] for c in result["checks"] if not c["ok"]}
    assert failed and failed >= fails - {"first_grad_norm_gap"}, result["checks"]
    # What each fault has nothing to do with still holds.
    assert not failed & ({"grad_rel_err", "grad_rel_err_after_loss",
                          "ring_clock_gap"} | (
        {"next_rows_out_of_place"} - fails)), result["checks"]
    assert result["failed"] == 0     # every step ran; what it ran on is off
    if fault is _return_the_state_unchanged:
        gaps = {c["check"]: c["value"] for c in result["checks"]}
        assert gaps["param_change_norm_gap"] == pytest.approx(1.0)


def test_the_corpus_copy_is_what_the_programs_loader_hands_out():
    from oobleck_tpu.execution.dataloader import (
        OobleckDataLoader,
        OobleckSampler,
    )
    from oobleck_tpu.execution.dataset import SyntheticTextDataset

    job = dict(TINY_CELL["traffic"], global_batch=64, microbatch_size=4)
    dataset = SyntheticTextDataset(256, 128, seed=SEED % (1 << 31))
    trained = len(dataset) - int(len(dataset) * 0.02)
    loaders = [OobleckDataLoader(dataset, OobleckSampler(
        trained, 4, i, [8, 8])) for i in range(2)]
    from oobleck_tpu.config import ExecutionArguments, JobArguments

    assert job["corpus"] == {
        "rows": len(dataset),
        "held_out_share": ExecutionArguments().eval_fraction,
        "order_seed": loaders[0].sampler.seed}
    defaults = JobArguments()
    assert (job["optimizer"]["weight_decay"], job["optimizer"]["clip_norm"]) == (
        defaults.weight_decay, defaults.max_grad_norm)
    per_epoch = trained // 64
    for step in range(1, per_epoch + 3):        # into the second epoch
        got = [dl.next_batch()["input_ids"].reshape(-1, 128) for dl in loaders]
        if step in (1, 2, per_epoch, per_epoch + 1, per_epoch + 2):
            want = plain.step_tokens(SEED, step, job, 256)
            assert (want == np.concatenate(got)).all(), step


def test_the_reference_in_the_programs_place_reads_not_correct():
    """The control as a test: one precision down, and with each fault a
    data-parallel step can have, against the reference itself; beside them
    the program on one device and one stage, which reads correct."""
    import jax

    ctx = _ctx()
    control.first_steps_readings(ctx, jax.local_devices()[0], True, True)
    got = {f["reading"]: f for f in _said(ctx, "first_steps")}
    assert set(got) == {"program_one_chip", "fp8", "half_batch_left_out",
                        "exchange_left_out"}
    assert got["program_one_chip"]["correct"], got["program_one_chip"]
    for name in ("fp8", "half_batch_left_out", "exchange_left_out"):
        assert not got[name]["correct"], got[name]
        assert "first_grad_rel_err" in got[name]["failed_numbers"]
    for name in ("half_batch_left_out", "exchange_left_out"):
        assert {"step_loss_rel_err", "param_change_norm_gap"} <= set(
            got[name]["failed_numbers"]), got[name]


def test_adamw_by_hand():
    """One leaf a layer, two steps, against the rule written out."""
    import jax.numpy as jnp

    job = {"learning_rate": 0.1, "lr_warmup_steps": 2,
           "optimizer": CELL["traffic"]["optimizer"]}
    tree = lambda x: {"embed": {"w": jnp.asarray(x, jnp.float32)},
                      "blocks": [], "head": {"w": jnp.asarray(x, jnp.float32)}}
    p, m, v = tree([1.0, -2.0]), tree([0.0, 0.0]), tree([0.0, 0.0])
    g = {"embed": {"w": jnp.asarray([3.0, 4.0])},          # norm 5: clipped
         "blocks": [], "head": {"w": jnp.asarray([0.3, 0.4])}}    # 0.5: not
    p, m, v = plain.adamw_step(p, g, m, v, 0, job)
    assert np.allclose(m["embed"]["w"], [0.06, 0.08])
    assert np.allclose(m["head"]["w"], [0.03, 0.04])
    # Step 1: m_hat / sqrt(v_hat) is the gradient's sign; lr 0.1 * 1/2.
    assert np.allclose(p["embed"]["w"],
                       [1.0 - 0.05 * (1 + 0.01), -2.0 - 0.05 * (1 - 0.02)],
                       atol=1e-6)
    p2, m2, v2 = plain.adamw_step(p, g, m, v, 1, job)
    assert np.allclose(m2["head"]["w"], [0.057, 0.076])
    assert np.allclose(v2["head"]["w"],
                       [0.09e-3 * 1.999, 0.16e-3 * 1.999], rtol=1e-5)


def test_norm_gaps_by_hand():
    want = {"a": 1.0, "b": 0.5, "c": 1e-6}
    # Against the leaf's norm, or the median leaf's where that is larger.
    assert plain.worst_norm_gap({"a": 1.1, "b": 0.5, "c": 2e-6}, want) == (
        pytest.approx(0.1), "a")
    assert plain.worst_norm_gap({"a": 1.0, "b": 0.5, "c": 0.2}, want)[0] == (
        pytest.approx((0.2 - 1e-6) / 0.5))
    # A leaf the program lacks, or left where it was, reads 1.
    assert plain.worst_norm_gap({"a": 1.0, "c": 1e-6}, want) == (1.0, "b")
    assert plain.worst_norm_gap({}, want, ["a", "b"])[0] == 1.0
    assert plain.worst_norm_gap({"a": float("nan")}, want, ["a"])[0] == float("inf")
    # A gradient nought to rounding: under a thousandth of the median.
    assert plain.moved_leaves({"a": 1.0, "b": 0.5, "c": 4e-4}) == ["a", "b"]
