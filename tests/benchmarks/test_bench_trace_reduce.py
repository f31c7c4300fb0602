"""Trace -> busy share, time by name, idle gaps by host span."""

import json
from pathlib import Path

import pytest

from benchmarks import trace_reduce as tr

DATA = Path(__file__).parent / "data"
MS = 1_000_000  # ns

# Hand-made: one device, four operations, two idle gaps.
#   op_a 0-10 ms, op_b 10-15 (back to back), gap 15-20 under "staging",
#   op_a 20-30, gap 30-50 under nothing, fusion.1 50-60 (and a nested
#   child 52-55 that must not count twice toward busy).
HAND = {
    "devices": {"/device:TPU:0": [
        ["op_a", 0 * MS, 10 * MS], ["op_b", 10 * MS, 5 * MS],
        ["op_a", 20 * MS, 10 * MS], ["fusion.1", 50 * MS, 10 * MS],
        ["child", 52 * MS, 3 * MS]]},
    "host": [["train_step", 0, 32 * MS], ["staging", 14 * MS, 7 * MS],
             ["unrelated", 100 * MS, 5 * MS]],
}


def test_busy_is_the_union_not_the_sum():
    r = tr.reduce(HAND, window_s=0.1)
    assert r["busy_s"] == pytest.approx(0.035)      # 15 + 10 + 10 ms
    assert r["window_s"] == 0.1 and r["n_devices"] == 1
    assert 1 - r["busy_s"] / r["window_s"] == pytest.approx(0.65)


def test_time_and_calls_by_name():
    r = tr.reduce(HAND, window_s=0.1)
    assert r["time_by_name"]["op_a"] == [pytest.approx(0.020), 2]
    assert r["time_by_name"]["op_b"] == [pytest.approx(0.005), 1]
    assert r["device_ops"][0] == ["op_a", pytest.approx(0.020)]
    assert len(r["device_ops"]) <= 10


def test_gaps_go_to_the_innermost_host_span_that_covers_them():
    gaps = dict(tr.reduce(HAND, window_s=0.1)["idle_gaps"])
    assert gaps == {"staging": pytest.approx(0.005),
                    tr.NO_HOST_SPAN: pytest.approx(0.020)}


def test_gaps_under_the_floor_are_launch_latency():
    trace = {"devices": {"d": [["a", 0, 1000], ["a", 1000 + tr.MIN_GAP_NS - 1,
                                                 1000]]}, "host": []}
    assert tr.reduce(trace, 1.0)["idle_gaps"] == []


def test_busy_is_averaged_over_devices():
    two = {"devices": {"/device:TPU:0": [["a", 0, 10 * MS]],
                       "/device:TPU:1": [["a", 0, 30 * MS]]}, "host": []}
    r = tr.reduce(two, 0.1)
    assert r["busy_s"] == pytest.approx(0.020) and r["n_devices"] == 2
    assert r["time_by_name"]["a"] == [pytest.approx(0.040), 2]


def test_a_trace_without_a_device_is_an_error():
    with pytest.raises(ValueError):
        tr.reduce({"devices": {}, "host": []}, 1.0)
    with pytest.raises(FileNotFoundError):
        tr.find_xplane(str(DATA))


@pytest.mark.parametrize("name", sorted(
    p.name for p in DATA.glob("trace_*.json")))
def test_recorded_trace(name):
    """A cut from a trace taken on the chip (PR 25), as `load_xplane`
    returned it: busy time is positive and under the cut's length, every
    operation is accounted for, gaps never exceed the idle time."""
    trace = json.loads((DATA / name).read_text())
    events = next(iter(trace["devices"].values()))
    t0 = min(e[1] for e in events)
    t1 = max(e[1] + e[2] for e in events)
    r = tr.reduce(trace, (t1 - t0) / 1e9)
    assert 0 < r["busy_s"] <= r["window_s"] * (1 + 1e-9)
    assert sum(c for _, c in r["time_by_name"].values()) == len(events)
    idle = r["window_s"] - r["busy_s"]
    assert sum(s for _, s in r["idle_gaps"]) <= idle + 1e-9
    assert r["device_ops"] == sorted(r["device_ops"], key=lambda x: -x[1])
