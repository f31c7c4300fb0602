"""SLO reducer: percentiles and regret accounting."""

from __future__ import annotations

import pytest

from oobleck_tpu.sim import slo
from oobleck_tpu.utils import metrics


@pytest.fixture(autouse=True)
def _fresh_registry(monkeypatch):
    monkeypatch.setattr(metrics, "_registry", metrics.Registry())


def _arm(latency, retention=1.0, lost_work=0.0, feasible=True):
    return {"latency_s": latency, "retention": retention,
            "lost_work_s": lost_work, "feasible": feasible}


def _run(incidents, duration=1000.0):
    return {"scenario": {"name": "manual", "seed": 0, "hosts": 4,
                         "duration_s": duration, "events": len(incidents)},
            "config": {"hosts": 4},
            "incidents": incidents,
            "goodput_ratio": 0.9,
            "lost_work_s": 0.0,
            "final": {"live_hosts": 4, "pipelines": 4, "quarantined": 0}}


def test_percentiles_nearest_rank():
    assert slo._pct([], 99) is None
    assert slo._pct([5.0], 50) == 5.0
    xs = [float(i) for i in range(1, 101)]
    assert slo._pct(xs, 50) == 50.0
    assert slo._pct(xs, 99) == 99.0


def test_zero_regret_when_chosen_matches_oracle():
    inc = {"t": 10.0, "mechanism": "reroute", "realized_recovery_s": 1.0,
           "arms": {"reroute": _arm(1.0),
                    "restore": _arm(25.0)}}
    report = slo.slo_report(_run([inc]))
    assert report["regret"]["total_s"] == 0.0
    assert report["regret"]["oracle_agreement"] == 1.0
    assert report["mechanisms"] == {"reroute": 1}


def test_regret_counts_hindsight_gap():
    # Chosen restore (25 s) when a full-retention reroute (1 s) was
    # feasible and no failure followed: 24 s of pure regret.
    inc = {"t": 10.0, "mechanism": "restore", "realized_recovery_s": 25.0,
           "arms": {"reroute": _arm(1.0), "restore": _arm(25.0)}}
    report = slo.slo_report(_run([inc]))
    assert report["regret"]["total_s"] == pytest.approx(24.0)
    assert report["regret"]["oracle_agreement"] == 0.0


def test_oracle_window_prices_degraded_throughput():
    # Reroute at 50% retention, next failure 10 s later: the oracle
    # charges 0.5 * 10 s of lost throughput against reroute's cheap
    # latency, so restore-at-5s wins the hindsight comparison.
    incs = [
        {"t": 10.0, "mechanism": "reroute", "realized_recovery_s": 1.0,
         "arms": {"reroute": _arm(1.0, retention=0.5),
                  "restore": _arm(5.0)}},
        {"t": 20.0, "mechanism": "restore", "realized_recovery_s": 5.0,
         "arms": {"restore": _arm(5.0)}},
    ]
    report = slo.slo_report(_run(incs))
    # incident 1: cost(reroute) = 1 + 0.5*10 = 6 > cost(restore) = 5.
    assert report["regret"]["total_s"] == pytest.approx(1.0)
    assert report["regret"]["oracle_agreement"] == pytest.approx(0.5)


def test_pool_block_passes_through_only_when_present():
    run = _run([])
    assert "pool" not in slo.slo_report(run)  # single-tenant: unchanged
    run["pool"] = {"granted": 2, "denied": 1, "held": 0,
                   "ended": {"expired": 2}, "still_active": 0,
                   "chip_seconds_lent": 360.0, "train_charged_s": 4.2}
    report = slo.slo_report(run)
    assert report["pool"] == run["pool"]
    assert '"pool"' in slo.render(report)


def test_render_is_canonical():
    report = slo.slo_report(_run([]))
    s = slo.render(report)
    assert s == slo.render(slo.slo_report(_run([])))
    assert "\n" not in s and ": " not in s
