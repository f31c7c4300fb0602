"""Fleet SLO reduction: one simulator run -> the numbers a PR is gated on.

Three SLO families, at fleet scale no hardware run could cover:

  * recovery latency percentiles (nearest-rank, so the report is exact
    and deterministic — no interpolation float drift);
  * goodput under churn — the piecewise-integrated delivered/demanded
    ratio from the cluster model;
  * decisions-vs-oracle regret — with hindsight, each incident's realized
    time-to-next-failure is known, so the oracle prices every feasible
    arm with the TRUE amortization window instead of the MTBF estimate
    the policy engine had to use. Regret is how many seconds the chosen
    arm cost over the hindsight-best one; agreement is how often they
    coincided. This is Chameleon's policy-evaluation framing (arxiv
    2508.21613) run entirely offline.

``crossval_report`` closes the loop the other way: it replays a RECORDED
incident (rig shape + calibrated op durations stored in the incident's
attrs) through the same classify/plan/fit code paths and compares the
simulator's projections against what the hardware measured.
"""

from __future__ import annotations

import math

from oobleck_tpu.degrade.classify import classify_failure
from oobleck_tpu.degrade.planner import PipelineSpec, plan_reroute
from oobleck_tpu.policy.scorer import AMORT_CAP_S
from oobleck_tpu.sim.corpus import Corpus
from oobleck_tpu.sim.priors import fit_priors

PERCENTILES = (50, 90, 99)


def _pct(xs: list[float], q: float) -> float | None:
    """Nearest-rank percentile over raw samples (exact, deterministic)."""
    if not xs:
        return None
    xs = sorted(xs)
    i = min(len(xs) - 1, max(0, math.ceil(q / 100.0 * len(xs)) - 1))
    return xs[i]


def _hindsight_cost(arm: dict, window_s: float) -> float:
    """The scorer's cost formula with the TRUE amortization window and no
    churn-risk hedge — with hindsight there is no risk, only what
    actually happened."""
    return (arm["latency_s"] + arm["lost_work_s"]
            + (1.0 - min(arm["retention"], 1.0))
            * min(window_s, AMORT_CAP_S))


def slo_report(run: dict) -> dict:
    """Reduce one SimCluster.run() record to the gated SLO report."""
    incidents = run["incidents"]
    duration = run["scenario"]["duration_s"]
    recoveries = [i["realized_recovery_s"] for i in incidents]
    mechanisms: dict[str, int] = {}
    prior_sources: set[str] = set()
    for inc in incidents:
        mechanisms[inc["mechanism"]] = mechanisms.get(inc["mechanism"], 0) + 1
        for arm in inc["arms"].values():
            if arm.get("prior_source"):
                prior_sources.add(arm["prior_source"])

    total_regret = 0.0
    agreements = 0
    for i, inc in enumerate(incidents):
        window = (incidents[i + 1]["t"] if i + 1 < len(incidents)
                  else duration) - inc["t"]
        window = max(window, 0.0)
        feasible = {m: a for m, a in inc["arms"].items() if a["feasible"]}
        if not feasible:
            continue
        costs = {m: _hindsight_cost(a, window) for m, a in feasible.items()}
        best = min(sorted(costs), key=lambda m: (costs[m], m))
        chosen = inc["mechanism"]
        if chosen == best:
            agreements += 1
        if chosen in costs:
            total_regret += costs[chosen] - costs[best]

    n = len(incidents)
    report = {
        "scenario": dict(run["scenario"]),
        "config": dict(run["config"]),
        "incidents": n,
        "mechanisms": mechanisms,
        "recovery": {f"p{q}_s": (round(v, 6) if v is not None else None)
                     for q in PERCENTILES
                     for v in [_pct(recoveries, q)]},
        "goodput_ratio": run["goodput_ratio"],
        "lost_work_s": run["lost_work_s"],
        "regret": {
            "total_s": round(total_regret, 6),
            "mean_s": round(total_regret / n, 6) if n else 0.0,
            "oracle_agreement": round(agreements / n, 6) if n else 1.0,
        },
        "prior_sources": sorted(prior_sources),
        "final": dict(run["final"]),
    }
    if "pool" in run:
        # Shared-pool scenarios only: lease traffic + the cross-tenant
        # bill. Absent otherwise, so single-tenant renders are unchanged.
        report["pool"] = dict(run["pool"])
    return report


def render(report: dict) -> str:
    """Canonical serialization: the byte-identical-across-runs contract
    tests and the determinism gate compare THIS string."""
    import json

    return json.dumps(report, sort_keys=True, separators=(",", ":"))


# -- cross-validation against the recorded corpus --------------------------- #

def replay_incident(inc, corpus: Corpus) -> dict | None:
    """Replay one recorded incident through the simulator's costing paths
    and put its projections next to the hardware measurements.

    Needs the rig shape + calibrated op durations the fixture generator
    stores in the incident's attrs; returns None for incidents without
    them (live-production incidents carry marks but not op calibration).
    """
    rig = inc.attrs.get("rig")
    op_list = inc.attrs.get("op_times")
    measured = inc.attrs.get("measured")
    if not (isinstance(rig, dict) and op_list and isinstance(measured, dict)):
        return None
    op_times = {(int(s), int(c), str(k)): (float(total), int(count))
                for s, c, k, total, count in op_list}
    chips = int(rig["chips_per_host"])
    hpp = int(rig["hosts_per_pipeline"])
    stages = hpp * chips
    n_pipes = int(rig["hosts"]) // hpp
    specs = [PipelineSpec(num_stages=stages,
                          num_microbatches=int(
                              rig["microbatches_per_pipeline"]),
                          virtual_stages=int(rig.get("virtual_stages", 1)),
                          op_times=op_times)
             for _ in range(n_pipes)]
    ranks = [[p * hpp * chips + i for i in range(hpp * chips)]
             for p in range(n_pipes)]
    report = classify_failure(int(rig["lost_host"]), ranks, chips)
    plan = plan_reroute(report, specs)

    fitted = fit_priors(corpus)["latency_s"]
    sim = {
        "feasible": plan.feasible,
        "survivor_slowdown": round(plan.slowdown, 6) if plan.feasible
        else None,
        "retention": round(plan.throughput_retention, 6),
        "recovery_s": fitted.get(inc.mechanism or "reroute"),
    }
    out = {"trace_id": inc.trace_id, "mechanism": inc.mechanism,
           "sim": sim, "measured": dict(measured), "rel_err": {}}
    for sim_key, meas_key in (
            ("survivor_slowdown", "survivor_slowdown_measured"),
            ("recovery_s", "recovery_to_next_step_s")):
        s, m = sim.get(sim_key), measured.get(meas_key)
        if isinstance(s, (int, float)) and isinstance(m, (int, float)) \
                and m > 0:
            out["rel_err"][sim_key] = round(abs(s - m) / m, 6)
    return out


def crossval_report(corpus: Corpus) -> dict:
    """Replay every replayable incident in the corpus; the cross-
    validation test gates on every rel_err staying within tolerance."""
    replays = [r for r in (replay_incident(i, corpus)
                           for i in corpus.incidents) if r]
    return {"corpus": corpus.root, "replayable": len(replays),
            "incidents": len(corpus.incidents), "replays": replays}
