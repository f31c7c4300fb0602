"""Trace-replay cluster simulator: what-if fleet planning with no hardware.

The sim plane answers "would this configuration survive that incident
pattern?" offline, at thousands-of-hosts scale, by replaying two kinds of
input against a discrete-event cluster model:

  * the recorded corpus — committed ``incident-*.json`` postmortems and
    ``flight-*.jsonl`` rings (``corpus.py``) — which also feeds
    ``priors.py``'s fitted per-mechanism latency priors; and
  * synthesized adversarial scenarios — churn storms, correlated rack
    loss, spot-preemption waves, flap sequences, diurnal traffic swings —
    from seeded generators with explicit PRNG state (``scenarios.py``).

The model (``cluster.py``) costs every recovery through the REAL
``degrade.classify`` / ``degrade.planner.plan_reroute`` /
``execution.schedule.replay_schedule`` / ``policy`` code paths — the
simulator cannot drift from the system it models because it has no
recovery model of its own. ``slo.py`` reduces a run to a fleet SLO report
(recovery p99, goodput under churn, decisions-vs-oracle regret).

Deterministic by construction: same seed + same corpus -> byte-identical
SLO report (no wall clock, no ambient entropy, hermetic metrics
registry). CLI: ``python -m oobleck_tpu.sim``.
"""

from __future__ import annotations
