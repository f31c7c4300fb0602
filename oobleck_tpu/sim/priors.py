"""Fit the policy scorer's latency priors from the incident corpus.

The shipped ``PRIOR_LATENCY_S`` table is what the scorer believes before
any history exists; this module replaces belief with evidence. Every
committed incident and dumped flight ring contributes its measured
failure-to-resume latency (``Corpus.latency_samples``); the fit is the
per-mechanism median — robust to the one 20x outlier a respawn
under load produces, and deterministic (no wall clock in the output, so
re-fitting an unchanged corpus is byte-identical).

The emitted ``learned_priors.json`` is what ``policy.signals`` loads when
``$OOBLECK_POLICY_PRIORS`` (or an engine's ``priors_path``) points at it;
from then on every PolicyDecision's arms carry
``prior_source="learned:<path>"`` instead of ``"hardcoded"``.
"""

from __future__ import annotations

import json
import os

from oobleck_tpu.policy.signals import PRIOR_LATENCY_S, PRIORS_VERSION
from oobleck_tpu.sim.corpus import Corpus

# Only mechanisms the scorer actually prices get fitted entries; anything
# else in the corpus (typos, future mechanisms) is reported, not used.
_KNOWN_MECHANISMS = tuple(sorted(PRIOR_LATENCY_S))


def _median(xs: list[float]) -> float:
    xs = sorted(xs)
    n = len(xs)
    mid = n // 2
    return xs[mid] if n % 2 else (xs[mid - 1] + xs[mid]) / 2.0


def fit_priors(corpus: Corpus, *, min_samples: int = 1) -> dict:
    """The ``learned_priors.json`` record: fitted ``latency_s`` for every
    mechanism with at least ``min_samples`` corpus observations (the rest
    keep falling through to the hardcoded table at decision time), plus
    provenance naming exactly what the fit saw."""
    samples = corpus.latency_samples()
    latency: dict[str, float] = {}
    provenance: dict[str, dict] = {}
    for mechanism, xs in sorted(samples.items()):
        prov = {
            "samples": len(xs),
            "median_s": round(_median(xs), 6),
            "mean_s": round(sum(xs) / len(xs), 6),
            "min_s": round(min(xs), 6),
            "max_s": round(max(xs), 6),
        }
        if mechanism not in _KNOWN_MECHANISMS:
            prov["ignored"] = "unknown_mechanism"
        elif len(xs) < min_samples:
            prov["ignored"] = f"fewer_than_{min_samples}_samples"
        else:
            latency[mechanism] = round(_median(xs), 6)
        provenance[mechanism] = prov
    return {
        "version": PRIORS_VERSION,
        "latency_s": latency,
        "provenance": {
            "fitted_from": corpus.root,
            "incidents": len(corpus.incidents),
            "flight_events": len(corpus.flight),
            "estimator": "median",
            "mechanisms": provenance,
        },
    }


def write_priors(path: str, priors: dict) -> str:
    """Atomically publish a fitted priors file (tmp + rename, so a reader
    mid-write never sees a torn table)."""
    tmp = f"{path}.tmp.{os.getpid()}"
    with open(tmp, "w") as f:
        json.dump(priors, f, indent=1, sort_keys=True)
        f.write("\n")
    os.replace(tmp, path)
    return path
