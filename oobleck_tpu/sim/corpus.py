"""Corpus loader: recorded traces -> typed events the simulator can replay.

Two sources, one Corpus:

  * ``incident-<n>.json`` — the obs plane's committed postmortems
    (schema-validated: unknown ``schema_version`` is skipped with a
    warning, records missing the core keys are skipped, duplicate
    trace_ids are deduped first-wins);
  * ``flight-*.jsonl`` — dumped flight-recorder rings (one JSON event per
    line; unparseable lines are counted, not fatal).

Beyond replay, the corpus is the policy plane's training set:
``latency_samples()`` extracts per-mechanism measured recovery latencies
(incident ``total_s`` preferred — it is the failure-to-resume metric the
scorer prices; flight ``degrade_decision`` / ``policy_decision_measured``
events fill in incidents the obs plane never committed),
deduped so an incident's embedded flight tail and a separately dumped
ring never double-count one recovery. ``priors.py`` fits
``learned_priors.json`` from exactly these samples.
"""

from __future__ import annotations

import json
import logging
import os
import re
from dataclasses import dataclass, field

from oobleck_tpu.obs.incident import SCHEMA_VERSION, list_incidents
from oobleck_tpu.utils import metrics

logger = logging.getLogger("oobleck.sim")

_FLIGHT_RE = re.compile(r"flight-.*\.jsonl$")

# Keys a parseable incident must carry to be replayable at all.
_REQUIRED_INCIDENT_KEYS = ("trace_id", "lost_ip", "marks")

@dataclass
class IncidentEvent:
    """One committed incident, reduced to what replay and fitting need."""

    path: str
    trace_id: str
    schema_version: int
    lost_ip: str
    cause: str
    marks: dict
    total_s: float
    mechanism: str = ""            # "" when no decision event was captured
    measured_recovery_s: float | None = None
    plan: dict = field(default_factory=dict)
    attrs: dict = field(default_factory=dict)
    flight: list = field(default_factory=list)


@dataclass
class FlightEvent:
    """One flight-recorder ring event from a dumped ``flight-*.jsonl``."""

    t: float
    event: str
    fields: dict
    source: str


@dataclass
class Corpus:
    """Everything loadable under one trace directory, plus what was not."""

    root: str
    incidents: list[IncidentEvent] = field(default_factory=list)
    flight: list[FlightEvent] = field(default_factory=list)
    skipped: list[tuple[str, str]] = field(default_factory=list)

    def latency_samples(self) -> dict[str, list[float]]:
        """mechanism -> measured recovery seconds, one sample per distinct
        recovery across both sources (see module docstring)."""
        samples: dict[str, list[float]] = {}
        consumed: set = set()

        def add(mechanism: str, seconds) -> None:
            if mechanism and isinstance(seconds, (int, float)) and seconds > 0:
                samples.setdefault(mechanism, []).append(float(seconds))

        for inc in self.incidents:
            for ev in inc.flight:
                if isinstance(ev, dict) and ev.get("event") in (
                        "degrade_decision", "policy_decision",
                        "policy_decision_measured"):
                    consumed.add(_decision_key(ev))
            if inc.mechanism and inc.mechanism != "disabled":
                # total_s (detect -> first post-recovery step) is the
                # failure-to-resume latency; fall back to the decision's
                # own measured reconfigure time when marks are partial.
                add(inc.mechanism, inc.total_s or inc.measured_recovery_s)
        for fe in self.flight:
            key = _decision_key({"event": fe.event, "t": fe.t, **fe.fields})
            if key in consumed:
                continue
            if fe.event in ("degrade_decision", "policy_decision_measured"):
                consumed.add(key)
                add(fe.fields.get("mechanism", ""),
                    fe.fields.get("measured_recovery_s"))
        return samples

    def stats(self) -> dict:
        """Summary block for reports and the CLI."""
        return {
            "incidents": len(self.incidents),
            "flight_events": len(self.flight),
            "skipped": len(self.skipped),
            "latency_samples": {m: len(v)
                                for m, v in self.latency_samples().items()},
        }


def _decision_key(ev: dict) -> tuple:
    """Identity of one recorded decision across ring copies: the same
    event embedded in an incident and dumped in a flight file carries the
    same trace_id/decided_at, whatever file it came from."""
    return (ev.get("event"), ev.get("trace_id"), ev.get("decided_at"),
            ev.get("t"))


def _incident_decision(rec: dict) -> tuple[str, float | None, dict]:
    """(mechanism, measured_recovery_s, plan) from an incident's embedded
    flight tail; policy_decision matching the trace wins over the raw
    degrade_decision (it is the authoritative verdict)."""
    mechanism, measured, plan = "", None, {}
    for ev in rec.get("flight") or ():
        if not isinstance(ev, dict):
            continue
        kind = ev.get("event")
        if kind == "degrade_decision" and not mechanism:
            mechanism = str(ev.get("mechanism") or "")
            measured = ev.get("measured_recovery_s")
            plan = ev.get("plan") or {}
        elif (kind == "policy_decision"
              and ev.get("trace_id") == rec.get("trace_id")):
            mechanism = str(ev.get("mechanism") or "")
    return mechanism, measured, plan


def load_corpus(root: str) -> Corpus:
    """Load every trace under ``root`` into one validated Corpus."""
    corpus = Corpus(root=root)
    reg = metrics.registry()
    events_total = reg.counter(
        "oobleck_sim_corpus_events_total",
        "Corpus records loaded by kind (incident/flight)")
    skipped_total = reg.counter(
        "oobleck_sim_corpus_skipped_total",
        "Corpus records skipped at load time, by reason")

    def skip(path: str, reason: str) -> None:
        corpus.skipped.append((path, reason))
        skipped_total.inc(reason=reason)
        logger.warning("sim corpus: skipping %s: %s", path, reason)

    seen_traces: set[str] = set()
    for path, rec in list_incidents(root):
        version = rec.get("schema_version", SCHEMA_VERSION)
        if not isinstance(version, int) or version > SCHEMA_VERSION:
            skip(path, f"unknown_schema_version:{version!r}")
            continue
        if any(k not in rec for k in _REQUIRED_INCIDENT_KEYS):
            skip(path, "missing_required_keys")
            continue
        trace_id = str(rec["trace_id"])
        if trace_id in seen_traces:
            skip(path, "duplicate_trace_id")
            continue
        seen_traces.add(trace_id)
        mechanism, measured, plan = _incident_decision(rec)
        corpus.incidents.append(IncidentEvent(
            path=path,
            trace_id=trace_id,
            schema_version=version,
            lost_ip=str(rec["lost_ip"]),
            cause=str(rec.get("cause") or ""),
            marks=dict(rec.get("marks") or {}),
            total_s=float(rec.get("total_s") or 0.0),
            mechanism=mechanism,
            measured_recovery_s=measured,
            plan=plan,
            attrs=dict(rec.get("attrs") or {}),
            flight=list(rec.get("flight") or ()),
        ))
        events_total.inc(kind="incident")

    try:
        names = sorted(os.listdir(root))
    except OSError:
        names = []
    for name in names:
        path = os.path.join(root, name)
        if _FLIGHT_RE.match(name):
            bad = 0
            try:
                with open(path) as f:
                    for line in f:
                        line = line.strip()
                        if not line:
                            continue
                        try:
                            ev = json.loads(line)
                        except ValueError:
                            bad += 1
                            continue
                        if not isinstance(ev, dict) or "event" not in ev:
                            bad += 1
                            continue
                        fields = {k: v for k, v in ev.items()
                                  if k not in ("t", "event")}
                        corpus.flight.append(FlightEvent(
                            t=float(ev.get("t") or 0.0),
                            event=str(ev["event"]),
                            fields=fields, source=path))
                        events_total.inc(kind="flight")
            except OSError as e:
                skip(path, f"unreadable:{e.__class__.__name__}")
                continue
            if bad:
                skip(path, f"unparseable_lines:{bad}")
    return corpus
