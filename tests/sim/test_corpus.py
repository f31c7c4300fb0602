"""Corpus loader: schema validation, dedup, and latency-sample extraction
over a synthetic trace directory. Pure filesystem + json — no jax."""

from __future__ import annotations

import json
import os

import pytest

from oobleck_tpu.obs.incident import SCHEMA_VERSION
from oobleck_tpu.sim.corpus import load_corpus
from oobleck_tpu.utils import metrics


@pytest.fixture(autouse=True)
def _fresh_registry(monkeypatch):
    monkeypatch.setattr(metrics, "_registry", metrics.Registry())


def _incident(trace_id, *, version=SCHEMA_VERSION, total_s=1.5,
              flight=(), **extra):
    rec = {
        "schema_version": version,
        "trace_id": trace_id,
        "lost_ip": "10.0.0.1",
        "cause": "test",
        "marks": {"detect": 100.0, "first_step": 100.0 + total_s},
        "total_s": total_s,
        "flight": list(flight),
    }
    rec.update(extra)
    return rec


def _write(d, name, rec):
    with open(os.path.join(d, name), "w") as f:
        json.dump(rec, f)


def test_load_valid_incident(tmp_path):
    d = str(tmp_path)
    _write(d, "incident-0.json", _incident("t0", flight=[
        {"t": 5.0, "event": "degrade_decision", "mechanism": "reroute",
         "measured_recovery_s": 0.4}]))
    corpus = load_corpus(d)
    assert len(corpus.incidents) == 1
    inc = corpus.incidents[0]
    assert inc.trace_id == "t0"
    assert inc.mechanism == "reroute"
    assert inc.total_s == 1.5
    assert not corpus.skipped


def test_unknown_schema_version_skipped_with_warning(tmp_path, caplog):
    d = str(tmp_path)
    _write(d, "incident-0.json", _incident("future",
                                           version=SCHEMA_VERSION + 1))
    _write(d, "incident-1.json", _incident("ok"))
    with caplog.at_level("WARNING", logger="oobleck.sim"):
        corpus = load_corpus(d)
    assert [i.trace_id for i in corpus.incidents] == ["ok"]
    assert any("unknown_schema_version" in r for _, r in corpus.skipped)
    assert any("skipping" in rec.message for rec in caplog.records)


def test_version_missing_defaults_to_current(tmp_path):
    d = str(tmp_path)
    rec = _incident("legacy")
    del rec["schema_version"]
    _write(d, "incident-0.json", rec)
    corpus = load_corpus(d)
    assert [i.trace_id for i in corpus.incidents] == ["legacy"]


def test_missing_required_keys_skipped(tmp_path):
    d = str(tmp_path)
    rec = _incident("nomarks")
    del rec["marks"]
    _write(d, "incident-0.json", rec)
    corpus = load_corpus(d)
    assert not corpus.incidents
    assert corpus.skipped[0][1] == "missing_required_keys"


def test_duplicate_trace_id_first_wins(tmp_path):
    d = str(tmp_path)
    _write(d, "incident-0.json", _incident("dup", total_s=1.0))
    _write(d, "incident-1.json", _incident("dup", total_s=9.0))
    corpus = load_corpus(d)
    assert len(corpus.incidents) == 1
    assert corpus.incidents[0].total_s == 1.0
    assert corpus.skipped[0][1] == "duplicate_trace_id"


def test_flight_file_and_bad_lines(tmp_path):
    d = str(tmp_path)
    with open(os.path.join(d, "flight-proc-1-1.jsonl"), "w") as f:
        f.write(json.dumps({"t": 1.0, "event": "degrade_decision",
                            "mechanism": "reroute",
                            "measured_recovery_s": 0.5}) + "\n")
        f.write("not json\n")
    corpus = load_corpus(d)
    assert len(corpus.flight) == 1
    assert corpus.flight[0].event == "degrade_decision"
    assert any(r.startswith("unparseable_lines") for _, r in corpus.skipped)


def test_latency_samples_dedup_embedded_vs_dumped(tmp_path):
    # The SAME decision event embedded in the incident's flight tail and
    # dumped in a standalone ring must count once — and the incident's
    # total_s wins as the sample.
    d = str(tmp_path)
    ev = {"t": 7.0, "event": "degrade_decision", "mechanism": "reroute",
          "measured_recovery_s": 0.05, "trace_id": "t0"}
    _write(d, "incident-0.json", _incident("t0", total_s=1.5, flight=[ev]))
    with open(os.path.join(d, "flight-proc-2-1.jsonl"), "w") as f:
        f.write(json.dumps(ev) + "\n")
    samples = load_corpus(d).latency_samples()
    assert samples == {"reroute": [1.5]}


def test_latency_samples_standalone_flight_counts(tmp_path):
    d = str(tmp_path)
    with open(os.path.join(d, "flight-proc-3-1.jsonl"), "w") as f:
        f.write(json.dumps({"t": 2.0, "event": "policy_decision_measured",
                            "mechanism": "restore",
                            "measured_recovery_s": 30.0}) + "\n")
    assert load_corpus(d).latency_samples() == {"restore": [30.0]}


def test_stats_shape(tmp_path):
    d = str(tmp_path)
    _write(d, "incident-0.json", _incident("t0", flight=[
        {"t": 1.0, "event": "degrade_decision", "mechanism": "reroute",
         "measured_recovery_s": 0.4}]))
    s = load_corpus(d).stats()
    assert s["incidents"] == 1
    assert s["latency_samples"] == {"reroute": 1}
