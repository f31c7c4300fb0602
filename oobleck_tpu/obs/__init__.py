"""Observability plane: distributed tracing and incident forensics.

Two pieces, both dependency-free:

- ``spans``: bounded-ring span recorder with trace-context propagation
  over the elastic verbs and Chrome-trace/Perfetto export.
- ``incident``: joins spans + flight-recorder rings + metrics snapshots
  into atomically committed ``incident-<n>.json`` postmortems with a
  recovery phase breakdown; rendered by ``python -m
  oobleck_tpu.obs.report`` (``make trace-report``).
"""

from oobleck_tpu.obs.incident import IncidentBuilder, list_incidents
from oobleck_tpu.obs.spans import (
    TRACE_KEY,
    SpanRecorder,
    event,
    extract,
    inject,
    new_trace_id,
    set_ambient,
    span,
    span_recorder,
    to_chrome_trace,
    write_chrome_trace,
)

__all__ = [
    "IncidentBuilder",
    "SpanRecorder",
    "TRACE_KEY",
    "event",
    "extract",
    "inject",
    "list_incidents",
    "new_trace_id",
    "set_ambient",
    "span",
    "span_recorder",
    "to_chrome_trace",
    "write_chrome_trace",
]
