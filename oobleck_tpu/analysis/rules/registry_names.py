"""OBL005 — metric / flight-event / span names must be registered.

History: the PR-8 forensics work found the observability plane's worst
failure mode is silent: a typo'd metric family (``oobleck_step_secnds``)
or flight-event kind just creates a parallel, never-read series, and the
dashboards that key on the real name read zero forever. The
generated registry (``obs/registry.py``, built by
``python -m oobleck_tpu.analysis.genregistry``) is the single source of
truth; this rule checks every statically-visible name against it, and
``OOBLECK_STRICT_REGISTRY=1`` makes the runtime enforce the same sets.

The name-collection logic lives here and is reused by the generator, so
the lint and the registry can never disagree about what counts as a
name-introducing call site.

Dynamic names (f-strings, variables) cannot be checked statically and
are flagged; intentionally-dynamic sites (``utils/recovery.py``'s
``recovery.{event}`` spans) carry ``# oobleck: allow[OBL005]``.
"""

from __future__ import annotations

import ast
from collections.abc import Iterator
from dataclasses import dataclass, field

from oobleck_tpu.analysis import astutil
from oobleck_tpu.analysis.core import Finding, ModuleInfo, Project, Rule

REGISTRY_MODULE = "obs/registry.py"
METRIC_METHODS = {"counter", "gauge", "histogram"}
REGISTRY_FACTORIES = {"registry"}
FLIGHT_FACTORIES = {"flight_recorder"}
SPAN_FACTORIES = {"span_recorder"}
# Module-alias receivers for the ``spans.span("name")`` / ``spans.event`` /
# ``spans.region`` free functions (each importer picks its own alias).
SPAN_MODULE_RECEIVERS = {"spans", "obs_spans", "spans_mod", "_spans"}
# Conventional local receiver names for a Registry (``reg = ... or
# metrics.registry()`` defeats assignment tracing; the idiom is stable).
REGISTRY_LOCAL_RECEIVERS = {"reg", "registry"}


@dataclass
class NameSite:
    """One statically-visible name-introducing call."""

    kind: str  # "metric" | "flight_event" | "span"
    name: str | None  # None when dynamic
    node: ast.Call
    module: ModuleInfo


@dataclass
class CollectedNames:
    metrics: set[str] = field(default_factory=set)
    flight_events: set[str] = field(default_factory=set)
    spans: set[str] = field(default_factory=set)

    def bucket(self, kind: str) -> set[str]:
        return {"metric": self.metrics, "flight_event": self.flight_events,
                "span": self.spans}[kind]


def _chained_factory(call: ast.Call) -> str | None:
    """``metrics.flight_recorder().record(...)`` -> ``flight_recorder``."""
    func = call.func
    if isinstance(func, ast.Attribute) and isinstance(func.value, ast.Call):
        return astutil.call_name(func.value)
    return None


def _site_kind(call: ast.Call, flight_vars: set[str],
               span_vars: set[str]) -> str | None:
    name = astutil.call_name(call)
    chained = _chained_factory(call)
    recv = astutil.receiver_name(call)
    if name in METRIC_METHODS:
        if chained in REGISTRY_FACTORIES or recv in REGISTRY_LOCAL_RECEIVERS:
            return "metric"
        return None
    if name == "record":
        if chained in FLIGHT_FACTORIES or recv in flight_vars:
            return "flight_event"
        if chained in SPAN_FACTORIES or recv in span_vars:
            return "span"
        return None
    if name in ("span", "event", "region") and recv in SPAN_MODULE_RECEIVERS:
        return "span"
    return None


def iter_name_sites(module: ModuleInfo) -> Iterator[NameSite]:
    """Every metric/flight-event/span name-introducing call in a module.
    Shared between this rule and the registry generator."""
    flight_vars: set[str] = set()
    span_vars: set[str] = set()
    for fns in astutil.functions_of(module.tree).values():
        for fn in fns:
            flight_vars |= astutil.resolve_recorder_vars(fn, FLIGHT_FACTORIES)
            span_vars |= astutil.resolve_recorder_vars(fn, SPAN_FACTORIES)
    for call in ast.walk(module.tree):
        if not isinstance(call, ast.Call):
            continue
        kind = _site_kind(call, flight_vars, span_vars)
        if kind is None:
            continue
        yield NameSite(kind=kind, name=astutil.first_str_arg(call),
                       node=call, module=module)


def collect_names(project: Project) -> CollectedNames:
    """All statically-known names across the project — the generator's
    input. Dynamic sites contribute nothing (they carry suppressions)."""
    out = CollectedNames()
    for module in project.modules:
        if module.relpath.endswith(REGISTRY_MODULE):
            continue
        for site in iter_name_sites(module):
            if site.name is not None:
                out.bucket(site.kind).add(site.name)
    return out


def parse_registry(module: ModuleInfo) -> dict[str, set[str]]:
    """String constants of each top-level frozenset assignment in the
    generated registry module, keyed by the assigned name."""
    out: dict[str, set[str]] = {}
    for node in module.tree.body:
        if isinstance(node, ast.Assign) and len(node.targets) == 1 \
                and isinstance(node.targets[0], ast.Name):
            out[node.targets[0].id] = {
                c.value for c in ast.walk(node.value)
                if isinstance(c, ast.Constant) and isinstance(c.value, str)
            }
    return out


KIND_TO_REGISTRY_NAME = {
    "metric": "METRIC_FAMILIES",
    "flight_event": "FLIGHT_EVENT_KINDS",
    "span": "SPAN_NAMES",
}


class RegistryNamesRule(Rule):
    code = "OBL005"
    name = "registry-names"
    rationale = ("metric/flight-event/span names must exist in the "
                 "generated obs/registry.py — typos create silent "
                 "never-read series")

    def check_project(self, project: Project) -> Iterator[Finding]:
        reg_mods = project.modules_matching(REGISTRY_MODULE)
        if not reg_mods:
            return  # registry not part of this project (rule fixtures)
        registered = parse_registry(reg_mods[0])
        for module in project.modules:
            if module.relpath.endswith(REGISTRY_MODULE):
                continue
            for site in iter_name_sites(module):
                reg_name = KIND_TO_REGISTRY_NAME[site.kind]
                allowed = registered.get(reg_name, set())
                if site.name is None:
                    yield module.finding(
                        self, site.node,
                        f"dynamic {site.kind} name cannot be checked "
                        f"against {reg_name}; use a literal, or suppress "
                        f"with a reason if dynamism is the point")
                elif site.name not in allowed:
                    yield module.finding(
                        self, site.node,
                        f"{site.kind} name '{site.name}' is not in "
                        f"obs/registry.py:{reg_name} — regenerate with "
                        f"`make gen-registry` (a typo here would emit a "
                        f"series nothing ever reads)")
