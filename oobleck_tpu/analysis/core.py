"""Rule framework for oobleck-lint.

A run parses every target file once into a :class:`Project`, hands it to
each registered :class:`Rule`, then filters raw findings through inline
suppressions and the checked-in baseline. Only what survives — NEW
findings — fails the run. Design constraints:

- stdlib only, no imports of the analyzed code (parsing, never running);
- fingerprints are line-number independent (rule | path | scope |
  source-line hash) so unrelated edits above a grandfathered finding
  don't churn the baseline;
- suppressions carry their reason in the comment itself
  (``# oobleck: allow[OBL002] -- eval sweep, off the hot path``), the
  baseline carries one per entry, so every exemption is justified where
  a reviewer will read it.
"""

from __future__ import annotations

import ast
import dataclasses
import hashlib
import json
import re
from collections.abc import Iterable, Iterator
from pathlib import Path

from oobleck_tpu.analysis import astutil

# `# oobleck: allow[OBL001]` or `# oobleck: allow[OBL001,OBL005] -- why`.
_SUPPRESS_RE = re.compile(r"#\s*oobleck:\s*allow\[([A-Z0-9,\s]+)\]")
# A line that is only a suppression comment extends its scope to the
# next source line (for statements too long to annotate inline).
_COMMENT_ONLY_RE = re.compile(r"^\s*#")

SEVERITIES = ("error", "warning")


@dataclasses.dataclass
class Finding:
    rule: str
    path: str  # project-relative, forward slashes
    line: int
    col: int
    message: str
    severity: str = "error"
    scope: str = "<module>"
    snippet: str = ""

    def fingerprint(self) -> str:
        digest = hashlib.sha1(
            self.snippet.strip().encode("utf-8", "replace")).hexdigest()[:12]
        return f"{self.rule}|{self.path}|{self.scope}|{digest}"

    def render(self) -> str:
        return (f"{self.path}:{self.line}:{self.col}: {self.rule} "
                f"[{self.severity}] {self.message} (in {self.scope})")

    def as_dict(self) -> dict:
        d = dataclasses.asdict(self)
        d["fingerprint"] = self.fingerprint()
        return d


class ModuleInfo:
    """One parsed source file plus its suppression map."""

    def __init__(self, path: Path, relpath: str, source: str):
        self.path = path
        self.relpath = relpath
        self.source = source
        self.lines = source.splitlines()
        self.tree = ast.parse(source, filename=str(path))
        astutil.attach_parents(self.tree)
        self.suppressions = self._scan_suppressions()

    def _scan_suppressions(self) -> dict[int, set[str]]:
        out: dict[int, set[str]] = {}
        for i, line in enumerate(self.lines, start=1):
            m = _SUPPRESS_RE.search(line)
            if not m:
                continue
            rules = {r.strip() for r in m.group(1).split(",") if r.strip()}
            out.setdefault(i, set()).update(rules)
            if _COMMENT_ONLY_RE.match(line):
                # Standalone comment line: covers the statement below it.
                out.setdefault(i + 1, set()).update(rules)
        return out

    def suppressed(self, rule: str, line: int) -> bool:
        return rule in self.suppressions.get(line, ())

    def line_text(self, line: int) -> str:
        if 1 <= line <= len(self.lines):
            return self.lines[line - 1]
        return ""

    def finding(self, rule: "Rule", node: ast.AST, message: str) -> Finding:
        line = getattr(node, "lineno", 1)
        return Finding(
            rule=rule.code,
            path=self.relpath,
            line=line,
            col=getattr(node, "col_offset", 0),
            message=message,
            severity=rule.severity,
            scope=astutil.scope_name(node),
            snippet=self.line_text(line),
        )


class Project:
    """Every parsed module of one run, plus lookup helpers for the
    cross-file rules (OBL004 reads message.py + agent.py + engine.py;
    OBL005 reads obs/registry.py)."""

    def __init__(self, root: Path, modules: list[ModuleInfo],
                 errors: list[str]):
        self.root = root
        self.modules = modules
        self.errors = errors
        self._by_rel = {m.relpath: m for m in modules}

    def module(self, relpath: str) -> ModuleInfo | None:
        return self._by_rel.get(relpath)

    def modules_matching(self, suffix: str) -> list[ModuleInfo]:
        return [m for m in self.modules if m.relpath.endswith(suffix)]


class Rule:
    """One named invariant. Subclasses override ``check_module`` (runs
    per file) and/or ``check_project`` (runs once, for cross-file
    rules)."""

    code = "OBL000"
    name = "unnamed"
    severity = "error"
    # One line shown by --explain and in the README table.
    rationale = ""

    def check_module(self, module: ModuleInfo,
                     project: Project) -> Iterator[Finding]:
        return iter(())

    def check_project(self, project: Project) -> Iterator[Finding]:
        return iter(())


def all_rules() -> list[Rule]:
    """The registered rule set, in code order."""
    from oobleck_tpu.analysis.rules import RULES

    return [cls() for cls in RULES]


# -------------------------------------------------------------------------
# baseline


def default_baseline_path(root: Path) -> Path:
    return root / "oobleck_tpu" / "analysis" / "baseline.json"


def load_baseline(path: Path) -> dict[str, str]:
    """{fingerprint: reason} — absent/empty file means empty baseline."""
    if not path.is_file():
        return {}
    data = json.loads(path.read_text())
    out: dict[str, str] = {}
    for entry in data.get("findings", []):
        out[entry["fingerprint"]] = entry.get("reason", "")
    return out


def write_baseline(path: Path, findings: Iterable[Finding],
                   reasons: dict[str, str] | None = None) -> None:
    reasons = reasons or {}
    entries = []
    for f in sorted(findings, key=lambda f: (f.path, f.line, f.rule)):
        fp = f.fingerprint()
        entries.append({
            "fingerprint": fp,
            "rule": f.rule,
            "path": f.path,
            "scope": f.scope,
            "reason": reasons.get(fp, "grandfathered at baseline creation"),
        })
    path.write_text(json.dumps(
        {"version": 1, "findings": entries}, indent=2) + "\n")


# -------------------------------------------------------------------------
# runner


DEFAULT_TARGETS = ("oobleck_tpu",)
_SKIP_PARTS = {"__pycache__"}


def _collect_files(root: Path, targets: Iterable[str]) -> list[Path]:
    files: list[Path] = []
    for target in targets:
        p = (root / target) if not Path(target).is_absolute() else Path(target)
        if p.is_file() and p.suffix == ".py":
            files.append(p)
        elif p.is_dir():
            files.extend(sorted(
                f for f in p.rglob("*.py")
                if not (_SKIP_PARTS & set(f.parts))
            ))
    return files


@dataclasses.dataclass
class AnalysisResult:
    new: list[Finding]
    suppressed: list[Finding]
    baselined: list[Finding]
    unused_baseline: list[str]  # stale fingerprints (fixed findings)
    parse_errors: list[str]
    rules_run: int
    files_scanned: int

    @property
    def exit_code(self) -> int:
        return 1 if (self.new or self.parse_errors) else 0

    def summary(self) -> dict:
        return {
            "rules": self.rules_run,
            "files": self.files_scanned,
            "findings_new": len(self.new),
            "findings_suppressed": len(self.suppressed),
            "findings_baselined": len(self.baselined),
            "baseline_unused": len(self.unused_baseline),
            "parse_errors": len(self.parse_errors),
        }


def build_project(root: Path,
                  targets: Iterable[str] = DEFAULT_TARGETS) -> Project:
    modules: list[ModuleInfo] = []
    errors: list[str] = []
    for path in _collect_files(root, targets):
        rel = path.relative_to(root).as_posix() \
            if path.is_relative_to(root) else path.as_posix()
        try:
            modules.append(ModuleInfo(path, rel, path.read_text()))
        except (SyntaxError, UnicodeDecodeError, OSError) as e:
            errors.append(f"{rel}: {type(e).__name__}: {e}")
    return Project(root, modules, errors)


def run_analysis(root: Path,
                 targets: Iterable[str] = DEFAULT_TARGETS,
                 rules: list[Rule] | None = None,
                 baseline: dict[str, str] | None = None) -> AnalysisResult:
    """Parse, run every rule, split findings into new / suppressed /
    baselined. ``baseline=None`` loads the checked-in default."""
    project = build_project(root, targets)
    if rules is None:
        rules = all_rules()
    if baseline is None:
        baseline = load_baseline(default_baseline_path(root))

    raw: list[Finding] = []
    for rule in rules:
        for module in project.modules:
            raw.extend(rule.check_module(module, project))
        raw.extend(rule.check_project(project))

    new: list[Finding] = []
    suppressed: list[Finding] = []
    baselined: list[Finding] = []
    seen_fps: set[str] = set()
    for f in sorted(raw, key=lambda f: (f.path, f.line, f.col, f.rule)):
        module = project.module(f.path)
        if module is not None and module.suppressed(f.rule, f.line):
            suppressed.append(f)
        elif f.fingerprint() in baseline:
            seen_fps.add(f.fingerprint())
            baselined.append(f)
        else:
            new.append(f)
    unused = sorted(set(baseline) - seen_fps)
    return AnalysisResult(
        new=new, suppressed=suppressed, baselined=baselined,
        unused_baseline=unused, parse_errors=project.errors,
        rules_run=len(rules), files_scanned=len(project.modules),
    )
