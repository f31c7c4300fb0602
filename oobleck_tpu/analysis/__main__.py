"""CLI for oobleck-lint: ``python -m oobleck_tpu.analysis [targets...]``.

Exit status is 0 when the tree is clean (no findings beyond inline
suppressions and the checked-in baseline) and 1 when there is anything
new — which is what lets ``make analyze`` gate the build. ``--json``
emits the machine-readable report; ``--write-baseline`` grandfathers the
current findings (use sparingly: the intended fix for a finding is a fix).
"""

from __future__ import annotations

import argparse
import json
import sys
from pathlib import Path

from oobleck_tpu.analysis.core import (
    DEFAULT_TARGETS,
    all_rules,
    default_baseline_path,
    load_baseline,
    run_analysis,
    write_baseline,
)


def _find_root(start: Path) -> Path:
    """Nearest ancestor containing the ``oobleck_tpu`` package."""
    for cand in (start, *start.parents):
        if (cand / "oobleck_tpu" / "__init__.py").is_file():
            return cand
    return start


def main(argv: list[str] | None = None) -> int:
    parser = argparse.ArgumentParser(
        prog="python -m oobleck_tpu.analysis",
        description="project-native static analysis (rules OBL001-OBL006)")
    parser.add_argument("targets", nargs="*", default=None,
                        help=f"files/dirs relative to the repo root "
                             f"(default: {' '.join(DEFAULT_TARGETS)})")
    parser.add_argument("--root", type=Path, default=None,
                        help="repo root (default: auto-detect from cwd)")
    parser.add_argument("--json", action="store_true",
                        help="emit a JSON report instead of text")
    parser.add_argument("--explain", action="store_true",
                        help="print the rule table and exit")
    parser.add_argument("--baseline", type=Path, default=None,
                        help="baseline file (default: the checked-in one)")
    parser.add_argument("--no-baseline", action="store_true",
                        help="ignore the baseline (report everything)")
    parser.add_argument("--write-baseline", action="store_true",
                        help="grandfather all current findings and exit 0")
    parser.add_argument("--show-suppressed", action="store_true",
                        help="also list suppressed/baselined findings")
    args = parser.parse_args(argv)

    rules = all_rules()
    if args.explain:
        for rule in rules:
            print(f"{rule.code}  {rule.name:<20} [{rule.severity}]  "
                  f"{rule.rationale}")
        return 0

    root = (args.root or _find_root(Path.cwd())).resolve()
    targets = tuple(args.targets) if args.targets else DEFAULT_TARGETS
    baseline_path = args.baseline or default_baseline_path(root)
    baseline = {} if (args.no_baseline or args.write_baseline) \
        else load_baseline(baseline_path)

    result = run_analysis(root, targets, rules=rules, baseline=baseline)

    if args.write_baseline:
        write_baseline(baseline_path, result.new)
        print(f"wrote {len(result.new)} finding(s) to {baseline_path}")
        return 0

    if args.json:
        print(json.dumps({
            "summary": result.summary(),
            "new": [f.as_dict() for f in result.new],
            "suppressed": [f.as_dict() for f in result.suppressed],
            "baselined": [f.as_dict() for f in result.baselined],
            "unused_baseline": result.unused_baseline,
            "parse_errors": result.parse_errors,
        }, indent=2))
        return result.exit_code

    for err in result.parse_errors:
        print(f"PARSE ERROR: {err}")
    for f in result.new:
        print(f.render())
    if args.show_suppressed:
        for f in result.suppressed:
            print(f"suppressed: {f.render()}")
        for f in result.baselined:
            print(f"baselined:  {f.render()}")
    for fp in result.unused_baseline:
        print(f"note: baseline entry no longer fires (remove it): {fp}")

    s = result.summary()
    print(f"oobleck-lint: {s['files']} file(s), {s['rules']} rule(s): "
          f"{s['findings_new']} new, {s['findings_suppressed']} suppressed, "
          f"{s['findings_baselined']} baselined")
    return result.exit_code


if __name__ == "__main__":
    sys.exit(main())
