"""Framework behavior: suppressions, baseline, CLI exit codes, and the
two repo-level gates (tree is lint-clean; generated registry is fresh).
"""

from __future__ import annotations

import json
from pathlib import Path

import pytest

from oobleck_tpu.analysis import (
    load_baseline,
    run_analysis,
)
from oobleck_tpu.analysis.__main__ import main as cli_main
from oobleck_tpu.analysis.core import DEFAULT_TARGETS, write_baseline
from oobleck_tpu.analysis.genregistry import generate, registry_path
from tests.analysis.conftest import codes

REPO_ROOT = Path(__file__).resolve().parents[2]

VIOLATION = """\
    import threading

    def work():
        jax.device_put(x)

    def start():
        threading.Thread(target=work).start()
"""

CLEAN = """\
    def main():
        return 1 + 1
"""


# --------------------------------------------------------------------------
# suppressions


def test_inline_suppression_same_line(analyze):
    result = analyze({"mod.py": """\
        import threading

        def work():
            jax.device_put(x)  # oobleck: allow[OBL001] -- test fixture

        def start():
            threading.Thread(target=work).start()
    """})
    assert codes(result) == []
    assert [f.rule for f in result.suppressed] == ["OBL001"]


def test_comment_line_above_covers_next_line(analyze):
    result = analyze({"mod.py": """\
        import threading

        def work():
            # oobleck: allow[OBL001] -- test fixture
            jax.device_put(x)

        def start():
            threading.Thread(target=work).start()
    """})
    assert codes(result) == []
    assert [f.rule for f in result.suppressed] == ["OBL001"]


def test_suppression_is_rule_specific(analyze):
    # An allow for a DIFFERENT rule must not silence OBL001.
    result = analyze({"mod.py": """\
        import threading

        def work():
            jax.device_put(x)  # oobleck: allow[OBL002] -- wrong rule

        def start():
            threading.Thread(target=work).start()
    """})
    assert codes(result) == ["OBL001"]


# --------------------------------------------------------------------------
# baseline


def test_baseline_grandfathers_finding(analyze):
    first = analyze({"mod.py": VIOLATION})
    assert codes(first) == ["OBL001"]
    baseline = {f.fingerprint(): "grandfathered" for f in first.new}
    second = analyze({"mod.py": VIOLATION}, baseline=baseline)
    assert codes(second) == []
    assert [f.rule for f in second.baselined] == ["OBL001"]
    assert second.exit_code == 0


def test_baseline_fingerprint_survives_line_shifts(analyze):
    first = analyze({"mod.py": VIOLATION})
    baseline = {f.fingerprint(): "grandfathered" for f in first.new}
    shifted = "    # a new comment\n    # another\n\n" + VIOLATION
    second = analyze({"mod.py": shifted}, baseline=baseline)
    assert codes(second) == []
    assert [f.rule for f in second.baselined] == ["OBL001"]


def test_unused_baseline_entries_reported(analyze):
    result = analyze({"mod.py": CLEAN},
                     baseline={"OBL001|gone.py|work|deadbeef0000": "stale"})
    assert result.unused_baseline == ["OBL001|gone.py|work|deadbeef0000"]
    assert result.exit_code == 0  # stale entries warn, never fail


def test_write_and_load_baseline_roundtrip(analyze, tmp_path):
    first = analyze({"mod.py": VIOLATION})
    path = tmp_path / "baseline.json"
    write_baseline(path, first.new)
    loaded = load_baseline(path)
    assert set(loaded) == {f.fingerprint() for f in first.new}
    assert all(reason for reason in loaded.values())


def test_parse_error_fails_the_run(analyze):
    result = analyze({"mod.py": "def broken(:\n"})
    assert result.parse_errors
    assert result.exit_code == 1


# --------------------------------------------------------------------------
# CLI


def _write_tree(root: Path, files: dict[str, str]) -> None:
    import textwrap

    for rel, src in files.items():
        p = root / rel
        p.parent.mkdir(parents=True, exist_ok=True)
        p.write_text(textwrap.dedent(src))


def test_cli_nonzero_exit_on_seeded_violation(tmp_path, capsys):
    _write_tree(tmp_path, {"mod.py": VIOLATION})
    rc = cli_main(["--root", str(tmp_path), "mod.py"])
    out = capsys.readouterr().out
    assert rc == 1
    assert "OBL001" in out


def test_cli_zero_exit_on_clean_tree(tmp_path, capsys):
    _write_tree(tmp_path, {"mod.py": CLEAN})
    rc = cli_main(["--root", str(tmp_path), "mod.py"])
    assert rc == 0
    assert "0 new" in capsys.readouterr().out


def test_cli_json_report(tmp_path, capsys):
    _write_tree(tmp_path, {"mod.py": VIOLATION})
    rc = cli_main(["--root", str(tmp_path), "--json", "mod.py"])
    report = json.loads(capsys.readouterr().out)
    assert rc == 1
    assert report["summary"]["findings_new"] == 1
    assert report["new"][0]["rule"] == "OBL001"
    assert report["new"][0]["fingerprint"].startswith("OBL001|mod.py|work|")


def test_cli_write_baseline_then_clean(tmp_path, capsys):
    _write_tree(tmp_path, {"mod.py": VIOLATION})
    baseline = tmp_path / "baseline.json"
    rc = cli_main(["--root", str(tmp_path), "--baseline", str(baseline),
                   "--write-baseline", "mod.py"])
    assert rc == 0
    rc = cli_main(["--root", str(tmp_path), "--baseline", str(baseline),
                   "mod.py"])
    capsys.readouterr()
    assert rc == 0


def test_cli_explain_lists_all_rules(capsys):
    rc = cli_main(["--explain"])
    out = capsys.readouterr().out
    assert rc == 0
    for code in ("OBL001", "OBL002", "OBL003", "OBL004", "OBL005", "OBL006"):
        assert code in out


# --------------------------------------------------------------------------
# repo-level gates


def test_repo_tree_is_lint_clean():
    """The actual tree passes the analyzer with the checked-in baseline:
    every intentional exemption is an inline suppression with a reason,
    and nothing new has crept in."""
    result = run_analysis(REPO_ROOT)
    assert not result.parse_errors
    assert [f.render() for f in result.new] == []
    assert result.files_scanned > 50
    assert result.rules_run == 6


def test_default_targets_all_exist():
    """A default target that names nothing is skipped in silence, and the
    gate then covers less than its line says."""
    assert DEFAULT_TARGETS
    for target in DEFAULT_TARGETS:
        assert (REPO_ROOT / target).exists(), target


def test_checked_in_registry_is_fresh():
    """obs/registry.py matches what the generator produces from the
    current tree — `make gen-registry` was run after the last rename."""
    assert registry_path(REPO_ROOT).read_text() == generate(REPO_ROOT)


@pytest.mark.smoke
def test_repo_baseline_is_empty():
    """The checked-in baseline holds no grandfathered findings: every
    true positive the analyzer found was fixed, not baselined (keep it
    that way)."""
    baseline = load_baseline(
        REPO_ROOT / "oobleck_tpu" / "analysis" / "baseline.json")
    assert baseline == {}
