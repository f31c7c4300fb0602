"""The benchmark is driven by data: every name resolves to a file."""

import copy
import importlib
import inspect
import json
import re
import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]
BENCH = ROOT / "benchmarks"
MANIFEST = json.loads((ROOT / "BENCHMARK.json").read_text())
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
SOURCES = {"device_trace", "program_span", "program_counter", "host_clock"}


def _load(path):
    return json.loads(Path(path).read_text())


@pytest.mark.parametrize("cell", MANIFEST["workloads"], ids=lambda c: c["name"])
def test_cell_files_are_found_by_name(cell):
    spec = _load(BENCH / "workloads" / f"{cell['name']}.json")
    assert spec["name"] == cell["name"] == f"{cell['config']}.{cell['traffic']}"
    assert spec["config"] == cell["config"] and spec["chips"] == cell["chips"]
    assert (BENCH / "configs" / f"{spec['config']}.json").exists()
    runner = importlib.import_module(f"benchmarks.runners.{spec['kind']}")
    assert callable(runner.run)
    assert len(cell["why"]) <= 200 and "\n" not in cell["why"]
    assert spec["correct"], "a cell decides `correct` on at least one limit"


@pytest.mark.parametrize("config", MANIFEST["configs"], ids=lambda c: c["name"])
def test_configuration_file_states_its_cut(config):
    spec = _load(ROOT / config["file"])
    assert spec["name"] == config["name"]
    assert spec["source"] == config["source"] and len(spec["source"]) <= 200
    assert spec["reduced"] == config["reduced"]
    for key in spec["reduced"]:
        assert key in spec and key in spec["source_values"]
        assert spec[key] != spec["source_values"][key]
        assert not key.endswith(("_dim", "_rank")) and "size" not in key
    assert spec["assumed"] and spec["deployment"] and spec["precision"]
    assert any(c["config"] == config["name"] for c in MANIFEST["workloads"])


@pytest.mark.parametrize("config", MANIFEST["configs"], ids=lambda c: c["name"])
def test_configuration_is_what_the_program_builds(config):
    """The sizes the file states are those of the model the runner asks
    the program for; no width differs from the source."""
    from oobleck_tpu.models import build_model

    spec = _load(ROOT / config["file"])
    c = build_model(spec["model_name"], dict(spec["model_args"])).config
    assert (c.vocab_size, c.max_position_embeddings, c.hidden_size,
            c.num_layers, c.num_heads, c.head_dim, c.ffn_dim) == (
        spec["vocab_size"], spec["max_position_embeddings"],
        spec["hidden_size"], spec["num_layers"], spec["num_heads"],
        spec["head_dim"], spec["intermediate_size"])


@pytest.mark.parametrize("metric", MANIFEST["per_layer"], ids=lambda m: m["name"])
def test_layer_metric_file_and_reader(metric):
    spec = _load(BENCH / "layer_metrics" / f"{metric['name']}.json")
    for key in ("name", "unit", "better", "source", "layer", "moves"):
        assert spec[key] == metric[key], key
    # Which cells report a metric is the manifest's to say, and only its:
    # a later cell that reports this metric edits no file that is here.
    assert "workloads" not in spec
    reader = importlib.import_module(f"benchmarks.readers.{spec['reader']}")
    assert callable(reader.read)
    # A reader that finds nothing to read returns nothing.
    assert reader.read({}, **spec.get("args", {})) is None
    e2e = {m["name"]: m for m in MANIFEST["end_to_end"]}
    assert metric["moves"] in e2e
    moved = e2e[metric["moves"]]
    cells = {c["name"] for c in MANIFEST["workloads"]}
    for cell in metric["workloads"]:
        assert cell in cells
        assert cell in moved.get("workloads", cells)


def test_every_layer_metric_file_is_in_the_manifest():
    listed = {m["name"] for m in MANIFEST["per_layer"]}
    on_disk = {_load(p)["name"] for p in (BENCH / "layer_metrics").glob("*.json")}
    assert on_disk == listed


def _named():
    for section in ("configs", "workloads", "end_to_end", "per_layer"):
        for entry in MANIFEST[section]:
            yield section, entry


@pytest.mark.parametrize("section,entry", list(_named()),
                         ids=lambda x: x if isinstance(x, str) else x["name"])
def test_names_units_and_sources(section, entry):
    assert NAME.match(entry["name"]), entry["name"]
    if section == "workloads":
        assert NAME.match(entry["config"]) and NAME.match(entry["traffic"])
        assert entry["chips"] in (1, 4)
    if section in ("end_to_end", "per_layer"):
        assert UNIT.match(entry["unit"]), entry["unit"]
        assert entry["better"] in ("lower", "higher")
        assert entry["source"] in SOURCES
    if section == "end_to_end":
        assert entry["source"] in ("host_clock", "device_trace")
        assert 0.01 <= entry["bound"] <= 0.1
    if section == "per_layer" and "roofline" in entry["name"]:
        assert entry["name"].endswith("_roofline") and entry["unit"] == "%"


def test_manifest_shape():
    assert set(MANIFEST) == {"command", "paths", "run_seconds", "configs",
                             "workloads", "end_to_end", "per_layer"}
    assert "setup_s" in {m["name"] for m in MANIFEST["end_to_end"]}
    assert isinstance(MANIFEST["run_seconds"], int)
    assert 1 <= MANIFEST["run_seconds"] <= 51
    for path in MANIFEST["paths"]:
        assert (ROOT / path).is_dir()
    four = sum(c["chips"] == 4 for c in MANIFEST["workloads"])
    assert four <= max(1, len(MANIFEST["workloads"]) // 4)
    names = [e["name"] for s in ("end_to_end", "per_layer") for e in MANIFEST[s]]
    assert len(names) == len(set(names))


def test_run_py_holds_no_list_of_cells_configs_or_metrics():
    text = (BENCH / "run.py").read_text()
    for _, entry in _named():
        assert f'"{entry["name"]}"' not in text.replace('"setup_s"', "")


# --------------------------------------------------------------------- #
# A PR that is NOT of kind `benchmark` adds new files and appends entries #
# (benchmarks/README.md, "What a PR of another kind may add"): every       #
# accepted test has to pass after such an append. The guard.              #
# --------------------------------------------------------------------- #

def _appended(manifest: dict) -> dict:
    """The manifest as a later PR of another kind may leave it: a
    configuration, a cell and a per-layer metric at the END of their lists,
    the cell's name at the end of `train_tokens_per_s`'s `workloads` and of
    every accepted per-layer metric's, and nothing there was changed,
    moved or dropped."""
    later = copy.deepcopy(manifest)
    first = later["workloads"][0]["name"]
    later["configs"].append(dict(
        later["configs"][0], name="the-guards-model",
        file="benchmarks/configs/the-guards-model.json"))
    later["workloads"].append(dict(
        later["workloads"][0], name="the-guards-model.steady",
        config="the-guards-model"))
    for metric in later["end_to_end"] + later["per_layer"]:
        if "workloads" in metric:
            metric["workloads"].append("the-guards-model.steady")
    later["per_layer"].append({
        "name": "the_guards_kernel_ms", "unit": "ms", "better": "lower",
        "source": "device_trace", "layer": "kernels",
        "moves": "train_tokens_per_s",
        "workloads": [first, "the-guards-model.steady"]})
    return later


def _cases(test) -> list[dict]:
    """The arguments pytest would call `test` with, from its `parametrize`
    marks; `[{}]` for a test that takes none."""
    cases = [{}]
    for mark in getattr(test, "pytestmark", []):
        if mark.name != "parametrize":
            continue
        names = mark.args[0]
        if isinstance(names, str):
            names = [n.strip() for n in names.split(",")]
        rows = [getattr(v, "values", v) for v in mark.args[1]]
        rows = [v if len(names) > 1 else (v,) for v in rows]
        cases = [dict(c, **dict(zip(names, row)))
                 for c in cases for row in rows]
    return cases


def test_appended_entries_pass_every_accepted_manifest_assertion(monkeypatch):
    """Every test under `tests/benchmarks/` that reads the module global
    `MANIFEST` (the family files keep `BENCHMARK.json` there) is called
    again, with every case it is parametrised over, on `_appended`'s copy:
    a test that holds a list's end (`[-5:]`, `configs[-1]`), a list's
    whole (`workloads == [cell]`) or a count of entries fails HERE, in the
    PR that writes it, and not in the later PR that appends. Found by
    reading the module, so a family file a later PR brings is held too.
    Of this file, the rules an entry itself must keep; that files and
    entries come together, `test_layer_metric_file_and_reader` and
    `test_every_layer_metric_file_is_in_the_manifest` hold on the repo's."""
    later = _appended(MANIFEST)
    here = sys.modules[__name__]
    called = 0
    for path in sorted(Path(__file__).parent.glob("test_bench_*.py")):
        module = importlib.import_module(f"{__package__}.{path.stem}")
        if module is here or not hasattr(module, "MANIFEST"):
            continue
        monkeypatch.setattr(module, "MANIFEST", later)
        for name, test in sorted(vars(module).items()):
            if not (name.startswith("test_") and inspect.isfunction(test)
                    and "MANIFEST" in test.__code__.co_names):
                continue
            for case in _cases(test):
                if set(inspect.signature(test).parameters) == set(case):
                    test(**case)
                    called += 1
    assert called >= 100, called
    monkeypatch.setattr(here, "MANIFEST", later)
    test_manifest_shape()
    for section in ("configs", "workloads", "per_layer"):
        test_names_units_and_sources(section, later[section][-1])
