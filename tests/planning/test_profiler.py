"""Profiler tests: JSON cache schema + loader roundtrip + planner hookup
(the reference's profiler test is GPU-gated and drifted,
/root/reference/tests/planning/test_profiler.py:23; ours runs on CPU with the
tiny model)."""

import json

import pytest

from oobleck_tpu.planning import profiler as prof
from oobleck_tpu.planning.profiler import load_profile, profile
from oobleck_tpu.planning.templates import TemplateGenerator


@pytest.fixture(scope="module")
def cache(tmp_path_factory, monkeypatch_module=None):
    import os

    tmp = tmp_path_factory.mktemp("profiles")
    old = os.environ.get("OOBLECK_TPU_CACHE")
    os.environ["OOBLECK_TPU_CACHE"] = str(tmp)
    yield tmp
    if old is None:
        os.environ.pop("OOBLECK_TPU_CACHE", None)
    else:
        os.environ["OOBLECK_TPU_CACHE"] = old


def test_profile_writes_reference_schema(cache):
    path = profile("gpt2-tiny", {}, microbatch_size=2, seq_len=32,
                   chips_per_host=4, max_hosts=4)
    for fname in ("mb2.json", "allreduce_in_node.json",
                  "allreduce_across_nodes.json", "model_args.json"):
        assert (path / fname).exists(), fname

    mb = json.loads((path / "mb2.json").read_text())
    assert len(mb) == 6  # embed + 4 blocks + head
    for row in mb:
        assert row["forward"] > 0 and row["backward"] > 0
        assert len(row["mem_required"]) == 2 and row["mem_required"][0] > 0

    ar_in = json.loads((path / "allreduce_in_node.json").read_text())
    assert set(ar_in[0].keys()) == {"1", "2", "4"}
    assert ar_in[1]["2"] > 0  # block layer, modeled ICI time


def test_load_profile_roundtrip(cache):
    profile("gpt2-tiny", {}, microbatch_size=2, seq_len=32,
            chips_per_host=4, max_hosts=4)
    profiles = load_profile("gpt2-tiny", "default", 2)
    assert len(profiles) == 6
    assert profiles[0].layer_index == 0
    assert profiles[2].allreduce_in_host[2] > 0
    assert profiles[2].allreduce_across_hosts[4] > 0


def test_profile_cache_hit_and_validation(cache):
    p1 = profile("gpt2-tiny", {}, microbatch_size=2, seq_len=32)
    mtime = (p1 / "mb2.json").stat().st_mtime
    p2 = profile("gpt2-tiny", {}, microbatch_size=2, seq_len=32)
    assert (p2 / "mb2.json").stat().st_mtime == mtime  # cache hit, no rerun
    with pytest.raises(ValueError, match="model_args"):
        profile("gpt2-tiny", {"n_layer": 2}, microbatch_size=2, seq_len=32)


@pytest.mark.parametrize("stale", ["absent", "older"])
def test_profile_of_another_version_is_measured_again(cache, stale):
    """A cache written before the timed programs changed (no version file,
    or another number) is no hit, and its rows of other microbatch sizes
    go with it."""
    path = profile("gpt2-tiny", {}, microbatch_size=2, seq_len=32)
    assert json.loads((path / "profile_version.json").read_text()) \
        == prof.PROFILE_VERSION
    (path / "mb2.json").write_text(json.dumps("old rows"))
    (path / "mb4.json").write_text(json.dumps("old rows"))
    if stale == "absent":
        (path / "profile_version.json").unlink()
    else:
        (path / "profile_version.json").write_text(
            json.dumps(prof.PROFILE_VERSION - 1))
    profile("gpt2-tiny", {}, microbatch_size=2, seq_len=32)
    assert len(json.loads((path / "mb2.json").read_text())) == 6
    assert not (path / "mb4.json").exists()
    assert json.loads((path / "profile_version.json").read_text()) \
        == prof.PROFILE_VERSION


def test_profiles_feed_planner(cache):
    profile("gpt2-tiny", {}, microbatch_size=2, seq_len=32)
    profiles = load_profile("gpt2-tiny", "default", 2)
    templates = TemplateGenerator(engine="python").create_pipeline_templates(
        profiles, (1, 2), 2
    )
    assert [t.num_hosts for t in templates] == [1, 2]
    assert templates[0].iteration_time > 0


def test_allreduce_model_monotone():
    t2 = prof.allreduce_time_model(10_000_000, 2, cross_host=True)
    t8 = prof.allreduce_time_model(10_000_000, 8, cross_host=True)
    assert 0 < t2 < t8
    assert prof.allreduce_time_model(10_000_000, 1, cross_host=True) == 0.0


def test_five_kinds_of_layer_in_one_list_are_each_timed_once(cache):
    """`nemotron-h-tiny` (pattern MEM*E): embedding, Mamba-2, experts,
    attention, head. A kind is timed at its first layer and its row
    reused: the two `M` rows and the two `E` rows are equal, the kinds
    differ, and the planner takes the list."""
    path = profile("nemotron-h-tiny", {}, microbatch_size=1, seq_len=32,
                   chips_per_host=4, max_hosts=4)
    rows = json.loads((path / "mb1.json").read_text())
    assert len(rows) == 7           # embed, M, E, M, *, E, head
    assert rows[1] == rows[3] and rows[2] == rows[5]
    params = [r["mem_required"][0] for r in rows]
    assert len({params[1], params[2], params[4]}) == 3
    assert all(r["forward"] > 0 and r["backward"] > 0 for r in rows)
    profiles = load_profile("nemotron-h-tiny", "default", 1)
    templates = TemplateGenerator(engine="python").create_pipeline_templates(
        profiles, (1, 2), 1)
    assert [t.num_hosts for t in templates] == [1, 2]
    assert all(sum(len(s.layer_indices) for s in t.stages) == 7
               for t in templates)
