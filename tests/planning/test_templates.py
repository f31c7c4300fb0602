"""Template-generator tests, mirroring the reference's planning coverage
(/root/reference/tests/planning/test_pipeline_template.py:15-93) plus a
Python-vs-C++ engine equivalence check."""

import random

import pytest

from oobleck_tpu.planning.templates import (
    LayerProfile,
    PipelineTemplate,
    TemplateGenerator,
    _python_create_templates,
)


def dummy_profiles(num_layers=8, chips_per_host=4, max_hosts=8, seed=0):
    """Random per-layer latencies, like the reference conftest's dummy
    profiles (tests/conftest.py:119-142)."""
    rng = random.Random(seed)
    out = []
    for i in range(num_layers):
        fwd = rng.uniform(1.0, 5.0)
        out.append(LayerProfile(
            layer_index=i,
            forward=fwd,
            backward=fwd * 3,
            allreduce_in_host={n: 0.05 * n for n in (1, 2, 4, 8, 16)
                               if n <= chips_per_host},
            allreduce_across_hosts={n: 0.2 * n for n in range(1, max_hosts + 1)},
            mem_params=10_000_000,
            mem_activation=1_000_000,
        ))
    return out


@pytest.fixture(scope="module")
def profiles():
    return dummy_profiles()


def test_single_host(profiles):
    gen = TemplateGenerator(engine="python")
    templates = gen.create_pipeline_templates(profiles, (1, 1), 4)
    assert len(templates) == 1
    t = templates[0]
    assert t.num_hosts == 1
    assert t.num_chips == 4
    # all layers covered exactly once, in order
    covered = [i for s in t.stages for i in s.layer_indices]
    assert covered == list(range(8))


def test_feasible_range(profiles):
    gen = TemplateGenerator(engine="python")
    templates = gen.create_pipeline_templates(profiles, (1, 4), 1)
    assert [t.num_hosts for t in templates] == [1, 2, 3, 4]
    for t in templates:
        assert t.num_stages >= t.num_hosts
        assert t.num_chips == t.num_hosts  # 1 chip/host
        assert t.iteration_time > 0


def test_too_many_hosts_infeasible(profiles):
    # more hosts than layers -> no feasible template for those counts
    gen = TemplateGenerator(engine="python")
    templates = gen.create_pipeline_templates(profiles, (9, 12), 1)
    assert templates == []


def test_stage_count_is_cost_optimal(profiles):
    """For one host with multiple chips the generator may fuse layers into
    fewer stages; whatever it picks must beat per-layer stages on cost."""
    gen = TemplateGenerator(engine="python")
    [t] = gen.create_pipeline_templates(profiles, (1, 1), 4)
    assert 1 <= t.num_stages <= 8


def test_rank_grid(profiles):
    gen = TemplateGenerator(engine="python")
    [t] = gen.create_pipeline_templates(profiles, (2, 2), 4)
    ranks = list(range(t.num_chips))
    grid = t.get_rank_grid(ranks)
    assert set(grid.keys()) == set(range(8))
    for layer_ranks in grid.values():
        assert len(layer_ranks) == 4  # chips_per_host entries per layer


def test_memory_aggregation(profiles):
    gen = TemplateGenerator(engine="python")
    [t] = gen.create_pipeline_templates(profiles, (1, 1), 4)
    total_mem = sum(s.mem_required for s in t.stages)
    assert total_mem == 8 * (6 * 10_000_000 + 1_000_000)


def test_native_matches_python():
    """The C++ engine must produce identical templates and costs."""
    pytest.importorskip("numpy")
    from oobleck_tpu.planning import _native

    for seed in (0, 1, 2):
        profiles = dummy_profiles(num_layers=6, chips_per_host=2, seed=seed)
        py = _python_create_templates(profiles, (1, 4), 2)
        cc = _native.create_pipeline_templates(profiles, (1, 4), 2)
        assert len(py) == len(cc)
        for a, b in zip(py, cc):
            assert a.num_hosts == b.num_hosts
            assert a.iteration_time == pytest.approx(b.iteration_time, rel=1e-9)
            assert a.layers_per_stage() == b.layers_per_stage()
            assert [s.num_chips for s in a.stages] == [s.num_chips for s in b.stages]


def test_native_builds_from_clean_tree(tmp_path, monkeypatch):
    """No binary ships in git: a source tree with no planner binary (a
    fresh checkout, or the copy of one the chip machine gets) must build it
    from planner.cpp on first use, and a changed planner.cpp must never load
    the binary of the old source — it builds its own, under a name that
    carries the source's digest (file times do not survive a copy).

    Runs against a COPY of csrc in tmp_path so the in-tree binary other
    tests have loaded is untouched."""
    import shutil

    from oobleck_tpu.planning import _native

    csrc = tmp_path / "csrc"
    csrc.mkdir()
    for src in _native._CSRC.iterdir():
        if src.suffix != ".so":  # clean tree: sources only
            shutil.copy2(src, csrc / src.name)
    monkeypatch.setattr(_native, "_CSRC", csrc)
    monkeypatch.setattr(_native, "_lib", None)
    profiles = dummy_profiles(num_layers=6, chips_per_host=2, seed=0)
    out = _native.create_pipeline_templates(profiles, (1, 2), 2)
    assert out, "rebuilt planner returned no templates"
    [built] = csrc.glob("*.so")

    with open(csrc / "planner.cpp", "a") as f:
        f.write("\n// edited\n")
    monkeypatch.setattr(_native, "_lib", None)
    assert _native.create_pipeline_templates(profiles, (1, 2), 2)
    assert len(list(csrc.glob("*.so"))) == 2, "edited source reused the binary"
    assert built.exists()
    # teardown restores _CSRC/_lib to their pre-test values, so later
    # tests keep using the real in-tree planner untouched.


def test_auto_engine_says_when_it_falls_back_to_python(monkeypatch, caplog):
    """engine="auto" survives a native planner that cannot build (no
    compiler on the machine) with the same templates from the Python twin —
    and says so; engine="native" raises."""
    import logging

    from oobleck_tpu.planning import _native

    def no_compiler():
        raise FileNotFoundError("make")

    monkeypatch.setattr(_native, "_load", no_compiler)
    profiles = dummy_profiles(num_layers=6, chips_per_host=2, seed=0)
    with caplog.at_level(logging.WARNING, logger="oobleck.planning"):
        got = TemplateGenerator(engine="auto").create_pipeline_templates(
            profiles, (1, 2), 2)
    assert got == TemplateGenerator(engine="python").create_pipeline_templates(
        profiles, (1, 2), 2)
    assert "native planner unavailable (FileNotFoundError" in caplog.text
    with pytest.raises(FileNotFoundError):
        TemplateGenerator(engine="native").create_pipeline_templates(
            profiles, (1, 2), 2)


def test_json_roundtrip(profiles):
    gen = TemplateGenerator(engine="python")
    [t] = gen.create_pipeline_templates(profiles, (2, 2), 4)
    t2 = PipelineTemplate.from_json(t.to_json(), t.num_layers)
    assert t2 == t


# --------------------------------------------------------------------- #
# comm-hidden-fraction: the overlapped-step cost model (parallel/overlap)
# --------------------------------------------------------------------- #

def test_comm_hidden_fraction_zero_is_reference(profiles):
    """hf=0.0 must reproduce the reference cost model bit-for-bit — the
    default argument cannot perturb existing plans."""
    gen = TemplateGenerator(engine="python")
    base = gen.create_pipeline_templates(profiles, (1, 4), 4)
    hf0 = gen.create_pipeline_templates(profiles, (1, 4), 4,
                                        comm_hidden_fraction=0.0)
    assert hf0 == base


def test_stage_spec_discounts_hidden_allreduce(profiles):
    from oobleck_tpu.planning.templates import StageSpec

    s0 = StageSpec.build(profiles, 0, 4, 4)
    sh = StageSpec.build(profiles, 0, 4, 4, comm_hidden_fraction=0.05)
    s1 = StageSpec.build(profiles, 0, 4, 4, comm_hidden_fraction=1.0)
    # dummy profiles: in-host ar (0.2) < every layer's per-chip compute
    # share, so hf=1 hides it entirely — forward collapses to pure compute.
    assert s1.forward == pytest.approx(
        sum(p.forward for p in profiles[:4]) / 4)
    assert s1.latency < sh.latency < s0.latency
    # only the latency projection moves; shape and memory are untouched
    assert (s0.layer_indices, s0.num_chips, s0.mem_required) == (
        s1.layer_indices, s1.num_chips, s1.mem_required)


def test_comm_hidden_fraction_lowers_iteration_time(profiles):
    gen = TemplateGenerator(engine="python")
    base = gen.create_pipeline_templates(profiles, (1, 4), 4)
    hf = gen.create_pipeline_templates(profiles, (1, 4), 4,
                                       comm_hidden_fraction=0.9)
    assert len(hf) == len(base)
    for t_hf, t_base in zip(hf, base):
        assert t_hf.iteration_time <= t_base.iteration_time + 1e-12
    # single-host template: every stage runs 4 chips, so the in-host
    # allreduce is on the path and the discount must strictly win
    [b1] = gen.create_pipeline_templates(profiles, (1, 1), 4)
    [h1] = gen.create_pipeline_templates(profiles, (1, 1), 4,
                                         comm_hidden_fraction=0.9)
    assert h1.iteration_time < b1.iteration_time


def test_auto_engine_honors_hf_via_python_fallback(profiles):
    """comm_hidden_fraction > 0 must bypass the native engine (which
    predates the overlap cost model): auto == python at the same hf, not
    the native hf=0 answer."""
    auto = TemplateGenerator(engine="auto").create_pipeline_templates(
        profiles, (1, 4), 4, comm_hidden_fraction=0.5)
    py = TemplateGenerator(engine="python").create_pipeline_templates(
        profiles, (1, 4), 4, comm_hidden_fraction=0.5)
    assert auto == py
