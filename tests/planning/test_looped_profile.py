"""The planner's rows for a model that repeats layers (`models/base.py`:
the contract; `planning/profiler.py`): a repeated layer is timed ONCE and
charged its forward, its backward and the bytes it hands on times its
passes, its parameters once; and a template over two stages balances on
what a microbatch really costs, not on one application."""

import jax
import pytest

from oobleck_tpu.models import build_model
from oobleck_tpu.models.base import param_bytes
from oobleck_tpu.planning import profiler
from oobleck_tpu.planning.templates import LayerProfile, TemplateGenerator

MB, SEQ = 1, 32
# One application's milliseconds, by what is timed: the head dear, so that
# where the cut falls depends on what the blocks are charged.
FORWARD = {"embed": 1.0, "block": 1.0, "close": 1.0, "head": 4.0}


@pytest.fixture
def timed_by_name(monkeypatch):
    """`rows_of(passes)`: ouro-tiny at four blocks and its profile rows,
    `_time_repeated` answering from `FORWARD` (a backward: twice that);
    and the calls it got."""
    calls = []

    def rows_of(passes):
        model = build_model("ouro-tiny", {"num_layers": 4,
                                          "num_passes": passes})
        # The profiler times the first layer of each name's prefix, its
        # forward and then its backward.
        timed = iter(name for name in FORWARD for _ in range(2))

        def answer(fn_once, x0, *fixed, reps=0):
            calls.append(fn_once.__name__)
            return FORWARD[next(timed)] * (
                2.0 if fn_once.__name__ == "bwd" else 1.0)

        monkeypatch.setattr(profiler, "_time_repeated", answer)
        return model, profiler.profile_execution_layers(model, MB, SEQ)

    return rows_of, calls


def test_a_repeated_layer_is_charged_its_passes_and_its_parameters_once(
        timed_by_name):
    rows_of, calls = timed_by_name
    model, rows = rows_of(3)
    _, once = rows_of(1)
    # embed, block (timed once for three), close, head: forward and
    # backward each, for either model.
    assert calls == ["fwd", "bwd"] * 8
    names = [model.layer_name(i) for i in range(6)]
    assert names == ["embed", "block_0", "block_1", "block_2", "close_3",
                     "head"]
    for li, (row, single) in enumerate(zip(rows, once)):
        passes = 3 if 1 <= li <= 4 else 1
        assert row.get("passes", 1) == passes
        assert "passes" not in single
        assert row["forward"] == passes * single["forward"]
        assert row["backward"] == passes * single["backward"]
        # Parameters once; the bytes a visit hands on (and saves) a pass.
        params = param_bytes(model.init_layer(jax.random.PRNGKey(0), li))
        assert row["mem_required"][0] == single["mem_required"][0] == params
    carry = SEQ * (2 * 64 + 3 * 2 * 64 + 3 * 4)        # h, 3 exits, 3 gates
    assert [r["mem_required"][1] for r in rows[:5]] == [
        carry, 3 * carry, 3 * carry, 3 * carry, 3 * carry]


def as_profiles(rows) -> list[LayerProfile]:
    return [LayerProfile(
        layer_index=i, forward=r["forward"], backward=r["backward"],
        allreduce_in_host={1: 0.0}, allreduce_across_hosts={1: 0.0, 2: 0.1},
        mem_params=r["mem_required"][0], mem_activation=r["mem_required"][1])
        for i, r in enumerate(rows)]


@pytest.mark.parametrize("passes,first_stage", [
    # One application each: five cheap layers weigh what the head weighs.
    (1, (0, 1, 2, 3, 4)),
    # Three passes: the blocks are the cost, and the cut falls among them.
    (3, (0, 1, 2, 3)),
])
def test_a_template_over_two_stages_balances_on_what_a_microbatch_costs(
        timed_by_name, passes, first_stage):
    rows_of, _ = timed_by_name
    _, rows = rows_of(passes)
    (template,) = TemplateGenerator(engine="python").create_pipeline_templates(
        as_profiles(rows), (2, 2), 1)
    assert template.num_stages == 2
    assert template.stages[0].layer_indices == first_stage
    charged = [r["forward"] + r["backward"] for r in rows]
    assert [s.latency for s in template.stages] == [
        pytest.approx(sum(charged[:len(first_stage)])),
        pytest.approx(sum(charged[len(first_stage):]))]
