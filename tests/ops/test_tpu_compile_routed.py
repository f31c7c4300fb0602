"""`test_tpu_compile.py` for the routed experts (`ops/moe.py`): their
kernels compiled for the described v5e at every routed cell's call, and
the cells' one-stage `jit_bwd` lowered. A file of its own: under `--dist
loadfile` these are two thirds of what was one worker's (ROADMAP.md D24).
"""

import functools
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from tests.ops.programs import cell_stage
from tests.ops.test_tpu_compile import (  # noqa: F401 (fixtures)
    MOE_WIDTHS, _compile, _compiled_for_tpu, _routed_shapes, _routed_sum, v5e)


def _routed_grads(top_k):
    # The value too, as a stage's `jit_bwd` asks for the loss: the gradient
    # alone needs no y, and the combine's sum would be dead code.
    return jax.value_and_grad(_routed_sum(top_k), argnums=(0, 1, 3, 4, 5))


def _the_row_buffers_are_allocated_not_filled(text, t, d, f, ne, held, top_k):
    """The tile loops of a routed gradient start from `moe._unwritten`
    buffers: the executable holds no whole-buffer zero fill (`broadcast`)
    of a `[buffer rows, D]` or `[buffer rows, F]` array, as its parent held
    six (four without a gate), and no `copy` of one, which is what XLA
    would insert had it merged two allocations or changed a layout; an
    `AllocateBuffer` custom call stands where each fill stood (PR 51)."""
    from oobleck_tpu.ops.moe import buffer_rows

    rows, _ = buffer_rows(t, top_k, held, ne)
    of_a_buffer = rf"= \w+\[{rows},(?:{d}|{f})\]\S* "
    made = re.findall(of_a_buffer + r"(broadcast|copy)\(", text)
    assert not made, made
    allocated = re.findall(
        of_a_buffer + r'custom-call\(\), custom_call_target="AllocateBuffer"',
        text)
    return len(allocated)


def _token_sums(text):
    """`moe_token_sum` calls of a compiled routed program, which holds no
    scatter into a `[tokens, D]` array: the loop of scatter-adds each of
    them stands in for is off the kernels' path."""
    assert not re.findall(r"= f32\[\d+,\d+\]\S* scatter\(", text)
    return len(re.findall(r"%moe_token_sum[.\d]* = ", text))


@pytest.mark.parametrize("width", sorted(MOE_WIDTHS))
@pytest.mark.parametrize("mode", ["fwd", "fwd_bwd"])
def test_routed_experts_compile(v5e, width, mode):
    top_k = MOE_WIDTHS[width][-1]
    fn = _routed_sum(top_k) if mode == "fwd" else _routed_grads(top_k)
    text = _compile(fn, v5e[0], *_routed_shapes(*MOE_WIDTHS[width]))
    # Three products and the combine's sum forward; three dX, three dW and
    # the dispatch's sum more backward (`moe_token_sum`: each sum ONE
    # kernel, and no float32 [tokens, D] scatter in a loop).
    assert text.count('custom_call_target="tpu_custom_call"') == (
        4 if mode == "fwd" else 11)
    assert _token_sums(text) == (1 if mode == "fwd" else 2)
    if mode == "fwd_bwd":
        # The dispatch and the activation; d rows of the combine, d gate
        # and d up, the sum of the two d rows.
        assert _the_row_buffers_are_allocated_not_filled(
            text, *MOE_WIDTHS[width]) == 6


# Experts WITHOUT a gate (`w3=None`), at `nemotron-3-nano-30b-a3b.steady`'s
# call: 1 x 4096 tokens, 8 of 128 experts of 2688 x 1856, top 6. 1856 =
# 14.5 x 128 has no lane-multiple tile: Mosaic takes 1856 columns (rows x W1,
# dW2) and a 1856-deep contraction (act x W2, dX of W1) whole. This is where
# that width meets the TPU compiler before the chip does.
UNGATED_WIDTHS = {
    "nemotron-3-nano-30b-a3b-cell": (4096, 2688, 1856, 128, 8, 6),
    "nemotron-tiles": (512, 384, 232, 16, 4, 2),
}


def _ungated_sum(top_k):
    from oobleck_tpu.ops.moe import routed_experts

    def fn(x, router, bias, w1, w2):
        return jnp.sum(routed_experts(
            x, router, bias, w1, None, w2, num_experts=router.shape[1],
            top_k=top_k).astype(jnp.float32))

    return fn


@pytest.mark.parametrize("width", sorted(UNGATED_WIDTHS))
@pytest.mark.parametrize("mode", ["fwd", "fwd_bwd"])
def test_ungated_experts_compile(v5e, width, mode):
    t, d, f, ne, held, top_k = UNGATED_WIDTHS[width]
    fn = _ungated_sum(top_k) if mode == "fwd" else jax.value_and_grad(
        _ungated_sum(top_k), argnums=(0, 1, 3, 4))
    shapes = _routed_shapes(t, d, f, ne, held, top_k)
    text = _compile(fn, v5e[0], *shapes[:4], shapes[5])
    # Two products and the combine's sum forward; two dX, two dW and the
    # dispatch's sum more backward.
    assert text.count('custom_call_target="tpu_custom_call"') == (
        3 if mode == "fwd" else 8)
    assert _token_sums(text) == (1 if mode == "fwd" else 2)
    if mode == "fwd_bwd":
        assert _the_row_buffers_are_allocated_not_filled(
            text, *UNGATED_WIDTHS[width]) == 4
    # The parameters stay as wide as published: no operand is padded.
    assert f"{held},{d},{f}" in text.replace(" ", "")


# A routed block's host cost at process start, as flash's
# (`test_tpu_compile.py`, `FLASH_GRAD_MODULE_CHARS`): the
# gradient of one routed layer at the cell's shapes lowers to 127 k
# characters (nine kernels with small bodies, the two sums' ONE body, which
# loops over the held experts and is not unrolled over them, the plan's
# sort, ten loops over the row tiles in use); the limit leaves room for a
# tenth more.
ROUTED_GRAD_MODULE_CHARS = 140_000


def test_routed_grad_module_stays_small(v5e):
    one = SingleDeviceSharding(v5e[0])
    args = [jax.ShapeDtypeStruct(s, d, sharding=one) for s, d in
            _routed_shapes(*MOE_WIDTHS["lfm2-24b-a2b-cell"])]
    text = jax.jit(_routed_grads(
        MOE_WIDTHS["lfm2-24b-a2b-cell"][-1])).lower(*args).as_text()
    assert text.count("tpu_custom_call") == 11
    assert len(text) < ROUTED_GRAD_MODULE_CHARS, len(text)


# ReGLU experts whose router reads rows of its own (`routed_experts(
# activation="reglu", router_x=)`), value and gradient, at
# `smallthinker-21b-a3b`'s call: 16384 tokens, 8 of 64 experts of 2560 x 768, top 6. 1,536 rows
# expected an expert sit on the edge of every tile up to 512: 1024-row
# tiles, a 106,496-row buffer. The kernels are SwiGLU's nine and the two
# sums (`moe_token_sum`: blocks of 512 tokens x all 2560 columns), the XLA
# between them differs.
def test_reglu_experts_with_a_router_of_their_own_compile(v5e):
    from oobleck_tpu.ops.moe import routed_experts

    t, d, f, ne, held, top_k = 16384, 2560, 768, 64, 8, 6

    def fn(x, router, r, w1, w3, w2):
        return jnp.sum(routed_experts(
            x, router, None, w1, w3, w2, num_experts=ne, top_k=top_k,
            score="softmax", activation="reglu",
            router_x=r).astype(jnp.float32))

    shapes = _routed_shapes(t, d, f, ne, held, top_k)
    shapes[2] = shapes[0]                       # the router's rows, not a bias
    text = _compile(jax.value_and_grad(fn, argnums=(0, 1, 2, 3, 4, 5)),
                    v5e[0], *shapes)
    assert text.count('custom_call_target="tpu_custom_call"') == 11
    assert _token_sums(text) == 2
    assert _the_row_buffers_are_allocated_not_filled(
        text, t, d, f, ne, held, top_k) == 6


# The routed experts' running gradient sums go INTO `moe_tgmm` (ops/moe.py):
# the model marks the held experts' matrices, the stage's `jit_bwd` hands
# their sums down, and the dW kernel takes each as a third tensor operand
# aliased to its output. (cell: (microbatch, sequence), dW kernels a
# backward = routed layers x matrices an expert.) Lowered, not compiled:
# what the compiler then leaves of adds and copies is read where the cells'
# `jit_bwd` is compiled anyway, tests/ops/cells.py.
ROUTED_CELLS = {
    "lfm2-24b-a2b": ((8, 1024), 4 * 3),
    "moonlight-16b-a3b": ((1, 4096), 4 * 3),
    "nemotron-3-nano-30b-a3b": ((1, 4096), 3 * 2),
    "qwen3-next-80b-a3b": ((1, 4096), 4 * 3),
    "smallthinker-21b-a3b": ((1, 16384), 4 * 3),
}


def _kernel_calls(text):
    """(kernel name, the call's attributes) of every Mosaic call of a
    lowered module, without the kernels' serialized bodies."""
    return re.findall(
        r'stablehlo\.custom_call @tpu_custom_call\([^)]*\) \{backend_config = '
        r'"[^"]*", kernel_name = "(\w+)"([^\n]*)', text)


@functools.cache
def _lowered_backward(cell, devices):
    """(the lowered text of the cell's one-stage `jit_bwd`, the rotations
    `models/routed.rotate_half` built into that one trace, by width):
    lowered once a cell for the tests below."""
    from oobleck_tpu.utils import metrics

    built = metrics.registry().counter("oobleck_rotary_calls_total")
    count = lambda: {w: built.value(width=w) for w in ("64", "128")}
    (mb, seq), sums = ROUTED_CELLS.get(cell, ((4, 1024), 0))
    st, params, batch = cell_stage(cell, devices, microbatch=mb, seq=seq)
    assert st.kernel_sums == [sums]
    before = count()
    text = st.bwd[0].lower(params, params, None, batch).as_text()
    return text, {w: n - before[w] for w, n in count().items()
                  if n > before[w]}


@pytest.mark.parametrize("cell", sorted(ROUTED_CELLS))
def test_routed_cell_backward_hands_every_sum_to_its_dw_kernel(v5e, cell):
    sums = ROUTED_CELLS[cell][1]
    calls = _kernel_calls(_lowered_backward(cell, tuple(v5e))[0])
    # The rooflines and `moe_*_ms` match `%moe_gmm.` / `%moe_tgmm.`.
    assert {n for n, _ in calls if n.startswith("moe")} == {
        "moe_gmm", "moe_tgmm", "moe_token_sum"}
    dw = [attrs for n, attrs in calls if n == "moe_tgmm"]
    assert len(dw) == sums
    # Operands of the lowered call: the grid's dynamic bound (the plan's
    # `num_tiles`, PR 56: it comes first), two tables, rows, rows, the sum.
    # `tgmm_call`'s own `input_output_aliases={4: 0}` counts without it.
    for attrs in dw:
        assert ("output_operand_aliases = [#stablehlo.output_operand_alias<"
                "output_tuple_indices = [], operand_index = 5, "
                "operand_tuple_indices = []>]") in attrs
    assert not any("output_operand_aliases" in attrs
                   for n, attrs in calls if n == "moe_gmm")


# cell -> sha256 and length of the one-stage `jit_bwd`'s lowered text with
# the kernels' serialized bodies (which carry source lines) blanked.
# `gpt3-2.7b`'s as PR 47 left it (every cell's stage holds a flash backward,
# and each text got SHORTER by the second backward call of each attention
# layer: 3.1 k in `gpt3-2.7b`'s three blocks). The five routed cells' as
# PR 52 left them: each routed layer's sums of rows into tokens (the
# combine's, forward and recomputed, and the dispatch's dx) are ONE
# `moe_token_sum` call each where a `while` around a float32 [tokens, D]
# scatter-add stood, and the plan gained the tokens' side
# (`moe.token_runs`: a cumulative sum, four small arrays): 1.4 k less in
# `lfm2-24b-a2b`'s and `moonlight-16b-a3b`'s text, 0.9 k in the ungated
# cell's three routed layers, 1.2 k in `smallthinker-21b-a3b`'s, 0.6 k MORE
# in `qwen3-next-80b-a3b`'s; before it PR 51 made every whole-buffer zero
# fill an `AllocateBuffer` custom call (`moe._unwritten`). `gpt3-2.7b` has no
# routed layer and its pair stood. `nemotron-3-nano-30b-a3b`'s as PR 54 left
# it: the three Mamba-2 layers' scan is `ssd_fwd` and `ssd_bwd` (`ops/ssd.py`)
# where the `jax.numpy` scan, its recompute and JAX's derivative of both
# stood, 128 k less; the five other cells hold no scan and their pairs
# stood. PR 55: each routed cell's `jit_bwd` has one more OUTPUT, its routed
# layers' loads (`ops/moe.load_of`, int32 [layers, held + 1]: a concatenate
# a layer and one stack), 0.8 k to 1.1 k more text; `gpt3-2.7b` has no routed
# layer and its pair stood; with the telemetry ring off all six lowered to the
# pairs PR 54 left (`LOWERED_RING_OFF`; all six read so by hand, PR 55, one
# held below). PR 56: the row axis of every `moe_gmm` / `moe_tgmm` grid is
# bounded by the plan's `num_tiles` (a dynamic grid bound): each call has one
# more operand, a scalar sliced from the plan, first in the custom call, and
# `moe_gmm` one prefetched table less; 7.4 k more text in the four gated
# cells, 3.7 k in the ungated cell's three routed layers, with the ring on
# and off alike (both tables' five routed pairs are this PR's, all ten read
# by hand); `gpt3-2.7b` has no routed layer and its pair stood. PR 59:
# `qwen3-next-80b-a3b`'s three Gated DeltaNet layers run everything after the
# delta rule's inverse as `gdn_fwd` and `gdn_bwd` (`ops/gdn.py`) where the
# `jax.numpy` scan across the chunks, its recompute and JAX's derivative of
# both stood, 197 k less text, ring on and off (the second read by hand); the
# five other cells hold no delta rule and their pairs stood. A PR that
# changes what one of these programs
# computes takes its new text's pair from a failing run; one that leaves a
# pair standing has shown that the program bypasses its change (PR 46's
# rotary left `gpt3-2.7b`'s and `nemotron-3-nano-30b-a3b`'s).
LOWERED = {
    "gpt3-2.7b": ("f815b23b0da3328e", 169829),
    "lfm2-24b-a2b": ("30ac16217d2aa3d1", 622621),
    "moonlight-16b-a3b": ("3a164cc50547f3f1", 779490),
    "nemotron-3-nano-30b-a3b": ("017819795168df87", 531837),
    "qwen3-next-80b-a3b": ("f0990c6af79f8854", 855564),
    "smallthinker-21b-a3b": ("20f2cca60c7db319", 685091),
}
# With `OOBLECK_TELEMETRY=0`: the programs without the loads' output: the
# texts PR 54 left, with PR 56's grid bounds.
LOWERED_RING_OFF = {
    "gpt3-2.7b": ("f815b23b0da3328e", 169829),
    "lfm2-24b-a2b": ("a5a182008b7e884f", 621595),
    "moonlight-16b-a3b": ("fa2544fa1ab56db0", 778461),
    "nemotron-3-nano-30b-a3b": ("d7e0e1bd093e0f07", 531033),
    "qwen3-next-80b-a3b": ("2ed89fe0ef5d70c6", 854511),
    "smallthinker-21b-a3b": ("bc1577e573eacc84", 684064),
}


# cell -> rotations `models/routed.rotate_half` builds into one trace of the
# stage: q and k of each rotary layer (the windowed three of four, the one
# attention layer of five, q's and the shared key's rope columns in all
# five blocks, the one gated-attention layer). Zero where six are expected
# means the mechanism did not engage; the two cells at zero have no rotary.
ROTATIONS = {
    "gpt3-2.7b": {}, "nemotron-3-nano-30b-a3b": {},
    "lfm2-24b-a2b": {"64": 2}, "moonlight-16b-a3b": {"64": 10},
    "qwen3-next-80b-a3b": {"64": 2}, "smallthinker-21b-a3b": {"128": 6},
}


@pytest.mark.parametrize("cell", sorted(LOWERED))
def test_a_cell_s_backward_lowers_to_the_text_it_lowered_to(v5e, cell):
    text, rotations = _lowered_backward(cell, tuple(v5e))
    assert rotations == ROTATIONS[cell]
    assert _pair(text) == LOWERED[cell]


def _pair(text):
    import hashlib

    text = re.sub(r'backend_config = "[^"]*"', 'backend_config = ""', text)
    return hashlib.sha256(text.encode()).hexdigest()[:16], len(text)


def test_with_the_telemetry_ring_off_a_routed_cell_lowers_to_pr_54_s_text(
        v5e, monkeypatch):
    """The switch that is there turns the loads' output off: the stage
    program is then the one PR 54 built, under PR 56's grid bounds (the
    cell with the shortest text; the five others read the same by hand)."""
    from oobleck_tpu.execution.pipeline import PROGRAMS
    from oobleck_tpu.obs import telemetry

    cell = "nemotron-3-nano-30b-a3b"
    monkeypatch.setenv(telemetry.ENV_TELEMETRY, "0")
    monkeypatch.setattr(telemetry, "_instance", telemetry.TelemetryRing())
    held = dict(PROGRAMS)
    try:
        text, _ = _lowered_backward.__wrapped__(cell, tuple(v5e))
    finally:
        PROGRAMS.clear()
        PROGRAMS.update(held)
    assert _pair(text) == LOWERED_RING_OFF[cell] != LOWERED[cell]
