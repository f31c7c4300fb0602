"""The benchmark cells' one-stage `jit_bwd`, compiled for a described (not
attached) TPU v5e: each forward kernel once a layer that has attention,
the inverse's series once a layer that has the rule, the expert kernels as
they were, temporaries under a bound. `tests/ops/test_remat_residuals.py`
has why: a layer's checkpoint (`ops/remat.checkpoint_layer`) keeps what
the forward kernels wrote by name. Nothing executes here; no number comes
out.

Not collected: the table, the fixtures and the readers of the
`test_remat_cells_*.py` files, which divide the seven cells between them
so that under `--dist loadfile` no worker compiles all seven alone
(17-63 s each alone; ROADMAP.md D24 d).
"""

import os
import re

os.environ.setdefault("TPU_LOG_DIR", "disabled")  # compiler logs: not /tmp

import jax
import pytest
from jax.experimental import topologies

from oobleck_tpu.ops import flash
from tests.ops.programs import cell_stage


def _calls(names, layers):
    return dict.fromkeys(names, layers)


SCAN = ("ssd_fwd", "ssd_bwd")       # `ops/ssd.py`'s two kernels
RULE = ("gdn_fwd", "gdn_bwd")       # `ops/gdn.py`'s two kernels
SELECTIVE = ("sscan_fwd", "sscan_bwd")   # `ops/sscan.py`'s two kernels


# cell -> (microbatch, sequence), its attention's kernels with how many
# layers call each, the bound on `jit_bwd`'s temporaries. The compile gave
# 1,188,759,552 / 2,136,438,784 / 1,732,474,368 bytes when the policy went
# in (PR 36; 923 MB / 2.22 GB / 1.68 GB before): O and LSE of every
# attention layer are the program's to hold, a second copy is not.
CELLS = {
    "gpt3-2.7b": ((4, 1024), _calls(flash.PLAIN, 3), 1.25e9),
    "lfm2-24b-a2b": ((8, 1024), _calls(flash.PLAIN, 1), 2.3e9),
    "moonlight-16b-a3b": ((1, 4096), _calls(flash.LATENT, 5), 1.8e9),
    # 1,592,583,680 when the cell went in (PR 37): under ISSUE 37's 2.2 GB.
    # 1,881,395,712 since the three Mamba-2 layers' scan is two kernels
    # (PR 54): y (33.5 MB) and the 32 chunk-start states (67 MB float32) of
    # each are the program's to hold across a microbatch's backward, where
    # the [Q, Q] blocks of `L` (134 MB a pass) were temporaries. One
    # `ssd_fwd` and one `ssd_bwd` a layer: the recomputed forward holds none.
    "nemotron-3-nano-30b-a3b": ((1, 4096), {**_calls(flash.PLAIN, 1),
                                            **_calls(SCAN, 3)}, 2.2e9),
    # 4,402,778,624 when the cell went in (PR 43): the dropless buffers of
    # 4096 x 10 + 16 x 128 rows (168 MB each at 2048 bfloat16 columns)
    # beside the delta rule's float32 [64, 64] blocks. One attention layer
    # of four, at heads of 256. 3,732,470,272 since the layers' checkpoint
    # keeps the rule's inverse (PR 44): 100.7 MB kept, and the recompute
    # holds no power and no partial product of the series. 2,677,982,720
    # since what comes after the inverse is two kernels (PR 59): o (33.5 MB)
    # and the 64 chunk-start states (134 MB float32) of each layer are the
    # program's to hold across a microbatch's backward, where `D`, `T`,
    # `Q K^T` (134 MB each a pass), `W`, `U` and `V'` were temporaries. One
    # `gdn_fwd` and one `gdn_bwd` a layer: the recomputed forward holds none.
    "qwen3-next-80b-a3b": ((1, 4096), {**_calls(flash.PLAIN, 1),
                                       **_calls(RULE, 3)}, 3.3e9),
    # 5,650,993,664 when the cell went in (PR 45), at ONE sequence of 16384:
    # the dropless buffers of 16384 x 6 + 8 x 1024 rows (545 MB each at 2560
    # bfloat16 columns) beside the head's float32 logits. One full-attention
    # layer through the plain kernels (528 grid steps a head) and three
    # windowed ones through `flash_swa_*` (252): one forward kernel a layer.
    # 5,762,886,144 since the rotary embedding is one pass (PR 46), where
    # its parent compiled to 5,764,079,616: the float32 copies of q and k
    # are gone (88.7 -> 70.2 GB of `bytes accessed`) and were never alive at
    # the peak, so there is nothing to lower the bound by. 5,920,677,376
    # since a token's sum over its rows is a kernel (PR 52, + 158 MB here):
    # the float32 [16384, 2560] sums are gone, but a kernel's bfloat16
    # result is a buffer of its own where the loop's convert could fuse
    # into its consumer (about two of 84 MB; not looked up in the buffer
    # assignment). On the chip `memory_peak_bytes` read + 2.3 MB
    # (11,292,769,792 -> 11,295,044,608; my chip runs, PR 52).
    "smallthinker-21b-a3b": ((1, 16384), {**_calls(flash.PLAIN, 1),
                                          **_calls(flash.WINDOW, 3)}, 6.05e9),
    # 1,944,687,616 when the cell went in (PR 60), at ONE sequence of 8192:
    # the feed-forwards' [8192, 20480] intermediates and the head's float32
    # logits over 25,088 rows. One Mamba-1 layer (one `sscan_fwd`, one
    # `sscan_bwd`: y, 84 MB, and the 64 chunk-start states, 21 MB float32,
    # are the program's to hold) and two layers of differential attention,
    # two softmaxes each through `flash_diff_*`: four forward calls and
    # four backward, none in the recompute. No routed block.
    "phi-4-mini-flash": ((1, 8192), {**_calls(SELECTIVE, 1),
                                     **_calls(flash.DIFF, 4)}, 2.15e9),
}
# The three Gated DeltaNet layers' inverse (`ops/gdn.unit_lower_inverse`,
# scope `gdn_inverse`): ten [64, 64] float32 products of the series a
# layer, and two of its own gradient rule. The series again in the
# recompute would be thirty more.
INVERSE_PRODUCTS = {"qwen3-next-80b-a3b": 3 * 10 + 3 * 2}
# Four routed layers of SwiGLU experts: 3 forward + 3 recomputed + 3 dX
# products, and 3 dW, a layer. Three of experts without a gate: 2 + 2 + 2
# and 2. And a layer's two sums of rows into tokens, the forward combine
# and the dispatch's dx (the recomputed combine's sum feeds nothing in the
# backward pass and the compiler drops it).
ROUTED = {"moe_gmm": 36, "moe_tgmm": 12, "moe_token_sum": 8}
UNGATED = {"moe_gmm": 18, "moe_tgmm": 6, "moe_token_sum": 6}


@pytest.fixture(scope="module")
def v5e():
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — no libtpu / unknown topology
        pytest.skip(f"cannot describe a v5e topology here: {e}")
    return topo.devices


# `gpt3-2.7b`'s executable has two readers (`test_remat_cells_a.py`) and
# is compiled for the first of them; the other cells' have one and are not
# kept alive.
_READ_TWICE = "gpt3-2.7b"
_kept = {}


def cell_backward(cell, devices):
    """(stage, params, executable) of a cell's one stage, which is first and
    last: bwd(params, sum, x=None, batch) is the loss's value-and-gradient,
    compiled for the described chip. Under `compiled_for_tpu` only."""
    if cell in _kept:
        return _kept[cell]
    (mb, seq), _, _ = CELLS[cell]
    st, params, batch = cell_stage(cell, devices, microbatch=mb, seq=seq)
    made = st, params, st.bwd[0].lower(params, params, None, batch).compile()
    if cell == _READ_TWICE:
        _kept[cell] = made
    return made


def cell_backward_holds_each_forward_kernel_once(cell, devices):
    """The body of `test_cell_backward_holds_each_forward_kernel_once`
    in every `test_remat_cells_*.py`, under `compiled_for_tpu`."""
    _, kernels, temp_bound = CELLS[cell]
    _, _, compiled = cell_backward(cell, devices)
    text = compiled.as_text()
    calls = re.findall(r"%([\w\-]+?)(?:\.\d+)? = [^\n]*custom_call_target="
                       r'"tpu_custom_call"', text)
    count = {k: calls.count(k) for k in set(calls)}
    assert {k: count.pop(k, 0) for k in kernels} == kernels
    # What the policy does not name is recomputed as before: the routed
    # layers' three forward products run twice (ROADMAP.md S8 a).
    assert count == {"gpt3-2.7b": {}, "phi-4-mini-flash": {},
                     "nemotron-3-nano-30b-a3b": UNGATED}.get(cell, ROUTED)
    assert compiled.memory_analysis().temp_size_in_bytes < temp_bound
    if cell in EXPERT_SETS:
        _the_experts_sums_are_the_kernels(cell, text)
    if set(SCAN) <= set(kernels):
        _the_scan_is_its_kernels(text)
    if set(RULE) <= set(kernels):
        _the_rule_is_its_kernels_and_its_inverse(text)
    if set(SELECTIVE) <= set(kernels):
        # The walk over the positions is the kernels': no loop in the
        # program, and no [L, C, N] array (8192 x 5120 x 16).
        assert " while(" not in text
        assert not re.search(r"\[(?:1,)?8192,5120,16\]|\[(?:1,)?8192,16,5120\]",
                             text)
    inverse = re.findall(
        r'= f32\[[\d,]*64,64\]\S* convolution\([^\n]*op_name="[^"]*/gdn/'
        r'gdn_inverse/dot_general"', text)
    assert len(inverse) == INVERSE_PRODUCTS.get(cell, 0)


def _the_scan_is_its_kernels(text):
    """Of what the program built under the scope `ssd`: no [Q, Q] float32
    block (`L`, `M`, `C B^T` at a chunk of 128: they live in VMEM) and no
    loop (the walk across the chunks is the kernels' grid)."""
    built = re.findall(
        r'^\s*(?:ROOT )?%[\w.\-]+ = (\S+) ([\w\-]+)\([^\n]*op_name="'
        r'[^"]*[/(]ssd[/)][^"]*"', text, re.M)
    assert len(built) > 2 * 3
    assert not [shape for shape, _ in built
                if re.match(r"f32\[[\d,]*128,128\]", shape)]
    assert "while" not in {kind for _, kind in built}


def _the_rule_is_its_kernels_and_its_inverse(text):
    """Of what the program built under the scope `gdn`: no loop (the walk
    across the 64 chunks is the kernels' grid), and of the [Q, Q] float32
    blocks a head and chunk only those outside the kernels' part: what
    builds `A` (`K K^T`, its decay block, `A`, their gradients: the rule's
    `einsum`, into whose fusions the compiler takes the elementwise rest),
    the inverse's own rule, and `X` in and `dX` out of the kernels (tuple
    elements, no operation of their own). `W`, `U` and `V'` ([..., 64, 128]
    a value head) are no arrays of it."""
    built = re.findall(
        r'^\s*(?:ROOT )?%[\w.\-]+ = (\S+) ([\w\-]+)\([^\n]*op_name="'
        r'([^"]*[/(]gdn[/)][^"]*)"', text, re.M)
    assert len(built) > 2 * 3
    assert "while" not in {kind for _, kind, _ in built}
    blocks = [(kind, name) for shape, kind, name in built
              if re.match(r"f32\[[\d,]*64,64\]", shape)
              and kind in ("fusion", "convolution")]
    assert blocks
    for kind, name in blocks:
        assert ("/gdn_inverse/" in name
                or "bzigd,bzjgd->bzgij" in name), (kind, name)
    assert not [shape for shape, _, _ in built
                if re.match(r"(?:bf16|f32)\[[\d,]*16,2,64,128\]", shape)]


# cell -> the float32 shapes of a routed layer's held experts (w1 / w3,
# w2), and the copies of them the compiler may leave. The running gradient
# sums of these go INTO `moe_tgmm` (ops/moe.py, execution/pipeline.py): a
# plain `add` of such a shape is a sum that stayed outside (6–15 ms each on
# the chip, PERF.md), a `copy` one that XLA could not alias in place.
# 1856 is no multiple of a lane, so the compiler lays [8, 2688, 1856]
# ENTRY operands out {1,2,0} and copies to and from the kernels' {2,1,0}:
# two reads of w1, the sum in and the sum out, a layer, as before the sums
# moved (PERF.md: the odd width's copy stayed, the add went).
EXPERT_SETS = {
    "lfm2-24b-a2b": (("8,2048,1536", "8,1536,2048"), 0),
    "moonlight-16b-a3b": (("8,2048,1408", "8,1408,2048"), 0),
    "nemotron-3-nano-30b-a3b": (("8,2688,1856", "8,1856,2688"), 3 * 4),
    "qwen3-next-80b-a3b": (("16,2048,512", "16,512,2048"), 0),
    "smallthinker-21b-a3b": (("8,2560,768", "8,768,2560"), 0),
}


def _the_experts_sums_are_the_kernels(cell, text):
    shapes, layout_copies = EXPERT_SETS[cell]
    of_a_set = "|".join(re.escape(s) for s in shapes)
    ops = re.findall(rf"%([a-z_\-]+)[.\d]* = f32\[(?:{of_a_set})\]\S* "
                     r"(\S+?)\(([^\n]*)", text)
    dw = [operands for name, kind, operands in ops if name == "moe_tgmm"]
    assert len(dw) == {"nemotron-3-nano-30b-a3b": UNGATED}.get(
        cell, ROUTED)["moe_tgmm"]
    for operands in dw:
        # The sum is the call's last operand: the donated `acc` leaf
        # itself (or its layout copy), written in place.
        # (Six operands since PR 56: the text numbers the sixth.)
        assert re.search(r", /\*index=5\*/%(acc_\w+|copy)[.\d]*\), "
                         r"custom_call_target", operands), operands[:300]
        # Operand 5 of the compiled call: the grid's dynamic bound comes
        # first since PR 56 (the plan's `num_tiles`), then the two tables
        # and the two row operands; it was 4.
        assert "output_to_operand_aliasing={{}: (5, {})}" in operands
    kinds = [kind for _, kind, _ in ops]
    assert kinds.count("add") == 0
    assert kinds.count("copy") == layout_copies
