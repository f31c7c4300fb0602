"""The main path's Pallas kernels must COMPILE for the chip, not only run
interpreted: each case AOT-compiles for a described (not attached) TPU v5e
at real widths and checks the Mosaic kernel is in the executable.

CPU interpret-mode parity (test_ops / test_paged_ops / test_verify_ops)
cannot see a block the chip's tiling refuses, a kernel over its VMEM
budget, or a `pallas_call` that will not trace inside the training step's
`check_vma=True` shard_maps. Nothing executes here; no number comes out.

`jax.default_backend()` is still "cpu" during such a compile, so the tests
steer `ops.kernel.on_tpu` themselves — the program has no option
for it.
"""

import math
import os
import re

os.environ.setdefault("TPU_LOG_DIR", "disabled")  # compiler logs: not /tmp

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
from jax.sharding import SingleDeviceSharding

from oobleck_tpu.config import ServeArguments
from oobleck_tpu.ops import attention
from oobleck_tpu.ops.flash import flash_attention, latent_flash_attention
from oobleck_tpu.ops.paged_attention import (
    paged_decode_attention,
    paged_verify_attention,
)
from oobleck_tpu.ops.remat import checkpoint_layer
from oobleck_tpu.serve.kv_blocks import pages_for
from tests.ops.cells import v5e  # noqa: F401 (a fixture)

# [B, H, S, D] of one microbatch's attention call: gpt2 124M
# (examples/gpt2.yaml: microbatch 8, 12 heads of 64, seq 1024), a llama-7B
# head geometry (flash sees K/V already repeated to 32 heads of 128), and
# gpt3-2.7b's head_dim 80 (padded to the 128-lane width in-kernel); then
# the benchmark cell's real microbatch, a 2048-token sequence (a grid of 10
# live 512 x 512 block pairs of 16) and a short serving prompt (one 128-row
# block): the tiles follow the sequence length (flash.choose_tiles), and
# every tile it returns has to fit the chip's VMEM. The backward keeps a
# head's dq resident, [S, D] f32 and the output block it is rounded to, so
# every benchmark cell's (batch, heads, S, D) is here (the windowed and the
# latent calls: WINDOW_CALLS, LATENT_WIDTHS): a kernel over the VMEM it
# asked for fails this compile, not a chip run.
FLASH_WIDTHS = {
    "gpt2": (8, 12, 1024, 64),
    "llama": (2, 32, 1024, 128),
    "gpt3-2.7b": (2, 32, 1024, 80),
    "gpt3-2.7b-cell": (4, 32, 1024, 80),
    "seq-2048": (1, 32, 2048, 80),
    "short-prompt": (1, 12, 128, 64),
    "lfm2-24b-a2b-cell": (8, 32, 1024, 64),
    "nemotron-3-nano-30b-a3b-cell": (1, 32, 4096, 128),
    # q, k AND v 256 wide (the latent call has 256-wide scores over
    # 128-wide values): 16 heads, the 2 key-value heads already repeated.
    "qwen3-next-80b-a3b-cell": (1, 16, 4096, 256),
    # The one full layer: 8 MB of resident dq a head, 528 grid steps.
    "smallthinker-21b-a3b-cell": (1, 28, 16384, 128),
}
# (Hq, Hkv, D) of the serve pools: gpt2 MHA and llama-style GQA.
PAGED_WIDTHS = {"gpt2": (12, 12, 64), "llama-gqa": (32, 8, 128)}


def _serve_geometry():
    """Lanes / pool pages / page size / table width / verify T exactly as
    ServingPlane._build_engine and _build_spec derive them from the
    ServeArguments defaults."""
    a = ServeArguments()
    num_pages = a.kv_pages or max(2, a.slots * a.max_seq // a.page_size)
    lanes = a.lanes or max(a.slots, min(num_pages - 1, 8 * a.slots))
    return (lanes, num_pages, a.page_size,
            pages_for(a.max_seq, a.page_size), a.spec_k + 1)


@pytest.fixture(autouse=True)
def _compiled_for_tpu(compiled_for_tpu):
    """Every test of this file, and of `test_tpu_compile_routed.py`."""


def _compile(fn, dev, *shapes):
    one = SingleDeviceSharding(dev)
    args = [jax.ShapeDtypeStruct(s, d, sharding=one) for s, d in shapes]
    text = jax.jit(fn).lower(*args).compile().as_text()
    assert "tpu_custom_call" in text, "no Mosaic kernel in the executable"
    return text


def _grads(fn):
    return jax.grad(lambda q, k, v: jnp.sum(fn(q, k, v).astype(jnp.float32)),
                    argnums=(0, 1, 2))


@pytest.mark.parametrize("width", sorted(FLASH_WIDTHS))
@pytest.mark.parametrize("mode", ["fwd", "fwd_bwd"])
def test_flash_compiles(v5e, width, mode):
    shape = FLASH_WIDTHS[width]
    fn = flash_attention if mode == "fwd" else _grads(flash_attention)
    text = _compile(fn, v5e[0], *[(shape, jnp.bfloat16)] * 3)
    assert text.count('custom_call_target="tpu_custom_call"') == (
        1 if mode == "fwd" else 2)


def test_flash_backward_refuses_a_head_its_vmem_cannot_hold():
    """The one shape test of the backward: a head's resident dq over
    `MAX_RESIDENT_DQ` is refused where the call is traced, by its size."""
    from oobleck_tpu.ops import flash

    ok = jax.ShapeDtypeStruct((1, 1, 65536, 128), jnp.bfloat16)
    jax.eval_shape(_grads(flash_attention), ok, ok, ok)
    assert 65536 * 128 * 8 == flash.MAX_RESIDENT_DQ
    over = jax.ShapeDtypeStruct((1, 1, 65536 + 128, 128), jnp.bfloat16)
    with pytest.raises(ValueError, match="keeps a head's dq"):
        jax.eval_shape(_grads(flash_attention), over, over, over)


@pytest.mark.parametrize("form", ["alibi_slopes", "bias", "non_causal"])
def test_flash_variant_compiles(v5e, form):
    """The Bloom (in-kernel ALiBi), materialised-bias and encoder forms,
    forward and backward, at gpt2 widths."""
    b, h, s, d = FLASH_WIDTHS["gpt2"]
    qkv = [((b, h, s, d), jnp.bfloat16)] * 3
    if form == "alibi_slopes":
        def fn(q, k, v, slopes):
            return _grads(lambda q, k, v: flash_attention(
                q, k, v, alibi_slopes=slopes))(q, k, v)
        _compile(fn, v5e[0], *qkv, ((h,), jnp.float32))
    elif form == "bias":
        def fn(q, k, v, bias):
            return _grads(lambda q, k, v: flash_attention(
                q, k, v, bias=bias))(q, k, v)
        _compile(fn, v5e[0], *qkv, ((h, s, s), jnp.float32))
    else:
        fn = _grads(lambda q, k, v: flash_attention(q, k, v, causal=False))
        _compile(fn, v5e[0], *qkv)


@pytest.mark.parametrize("remat", [False, True], ids=["bare", "remat"])
def test_flash_compiles_inside_check_vma_shard_map(v5e, remat):
    """The training step's shape: causal_attention(impl="auto") under a
    default (check_vma=True) shard_map with the batch split over a data
    axis, differentiated from OUTSIDE so the spec transposes run. A bare
    out_shape (no `vma`) fails this at trace time on any TPU. Under the
    fused step's remat the body is a `checkpoint_layer` inside the
    shard_map: O and LSE keep their varying axes through their names, and
    the forward kernel is in the program once."""
    mesh = Mesh(v5e[:2], ("data",))
    spec = P("data")
    body = lambda q, k, v: attention.causal_attention(q, k, v, impl="auto")
    sm = jax.shard_map(checkpoint_layer(body) if remat else body,
                       mesh=mesh, in_specs=(spec,) * 3, out_specs=spec)
    b, h, s, d = FLASH_WIDTHS["gpt2"]
    arg = jax.ShapeDtypeStruct((2 * b, h, s, d), jnp.bfloat16,
                               sharding=NamedSharding(mesh, spec))
    text = jax.jit(_grads(sm)).lower(arg, arg, arg).compile().as_text()
    assert "tpu_custom_call" in text
    assert len(re.findall(r"%flash_fwd(?:\.\d+)? = ", text)) == 1


@pytest.mark.parametrize("width", sorted(PAGED_WIDTHS))
@pytest.mark.parametrize("kernel", ["decode", "verify"])
def test_paged_compiles_at_serve_defaults(v5e, width, kernel):
    hq, hkv, d = PAGED_WIDTHS[width]
    lanes, num_pages, page, table_pages, t = _serve_geometry()
    pool = ((num_pages, hkv, page, d), jnp.bfloat16)
    tables = ((lanes, table_pages), jnp.int32)
    lengths = ((lanes,), jnp.int32)
    if kernel == "decode":
        q = ((lanes, hq, d), jnp.bfloat16)
        fn = paged_decode_attention
    else:
        q = ((lanes, t, hq, d), jnp.bfloat16)
        fn = paged_verify_attention
    _compile(fn, v5e[0], q, pool, pool, tables, lengths)


def test_paged_alibi_compiles(v5e):
    """Bloom serving: per-head slopes ride into both paged kernels."""
    hq, hkv, d = PAGED_WIDTHS["gpt2"]
    lanes, num_pages, page, table_pages, t = _serve_geometry()
    pool = ((num_pages, hkv, page, d), jnp.bfloat16)
    rest = (pool, pool, ((lanes, table_pages), jnp.int32),
            ((lanes,), jnp.int32), ((hq,), jnp.float32))

    def decode(q, kp, vp, bt, ln, slopes):
        return paged_decode_attention(q, kp, vp, bt, ln, alibi_slopes=slopes)

    def verify(q, kp, vp, bt, ln, slopes):
        return paged_verify_attention(q, kp, vp, bt, ln, alibi_slopes=slopes)

    _compile(decode, v5e[0], ((lanes, hq, d), jnp.bfloat16), *rest)
    _compile(verify, v5e[0], ((lanes, t, hq, d), jnp.bfloat16), *rest)


# Routed experts (ops/moe.py): (tokens, hidden, expert width, experts,
# experts held, picks a token) of one microbatch's call. The benchmark
# cell's (8 x 1024 tokens, 8 of 64 experts of 2048 x 1536, top 4: a
# 36,096-row buffer in 384-row tiles, [2048, 512] float32 blocks of an
# expert's matrix in VMEM), all 64 experts held, and a small call whose
# tiles are one step above the smallest. Compiled at these widths in
# `test_tpu_compile_routed.py`; here `lfm2-tiles` gives the kernels' names.
MOE_WIDTHS = {
    "lfm2-24b-a2b-cell": (8192, 2048, 1536, 64, 8, 4),
    "moonlight-16b-a3b-cell": (4096, 2048, 1408, 64, 8, 6),
    "moonlight-16b-a3b-seq-2048": (2048, 2048, 1408, 64, 8, 6),
    # 80 rows expected an expert: 128-row tiles, a 43,008-row buffer.
    "qwen3-next-80b-a3b-cell": (4096, 2048, 512, 512, 16, 10),
    "all-held": (1024, 2048, 1536, 64, 64, 4),
    "lfm2-tiles": (512, 256, 384, 16, 4, 2),
}


def _routed_sum(top_k):
    from oobleck_tpu.ops.moe import routed_experts

    def fn(x, router, bias, w1, w3, w2):
        return jnp.sum(routed_experts(
            x, router, bias, w1, w3, w2, num_experts=router.shape[1],
            top_k=top_k).astype(jnp.float32))

    return fn


def _routed_shapes(t, d, f, ne, held, top_k):
    return [((t, d), jnp.bfloat16), ((d, ne), jnp.float32),
            ((ne,), jnp.float32), ((held, d, f), jnp.float32),
            ((held, d, f), jnp.float32), ((held, f, d), jnp.float32)]


# The kernels' host cost at process start, held down without a clock. A
# process pays trace + lower of every flash call site on its first step,
# cache hit or not (the cache's key is computed from the lowered module),
# and that time follows the size of the kernel bodies. grad(flash) at the
# benchmark cell's microbatch lowers to 26.7 k characters with the 128 x 128
# kernels of PR 26 and to 28.5 k with PR 28's (the same three bodies, plus
# three small step tables and their index maps per call); both take 0.09 to
# 0.10 s to trace and lower here; to 23.4 k since PR 47 made the backward
# ONE body over one set of tables. A body unrolled in Python over the block
# pairs of a row (2 to 8 at these tiles) adds a body's 3 k per kernel and
# pair, and a second copy of each body (a masked and an unmasked form,
# tried and measured to buy nothing) 4 k in all: the limit leaves room for
# a quarter more than there is and for no such loop.
FLASH_GRAD_MODULE_CHARS = 30_000


def test_flash_grad_module_stays_small(v5e):
    one = SingleDeviceSharding(v5e[0])
    arg = jax.ShapeDtypeStruct(FLASH_WIDTHS["gpt3-2.7b-cell"], jnp.bfloat16,
                               sharding=one)
    text = jax.jit(_grads(flash_attention)).lower(arg, arg, arg).as_text()
    assert text.count("tpu_custom_call") == 2
    assert len(text) < FLASH_GRAD_MODULE_CHARS, len(text)


# Latent attention at `moonlight-16b-a3b.steady`'s microbatch, one sequence
# of 4096 (and at 2048, ISSUE 35's fallback: the same tiles in fewer pairs), 16
# heads, scores 128 + 64 wide (padded to 256 in the kernel),
# values 128 wide and NOT padded to the scores' width, one rotary key a
# position for all heads. The same two bodies as the plain calls, so the
# lowered gradient stays within the bound the plain kernels are held to.
LATENT_WIDTHS = (16, 128, 64, 128)          # heads, Dn, Dr, Dv


def _latent_shapes(b, h, s, dn, dr, dv):
    bf = jnp.bfloat16
    return [((b, h, s, dn), bf), ((b, h, s, dr), bf), ((b, h, s, dn), bf),
            ((b, s, dr), bf), ((b, h, s, dv), bf)]


def _latent_grads(fn):
    return jax.grad(lambda *a: jnp.sum(fn(*a).astype(jnp.float32)),
                    argnums=(0, 1, 2, 3, 4))


@pytest.mark.parametrize("seq", [2048, 4096])
@pytest.mark.parametrize("mode", ["fwd", "fwd_bwd"])
def test_latent_flash_compiles_at_the_cell(v5e, mode, seq):
    fn = (latent_flash_attention if mode == "fwd"
          else _latent_grads(latent_flash_attention))
    text = _compile(fn, v5e[0], *_latent_shapes(
        1, LATENT_WIDTHS[0], seq, *LATENT_WIDTHS[1:]))
    assert text.count('custom_call_target="tpu_custom_call"') == (
        1 if mode == "fwd" else 2)


def test_latent_grad_module_stays_small(v5e):
    one = SingleDeviceSharding(v5e[0])
    args = [jax.ShapeDtypeStruct(s, d, sharding=one) for s, d in
            _latent_shapes(1, LATENT_WIDTHS[0], 4096, *LATENT_WIDTHS[1:])]
    text = jax.jit(_latent_grads(latent_flash_attention)).lower(
        *args).as_text()
    assert text.count("tpu_custom_call") == 2
    assert len(text) < FLASH_GRAD_MODULE_CHARS, len(text)
    # Values, O and dO travel 128 wide; q and k 256.
    assert "16x4096x128xbf16" in text and "16x4096x256xbf16" in text


# A sliding window (`flash_attention(window=)`): the plain kernels over the
# band's block pairs, under `flash_swa_*`. `smallthinker-21b-a3b.steady`'s
# call: one sequence of 16384, 28 heads of 128 (the 4 key-value heads
# already repeated), window 4096: 252 grid steps a head of the causal 528;
# and a window no multiple of a block. The scalar-prefetched tables follow the band, the bodies do not
# change, so the lowered gradient stays within the plain kernels' bound.
WINDOW_CALLS = {"cell": (16384, 4096), "ragged-window": (2048, 700)}


def _windowed(window):
    return lambda q, k, v: flash_attention(q, k, v, window=window)


@pytest.mark.parametrize("call", sorted(WINDOW_CALLS))
@pytest.mark.parametrize("mode", ["fwd", "fwd_bwd"])
def test_window_flash_compiles(v5e, mode, call):
    seq, window = WINDOW_CALLS[call]
    fn = _windowed(window) if mode == "fwd" else _grads(_windowed(window))
    text = _compile(fn, v5e[0], *[((1, 28, seq, 128), jnp.bfloat16)] * 3)
    calls = re.findall(r"%([\w\-]+?)(?:\.\d+)? = [^\n]*custom_call_target="
                       r'"tpu_custom_call"', text)
    # (Bare of any outer scope a transformation's wrapper goes around the
    # kernel's own name, `jvp_flash_swa_fwd_`: a layer's scope keeps it
    # whole, `test_kernel_is_named_in_location_and_executable`.)
    assert len(calls) == (1 if mode == "fwd" else 2)
    assert all("flash_swa_" in name for name in calls), calls


def test_window_grad_module_stays_small(v5e):
    one = SingleDeviceSharding(v5e[0])
    arg = jax.ShapeDtypeStruct((1, 28, 16384, 128), jnp.bfloat16,
                               sharding=one)
    text = jax.jit(_grads(_windowed(4096))).lower(arg, arg, arg).as_text()
    assert text.count("tpu_custom_call") == 2
    # 33,401: the plain kernels' two bodies and, written out as
    # constants, six tables of 252 steps (the causal call at this length
    # would hold six of 528).
    assert len(text) < FLASH_GRAD_MODULE_CHARS + 9_000, len(text)
    assert "tensor<252xi32>" in text and "tensor<528xi32>" not in text


# The rotary embedding ALONE (`models/routed.rotate_half`) at the same
# cell's q, forward and transposed: one pass, the operand in and the result
# out. The formula by slices and a concatenation compiled to the operand
# WRITTEN OUT as float32 (235 MB), its two halves as `f32[..., 64]` (a
# 64-wide minor dimension fills 128 lanes: 235 MB each) and a fusion that
# read all three: 2.533 GB a call each way where 0.235 is operand in +
# result out; as a product with a signed permutation 0.420 (XLA's own
# count). No kernel in it: seconds, not a cell's `jit_bwd`.
@pytest.mark.parametrize("mode", ["fwd", "vjp"])
def test_rotary_alone_is_one_pass_over_its_operand(v5e, mode):
    from oobleck_tpu.models.routed import rotate_half

    shape, theta = (1, 28, 16384, 128), 1.5e6
    rotate = lambda x: rotate_half(x, theta)
    fn = rotate if mode == "fwd" else (
        lambda x, g: jax.vjp(rotate, x)[1](g)[0])
    one = SingleDeviceSharding(v5e[0])
    arg = jax.ShapeDtypeStruct(shape, jnp.bfloat16, sharding=one)
    compiled = jax.jit(fn).lower(*[arg] * (1 if mode == "fwd" else 2)).compile()
    text = compiled.as_text()
    entry = text[text.index("ENTRY"):]
    elements = math.prod(shape)
    wide = [m.group(0) for m in re.finditer(r"= f32\[([\d,]+)\]", entry)
            if math.prod(map(int, m.group(1).split(",")))
            in (elements, elements // 2)]
    assert not wide, wide
    assert compiled.cost_analysis()["bytes accessed"] < 0.6e9


# The Mamba-2 scan's two kernels (`ops/ssd.py`) at `nemotron-3-nano-30b-a3b`'s
# call: [1, 4096, 64, 64] in 8 groups, a state of 128, chunks of 128. A grid
# step holds a group's 8 heads side by side ([128, 512] blocks), the state
# [128, 512] float32 in a scratch, and a head's [128, 128] float32 blocks
# one after another: inside the 16 MiB a kernel has without asking.
SSD_CELL = dict(heads=64, groups=8, head_dim=64, state=128, chunk=128)


def _scan(*operands):
    from oobleck_tpu.ops.ssd import ssd_scan

    return ssd_scan(*operands, chunk=SSD_CELL["chunk"])


def _scan_grads(fn):
    return jax.grad(lambda *a: jnp.sum(fn(*a).astype(jnp.float32)),
                    argnums=range(6))


def _scan_shapes(batch, seq):
    h, g, p, n = (SSD_CELL[k] for k in ("heads", "groups", "head_dim", "state"))
    bf, f32 = jnp.bfloat16, jnp.float32
    return [((batch, seq, h, p), bf), ((batch, seq, h), f32), ((h,), f32),
            ((batch, seq, g, n), bf), ((batch, seq, g, n), bf), ((h,), f32)]


@pytest.mark.parametrize("mode", ["fwd", "fwd_bwd"])
def test_ssd_compiles_at_the_cell(v5e, mode):
    text = _compile(_scan if mode == "fwd" else _scan_grads(_scan), v5e[0],
                    *_scan_shapes(1, 4096))
    calls = re.findall(r"%(ssd_\w+?)(?:\.\d+)? = [^\n]*custom_call_target="
                       r'"tpu_custom_call"', text)
    assert sorted(calls) == (["ssd_fwd"] if mode == "fwd"
                             else ["ssd_bwd", "ssd_fwd"])
    # Neither `L` nor `M` nor `C B^T` is an array of the program.
    assert not re.search(r"= f32\[[\d,]*128,128\]", text)
    assert " while(" not in text


def test_ssd_compiles_inside_check_vma_shard_map_under_the_layers_checkpoint(
        v5e):
    """As a stage program holds it: under `checkpoint_layer` inside a
    default (check_vma=True) shard_map, the batch over a data axis,
    differentiated from outside. y and the chunk-start states keep their
    varying axes through their names, and each kernel is in the program
    once: the recomputed forward holds none."""
    mesh = Mesh(v5e[:2], ("data",))
    row, all_ = P("data"), P()
    specs = (row, row, all_, row, row, all_)
    sm = jax.shard_map(checkpoint_layer(_scan), mesh=mesh, in_specs=specs,
                       out_specs=row)
    args = [jax.ShapeDtypeStruct(s, d, sharding=NamedSharding(mesh, spec))
            for (s, d), spec in zip(_scan_shapes(2, 1024), specs)]
    text = jax.jit(_scan_grads(sm)).lower(*args).compile().as_text()
    for kernel in ("ssd_fwd", "ssd_bwd"):
        assert len(re.findall(rf"%{kernel}(?:\.\d+)? = ", text)) == 1


# The Mamba-1 selective scan's two kernels (`ops/sscan.py`) at
# `phi-4-mini-flash`'s call: [1, 8192, 5120] channels of 16 states, chunks
# of 128. A grid step holds a channel tile's [128, 1024] blocks, the state
# [16, 1024] float32 in a scratch and, in the backward, the chunk's 129
# states [129, 16, 1024] (8.5 MB), which the backward asks for by
# `vmem_limit_bytes` beside the 16 MiB a kernel has without asking.
SSCAN_CELL = dict(channels=5120, state=16)


def _selective(*operands):
    from oobleck_tpu.ops.sscan import selective_scan

    return selective_scan(*operands)


def _selective_shapes(batch, seq):
    c, n = SSCAN_CELL["channels"], SSCAN_CELL["state"]
    bf, f32 = jnp.bfloat16, jnp.float32
    return [((batch, seq, c), bf), ((batch, seq, c), f32), ((c, n), f32),
            ((batch, seq, n), f32), ((batch, seq, n), f32), ((c,), f32)]


@pytest.mark.parametrize("mode", ["fwd", "fwd_bwd"])
def test_sscan_compiles_at_the_cell(v5e, mode):
    text = _compile(_selective if mode == "fwd" else _scan_grads(_selective),
                    v5e[0], *_selective_shapes(1, 8192))
    calls = re.findall(r"%(sscan_\w+?)(?:\.\d+)? = [^\n]*custom_call_target="
                       r'"tpu_custom_call"', text)
    assert sorted(calls) == (["sscan_fwd"] if mode == "fwd"
                             else ["sscan_bwd", "sscan_fwd"])
    # The state at every position is no array of the program, and the walk
    # over the positions is the kernels'.
    assert not re.search(r"\[(?:1,)?8192,5120,16\]|\[(?:1,)?8192,16,5120\]",
                         text)
    assert " while(" not in text


def test_sscan_compiles_inside_check_vma_shard_map_under_the_layers_checkpoint(
        v5e):
    """As `ssd`'s: under `checkpoint_layer` inside a default
    (check_vma=True) shard_map, the batch over a data axis, differentiated
    from outside; each kernel is in the program once."""
    mesh = Mesh(v5e[:2], ("data",))
    row, all_ = P("data"), P()
    specs = (row, row, all_, row, row, all_)
    sm = jax.shard_map(checkpoint_layer(_selective), mesh=mesh,
                       in_specs=specs, out_specs=row)
    args = [jax.ShapeDtypeStruct(s, d, sharding=NamedSharding(mesh, spec))
            for (s, d), spec in zip(_selective_shapes(2, 1024), specs)]
    text = jax.jit(_scan_grads(sm)).lower(*args).compile().as_text()
    for kernel in ("sscan_fwd", "sscan_bwd"):
        assert len(re.findall(rf"%{kernel}(?:\.\d+)? = ", text)) == 1


# Differential attention's two softmaxes (`ops/flash.
# differential_flash_attention`) at `phi-4-mini-flash`'s call: 20 paired
# heads at 8192 positions, queries and keys 64 wide (padded to the lane by
# `_pad_inputs`), values 128 wide: the plain kernels under `DIFF`'s names,
# a head's dq [8192, 128] float32 resident in the backward.
def _differential(window=None):
    from oobleck_tpu.ops.flash import differential_flash_attention

    # Under a scope, as the model's mixer calls it: the chip's compiler
    # names a custom call after the innermost component of its name stack,
    # and under a transformation that is `jvp(flash_diff_fwd)` where the
    # kernel's name is the stack's only component.
    @jax.named_scope("diff_attn")
    def fn(q1, k1, q2, k2, v):
        a1, a2 = differential_flash_attention(q1, k1, q2, k2, v,
                                              window=window)
        return a1 - 0.5 * a2
    return fn


def _diff_shapes(batch, pairs, seq, d=64):
    bf = jnp.bfloat16
    return [((batch, pairs, seq, d), bf)] * 4 + [((batch, pairs, seq, 2 * d),
                                                  bf)]


def _diff_grads(fn):
    return jax.grad(lambda *a: jnp.sum(fn(*a).astype(jnp.float32)),
                    argnums=range(5))


@pytest.mark.parametrize("mode", ["fwd", "fwd_bwd"])
def test_differential_attention_compiles_at_the_cell(v5e, mode):
    fn = _differential()
    text = _compile(fn if mode == "fwd" else _diff_grads(fn), v5e[0],
                    *_diff_shapes(1, 20, 8192))
    calls = re.findall(r"%(flash_\w+?)(?:\.\d+)? = [^\n]*custom_call_target="
                       r'"tpu_custom_call"', text)
    assert sorted(calls) == (["flash_diff_fwd"] * 2 if mode == "fwd" else
                             ["flash_diff_bwd_dqkv"] * 2
                             + ["flash_diff_fwd"] * 2)


# The gated delta rule's two kernels (`ops/gdn.py`) at `qwen3-next-80b-a3b`'s
# call: [1, 4096, 32, 128] values over 16 key heads of 128, chunks of 64. A
# grid step holds a key head's two value heads side by side ([64, 256]
# blocks), their inverse [2, 64, 64] float32, the state [128, 256] float32 in
# a scratch and a head's [64, 64] float32 blocks one after another.
GDN_CELL = dict(heads=32, key_heads=16, head_dim=128, chunk=64)


def _rule(*operands):
    from oobleck_tpu.ops.gdn import gated_delta_rule

    return gated_delta_rule(*operands, chunk=GDN_CELL["chunk"])


def _rule_grads(fn):
    return jax.grad(lambda *a: jnp.sum(fn(*a).astype(jnp.float32)),
                    argnums=range(5))


def _rule_shapes(batch, seq):
    h, g, d = (GDN_CELL[k] for k in ("heads", "key_heads", "head_dim"))
    bf, f32 = jnp.bfloat16, jnp.float32
    return [((batch, seq, g, d), bf), ((batch, seq, g, d), bf),
            ((batch, seq, h, d), bf), ((batch, seq, h), f32),
            ((batch, seq, h), f32)]


@pytest.mark.parametrize("mode", ["fwd", "fwd_bwd"])
def test_gdn_compiles_at_the_cell(v5e, mode):
    text = _compile(_rule if mode == "fwd" else _rule_grads(_rule), v5e[0],
                    *_rule_shapes(1, 4096))
    calls = re.findall(r"%(gdn_\w+?)(?:\.\d+)? = [^\n]*custom_call_target="
                       r'"tpu_custom_call"', text)
    assert sorted(calls) == (["gdn_fwd"] if mode == "fwd"
                             else ["gdn_bwd", "gdn_fwd"])
    # No loop walks the chunks, and of the [Q, Q] float32 blocks a head and
    # chunk the program writes those that build `A`, the inverse and its
    # cotangent: nothing under the scope `gdn` that is a product with q or
    # v, `W`, `U` or `V'` ([..., 64, 128] a value head).
    assert " while(" not in text
    assert not re.search(r"= (?:bf16|f32)\[[\d,]*16,2,64,128\]", text)


def test_gdn_compiles_inside_check_vma_shard_map_under_the_layers_checkpoint(
        v5e):
    """As a stage program holds it: under `checkpoint_layer` inside a
    default (check_vma=True) shard_map, the batch over a data axis,
    differentiated from outside. o, the chunk-start states and the inverse
    keep their varying axes through their names, and each kernel is in the
    program once: the recomputed forward holds none."""
    mesh = Mesh(v5e[:2], ("data",))
    specs = (P("data"),) * 5
    sm = jax.shard_map(checkpoint_layer(_rule), mesh=mesh, in_specs=specs,
                       out_specs=P("data"))
    args = [jax.ShapeDtypeStruct(s, d, sharding=NamedSharding(mesh, spec))
            for (s, d), spec in zip(_rule_shapes(2, 1024), specs)]
    text = jax.jit(_rule_grads(sm)).lower(*args).compile().as_text()
    for kernel in ("gdn_fwd", "gdn_bwd"):
        assert len(re.findall(rf"%{kernel}(?:\.\d+)? = ", text)) == 1


# The Kimi delta rule (`ops/kda.py`: `jax.numpy` alone, no kernel yet) at
# `kimi-linear-48b-a3b`'s call: [1, 4096, 32, 128] q, k, v and a decay a
# CHANNEL, chunks of 64, differentiated under the layer's checkpoint. What
# the compile holds it to is its memory: no array with a [64, 64, 128]
# block a head (the decayed products written out would be 4.3 GB), and all
# its temporaries together no more than they are: 1,857,094,144 B (1.73 GiB)
# when this was written, held here with 4 % of room. ISSUE 67's target is
# 1 GB a layer: the `perf_opt` that takes the levels into VMEM (PERF.md
# section 7) TIGHTENS this number, and nothing may loosen it.
def test_kda_compiles_at_the_cell_and_writes_no_q_q_dk_block(v5e):
    from oobleck_tpu.ops.kda import kimi_delta_rule

    bf, f32 = jnp.bfloat16, jnp.float32
    shape = (1, 4096, 32, 128)
    one = SingleDeviceSharding(v5e[0])
    args = [jax.ShapeDtypeStruct(s, d, sharding=one) for s, d in
            [(shape, bf)] * 3 + [(shape, f32), (shape[:3], f32)]]
    rule = checkpoint_layer(lambda *a: kimi_delta_rule(*a, chunk=64))
    compiled = jax.jit(jax.grad(
        lambda *a: jnp.sum(rule(*a).astype(f32)),
        argnums=range(5))).lower(*args).compile()
    text = compiled.as_text()
    assert "tpu_custom_call" not in text
    assert not re.search(r"= (?:bf16|f32)\[[\d,]*64,64,128\]", text)
    assert compiled.memory_analysis().temp_size_in_bytes < 1.8 * 2 ** 30


# Every kernel has a stable name on the device: `name=` on its pallas_call
# is the innermost component of the operation's JAX name stack, and the
# chip's compiler names the custom call after that component. A profiler
# trace shows that instruction name, so the benchmark's kernel metrics
# (`%flash_fwd.`, `%flash_bwd_`, ...) find it after any refactor.
KERNEL_NAMES = {
    "flash_fwd": "flash", "flash_bwd_dqkv": "flash",
    "flash_mla_fwd": "latent", "flash_mla_bwd_dqkv": "latent",
    "flash_swa_fwd": "window", "flash_swa_bwd_dqkv": "window",
    "paged_decode": "decode", "paged_verify": "verify",
    "moe_gmm": "moe", "moe_tgmm": "moe", "moe_token_sum": "moe",
    "ssd_fwd": "ssd", "ssd_bwd": "ssd",
    "gdn_fwd": "gdn", "gdn_bwd": "gdn",
    "sscan_fwd": "sscan", "sscan_bwd": "sscan",
    "flash_diff_fwd": "diff", "flash_diff_bwd_dqkv": "diff",
    "flash_diff_swa_fwd": "diff_window",
    "flash_diff_swa_bwd_dqkv": "diff_window",
}


@pytest.mark.parametrize("name", sorted(KERNEL_NAMES))
def test_kernel_is_named_in_location_and_executable(v5e, name):
    one = SingleDeviceSharding(v5e[0])
    if KERNEL_NAMES[name] == "moe":
        # Under remat and grad, as a routed block's stage program runs it.
        width = MOE_WIDTHS["lfm2-tiles"]
        fn = jax.grad(jax.checkpoint(_routed_sum(width[-1])),
                      argnums=(0, 1, 3, 4, 5))
        shapes = _routed_shapes(*width)
    elif KERNEL_NAMES[name] == "flash":
        # Under remat, as the training step runs it: the recompute's
        # forward call keeps the kernel's name too.
        fn = _grads(jax.checkpoint(flash_attention))
        shapes = [(FLASH_WIDTHS["gpt3-2.7b"], jnp.bfloat16)] * 3
    elif KERNEL_NAMES[name] == "window":
        fn = _grads(jax.checkpoint(_windowed(512)))
        shapes = [(FLASH_WIDTHS["seq-2048"], jnp.bfloat16)] * 3
    elif KERNEL_NAMES[name] == "latent":
        fn = _latent_grads(jax.checkpoint(latent_flash_attention))
        shapes = _latent_shapes(1, 4, 1024, *LATENT_WIDTHS[1:])
    elif KERNEL_NAMES[name] == "ssd":
        # `ssd_scan` is a scope of its own, and its backward rule opens it
        # again: the wrappers go around "ssd", not around the kernels.
        fn = _scan_grads(jax.checkpoint(_scan))
        shapes = _scan_shapes(1, 512)
    elif KERNEL_NAMES[name] == "gdn":
        # As `ssd`'s: the rule is a scope of its own and its backward rule
        # opens it again.
        fn = _rule_grads(jax.checkpoint(_rule))
        shapes = _rule_shapes(1, 256)
    elif KERNEL_NAMES[name] == "sscan":
        fn = _scan_grads(jax.checkpoint(_selective))
        shapes = _selective_shapes(1, 256)
    elif KERNEL_NAMES[name].startswith("diff"):
        window = 512 if KERNEL_NAMES[name] == "diff_window" else None
        fn = _diff_grads(jax.checkpoint(_differential(window)))
        shapes = _diff_shapes(1, 4, 1024)
    else:
        hq, hkv, d = PAGED_WIDTHS["gpt2"]
        lanes, num_pages, page, table_pages, t = _serve_geometry()
        pool = ((num_pages, hkv, page, d), jnp.bfloat16)
        q = ((lanes, hq, d) if name == "paged_decode"
             else (lanes, t, hq, d), jnp.bfloat16)
        fn = (paged_decode_attention if name == "paged_decode"
              else paged_verify_attention)
        shapes = [q, pool, pool, ((lanes, table_pages), jnp.int32),
                  ((lanes,), jnp.int32)]
    args = [jax.ShapeDtypeStruct(s, d, sharding=one) for s, d in shapes]
    lowered = jax.jit(fn).lower(*args)
    assert f"/{name}/pallas_call" in lowered.as_text(debug_info=True)
    calls = re.findall(r"%([\w\-]+?)(?:\.\d+)? = [^\n]*custom_call_target="
                       r'"tpu_custom_call"', lowered.compile().as_text())
    assert name in calls, calls
    # No Pallas call of these programs goes by a stand-in.
    assert set(calls) <= set(KERNEL_NAMES), calls


# The last pipeline stage's one program returns the loss with the gradients
# (execution/pipeline.py). Asking for the loss's value must not make the
# compiler write the float32 logits out: picking the target's logit with a
# gather did (a second fusion output of 824 MB a microbatch at the cell's
# [4, 1024, 50304], +13 ms and +475 MB of peak a step on the chip, PR 30);
# as a masked sum it fuses into the reductions. The bfloat16 logits and
# their gradient, 412 MB each, are the program's to hold.
def test_head_loss_value_and_grad_holds_no_f32_logits(v5e):
    from oobleck_tpu.models.gpt import cross_entropy_loss

    mb, seq, hidden, vocab, padded = 4, 1024, 2560, 50257, 50304

    def head_loss(w, x, tokens):
        logits = (x @ w.astype(jnp.bfloat16)).astype(jnp.float32)
        return cross_entropy_loss(logits, tokens, vocab)

    one = SingleDeviceSharding(v5e[0])
    args = [jax.ShapeDtypeStruct(s, d, sharding=one) for s, d in (
        ((hidden, padded), jnp.float32), ((mb, seq, hidden), jnp.bfloat16),
        ((mb, seq), jnp.int32))]
    compiled = jax.jit(jax.value_and_grad(head_loss, argnums=(0, 1))).lower(
        *args).compile()
    f32_logits = mb * (seq - 1) * padded * 4
    assert compiled.memory_analysis().temp_size_in_bytes < f32_logits
