"""`models/routed.rotate_half` against the formula it replaced, WRITTEN OUT
here in numpy: float32 products and sum, `[-x2, x1]` by slices and a
concatenation, one rounding at the end; its transpose likewise (what
autodiff made of it: `g cos + [u2, -u1]` with `u = g sin`). The function
builds `[-x2, x1]` as a product with a signed permutation and carries its
own transpose (the rotation by the negated angle), so that the chip reads
the operand once and writes the result once; neither may change a bit of
the result or of the cotangent.

The function runs op by op (no `jit`): XLA:CPU contracts `a b + c d` into
fused multiply-adds fusion by fusion, so one formula jitted reads another
last bit than the same formula op by op, or in numpy. The tables are the
function's own expressions through `jax.numpy` (numpy's `cos` and `**`
round otherwise).
"""

import functools

import jax
import jax.numpy as jnp
import ml_dtypes
import numpy as np
import pytest

from oobleck_tpu.models import build_model
from oobleck_tpu.models.routed import rotate_half

F32 = np.float32
DTYPES = pytest.mark.parametrize(
    "dtype", [ml_dtypes.bfloat16, np.float32], ids=["bf16", "f32"])


@functools.cache
def tables(s, d, theta):
    freqs = 1.0 / (theta ** (jnp.arange(0, d, 2, dtype=F32) / d))
    angles = jnp.arange(s, dtype=F32)[:, None] * freqs[None, :]
    return (np.asarray(jnp.concatenate([jnp.cos(angles)] * 2, axis=-1)),
            np.asarray(jnp.concatenate([jnp.sin(angles)] * 2, axis=-1)))


def written_out(x, theta):
    d = x.shape[-1]
    cos, sin = tables(x.shape[-2], d, theta)
    x32 = x.astype(F32)
    x1, x2 = x32[..., : d // 2], x32[..., d // 2:]
    rotated = np.concatenate([-x2, x1], axis=-1)
    return (x32 * cos + rotated * sin).astype(x.dtype)


def written_out_transposed(g, theta):
    d = g.shape[-1]
    cos, sin = tables(g.shape[-2], d, theta)
    g32 = g.astype(F32)
    u = g32 * sin
    return (g32 * cos + np.concatenate(
        [u[..., d // 2:], -u[..., : d // 2]], axis=-1)).astype(g.dtype)


def operands(shape, dtype, seed):
    rng = np.random.default_rng(seed)
    return (rng.standard_normal(shape, F32).astype(dtype),
            rng.standard_normal(shape, F32).astype(dtype))


def same_bits(got, want):
    got = np.asarray(got)
    assert got.dtype == want.dtype and got.shape == want.shape
    bits = {2: np.uint16, 4: np.uint32}[want.dtype.itemsize]
    np.testing.assert_array_equal(got.view(bits), want.view(bits))


def check(fn, x, g, want, want_cotangent):
    """`fn`'s result at x and its cotangent for g through `jax.vjp`, bit
    for bit."""
    y, vjp = jax.vjp(fn, jnp.asarray(x))
    same_bits(y, want)
    same_bits(vjp(jnp.asarray(g))[0], want_cotangent)


@pytest.mark.parametrize("positions", [128, 4096])
@pytest.mark.parametrize("width", [64, 128])
@DTYPES
def test_rotation_and_cotangent_bit_for_bit(dtype, width, positions):
    theta = 1.5e6
    x, g = operands((1, 3, positions, width), dtype, width + positions)
    check(lambda x: rotate_half(x, theta), x, g,
          written_out(x, theta), written_out_transposed(g, theta))


@DTYPES
def test_partial_form_bit_for_bit(dtype):
    """qwen3-next's: a quarter of a head's columns, the rest passed on."""
    model = build_model("qwen3-next-tiny", {})
    r, theta = model.config.rotary_dim, model.config.rope_theta
    x, g = operands((1, 2, 128, model.config.head_dim), dtype, 7)
    assert 0 < r < x.shape[-1]
    check(model._partial_rotary, x, g,
          np.concatenate([written_out(x[..., :r], theta), x[..., r:]], -1),
          np.concatenate([written_out_transposed(g[..., :r], theta),
                          g[..., r:]], -1))


@DTYPES
@pytest.mark.parametrize("shape", [(1, 4, 256, 192), (1, 256, 512 + 64)],
                         ids=["q_rope_columns", "shared_key"])
def test_sliced_columns_bit_for_bit(shape, dtype):
    """deepseek-v3's: the last 64 columns of each head of q, and of the
    ONE key a position (`kv_a`: [B, S, r + 64], no head axis)."""
    theta, cut = 5e4, shape[-1] - 64
    x, g = operands(shape, dtype, len(shape))
    g = g[..., cut:]
    check(lambda x: rotate_half(x[..., cut:], theta), x, g,
          written_out(x[..., cut:], theta),
          np.concatenate([np.zeros_like(x[..., :cut]),
                          written_out_transposed(g, theta)], -1))


def test_one_rotation_is_counted_a_call_site_and_trace():
    from oobleck_tpu.utils import metrics

    built = metrics.registry().counter("oobleck_rotary_calls_total")
    before = built.value(width="64"), built.value(width="128")
    x = jnp.ones((1, 2, 16, 64), jnp.bfloat16)
    grad = jax.jit(jax.grad(lambda x: jnp.sum(
        rotate_half(rotate_half(x, 1e4), 1e4).astype(F32))))
    grad(x)
    grad(x)            # the compiled program again: nothing is traced
    assert (built.value(width="64") - before[0],
            built.value(width="128") - before[1]) == (2, 0)
