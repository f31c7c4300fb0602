"""`ops/sscan.selective_scan`: the Mamba-1 recurrence against the recurrence
itself, one position after another, written out here.

The `jax.numpy` path (the CPU's) and the two Pallas kernels (`sscan_fwd`,
`sscan_bwd`, the TPU's path, under the interpreter): the forward and every
gradient (x, dt, A, B, C, D), at a channel tile that is not the whole, a
length that is no multiple of the chunk, more than one batch row; which
shapes take which path; what the scan counts where it is built; and that
the kernels' bodies carry no jitted helper into the compile cache's key.
"""

import functools

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax import lax

from oobleck_tpu.ops import kernel, sscan
from oobleck_tpu.ops.sscan import selective_scan
from tests.ops.programs import kernel_calls

ARGS = ("x", "dt", "A", "B", "C", "D")
N = 16
# (batch, length, channels, chunk)
CASES = {
    "two_tiles_three_chunks": (1, 48, 256, 16),
    "ragged_tail_two_rows": (2, 37, 128, 16),
    "one_chunk_only": (1, 24, 128, 32),
}


def recurrence(x, dt, a_neg, b, c, d_skip):
    """h_t = exp(dt_t A) h_{t-1} + dt_t B_t x_t; y_t = h_t C_t + D x_t, one
    position after another."""
    def position(h, row):
        x_t, dt_t, b_t, c_t = row
        h = (jnp.exp(dt_t[..., None] * a_neg) * h
             + (dt_t * x_t)[..., None] * b_t[:, None, :])
        return h, jnp.einsum("bcn,bn->bc", h, c_t) + d_skip * x_t

    _, y = lax.scan(position, jnp.zeros((*x.shape[::2], a_neg.shape[1])),
                    tuple(jnp.moveaxis(t, 1, 0) for t in (x, dt, b, c)))
    return jnp.moveaxis(y, 0, 1)


def operands(case, *, seed=0, dtype=jnp.float32):
    bsz, length, channels, _ = CASES[case]
    k = jax.random.split(jax.random.PRNGKey(seed), 6)
    return (jax.random.normal(k[0], (bsz, length, channels)).astype(dtype),
            jax.nn.softplus(jax.random.normal(k[1], (bsz, length, channels))),
            -jnp.exp(jax.random.normal(k[2], (channels, N))),
            jax.random.normal(k[3], (bsz, length, N)),
            jax.random.normal(k[4], (bsz, length, N)),
            jax.random.normal(k[5], (channels,)))


@pytest.fixture
def kernels(kernels_interpreted, monkeypatch):
    """`selective_scan` takes the kernels' path as on a TPU, interpreted,
    at channel tiles of 128 (so 256 channels are two tiles)."""
    monkeypatch.setattr(sscan, "FWD_TILE", 128)
    monkeypatch.setattr(sscan, "BWD_TILE", 128)


@functools.cache
def _readings(case, path):
    """(y, the six gradients) of a case through one path and through the
    recurrence: computed once, compared one operand a test."""
    args = operands(case, seed=1)
    chunk = CASES[case][3]
    target = jax.random.normal(jax.random.PRNGKey(9), args[0].shape)
    both = lambda fn: jax.jit(jax.value_and_grad(
        lambda *a: jnp.sum(fn(*a) * target), argnums=range(6)))(*args)[1]
    scan = functools.partial(selective_scan, chunk=chunk)
    return ((jax.jit(scan)(*args), both(scan)),
            (jax.jit(recurrence)(*args), both(recurrence)))


def _close(got, want):
    assert got.shape == want.shape and got.dtype == want.dtype
    scale = max(float(jnp.max(jnp.abs(want))), 1.0)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               atol=2e-5 * scale, rtol=2e-4)


@pytest.mark.parametrize("case", sorted(CASES))
def test_the_numpy_path_is_the_recurrence(case):
    (got, _), (want, _) = _readings(case, "numpy")
    _close(got, want)


@pytest.mark.parametrize("wrt", range(6), ids=ARGS)
@pytest.mark.parametrize("case", sorted(CASES))
def test_every_gradient_of_the_numpy_path_is_the_recurrences(case, wrt):
    (_, got), (_, want) = _readings(case, "numpy")
    _close(got[wrt], want[wrt])


@pytest.mark.parametrize("case", sorted(CASES))
def test_the_forward_kernel_is_the_recurrence(kernels, case):
    (got, _), (want, _) = _readings(case, "kernels")
    _close(got, want)


@pytest.mark.parametrize("wrt", range(6), ids=ARGS)
@pytest.mark.parametrize("case", sorted(CASES))
def test_every_gradient_of_the_kernels_is_the_recurrences(kernels, case, wrt):
    (_, got), (_, want) = _readings(case, "kernels")
    _close(got[wrt], want[wrt])


def test_bfloat16_x_keeps_the_state_and_the_rest_in_float32(kernels):
    """`x` goes in as it is and `y`, `dx` come back in its dtype; `dt`, `A`,
    `B`, `C`, `D`, their gradients and the chunk-start states are float32."""
    args = operands("two_tiles_three_chunks", dtype=jnp.bfloat16)
    grad = jax.grad(lambda *a: jnp.sum(
        selective_scan(*a, chunk=16).astype(jnp.float32)), argnums=range(6))
    fwd, bwd = _kernel_calls(grad, *args)
    assert [v.aval.dtype for v in fwd.invars] == [jnp.bfloat16] + [
        jnp.float32] * 5
    assert [(v.aval.shape, v.aval.dtype) for v in fwd.outvars] == [
        ((1, 48, 256), jnp.bfloat16), ((1, 3, N, 256), jnp.float32)]
    assert [v.aval.dtype for v in bwd.outvars] == [jnp.bfloat16] + [
        jnp.float32] * 4
    got = jax.jit(grad)(*args)
    want = jax.jit(jax.grad(
        lambda *a: jnp.sum(recurrence(*a)), argnums=range(6)))(
        args[0].astype(jnp.float32), *args[1:])
    for g, w in zip(got, want):
        err = float(jnp.linalg.norm(g.astype(jnp.float32) - w)
                    / jnp.linalg.norm(w))
        assert err < 1e-2, err


def _kernel_calls(fn, *args):
    """The `pallas_call`s of `fn`'s jaxpr, by name: sscan_fwd before
    sscan_bwd."""
    return sorted(kernel_calls(fn, *args), key=lambda e: e.params["name"],
                  reverse=True)


def test_the_grid_is_batch_tile_chunk_and_no_state_a_position_leaves_a_kernel(
        kernels):
    grad = jax.grad(lambda *a: jnp.sum(selective_scan(*a, chunk=16)),
                    argnums=range(6))
    fwd, bwd = _kernel_calls(grad, *operands("two_tiles_three_chunks"))
    assert [c.params["name"] for c in (fwd, bwd)] == ["sscan_fwd",
                                                      "sscan_bwd"]
    # The grid: (batch, channel tile, chunk); no [L, C, N] operand or result.
    assert fwd.params["grid_mapping"].grid == (1, 2, 3)
    for call in (fwd, bwd):
        for v in (*call.invars, *call.outvars):
            assert int(np.prod(v.aval.shape)) < 48 * 256 * N


# (chunk, channels, states) the kernels do not tile: channels that do not
# fill lanes, states that do not fill sublanes, a chunk of no whole eights.
NOT_TAKEN = {"channels_96": (16, 96, 16), "states_4": (16, 128, 4),
             "chunk_12": (12, 128, 16)}


@pytest.mark.parametrize("shape", ["taken", *sorted(NOT_TAKEN)])
def test_the_counters_say_which_path_a_scan_took(monkeypatch, shape):
    """On a TPU (`kernel.on_tpu`): one `fwd` and one `bwd` a scan built where
    the kernels tile the shape, none where they do not; on the CPU none;
    the chunks by layer either way. Traced only: nothing runs."""
    from oobleck_tpu.utils import metrics

    reg = metrics.registry()
    calls = reg.counter("oobleck_sscan_calls_total")
    chunks = reg.counter("oobleck_sscan_chunks_total")
    chunk, channels, n = NOT_TAKEN.get(shape, (16, 128, 16))
    assert sscan._kernels_take(chunk, channels, n) == (shape == "taken")
    args = (jnp.ones((1, 40, channels)), jnp.ones((1, 40, channels)),
            -jnp.ones((channels, n)), jnp.ones((1, 40, n)),
            jnp.ones((1, 40, n)), jnp.ones((channels,)))
    read = lambda: (calls.value(kernel="fwd"), calls.value(kernel="bwd"),
                    chunks.value(layer="7"))

    def built(on_tpu):
        monkeypatch.setattr(kernel, "on_tpu", lambda: on_tpu)
        before = read()
        found = _kernel_calls(jax.grad(lambda *a: jnp.sum(
            selective_scan(*a, chunk=chunk, layer="7")), argnums=0), *args)
        return (tuple(b - a for a, b in zip(before, read())),
                [e.params["name"] for e in found])

    nc = -(-40 // chunk)
    assert built(on_tpu=False) == ((0, 0, nc), [])
    took = shape == "taken"
    assert built(on_tpu=True) == (
        (int(took), int(took), nc), ["sscan_fwd", "sscan_bwd"] if took else [])
