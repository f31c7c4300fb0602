"""`ops/ssd.ssd_scan`: the Mamba-2 recurrence in chunks, against the
recurrence itself, one position after another, written out here.

Forward and every gradient (x, dt, A, B, C, D) over lengths, chunks, heads
and groups: a length that is no multiple of the chunk, one chunk only, one
group for all heads, a group a head; decays of e^-20 a chunk and far beyond
(no inf, no nan, forward or backward: `L` comes from a difference of running
sums); bfloat16 operands; and what the scan counts where it is built.
"""

import functools

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax import lax

from oobleck_tpu.ops.ssd import ssd_scan

# (length, chunk, heads, groups)
CASES = {
    "whole_chunks": (64, 16, 4, 2),
    "ragged_tail": (37, 16, 4, 2),
    "one_chunk_only": (24, 32, 4, 2),
    "one_position_chunks": (9, 1, 2, 1),
    "one_group": (40, 8, 6, 1),
    "a_group_a_head": (33, 8, 3, 3),
}
B, P, N = 2, 8, 16
ARGS = ("x", "dt", "A", "B", "C", "D")


def recurrence(x, dt, a_neg, b, c, d_skip):
    """H_t = exp(dt_t A) H_{t-1} + dt_t x_t (x) B_t; y_t = H_t C_t + D x_t,
    one position after another; head h reads group h // (H / G)."""
    bsz, _, heads, p = x.shape
    rep = heads // b.shape[2]
    bh, ch = jnp.repeat(b, rep, axis=2), jnp.repeat(c, rep, axis=2)

    def position(state, row):
        x_t, dt_t, b_t, c_t = row
        state = (jnp.exp(dt_t * a_neg)[..., None, None] * state
                 + (dt_t[..., None] * x_t)[..., None] * b_t[..., None, :])
        return state, (jnp.einsum("bhpn,bhn->bhp", state, c_t)
                       + d_skip[:, None] * x_t)

    _, y = lax.scan(position, jnp.zeros((bsz, heads, p, b.shape[-1])),
                    tuple(jnp.moveaxis(t, 1, 0) for t in (x, dt, bh, ch)))
    return jnp.moveaxis(y, 0, 1)


def scan(*args, chunk):
    return jax.jit(functools.partial(ssd_scan, chunk=chunk))(*args)


def operands(length, heads, groups, *, seed=0):
    k = jax.random.split(jax.random.PRNGKey(seed), 6)
    return (jax.random.normal(k[0], (B, length, heads, P)),
            jax.nn.softplus(jax.random.normal(k[1], (B, length, heads))),
            -jnp.exp(jax.random.normal(k[2], (heads,))),
            jax.random.normal(k[3], (B, length, groups, N)),
            jax.random.normal(k[4], (B, length, groups, N)),
            jax.random.normal(k[5], (heads,)))


@pytest.mark.parametrize("case", sorted(CASES))
def test_chunked_scan_is_the_recurrence(case):
    length, chunk, heads, groups = CASES[case]
    args = operands(length, heads, groups)
    got = scan(*args, chunk=chunk)
    assert got.shape == (B, length, heads, P) and got.dtype == jnp.float32
    np.testing.assert_allclose(np.asarray(got),
                               np.asarray(jax.jit(recurrence)(*args)),
                               atol=2e-4)


@functools.cache
def _both_gradients(case):
    """All six gradients of a case, chunked and step by step: computed
    once, compared one operand a test."""
    length, chunk, heads, groups = CASES[case]
    args = operands(length, heads, groups, seed=1)
    target = jax.random.normal(jax.random.PRNGKey(9), (B, length, heads, P))
    got = jax.jit(jax.grad(
        lambda *a: jnp.sum(ssd_scan(*a, chunk=chunk) * target),
        argnums=range(6)))(*args)
    want = jax.jit(jax.grad(lambda *a: jnp.sum(recurrence(*a) * target),
                            argnums=range(6)))(*args)
    return got, want


@pytest.mark.parametrize("wrt", range(6), ids=ARGS)
@pytest.mark.parametrize("case", sorted(CASES))
def test_every_gradient_is_the_recurrences(case, wrt):
    got, want = (g[wrt] for g in _both_gradients(case))
    scale = float(jnp.max(jnp.abs(want)))
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               atol=2e-5 * max(scale, 1.0), rtol=2e-4)


@pytest.mark.parametrize("decay_a_chunk", [20.0, 2000.0],
                         ids=["e-20", "e-2000"])
def test_a_chunk_that_decays_to_nothing_has_no_inf_and_no_nan(decay_a_chunk):
    """exp(cum_i) / exp(cum_j) would be 0 / 0 here; exp(cum_i - cum_j)
    is not. The masked half of `L` (a POSITIVE difference, e^+2000 = inf)
    must not reach a gradient either."""
    length, chunk, heads, groups = 48, 16, 4, 2
    x, dt, a_neg, b, c, d = operands(length, heads, groups)
    dt = jnp.full_like(dt, decay_a_chunk / chunk)
    a_neg = -jnp.ones_like(a_neg)
    args = (x, dt, a_neg, b, c, d)
    y = scan(*args, chunk=chunk)
    assert np.isfinite(np.asarray(y)).all()
    np.testing.assert_allclose(np.asarray(y), np.asarray(jax.jit(recurrence)(*args)),
                               atol=2e-4, rtol=2e-4)
    grads = jax.jit(jax.grad(lambda *a: jnp.sum(ssd_scan(*a, chunk=chunk)),
                             argnums=range(6)))(*args)
    assert all(np.isfinite(np.asarray(g)).all() for g in grads)


def test_padding_rows_move_no_state():
    """A ragged tail is padded with dt = 0 rows: the positions before it
    read what they read without it."""
    length, chunk, heads, groups = 37, 16, 4, 2
    args = operands(length, heads, groups)
    whole = scan(*args, chunk=chunk)
    cut = scan(*(a[:, :32] if a.ndim > 1 else a for a in args), chunk=chunk)
    np.testing.assert_allclose(np.asarray(whole[:, :32]), np.asarray(cut),
                               atol=1e-5)


def test_bfloat16_operands_keep_decays_and_state_in_float32():
    length, chunk, heads, groups = 64, 16, 4, 2
    x, dt, a_neg, b, c, d = operands(length, heads, groups)
    bf = lambda t: t.astype(jnp.bfloat16)
    got = scan(bf(x), dt, a_neg, bf(b), bf(c), d, chunk=chunk)
    assert got.dtype == jnp.bfloat16
    want = recurrence(bf(x).astype(jnp.float32), dt, a_neg,
                      bf(b).astype(jnp.float32), bf(c).astype(jnp.float32), d)
    err = np.abs(np.asarray(got, np.float32) - np.asarray(want))
    assert err.max() < 0.05 * np.abs(np.asarray(want)).max()
    # The running sums never went through bfloat16: every float32 exp of
    # the jaxpr reads a float32 operand.
    jaxpr = jax.make_jaxpr(lambda *a: ssd_scan(*a, chunk=chunk))(
        bf(x), dt, a_neg, bf(b), bf(c), d)
    exps = [e for e in jaxpr.jaxpr.eqns if e.primitive.name == "exp"]
    assert exps and all(e.invars[0].aval.dtype == jnp.float32 for e in exps)


def test_groups_are_read_through_an_index_never_copied():
    """No operand of the jaxpr is B or C repeated to the heads."""
    length, chunk, heads, groups = 32, 16, 6, 2
    args = operands(length, heads, groups)
    jaxpr = jax.make_jaxpr(lambda *a: ssd_scan(*a, chunk=chunk))(*args)
    shapes = {tuple(v.aval.shape) for e in jaxpr.jaxpr.eqns
              for v in e.outvars}
    assert shapes and not any(s[-2:] == (heads, N) for s in shapes)


def test_the_scan_counts_what_it_builds():
    from oobleck_tpu.utils import metrics

    reg = metrics.registry()
    built = reg.counter("oobleck_ssd_scans_total")
    before = built.value()
    args = operands(37, 4, 2)
    fn = jax.jit(lambda *a: ssd_scan(*a, chunk=16, layer="3"))
    fn(*args)
    fn(*args)                       # a cache hit traces nothing
    assert built.value() - before == 1
    assert reg.gauge("oobleck_ssd_chunks").value(layer="3") == 3
