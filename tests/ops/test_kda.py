"""`ops/kda.kimi_delta_rule`: the delta rule whose decay is a VECTOR a head,
in chunks, against the recurrence itself, one position after another,
written out here.

Forward and every gradient (q, k, v, g, beta, and through the inverse: `a`'s
cotangent reaches k, g and beta by no other way) at chunks of 64 with a
length that is no multiple of it, and at small chunks; a chunk in which some
channels decay by e^-20 and far beyond while others decay by nothing (no
inf, no nan, forward or backward: every `exp` of `decayed_products` has an
argument that is never positive); equal channels reproduce
`ops/gdn.gated_delta_rule`, the accepted rule with one decay a head;
bfloat16 operands; what the rule counts where it is built; and what a
layer's checkpoint keeps of it, the inverse by its name.

The rule has no kernels yet (`ops/kda.py` says so): the `jax.numpy` body is
the path on every backend, and one compile for a described v5e at the cell's
shape holds it to its memory (`tests/ops/test_tpu_compile.py`).
"""

import functools

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax import lax

from oobleck_tpu.ops import kda, remat
from oobleck_tpu.ops.gdn import gated_delta_rule
from oobleck_tpu.ops.kda import decayed_products, kimi_delta_rule
from tests.ops.programs import all_eqns

# (length, chunk, heads)
CASES = {
    "chunks_of_64_ragged_tail": (150, 64, 3),
    "whole_chunks": (64, 16, 4),
    "one_chunk_only": (24, 32, 2),
    "one_position_chunks": (9, 1, 2),
    "pairs": (13, 2, 2),
}
B, DK, DV = 2, 16, 8
ARGS = ("q", "k", "v", "g", "beta")


def recurrence(q, k, v, g, beta):
    """S' = Diag(exp(g_t)) S_{t-1}; u_t = beta_t (v_t - S'^T k_t); S_t = S'
    + k_t u_t^T; o_t = S_t^T q_t, one position after another."""
    bsz, _, heads, dv = v.shape

    def position(state, row):
        q_t, k_t, v_t, g_t, beta_t = row
        state = jnp.exp(g_t)[..., None] * state
        u_t = beta_t[..., None] * (
            v_t - jnp.einsum("bhkv,bhk->bhv", state, k_t))
        state = state + k_t[..., :, None] * u_t[..., None, :]
        return state, jnp.einsum("bhkv,bhk->bhv", state, q_t)

    _, o = lax.scan(position, jnp.zeros((bsz, heads, k.shape[-1], dv)),
                    tuple(jnp.moveaxis(t, 1, 0) for t in (q, k, v, g, beta)))
    return jnp.moveaxis(o, 0, 1)


def unit(x):
    return x / jnp.linalg.norm(x, axis=-1, keepdims=True)


def operands(length, heads, *, seed=0, dk=DK, dv=DV):
    """q and k as the mixer hands them over: unit length a head, q scaled
    by dk^-1/2; g the log of a decay a channel; beta a sigmoid."""
    ks = jax.random.split(jax.random.PRNGKey(seed), 5)
    return (unit(jax.random.normal(ks[0], (B, length, heads, dk))) * dk ** -0.5,
            unit(jax.random.normal(ks[1], (B, length, heads, dk))),
            jax.random.normal(ks[2], (B, length, heads, dv)),
            -jax.nn.softplus(jax.random.normal(ks[3], (B, length, heads, dk))),
            jax.nn.sigmoid(jax.random.normal(ks[4], (B, length, heads))))


@pytest.mark.parametrize("case", sorted(CASES))
def test_chunked_rule_is_the_recurrence(case):
    length, chunk, heads = CASES[case]
    args = operands(length, heads)
    got = jax.jit(functools.partial(kimi_delta_rule, chunk=chunk))(*args)
    assert got.shape == (B, length, heads, DV) and got.dtype == jnp.float32
    np.testing.assert_allclose(np.asarray(got),
                               np.asarray(jax.jit(recurrence)(*args)),
                               atol=2e-5)


@functools.cache
def _both_gradients(case):
    """All five gradients of a case, chunked and step by step: computed
    once, compared one operand a test."""
    length, chunk, heads = CASES[case]
    args = operands(length, heads, seed=1)
    target = jax.random.normal(jax.random.PRNGKey(9), (B, length, heads, DV))
    got = jax.jit(jax.grad(
        lambda *a: jnp.sum(kimi_delta_rule(*a, chunk=chunk) * target),
        argnums=range(5)))(*args)
    want = jax.jit(jax.grad(lambda *a: jnp.sum(recurrence(*a) * target),
                            argnums=range(5)))(*args)
    return got, want


@pytest.mark.parametrize("arg", ARGS)
@pytest.mark.parametrize("case", ["chunks_of_64_ragged_tail", "whole_chunks",
                                  "pairs"])
def test_gradient_is_the_recurrences(case, arg):
    got, want = _both_gradients(case)
    i = ARGS.index(arg)
    scale = float(jnp.max(jnp.abs(want[i])))
    np.testing.assert_allclose(np.asarray(got[i]), np.asarray(want[i]),
                               atol=3e-5 * max(scale, 1.0))


def test_gradient_through_the_inverse_alone():
    """What reaches k, g and beta THROUGH `a` and its inverse is checked
    where it is made (the recurrence has no such part to hold the others
    constant in): the gradient by this module's rule round the imported
    series, `-X^T dX X^T`, against JAX's differentiation of a solve."""
    n = 16
    a = 0.2 * jnp.tril(jax.random.normal(jax.random.PRNGKey(2), (3, n, n)), -1)
    w = jax.random.normal(jax.random.PRNGKey(3), (3, n, n))
    eye = jnp.eye(n)
    got = jax.jit(jax.grad(lambda a: jnp.sum(kda._inverse(a) * w)))(a)
    want = jax.jit(jax.grad(lambda a: jnp.sum(
        jnp.linalg.solve(eye + a, eye[None] + 0 * a) * w)))(a)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=1e-3,
                               atol=1e-4)


def test_decayed_products_are_the_sum_over_channels_written_out():
    """`M(a)_ij = sum_c a_ic k_jc exp(cum_ic - cum_jc)` for i >= j, [Q, Q,
    dk] written out (at a size where that is nothing)."""
    qn = 64
    q, k, _, g, _ = operands(qn, 2, seed=4)
    q, k, cum = (jnp.swapaxes(t, 1, 2) for t in (q, k, jnp.cumsum(g, 1)))
    i = jnp.arange(qn)
    between = jnp.exp(jnp.where((i[:, None] >= i[None, :])[..., None],
                                cum[..., :, None, :] - cum[..., None, :, :],
                                -jnp.inf))
    kk, qk = jax.jit(decayed_products)(q, k, cum)
    for got, rows in ((kk, k), (qk, q)):
        want = jnp.sum(rows[..., :, None, :] * k[..., None, :, :] * between,
                       -1)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   atol=1e-6)


@functools.cache
def _rule_and_gradients(chunk):
    """One compile of each for every case that calls them at one shape."""
    rule = functools.partial(kimi_delta_rule, chunk=chunk)
    return jax.jit(rule), jax.jit(jax.grad(
        lambda *a: jnp.sum(jnp.sin(rule(*a))), argnums=range(5)))


@pytest.mark.parametrize("decay", [20.0, 200.0, 2000.0])
def test_channels_that_decay_by_far_beside_channels_that_do_not(decay):
    """Half of the channels decay by e^-`decay` over a chunk of 64 (e^-2000:
    far beyond what float32's exponent holds as a quotient), the others by
    nothing: no inf and no nan in the output or in any gradient, and the
    output is the recurrence's."""
    length, chunk, heads = 128, 64, 2
    q, k, v, _, beta = operands(length, heads, seed=5)
    g = jnp.broadcast_to(
        jnp.where(jnp.arange(DK) % 2 == 0, -decay / chunk, 0.0),
        (B, length, heads, DK))
    rule, gradients = _rule_and_gradients(chunk)
    out = rule(q, k, v, g, beta)
    grads = gradients(q, k, v, g, beta)
    for t in (out, *grads):
        assert bool(jnp.all(jnp.isfinite(t)))
    np.testing.assert_allclose(
        np.asarray(out), np.asarray(jax.jit(recurrence)(q, k, v, g, beta)),
        atol=2e-5)


def test_equal_channels_are_the_gated_delta_rule():
    """A vector decay whose entries are one number a head and position is
    `ops/gdn.py`'s scalar decay: the two rules give the same output and the
    same gradients (g's summed over the channels) to rounding."""
    length, chunk, heads = 100, 64, 4
    q, k, v, g, beta = operands(length, heads, seed=6, dk=32, dv=32)
    one = g[..., 0]
    wide = lambda one: jnp.broadcast_to(one[..., None], g.shape)
    target = jax.random.normal(jax.random.PRNGKey(7), v.shape)
    vector = lambda q, k, v, one, beta: kimi_delta_rule(
        q, k, v, wide(one), beta, chunk=chunk)
    scalar = functools.partial(gated_delta_rule, chunk=chunk)
    np.testing.assert_allclose(
        np.asarray(jax.jit(vector)(q, k, v, one, beta)),
        np.asarray(jax.jit(scalar)(q, k, v, one, beta)), atol=2e-6)
    grads = lambda fn: jax.jit(jax.grad(
        lambda *a: jnp.sum(fn(*a) * target), argnums=range(5)))(
            q, k, v, one, beta)
    for got, want in zip(grads(vector), grads(scalar)):
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   atol=2e-5)


def test_beta_of_nought_writes_nothing_and_padding_moves_no_state():
    length, chunk, heads = 40, 16, 2
    q, k, v, g, beta = operands(length, heads, seed=8)
    rule = jax.jit(functools.partial(kimi_delta_rule, chunk=chunk))
    out = rule(q, k, v, g, jnp.zeros_like(beta))
    assert float(jnp.max(jnp.abs(out))) == 0.0
    # 40 positions in chunks of 16 are 8 padded rows: the first 33 outputs
    # of a 40-long call are those of a 33-long call.
    short = rule(*(t[:, :33] for t in (q, k, v, g, beta)))
    whole = rule(q, k, v, g, beta)
    np.testing.assert_allclose(np.asarray(whole[:, :33]), np.asarray(short),
                               atol=1e-6)


def test_bfloat16_operands_keep_the_decay_and_the_state_in_float32():
    length, chunk, heads = 96, 32, 2
    q, k, v, g, beta = operands(length, heads, seed=10)
    bf = jnp.bfloat16
    out = jax.jit(functools.partial(kimi_delta_rule, chunk=chunk))(
        q.astype(bf), k.astype(bf), v.astype(bf), g, beta)
    assert out.dtype == bf
    want = recurrence(q, k, v, g, beta)
    assert float(jnp.linalg.norm(out.astype(jnp.float32) - want)
                 / jnp.linalg.norm(want)) < 2e-2
    jaxpr = jax.make_jaxpr(functools.partial(kimi_delta_rule, chunk=chunk))(
        q.astype(bf), k.astype(bf), v.astype(bf), g, beta)
    for eqn in all_eqns(jaxpr.jaxpr):
        if eqn.primitive.name == "exp":
            assert eqn.invars[0].aval.dtype == jnp.float32


def test_the_rule_counts_where_it_is_built_and_names_its_inverse():
    from oobleck_tpu.utils import metrics

    reg = metrics.registry()
    scans = reg.counter("oobleck_kda_scans_total")
    named = reg.counter("oobleck_kda_residuals_named_total")
    before = scans.value(), named.value()
    args = operands(40, 2, seed=11)
    fn = jax.jit(jax.grad(lambda *a: jnp.sum(
        remat.checkpoint_layer(functools.partial(
            kimi_delta_rule, chunk=16, layer="7"))(*a))))
    fn(*args)
    fn(*args)                       # a cache hit traces nothing
    assert scans.value() - before[0] >= 1
    assert named.value() - before[1] >= 1
    assert reg.gauge("oobleck_kda_chunks").value(layer="7") == 3
    assert set(kda.RESIDUAL_NAMES) <= set(remat.KEPT)


def test_a_layers_checkpoint_keeps_the_inverse_and_recomputes_no_series():
    """Under `checkpoint_layer` the backward program holds the series'
    products once (the forward's), not twice: the inverse is a residual by
    its name."""
    args = operands(64, 2, seed=12)
    rule = functools.partial(kimi_delta_rule, chunk=16)

    def series_products(wrap):
        jaxpr = jax.make_jaxpr(jax.grad(
            lambda *a: jnp.sum(wrap(rule)(*a))))(*args)
        return sum(1 for e in all_eqns(jaxpr.jaxpr)
                   if e.primitive.name == "dot_general"
                   and e.params["precision"] is not None
                   and e.invars[0].aval.shape[-2:] == (16, 16)
                   and e.invars[1].aval.shape[-2:] == (16, 16))

    kept = series_products(remat.checkpoint_layer)
    recomputed = series_products(jax.checkpoint)
    assert kept < recomputed
