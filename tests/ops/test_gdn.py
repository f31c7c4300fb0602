"""`ops/gdn.gated_delta_rule`: Gated DeltaNet's recurrence in chunks,
against the recurrence itself, one position after another, written out
here.

Forward and every gradient (q, k, v, g, beta) over lengths, chunks, value
heads and key heads: a length that is no multiple of the chunk, one chunk
only, one key head for all value heads, a key head a value head; decays of
e^-20 a chunk and far beyond (no inf, no nan, forward or backward: `D` comes
from a difference of running sums); beta at 0 (nothing is written) and at 1
(the plain delta rule); the inverse of a unit lower-triangular matrix as a
product; bfloat16 operands; what the rule counts where it is built; and what
a layer's checkpoint keeps of it: the inverse by its name, so that the
recomputed forward holds no series.

Those tests run the `jax.numpy` path, the CPU's. The second half of the
file runs the TPU's path, the two Pallas kernels (`gdn_fwd`, `gdn_bwd`)
that take everything after the inverse, under the interpreter and against
that `jax.numpy` path at the widths the kernels tile (chunks of 64, heads
of 128, one and two value heads a key head): the forward and each of the
five gradients, the same decays, padding and float32 claims, which shapes
take which path and what the kernels' counter then says.
"""

import functools

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax import lax

from oobleck_tpu.ops import flash, gdn, kernel, remat
from oobleck_tpu.ops.gdn import gated_delta_rule, unit_lower_inverse
from tests.ops.programs import all_eqns, checkpoint_keeping, kernel_calls

# (length, chunk, value heads, key heads)
CASES = {
    "whole_chunks": (64, 16, 4, 2),
    "ragged_tail": (37, 16, 4, 2),
    "one_chunk_only": (24, 32, 4, 2),
    "one_position_chunks": (9, 1, 2, 1),
    "one_key_head": (40, 8, 6, 1),
    "a_key_head_a_value_head": (33, 8, 3, 3),
}
B, DK, DV = 2, 16, 8
ARGS = ("q", "k", "v", "g", "beta")


def recurrence(q, k, v, g, beta):
    """S' = exp(g_t) S_{t-1}; u_t = beta_t (v_t - S'^T k_t); S_t = S' +
    k_t u_t^T; o_t = S_t^T q_t, one position after another; value head h
    reads key head h // (H / G)."""
    bsz, _, heads, dv = v.shape
    rep = heads // k.shape[2]
    qh, kh = jnp.repeat(q, rep, axis=2), jnp.repeat(k, rep, axis=2)

    def position(state, row):
        q_t, k_t, v_t, g_t, beta_t = row
        state = jnp.exp(g_t)[..., None, None] * state
        u_t = beta_t[..., None] * (
            v_t - jnp.einsum("bhkv,bhk->bhv", state, k_t))
        state = state + k_t[..., :, None] * u_t[..., None, :]
        return state, jnp.einsum("bhkv,bhk->bhv", state, q_t)

    _, o = lax.scan(position, jnp.zeros((bsz, heads, k.shape[-1], dv)),
                    tuple(jnp.moveaxis(t, 1, 0) for t in (qh, kh, v, g, beta)))
    return jnp.moveaxis(o, 0, 1)


def rule(*args, chunk):
    return jax.jit(functools.partial(gated_delta_rule, chunk=chunk))(*args)


def unit(x):
    return x / jnp.linalg.norm(x, axis=-1, keepdims=True)


def operands(length, heads, groups, *, seed=0):
    """q and k as the mixer hands them over: unit length a head, q scaled
    by dk^-1/2; g the log of a decay; beta a sigmoid."""
    ks = jax.random.split(jax.random.PRNGKey(seed), 5)
    return (unit(jax.random.normal(ks[0], (B, length, groups, DK))) * DK ** -0.5,
            unit(jax.random.normal(ks[1], (B, length, groups, DK))),
            jax.random.normal(ks[2], (B, length, heads, DV)),
            -jax.nn.softplus(jax.random.normal(ks[3], (B, length, heads))),
            jax.nn.sigmoid(jax.random.normal(ks[4], (B, length, heads))))


@pytest.mark.parametrize("case", sorted(CASES))
def test_chunked_rule_is_the_recurrence(case):
    length, chunk, heads, groups = CASES[case]
    args = operands(length, heads, groups)
    got = rule(*args, chunk=chunk)
    assert got.shape == (B, length, heads, DV) and got.dtype == jnp.float32
    np.testing.assert_allclose(np.asarray(got),
                               np.asarray(jax.jit(recurrence)(*args)),
                               atol=2e-5)


@functools.cache
def _both_gradients(case):
    """All five gradients of a case, chunked and step by step: computed
    once, compared one operand a test."""
    length, chunk, heads, groups = CASES[case]
    args = operands(length, heads, groups, seed=1)
    target = jax.random.normal(jax.random.PRNGKey(9), (B, length, heads, DV))
    got = jax.jit(jax.grad(
        lambda *a: jnp.sum(gated_delta_rule(*a, chunk=chunk) * target),
        argnums=range(5)))(*args)
    want = jax.jit(jax.grad(lambda *a: jnp.sum(recurrence(*a) * target),
                            argnums=range(5)))(*args)
    return got, want


@pytest.mark.parametrize("wrt", range(5), ids=ARGS)
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
    is not. The masked half of `D` (a POSITIVE difference, e^+2000 = inf)
    must not reach a gradient either."""
    length, chunk, heads, groups = 48, 16, 4, 2
    q, k, v, g, beta = operands(length, heads, groups)
    args = (q, k, v, jnp.full_like(g, -decay_a_chunk / chunk), beta)
    o = rule(*args, chunk=chunk)
    assert np.isfinite(np.asarray(o)).all()
    np.testing.assert_allclose(np.asarray(o),
                               np.asarray(jax.jit(recurrence)(*args)),
                               atol=2e-5, rtol=2e-4)
    grads = jax.jit(jax.grad(
        lambda *a: jnp.sum(gated_delta_rule(*a, chunk=chunk)),
        argnums=range(5)))(*args)
    assert all(np.isfinite(np.asarray(x)).all() for x in grads)


@pytest.mark.parametrize("strength", [0.0, 1.0], ids=["beta_0", "beta_1"])
def test_beta_at_its_ends(strength):
    """beta = 0 writes nothing: the state stays 0 and so does the output.
    beta = 1 with no decay is the plain delta rule: right after position t
    wrote, the state answers k_t with v_t (k_t of unit length)."""
    length, chunk, heads, groups = 40, 16, 4, 2
    q, k, v, g, beta = operands(length, heads, groups)
    args = (q, k, v, g, jnp.full_like(beta, strength))
    o = rule(*args, chunk=chunk)
    np.testing.assert_allclose(np.asarray(o),
                               np.asarray(jax.jit(recurrence)(*args)),
                               atol=2e-5)
    if strength == 0.0:
        assert not np.asarray(o).any()
        return
    read_back = rule(k, k, v, jnp.zeros_like(g), jnp.ones_like(beta),
                     chunk=chunk)
    np.testing.assert_allclose(np.asarray(read_back), np.asarray(v),
                               atol=2e-5)


@pytest.mark.parametrize("size", [1, 2, 16, 64])
def test_the_inverse_of_a_unit_lower_triangle_is_a_product(size):
    a = jnp.tril(jax.random.normal(jax.random.PRNGKey(size),
                                   (3, size, size)) * 0.3, -1)
    got = np.asarray(jax.jit(unit_lower_inverse)(a), np.float64)
    eye = np.eye(size)
    np.testing.assert_allclose(got @ (eye + np.asarray(a, np.float64)),
                               np.broadcast_to(eye, a.shape), atol=1e-4)
    assert not np.triu(got, 1).any()
    # log2(size) squarings and as many products, no triangular solve.
    names = [e.primitive.name for e in
             all_eqns(jax.make_jaxpr(unit_lower_inverse)(a).jaxpr)]
    assert names.count("dot_general") == 2 * max(size.bit_length() - 2, 0)
    assert "triangular_solve" not in names


def _series(a):
    """The same product, left to JAX's differentiation."""
    power = -a
    inverse = jnp.eye(a.shape[-1]) + power
    for _ in range(max(a.shape[-1].bit_length() - 2, 0)):
        power = power @ power
        inverse = inverse + inverse @ power
    return inverse


def test_the_inverse_s_gradient_is_two_products_and_the_series_own():
    """`-X^T dX X^T`: what differentiating the series gives, in two
    products where that takes two a product of the series (twenty at 64
    positions a chunk; the compiler drops two)."""
    a = jnp.tril(jax.random.normal(jax.random.PRNGKey(3), (3, 64, 64)) * 0.2,
                 -1)
    target = jax.random.normal(jax.random.PRNGKey(4), (3, 64, 64))
    loss = lambda inverse: lambda a: jnp.sum(inverse(jnp.tril(a, -1)) * target)
    got = jax.grad(loss(unit_lower_inverse))(a)
    want = jax.grad(loss(_series))(a)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=2e-4)
    assert not np.triu(np.asarray(got)).any()
    dots = lambda f: [e.primitive.name for e in all_eqns(
        jax.make_jaxpr(jax.grad(loss(f)))(a).jaxpr)].count("dot_general")
    assert (dots(unit_lower_inverse), dots(_series)) == (10 + 2, 10 + 20)


def test_padding_rows_move_no_state():
    """A ragged tail is padded with g = 0, beta = 0 rows: the positions
    before it read what they read without it."""
    length, chunk, heads, groups = 37, 16, 4, 2
    args = operands(length, heads, groups)
    whole = rule(*args, chunk=chunk)
    cut = rule(*(a[:, :32] for a in args), chunk=chunk)
    np.testing.assert_allclose(np.asarray(whole[:, :32]), np.asarray(cut),
                               atol=1e-6)


def test_bfloat16_operands_keep_decays_inverse_and_state_in_float32():
    length, chunk, heads, groups = 64, 16, 4, 2
    q, k, v, g, beta = operands(length, heads, groups)
    bf = lambda t: t.astype(jnp.bfloat16)
    f32 = lambda t: bf(t).astype(jnp.float32)
    got = rule(bf(q), bf(k), bf(v), g, beta, chunk=chunk)
    assert got.dtype == jnp.bfloat16
    want = recurrence(f32(q), f32(k), f32(v), g, beta)
    err = np.abs(np.asarray(got, np.float32) - np.asarray(want))
    assert err.max() < 0.05 * np.abs(np.asarray(want)).max()
    # The running sums never went through bfloat16: every exp of the jaxpr
    # reads a float32 operand; the inverse's products are float32 too, and
    # the scan carries a float32 state.
    jaxpr = jax.make_jaxpr(lambda *a: gated_delta_rule(*a, chunk=chunk))(
        bf(q), bf(k), bf(v), g, beta)
    exps = [e for e in all_eqns(jaxpr.jaxpr) if e.primitive.name == "exp"]
    assert exps and all(e.invars[0].aval.dtype == jnp.float32 for e in exps)
    square = [e for e in all_eqns(jaxpr.jaxpr)
              if e.primitive.name == "dot_general"
              and e.params["precision"] is not None]
    assert len(square) == 2 * (chunk.bit_length() - 2)
    assert all(v.aval.dtype == jnp.float32 for e in square for v in e.invars)
    (scan,) = [e for e in jaxpr.jaxpr.eqns if e.primitive.name == "scan"]
    assert scan.outvars[0].aval.dtype == jnp.float32
    assert scan.outvars[0].aval.shape == (B, groups, heads // groups, DK, DV)


def test_key_heads_are_read_through_an_index_never_copied():
    """No value of the jaxpr is q or k repeated to the value heads."""
    length, chunk, heads, groups = 32, 8, 6, 2
    args = operands(length, heads, groups)
    jaxpr = jax.make_jaxpr(lambda *a: gated_delta_rule(*a, chunk=chunk))(*args)
    shapes = {tuple(v.aval.shape) for e in jaxpr.jaxpr.eqns
              for v in e.outvars}
    assert shapes and not any(s[-2:] == (heads, DK) for s in shapes)
    # [.., G, R, Q, dk] is W = T (K e^cum), a product a value head, and
    # its cast: nothing else has K's columns a value head.
    w_like = [v for e in jaxpr.jaxpr.eqns for v in e.outvars
              if tuple(v.aval.shape[-4:]) == (groups, heads // groups, chunk,
                                              DK)]
    assert len(w_like) <= 2


def test_the_rule_counts_what_it_builds():
    from oobleck_tpu.utils import metrics

    reg = metrics.registry()
    built = reg.counter("oobleck_gdn_scans_total")
    before = built.value()
    args = operands(37, 4, 2)
    fn = jax.jit(lambda *a: gated_delta_rule(*a, chunk=16, layer="3"))
    fn(*args)
    fn(*args)                       # a cache hit traces nothing
    assert built.value() - before == 1
    assert reg.gauge("oobleck_gdn_chunks").value(layer="3") == 3


def _layer_gradients(wrap):
    """All five gradients of one rule at 64 positions a chunk (ten
    products a series) under a layer's checkpoint `wrap`."""
    layer = wrap(functools.partial(gated_delta_rule, chunk=64))
    return jax.grad(lambda *a: jnp.sum(layer(*a) ** 2), argnums=range(5))


def _products(fn, *args):
    return [e.primitive.name for e in all_eqns(
        jax.make_jaxpr(fn)(*args).jaxpr)].count("dot_general")


@pytest.mark.parametrize("wrap,fewer", [
    (remat.checkpoint_layer, 10),
    (checkpoint_keeping(*gdn.RESIDUAL_NAMES), 10),
    # The name is emitted and the policy does not keep it: a bare
    # checkpoint's count, the series twice.
    (checkpoint_keeping(*flash.RESIDUAL_NAMES), 0),
], ids=["the_layers_checkpoint", "the_rules_name_alone", "flash_s_names_alone"])
def test_a_checkpoint_that_keeps_the_inverse_recomputes_no_series(wrap, fewer):
    args = operands(128, 4, 2)
    bare = _products(_layer_gradients(jax.checkpoint), *args)
    assert bare - _products(_layer_gradients(wrap), *args) == fewer


@functools.cache
def _kept_and_bare_gradients():
    args = operands(128, 4, 2, seed=2)
    return (jax.jit(_layer_gradients(remat.checkpoint_layer))(*args),
            jax.jit(_layer_gradients(jax.checkpoint))(*args))


@pytest.mark.parametrize("wrt", range(5), ids=ARGS)
def test_the_kept_inverse_is_the_one_a_second_series_would_give(wrt):
    got, want = (g[wrt] for g in _kept_and_bare_gradients())
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


def test_a_named_inverse_is_counted_once_a_forward_rule_traced():
    from oobleck_tpu.utils import metrics

    named = metrics.registry().counter("oobleck_gdn_residuals_named_total")
    before = named.value()
    args = operands(37, 4, 2)
    fn = jax.jit(jax.grad(
        lambda *a: jnp.sum(gated_delta_rule(*a, chunk=16)), argnums=(0, 1)))
    fn(*args)
    fn(*args)                       # a cache hit traces nothing
    assert named.value() - before == 1
    # A rule that is not differentiated runs no forward rule.
    jax.jit(lambda *a: gated_delta_rule(*a, chunk=16, layer="5"))(*args)
    assert named.value() - before == 1


# --------------------------------------------------------------------- #
# the kernels, interpreted, against the jax.numpy path                   #
# --------------------------------------------------------------------- #

# (batch, length, value heads, key heads) at chunk 64, heads of 128.
KERNEL_CASES = {
    "two_chunks_two_heads_a_key_head": (1, 128, 4, 2),
    "three_chunks_a_head_a_key_head": (2, 192, 2, 2),
    "ragged_tail": (1, 150, 2, 1),
}
KQ, KD = 64, 128


def kernel_operands(case, *, seed=0, dtype=jnp.float32):
    bsz, length, heads, groups = KERNEL_CASES[case]
    ks = jax.random.split(jax.random.PRNGKey(seed), 5)
    return ((unit(jax.random.normal(ks[0], (bsz, length, groups, KD)))
             * KD ** -0.5).astype(dtype),
            unit(jax.random.normal(ks[1], (bsz, length, groups, KD))).astype(
                dtype),
            jax.random.normal(ks[2], (bsz, length, heads, KD)).astype(dtype),
            -jax.nn.softplus(jax.random.normal(ks[3], (bsz, length, heads))),
            jax.nn.sigmoid(jax.random.normal(ks[4], (bsz, length, heads))))


def numpy_path(*args, chunk=KQ):
    """`gated_delta_rule` as the CPU runs it, whatever the fixture says."""
    with pytest.MonkeyPatch.context() as patch:
        patch.setattr(kernel, "on_tpu", lambda: False)
        return gated_delta_rule(*args, chunk=chunk)


@pytest.mark.parametrize("case", sorted(KERNEL_CASES))
def test_the_forward_kernel_is_the_numpy_path(kernels_interpreted, case):
    args = kernel_operands(case)
    got = rule(*args, chunk=KQ)
    want = jax.jit(numpy_path)(*args)
    assert got.shape == want.shape and got.dtype == want.dtype
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=1e-6)


@functools.cache
def _kernel_gradients(case):
    """All five gradients of a case, through the kernels and through the
    `jax.numpy` path: computed once, compared one operand a test. (Called
    under the `kernels` fixture only.)"""
    args = kernel_operands(case, seed=1)
    target = jax.random.normal(jax.random.PRNGKey(9), args[2].shape)
    got = jax.jit(jax.grad(
        lambda *a: jnp.sum(gated_delta_rule(*a, chunk=KQ) * target),
        argnums=range(5)))(*args)
    want = jax.jit(jax.grad(lambda *a: jnp.sum(numpy_path(*a) * target),
                            argnums=range(5)))(*args)
    return got, want


@pytest.mark.parametrize("wrt", range(5), ids=ARGS)
@pytest.mark.parametrize("case", sorted(KERNEL_CASES))
def test_every_gradient_of_the_kernels_is_the_numpy_paths(kernels_interpreted, case, wrt):
    got, want = (g[wrt] for g in _kernel_gradients(case))
    assert got.shape == want.shape and got.dtype == want.dtype
    scale = float(jnp.max(jnp.abs(want)))
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               atol=2e-5 * max(scale, 1.0), rtol=2e-4)


@pytest.mark.parametrize("decay_a_chunk", [20.0, 2000.0],
                         ids=["e-20", "e-2000"])
def test_both_kernels_have_no_inf_and_no_nan_in_a_chunk_that_decays_to_nothing(
        kernels_interpreted, decay_a_chunk):
    q, k, v, g, beta = kernel_operands("two_chunks_two_heads_a_key_head")
    args = (q, k, v, jnp.full_like(g, -decay_a_chunk / KQ), beta)
    o = rule(*args, chunk=KQ)
    assert np.isfinite(np.asarray(o)).all()
    np.testing.assert_allclose(np.asarray(o), np.asarray(numpy_path(*args)),
                               atol=2e-5, rtol=2e-4)
    grads = jax.jit(jax.grad(
        lambda *a: jnp.sum(gated_delta_rule(*a, chunk=KQ)),
        argnums=range(5)))(*args)
    assert all(np.isfinite(np.asarray(x)).all() for x in grads)


def test_padding_rows_move_no_state_through_the_kernels(kernels_interpreted):
    args = kernel_operands("ragged_tail")
    whole = rule(*args, chunk=KQ)
    cut = rule(*(a[:, :128] for a in args), chunk=KQ)
    np.testing.assert_allclose(np.asarray(whole[:, :128]), np.asarray(cut),
                               atol=1e-6)
    # ... nor take a gradient: the tail's rows past the length are not there.
    grads = jax.jit(jax.grad(
        lambda *a: jnp.sum(gated_delta_rule(*a, chunk=KQ)),
        argnums=range(5)))(*args)
    assert all(g.shape == a.shape for g, a in zip(grads, args))


def test_the_kernels_keep_running_sums_inverse_and_state_in_float32(kernels_interpreted):
    bf = lambda t: t.astype(jnp.bfloat16)
    f32 = lambda t: bf(t).astype(jnp.float32)
    q, k, v, g, beta = kernel_operands("two_chunks_two_heads_a_key_head")
    args = (bf(q), bf(k), bf(v), g, beta)
    got = rule(*args, chunk=KQ)
    assert got.dtype == jnp.bfloat16
    want = recurrence(f32(q), f32(k), f32(v), g, beta)
    err = np.abs(np.asarray(got, np.float32) - np.asarray(want))
    assert err.max() < 0.05 * np.abs(np.asarray(want)).max()
    grad = jax.grad(
        lambda *a: jnp.sum(gated_delta_rule(*a, chunk=KQ).astype(jnp.float32)),
        argnums=range(5))
    fwd, bwd = kernel_calls(grad, *args)
    assert [e.params["name"] for e in (fwd, bwd)] == ["gdn_fwd", "gdn_bwd"]
    for call in (fwd, bwd):
        dtypes = [v.aval.dtype for v in call.invars]
        # q, k, v in the operands' dtype; the inverse, the running sums and
        # beta (twice) and the states float32; dO, last, in v's dtype.
        assert dtypes[:3] == [jnp.bfloat16] * 3
        assert set(dtypes[3:7]) == {jnp.dtype(jnp.float32)}
        assert dtypes[7:] == ([jnp.bfloat16] if call is bwd else [])
        # Every exp inside the kernel reads float32, and so does the carried
        # state (the one scratch).
        inner = call.params["jaxpr"]
        exps = [e for e in all_eqns(inner) if e.primitive.name == "exp"]
        assert exps and all(e.invars[0].aval.dtype == jnp.float32 for e in exps)
        assert inner.invars[-1].aval.dtype == jnp.float32
    # The states the forward wrote for the backward, and the inverse's
    # cotangent: float32 too.
    assert fwd.outvars[1].aval.dtype == jnp.float32
    assert fwd.outvars[1].aval.shape == (1, 2, 2, KD, 2 * KD)
    assert bwd.outvars[3].aval.dtype == jnp.float32
    assert bwd.outvars[3].aval.shape == (1, 2, 2, 2, KQ, KQ)
    # The series is the program's as before: ten float32 products at
    # HIGHEST, outside both kernels.
    outside = [e for e in all_eqns(jax.make_jaxpr(
        lambda *a: gated_delta_rule(*a, chunk=KQ))(*args).jaxpr)
        if e.primitive.name == "dot_general"
        and e.params["precision"] is not None
        and e.invars[0].aval.shape[-2:] == (KQ, KQ) == e.invars[1].aval.shape[-2:]]
    assert len(outside) == 2 * (KQ.bit_length() - 2)
    assert all(v.aval.dtype == jnp.float32 for e in outside for v in e.invars)


def test_a_key_head_is_read_through_the_block_index_never_copied(kernels_interpreted):
    args = kernel_operands("two_chunks_two_heads_a_key_head")
    fwd, = kernel_calls(lambda *a: gated_delta_rule(*a, chunk=KQ), *args)
    # q and k go in as [B, S, G dk]: one key head for its two value heads.
    assert [v.aval.shape for v in fwd.invars[:2]] == [(1, 128, 2 * KD)] * 2
    assert fwd.invars[2].aval.shape == (1, 128, 4 * KD)


def test_the_kernels_walk_no_loop(kernels_interpreted):
    """The walk across the chunks is the kernels' grid: the differentiated
    program holds no `scan`, where the `jax.numpy` path's holds the
    forward's and the backward's."""
    args = kernel_operands("two_chunks_two_heads_a_key_head")
    grad = jax.grad(lambda *a: jnp.sum(gated_delta_rule(*a, chunk=KQ)),
                    argnums=range(5))
    kinds = lambda fn: [e.primitive.name
                        for e in all_eqns(jax.make_jaxpr(fn)(*args).jaxpr)]
    assert "scan" not in kinds(grad) and "while" not in kinds(grad)
    with pytest.MonkeyPatch.context() as patch:
        patch.setattr(kernel, "on_tpu", lambda: False)
        assert kinds(jax.grad(
            lambda *a: jnp.sum(gated_delta_rule(*a, chunk=KQ)),
            argnums=range(5))).count("scan") == 2


# (chunk, value heads, key heads, dk, dv) the kernels do not tile: a key
# width of 64, a value width of 64, a chunk of 256 (its [Q, Q] float32 blocks
# are more than a lane tile wide), a chunk of 12 (no whole sublane tile).
NOT_TAKEN = {"keys_of_64": (64, 4, 2, 64, 128),
             "values_of_64": (64, 4, 2, 128, 64),
             "chunk_256": (256, 4, 2, 128, 128),
             "chunk_12": (12, 4, 2, 128, 128)}
TAKEN = {"taken": (64, 4, 2, 128, 128), "taken_one_head": (64, 2, 2, 128, 128),
         "taken_chunk_128": (128, 2, 1, 128, 128)}


@pytest.mark.parametrize("shape", [*sorted(TAKEN), *sorted(NOT_TAKEN)])
def test_the_counter_says_which_path_a_rule_took(monkeypatch, shape):
    """On a TPU (`kernel.on_tpu`): one `fwd` and one `bwd` a rule built where
    the kernels tile the shape, none where they do not; on the CPU none.
    Traced only: nothing runs."""
    from oobleck_tpu.utils import metrics

    reg = metrics.registry()
    scans = reg.counter("oobleck_gdn_scans_total")
    named = reg.counter("oobleck_gdn_residuals_named_total")
    calls = reg.counter("oobleck_gdn_kernel_calls_total")
    chunk, heads, groups, dk, dv = {**TAKEN, **NOT_TAKEN}[shape]
    k = jax.random.split(jax.random.PRNGKey(0), 3)
    args = (jax.random.normal(k[0], (1, 256, groups, dk)),
            jax.random.normal(k[1], (1, 256, groups, dk)),
            jax.random.normal(k[2], (1, 256, heads, dv)),
            -jnp.ones((1, 256, heads)), jnp.ones((1, 256, heads)) / 2)
    read = lambda: (scans.value(), named.value(), calls.value(kernel="fwd"),
                    calls.value(kernel="bwd"))

    def built(on_tpu):
        monkeypatch.setattr(kernel, "on_tpu", lambda: on_tpu)
        before = read()
        # A function of its own a trace: an equal one would be a cache hit.
        found = kernel_calls(jax.grad(
            lambda *a: jnp.sum(gated_delta_rule(*a, chunk=chunk)),
            argnums=1), *args)
        return (tuple(b - a for a, b in zip(before, read())),
                sorted(e.params["name"] for e in found))

    assert built(on_tpu=False) == ((1, 1, 0, 0), [])
    took = shape in TAKEN
    assert built(on_tpu=True) == (
        (1, 1, int(took), int(took)), ["gdn_bwd", "gdn_fwd"] if took else [])


def _kernel_layer_gradients(wrap):
    layer = wrap(functools.partial(gated_delta_rule, chunk=KQ))
    return jax.grad(lambda *a: jnp.sum(layer(*a) ** 2), argnums=range(5))


@pytest.mark.parametrize("wrap,fwd_calls", [
    (jax.checkpoint, 2), (remat.checkpoint_layer, 1),
    (checkpoint_keeping(*gdn.RESIDUAL_NAMES), 1),
    (checkpoint_keeping(gdn.RESIDUAL_NAMES[0]), 2),
], ids=["bare", "the_layers_checkpoint", "the_rules_names_alone",
        "the_inverse_s_name_alone"])
def test_a_checkpoint_that_keeps_what_the_forward_kernel_wrote_recomputes_none(
        kernels_interpreted, wrap, fwd_calls):
    args = kernel_operands("two_chunks_two_heads_a_key_head")
    names = [e.params["name"] for e in
             kernel_calls(_kernel_layer_gradients(wrap), *args)]
    assert names.count("gdn_fwd") == fwd_calls
    assert names.count("gdn_bwd") == 1


@functools.cache
def _kept_and_bare_kernel_gradients():
    args = kernel_operands("two_chunks_two_heads_a_key_head", seed=2)
    return (jax.jit(_kernel_layer_gradients(remat.checkpoint_layer))(*args),
            jax.jit(_kernel_layer_gradients(jax.checkpoint))(*args))


@pytest.mark.parametrize("wrt", range(5), ids=ARGS)
def test_what_the_forward_kernel_wrote_is_what_a_second_call_would_write(
        kernels_interpreted, wrt):
    got, want = (g[wrt] for g in _kept_and_bare_kernel_gradients())
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
