"""Latent attention's core (`ops/flash.latent_flash_attention`): forward
and all five gradients against dense attention written out in `jax.numpy`,
at the tiles `choose_tiles` returns; and the plain calls as they were:
one-width calls build the tables, shapes and names there were."""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from oobleck_tpu.ops import attention, flash
from tests.ops.programs import pallas_calls

NAMES = ("out", "dq_nope", "dq_rope", "dk_nope", "dk_rope", "dv")


def _dense(q_nope, q_rope, k_nope, k_rope, v):
    """The equations, whole: 192-wide scores, one rotary key a position for
    all heads, causal softmax, 128-wide values."""
    s = q_nope.shape[2]
    scores = (jnp.einsum("bhqd,bhkd->bhqk", q_nope, k_nope)
              + jnp.einsum("bhqd,bkd->bhqk", q_rope, k_rope))
    scores = scores / np.sqrt(q_nope.shape[-1] + q_rope.shape[-1])
    live = jnp.arange(s)[None, :] <= jnp.arange(s)[:, None]
    probs = jax.nn.softmax(jnp.where(live, scores, -1e30), axis=-1)
    return jnp.einsum("bhqk,bhkd->bhqd", probs, v)


def _operands(batch, heads, seq, dn, dr, dv):
    ks = jax.random.split(jax.random.PRNGKey(seq + heads), 5)
    shapes = [(batch, heads, seq, dn), (batch, heads, seq, dr),
              (batch, heads, seq, dn), (batch, seq, dr),
              (batch, heads, seq, dv)]
    return [jax.random.normal(k, s, jnp.float32) * 0.3
            for k, s in zip(ks, shapes)]


def _fwd_and_grads(fn, args):
    co = jax.random.normal(jax.random.PRNGKey(3),
                           (*args[0].shape[:3], args[4].shape[-1]))
    return jax.jit(lambda *a: (lambda out, vjp: (out, *vjp(co)))(
        *jax.vjp(fn, *a)))(*args)


# (batch, heads, seq, Dn, Dr, Dv): the cell's widths at one block (128), a
# sequence that is no multiple of the tile (200: padded rows in the one
# block; 600: in the last of several key blocks), one q block against the
# whole row (640), a grid of 3 live 512 x 512 pairs of 4 (1024); then small
# widths under a lane, and values WIDER than a lane beside narrow scores.
CASES = {
    "cell_widths_one_block": (1, 2, 128, 128, 64, 128),
    "cell_widths_ragged_200": (1, 2, 200, 128, 64, 128),
    "cell_widths_ragged_600": (1, 3, 600, 128, 64, 128),
    "one_q_block_640": (1, 2, 640, 128, 64, 128),
    "three_live_pairs_1024": (1, 2, 1024, 128, 64, 128),
    "two_sequences_small_widths": (2, 4, 384, 16, 8, 16),
    "values_wider_than_scores": (1, 2, 256, 32, 16, 160),
}


@pytest.mark.parametrize("case", sorted(CASES))
def test_latent_kernels_match_dense_attention(case):
    args = _operands(*CASES[case])
    got = _fwd_and_grads(flash.latent_flash_attention, args)
    want = _fwd_and_grads(_dense, args)
    assert got[0].shape == (*args[0].shape[:3], args[4].shape[-1])
    assert got[4].shape == args[3].shape          # ONE key a position
    for name, a, b in zip(NAMES, got, want):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=2e-3,
                                   atol=2e-3, err_msg=name)


def test_the_shared_keys_gradient_is_the_sum_over_heads():
    """dk_rope is what the heads' own keys would get, added up."""
    b, h, s, dn, dr, dv = CASES["cell_widths_ragged_200"]
    q_nope, q_rope, k_nope, k_rope, v = _operands(b, h, s, dn, dr, dv)
    co = jax.random.normal(jax.random.PRNGKey(3), (b, h, s, dv))

    def per_head(k_rope_h):                        # [B, H, S, Dr]
        q = jnp.concatenate([q_nope, q_rope], -1)
        k = jnp.concatenate([k_nope, k_rope_h], -1)
        return jnp.sum(flash.flash_attention(q, k, v) * co)

    by_head = jax.grad(per_head)(jnp.broadcast_to(k_rope[:, None],
                                                  q_rope.shape))
    shared = jax.grad(lambda kr: jnp.sum(flash.latent_flash_attention(
        q_nope, q_rope, k_nope, kr, v) * co))(k_rope)
    np.testing.assert_allclose(np.asarray(shared),
                               np.asarray(by_head.sum(axis=1)), atol=1e-5)


@pytest.mark.parametrize("impl", ["xla", "pallas", "auto"])
def test_the_dispatch_computes_the_same_on_every_path(impl):
    args = _operands(*CASES["two_sequences_small_widths"])
    got = attention.latent_attention(*args, impl=impl)
    np.testing.assert_allclose(np.asarray(got), np.asarray(_dense(*args)),
                               atol=2e-3)


def test_a_rotary_key_per_head_is_refused():
    q_nope, q_rope, k_nope, k_rope, v = _operands(1, 2, 128, 16, 8, 16)
    with pytest.raises(ValueError, match="one key a position"):
        flash.latent_flash_attention(q_nope, q_rope, k_nope, q_rope, v)
    with pytest.raises(ValueError, match="unknown attention impl"):
        attention.latent_attention(q_nope, q_rope, k_nope, k_rope, v,
                                   impl="paged")


def _kernels(fn, *args):
    """(name, operand shapes) of every pallas_call of grad(fn)."""
    return pallas_calls(jax.make_jaxpr(jax.grad(
        lambda *a: jnp.sum(fn(*a)), argnums=tuple(range(len(args)))))(
            *args).jaxpr)


def test_names_of_both_families():
    plain = [jnp.zeros((1, 2, 256, 80), jnp.bfloat16)] * 3
    calls = dict(_kernels(flash.flash_attention, *plain))
    assert set(calls) == set(flash.PLAIN) == {"flash_fwd", "flash_bwd_dqkv"}
    assert set(map(tuple, calls["flash_fwd"])) == {(2, 256, 128)}
    latent = [jnp.zeros(s, jnp.bfloat16) for s in (
        (1, 2, 256, 128), (1, 2, 256, 64), (1, 2, 256, 128), (1, 256, 64),
        (1, 2, 256, 128))]
    calls = dict(_kernels(flash.latent_flash_attention, *latent))
    assert set(calls) == set(flash.LATENT) == {
        "flash_mla_fwd", "flash_mla_bwd_dqkv"}
    # No name of the one family matches the other's trace pattern.
    for name in flash.LATENT:
        assert not name.startswith(("flash_fwd", "flash_bwd_"))
    # q and k travel at 256 (192 padded to the lane); v, O and dO at 128,
    # not padded to the scores' width.
    assert calls["flash_mla_fwd"] == [(2, 256, 256), (2, 256, 256),
                                      (2, 256, 128)]
    q, k, v, o, do, lse = calls["flash_mla_bwd_dqkv"]
    assert (q, k, v, o, do) == ((2, 256, 256), (2, 256, 256), (2, 256, 128),
                                (2, 256, 128), (2, 256, 128))


@pytest.mark.parametrize("seq,head_dim", [(1024, 80), (2048, 128), (600, 64)])
def test_one_width_calls_are_what_they_were(seq, head_dim):
    """A call with one width pads q, k and v alike, to the shapes and over
    the tables there were before `v` had a width of its own."""
    q = jnp.zeros((2, 3, seq, head_dim), jnp.bfloat16)
    qp, kp, vp, _, info = flash._pad_inputs(q, q, q, None)
    t = flash.choose_tiles(seq)
    wide = -(-head_dim // 128) * 128
    assert qp.shape == kp.shape == vp.shape == (6, t.seq, wide)
    assert info == (2, 3, seq, head_dim, head_dim, 6, t.seq, wide, wide)
    calls = _kernels(flash.flash_attention, q, q, q)
    assert sorted(n for n, _ in calls) == ["flash_bwd_dqkv", "flash_fwd"]
    for _, shapes in calls:
        assert set(shapes) <= {(6, t.seq, wide), (6, t.seq, 128)}
    steps = len(flash._live_pairs(t, True, True)[0])
    assert steps == sum(
        1 for qi in range(t.seq // t.block_q)
        for ki in range(t.seq // t.block_k)
        if ki * t.block_k < (qi + 1) * t.block_q)
    # Latent calls at the same length walk the same tables.
    assert flash._live_pairs(t, True, True) is flash._live_pairs(
        flash.choose_tiles(seq), True, True)


def test_the_calls_are_counted_where_the_kernels_are_built():
    from oobleck_tpu.utils import metrics

    built = metrics.registry().counter("oobleck_flash_mla_calls_total")
    before = {n: built.value(kernel=n) for n in flash.LATENT}
    args = _operands(1, 2, 128, 16, 8, 16)
    fn = jax.jit(flash.latent_flash_attention)
    fn(*args)
    fn(*args)                       # a cache hit builds nothing
    for n in flash.LATENT:
        assert built.value(kernel=n) - before[n] == 1
