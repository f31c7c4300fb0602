"""A sliding window in the flash kernels (`ops/flash.py`, `window=`): the
two kernels in interpret mode against `_xla_causal_attention` under the
band mask, output and all three gradients, for windows smaller than a
block, equal to one, not a multiple of one, a multiple, and at least the
sequence; the table of live block pairs at the benchmark cell's sizes;
the names the calls go out under; who refuses a window."""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from oobleck_tpu.ops import attention, flash, remat
from oobleck_tpu.ops.attention import _xla_causal_attention, causal_attention
from tests.ops.programs import pallas_calls

NAMES = ("out", "dq", "dk", "dv")


def _operands(batch, heads, seq, d):
    ks = jax.random.split(jax.random.PRNGKey(seq + heads), 4)
    return [jax.random.normal(k, (batch, heads, seq, d), jnp.float32) * 0.5
            for k in ks]


def _fwd_and_grads(fn, q, k, v, co):
    return jax.jit(lambda q, k, v: (lambda out, vjp: (out, *vjp(co)))(
        *jax.vjp(fn, q, k, v)))(q, k, v)


# (batch, heads, seq, head width, window). 1024 rows are 512 x 512 blocks,
# 1536 three of them a side, 640 one 640-row query block against 128-row
# key blocks, 600 pads to 640.
CASES = {
    "smaller_than_a_block": (1, 2, 1024, 64, 100),
    "one_key": (1, 2, 256, 64, 1),
    "equal_to_a_block": (1, 2, 1024, 64, 512),
    "not_a_multiple_of_a_block": (1, 2, 1536, 64, 700),
    "a_multiple_of_a_block": (2, 2, 1536, 32, 1024),
    "at_least_the_sequence": (1, 2, 1024, 64, 1024),
    "past_the_sequence": (1, 2, 512, 64, 4096),
    "ragged_sequence_uneven_blocks": (1, 3, 600, 128, 130),
}


@pytest.mark.parametrize("case", sorted(CASES))
def test_window_kernels_match_the_band_mask(case):
    b, h, s, d, window = CASES[case]
    q, k, v, co = _operands(b, h, s, d)
    got = _fwd_and_grads(
        lambda q, k, v: flash.flash_attention(q, k, v, window=window),
        q, k, v, co)
    want = _fwd_and_grads(
        lambda q, k, v: _xla_causal_attention(q, k, v, window=window),
        q, k, v, co)
    for name, a, w in zip(NAMES, got, want):
        np.testing.assert_allclose(np.asarray(a), np.asarray(w), rtol=2e-5,
                                   atol=2e-5, err_msg=name)


def test_the_band_mask_is_the_published_one():
    """Query i sees key j iff 0 <= i - j < window: itself and the window -
    1 keys before it (`kv_idx > q_idx - sliding_window`)."""
    s, window = 12, 4
    v = jnp.eye(s)[None, None]                   # out[i] = probabilities
    q = k = jnp.zeros((1, 1, s, 8))              # uniform over what is seen
    probs = np.asarray(_xla_causal_attention(q, k, v, window=window))[0, 0]
    i, j = np.arange(s)[:, None], np.arange(s)[None, :]
    seen = (j <= i) & (j > i - window)
    np.testing.assert_array_equal(probs > 0, seen)
    np.testing.assert_allclose(probs.sum(-1), 1.0, rtol=1e-6)
    assert (probs > 0).sum(-1).tolist() == [1, 2, 3] + [4] * 9


@pytest.mark.parametrize("case", ["at_least_the_sequence",
                                  "past_the_sequence"])
def test_a_window_the_sequence_never_reaches_is_the_causal_call(case):
    """Bit for bit: the same table, the same select on every pair."""
    b, h, s, d, window = CASES[case]
    t = flash.choose_tiles(s)
    for q_major in (True, False):
        for a, c in zip(flash._live_pairs(t, True, q_major, window),
                        flash._live_pairs(t, True, q_major)):
            np.testing.assert_array_equal(a, c)
    q, k, v, co = _operands(b, h, s, d)
    got = _fwd_and_grads(
        lambda q, k, v: flash.flash_attention(q, k, v, window=window),
        q, k, v, co)
    want = _fwd_and_grads(flash.flash_attention, q, k, v, co)
    for name, a, w in zip(NAMES, got, want):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(w), name)


# (sequence, window) -> (steps a head under the window, causal steps): the
# benchmark cell's, ISSUE 45's fallback (a), and a window of one block.
TABLES = {(16384, 4096): (252, 528), (8192, 4096): (108, 136),
          (4096, 4096): (36, 36), (2048, 512): (7, 10)}


@pytest.mark.parametrize("q_major", [True, False], ids=["q_major", "kv_major"])
@pytest.mark.parametrize("shape", sorted(TABLES))
def test_live_pairs_are_the_band_s(shape, q_major):
    seq, window = shape
    t = flash.choose_tiles(seq)
    assert (t.block_q, t.block_k) == (512, 512)
    q_of, k_of, flags = flash._live_pairs(t, True, q_major, window)
    banded, causal = TABLES[shape]
    assert len(q_of) == banded
    assert len(flash._live_pairs(t, True, q_major)[0]) == causal
    # Exactly the block pairs that hold a (query, key) pair of the band.
    blocks = seq // 512
    want = {(qi, ki) for qi in range(blocks) for ki in range(blocks)
            if ki <= qi and qi * 512 - (ki * 512 + 511) < window}
    assert set(zip(q_of.tolist(), k_of.tolist())) == want
    # The steps of one accumulation adjoin, each opened by a FIRST and
    # closed by a LAST; a row's keys ascend, so its diagonal pair comes
    # last (`_scores`: what a wholly masked row added is rescaled away).
    major, minor = (q_of, k_of) if q_major else (k_of, q_of)
    open_ = False
    for step, flag in enumerate(flags.tolist()):
        if flag & flash.FIRST:
            assert not open_
            open_ = True
        else:
            assert open_ and major[step] == major[step - 1]
            assert minor[step] > minor[step - 1]
        if flag & flash.LAST:
            open_ = False
    assert not open_
    assert (flags & flash.FIRST != 0).sum() == (
        flags & flash.LAST != 0).sum() == blocks


def test_window_calls_go_out_under_names_of_their_own():
    """A reader of `%flash_fwd.` counts a causal half: a windowed call
    never runs under that name, and O and LSE are named for the layer's
    checkpoint all the same."""
    from oobleck_tpu.utils import metrics

    q, k, v, _ = _operands(1, 2, 256, 64)
    reg = metrics.registry()
    built = reg.counter("oobleck_flash_window_calls_total")
    named = reg.counter("oobleck_flash_residuals_named_total")
    before = {n: built.value(kernel=n) for n in flash.WINDOW + flash.PLAIN}
    named_before = named.value(kernel="flash_swa_fwd")
    grad = jax.grad(lambda q, k, v: jnp.sum(
        remat.checkpoint_layer(
            lambda q, k, v: flash.flash_attention(q, k, v, window=100)
        )(q, k, v)), argnums=(0, 1, 2))
    calls = [n for n, _ in pallas_calls(jax.make_jaxpr(grad)(q, k, v).jaxpr)]
    # One forward kernel: the checkpoint kept what it wrote.
    assert sorted(calls) == sorted(flash.WINDOW)
    assert named.value(kernel="flash_swa_fwd") - named_before == 1
    for n in flash.WINDOW:
        assert built.value(kernel=n) - before[n] >= 1
    for n in flash.PLAIN:
        assert built.value(kernel=n) == before[n]
    plain = [n for n, _ in pallas_calls(jax.make_jaxpr(
        lambda q, k, v: flash.flash_attention(q, k, v))(q, k, v).jaxpr)]
    assert plain == ["flash_fwd"]
    assert len(set(flash.WINDOW + flash.PLAIN + flash.LATENT)) == 6


def test_live_pairs_gauge_reads_the_last_call_s_grid_steps():
    from oobleck_tpu.utils import metrics

    gauge = metrics.registry().gauge("oobleck_flash_live_pairs")
    q, k, v, _ = _operands(1, 1, 2048, 64)
    jax.make_jaxpr(jax.grad(lambda q: jnp.sum(
        flash.flash_attention(q, k, v, window=512))))(q)
    jax.make_jaxpr(lambda q: flash.flash_attention(q, k, v))(q)
    assert [gauge.value(kernel=n) for n in flash.WINDOW] == [7, 7]
    assert gauge.value(kernel="flash_fwd") == 10


def test_causal_attention_hands_the_window_down(monkeypatch):
    q, k, v, _ = _operands(1, 2, 256, 64)
    want = _xla_causal_attention(q, k, v, window=77)
    np.testing.assert_array_equal(
        np.asarray(causal_attention(q, k, v, impl="xla", window=77)),
        np.asarray(want))
    # "auto" off the TPU is the XLA path; "pallas" the interpreter.
    np.testing.assert_array_equal(
        np.asarray(causal_attention(q, k, v, window=77)), np.asarray(want))
    np.testing.assert_allclose(
        np.asarray(causal_attention(q, k, v, impl="pallas", window=77)),
        np.asarray(want), rtol=2e-5, atol=2e-5)
    assert float(jnp.max(jnp.abs(want - _xla_causal_attention(q, k, v)))) > 1e-3


@pytest.mark.parametrize("kwargs,match", [
    (dict(impl="ring"), "sliding window"),
    (dict(impl="ulysses"), "sliding window"),
    (dict(impl="xla", causal=False), "sliding window"),
    (dict(impl="pallas", causal=False), "sliding window"),
], ids=["ring", "ulysses", "non_causal_xla", "non_causal_pallas"])
def test_a_window_is_refused_where_it_cannot_be_kept(kwargs, match):
    q, k, v, _ = _operands(1, 2, 128, 64)
    with pytest.raises(ValueError, match=match):
        causal_attention(q, k, v, window=32, **kwargs)
    # Without a window the same call is fine (ring falls back off a mesh
    # only for biased calls: leave it out).
    if kwargs["impl"] not in ("ring",):
        causal_attention(q, k, v, **kwargs)


@pytest.mark.parametrize("call,match", [
    (lambda q, k, v: flash.flash_attention(q, k, v, causal=False, window=8),
     "causal"),
    (lambda q, k, v: flash.flash_attention(q, k, v, window=0), "at least 1"),
    (lambda q, k, v: _xla_causal_attention(q, k, v, causal=False, window=8),
     "causal"),
], ids=["flash_non_causal", "flash_window_zero", "xla_non_causal"])
def test_the_kernels_and_the_xla_path_refuse_it_themselves(call, match):
    q, k, v, _ = _operands(1, 2, 128, 64)
    with pytest.raises(ValueError, match=match):
        call(q, k, v)


def test_ring_and_ulysses_have_no_window_to_ignore():
    """Neither takes a `window` at all: a caller cannot hand one over and
    have it dropped."""
    import inspect

    from oobleck_tpu.ops.ring_attention import ring_attention
    from oobleck_tpu.ops.ulysses import ulysses_attention

    for fn in (ring_attention, ulysses_attention):
        assert "window" not in inspect.signature(fn).parameters
    assert attention.select_attention_impl("ring") is ring_attention
