"""Kernel tests: flash attention (Pallas, interpreter mode on CPU) and ring
attention (4-way sequence-parallel mesh) against the XLA reference."""

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P

from oobleck_tpu.ops.attention import _xla_causal_attention, causal_attention
from oobleck_tpu.ops.flash import flash_attention
from oobleck_tpu.ops.ring_attention import ring_attention

B, H, S, D = 2, 4, 256, 64


@pytest.fixture(scope="module")
def qkv():
    ks = jax.random.split(jax.random.PRNGKey(0), 3)
    mk = lambda k: jax.random.normal(k, (B, H, S, D), jnp.float32) * 0.3
    return mk(ks[0]), mk(ks[1]), mk(ks[2])


def test_flash_matches_xla(qkv):
    q, k, v = qkv
    want = _xla_causal_attention(q, k, v)
    got = flash_attention(q, k, v)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-3, atol=2e-3)


def test_flash_unaligned_seq_and_head(qkv):
    q, k, v = (x[:, :, :200, :48] for x in qkv)
    want = _xla_causal_attention(q, k, v)
    got = flash_attention(q, k, v)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-3, atol=2e-3)


def test_flash_grads_match_xla(qkv):
    q, k, v = qkv

    def loss_flash(q, k, v):
        return jnp.sum(flash_attention(q, k, v) ** 2)

    def loss_xla(q, k, v):
        return jnp.sum(_xla_causal_attention(q, k, v) ** 2)

    g1 = jax.grad(loss_flash, argnums=(0, 1, 2))(q, k, v)
    g2 = jax.grad(loss_xla, argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(g1, g2):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=2e-2, atol=2e-3)


def _grads(fn, *args):
    return jax.grad(lambda *a: jnp.sum(fn(*a) ** 2), argnums=(0, 1, 2))(*args)


@pytest.mark.parametrize("shape,causal,with_bias,window,v_width", [
    ((2, 4, 256, 64), True, False, None, None),    # aligned causal
    ((2, 4, 200, 48), True, False, None, None),    # unaligned seq + head
    ((2, 4, 256, 64), True, True, None, None),     # ALiBi-style bias
    ((2, 4, 200, 48), True, True, None, None),     # unaligned + bias
    ((2, 4, 256, 64), False, False, None, None),   # bidirectional (encoder)
    ((2, 4, 200, 48), False, True, None, None),    # bidirectional + bias, unaligned
    # The resident dq. Four kv columns of 512 under a window of 700: a
    # column's band spans three q blocks, so a q block's rows are added to
    # in three columns, steps apart; two heads of two sequences, so the
    # accumulator opens and closes four times.
    ((2, 2, 2048, 64), True, False, 700, None),
    # One kv column of 640 against five q blocks of 128: the head's first
    # step is its column's first, its last the column's last.
    ((2, 2, 640, 48), True, False, None, None),
    ((2, 2, 640, 48), False, True, None, None),
    # One 128-row block: the head opens and closes in the one step.
    ((2, 2, 128, 64), True, False, None, None),
    # Latent widths: scores 192 wide (256 in the kernel), values 128; dq
    # and dk 256 wide beside a 128-wide dv, over 3 live pairs.
    ((2, 2, 1024, 192), True, False, None, 128),
])
def test_flash_bwd_kernel_matches_xla(shape, causal, with_bias, window,
                                      v_width):
    """The one Pallas backward kernel (dq, dk, dv) against XLA autodiff,
    every shape class."""
    ks = jax.random.split(jax.random.PRNGKey(7), 4)
    q, k, v = (jax.random.normal(kk, shape, jnp.float32) * 0.3 for kk in ks[:3])
    v = v[..., :v_width]
    bias = None
    if with_bias:
        from oobleck_tpu.ops.attention import alibi_bias

        bias = alibi_bias(shape[1], shape[2], shape[2])
    if v_width is None:
        kernel = lambda q, k, v: flash_attention(q, k, v, bias=bias,
                                                 causal=causal, window=window)
    else:
        from oobleck_tpu.ops import flash

        kernel = lambda q, k, v: flash._flash(
            q, k, v, None, None, shape[-1] ** -0.5, True, flash.LATENT, None)
    reference = lambda q, k, v: _xla_causal_attention(
        q, k, v, bias=bias, causal=causal, window=window)
    np.testing.assert_allclose(np.asarray(kernel(q, k, v)),
                               np.asarray(reference(q, k, v)),
                               rtol=2e-3, atol=2e-3)
    for a, b in zip(_grads(kernel, q, k, v), _grads(reference, q, k, v)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=2e-2, atol=2e-3)


@pytest.mark.parametrize("shape,causal", [
    ((2, 4, 256, 64), True),
    ((2, 4, 200, 48), True),      # unaligned seq + head
    ((2, 4, 200, 48), False),     # bidirectional, unaligned
])
def test_flash_inkernel_alibi_slopes_match_bias(shape, causal):
    """ALiBi via in-kernel slopes must equal the materialized-bias paths
    (flash-with-bias AND XLA), forward and grads — the [H, S, S] bias
    buffer is gone from HBM, the math must not move."""
    from oobleck_tpu.ops.attention import alibi_bias, alibi_slopes

    ks = jax.random.split(jax.random.PRNGKey(11), 3)
    q, k, v = (jax.random.normal(kk, shape, jnp.float32) * 0.3 for kk in ks)
    slopes = alibi_slopes(shape[1])
    bias = alibi_bias(shape[1], shape[2], shape[2], causal=causal)

    got = flash_attention(q, k, v, alibi_slopes=slopes, causal=causal)
    via_bias = flash_attention(q, k, v, bias=bias, causal=causal)
    via_xla = _xla_causal_attention(q, k, v, bias=bias, causal=causal)
    np.testing.assert_allclose(np.asarray(got), np.asarray(via_bias),
                               rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(np.asarray(got), np.asarray(via_xla),
                               rtol=2e-3, atol=2e-3)
    g1 = _grads(lambda q, k, v: flash_attention(
        q, k, v, alibi_slopes=slopes, causal=causal), q, k, v)
    g2 = _grads(lambda q, k, v: _xla_causal_attention(
        q, k, v, bias=bias, causal=causal), q, k, v)
    for a, b in zip(g1, g2):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=2e-2, atol=2e-3)


FORMS = ["causal", "non_causal", "alibi_slopes", "bias"]


def _flash_case(seq, head_dim, form):
    """(flash, reference) of one form, each q, k, v -> out, and the inputs:
    one batch of two heads, enough for a per-head slope or bias."""
    from oobleck_tpu.ops.attention import alibi_bias, alibi_slopes

    ks = jax.random.split(jax.random.PRNGKey(seq + head_dim), 3)
    q, k, v = (jax.random.normal(kk, (1, 2, seq, head_dim), jnp.float32) * 0.3
               for kk in ks)
    causal = form != "non_causal"
    bias = alibi_bias(2, seq, seq) if form in ("alibi_slopes", "bias") else None
    kw = {"causal": causal}
    if form == "alibi_slopes":
        kw["alibi_slopes"] = alibi_slopes(2)
    elif form == "bias":
        kw["bias"] = bias
    flash = lambda q, k, v: flash_attention(q, k, v, **kw)
    ref = lambda q, k, v: _xla_causal_attention(q, k, v, bias=bias,
                                                causal=causal)
    return flash, ref, (q, k, v)


def _assert_fwd_and_grads_match(flash, ref, args):
    # One vjp each: the forward and dq, dk, dv of the same call.
    co = jax.random.normal(jax.random.PRNGKey(3), args[0].shape, jnp.float32)
    both = lambda fn: jax.jit(
        lambda q, k, v: (lambda out, vjp: (out, *vjp(co)))(
            *jax.vjp(fn, q, k, v)))
    got, want = both(flash)(*args), both(ref)(*args)
    for name, a, b in zip(("out", "dq", "dk", "dv"), got, want):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=2e-3,
                                   atol=2e-3, err_msg=name)


@pytest.mark.parametrize("form", FORMS)
@pytest.mark.parametrize("head_dim", [64, 80, 128])
@pytest.mark.parametrize("seq", [128, 384, 640, 1024, 2048])
def test_flash_matches_xla_at_every_chosen_tile(seq, head_dim, form):
    """Forward and dq, dk, dv at the tiles `choose_tiles` returns: one
    block (128, 384), one q block against the whole row (640), and grids of
    3 and 10 live 512 x 512 pairs of 4 and 16 (1024, 2048), where the
    wholly masked pairs are no step at all."""
    _assert_fwd_and_grads_match(*_flash_case(seq, head_dim, form))


@pytest.mark.parametrize("form", FORMS)
@pytest.mark.parametrize("seq", [600, 1000])
def test_flash_matches_xla_with_padded_rows_in_the_last_block(seq, form):
    """A sequence that is not a multiple of 128 is padded to the next one
    and no further; the padded key columns sit in the last block of several
    (masked there, and only there, in the bidirectional form)."""
    _assert_fwd_and_grads_match(*_flash_case(seq, 80, form))


def test_choose_tiles_is_a_pure_function_of_the_sequence_length():
    import inspect

    from oobleck_tpu.ops import flash

    # No knob: the sequence length is all it takes, and nothing of the
    # module reads the environment.
    assert list(inspect.signature(flash.choose_tiles).parameters) == [
        "seq_len"]
    assert "environ" not in inspect.getsource(flash)
    assert flash.choose_tiles(1024) is flash.choose_tiles(1024)  # memoised
    assert flash.choose_tiles(128) == (128, 128, 128)
    assert flash.choose_tiles(5) == (128, 128, 128)
    assert flash.choose_tiles(640) == (640, 128, 640)
    assert flash.choose_tiles(1024) == (1024, 512, 512)
    assert flash.choose_tiles(2048) == (2048, 512, 512)
    for seq_len in range(1, 4200, 7):
        t = flash.choose_tiles(seq_len)
        # Never a whole 128-row block of padding.
        assert seq_len <= t.seq < seq_len + 128 and t.seq % 128 == 0
        for block in (t.block_q, t.block_k):
            assert block % 128 == 0 and t.seq % block == 0
        assert t.block_q <= flash.MAX_BLOCK
        assert t.block_q * t.block_k <= flash.MAX_PAIR


@pytest.mark.parametrize("causal", [True, False])
@pytest.mark.parametrize("seq_len", [128, 600, 640, 1024, 1100, 2048, 4096])
@pytest.mark.parametrize("q_major", [True, False])
def test_live_pairs_are_exactly_the_pairs_the_mask_leaves(seq_len, causal,
                                                          q_major):
    """The grid's steps against the mask drawn out in full: a step for
    every block pair with a live element and for no other, FIRST and LAST
    once per accumulation, an accumulation's steps together."""
    from oobleck_tpu.ops import flash

    t = flash.choose_tiles(seq_len)
    q_of, k_of, flag_of = flash._live_pairs(t, causal, q_major)
    pos = np.arange(t.seq)
    live = (pos[:, None] >= pos[None, :]) if causal else np.ones(
        (t.seq, t.seq), bool)
    blocks = live.reshape(t.seq // t.block_q, t.block_q,
                          t.seq // t.block_k, t.block_k)
    want = {(qi, ki) for qi in range(blocks.shape[0])
            for ki in range(blocks.shape[2]) if blocks[qi, :, ki].any()}
    got = list(zip(q_of.tolist(), k_of.tolist()))
    assert set(got) == want and len(got) == len(want)
    major = q_of if q_major else k_of
    groups = len(set(major.tolist()))
    assert sum(bool(f & flash.FIRST) for f in flag_of) == groups
    assert sum(bool(f & flash.LAST) for f in flag_of) == groups
    assert np.all(np.diff(major) >= 0)          # an accumulation's steps adjoin
    assert flash._live_pairs(t, causal, q_major)[0] is q_of      # memoised


def test_flash_bwd_is_pallas_not_xla_recompute():
    """The VJP must not rebuild the [S, S] logits through XLA: no dot with an
    S x S operand may appear in the backward jaxpr outside pallas calls."""
    q = jnp.zeros((1, 2, 256, 64), jnp.float32)
    jaxpr = jax.make_jaxpr(
        lambda q, k, v: jax.grad(
            lambda q_: jnp.sum(flash_attention(q_, k, v)))(q))(q, q, q)
    flat = str(jaxpr)
    # the only dot_generals outside pallas_call bodies are in the delta
    # computation (sum(do*o)) which has no S x S operand; pallas kernels are
    # opaque closed calls so S x S dots inside them do not appear here.
    import re

    for m in re.finditer(r"dot_general\[[^\]]*\][^\n]*", flat):
        line = m.group(0)
        assert "256,256" not in line, f"S x S matmul leaked into bwd: {line}"


def test_registry_resolves_all():
    for impl in ("xla", "pallas", "ring", "auto"):
        assert causal_attention is not None
        from oobleck_tpu.ops.attention import select_attention_impl

        assert select_attention_impl(impl) is not None


# ----------------------------------------------------------------- #
# ring attention over a 4-way sequence-parallel mesh


def test_ring_matches_xla(qkv, devices8):
    q, k, v = qkv
    n = 4
    mesh = Mesh(np.array(devices8[:n]), ("sp",))
    spec = P(None, None, "sp", None)

    ring = jax.jit(jax.shard_map(
        lambda q, k, v: ring_attention(q, k, v, axis_name="sp"),
        mesh=mesh, in_specs=(spec, spec, spec), out_specs=spec,
        axis_names={"sp"},
    ))
    got = ring(q, k, v)
    want = _xla_causal_attention(q, k, v)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-3, atol=2e-3)


def test_ring_grads_match_xla(qkv, devices8):
    q, k, v = qkv
    n = 4
    mesh = Mesh(np.array(devices8[:n]), ("sp",))
    spec = P(None, None, "sp", None)

    def ring_loss(q, k, v):
        out = jax.shard_map(
            lambda q, k, v: ring_attention(q, k, v, axis_name="sp"),
            mesh=mesh, in_specs=(spec, spec, spec), out_specs=spec,
            axis_names={"sp"},
        )(q, k, v)
        return jnp.sum(out ** 2)

    def xla_loss(q, k, v):
        return jnp.sum(_xla_causal_attention(q, k, v) ** 2)

    g1 = jax.jit(jax.grad(ring_loss, argnums=(0, 1, 2)))(q, k, v)
    g2 = jax.grad(xla_loss, argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(g1, g2):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=2e-2, atol=2e-3)


# ---------------------------------------------------------------------- #
# ulysses attention over a 4-way sequence-parallel mesh


def _ulysses_shard_map(mesh, bias=None):
    from oobleck_tpu.ops.ulysses import ulysses_attention

    spec = P(None, None, "sp", None)
    return jax.shard_map(
        lambda q, k, v: ulysses_attention(q, k, v, axis_name="sp",
                                          bias=bias),
        mesh=mesh, in_specs=(spec, spec, spec), out_specs=spec,
        axis_names={"sp"},
    )


def test_ulysses_matches_xla(qkv, devices8):
    q, k, v = qkv
    mesh = Mesh(np.array(devices8[:4]), ("sp",))
    got = jax.jit(_ulysses_shard_map(mesh))(q, k, v)
    want = _xla_causal_attention(q, k, v)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-3, atol=2e-3)


def test_ulysses_grads_match_xla(qkv, devices8):
    q, k, v = qkv
    mesh = Mesh(np.array(devices8[:4]), ("sp",))
    smap = _ulysses_shard_map(mesh)

    def uly_loss(q, k, v):
        return jnp.sum(smap(q, k, v) ** 2)

    def xla_loss(q, k, v):
        return jnp.sum(_xla_causal_attention(q, k, v) ** 2)

    g1 = jax.jit(jax.grad(uly_loss, argnums=(0, 1, 2)))(q, k, v)
    g2 = jax.grad(xla_loss, argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(g1, g2):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=2e-2, atol=2e-3)


def test_alibi_bidirectional_bias_is_symmetric_penalty():
    """causal=False ALiBi uses -slope * |q - k|: symmetric in (q, k), never
    positive (the signed form would REWARD attending to future keys), and
    identical to the causal form on the lower triangle where both apply."""
    from oobleck_tpu.ops.attention import alibi_bias

    H, S = 4, 16
    sym = np.asarray(alibi_bias(H, S, S, causal=False))
    signed = np.asarray(alibi_bias(H, S, S, causal=True))
    assert np.all(sym <= 0)
    np.testing.assert_array_equal(sym, np.transpose(sym, (0, 2, 1)))
    lower = np.tril_indices(S)
    for h in range(H):
        np.testing.assert_array_equal(sym[h][lower], signed[h][lower])
    # and the signed form does reward the future half — the bug this guards
    assert np.all(signed[:, 0, 1:] > 0)


def test_ulysses_alibi_bias_matches_xla(qkv, devices8):
    """ALiBi + sequence parallelism: the ring layout cannot carry a
    position-dependent bias; the Ulysses layout holds the full sequence and
    must match full ALiBi attention exactly."""
    from oobleck_tpu.ops.attention import alibi_bias

    q, k, v = qkv
    mesh = Mesh(np.array(devices8[:4]), ("sp",))
    bias = alibi_bias(H, S, S)
    got = jax.jit(_ulysses_shard_map(mesh, bias=bias))(q, k, v)
    want = _xla_causal_attention(q, k, v, bias=bias)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-3, atol=2e-3)


def test_flash_traces_inside_check_vma_shard_map(qkv, devices8):
    """The training step's shape — flash under a default (check_vma=True)
    shard_map, differentiated from outside so the spec transposes run —
    must trace (a bare pallas out_shape does not) and agree with XLA.
    tests/ops/test_tpu_compile.py compiles the same shape for the chip."""
    q, k, v = (jnp.concatenate([x, x * 0.5]) for x in qkv)
    mesh = Mesh(np.array(devices8[:2]), ("data",))

    def sharded(fn):
        return jax.shard_map(fn, mesh=mesh, in_specs=(P("data"),) * 3,
                             out_specs=P("data"))

    loss = lambda fn: (lambda q, k, v: jnp.sum(sharded(fn)(q, k, v) ** 2))
    got = jax.grad(loss(flash_attention), argnums=(0, 1, 2))(q, k, v)
    want = jax.grad(loss(_xla_causal_attention), argnums=(0, 1, 2))(q, k, v)
    for g, w in zip(got, want):
        np.testing.assert_allclose(g, w, atol=2e-4, rtol=2e-4)


@pytest.mark.parametrize("tpu_visible", [False, True])
def test_auto_never_hides_a_tpu_behind_the_cpu(monkeypatch, tpu_visible):
    """Off-TPU "auto" is the XLA reference and explicit pallas runs
    interpreted — for processes with no TPU. One that can reach a TPU while
    its default backend is something else must not idle the chip quietly."""
    from oobleck_tpu.ops import kernel

    def devices(backend=None):
        if backend == "tpu" and not tpu_visible:
            raise RuntimeError("Unknown backend tpu")
        return ["a device"]

    monkeypatch.setattr(jax, "devices", devices)
    assert jax.default_backend() == "cpu"
    if tpu_visible:
        with pytest.raises(RuntimeError, match="a TPU is visible"):
            kernel.on_tpu()
    else:
        assert kernel.on_tpu() is False
