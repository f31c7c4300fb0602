"""What a layer's checkpoint keeps: of flash attention (`ops/flash.py`),
of the gated delta rule (`ops/gdn.py`) and of the Mamba-2 scan
(`ops/ssd.py`).

`_flash_fwd` names the two things the forward kernel wrote, O and the row
logsumexp, `ops/gdn._inverse_fwd` the inverse its series gave and
`ops/gdn._rule_fwd` the two things the rule's forward kernel wrote, o and
the state at every chunk's start, `ops/ssd._scan_fwd` the two things ITS
forward kernel wrote, y and the state at every chunk's start, and
`checkpoint_layer` is `jax.checkpoint` with the policy that keeps values
by those names. So the forward a backward pass recomputes holds no kernel
call and no series: their only consumers are the kept values, and neither
has a side effect.

(i) The benchmark cells' one-stage `jit_bwd`, compiled for a described
TPU v5e, holds each forward kernel once: `tests/ops/cells.py` and the
three `test_remat_cells_*.py` files that divide its seven compiles.
Nothing executes there; no number comes out. (ii) On the CPU, kernels
interpreted: half the forward-kernel equations of a bare `jax.checkpoint`
and bit-identical gradients (the rule's: `tests/ops/test_gdn.py`).
(iii) A layer emits no value by a name: nothing is kept by it, and the
layer lowers to the text it lowered to without that name in the policy.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from oobleck_tpu.ops import attention, flash, remat
from tests.ops.programs import checkpoint_keeping, pallas_calls


def _plain_block(w, x):
    b, s, d = x.shape
    q, k, v = (jnp.transpose((x @ w[i]).reshape(b, s, 2, d // 2), (0, 2, 1, 3))
               for i in range(3))
    out = flash.flash_attention(q, k, v)
    return x + jnp.transpose(out, (0, 2, 1, 3)).reshape(b, s, d) @ w[3]


def _latent_block(w, x):
    # Two heads: scores 16 + 8 wide over values of 16, one rotary key a
    # position.
    b, s, d = x.shape
    heads = lambda y: jnp.transpose(y.reshape(b, s, 2, -1), (0, 2, 1, 3))
    q_nope, k_nope, v = (heads(x @ w[i]) for i in range(3))
    q_rope = heads(x @ w[3][:, :16])
    k_rope = x @ w[3][:, 16:24]
    out = flash.latent_flash_attention(q_nope, q_rope, k_nope, k_rope, v)
    return x + jnp.transpose(out, (0, 2, 1, 3)).reshape(b, s, d) @ w[3]


def _xla_block(w, x):
    b, s, d = x.shape
    q, k, v = (jnp.transpose((x @ w[i]).reshape(b, s, 2, d // 2), (0, 2, 1, 3))
               for i in range(3))
    out = attention.causal_attention(q, k, v, impl="xla")
    return x + jnp.transpose(out, (0, 2, 1, 3)).reshape(b, s, d) @ w[3]


def _two_blocks(block, wrap):
    layer = wrap(block)
    return jax.grad(lambda w, x: jnp.sum(layer(w[1], layer(w[0], x)) ** 2),
                    argnums=(0, 1))


def _operands():
    rng = np.random.default_rng(0)
    w = jnp.asarray(rng.normal(0, 0.2, (2, 4, 32, 32)), jnp.float32)
    x = jnp.asarray(rng.normal(0, 1.0, (1, 128, 32)), jnp.float32)
    return w, x


@pytest.mark.parametrize("block,names", [(_plain_block, flash.PLAIN),
                                         (_latent_block, flash.LATENT)],
                         ids=["plain", "latent"])
def test_kept_residuals_halve_the_forward_calls_and_keep_the_gradients(
        block, names):
    w, x = _operands()
    bare = _two_blocks(block, jax.checkpoint)
    kept = _two_blocks(block, remat.checkpoint_layer)
    calls = lambda fn: [name for name, _ in pallas_calls(
        jax.make_jaxpr(fn)(w, x).jaxpr)]
    assert calls(bare).count(names.fwd) == 4
    assert calls(kept).count(names.fwd) == 2
    for fn in (bare, kept):     # the ONE backward kernel: once a block
        assert calls(fn).count(names.bwd) == 2
    # A kept value is the value a second call would have written.
    for got, want in zip(jax.tree.leaves(jax.jit(kept)(w, x)),
                         jax.tree.leaves(jax.jit(bare)(w, x))):
        np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


def _scan_block(w, x):
    """A layer around `ssd_scan`: 2 heads of 64 in one group, a state of
    128, chunks of 128 (what the kernels tile), all made from x [1, S, 32]."""
    from oobleck_tpu.ops.ssd import ssd_scan

    b, s, d = x.shape
    y = ssd_scan((x @ w[0]).reshape(b, s, 2, 64),
                 jax.nn.softplus(x @ w[1][:, :2]), -jnp.ones((2,)),
                 (x @ w[2]).reshape(b, s, 1, 128),
                 (x @ w[3]).reshape(b, s, 1, 128), jnp.ones((2,)), chunk=128)
    return x + y.reshape(b, s, 128) @ w[4]


def test_the_scan_s_kept_residuals_halve_its_forward_calls_and_keep_the_gradients(
        kernels_interpreted):
    rng = np.random.default_rng(0)
    w = [jnp.asarray(rng.normal(0, 0.2, (2, *shape)), jnp.float32)
         for shape in ((32, 128), (32, 128), (32, 128), (32, 128), (128, 32))]
    x = jnp.asarray(rng.normal(0, 1.0, (1, 256, 32)), jnp.float32)

    def two_blocks(wrap):
        layer = wrap(_scan_block)
        return jax.grad(lambda w, x: jnp.sum(
            layer([m[1] for m in w], layer([m[0] for m in w], x)) ** 2),
            argnums=(0, 1))

    bare, kept = two_blocks(jax.checkpoint), two_blocks(remat.checkpoint_layer)
    calls = lambda fn: [name for name, _ in pallas_calls(
        jax.make_jaxpr(fn)(w, x).jaxpr)]
    assert calls(bare).count("ssd_fwd") == 4
    assert calls(kept).count("ssd_fwd") == 2
    for fn in (bare, kept):
        assert calls(fn).count("ssd_bwd") == 2
    for got, want in zip(jax.tree.leaves(jax.jit(kept)(w, x)),
                         jax.tree.leaves(jax.jit(bare)(w, x))):
        np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


def _lowered(block, wrap):
    w, x = _operands()
    return jax.jit(_two_blocks(block, wrap)).lower(w, x).as_text()


def test_a_layer_on_the_xla_path_lowers_as_under_a_bare_checkpoint():
    """Names absent, nothing kept: what every CPU test leans on."""
    w, x = _operands()
    assert _lowered(_xla_block, remat.checkpoint_layer) == _lowered(
        _xla_block, jax.checkpoint)
    assert not pallas_calls(jax.make_jaxpr(
        _two_blocks(_xla_block, remat.checkpoint_layer))(w, x).jaxpr)


@pytest.mark.parametrize("block", [_plain_block, _latent_block],
                         ids=["plain", "latent"])
def test_a_layer_without_the_delta_rule_keeps_nothing_new(block):
    """The policy also names the rule's inverse (`ops/gdn.py`); a layer
    that emits no such value lowers to the text it lowered to under
    flash's two names alone."""
    assert _lowered(block, remat.checkpoint_layer) == _lowered(
        block, checkpoint_keeping(*flash.RESIDUAL_NAMES))


def test_named_residuals_are_counted_once_a_forward_rule_traced():
    from oobleck_tpu.utils import metrics

    named = metrics.registry().counter("oobleck_flash_residuals_named_total")
    before = {n: named.value(kernel=n) for n in ("flash_fwd", "flash_mla_fwd")}
    w, x = _operands()
    fn = jax.jit(jax.grad(lambda w, x: jnp.sum(_plain_block(w[0], x))))
    fn(w, x)
    fn(w, x)                        # a cache hit traces nothing
    assert named.value(kernel="flash_fwd") - before["flash_fwd"] == 1
    # The XLA path and a call that is not differentiated name nothing.
    jax.jit(jax.grad(lambda w, x: jnp.sum(_xla_block(w[0], x))))(w, x)
    jax.jit(_latent_block)(w[0], x)
    assert named.value(kernel="flash_fwd") - before["flash_fwd"] == 1
    assert named.value(kernel="flash_mla_fwd") == before["flash_mla_fwd"]
