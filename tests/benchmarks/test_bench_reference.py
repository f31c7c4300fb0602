"""The plain reference against the program's model, and the control.

The same comparison the cells make on the chip, at a size a test run can
hold (gpt2-tiny): the program in float32 agrees with the reference to
rounding; in bfloat16, as the cells' configurations state, it stays under a
limit that the reference computed one precision lower (fp8, the control)
breaks.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks import traffic
from benchmarks.reference import gpt as ref
from benchmarks.runners.train import checks_from

SEEDS = [3, 2**31 + 5, 77]
# Jitted once per (config, mode): eager autodiff through the scan is slow.
ref_forward = jax.jit(ref.forward, static_argnames=("c", "mode"))
ref_loss_and_grads = jax.jit(ref.loss_and_grads, static_argnames=("c", "mode"))


def _tiny(**overrides):
    from oobleck_tpu.models import build_model

    model = build_model("gpt2-tiny", dict(attention_impl="xla", **overrides))
    c = model.config
    rc = ref.RefConfig(c.vocab_size, c.max_position_embeddings,
                       c.hidden_size, c.num_layers, c.num_heads)
    return model, rc


def _rel(got, want):
    got, want = np.asarray(got, np.float64), np.asarray(want, np.float64)
    return float(np.linalg.norm(got - want) / np.linalg.norm(want))


def _grad_err(g, want):
    sq = lambda t: sum(float(jnp.sum(jnp.square(x.astype(jnp.float32))))
                       for x in jax.tree.leaves(t))
    diff = jax.tree.map(lambda a, b: a.astype(jnp.float32) - b, g, want)
    return (sq(diff) / sq(want)) ** 0.5


def test_init_is_seeded_and_in_the_programs_layout():
    model, rc = _tiny()
    a = ref.init_params(SEEDS[1], rc, stacked=True)
    b = ref.init_params(SEEDS[1], rc, stacked=True)
    c = ref.init_params(SEEDS[1] + 1, rc, stacked=True)
    assert all((x == y).all() for x, y in zip(jax.tree.leaves(a),
                                              jax.tree.leaves(b)))
    assert not (a["embed"]["wte"] == c["embed"]["wte"]).all()
    want = jax.eval_shape(lambda: model.init_params(jax.random.PRNGKey(0)))
    assert jax.tree.structure(a) == jax.tree.structure(want)
    assert [x.shape for x in jax.tree.leaves(a)] \
        == [x.shape for x in jax.tree.leaves(want)]
    layers = ref.init_params(SEEDS[1], rc, stacked=False)
    assert len(layers["blocks"]) == rc.num_layers


@pytest.mark.parametrize("seed", SEEDS)
def test_reference_agrees_with_the_model_in_float32(seed):
    model, rc = _tiny(dtype=jnp.float32, remat=False)
    params = ref.init_params(seed, rc, stacked=True)
    tokens = jnp.asarray(traffic.token_block(seed, 2, 96, rc.vocab_size))
    with jax.default_matmul_precision("highest"):
        got = jax.jit(model.forward)(params, tokens)
        got_loss, got_grads = jax.jit(jax.value_and_grad(model.loss))(
            params, {"input_ids": tokens})
    want = ref_forward(params, tokens, c=rc)
    v = rc.vocab_size
    assert _rel(got[..., :v], want[..., :v]) < 1e-5
    want_loss, want_grads = ref_loss_and_grads(params, tokens, c=rc)
    assert abs(float(got_loss) - float(want_loss)) < 1e-5 * float(want_loss)
    assert _grad_err(got_grads, want_grads) < 1e-4


@pytest.mark.parametrize("seed", SEEDS)
def test_control_is_not_correct_where_the_program_is(seed):
    """Logits, and gradients (the training cells' number): bfloat16
    program under the limit, fp8 control over it, with the threefold room
    the benchmark's rule asks for."""
    model, rc = _tiny()                       # bfloat16, as configured
    params = ref.init_params(seed, rc, stacked=True)
    tokens = jnp.asarray(traffic.token_block(seed, 1, 96, rc.vocab_size))
    v = rc.vocab_size
    want = ref_forward(params, tokens, c=rc)[..., :v]
    program = _rel(jax.jit(model.forward)(params, tokens)[..., :v], want)
    control = _rel(ref_forward(params, tokens, c=rc, mode="fp8")[..., :v], want)
    assert control > 3 * program
    limit = (program * control) ** 0.5
    assert checks_from({"e": program}, {"e": limit})[0]["ok"]
    assert not checks_from({"e": control}, {"e": limit})[0]["ok"]

    _, want_g = ref_loss_and_grads(params, tokens, c=rc)
    _, prog_g = jax.jit(jax.value_and_grad(model.loss))(
        params, {"input_ids": tokens})
    _, ctrl_g = ref_loss_and_grads(params, tokens, c=rc, mode="fp8")
    program, control = _grad_err(prog_g, want_g), _grad_err(ctrl_g, want_g)
    assert control > 3 * program
    limit = (program * control) ** 0.5
    assert checks_from({"e": program}, {"e": limit})[0]["ok"]
    assert not checks_from({"e": control}, {"e": limit})[0]["ok"]


def test_a_check_that_gives_no_number_fails():
    assert not checks_from({"e": float("nan")}, {"e": 1.0})[0]["ok"]


def test_unknown_mode_is_an_error():
    _, rc = _tiny()
    params = ref.init_params(1, rc, stacked=True)
    with pytest.raises(ValueError):
        ref.forward(params, jnp.zeros((1, 8), jnp.int32), rc, "int4")
