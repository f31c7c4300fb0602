"""The optimizer's step (`PipelineInstance.apply_updates`): the layers largest
first, the caller's dict consumed, nothing donated. gpt2-tiny's six layers:
the embedding 98,304 B, four blocks of 199,936 B, the head 66,048 B, so the
order by size (1, 2, 3, 4, 0, 5) is not the dict's."""

import gc
import weakref

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from oobleck_tpu.execution import pipeline as pipeline_mod
from oobleck_tpu.execution.pipeline import optimizer_update_program
from oobleck_tpu.models.base import param_bytes
from oobleck_tpu.parallel.train import make_optimizer
from tests.execution.test_pipeline_mpmd import (  # noqa: F401 (fixtures)
    _make_pipe, batch, make_template, model)

BY_SIZE = (1, 2, 3, 4, 0, 5)
ONE_STAGE = ([(0, 6)], [1])
TWO_STAGES = ([(0, 3), (3, 6)], [1, 1])


@pytest.fixture(scope="module")
def optimizer():
    return make_optimizer(learning_rate=1e-2, warmup_steps=1)


def make_pipe(model, devices, splits, chips, v=1, params=None):
    return _make_pipe(model, devices, make_template(splits, chips), v,
                      params=params)


def enqueued(monkeypatch, pipe):
    """The layers `pipe`'s next updates are enqueued for, in order: each
    call of the optimizer's program is named after the parameters it is
    handed."""
    calls = []

    def recording(optimizer):
        program = optimizer_update_program(optimizer)
        layer_of = {id(p): li for li, p in pipe.params.items()}

        def call(g, state, p):
            calls.append(layer_of[id(p)])
            return program(g, state, p)

        return call

    monkeypatch.setattr(pipeline_mod, "optimizer_update_program", recording)
    return calls


def leaves(tree):
    return [np.asarray(x) for x in jax.tree.leaves(tree)]


@pytest.mark.parametrize("splits, chips, v", [
    (*ONE_STAGE, 1), (*TWO_STAGES, 1), (*TWO_STAGES, 2)],
    ids=["one_stage", "two_stages", "interleaved"])
def test_updates_enqueued_largest_first(
        monkeypatch, model, batch, optimizer, devices8, splits, chips, v):
    pipe = make_pipe(model, devices8, splits, chips, v)
    sizes = [param_bytes(pipe.params[li]) for li in pipe.update_order]
    assert sizes == sorted(sizes, reverse=True)
    assert pipe.update_order == BY_SIZE          # ties in layer order
    pipe.train_step(batch)
    calls = enqueued(monkeypatch, pipe)
    pipe.apply_updates(optimizer, pipe.init_opt_state(optimizer), pipe.grads)
    assert tuple(calls) == BY_SIZE


def test_order_follows_a_replacement(monkeypatch, model, batch, optimizer,
                                     devices8):
    """What a reconfiguration does: a new instance over the old one's
    parameters, on another layout. Its layers are placed again and its
    updates go in the order of what IT holds."""
    old = make_pipe(model, devices8, *ONE_STAGE)
    new = make_pipe(model, devices8, *TWO_STAGES, params=dict(old.params))
    assert new.update_order == BY_SIZE
    assert set(new.update_order) == set(new.params)
    new.train_step(batch)
    calls = enqueued(monkeypatch, new)
    new.apply_updates(optimizer, new.init_opt_state(optimizer), new.grads)
    assert tuple(calls) == BY_SIZE


def test_two_steps_give_the_bits_of_the_dicts_order(model, batch, optimizer,
                                                    devices8):
    """The layers' updates share nothing: largest first or in the dict's
    order (the loop `apply_updates` had), parameters and state are the same
    bits after two steps."""
    got = make_pipe(model, devices8, *TWO_STAGES)
    want = make_pipe(model, devices8, *TWO_STAGES)
    got_state = got.init_opt_state(optimizer)
    want_state = want.init_opt_state(optimizer)
    program = optimizer_update_program(optimizer)
    for _ in range(2):
        got.train_step(batch)
        got_state = got.apply_updates(optimizer, got_state, got.grads)
        want.train_step(batch)
        for li in want.params:
            want.params[li], want_state[li] = program(
                want.grads[li], want_state[li], want.params[li])
    assert tuple(want.params) != got.update_order
    for li in want.params:
        for a, b in zip(leaves((got.params[li], got_state[li])),
                        leaves((want.params[li], want_state[li])),
                        strict=True):
            np.testing.assert_array_equal(a, b)


def test_the_given_dict_comes_back_and_old_moments_die(model, batch,
                                                       optimizer, devices8):
    pipe = make_pipe(model, devices8, *ONE_STAGE)
    state = pipe.init_opt_state(optimizer)
    pipe.train_step(batch)
    state = pipe.apply_updates(optimizer, state, pipe.grads)
    # A moment that is not the zeros a state starts from; only `state`
    # holds it.
    old = [weakref.ref(x) for li in pipe.params
           for x in jax.tree.leaves(state[li]) if x.ndim]
    assert old and all(r() is not None for r in old)
    pipe.train_step(batch)
    assert pipe.apply_updates(optimizer, state, pipe.grads) is state
    gc.collect()
    assert [r() for r in old] == [None] * len(old)


@pytest.mark.parametrize("what", ["moments", "parameters"])
def test_an_alias_held_before_an_update_is_readable_after_it(
        model, batch, optimizer, devices8, what):
    """`benchmarks/runners/train_hostloss.py`'s `hold`: `jax.device_put` of
    an array to the device it lies on is a new Array over the same buffer,
    so an update that donated its operands would delete what is held here."""
    pipe = make_pipe(model, devices8, *ONE_STAGE)
    state = pipe.init_opt_state(optimizer)
    pipe.train_step(batch)
    state = pipe.apply_updates(optimizer, state, pipe.grads)

    def current():
        return jax.tree.leaves(state if what == "moments" else pipe.params)

    # Read from copies: on the CPU `np.asarray` of an array is a view that
    # keeps its buffer from being donated at all.
    before = leaves([jnp.copy(x) for x in current()])
    held = [jax.device_put(x, next(iter(x.devices()))) for x in current()]
    pipe.train_step(batch)
    pipe.apply_updates(optimizer, state, pipe.grads)
    for h, b in zip(held, before, strict=True):
        np.testing.assert_array_equal(np.asarray(h), b)
    assert any(not np.array_equal(a, b)
               for a, b in zip(leaves(current()), before))
