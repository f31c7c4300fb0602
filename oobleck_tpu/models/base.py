"""The model contract: an explicit layer list.

The reference obtains per-layer granularity by fx-tracing an HF model and
splitting the graph at per-architecture module boundaries
(/root/reference/oobleck/module/sharding.py:110-196,
/root/reference/oobleck/module/model.py:71-83). On TPU there is nothing to
trace: models are *defined* as a list of layers — layer 0 embeds, layers
1..N are transformer blocks, layer N+1 is the norm+head. That list is the unit
of planning (per-layer profile costs), pipeline splitting (stage = contiguous
layer range), and elastic state copy (per-layer weight broadcast).

Two views of the same parameters:

  - per-layer list (`init_layer` / `apply_layer`): used by the profiler and
    the MPMD pipeline interpreter, where each stage owns a contiguous slice.
  - fused/stacked (`init_params` / `loss`): blocks stacked on a leading
    [num_blocks, ...] axis so the SPMD pipeline can shard them over the
    `stage` mesh axis and scan over them; used by the fast path.

`stack_layer_params` / `unstack_layer_params` convert between them.

WHAT REPEATS. A model may say that a contiguous range of its layers is gone
through several times a microbatch over ONE set of parameters
(`repeated_layers`, a `range` of layer indices, and `num_passes`; a model
that says neither repeats nothing, which is every family but
`models/ouro.py`). The list stays the unit of everything above: a layer's
parameters are held once, a stage is still a contiguous range, a recovery
still re-cuts the list. What changes is the WALK (`layer_walk`): the layers
in front once, the repeated range `num_passes` times, the layers behind
once. The carry that leaves the range's last layer is the carry its first
layer takes, so a model that repeats gives its carry ONE tree and ONE set
of shapes from the range's first layer to its last; a parameter's gradient
is the sum over its uses. The pipeline derives its visits from these two
attributes (`execution/pipeline.py`), the profiler charges a repeated
layer's time and saved activations `num_passes` times and its parameters
once (`planning/profiler.py`), and the FLOP estimate counts applied
parameters (`applied_param_count`). No execution argument says any of it.
"""

from __future__ import annotations

from typing import Any, Protocol

import jax
import jax.numpy as jnp

PyTree = Any


class LayerListModel(Protocol):
    """Uniform duck-typed interface every model family implements."""

    @property
    def num_pipeline_layers(self) -> int: ...

    def layer_name(self, index: int) -> str: ...

    def init_layer(self, rng: jax.Array, index: int) -> PyTree: ...

    def apply_layer(
        self, index: int, params: PyTree, carry: PyTree, batch: dict[str, jax.Array]
    ) -> PyTree: ...

    def loss_from_logits(
        self, logits: jax.Array, batch: dict[str, jax.Array]
    ) -> jax.Array: ...

    def sample_batch(self, batch_size: int, seq_len: int) -> dict[str, jax.Array]: ...

    # Optional: the layers a microbatch goes through `num_passes` times
    # (module docstring). Read through `repeated`, never directly.
    repeated_layers: range
    num_passes: int


def repeated(model) -> tuple[range, int]:
    """(the layers `model` repeats, how often a microbatch goes through
    them): (`range(0)`, 1) for a model that repeats nothing."""
    layers = getattr(model, "repeated_layers", None)
    passes = int(getattr(model, "num_passes", 1))
    if not layers or passes <= 1:
        return range(0), 1
    return layers, passes


def passes_of(model, layer: int) -> int:
    """How often a microbatch goes through `layer`."""
    layers, passes = repeated(model)
    return passes if layer in layers else 1


def layer_walk(model, layers=None) -> tuple[int, ...]:
    """The layer applications of one microbatch over `layers` (default: the
    whole list), in order: the repeated range `num_passes` times where
    `layers` holds ALL of it, every layer once otherwise (a part of the
    range is one visit's share; who holds it schedules the visits). Who
    runs a walk that repeats may run the repeats as the trips of one loop
    over the range (`execution/pipeline.py` does, a `lax.scan`): the carry
    into the range's first layer and out of its last is one tree of one
    set of shapes, which is all a loop's carry has to be."""
    layers = tuple(range(model.num_pipeline_layers)
                   if layers is None else layers)
    rep, passes = repeated(model)
    if passes == 1 or not set(rep) <= set(layers):
        return layers
    first, last = layers.index(rep[0]), layers.index(rep[-1])
    return (layers[:first] + layers[first:last + 1] * passes
            + layers[last + 1:])


def stack_layer_params(layer_params: list[PyTree]) -> PyTree:
    """Stack homogeneous per-layer pytrees along a new leading axis."""
    return jax.tree.map(lambda *xs: jnp.stack(xs, axis=0), *layer_params)


def unstack_layer_params(stacked: PyTree) -> list[PyTree]:
    """Inverse of stack_layer_params."""
    num = jax.tree.leaves(stacked)[0].shape[0]
    return [jax.tree.map(lambda x, i=i: x[i], stacked) for i in range(num)]


def param_count(params: PyTree) -> int:
    return sum(x.size for x in jax.tree.leaves(params))


def applied_param_count(model) -> int:
    """Parameters a token is multiplied through, each counted once a use:
    a repeated layer's `num_passes` times. A model whose applications are
    not its parameters' sizes times its passes (several exits through one
    head) says its own count (`applied_params`). For a model that repeats
    nothing this is its parameter count."""
    own = getattr(model, "applied_params", None)
    if own is not None:
        return int(own())
    rng = jax.random.PRNGKey(0)
    return sum(
        passes_of(model, li) * param_count(
            jax.eval_shape(lambda r, _li=li: model.init_layer(r, _li), rng))
        for li in range(model.num_pipeline_layers))


def param_bytes(params: PyTree) -> int:
    return sum(x.size * x.dtype.itemsize for x in jax.tree.leaves(params))


def argmax_accuracy(logits, labels):
    """Shared task metric for evaluate() (the reference builds an accuracy
    metric via `evaluate` but never reports it, dataset.py:39-54): returns
    (correct_count, total_count) for argmax-vs-labels families
    (classification, seq2seq token accuracy)."""
    import jax.numpy as jnp

    pred = jnp.argmax(logits, axis=-1)
    correct = (pred == labels).astype(jnp.float32)
    return jnp.sum(correct), jnp.float32(correct.size)
