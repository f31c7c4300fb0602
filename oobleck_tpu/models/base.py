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


def stack_layer_params(layer_params: list[PyTree]) -> PyTree:
    """Stack homogeneous per-layer pytrees along a new leading axis."""
    return jax.tree.map(lambda *xs: jnp.stack(xs, axis=0), *layer_params)


def unstack_layer_params(stacked: PyTree) -> list[PyTree]:
    """Inverse of stack_layer_params."""
    num = jax.tree.leaves(stacked)[0].shape[0]
    return [jax.tree.map(lambda x, i=i: x[i], stacked) for i in range(num)]


def param_count(params: PyTree) -> int:
    return sum(x.size for x in jax.tree.leaves(params))


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
