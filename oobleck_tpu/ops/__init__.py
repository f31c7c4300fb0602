"""TPU compute kernels: attention (XLA, Pallas flash, ring, Ulysses), the
routed experts and the layers' checkpoint policy. Heavy submodules import
lazily at their call sites; this surface re-exports the dispatching entry
points."""

from oobleck_tpu.ops.attention import causal_attention, select_attention_impl


def checkpoint_layer(fn, **kwargs):
    from oobleck_tpu.ops.remat import checkpoint_layer as wrap

    return wrap(fn, **kwargs)


def ring_attention(*args, **kwargs):
    from oobleck_tpu.ops.ring_attention import ring_attention as fn

    return fn(*args, **kwargs)


def ulysses_attention(*args, **kwargs):
    from oobleck_tpu.ops.ulysses import ulysses_attention as fn

    return fn(*args, **kwargs)


__all__ = ["causal_attention", "select_attention_impl", "checkpoint_layer",
           "ring_attention", "ulysses_attention"]
