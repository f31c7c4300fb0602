"""TPU compute kernels: attention (`attention.py`: the XLA reference and the
dispatch; `flash.py`: the Pallas flash kernels and their latent, windowed
and differential forms; `ring_attention.py`; `ulysses.py`;
`paged_attention.py`: paged decode and verify), the routed experts
(`moe.py`), the recurrences (`ssd.py`, `gdn.py`, `sscan.py`: two kernels
each; `kda.py`: `jax.numpy` alone so far), the layers' checkpoint policy (`remat.py`) and what two or
more kernel modules need (`kernel.py`). Heavy submodules import lazily at
their call sites; this surface re-exports the dispatching entry points.

The rule for a kernel's body and its blocks' index maps: no `jnp.where`,
`//`, `%`, `jnp.sum` or `jnp.dot` on a traced value there (`lax.select`,
`lax.div`, `lax.rem`, `lax.dot_general`; a grid axis for what a map would
divide by). Those are jitted helpers whose cached jaxpr carries the source
location of its FIRST trace in the process into the kernel's serialized
body; the compile cache's key follows, and a warm start that reaches that
trace by another call stack misses what the cold one wrote (+ 54 s of
`setup_s` in PR 52, 88-93 s against 43-51 in PR 54).
`tests/ops/test_kernel_bodies.py` holds every `pallas_call` name to it and
lists the bodies that still hold one."""

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
