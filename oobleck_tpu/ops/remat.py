"""The layers' one checkpoint policy.

An op module whose forward rule emits a value that only more kernel or
product time could give back NAMES it (`checkpoint_name`) and declares the
names beside that rule, as `RESIDUAL_NAMES`. `KEPT` is the one place that
lists those modules; a further one is one more line of it.
"""

from __future__ import annotations

import jax

from oobleck_tpu.ops import flash, gdn, kda, sscan, ssd

KEPT = (
    *flash.RESIDUAL_NAMES,   # what the flash forward kernel wrote: O, LSE
    *gdn.RESIDUAL_NAMES,     # the delta rule's inverse, and what its forward
                             # kernel wrote: o, the state at every chunk's
                             # start
    *kda.RESIDUAL_NAMES,     # the Kimi delta rule's inverse
    *ssd.RESIDUAL_NAMES,     # what the scan's forward kernel wrote: y, the
                             # state at every chunk's start
    *sscan.RESIDUAL_NAMES,   # what the selective scan's forward kernel
                             # wrote: y, the state at every chunk's start
)


def checkpoint_layer(fn, **kwargs):
    """`jax.checkpoint` for a layer, whatever its body can reach: the
    backward pass recomputes the layer from its input, all but what the op
    modules' forward rules named (`KEPT`). A layer whose body emits no
    value by a name (attention on the XLA path, no delta rule) keeps
    nothing by it."""
    return jax.checkpoint(
        fn, policy=jax.checkpoint_policies.save_only_these_names(*KEPT),
        **kwargs)
