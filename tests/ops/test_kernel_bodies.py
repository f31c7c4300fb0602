"""No kernel's body, and no block's index map, calls a jitted helper:
`oobleck_tpu/ops/__init__.py` has the rule and why (the first trace's source
location in the kernel's serialized body, the compile cache's key, a warm
start that compiles the stage again: + 54 s of `setup_s`, my chip run, PR
52; 88.3 and 92.6 s against 43.3 and 51.3, my chip runs, PR 54). A helper
jitted with `inline=True` (`jnp.exp`, `*`) is traced anew each time and
leaves no equation of its own.

One case a `pallas_call` name the package builds (the latent and the
differential names run the plain flash bodies). Traced as on a TPU, so the
body walked is the one the chip's compiler is given; nothing runs.
"""

import functools

import jax
import jax.numpy as jnp
import pytest

from oobleck_tpu.ops import flash, gdn, moe, paged_attention, sscan, ssd
from tests.ops.programs import all_eqns, kernel_calls

CALLS = {"jit", "pjit", "closed_call", "core_call"}

# name -> the jitted helpers its body holds today, by their own names: the
# mask's `jnp.where` in `flash._scores`, `_paged_kernel` and
# `_paged_verify_kernel`, and the `// g` of the last's row lengths. A PR
# that clears a body takes its line out (ROADMAP.md D30; S2 (i) rewrites
# flash's backward); none may add to it.
KNOWN = {
    "flash_fwd": {"_where"},
    "flash_bwd_dqkv": {"_where"},
    "flash_swa_fwd": {"_where"},
    "flash_swa_bwd_dqkv": {"_where"},
    "paged_decode": {"_where"},
    "paged_verify": {"_where", "floor_divide"},
}

f32 = lambda *shape: jax.ShapeDtypeStruct(shape, jnp.float32)
i32 = lambda *shape: jax.ShapeDtypeStruct(shape, jnp.int32)
total = lambda fn: lambda *a: jnp.sum(fn(*a))


def _flash(window):
    attend = lambda q, k, v: flash.flash_attention(q, k, v, window=window)
    return jax.grad(total(attend), argnums=(0, 1, 2)), [f32(1, 2, 256, 64)] * 3


def _experts():
    """Eight experts of which four are held, SwiGLU: three `moe_gmm`
    forward, dX and dW (`moe_tgmm`) backward, and both sums of rows into
    tokens."""
    call = lambda x, router, w1, w3, w2: moe.routed_experts(
        x, router, None, w1, w3, w2, num_experts=8, top_k=2, expert_offset=2)
    return (jax.grad(total(call), argnums=(0, 2, 3, 4)),
            [f32(48, 32), f32(32, 8), f32(4, 32, 64), f32(4, 32, 64),
             f32(4, 64, 32)])


def _paged(fn, *q):
    call = functools.partial(fn, impl="pallas")
    return call, [f32(*q), f32(8, 2, 16, 64), f32(8, 2, 16, 64), i32(2, 3),
                  i32(2)]


# what is traced -> the kernels it must hold
TRACES = {
    lambda: (jax.grad(total(functools.partial(ssd.ssd_scan, chunk=128)),
                      argnums=tuple(range(6))),
             [f32(1, 256, 4, 64), f32(1, 256, 4), f32(4), f32(1, 256, 2, 128),
              f32(1, 256, 2, 128), f32(4)]): ("ssd_fwd", "ssd_bwd"),
    lambda: (jax.grad(total(functools.partial(gdn.gated_delta_rule, chunk=64)),
                      argnums=tuple(range(5))),
             [f32(1, 128, 1, 128), f32(1, 128, 1, 128), f32(1, 128, 2, 128),
              f32(1, 128, 2), f32(1, 128, 2)]): ("gdn_fwd", "gdn_bwd"),
    lambda: (jax.grad(total(functools.partial(sscan.selective_scan, chunk=16)),
                      argnums=tuple(range(6))),
             [f32(1, 48, 256), f32(1, 48, 256), f32(256, 16), f32(1, 48, 16),
              f32(1, 48, 16), f32(256)]): ("sscan_fwd", "sscan_bwd"),
    _experts: ("moe_gmm", "moe_tgmm", "moe_token_sum"),
    lambda: _flash(None): flash.PLAIN,
    lambda: _flash(128): flash.WINDOW,
    lambda: _paged(paged_attention.paged_decode_attention, 2, 4, 64): (
        "paged_decode",),
    lambda: _paged(paged_attention.paged_verify_attention, 2, 3, 4, 64): (
        "paged_verify",),
}
TRACE_OF = {name: trace for trace, names in TRACES.items() for name in names}


@functools.cache
def _kernels(trace):
    """name -> its `pallas_call` equations in the traced program."""
    fn, args = trace()
    found = {}
    for e in kernel_calls(fn, *args):
        found.setdefault(e.params["name"], []).append(e)
    return found


@pytest.mark.parametrize("name", sorted(TRACE_OF))
def test_the_kernel_s_body_and_index_maps_call_no_jitted_helper(as_on_tpu,
                                                                name):
    trace = TRACE_OF[name]
    kernels = _kernels(trace)
    assert sorted(kernels) == sorted(TRACES[trace])
    held = set()
    for call in kernels[name]:
        assert not call.params["interpret"]
        mapping = call.params["grid_mapping"]
        maps = [m.index_map_jaxpr.jaxpr for m in mapping.block_mappings]
        # Every operand but the grid's own bound and the prefetched tables,
        # and every result.
        assert len(maps) == len(call.invars) + len(call.outvars) - (
            mapping.num_dynamic_grid_bounds + mapping.num_index_operands)
        for jaxpr in (call.params["jaxpr"], *maps):
            held |= {e.params.get("name", e.primitive.name)
                     for e in all_eqns(jaxpr) if e.primitive.name in CALLS}
    assert held == KNOWN.get(name, set()), (name, sorted(held))


def test_every_name_the_package_builds_has_a_case():
    assert sorted(TRACE_OF) == sorted([
        "ssd_fwd", "ssd_bwd", "gdn_fwd", "gdn_bwd", "sscan_fwd", "sscan_bwd",
        "moe_gmm", "moe_tgmm", "moe_token_sum", "flash_fwd", "flash_bwd_dqkv",
        "flash_swa_fwd", "flash_swa_bwd_dqkv", "paged_decode",
        "paged_verify"])
    assert set(KNOWN) <= set(TRACE_OF)
