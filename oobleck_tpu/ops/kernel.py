"""What TWO OR MORE Pallas kernel modules of this package need, once: the
decision between compiled Pallas, interpreted Pallas and `jax.numpy`
(`on_tpu`, `interpret`); what a call needs on this chip (`LANE`,
`SCOPED_VMEM`, `out_struct`); the call whose grid's last axis is walked in
order, and what the interpreter needs of it (`sequential_call`); the
gradient rule around a forward and a backward kernel (`kernel_vjp`); the
block helpers the recurrences share.

Not here: which shapes a kernel tiles (`_kernels_take`, `flash_ok`), a tile
choice (`choose_tiles`, `choose_row_tile`), a grid of one module's own
(flash's pair tables, the experts' plan-bounded grids, the page tables), a
counter of one family's own. No function here asks which family calls it.

Callers ask the decision THROUGH the module (`kernel.on_tpu()`, never
`from ... import on_tpu`), so that one patch steers every kernel module
(`tests/conftest.py`: `kernels_interpreted`, `as_on_tpu`).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.ad_checkpoint import checkpoint_name
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

LANE = 128
# What a kernel may hold of VMEM unless it says otherwise (16 MiB on a
# v5e). A kernel that keeps more than its step's blocks resident asks for
# this much beside it (`vmem_limit_bytes`).
SCOPED_VMEM = 16 << 20


def on_tpu() -> bool:
    """True when Pallas TPU kernels run compiled (i.e. the backend is TPU).

    Shared by the "auto" policies, the recurrences' and the experts' choice
    of path and the kernels' interpret toggles: off-TPU the kernels would
    run in interpreter mode — correct but slow — so auto selection falls
    back to XLA and explicit pallas requests flip `interpret=True` (CPU
    parity tests). One helper so the policy and the toggle can never
    disagree.

    That fallback is for processes with no TPU. One that can reach a TPU
    while its default backend is something else would run the interpreter
    or the XLA reference beside an idle chip, so there the question is an
    error, not False."""
    backend = jax.default_backend()
    if backend == "tpu":
        return True
    try:
        jax.devices("tpu")
    except RuntimeError:  # no TPU backend in this process
        return False
    raise RuntimeError(
        f"a TPU is visible but the default JAX backend is {backend!r}: "
        "refusing to pick interpret-mode Pallas or the XLA reference in "
        "its place (fix JAX_PLATFORMS, or ask for attention_impl 'xla')")


def interpret() -> bool:
    """A `pallas_call`'s `interpret=`: the kernels' arithmetic, on the CPU."""
    return not on_tpu()


def out_struct(shape, dtype, *operands) -> jax.ShapeDtypeStruct:
    """A `pallas_call` out_shape entry varying over every mesh axis any
    operand varies over: inside a `check_vma=True` shard_map (the fused
    step's three phases, the MPMD stage programs) Pallas refuses an output
    whose varying-manual-axes it would have to guess."""
    vma = frozenset().union(*(jax.typeof(x).vma for x in operands))
    return jax.ShapeDtypeStruct(shape, dtype, vma=vma)


def sequential_call(body, name: str, operands, in_specs, out_shape, out_specs,
                    *, grid, scratch, count, prefetch=(),
                    vmem_limit_bytes: int | None = None, **statics):
    """One `pallas_call` named `name` on `grid`, whose LAST axis is walked
    in order (a recurrence's chunks, flash's live pairs; the other axes are
    independent work): a step runs `body(z, *tables, *refs, **statics)`,
    `z` its index on that axis, `tables` the int32 `prefetch` in SMEM (which
    the blocks' index maps read too). `scratch`: the shapes of the float32
    VMEM scratches that live across the steps. `count()`: the family's
    counter of kernels built into traced programs.

    The interpreter evaluates a kernel's top level as plain operations of
    the enclosing program, and inside a `check_vma=True` shard_map those
    refuse a block (varying over the mesh) beside a constant (not varying);
    a branch's body is opaque to that check. So under the interpreter, and
    only there, the step runs inside a branch that is always taken."""
    interpreted = interpret()
    count()

    def step(*refs):
        z = pl.program_id(len(grid) - 1)
        work = functools.partial(body, z, *refs, **statics)
        if interpreted:
            pl.when(z >= 0)(work)
        else:
            work()

    return pl.pallas_call(
        step,
        out_shape=out_shape,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=len(prefetch), grid=grid,
            in_specs=in_specs, out_specs=out_specs,
            scratch_shapes=[pltpu.VMEM(s, jnp.float32) for s in scratch]),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel",) * (len(grid) - 1)
            + ("arbitrary",),
            vmem_limit_bytes=vmem_limit_bytes),
        interpret=interpreted,
        name=name,
    )(*prefetch, *operands)


def kernel_vjp(forward, backward, *, names, scope: str,
               nondiff_argnums: tuple[int, ...] = ()):
    """`forward`'s first output as a function whose gradient is `backward`'s.
    `forward(*args)` returns what the forward kernel wrote, the result
    first; `backward(*operands, *rest, cotangent, *static)` takes the
    differentiable operands, the rest of what the forward wrote and the
    arguments at `nondiff_argnums`.

    All that the kernel wrote goes by a name (`names`, one an output), so
    that a layer's checkpoint (`ops/remat.checkpoint_layer`) keeps it and
    the recomputed forward holds no kernel. The operands are not named:
    they come back from the layer's input by XLA. The backward rule is
    traced where the program is transposed, outside the caller's scope:
    under `jax.named_scope(scope)` again, the kernel is `%<scope>_bwd.N`
    and a reader of the scope finds the whole backward."""
    @functools.partial(jax.custom_vjp, nondiff_argnums=nondiff_argnums)
    def rule(*args):
        return forward(*args)[0]

    def rule_fwd(*args):
        result, *rest = (checkpoint_name(written, name) for written, name
                         in zip(forward(*args), names, strict=True))
        operands = [a for i, a in enumerate(args) if i not in nondiff_argnums]
        return result, (*operands, *rest)

    def rule_bwd(*args):
        *static, residuals, cotangent = args
        with jax.named_scope(scope):
            return backward(*residuals, cotangent, *static)

    rule.defvjp(rule_fwd, rule_bwd)

    def call(*args):
        # Inside a `check_vma=True` shard_map a parameter (Mamba's `A`, `D`)
        # varies over fewer mesh axes than the activations, and a
        # `custom_vjp` must hand each operand a gradient that varies as the
        # operand does. So every operand is cast to vary as the first does
        # HERE, outside the rule: the cast's own transpose is the sum over
        # those axes.
        from oobleck_tpu.parallel.collectives import pvary_to

        vma = tuple(jax.typeof(args[0]).vma)
        return rule(*(a if i in nondiff_argnums else pvary_to(a, vma)
                      for i, a in enumerate(args)))

    return call


# Blocks inside a body: `lax` alone (`ops/__init__.py` has the rule).

def nn(x, y):
    return lax.dot_general(x, y, (((1,), (0,)), ((), ())),
                           preferred_element_type=jnp.float32)


def nt(x, y):
    return lax.dot_general(x, y, (((1,), (1,)), ((), ())),
                           preferred_element_type=jnp.float32)


def tn(x, y):
    return lax.dot_general(x, y, (((0,), (0,)), ((), ())),
                           preferred_element_type=jnp.float32)


def lower(q: int):
    """The [q, q] mask of a chunk's positions i >= j."""
    return (lax.broadcasted_iota(jnp.int32, (q, q), 0)
            >= lax.broadcasted_iota(jnp.int32, (q, q), 1))


def decays(col_ref, row_ref, head: int, mask):
    """exp(cum_i - cum_j) of one head under `mask` (`lower`), [Q, Q]
    float32, from the DIFFERENCE of the running sums: handed in twice,
    positions along rows (`col_ref` [Q, heads]) and along lanes (`row_ref`)."""
    diff = col_ref[:, head:head + 1] - row_ref[head:head + 1, :]
    return jnp.exp(lax.select(mask, diff, jnp.full_like(diff, -jnp.inf)))


def running_sums(t, axis: int = -1, reverse: bool = False):
    """The running sum of a float32 array along `axis` (a chunk's
    positions), from the last position back if `reverse`: a product with
    the [Q, Q] triangle of ones at float32's own precision. (XLA's `cumsum`
    of such a shape is a `reduce_window` of 0.41 ms on a v5e, three a scan
    and more than both of `ops/ssd.py`'s kernels: my chip run, PR 54.)"""
    ones = lower(t.shape[axis]).astype(jnp.float32)
    after = "xyz"[:t.ndim - 1 - axis % t.ndim]
    return jnp.einsum(
        f"{'ji' if reverse else 'ij'},...j{after}->...i{after}", ones, t,
        precision=lax.Precision.HIGHEST)
