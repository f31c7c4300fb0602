"""The gated delta rule (Gated DeltaNet's recurrence), in chunks.

Per value head, with a scalar decay `exp(g_t)` and a scalar write strength
`beta_t` a position, a state `S` [dk, dv] in float32:

    S' = exp(g_t) S_{t-1}                               S_{-1} = 0
    u_t = beta_t (v_t - S'^T k_t)
    S_t = S' + k_t u_t^T
    o_t = S_t^T q_t

Every position READS the state it is about to write (`S'^T k_t`), which is
what `ops/ssd.py`'s recurrence does not do; inside a chunk of `Q` positions
that dependence is a unit lower-triangular system. With `cum` the running
sum of `g` INSIDE a chunk and `D_ij = exp(cum_i - cum_j)` for i >= j:

  system  A = strict_lower(diag(beta) (K K^T * D)),  T = (I + A)^-1 diag(beta)
          W = T (K * e^cum),  U = T V
  inter   a chunk that starts at state S:   V' = U - W S  (the chunk's u_t)
          S_next = e^{cum_Q} S + (K * e^{cum_Q - cum})^T V'
  out     O = (Q * e^cum) S + lower(Q K^T * D) V'

`(I + A)^-1`: `A` is strictly lower triangular, so `N = -A` is nilpotent
(`N^Q = 0`) and the inverse is the finite series `sum_n N^n`, taken as the
product `(I + N)(I + N^2)(I + N^4)...` of `log2 Q` factors: batched [Q, Q]
matrix products the compiler knows, and no triangular solve walked row by
row. The system and its inverse are XLA's on every backend: `A` is one
`einsum` and one fusion, differentiated by JAX; the inverse's gradient is
written down (`-X^T dX X^T`: two products a head and chunk where the
series' own would be eighteen) and its forward rule NAMES it
(`RESIDUAL_NAMES`), so that a layer's checkpoint
(`ops/remat.checkpoint_layer`) keeps it and the recomputed forward holds no
series.

What comes AFTER the inverse has two paths, and the backend decides between
them (`kernel.on_tpu`; a shape the kernels do not tile,
`_kernels_take`, is the other reason for the second):

  on a TPU   two Pallas kernels behind a `jax.custom_vjp` that takes q, k,
             v, the running sums, beta and the inverse `X`. `gdn_fwd` walks
             a (batch, key head)'s chunks in order on a grid of (batch, key
             head, chunk): a step holds the chunk's `q` and `k` [Q, dk], the
             key head's R value heads of `v` side by side [Q, R dv], `X`
             [R, Q, Q] float32 and the running sums and beta a position
             (positions along rows and along lanes, as `ops/ssd.py` hands
             its sums); makes `D`, `T`, `W`, `U`, `V'` a head and `Q K^T`
             once in VMEM; carries the R heads' state [dk, R dv] float32 in a
             VMEM scratch from chunk to chunk; writes `o` and the state at
             the chunk's START. `gdn_bwd` walks the same grid from the last
             chunk to the first with the state's gradient in the scratch,
             makes `D`, `W`, `U`, `V'` again, and writes dq, dk (summed over
             the R heads inside the step), dv, `dX` [R, Q, Q] float32 (the
             cotangent the inverse's own rule takes), and the sums a
             position and a chunk that the running sums' and beta's
             gradients are made of. `D`, `T`, `W`, `U`, `Q K^T` and `V'`
             never reach HBM, and no `while` walks the chunks. The running
             sums are `kernel.running_sums`, not `cumsum`. The rule
             (`kernel.kernel_vjp`) names what `gdn_fwd` wrote.
  elsewhere  plain `jax.numpy` (`_rule_xla`): batched `einsum`s and a
             `lax.scan` across the chunks, gradients by JAX's
             differentiation of them, `D`, `T` and `Q K^T` written out. The
             CPU's path, and what the kernels are tested against.

Both are held to what `ops/ssd.py` is held to: `g`, `cum`, every `exp`, the
inverse and the state in float32 (the inverse's products at
`Precision.HIGHEST`); `D` from the DIFFERENCE of running sums (never a
quotient of exponentials: a chunk that decays by e^-20 has no inf and no
nan in it, forward or backward), and `e^cum`, `e^{cum_Q - cum}` of
arguments that are never positive; the other products' operands in `v`'s
dtype with float32 accumulation, rounded at the same places on both paths
(`T * e^cum`, `T`, `W`, `U`, the state and `V'` where each enters a
product); a length that is no multiple of `Q` padded with `g = 0, beta = 0`
rows, which move no state, and cut off again; the `G` key heads read by
their `H / G` value heads through an index (an `einsum`'s, a block's),
never copied.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.ad_checkpoint import checkpoint_name
from jax.experimental import pallas as pl

from oobleck_tpu.ops import kernel
from oobleck_tpu.ops.kernel import LANE, decays, lower, nn, nt, out_struct, tn

# The forward rules' names for what only more product or kernel time could
# give back: the inverse (`decay`, `kk` and `a` come back by cheap XLA), and
# what `gdn_fwd` wrote, o and the state at every chunk's start.
RESIDUAL_NAMES = ("gdn_inverse", "gdn_out", "gdn_starts")


def _count(chunks: int, layer: str | None) -> None:
    """`oobleck_gdn_scans_total`: counted where the rule is built, once a
    call of every program traced (not once a step), and
    `oobleck_gdn_chunks{layer}`, the chunks a sequence of the last traced
    call."""
    from oobleck_tpu.utils import metrics

    reg = metrics.registry()
    reg.counter(
        "oobleck_gdn_scans_total",
        "Chunked gated delta rules built into traced programs").inc()
    reg.gauge(
        "oobleck_gdn_chunks",
        "Chunks a sequence of the LAST traced gated delta rule was cut "
        "into, by layer").set(chunks, layer=str(layer))


def _count_named_residuals() -> None:
    """`oobleck_gdn_residuals_named_total`: once a forward rule of the
    inverse traced (not once a step), with the inverse named."""
    from oobleck_tpu.utils import metrics

    metrics.registry().counter(
        "oobleck_gdn_residuals_named_total",
        "Forward rules of the delta rule's inverse traced with the inverse "
        "named for the layer's checkpoint").inc()


def _count_call(which: str) -> None:
    """`oobleck_gdn_kernel_calls_total{kernel}`: where a kernel is built
    into a traced program (not once a step). A rule on the `jax.numpy` path
    counts none."""
    from oobleck_tpu.utils import metrics

    metrics.registry().counter(
        "oobleck_gdn_kernel_calls_total",
        "Pallas kernels of the gated delta rule built into traced "
        "programs, by kernel (fwd, bwd)").inc(kernel=which)


def _dot(x: jax.Array, y: jax.Array) -> jax.Array:
    return jnp.matmul(x, y, precision=lax.Precision.HIGHEST)


@jax.custom_vjp
@jax.named_scope("gdn_inverse")
def unit_lower_inverse(a: jax.Array) -> jax.Array:
    """(I + a)^-1 for `a` [..., Q, Q] strictly lower triangular, float32:
    with n = -a, (I + n)(I + n^2)(I + n^4)... until the power is zero.
    Its gradient is the inverse's own, `-X^T dX X^T` with `X` the result
    (two products, where differentiating the 2 log2 Q products of the
    series costs twice as many again and keeps every power)."""
    q = a.shape[-1]
    power = -a
    inverse = jnp.eye(q, dtype=a.dtype) + power
    reach = 2                       # `inverse` holds the series below n^reach
    while reach < q:
        power = _dot(power, power)
        inverse = inverse + _dot(inverse, power)
        reach *= 2
    return inverse


def _inverse_fwd(a):
    inverse = checkpoint_name(unit_lower_inverse(a), RESIDUAL_NAMES[0])
    _count_named_residuals()
    return inverse, inverse


@jax.named_scope("gdn_inverse")
def _inverse_bwd(inverse, d_inverse):
    transposed = jnp.swapaxes(inverse, -1, -2)
    return (-_dot(_dot(transposed, d_inverse), transposed),)


unit_lower_inverse.defvjp(_inverse_fwd, _inverse_bwd)


def _kernels_take(chunk: int, r: int, dk: int, dv: int) -> bool:
    """The shapes the kernels tile: a head a lane tile (`dk = dv = 128`: the
    state of a key head's `r` value heads is [128, r 128]) and a chunk whose
    [Q, Q] float32 blocks are whole sublane tiles and at most one lane
    tile."""
    del r                           # any number of heads side by side
    return dk == LANE and dv == LANE and chunk % 8 == 0 and chunk <= LANE


@jax.named_scope("gdn")
def gated_delta_rule(q: jax.Array, k: jax.Array, v: jax.Array, g: jax.Array,
                     beta: jax.Array, *, chunk: int,
                     layer: str | None = None) -> jax.Array:
    """q, k [B, S, G, dk] (as the rule reads them: the caller normalises
    and scales); v [B, S, H, dv] with G dividing H (value head h reads key
    head h // (H / G)); g [B, S, H], the log of the decay, never positive;
    beta [B, S, H]. Returns o [B, S, H, dv] in v's dtype."""
    f32 = jnp.float32
    bsz, seq, heads, dv = v.shape
    groups, dk = k.shape[2], k.shape[3]
    assert heads % groups == 0, (heads, groups)
    r = heads // groups
    nc = -(-seq // chunk)
    _count(nc, layer)
    pad = nc * chunk - seq
    if pad:
        rows = lambda t: jnp.pad(t, ((0, 0), (0, pad)) + ((0, 0),) * (t.ndim - 2))
        q, k, v, g, beta = rows(q), rows(k), rows(v), rows(g), rows(beta)
    dtype = v.dtype
    q, k = q.astype(dtype), k.astype(dtype)
    kernels = kernel.on_tpu() and _kernels_take(chunk, r, dk, dv)
    # Heads before positions: the [Q, Q] blocks are the minor dimensions.
    per_head = lambda t: jnp.moveaxis(
        t.astype(f32).reshape(bsz, nc, chunk, groups, r), 2, -1)
    beta_h = per_head(beta)                                # [B, nc, G, R, Q]
    cum = (kernel.running_sums if kernels else
           functools.partial(jnp.cumsum, axis=-1))(per_head(g))
    i = jnp.arange(chunk)
    decay = jnp.exp(jnp.where(i[:, None] >= i[None, :],
                              cum[..., :, None] - cum[..., None, :],
                              -jnp.inf))                   # [B, nc, G, R, Q, Q]

    # system: every position's write against the writes before it.
    kg = k.reshape(bsz, nc, chunk, groups, dk)
    kk = jnp.einsum("bzigd,bzjgd->bzgij", kg, kg, preferred_element_type=f32)
    a = jnp.where(i[:, None] > i[None, :],
                  beta_h[..., :, None] * kk[:, :, :, None] * decay, 0.0)
    inverse = unit_lower_inverse(a)
    if kernels:
        return _rule_kernels(q, k, v, cum, beta_h, inverse)[:, :seq]
    return _rule_xla(q, k, v, cum, beta_h, inverse, decay)[:, :seq]


# --------------------------------------------------------------------- #
# off the chip: jax.numpy                                                #
# --------------------------------------------------------------------- #

def _rule_xla(q, k, v, cum, beta_h, inverse, decay):
    """Whole chunks; q and k in v's dtype; cum, beta_h [B, nc, G, R, Q] and
    inverse, decay [B, nc, G, R, Q, Q] float32."""
    f32 = jnp.float32
    dtype = v.dtype
    bsz, nc, groups, r, chunk = cum.shape
    dk, dv = k.shape[-1], v.shape[-1]
    qg = q.reshape(bsz, nc, chunk, groups, dk)
    kg = k.reshape(bsz, nc, chunk, groups, dk)
    vg = v.reshape(bsz, nc, chunk, groups, r, dv)
    per_row = lambda t: jnp.moveaxis(t, -1, 2)             # [B, nc, Q, G, R]
    total = cum[..., -1]                                   # [B, nc, G, R]
    from_start = jnp.exp(cum)           # the decay since the chunk's start
    t = inverse * beta_h[..., None, :]
    w = jnp.einsum("bzgrij,bzjgd->bzgrid",
                   (t * from_start[..., None, :]).astype(dtype), kg,
                   preferred_element_type=f32).astype(dtype)
    u = jnp.einsum("bzgrij,bzjgrd->bzgrid", t.astype(dtype), vg,
                   preferred_element_type=f32).astype(dtype)

    # inter: the state at every chunk's start, and the chunk's writes.
    to_end = jnp.exp(total[..., None] - cum)               # [B, nc, G, R, Q]

    def step(state, chunk_in):
        w_c, u_c, k_c, to_end_c, total_c = chunk_in
        wrote = u_c.astype(f32) - jnp.einsum(
            "bgrid,bgrdv->bgriv", w_c, state.astype(dtype),
            preferred_element_type=f32)
        after = jnp.exp(total_c)[..., None, None] * state + jnp.einsum(
            "bjgd,bgrjv->bgrdv", k_c,
            (wrote * to_end_c[..., None]).astype(dtype),
            preferred_element_type=f32)
        return after, (state, wrote.astype(dtype))

    by_chunk = lambda x: jnp.moveaxis(x, 1, 0)
    _, (starts, wrote) = lax.scan(
        step, jnp.zeros((bsz, groups, r, dk, dv), f32),
        tuple(by_chunk(x) for x in (w, u, kg, to_end, total)))
    starts = by_chunk(starts)                          # [B, nc, G, R, dk, dv]
    wrote = by_chunk(wrote)                            # [B, nc, G, R, Q, dv]

    # out: what the state at the chunk's start gives, and the chunk's own
    # writes up to and including the position's.
    qk = jnp.einsum("bzigd,bzjgd->bzgij", qg, kg, preferred_element_type=f32)
    o = jnp.einsum("bzgrij,bzgrjv->bzigrv",
                   (qk[:, :, :, None] * decay).astype(dtype), wrote,
                   preferred_element_type=f32)
    o = o + per_row(from_start)[..., None] * jnp.einsum(
        "bzigd,bzgrdv->bzigrv", qg, starts.astype(dtype),
        preferred_element_type=f32)
    return o.reshape(v.shape).astype(dtype)


# --------------------------------------------------------------------- #
# on the chip: two kernels                                               #
# --------------------------------------------------------------------- #
#
# A step's blocks, for batch row `i`, key head `g`, chunk `z` (R value heads
# of width dv a key head, side by side on R dv lanes):
#
#   q, k, dq, dk               [Q, dk]     of [B, S, G dk]
#   v, o, dO, dv               [Q, R dv]   of [B, S, H dv]
#   X, dX                      [R, Q, Q]   of [B, nc, G, R, Q, Q]   float32
#   cum, positions on rows     [Q, R]      of [B, nc, G, Q, R]      ("cols")
#   cum then beta, on lanes    [2 R, Q]    of [B, nc, G, 2 R, Q]    ("rows")
#   the state at z's start     [dk, R dv]  of [B, nc, G, dk, R dv]  float32
#
# Inside the bodies `lax.select`, never `jnp.where`, and no `//` or `%` in
# an index map: `ops/__init__.py` has the rule.

def _head(k_ref, v_ref, x_ref, col_ref, row_ref, start, h: int, *, r: int,
          dv: int):
    """What both kernels make of value head `h` of a chunk, in VMEM: its
    running sums' exponentials, `D`, `T`, `T * e^cum` (float32), `W`, `U`
    (v's dtype) and `V'` (float32), given the chunk-start state `start`
    [dk, R dv] in v's dtype."""
    f32 = jnp.float32
    dtype = v_ref.dtype
    qn = k_ref.shape[0]
    lanes = slice(h * dv, (h + 1) * dv)
    cum = col_ref[:, h:h + 1]                                   # [Q, 1]
    since_row = jnp.exp(row_ref[h:h + 1, :])                    # [1, Q]
    total = cum[qn - 1:qn, :]                                   # [1, 1]
    t = x_ref[h] * row_ref[r + h:r + h + 1, :]                  # X diag(beta)
    tw = t * since_row
    w = nn(tw.astype(dtype), k_ref[...]).astype(dtype)         # [Q, dk]
    u = nn(t.astype(dtype), v_ref[:, lanes]).astype(dtype)     # [Q, dv]
    wrote = u.astype(f32) - nn(w, start[:, lanes])             # V'
    return dict(lanes=lanes, since=jnp.exp(cum), to_end=jnp.exp(total - cum),
                whole=jnp.exp(jnp.broadcast_to(total, (1, dv))),
                since_row=since_row,
                decay=decays(col_ref, row_ref, h, lower(qn)),
                t=t, tw=tw, w=w, wrote=wrote)


def _fwd_kernel(z, q_ref, k_ref, v_ref, x_ref, col_ref, row_ref,
                o_ref, start_ref, state, *, r: int, dv: int):
    dtype = v_ref.dtype

    @pl.when(z == 0)
    def _():
        state[...] = jnp.zeros_like(state)

    start_ref[...] = state[...]
    qm, km = q_ref[...], k_ref[...]
    start = state[...].astype(dtype)
    qk = nt(qm, km)                                            # [Q, Q]
    o_off = nn(qm, start)                                      # Q S
    for h in range(r):
        c = _head(k_ref, v_ref, x_ref, col_ref, row_ref, start, h, r=r, dv=dv)
        lanes, wrote = c["lanes"], c["wrote"]
        o = nn((qk * c["decay"]).astype(dtype), wrote.astype(dtype))
        o_ref[:, lanes] = (o + c["since"] * o_off[:, lanes]).astype(o_ref.dtype)
        state[:, lanes] = c["whole"] * state[:, lanes] + tn(
            km, (wrote * c["to_end"]).astype(dtype))


def _bwd_kernel(z, q_ref, k_ref, v_ref, x_ref, col_ref, row_ref, start_ref,
                do_ref, dq_ref, dk_ref, dv_ref, dx_ref, dcol_ref, drow_ref,
                whole_ref, dstate, *, r: int, dv: int):
    """One chunk of the reverse walk: `dstate` comes in as the gradient of
    the state this chunk ENDS in and leaves as that of the state it starts
    from. Beside dq, dk, dv and dX it writes what the [B, S, H]-sized rest
    outside needs and no more: a position's part of the running sums'
    gradient, what reads the position's sum along rows (`dcol_ref`) and
    along lanes (`drow_ref`, then beta's gradient, which is `T`'s against
    `X` summed over rows), and the chunk total's gradient summed over the
    chunk's rows only (`whole_ref`: `e^total <S, dS_end>` and, from the
    very products the positions took theirs from, what they gave the
    state: the two cancel at the last position)."""
    f32 = jnp.float32
    dtype = v_ref.dtype
    qn = q_ref.shape[0]

    @pl.when(z == 0)
    def _():
        dstate[...] = jnp.zeros_like(dstate)

    qm, km = q_ref[...], k_ref[...]
    state = start_ref[...]
    start = state.astype(dtype)
    d_end = dstate[...]
    d_end_low = d_end.astype(dtype)
    qk = nt(qm, km)
    o_off = nn(qm, start)                                      # Q S
    d_to_state = nn(km, d_end_low)                             # K dS_end
    dqk = jnp.zeros((qn, qn), f32)
    dq = jnp.zeros(dq_ref.shape, f32)
    dk = jnp.zeros(dk_ref.shape, f32)
    for h in range(r):
        c = _head(k_ref, v_ref, x_ref, col_ref, row_ref, start, h, r=r, dv=dv)
        lanes, wrote, decay = c["lanes"], c["wrote"], c["decay"]
        since, to_end, since_row = c["since"], c["to_end"], c["since_row"]
        do = do_ref[:, lanes]
        dof = do.astype(f32)
        # out: O = (Q K^T * D) V' + e^cum * (Q S)
        p = qk * decay
        dp = nt(do, wrote.astype(dtype))                       # [Q, Q]
        do_since = (dof * since).astype(dtype)
        dq = dq + nt(do_since, start[:, lanes])
        dqk = dqk + dp * decay
        # inter: S_end = e^total S + K^T (V' * e^{total - cum})
        added = to_end * d_to_state[:, lanes]
        dk = dk + nt((wrote * to_end).astype(dtype), d_end_low[:, lanes])
        d_wrote = (tn(p.astype(dtype), do) + added).astype(dtype)
        # V' = U - W S;  U = T V;  W = (T * e^cum) K
        dw = (-nt(d_wrote, start[:, lanes])).astype(dtype)     # [Q, dk]
        dtw = nt(dw, km)                                       # [Q, Q]
        dt = nt(d_wrote, v_ref[:, lanes]) + dtw * since_row
        dv_ref[:, lanes] = tn(c["t"].astype(dtype), d_wrote).astype(
            dv_ref.dtype)
        dk = dk + tn(c["tw"].astype(dtype), dw)
        dx_ref[h] = dt * row_ref[r + h:r + h + 1, :]
        dstate[:, lanes] = (c["whole"] * d_end[:, lanes]
                            + tn(qm, do_since) - tn(c["w"], d_wrote))
        # the running sums' gradient and beta's
        of_decay = dp * p
        dcol_ref[:, h:h + 1] = (
            jnp.sum(of_decay, axis=1, keepdims=True)
            + jnp.sum(dof * (since * o_off[:, lanes]) - added * wrote,
                      axis=1, keepdims=True))
        drow_ref[h:h + 1, :] = jnp.sum(dtw * c["tw"] - of_decay, axis=0,
                                       keepdims=True)
        drow_ref[r + h:r + h + 1, :] = jnp.sum(dt * x_ref[h], axis=0,
                                               keepdims=True)
        whole_ref[:, lanes] = (
            c["whole"] * jnp.sum(state[:, lanes] * d_end[:, lanes], axis=0,
                                 keepdims=True)
            + jnp.sum(wrote * added, axis=0, keepdims=True))
    dqk = dqk.astype(dtype)
    dq_ref[...] = (dq + nn(dqk, km)).astype(dq_ref.dtype)
    dk_ref[...] = (dk + tn(dqk, qm)).astype(dk_ref.dtype)


def _operands(q, k, v, cum, beta_h, inverse, reverse: bool):
    """What both kernels read, with its block specs. Returns (operands,
    in_specs, narrow, wide, of_chunk), the last three the specs of a
    [Q, dk] block, a [Q, R dv] block and a block a (batch, chunk, key
    head). `reverse` walks the chunks from the last to the first."""
    bsz, nc, groups, r, chunk = cum.shape
    dk, dv = k.shape[-1], v.shape[-1]
    cols = jnp.swapaxes(cum, -1, -2)                       # [B, nc, G, Q, R]
    rows = jnp.concatenate([cum, beta_h], axis=-2)         # [B, nc, G, 2R, Q]
    flat = lambda t: t.reshape(bsz, nc * chunk, -1)

    at = (lambda z: nc - 1 - z) if reverse else (lambda z: z)
    in_chunk = lambda i, g, z: (i, at(z), g)
    narrow = pl.BlockSpec((None, chunk, dk), in_chunk)
    wide = pl.BlockSpec((None, chunk, r * dv), in_chunk)
    of_chunk = lambda *block: pl.BlockSpec(
        (None, None, None, *block),
        lambda i, g, z: (*in_chunk(i, g, z), *(0,) * len(block)))
    return ((flat(q), flat(k), flat(v), inverse, cols, rows),
            [narrow, narrow, wide, of_chunk(r, chunk, chunk),
             of_chunk(chunk, r), of_chunk(2 * r, chunk)],
            narrow, wide, of_chunk)


def _forward(q, k, v, cum, beta_h, inverse):
    bsz, nc, groups, r, chunk = cum.shape
    dk, dv = k.shape[-1], v.shape[-1]
    operands, in_specs, _, wide, of_chunk = _operands(
        q, k, v, cum, beta_h, inverse, reverse=False)
    o, starts = kernel.sequential_call(
        _fwd_kernel, "gdn_fwd", operands, in_specs,
        (out_struct(operands[2].shape, v.dtype, *operands),
         out_struct((bsz, nc, groups, dk, r * dv), jnp.float32, *operands)),
        (wide, of_chunk(dk, r * dv)), grid=(bsz, groups, nc),
        scratch=[(dk, r * dv)],
        count=functools.partial(_count_call, "fwd"), r=r, dv=dv)
    return o.reshape(v.shape), starts


def _backward(q, k, v, cum, beta_h, inverse, starts, do):
    f32 = jnp.float32
    bsz, nc, groups, r, chunk = cum.shape
    dk, dv = k.shape[-1], v.shape[-1]
    operands, in_specs, narrow, wide, of_chunk = _operands(
        q, k, v, cum, beta_h, inverse, reverse=True)
    qf, kf, vf = operands[:3]
    operands = (*operands, starts, do.astype(v.dtype).reshape(vf.shape))
    dq, dk_, dv_, dx, dcol, drow, whole = kernel.sequential_call(
        _bwd_kernel, "gdn_bwd", operands,
        [*in_specs, of_chunk(dk, r * dv), wide],
        (out_struct(qf.shape, q.dtype, *operands),
         out_struct(kf.shape, k.dtype, *operands),
         out_struct(vf.shape, v.dtype, *operands),
         out_struct(inverse.shape, f32, *operands),
         out_struct((bsz, nc, groups, chunk, r), f32, *operands),
         out_struct((bsz, nc, groups, 2 * r, chunk), f32, *operands),
         out_struct((bsz, nc, groups, 1, r * dv), f32, *operands)),
        (narrow, narrow, wide, of_chunk(r, chunk, chunk), of_chunk(chunk, r),
         of_chunk(2 * r, chunk), of_chunk(1, r * dv)),
        grid=(bsz, groups, nc), scratch=[(dk, r * dv)],
        count=functools.partial(_count_call, "bwd"), r=r, dv=dv)

    # The [B, S, H]-sized rest. A chunk's total is its last running sum.
    whole = jnp.sum(whole.reshape(bsz, nc, groups, r, dv), axis=-1)
    dcum = (jnp.swapaxes(dcol, -1, -2) + drow[..., :r, :]
            ).at[..., -1].add(whole)
    return (dq.reshape(q.shape), dk_.reshape(k.shape), dv_.reshape(v.shape),
            dcum, drow[..., r:, :], dx)


# Whole chunks; q and k in v's dtype; cum, beta_h [B, nc, G, R, Q] and
# inverse [B, nc, G, R, Q, Q] float32. The inverse is kept by its own name.
_rule_kernels = kernel.kernel_vjp(
    _forward, _backward, names=RESIDUAL_NAMES[1:], scope="gdn")
