"""The Mamba-2 recurrence (state-space duality), in chunks.

Per head, with a scalar decay a position, a state `H` [P, N] in float32:

    H_t = exp(dt_t A) H_{t-1} + dt_t x_t (x) B_t        H_{-1} = 0
    y_t = H_t C_t + D x_t

The sequence is not walked step by step. With chunks of `Q` positions,
`a_t = dt_t A` and `cum` the running sum of `a` INSIDE a chunk:

  intra  inside a chunk      Y_diag = ((C B^T) * L)(dt * x),
                             L_ij = exp(cum_i - cum_j) for i >= j, else 0
  state  a chunk's own part  S_c = ((exp(cum_Q - cum) * dt) * x)^T B
  inter  across the chunks   H_{c+1} = exp(cum_Q of chunk c) H_c + S_c
         and back in         Y_off = exp(cum) * (C H_c^T)

and `y = Y_diag + Y_off + D x`.

Two paths, and the backend decides between them (`kernel.on_tpu`; a
shape the kernels do not tile, `_kernels_take`, is the other reason for
the second):

  on a TPU   two Pallas kernels behind a `jax.custom_vjp`. `ssd_fwd` walks a
             (batch, group)'s chunks in order on a grid of (batch, group,
             chunk): a step holds the chunk's `x` as [Q, R P] (the group's
             R heads side by side, the array's own layout), `B` and `C` as
             [Q, N]; makes `dt * x`, `C B^T` once a group and `L`, `M`,
             `M x~` a head in VMEM; carries the state of the R heads,
             transposed ([N, R P] float32), in a VMEM scratch from chunk
             to chunk; writes `y` and the state at the chunk's START.
             `ssd_bwd` walks the same grid from the last chunk to the
             first with the state's gradient in the scratch, makes `C B^T`,
             `L` and `M` again, and writes dx, dB, dC (summed over the
             group's heads inside the step) and the few sums a position
             and a chunk the running sums' gradient is made from. `L`, `M`
             and `C B^T` never reach HBM. What is [B, S, H]-sized stays XLA's, outside: the
             running sums going in (a product with a triangle of ones, not
             `cumsum`; handed in twice, positions along rows and positions
             along lanes, as flash hands its LSE), and coming back the
             running sums' gradient, which needs no [Q, Q] block:
             dcum_j = sum_p (dY y - D dY x - dx~ x~)_jp, plus, at a chunk's
             last position, the chunk total's (the kernel sums it from the
             products its positions read: the two cancel). The rule
             (`kernel.kernel_vjp`) names what `ssd_fwd` wrote:
             `RESIDUAL_NAMES`.
  elsewhere  plain `jax.numpy` (`_scan_xla`): batched `einsum`s, gradients
             by JAX's differentiation of them, `L` written out. The CPU's
             path, and what the kernels are tested against.

Both are held to: `a`, `cum`, every `exp` and the state in float32; `L` from
the DIFFERENCE of running sums (never a quotient of exponentials: a chunk
that decays by e^-20 has no inf and no nan in it, forward or backward); the
products' operands in `x`'s dtype with float32 accumulation; a length that
is no multiple of `Q` padded with `dt = 0` rows, which move no state, and
cut off again; the `G` groups of `B` and `C` read by their heads through an
index (an `einsum`'s, a block's), never copied `H / G` times.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl

from oobleck_tpu.ops import kernel
from oobleck_tpu.ops.kernel import LANE, decays, lower, nn, nt, out_struct, tn

# The forward rule's names for what `ssd_fwd` wrote, y and the state at
# every chunk's start: what only a second kernel call could give back.
RESIDUAL_NAMES = ("ssd_out", "ssd_starts")
# The longest chunk the kernels hold: a head's [Q, Q] float32 blocks (L, M,
# dM) are 64 vregs each at 256.
MAX_CHUNK = 256


def _count(chunks: int, layer: str | None) -> None:
    """`oobleck_ssd_scans_total`: counted where the scan is built, once a
    scan of every program traced (not once a step; its three parts, intra,
    state and inter, are built together and are not told apart), and
    `oobleck_ssd_chunks{layer}`, the chunks a sequence of the last traced
    call."""
    from oobleck_tpu.utils import metrics

    reg = metrics.registry()
    reg.counter(
        "oobleck_ssd_scans_total",
        "Chunked state-space scans built into traced programs").inc()
    reg.gauge(
        "oobleck_ssd_chunks",
        "Chunks a sequence of the LAST traced state-space scan was cut "
        "into, by layer").set(chunks, layer=str(layer))


def _count_call(which: str) -> None:
    """`oobleck_ssd_kernel_calls_total{kernel}`: where a kernel is built
    into a traced program (not once a step). A scan on the `jax.numpy` path
    counts none."""
    from oobleck_tpu.utils import metrics

    metrics.registry().counter(
        "oobleck_ssd_kernel_calls_total",
        "Pallas kernels of the state-space scan built into traced "
        "programs, by kernel (fwd, bwd)").inc(kernel=which)


def _kernels_take(chunk: int, r: int, p: int, n: int) -> bool:
    """The shapes the kernels tile: whole [128, 128] blocks of `L`, a state
    width that fills lanes, and heads that fill lanes when the group's `r`
    heads of width `p` lie side by side (two of 64 a lane tile)."""
    return (chunk % LANE == 0 and chunk <= MAX_CHUNK and n % LANE == 0
            and LANE % p == 0 and (r * p) % LANE == 0)


@jax.named_scope("ssd")
def ssd_scan(x: jax.Array, dt: jax.Array, a_neg: jax.Array, b: jax.Array,
             c: jax.Array, d_skip: jax.Array, *, chunk: int,
             layer: str | None = None) -> jax.Array:
    """x [B, S, H, P]; dt [B, S, H] (after its softplus); a_neg [H] (A, a
    negative scalar a head); b, c [B, S, G, N] with G dividing H (head h
    reads group h // (H / G)); d_skip [H]. Returns y [B, S, H, P] in x's
    dtype."""
    seq, heads, p = x.shape[1:]
    groups, n = b.shape[2], b.shape[3]
    assert heads % groups == 0, (heads, groups)
    nc = -(-seq // chunk)
    _count(nc, layer)
    pad = nc * chunk - seq
    if pad:
        rows = lambda t: jnp.pad(t, ((0, 0), (0, pad)) + ((0, 0),) * (t.ndim - 2))
        x, dt, b, c = rows(x), rows(dt), rows(b), rows(c)
    dt = dt.astype(jnp.float32)
    if kernel.on_tpu() and _kernels_take(chunk, heads // groups, p, n):
        return _scan_kernels(x, dt, a_neg, b, c, d_skip, chunk)[:, :seq]
    return _scan_xla(x, dt, a_neg, b, c, d_skip, chunk)[:, :seq]


# --------------------------------------------------------------------- #
# off the chip: jax.numpy                                                #
# --------------------------------------------------------------------- #

def _scan_xla(x, dt, a_neg, b, c, d_skip, chunk: int):
    """Whole chunks, `dt` in float32."""
    f32 = jnp.float32
    bsz, seq, heads, p = x.shape
    groups, n = b.shape[2], b.shape[3]
    r, nc = heads // groups, seq // chunk
    dtype = x.dtype
    xg = x.reshape(bsz, nc, chunk, groups, r, p)
    bg = b.reshape(bsz, nc, chunk, groups, n)
    cg = c.reshape(bsz, nc, chunk, groups, n)
    dtg = dt.reshape(bsz, nc, chunk, groups, r)
    # Heads before positions: the [Q, Q] blocks are the minor dimensions.
    per_head = lambda t: jnp.moveaxis(t, 2, -1)            # [B, nc, G, R, Q]
    per_row = lambda t: jnp.moveaxis(t, -1, 2)             # [B, nc, Q, G, R]
    cum = jnp.cumsum(
        per_head(dtg) * a_neg.astype(f32).reshape(groups, r, 1), axis=-1)
    total = cum[..., -1]                                   # [B, nc, G, R]
    x_dt = (xg.astype(f32) * dtg[..., None]).astype(dtype)

    # intra: the chunk's own positions, through L.
    i = jnp.arange(chunk)
    decay = jnp.exp(jnp.where(i[:, None] >= i[None, :],
                              cum[..., :, None] - cum[..., None, :],
                              -jnp.inf))                   # [B, nc, G, R, Q, Q]
    cb = jnp.einsum("bzign,bzjgn->bzgij", cg, bg, preferred_element_type=f32)
    y = jnp.einsum("bzgrij,bzjgrp->bzigrp",
                   (cb[:, :, :, None] * decay).astype(dtype), x_dt,
                   preferred_element_type=f32)

    # state: what a chunk adds to the state, decayed to the chunk's end.
    to_end = per_row(jnp.exp(total[..., None] - cum))      # [B, nc, Q, G, R]
    added = jnp.einsum(
        "bzjgrp,bzjgn->bzgrpn",
        (xg.astype(f32) * (to_end * dtg)[..., None]).astype(dtype), bg,
        preferred_element_type=f32)

    # inter: the state at every chunk's start, then its part of y.
    def step(state, chunk_in):
        decay_c, added_c = chunk_in
        return decay_c[..., None, None] * state + added_c, state

    _, starts = lax.scan(
        step, jnp.zeros((bsz, groups, r, p, n), f32),
        (jnp.moveaxis(jnp.exp(total), 1, 0), jnp.moveaxis(added, 1, 0)))
    starts = jnp.moveaxis(starts, 0, 1)                    # [B, nc, G, R, P, N]
    y = y + per_row(jnp.exp(cum))[..., None] * jnp.einsum(
        "bzign,bzgrpn->bzigrp", cg, starts.astype(dtype),
        preferred_element_type=f32)

    y = y + xg.astype(f32) * d_skip.astype(f32).reshape(groups, r, 1)
    return y.reshape(bsz, seq, heads, p).astype(dtype)


# --------------------------------------------------------------------- #
# on the chip: two kernels                                               #
# --------------------------------------------------------------------- #
#
# A step's blocks, for batch row `i`, group `g`, chunk `z` (R heads of width
# P a group, W = R P lanes):
#
#   x, x~ = dt x, y, dY, dx~   [Q, W]   of [B, S, H P]
#   B, C, dB, dC               [Q, N]   of [B, S, G N]
#   cum, positions on rows     [Q, R]   of [B, nc, G, Q, R]   ("cols")
#   cum, positions on lanes    [R, Q]   of [B, nc, G, R, Q]   ("rows")
#   the state at z's start     [N, W]   of [B, nc, G, N, W]   (transposed)
#
# The state lies transposed so that what touches every head of the group
# at once is ONE product over the W lanes (C H^T, B dH^T, B^T x~, ...).
# Only `L`, `M` and their two products are a head's own, and a head is half
# a lane tile at P = 64: a tile's two heads both multiply the whole tile,
# and each keeps its lanes of the result (or, where the lanes are summed
# over, zeroes the other's lanes of an operand first).

def _lane_tiles(r: int, p: int):
    """(lanes, heads) of every 128-lane tile of a group's [Q, R P]."""
    per = LANE // p
    return [(slice(k * LANE, (k + 1) * LANE), range(k * per, (k + 1) * per))
            for k in range(r // per)]


# Inside the kernels' bodies `lax.select`, never `jnp.where`, and a grid
# axis a thing the index maps would divide by (no `//`, no `%`):
# `ops/__init__.py` has the rule.

def _by_head(parts, p: int):
    """One [rows, 128] tile from its heads' `parts` (each [rows, 128] or
    [rows, 1]): head t of the tile gives lanes t p .. (t + 1) p."""
    rows = max(part.shape[0] for part in parts)
    whole = lambda part: jnp.broadcast_to(part, (rows, LANE))
    tile = whole(parts[0])
    if len(parts) > 1:
        lane = lax.broadcasted_iota(jnp.int32, (rows, LANE), 1)
        for t, part in enumerate(parts[1:], 1):
            tile = lax.select(lane >= t * p, whole(part), tile)
    return tile


def _only_head(tile, t: int, p: int):
    """The tile with every lane but head t's zeroed (the whole tile where
    a head fills it)."""
    if p == LANE:
        return tile
    lane = lax.broadcasted_iota(jnp.int32, tile.shape, 1)
    mine = (lane >= t * p) & (lane < (t + 1) * p)
    wide = tile.astype(jnp.float32)
    return lax.select(mine, wide, jnp.zeros_like(wide)).astype(tile.dtype)


def _chunk_scalars(col_ref, r: int):
    """From a chunk's [Q, 2 R] block of per-position float32 values (the
    running sums, then dt; a head a lane): exp(cum), exp(cum_Q - cum), dt,
    each [Q, R]."""
    q = col_ref.shape[0]
    cum, dt = col_ref[:, :r], col_ref[:, r:]
    return jnp.exp(cum), jnp.exp(cum[q - 1:q, :] - cum), dt


def _fwd_kernel(z, x_ref, b_ref, c_ref, col_ref, row_ref, skip_ref,
                y_ref, start_ref, state, *, r: int, p: int):
    f32 = jnp.float32
    dtype = x_ref.dtype
    q = x_ref.shape[0]

    @pl.when(z == 0)
    def _():
        state[...] = jnp.zeros_like(state)

    start_ref[...] = state[...]
    bm, cm = b_ref[...], c_ref[...]
    cb = nt(cm, bm)                                       # [Q, Q]
    mask = lower(q)
    from_start, to_end, dt = _chunk_scalars(col_ref, r)
    y_off = nn(cm, state[...].astype(dtype))              # C H^T  [Q, W]
    for lanes, heads in _lane_tiles(r, p):
        spread = lambda cols: _by_head([cols[:, h:h + 1] for h in heads], p)
        x = x_ref[:, lanes].astype(f32)
        xt = (x * spread(dt)).astype(dtype)
        y_diag = _by_head(
            [nn((cb * decays(col_ref, row_ref, h, mask)).astype(dtype), xt)
             for h in heads], p)
        since = spread(from_start)
        y_ref[:, lanes] = (y_diag + since * y_off[:, lanes]
                           + skip_ref[:, lanes] * x).astype(y_ref.dtype)
        state[:, lanes] = since[q - 1:q, :] * state[:, lanes] + tn(
            bm, (xt.astype(f32) * spread(to_end)).astype(dtype))


def _position_sums(tile, k: int, p: int, *, parts: int, at: int = 0):
    """A [Q, 128] float32 tile summed over each head's lanes, through the
    MXU: head t of tile k lands in lane `at + k (128 / p) + t` of the
    [Q, 128] result. The tile goes in as `parts` bfloat16 parts (what a
    rounding leaves is rounded again: 8 bits of every term a part, three
    for float32's own 24); the 0 / 1 matrix is exact."""
    f32, bf16 = jnp.float32, jnp.bfloat16
    lane = lax.broadcasted_iota(jnp.int32, (LANE, LANE), 0)
    head = (lax.broadcasted_iota(jnp.int32, (LANE, LANE), 1)
            - (at + k * (LANE // p)))
    pick = lax.select((lane >= head * p) & (lane < (head + 1) * p),
                      jnp.ones((LANE, LANE), f32),
                      jnp.zeros((LANE, LANE), f32)).astype(bf16)
    sums = jnp.zeros(tile.shape, f32)
    for _ in range(parts):
        part = tile.astype(bf16)
        sums = sums + nn(part, pick)
        tile = tile - part.astype(f32)
    return sums


def _bwd_kernel(z, x_ref, dy_ref, b_ref, c_ref, col_ref, row_ref, skip_ref,
                start_ref, dx_ref, db_ref, dc_ref, sums_ref, whole_ref,
                dstate, *, r: int, p: int):
    """One chunk of the reverse walk: `dstate` comes in as the gradient of
    the state this chunk ENDS in and leaves as that of the state it starts
    from. Beside dx, dB and dC it writes what the [B, S, H]-sized rest
    outside needs and no more: a position's sums over P (`sums_ref`, a head
    a lane: first of dY y - dx~ x~, y without its skip, which is the running
    sums' gradient but for the chunk's total; then of dx~ x, dt's own part)
    and the chunk's sums over its rows (`whole_ref`: the gradient of the
    chunk's total, exp(total) H dH of the state at the chunk's start and
    the gradient of the state at its end, over N, plus what the positions
    gave the state, over Q; of dY x, D's part)."""
    f32 = jnp.float32
    dtype = x_ref.dtype
    q = x_ref.shape[0]

    @pl.when(z == 0)
    def _():
        dstate[...] = jnp.zeros_like(dstate)

    bm, cm = b_ref[...], c_ref[...]
    cb = nt(cm, bm)
    mask = lower(q)
    from_start, to_end, dt = _chunk_scalars(col_ref, r)
    y_off = nn(cm, start_ref[...].astype(dtype))          # C H^T   [Q, W]
    d_added = nn(bm, dstate[...].astype(dtype))           # B dH^T  [Q, W]
    ds = jnp.zeros((q, q), f32)
    dc = jnp.zeros(dc_ref.shape, f32)
    db = jnp.zeros(db_ref.shape, f32)
    sums = jnp.zeros((q, LANE), f32)
    for k, (lanes, heads) in enumerate(_lane_tiles(r, p)):
        spread = lambda cols: _by_head([cols[:, h:h + 1] for h in heads], p)
        x, dy = x_ref[:, lanes].astype(f32), dy_ref[:, lanes]
        step, since, until = spread(dt), spread(from_start), spread(to_end)
        xt = (x * step).astype(dtype)
        xtf = xt.astype(f32)
        y_diag, dxt_diag, y_low, dxt_low = [], [], [], []
        for t, h in enumerate(heads):
            decay = decays(col_ref, row_ref, h, mask)
            m = cb * decay
            m_high = m.astype(dtype)
            y_diag.append(nn(m_high, xt))
            dxt_diag.append(tn(m_high, dy))
            ds = ds + nt(_only_head(dy, t, p), xt) * decay
            if dtype != f32:
                # What rounding M dropped, for the running sums' gradient
                # alone: it is sum_j dM_ij M_ij of the float32 M, less the
                # same over i, and a product with the rounded M would be
                # off by M's rounding in every term.
                m_low = (m - m_high.astype(f32)).astype(dtype)
                y_low.append(nn(m_low, xt))
                dxt_low.append(tn(m_low, dy))
        dyf = dy.astype(f32)
        added = until * d_added[:, lanes]
        dxt = _by_head(dxt_diag, p) + added
        dx_ref[:, lanes] = (step * dxt + skip_ref[:, lanes] * dyf
                            ).astype(dx_ref.dtype)
        y = _by_head(y_diag, p) + since * y_off[:, lanes]
        of_cum = dyf * y - xtf * dxt
        if y_low:
            of_cum = of_cum + (dyf * _by_head(y_low, p)
                               - xtf * _by_head(dxt_low, p))
        sums = (sums + _position_sums(of_cum, k, p, parts=3)
                + _position_sums(x * dxt, k, p, parts=2, at=r))
        dy_since = (dyf * since).astype(dtype)
        x_until = (xtf * until).astype(dtype)
        start = start_ref[:, lanes]
        d_end = dstate[:, lanes]
        dc = dc + nt(dy_since, start.astype(dtype))
        db = db + nt(x_until, d_end.astype(dtype))
        dstate[:, lanes] = since[q - 1:q, :] * d_end + tn(cm, dy_since)
        # The chunk total's gradient, from the very products the positions
        # took theirs from (`added`, inside dx~): in exact arithmetic
        # <H, dH> of the state the chunk ends in, but a float32 <H, dH>
        # beside positions that read rounded operands leaves the two, which
        # cancel, apart by the rounding of a whole chunk.
        whole_ref[0:1, lanes] = (
            since[q - 1:q, :] * jnp.sum(start * d_end, axis=0, keepdims=True)
            + jnp.sum(xtf * added, axis=0, keepdims=True))
        whole_ref[1:2, lanes] = jnp.sum(dyf * x, axis=0, keepdims=True)
    ds = ds.astype(dtype)
    dc_ref[...] = (dc + nn(ds, bm)).astype(dc_ref.dtype)
    db_ref[...] = (db + tn(ds, cm)).astype(db_ref.dtype)
    sums_ref[...] = sums[:, :2 * r]


def _operands(x, dt, a_neg, b, c, d_skip, chunk: int, reverse: bool):
    """What both kernels read, with its block specs: x [B, S, H P], B and C
    [B, S, G N], a chunk's per-position float32 values twice (positions
    along rows, [B, nc, G, Q, 2 R]: the running sums, then dt; the running
    sums again with positions along lanes, [B, nc, G, R, Q]) and D a lane
    [G, 1, R P]. Returns (operands, in_specs, wide, narrow, of_chunk), the
    last three the specs of a [Q, R P] block, a [Q, N] block and a block a
    (batch, chunk, group). `reverse` walks the chunks from the last to the
    first."""
    bsz, seq, heads, p = x.shape
    groups, n = b.shape[2], b.shape[3]
    r, nc = heads // groups, seq // chunk
    w = r * p
    a = dt * a_neg.astype(jnp.float32)
    per_chunk = lambda t: t.reshape(bsz, nc, chunk, groups, r)
    cum = kernel.running_sums(per_chunk(a), axis=2)
    cols = jnp.transpose(jnp.concatenate([cum, per_chunk(dt)], axis=-1),
                         (0, 1, 3, 2, 4))
    rows = jnp.transpose(cum, (0, 1, 3, 4, 2))
    skip = jnp.repeat(d_skip.astype(jnp.float32), p).reshape(groups, 1, w)
    flat = lambda t: t.reshape(bsz, seq, -1)

    at = (lambda z: nc - 1 - z) if reverse else (lambda z: z)
    in_chunk = lambda i, g, z: (i, at(z), g)
    wide = pl.BlockSpec((None, chunk, w), in_chunk)
    narrow = pl.BlockSpec((None, chunk, n), in_chunk)
    of_chunk = lambda height, width: pl.BlockSpec(
        (None, None, None, height, width),
        lambda i, g, z: (*in_chunk(i, g, z), 0, 0))
    return ((flat(x), flat(b), flat(c), cols, rows, skip),
            [wide, narrow, narrow, of_chunk(chunk, 2 * r), of_chunk(r, chunk),
             pl.BlockSpec((None, 1, w), lambda i, g, z: (g, 0, 0))],
            wide, narrow, of_chunk)


def _forward(x, dt, a_neg, b, c, d_skip, chunk: int):
    bsz, seq, heads, p = x.shape
    groups, n = b.shape[2], b.shape[3]
    r, nc = heads // groups, seq // chunk
    operands, in_specs, wide, _, of_chunk = _operands(
        x, dt, a_neg, b, c, d_skip, chunk, reverse=False)
    y, starts = kernel.sequential_call(
        _fwd_kernel, "ssd_fwd", operands, in_specs,
        (out_struct((bsz, seq, heads * p), x.dtype, *operands),
         out_struct((bsz, nc, groups, n, r * p), jnp.float32, *operands)),
        (wide, of_chunk(n, r * p)), grid=(bsz, groups, nc),
        scratch=[(n, r * p)],
        count=functools.partial(_count_call, "fwd"), r=r, p=p)
    return y.reshape(x.shape), starts


def _backward(x, dt, a_neg, b, c, d_skip, starts, dy, chunk: int):
    f32 = jnp.float32
    bsz, seq, heads, p = x.shape
    groups, n = b.shape[2], b.shape[3]
    r, nc = heads // groups, seq // chunk
    operands, in_specs, wide, narrow, of_chunk = _operands(
        x, dt, a_neg, b, c, d_skip, chunk, reverse=True)
    xf, bf, cf = operands[:3]
    operands = (xf, dy.astype(x.dtype).reshape(xf.shape), *operands[1:],
                starts)
    dx, db, dc, sums, whole = kernel.sequential_call(
        _bwd_kernel, "ssd_bwd", operands,
        [wide, wide, *in_specs[1:], of_chunk(n, r * p)],
        (out_struct(xf.shape, x.dtype, *operands),
         out_struct(bf.shape, b.dtype, *operands),
         out_struct(cf.shape, c.dtype, *operands),
         out_struct((bsz, nc, groups, chunk, 2 * r), f32, *operands),
         out_struct((bsz, nc, groups, 2, r * p), f32, *operands)),
        (wide, narrow, narrow, of_chunk(chunk, 2 * r), of_chunk(2, r * p)),
        grid=(bsz, groups, nc), scratch=[(n, r * p)],
        count=functools.partial(_count_call, "bwd"), r=r, p=p)

    # The [B, S, H]-sized rest. A chunk's total is its last running sum.
    whole = jnp.sum(whole.reshape(bsz, nc, groups, 2, r, p), axis=-1)
    sums = jnp.swapaxes(sums, 2, 3)                        # [B, nc, Q, G, 2 R]
    dcum = sums[..., :r].at[:, :, -1].add(whole[:, :, :, 0])
    da = kernel.running_sums(dcum, axis=2, reverse=True).reshape(
        bsz, seq, heads)
    d_dt = sums[..., r:].reshape(bsz, seq, heads) + da * a_neg.astype(f32)
    return (dx.reshape(x.shape), d_dt,
            jnp.sum(da * dt, axis=(0, 1)).astype(a_neg.dtype),
            db.reshape(b.shape), dc.reshape(c.shape),
            jnp.sum(whole[:, :, :, 1], axis=(0, 1)).reshape(heads).astype(
                d_skip.dtype))


# Whole chunks, `dt` in float32.
_scan_kernels = kernel.kernel_vjp(
    _forward, _backward, names=RESIDUAL_NAMES, scope="ssd",
    nondiff_argnums=(6,))
