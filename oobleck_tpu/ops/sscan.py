"""The Mamba-1 recurrence (the selective scan): a decay a (channel, state)
pair.

Per channel `c` of `C` and state `n` of `N`, the state `h` in float32:

    h_t[c, n] = exp(dt_t[c] A[c, n]) h_{t-1}[c, n] + dt_t[c] B_t[n] x_t[c]
    y_t[c]    = sum_n C_t[n] h_t[c, n] + D[c] x_t[c]            h_{-1} = 0

The decay is not a scalar a head, so there is no [Q, Q] matrix form
(`ops/ssd.py`'s) and nothing here is a matrix product: the positions are
walked, and what the walk needs is small (C N multiply-adds and
exponentials a position). What must never exist is the state at every
position, [L, C, N], in HBM.

Two paths, and the backend decides between them (`kernel.on_tpu`; a
shape the kernels do not tile, `_kernels_take`, is the other reason for the
second):

  on a TPU   two Pallas kernels behind a `jax.custom_vjp`, on a grid of
             (batch, channel tile, chunk) with the chunk axis sequential.
             The state of a tile lies TRANSPOSED, [N, tile] float32: the
             channels on the lanes, so a position's `x`, `dt` and `y` are
             rows of their [chunk, tile] blocks as the arrays have them,
             and `B_t`, `C_t` are columns, read from blocks with a few
             positions on the lanes ([chunk / 8, N, 8]: a leading index
             picks eight positions, a static lane each).
             `sscan_fwd` carries the state in a VMEM scratch from chunk to
             chunk, writes `y` and the state at every chunk's START.
             `sscan_bwd` walks the chunks from the last to the first: a
             chunk's states are made again from its start into a VMEM
             scratch ([chunk + 1, N, tile]), then its positions are walked
             backwards with the state's gradient in a scratch of its own;
             it writes dx, d dt, dB and dC (a tile's part: the tiles are
             summed outside) and sums dA over the chunks in its output
             block. The rule (`kernel.kernel_vjp`) names what `sscan_fwd`
             wrote: `RESIDUAL_NAMES`.
  elsewhere  plain `jax.numpy` (`_scan_xla`): a `lax.scan` over chunks
             around an associative scan of one chunk's positions, each
             chunk a `jax.checkpoint`; gradients by JAX's differentiation.
             The CPU's path, and what the kernels are tested against.

Both are held to: `dt`, `A`, every `exp`, the products and the state in
float32 (`x` is read in its own dtype and widened); a length that is no
multiple of the chunk padded with `dt = 0`, `x = 0` rows, which move no
state, and cut off again.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl

from oobleck_tpu.ops import kernel
from oobleck_tpu.ops.kernel import LANE, SCOPED_VMEM, out_struct

# The forward rule's names for what `sscan_fwd` wrote, y and the state at
# every chunk's start: what only a second kernel call could give back.
RESIDUAL_NAMES = ("sscan_out", "sscan_starts")
# Positions a chunk: the backward holds a chunk's states, [CHUNK + 1, N,
# tile] float32 (8.5 MB at 16 states and 1024 channels).
CHUNK = 128
# Positions walked between two loop tests: one sublane tile of `x`'s rows,
# and the lanes of a `B` / `C` block.
UNROLL = 8
# The widest channel tiles. The wider, the fewer grid steps and the longer
# the rows a position's work is spread over: at [1, 8192, 5120] x 16 the
# forward takes 3.52 / 2.35 / 2.00 ms at 256 / 512 / 1024 channels, forward
# and backward together 37.99 / 20.02 / 11.87 / 8.62 ms at a backward of 128
# / 256 / 512 / 1024 (my chip runs, PR 60). The backward's chunk of states
# is 8.5 MB at 1024, beside 2.2 MB of row scratches: the backward asks for
# them by `vmem_limit_bytes`.
FWD_TILE, BWD_TILE = 1024, 1024


def _count(chunks: int, layer: str | None) -> None:
    """`oobleck_sscan_chunks_total{layer}`: the chunks a sequence is cut
    into, added where a scan is built into a traced program (not once a
    step)."""
    from oobleck_tpu.utils import metrics

    metrics.registry().counter(
        "oobleck_sscan_chunks_total",
        "Chunks of the selective scans built into traced programs, by "
        "layer").inc(chunks, layer=str(layer))


def _count_call(which: str) -> None:
    """`oobleck_sscan_calls_total{kernel}`: where a kernel is built into a
    traced program (not once a step). A scan on the `jax.numpy` path counts
    none."""
    from oobleck_tpu.utils import metrics

    metrics.registry().counter(
        "oobleck_sscan_calls_total",
        "Pallas kernels of the selective scan built into traced programs, "
        "by kernel (fwd, bwd)").inc(kernel=which)


def _tile(channels: int, most: int) -> int:
    """The widest whole number of lane tiles up to `most` that divides
    `channels`."""
    return max(w for w in range(LANE, most + 1, LANE) if channels % w == 0)


def _kernels_take(chunk: int, channels: int, n: int) -> bool:
    """The shapes the kernels tile: channels that fill lanes, states that
    fill sublanes, chunks of whole groups of `UNROLL` positions."""
    return channels % LANE == 0 and n % 8 == 0 and chunk % UNROLL == 0


@jax.named_scope("sscan")
def selective_scan(x: jax.Array, dt: jax.Array, a_neg: jax.Array,
                   b: jax.Array, c: jax.Array, d_skip: jax.Array, *,
                   chunk: int = CHUNK, layer: str | None = None) -> jax.Array:
    """x, dt [B, L, C] (dt after its softplus); a_neg [C, N] (A, negative);
    b, c [B, L, N]; d_skip [C]. Returns y [B, L, C] in x's dtype."""
    seq, channels = x.shape[1:]
    n = a_neg.shape[1]
    nc = -(-seq // chunk)
    _count(nc, layer)
    pad = nc * chunk - seq
    if pad:
        rows = lambda t: jnp.pad(t, ((0, 0), (0, pad), (0, 0)))
        x, dt, b, c = rows(x), rows(dt), rows(b), rows(c)
    f32 = jnp.float32
    dt, a_neg, b, c, d_skip = (t.astype(f32)
                               for t in (dt, a_neg, b, c, d_skip))
    if kernel.on_tpu() and _kernels_take(chunk, channels, n):
        return _scan_kernels(x, dt, a_neg, b, c, d_skip, chunk)[:, :seq]
    return _scan_xla(x, dt, a_neg, b, c, d_skip, chunk)[:, :seq]


# --------------------------------------------------------------------- #
# off the chip: jax.numpy                                                #
# --------------------------------------------------------------------- #

def _scan_xla(x, dt, a_neg, b, c, d_skip, chunk: int):
    """Whole chunks; everything but `x` float32."""
    f32 = jnp.float32
    bsz, seq, channels = x.shape
    n = a_neg.shape[1]
    xf = x.astype(f32)

    def combine(left, right):
        # (a2, u2) after (a1, u1): h -> a2 (a1 h + u1) + u2
        return right[0] * left[0], right[0] * left[1] + right[1]

    @jax.checkpoint
    def one_chunk(state, rows):
        x_c, dt_c, b_c, c_c = rows                         # [Q, B, ...]
        decay = jnp.exp(dt_c[..., None] * a_neg)           # [Q, B, C, N]
        added = (dt_c * x_c)[..., None] * b_c[:, :, None, :]
        since, own = lax.associative_scan(combine, (decay, added))
        h = since * state + own
        y = jnp.sum(h * c_c[:, :, None, :], axis=-1)
        return h[-1], y

    by_chunk = lambda t: jnp.moveaxis(t, 1, 0).reshape(
        seq // chunk, chunk, bsz, t.shape[-1])
    _, y = lax.scan(one_chunk, jnp.zeros((bsz, channels, n), f32),
                    tuple(by_chunk(t) for t in (xf, dt, b, c)))
    y = jnp.moveaxis(y.reshape(seq, bsz, channels), 0, 1)
    return (y + d_skip * xf).astype(x.dtype)


# --------------------------------------------------------------------- #
# on the chip: two kernels                                               #
# --------------------------------------------------------------------- #
#
# A step's blocks, for batch row `i`, channel tile `j` (W channels), chunk
# `z` (Q positions, U = UNROLL):
#
#   x, dt, y, dy, dx, d dt      [Q, W]        of [B, L, C]
#   B, C (transposed in 8s)     [Q / U, N, U] of [B, L / U, N, U]
#   dB, dC, a tile's part       [Q / U, N, U] of [B, C / W, L / U, N, U]
#   A (transposed), dA          [N, W]        of [N, C], [B, N, C]
#   D                           [1, W]        of [1, C]
#   the state at z's start      [N, W]        of [B, nc, N, C]
#
# Inside the bodies `lax.select` and `lax.broadcast_in_dim`, never
# `jnp.where`: `ops/__init__.py` has the rule.

def _rows_of(row, u: int, tile):
    """`tile` [U, W] with row `u` replaced by `row` [1, W]."""
    at = lax.broadcasted_iota(jnp.int32, tile.shape, 0) == u
    return lax.select(at, lax.broadcast_in_dim(row, tile.shape, (0, 1)), tile)


def _lanes_of(col, u: int, tile):
    """`tile` [N, U] with lane `u` replaced by `col` [N, 1]."""
    at = lax.broadcasted_iota(jnp.int32, tile.shape, 1) == u
    return lax.select(at, lax.broadcast_in_dim(col, tile.shape, (0, 1)), tile)


def _walk(state, a, dt_ref, u_ref, b_ref, c_ref, each, *, groups: int):
    """The recurrence over a chunk's positions, in groups of UNROLL, the
    state read from and left in the scratch `state` (a loop that carries
    nothing: inside a `check_vma=True` shard_map a carried value would
    have to vary as every block it meets does). `each(base, y8, states)`
    gets, a group, the [U, W] tile of y it would write (None where `c_ref`
    is None) and the states after each of its positions."""
    def group(g, _):
        base = pl.multiple_of(g * UNROLL, UNROLL)
        dt8 = dt_ref[pl.ds(base, UNROLL), :]
        u8 = u_ref[pl.ds(base, UNROLL), :]
        bt = b_ref[g]
        ct = y8 = None
        if c_ref is not None:
            ct, y8 = c_ref[g], jnp.zeros(dt8.shape, jnp.float32)
        h = state[...]
        states = []
        for u in range(UNROLL):
            h = (jnp.exp(dt8[u:u + 1, :] * a) * h
                 + bt[:, u:u + 1] * u8[u:u + 1, :])
            states.append(h)
            if ct is not None:
                y8 = _rows_of(jnp.sum(h * ct[:, u:u + 1], axis=0,
                                      keepdims=True), u, y8)
        state[...] = h
        each(base, y8, states)
        return 0

    lax.fori_loop(0, groups, group, 0)


def _fwd_kernel(z, x_ref, dt_ref, b_ref, c_ref, a_ref, skip_ref,
                y_ref, start_ref, state, us, ys):
    f32 = jnp.float32

    @pl.when(z == 0)
    def _():
        state[...] = jnp.zeros_like(state)

    start_ref[...] = state[...]
    x = x_ref[...].astype(f32)
    us[...] = x * dt_ref[...]

    def each(base, y8, states):
        ys[pl.ds(base, UNROLL), :] = y8

    _walk(state, a_ref[...], dt_ref, us, b_ref, c_ref, each,
          groups=x_ref.shape[0] // UNROLL)
    y_ref[...] = (ys[...] + skip_ref[...] * x).astype(y_ref.dtype)


def _bwd_kernel(z, x_ref, dt_ref, dy_ref, b_ref, c_ref, a_ref, skip_ref,
                start_ref, dx_ref, ddt_ref, db_ref, dc_ref, da_ref,
                dstate, state, us, dus, ddts, dys, hs):
    """One chunk of the reverse walk. `dstate` comes in as the gradient of
    the state this chunk ends in, already times the next position's decay,
    and leaves as that of the state it starts from, times this chunk's
    first. `hs[t + 1]` is the state after position t, `hs[0]` the chunk's
    start."""
    f32 = jnp.float32
    q = x_ref.shape[0]
    groups = q // UNROLL

    @pl.when(z == 0)
    def _():
        dstate[...] = jnp.zeros_like(dstate)
        da_ref[...] = jnp.zeros_like(da_ref)

    a = a_ref[...]
    x = x_ref[...].astype(f32)
    dy = dy_ref[...].astype(f32)
    us[...] = x * dt_ref[...]
    dys[...] = dy
    hs[0] = state[...] = start_ref[...]

    def keep(base, y8, states):
        for u, h in enumerate(states):
            hs[base + u + 1] = h

    _walk(state, a, dt_ref, us, b_ref, None, keep, groups=groups)

    def group(k, _):
        # dA's sum over the chunks so far is read from and left in its
        # output block, which stays put along the chunk axis.
        dh, da = dstate[...], da_ref[...]
        g = groups - 1 - k
        base = pl.multiple_of(g * UNROLL, UNROLL)
        dt8 = dt_ref[pl.ds(base, UNROLL), :]
        u8 = us[pl.ds(base, UNROLL), :]
        dy8 = dys[pl.ds(base, UNROLL), :]
        bt, ct = b_ref[g], c_ref[g]
        du8 = jnp.zeros(dt8.shape, f32)
        ddt8 = jnp.zeros(dt8.shape, f32)
        dbt = jnp.zeros(bt.shape, f32)
        dct = jnp.zeros(ct.shape, f32)
        for u in reversed(range(UNROLL)):
            dt_row, dy_row = dt8[u:u + 1, :], dy8[u:u + 1, :]
            h_t, h_prev = hs[base + u + 1], hs[base + u]
            decay = jnp.exp(dt_row * a)
            dh = ct[:, u:u + 1] * dy_row + dh           # d h_t      [N, W]
            dct = _lanes_of(jnp.sum(dy_row * h_t, axis=1, keepdims=True),
                            u, dct)
            dbt = _lanes_of(jnp.sum(dh * u8[u:u + 1, :], axis=1,
                                    keepdims=True), u, dbt)
            du8 = _rows_of(jnp.sum(dh * bt[:, u:u + 1], axis=0,
                                   keepdims=True), u, du8)
            dh = dh * decay                             # d h_{t-1}'s part
            of_log = dh * h_prev                        # d (dt_t A)
            ddt8 = _rows_of(jnp.sum(of_log * a, axis=0, keepdims=True),
                            u, ddt8)
            da = da + of_log * dt_row
        dus[pl.ds(base, UNROLL), :] = du8
        ddts[pl.ds(base, UNROLL), :] = ddt8
        db_ref[g] = dbt
        dc_ref[g] = dct
        dstate[...], da_ref[...] = dh, da
        return 0

    lax.fori_loop(0, groups, group, 0)
    du = dus[...]
    dx_ref[...] = (du * dt_ref[...] + skip_ref[...] * dy).astype(dx_ref.dtype)
    ddt_ref[...] = ddts[...] + du * x


def _operands(x, dt, a_neg, b, c, d_skip, chunk: int, tile: int,
              reverse: bool):
    """What both kernels read, with its block specs. Returns (operands,
    in_specs, wide, of_chunk): the last two the spec of a [Q, W] block and
    the index map of a chunk's [Q / U, N, U] block behind `lead` leading
    indices."""
    bsz, seq, channels = x.shape
    n = a_neg.shape[1]
    nc = seq // chunk
    in_eights = lambda t: jnp.swapaxes(
        t.reshape(bsz, seq // UNROLL, UNROLL, n), 2, 3)
    at = (lambda z: nc - 1 - z) if reverse else (lambda z: z)
    wide = pl.BlockSpec((None, chunk, tile), lambda i, j, z: (i, at(z), j))
    eights = pl.BlockSpec((None, chunk // UNROLL, n, UNROLL),
                          lambda i, j, z: (i, at(z), 0, 0))
    return ((x, dt, in_eights(b), in_eights(c), a_neg.T,
             d_skip.reshape(1, channels)),
            [wide, wide, eights, eights,
             pl.BlockSpec((n, tile), lambda i, j, z: (0, j)),
             pl.BlockSpec((1, tile), lambda i, j, z: (0, j))],
            wide, at)


def _forward(x, dt, a_neg, b, c, d_skip, chunk: int):
    bsz, seq, channels = x.shape
    n = a_neg.shape[1]
    tile = _tile(channels, FWD_TILE)
    operands, in_specs, wide, at = _operands(
        x, dt, a_neg, b, c, d_skip, chunk, tile, reverse=False)
    return kernel.sequential_call(
        _fwd_kernel, "sscan_fwd", operands, in_specs,
        (out_struct(x.shape, x.dtype, *operands),
         out_struct((bsz, seq // chunk, n, channels), jnp.float32,
                    *operands)),
        (wide, pl.BlockSpec((None, None, n, tile),
                            lambda i, j, z: (i, at(z), 0, j))),
        grid=(bsz, channels // tile, seq // chunk),
        scratch=[(n, tile), (chunk, tile), (chunk, tile)],
        count=functools.partial(_count_call, "fwd"))


def _backward(x, dt, a_neg, b, c, d_skip, starts, dy, chunk: int):
    f32 = jnp.float32
    bsz, seq, channels = x.shape
    n = a_neg.shape[1]
    tile = _tile(channels, BWD_TILE)
    tiles = channels // tile
    operands, in_specs, wide, at = _operands(
        x, dt, a_neg, b, c, d_skip, chunk, tile, reverse=True)
    operands = (*operands[:2], dy.astype(x.dtype), *operands[2:], starts)
    part = pl.BlockSpec((None, None, chunk // UNROLL, n, UNROLL),
                        lambda i, j, z: (i, j, at(z), 0, 0))
    parts = out_struct((bsz, tiles, seq // UNROLL, n, UNROLL), f32, *operands)
    scratch = [(n, tile), (n, tile), (chunk, tile), (chunk, tile),
               (chunk, tile), (chunk, tile), (chunk + 1, n, tile)]
    dx, ddt, db, dc, da = kernel.sequential_call(
        _bwd_kernel, "sscan_bwd", operands,
        [*in_specs[:2], wide, *in_specs[2:],
         pl.BlockSpec((None, None, n, tile),
                      lambda i, j, z: (i, at(z), 0, j))],
        (out_struct(x.shape, x.dtype, *operands),
         out_struct(x.shape, f32, *operands), parts, parts,
         out_struct((bsz, n, channels), f32, *operands)),
        (wide, wide, part, part,
         pl.BlockSpec((None, n, tile), lambda i, j, z: (i, 0, j))),
        grid=(bsz, tiles, seq // chunk), scratch=scratch,
        count=functools.partial(_count_call, "bwd"),
        # The chunk's states stay in VMEM while its positions are walked
        # back: the kernel asks for its scratches beside the default scoped
        # limit, which is left to the blocks and the values in flight (at a
        # tile of 1024 the whole is 152 KB over that limit without asking:
        # the compile for a described v5e said so, PR 60).
        vmem_limit_bytes=SCOPED_VMEM + 4 * sum(
            math.prod(shape) for shape in scratch))
    # The tiles' parts of dB and dC, summed, positions back in order.
    whole = lambda t: jnp.swapaxes(jnp.sum(t, axis=1), 2, 3).reshape(
        bsz, seq, n)
    d_skip_grad = jnp.sum(dy.astype(f32) * x.astype(f32), axis=(0, 1))
    return (dx, ddt, jnp.sum(da, axis=0).T, whole(db), whole(dc),
            d_skip_grad)


# Whole chunks; everything but `x` float32.
_scan_kernels = kernel.kernel_vjp(
    _forward, _backward, names=RESIDUAL_NAMES, scope="sscan",
    nondiff_argnums=(6,))
