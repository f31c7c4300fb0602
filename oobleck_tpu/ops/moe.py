"""Routed experts: dropless top-k over a held range of the experts.

The expert layer of the repo. `routed_experts` drops no token and does
work for the (token, slot) pairs that are routed to the experts this chip
HOLDS: pairs are laid out by expert in one buffer of the static worst-case
length, each expert's rows padded to a whole number of row tiles, and the
products (three of a SwiGLU expert, two of an expert without a gate) run as
grouped matrix multiplications whose grids END at the tiles in use: the row
axis's bound is the plan's `num_tiles`, a dynamic grid bound, so the empty
tail is no grid step at all (as steps that did nothing, the tail was 83 to
95 % of the benchmark cells' grids and real time: PERF.md section 6, PR 56).
What XLA does around them (rows in and out of the buffer, the activation)
loops over the tiles in use too, and starts from a buffer that is allocated
and not filled (`_unwritten`), so no work follows the buffer's length.
Kernels (stable names on the
`pallas_call`, so a device trace shows `%moe_gmm.N` / `%moe_tgmm.N` /
`%moe_token_sum.N`):

  moe_gmm        rows [M, K] x experts [E, K, N] -> [M, N]   forward, and
                 dX with the expert matrices read transposed
  moe_tgmm       rows^T [M, K] x rows [M, N] -> [E, K, N]    dW, on top of
                 a running gradient sum where the caller hands one
                 (`GradSum`)
  moe_token_sum  rows [M, D] -> tokens [T, D]: a token the (weighted) sum
                 of its rows, a block of tokens a grid step, the float32
                 sum in VMEM until the block is written

On one chip nothing is exchanged; with `num_experts_held` < `num_experts`
the result is this chip's PART of the layer's output (the partial sums
of all shares add up to the uncut layer, tests/ops/test_routed_experts.py).
"""

from __future__ import annotations

import contextlib
import dataclasses
import functools
import threading
from typing import NamedTuple

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from oobleck_tpu.ops import kernel
from oobleck_tpu.ops.kernel import LANE

MAX_ROW_TILE = 512        # rows of one tile: what an expert's rows pad to
SUBLANE = 16              # a bfloat16 tile's rows
MAX_COL_TILE = 512        # columns of the output one grid step produces
MAX_TGMM_ROWS = 1024      # rows of dW one grid step accumulates
MAX_WHOLE_COLS = 2048     # a width no tile but one lane divides, taken whole
MAX_TOKEN_BLOCK = 1024    # tokens whose sum one grid step of moe_token_sum owns
TOKEN_SUM_VMEM = 24 * 1024 * 1024   # what a block's sum and its passes may take
_GMM_PARAMS = pltpu.CompilerParams(
    dimension_semantics=("parallel", "arbitrary"),
    vmem_limit_bytes=64 * 1024 * 1024)
_TGMM_PARAMS = pltpu.CompilerParams(
    dimension_semantics=("parallel", "parallel", "arbitrary"),
    vmem_limit_bytes=64 * 1024 * 1024)


def _col_tile(n: int, limit: int) -> int:
    """The largest multiple of the lane width that divides `n` and stays
    within `limit`; the whole of `n` where no such tile exists (widths
    under a lane, the tests' sizes) and where the only one is a single
    lane and `n` fits MAX_WHOLE_COLS (1408 = 11 x 128: in 128-column tiles
    the rows are read eleven times over and a grid step carries a tenth of
    a microsecond of products; whole, `moe_gmm_roofline` read 27.2 % against
    16.9 % and the step 920.6 against 1,008.8 ms, my chip runs, PR 35).
    1856 = 14.5 x 128 has no tile at all and is taken whole too: padded to
    1920 by the call (384-column tiles) the step read 993.1 against 928.4
    ms and `moe_tgmm` 0.622 against 0.399 ms a call (my chip runs, PR 37)."""
    tiles = [LANE * m for m in range(1, limit // LANE + 1)
             if n % (LANE * m) == 0]
    if tiles == [LANE] and LANE < n <= MAX_WHOLE_COLS:
        return n
    return max(tiles) if tiles else n


@functools.cache
def choose_row_tile(num_pairs: int, num_experts: int) -> int:
    """Rows of one tile, from the call's shape: among the multiples of the
    lane width up to MAX_ROW_TILE (of a bfloat16 sublane tile where an
    expert's rows and half as many again stay under a lane), the tile whose
    edges lie furthest from the rows an expert EXPECTS (the pairs over all
    the experts), so that an expert's rows fill the same number of tiles
    whatever the router does to its load within that room; room beyond half
    the expected rows counts for nothing. Ties go to the fewest tiles an
    expert, then to the smallest tile. 512 expected rows get 384 (two
    tiles from 385 to 768 rows), 384 get 512 (one tile up to 512), 256 get
    384. With a tile whose edge the expected rows sit on (256 at 512 rows:
    two tiles or three at the slightest excess) the tiles in use, and the
    step's time with them, changed with every seed's router: 0.9 % between
    six seeds of the benchmark's cell (my chip runs, PR 29).

    1,536 expected rows (16384 tokens x 6 picks over 64 experts) are 3 x
    512 = 4 x 384 = 6 x 256 = 12 x 128: on the edge of EVERY tile up to
    MAX_ROW_TILE. At 512 each held expert filled three tiles or four as its
    1,536 +- 60 rows fell, 105 to 117 tiles over a sequence's four layers
    from seed to seed, and `smallthinker-21b-a3b.steady`'s rate followed
    them: 39,270 to 39,626 tokens/s over six seeds, a spread of 0.55 % (my
    chip runs, PR 45). Only where every tile up to MAX_ROW_TILE leaves the
    expected rows no room at all are tiles up to twice that looked at: 1024
    here, two tiles an expert from 1,025 to 2,048 rows. Every other call's
    tile is what it was."""
    expected = num_pairs / num_experts
    unit = LANE if 1.5 * expected >= LANE else SUBLANE

    def preference(tile: int):
        tiles = max(-(-expected // tile), 1)
        room = min(expected - (tiles - 1) * tile, tiles * tile - expected,
                   expected / 2)
        return (-room, tiles, tile)

    def best(limit: int) -> int:
        return min(range(unit, limit + 1, unit), key=preference)

    tile = best(MAX_ROW_TILE)
    if preference(tile)[0] == 0:
        tile = best(2 * MAX_ROW_TILE)
    return tile


class RoutingPlan(NamedTuple):
    """Where the routed pairs' rows lie, for one call, by row TILE. Pairs
    are (token, slot) flattened to t * k + s; `order` lists them by held
    expert (stable, so in the pairs' own order inside an expert), and a
    tile's rows are a contiguous run of it. Nothing here is as long as the
    buffer.

    The last four are the same layout read from the TOKENS' side, for
    `moe_token_sum` (None where the sums are XLA's loop: `token_runs`).
    They rest on the order inside an expert's region: the sort is stable
    and a token picks an expert at most once, so there the rows ascend by
    token and none repeats, and the rows expert e gives a BLOCK of tokens
    are one contiguous run of the buffer."""
    tile_group: jax.Array      # [tiles] int32: the held expert of a row tile
    num_tiles: jax.Array       # [1] int32: tiles in use; the rest is skipped
    padded_sizes: jax.Array    # [held] int32: each expert's rows, padded
    group_sizes: jax.Array     # [held] int32: each expert's rows
    order: jax.Array           # [pairs + tile] int32: pairs by held expert
    tile_first: jax.Array      # [tiles] int32: where in `order` a tile starts
    tile_rows: jax.Array       # [tiles] int32: rows of the tile that hold a pair
    src_row: jax.Array | None = None    # [T, held] int32: the row of the
    #                            token's pair on held expert e, or -1
    w_held: jax.Array | None = None     # [T, held] float32: that pair's weight
    run_first: jax.Array | None = None  # [T / tb * held] int32, block-major:
    #                            the first row expert e gives the block
    run_len: jax.Array | None = None    # likewise: how many rows it gives


def buffer_rows(num_tokens: int, top_k: int, held: int,
                num_experts: int) -> tuple[int, int]:
    """(rows of the buffer, rows of a tile): the worst case, every pick of
    every token held here, plus one tile of padding for every expert."""
    pairs = num_tokens * min(top_k, held)
    tile = choose_row_tile(num_tokens * top_k, num_experts)
    return -(-pairs // tile) * tile + held * tile, tile


def _unwritten(shape, dtype) -> jax.Array:
    """A buffer of rows that a loop over the row tiles IN USE is about to
    write: allocated, not filled (on a TPU an `AllocateBuffer` custom call
    with no operand and no write; zeros elsewhere), because a fill follows
    the worst-case length and nobody reads it. The one place that makes
    such a buffer, and its contract is the one `gmm_call`'s output already
    keeps: rows of tiles past `num_tiles` hold NOTHING (whatever the memory
    held), rows of a tile in use that hold no pair are zero because the
    loop writes them so, and no consumer may read the former: the kernels'
    grids end at `num_tiles`, the loops run `num_tiles` trips. Counted where
    it is built, as the calls below are:
    `oobleck_moe_unfilled_buffers_total`."""
    from oobleck_tpu.utils import metrics

    metrics.registry().counter(
        "oobleck_moe_unfilled_buffers_total",
        "Row buffers of the routed experts handed out allocated and not "
        "filled, built into traced programs").inc()
    return lax.empty(shape, dtype)


def plan_routing(local_expert: jax.Array, held: int, rows: int,
                 tile: int) -> RoutingPlan:
    """`local_expert` [pairs]: the held expert's index, or `held` for a
    pair routed elsewhere. Static shapes, no scatter: one stable sort, and
    arithmetic on `held` and `rows // tile` numbers."""
    i32 = jnp.int32
    order = jnp.argsort(local_expert, stable=True).astype(i32)  # by expert
    sizes = jnp.sum(local_expert[None, :] == jnp.arange(held, dtype=i32)[:, None],
                    axis=1, dtype=i32)
    # Every expert has one tile at least, so dW of an expert that got no
    # token is written (as zeros) by the kernel that visits its tile.
    tiles = jnp.maximum(-(-sizes // tile), 1)
    first_tile = jnp.cumsum(tiles) - tiles
    sorted_start = jnp.cumsum(sizes) - sizes
    t = jnp.arange(rows // tile, dtype=i32)
    group = jnp.minimum(
        jnp.searchsorted(jnp.cumsum(tiles), t, side="right"), held - 1
    ).astype(i32)
    within = (t - first_tile[group]) * tile       # rows of its expert before
    in_use = t < jnp.sum(tiles)
    tile_rows = jnp.where(in_use, jnp.clip(sizes[group] - within, 0, tile), 0)
    tile_first = jnp.where(in_use, sorted_start[group] + within, 0)
    return RoutingPlan(
        group, jnp.sum(tiles).reshape(1).astype(i32),
        (tiles * tile).astype(i32), sizes,
        jnp.concatenate([order, jnp.zeros((tile,), i32)]),
        tile_first.astype(i32), tile_rows.astype(i32))


def _sum_chunk(tile: int) -> int:
    """Buffer rows `moe_token_sum` copies at a time: one pass of the MXU's
    contraction, and no more than a row tile, so a chunk that ends with the
    last tile in use always starts inside the tiles in use (every expert
    has a tile)."""
    return min(LANE, tile)


@functools.cache
def choose_token_block(num_tokens: int, top_k: int, num_experts: int,
                       tile: int) -> int:
    """Tokens of one block of `moe_token_sum`, from the call's shape. A
    grid step takes, for every held expert, ONE chunk of `_sum_chunk(tile)`
    buffer rows for the block's tokens and a further one only where the
    expert's run is longer, and the chunk's products cost the same however
    many of its rows the block uses. So: the largest block (a multiple of
    the bfloat16 sublane tile that divides the tokens, up to
    MAX_TOKEN_BLOCK) whose EXPECTED rows an expert, block x k / experts,
    fill half a chunk at most: the other half is room for where the run
    starts in its sublane tile and for what the router does to the load.
    512 tokens at 16384 x top 6 of 64 (48 rows expected of 128), 1024 at
    top 4 of 64 and at top 10 of 512. All the tokens where no such block
    divides them."""
    fits = _sum_chunk(tile) / 2
    blocks = [b for b in range(SUBLANE, min(num_tokens, MAX_TOKEN_BLOCK) + 1,
                               SUBLANE) if num_tokens % b == 0]
    if not blocks:
        return num_tokens
    roomy = [b for b in blocks if b * top_k / num_experts <= fits]
    return max(roomy) if roomy else min(blocks)


def token_runs(plan: RoutingPlan, local_expert: jax.Array,
               weights: jax.Array, block: int) -> RoutingPlan:
    """The plan with its last four fields: `local_expert` and `weights`
    [T, k] as `route` gave them (the held expert's index, or `held`).
    Dense arithmetic only: compares, one cumulative sum along the tokens,
    and slices of it; no sort, and no scatter of an integer a pair. A
    pair's row is its expert's first row plus its rank among the expert's
    pairs, which in a stable order is a running count.

    PRECONDITION, the caller's: a token's picks are DISTINCT experts
    (`lax.top_k`'s are; `forced_experts` come from a reference's own
    top-k). A token that named one held expert twice would own two rows
    there and `src_row` has room for one."""
    i32 = jnp.int32
    held = plan.padded_sizes.shape[0]
    num_tokens = local_expert.shape[0]
    picked = local_expert[None] == jnp.arange(held, dtype=i32)[:, None, None]
    hit = jnp.any(picked, axis=2).astype(i32)                   # [held, T]
    upto = jnp.cumsum(hit, axis=1)                              # inclusive
    first_row = (jnp.cumsum(plan.padded_sizes) - plan.padded_sizes)[:, None]
    src_row = jnp.where(hit > 0, first_row + upto - hit, -1)
    ends = upto[:, block - 1::block]                            # [held, T/tb]
    before = jnp.concatenate([jnp.zeros((held, 1), i32), ends[:, :-1]], 1)
    assert ends.shape[1] * block == num_tokens, (num_tokens, block)
    w_held = jnp.sum(jnp.where(picked, lax.stop_gradient(weights)[None], 0.0),
                     axis=2)
    return plan._replace(
        src_row=src_row.T, w_held=w_held.T,
        run_first=(first_row + before).T.reshape(-1),
        run_len=(ends - before).T.reshape(-1))


# --------------------------------------------------------------------- #
# kernels                                                                #
# --------------------------------------------------------------------- #

def _gmm_body(tile_group, lhs_ref, rhs_ref, out_ref, w_scratch, *,
              transpose_rhs: bool):
    """One row tile times its expert's [K, tn] (or [tn, K], transposed)
    block. The expert's block stays in VMEM over the tiles of one expert
    (its block index does not change), so an expert's matrix is read once
    a call; it is cast to the rows' dtype once per expert too."""
    m = pl.program_id(1)
    if w_scratch is None:
        w = rhs_ref[...]
    else:
        new_expert = jnp.logical_or(
            m == 0, tile_group[m] != tile_group[jnp.maximum(m - 1, 0)])

        @pl.when(new_expert)
        def _():
            w_scratch[...] = rhs_ref[...].astype(w_scratch.dtype)

        w = w_scratch[...]
    dims = (((1,), (1,)), ((), ())) if transpose_rhs else (
        ((1,), (0,)), ((), ()))
    out_ref[...] = lax.dot_general(
        lhs_ref[...], w, dims,
        preferred_element_type=jnp.float32).astype(out_ref.dtype)


def _count_plan_bounded_grid(which: str) -> None:
    """`oobleck_moe_plan_bounded_grids_total{kernel}`: counted where the
    kernel is built, once a call traced (not once a step). How many steps
    the grids then run is read every step:
    `oobleck_moe_step_tile_rows_total` over the tile's rows."""
    from oobleck_tpu.utils import metrics

    metrics.registry().counter(
        "oobleck_moe_plan_bounded_grids_total",
        "Grouped expert kernels whose grid's row axis ends at the plan's "
        "tiles in use (a dynamic grid bound), built into traced programs"
    ).inc(kernel=which)


def gmm_call(lhs, rhs, tile_group, num_tiles, *, tile: int,
             transpose_rhs: bool = False):
    """`moe_gmm`: lhs [M, K] (rows by expert, whole tiles) x rhs [E, K, N]
    -> [M, N]; with `transpose_rhs`, rhs is [E, N, K]. Rows of tiles past
    `num_tiles` are not written: the grid's row axis ends there (a dynamic
    bound; `num_tiles` >= the experts >= 1, so no grid is empty)."""
    m_rows, k = lhs.shape
    n = rhs.shape[1] if transpose_rhs else rhs.shape[2]
    assert rhs.shape[2 if transpose_rhs else 1] == k, (lhs.shape, rhs.shape)
    assert m_rows % tile == 0, (m_rows, tile)
    tn = _col_tile(n, MAX_COL_TILE)
    cast = rhs.dtype != lhs.dtype
    _count_plan_bounded_grid("gmm")

    if transpose_rhs:
        rhs_block, rhs_of = (None, tn, k), lambda n_, m_, tg: (tg[m_], n_, 0)
    else:
        rhs_block, rhs_of = (None, k, tn), lambda n_, m_, tg: (tg[m_], 0, n_)
    scratch = [pltpu.VMEM(rhs_block[1:], lhs.dtype)] if cast else []
    body = functools.partial(_gmm_body, transpose_rhs=transpose_rhs)
    step = body if cast else (lambda tg, l, r, o: body(tg, l, r, o, None))
    return pl.pallas_call(
        step,
        out_shape=jax.ShapeDtypeStruct((m_rows, n), lhs.dtype),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(n // tn, num_tiles[0]),
            in_specs=[pl.BlockSpec((tile, k), lambda n_, m_, tg: (m_, 0)),
                      pl.BlockSpec(rhs_block, rhs_of)],
            out_specs=pl.BlockSpec((tile, tn), lambda n_, m_, tg: (m_, n_)),
            scratch_shapes=scratch),
        compiler_params=_GMM_PARAMS,
        interpret=kernel.interpret(),
        name="moe_gmm",
    )(tile_group, lhs, rhs)


def _tgmm_body(tile_group, num_tiles, lhs_ref, rhs_ref, *refs):
    """Rows^T x rows of one tile, summed over the tiles of one expert into
    its [tk, tn] block of dW. `refs` is (out, acc) or (start, out, acc):
    with `start`, an expert's sum begins from its block of it and not from
    zeros."""
    *start_ref, out_ref, acc = refs
    m = pl.program_id(2)
    used = num_tiles[0]
    group = tile_group[m]
    first = jnp.logical_or(
        m == 0, group != tile_group[jnp.maximum(m - 1, 0)])
    last = jnp.logical_or(
        m == used - 1, group != tile_group[jnp.minimum(m + 1, used - 1)])

    @pl.when(first)
    def _():
        if start_ref:
            acc[...] = start_ref[0][...].astype(acc.dtype)
        else:
            acc[...] = jnp.zeros_like(acc)

    acc[...] += lax.dot_general(
        lhs_ref[...], rhs_ref[...], (((0,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)

    @pl.when(last)
    def _():
        out_ref[...] = acc[...].astype(out_ref.dtype)


def tgmm_call(lhs, rhs, tile_group, num_tiles, *, tile: int, num_groups: int,
              out_dtype, start=None):
    """`moe_tgmm`: lhs [M, K], rhs [M, N] -> [E, K, N], expert e's block
    the product over e's rows. The grid's row axis ends at `num_tiles` (a
    dynamic bound, as `gmm_call`'s); every expert has a tile, so every
    block is written.

    With `start` [E, K, N] in `out_dtype` the result is `start` + that
    product, IN `start`'s buffer: a third tensor operand under the output's
    own block map, aliased to the output. Every block is visited once (an
    expert's tiles are contiguous), read before its one write, and no two
    blocks overlap, which is what makes the alias sound."""
    m_rows, k = lhs.shape
    n = rhs.shape[1]
    assert rhs.shape[0] == m_rows and m_rows % tile == 0
    tk = _col_tile(k, MAX_TGMM_ROWS)
    tn = _col_tile(n, MAX_COL_TILE)
    _count_plan_bounded_grid("tgmm")

    out_shape = jax.ShapeDtypeStruct((num_groups, k, n), out_dtype)
    block_of_dw = pl.BlockSpec(
        (None, tk, tn), lambda k_, n_, m_, tg, nt: (tg[m_], k_, n_))
    started = [] if start is None else [start]
    for operand in started:
        assert (operand.shape, operand.dtype) == (
            out_shape.shape, out_shape.dtype), (operand, out_shape)
    return pl.pallas_call(
        _tgmm_body,
        out_shape=out_shape,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            # `num_tiles` twice: the row axis's bound, and a prefetched
            # table for the body's test of the last tile.
            num_scalar_prefetch=2,
            grid=(k // tk, n // tn, num_tiles[0]),
            in_specs=[
                pl.BlockSpec((tile, tk), lambda k_, n_, m_, tg, nt: (m_, k_)),
                pl.BlockSpec((tile, tn), lambda k_, n_, m_, tg, nt: (m_, n_)),
                *[block_of_dw for _ in started]],
            out_specs=block_of_dw,
            scratch_shapes=[pltpu.VMEM((tk, tn), jnp.float32)]),
        # Counted over the call's own operands, the two prefetched tables
        # among them: `start` is the fifth. (In the lowered custom call
        # the grid's bound comes first and the alias reads 5.)
        input_output_aliases={4: 0} if started else {},
        compiler_params=_TGMM_PARAMS,
        interpret=kernel.interpret(),
        name="moe_tgmm",
    )(tile_group, num_tiles, lhs, rhs, *started)


def _token_sum_body(run_first, run_len, num_tiles, src_ref, *refs,
                    held: int, tile: int, weighted: bool):
    """The sum of ONE block of tokens (and block of columns), in float32
    in `acc` until it is written. The held experts in ascending order, as
    the row tiles have them, so a token's sum adds the same terms in the
    same order as a loop over the tiles would. For each, the rows it gives
    the block are a contiguous run of the buffer (`RoutingPlan`): a chunk
    of rows is copied from where the run starts, aligned down to a sublane
    tile and held back to end with the last tile in use, and the one-hot
    `P[token, row of the chunk]` times the chunk, on the MXU with float32
    accumulation, puts each token's row in its place EXACTLY (one bfloat16
    value times one, and zeros). The pair's weight and the sum are the
    VPU's, in float32. A run longer than a chunk takes further chunks, so
    nothing is dropped whatever the router does. No row past the tiles in
    use is read (they hold NOTHING, `_unwritten`): every chunk ends at or
    before the last one's end.

    The next expert's first chunk is in flight while this one's is added.

    `lax` primitives where `jnp` has a jitted helper (`ops/__init__.py` has
    the rule): with `//` here a cell's second run compiled `jit_bwd` anew
    (+ 54 s of `setup_s`, my chip run, PR 52)."""
    w_ref = refs[0] if weighted else None
    rows_ref, out_ref, acc, buf, sem = refs[1:] if weighted else refs
    b, n = pl.program_id(0), pl.program_id(1)
    tb, tn = acc.shape
    chunk = buf.shape[1]
    last_start = num_tiles[0] * tile - chunk
    lane = lax.broadcasted_iota(jnp.int32, (tb, chunk), 1)

    def copy(start, slot):
        return pltpu.make_async_copy(
            rows_ref.at[pl.ds(start, chunk), pl.ds(n * tn, tn)],
            buf.at[slot], sem.at[slot])

    def first_chunk(e):
        aligned = lax.div(run_first[b * held + e], SUBLANE) * SUBLANE
        return jnp.minimum(aligned, last_start)

    def column(ref, e):
        """Column `e` of a [tb, held] block as [tb, 1]: the lane is picked
        by a select and a sum along the lanes, in float32 (a row's index is
        far under 2**24), because the expert is a loop's index and a
        dynamic lane offset is no load the chip has."""
        picked = lax.broadcasted_iota(jnp.int32, ref.shape, 1) == e
        values = ref[...].astype(jnp.float32)
        return lax.expand_dims(lax.reduce_sum(
            lax.select(picked, values, jnp.zeros_like(values)), (1,)), (1,))

    def add(e, start, slot, from_row=None):
        """acc += (weight x) the chunk's rows at the block's tokens whose
        row lies in it, at or after `from_row`."""
        src = column(src_ref, e).astype(jnp.int32)
        at = src - start == lane
        if from_row is not None:
            at = jnp.logical_and(at, src >= from_row)
        got = lax.dot_general(
            at.astype(jnp.float32).astype(buf.dtype), buf[slot],
            (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32)
        acc[...] += column(w_ref, e) * got if weighted else got

    def expert(e, carry):
        slot = lax.rem(e, 2)
        start = first_chunk(e)

        @pl.when(e + 1 < held)
        def _():
            copy(first_chunk(e + 1), 1 - slot).start()

        copy(start, slot).wait()
        end = run_first[b * held + e] + run_len[b * held + e]

        @pl.when(run_len[b * held + e] > 0)
        def _():
            add(e, start, slot)

        def further(j, carry):
            row = start + (j + 1) * chunk       # the first row not yet added
            at = jnp.minimum(row, last_start)
            more = copy(at, 2)
            more.start()
            more.wait()
            add(e, at, 2, from_row=row)
            return carry

        left = jnp.maximum(end - start - chunk, 0)
        return lax.fori_loop(0, lax.div(left + chunk - 1, chunk), further,
                             carry)

    acc[...] = jnp.zeros_like(acc)
    copy(first_chunk(0), 0).start()
    lax.fori_loop(0, held, expert, 0)
    out_ref[...] = acc[...].astype(out_ref.dtype)


def _sum_col_tile(d: int, block: int) -> int:
    """Columns of one block of `moe_token_sum`: all of them where the
    block's float32 sum and the passes over it (the product, the weighted
    product, the result's two buffers: 16 bytes an element) stay within
    TOKEN_SUM_VMEM; else the largest lane multiple that divides them and
    does."""
    room = TOKEN_SUM_VMEM // (16 * block)
    if d <= room or d % LANE:
        return d
    return _col_tile(d, max(room, LANE))


def token_sum_call(rows, plan: RoutingPlan, *, tile: int, weighted: bool,
                   chunk: int | None = None):
    """`moe_token_sum`: rows [M, D] (the buffer; rows of tiles past
    `num_tiles` hold nothing and are not read) -> [T, D] in the rows'
    dtype, token t the float32 sum over the held experts it picked of its
    row there (times `plan.w_held` with `weighted`). ONE kernel a sum: the
    buffer stays in HBM and each grid step copies the chunks its block
    needs; the sum never leaves VMEM before it is whole. `chunk` is the
    tests', to make a short run span several. Counted where it is built:
    `oobleck_moe_token_sum_kernels_total`."""
    from oobleck_tpu.utils import metrics

    num_tokens, held = plan.src_row.shape
    m_rows, d = rows.shape
    blocks = plan.run_first.shape[0] // held
    tb = num_tokens // blocks
    chunk = _sum_chunk(tile) if chunk is None else chunk
    assert blocks * tb == num_tokens and m_rows % tile == 0
    assert tile % chunk == 0 and chunk % SUBLANE == 0, (tile, chunk)
    assert m_rows < 2 ** 24, m_rows     # a row's number, exact in float32
    tn = _sum_col_tile(d, tb)
    metrics.registry().counter(
        "oobleck_moe_token_sum_kernels_total",
        "Sums of the routed experts' rows into their tokens built into "
        "traced programs as one moe_token_sum kernel each").inc()

    by_token = pl.BlockSpec((tb, held), lambda b, n, *_: (b, 0))
    operands = [plan.src_row] + ([plan.w_held] if weighted else []) + [rows]
    item = rows.dtype.itemsize
    # Asked for from the shape: the sum and two float32 passes over it (the
    # product, the weighted product), the result's two buffers, the three
    # chunks, the by-token blocks (two buffers each, padded to a lane
    # tile), and the 16 MiB a kernel has without asking.
    vmem = (tb * tn * (12 + 2 * item) + 3 * chunk * tn * item
            + 4 * tb * LANE * 4 + 16 * 1024 * 1024)
    return pl.pallas_call(
        functools.partial(_token_sum_body, held=held, tile=tile,
                          weighted=weighted),
        out_shape=jax.ShapeDtypeStruct((num_tokens, d), rows.dtype),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,
            grid=(blocks, d // tn),
            in_specs=[by_token] * (len(operands) - 1) + [
                pl.BlockSpec(memory_space=pl.ANY)],
            out_specs=pl.BlockSpec((tb, tn), lambda b, n, *_: (b, n)),
            scratch_shapes=[pltpu.VMEM((tb, tn), jnp.float32),
                            pltpu.VMEM((3, chunk, tn), rows.dtype),
                            pltpu.SemaphoreType.DMA((3,))]),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"),
            vmem_limit_bytes=vmem),
        interpret=kernel.interpret(),
        name="moe_token_sum",
    )(plan.run_first, plan.run_len, plan.num_tiles, *operands)


@jax.tree_util.register_dataclass
@dataclasses.dataclass(frozen=True)
class GradSum:
    """A weight's running gradient sum, handed down to the product that
    reads the weight: `grouped_matmul`'s backward rule then gives `value` +
    dW as the weight's cotangent, written by `moe_tgmm` into `value`'s own
    buffer, and whoever handed it takes that cotangent as the new sum where
    it would have added. `name` says whose sum it is (static: it rides
    through `jax.checkpoint` and the rule's residuals)."""
    value: jax.Array
    name: str = dataclasses.field(metadata=dict(static=True))


_HANDED = threading.local()     # .open: {name: kernel calls that took it}


@contextlib.contextmanager
def handing_sums(names):
    """Around the trace of a differentiated program that hands `GradSum`s
    of these names down. A sum that no `moe_tgmm` call took leaves the
    cotangent without it, and one that two took holds it twice: neither
    shows in a shape, so both fail here, at trace time."""
    assert getattr(_HANDED, "open", None) is None, "handing_sums does not nest"
    _HANDED.open = taken = dict.fromkeys(names, 0)
    try:
        yield
    finally:
        _HANDED.open = None
    wrong = {name: n for name, n in taken.items() if n != 1}
    if wrong:
        raise ValueError(
            "gradient sums handed down and not taken by exactly one "
            f"moe_tgmm call (name: calls): {wrong}")


def _take(dw_sum: GradSum) -> jax.Array:
    taken = getattr(_HANDED, "open", None)
    if taken is None or dw_sum.name not in taken:
        raise ValueError(
            f"gradient sum {dw_sum.name!r} reached moe_tgmm outside "
            "`handing_sums`, or under a name it was not opened with")
    taken[dw_sum.name] += 1
    return dw_sum.value


@functools.partial(jax.custom_vjp, nondiff_argnums=(5,))
def _gmm(lhs, rhs, dw_sum, tile_group, num_tiles, tile):
    del dw_sum      # the backward rule's
    return gmm_call(lhs, rhs, tile_group, num_tiles, tile=tile)


def _gmm_fwd(lhs, rhs, dw_sum, tile_group, num_tiles, tile):
    out = gmm_call(lhs, rhs, tile_group, num_tiles, tile=tile)
    return out, (lhs, rhs, dw_sum, tile_group, num_tiles)


def _gmm_bwd(tile, res, d_out):
    """dX, and dW as `rhs`'s cotangent: on top of `dw_sum` (None: of zeros)
    and in its buffer where one was handed, so the cotangent IS the new
    sum. The sum itself gets no cotangent."""
    lhs, rhs, dw_sum, tile_group, num_tiles = res
    d_lhs = gmm_call(d_out, rhs, tile_group, num_tiles, tile=tile,
                     transpose_rhs=True)
    d_rhs = tgmm_call(lhs, d_out, tile_group, num_tiles, tile=tile,
                      num_groups=rhs.shape[0], out_dtype=rhs.dtype,
                      start=None if dw_sum is None else _take(dw_sum))
    return d_lhs, d_rhs, None, None, None


_gmm.defvjp(_gmm_fwd, _gmm_bwd)


def grouped_matmul(rows, experts, plan: RoutingPlan, tile: int,
                   dw_sum: GradSum | None = None):
    """rows [M, K] x experts [E, K, N] -> [M, N] over the plan's layout.
    The Pallas kernels on a TPU; elsewhere XLA's own ragged product over
    the same padded groups (the kernels' interpreter is for their tests).
    `dw_sum`, `experts`' running gradient sum, is the kernels' alone."""
    if kernel.on_tpu():
        # Under an outer scope a transformation's wrapper (jvp(...),
        # transpose(...)) goes around THAT component of the name stack and
        # the kernels keep their own: `%moe_gmm.N`, not `%jvp_moe_gmm_.N`.
        with jax.named_scope("routed_experts"):
            return _gmm(rows, experts, dw_sum, plan.tile_group,
                        plan.num_tiles, tile)
    assert dw_sum is None, "a gradient sum handed off the kernels' path"
    return lax.ragged_dot(rows, experts.astype(rows.dtype), plan.padded_sizes)


# --------------------------------------------------------------------- #
# rows in and out of the buffer                                          #
# --------------------------------------------------------------------- #
#
# Both directions, forward and backward, are one of two passes over the row
# tiles IN USE, so the work follows the rows routed here and not the
# worst-case buffer. A tile's rows gathered from their tokens: a
# `fori_loop` whose trip count is the plan's `num_tiles`. A whole-buffer
# gather costs the chip about 80 ns a row, used or not: at 8 x 1024 tokens,
# top 4, eight such gathers a layer and microbatch were 40 % of the cell's
# step (my chip run, PR 29). And a token the sum of its rows (`_token_sum`:
# the combine, and the dispatch's dx): on the kernels' path ONE
# `moe_token_sum` call, token-block major, because as a loop of XLA
# scatter-adds into a float32 [tokens, D] array in HBM it walked the rows
# one at a time, 283 ns a row at [16384, 2560], 147 ms of
# `smallthinker-21b-a3b.steady`'s 1,383 ms step (my chip run, PR 51). What
# lets a dense kernel do it is the order inside an expert's region of the
# buffer (`RoutingPlan`): ascending by token, none twice, so a block of
# tokens reads one contiguous run of rows an expert. Elsewhere (the CPU,
# the tests' engines) the sum stays the loop, which is also what the kernel
# is tested against, bit for bit.

def _tile_of(plan: RoutingPlan, i, tile: int, top_k: int):
    """(first row, pairs, tokens, valid) of row tile `i`."""
    pair = lax.dynamic_slice(plan.order, (plan.tile_first[i],), (tile,))
    valid = jnp.arange(tile, dtype=jnp.int32) < plan.tile_rows[i]
    return i * tile, pair, pair // top_k, valid


def _rows_from_tokens(src, plan: RoutingPlan, tile: int, top_k: int,
                      weights=None):
    """[T, D] -> the buffer's rows [M, D]: row r is `src[token of r]`
    (times its pair's weight); rows of a tile in use that hold no pair are
    zero, tiles not in use are not written (`_unwritten`)."""
    rows, d = plan.tile_group.shape[0] * tile, src.shape[1]

    def one_tile(i, out):
        start, pair, token, valid = _tile_of(plan, i, tile, top_k)
        block = src[token]
        if weights is not None:
            block = block.astype(jnp.float32) * weights.reshape(-1)[pair][:, None]
        block = jnp.where(valid[:, None], block, 0).astype(src.dtype)
        return lax.dynamic_update_slice(out, block, (start, 0))

    return lax.fori_loop(0, plan.num_tiles[0], one_tile,
                         _unwritten((rows, d), src.dtype))


def _tokens_from_rows(rows, plan: RoutingPlan, tile: int, top_k: int,
                      num_tokens: int, weights=None):
    """The buffer's rows [M, D] -> [T, D] float32, as a loop of XLA
    scatter-adds over the tiles in use (off the kernels' path, and the
    reference `moe_token_sum` is held to): a token is the sum of its rows
    (times their pairs' weights); a row that holds no pair adds past the
    end and is dropped. (Within a tile the tokens ascend and none repeats,
    but telling the scatter so made it 2.5 x slower on the chip: 1.86
    against 0.76 ms over 20 tiles, my chip run, PR 29.)"""
    d = rows.shape[1]

    def one_tile(i, acc):
        start, pair, token, valid = _tile_of(plan, i, tile, top_k)
        block = lax.dynamic_slice(rows, (start, 0), (tile, d)).astype(
            jnp.float32)
        if weights is not None:
            block = block * weights.reshape(-1)[pair][:, None]
        return acc.at[jnp.where(valid, token, num_tokens)].add(
            block, mode="drop")

    return lax.fori_loop(0, plan.num_tiles[0], one_tile,
                         jnp.zeros((num_tokens, d), jnp.float32))


def _token_sum(rows, plan: RoutingPlan, tile: int, top_k: int,
               num_tokens: int, weights=None):
    """The buffer's rows [M, D] -> [T, D] in their dtype, a token the
    float32 sum of its rows (times their pairs' weights): `moe_token_sum`
    where the plan carries the tokens' side (`token_runs`), else the
    loop."""
    if plan.src_row is None:
        return _tokens_from_rows(rows, plan, tile, top_k, num_tokens,
                                 weights).astype(rows.dtype)
    with jax.named_scope("routed_experts"):     # as `grouped_matmul`'s
        return token_sum_call(rows, plan, tile=tile,
                              weighted=weights is not None)


@functools.partial(jax.custom_vjp, nondiff_argnums=(2, 3))
def _dispatch(x, plan: RoutingPlan, tile: int, top_k: int):
    """x [T, D] -> the buffer's rows [M, D]. Backward: a token's gradient
    is the sum of its rows'."""
    return _rows_from_tokens(x, plan, tile, top_k)


def _dispatch_fwd(x, plan, tile, top_k):
    return _rows_from_tokens(x, plan, tile, top_k), (plan, x.shape[0])


def _dispatch_bwd(tile, top_k, res, d_rows):
    plan, tokens = res
    return _token_sum(d_rows, plan, tile, top_k, tokens), None


_dispatch.defvjp(_dispatch_fwd, _dispatch_bwd)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4))
def _combine(rows, weights, plan: RoutingPlan, tile: int, top_k: int):
    """The buffer's rows [M, D] and the pairs' weights [T, k] (float32)
    -> y [T, D], a token the weighted sum of its rows."""
    return _token_sum(rows, plan, tile, top_k, weights.shape[0], weights)


def _combine_fwd(rows, weights, plan, tile, top_k):
    return (_combine(rows, weights, plan, tile, top_k),
            (rows, weights, plan))


def _combine_bwd(tile, top_k, res, dy):
    """d rows = weight x the token's dy; d weight of a pair = its row .
    its token's dy. One loop over the tiles in use gives both; d rows of
    tiles not in use are not written (`_unwritten`), d weight is a sum and
    starts from zeros."""
    rows, weights, plan = res
    m, d = rows.shape
    flat = weights.reshape(-1)

    def one_tile(i, carry):
        d_rows, d_w = carry
        start, pair, token, valid = _tile_of(plan, i, tile, top_k)
        dy_block = jnp.where(valid[:, None], dy[token], 0).astype(jnp.float32)
        block = lax.dynamic_slice(rows, (start, 0), (tile, d))
        by_row = jnp.sum(
            dy_block * jnp.where(valid[:, None], block, 0).astype(jnp.float32),
            axis=-1)
        d_rows = lax.dynamic_update_slice(
            d_rows, (dy_block * flat[pair][:, None]).astype(rows.dtype),
            (start, 0))
        d_w = d_w.at[jnp.where(valid, pair, flat.shape[0])].add(
            by_row, mode="drop")
        return d_rows, d_w

    d_rows, d_w = lax.fori_loop(
        0, plan.num_tiles[0], one_tile,
        (_unwritten((m, d), rows.dtype), jnp.zeros(flat.shape, jnp.float32)))
    return d_rows, d_w.reshape(weights.shape).astype(weights.dtype), None


_combine.defvjp(_combine_fwd, _combine_bwd)


def _over_tiles(plan: RoutingPlan, tile: int, fn, *operands):
    """`fn` on the row tiles in use of [M, .] operands, into [M, .]
    results that are not written elsewhere (`_unwritten`): elementwise work
    over the buffer follows the rows routed here too."""
    m = operands[0].shape[0]
    shapes = jax.eval_shape(fn, *[
        jax.ShapeDtypeStruct((tile, o.shape[1]), o.dtype) for o in operands])

    def one_tile(i, outs):
        start = i * tile
        blocks = [lax.dynamic_slice(o, (start, 0), (tile, o.shape[1]))
                  for o in operands]
        return tuple(lax.dynamic_update_slice(out, r, (start, 0))
                     for out, r in zip(outs, fn(*blocks)))

    return lax.fori_loop(
        0, plan.num_tiles[0], one_tile,
        tuple(_unwritten((m, sh.shape[1]), sh.dtype) for sh in shapes))


@functools.partial(jax.custom_vjp, nondiff_argnums=(3,))
def _swiglu(gate, up, plan: RoutingPlan, tile: int):
    """silu(gate) * up in float32, over the tiles in use."""
    return _over_tiles(plan, tile, lambda g, u: ((
        jax.nn.silu(g.astype(jnp.float32)) * u.astype(jnp.float32)
    ).astype(g.dtype),), gate, up)[0]


def _swiglu_fwd(gate, up, plan, tile):
    return _swiglu(gate, up, plan, tile), (gate, up, plan)


def _swiglu_bwd(tile, res, d_hidden):
    gate, up, plan = res

    def grads(g, u, d):
        g, u, d = (a.astype(jnp.float32) for a in (g, u, d))
        sig = jax.nn.sigmoid(g)
        d_gate = d * u * sig * (1.0 + g * (1.0 - sig))
        return d_gate.astype(gate.dtype), (d * g * sig).astype(up.dtype)

    d_gate, d_up = _over_tiles(plan, tile, grads, gate, up, d_hidden)
    return d_gate, d_up, None


_swiglu.defvjp(_swiglu_fwd, _swiglu_bwd)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3,))
def _reglu(gate, up, plan: RoutingPlan, tile: int):
    """relu(gate) * up in float32, over the tiles in use."""
    return _over_tiles(plan, tile, lambda g, u: ((
        jax.nn.relu(g.astype(jnp.float32)) * u.astype(jnp.float32)
    ).astype(g.dtype),), gate, up)[0]


def _reglu_fwd(gate, up, plan, tile):
    return _reglu(gate, up, plan, tile), (gate, up, plan)


def _reglu_bwd(tile, res, d_hidden):
    gate, up, plan = res

    def grads(g, u, d):
        g, u, d = (a.astype(jnp.float32) for a in (g, u, d))
        return (jnp.where(g > 0, d * u, 0.0).astype(gate.dtype),
                (d * jax.nn.relu(g)).astype(up.dtype))

    d_gate, d_up = _over_tiles(plan, tile, grads, gate, up, d_hidden)
    return d_gate, d_up, None


_reglu.defvjp(_reglu_fwd, _reglu_bwd)


@functools.partial(jax.custom_vjp, nondiff_argnums=(2,))
def _relu2(pre, plan: RoutingPlan, tile: int):
    """relu(pre)^2 in float32, over the tiles in use: the activation of
    experts without a gate."""
    return _over_tiles(plan, tile, lambda g: (
        jnp.square(jax.nn.relu(g.astype(jnp.float32))).astype(g.dtype),),
        pre)[0]


def _relu2_fwd(pre, plan, tile):
    return _relu2(pre, plan, tile), (pre, plan)


def _relu2_bwd(tile, res, d_hidden):
    pre, plan = res
    (d_pre,) = _over_tiles(plan, tile, lambda g, d: ((
        2.0 * jax.nn.relu(g.astype(jnp.float32)) * d.astype(jnp.float32)
    ).astype(pre.dtype),), pre, d_hidden)
    return d_pre, None


_relu2.defvjp(_relu2_fwd, _relu2_bwd)


@functools.partial(jax.custom_vjp, nondiff_argnums=(5,))
def _gate_and_up(rows, w1, w3, dw_sums, plan: RoutingPlan, tile: int):
    """rows x W1 and rows x W3. One function, so that the two gradients of
    `rows` are added over the tiles in use and not, as autodiff would add
    two cotangents, over the whole buffer. `dw_sums`: (W1's, W3's)."""
    return (grouped_matmul(rows, w1, plan, tile),
            grouped_matmul(rows, w3, plan, tile))


def _gate_and_up_fwd(rows, w1, w3, dw_sums, plan, tile):
    return (_gate_and_up(rows, w1, w3, dw_sums, plan, tile),
            (rows, w1, w3, dw_sums, plan))


def _gate_and_up_bwd(tile, res, cotangents):
    rows, w1, w3, dw_sums, plan = res
    d_rows, pulls = [], []
    for w, dw_sum, d in zip((w1, w3), dw_sums, cotangents):
        _, pull = jax.vjp(
            lambda r, w_: grouped_matmul(r, w_, plan, tile, dw_sum), rows, w)
        d_r, d_w = pull(d)
        d_rows.append(d_r)
        pulls.append(d_w)
    (total,) = _over_tiles(plan, tile, lambda a, b: (a + b,), *d_rows)
    return total, pulls[0], pulls[1], None, None


_gate_and_up.defvjp(_gate_and_up_fwd, _gate_and_up_bwd)


def _count_ungated_call() -> None:
    """`oobleck_moe_ungated_calls_total`: counted where the call is built,
    once a routed layer of every program traced (not once a step)."""
    from oobleck_tpu.utils import metrics

    metrics.registry().counter(
        "oobleck_moe_ungated_calls_total",
        "Routed-expert calls without a gate (W2 relu(W1 x)^2) built into "
        "traced programs").inc()


def _count_softmax_call() -> None:
    """`oobleck_moe_softmax_routed_calls_total`: counted as the ungated
    calls are, once a routed layer of every program traced."""
    from oobleck_tpu.utils import metrics

    metrics.registry().counter(
        "oobleck_moe_softmax_routed_calls_total",
        "Routed-expert calls whose scores are a softmax over all the "
        "experts, built into traced programs").inc()


def _count_reglu_call() -> None:
    """`oobleck_moe_reglu_calls_total`: counted as the ungated calls are."""
    from oobleck_tpu.utils import metrics

    metrics.registry().counter(
        "oobleck_moe_reglu_calls_total",
        "Routed-expert calls whose experts are ReGLU (W2 (relu(W1 x) * "
        "W3 x)) built into traced programs").inc()


def _count_early_router_call() -> None:
    """`oobleck_moe_early_router_calls_total`: counted as the ungated
    calls are."""
    from oobleck_tpu.utils import metrics

    metrics.registry().counter(
        "oobleck_moe_early_router_calls_total",
        "Routed-expert calls whose router reads rows of its own, not the "
        "rows the experts are handed, built into traced programs").inc()


SIGMOID, SOFTMAX = "sigmoid", "softmax"
SWIGLU, REGLU = "swiglu", "reglu"


def route(x, router_w, expert_bias, *, top_k: int,
          norm_topk_prob: bool = True, routed_scaling_factor: float = 1.0,
          forced_experts: jax.Array | None = None, score: str = SIGMOID):
    """Scores in float32 as the family says (`score`): sigmoid, the top-k
    of score + bias, weights from the scores alone, normalised over the
    chosen (+ 1e-6); or a softmax over ALL the experts, its top-k, weights
    from it, normalised over the chosen (their sum is no less than
    k / experts: no epsilon). x [T, D] -> (experts [T, k] int32, weights
    [T, k] float32). The bias selects and is not trained.
    `forced_experts` replaces the selection; the weights still come from
    these scores."""
    assert score in (SIGMOID, SOFTMAX), score
    logits = jnp.dot(x.astype(jnp.float32), router_w.astype(jnp.float32),
                     precision=lax.Precision.HIGHEST)
    if score == SOFTMAX:
        scores = jax.nn.softmax(logits, axis=-1)                 # [T, NE]
    else:
        scores = jax.nn.sigmoid(logits)
    if forced_experts is not None:
        experts = forced_experts
    else:
        chosen = scores
        if expert_bias is not None:
            chosen = scores + lax.stop_gradient(
                expert_bias.astype(jnp.float32))
        _, experts = lax.top_k(chosen, top_k)
    picked = jax.nn.one_hot(experts, scores.shape[-1], dtype=jnp.float32)
    weights = jnp.einsum("tke,te->tk", picked, scores)
    if norm_topk_prob:
        weights = weights / (jnp.sum(weights, axis=-1, keepdims=True)
                             + (0.0 if score == SOFTMAX else 1e-6))
    return experts.astype(jnp.int32), weights * routed_scaling_factor


def routed_experts(
    x: jax.Array,
    router_w: jax.Array,
    expert_bias: jax.Array | None,
    w1: jax.Array,
    w3: jax.Array | None,
    w2: jax.Array,
    *,
    num_experts: int,
    top_k: int,
    expert_offset: int = 0,
    norm_topk_prob: bool = True,
    routed_scaling_factor: float = 1.0,
    forced_experts: jax.Array | None = None,
    return_routing: bool = False,
    dw_sums: tuple[GradSum | None, GradSum | None, GradSum | None] = (
        None, None, None),
    score: str = SIGMOID,
    activation: str = SWIGLU,
    router_x: jax.Array | None = None,
    return_load: bool = False,
):
    """Dropless top-k routed experts (`score`: sigmoid or softmax scores,
    `route`), the part that the experts held here give. Gated experts by
    `activation`, SwiGLU or ReGLU (`W2 (relu(W1 x) * W3 x)`: the XLA
    between the grouped products differs, the kernels do not), or, with
    `w3` None, experts WITHOUT a gate: W2 relu(W1 x)^2, two grouped
    products forward and four backward (`grouped_matmul`'s own dX and dW)
    where a gated expert has three and six. `router_x` [T, D]: the rows
    the router scores, where they are not the rows the experts are handed
    (a router placed before the block's attention); None: `x`.

    x [T, D]; router_w [D, num_experts]; expert_bias [num_experts] or
    None; w1, w3 [held, D, F], w2 [held, F, D]: experts `expert_offset` ..
    `expert_offset + held - 1` of `num_experts`. Every token is routed
    over ALL experts; y [T, D] = sum over the token's picks that are held
    of weight x W2 (silu(W1 x) * W3 x). With all experts held that is the
    whole layer. `forced_experts` [T, k] replaces the selection (the
    weights still come from this call's own scores). With
    `return_routing`, also the chosen experts [T, k]. `dw_sums`: the
    running gradient sums of (w1, w3, w2) that the dW kernels are to add
    to (`GradSum`); their cotangents then come back as sum + dW. With
    `return_load`, last, the call's load (`load_of`): int32 [held + 1],
    each held expert's rows and the row tiles in use."""
    t, d = x.shape
    held = w1.shape[0]
    assert router_w.shape == (d, num_experts), router_w.shape
    assert 0 <= expert_offset and expert_offset + held <= num_experts
    assert activation in (SWIGLU, REGLU), activation
    if router_x is not None:
        assert router_x.shape == x.shape, (router_x.shape, x.shape)
        _count_early_router_call()
    experts, weights = route(
        x if router_x is None else router_x, router_w, expert_bias,
        top_k=top_k, norm_topk_prob=norm_topk_prob,
        routed_scaling_factor=routed_scaling_factor,
        forced_experts=forced_experts, score=score)
    if score == SOFTMAX:
        _count_softmax_call()

    local = experts.reshape(-1) - expert_offset
    local = jnp.where((local >= 0) & (local < held), local, held).astype(
        jnp.int32)
    rows, tile = buffer_rows(t, top_k, held, num_experts)
    plan = plan_routing(local, held, rows, tile)
    if kernel.on_tpu():
        plan = token_runs(plan, local.reshape(t, top_k), weights,
                          choose_token_block(t, top_k, num_experts, tile))

    xs = _dispatch(x, plan, tile, top_k)
    if w3 is None:
        _count_ungated_call()
        hidden = _relu2(grouped_matmul(xs, w1, plan, tile, dw_sums[0]),
                        plan, tile)
    else:
        gate, up = _gate_and_up(xs, w1, w3, dw_sums[:2], plan, tile)
        if activation == REGLU:
            _count_reglu_call()
            hidden = _reglu(gate, up, plan, tile)
        else:
            hidden = _swiglu(gate, up, plan, tile)
    out = grouped_matmul(hidden, w2, plan, tile, dw_sums[2])
    y = _combine(out, weights, plan, tile, top_k)
    extras = ((experts,) if return_routing else ()) + (
        (load_of(plan),) if return_load else ())
    return (y, *extras) if extras else y


def load_of(plan: RoutingPlan) -> jax.Array:
    """What a call's grouped products walked, as ONE small integer array a
    training step can hand out beside its loss: int32 [held + 1], the rows
    that hold a pair of each held expert (`group_sizes`) and, last, the
    row tiles in use (`num_tiles`). Two things the plan already holds: no
    new pass over the tokens. The rows of a tile are static
    (`buffer_rows`) and stay on the host."""
    return jnp.concatenate([plan.group_sizes, plan.num_tiles])
