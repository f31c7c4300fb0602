"""Ragged paged decode attention — block-table KV gather, Pallas + XLA.

The dense serving cache (`[L, slots, H, max_seq, D]`) makes HBM per slot
scale with max_seq and makes every decode step attend over max_seq of
padding. Here K/V live in a pool of fixed-size PAGES (`[N_pages, Hkv,
page, D]` per layer) and each request owns a small chain of pages named
by a block table; decode gathers keys THROUGH the table and masks to the
request's true length (ragged batch — no padding attended, no per-slot
max_seq reservation).

Two implementations behind the `select_attention_impl` seam
(ops/attention.py resolves "paged" to `paged_decode_attention`):

  - XLA reference: gather the table's pages into a contiguous [B, Hkv,
    P*page, D] view and run masked softmax. Shape-identical to the
    kernel output; the correctness oracle for tests.
  - Pallas TPU kernel: the block table and lengths ride as SCALAR
    PREFETCH operands, so each grid step DMAs exactly one live page from
    HBM into VMEM (`BlockSpec` index map reads the table) and the online
    softmax streams pages — the gathered [B, P*page] intermediate never
    exists in HBM. Pages past the request's length are predicated away,
    so a short request costs its true length, not max_seq.

Both support grouped-query caches (Hq a multiple of Hkv: query heads
fold into groups against the unrepeated pool) and ALiBi slopes.
`paged_cache_write` is the matching one-token-per-lane scatter.

Speculative decode adds the MULTI-QUERY verify pair: `paged_verify_attention`
scores T = k+1 candidate positions per lane against the pool in one call
(query row i of lane b sits at absolute position lengths[b]-1+i and
attends keys < lengths[b]+i — masking, GQA folding and ALiBi true
distance identical to decode, of which T=1 is the exact special case),
and `paged_cache_write_multi` is the matching T-token scatter whose
padded rows land on the reserved garbage page.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from oobleck_tpu.ops import kernel
from oobleck_tpu.ops.kernel import LANE

NEG_INF = -1e9


# -- block-table plumbing ------------------------------------------------ #

def paged_gather_kv(pool: jax.Array, block_tables: jax.Array) -> jax.Array:
    """Materialize block-table chains from a page pool.

    pool [N, Hkv, page, D]; block_tables [B, P] int32 -> [B, Hkv, P*page, D]
    (position p*page+i of row b is entry i of page block_tables[b, p]).
    """
    b, p = block_tables.shape
    _, hkv, page, d = pool.shape
    gathered = pool[block_tables]                 # [B, P, Hkv, page, D]
    gathered = gathered.transpose(0, 2, 1, 3, 4)  # [B, Hkv, P, page, D]
    return gathered.reshape(b, hkv, p * page, d)


def paged_cache_write(pool: jax.Array, new: jax.Array,
                      block_tables: jax.Array, pos: jax.Array) -> jax.Array:
    """Write one token's K or V per lane into its block-table page.

    pool [N, Hkv, page, D]; new [B, Hkv, D]; block_tables [B, P]; pos [B]
    (lane b's token sits at logical position pos[b], i.e. page
    block_tables[b, pos[b] // page] offset pos[b] % page). Lanes that
    share a page id (inactive lanes parked on the reserved garbage page)
    scatter in lane order; live lanes never alias by construction.
    Safe to donate."""
    page = pool.shape[2]
    b = new.shape[0]
    page_idx = jnp.take_along_axis(
        block_tables, (pos // page)[:, None], axis=1)[:, 0]    # [B]
    off = pos % page
    return pool.at[page_idx, :, off, :].set(
        new.astype(pool.dtype), mode="drop")


def paged_cache_write_multi(pool: jax.Array, new: jax.Array,
                            block_tables: jax.Array, pos: jax.Array,
                            n_live: jax.Array) -> jax.Array:
    """Write T consecutive tokens' K or V per lane through its block table.

    pool [N, Hkv, page, D]; new [B, T, Hkv, D]; block_tables [B, P];
    pos [B] (absolute position of lane b's FIRST token — token i lands at
    pos[b] + i); n_live [B] (tokens i >= n_live[b] are bucket padding and
    scatter to the reserved garbage page 0 instead). The T=1, n_live=1
    case degenerates to `paged_cache_write`. Safe to donate."""
    page = pool.shape[2]
    b, t = new.shape[0], new.shape[1]
    p = block_tables.shape[1]
    i = jnp.arange(t)[None, :]                                 # [1, T]
    pos_abs = pos[:, None] + i                                 # [B, T]
    page_idx = jnp.take_along_axis(
        block_tables, jnp.clip(pos_abs // page, 0, p - 1), axis=1)
    page_idx = jnp.where(i < n_live[:, None], page_idx, 0)  # garbage page
    off = pos_abs % page
    return pool.at[page_idx.reshape(-1), :, off.reshape(-1), :].set(
        new.reshape(b * t, *new.shape[2:]).astype(pool.dtype), mode="drop")


# -- XLA reference ------------------------------------------------------- #

def _paged_decode_xla(
    q: jax.Array, k_pool: jax.Array, v_pool: jax.Array,
    block_tables: jax.Array, lengths: jax.Array, *,
    scale: float | None = None, alibi_slopes: jax.Array | None = None,
) -> jax.Array:
    """Reference ragged paged decode: gather-then-mask.

    q [B, Hq, D]; pools [N, Hkv, page, D]; block_tables [B, P];
    lengths [B] (keys at positions < lengths[b] are live; the newest
    token's key must already be written, so lengths = pos + 1).
    Returns [B, Hq, D]."""
    b, hq, d = q.shape
    hkv = k_pool.shape[1]
    if scale is None:
        scale = d**-0.5
    g = hq // hkv
    k = paged_gather_kv(k_pool, block_tables)     # [B, Hkv, S, D]
    v = paged_gather_kv(v_pool, block_tables)
    s_len = k.shape[2]
    qg = q.reshape(b, hkv, g, d)
    logits = jnp.einsum("bkgd,bksd->bkgs", qg, k) * scale
    k_idx = jnp.arange(s_len)
    if alibi_slopes is not None:
        dist = ((lengths[:, None] - 1) - k_idx[None, :]).astype(jnp.float32)
        slopes = alibi_slopes.reshape(hkv, g)
        logits = logits - slopes[None, :, :, None] * dist[:, None, None, :]
    live = k_idx[None, :] < lengths[:, None]      # [B, S]
    logits = jnp.where(live[:, None, None, :], logits, NEG_INF)
    probs = jax.nn.softmax(logits.astype(jnp.float32), axis=-1).astype(q.dtype)
    return jnp.einsum("bkgs,bksd->bkgd", probs, v).reshape(b, hq, d)


def _paged_verify_xla(
    q: jax.Array, k_pool: jax.Array, v_pool: jax.Array,
    block_tables: jax.Array, lengths: jax.Array, *,
    scale: float | None = None, alibi_slopes: jax.Array | None = None,
) -> jax.Array:
    """Reference ragged multi-query verify: gather-then-mask.

    q [B, T, Hq, D] (T = k+1 speculative positions per lane; all T
    tokens' keys must already be written); pools [N, Hkv, page, D];
    block_tables [B, P]; lengths [B] (live keys for query row 0 — row i
    attends keys at positions < lengths[b] + i, so each draft token sees
    exactly the prefix a sequential decode would have). Returns
    [B, T, Hq, D]; row 0 is bit-compatible with `_paged_decode_xla`."""
    b, t, hq, d = q.shape
    hkv = k_pool.shape[1]
    if scale is None:
        scale = d**-0.5
    g = hq // hkv
    k = paged_gather_kv(k_pool, block_tables)     # [B, Hkv, S, D]
    v = paged_gather_kv(v_pool, block_tables)
    s_len = k.shape[2]
    qg = q.reshape(b, t, hkv, g, d).transpose(0, 2, 3, 1, 4)  # [B,Hkv,G,T,D]
    logits = jnp.einsum("bkgtd,bksd->bkgts", qg, k) * scale
    k_idx = jnp.arange(s_len)
    row_len = lengths[:, None] + jnp.arange(t)[None, :]        # [B, T]
    if alibi_slopes is not None:
        dist = ((row_len[:, :, None] - 1)
                - k_idx[None, None, :]).astype(jnp.float32)    # [B, T, S]
        slopes = alibi_slopes.reshape(hkv, g)
        logits = logits - slopes[None, :, :, None, None] * dist[:, None, None]
    live = k_idx[None, None, :] < row_len[:, :, None]          # [B, T, S]
    logits = jnp.where(live[:, None, None], logits, NEG_INF)
    probs = jax.nn.softmax(logits.astype(jnp.float32), axis=-1).astype(q.dtype)
    out = jnp.einsum("bkgts,bksd->bkgtd", probs, v)            # [B,Hkv,G,T,D]
    return out.transpose(0, 3, 1, 2, 4).reshape(b, t, hq, d)


# -- Pallas kernel ------------------------------------------------------- #

def _paged_kernel(bt_ref, len_ref, q_ref, k_ref, v_ref, *rest,
                  scale: float, pages: int, page: int, has_slopes: bool):
    """One (lane, kv-head, page) grid step of the streamed decode.

    Scalar-prefetch refs first (block table, lengths), then the VMEM
    blocks. Scratch carries the online-softmax state across the page
    axis (innermost, sequential)."""
    rest = list(rest)
    slope_ref = rest.pop(0) if has_slopes else None
    o_ref = rest.pop(0)
    acc_ref, m_ref, l_ref = rest
    b = pl.program_id(0)
    p = pl.program_id(2)
    length = len_ref[b]

    @pl.when(p == 0)
    def _():
        acc_ref[:] = jnp.zeros_like(acc_ref)
        m_ref[:] = jnp.full_like(m_ref, NEG_INF)
        l_ref[:] = jnp.zeros_like(l_ref)

    # Pages wholly past the live length contribute nothing — predicate
    # the DMA'd block's compute away so a short request costs its true
    # length. (The ragged win: no max_seq of padding in the loop.)
    @pl.when(p * page < length)
    def _():
        qg = q_ref[0, 0]                           # [G, D] native dtype
        k = k_ref[0, 0]                            # [page, D]
        v = v_ref[0, 0]
        s = jax.lax.dot_general(
            qg, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * scale   # [G, page] f32
        k_pos = p * page + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
        if slope_ref is not None:
            dist = ((length - 1) - k_pos).astype(jnp.float32)
            s = s - slope_ref[0, :, :1] * dist
        s = jnp.where(k_pos < length, s, NEG_INF)

        m_prev = m_ref[:, :1]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
        pexp = jnp.exp(s - m_new)
        corr = jnp.exp(m_prev - m_new)
        l_ref[:] = jnp.broadcast_to(
            l_ref[:, :1] * corr + jnp.sum(pexp, axis=-1, keepdims=True),
            l_ref.shape)
        acc_ref[:] = acc_ref[:] * corr + jax.lax.dot_general(
            pexp.astype(v.dtype), v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        m_ref[:] = jnp.broadcast_to(m_new, m_ref.shape)

    @pl.when(p == pages - 1)
    def _():
        # Inactive lanes (length 0) never accumulate; guard the divide.
        l = jnp.maximum(l_ref[:, :1], 1e-30)
        o_ref[0, 0] = (acc_ref[:] / l).astype(o_ref.dtype)


def _paged_decode_pallas(
    q: jax.Array, k_pool: jax.Array, v_pool: jax.Array,
    block_tables: jax.Array, lengths: jax.Array, *,
    scale: float | None = None, alibi_slopes: jax.Array | None = None,
) -> jax.Array:
    """Streamed ragged paged decode (see module docstring). Same contract
    as `_paged_decode_xla`."""
    b, hq, d = q.shape
    n, hkv, page, _ = k_pool.shape
    pages = block_tables.shape[1]
    if scale is None:
        scale = d**-0.5
    g = hq // hkv
    d_pad = (LANE - d % LANE) % LANE
    if d_pad:
        pad4 = ((0, 0), (0, 0), (0, 0), (0, d_pad))
        k_pool = jnp.pad(k_pool, pad4)
        v_pool = jnp.pad(v_pool, pad4)
        q = jnp.pad(q, ((0, 0), (0, 0), (0, d_pad)))
    dp = d + d_pad
    qg = q.reshape(b, hkv, g, dp)
    has_slopes = alibi_slopes is not None

    in_specs = [
        pl.BlockSpec((1, 1, g, dp), lambda bi, h, p, bt, ln: (bi, h, 0, 0)),
        # The block table IS the index map: page p of lane bi comes from
        # pool row bt[bi, p] — the gather never materializes in HBM.
        pl.BlockSpec((1, 1, page, dp),
                     lambda bi, h, p, bt, ln: (bt[bi, p], h, 0, 0)),
        pl.BlockSpec((1, 1, page, dp),
                     lambda bi, h, p, bt, ln: (bt[bi, p], h, 0, 0)),
    ]
    operands = [qg, k_pool, v_pool]
    if has_slopes:
        slopes = jnp.asarray(alibi_slopes, jnp.float32).reshape(hkv, g, 1)
        in_specs.append(
            pl.BlockSpec((1, g, 1), lambda bi, h, p, bt, ln: (h, 0, 0)))
        operands.append(slopes)

    # k/v blocks arrive [1, page, dp] (head dim collapsed by the block
    # shape's leading 1s — Pallas drops size-1 block dims only when the
    # BlockSpec says so; keep explicit [1, ...] and index [0] in-kernel).
    body = functools.partial(
        _paged_kernel, scale=scale, pages=pages, page=page,
        has_slopes=has_slopes)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(b, hkv, pages),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((1, 1, g, dp),
                               lambda bi, h, p, bt, ln: (bi, h, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((g, dp), jnp.float32),
            pltpu.VMEM((g, LANE), jnp.float32),
            pltpu.VMEM((g, LANE), jnp.float32),
        ],
    )
    out = pl.pallas_call(
        body,
        grid_spec=grid_spec,
        out_shape=kernel.out_struct((b, hkv, g, dp), q.dtype, *operands),
        interpret=kernel.interpret(),
        name="paged_decode",
    )(jnp.asarray(block_tables, jnp.int32), jnp.asarray(lengths, jnp.int32),
      *operands)
    return out.reshape(b, hq, dp)[:, :, :d]


def _paged_verify_kernel(bt_ref, len_ref, q_ref, k_ref, v_ref, *rest,
                         scale: float, pages: int, page: int, t: int,
                         g: int, has_slopes: bool):
    """One (lane, kv-head, page) grid step of the streamed multi-query
    verify. Identical structure to `_paged_kernel`, but the q block
    carries T*G rows (T speculative positions x G grouped query heads)
    and the causal bound is PER ROW: row r's query position is
    length - 1 + r // G, so its live-key bound is length + r // G."""
    rest = list(rest)
    slope_ref = rest.pop(0) if has_slopes else None
    o_ref = rest.pop(0)
    acc_ref, m_ref, l_ref = rest
    b = pl.program_id(0)
    p = pl.program_id(2)
    length = len_ref[b]

    @pl.when(p == 0)
    def _():
        acc_ref[:] = jnp.zeros_like(acc_ref)
        m_ref[:] = jnp.full_like(m_ref, NEG_INF)
        l_ref[:] = jnp.zeros_like(l_ref)

    # A page is live iff ANY row can see it — the deepest row (t-1)
    # bounds the predicate; rows that see less mask per-element below.
    @pl.when(p * page < length + t - 1)
    def _():
        qg = q_ref[0, 0]                           # [T*G, D] native dtype
        k = k_ref[0, 0]                            # [page, D]
        v = v_ref[0, 0]
        s = jax.lax.dot_general(
            qg, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * scale   # [T*G, page] f32
        k_pos = p * page + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
        row_len = length + jax.lax.broadcasted_iota(jnp.int32, s.shape, 0) // g
        if slope_ref is not None:
            dist = (row_len - 1 - k_pos).astype(jnp.float32)
            s = s - slope_ref[0, :, :1] * dist
        s = jnp.where(k_pos < row_len, s, NEG_INF)

        m_prev = m_ref[:, :1]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
        pexp = jnp.exp(s - m_new)
        corr = jnp.exp(m_prev - m_new)
        l_ref[:] = jnp.broadcast_to(
            l_ref[:, :1] * corr + jnp.sum(pexp, axis=-1, keepdims=True),
            l_ref.shape)
        acc_ref[:] = acc_ref[:] * corr + jax.lax.dot_general(
            pexp.astype(v.dtype), v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        m_ref[:] = jnp.broadcast_to(m_new, m_ref.shape)

    @pl.when(p == pages - 1)
    def _():
        l = jnp.maximum(l_ref[:, :1], 1e-30)
        o_ref[0, 0] = (acc_ref[:] / l).astype(o_ref.dtype)


def _paged_verify_pallas(
    q: jax.Array, k_pool: jax.Array, v_pool: jax.Array,
    block_tables: jax.Array, lengths: jax.Array, *,
    scale: float | None = None, alibi_slopes: jax.Array | None = None,
) -> jax.Array:
    """Streamed ragged multi-query verify. Same contract as
    `_paged_verify_xla`."""
    b, t, hq, d = q.shape
    n, hkv, page, _ = k_pool.shape
    pages = block_tables.shape[1]
    if scale is None:
        scale = d**-0.5
    g = hq // hkv
    d_pad = (LANE - d % LANE) % LANE
    if d_pad:
        pad4 = ((0, 0), (0, 0), (0, 0), (0, d_pad))
        k_pool = jnp.pad(k_pool, pad4)
        v_pool = jnp.pad(v_pool, pad4)
        q = jnp.pad(q, ((0, 0), (0, 0), (0, 0), (0, d_pad)))
    dp = d + d_pad
    # Rows ordered (position, group): row r = i*G + gi.
    qg = q.reshape(b, t, hkv, g, dp).transpose(0, 2, 1, 3, 4)
    qg = qg.reshape(b, hkv, t * g, dp)
    has_slopes = alibi_slopes is not None

    in_specs = [
        pl.BlockSpec((1, 1, t * g, dp), lambda bi, h, p, bt, ln: (bi, h, 0, 0)),
        pl.BlockSpec((1, 1, page, dp),
                     lambda bi, h, p, bt, ln: (bt[bi, p], h, 0, 0)),
        pl.BlockSpec((1, 1, page, dp),
                     lambda bi, h, p, bt, ln: (bt[bi, p], h, 0, 0)),
    ]
    operands = [qg, k_pool, v_pool]
    if has_slopes:
        # Row r's slope is slopes[r % G] — tile the [Hkv, G] groups T times.
        slopes = jnp.asarray(alibi_slopes, jnp.float32).reshape(hkv, g, 1)
        slopes = jnp.tile(slopes, (1, t, 1))               # [Hkv, T*G, 1]
        in_specs.append(
            pl.BlockSpec((1, t * g, 1), lambda bi, h, p, bt, ln: (h, 0, 0)))
        operands.append(slopes)

    body = functools.partial(
        _paged_verify_kernel, scale=scale, pages=pages, page=page, t=t, g=g,
        has_slopes=has_slopes)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(b, hkv, pages),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((1, 1, t * g, dp),
                               lambda bi, h, p, bt, ln: (bi, h, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((t * g, dp), jnp.float32),
            pltpu.VMEM((t * g, LANE), jnp.float32),
            pltpu.VMEM((t * g, LANE), jnp.float32),
        ],
    )
    out = pl.pallas_call(
        body,
        grid_spec=grid_spec,
        out_shape=kernel.out_struct((b, hkv, t * g, dp), q.dtype, *operands),
        interpret=kernel.interpret(),
        name="paged_verify",
    )(jnp.asarray(block_tables, jnp.int32), jnp.asarray(lengths, jnp.int32),
      *operands)
    out = out.reshape(b, hkv, t, g, dp).transpose(0, 2, 1, 3, 4)
    return out.reshape(b, t, hq, dp)[:, :, :, :d]


# -- dispatch ------------------------------------------------------------ #

@functools.cache
def _select_paged_impl(impl: str = "auto"):
    if impl == "xla":
        return _paged_decode_xla
    if impl == "pallas":
        return _paged_decode_pallas
    if impl == "auto":
        # Same policy as select_attention_impl("auto"): the Pallas kernel
        # on TPU (streamed pages, no HBM gather), the fused XLA gather on
        # CPU where the kernel would run interpreted.
        if kernel.on_tpu():
            return _paged_decode_pallas
        return _paged_decode_xla
    raise ValueError(f"unknown paged attention impl: {impl!r}")


def paged_decode_attention(
    q: jax.Array, k_pool: jax.Array, v_pool: jax.Array,
    block_tables: jax.Array, lengths: jax.Array, *,
    scale: float | None = None, alibi_slopes: jax.Array | None = None,
    impl: str = "auto",
) -> jax.Array:
    """Ragged paged decode attention (dispatching entry point).

    q [B, Hq, D]; k_pool/v_pool [N, Hkv, page, D]; block_tables [B, P]
    int32; lengths [B] int32 (live keys per lane; 0 = inactive lane,
    which computes garbage harmlessly). Grouped-query pools fold query
    heads into [Hkv, G] groups. Returns [B, Hq, D]."""
    hq, hkv = q.shape[1], k_pool.shape[1]
    if hq % hkv:
        raise ValueError(f"query heads {hq} not a multiple of KV heads {hkv}")
    if alibi_slopes is not None and alibi_slopes.shape != (hq,):
        raise ValueError(
            f"alibi_slopes must be [Hq]={hq}, got {alibi_slopes.shape}")
    fn = _select_paged_impl(impl)
    return fn(q, k_pool, v_pool, block_tables, lengths, scale=scale,
              alibi_slopes=alibi_slopes)


@functools.cache
def _select_paged_verify_impl(impl: str = "auto"):
    if impl == "xla":
        return _paged_verify_xla
    if impl == "pallas":
        return _paged_verify_pallas
    if impl == "auto":
        if kernel.on_tpu():
            return _paged_verify_pallas
        return _paged_verify_xla
    raise ValueError(f"unknown paged attention impl: {impl!r}")


def paged_verify_attention(
    q: jax.Array, k_pool: jax.Array, v_pool: jax.Array,
    block_tables: jax.Array, lengths: jax.Array, *,
    scale: float | None = None, alibi_slopes: jax.Array | None = None,
    impl: str = "auto",
) -> jax.Array:
    """Ragged multi-query speculative verify (dispatching entry point).

    q [B, T, Hq, D] — T = k+1 candidate positions per lane, all of whose
    K/V must already be written (`paged_cache_write_multi`); lengths [B]
    int32 is the live-key count for query row 0 (= row 0's position + 1),
    and row i attends keys < lengths[b] + i — the exact prefix a
    sequential decode of the accepted tokens would see. Lanes with fewer
    live candidates than T compute garbage in their padded rows
    harmlessly (their writes landed on the garbage page). T=1 is
    `paged_decode_attention` exactly. Returns [B, T, Hq, D]."""
    if q.ndim != 4:
        raise ValueError(f"verify q must be [B, T, Hq, D], got {q.shape}")
    hq, hkv = q.shape[2], k_pool.shape[1]
    if hq % hkv:
        raise ValueError(f"query heads {hq} not a multiple of KV heads {hkv}")
    if alibi_slopes is not None and alibi_slopes.shape != (hq,):
        raise ValueError(
            f"alibi_slopes must be [Hq]={hq}, got {alibi_slopes.shape}")
    fn = _select_paged_verify_impl(impl)
    return fn(q, k_pool, v_pool, block_tables, lengths, scale=scale,
              alibi_slopes=alibi_slopes)
