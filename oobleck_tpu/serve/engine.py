"""Decode engines: jitted prefill/decode over paged or dense-slot KV state.

An engine owns the device-side serving state for one model: the current
weights (swappable between decode steps), the KV cache, and the compiled
prefill/decode executables. The serving plane builds one kind:

  PagedDecodeEngine  block/paged — `[L, N_pages, Hkv, page, D]` pool,
                     per-request page chains (serve/kv_blocks.py), ragged
                     paged attention (ops/paged_attention.py), prefix
                     reuse. HBM per request is its true token span, so
                     concurrency is bounded by total live tokens, not by
                     a handful of max_seq reservations.
  DecodeEngine       dense slots — `[L, slots, H, max_seq, D]`, HBM per
                     slot scales with max_seq regardless of actual
                     lengths. Nothing serves from it: it is the reference
                     the paged engine's tests compare against.

Prompt lengths are padded to a small set of power-of-two buckets so the
number of distinct prefill programs is O(log max_seq) instead of one per
prompt length; both program families route through the persistent
compilation cache (`utils/compile_cache.ensure_persistent_cache`) so a
server cold-start on an accelerator deserializes instead of recompiling. The paged engine
additionally buckets cached-head page counts (prefix hits) the same way;
head-bucket programs compile lazily on first hit and persist like the
rest.

All engine methods must be called from ONE thread (the batcher's): the
jitted calls donate the cache buffers, so a concurrent caller would race
on an invalidated buffer. Weight STAGING (host->device) is the exception
— `stage_params` is thread-safe and runs on the reload watcher so the
batcher-side swap is a pointer assignment.
"""

from __future__ import annotations

import logging
import threading

import jax
import jax.numpy as jnp
import numpy as np

from oobleck_tpu.serve.kv_blocks import (
    GARBAGE_PAGE,
    BlockAllocator,
    PagesExhausted,
    pages_for,
)
from oobleck_tpu.utils import metrics
from oobleck_tpu.utils.compile_cache import ensure_persistent_cache

logger = logging.getLogger("oobleck.serve")


def default_prefill_buckets(max_seq: int, smallest: int = 16) -> tuple[int, ...]:
    """Power-of-two prompt-length buckets up to max_seq."""
    out = []
    b = min(smallest, max_seq)
    while b < max_seq:
        out.append(b)
        b *= 2
    out.append(max_seq)
    return tuple(out)


class _EngineBase:
    """Weights + compile-cache plumbing shared by both cache disciplines."""

    def __init__(self, model, *, max_seq: int,
                 prefill_buckets: tuple[int, ...] | None = None):
        self.model = model
        self.max_seq = int(max_seq)
        if max_seq > model.config.max_position_embeddings:
            raise ValueError(
                f"max_seq {max_seq} exceeds the model's "
                f"max_position_embeddings {model.config.max_position_embeddings}")
        self.prefill_buckets = tuple(sorted(
            prefill_buckets or default_prefill_buckets(self.max_seq)))
        if self.prefill_buckets[-1] > self.max_seq:
            raise ValueError("prefill bucket exceeds max_seq")

        self.compile_cache_dir = ensure_persistent_cache()
        if self.compile_cache_dir is not None:
            # Decode programs are tiny and compile fast; the default
            # min-compile-time threshold would skip persisting them, and a
            # server cold-start wants ALL its programs served from cache.
            jax.config.update(
                "jax_persistent_cache_min_compile_time_secs", 0.0)

        self.params = None          # device-resident fused tree
        self.params_step: int = -1  # checkpoint step the weights came from
        self._stage_lock = threading.Lock()

    # -- weights -------------------------------------------------------- #

    def stage_params(self, host_params):
        """Host checkpoint tree -> device tree, blocking until resident.

        Thread-safe; called by the reload watcher so the expensive
        host->device copy happens OFF the decode thread and the batcher's
        swap is a reference assignment."""
        with self._stage_lock:
            staged = jax.device_put(
                jax.tree.map(jnp.asarray, host_params))
            jax.block_until_ready(staged)
            return staged

    def set_params(self, device_params, step: int) -> None:
        """Swap the served weights (decode-step barrier: the batcher calls
        this between decode steps, never mid-step). In-flight requests
        keep their KV cache — entries computed under the old weights mix
        with new-weight queries, the standard continuous-serving
        tradeoff; the alternative (drop + re-prefill) violates the
        zero-dropped-requests contract."""
        self.params = device_params
        self.params_step = int(step)

    def bucket_for(self, n: int) -> int | None:
        for b in self.prefill_buckets:
            if n <= b:
                return b
        return None


class DecodeEngine(_EngineBase):
    """Dense-slot state: weights + slot KV cache + compiled steps. The
    reference that the tests of the paged engine, of the prefill kernels
    and of the programs' names compare against, and nothing else: the
    serving plane builds `PagedDecodeEngine` only."""

    def __init__(self, model, *, slots: int, max_seq: int,
                 prefill_buckets: tuple[int, ...] | None = None):
        super().__init__(model, max_seq=max_seq,
                         prefill_buckets=prefill_buckets)
        self.slots = int(slots)
        self.cache = model.init_kv_cache(self.slots, self.max_seq)

        # argnums: 0=params, 1=cache (donated), rest per call.
        def decode_step(p, cache, token, pos):
            return model.forward_decode(p, token, cache, pos)

        def prefill(p, cache, tokens, slot, length):
            return model.forward_prefill(p, tokens, cache, slot, length)

        self._decode_fn = jax.jit(decode_step, donate_argnums=(1,))
        self._prefill_fn = jax.jit(prefill, donate_argnums=(1,))

    def warmup(self) -> int:
        """Compile the decode step and every prefill bucket up front (cold
        starts pay compiles at startup, not on the first request). Returns
        the number of programs compiled. Requires weights."""
        assert self.params is not None, "set_params before warmup"
        n = 0
        for b in self.prefill_buckets:
            tokens = jnp.zeros((1, b), jnp.int32)
            logits, self.cache = jax.block_until_ready(self._prefill_fn(
                self.params, self.cache, tokens, jnp.int32(0), jnp.int32(1)))
            n += 1
        token = jnp.zeros((self.slots,), jnp.int32)
        pos = jnp.zeros((self.slots,), jnp.int32)
        logits, self.cache = jax.block_until_ready(
            self._decode_fn(self.params, self.cache, token, pos))
        n += 1
        logger.info("serve warmup: %d programs (buckets %s), cache dir %s",
                    n, self.prefill_buckets, self.compile_cache_dir)
        return n

    # -- steps (batcher thread only) ------------------------------------ #

    def prefill(self, tokens: list[int], slot: int) -> np.ndarray:
        """Run one request's prompt into `slot`; returns next-token logits
        [V] as a host array."""
        n = len(tokens)
        b = self.bucket_for(n)
        if b is None:
            raise ValueError(f"prompt length {n} exceeds max_seq {self.max_seq}")
        padded = np.zeros((1, b), np.int32)
        padded[0, :n] = tokens
        logits, self.cache = self._prefill_fn(
            self.params, self.cache, jnp.asarray(padded),
            jnp.int32(slot), jnp.int32(n))
        return np.asarray(logits)

    def decode(self, token: np.ndarray, pos: np.ndarray) -> np.ndarray:
        """One decode step over ALL slots (inactive slots compute garbage
        harmlessly); returns logits [slots, V] on host."""
        logits, self.cache = self._decode_fn(
            self.params, self.cache,
            jnp.asarray(token, jnp.int32), jnp.asarray(pos, jnp.int32))
        return np.asarray(logits)


def default_head_buckets(max_pages: int) -> tuple[int, ...]:
    """Power-of-two cached-head page-count buckets: one jitted tail-prefill
    program per (tail bucket, head bucket) pair actually seen."""
    out = [1]
    while out[-1] < max_pages:
        out.append(min(out[-1] * 2, max_pages))
    return tuple(dict.fromkeys(out))


class PagedDecodeEngine(_EngineBase):
    """Paged serving state: page pool + block tables + prefix reuse.

    `lanes` is the decode batch width (the analogue of dense `slots`, but
    cheap: a lane is two int arrays, not a max_seq KV reservation), exposed
    as `.slots` so the batcher drives both engines identically. Admission
    capacity is PAGES: `can_admit` answers whether a request's full token
    span (prompt + max_tokens, minus its cached prefix) fits the pool, and
    `release` returns a finished request's pages immediately."""

    def __init__(self, model, *, lanes: int, max_seq: int,
                 page_size: int = 16, num_pages: int = 0,
                 prefill_buckets: tuple[int, ...] | None = None):
        super().__init__(model, max_seq=max_seq,
                         prefill_buckets=prefill_buckets)
        self.page_size = int(page_size)
        if num_pages <= 0:
            raise ValueError("num_pages must be explicit and positive")
        self.num_pages = int(num_pages)
        self.slots = self.lanes = int(lanes)
        self.table_pages = pages_for(self.max_seq, self.page_size)
        self.head_buckets = default_head_buckets(self.table_pages)

        self.allocator = BlockAllocator(self.num_pages, self.page_size)
        self.cache = model.init_paged_kv_cache(self.num_pages, self.page_size)
        # Host-side lane state; device tables rebuilt per call (tiny int32).
        self.tables = np.full((self.lanes, self.table_pages), GARBAGE_PAGE,
                              np.int32)
        self._lane_pages: list[list[int]] = [[] for _ in range(self.lanes)]

        def decode_step(p, cache, token, tables, pos):
            return model.forward_decode_paged(p, token, cache, tables, pos)

        def prefill(p, cache, tokens, tables, length):
            return model.forward_prefill_paged(p, tokens, cache, tables,
                                               length)

        def prefill_tail(p, cache, tokens, tables, length, head, prior):
            return model.forward_prefill_paged(
                p, tokens, cache, tables, length,
                head_tables=head, prior_len=prior)

        self._decode_fn = jax.jit(decode_step, donate_argnums=(1,))
        # One callable; jit retraces per (tail bucket, head bucket) shape
        # pair. head_tables=None (shape-free) is the no-hit fast path.
        self._prefill_fn = jax.jit(prefill, donate_argnums=(1,))
        self._prefill_head_fn = jax.jit(prefill_tail, donate_argnums=(1,))

        reg = metrics.registry()
        self.m_pages_in_use = reg.gauge(
            "oobleck_serve_kv_pages_in_use", "KV pool pages owned by requests")
        self.m_pages_free = reg.gauge(
            "oobleck_serve_kv_pages_free", "KV pool pages on the free list")
        self.m_prefix_hits = reg.counter(
            "oobleck_serve_prefix_hits_total",
            "Prefills that reused at least one cached prefix page")
        self.m_prompt_tokens = reg.counter(
            "oobleck_serve_prompt_tokens_total", "Prompt tokens admitted")
        self.m_cached_tokens = reg.counter(
            "oobleck_serve_prefix_cached_tokens_total",
            "Prompt tokens served from cached prefix pages (prefill skipped)")
        self._set_page_gauges()

    def _set_page_gauges(self) -> None:
        self.m_pages_in_use.set(self.allocator.pages_in_use)
        self.m_pages_free.set(self.allocator.free_pages)

    # -- admission capacity (batcher thread only) ------------------------ #

    def can_admit(self, tokens: list[int], max_tokens: int) -> bool:
        """Whether prompt + max_tokens fits the pool right now, net of the
        request's cached prefix. Single-threaded with prefill, so a True
        answer cannot be raced stale."""
        need = pages_for(len(tokens) + max_tokens, self.page_size)
        need -= self.allocator.peek_prefix(tokens) // self.page_size
        return self.allocator.can_allocate(need)

    def release(self, lane: int) -> None:
        """Return a finished request's pages (refcounted: pages shared with
        a live prefix stay resident). Incremental — runs per finish, not
        per batch."""
        if self._lane_pages[lane]:
            self.allocator.release(self._lane_pages[lane])
            self._lane_pages[lane] = []
        self.tables[lane] = GARBAGE_PAGE
        self._set_page_gauges()

    def _head_bucket(self, n: int) -> int:
        for b in self.head_buckets:
            if n <= b:
                return b
        raise ValueError(f"cached head of {n} pages exceeds table "
                         f"{self.table_pages}")

    # -- steps (batcher thread only) ------------------------------------ #

    def warmup(self) -> int:
        """Compile the decode step, every no-hit prefill bucket, and the
        smallest prefix-hit variant. Remaining (tail, head) pairs compile
        lazily on first hit and persist like the rest. Requires weights."""
        assert self.params is not None, "set_params before warmup"
        n = 0
        tables = jnp.zeros((self.table_pages,), jnp.int32)
        for b in self.prefill_buckets:
            tokens = jnp.zeros((1, b), jnp.int32)
            logits, self.cache = jax.block_until_ready(self._prefill_fn(
                self.params, self.cache, tokens, tables, jnp.int32(1)))
            n += 1
        head = jnp.zeros((self.head_buckets[0],), jnp.int32)
        tokens = jnp.zeros((1, self.prefill_buckets[0]), jnp.int32)
        logits, self.cache = jax.block_until_ready(self._prefill_head_fn(
            self.params, self.cache, tokens, tables, jnp.int32(1),
            head, jnp.int32(0)))
        n += 1
        token = np.zeros((self.lanes,), np.int32)
        pos = np.zeros((self.lanes,), np.int32)
        logits, self.cache = jax.block_until_ready(self._decode_fn(
            self.params, self.cache, jnp.asarray(token),
            jnp.asarray(self.tables), jnp.asarray(pos)))
        n += 1
        logger.info(
            "paged serve warmup: %d programs (buckets %s, head buckets %s, "
            "%d pages x %d), cache dir %s", n, self.prefill_buckets,
            self.head_buckets, self.num_pages, self.page_size,
            self.compile_cache_dir)
        return n

    def prefill(self, tokens: list[int], lane: int, *,
                max_tokens: int = 0) -> np.ndarray:
        """Admit one request into `lane`: match its cached prefix, reserve
        pages for its full span, prefill only the uncached tail, and
        register the prompt's full pages for future reuse. Returns
        next-token logits [V] on host. Raises PagesExhausted (allocation
        untouched) when the pool cannot hold the span — callers gate on
        `can_admit` so this is a defensive backstop."""
        n = len(tokens)
        head_pages, cached_len = self.allocator.match_prefix(tokens)
        tail = tokens[cached_len:]
        b = self.bucket_for(len(tail))
        if b is None:
            self.allocator.release(head_pages)
            raise ValueError(f"prompt length {n} exceeds max_seq {self.max_seq}")
        try:
            fresh = self.allocator.allocate(
                pages_for(n + max_tokens, self.page_size) - len(head_pages))
        except PagesExhausted:
            self.allocator.release(head_pages)
            raise
        table = head_pages + fresh

        self.m_prompt_tokens.inc(n)
        if cached_len:
            self.m_prefix_hits.inc()
            self.m_cached_tokens.inc(cached_len)
        # Defensive CoW: the first tail write lands on the first fresh page
        # (cached_len is page-aligned), so shared pages are never written in
        # the natural flow — but if that invariant ever breaks, copy rather
        # than corrupt a neighbor's prefix.
        moved = self.allocator.make_writable(
            table, cached_len // self.page_size)
        if moved is not None:
            src, dst = moved
            self.cache = {
                "k": self.cache["k"].at[:, dst].set(self.cache["k"][:, src]),
                "v": self.cache["v"].at[:, dst].set(self.cache["v"][:, src]),
            }

        padded = np.zeros((1, b), np.int32)
        padded[0, :len(tail)] = tail
        dev_table = np.full((self.table_pages,), GARBAGE_PAGE, np.int32)
        dev_table[:len(table)] = table
        if cached_len:
            hb = self._head_bucket(len(head_pages))
            head = np.full((hb,), GARBAGE_PAGE, np.int32)
            head[:len(head_pages)] = head_pages
            logits, self.cache = self._prefill_head_fn(
                self.params, self.cache, jnp.asarray(padded),
                jnp.asarray(dev_table), jnp.int32(len(tail)),
                jnp.asarray(head), jnp.int32(cached_len))
        else:
            logits, self.cache = self._prefill_fn(
                self.params, self.cache, jnp.asarray(padded),
                jnp.asarray(dev_table), jnp.int32(len(tail)))

        self.allocator.register_chain(tokens, table)
        self._lane_pages[lane] = table
        self.tables[lane] = dev_table
        self._set_page_gauges()
        return np.asarray(logits)

    def decode(self, token: np.ndarray, pos: np.ndarray) -> np.ndarray:
        """One ragged decode step over ALL lanes (inactive lanes ride the
        garbage page harmlessly); returns logits [lanes, V] on host."""
        logits, self.cache = self._decode_fn(
            self.params, self.cache, jnp.asarray(token, jnp.int32),
            jnp.asarray(self.tables), jnp.asarray(pos, jnp.int32))
        return np.asarray(logits)

    # -- speculative multi-token verify (batcher thread only) ------------- #

    @property
    def supports_verify(self) -> bool:
        return hasattr(self.model, "forward_verify_paged")

    def _get_verify_fn(self):
        fn = getattr(self, "_verify_fn", None)
        if fn is None:
            model = self.model

            def verify_step(p, cache, tokens, tables, pos, live):
                return model.forward_verify_paged(
                    p, tokens, cache, tables, pos, live)

            fn = self._verify_fn = jax.jit(verify_step, donate_argnums=(1,))
        return fn

    def warmup_verify(self, t: int) -> None:
        """Compile the T-wide verify program up front (one program per
        distinct T; the batcher uses a fixed T = k_max + 1, so this is
        one compile). No-op for T <= 1 — that's the plain decode path."""
        if t <= 1 or not self.supports_verify:
            return
        assert self.params is not None, "set_params before warmup"
        tokens = np.zeros((self.lanes, t), np.int32)
        pos = np.zeros((self.lanes,), np.int32)
        live = np.zeros((self.lanes,), np.int32)
        logits, self.cache = jax.block_until_ready(self._get_verify_fn()(
            self.params, self.cache, jnp.asarray(tokens),
            jnp.asarray(self.tables), jnp.asarray(pos), jnp.asarray(live)))
        logger.info("paged serve warmup: verify program T=%d compiled", t)

    def verify(self, tokens: np.ndarray, pos: np.ndarray,
               n_live: np.ndarray) -> np.ndarray:
        """One multi-token verify step over ALL lanes.

        `tokens[b]` is [last emitted token, draft_1..draft_{T-1}] fed at
        absolute positions pos[b]..pos[b]+T-1; only the first n_live[b]
        columns are real — the rest scatter their KV to the garbage page
        and compute junk logits the caller ignores. Returns logits
        [lanes, T, V] on host; row j of lane b is exactly what
        sequential decode would produce after emitting tokens[b, :j+1],
        which is what makes greedy acceptance byte-exact."""
        logits, self.cache = self._get_verify_fn()(
            self.params, self.cache, jnp.asarray(tokens, jnp.int32),
            jnp.asarray(self.tables), jnp.asarray(pos, jnp.int32),
            jnp.asarray(n_live, jnp.int32))
        return np.asarray(logits)

    def rollback(self, lane: int, first_pos: int, last_pos: int) -> None:
        """Rewind a lane's KV write cursor after verify rejected the draft
        suffix at positions [first_pos, last_pos]. The allocator evicts
        any prefix registration on the touched pages and CoWs shared ones
        (serve/kv_blocks.rewind_span); the device copies owed for a CoW
        use the same .at[].set pattern as prefill's defensive copy. The
        rejected bytes themselves stay in place for the OWNING lane —
        masked by every ragged length until the next accepted token
        overwrites them."""
        copies = self.allocator.rewind_span(
            self._lane_pages[lane], first_pos, last_pos)
        for src, dst in copies:
            self.cache = {
                "k": self.cache["k"].at[:, dst].set(self.cache["k"][:, src]),
                "v": self.cache["v"].at[:, dst].set(self.cache["v"][:, src]),
            }
        if copies:
            table = self._lane_pages[lane]
            self.tables[lane, :len(table)] = table
        self._set_page_gauges()
