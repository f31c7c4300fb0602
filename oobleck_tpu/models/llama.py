"""Llama family decoder as an explicit layer list.

BASELINE.json config 5 ("Llama-2-7B via HF model_name — stretch the template
planner to non-GPT arch"); the reference cannot run Llama at all (its split
registry has no llama entry, /root/reference/oobleck/module/sharding.py:15-41).

Same pipeline layer list contract as GPT ([embed, block_0.., head], see
models/gpt.py) and the same ShardCtx manual-parallel protocol, with the Llama
architecture: RMSNorm, rotary position embeddings (no learned positions —
seq-parallel offsets rotate RoPE phases instead of slicing a table), SwiGLU
MLP, no biases, untied head, optional grouped-query attention.
"""

from __future__ import annotations

from dataclasses import dataclass, replace
from typing import Any

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import PartitionSpec as P

from oobleck_tpu.models.base import stack_layer_params
from oobleck_tpu.models.gpt import (
    NEG_INF,
    ShardCtx,
    _explicit_bwd,
    _maybe_megatron_f,
)
from oobleck_tpu.ops import checkpoint_layer
from oobleck_tpu.ops.attention import causal_attention
from oobleck_tpu.parallel.collectives import (
    reduce_from_tp,
    unshard_fsdp,
    vocab_parallel_embed,
    vocab_parallel_logits_loss,
)


@dataclass(frozen=True)
class LlamaConfig:
    vocab_size: int = 32000
    max_position_embeddings: int = 4096
    hidden_size: int = 4096
    num_layers: int = 32
    num_heads: int = 32
    num_kv_heads: int | None = None     # None = MHA
    intermediate_size: int | None = None
    rms_norm_eps: float = 1e-5
    rope_theta: float = 10000.0
    initializer_range: float = 0.02
    dtype: Any = jnp.bfloat16
    param_dtype: Any = jnp.float32
    attention_impl: str = "auto"
    remat: bool = True
    vocab_pad_multiple: int = 128

    @property
    def padded_vocab_size(self) -> int:
        m = self.vocab_pad_multiple
        return (self.vocab_size + m - 1) // m * m

    @property
    def kv_heads(self) -> int:
        return self.num_kv_heads or self.num_heads

    @property
    def ffn_dim(self) -> int:
        if self.intermediate_size:
            return self.intermediate_size
        # Llama sizing: 2/3 * 4E rounded up to a multiple of 256.
        f = int(2 * 4 * self.hidden_size / 3)
        return (f + 255) // 256 * 256

    @property
    def head_dim(self) -> int:
        assert self.hidden_size % self.num_heads == 0
        return self.hidden_size // self.num_heads

    def override(self, **kwargs) -> "LlamaConfig":
        alias = {
            "n_embd": "hidden_size", "n_layer": "num_layers",
            "n_head": "num_heads", "n_positions": "max_position_embeddings",
        }
        kwargs = {alias.get(k, k): v for k, v in kwargs.items()}
        unknown = [k for k in kwargs if k not in LlamaConfig.__dataclass_fields__]
        if unknown:
            raise ValueError(f"unknown model_args {unknown}")
        return replace(self, **kwargs)


def _rms_norm(x: jax.Array, scale: jax.Array, eps: float) -> jax.Array:
    dtype = x.dtype
    x = x.astype(jnp.float32)
    var = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
    return (x * jax.lax.rsqrt(var + eps) * scale).astype(dtype)


def _rope(x: jax.Array, positions: jax.Array, theta: float) -> jax.Array:
    """Rotary embedding. x: [B, H, S, D]; positions: [S]."""
    d = x.shape[-1]
    freqs = 1.0 / (theta ** (jnp.arange(0, d, 2, dtype=jnp.float32) / d))
    angles = positions[:, None].astype(jnp.float32) * freqs[None, :]  # [S, D/2]
    cos, sin = jnp.cos(angles), jnp.sin(angles)
    x1, x2 = x[..., 0::2], x[..., 1::2]
    out1 = x1 * cos - x2 * sin
    out2 = x2 * cos + x1 * sin
    return jnp.stack([out1, out2], axis=-1).reshape(x.shape).astype(x.dtype)


def _rope_one(x: jax.Array, pos: jax.Array, theta: float) -> jax.Array:
    """Rotary embedding for one token per batch row (decode step).
    x: [B, H, D]; pos: [B] — the same phases `_rope` applies at these
    absolute positions, so cache entries and decode queries agree."""
    d = x.shape[-1]
    freqs = 1.0 / (theta ** (jnp.arange(0, d, 2, dtype=jnp.float32) / d))
    angles = pos[:, None].astype(jnp.float32) * freqs[None, :]  # [B, D/2]
    cos, sin = jnp.cos(angles)[:, None, :], jnp.sin(angles)[:, None, :]
    x1, x2 = x[..., 0::2], x[..., 1::2]
    out1 = x1 * cos - x2 * sin
    out2 = x2 * cos + x1 * sin
    return jnp.stack([out1, out2], axis=-1).reshape(x.shape).astype(x.dtype)


def _rope_multi(x: jax.Array, pos: jax.Array, theta: float) -> jax.Array:
    """Rotary embedding for T tokens per batch row at per-lane absolute
    positions (speculative verify). x: [B, H, T, D]; pos: [B, T] — the
    same phases `_rope`/`_rope_one` apply at these positions, so cached
    keys and verify queries agree with a sequential decode."""
    d = x.shape[-1]
    freqs = 1.0 / (theta ** (jnp.arange(0, d, 2, dtype=jnp.float32) / d))
    angles = pos[..., None].astype(jnp.float32) * freqs      # [B, T, D/2]
    cos, sin = jnp.cos(angles)[:, None], jnp.sin(angles)[:, None]
    x1, x2 = x[..., 0::2], x[..., 1::2]
    out1 = x1 * cos - x2 * sin
    out2 = x2 * cos + x1 * sin
    return jnp.stack([out1, out2], axis=-1).reshape(x.shape).astype(x.dtype)


def _maybe(fn, x, axis, *a):
    return fn(x, axis, *a) if axis else x


def _maybe_reduce(x, axis, ctx):
    return reduce_from_tp(x, axis, identity_bwd=_explicit_bwd(ctx)) if axis else x


class LlamaModel:
    """Layer-list Llama decoder; same contract as GPTModel."""

    data_kind = "causal_lm"
    fused_supported = True

    def __init__(self, config: LlamaConfig):
        self.config = config

    # ---- layer list ----

    @property
    def num_pipeline_layers(self) -> int:
        return self.config.num_layers + 2

    def layer_name(self, index: int) -> str:
        if index == 0:
            return "embed"
        if index == self.num_pipeline_layers - 1:
            return "head"
        return f"block_{index - 1}"

    def init_layer(self, rng: jax.Array, index: int):
        ks = jax.random.split(rng, 3)
        if index == 0:
            return self._init_embed(ks[0])
        if index == self.num_pipeline_layers - 1:
            return self._init_head(ks[2])
        return self._init_block(jax.random.fold_in(ks[1], index))

    def apply_layer(self, index: int, params, carry, batch, ctx=None):
        if index == 0:
            return self.embed(params, batch["input_ids"], ctx)
        if index == self.num_pipeline_layers - 1:
            return self.head(params, carry, ctx)
        return self.apply_block(params, carry, ctx)

    @jax.named_scope("lm_head")
    def loss_from_logits(self, logits, batch):
        from oobleck_tpu.models.gpt import cross_entropy_loss

        return cross_entropy_loss(logits, batch["input_ids"], self.config.vocab_size)

    def sample_batch(self, batch_size: int, seq_len: int):
        tokens = jax.random.randint(
            jax.random.PRNGKey(0), (batch_size, seq_len), 0,
            self.config.vocab_size, dtype=jnp.int32,
        )
        return {"input_ids": tokens}

    # ---- init ----

    def _init_embed(self, rng):
        c = self.config
        return {"wte": jax.random.normal(
            rng, (c.padded_vocab_size, c.hidden_size), c.param_dtype
        ) * c.initializer_range}

    def _init_block(self, rng):
        c = self.config
        ks = jax.random.split(rng, 5)
        std = c.initializer_range
        res_std = std / (2 * c.num_layers) ** 0.5
        e, f, h, kv, d = (c.hidden_size, c.ffn_dim, c.num_heads,
                          c.kv_heads, c.head_dim)
        return {
            "ln1": {"scale": jnp.ones((e,), c.param_dtype)},
            "attn": {
                "wq": jax.random.normal(ks[0], (e, h, d), c.param_dtype) * std,
                "wkv": jax.random.normal(ks[1], (e, 2, kv, d), c.param_dtype) * std,
                "wo": jax.random.normal(ks[2], (h, d, e), c.param_dtype) * res_std,
            },
            "ln2": {"scale": jnp.ones((e,), c.param_dtype)},
            "mlp": {
                "wg": jax.random.normal(ks[3], (e, f), c.param_dtype) * std,
                "wu": jax.random.normal(ks[4], (e, f), c.param_dtype) * std,
                "wo": jax.random.normal(
                    jax.random.fold_in(ks[3], 1), (f, e), c.param_dtype
                ) * res_std,
            },
        }

    def _init_head(self, rng):
        c = self.config
        return {
            "ln_f": {"scale": jnp.ones((c.hidden_size,), c.param_dtype)},
            "w": jax.random.normal(
                rng, (c.hidden_size, c.padded_vocab_size), c.param_dtype
            ) * c.initializer_range,
        }

    def init_params(self, rng):
        ks = jax.random.split(rng, 3)
        blocks = [self._init_block(jax.random.fold_in(ks[1], i + 1))
                  for i in range(self.config.num_layers)]
        return {"embed": self._init_embed(ks[0]),
                "blocks": stack_layer_params(blocks),
                "head": self._init_head(ks[2])}

    # ---- forward ----

    @jax.named_scope("embed")
    def embed(self, p, tokens, ctx: ShardCtx | None = None):
        c = self.config
        if ctx and ctx.tensor:
            vlocal = p["wte"].shape[0]
            x = vocab_parallel_embed(p["wte"], tokens,
                                     ctx.tp_rank() * vlocal, ctx.tensor,
                                     identity_bwd=_explicit_bwd(ctx))
        else:
            x = p["wte"][tokens]
        return x.astype(c.dtype)

    def _positions(self, s_local: int, ctx: ShardCtx | None):
        if ctx and ctx.seq:
            return ctx.seq_rank() * s_local + jnp.arange(s_local)
        return jnp.arange(s_local)

    def apply_block(self, p, x, ctx: ShardCtx | None = None):
        x = self.attention_sublayer(p, x, ctx)
        return self.mlp_sublayer(p, x, ctx)

    @jax.named_scope("attention")
    def attention_sublayer(self, p, x, ctx: ShardCtx | None = None, *,
                           return_kv: bool = False):
        """ln1 -> RoPE attention (GQA, SP aware) -> residual. `return_kv=True`
        (prefill) also returns this layer's post-RoPE, pre-repeat K/V
        [B, KV, S, D] — the form the serving cache stores."""
        c = self.config
        dt = c.dtype
        t = ctx.tensor if ctx else None
        f_ = ctx.fsdp if ctx else None
        b, s, _ = x.shape
        pos = self._positions(s, ctx)

        # (Megatron `f` only in explicit_bwd mode: on the default path the
        # shard_map spec transpose supplies the backward psum at the
        # replicated->varying boundary; see the regime note in collectives.py.)
        h = _rms_norm(x, p["ln1"]["scale"], c.rms_norm_eps)
        h = _maybe_megatron_f(h, ctx)
        wq = _maybe(unshard_fsdp, p["attn"]["wq"], f_, 0).astype(dt)      # [E,Hl,D]
        wkv = _maybe(unshard_fsdp, p["attn"]["wkv"], f_, 0).astype(dt)    # [E,2,KVl,D]
        q = jnp.einsum("bse,ehd->bhsd", h, wq)
        kv = jnp.einsum("bse,ekhd->kbhsd", h, wkv)
        k, v = kv[0], kv[1]
        q = _rope(q, pos, c.rope_theta)
        k = _rope(k, pos, c.rope_theta)
        cached_k, cached_v = k, v
        if c.kv_heads != c.num_heads:
            rep = c.num_heads // c.kv_heads
            k = jnp.repeat(k, rep, axis=1)
            v = jnp.repeat(v, rep, axis=1)
        if ctx and ctx.seq:
            from oobleck_tpu.ops.ring_attention import ring_attention

            attn = ring_attention(q, k, v, axis_name=ctx.seq)
        else:
            attn = causal_attention(q, k, v, impl=c.attention_impl)
        wo = _maybe(unshard_fsdp, p["attn"]["wo"], f_, 2).astype(dt)      # [Hl,D,E]
        out = jnp.einsum("bhsd,hde->bse", attn, wo)
        y = x + _maybe_reduce(out, t, ctx)
        if return_kv:
            return y, cached_k, cached_v
        return y

    @jax.named_scope("mlp")
    def mlp_sublayer(self, p, x, ctx: ShardCtx | None = None):
        """ln2 -> SwiGLU -> residual. Shape-agnostic over leading dims: the
        decode path calls it on [B, E] single-token activations."""
        c = self.config
        dt = c.dtype
        t = ctx.tensor if ctx else None
        f_ = ctx.fsdp if ctx else None
        h = _rms_norm(x, p["ln2"]["scale"], c.rms_norm_eps)
        h = _maybe_megatron_f(h, ctx)
        wg = _maybe(unshard_fsdp, p["mlp"]["wg"], f_, 0).astype(dt)
        wu = _maybe(unshard_fsdp, p["mlp"]["wu"], f_, 0).astype(dt)
        g = jax.nn.silu(h @ wg) * (h @ wu)
        wo = _maybe(unshard_fsdp, p["mlp"]["wo"], f_, 1).astype(dt)
        out = g @ wo
        return x + _maybe_reduce(out, t, ctx)

    @jax.named_scope("lm_head")
    def head(self, p, x, ctx: ShardCtx | None = None):
        c = self.config
        x = _rms_norm(x, p["ln_f"]["scale"], c.rms_norm_eps)
        logits = (x @ p["w"].astype(c.dtype)).astype(jnp.float32)
        if ctx and ctx.tensor:
            logits = lax.all_gather(logits, ctx.tensor, axis=-1, tiled=True)
        mask = jnp.arange(logits.shape[-1]) < c.vocab_size
        return jnp.where(mask, logits, NEG_INF)

    @jax.named_scope("lm_head")
    def head_loss_shifted(self, p, x, targets, mask, ctx: ShardCtx | None = None):
        c = self.config
        x = _rms_norm(x, p["ln_f"]["scale"], c.rms_norm_eps)
        x = _maybe_megatron_f(x, ctx)
        local_logits = (x @ p["w"].astype(c.dtype)).astype(jnp.float32)
        vlocal = local_logits.shape[-1]
        offset = (ctx.tp_rank() * vlocal) if (ctx and ctx.tensor) else 0
        col_ids = jnp.arange(vlocal) + offset
        local_logits = jnp.where(col_ids < c.vocab_size, local_logits, NEG_INF)
        per_pos = vocab_parallel_logits_loss(
            local_logits, targets, offset, ctx.tensor if ctx else None,
            identity_bwd=_explicit_bwd(ctx),
        )
        return jnp.sum(per_pos * mask)

    def forward(self, params, tokens):
        c = self.config
        x = self.embed(params["embed"], tokens)
        block = self.apply_block
        if c.remat:
            block = checkpoint_layer(block)

        def body(x, bp):
            return block(bp, x), None

        x, _ = jax.lax.scan(body, x, params["blocks"])
        return self.head(params["head"], x)

    def loss(self, params, batch):
        return self.loss_from_logits(self.forward(params, batch["input_ids"]), batch)

    # ---- incremental decode (serving) ----

    def init_kv_cache(self, batch_size: int, max_seq: int, dtype=None):
        """Preallocated KV cache [L, B, KV, S, D] — unrepeated KV heads;
        decode folds query heads into groups against it (GQA caches 1/rep
        the bytes of the repeated form)."""
        c = self.config
        shape = (c.num_layers, batch_size, c.kv_heads, max_seq, c.head_dim)
        dt = c.dtype if dtype is None else dtype
        return {"k": jnp.zeros(shape, dt), "v": jnp.zeros(shape, dt)}

    @jax.named_scope("attention")
    def _decode_attention_sublayer(self, p, x, k_cache, v_cache, pos):
        """attention_sublayer for ONE new token per slot against the KV
        cache. x [B, E]; k_cache/v_cache [B, KV, S, D]; pos [B]."""
        c = self.config
        dt = c.dtype
        from oobleck_tpu.ops.attention import cache_write, decode_attention

        h = _rms_norm(x, p["ln1"]["scale"], c.rms_norm_eps)
        q = jnp.einsum("be,ehd->bhd", h, p["attn"]["wq"].astype(dt))
        kv = jnp.einsum("be,ekhd->kbhd", h, p["attn"]["wkv"].astype(dt))
        q = _rope_one(q, pos, c.rope_theta)
        k = _rope_one(kv[0], pos, c.rope_theta)
        k_cache = cache_write(k_cache, k, pos)
        v_cache = cache_write(v_cache, kv[1], pos)
        attn = decode_attention(q, k_cache, v_cache, pos)  # GQA folded inside
        out = jnp.einsum("bhd,hde->be", attn, p["attn"]["wo"].astype(dt))
        return x + out, k_cache, v_cache

    def forward_prefill(self, params, tokens, kv_cache, slot, length):
        """Prompt pass for ONE request into batch slot `slot`; same contract
        as GPTModel.forward_prefill (tokens [1, T] possibly padded past
        `length`; returns next-token logits [V] f32 + updated cache)."""
        x = self.embed(params["embed"], tokens)

        def body(x, bp):
            x, k, v = self.attention_sublayer(bp, x, return_kv=True)
            return self.mlp_sublayer(bp, x), (k, v)

        x, (ks, vs) = jax.lax.scan(body, x, params["blocks"])
        k_cache = lax.dynamic_update_slice(
            kv_cache["k"], ks.astype(kv_cache["k"].dtype), (0, slot, 0, 0, 0))
        v_cache = lax.dynamic_update_slice(
            kv_cache["v"], vs.astype(kv_cache["v"].dtype), (0, slot, 0, 0, 0))
        logits = self.head(params["head"], x)[0, length - 1]
        return logits, {"k": k_cache, "v": v_cache}

    def forward_decode(self, params, token, kv_cache, pos):
        """One decode step over all slots; same contract as
        GPTModel.forward_decode (token [B], pos [B] -> logits [B, V] f32)."""
        x = params["embed"]["wte"][token].astype(self.config.dtype)

        def body(x, sl):
            bp, kc, vc = sl
            x, kc, vc = self._decode_attention_sublayer(bp, x, kc, vc, pos)
            return self.mlp_sublayer(bp, x), (kc, vc)

        x, (k_new, v_new) = jax.lax.scan(
            body, x, (params["blocks"], kv_cache["k"], kv_cache["v"]))
        logits = self.head(params["head"], x[:, None, :])[:, 0]
        return logits, {"k": k_new, "v": v_new}

    # ---- paged incremental decode (serving, block-table KV) ----

    def init_paged_kv_cache(self, num_pages: int, page_size: int, dtype=None):
        """Paged KV pool [L, N_pages, KV, page, D] — unrepeated KV heads,
        post-RoPE keys (absolute phases baked in, so gathered head pages
        are position-correct without recompute). Page 0 is the reserved
        garbage page."""
        c = self.config
        shape = (c.num_layers, num_pages, c.kv_heads, page_size, c.head_dim)
        dt = c.dtype if dtype is None else dtype
        return {"k": jnp.zeros(shape, dt), "v": jnp.zeros(shape, dt)}

    def _paged_impl(self) -> str:
        impl = self.config.attention_impl
        return impl if impl in ("xla", "pallas") else "auto"

    @jax.named_scope("attention")
    def _paged_decode_sublayer(self, p, x, k_pool, v_pool, block_tables, pos):
        """_decode_attention_sublayer against a page pool; GQA folds query
        heads inside paged_decode_attention against the unrepeated pool."""
        c = self.config
        dt = c.dtype
        from oobleck_tpu.ops.paged_attention import (
            paged_cache_write, paged_decode_attention)

        h = _rms_norm(x, p["ln1"]["scale"], c.rms_norm_eps)
        q = jnp.einsum("be,ehd->bhd", h, p["attn"]["wq"].astype(dt))
        kv = jnp.einsum("be,ekhd->kbhd", h, p["attn"]["wkv"].astype(dt))
        q = _rope_one(q, pos, c.rope_theta)
        k = _rope_one(kv[0], pos, c.rope_theta)
        k_pool = paged_cache_write(k_pool, k, block_tables, pos)
        v_pool = paged_cache_write(v_pool, kv[1], block_tables, pos)
        attn = paged_decode_attention(q, k_pool, v_pool, block_tables, pos + 1,
                                      impl=self._paged_impl())
        out = jnp.einsum("bhd,hde->be", attn, p["attn"]["wo"].astype(dt))
        return x + out, k_pool, v_pool

    @jax.named_scope("attention")
    def _tail_prefill_sublayer(self, p, x, k_pool, v_pool, head_tables,
                               prior_len):
        """Prompt-tail attention over a gathered cached head (see
        GPTModel._tail_prefill_sublayer): head pages hold post-RoPE K, so
        the prefix hit skips the head's compute; tail queries/keys rotate
        at absolute positions prior_len + i; mask is explicit."""
        c = self.config
        dt = c.dtype
        from oobleck_tpu.ops import attention
        from oobleck_tpu.ops.paged_attention import paged_gather_kv

        h = _rms_norm(x, p["ln1"]["scale"], c.rms_norm_eps)
        wq = p["attn"]["wq"].astype(dt)
        wkv = p["attn"]["wkv"].astype(dt)
        q = jnp.einsum("bse,ehd->bhsd", h, wq)
        kv = jnp.einsum("bse,ekhd->kbhsd", h, wkv)
        t_len = q.shape[2]
        pos = prior_len + jnp.arange(t_len)
        q = _rope(q, pos, c.rope_theta)
        k_tail = _rope(kv[0], pos, c.rope_theta)
        v_tail = kv[1]
        head_k = paged_gather_kv(k_pool, head_tables[None]).astype(dt)
        head_v = paged_gather_kv(v_pool, head_tables[None]).astype(dt)
        k = jnp.concatenate([head_k, k_tail], axis=2)
        v = jnp.concatenate([head_v, v_tail], axis=2)
        if c.kv_heads != c.num_heads:
            rep = c.num_heads // c.kv_heads
            k = jnp.repeat(k, rep, axis=1)
            v = jnp.repeat(v, rep, axis=1)
        s_head = head_k.shape[2]
        live = jnp.concatenate([
            jnp.broadcast_to(jnp.arange(s_head) < prior_len, (t_len, s_head)),
            jnp.tril(jnp.ones((t_len, t_len), bool)),
        ], axis=1)
        bias = jnp.where(live, 0.0, NEG_INF)[None]                      # [1,T,S]
        attn = attention._xla_causal_attention(q, k, v, bias=bias, causal=False)
        out = jnp.einsum("bhsd,hde->bse", attn, p["attn"]["wo"].astype(dt))
        return x + out, k_tail, v_tail

    def forward_prefill_paged(self, params, tokens, kv_cache, block_tables,
                              length, head_tables=None, prior_len=0):
        """Same contract as GPTModel.forward_prefill_paged (prompt tail into
        pool pages, optional cached head via head_tables/prior_len)."""
        from oobleck_tpu.models.gpt import GPTModel

        c = self.config
        prior_len = jnp.asarray(prior_len, jnp.int32)
        x = params["embed"]["wte"][tokens].astype(c.dtype)

        def body(x, sl):
            bp, kp, vp = sl
            if head_tables is None:
                x, k, v = self.attention_sublayer(bp, x, return_kv=True)
            else:
                x, k, v = self._tail_prefill_sublayer(
                    bp, x, kp, vp, head_tables, prior_len)
            return self.mlp_sublayer(bp, x), (k, v)

        x, (ks, vs) = jax.lax.scan(
            body, x, (params["blocks"], kv_cache["k"], kv_cache["v"]))
        kv_cache = GPTModel._paged_tail_write(
            self, kv_cache, ks, vs, block_tables, prior_len, length)
        logits = self.head(params["head"], x)[0, length - 1]
        return logits, kv_cache

    def forward_decode_paged(self, params, token, kv_cache, block_tables, pos):
        """Same contract as GPTModel.forward_decode_paged."""
        x = params["embed"]["wte"][token].astype(self.config.dtype)

        def body(x, sl):
            bp, kp, vp = sl
            x, kp, vp = self._paged_decode_sublayer(
                bp, x, kp, vp, block_tables, pos)
            return self.mlp_sublayer(bp, x), (kp, vp)

        x, (k_new, v_new) = jax.lax.scan(
            body, x, (params["blocks"], kv_cache["k"], kv_cache["v"]))
        logits = self.head(params["head"], x[:, None, :])[:, 0]
        return logits, {"k": k_new, "v": v_new}

    @jax.named_scope("attention")
    def _paged_verify_sublayer(self, p, x, k_pool, v_pool, block_tables,
                               pos, n_live):
        """_paged_decode_sublayer for T speculative tokens per lane (see
        GPTModel._paged_verify_sublayer): queries and keys rotate at their
        true absolute positions pos + i, K/V for all T candidates scatter
        through the block table (padding to the garbage page), and GQA
        folds query heads inside paged_verify_attention."""
        c = self.config
        dt = c.dtype
        from oobleck_tpu.ops.paged_attention import (
            paged_cache_write_multi, paged_verify_attention)

        h = _rms_norm(x, p["ln1"]["scale"], c.rms_norm_eps)             # [B,T,E]
        q = jnp.einsum("bte,ehd->bhtd", h, p["attn"]["wq"].astype(dt))
        kv = jnp.einsum("bte,ekhd->kbhtd", h, p["attn"]["wkv"].astype(dt))
        t_len = x.shape[1]
        pos_abs = pos[:, None] + jnp.arange(t_len)                      # [B,T]
        q = _rope_multi(q, pos_abs, c.rope_theta)
        k = _rope_multi(kv[0], pos_abs, c.rope_theta)
        k_pool = paged_cache_write_multi(
            k_pool, k.transpose(0, 2, 1, 3), block_tables, pos, n_live)
        v_pool = paged_cache_write_multi(
            v_pool, kv[1].transpose(0, 2, 1, 3), block_tables, pos, n_live)
        attn = paged_verify_attention(
            q.transpose(0, 2, 1, 3), k_pool, v_pool, block_tables, pos + 1,
            impl=self._paged_impl())
        out = jnp.einsum("bthd,hde->bte", attn, p["attn"]["wo"].astype(dt))
        return x + out, k_pool, v_pool

    def forward_verify_paged(self, params, tokens, kv_cache, block_tables,
                             pos, n_live):
        """Same contract as GPTModel.forward_verify_paged (T candidate
        tokens per lane at absolute positions, post-RoPE keys cached)."""
        x = params["embed"]["wte"][tokens].astype(self.config.dtype)

        def body(x, sl):
            bp, kp, vp = sl
            x, kp, vp = self._paged_verify_sublayer(
                bp, x, kp, vp, block_tables, pos, n_live)
            return self.mlp_sublayer(bp, x), (kp, vp)

        x, (k_new, v_new) = jax.lax.scan(
            body, x, (params["blocks"], kv_cache["k"], kv_cache["v"]))
        logits = self.head(params["head"], x)
        return logits, {"k": k_new, "v": v_new}

    # ---- sharding ----

    def param_specs(self, *, stacked: bool = True):
        s = ("stage",) if stacked else ()
        block = {
            "ln1": {"scale": P(*s)},
            "attn": {
                "wq": P(*s, "fsdp", "tensor", None),
                "wkv": P(*s, "fsdp", None, "tensor", None),
                "wo": P(*s, "tensor", None, "fsdp"),
            },
            "ln2": {"scale": P(*s)},
            "mlp": {
                "wg": P(*s, "fsdp", "tensor"),
                "wu": P(*s, "fsdp", "tensor"),
                "wo": P(*s, "tensor", "fsdp"),
            },
        }
        return {
            "embed": {"wte": P("tensor", None)},
            "blocks": block,
            "head": {"ln_f": {"scale": P()}, "w": P(None, "tensor")},
        }
