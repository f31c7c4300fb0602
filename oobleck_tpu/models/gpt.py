"""GPT-2 / GPT-3 family decoder as an explicit layer list.

Capability match for the reference's gpt2 path (HF AutoModel + fx shard,
/root/reference/oobleck/module/model.py:21-33, sharding.py:15-18), designed
TPU-first: pure-functional params pytrees, bf16 compute / f32 params, static
shapes, and explicit Megatron-style tensor parallelism + fsdp parameter
gathering for full-manual shard_map execution.

Pipeline layer list: [embed, block_0 .. block_{L-1}, head] — L+2 planning
units, matching the reference's "one split point per transformer block + final
norm/head" granularity (sharding.py:15-18).

Parameter layout is chosen for manual TP:
  wqkv [E, 3, H, D]   — heads on a dedicated dim, sharded over `tensor`
  wo   [H, D, E]      — row-parallel output proj
  wi   [E, F] / wo [F, E] — column/row-parallel MLP
  wte  [Vp, E]        — vocab-parallel embedding (Vp = vocab padded to 128)
Every apply function takes an optional ShardCtx; with ctx=None the same code
runs as a plain single-device program (used by tests and the profiler).
"""

from __future__ import annotations

from dataclasses import dataclass, replace
from typing import Any

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import PartitionSpec as P

from oobleck_tpu.models.base import stack_layer_params
from oobleck_tpu.ops import checkpoint_layer
from oobleck_tpu.ops.attention import causal_attention
from oobleck_tpu.parallel.collectives import (
    megatron_f,
    reduce_from_tp,
    unshard_fsdp,
    vocab_parallel_embed,
    vocab_parallel_logits_loss,
)

NEG_INF = -1e9


@dataclass(frozen=True)
class ShardCtx:
    """Axis names for manual-collective execution; None member = skip."""

    tensor: str | None = None
    fsdp: str | None = None
    seq: str | None = None   # sequence parallelism: ring attention + offsets
    # Explicit-backward mode (parallel/overlap.py): value_and_grad runs INSIDE
    # one check_rep=False shard_map, so no spec transposes insert backward
    # psums — the model must place Megatron `f` at each replicated->column-
    # parallel entry and make every forward tensor-psum identity-backward.
    explicit_bwd: bool = False

    def tp_size(self) -> int:
        return lax.axis_size(self.tensor) if self.tensor else 1

    def tp_rank(self):
        return lax.axis_index(self.tensor) if self.tensor else 0

    def seq_rank(self):
        return lax.axis_index(self.seq) if self.seq else 0


@dataclass(frozen=True)
class GPTConfig:
    vocab_size: int = 50257
    max_position_embeddings: int = 1024
    hidden_size: int = 768
    num_layers: int = 12
    num_heads: int = 12
    intermediate_size: int | None = None
    layer_norm_epsilon: float = 1e-5
    initializer_range: float = 0.02
    dtype: Any = jnp.bfloat16      # compute/activation dtype
    param_dtype: Any = jnp.float32  # parameter storage dtype
    attention_impl: str = "auto"
    remat: bool = True
    vocab_pad_multiple: int = 128   # pad vocab so `tensor` can shard it
    # "learned" (GPT-2) or "alibi" (Bloom family: no wpe, per-head distance
    # bias in attention).
    position_embedding: str = "learned"

    def __post_init__(self):
        if self.position_embedding not in ("learned", "alibi"):
            raise ValueError(
                f"position_embedding must be 'learned' or 'alibi', got "
                f"{self.position_embedding!r}"
            )

    @property
    def padded_vocab_size(self) -> int:
        m = self.vocab_pad_multiple
        return (self.vocab_size + m - 1) // m * m

    @property
    def ffn_dim(self) -> int:
        return self.intermediate_size or 4 * self.hidden_size

    @property
    def head_dim(self) -> int:
        assert self.hidden_size % self.num_heads == 0
        return self.hidden_size // self.num_heads

    def override(self, **kwargs) -> "GPTConfig":
        # HF model_args names accepted for config-compat with the reference
        # contract (training_util.py:27-32): n_embd/n_layer/n_head/n_positions.
        alias = {
            "n_embd": "hidden_size",
            "n_layer": "num_layers",
            "n_head": "num_heads",
            "n_positions": "max_position_embeddings",
            "n_inner": "intermediate_size",
        }
        kwargs = {alias.get(k, k): v for k, v in kwargs.items()}
        unknown = [k for k in kwargs if k not in GPTConfig.__dataclass_fields__]
        if unknown:
            raise ValueError(
                f"unknown model_args {unknown}; known fields: "
                f"{sorted(GPTConfig.__dataclass_fields__)} (+ HF aliases {sorted(alias)})"
            )
        return replace(self, **kwargs)


def _layer_norm(x: jax.Array, scale: jax.Array, bias: jax.Array, eps: float) -> jax.Array:
    dtype = x.dtype
    x = x.astype(jnp.float32)
    mean = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(x - mean), axis=-1, keepdims=True)
    y = (x - mean) * jax.lax.rsqrt(var + eps)
    return (y * scale + bias).astype(dtype)


def _maybe_reduce_from_tp(x, axis, identity_bwd=False):
    return reduce_from_tp(x, axis, identity_bwd=identity_bwd) if axis else x


def _maybe_megatron_f(x, ctx: "ShardCtx | None"):
    """Megatron `f` at a replicated->column-parallel entry, only in
    explicit-backward mode (the default path's spec transposes handle it)."""
    if ctx is not None and ctx.explicit_bwd and ctx.tensor:
        return megatron_f(x, ctx.tensor)
    return x


def _explicit_bwd(ctx: "ShardCtx | None") -> bool:
    return ctx is not None and ctx.explicit_bwd


def _maybe_unshard(p, axis, dim):
    return unshard_fsdp(p, axis, dim) if axis else p


class GPTModel:
    """Layer-list GPT decoder. See module docstring for the pipeline layout."""

    data_kind = "causal_lm"
    fused_supported = True  # the compiled SPMD step (parallel/train.py)

    def __init__(self, config: GPTConfig):
        self.config = config

    # ------------------------------------------------------------------ #
    # layer list view (planning / MPMD pipeline)                          #
    # ------------------------------------------------------------------ #

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
        # Same key derivation as init_params so the layer-list and fused
        # views of one seed produce identical weights.
        ks = jax.random.split(rng, 3)
        if index == 0:
            return self._init_embed(ks[0])
        if index == self.num_pipeline_layers - 1:
            return self._init_head(ks[2])
        return self._init_block(jax.random.fold_in(ks[1], index))

    def apply_layer(self, index: int, params, carry, batch, ctx: ShardCtx | None = None):
        if index == 0:
            return self.embed(params, batch["input_ids"], ctx)
        if index == self.num_pipeline_layers - 1:
            return self.head(params, carry, ctx)
        return self.apply_block(params, carry, ctx)

    @jax.named_scope("lm_head")
    def loss_from_logits(self, logits: jax.Array, batch) -> jax.Array:
        return cross_entropy_loss(logits, batch["input_ids"], self.config.vocab_size)

    def sample_batch(self, batch_size: int, seq_len: int):
        tokens = jax.random.randint(
            jax.random.PRNGKey(0), (batch_size, seq_len), 0, self.config.vocab_size,
            dtype=jnp.int32,
        )
        return {"input_ids": tokens}

    # ------------------------------------------------------------------ #
    # parameter init                                                      #
    # ------------------------------------------------------------------ #

    def _init_embed(self, rng: jax.Array):
        c = self.config
        k1, k2 = jax.random.split(rng)
        std = c.initializer_range
        out = {
            "wte": jax.random.normal(k1, (c.padded_vocab_size, c.hidden_size), c.param_dtype) * std,
        }
        if c.position_embedding == "learned":
            out["wpe"] = jax.random.normal(
                k2, (c.max_position_embeddings, c.hidden_size), c.param_dtype
            ) * std
        return out

    def _init_block(self, rng: jax.Array):
        c = self.config
        ks = jax.random.split(rng, 4)
        std = c.initializer_range
        # GPT-2 residual-projection scaling: 1/sqrt(2*L) on the output projs.
        res_std = std / (2 * c.num_layers) ** 0.5
        e, f, h, d = c.hidden_size, c.ffn_dim, c.num_heads, c.head_dim
        return {
            "ln1": {"scale": jnp.ones((e,), c.param_dtype), "bias": jnp.zeros((e,), c.param_dtype)},
            "attn": {
                "wqkv": jax.random.normal(ks[0], (e, 3, h, d), c.param_dtype) * std,
                "bqkv": jnp.zeros((3, h, d), c.param_dtype),
                "wo": jax.random.normal(ks[1], (h, d, e), c.param_dtype) * res_std,
                "bo": jnp.zeros((e,), c.param_dtype),
            },
            "ln2": {"scale": jnp.ones((e,), c.param_dtype), "bias": jnp.zeros((e,), c.param_dtype)},
            "mlp": {
                "wi": jax.random.normal(ks[2], (e, f), c.param_dtype) * std,
                "bi": jnp.zeros((f,), c.param_dtype),
                "wo": jax.random.normal(ks[3], (f, e), c.param_dtype) * res_std,
                "bo": jnp.zeros((e,), c.param_dtype),
            },
        }

    def _init_head(self, rng: jax.Array):
        c = self.config
        e = c.hidden_size
        return {
            "ln_f": {"scale": jnp.ones((e,), c.param_dtype), "bias": jnp.zeros((e,), c.param_dtype)},
            # Untied lm head, matching the reference's behavior of not tying
            # embeddings across first/last stages (README.md:99).
            "w": jax.random.normal(rng, (e, c.padded_vocab_size), c.param_dtype) * c.initializer_range,
        }

    def init_params(self, rng: jax.Array):
        """Fused view: blocks stacked on a leading [num_layers, ...] axis."""
        ks = jax.random.split(rng, 3)
        blocks = [self._init_block(jax.random.fold_in(ks[1], i + 1))
                  for i in range(self.config.num_layers)]
        return {
            "embed": self._init_embed(ks[0]),
            "blocks": stack_layer_params(blocks),
            "head": self._init_head(ks[2]),
        }

    # ------------------------------------------------------------------ #
    # forward (ctx=None: plain; ctx set: manual TP/fsdp collectives)      #
    # ------------------------------------------------------------------ #

    @jax.named_scope("embed")
    def embed(self, p, tokens: jax.Array, ctx: ShardCtx | None = None) -> jax.Array:
        c = self.config
        seq = tokens.shape[-1]
        if ctx and ctx.tensor:
            vlocal = p["wte"].shape[0]
            offset = ctx.tp_rank() * vlocal
            x = vocab_parallel_embed(p["wte"], tokens, offset, ctx.tensor,
                                     identity_bwd=_explicit_bwd(ctx))
        else:
            x = p["wte"][tokens]
        if c.position_embedding == "learned":
            if ctx and ctx.seq:
                # Sequence-parallel: this shard holds [r*seq, (r+1)*seq).
                pos0 = ctx.seq_rank() * seq
                x = x + lax.dynamic_slice_in_dim(p["wpe"], pos0, seq, axis=0)
            else:
                x = x + p["wpe"][:seq]
        return x.astype(c.dtype)

    def apply_block(self, p, x: jax.Array, ctx: ShardCtx | None = None) -> jax.Array:
        x = self.attention_sublayer(p, x, ctx)
        return self.mlp_sublayer(p, x, ctx)

    @jax.named_scope("mlp")
    def mlp_sublayer(self, p, x: jax.Array, ctx: ShardCtx | None = None) -> jax.Array:
        """ln2 -> gelu MLP -> residual. Shape-agnostic over leading dims:
        the decode path calls it on [B, E] single-token activations."""
        c = self.config
        dt = c.dtype
        t = ctx.tensor if ctx else None
        f_ = ctx.fsdp if ctx else None
        h = _layer_norm(x, p["ln2"]["scale"], p["ln2"]["bias"], c.layer_norm_epsilon)
        h = _maybe_megatron_f(h, ctx)
        wi = _maybe_unshard(p["mlp"]["wi"], f_, 0).astype(dt)           # [E,Fl]
        h = jax.nn.gelu(h @ wi + p["mlp"]["bi"].astype(dt))
        wo = _maybe_unshard(p["mlp"]["wo"], f_, 1).astype(dt)           # [Fl,E]
        out = h @ wo
        out = _maybe_reduce_from_tp(out, t, _explicit_bwd(ctx)) + p["mlp"]["bo"].astype(dt)
        return x + out

    @jax.named_scope("attention")
    def attention_sublayer(self, p, x: jax.Array,
                           ctx: ShardCtx | None = None, *,
                           return_kv: bool = False):
        """ln1 -> attention (impl dispatch, ALiBi, TP/SP aware) -> residual.
        Split out of apply_block so MoE variants swap only the MLP half.
        `return_kv=True` (prefill) also returns this layer's K/V [B, H, S, D]
        for the serving KV cache."""
        c = self.config
        dt = c.dtype
        t = ctx.tensor if ctx else None
        f_ = ctx.fsdp if ctx else None

        # --- attention ---
        # (Megatron `f` only in explicit_bwd mode: on the default path the
        # shard_map spec transpose psums the replicated->varying boundary
        # cotangent itself; see the regime note in collectives.py.)
        h = _layer_norm(x, p["ln1"]["scale"], p["ln1"]["bias"], c.layer_norm_epsilon)
        h = _maybe_megatron_f(h, ctx)
        wqkv = _maybe_unshard(p["attn"]["wqkv"], f_, 0).astype(dt)     # [E,3,Hl,D]
        bqkv = p["attn"]["bqkv"].astype(dt)                             # [3,Hl,D]
        qkv = jnp.einsum("bse,ethd->tbhsd", h, wqkv) + bqkv[:, None, :, None, :]

        def local_alibi_slopes():
            # Slopes only ([Hl] after the TP-local slice) — never a
            # materialized [H, S, S] bias: the flash kernel generates the
            # bias IN-KERNEL (zero HBM bias bytes at any S); non-flash
            # fallbacks and the Ulysses seq-shard materialize only their
            # own head block from these slopes (round-4 advisor: the
            # full bias was O(H S^2) HBM per device).
            if c.position_embedding != "alibi":
                return None
            from oobleck_tpu.ops.attention import alibi_slopes

            full = alibi_slopes(c.num_heads)
            if ctx and ctx.tensor:
                h_local = qkv.shape[2]
                return lax.dynamic_slice_in_dim(
                    full, ctx.tp_rank() * h_local, h_local, axis=0)
            return full

        if ctx and ctx.seq:
            if c.attention_impl == "ulysses" or c.position_embedding == "alibi":
                # Ulysses all-to-all layout: full sequence per device on
                # H/P heads — position-dependent biases (ALiBi) work
                # unchanged, which the ring layout cannot offer.
                from oobleck_tpu.ops.ulysses import ulysses_attention

                attn_out = ulysses_attention(
                    qkv[0], qkv[1], qkv[2], axis_name=ctx.seq,
                    alibi_slopes=local_alibi_slopes(),
                )
            else:
                from oobleck_tpu.ops.ring_attention import ring_attention

                attn_out = ring_attention(qkv[0], qkv[1], qkv[2],
                                          axis_name=ctx.seq)
        else:
            attn_out = causal_attention(
                qkv[0], qkv[1], qkv[2], impl=c.attention_impl,
                alibi_slopes=local_alibi_slopes(),
                constant_bias=True,  # ALiBi is position-only
            )
        wo = _maybe_unshard(p["attn"]["wo"], f_, 2).astype(dt)          # [Hl,D,E]
        out = jnp.einsum("bhsd,hde->bse", attn_out, wo)
        out = _maybe_reduce_from_tp(out, t, _explicit_bwd(ctx)) + p["attn"]["bo"].astype(dt)
        if return_kv:
            return x + out, qkv[1], qkv[2]
        return x + out

    @jax.named_scope("lm_head")
    def head(self, p, x: jax.Array, ctx: ShardCtx | None = None) -> jax.Array:
        """Full (unsharded-output) logits in f32; masks vocab padding."""
        c = self.config
        x = _layer_norm(x, p["ln_f"]["scale"], p["ln_f"]["bias"], c.layer_norm_epsilon)
        logits = (x @ p["w"].astype(c.dtype)).astype(jnp.float32)
        if ctx and ctx.tensor:
            logits = lax.all_gather(logits, ctx.tensor, axis=-1, tiled=True)
        mask = jnp.arange(logits.shape[-1]) < c.vocab_size
        return jnp.where(mask, logits, NEG_INF)

    @jax.named_scope("lm_head")
    def head_loss_shifted(self, p, x: jax.Array, targets: jax.Array,
                          mask: jax.Array, ctx: ShardCtx | None = None) -> jax.Array:
        """SUM of masked per-position losses with *pre-shifted* targets
        (targets[t] = token[t+1], mask 0 on invalid positions).

        Used by the sequence-parallel fused path: the next-token shift
        crosses shard boundaries when the sequence dim is sharded, so the
        caller shifts globally before sharding instead."""
        c = self.config
        x = _layer_norm(x, p["ln_f"]["scale"], p["ln_f"]["bias"], c.layer_norm_epsilon)
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

    def forward(self, params, tokens: jax.Array) -> jax.Array:
        """Fused single-program forward over stacked blocks (ctx-free)."""
        c = self.config
        x = self.embed(params["embed"], tokens)
        block = self.apply_block
        if c.remat:
            block = checkpoint_layer(block)

        def body(x, bp):
            return block(bp, x), None

        x, _ = jax.lax.scan(body, x, params["blocks"])
        return self.head(params["head"], x)

    def loss(self, params, batch) -> jax.Array:
        return self.loss_from_logits(self.forward(params, batch["input_ids"]), batch)

    # ------------------------------------------------------------------ #
    # incremental decode (serving)                                        #
    # ------------------------------------------------------------------ #

    def init_kv_cache(self, batch_size: int, max_seq: int, dtype: Any = None):
        """Preallocated per-layer KV cache, stacked [L, B, H, S, D] (compute
        dtype, bf16 by default) so decode scans blocks and cache slices
        together. `batch_size` is the number of continuous-batching slots."""
        c = self.config
        shape = (c.num_layers, batch_size, c.num_heads, max_seq, c.head_dim)
        dt = c.dtype if dtype is None else dtype
        return {"k": jnp.zeros(shape, dt), "v": jnp.zeros(shape, dt)}

    @jax.named_scope("attention")
    def _decode_attention_sublayer(self, p, x, k_cache, v_cache, pos):
        """attention_sublayer for ONE new token per slot against the KV
        cache. x [B, E]; k_cache/v_cache [B, H, S, D]; pos [B]."""
        c = self.config
        dt = c.dtype
        from oobleck_tpu.ops.attention import (
            alibi_slopes, cache_write, decode_attention)

        h = _layer_norm(x, p["ln1"]["scale"], p["ln1"]["bias"], c.layer_norm_epsilon)
        wqkv = p["attn"]["wqkv"].astype(dt)                             # [E,3,H,D]
        qkv = jnp.einsum("be,ethd->tbhd", h, wqkv) + p["attn"]["bqkv"].astype(dt)[:, None]
        k_cache = cache_write(k_cache, qkv[1], pos)
        v_cache = cache_write(v_cache, qkv[2], pos)
        slopes = alibi_slopes(c.num_heads) if c.position_embedding == "alibi" else None
        attn = decode_attention(qkv[0], k_cache, v_cache, pos, alibi_slopes=slopes)
        out = jnp.einsum("bhd,hde->be", attn, p["attn"]["wo"].astype(dt))
        out = out + p["attn"]["bo"].astype(dt)
        return x + out, k_cache, v_cache

    def forward_prefill(self, params, tokens: jax.Array, kv_cache,
                        slot: jax.Array, length: jax.Array):
        """Prompt pass for ONE request: training-mode block math over
        tokens [1, T] (T may be padded past the live `length`), writing each
        layer's K/V into batch slot `slot` of the cache. Returns (next-token
        logits [V] f32 taken at position length-1, updated cache). Padded
        positions land in the cache but are never attended: prefill is
        causal and decode masks k_idx <= pos, and every decode step
        overwrites its own position before reading it."""
        x = self.embed(params["embed"], tokens)

        def body(x, bp):
            x, k, v = self.attention_sublayer(bp, x, return_kv=True)
            return self.mlp_sublayer(bp, x), (k, v)

        x, (ks, vs) = lax.scan(body, x, params["blocks"])
        # ks/vs [L, 1, H, T, D]: one slice-write into slot `slot`.
        k_cache = lax.dynamic_update_slice(
            kv_cache["k"], ks.astype(kv_cache["k"].dtype), (0, slot, 0, 0, 0))
        v_cache = lax.dynamic_update_slice(
            kv_cache["v"], vs.astype(kv_cache["v"].dtype), (0, slot, 0, 0, 0))
        logits = self.head(params["head"], x)[0, length - 1]
        return logits, {"k": k_cache, "v": v_cache}

    def forward_decode(self, params, token: jax.Array, kv_cache, pos: jax.Array):
        """One decode step for a batch of slots: token [B] (each slot's
        previous token), pos [B] (its position), cache from init_kv_cache.
        Returns (logits [B, V] f32, updated cache). Inactive slots decode
        garbage harmlessly — their slot is rewritten by the next prefill."""
        c = self.config
        pe = params["embed"]
        x = pe["wte"][token]
        if c.position_embedding == "learned":
            x = x + pe["wpe"][pos]
        x = x.astype(c.dtype)

        def body(x, sl):
            bp, kc, vc = sl
            x, kc, vc = self._decode_attention_sublayer(bp, x, kc, vc, pos)
            return self.mlp_sublayer(bp, x), (kc, vc)

        x, (k_new, v_new) = lax.scan(
            body, x, (params["blocks"], kv_cache["k"], kv_cache["v"]))
        logits = self.head(params["head"], x[:, None, :])[:, 0]
        return logits, {"k": k_new, "v": v_new}

    # ------------------------------------------------------------------ #
    # paged incremental decode (serving, block-table KV)                  #
    # ------------------------------------------------------------------ #

    def init_paged_kv_cache(self, num_pages: int, page_size: int,
                            dtype: Any = None):
        """Paged KV pool, stacked [L, N_pages, H, page, D]. Requests own
        page chains via block tables (serve/kv_blocks.py); page 0 is the
        reserved garbage page inactive lanes and padding write to."""
        c = self.config
        shape = (c.num_layers, num_pages, c.num_heads, page_size, c.head_dim)
        dt = c.dtype if dtype is None else dtype
        return {"k": jnp.zeros(shape, dt), "v": jnp.zeros(shape, dt)}

    def _paged_impl(self) -> str:
        # Training impl names (ring/ulysses) have no paged meaning; only a
        # forced xla/pallas carries over, everything else resolves by
        # backend.
        impl = self.config.attention_impl
        return impl if impl in ("xla", "pallas") else "auto"

    @jax.named_scope("attention")
    def _paged_decode_sublayer(self, p, x, k_pool, v_pool, block_tables, pos):
        """_decode_attention_sublayer against a page pool: write the new
        token's K/V through the block table, then ragged paged attention.
        x [B, E]; pools [N, H, page, D]; block_tables [B, P]; pos [B]."""
        c = self.config
        dt = c.dtype
        from oobleck_tpu.ops.attention import alibi_slopes
        from oobleck_tpu.ops.paged_attention import (
            paged_cache_write, paged_decode_attention)

        h = _layer_norm(x, p["ln1"]["scale"], p["ln1"]["bias"], c.layer_norm_epsilon)
        wqkv = p["attn"]["wqkv"].astype(dt)                             # [E,3,H,D]
        qkv = jnp.einsum("be,ethd->tbhd", h, wqkv) + p["attn"]["bqkv"].astype(dt)[:, None]
        k_pool = paged_cache_write(k_pool, qkv[1], block_tables, pos)
        v_pool = paged_cache_write(v_pool, qkv[2], block_tables, pos)
        slopes = alibi_slopes(c.num_heads) if c.position_embedding == "alibi" else None
        attn = paged_decode_attention(
            qkv[0], k_pool, v_pool, block_tables, pos + 1,
            alibi_slopes=slopes, impl=self._paged_impl())
        out = jnp.einsum("bhd,hde->be", attn, p["attn"]["wo"].astype(dt))
        out = out + p["attn"]["bo"].astype(dt)
        return x + out, k_pool, v_pool

    @jax.named_scope("attention")
    def _tail_prefill_sublayer(self, p, x, k_pool, v_pool, head_tables,
                               prior_len):
        """attention_sublayer for a prompt TAIL whose head (`prior_len`
        tokens) is already cached in pool pages named by `head_tables`
        (static page count, garbage-padded past the live head): the prefix
        hit skips the head's block compute entirely — head K/V are
        GATHERED, not recomputed. Tail queries sit at absolute positions
        prior_len + i, so the mask is explicit (head key j live iff
        j < prior_len; causal among the tail) and ALiBi uses true
        distances. seq_q != seq_k, so this is inherently the XLA path."""
        c = self.config
        dt = c.dtype
        from oobleck_tpu.ops.attention import (
            _xla_causal_attention, alibi_slopes)
        from oobleck_tpu.ops.paged_attention import paged_gather_kv

        h = _layer_norm(x, p["ln1"]["scale"], p["ln1"]["bias"], c.layer_norm_epsilon)
        wqkv = p["attn"]["wqkv"].astype(dt)
        qkv = jnp.einsum("bse,ethd->tbhsd", h, wqkv) + p["attn"]["bqkv"].astype(dt)[:, None, :, None, :]
        q, k_tail, v_tail = qkv[0], qkv[1], qkv[2]
        head_k = paged_gather_kv(k_pool, head_tables[None]).astype(dt)
        head_v = paged_gather_kv(v_pool, head_tables[None]).astype(dt)
        k = jnp.concatenate([head_k, k_tail], axis=2)
        v = jnp.concatenate([head_v, v_tail], axis=2)
        t_len, s_head = q.shape[2], head_k.shape[2]
        q_abs = prior_len + jnp.arange(t_len)                           # [T]
        k_abs = jnp.concatenate([jnp.arange(s_head), q_abs])            # [S]
        live = jnp.concatenate([
            jnp.broadcast_to(jnp.arange(s_head) < prior_len, (t_len, s_head)),
            jnp.tril(jnp.ones((t_len, t_len), bool)),
        ], axis=1)                                                      # [T, S]
        bias = jnp.where(live, 0.0, NEG_INF)[None]                      # [1,T,S]
        if c.position_embedding == "alibi":
            dist = (q_abs[:, None] - k_abs[None, :]).astype(jnp.float32)
            bias = bias - alibi_slopes(c.num_heads)[:, None, None] * dist
        attn = _xla_causal_attention(q, k, v, bias=bias, causal=False)
        out = jnp.einsum("bhsd,hde->bse", attn, p["attn"]["wo"].astype(dt))
        out = out + p["attn"]["bo"].astype(dt)
        return x + out, k_tail, v_tail

    def _paged_tail_write(self, kv_cache, ks, vs, block_tables, prior_len,
                          length):
        """Scatter a prefill tail's K/V ([L, 1, Hkv, T, D]) into pool pages
        at absolute positions prior_len + i. Padded positions (i >= length)
        land on the garbage page 0."""
        page = kv_cache["k"].shape[3]
        t_len = ks.shape[3]
        i = jnp.arange(t_len)
        pos_abs = prior_len + i
        page_idx = jnp.where(
            i < length,
            jnp.take(block_tables, pos_abs // page, mode="clip"), 0)    # [T]
        off = pos_abs % page
        # Advanced indices at dims 1/3 front the result: update [T, L, H, D].
        upd_k = ks[:, 0].transpose(2, 0, 1, 3).astype(kv_cache["k"].dtype)
        upd_v = vs[:, 0].transpose(2, 0, 1, 3).astype(kv_cache["v"].dtype)
        return {
            "k": kv_cache["k"].at[:, page_idx, :, off, :].set(upd_k),
            "v": kv_cache["v"].at[:, page_idx, :, off, :].set(upd_v),
        }

    def forward_prefill_paged(self, params, tokens: jax.Array, kv_cache,
                              block_tables: jax.Array, length: jax.Array,
                              head_tables: jax.Array | None = None,
                              prior_len: jax.Array | int = 0):
        """Prompt pass for ONE request into pool pages. tokens [1, T] is the
        prompt TAIL (bucket-padded past the live `length`); block_tables [P]
        names the request's page chain (cached head included); on a prefix
        hit `head_tables` [P_head] (static count — a jit bucket) names the
        cached head pages and `prior_len` its live token count, and the
        head's compute is skipped. Returns (next-token logits [V] f32 at
        tail position length-1, updated pool)."""
        c = self.config
        t_len = tokens.shape[-1]
        prior_len = jnp.asarray(prior_len, jnp.int32)
        pe = params["embed"]
        x = pe["wte"][tokens]
        if c.position_embedding == "learned":
            x = x + lax.dynamic_slice_in_dim(pe["wpe"], prior_len, t_len, axis=0)
        x = x.astype(c.dtype)

        def body(x, sl):
            bp, kp, vp = sl
            if head_tables is None:
                x, k, v = self.attention_sublayer(bp, x, return_kv=True)
            else:
                x, k, v = self._tail_prefill_sublayer(
                    bp, x, kp, vp, head_tables, prior_len)
            return self.mlp_sublayer(bp, x), (k, v)

        x, (ks, vs) = lax.scan(
            body, x, (params["blocks"], kv_cache["k"], kv_cache["v"]))
        kv_cache = self._paged_tail_write(
            kv_cache, ks, vs, block_tables, prior_len, length)
        logits = self.head(params["head"], x)[0, length - 1]
        return logits, kv_cache

    def forward_decode_paged(self, params, token: jax.Array, kv_cache,
                             block_tables: jax.Array, pos: jax.Array):
        """One paged decode step over all lanes: token [B], pos [B],
        block_tables [B, P]. Same contract as forward_decode; inactive
        lanes park on the garbage page and decode harmlessly."""
        c = self.config
        pe = params["embed"]
        x = pe["wte"][token]
        if c.position_embedding == "learned":
            x = x + pe["wpe"][pos]
        x = x.astype(c.dtype)

        def body(x, sl):
            bp, kp, vp = sl
            x, kp, vp = self._paged_decode_sublayer(
                bp, x, kp, vp, block_tables, pos)
            return self.mlp_sublayer(bp, x), (kp, vp)

        x, (k_new, v_new) = lax.scan(
            body, x, (params["blocks"], kv_cache["k"], kv_cache["v"]))
        logits = self.head(params["head"], x[:, None, :])[:, 0]
        return logits, {"k": k_new, "v": v_new}

    @jax.named_scope("attention")
    def _paged_verify_sublayer(self, p, x, k_pool, v_pool, block_tables,
                               pos, n_live):
        """_paged_decode_sublayer for T speculative tokens per lane: write
        all T candidates' K/V through the block table (padding past
        n_live lands on the garbage page), then ragged multi-query
        attention where row i attends keys < pos + 1 + i. x [B, T, E];
        pools [N, H, page, D]; block_tables [B, P]; pos/n_live [B]."""
        c = self.config
        dt = c.dtype
        from oobleck_tpu.ops.attention import alibi_slopes
        from oobleck_tpu.ops.paged_attention import (
            paged_cache_write_multi, paged_verify_attention)

        h = _layer_norm(x, p["ln1"]["scale"], p["ln1"]["bias"], c.layer_norm_epsilon)
        wqkv = p["attn"]["wqkv"].astype(dt)                             # [E,3,H,D]
        qkv = jnp.einsum("bse,ethd->tbshd", h, wqkv) \
            + p["attn"]["bqkv"].astype(dt)[:, None, None]               # [3,B,T,H,D]
        k_pool = paged_cache_write_multi(
            k_pool, qkv[1], block_tables, pos, n_live)
        v_pool = paged_cache_write_multi(
            v_pool, qkv[2], block_tables, pos, n_live)
        slopes = alibi_slopes(c.num_heads) if c.position_embedding == "alibi" else None
        attn = paged_verify_attention(
            qkv[0], k_pool, v_pool, block_tables, pos + 1,
            alibi_slopes=slopes, impl=self._paged_impl())
        out = jnp.einsum("bthd,hde->bte", attn, p["attn"]["wo"].astype(dt))
        out = out + p["attn"]["bo"].astype(dt)
        return x + out, k_pool, v_pool

    def forward_verify_paged(self, params, tokens: jax.Array, kv_cache,
                             block_tables: jax.Array, pos: jax.Array,
                             n_live: jax.Array):
        """One speculative verify step over all lanes: tokens [B, T] (lane
        b's last emitted token followed by its k = T-1 draft candidates;
        columns past n_live[b] are bucket padding), pos [B] (absolute
        position of column 0), block_tables [B, P]. Column i embeds and
        attends at absolute position pos + i (wpe / ALiBi true distance),
        and its K/V is written through the table exactly as a sequential
        decode would have. Returns (logits [B, T, V] f32, updated pool);
        row i scores the token for position pos + i + 1, so row 0 of a
        T=1 call reproduces forward_decode_paged. Padded columns write to
        the garbage page and score garbage harmlessly."""
        c = self.config
        t_len = tokens.shape[-1]
        pe = params["embed"]
        x = pe["wte"][tokens]                                           # [B,T,E]
        if c.position_embedding == "learned":
            # Clip: a padded column of a near-max_seq lane may index past
            # the table; its output is garbage (and masked) either way.
            pos_abs = jnp.clip(
                pos[:, None] + jnp.arange(t_len), 0, pe["wpe"].shape[0] - 1)
            x = x + pe["wpe"][pos_abs]
        x = x.astype(c.dtype)

        def body(x, sl):
            bp, kp, vp = sl
            x, kp, vp = self._paged_verify_sublayer(
                bp, x, kp, vp, block_tables, pos, n_live)
            return self.mlp_sublayer(bp, x), (kp, vp)

        x, (k_new, v_new) = lax.scan(
            body, x, (params["blocks"], kv_cache["k"], kv_cache["v"]))
        logits = self.head(params["head"], x)
        return logits, {"k": k_new, "v": v_new}

    # ------------------------------------------------------------------ #
    # sharding + gradient-reduction rules                                 #
    # ------------------------------------------------------------------ #

    def param_specs(self, *, stacked: bool = True):
        """PartitionSpecs for full-manual execution over mesh axes
        (data, stage, fsdp, tensor). Blocks carry a leading layer dim sharded
        over `stage` when stacked."""
        s = ("stage",) if stacked else ()

        block = {
            "ln1": {"scale": P(*s), "bias": P(*s)},
            "attn": {
                "wqkv": P(*s, "fsdp", None, "tensor", None),
                "bqkv": P(*s, None, "tensor", None),
                "wo": P(*s, "tensor", None, "fsdp"),
                "bo": P(*s),
            },
            "ln2": {"scale": P(*s), "bias": P(*s)},
            "mlp": {
                "wi": P(*s, "fsdp", "tensor"),
                "bi": P(*s, "tensor"),
                "wo": P(*s, "tensor", "fsdp"),
                "bo": P(*s),
            },
        }
        embed = {"wte": P("tensor", None)}
        if self.config.position_embedding == "learned":
            embed["wpe"] = P(None, None)
        head = {"ln_f": {"scale": P(), "bias": P()}, "w": P(None, "tensor")}
        return {"embed": embed, "blocks": block, "head": head}


def cross_entropy_loss(logits: jax.Array, tokens: jax.Array,
                       vocab_size: int | None = None) -> jax.Array:
    """Next-token LM loss: positions :-1 predict tokens 1:. Any leading dims.
    `vocab_size` masks padded vocab columns when logits are padded."""
    logits = logits[..., :-1, :].astype(jnp.float32)
    if vocab_size is not None and logits.shape[-1] > vocab_size:
        mask = jnp.arange(logits.shape[-1]) < vocab_size
        logits = jnp.where(mask, logits, NEG_INF)
    targets = tokens[..., 1:]
    logz = jax.nn.logsumexp(logits, axis=-1)
    # The target's logit as a masked sum over the vocabulary (exact: one
    # term is not zero), not a gather: the sum fuses into the passes that
    # reduce logsumexp, while a gather has XLA write the float32 logits out
    # first, 824 MB a microbatch at [4, 1024, 50304], wherever the loss's
    # VALUE is wanted (pipeline.py's last stage returns it with the
    # gradients).
    hit = jnp.arange(logits.shape[-1]) == targets[..., None]
    gold = jnp.sum(jnp.where(hit, logits, 0.0), axis=-1)
    return jnp.mean(logz - gold)
