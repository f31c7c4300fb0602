"""SmallThinker-family decoder (`model_name: smallthinker_21b_instruct`,
SmallThinker-21BA3B-Instruct) as an explicit layer list whose layers DIFFER
by their attention's kind.

Every block is

    h = N1(x);  x = x + Attn_i(h);  x = x + Experts(N2(x); routed by h)

with the plain RMSNorm `N` (a weight initialised 1): the ROUTER reads the
block's input as the attention does (`router_reads = OP`), the experts read
the normed residual stream after the attention.

  Attn  `num_heads` query heads and `num_kv_heads` key-value heads of
        `head_dim`, no bias, no norm over a head; each key-value head
        serves `num_heads / num_kv_heads` query heads; scale
        head_dim^-1/2. Where `sliding_window_layout[i]` is 0 the layer is
        full causal attention, else query i sees key j iff
        0 <= i - j < `sliding_window_size`; where `rope_layout[i]` is 1 q
        and k take rotate-half rotary over the whole head at `rope_theta`,
        else the layer has no positional term at all. Both lists are the
        configuration's (published: 0 every fourth layer, 1 between).
  FF    a softmax over ALL `num_experts` in float32, its top k, weights
        normalised over the chosen (`ops/moe.route(score="softmax")`: the
        published softmax over the top-k logits is that, and the softmax is
        monotone, so the choice is the same); ReGLU experts
        `W2 (relu(W1 y) * W3 y)`; no shared expert, no selection bias, no
        scaling factor.

The layer list, one chip's share (`num_experts_held`, `expert_offset`,
`vocab_rows_held`), the routed call and the routing probe are
`models/routed.py`'s, shared with the four families before this one.
`layer_name` names a block by its attention's kind, so the planner's
profiler times each kind once.
"""

from __future__ import annotations

from dataclasses import dataclass, replace
from typing import Any

import jax
import jax.numpy as jnp

from oobleck_tpu.models.routed import (
    OP,
    HeldShare,
    RoutedShareModel,
    rotate_half,
)
from oobleck_tpu.ops.attention import causal_attention

FULL, SWA = "full_attn", "swa_attn"
# The embedding alone is drawn at unit variance: at `initializer_range` the
# residual stream of a freshly drawn model is what the attention layers
# AVERAGE (all tokens of a sequence alike), and a router that reads it
# sends every token of a sequence to the same few experts (PERF.md, PR 45).
EMBEDDING_STD = 1.0


def published_layout(num_layers: int) -> tuple[int, ...]:
    """0 every fourth layer from layer 0, 1 between: the published
    `sliding_window_layout` and `rope_layout` alike."""
    return tuple(int(i % 4 != 0) for i in range(num_layers))


@dataclass(frozen=True)
class SmallThinkerConfig(HeldShare):
    """Defaults: SmallThinker-21BA3B-Instruct as published."""

    vocab_size: int = 151936
    vocab_rows_held: int | None = None           # None: all of them
    max_position_embeddings: int = 16384
    hidden_size: int = 2560
    num_layers: int = 52
    num_heads: int = 28
    num_kv_heads: int = 4
    head_dim: int = 128
    sliding_window_size: int = 4096
    sliding_window_layout: tuple[int, ...] | None = None   # None: published
    rope_layout: tuple[int, ...] | None = None             # None: published
    rope_theta: float = 1.5e6
    moe_intermediate_size: int = 768
    num_experts: int = 64
    num_experts_per_tok: int = 6
    norm_topk_prob: bool = True
    routed_scaling_factor: float = 1.0
    norm_eps: float = 1e-6
    expert_offset: int = 0
    num_experts_held: int | None = None          # None: all of them
    initializer_range: float = 0.02
    dtype: Any = jnp.bfloat16
    param_dtype: Any = jnp.float32
    attention_impl: str = "auto"
    remat: bool = True
    vocab_pad_multiple: int = 128

    @property
    def intermediate_size(self) -> int:
        """No layer is dense: `HeldShare.ffn_dim` reads the experts'."""
        return self.moe_intermediate_size

    @property
    def windowed(self) -> tuple[int, ...]:
        return (published_layout(self.num_layers)
                if self.sliding_window_layout is None
                else self.sliding_window_layout)

    @property
    def rotary(self) -> tuple[int, ...]:
        return (published_layout(self.num_layers)
                if self.rope_layout is None else self.rope_layout)

    def override(self, **kwargs) -> "SmallThinkerConfig":
        fields = SmallThinkerConfig.__dataclass_fields__
        unknown = [k for k in kwargs if k not in fields]
        if unknown:
            raise ValueError(f"unknown model_args {unknown}")
        for layout in ("sliding_window_layout", "rope_layout"):
            if kwargs.get(layout) is not None:
                kwargs[layout] = tuple(int(v) for v in kwargs[layout])
        new = replace(self, **kwargs)
        for name, layout in (("sliding_window_layout", new.windowed),
                             ("rope_layout", new.rotary)):
            if len(layout) != new.num_layers or set(layout) - {0, 1}:
                raise ValueError(
                    f"{name} must hold {new.num_layers} entries of 0 / 1, "
                    f"got {layout}")
        if new.num_heads % new.num_kv_heads or new.head_dim % 2 or (
                new.sliding_window_size < 1):
            raise ValueError(
                f"query {new.num_heads} / key-value {new.num_kv_heads} "
                f"heads of {new.head_dim}, window {new.sliding_window_size}")
        new.check_share()
        return new


class SmallThinkerModel(RoutedShareModel):
    """Layer-list SmallThinker decoder; generic stage path only."""

    router_score = "softmax"
    router_reads = OP
    expert_activation = "reglu"

    def kind(self, block: int) -> str:
        return SWA if self.config.windowed[block] else FULL

    def layer_name(self, index: int) -> str:
        """A block is named by its attention's kind, then its index: the
        profiler times the first of each prefix and reuses it for the
        rest."""
        name = super().layer_name(index)
        if not name.startswith("block_"):
            return name
        return f"{self.kind(index - 1)}_{index - 1}"

    def is_routed(self, block: int) -> bool:
        return True

    def _init_embed(self, rng):
        c = self.config
        return {"wte": jax.random.normal(
            rng, (c.padded_vocab_size, c.hidden_size), c.param_dtype
        ) * EMBEDDING_STD}

    def _init_block(self, rng, block: int):
        c = self.config
        ks = jax.random.split(rng, 8)
        pd, std = c.param_dtype, c.initializer_range
        res_std = std / (2 * c.num_layers) ** 0.5
        e, h, kv, d = c.hidden_size, c.num_heads, c.num_kv_heads, c.head_dim
        f, held = c.moe_intermediate_size, c.experts_held
        normal = lambda k, shape, s: jax.random.normal(k, shape, pd) * s
        return {
            "ln_op": {"scale": jnp.ones((e,), pd)},
            "ln_ff": {"scale": jnp.ones((e,), pd)},
            "attn": {"wq": normal(ks[0], (e, h, d), std),
                     "wk": normal(ks[1], (e, kv, d), std),
                     "wv": normal(ks[2], (e, kv, d), std),
                     "wo": normal(ks[3], (h, d, e), res_std)},
            "ff": {"router": normal(ks[4], (e, c.num_experts), std),
                   "w1": normal(ks[5], (held, e, f), std),
                   "w3": normal(ks[6], (held, e, f), std),
                   "w2": normal(ks[7], (held, f, e), res_std)}}

    def _attention(self, block: int, p, u):
        c = self.config
        dt = c.dtype
        q = jnp.einsum("bse,ehd->bhsd", u, p["wq"].astype(dt))
        k = jnp.einsum("bse,ehd->bhsd", u, p["wk"].astype(dt))
        v = jnp.einsum("bse,ehd->bhsd", u, p["wv"].astype(dt))
        if c.rotary[block]:
            q = rotate_half(q, c.rope_theta)
            k = rotate_half(k, c.rope_theta)
        rep = c.num_heads // c.num_kv_heads
        if rep > 1:
            k = jnp.repeat(k, rep, axis=1)
            v = jnp.repeat(v, rep, axis=1)
        attn = causal_attention(
            q, k, v, impl=c.attention_impl,
            window=c.sliding_window_size if c.windowed[block] else None)
        return jnp.einsum("bhsd,hde->bse", attn, p["wo"].astype(dt))

    def operator_out(self, block: int, p, h):
        with jax.named_scope(self.kind(block)):
            return self._attention(block, p["attn"], h)
