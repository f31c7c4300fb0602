"""LFM2-MoE decoder (LiquidAI `lfm2_moe`, e.g. LFM2-24B-A2B) as an explicit
layer list whose layers DIFFER.

Every block is `h = x + Op(N(x)); y = h + FF(N(h))` with RMSNorm `N`:

  Op  "conv"            gated short convolution: [B | C | u] = W_in x,
                        z = causal depthwise conv (kernel `conv_L_cache`)
                        of B * u, Op = W_out (C * z). No activation.
      "full_attention"  grouped-query attention, RMSNorm over each head of
                        q and k, rotate-half RoPE, causal softmax.
  FF  dense SwiGLU      for layer index < `num_dense_layers`;
      routed experts    otherwise: sigmoid scores over ALL `num_experts`,
                        top-k of score + bias, weights from the scores
                        (`ops/moe.routed_experts`).

`layer_types` says which operator a layer has. The layer list is
[embed, block_0 .. block_{L-1}, head], as every family's, so the planner
profiles and the MPMD pipeline splits it unchanged; the model runs the
generic stage path (no manual-collective contract).

One chip's share of an expert-parallel deployment is a model of its own
here (`num_experts_held` experts from `expert_offset`, the first
`vocab_rows_held` rows of the vocabulary): `models/routed.py` holds that,
the layer list, the dense and routed feed-forwards and the routing probe,
for this family and `models/deepseek_v3.py` alike.

The expert bias is a leaf of the parameters that is never trained
(`frozen_param_names`): no gradient reaches it and the optimizer keeps no
state for it.
"""

from __future__ import annotations

from dataclasses import dataclass, replace
from typing import Any

import jax
import jax.numpy as jnp

from oobleck_tpu.models.routed import (  # noqa: F401  (routing_probe: its callers' name for it)
    HeldShare,
    RoutedShareModel,
    _probe_program,
    rms_norm,
    rotate_half,
    routing_probe,
    short_conv,
)
from oobleck_tpu.ops.attention import causal_attention

CONV, ATTN = "conv", "full_attention"


def published_layer_types(num_layers: int) -> tuple[str, ...]:
    """conv, conv, attention, then (conv, conv, conv, attention) repeating:
    the published list (30 conv + 10 attention at 40 layers)."""
    return tuple(ATTN if i >= 2 and (i - 2) % 4 == 0 else CONV
                 for i in range(num_layers))


@dataclass(frozen=True)
class Lfm2Config(HeldShare):
    vocab_size: int = 65536
    vocab_rows_held: int | None = None           # None: all of them
    max_position_embeddings: int = 128000
    hidden_size: int = 2048
    num_layers: int = 40
    num_heads: int = 32
    num_kv_heads: int = 8
    intermediate_size: int = 11776
    moe_intermediate_size: int = 1536
    num_experts: int = 64
    num_experts_per_tok: int = 4
    num_dense_layers: int = 2
    layer_types: tuple[str, ...] | None = None   # None: the published list
    norm_topk_prob: bool = True
    use_expert_bias: bool = True
    routed_scaling_factor: float = 1.0
    conv_L_cache: int = 3
    norm_eps: float = 1e-5
    rope_theta: float = 1e6
    expert_offset: int = 0
    num_experts_held: int | None = None          # None: all of them
    initializer_range: float = 0.02
    expert_bias_range: float = 0.01
    dtype: Any = jnp.bfloat16
    param_dtype: Any = jnp.float32
    attention_impl: str = "auto"
    remat: bool = True
    vocab_pad_multiple: int = 128

    @property
    def head_dim(self) -> int:
        assert self.hidden_size % self.num_heads == 0
        return self.hidden_size // self.num_heads

    @property
    def operators(self) -> tuple[str, ...]:
        return (published_layer_types(self.num_layers)
                if self.layer_types is None else self.layer_types)

    def override(self, **kwargs) -> "Lfm2Config":
        unknown = [k for k in kwargs if k not in Lfm2Config.__dataclass_fields__]
        if unknown:
            raise ValueError(f"unknown model_args {unknown}")
        if kwargs.get("layer_types") is not None:
            kwargs["layer_types"] = tuple(kwargs["layer_types"])
        new = replace(self, **kwargs)
        ops = new.operators
        if len(ops) != new.num_layers or set(ops) - {CONV, ATTN}:
            raise ValueError(
                f"layer_types must name {new.num_layers} operators out of "
                f"{CONV!r} / {ATTN!r}, got {ops}")
        new.check_share()
        return new


class Lfm2Model(RoutedShareModel):
    """Layer-list LFM2-MoE decoder; generic stage path only."""

    # Leaves with these names take no gradient and no optimizer state
    # (parallel/train.py::make_optimizer).
    frozen_param_names = ("expert_bias",)

    def operator(self, block: int) -> str:
        return self.config.operators[block]

    def is_routed(self, block: int) -> bool:
        return block >= self.config.num_dense_layers

    def _init_block(self, rng, block: int):
        c = self.config
        ks = jax.random.split(rng, 12)
        pd, std = c.param_dtype, c.initializer_range
        res_std = std / (2 * c.num_layers) ** 0.5
        e, h, kv, d = c.hidden_size, c.num_heads, c.num_kv_heads, c.head_dim
        normal = lambda k, shape, s: jax.random.normal(k, shape, pd) * s
        p = {"ln_op": {"scale": jnp.ones((e,), pd)},
             "ln_ff": {"scale": jnp.ones((e,), pd)}}
        if self.operator(block) == CONV:
            p["conv"] = {
                "w_in": normal(ks[0], (e, 3, e), std),
                "taps": normal(ks[1], (c.conv_L_cache, e),
                               c.conv_L_cache ** -0.5),
                "w_out": normal(ks[2], (e, e), res_std),
            }
        else:
            p["attn"] = {
                "wq": normal(ks[0], (e, h, d), std),
                "wk": normal(ks[1], (e, kv, d), std),
                "wv": normal(ks[2], (e, kv, d), std),
                "q_norm": jnp.ones((d,), pd),
                "k_norm": jnp.ones((d,), pd),
                "wo": normal(ks[3], (h, d, e), res_std),
            }
        if not self.is_routed(block):
            f = c.intermediate_size
            p["ff"] = {"w1": normal(ks[4], (e, f), std),
                       "w3": normal(ks[5], (e, f), std),
                       "w2": normal(ks[6], (f, e), res_std)}
        else:
            f, ne, held = c.moe_intermediate_size, c.num_experts, c.experts_held
            p["ff"] = {"router": normal(ks[7], (e, ne), std),
                       "w1": normal(ks[8], (held, e, f), std),
                       "w3": normal(ks[9], (held, e, f), std),
                       "w2": normal(ks[10], (held, f, e), res_std)}
            if c.use_expert_bias:
                # Seeded and not zero, so that selecting by score + bias
                # and weighting by score really differ; small beside the
                # scores' spread, as a bias that its (unpublished) update
                # rule has balanced the experts' loads with would be.
                p["ff"]["expert_bias"] = normal(
                    ks[11], (ne,), c.expert_bias_range)
        return p

    @jax.named_scope("conv")
    def conv_operator(self, p, h):
        dt = self.config.dtype
        bcu = jnp.einsum("bse,ekd->kbsd", h, p["w_in"].astype(dt))
        z = short_conv(bcu[0] * bcu[2], p["taps"].astype(dt))
        return (bcu[1] * z) @ p["w_out"].astype(dt)

    @jax.named_scope("attention")
    def attention_operator(self, p, h):
        c = self.config
        dt = c.dtype
        q = jnp.einsum("bse,ehd->bhsd", h, p["wq"].astype(dt))
        k = jnp.einsum("bse,ehd->bhsd", h, p["wk"].astype(dt))
        v = jnp.einsum("bse,ehd->bhsd", h, p["wv"].astype(dt))
        q = rotate_half(rms_norm(q, p["q_norm"], c.norm_eps), c.rope_theta)
        k = rotate_half(rms_norm(k, p["k_norm"], c.norm_eps), c.rope_theta)
        rep = c.num_heads // c.num_kv_heads
        if rep > 1:
            k = jnp.repeat(k, rep, axis=1)
            v = jnp.repeat(v, rep, axis=1)
        attn = causal_attention(q, k, v, impl=c.attention_impl)
        return jnp.einsum("bhsd,hde->bse", attn, p["wo"].astype(dt))

    def operator_out(self, block: int, p, h):
        if self.operator(block) == CONV:
            return self.conv_operator(p["conv"], h)
        return self.attention_operator(p["attn"], h)
