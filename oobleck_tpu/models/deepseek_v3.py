"""DeepSeek-V3-family decoder (`model_type: deepseek_v3`, e.g.
Moonlight-16B-A3B) as an explicit layer list: latent attention in every
block, a dense SwiGLU in the first `first_k_dense_replace` blocks, routed
experts BESIDE shared experts in the rest.

Every block is `h = x + Attn(N(x)); y = h + FF(N(h))`, RMSNorm `N`:

  Attn  latent attention (MLA). Queries straight from the hidden state
        (`q_lora_rank` null), per head [q_nope | q_rope]. Keys and values
        through a latent: `[c | k_rope] = h Wkv_a`, `c` of `kv_lora_rank`
        normed by an RMSNorm of its own, `[k_nope | v] = N(c) Wkv_b` per
        head; `k_rope` is ONE vector a position that all heads share.
        Rotary (rotate-half, whole `qk_rope_head_dim`) on q_rope and
        k_rope; scores over the `qk_nope_head_dim + qk_rope_head_dim`
        wide [nope | rope], values `v_head_dim` wide
        (`ops/attention.latent_attention`). The mixer is `latent_mixer`,
        which has two users: this family, WITH the rotary, and
        `models/kimi_linear.py`'s latent layers, WITHOUT (no positions:
        q_rope and k_rope enter the scores as projected).
  FF    dense   SwiGLU of `intermediate_size`;
        routed  sigmoid scores over ALL `num_experts`, the top k of
                score + bias (one group: `n_group` = `topk_group` = 1),
                weights normalised over the chosen times
                `routed_scaling_factor` (`ops/moe.routed_experts`) PLUS
                the shared experts: one SwiGLU of `n_shared_experts` x
                `moe_intermediate_size` on every token, weight 1.

The layer list, one chip's share (`num_experts_held`, `expert_offset`,
`vocab_rows_held`), the feed-forwards, the shared experts' sum and the
routing probe are `models/routed.py`'s, shared with `models/lfm2.py` and
`models/nemotron_h.py`. The shared experts are added there and not by
`routed_experts`: every chip of an expert-parallel group computes them
alike, so the parts the shares give add up to the layer with them counted
once. `layer_name` names a block by its kind, so the planner's profiler
times each kind once.
"""

from __future__ import annotations

from dataclasses import dataclass, replace
from typing import Any

import jax
import jax.numpy as jnp

from oobleck_tpu.models.routed import (
    HeldShare,
    RoutedShareModel,
    rms_norm,
    rotate_half,
)
from oobleck_tpu.ops.attention import latent_attention


@dataclass(frozen=True)
class DeepseekV3Config(HeldShare):
    """Defaults: Moonlight-16B-A3B as published."""

    vocab_size: int = 163840
    vocab_rows_held: int | None = None           # None: all of them
    max_position_embeddings: int = 8192
    hidden_size: int = 2048
    num_layers: int = 27
    num_heads: int = 16
    kv_lora_rank: int = 512
    qk_nope_head_dim: int = 128
    qk_rope_head_dim: int = 64
    v_head_dim: int = 128
    intermediate_size: int = 11264
    moe_intermediate_size: int = 1408
    first_k_dense_replace: int = 1
    num_experts: int = 64                        # n_routed_experts
    num_experts_per_tok: int = 6
    n_shared_experts: int = 2
    norm_topk_prob: bool = True
    routed_scaling_factor: float = 2.446
    norm_eps: float = 1e-5
    latent_norm_eps: float = 1e-6
    rope_theta: float = 50000.0
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
        """A head's width in the scores: [nope | rope]."""
        return self.qk_nope_head_dim + self.qk_rope_head_dim

    @property
    def shared_intermediate_size(self) -> int:
        return self.n_shared_experts * self.moe_intermediate_size

    def override(self, **kwargs) -> "DeepseekV3Config":
        fields = DeepseekV3Config.__dataclass_fields__
        unknown = [k for k in kwargs if k not in fields]
        if unknown:
            raise ValueError(f"unknown model_args {unknown}")
        new = replace(self, **kwargs)
        if not 0 <= new.first_k_dense_replace <= new.num_layers:
            raise ValueError(
                f"first_k_dense_replace {new.first_k_dense_replace} of "
                f"{new.num_layers} layers")
        if new.qk_rope_head_dim % 2:
            raise ValueError(
                f"qk_rope_head_dim {new.qk_rope_head_dim} is not even")
        new.check_share()
        return new


class DeepseekV3Model(RoutedShareModel):
    """Layer-list DeepSeek-V3-family decoder; generic stage path only."""

    # Leaves with these names take no gradient and no optimizer state
    # (parallel/train.py::make_optimizer): the selection bias selects.
    frozen_param_names = ("expert_bias",)

    def layer_name(self, index: int) -> str:
        """A block is named by its kind, then its index: the profiler
        times the first of each prefix and reuses it for the rest."""
        name = super().layer_name(index)
        if not name.startswith("block_"):
            return name
        kind = "routed" if self.is_routed(index - 1) else "dense"
        return f"{kind}_{index - 1}"

    def is_routed(self, block: int) -> bool:
        return block >= self.config.first_k_dense_replace

    # ---- init ----

    def _init_block(self, rng, block: int):
        c = self.config
        ks = jax.random.split(rng, 13)
        pd, std = c.param_dtype, c.initializer_range
        res_std = std / (2 * c.num_layers) ** 0.5
        e, h, r = c.hidden_size, c.num_heads, c.kv_lora_rank
        dn, dr, dv = c.qk_nope_head_dim, c.qk_rope_head_dim, c.v_head_dim
        normal = lambda k, shape, s: jax.random.normal(k, shape, pd) * s
        swiglu = lambda k1, k3, k2, lead, f: {
            "w1": normal(k1, (*lead, e, f), std),
            "w3": normal(k3, (*lead, e, f), std),
            "w2": normal(k2, (*lead, f, e), res_std)}
        p = {"ln_op": {"scale": jnp.ones((e,), pd)},
             "ln_ff": {"scale": jnp.ones((e,), pd)},
             "attn": {"wq": normal(ks[0], (e, h, dn + dr), std),
                      "wkv_a": normal(ks[1], (e, r + dr), std),
                      "kv_norm": jnp.ones((r,), pd),
                      "wkv_b": normal(ks[2], (r, h, dn + dv), std),
                      "wo": normal(ks[3], (h, dv, e), res_std)}}
        if not self.is_routed(block):
            p["ff"] = swiglu(ks[4], ks[5], ks[6], (), c.intermediate_size)
        else:
            p["ff"] = {
                "router": normal(ks[7], (e, c.num_experts), std),
                # Seeded and not zero, so that selecting by score + bias
                # and weighting by score really differ (models/lfm2.py).
                "expert_bias": normal(ks[8], (c.num_experts,),
                                      c.expert_bias_range),
                **swiglu(ks[9], ks[10], ks[11], (c.experts_held,),
                         c.moe_intermediate_size),
                "shared": swiglu(*jax.random.split(ks[12], 3), (),
                                 c.shared_intermediate_size)}
        return p

    # ---- forward ----

    @jax.named_scope("attention")
    def operator_out(self, block: int, p, h):
        theta = self.config.rope_theta
        return latent_mixer(self.config, p["attn"], h,
                            rotary=lambda x: rotate_half(x, theta))


def latent_mixer(c, p, h, *, rotary):
    """Latent attention of `h` [B, S, E] under the projections `p` (`wq`,
    `wkv_a`, `kv_norm`, `wkv_b`, `wo`) and the widths of `c`. `rotary`
    turns q_rope [B, H, S, Dr] and the shared k_rope [B, S, Dr] by their
    positions; None leaves both as projected (`models/kimi_linear.py`'s
    latent layers, which carry no positions)."""
    dt = c.dtype
    dn, r = c.qk_nope_head_dim, c.kv_lora_rank
    rotary = rotary or (lambda x: x)
    q = jnp.einsum("bse,ehd->bhsd", h, p["wq"].astype(dt))
    kv_a = h @ p["wkv_a"].astype(dt)                  # [B, S, r + Dr]
    latent = rms_norm(kv_a[..., :r], p["kv_norm"], c.latent_norm_eps)
    kv = jnp.einsum("bsr,rhd->bhsd", latent, p["wkv_b"].astype(dt))
    attn = latent_attention(
        q[..., :dn], rotary(q[..., dn:]), kv[..., :dn], rotary(kv_a[..., r:]),
        kv[..., dn:], impl=c.attention_impl)
    return jnp.einsum("bhsd,hde->bse", attn, p["wo"].astype(dt))
