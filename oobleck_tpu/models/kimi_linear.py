"""Kimi-Linear-family decoder (`model_type: kimi_linear`, e.g.
Kimi-Linear-48B-A3B-Instruct) as an explicit layer list whose blocks are of
THREE kinds.

Every block is `h = x + Mixer(N(x)); y = h + FF(N(h))`, RMSNorm `N`. The
published lists number layers from 1: layer `l` mixes by Kimi Delta
Attention where `l` is in `kda_layers` and by latent attention where it is
in `full_attn_layers`; the first `first_k_dense_replace` layers' FF is
dense, the others' routed. `u = N(x)`:

  Mixer  KDA      `q = silu(conv(u W_q))`, `k = silu(conv(u W_k))`, `v =
                  silu(conv(u W_v))`: three depthwise causal convolutions
                  of `short_conv_kernel_size` taps, no bias
                  (`models/routed.short_conv`); q and k of unit length a
                  head (eps 1e-6), q times dk^-1/2; the log of the decay A
                  CHANNEL `g = -exp(A_log_h) softplus(u W_fa W_fb +
                  dt_bias)` and the write strength `beta = sigmoid(u W_b)`
                  a head, float32; the delta rule with a vector decay in
                  chunks of `chunk_size` (`ops/kda.py`); `y = w_n * o /
                  rms(o) * sigmoid(u W_ga W_gb)` a head over its dv (the
                  norm first, then the gate); `W_o`. The two gates'
                  projections are low-rank (`gate_rank`).
         latent   `models/deepseek_v3.latent_mixer` WITHOUT positions
                  (`mla_use_nope`): the same projections, the same shared
                  key a position, scores `(q_n . k_n + q_r . k_r) /
                  sqrt(dn + dr)`, no rotary on either. The positions are
                  the KDA layers'.
  FF     dense    SwiGLU of `intermediate_size`;
         routed   sigmoid scores over ALL `num_experts`, the top k of
                  score + bias (one group), weights normalised over the
                  chosen times `routed_scaling_factor`
                  (`ops/moe.routed_experts`) PLUS the shared experts: one
                  SwiGLU of `num_shared_experts` x `moe_intermediate_size`
                  on every token, weight 1.

The layer list, one chip's share (`num_experts_held`, `expert_offset`,
`vocab_rows_held`), the feed-forwards, the shared experts' sum and the
routing probe are `models/routed.py`'s. `layer_name` names a block by its
kind (`kda_dense_`, `kda_routed_`, `mla_routed_`, ...), so the planner's
profiler times each kind once.
"""

from __future__ import annotations

from dataclasses import dataclass, replace
from typing import Any

import jax
import jax.numpy as jnp

from oobleck_tpu.models.deepseek_v3 import latent_mixer
from oobleck_tpu.models.qwen3_next import unit_length
from oobleck_tpu.models.routed import (
    HeldShare,
    RoutedShareModel,
    rms_norm,
    short_conv,
)
from oobleck_tpu.ops.kda import kimi_delta_rule

KDA, MLA = "kda", "mla"
PUBLISHED_MLA = (4, 8, 12, 16, 20, 24, 27)


@dataclass(frozen=True)
class KimiLinearConfig(HeldShare):
    """Defaults: Kimi-Linear-48B-A3B-Instruct as published."""

    vocab_size: int = 163840
    vocab_rows_held: int | None = None           # None: all of them
    max_position_embeddings: int = 1048576       # model_max_length
    hidden_size: int = 2304
    num_layers: int = 27
    # The published lists, layers numbered from 1.
    kda_layers: tuple[int, ...] = tuple(
        l for l in range(1, 28) if l not in PUBLISHED_MLA)
    full_attn_layers: tuple[int, ...] = PUBLISHED_MLA
    linear_num_heads: int = 32                   # linear_attn_config.num_heads
    linear_head_dim: int = 128                   # linear_attn_config.head_dim
    short_conv_kernel_size: int = 4
    gate_rank: int = 128                         # the config is silent
    chunk_size: int = 64                         # the config is silent
    num_heads: int = 32
    head_dim: int = 72                           # published; no layer uses it
    kv_lora_rank: int = 512
    qk_nope_head_dim: int = 128
    qk_rope_head_dim: int = 64
    v_head_dim: int = 128
    intermediate_size: int = 9216
    moe_intermediate_size: int = 1024
    first_k_dense_replace: int = 1
    num_experts: int = 256
    num_experts_per_tok: int = 8
    num_shared_experts: int = 1
    norm_topk_prob: bool = True                  # moe_renormalize
    routed_scaling_factor: float = 2.446
    norm_eps: float = 1e-5
    latent_norm_eps: float = 1e-6
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
    def qk_head_dim(self) -> int:
        """A latent head's width in the scores: [nope | rope]."""
        return self.qk_nope_head_dim + self.qk_rope_head_dim

    @property
    def linear_dim(self) -> int:
        return self.linear_num_heads * self.linear_head_dim

    @property
    def shared_intermediate_size(self) -> int:
        return self.num_shared_experts * self.moe_intermediate_size

    def override(self, **kwargs) -> "KimiLinearConfig":
        fields = KimiLinearConfig.__dataclass_fields__
        unknown = [k for k in kwargs if k not in fields]
        if unknown:
            raise ValueError(f"unknown model_args {unknown}")
        for lists in ("kda_layers", "full_attn_layers"):
            if lists in kwargs:                  # a YAML or JSON list
                kwargs[lists] = tuple(int(l) for l in kwargs[lists])
        new = replace(self, **kwargs)
        if sorted(new.kda_layers + new.full_attn_layers) != list(
                range(1, new.num_layers + 1)):
            raise ValueError(
                f"kda_layers {new.kda_layers} and full_attn_layers "
                f"{new.full_attn_layers} do not name each of the "
                f"{new.num_layers} layers once")
        if not 0 <= new.first_k_dense_replace <= new.num_layers:
            raise ValueError(
                f"first_k_dense_replace {new.first_k_dense_replace} of "
                f"{new.num_layers} layers")
        new.check_share()
        return new


class KimiLinearModel(RoutedShareModel):
    """Layer-list Kimi-Linear decoder; generic stage path only."""

    # Leaves with these names take no gradient and no optimizer state
    # (parallel/train.py::make_optimizer): the selection bias selects.
    frozen_param_names = ("expert_bias",)

    def kind(self, block: int) -> str:
        return MLA if block + 1 in self.config.full_attn_layers else KDA

    def is_routed(self, block: int) -> bool:
        return block >= self.config.first_k_dense_replace

    def layer_name(self, index: int) -> str:
        """A block is named by its mixer and its FF, then its index: the
        profiler times the first of each prefix and reuses it for the
        rest."""
        name = super().layer_name(index)
        if not name.startswith("block_"):
            return name
        block = index - 1
        ff = "routed" if self.is_routed(block) else "dense"
        return f"{self.kind(block)}_{ff}_{block}"

    # ---- init ----

    def _init_block(self, rng, block: int):
        c = self.config
        ks = jax.random.split(rng, 24)
        pd, std = c.param_dtype, c.initializer_range
        res_std = std / (2 * c.num_layers) ** 0.5
        e = c.hidden_size
        normal = lambda k, shape, s: jax.random.normal(k, shape, pd) * s
        swiglu = lambda k1, k3, k2, lead, f: {
            "w1": normal(k1, (*lead, e, f), std),
            "w3": normal(k3, (*lead, e, f), std),
            "w2": normal(k2, (*lead, f, e), res_std)}
        p = {"ln_op": {"scale": jnp.ones((e,), pd)},
             "ln_ff": {"scale": jnp.ones((e,), pd)}}
        if self.kind(block) == KDA:
            h, d, r = c.linear_num_heads, c.linear_head_dim, c.gate_rank
            wide, taps = c.linear_dim, c.short_conv_kernel_size
            bound = taps ** -0.5
            conv = lambda k: jax.random.uniform(k, (taps, wide), pd,
                                                -bound, bound)
            # The step dt = softplus(dt_bias) log-uniform in [1e-3, 0.1].
            dt = jnp.exp(jax.random.uniform(
                ks[9], (wide,), pd, jnp.log(1e-3), jnp.log(0.1)))
            p[KDA] = {
                "w_q": normal(ks[0], (e, wide), std),
                "w_k": normal(ks[1], (e, wide), std),
                "w_v": normal(ks[2], (e, wide), std),
                "conv_q": conv(ks[3]), "conv_k": conv(ks[4]),
                "conv_v": conv(ks[5]),
                "w_fa": normal(ks[6], (e, r), std),
                "w_fb": normal(ks[7], (r, wide), std),
                # A uniform in [1, 16]: its log is a parameter.
                "A_log": jnp.log(jax.random.uniform(ks[8], (h,), pd, 1.0,
                                                    16.0)),
                "dt_bias": dt + jnp.log(-jnp.expm1(-dt)),
                "w_b": normal(ks[10], (e, h), std),
                "w_ga": normal(ks[11], (e, r), std),
                "w_gb": normal(ks[12], (r, wide), std),
                "norm": jnp.ones((d,), pd),
                "w_o": normal(ks[13], (wide, e), res_std)}
        else:
            h, r = c.num_heads, c.kv_lora_rank
            dn, dr, dv = c.qk_nope_head_dim, c.qk_rope_head_dim, c.v_head_dim
            p["attn"] = {"wq": normal(ks[0], (e, h, dn + dr), std),
                         "wkv_a": normal(ks[1], (e, r + dr), std),
                         "kv_norm": jnp.ones((r,), pd),
                         "wkv_b": normal(ks[2], (r, h, dn + dv), std),
                         "wo": normal(ks[3], (h, dv, e), res_std)}
        if not self.is_routed(block):
            p["ff"] = swiglu(ks[14], ks[15], ks[16], (), c.intermediate_size)
        else:
            p["ff"] = {
                "router": normal(ks[17], (e, c.num_experts), std),
                # Seeded and not zero, so that selecting by score + bias
                # and weighting by score really differ (models/lfm2.py).
                "expert_bias": normal(ks[18], (c.num_experts,),
                                      c.expert_bias_range),
                **swiglu(ks[19], ks[20], ks[21], (c.experts_held,),
                         c.moe_intermediate_size),
                "shared": swiglu(*jax.random.split(ks[22], 3), (),
                                 c.shared_intermediate_size)}
        return p

    # ---- forward ----

    @jax.named_scope("kda_mixer")
    def kda_operator(self, block: int, p, u):
        c = self.config
        dt, f32 = c.dtype, jnp.float32
        b, s, _ = u.shape
        h, d = c.linear_num_heads, c.linear_head_dim
        heads = lambda t: t.reshape(b, s, h, d)
        mixed = lambda w, taps: heads(jax.nn.silu(short_conv(
            (u @ p[w].astype(dt)).astype(f32), p[taps].astype(f32))))
        low_rank = lambda a, b_: ((u @ p[a].astype(dt))
                                  @ p[b_].astype(dt)).astype(f32)
        q = unit_length(mixed("w_q", "conv_q")) * d ** -0.5
        k = unit_length(mixed("w_k", "conv_k"))
        v = mixed("w_v", "conv_v")
        g = -jnp.exp(p["A_log"].astype(f32))[:, None] * jax.nn.softplus(
            heads(low_rank("w_fa", "w_fb") + p["dt_bias"].astype(f32)))
        beta = jax.nn.sigmoid((u @ p["w_b"].astype(dt)).astype(f32))
        o = kimi_delta_rule(q.astype(dt), k.astype(dt), v.astype(dt), g, beta,
                            chunk=c.chunk_size, layer=str(block))
        y = rms_norm(o.astype(f32), p["norm"].astype(f32), c.norm_eps)
        y = y * jax.nn.sigmoid(heads(low_rank("w_ga", "w_gb")))
        return y.reshape(b, s, h * d).astype(dt) @ p["w_o"].astype(dt)

    def operator_out(self, block: int, p, h):
        if self.kind(block) == KDA:
            return self.kda_operator(block, p[KDA], h)
        with jax.named_scope("mla_mixer"):
            return latent_mixer(self.config, p["attn"], h, rotary=None)
