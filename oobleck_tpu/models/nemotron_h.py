"""Nemotron-H-family decoder (`model_type: nemotron_h`, e.g.
NVIDIA-Nemotron-3-Nano-30B-A3B) as an explicit layer list whose layers
DIFFER: every layer is ONE mixer behind one RMSNorm, `x + Mixer(N(x))`, and
a published string (`hybrid_override_pattern`) says which, a character a
layer:

  M  Mamba-2. `[z | xBC | dt] = u W_in`; `xBC = silu(conv(xBC) + b)`, a
     depthwise causal convolution of `conv_kernel` taps a channel
     (`models/routed.short_conv`); xBC splits into x (`mamba_num_heads`
     heads of `mamba_head_dim`), B and C (`n_groups` groups of
     `ssm_state_size`; head h reads group h // (heads / groups));
     `dt = softplus(dt + dt_bias)`, `A = -exp(A_log)`, a scalar a head;
     the recurrence `H_t = exp(dt_t A) H_{t-1} + dt_t x_t (x) B_t`,
     `y_t = H_t C_t + D x_t` in chunks of `chunk_size` (`ops/ssd.py`: on
     a TPU two Pallas kernels, `ssd_fwd` and `ssd_bwd`, whose forward's
     outputs the block's checkpoint keeps; `jax.numpy` elsewhere);
     `y * silu(z)`, then an RMSNorm over each group's channels with a
     learned scale (gate first, norm after); `W_out`. No projection bias.
  *  grouped-query attention: `num_heads` query and `num_kv_heads`
     key-value heads of `head_dim`, causal softmax, no bias, no rotary or
     other positional term.
  E  routed experts WITHOUT a gate, `W2 relu(W1 u)^2`: sigmoid scores over
     ALL `num_experts`, the top k of score + bias, weights normalised over
     the chosen times `routed_scaling_factor` (`ops/moe.routed_experts`),
     BESIDE one shared expert of `moe_shared_expert_intermediate_size`
     on every token.

M and * are a block's operator branch, E its feed-forward branch
(`models/routed.py`: the layer list, one chip's share (`num_experts_held`,
`expert_offset`, `vocab_rows_held`), the feed-forwards, the shared expert's
sum and the routing probe are its, shared with `models/lfm2.py` and
`models/deepseek_v3.py`). `layer_name` names a block by its kind, so the
planner's profiler times each kind once.
"""

from __future__ import annotations

import math
from dataclasses import dataclass, replace
from typing import Any

import jax
import jax.numpy as jnp

from oobleck_tpu.models.routed import (
    FF,
    OP,
    HeldShare,
    RoutedShareModel,
    short_conv,
)
from oobleck_tpu.ops.attention import causal_attention
from oobleck_tpu.ops.ssd import ssd_scan

MAMBA, ATTN, EXPERTS = "M", "*", "E"
KIND_NAMES = {MAMBA: "mamba", ATTN: "attn", EXPERTS: "routed"}
PUBLISHED_PATTERN = "MEMEM*EMEMEM*EMEMEM*EMEMEM*EMEMEM*EMEMEMEM*EMEMEMEME"


@dataclass(frozen=True)
class NemotronHConfig(HeldShare):
    """Defaults: NVIDIA-Nemotron-3-Nano-30B-A3B as published."""

    vocab_size: int = 131072
    vocab_rows_held: int | None = None           # None: all of them
    max_position_embeddings: int = 262144
    hidden_size: int = 2688
    num_layers: int = 52
    hybrid_override_pattern: str = PUBLISHED_PATTERN
    mamba_num_heads: int = 64
    mamba_head_dim: int = 64
    ssm_state_size: int = 128
    n_groups: int = 8
    conv_kernel: int = 4
    chunk_size: int = 128
    time_step_min: float = 0.001
    time_step_max: float = 0.1
    time_step_floor: float = 1e-4
    num_heads: int = 32
    num_kv_heads: int = 2
    head_dim: int = 128
    intermediate_size: int = 1856                # published; `HeldShare.ffn_dim`
    moe_intermediate_size: int = 1856
    moe_shared_expert_intermediate_size: int = 3712
    num_experts: int = 128                       # n_routed_experts
    num_experts_per_tok: int = 6
    norm_topk_prob: bool = True
    routed_scaling_factor: float = 2.5
    norm_eps: float = 1e-5
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
    def mamba_inner(self) -> int:
        return self.mamba_num_heads * self.mamba_head_dim

    @property
    def conv_dim(self) -> int:
        """Channels of xBC: x, then B and C of every group."""
        return self.mamba_inner + 2 * self.n_groups * self.ssm_state_size

    def override(self, **kwargs) -> "NemotronHConfig":
        fields = NemotronHConfig.__dataclass_fields__
        unknown = [k for k in kwargs if k not in fields]
        if unknown:
            raise ValueError(f"unknown model_args {unknown}")
        new = replace(self, **kwargs)
        pattern = new.hybrid_override_pattern
        if len(pattern) != new.num_layers or set(pattern) - set(KIND_NAMES):
            raise ValueError(
                f"hybrid_override_pattern must name {new.num_layers} layers "
                f"out of {sorted(KIND_NAMES)}, got {pattern!r}")
        if (new.mamba_num_heads % new.n_groups
                or new.num_heads % new.num_kv_heads):
            raise ValueError(
                f"heads {new.mamba_num_heads} / groups {new.n_groups}, "
                f"query {new.num_heads} / key-value {new.num_kv_heads}")
        new.check_share()
        return new


def grouped_rms_norm(y: jax.Array, scale: jax.Array, groups: int,
                     eps: float) -> jax.Array:
    """RMSNorm over each of `groups` runs of the last dimension, in
    float32, times a learned scale of the whole width."""
    shape = y.shape
    y = y.astype(jnp.float32).reshape(*shape[:-1], groups, -1)
    y = y * jax.lax.rsqrt(jnp.mean(jnp.square(y), -1, keepdims=True) + eps)
    return y.reshape(shape) * scale.astype(jnp.float32)


class NemotronHModel(RoutedShareModel):
    """Layer-list Nemotron-H decoder; generic stage path only."""

    # Leaves with these names take no gradient and no optimizer state
    # (parallel/train.py::make_optimizer): the selection bias selects.
    frozen_param_names = ("expert_bias",)

    def kind(self, block: int) -> str:
        return self.config.hybrid_override_pattern[block]

    def layer_name(self, index: int) -> str:
        """A block is named by its kind, then its index: the profiler
        times the first of each prefix and reuses it for the rest."""
        name = super().layer_name(index)
        if not name.startswith("block_"):
            return name
        return f"{KIND_NAMES[self.kind(index - 1)]}_{index - 1}"

    def is_routed(self, block: int) -> bool:
        return self.kind(block) == EXPERTS

    def branches(self, block: int) -> tuple[str, ...]:
        return (FF,) if self.is_routed(block) else (OP,)

    # ---- init ----

    def _init_block(self, rng, block: int):
        c = self.config
        ks = jax.random.split(rng, 8)
        pd, std = c.param_dtype, c.initializer_range
        res_std = std / (2 * c.num_layers) ** 0.5
        e = c.hidden_size
        normal = lambda k, shape, s: jax.random.normal(k, shape, pd) * s
        norm = lambda: {"scale": jnp.ones((e,), pd)}
        kind = self.kind(block)
        if kind == EXPERTS:
            f, fs = c.moe_intermediate_size, c.moe_shared_expert_intermediate_size
            return {"ln_ff": norm(), "ff": {
                "router": normal(ks[0], (e, c.num_experts), std),
                # Seeded and not zero, so that selecting by score + bias
                # and weighting by score really differ (models/lfm2.py).
                "expert_bias": normal(ks[1], (c.num_experts,),
                                      c.expert_bias_range),
                "w1": normal(ks[2], (c.experts_held, e, f), std),
                "w2": normal(ks[3], (c.experts_held, f, e), res_std),
                "shared": {"w1": normal(ks[4], (e, fs), std),
                           "w2": normal(ks[5], (fs, e), res_std)}}}
        if kind == ATTN:
            h, kv, d = c.num_heads, c.num_kv_heads, c.head_dim
            return {"ln_op": norm(), "attn": {
                "wq": normal(ks[0], (e, h, d), std),
                "wk": normal(ks[1], (e, kv, d), std),
                "wv": normal(ks[2], (e, kv, d), std),
                "wo": normal(ks[3], (h, d, e), res_std)}}
        heads, inner, conv = c.mamba_num_heads, c.mamba_inner, c.conv_dim
        uniform = lambda k, shape, lo, hi: jax.random.uniform(
            k, shape, pd, lo, hi)
        # A step drawn log-uniformly in [time_step_min, time_step_max],
        # floored; `dt_bias` its inverse softplus.
        step = jnp.maximum(jnp.exp(uniform(
            ks[4], (heads,), math.log(c.time_step_min),
            math.log(c.time_step_max))), c.time_step_floor)
        bound = c.conv_kernel ** -0.5
        return {"ln_op": norm(), "mamba": {
            "w_in": normal(ks[0], (e, inner + conv + heads), std),
            "conv_taps": uniform(ks[1], (c.conv_kernel, conv), -bound, bound),
            "conv_bias": uniform(ks[2], (conv,), -bound, bound),
            "dt_bias": step + jnp.log(-jnp.expm1(-step)),
            "A_log": jnp.log(uniform(ks[3], (heads,), 1.0, 16.0)),
            "D": jnp.ones((heads,), pd),
            "norm": jnp.ones((inner,), pd),
            "w_out": normal(ks[5], (inner, e), res_std)}}

    # ---- forward ----

    @jax.named_scope("mamba")
    def mamba_operator(self, block: int, p, u):
        c = self.config
        dt, f32 = c.dtype, jnp.float32
        b, s, _ = u.shape
        inner, conv = c.mamba_inner, c.conv_dim
        heads, groups, n = c.mamba_num_heads, c.n_groups, c.ssm_state_size
        zxbcdt = u @ p["w_in"].astype(dt)
        z = zxbcdt[..., :inner]
        xbc = zxbcdt[..., inner:inner + conv]
        step = jax.nn.softplus(
            zxbcdt[..., inner + conv:].astype(f32) + p["dt_bias"].astype(f32))
        xbc = jax.nn.silu(
            short_conv(xbc.astype(f32), p["conv_taps"].astype(f32))
            + p["conv_bias"].astype(f32)).astype(dt)
        bc = xbc[..., inner:].reshape(b, s, 2, groups, n)
        y = ssd_scan(
            xbc[..., :inner].reshape(b, s, heads, c.mamba_head_dim), step,
            -jnp.exp(p["A_log"].astype(f32)), bc[:, :, 0], bc[:, :, 1],
            p["D"], chunk=c.chunk_size, layer=str(block))
        y = y.reshape(b, s, inner).astype(f32) * jax.nn.silu(z.astype(f32))
        y = grouped_rms_norm(y, p["norm"], groups, c.norm_eps).astype(dt)
        return y @ p["w_out"].astype(dt)

    @jax.named_scope("attention")
    def attention_operator(self, p, u):
        c = self.config
        dt = c.dtype
        q = jnp.einsum("bse,ehd->bhsd", u, p["wq"].astype(dt))
        k = jnp.einsum("bse,ehd->bhsd", u, p["wk"].astype(dt))
        v = jnp.einsum("bse,ehd->bhsd", u, p["wv"].astype(dt))
        rep = c.num_heads // c.num_kv_heads
        if rep > 1:
            k = jnp.repeat(k, rep, axis=1)
            v = jnp.repeat(v, rep, axis=1)
        attn = causal_attention(q, k, v, impl=c.attention_impl)
        return jnp.einsum("bhsd,hde->bse", attn, p["wo"].astype(dt))

    def operator_out(self, block: int, p, h):
        if self.kind(block) == MAMBA:
            return self.mamba_operator(block, p["mamba"], h)
        return self.attention_operator(p["attn"], h)
