"""Qwen3-Next-family decoder (`model_type: qwen3_next`, e.g.
Qwen3-Next-80B-A3B-Instruct) as an explicit layer list whose layers DIFFER.

Every block is `h = x + Op(N(x)); y = h + FF(N(h))` with the ZERO-CENTRED
RMSNorm `N(x) = x / rms(x) * (1 + w)`, `w` initialised 0 (`norm`; the head's
too). Layer `i` is full attention where `(i + 1) % full_attention_interval
== 0`, else Gated DeltaNet; every layer's FF is routed.

  Op  Gated DeltaNet   `[q | k | v | z] = u W_qkvz`, `[b | a] = u W_ba`;
                       `[q | k | v] = silu(conv([q | k | v]))`, a depthwise
                       causal convolution of `linear_conv_kernel_dim` taps,
                       no bias (`models/routed.short_conv`); `beta =
                       sigmoid(b)`, `g = -exp(A_log) softplus(a + dt_bias)`
                       in float32, a value head and position; q and k of
                       unit length a head (eps 1e-6), q times dk^-1/2; key
                       head j serves value heads 2j, 2j + 1; the gated
                       delta rule in chunks of `chunk_size` (`ops/gdn.py`);
                       `y = w_n * o / rms(o) * silu(z)` a head over its dv
                       (a plain weight, initialised 1); `W_out`.
      gated attention  `[q | gate] = u W_q` a head, k, v from
                       `num_kv_heads`; zero-centred RMSNorm over each head
                       of q and k; rotate-half rotary over the first
                       `partial_rotary_factor` of a head's columns, the
                       rest untouched; causal softmax at head_dim^-1/2,
                       each key-value head serving `num_heads /
                       num_kv_heads` query heads; `attn * sigmoid(gate)`;
                       `W_o`.
  FF  routed experts   a SOFTMAX over ALL `num_experts` in float32, its
                       top k, weights normalised over the chosen, no
                       selection bias, no scaling factor
                       (`ops/moe.routed_experts`, `score="softmax"`);
                       SwiGLU experts; beside them on every token one
                       shared SwiGLU expert times `sigmoid(u w_g)`
                       (`models/routed.py::feed_forward`).

The layer list, one chip's share (`num_experts_held`, `expert_offset`,
`vocab_rows_held`), the feed-forwards, the shared expert's sum and the
routing probe are `models/routed.py`'s, shared with `models/lfm2.py`,
`models/deepseek_v3.py` and `models/nemotron_h.py`. `layer_name` names a
block by its operator, so the planner's profiler times each kind once.
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
    short_conv,
)
from oobleck_tpu.ops.attention import causal_attention
from oobleck_tpu.ops.gdn import gated_delta_rule

GDN, ATTN = "gdn", "attn"
L2_EPS = 1e-6


@dataclass(frozen=True)
class Qwen3NextConfig(HeldShare):
    """Defaults: Qwen3-Next-80B-A3B-Instruct as published."""

    vocab_size: int = 151936
    vocab_rows_held: int | None = None           # None: all of them
    max_position_embeddings: int = 262144
    hidden_size: int = 2048
    num_layers: int = 48
    full_attention_interval: int = 4
    linear_num_key_heads: int = 16
    linear_num_value_heads: int = 32
    linear_key_head_dim: int = 128
    linear_value_head_dim: int = 128
    linear_conv_kernel_dim: int = 4
    chunk_size: int = 64                         # the config is silent
    num_heads: int = 16
    num_kv_heads: int = 2
    head_dim: int = 256
    partial_rotary_factor: float = 0.25
    rope_theta: float = 1e7
    intermediate_size: int = 5120                # published; no layer is dense
    moe_intermediate_size: int = 512
    shared_expert_intermediate_size: int = 512
    num_experts: int = 512
    num_experts_per_tok: int = 10
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
    def key_dim(self) -> int:
        return self.linear_num_key_heads * self.linear_key_head_dim

    @property
    def value_dim(self) -> int:
        return self.linear_num_value_heads * self.linear_value_head_dim

    @property
    def rotary_dim(self) -> int:
        return int(self.head_dim * self.partial_rotary_factor)

    def override(self, **kwargs) -> "Qwen3NextConfig":
        fields = Qwen3NextConfig.__dataclass_fields__
        unknown = [k for k in kwargs if k not in fields]
        if unknown:
            raise ValueError(f"unknown model_args {unknown}")
        new = replace(self, **kwargs)
        if (new.linear_num_value_heads % new.linear_num_key_heads
                or new.num_heads % new.num_kv_heads or new.rotary_dim % 2):
            raise ValueError(
                f"value heads {new.linear_num_value_heads} / key heads "
                f"{new.linear_num_key_heads}, query {new.num_heads} / "
                f"key-value {new.num_kv_heads}, rotary {new.rotary_dim}")
        new.check_share()
        return new


def unit_length(x: jax.Array) -> jax.Array:
    """x / |x| over the last dimension, float32 (eps inside the root)."""
    x = x.astype(jnp.float32)
    return x * jax.lax.rsqrt(jnp.sum(jnp.square(x), -1, keepdims=True) + L2_EPS)


class Qwen3NextModel(RoutedShareModel):
    """Layer-list Qwen3-Next decoder; generic stage path only."""

    router_score = "softmax"

    def kind(self, block: int) -> str:
        c = self.config
        return ATTN if (block + 1) % c.full_attention_interval == 0 else GDN

    def layer_name(self, index: int) -> str:
        """A block is named by its operator, then its index: the profiler
        times the first of each prefix and reuses it for the rest."""
        name = super().layer_name(index)
        if not name.startswith("block_"):
            return name
        return f"{self.kind(index - 1)}_{index - 1}"

    def is_routed(self, block: int) -> bool:
        return True

    def norm(self, x, scale):
        return rms_norm(x, 1.0 + scale, self.config.norm_eps)

    # ---- init ----

    def _init_head(self, rng):
        p = super()._init_head(rng)
        p["ln_f"]["scale"] = jnp.zeros_like(p["ln_f"]["scale"])
        return p

    def _init_block(self, rng, block: int):
        c = self.config
        ks = jax.random.split(rng, 16)
        pd, std = c.param_dtype, c.initializer_range
        res_std = std / (2 * c.num_layers) ** 0.5
        e = c.hidden_size
        normal = lambda k, shape, s: jax.random.normal(k, shape, pd) * s
        p = {"ln_op": {"scale": jnp.zeros((e,), pd)},
             "ln_ff": {"scale": jnp.zeros((e,), pd)}}
        if self.kind(block) == GDN:
            hv, kd, vd = c.linear_num_value_heads, c.key_dim, c.value_dim
            taps = c.linear_conv_kernel_dim
            bound = taps ** -0.5
            p[GDN] = {
                "w_qkvz": normal(ks[0], (e, 2 * kd + 2 * vd), std),
                "w_ba": normal(ks[1], (e, 2 * hv), std),
                "conv_taps": jax.random.uniform(
                    ks[2], (taps, 2 * kd + vd), pd, -bound, bound),
                # A uniform in (0, 16], never 0: its log is a parameter.
                "A_log": jnp.log(16.0 * (1.0 - jax.random.uniform(
                    ks[3], (hv,), pd))),
                "dt_bias": jnp.ones((hv,), pd),
                "norm": jnp.ones((c.linear_value_head_dim,), pd),
                "w_out": normal(ks[4], (vd, e), res_std)}
        else:
            h, kv, d = c.num_heads, c.num_kv_heads, c.head_dim
            p[ATTN] = {
                "wq": normal(ks[0], (e, h, 2 * d), std),
                "wk": normal(ks[1], (e, kv, d), std),
                "wv": normal(ks[2], (e, kv, d), std),
                "q_norm": jnp.zeros((d,), pd),
                "k_norm": jnp.zeros((d,), pd),
                "wo": normal(ks[3], (h, d, e), res_std)}
        f, fs = c.moe_intermediate_size, c.shared_expert_intermediate_size
        held = c.experts_held
        p["ff"] = {
            "router": normal(ks[5], (e, c.num_experts), std),
            "w1": normal(ks[6], (held, e, f), std),
            "w3": normal(ks[7], (held, e, f), std),
            "w2": normal(ks[8], (held, f, e), res_std),
            "shared": {"w1": normal(ks[9], (e, fs), std),
                       "w3": normal(ks[10], (e, fs), std),
                       "w2": normal(ks[11], (fs, e), res_std),
                       "w_g": normal(ks[12], (e,), std)}}
        return p

    # ---- forward ----

    @jax.named_scope("gdn_mixer")
    def gdn_operator(self, block: int, p, u):
        c = self.config
        dt, f32 = c.dtype, jnp.float32
        b, s, _ = u.shape
        hk, hv = c.linear_num_key_heads, c.linear_num_value_heads
        dk, dv = c.linear_key_head_dim, c.linear_value_head_dim
        kd, vd = c.key_dim, c.value_dim
        qkvz = u @ p["w_qkvz"].astype(dt)
        ba = (u @ p["w_ba"].astype(dt)).astype(f32)
        qkv = jax.nn.silu(short_conv(qkvz[..., :2 * kd + vd].astype(f32),
                                     p["conv_taps"].astype(f32)))
        z = qkvz[..., 2 * kd + vd:].reshape(b, s, hv, dv)
        beta = jax.nn.sigmoid(ba[..., :hv])
        g = -jnp.exp(p["A_log"].astype(f32)) * jax.nn.softplus(
            ba[..., hv:] + p["dt_bias"].astype(f32))
        q = unit_length(qkv[..., :kd].reshape(b, s, hk, dk)) * dk ** -0.5
        k = unit_length(qkv[..., kd:2 * kd].reshape(b, s, hk, dk))
        o = gated_delta_rule(
            q.astype(dt), k.astype(dt),
            qkv[..., 2 * kd:].astype(dt).reshape(b, s, hv, dv), g, beta,
            chunk=c.chunk_size, layer=str(block))
        y = rms_norm(o.astype(f32), p["norm"].astype(f32), c.norm_eps)
        y = y * jax.nn.silu(z.astype(f32))
        return y.reshape(b, s, vd).astype(dt) @ p["w_out"].astype(dt)

    def _partial_rotary(self, x):
        r = self.config.rotary_dim
        return jnp.concatenate(
            [rotate_half(x[..., :r], self.config.rope_theta), x[..., r:]], -1)

    @jax.named_scope("gated_attn")
    def attention_operator(self, p, u):
        c = self.config
        dt, d = c.dtype, c.head_dim
        q_gate = jnp.einsum("bse,ehd->bhsd", u, p["wq"].astype(dt))
        q, gate = q_gate[..., :d], q_gate[..., d:]
        k = jnp.einsum("bse,ehd->bhsd", u, p["wk"].astype(dt))
        v = jnp.einsum("bse,ehd->bhsd", u, p["wv"].astype(dt))
        q = self._partial_rotary(self.norm(q, p["q_norm"]))
        k = self._partial_rotary(self.norm(k, p["k_norm"]))
        rep = c.num_heads // c.num_kv_heads
        if rep > 1:
            k = jnp.repeat(k, rep, axis=1)
            v = jnp.repeat(v, rep, axis=1)
        attn = causal_attention(q, k, v, impl=c.attention_impl)
        attn = attn * jax.nn.sigmoid(gate.astype(jnp.float32)).astype(dt)
        return jnp.einsum("bhsd,hde->bse", attn, p["wo"].astype(dt))

    def operator_out(self, block: int, p, h):
        if self.kind(block) == GDN:
            return self.gdn_operator(block, p[GDN], h)
        return self.attention_operator(p[ATTN], h)
