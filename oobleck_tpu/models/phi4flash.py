"""Phi-4-mini-flash-reasoning (`model_type: phi4flash`; the SambaY
decoder-hybrid-decoder, arXiv:2507.06607) as an explicit layer list whose
layers DIFFER and READ EACH OTHER: every block is

    x = x + Mixer(LN1(x));   x = x + FF(LN2(x))

`LN` a LayerNorm with scale and bias, `FF(u) = W2 (silu(u W1) * (u W3))`
without bias (`[W1 | W3]` is the published fused 2560 x 20480), and a list
(`layer_kinds`) says which mixer a block has:

  mamba         Mamba-1. `[x | z] = u W_in`; `x = silu(conv(x) + b_c)`, a
                depthwise causal convolution of `d_conv` taps
                (`models/routed.short_conv`); `[d | B | C] = x W_x`
                (`dt_rank` + 2 `d_state`); `dt = softplus(d W_dt + b_dt)`;
                `A = -exp(A_log)`, [d_inner, d_state]; the recurrence
                `h_t = exp(dt_t A) h_{t-1} + dt_t B_t x_t`,
                `y_t = h_t C_t + D x_t` in float32 (`ops/sscan.py`: on a
                TPU two Pallas kernels whose forward's outputs the block's
                checkpoint keeps; `jax.numpy` elsewhere); out
                `W_out (y * silu(z))`.
  mamba_source  the same, and `y` (BEFORE the gate) goes into the carry as
                the memory `m`.
  gmu           Gated Memory Unit: `W_out (m * silu(u W_in))`, `m` the
                source's `y` at the same position. No conv, no scan, no
                state.
  swa           differential attention under a causal window of
                `sliding_window` keys.
  full_source   differential attention, causal; its `k, v` (after the bias,
                before any pairing) go into the carry.
  cross         differential attention with `W_q` and `W_o` only, over the
                carry's `k, v`, same causal mask.

Differential attention: `num_heads` query and `num_kv_heads` key-value
heads of `head_dim`, `W_qkv` and `W_o` with bias; consecutive heads pair
up, `(q1, q2) = (q[2j], q[2j+1])`, `(k1, k2)`, `(v1, v2)` likewise, query
pair j reads key-value pair j // (query pairs / key-value pairs),
`v = [v1 | v2]`; `a_r = softmax(q_r k_r^T / sqrt(head_dim) + mask) v`;
`lambda = exp(lq1 . lk1) - exp(lq2 . lk2) + lambda_init`, four learned
vectors a layer, `lambda_init = 0.8 - 0.6 exp(-0.3 i)` at layer i of the
PUBLISHED numbering (`layer_offset` + the block's index); out
`W_o concat_j[(1 - lambda_init) RMSNorm(a_1 - lambda a_2)]`, the norm over
the pair's 2 `head_dim` with a learned scale. No positional term.

The published layout, N = `num_layers`, N % 4 == 0 (`published_kinds`):
layer i is a state-space layer iff i % `mb_per_layer` == 0, else attention;
attention has the window iff i < N/2; layer N/2 is the memory's source,
N/2 + 1 the keys' and values'; from N/2 + 2 on state-space layers are
`gmu`, attention layers `cross`.

THE CARRY is what a layer hands the next, and the only way from one layer
to another in a layer list (any contiguous range is a stage; a recovery
re-cuts the list): `hidden` up to the memory's source, `(hidden, m)` after
it, `(hidden, m, k, v)` after the keys' and values' source and to the head,
which reads `hidden` alone; all [B, S, width] in the compute dtype.

`models/routed.py`'s are the layer list, the vocabulary share
(`vocab_rows_held`), the embedding, the untied head, the feed-forward and
the loss. No block is routed.
"""

from __future__ import annotations

import math
from dataclasses import dataclass, replace
from typing import Any

import jax
import jax.numpy as jnp

from oobleck_tpu.models.gpt import _layer_norm as layer_norm
from oobleck_tpu.models.routed import HeldShare, RoutedShareModel, short_conv
from oobleck_tpu.ops.attention import differential_attention
from oobleck_tpu.ops.sscan import selective_scan

MAMBA, MAMBA_SOURCE, GMU = "mamba", "mamba_source", "gmu"
SWA, FULL_SOURCE, CROSS = "swa", "full_source", "cross"
KINDS = (MAMBA, SWA, MAMBA_SOURCE, FULL_SOURCE, GMU, CROSS)
ATTENTION = (SWA, FULL_SOURCE, CROSS)


def published_kinds(num_layers: int, mb_per_layer: int = 2) -> tuple[str, ...]:
    """The public modelling code's rule for which mixer layer i has."""
    if num_layers % 4 or mb_per_layer != 2:
        raise ValueError(
            f"the published layout needs num_layers % 4 == 0 and "
            f"mb_per_layer 2 (got {num_layers}, {mb_per_layer})")
    half = num_layers // 2
    kinds = []
    for i in range(num_layers):
        if i % mb_per_layer == 0:
            kinds.append(MAMBA if i < half else
                         MAMBA_SOURCE if i == half else GMU)
        else:
            kinds.append(SWA if i < half else
                         FULL_SOURCE if i == half + 1 else CROSS)
    return tuple(kinds)


def check_kinds(kinds: tuple[str, ...]) -> None:
    """A `gmu` or `cross` before its source (or a second source) is an
    error, not a fallback."""
    seen: set[str] = set()
    for i, kind in enumerate(kinds):
        if kind not in KINDS:
            raise ValueError(f"layer {i}: unknown kind {kind!r}; one of "
                             f"{KINDS}")
        if kind in (MAMBA_SOURCE, FULL_SOURCE) and kind in seen:
            raise ValueError(f"layer {i}: a second {kind}")
        if kind == GMU and MAMBA_SOURCE not in seen:
            raise ValueError(f"layer {i}: gmu before any mamba_source")
        if kind == CROSS and FULL_SOURCE not in seen:
            raise ValueError(f"layer {i}: cross before any full_source")
        seen.add(kind)


@dataclass(frozen=True)
class Phi4FlashConfig(HeldShare):
    """Defaults: Phi-4-mini-flash-reasoning as published."""

    vocab_size: int = 200064
    vocab_rows_held: int | None = None           # None: all of them
    max_position_embeddings: int = 262144
    hidden_size: int = 2560
    num_layers: int = 32
    # One kind a layer; None: the published rule at `num_layers`.
    layer_kinds: tuple[str, ...] | None = None
    # The published index of block 0 (`lambda_init` reads it).
    layer_offset: int = 0
    mb_per_layer: int = 2
    num_heads: int = 40
    num_kv_heads: int = 20
    head_dim: int = 64
    sliding_window: int = 512
    intermediate_size: int = 10240
    d_state: int = 16
    d_conv: int = 4
    expand: int = 2
    dt_rank: int | None = None                   # None: ceil(hidden / 16)
    time_step_min: float = 0.001
    time_step_max: float = 0.1
    layer_norm_eps: float = 1e-5
    initializer_range: float = 0.02
    lambda_range: float = 0.1
    dtype: Any = jnp.bfloat16
    param_dtype: Any = jnp.float32
    attention_impl: str = "auto"
    remat: bool = True
    vocab_pad_multiple: int = 128
    # `HeldShare`'s: this family routes nothing.
    num_experts: int = 0
    num_experts_held: int | None = None
    expert_offset: int = 0

    @property
    def kinds(self) -> tuple[str, ...]:
        return (published_kinds(self.num_layers, self.mb_per_layer)
                if self.layer_kinds is None else self.layer_kinds)

    @property
    def d_inner(self) -> int:
        return self.expand * self.hidden_size

    @property
    def rank(self) -> int:
        return (-(-self.hidden_size // 16) if self.dt_rank is None
                else self.dt_rank)

    def lambda_init(self, block: int) -> float:
        return 0.8 - 0.6 * math.exp(-0.3 * (self.layer_offset + block))

    def override(self, **kwargs) -> "Phi4FlashConfig":
        fields = Phi4FlashConfig.__dataclass_fields__
        unknown = [k for k in kwargs if k not in fields]
        if unknown:
            raise ValueError(f"unknown model_args {unknown}")
        if kwargs.get("layer_kinds") is not None:
            kwargs["layer_kinds"] = tuple(kwargs["layer_kinds"])
        new = replace(self, **kwargs)
        if len(new.kinds) != new.num_layers:
            raise ValueError(
                f"layer_kinds names {len(new.kinds)} layers, num_layers is "
                f"{new.num_layers}")
        check_kinds(new.kinds)
        if (new.num_heads % 2 or new.num_kv_heads % 2
                or new.num_heads % new.num_kv_heads):
            raise ValueError(
                f"heads pair up: query {new.num_heads} / key-value "
                f"{new.num_kv_heads}")
        new.check_share()
        return new


class Phi4FlashModel(RoutedShareModel):
    """Layer-list Phi-4-mini-flash decoder; generic stage path only."""

    def kind(self, block: int) -> str:
        return self.config.kinds[block]

    def layer_name(self, index: int) -> str:
        """A block is named by its kind, then its index: the profiler
        times the first of each prefix and reuses it for the rest."""
        name = super().layer_name(index)
        if not name.startswith("block_"):
            return name
        return f"{self.kind(index - 1)}_{index - 1}"

    def is_routed(self, block: int) -> bool:
        return False

    def load_layers(self, num_tokens: int) -> dict:
        return {}

    def norm(self, x, ln):
        """LayerNorm with scale and bias where every other family of
        `models/routed.py` has an RMSNorm; `ln` the branch's {scale,
        bias}."""
        return layer_norm(x, ln["scale"], ln["bias"],
                          self.config.layer_norm_eps)

    # ---- the carry ----

    def _split(self, block: int, carry):
        """(hidden, m or None, (k, v) or None) of the carry ENTERING
        `block`, by the kinds before it."""
        before = self.config.kinds[:block]
        have_m, have_kv = MAMBA_SOURCE in before, FULL_SOURCE in before
        if not (have_m or have_kv):
            return carry, None, None
        x, *rest = carry
        m = rest.pop(0) if have_m else None
        return x, m, (tuple(rest) if have_kv else None)

    @staticmethod
    def _join(x, m, kv):
        if m is None and kv is None:
            return x
        return (x, *(() if m is None else (m,)), *(() if kv is None else kv))

    # ---- init ----

    def _init_head(self, rng):
        head = super()._init_head(rng)
        head["ln_f"]["bias"] = jnp.zeros_like(head["ln_f"]["scale"])
        return head

    def _init_block(self, rng, block: int):
        c = self.config
        ks = jax.random.split(rng, 16)
        pd, std = c.param_dtype, c.initializer_range
        res_std = std / (2 * c.num_layers) ** 0.5
        e, f, inner = c.hidden_size, c.intermediate_size, c.d_inner
        normal = lambda k, shape, s: jax.random.normal(k, shape, pd) * s
        uniform = lambda k, shape, lo, hi: jax.random.uniform(
            k, shape, pd, lo, hi)
        ln = lambda: {"scale": jnp.ones((e,), pd), "bias": jnp.zeros((e,), pd)}
        p = {"ln_op": ln(), "ln_ff": ln(),
             "ff": {"w1": normal(ks[0], (e, f), std),
                    "w3": normal(ks[1], (e, f), std),
                    "w2": normal(ks[2], (f, e), res_std)}}
        kind = self.kind(block)
        if kind == GMU:
            p["gmu"] = {"w_in": normal(ks[3], (e, inner), std),
                        "w_out": normal(ks[4], (inner, e), res_std)}
        elif kind in ATTENTION:
            d = c.head_dim
            wide = c.num_heads * d
            if kind != CROSS:
                wide += 2 * c.num_kv_heads * d
            first = "q" if kind == CROSS else "qkv"
            p["attn"] = {
                f"w_{first}": normal(ks[3], (e, wide), std),
                f"b_{first}": jnp.zeros((wide,), pd),
                "w_o": normal(ks[4], (c.num_heads * d, e), res_std),
                "b_o": jnp.zeros((e,), pd),
                **{name: normal(k, (d,), c.lambda_range) for name, k in zip(
                    ("lambda_q1", "lambda_k1", "lambda_q2", "lambda_k2"),
                    ks[5:9])},
                "subln": jnp.ones((2 * d,), pd)}
        else:
            n, r = c.d_state, c.rank
            # A step drawn log-uniformly in [time_step_min, time_step_max];
            # `dt_bias` its inverse softplus.
            step = jnp.exp(uniform(ks[8], (inner,), math.log(c.time_step_min),
                                   math.log(c.time_step_max)))
            bound = c.d_conv ** -0.5
            p["mamba"] = {
                "w_in": normal(ks[3], (e, 2 * inner), std),
                "conv_taps": uniform(ks[4], (c.d_conv, inner), -bound, bound),
                "conv_bias": uniform(ks[5], (inner,), -bound, bound),
                "w_x": normal(ks[6], (inner, r + 2 * n), std),
                "w_dt": normal(ks[7], (r, inner), std),
                "dt_bias": step + jnp.log(-jnp.expm1(-step)),
                "A_log": jnp.broadcast_to(
                    jnp.log(jnp.arange(1, n + 1, dtype=pd)), (inner, n)),
                "D": jnp.ones((inner,), pd),
                "w_out": normal(ks[9], (inner, e), res_std)}
        return p

    # ---- mixers ----

    @jax.named_scope("mamba1")
    def mamba_mixer(self, block: int, p, u):
        """(W_out (y * silu(z)), y): `y` the scan's output with its skip,
        before the gate."""
        c = self.config
        dt, f32 = c.dtype, jnp.float32
        inner, n, r = c.d_inner, c.d_state, c.rank
        xz = u @ p["w_in"].astype(dt)
        x, z = xz[..., :inner], xz[..., inner:]
        x = jax.nn.silu(
            short_conv(x.astype(f32), p["conv_taps"].astype(f32))
            + p["conv_bias"].astype(f32)).astype(dt)
        dbc = jnp.einsum("bsc,cr->bsr", x, p["w_x"].astype(dt),
                         preferred_element_type=f32)
        step = jax.nn.softplus(
            jnp.einsum("bsr,rc->bsc", dbc[..., :r].astype(dt),
                       p["w_dt"].astype(dt), preferred_element_type=f32)
            + p["dt_bias"].astype(f32))
        y = selective_scan(
            x, step, -jnp.exp(p["A_log"].astype(f32)), dbc[..., r:r + n],
            dbc[..., r + n:], p["D"], layer=str(block))
        gated = (y.astype(f32) * jax.nn.silu(z.astype(f32))).astype(dt)
        return gated @ p["w_out"].astype(dt), y

    @jax.named_scope("gmu")
    def gmu_mixer(self, p, u, m):
        dt, f32 = self.config.dtype, jnp.float32
        gate = jax.nn.silu((u @ p["w_in"].astype(dt)).astype(f32))
        return (m.astype(f32) * gate).astype(dt) @ p["w_out"].astype(dt)

    @jax.named_scope("diff_attn")
    def attention_mixer(self, block: int, p, u, kv):
        """(the mixer's output, this layer's (k, v) [B, S, KV d] or, of a
        `cross` layer, the carry's)."""
        c = self.config
        dt, f32 = c.dtype, jnp.float32
        b, s, _ = u.shape
        h, g, d = c.num_heads, c.num_kv_heads, c.head_dim
        kind = self.kind(block)
        if kind == CROSS:
            q = u @ p["w_q"].astype(dt) + p["b_q"].astype(dt)
        else:
            qkv = u @ p["w_qkv"].astype(dt) + p["b_qkv"].astype(dt)
            q = qkv[..., :h * d]
            kv = (qkv[..., h * d:(h + g) * d], qkv[..., (h + g) * d:])
        k, v = kv

        def pairs(t, heads):
            """[B, S, heads d] -> [B, heads / 2, 2, S, d]."""
            return t.reshape(b, s, heads // 2, 2, d).transpose(0, 2, 3, 1, 4)

        q, k, v = pairs(q, h), pairs(k, g), pairs(v, g)
        rep = h // g
        if rep > 1:
            k, v = jnp.repeat(k, rep, axis=1), jnp.repeat(v, rep, axis=1)
        values = jnp.concatenate([v[:, :, 0], v[:, :, 1]], axis=-1)
        a1, a2 = differential_attention(
            q[:, :, 0], k[:, :, 0], q[:, :, 1], k[:, :, 1], values,
            impl=c.attention_impl,
            window=c.sliding_window if kind == SWA else None)
        dot = lambda x, y: jnp.sum(p[x].astype(f32) * p[y].astype(f32))
        init = c.lambda_init(block)
        lam = (jnp.exp(dot("lambda_q1", "lambda_k1"))
               - jnp.exp(dot("lambda_q2", "lambda_k2")) + init)
        a = a1.astype(f32) - lam * a2.astype(f32)           # [B, H/2, S, 2d]
        a = a * jax.lax.rsqrt(
            jnp.mean(jnp.square(a), -1, keepdims=True) + c.layer_norm_eps)
        a = (a * p["subln"].astype(f32) * (1.0 - init)).astype(dt)
        a = a.transpose(0, 2, 1, 3).reshape(b, s, h * d)
        return a @ p["w_o"].astype(dt) + p["b_o"].astype(dt), kv

    # ---- forward ----

    def apply_block(self, block: int, p, carry, **_):
        """The carry LEAVING `block`: the hidden state, and whatever a
        source has put beside it so far."""
        kind = self.kind(block)
        x, m, kv = self._split(block, carry)
        u = self.norm(x, p["ln_op"])
        if kind in (MAMBA, MAMBA_SOURCE):
            out, y = self.mamba_mixer(block, p["mamba"], u)
            if kind == MAMBA_SOURCE:
                m = y
        elif kind == GMU:
            out = self.gmu_mixer(p["gmu"], u, m)
        elif kind == CROSS:
            with jax.named_scope("cross_attn"):
                out, _ = self.attention_mixer(block, p["attn"], u, kv)
        else:
            out, own = self.attention_mixer(block, p["attn"], u, kv)
            if kind == FULL_SOURCE:
                kv = own
        x = x + out
        x = x + self.feed_forward(block, p["ff"], self.norm(x, p["ln_ff"]))
        return self._join(x, m, kv)

    @jax.named_scope("lm_head")
    def head(self, p, carry):
        c = self.config
        x = self._split(c.num_layers, carry)[0]
        x = self.norm(x, p["ln_f"])
        return (x @ p["w"].astype(c.dtype)).astype(jnp.float32)
