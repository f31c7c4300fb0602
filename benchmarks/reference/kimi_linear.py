"""Plain reference for the Kimi-Linear family (`model_type: kimi_linear`:
Kimi-Linear-48B-A3B-Instruct).

RMSNorm `N(x) = x / rms(x) * w`, eps 1e-5. A block is

    x = x + Mixer_l(N1(x));   x = x + FF_l(N2(x));   logits = Head(N_f(x))

The published lists number layers from 1: layer `l` mixes by Kimi Delta
Attention where `l` is in `kda_layers`, by latent attention where it is in
`full_attn_layers`; layer 1's FF is dense (`first_k_dense_replace` 1), the
others' routed. `u` = N(x), [B, S, E]:

    Kimi Delta Attention (32 heads of 128, 4096 wide).
        q = silu(conv(u W_q)), k = silu(conv(u W_k)), v = silu(conv(u W_v)),
        each conv depthwise and causal with 4 taps a channel and no bias:
        conv(x)_t = sum_{j<4} w_j * x_{t-j}, zeros before position 0.
        q = q / |q| / sqrt(128), k = k / |k| a head (|x| = sqrt(sum x^2 +
        1e-6)). The log of the decay, a number a CHANNEL: g = -exp(A_log_h)
        softplus(u W_fa W_fb + dt_bias) [S, 32, 128], never positive (W_fa
        2304 -> 128, W_fb 128 -> 4096). beta = sigmoid(u W_b) [S, 32]. A
        head's state S [128, 128], S_{-1} = 0:
            S' = Diag(exp(g_t)) S_{t-1}
            u_t = beta_t (v_t - S'^T k_t)
            S_t = S' + k_t u_t^T
            o_t = S_t^T q_t
        computed HERE AS WRITTEN, one position after another (`lax.scan`);
        nothing is chunked, no [Q, Q] block, no level and no inverse exists.
        y_t = w_n * o_t / rms(o_t) * sigmoid(u W_ga W_gb) a head over its
        128 (the norm first, then the gate; eps 1e-5); output y W_o.
    latent attention WITHOUT positions (32 heads).
        q = u Wq a head (2304 -> 32 x (128 + 64)); [c | k_r] = u Wkv_a
        (2304 -> 512 + 64); [k_n | v] = N_c(c) Wkv_b a head (512 -> 32 x
        (128 + 128); N_c an RMSNorm over 512, eps 1e-6); k_r ONE vector a
        position that all heads share; scores (q_n . k_n + q_r . k_r) /
        sqrt(192), NO rotary on either, key j visible to query i iff j <= i;
        softmax x v; Wo (32 x 128 -> 2304).
    FF. dense: W2 (silu(W1 u) * W3 u) at 9216. routed: s = sigmoid(u Wr)
        over all 256; I = the 8 largest of s + bias; w_e = 2.446 s_e /
        (sum_{j in I} s_j + 1e-6). Output = sum_{e in I, e HELD} w_e
        SwiGLU_e(u) + SwiGLU_s(u) at 1024: the shared expert on every
        token, weight 1.

The configuration file's `assumed` states what the published config is
silent on, numbered there. `held` is the contiguous range of experts the
share holds (`expert_offset`, `num_experts_held`); with all of them it is
the published layer. The vocabulary is the rows held, padded to a multiple
of 128 rows as the program pads it (20,480 is one already); the padded
logits are left out of the loss. Plain `jax.numpy`, float32, every
contraction at `Precision.HIGHEST`. Nothing is imported from `oobleck_tpu`;
the modes of arithmetic (`highest`, `bfloat16`, `fp8`) are
`reference/gpt.py`'s and apply to every contraction, the recurrence's three
included (S'^T k, k (x) u and S^T q; the state itself stays float32).

Three things are here for size and change no value (as
`reference/qwen3_next.py`): the recurrence runs as a scan over blocks of
`SCAN_BLOCK` positions around a scan over the positions of a block, the
inner one a `jax.checkpoint`; attention runs over blocks of heads and
queries (`reference/deepseek_v3.py::attend`); each layer is a
`jax.checkpoint`.

`forward` can be handed, per routed layer, the expert indices to use
(`forced`); what this file would have selected is returned beside it
(`own`), and `mismatch_share` counts the (token, layer) pairs whose top-k
SET differs. `fault=` plants one of five faults for the control, the first
four each a program that a reader of the model's description could have
written:
`scalar_decay` (one decay a head, the mean over its channels: Gated
DeltaNet's rule), `rotary_on` (the latent layers rotate q_r and k_r at
theta 10000, as the config's unused `rope_theta` would), `beta_left_out`
(every write at full strength), `shared_left_out` (the routed layers
without the shared expert); the fifth, `decay_grad_cut`, a right forward
whose BACKWARD loses the decay's gradient (`stop_gradient` on g: what a
kernel's gradient rule that forgot dg would give `A_log`, `dt_bias` and the
decay's low-rank pair), which no norm over all the parameters shows.
"""

from __future__ import annotations

import functools
from dataclasses import dataclass

import jax
import jax.numpy as jnp
from jax import lax

from benchmarks.reference.deepseek_v3 import (
    BALANCE_TOKENS,
    _rms_norm,
    _rope,
    _routed,
    _swiglu,
    attend,
    balanced_bias,
)
from benchmarks.reference.gpt import _contract
from benchmarks.reference.lfm2 import mismatch_share  # noqa: F401
from benchmarks.reference.qwen3_next import SCAN_BLOCK, _causal_conv, _unit

KDA, MLA = "kda", "mla"
FAULTS = (None, "scalar_decay", "rotary_on", "beta_left_out",
          "shared_left_out", "decay_grad_cut")
FAULT_ROPE_THETA = 10000.0


@dataclass(frozen=True)
class RefConfig:
    vocab_size: int                    # the rows of the vocabulary held
    hidden_size: int
    num_layers: int                    # as run
    kda_layers: tuple                  # numbered from 1, as published
    full_attn_layers: tuple
    linear_num_heads: int
    linear_head_dim: int
    short_conv_kernel_size: int
    gate_rank: int
    num_heads: int
    kv_lora_rank: int
    qk_nope_head_dim: int
    qk_rope_head_dim: int
    v_head_dim: int
    intermediate_size: int
    moe_intermediate_size: int
    first_k_dense_replace: int
    num_experts: int
    num_experts_per_tok: int
    num_shared_experts: int
    num_experts_held: int
    expert_offset: int = 0
    routed_scaling_factor: float = 2.446
    norm_eps: float = 1e-5
    latent_norm_eps: float = 1e-6
    initializer_range: float = 0.02
    expert_bias_range: float = 0.01
    vocab_pad_multiple: int = 128

    @property
    def padded_vocab_size(self) -> int:
        m = self.vocab_pad_multiple
        return (self.vocab_size + m - 1) // m * m

    @property
    def linear_dim(self) -> int:
        return self.linear_num_heads * self.linear_head_dim

    @property
    def shared_intermediate_size(self) -> int:
        return self.num_shared_experts * self.moe_intermediate_size

    @property
    def routed_blocks(self) -> tuple[int, ...]:
        return tuple(range(self.first_k_dense_replace, self.num_layers))

    def kind(self, block: int) -> str:
        return MLA if block + 1 in self.full_attn_layers else KDA

    @classmethod
    def from_config(cls, config: dict) -> "RefConfig":
        """From a file under benchmarks/configs/: the sizes as they are
        run, under the published keys."""
        linear = config["linear_attn_config"]
        return cls(
            vocab_size=config["vocab_rows_held"],
            hidden_size=config["hidden_size"],
            num_layers=config["num_hidden_layers"],
            kda_layers=tuple(linear["kda_layers"]),
            full_attn_layers=tuple(linear["full_attn_layers"]),
            linear_num_heads=linear["num_heads"],
            linear_head_dim=linear["head_dim"],
            short_conv_kernel_size=linear["short_conv_kernel_size"],
            gate_rank=config["gate_rank"],
            num_heads=config["num_attention_heads"],
            kv_lora_rank=config["kv_lora_rank"],
            qk_nope_head_dim=config["qk_nope_head_dim"],
            qk_rope_head_dim=config["qk_rope_head_dim"],
            v_head_dim=config["v_head_dim"],
            intermediate_size=config["intermediate_size"],
            moe_intermediate_size=config["moe_intermediate_size"],
            first_k_dense_replace=config["first_k_dense_replace"],
            num_experts=config["num_experts"],
            num_experts_per_tok=config["num_experts_per_token"],
            num_shared_experts=config["num_shared_experts"],
            num_experts_held=config["num_experts_held"],
            expert_offset=config.get("expert_offset", 0),
            routed_scaling_factor=config["routed_scaling_factor"],
            norm_eps=config["rms_norm_eps"],
            initializer_range=config.get("initializer_range", 0.02))

    def block_params(self, block: int) -> dict[str, int]:
        """Parameters of one block by part (for sizes and FLOP counts)."""
        e = self.hidden_size
        if self.kind(block) == KDA:
            wide, r = self.linear_dim, self.gate_rank
            mixer = (3 * e * wide + 3 * self.short_conv_kernel_size * wide
                     + 2 * (e * r + r * wide) + wide + self.linear_num_heads
                     + e * self.linear_num_heads + self.linear_head_dim
                     + wide * e)
        else:
            h, r = self.num_heads, self.kv_lora_rank
            dn, dr, dv = (self.qk_nope_head_dim, self.qk_rope_head_dim,
                          self.v_head_dim)
            mixer = (e * h * (dn + dr) + e * (r + dr) + r
                     + r * h * (dn + dv) + h * dv * e)
        if block < self.first_k_dense_replace:
            ff, shared, router = 3 * e * self.intermediate_size, 0, 0
        else:
            ff = self.num_experts_held * 3 * e * self.moe_intermediate_size
            shared = 3 * e * self.shared_intermediate_size
            router = e * self.num_experts + self.num_experts
        return {"mixer": mixer, "ff": ff, "shared": shared, "router": router,
                "norms": 2 * e}

    def num_params(self) -> int:
        blocks = sum(sum(self.block_params(b).values())
                     for b in range(self.num_layers))
        return (2 * self.vocab_size * self.hidden_size + self.hidden_size
                + blocks)


# --------------------------------------------------------------------- #
# weights from a seed, in the program's layout                           #
# --------------------------------------------------------------------- #

def _block(key, c: RefConfig, block: int):
    ks = jax.random.split(key, 26)
    f32 = jnp.float32
    std = c.initializer_range
    res_std = std / (2 * c.num_layers) ** 0.5
    e = c.hidden_size
    normal = lambda k, shape, s: jax.random.normal(k, shape, f32) * s
    # Scales and biases off their neutral values (`assumed` 4): a scale of
    # one hides where it is applied.
    near_one = lambda k, shape: 1.0 + normal(k, shape, 0.1)
    swiglu = lambda k1, k3, k2, lead, f: {
        "w1": normal(k1, (*lead, e, f), std),
        "w3": normal(k3, (*lead, e, f), std),
        "w2": normal(k2, (*lead, f, e), res_std)}
    p = {"ln_op": {"scale": near_one(ks[23], (e,))},
         "ln_ff": {"scale": near_one(ks[24], (e,))}}
    if c.kind(block) == KDA:
        h, d, r = c.linear_num_heads, c.linear_head_dim, c.gate_rank
        wide, taps = c.linear_dim, c.short_conv_kernel_size
        bound = taps ** -0.5
        conv = lambda k: jax.random.uniform(k, (taps, wide), f32, -bound,
                                            bound)
        dt = jnp.exp(jax.random.uniform(ks[9], (wide,), f32, jnp.log(1e-3),
                                        jnp.log(0.1)))
        p[KDA] = {
            "w_q": normal(ks[0], (e, wide), std),
            "w_k": normal(ks[1], (e, wide), std),
            "w_v": normal(ks[2], (e, wide), std),
            "conv_q": conv(ks[3]), "conv_k": conv(ks[4]),
            "conv_v": conv(ks[5]),
            "w_fa": normal(ks[6], (e, r), std),
            "w_fb": normal(ks[7], (r, wide), std),
            "A_log": jnp.log(jax.random.uniform(ks[8], (h,), f32, 1.0, 16.0)),
            "dt_bias": dt + jnp.log(-jnp.expm1(-dt)),
            "w_b": normal(ks[10], (e, h), std),
            "w_ga": normal(ks[11], (e, r), std),
            "w_gb": normal(ks[12], (r, wide), std),
            "norm": near_one(ks[25], (d,)),
            "w_o": normal(ks[13], (wide, e), res_std)}
    else:
        h, r = c.num_heads, c.kv_lora_rank
        dn, dr, dv = c.qk_nope_head_dim, c.qk_rope_head_dim, c.v_head_dim
        p["attn"] = {"wq": normal(ks[0], (e, h, dn + dr), std),
                     "wkv_a": normal(ks[1], (e, r + dr), std),
                     "kv_norm": near_one(ks[4], (r,)),
                     "wkv_b": normal(ks[2], (r, h, dn + dv), std),
                     "wo": normal(ks[3], (h, dv, e), res_std)}
    if block < c.first_k_dense_replace:
        p["ff"] = swiglu(ks[14], ks[15], ks[16], (), c.intermediate_size)
    else:
        p["ff"] = {
            "router": normal(ks[17], (e, c.num_experts), std),
            "expert_bias": normal(ks[18], (c.num_experts,),
                                  c.expert_bias_range),
            **swiglu(ks[19], ks[20], ks[21], (c.num_experts_held,),
                     c.moe_intermediate_size),
            "shared": swiglu(*jax.random.split(ks[22], 3), (),
                             c.shared_intermediate_size)}
    return p


def _balance(params, key, c: RefConfig, balance_tokens):
    """Replace every routed block's seeded bias by the one that balances
    its router on seeded uniform token ids, block after block (a block's
    input depends on the routing before it)."""
    tokens = jax.random.randint(key, balance_tokens, 0, c.vocab_size)
    x = params["embed"]["wte"][tokens]
    for block, p in enumerate(params["blocks"]):
        if block in c.routed_blocks:
            h = _rms_norm(_mixer_half(p, x, c, block, "highest", None),
                          p["ln_ff"]["scale"], c.norm_eps)
            scores = jax.nn.sigmoid(
                _contract("bse,en->bsn", h, p["ff"]["router"], "highest"))
            p["ff"]["expert_bias"] = balanced_bias(
                scores.reshape(-1, c.num_experts), c.num_experts_per_tok)
        x, _ = _block_forward(p, x, c, block, "highest", None, None)
    return params


def init_params(seed: int, c: RefConfig, balance_tokens=BALANCE_TOKENS):
    """Seeded float32 weights, made on the device in ONE jitted call:
    {"embed": {wte}, "blocks": [per-block trees], "head": {ln_f, w}}, each
    tree in the layout of `oobleck_tpu/models/kimi_linear.py`'s layer. The
    embedding at unit variance (`assumed` 10); the selection bias what its
    own rule would have made of it (`reference/deepseek_v3.py`)."""

    @jax.jit
    def make(key):
        k_e, k_b, k_h, k_t, k_n = jax.random.split(key, 5)
        e, v = c.hidden_size, c.padded_vocab_size
        keys = jax.random.split(k_b, c.num_layers)
        params = {
            "embed": {"wte": jax.random.normal(k_e, (v, e), jnp.float32)},
            "blocks": [_block(keys[i], c, i) for i in range(c.num_layers)],
            "head": {"ln_f": {"scale": 1.0 + 0.1 * jax.random.normal(
                         k_n, (e,), jnp.float32)},
                     "w": jax.random.normal(k_h, (e, v), jnp.float32)
                     * c.initializer_range},
        }
        return _balance(params, k_t, c, tuple(balance_tokens))

    seed = int(seed)
    key = jax.random.fold_in(jax.random.PRNGKey(seed & 0x7FFFFFFF), seed >> 31)
    return make(key)


# --------------------------------------------------------------------- #
# arithmetic                                                             #
# --------------------------------------------------------------------- #

def recurrence(q, k, v, g, beta, mode: str):
    """The delta rule with a decay a channel, one position after another.
    q, k, g [B, S, H, dk]; v [B, S, H, dv]; beta [B, S, H]. Returns o
    [B, S, H, dv]."""
    bsz, s, heads, dv = v.shape
    dk = k.shape[-1]
    block = SCAN_BLOCK if s % SCAN_BLOCK == 0 else s

    def position(state, row):
        q_t, k_t, v_t, g_t, beta_t = row
        state = jnp.exp(g_t)[..., None] * state
        read = _contract("bhkv,bhk->bhv", state, k_t, mode)
        u_t = beta_t[..., None] * (v_t - read)
        state = state + _contract("bhk,bhv->bhkv", k_t, u_t, mode)
        return state, _contract("bhkv,bhk->bhv", state, q_t, mode)

    @jax.checkpoint
    def positions(state, rows):
        return lax.scan(position, state, rows)

    by_block = lambda t: jnp.moveaxis(t, 1, 0).reshape(
        s // block, block, *t.shape[:1], *t.shape[2:])
    _, o = lax.scan(positions, jnp.zeros((bsz, heads, dk, dv), jnp.float32),
                    tuple(by_block(t) for t in (q, k, v, g, beta)))
    return jnp.moveaxis(o.reshape(s, bsz, heads, dv), 0, 1)


def _kda(p, u, c: RefConfig, mode: str, fault):
    bsz, s, _ = u.shape
    h, d = c.linear_num_heads, c.linear_head_dim
    heads = lambda t: t.reshape(bsz, s, h, d)
    proj = lambda w: _contract("bse,ef->bsf", u, p[w], mode)
    low_rank = lambda a, b: _contract("bsr,rf->bsf", proj(a), p[b], mode)
    mixed = lambda w, taps: heads(jax.nn.silu(_causal_conv(proj(w), p[taps])))
    q = _unit(mixed("w_q", "conv_q")) * d ** -0.5
    k = _unit(mixed("w_k", "conv_k"))
    v = mixed("w_v", "conv_v")
    g = -jnp.exp(p["A_log"])[:, None] * jax.nn.softplus(
        heads(low_rank("w_fa", "w_fb") + p["dt_bias"]))
    beta = jax.nn.sigmoid(proj("w_b"))
    if fault == "scalar_decay":
        g = jnp.broadcast_to(jnp.mean(g, -1, keepdims=True), g.shape)
    if fault == "beta_left_out":
        beta = jnp.ones_like(beta)
    if fault == "decay_grad_cut":
        g = lax.stop_gradient(g)
    o = recurrence(q, k, v, g, beta, mode)
    y = o * lax.rsqrt(jnp.mean(jnp.square(o), -1, keepdims=True)
                      + c.norm_eps) * p["norm"]
    y = y * jax.nn.sigmoid(heads(low_rank("w_ga", "w_gb")))
    return _contract("bsf,fe->bse", y.reshape(bsz, s, h * d), p["w_o"], mode)


def _latent(p, u, c: RefConfig, mode: str, fault):
    dn, r = c.qk_nope_head_dim, c.kv_lora_rank
    q = _contract("bse,ehd->bhsd", u, p["wq"], mode)
    kv_a = _contract("bse,ed->bsd", u, p["wkv_a"], mode)
    latent = _rms_norm(kv_a[..., :r], p["kv_norm"], c.latent_norm_eps)
    kv = _contract("bsr,rhd->bhsd", latent, p["wkv_b"], mode)
    q_r, k_r = q[..., dn:], kv_a[..., r:]
    if fault == "rotary_on":
        q_r, k_r = _rope(q_r, FAULT_ROPE_THETA), _rope(k_r, FAULT_ROPE_THETA)
    q = jnp.concatenate([q[..., :dn], q_r], -1)
    k = jnp.concatenate(
        [kv[..., :dn],
         jnp.broadcast_to(k_r[:, None], (*kv.shape[:3], k_r.shape[-1]))], -1)
    a = jnp.stack([attend(q[b], k[b], kv[b, ..., dn:], mode)
                   for b in range(q.shape[0])])            # [B, H, S, Dv]
    return _contract("bhsd,hde->bse", a, p["wo"], mode)


def _mixer_half(p, x, c: RefConfig, block: int, mode: str, fault):
    u = _rms_norm(x, p["ln_op"]["scale"], c.norm_eps)
    if c.kind(block) == KDA:
        return x + _kda(p[KDA], u, c, mode, fault)
    return x + _latent(p["attn"], u, c, mode, fault)


def _block_forward(p, x, c: RefConfig, block: int, mode: str, forced, fault):
    x = _mixer_half(p, x, c, block, mode, fault)
    h = _rms_norm(x, p["ln_ff"]["scale"], c.norm_eps)
    if block < c.first_k_dense_replace:
        return x + _swiglu(p["ff"], h, mode), None
    y, own = _routed(p["ff"], h, c, mode, forced)
    if fault == "shared_left_out":
        y = y - _swiglu(p["ff"]["shared"], h, mode)
    return x + y, own


def forward(params, tokens, c: RefConfig, mode: str = "highest",
            forced=None, fault=None):
    """tokens [B, S] -> (logits [B, S, vocab] float32, own), `own` the
    experts this file would choose in every routed block, a list of
    [B, S, k] in `routed_blocks` order; `forced`, a list like it, replaces
    the selection."""
    if fault not in FAULTS:
        raise ValueError(f"fault must be one of {FAULTS}, got {fault!r}")
    x = params["embed"]["wte"][tokens]
    own = []
    for block, p in enumerate(params["blocks"]):
        routed_index = block - c.first_k_dense_replace
        f = (forced[routed_index]
             if forced is not None and routed_index >= 0 else None)
        x, chose = jax.checkpoint(functools.partial(
            _block_forward, c=c, block=block, mode=mode, fault=fault)
        )(p, x, forced=f)
        if chose is not None:
            own.append(chose)
    x = _rms_norm(x, params["head"]["ln_f"]["scale"], c.norm_eps)
    logits = _contract("bse,ev->bsv", x, params["head"]["w"], mode)
    return logits[..., :c.vocab_size], own


def loss(params, tokens, c: RefConfig, mode: str = "highest", forced=None,
         fault=None):
    """(mean next-token cross entropy, own choices)."""
    logits, own = forward(params, tokens, c, mode, forced, fault)
    logits = logits[:, :-1]
    logz = jax.nn.logsumexp(logits, axis=-1)
    gold = jnp.take_along_axis(logits, tokens[:, 1:, None], axis=-1)[..., 0]
    return jnp.mean(logz - gold), own


def loss_and_grads(params, tokens, c: RefConfig, mode: str = "highest",
                   forced=None, fault=None):
    """((loss, own choices), gradients of every parameter; the selection
    bias's is zero: it selects and is not trained)."""
    return jax.value_and_grad(
        functools.partial(loss, c=c, mode=mode, forced=forced, fault=fault),
        has_aux=True)(params, tokens)
