"""Plain reference for the Qwen3-Next family (`model_type: qwen3_next`:
Qwen3-Next-80B-A3B-Instruct).

Norm `N(x) = x / rms(x) * (1 + w)`, `w` initialised 0, eps 1e-6 (ZERO-
CENTRED: the learned leaf is the scale's distance from 1). A block is

    x = x + Op_l(N1(x));   x = x + FF(N2(x));   logits = Head(N_f(x_last))

Layer `l` is full attention where `(l + 1) % 4 == 0`, else Gated DeltaNet;
every layer's FF is routed. `u` = N(x), [B, S, E]:

    Gated DeltaNet (16 key heads, 32 value heads, dk = dv = 128).
        [q | k | v | z] = u W_qkvz (2048 -> 2048 + 2048 + 4096 + 4096),
        [b | a] = u W_ba (2048 -> 32 + 32). [q | k | v] = silu(conv([q | k
        | v])), conv depthwise and causal with 4 taps a channel and no
        bias: conv(x)_t = sum_{j<4} w_j * x_{t-j}, zeros before position
        0. beta = sigmoid(b); g = -exp(A_log) softplus(a + dt_bias), one
        scalar a value head and position. q = q / |q| / sqrt(dk), k = k /
        |k| a head (|x| = sqrt(sum x^2 + 1e-6)); key head j serves value
        heads 2j, 2j + 1. A value head's state S [dk, dv], S_{-1} = 0:
            S' = exp(g_t) S_{t-1}
            u_t = beta_t (v_t - S'^T k_t)
            S_t = S' + k_t u_t^T
            o_t = S_t^T q_t
        computed HERE AS WRITTEN, one position after another (`lax.scan`);
        nothing is chunked, no [Q, Q] block and no inverse exists.
        y_t = w_n * o_t / rms(o_t) * silu(z_t) a head over its 128 (a
        plain weight, initialised 1; eps 1e-6); output y W_out.
    gated attention (16 query heads, 2 key-value heads, of 256).
        [q | gate] = u Wq a head (2048 -> 16 x (256 + 256)); k = u Wk,
        v = u Wv; q, k = N_head(q), N_head(k) (zero-centred, over 256);
        rotate-half rotary at theta 1e7 over columns 0-63 of each head,
        the other 192 untouched; query head h reads key-value head
        h // 8; scores q k^T / sqrt(256), key j visible to query i iff
        j <= i; softmax x v; times sigmoid(gate); Wo.
    FF. p = softmax(u Wr) over all 512; I = the 10 largest of p; w_e =
        p_e / sum_{j in I} p_j. Output = sum_{e in I, e HELD} w_e SwiGLU_e(u)
        + sigmoid(u . w_g) SwiGLU_s(u), SwiGLU(u) = W2 (silu(W1 u) * W3 u)
        at 512: the shared expert on every token behind a gate of its own.
        No selection bias, no scaling factor.

The published config fixes every size. It is silent on what the
configuration file's `assumed` states, numbered there as here:
  (1) the chunk of 64 is the program's alone: nothing here is chunked;
  (2) the fused projections' column order: [q | k | v | z] and [b | a],
      each part head-major (the family's checkpoint interleaves them by
      key head; a permutation of columns that changes no product's shape);
  (3) no auxiliary or balance loss (`router_aux_loss_coef` is in the
      published file and not in the catalog's);
  (4) no multi-token-prediction head;
  (5) initialisers: `A_log = log a`, `a` uniform in (0, 16]; `dt_bias` 1
      (the family's public modelling code); conv taps uniform in +- 1/sqrt(4);
      every norm's `w` 0 and the gated norm's weight 1; every matrix as
      `lfm2-24b-a2b`'s (normal 0.02, outputs into the residual stream
      0.02 / sqrt(2 x layers as run));
  (6) AdamW's weight decay covers every trained leaf, `A_log`, `dt_bias`
      and the norms included: the optimizer's, and nothing this file
      computes;
  (7) nothing stands in for the 31 absent chips.

`held` is the contiguous range of experts the share holds (`expert_offset`,
`num_experts_held`); with all of them it is the published layer. The
shared expert is on every token whatever is held. The vocabulary is the
rows held, padded to a multiple of 128 rows as the program pads it (18,992
-> 19,072): the padded logits are left out of the loss, so the padding
changes no result and its gradients are zero. Plain `jax.numpy`, float32,
every contraction at `Precision.HIGHEST`. Nothing is imported from
`oobleck_tpu`; the modes of arithmetic (`highest`, `bfloat16`, `fp8`) are
`reference/gpt.py`'s and apply to every contraction, the recurrence's
three included (S'^T k, k (x) u and S^T q; the state itself stays float32).

Three things are here for size and change no value. The recurrence runs as
a scan over blocks of `SCAN_BLOCK` positions around a scan over the
positions of a block, the inner one a `jax.checkpoint`: its gradient at
4096 positions keeps 32 boundary states and not 4096. Attention runs over
blocks of heads and queries (`reference/deepseek_v3.py::attend`). Each
layer is a `jax.checkpoint`. (In `fp8` mode a contraction's one scale is
then a block's or a position's, not the whole tensor's.)

Departure, as `reference/lfm2.py`: `forward` can be handed, per layer, the
expert indices to use (`forced`); what this file would have selected is
returned beside it (`own`), and `mismatch_share` counts the (token, layer)
pairs whose top-k SET differs.
"""

from __future__ import annotations

import functools
from dataclasses import dataclass

import jax
import jax.numpy as jnp
from jax import lax

from benchmarks.reference.deepseek_v3 import attend
from benchmarks.reference.gpt import MODES, _contract  # noqa: F401
from benchmarks.reference.lfm2 import mismatch_share  # noqa: F401

GDN, ATTN = "gdn", "attn"
SCAN_BLOCK = 128
L2_EPS = 1e-6


@dataclass(frozen=True)
class RefConfig:
    vocab_size: int                    # the rows of the vocabulary held
    hidden_size: int
    num_layers: int                    # as run
    full_attention_interval: int
    linear_num_key_heads: int
    linear_num_value_heads: int
    linear_key_head_dim: int
    linear_value_head_dim: int
    linear_conv_kernel_dim: int
    num_heads: int
    num_kv_heads: int
    head_dim: int
    partial_rotary_factor: float
    rope_theta: float
    moe_intermediate_size: int
    shared_expert_intermediate_size: int
    num_experts: int
    num_experts_per_tok: int
    num_experts_held: int
    expert_offset: int = 0
    norm_eps: float = 1e-6
    initializer_range: float = 0.02
    vocab_pad_multiple: int = 128

    @property
    def padded_vocab_size(self) -> int:
        m = self.vocab_pad_multiple
        return (self.vocab_size + m - 1) // m * m

    @property
    def key_dim(self) -> int:
        return self.linear_num_key_heads * self.linear_key_head_dim

    @property
    def value_dim(self) -> int:
        return self.linear_num_value_heads * self.linear_value_head_dim

    @property
    def rotary_dim(self) -> int:
        return int(self.head_dim * self.partial_rotary_factor)

    @property
    def routed_blocks(self) -> tuple[int, ...]:
        return tuple(range(self.num_layers))

    def kind(self, block: int) -> str:
        return (ATTN if (block + 1) % self.full_attention_interval == 0
                else GDN)

    @classmethod
    def from_config(cls, config: dict) -> "RefConfig":
        """From a file under benchmarks/configs/: the sizes as they are
        run, under the published keys."""
        return cls(
            vocab_size=config["vocab_rows_held"],
            hidden_size=config["hidden_size"],
            num_layers=config["num_hidden_layers"],
            full_attention_interval=config["full_attention_interval"],
            linear_num_key_heads=config["linear_num_key_heads"],
            linear_num_value_heads=config["linear_num_value_heads"],
            linear_key_head_dim=config["linear_key_head_dim"],
            linear_value_head_dim=config["linear_value_head_dim"],
            linear_conv_kernel_dim=config["linear_conv_kernel_dim"],
            num_heads=config["num_attention_heads"],
            num_kv_heads=config["num_key_value_heads"],
            head_dim=config["head_dim"],
            partial_rotary_factor=config["partial_rotary_factor"],
            rope_theta=config["rope_theta"],
            moe_intermediate_size=config["moe_intermediate_size"],
            shared_expert_intermediate_size=config[
                "shared_expert_intermediate_size"],
            num_experts=config["num_experts"],
            num_experts_per_tok=config["num_experts_per_tok"],
            num_experts_held=config["num_experts_held"],
            expert_offset=config.get("expert_offset", 0),
            norm_eps=config["rms_norm_eps"])

    def block_params(self, block: int) -> dict[str, int]:
        """Parameters of one layer by part (for sizes and FLOP counts)."""
        e = self.hidden_size
        f, fs = self.moe_intermediate_size, self.shared_expert_intermediate_size
        parts = {"router": e * self.num_experts,
                 "shared": 3 * e * fs + e,
                 "ff": self.num_experts_held * 3 * e * f,
                 "norms": 2 * e}
        if self.kind(block) == GDN:
            hv, kd, vd = self.linear_num_value_heads, self.key_dim, self.value_dim
            parts.update(
                w_qkvz=e * (2 * kd + 2 * vd), w_ba=e * 2 * hv,
                conv=self.linear_conv_kernel_dim * (2 * kd + vd),
                scalars=2 * hv + self.linear_value_head_dim, w_out=vd * e)
        else:
            h, kv, d = self.num_heads, self.num_kv_heads, self.head_dim
            parts.update(attention=e * h * 2 * d + 2 * e * kv * d + h * d * e,
                         head_norms=2 * d)
        return parts

    def num_params(self) -> int:
        """Over the rows of the vocabulary held; the rows the padding adds
        (no id reaches them, no gradient either) are not counted."""
        blocks = sum(sum(self.block_params(b).values())
                     for b in range(self.num_layers))
        return (2 * self.vocab_size * self.hidden_size + self.hidden_size
                + blocks)


# --------------------------------------------------------------------- #
# weights from a seed, in the program's layout                           #
# --------------------------------------------------------------------- #

def _block(key, c: RefConfig, block: int):
    ks = jax.random.split(key, 16)
    f32 = jnp.float32
    std = c.initializer_range
    res_std = std / (2 * c.num_layers) ** 0.5
    e = c.hidden_size
    normal = lambda k, shape, s: jax.random.normal(k, shape, f32) * s
    p = {"ln_op": {"scale": jnp.zeros((e,), f32)},
         "ln_ff": {"scale": jnp.zeros((e,), f32)}}
    if c.kind(block) == GDN:
        hv, kd, vd = c.linear_num_value_heads, c.key_dim, c.value_dim
        taps = c.linear_conv_kernel_dim
        bound = taps ** -0.5
        p[GDN] = {
            "w_qkvz": normal(ks[0], (e, 2 * kd + 2 * vd), std),
            "w_ba": normal(ks[1], (e, 2 * hv), std),
            "conv_taps": jax.random.uniform(ks[2], (taps, 2 * kd + vd), f32,
                                            -bound, bound),
            "A_log": jnp.log(16.0 * (1.0 - jax.random.uniform(
                ks[3], (hv,), f32))),
            "dt_bias": jnp.ones((hv,), f32),
            "norm": jnp.ones((c.linear_value_head_dim,), f32),
            "w_out": normal(ks[4], (vd, e), res_std)}
    else:
        h, kv, d = c.num_heads, c.num_kv_heads, c.head_dim
        p[ATTN] = {
            "wq": normal(ks[0], (e, h, 2 * d), std),
            "wk": normal(ks[1], (e, kv, d), std),
            "wv": normal(ks[2], (e, kv, d), std),
            "q_norm": jnp.zeros((d,), f32),
            "k_norm": jnp.zeros((d,), f32),
            "wo": normal(ks[3], (h, d, e), res_std)}
    f, fs = c.moe_intermediate_size, c.shared_expert_intermediate_size
    held = c.num_experts_held
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


def init_params(seed: int, c: RefConfig):
    """Seeded float32 weights, made on the device in ONE jitted call:
    {"embed": {wte}, "blocks": [per-layer trees], "head": {ln_f, w}}, each
    tree in the layout of `oobleck_tpu/models/qwen3_next.py`'s layer (the
    vocabulary padded as the program pads it)."""

    @jax.jit
    def make(key):
        k_e, k_b, k_h = jax.random.split(key, 3)
        e, v = c.hidden_size, c.padded_vocab_size
        keys = jax.random.split(k_b, c.num_layers)
        return {
            "embed": {"wte": jax.random.normal(k_e, (v, e), jnp.float32)
                      * c.initializer_range},
            "blocks": [_block(keys[i], c, i) for i in range(c.num_layers)],
            "head": {"ln_f": {"scale": jnp.zeros((e,), jnp.float32)},
                     "w": jax.random.normal(k_h, (e, v), jnp.float32)
                     * c.initializer_range},
        }

    seed = int(seed)
    key = jax.random.fold_in(jax.random.PRNGKey(seed & 0x7FFFFFFF), seed >> 31)
    return make(key)


# --------------------------------------------------------------------- #
# arithmetic                                                             #
# --------------------------------------------------------------------- #

def _rms(x, eps):
    return x * lax.rsqrt(jnp.mean(jnp.square(x), -1, keepdims=True) + eps)


def _norm(x, w, eps):
    """The zero-centred RMSNorm."""
    return _rms(x, eps) * (1.0 + w)


def _unit(x):
    return x * lax.rsqrt(jnp.sum(jnp.square(x), -1, keepdims=True) + L2_EPS)


def _causal_conv(v, taps):
    """v [B, S, D], taps [L, D]: sum_j taps[j] * v_{t-j}, zeros before
    position 0."""
    s = v.shape[1]
    out = 0.0
    for j in range(taps.shape[0]):
        out = out + taps[j] * jnp.pad(v, ((0, 0), (j, 0), (0, 0)))[:, :s]
    return out


def _partial_rope(x, rotary: int, theta: float):
    """Rotate-half rotary at positions 0..S-1 over columns 0..rotary-1 of
    the last dimension; the rest untouched. x [..., S, D]."""
    s = x.shape[-2]
    freqs = 1.0 / (theta ** (jnp.arange(0, rotary, 2, dtype=jnp.float32)
                             / rotary))
    angles = jnp.arange(s, dtype=jnp.float32)[:, None] * freqs[None, :]
    cos = jnp.concatenate([jnp.cos(angles)] * 2, -1)       # [S, rotary]
    sin = jnp.concatenate([jnp.sin(angles)] * 2, -1)
    r = x[..., :rotary]
    half = jnp.concatenate([-r[..., rotary // 2:], r[..., :rotary // 2]], -1)
    return jnp.concatenate([r * cos + half * sin, x[..., rotary:]], -1)


def recurrence(q, k, v, g, beta, mode: str):
    """The gated delta rule, one position after another. q, k [B, S, G,
    dk]; v [B, S, G, R, dv] (value head g * R + r reads key head g); g,
    beta [B, S, G, R]. Returns o [B, S, G, R, dv]."""
    bsz, s, groups, r, dv = v.shape
    dk = k.shape[-1]
    block = SCAN_BLOCK if s % SCAN_BLOCK == 0 else s

    def position(state, row):
        q_t, k_t, v_t, g_t, beta_t = row
        state = jnp.exp(g_t)[..., None, None] * state
        read = _contract("bgrkv,bgk->bgrv", state, k_t, mode)
        u_t = beta_t[..., None] * (v_t - read)
        state = state + _contract("bgk,bgrv->bgrkv", k_t, u_t, mode)
        return state, _contract("bgrkv,bgk->bgrv", state, q_t, mode)

    @jax.checkpoint
    def positions(state, rows):
        return lax.scan(position, state, rows)

    by_block = lambda t: jnp.moveaxis(t, 1, 0).reshape(
        s // block, block, *t.shape[:1], *t.shape[2:])
    _, o = lax.scan(positions,
                    jnp.zeros((bsz, groups, r, dk, dv), jnp.float32),
                    tuple(by_block(t) for t in (q, k, v, g, beta)))
    return jnp.moveaxis(o.reshape(s, bsz, groups, r, dv), 0, 1)


def _gdn(p, u, c: RefConfig, mode: str):
    bsz, s, _ = u.shape
    hk, hv = c.linear_num_key_heads, c.linear_num_value_heads
    dk, dv = c.linear_key_head_dim, c.linear_value_head_dim
    kd, vd, r = c.key_dim, c.value_dim, hv // hk
    qkvz = _contract("bse,ef->bsf", u, p["w_qkvz"], mode)
    ba = _contract("bse,ef->bsf", u, p["w_ba"], mode)
    qkv = jax.nn.silu(_causal_conv(qkvz[..., :2 * kd + vd], p["conv_taps"]))
    z = qkvz[..., 2 * kd + vd:].reshape(bsz, s, hv, dv)
    beta = jax.nn.sigmoid(ba[..., :hv])
    g = -jnp.exp(p["A_log"]) * jax.nn.softplus(ba[..., hv:] + p["dt_bias"])
    o = recurrence(
        _unit(qkv[..., :kd].reshape(bsz, s, hk, dk)) * dk ** -0.5,
        _unit(qkv[..., kd:2 * kd].reshape(bsz, s, hk, dk)),
        qkv[..., 2 * kd:].reshape(bsz, s, hk, r, dv),
        g.reshape(bsz, s, hk, r), beta.reshape(bsz, s, hk, r), mode)
    y = _rms(o.reshape(bsz, s, hv, dv), c.norm_eps) * p["norm"] \
        * jax.nn.silu(z)
    return _contract("bsf,fe->bse", y.reshape(bsz, s, vd), p["w_out"], mode)


def _attention(p, u, c: RefConfig, mode: str):
    d = c.head_dim
    q_gate = _contract("bse,ehd->bhsd", u, p["wq"], mode)
    q, gate = q_gate[..., :d], q_gate[..., d:]
    k = _contract("bse,ehd->bhsd", u, p["wk"], mode)
    v = _contract("bse,ehd->bhsd", u, p["wv"], mode)
    q = _partial_rope(_norm(q, p["q_norm"], c.norm_eps), c.rotary_dim,
                      c.rope_theta)
    k = _partial_rope(_norm(k, p["k_norm"], c.norm_eps), c.rotary_dim,
                      c.rope_theta)
    rep = c.num_heads // c.num_kv_heads
    k, v = jnp.repeat(k, rep, axis=1), jnp.repeat(v, rep, axis=1)
    a = jnp.stack([attend(q[i], k[i], v[i], mode)
                   for i in range(q.shape[0])])            # [B, H, S, D]
    return _contract("bhsd,hde->bse", a * jax.nn.sigmoid(gate), p["wo"], mode)


def _swiglu(w1, w3, w2, u, mode: str):
    hidden = (jax.nn.silu(_contract("bse,ef->bsf", u, w1, mode))
              * _contract("bse,ef->bsf", u, w3, mode))
    return _contract("bsf,fe->bse", hidden, w2, mode)


def _experts(p, u, c: RefConfig, mode: str, forced):
    """u [B, S, E] -> (held experts' part + gated shared expert [B, S, E],
    own choice [B, S, k]). `forced` [B, S, k] replaces the selection."""
    probs = jax.nn.softmax(_contract("bse,en->bsn", u, p["router"], mode), -1)
    _, own = lax.top_k(lax.stop_gradient(probs), c.num_experts_per_tok)
    chosen = own if forced is None else forced
    picked = jnp.sum(jax.nn.one_hot(chosen, c.num_experts, dtype=probs.dtype),
                     axis=-2)                              # [B, S, NE] 0/1
    w = picked * probs
    w = w / jnp.sum(w, -1, keepdims=True)
    s = p["shared"]
    out = jax.nn.sigmoid(_contract("bse,e->bs", u, s["w_g"], mode))[
        ..., None] * _swiglu(s["w1"], s["w3"], s["w2"], u, mode)
    for held in range(c.num_experts_held):
        out = out + w[..., c.expert_offset + held, None] * _swiglu(
            p["w1"][held], p["w3"][held], p["w2"][held], u, mode)
    return out, own


def _block_forward(p, x, c: RefConfig, block: int, mode: str, forced):
    u = _norm(x, p["ln_op"]["scale"], c.norm_eps)
    if c.kind(block) == GDN:
        x = x + _gdn(p[GDN], u, c, mode)
    else:
        x = x + _attention(p[ATTN], u, c, mode)
    y, own = _experts(p["ff"], _norm(x, p["ln_ff"]["scale"], c.norm_eps), c,
                      mode, forced)
    return x + y, own


def forward(params, tokens, c: RefConfig, mode: str = "highest",
            forced=None):
    """tokens [B, S] -> (logits [B, S, vocab rows held] float32, own),
    `own` the experts this file would choose in every layer, a list of
    [B, S, k]; `forced`, a list like it, replaces the selection."""
    x = params["embed"]["wte"][tokens]
    own = []
    for block, p in enumerate(params["blocks"]):
        x, chose = jax.checkpoint(
            functools.partial(_block_forward, c=c, block=block, mode=mode)
        )(p, x, forced=None if forced is None else forced[block])
        own.append(chose)
    x = _norm(x, params["head"]["ln_f"]["scale"], c.norm_eps)
    logits = _contract("bse,ev->bsv", x, params["head"]["w"], mode)
    return logits[..., :c.vocab_size], own


def loss(params, tokens, c: RefConfig, mode: str = "highest", forced=None):
    """(mean next-token cross entropy, own choices)."""
    logits, own = forward(params, tokens, c, mode, forced)
    logits = logits[:, :-1]
    logz = jax.nn.logsumexp(logits, axis=-1)
    gold = jnp.take_along_axis(logits, tokens[:, 1:, None], axis=-1)[..., 0]
    return jnp.mean(logz - gold), own


def loss_and_grads(params, tokens, c: RefConfig, mode: str = "highest",
                   forced=None):
    """((loss, own choices), gradients of every parameter)."""
    return jax.value_and_grad(
        functools.partial(loss, c=c, mode=mode, forced=forced),
        has_aux=True)(params, tokens)
