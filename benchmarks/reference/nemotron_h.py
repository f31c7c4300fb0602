"""Plain reference for the Nemotron-H family (`model_type: nemotron_h`:
NVIDIA-Nemotron-3-Nano-30B-A3B).

Every layer `l` is ONE mixer behind one RMSNorm `N` (learned scale, eps
1e-5), by a character of `hybrid_override_pattern`; the residual stream
stays in the compute dtype (`residual_in_fp32` false):

    x = x + Mixer_l(N(x));   logits = Head(N_f(x_last))

`u` = N(x), [B, S, E]:

    M   Mamba-2. [z | xBC | dt] = u W_in, xBC = [x | B | C] of 64 heads x
        64, 8 groups x 128 and 8 groups x 128. xBC = silu(conv(xBC) +
        b_conv), conv depthwise and causal with 4 taps a channel:
        conv(v)_t = sum_{j<4} w_j * v_{t-j}, zeros before position 0. Head
        h reads group h // 8. D = softplus(dt + dt_bias), A = -exp(A_log),
        one scalar a head. State H_t [64, 128] a head, H_{-1} = 0, float32:
            H_t = exp(D_t A) H_{t-1} + D_t x_t (x) B_t
            y_t = H_t C_t + D_skip x_t
        computed HERE AS WRITTEN, one position after another (`lax.scan`);
        nothing is chunked, no [Q, Q] block exists. y = y * silu(z), then
        RMSNorm over each of the 8 groups of 512 channels with a learned
        scale of 4096 (gate first, norm after), eps 1e-5; output y W_out.
    *   attention. q = u Wq (32 heads of 128), k = u Wk, v = u Wv (2 heads
        of 128), query head h reads key-value head h // 16. Scores
        q k^T / sqrt(128), key j visible to query i iff j <= i; softmax x
        v; Wo. No bias.
    E   experts. s = sigmoid(u Wr) over all 128; I = the 6 largest of s + b
        (`b` the selection bias: it selects and never weighs; `n_group` =
        `topk_group` = 1, so the group step is the identity); w_e = s_e /
        sum_{j in I} s_j x 2.5. Output = sum_{e in I, e HELD} w_e W2_e
        relu(W1_e u)^2 + W2_s relu(W1_s u)^2: experts WITHOUT a gate, the
        shared one (3712 wide) on every token, weight 1.

The published config fixes every size. It is silent on eight things, taken
here as the configuration file's `assumed` states them:
  (1) no rotary or other positional term in the attention layers: the
      family's public modelling code builds its attention without one, and
      the config's `rope_theta` 10000 and `partial_rotary_factor` 1 are
      carried in the file and unused;
  (2) the step is not clamped after the softplus (the family's
      `time_step_limit` default, 0 to infinity);
  (3) gate before norm, group size 4096 / 8;
  (4) initialisers: `A_log = log a`, `a` uniform in [1, 16]; `D = 1`;
      `dt_bias` the inverse softplus of a step drawn log-uniformly in
      [`time_step_min` 0.001, `time_step_max` 0.1] and floored at
      `time_step_floor` 1e-4; conv taps and bias uniform in +- 1/sqrt(4);
      every other matrix as `lfm2-24b-a2b`'s (normal 0.02, outputs into
      the residual stream 0.02 / sqrt(2 x layers as run));
  (5) no auxiliary or balance loss;
  (6) the selection bias takes no gradient and has no update rule here,
      and the seeded weights carry the bias that balances the seed's router
      on uniform ids (`_balance`, as `reference/lfm2.py` does);
  (7) the weight normaliser's epsilon is 1e-6 where the family's code has
      1e-20;
  (8) AdamW's weight decay covers every trained leaf, `A_log`, `D`,
      `dt_bias` and the norms included (the family's recipe exempts them):
      the optimizer's, and nothing this file computes.

`held` is the contiguous range of experts the share holds (`expert_offset`,
`num_experts_held`); with all of them it is the published layer. The
shared expert is on every token whatever is held. The vocabulary is the
rows held. Plain `jax.numpy`, float32, every contraction at
`Precision.HIGHEST`. Nothing is imported from `oobleck_tpu`; the modes of
arithmetic (`highest`, `bfloat16`, `fp8`) are `reference/gpt.py`'s and
apply to every contraction, the recurrence's two included (x (x) B and
H C; the state itself stays float32).

Three things are here for size and change no value. The recurrence runs as
a scan over blocks of `SCAN_BLOCK` positions around a scan over the
positions of a block, the inner one a `jax.checkpoint`: its gradient at
4096 positions keeps 32 boundary states and not 4096 (4096 states of 64 x
64 x 128 float32 are 8.6 GB a layer). Attention runs over blocks of heads
and queries (`reference/deepseek_v3.py::attend`). Each layer is a
`jax.checkpoint`. (In `fp8` mode a contraction's one scale is then a
block's or a position's, not the whole tensor's.)

Departure, as `reference/lfm2.py`: `forward` can be handed, per `E` layer,
the expert indices to use (`forced`); what this file would have selected is
returned beside it (`own`), and `mismatch_share` counts the (token, layer)
pairs whose top-k SET differs.
"""

from __future__ import annotations

import functools
import math
from dataclasses import dataclass

import jax
import jax.numpy as jnp
from jax import lax

from benchmarks.reference.deepseek_v3 import attend
from benchmarks.reference.gpt import MODES, _contract  # noqa: F401
from benchmarks.reference.lfm2 import (  # noqa: F401
    balanced_bias,
    mismatch_share,
)

MAMBA, ATTN, EXPERTS = "M", "*", "E"
SCAN_BLOCK = 128
BALANCE_TOKENS = (2, 4096)     # sequences x length the bias is balanced on


@dataclass(frozen=True)
class RefConfig:
    vocab_size: int                    # the rows of the vocabulary held
    hidden_size: int
    pattern: str                       # a character a layer, as run
    mamba_num_heads: int
    mamba_head_dim: int
    ssm_state_size: int
    n_groups: int
    conv_kernel: int
    num_heads: int
    num_kv_heads: int
    head_dim: int
    moe_intermediate_size: int
    shared_intermediate_size: int
    num_experts: int
    num_experts_per_tok: int
    num_experts_held: int
    expert_offset: int = 0
    routed_scaling_factor: float = 2.5
    norm_eps: float = 1e-5
    time_step_min: float = 0.001
    time_step_max: float = 0.1
    time_step_floor: float = 1e-4
    initializer_range: float = 0.02
    expert_bias_range: float = 0.01

    @property
    def num_layers(self) -> int:
        return len(self.pattern)

    @property
    def routed_blocks(self) -> tuple[int, ...]:
        return tuple(i for i, k in enumerate(self.pattern) if k == EXPERTS)

    @property
    def mamba_inner(self) -> int:
        return self.mamba_num_heads * self.mamba_head_dim

    @property
    def conv_dim(self) -> int:
        return self.mamba_inner + 2 * self.n_groups * self.ssm_state_size

    @classmethod
    def from_config(cls, config: dict) -> "RefConfig":
        """From a file under benchmarks/configs/: the sizes as they are
        run, under the published keys."""
        assert len(config["hybrid_override_pattern"]) == config[
            "num_hidden_layers"]
        return cls(
            vocab_size=config["vocab_rows_held"],
            hidden_size=config["hidden_size"],
            pattern=config["hybrid_override_pattern"],
            mamba_num_heads=config["mamba_num_heads"],
            mamba_head_dim=config["mamba_head_dim"],
            ssm_state_size=config["ssm_state_size"],
            n_groups=config["n_groups"],
            conv_kernel=config["conv_kernel"],
            num_heads=config["num_attention_heads"],
            num_kv_heads=config["num_key_value_heads"],
            head_dim=config["head_dim"],
            moe_intermediate_size=config["moe_intermediate_size"],
            shared_intermediate_size=config[
                "moe_shared_expert_intermediate_size"],
            num_experts=config["n_routed_experts"],
            num_experts_per_tok=config["num_experts_per_tok"],
            num_experts_held=config["num_experts_held"],
            expert_offset=config.get("expert_offset", 0),
            routed_scaling_factor=config["routed_scaling_factor"],
            norm_eps=config["norm_eps"],
            time_step_min=config["time_step_min"],
            time_step_max=config["time_step_max"],
            time_step_floor=config["time_step_floor"])

    def block_params(self, block: int) -> dict[str, int]:
        """Parameters of one layer by part (for sizes and FLOP counts)."""
        e, kind = self.hidden_size, self.pattern[block]
        if kind == MAMBA:
            heads, inner, conv = (self.mamba_num_heads, self.mamba_inner,
                                  self.conv_dim)
            return {"w_in": e * (inner + conv + heads), "w_out": inner * e,
                    "conv": (self.conv_kernel + 1) * conv,
                    "scalars": 3 * heads, "norms": inner + e}
        if kind == ATTN:
            h, kv, d = self.num_heads, self.num_kv_heads, self.head_dim
            return {"attention": 2 * e * h * d + 2 * e * kv * d, "norms": e}
        return {"ff": self.num_experts_held * 2 * e
                * self.moe_intermediate_size,
                "shared": 2 * e * self.shared_intermediate_size,
                "router": e * self.num_experts + self.num_experts,
                "norms": e}

    def num_params(self) -> int:
        blocks = sum(sum(self.block_params(b).values())
                     for b in range(self.num_layers))
        return (2 * self.vocab_size * self.hidden_size + self.hidden_size
                + blocks)


# --------------------------------------------------------------------- #
# weights from a seed, in the program's layout                           #
# --------------------------------------------------------------------- #

def _block(key, c: RefConfig, block: int):
    ks = jax.random.split(key, 8)
    f32 = jnp.float32
    std = c.initializer_range
    res_std = std / (2 * c.num_layers) ** 0.5
    e, kind = c.hidden_size, c.pattern[block]
    normal = lambda k, shape, s: jax.random.normal(k, shape, f32) * s
    uniform = lambda k, shape, lo, hi: jax.random.uniform(
        k, shape, f32, lo, hi)
    norm = lambda: {"scale": jnp.ones((e,), f32)}
    if kind == EXPERTS:
        f, fs = c.moe_intermediate_size, c.shared_intermediate_size
        return {"ln_ff": norm(), "ff": {
            "router": normal(ks[0], (e, c.num_experts), std),
            "expert_bias": normal(ks[1], (c.num_experts,),
                                  c.expert_bias_range),
            "w1": normal(ks[2], (c.num_experts_held, e, f), std),
            "w2": normal(ks[3], (c.num_experts_held, f, e), res_std),
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
    step = jnp.maximum(
        jnp.exp(uniform(ks[4], (heads,), math.log(c.time_step_min),
                        math.log(c.time_step_max))), c.time_step_floor)
    bound = c.conv_kernel ** -0.5
    return {"ln_op": norm(), "mamba": {
        "w_in": normal(ks[0], (e, inner + conv + heads), std),
        "conv_taps": uniform(ks[1], (c.conv_kernel, conv), -bound, bound),
        "conv_bias": uniform(ks[2], (conv,), -bound, bound),
        "dt_bias": step + jnp.log(-jnp.expm1(-step)),   # inverse softplus
        "A_log": jnp.log(uniform(ks[3], (heads,), 1.0, 16.0)),
        "D": jnp.ones((heads,), f32),
        "norm": jnp.ones((inner,), f32),
        "w_out": normal(ks[5], (inner, e), res_std)}}


def _balance(params, key, c: RefConfig, balance_tokens):
    """Replace every `E` layer's seeded bias by the one that balances its
    router on seeded uniform token ids, layer after layer (a layer's input
    depends on the routing before it)."""
    tokens = jax.random.randint(key, balance_tokens, 0, c.vocab_size)
    x = params["embed"]["wte"][tokens]
    for block, p in enumerate(params["blocks"]):
        if c.pattern[block] == EXPERTS:
            h = _rms_norm(x, p["ln_ff"]["scale"], c.norm_eps)
            scores = jax.nn.sigmoid(
                _contract("bse,en->bsn", h, p["ff"]["router"], "highest"))
            p["ff"]["expert_bias"] = balanced_bias(
                scores.reshape(-1, c.num_experts), c.num_experts_per_tok)
        x, _ = _block_forward(p, x, c, block, "highest", None)
    return params


def init_params(seed: int, c: RefConfig, balance_tokens=BALANCE_TOKENS):
    """Seeded float32 weights, made on the device in ONE jitted call:
    {"embed": {wte}, "blocks": [per-layer trees], "head": {ln_f, w}}, each
    tree in the layout of `oobleck_tpu/models/nemotron_h.py`'s layer.

    The selection bias is what its own rule would have made of it:
    balanced, for the seed's router, on `balance_tokens` (sequences, length)
    uniform token ids (`reference/lfm2.py::init_params` says why)."""

    @jax.jit
    def make(key):
        k_e, k_b, k_h, k_t = jax.random.split(key, 4)
        e, v = c.hidden_size, c.vocab_size
        keys = jax.random.split(k_b, c.num_layers)
        params = {
            "embed": {"wte": jax.random.normal(k_e, (v, e), jnp.float32)
                      * c.initializer_range},
            "blocks": [_block(keys[i], c, i) for i in range(c.num_layers)],
            "head": {"ln_f": {"scale": jnp.ones((e,), jnp.float32)},
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

def _rms_norm(x, scale, eps):
    return x * lax.rsqrt(jnp.mean(jnp.square(x), -1, keepdims=True) + eps) \
        * scale


def _causal_conv(v, taps, bias):
    """v [B, S, D], taps [L, D]: sum_j taps[j] * v_{t-j} + bias, zeros
    before position 0."""
    s = v.shape[1]
    out = bias
    for j in range(taps.shape[0]):
        out = out + taps[j] * jnp.pad(v, ((0, 0), (j, 0), (0, 0)))[:, :s]
    return out


def recurrence(x, dt, a_neg, b, c, d_skip, mode: str):
    """The state-space recurrence, one position after another. x [B, S,
    G, R, P] (head g * R + r reads group g); dt [B, S, G, R]; a_neg,
    d_skip [G, R]; b, c [B, S, G, N]. Returns y [B, S, G, R, P]."""
    bsz, s, g, r, p = x.shape
    n = b.shape[-1]
    block = SCAN_BLOCK if s % SCAN_BLOCK == 0 else s

    def position(state, row):
        x_t, dt_t, b_t, c_t = row
        state = (jnp.exp(dt_t * a_neg)[..., None, None] * state
                 + _contract("bgrp,bgn->bgrpn", dt_t[..., None] * x_t, b_t,
                             mode))
        y_t = _contract("bgrpn,bgn->bgrp", state, c_t, mode)
        return state, y_t + d_skip[..., None] * x_t

    @jax.checkpoint
    def positions(state, rows):
        return lax.scan(position, state, rows)

    by_block = lambda t: jnp.moveaxis(t, 1, 0).reshape(
        s // block, block, *t.shape[:1], *t.shape[2:])
    _, y = lax.scan(positions, jnp.zeros((bsz, g, r, p, n), jnp.float32),
                    tuple(by_block(t) for t in (x, dt, b, c)))
    return jnp.moveaxis(y.reshape(s, bsz, g, r, p), 0, 1)


def _mamba(p, u, c: RefConfig, mode: str):
    bsz, s, _ = u.shape
    inner, conv = c.mamba_inner, c.conv_dim
    g, n = c.n_groups, c.ssm_state_size
    r = c.mamba_num_heads // g
    zxbcdt = _contract("bse,ef->bsf", u, p["w_in"], mode)
    z = zxbcdt[..., :inner]
    xbc = jax.nn.silu(_causal_conv(zxbcdt[..., inner:inner + conv],
                                   p["conv_taps"], p["conv_bias"]))
    dt = jax.nn.softplus(zxbcdt[..., inner + conv:] + p["dt_bias"])
    y = recurrence(
        xbc[..., :inner].reshape(bsz, s, g, r, c.mamba_head_dim),
        dt.reshape(bsz, s, g, r), -jnp.exp(p["A_log"]).reshape(g, r),
        xbc[..., inner:inner + g * n].reshape(bsz, s, g, n),
        xbc[..., inner + g * n:].reshape(bsz, s, g, n),
        p["D"].reshape(g, r), mode)
    y = y.reshape(bsz, s, inner) * jax.nn.silu(z)
    y = _rms_norm(y.reshape(bsz, s, g, inner // g), 1.0, c.norm_eps)
    return _contract("bsf,fe->bse", y.reshape(bsz, s, inner) * p["norm"],
                     p["w_out"], mode)


def _attention(p, u, c: RefConfig, mode: str):
    q = _contract("bse,ehd->bhsd", u, p["wq"], mode)
    k = _contract("bse,ehd->bhsd", u, p["wk"], mode)
    v = _contract("bse,ehd->bhsd", u, p["wv"], mode)
    rep = c.num_heads // c.num_kv_heads
    k, v = jnp.repeat(k, rep, axis=1), jnp.repeat(v, rep, axis=1)
    a = jnp.stack([attend(q[i], k[i], v[i], mode)
                   for i in range(q.shape[0])])            # [B, H, S, D]
    return _contract("bhsd,hde->bse", a, p["wo"], mode)


def _relu2_ff(w1, w2, u, mode: str):
    hidden = jnp.square(jax.nn.relu(_contract("bse,ef->bsf", u, w1, mode)))
    return _contract("bsf,fe->bse", hidden, w2, mode)


def _experts(p, u, c: RefConfig, mode: str, forced):
    """u [B, S, E] -> (held experts' part + shared expert [B, S, E], own
    choice [B, S, k]). `forced` [B, S, k] replaces the selection."""
    scores = jax.nn.sigmoid(_contract("bse,en->bsn", u, p["router"], mode))
    _, own = lax.top_k(lax.stop_gradient(scores + p["expert_bias"]),
                       c.num_experts_per_tok)
    chosen = own if forced is None else forced
    picked = jnp.sum(jax.nn.one_hot(chosen, c.num_experts, dtype=scores.dtype),
                     axis=-2)                              # [B, S, NE] 0/1
    w = picked * scores
    w = w / (jnp.sum(w, -1, keepdims=True) + 1e-6) * c.routed_scaling_factor
    out = _relu2_ff(p["shared"]["w1"], p["shared"]["w2"], u, mode)
    for held in range(c.num_experts_held):
        out = out + w[..., c.expert_offset + held, None] * _relu2_ff(
            p["w1"][held], p["w2"][held], u, mode)
    return out, own


def _block_forward(p, x, c: RefConfig, block: int, mode: str, forced):
    kind = c.pattern[block]
    if kind == EXPERTS:
        y, own = _experts(p["ff"], _rms_norm(x, p["ln_ff"]["scale"],
                                             c.norm_eps), c, mode, forced)
        return x + y, own
    u = _rms_norm(x, p["ln_op"]["scale"], c.norm_eps)
    if kind == MAMBA:
        return x + _mamba(p["mamba"], u, c, mode), None
    return x + _attention(p["attn"], u, c, mode), None


def forward(params, tokens, c: RefConfig, mode: str = "highest",
            forced=None):
    """tokens [B, S] -> (logits [B, S, vocab] float32, own), `own` the
    experts this file would choose in every `E` layer, a list of
    [B, S, k] in `routed_blocks` order; `forced`, a list like it, replaces
    the selection."""
    x = params["embed"]["wte"][tokens]
    own = []
    for block, p in enumerate(params["blocks"]):
        f = None
        if forced is not None and block in c.routed_blocks:
            f = forced[c.routed_blocks.index(block)]
        x, chose = jax.checkpoint(
            functools.partial(_block_forward, c=c, block=block, mode=mode)
        )(p, x, forced=f)
        if chose is not None:
            own.append(chose)
    x = _rms_norm(x, params["head"]["ln_f"]["scale"], c.norm_eps)
    return _contract("bse,ev->bsv", x, params["head"]["w"], mode), own


def loss(params, tokens, c: RefConfig, mode: str = "highest", forced=None):
    """(mean next-token cross entropy, own choices)."""
    logits, own = forward(params, tokens, c, mode, forced)
    logits = logits[:, :-1]
    logz = jax.nn.logsumexp(logits, axis=-1)
    gold = jnp.take_along_axis(logits, tokens[:, 1:, None], axis=-1)[..., 0]
    return jnp.mean(logz - gold), own


def loss_and_grads(params, tokens, c: RefConfig, mode: str = "highest",
                   forced=None):
    """((loss, own choices), gradients of every parameter; the selection
    bias's is zero: it selects and is not trained)."""
    return jax.value_and_grad(
        functools.partial(loss, c=c, mode=mode, forced=forced),
        has_aux=True)(params, tokens)
