"""Plain reference for the LFM2-MoE family (LiquidAI `lfm2_moe`: LFM2-24B-A2B).

Every block, with `N` = RMSNorm (learned scale):

    h = x + Op(N_op(x));    y = h + FF(N_ff(h));    logits = Head(N_f(y_last))
    Op conv:  [B | C | u] = W_in x;  z = causal depthwise conv (kernel 3)
              of B * u;  Op = W_out (C * z)            (no activation)
    Op attn:  q, k, v = W_q x, W_k x, W_v x (32 / 8 / 8 heads of 64);
              q, k <- RMSNorm over each head; rotate-half RoPE on q, k;
              causal softmax(q k^T / sqrt(64)) v, a KV head serving 4 q
              heads;  Op = W_o o
    FF dense  (block index < num_dense_layers): W_2 (silu(W_1 x) * W_3 x)
    FF routed: s = sigmoid(W_r x); I = top-k of (s + b); g_i = s_i /
              (sum_{j in I} s_j + 1e-6) * routed_scaling_factor;
              FF = sum_{i in I, i held} g_i W_2^i (silu(W_1^i x) * W_3^i x)

`held` is the contiguous range of experts the share holds (`expert_offset`,
`num_experts_held`); with all of them it is the published layer. The
vocabulary is the rows held. Plain `jax.numpy`, float32, every contraction
at `Precision.HIGHEST`; the routed layer runs EVERY held expert on every
token and weights the results with a dense [tokens, experts] matrix of g:
no sort, no kernel. Nothing is imported from `oobleck_tpu`; the modes of
arithmetic (`highest`, `bfloat16`, `fp8`) are `reference/gpt.py`'s.

Departure, made for the comparison that decides `correct`: `forward` can be
handed, per routed block, the expert indices to use (`forced`). A rounding
error of a percent on a router score swaps a token's fourth and fifth
expert, and a swapped token's expert output is simply another vector, so a
free-running comparison of gradients measures how often that happened and
not the arithmetic. With `forced`, the selection is the program's and
everything else -- scores, weights g, experts, loss, every gradient -- is
this file's own. What the reference WOULD have selected, from its own
hidden state, is returned beside it (`own`), and `mismatch_share` counts
the (token, block) pairs whose top-k SET differs.
"""

from __future__ import annotations

import functools
from dataclasses import dataclass

import jax
import jax.numpy as jnp
from jax import lax

from benchmarks.reference.gpt import MODES, _contract  # noqa: F401

NEG_INF = -1e30
CONV, ATTN = "conv", "full_attention"


@dataclass(frozen=True)
class RefConfig:
    vocab_size: int                    # the rows of the vocabulary held
    hidden_size: int
    num_layers: int
    num_heads: int
    num_kv_heads: int
    intermediate_size: int
    moe_intermediate_size: int
    num_experts: int
    num_experts_per_tok: int
    num_dense_layers: int
    layer_types: tuple[str, ...]
    num_experts_held: int
    expert_offset: int = 0
    norm_topk_prob: bool = True
    use_expert_bias: bool = True
    routed_scaling_factor: float = 1.0
    conv_L_cache: int = 3
    norm_eps: float = 1e-5
    rope_theta: float = 1e6
    initializer_range: float = 0.02
    expert_bias_range: float = 0.01

    @property
    def head_dim(self) -> int:
        return self.hidden_size // self.num_heads

    @property
    def routed_blocks(self) -> tuple[int, ...]:
        return tuple(range(self.num_dense_layers, self.num_layers))

    @classmethod
    def from_config(cls, config: dict) -> "RefConfig":
        """From a file under benchmarks/configs/: the sizes as they are run."""
        return cls(
            vocab_size=config["vocab_rows_held"],
            hidden_size=config["hidden_size"],
            num_layers=config["num_layers"],
            num_heads=config["num_heads"],
            num_kv_heads=config["num_kv_heads"],
            intermediate_size=config["intermediate_size"],
            moe_intermediate_size=config["moe_intermediate_size"],
            num_experts=config["num_experts"],
            num_experts_per_tok=config["num_experts_per_tok"],
            num_dense_layers=config["num_dense_layers"],
            layer_types=tuple(config["layer_types"]),
            num_experts_held=config["num_experts_held"],
            expert_offset=config.get("expert_offset", 0))

    def block_params(self, block: int) -> dict[str, int]:
        """Parameters of one block by part (for sizes and FLOP counts)."""
        e, d = self.hidden_size, self.head_dim
        op = (3 * e * e + self.conv_L_cache * e + e * e
              if self.layer_types[block] == CONV
              else e * d * (2 * self.num_heads + 2 * self.num_kv_heads)
              + 2 * d)
        if block < self.num_dense_layers:
            ff, router, bias = 3 * e * self.intermediate_size, 0, 0
        else:
            ff = self.num_experts_held * 3 * e * self.moe_intermediate_size
            router = e * self.num_experts
            bias = self.num_experts if self.use_expert_bias else 0
        return {"operator": op, "ff": ff, "router": router, "bias": bias,
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
    ks = jax.random.split(key, 12)
    f32 = jnp.float32
    std = c.initializer_range
    res_std = std / (2 * c.num_layers) ** 0.5
    e, h, kv, d = c.hidden_size, c.num_heads, c.num_kv_heads, c.head_dim
    normal = lambda k, shape, s: jax.random.normal(k, shape, f32) * s
    p = {"ln_op": {"scale": jnp.ones((e,), f32)},
         "ln_ff": {"scale": jnp.ones((e,), f32)}}
    if c.layer_types[block] == CONV:
        p["conv"] = {"w_in": normal(ks[0], (e, 3, e), std),
                     "taps": normal(ks[1], (c.conv_L_cache, e),
                                    c.conv_L_cache ** -0.5),
                     "w_out": normal(ks[2], (e, e), res_std)}
    else:
        p["attn"] = {"wq": normal(ks[0], (e, h, d), std),
                     "wk": normal(ks[1], (e, kv, d), std),
                     "wv": normal(ks[2], (e, kv, d), std),
                     "q_norm": jnp.ones((d,), f32),
                     "k_norm": jnp.ones((d,), f32),
                     "wo": normal(ks[3], (h, d, e), res_std)}
    if block < c.num_dense_layers:
        f = c.intermediate_size
        p["ff"] = {"w1": normal(ks[4], (e, f), std),
                   "w3": normal(ks[5], (e, f), std),
                   "w2": normal(ks[6], (f, e), res_std)}
    else:
        f, ne, held = (c.moe_intermediate_size, c.num_experts,
                       c.num_experts_held)
        p["ff"] = {"router": normal(ks[7], (e, ne), std),
                   "w1": normal(ks[8], (held, e, f), std),
                   "w3": normal(ks[9], (held, e, f), std),
                   "w2": normal(ks[10], (held, f, e), res_std)}
        if c.use_expert_bias:
            p["ff"]["expert_bias"] = normal(ks[11], (ne,),
                                            c.expert_bias_range)
    return p


BALANCE_TOKENS = (4, 1024)     # sequences x length the bias is balanced on
BALANCE_STEPS = 64


def balanced_bias(scores, top_k: int):
    """The expert bias that evens the experts' loads on `scores` [N, NE]:
    the fixed point of the rule such a bias is published with (after every
    batch, raise the bias of an expert under the mean load and lower that
    of one over it), run here with a step that decays from 0.02 to 2e-4."""
    n, ne = scores.shape

    def step(i, bias):
        _, chosen = lax.top_k(scores + bias, top_k)
        load = jnp.sum(jax.nn.one_hot(chosen, ne, dtype=jnp.float32),
                       axis=(0, 1))
        return bias + 0.02 * 0.93 ** i * jnp.sign(n * top_k / ne - load)

    return lax.fori_loop(0, BALANCE_STEPS, step, jnp.zeros((ne,), jnp.float32))


def _balance(params, key, c: RefConfig):
    """Replace every routed block's seeded bias by the one that balances
    its router on seeded uniform token ids, block after block (a block's
    input depends on the routing before it)."""
    tokens = jax.random.randint(key, BALANCE_TOKENS, 0, c.vocab_size)
    x = params["embed"]["wte"][tokens]
    for block, p in enumerate(params["blocks"]):
        x = _operator_half(p, x, c, block, "highest")
        if "expert_bias" in p["ff"]:
            h = _rms_norm(x, p["ln_ff"]["scale"], c.norm_eps)
            scores = jax.nn.sigmoid(
                _contract("bse,en->bsn", h, p["ff"]["router"], "highest"))
            p["ff"]["expert_bias"] = balanced_bias(
                scores.reshape(-1, c.num_experts), c.num_experts_per_tok)
        x, _ = _ff_half(p, x, c, block, "highest", None)
    return params


def init_params(seed: int, c: RefConfig):
    """Seeded float32 weights, made on the device in ONE jitted call:
    {"embed": {wte}, "blocks": [per-block trees], "head": {ln_f, w}}, each
    tree in the layout of `oobleck_tpu/models/lfm2.py`'s layer.

    The expert bias is what its own rule would have made of it: balanced,
    for the seed's router, on uniform token ids (`_balance`). A bias drawn
    at random leaves the experts' loads a fifth apart and differently so
    with every seed, and the rows routed to the held experts, the tiles in
    use and the step's time with them (0.9 % between six seeds, my chip
    runs, PR 29); in a deployment the bias exists to make the loads even."""

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
        return _balance(params, k_t, c) if c.use_expert_bias else params

    seed = int(seed)
    key = jax.random.fold_in(jax.random.PRNGKey(seed & 0x7FFFFFFF), seed >> 31)
    return make(key)


# --------------------------------------------------------------------- #
# arithmetic                                                             #
# --------------------------------------------------------------------- #

def _rms_norm(x, scale, eps):
    return x * lax.rsqrt(jnp.mean(jnp.square(x), -1, keepdims=True) + eps) \
        * scale


def _rope(x, theta):
    """Rotate-half rotary embedding. x [B, H, S, D], positions 0..S-1."""
    d, s = x.shape[-1], x.shape[-2]
    inv = theta ** (-jnp.arange(0, d, 2, dtype=jnp.float32) / d)
    ang = jnp.arange(s, dtype=jnp.float32)[:, None] * inv[None, :]
    cos = jnp.concatenate([jnp.cos(ang), jnp.cos(ang)], -1)
    sin = jnp.concatenate([jnp.sin(ang), jnp.sin(ang)], -1)
    half = jnp.concatenate([-x[..., d // 2:], x[..., : d // 2]], -1)
    return x * cos + half * sin


def _conv_operator(p, h, c: RefConfig, mode: str):
    bcu = _contract("bse,ekd->kbsd", h, p["w_in"], mode)
    bu = bcu[0] * bcu[2]                                   # [B, S, D]
    taps = p["taps"]                                       # [L, D]
    # Depthwise causal convolution: z_t = sum_j taps[j] * bu_{t-j}, the
    # inputs before the sequence's start zero. Written as shifts: XLA's own
    # grouped convolution (`lax.conv_general_dilated`, which the program's
    # operator is tested against on the CPU) fails the TPU compiler's
    # verifier in the gradient at 2048 groups (my chip run, PR 29).
    s = bu.shape[1]
    z = jnp.zeros_like(bu)
    for j in range(taps.shape[0]):
        shifted = jnp.concatenate(
            [jnp.zeros_like(bu[:, :j]), bu[:, :s - j]], axis=1)
        z = z + shifted * taps[j]
    return _contract("bsd,de->bse", bcu[1] * z, p["w_out"], mode)


def _attention_operator(p, h, c: RefConfig, mode: str):
    s = h.shape[1]
    q = _contract("bse,ehd->bhsd", h, p["wq"], mode)
    k = _contract("bse,ehd->bhsd", h, p["wk"], mode)
    v = _contract("bse,ehd->bhsd", h, p["wv"], mode)
    q = _rope(_rms_norm(q, p["q_norm"], c.norm_eps), c.rope_theta)
    k = _rope(_rms_norm(k, p["k_norm"], c.norm_eps), c.rope_theta)
    rep = c.num_heads // c.num_kv_heads
    k, v = jnp.repeat(k, rep, axis=1), jnp.repeat(v, rep, axis=1)
    scores = _contract("bhqd,bhkd->bhqk", q, k, mode) * c.head_dim ** -0.5
    scores = jnp.where(jnp.tril(jnp.ones((s, s), bool)), scores, NEG_INF)
    attn = _contract("bhqk,bhkd->bhqd", jax.nn.softmax(scores, -1), v, mode)
    return _contract("bhsd,hde->bse", attn, p["wo"], mode)


def _routed(p, h, c: RefConfig, mode: str, forced):
    """h [B, S, E] -> (FF [B, S, E], own choice [B, S, k]). `forced`
    [B, S, k] replaces the selection where given."""
    scores = jax.nn.sigmoid(_contract("bse,en->bsn", h, p["router"], mode))
    biased = scores + p["expert_bias"] if "expert_bias" in p else scores
    _, own = lax.top_k(lax.stop_gradient(biased), c.num_experts_per_tok)
    chosen = own if forced is None else forced
    picked = jnp.sum(jax.nn.one_hot(chosen, c.num_experts, dtype=scores.dtype),
                     axis=-2)                              # [B, S, NE] 0/1
    g = picked * scores
    if c.norm_topk_prob:
        g = g / (jnp.sum(g, -1, keepdims=True) + 1e-6)
    g = g * c.routed_scaling_factor
    g = g[..., c.expert_offset:c.expert_offset + c.num_experts_held]
    gate = _contract("bse,xef->xbsf", h, p["w1"], mode)
    up = _contract("bse,xef->xbsf", h, p["w3"], mode)
    out = _contract("xbsf,xfe->xbse", jax.nn.silu(gate) * up, p["w2"], mode)
    return jnp.einsum("xbse,bsx->bse", out, g,
                      precision=lax.Precision.HIGHEST), own


def _operator_half(p, x, c: RefConfig, block: int, mode: str):
    h = _rms_norm(x, p["ln_op"]["scale"], c.norm_eps)
    if c.layer_types[block] == CONV:
        return x + _conv_operator(p["conv"], h, c, mode)
    return x + _attention_operator(p["attn"], h, c, mode)


def _ff_half(p, x, c: RefConfig, block: int, mode: str, forced):
    h = _rms_norm(x, p["ln_ff"]["scale"], c.norm_eps)
    if block < c.num_dense_layers:
        ff = p["ff"]
        y = _contract(
            "bsf,fe->bse",
            jax.nn.silu(_contract("bse,ef->bsf", h, ff["w1"], mode))
            * _contract("bse,ef->bsf", h, ff["w3"], mode), ff["w2"], mode)
        return x + y, None
    y, own = _routed(p["ff"], h, c, mode, forced)
    return x + y, own


def _block_forward(p, x, c: RefConfig, block: int, mode: str, forced):
    return _ff_half(p, _operator_half(p, x, c, block, mode), c, block, mode,
                    forced)


def forward(params, tokens, c: RefConfig, mode: str = "highest",
            forced=None):
    """tokens [B, S] -> (logits [B, S, vocab] float32, own), `own` the
    experts this file would choose in every routed block, a list of
    [B, S, k] in `routed_blocks` order; `forced`, a list like it, replaces
    the selection."""
    x = params["embed"]["wte"][tokens]
    own = []
    for block, p in enumerate(params["blocks"]):
        routed_index = block - c.num_dense_layers
        f = (forced[routed_index]
             if forced is not None and routed_index >= 0 else None)
        x, chose = _block_forward(p, x, c, block, mode, f)
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
    """((loss, own choices), gradients of every parameter; the expert
    bias's is zero: it selects and is not trained)."""
    return jax.value_and_grad(
        functools.partial(loss, c=c, mode=mode, forced=forced),
        has_aux=True)(params, tokens)


def mismatch_share(chosen, own) -> jax.Array:
    """Share of (token, routed block) pairs whose top-k SET differs between
    two lists of [B, S, k] choices."""
    differs = [jnp.any(jnp.sort(a, -1) != jnp.sort(b, -1), axis=-1)
               for a, b in zip(chosen, own)]
    return jnp.mean(jnp.stack(differs).astype(jnp.float32))
