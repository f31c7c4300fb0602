"""Plain reference for the Ouro family the benchmark runs (`model_type:
ouro`, here Ouro-2.6B; Zhu et al. 2025, "Scaling Latent Reasoning via Looped
Language Models", arXiv:2510.25741): a stack of L blocks gone through R
times over ONE set of weights, the final norm, an exit gate and an exit
after every pass, the loss over all R exits.

With `N(x; w) = x / sqrt(mean(x^2) + eps) * w`:

    u = wte[tokens]
    for t = 1 .. R:
        for b = 0 .. L-1:
            u = u + N(Attn_b(N(u; n1_b)); n2_b)
            u = u + N(FF_b(N(u; n3_b)); n4_b)
        x_t = N(u; n_f);  u = x_t
        g_t = x_t . w_g + b_g;  z_t = x_t W_head
    Attn: q, k, v = h W_q, h W_k, h W_v (no bias), rotate-half rotary over
          the whole head at theta on q and k, softmax(q k^T / sqrt(d)) v
          under the mask j <= i, W_o.   FF(h) = (silu(h W_1) * h W_3) W_2
    l_t[n] = -log softmax(z_t[n])[token[n+1]];  lam_t = sigmoid(g_t)
    p_t = lam_t prod_{j<t} (1 - lam_j)  (t < R);  p_R = prod_{j<R} (1 - lam_j)
    loss = mean_n [ sum_t p_t[n] l_t[n] + beta sum_t p_t[n] log p_t[n] ]

Written as the equations are: the passes a Python loop, the blocks a
Python loop inside it, attention a full softmax of a block of queries
against all keys under a mask built from positions
(`reference/smallthinker.py`'s `attend`), the exit distribution a product
of sigmoids, the loss one exit after another over blocks of `LOSS_BLOCK`
positions. `jax.checkpoint` around a block visit and around an exit's
block of positions is memory only. Float32, `Precision.HIGHEST`, nothing
imported from `oobleck_tpu`. `mode` rounds the operands of every
contraction (`reference/gpt.py`: "bfloat16", "fp8"), the gate's product
included.

`fault=` plants one of three faults for the control, each a program that a
looped model's machinery could be and must not pass for right:

  one_pass_short    R - 1 passes (and R - 1 exits)
  last_visit_grad   a shared weight's gradient taken from its LAST visit
                    alone: the blocks' and the close's parameters are
                    constants in passes 1 .. R - 1
  last_exit_only    the last exit's loss alone, p = (0, .., 0, 1)

The weights come from a seed alone, in the tree the program's layers hold
(`{"embed": {wte}, "blocks": [...], "head": {w}}`; the final norm and the
gate under `close` in the last block's tree, where
`oobleck_tpu/models/ouro.py` keeps them). Every norm's scale is drawn
1 + normal 0.02 and the gate's bias normal 0.02, where the program's own
init has 1 and 0: a scale of one or a bias of zero hides where it is
applied.
"""

from __future__ import annotations

import functools
from dataclasses import dataclass

import jax
import jax.numpy as jnp
from jax import lax

from benchmarks.reference.gpt import _contract
from benchmarks.reference.smallthinker import _norm, _rope, attend

FAULTS = (None, "one_pass_short", "last_visit_grad", "last_exit_only")
LOSS_BLOCK = 1024


@dataclass(frozen=True)
class RefConfig:
    vocab_size: int
    hidden_size: int
    num_layers: int                    # as run
    num_passes: int
    num_heads: int
    num_kv_heads: int
    head_dim: int
    intermediate_size: int
    rope_theta: float
    norm_eps: float = 1e-6
    exit_entropy_weight: float = 0.1
    initializer_range: float = 0.02

    @classmethod
    def from_config(cls, config: dict) -> "RefConfig":
        """From a file under benchmarks/configs/: the sizes as they are
        run, under the published keys."""
        return cls(
            vocab_size=config["vocab_size"],
            hidden_size=config["hidden_size"],
            num_layers=config["num_hidden_layers"],
            num_passes=config["total_ut_steps"],
            num_heads=config["num_attention_heads"],
            num_kv_heads=config["num_key_value_heads"],
            head_dim=config["head_dim"],
            intermediate_size=config["intermediate_size"],
            rope_theta=config["rope_theta"],
            norm_eps=config["rms_norm_eps"],
            exit_entropy_weight=config["exit_entropy_weight"])

    def block_params(self) -> dict[str, int]:
        """Parameters of one block by part (every block alike)."""
        e, d = self.hidden_size, self.head_dim
        return {"attention": 2 * e * (self.num_heads + self.num_kv_heads) * d,
                "ff": 3 * e * self.intermediate_size,
                "norms": 4 * e}

    def num_params(self) -> int:
        """Every parameter HELD, each once: embedding, blocks, the final
        norm and the gate, the head."""
        e = self.hidden_size
        return (2 * self.vocab_size * e + 2 * e + 1
                + self.num_layers * sum(self.block_params().values()))

    def applied_params(self) -> int:
        """Matrix parameters a token is multiplied through, once a use: the
        blocks' and the head's, `num_passes` times each."""
        block = self.block_params()
        return self.num_passes * (
            self.num_layers * (block["attention"] + block["ff"])
            + self.vocab_size * self.hidden_size)


# --------------------------------------------------------------------- #
# weights from a seed, in the program's layout                           #
# --------------------------------------------------------------------- #

def _block(key, c: RefConfig, last: bool):
    ks = jax.random.split(key, 14)
    f32 = jnp.float32
    std = c.initializer_range
    res_std = std / (2 * c.num_layers) ** 0.5
    e, h, kv, d = c.hidden_size, c.num_heads, c.num_kv_heads, c.head_dim
    f = c.intermediate_size
    normal = lambda k, shape, s: jax.random.normal(k, shape, f32) * s
    scale = lambda k: {"scale": 1.0 + normal(k, (e,), std)}
    p = {"ln_op": scale(ks[7]), "ln_op_out": scale(ks[8]),
         "ln_ff": scale(ks[9]), "ln_ff_out": scale(ks[10]),
         "attn": {"wq": normal(ks[0], (e, h, d), std),
                  "wk": normal(ks[1], (e, kv, d), std),
                  "wv": normal(ks[2], (e, kv, d), std),
                  "wo": normal(ks[3], (h, d, e), res_std)},
         "ff": {"w1": normal(ks[4], (e, f), std),
                "w3": normal(ks[5], (e, f), std),
                "w2": normal(ks[6], (f, e), res_std)}}
    if last:
        p["close"] = {"ln_f": scale(ks[11]), "w_g": normal(ks[12], (e,), std),
                      "b_g": normal(ks[13], (), std)}
    return p


def init_params(seed: int, c: RefConfig):
    """Seeded float32 weights, made on the device in ONE jitted call."""

    @jax.jit
    def make(key):
        k_e, k_b, k_h = jax.random.split(key, 3)
        e, v = c.hidden_size, c.vocab_size
        keys = jax.random.split(k_b, c.num_layers)
        return {
            "embed": {"wte": jax.random.normal(k_e, (v, e), jnp.float32)
                      * c.initializer_range},
            "blocks": [_block(keys[i], c, i == c.num_layers - 1)
                       for i in range(c.num_layers)],
            "head": {"w": jax.random.normal(k_h, (e, v), jnp.float32)
                     * c.initializer_range},
        }

    seed = int(seed)
    key = jax.random.fold_in(jax.random.PRNGKey(seed & 0x7FFFFFFF), seed >> 31)
    return make(key)


# --------------------------------------------------------------------- #
# the model                                                              #
# --------------------------------------------------------------------- #

def _attention(p, h, c: RefConfig, mode: str):
    q = _contract("bse,ehd->bhsd", h, p["wq"], mode)
    k = _contract("bse,ehd->bhsd", h, p["wk"], mode)
    v = _contract("bse,ehd->bhsd", h, p["wv"], mode)
    q, k = _rope(q, c.rope_theta), _rope(k, c.rope_theta)
    a = jnp.stack([attend(q[i], k[i], v[i], mode, None)
                   for i in range(q.shape[0])])            # [B, H, S, D]
    return _contract("bhsd,hde->bse", a, p["wo"], mode)


def _swiglu(p, h, mode: str):
    hidden = (jax.nn.silu(_contract("bse,ef->bsf", h, p["w1"], mode))
              * _contract("bse,ef->bsf", h, p["w3"], mode))
    return _contract("bsf,fe->bse", hidden, p["w2"], mode)


def _block_forward(p, u, c: RefConfig, mode: str):
    n = lambda x, name: _norm(x, p[name]["scale"], c.norm_eps)
    u = u + n(_attention(p["attn"], n(u, "ln_op"), c, mode), "ln_op_out")
    return u + n(_swiglu(p["ff"], n(u, "ln_ff"), mode), "ln_ff_out")


def exits(params, tokens, c: RefConfig, mode: str = "highest", fault=None,
          untied: bool = False):
    """tokens [B, S] -> (the exit states x_t, a list of [B, S, E]; the gate
    logits g_t, a list of [B, S]). `untied`: `params["blocks"]` is a list
    of R block lists, pass t going through its own copy (what a test sums
    the gradients of)."""
    if fault not in FAULTS:
        raise ValueError(f"fault must be one of {FAULTS}, got {fault!r}")
    passes = c.num_passes - (fault == "one_pass_short")
    block = jax.checkpoint(functools.partial(_block_forward, c=c, mode=mode))
    u = params["embed"]["wte"][tokens]
    states, gates = [], []
    for t in range(passes):
        blocks = params["blocks"][t] if untied else params["blocks"]
        if fault == "last_visit_grad" and t < passes - 1:
            blocks = lax.stop_gradient(blocks)
        for p in blocks:
            u = block(p, u)
        close = blocks[-1]["close"]
        u = _norm(u, close["ln_f"]["scale"], c.norm_eps)
        states.append(u)
        gates.append(_contract("bse,e->bs", u, close["w_g"], mode)
                     + close["b_g"])
    return states, gates


def exit_distribution(gates):
    """p_t [R, ...] from the gate logits g_t [R, ...]."""
    lam = jax.nn.sigmoid(gates)
    stay = jnp.cumprod(1.0 - lam, axis=0)                  # prod over j <= t
    before = jnp.concatenate([jnp.ones_like(stay[:1]), stay[:-1]])
    return jnp.concatenate([(lam * before)[:-1], before[-1:]])


def _exit_ce(x, w, tokens, c: RefConfig, mode: str):
    """One exit's cross-entropy of token n + 1 at position n, [B, S - 1],
    over blocks of `LOSS_BLOCK` positions (the whole sequence where that
    does not divide it)."""
    b, s, _ = x.shape
    size = LOSS_BLOCK if s % LOSS_BLOCK == 0 else s
    targets = jnp.pad(tokens[:, 1:], ((0, 0), (0, 1)))

    @jax.checkpoint
    def block_ce(x_b, targets_b):
        logits = _contract("bse,ev->bsv", x_b, w, mode)
        gold = jnp.take_along_axis(logits, targets_b[..., None], -1)[..., 0]
        return jax.nn.logsumexp(logits, -1) - gold

    by_block = lambda t: jnp.moveaxis(
        t.reshape(b, s // size, size, *t.shape[2:]), 1, 0)
    ce = lax.map(lambda args: block_ce(*args),
                 (by_block(x), by_block(targets)))         # [blocks, B, size]
    return jnp.moveaxis(ce, 0, 1).reshape(b, s)[:, :-1]


def loss(params, tokens, c: RefConfig, mode: str = "highest", fault=None,
         untied: bool = False):
    """(the loss, each exit's own mean cross-entropy [R])."""
    states, gates = exits(params, tokens, c, mode, fault, untied)
    ce = jnp.stack([_exit_ce(x, params["head"]["w"], tokens, c, mode)
                    for x in states])                      # [R, B, S-1]
    p = exit_distribution(jnp.stack(gates)[..., :-1])
    if fault == "last_exit_only":
        p = jnp.zeros_like(p).at[-1].set(1.0)
    entropy = -jnp.sum(jnp.where(p > 0, p * jnp.log(jnp.maximum(p, 1e-37)),
                                 0.0), axis=0)
    per_position = jnp.sum(p * ce, axis=0) - c.exit_entropy_weight * entropy
    return jnp.mean(per_position), jnp.mean(ce, axis=(1, 2))


def loss_and_grads(params, tokens, c: RefConfig, mode: str = "highest",
                   fault=None):
    """((loss, each exit's mean cross-entropy), gradients of every
    parameter)."""
    return jax.value_and_grad(
        functools.partial(loss, c=c, mode=mode, fault=fault),
        has_aux=True)(params, tokens)
