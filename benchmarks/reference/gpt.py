"""Plain reference for the GPT family the benchmark runs (gpt2-*, gpt3-*).

The published model (Radford et al. 2019; Brown et al. 2020, section 2.1):
token + learned position embeddings, pre-LayerNorm blocks of causal
multi-head attention and a 4x GELU (tanh form) MLP, a final LayerNorm and a
vocabulary projection; next-token cross entropy. Written in straightforward
`jax.numpy`, float32, every contraction at `Precision.HIGHEST`; no kernel,
no cache, no batching tricks. It imports nothing from `oobleck_tpu`.

Departures from the published model, both made so that the reference and
the program take the same seeded weights:

  * the output head is NOT tied to the token embedding (the program keeps
    an untied `head.w`, `oobleck_tpu/models/gpt.py::_init_head`);
  * the vocabulary is padded to a multiple of 128 rows (50257 -> 50304);
    the padded logits are masked out, so they change no result.

`mode` selects the arithmetic of every contraction:

  "highest"   float32 operands, Precision.HIGHEST       (the reference)
  "bfloat16"  operands rounded to bfloat16, f32 sums    (what the cells'
              configurations state)
  "fp8"       operands rounded to float8_e4m3 with one scale per tensor,
              f32 sums (the control: the nearest precision BELOW the one
              the configurations state; `correct` must come out false)

Weights come from a seed alone (`init_params`), in one jitted call on the
default device, in the tree layout the program uses, so they can be handed
to the program and to this file alike.
"""

from __future__ import annotations

import functools
from dataclasses import dataclass

import jax
import jax.numpy as jnp
from jax import lax

NEG_INF = -1e30
MODES = ("highest", "bfloat16", "fp8")
_F8_MAX = 448.0


@dataclass(frozen=True)
class RefConfig:
    vocab_size: int
    max_position_embeddings: int
    hidden_size: int
    num_layers: int
    num_heads: int
    layer_norm_epsilon: float = 1e-5
    initializer_range: float = 0.02
    vocab_pad_multiple: int = 128

    @property
    def padded_vocab_size(self) -> int:
        m = self.vocab_pad_multiple
        return (self.vocab_size + m - 1) // m * m

    @property
    def head_dim(self) -> int:
        return self.hidden_size // self.num_heads

    @classmethod
    def from_config(cls, config: dict) -> "RefConfig":
        """From a file under benchmarks/configs/: the sizes as they are run."""
        return cls(vocab_size=config["vocab_size"],
                   max_position_embeddings=config["max_position_embeddings"],
                   hidden_size=config["hidden_size"],
                   num_layers=config["num_layers"],
                   num_heads=config["num_heads"])

    def num_params(self) -> int:
        e, v = self.hidden_size, self.padded_vocab_size
        block = 12 * e * e + 13 * e
        return (2 * v * e + self.max_position_embeddings * e + 2 * e
                + self.num_layers * block)


# --------------------------------------------------------------------- #
# weights from a seed                                                    #
# --------------------------------------------------------------------- #

def _block(key, c: RefConfig):
    ks = jax.random.split(key, 4)
    e, h, d = c.hidden_size, c.num_heads, c.head_dim
    std = c.initializer_range
    res_std = std / (2 * c.num_layers) ** 0.5
    f32 = jnp.float32
    return {
        "ln1": {"scale": jnp.ones((e,), f32), "bias": jnp.zeros((e,), f32)},
        "attn": {
            "wqkv": jax.random.normal(ks[0], (e, 3, h, d), f32) * std,
            "bqkv": jnp.zeros((3, h, d), f32),
            "wo": jax.random.normal(ks[1], (h, d, e), f32) * res_std,
            "bo": jnp.zeros((e,), f32),
        },
        "ln2": {"scale": jnp.ones((e,), f32), "bias": jnp.zeros((e,), f32)},
        "mlp": {
            "wi": jax.random.normal(ks[2], (e, 4 * e), f32) * std,
            "bi": jnp.zeros((4 * e,), f32),
            "wo": jax.random.normal(ks[3], (4 * e, e), f32) * res_std,
            "bo": jnp.zeros((e,), f32),
        },
    }


def init_params(seed: int, c: RefConfig, *, stacked: bool):
    """Seeded float32 weights, made on the device in ONE jitted call.

    {"embed": {wte, wpe}, "blocks": ..., "head": {ln_f, w}}; `blocks` is a
    list of per-layer trees, or with `stacked` one tree whose leaves carry
    a leading [num_layers] axis (the layout `models/gpt.py` scans over).
    """

    @jax.jit
    def make(key):
        k_e, k_p, k_b, k_h = jax.random.split(key, 4)
        e, v = c.hidden_size, c.padded_vocab_size
        std = c.initializer_range
        f32 = jnp.float32
        keys = jax.random.split(k_b, c.num_layers)
        if stacked:
            blocks = jax.vmap(lambda k: _block(k, c))(keys)
        else:
            blocks = [_block(keys[i], c) for i in range(c.num_layers)]
        return {
            "embed": {
                "wte": jax.random.normal(k_e, (v, e), f32) * std,
                "wpe": jax.random.normal(
                    k_p, (c.max_position_embeddings, e), f32) * std,
            },
            "blocks": blocks,
            "head": {
                "ln_f": {"scale": jnp.ones((e,), f32),
                         "bias": jnp.zeros((e,), f32)},
                "w": jax.random.normal(k_h, (e, v), f32) * std,
            },
        }

    # A seed can exceed 32 signed bits; fold it into two words.
    seed = int(seed)
    key = jax.random.fold_in(jax.random.PRNGKey(seed & 0x7FFFFFFF), seed >> 31)
    return make(key)


# --------------------------------------------------------------------- #
# arithmetic                                                             #
# --------------------------------------------------------------------- #

def _round_e4m3(x):
    """Round float32 values in [-448, 448] to the nearest float8_e4m3
    value (4 significant bits; subnormals below 2**-6), in arithmetic the
    compiler cannot drop: a cast to float8 and back is a pair of converts
    that XLA may remove as excess precision, and on the TPU it did (the
    training control then read exactly the bfloat16 error)."""
    _, exp = jnp.frexp(x)                       # |x| in [2**(exp-1), 2**exp)
    step = jnp.exp2((jnp.maximum(exp - 1, -6) - 3).astype(jnp.float32))
    return jnp.clip(jnp.round(x / step) * step, -_F8_MAX, _F8_MAX)


def _round(x, mode: str):
    if mode == "highest":
        return x.astype(jnp.float32)
    if mode == "bfloat16":
        return x.astype(jnp.bfloat16)
    if mode == "fp8":
        x = x.astype(jnp.float32)
        scale = jnp.maximum(jnp.max(jnp.abs(x)), 1e-30) / _F8_MAX
        rounded = _round_e4m3(x / scale) * scale
        # Straight-through: the rounding changes the value a contraction
        # sees, forward and backward, and passes the gradient unchanged.
        return (x + lax.stop_gradient(rounded - x)).astype(jnp.bfloat16)
    raise ValueError(f"mode must be one of {MODES}, got {mode!r}")


def _contract(spec: str, a, b, mode: str):
    precision = lax.Precision.HIGHEST if mode == "highest" else None
    return jnp.einsum(spec, _round(a, mode), _round(b, mode),
                      precision=precision,
                      preferred_element_type=jnp.float32)


def _layer_norm(x, p, eps):
    mean = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(x - mean), axis=-1, keepdims=True)
    return (x - mean) * lax.rsqrt(var + eps) * p["scale"] + p["bias"]


def _gelu(x):
    return 0.5 * x * (1.0 + jnp.tanh(
        0.7978845608028654 * (x + 0.044715 * x ** 3)))


def _block_forward(p, x, c: RefConfig, mode: str):
    """x [B, S, E] float32 -> [B, S, E]."""
    s = x.shape[1]
    h = _layer_norm(x, p["ln1"], c.layer_norm_epsilon)
    qkv = _contract("bse,ethd->tbhsd", h, p["attn"]["wqkv"], mode)
    qkv = qkv + p["attn"]["bqkv"][:, None, :, None, :]
    q, k, v = qkv[0], qkv[1], qkv[2]
    scores = _contract("bhqd,bhkd->bhqk", q, k, mode) * c.head_dim ** -0.5
    causal = jnp.tril(jnp.ones((s, s), bool))
    scores = jnp.where(causal, scores, NEG_INF)
    probs = jax.nn.softmax(scores, axis=-1)
    attn = _contract("bhqk,bhkd->bhqd", probs, v, mode)
    out = _contract("bhsd,hde->bse", attn, p["attn"]["wo"], mode)
    x = x + out + p["attn"]["bo"]
    h = _layer_norm(x, p["ln2"], c.layer_norm_epsilon)
    h = _gelu(_contract("bse,ef->bsf", h, p["mlp"]["wi"], mode)
              + p["mlp"]["bi"])
    return x + _contract("bsf,fe->bse", h, p["mlp"]["wo"], mode) \
        + p["mlp"]["bo"]


def forward(params, tokens, c: RefConfig, mode: str = "highest"):
    """tokens [B, S] int32 -> logits [B, S, padded vocab] float32, the
    padded columns at NEG_INF. A full forward pass: no cache."""
    s = tokens.shape[1]
    x = params["embed"]["wte"][tokens] + params["embed"]["wpe"][:s]
    blocks = params["blocks"]
    if isinstance(blocks, (list, tuple)):
        for bp in blocks:
            x = _block_forward(bp, x, c, mode)
    else:  # stacked on a leading layer axis
        x, _ = lax.scan(
            lambda x, bp: (_block_forward(bp, x, c, mode), None), x, blocks)
    x = _layer_norm(x, params["head"]["ln_f"], c.layer_norm_epsilon)
    logits = _contract("bse,ev->bsv", x, params["head"]["w"], mode)
    live = jnp.arange(logits.shape[-1]) < c.vocab_size
    return jnp.where(live, logits, NEG_INF)


def loss(params, tokens, c: RefConfig, mode: str = "highest"):
    """Mean next-token cross entropy: positions :-1 predict tokens 1:."""
    logits = forward(params, tokens, c, mode)[:, :-1]
    logz = jax.nn.logsumexp(logits, axis=-1)
    gold = jnp.take_along_axis(logits, tokens[:, 1:, None], axis=-1)[..., 0]
    return jnp.mean(logz - gold)


def loss_and_grads(params, tokens, c: RefConfig, mode: str = "highest"):
    return jax.value_and_grad(
        functools.partial(loss, c=c, mode=mode))(params, tokens)
