"""Plain reference for the SmallThinker family (`model_name:
smallthinker_21b_instruct`: SmallThinker-21BA3B-Instruct).

Norm `N(x) = w * x / rms(x)`, a plain weight initialised 1, eps 1e-6. Every
block alike but for its attention's kind:

    h = N1(x);   x = x + Attn_i(h);   x = x + Experts(N2(x); routed by h)
    logits = Head(N_f(x_last))

    Attn_i (28 query heads, 4 key-value heads, of 128; no bias, no norm
        over a head). q = h Wq (2560 -> 28 x 128), k = h Wk, v = h Wv (2560
        -> 4 x 128); query head n reads key-value head n // 7; scores
        q k^T / sqrt(128); Wo (28 x 128 -> 2560).
        Where `sliding_window_layout[i]` is 0: key j visible to query i iff
        j <= i. Where it is 1: iff 0 <= i - j < `sliding_window_size` (4096
        keys, itself included: the published mask's `kv_idx > q_idx -
        sliding_window`). The mask is built HERE from the two positions, a
        block of queries against ALL keys: nothing is skipped.
        Where `rope_layout[i]` is 1: rotate-half rotary over all 128
        columns at theta 1.5e6 on q and k. Where it is 0: NO positional
        term at all.
    Experts. l = h Wr (2560 -> 64): the ROUTER reads the block's input as
        the attention does, not the experts' input. I = the 6 largest of l;
        w_e = softmax over those six logits (`moe_primary_router_apply_
        softmax`, `norm_topk_prob`): AS PUBLISHED, where the program takes
        a softmax over all 64 and renormalises over the chosen. Output =
        sum_{e in I, e HELD} w_e W2_e (relu(W1_e y) * W3_e y) at 768, y =
        N2(x): ReGLU, computed as a loop over the held experts on every
        token. No shared expert, no selection bias, no scaling factor.

The published config fixes every size. It is silent on what the
configuration file's `assumed` states, numbered there as here:
  (1) the experts are ReGLU (`described_as`: "sparse ReGLU");
  (2) the router reads N1(x) (`described_as`: "router placed before
      attention");
  (3) no secondary experts: `config` has primary experts only;
  (4) no attention bias, no norm over a head;
  (5) no auxiliary or balance loss;
  (6) initialisers as `lfm2-24b-a2b`'s (normal 0.02, outputs into the
      residual stream 0.02 / sqrt(2 x layers as run), norms 1), BUT the
      embedding, drawn at unit variance: at 0.02 what a layer's attention
      averages over a sequence outweighs what a token brings, the stream
      is 89-93 % what all tokens of a sequence share by layer 2, and the
      router, which reads it, sends every token of a sequence to the same
      few experts (rows on the 8 held experts 304-25,331 a layer where
      12,288 are expected, another draw every sequence);
  (7) AdamW's weight decay covers every trained leaf: the optimizer's, and
      nothing this file computes;
  (8) nothing stands in for the 7 absent chips.

`held` is the contiguous range of experts the share holds (`expert_offset`,
`num_experts_held`); with all of them it is the published layer. The
vocabulary is the rows held, padded to a multiple of 128 rows as the
program pads it (18,992 -> 19,072): the padded logits are left out of the
loss, so the padding changes no result and its gradients are zero. Plain
`jax.numpy`, float32, every contraction at `Precision.HIGHEST`. Nothing is
imported from `oobleck_tpu`; the modes of arithmetic (`highest`,
`bfloat16`, `fp8`) are `reference/gpt.py`'s and apply to every contraction.

Three things are here for size and change no value, so that 16384
positions fit beside the engine: attention runs over blocks of `Q_BLOCK`
queries of the seven query heads one key-value head serves (7 x 512 x
16384 float32 scores = 235 MB a block; keys and values are read at their
own four heads and never repeated), each a `jax.checkpoint`; each layer and each held expert's term is
a `jax.checkpoint`; and the loss runs the head over blocks of `LOSS_BLOCK`
positions (2048 x 19072 float32 logits = 156 MB a block where the whole
sequence's are 1.25 GB, several times over in the gradient), each a
`jax.checkpoint` too. (In `fp8` mode a contraction's one scale is then a
block's, not the whole tensor's.)

Departures, as `reference/lfm2.py`: `forward` can be handed, per layer,
the expert indices to use (`forced`); what this file would have selected
is returned beside it (`own`), and `mismatch_share` counts the (token,
layer) pairs whose top-k SET differs. And `ignore_window`: every layer
full causal, the window's mask left out, for the control that shows a
program which skipped the mask could not pass.
"""

from __future__ import annotations

import functools
from dataclasses import dataclass

import jax
import jax.numpy as jnp
from jax import lax

from benchmarks.reference.gpt import _contract
from benchmarks.reference.lfm2 import mismatch_share  # noqa: F401

FULL, SWA = "full_attn", "swa_attn"
NEG_INF = -1e30
Q_BLOCK = 512
LOSS_BLOCK = 2048
EMBEDDING_STD = 1.0                    # `assumed` (6)


@dataclass(frozen=True)
class RefConfig:
    vocab_size: int                    # the rows of the vocabulary held
    hidden_size: int
    num_layers: int                    # as run
    num_heads: int
    num_kv_heads: int
    head_dim: int
    sliding_window_size: int
    sliding_window_layout: tuple[int, ...]
    rope_layout: tuple[int, ...]
    rope_theta: float
    moe_intermediate_size: int
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

    def kind(self, block: int) -> str:
        return SWA if self.sliding_window_layout[block] else FULL

    @classmethod
    def from_config(cls, config: dict) -> "RefConfig":
        """From a file under benchmarks/configs/: the sizes as they are
        run, under the published keys."""
        return cls(
            vocab_size=config["vocab_rows_held"],
            hidden_size=config["hidden_size"],
            num_layers=config["num_hidden_layers"],
            num_heads=config["num_attention_heads"],
            num_kv_heads=config["num_key_value_heads"],
            head_dim=config["head_dim"],
            sliding_window_size=config["sliding_window_size"],
            sliding_window_layout=tuple(config["sliding_window_layout"]),
            rope_layout=tuple(config["rope_layout"]),
            rope_theta=config["rope_theta"],
            moe_intermediate_size=config["moe_ffn_hidden_size"],
            num_experts=config["moe_num_primary_experts"],
            num_experts_per_tok=config["moe_num_active_primary_experts"],
            num_experts_held=config["num_experts_held"],
            expert_offset=config.get("expert_offset", 0),
            norm_eps=config["rms_norm_eps"])

    def block_params(self) -> dict[str, int]:
        """Parameters of one layer by part (every layer alike)."""
        e, h, kv, d = (self.hidden_size, self.num_heads, self.num_kv_heads,
                       self.head_dim)
        return {"attention": 2 * e * h * d + 2 * e * kv * d,
                "router": e * self.num_experts,
                "experts": self.num_experts_held * 3 * e
                * self.moe_intermediate_size,
                "norms": 2 * e}

    def num_params(self) -> int:
        """Over the rows of the vocabulary held; the rows the padding adds
        (no id reaches them, no gradient either) are not counted."""
        return (2 * self.vocab_size * self.hidden_size + self.hidden_size
                + self.num_layers * sum(self.block_params().values()))


# --------------------------------------------------------------------- #
# weights from a seed, in the program's layout                           #
# --------------------------------------------------------------------- #

def _block(key, c: RefConfig):
    ks = jax.random.split(key, 8)
    f32 = jnp.float32
    std = c.initializer_range
    res_std = std / (2 * c.num_layers) ** 0.5
    e, h, kv, d = c.hidden_size, c.num_heads, c.num_kv_heads, c.head_dim
    f, held = c.moe_intermediate_size, c.num_experts_held
    normal = lambda k, shape, s: jax.random.normal(k, shape, f32) * s
    return {
        "ln_op": {"scale": jnp.ones((e,), f32)},
        "ln_ff": {"scale": jnp.ones((e,), f32)},
        "attn": {"wq": normal(ks[0], (e, h, d), std),
                 "wk": normal(ks[1], (e, kv, d), std),
                 "wv": normal(ks[2], (e, kv, d), std),
                 "wo": normal(ks[3], (h, d, e), res_std)},
        "ff": {"router": normal(ks[4], (e, c.num_experts), std),
               "w1": normal(ks[5], (held, e, f), std),
               "w3": normal(ks[6], (held, e, f), std),
               "w2": normal(ks[7], (held, f, e), res_std)}}


def init_params(seed: int, c: RefConfig):
    """Seeded float32 weights, made on the device in ONE jitted call:
    {"embed": {wte}, "blocks": [per-layer trees], "head": {ln_f, w}}, each
    tree in the layout of `oobleck_tpu/models/smallthinker.py`'s layer (the
    vocabulary padded as the program pads it)."""

    @jax.jit
    def make(key):
        k_e, k_b, k_h = jax.random.split(key, 3)
        e, v = c.hidden_size, c.padded_vocab_size
        keys = jax.random.split(k_b, c.num_layers)
        return {
            "embed": {"wte": jax.random.normal(k_e, (v, e), jnp.float32)
                      * EMBEDDING_STD},
            "blocks": [_block(keys[i], c) for i in range(c.num_layers)],
            "head": {"ln_f": {"scale": jnp.ones((e,), jnp.float32)},
                     "w": jax.random.normal(k_h, (e, v), jnp.float32)
                     * c.initializer_range},
        }

    seed = int(seed)
    key = jax.random.fold_in(jax.random.PRNGKey(seed & 0x7FFFFFFF), seed >> 31)
    return make(key)


# --------------------------------------------------------------------- #
# arithmetic                                                             #
# --------------------------------------------------------------------- #

def _norm(x, w, eps):
    return x * lax.rsqrt(jnp.mean(jnp.square(x), -1, keepdims=True) + eps) * w


def _rope(x, theta):
    """Rotate-half rotary embedding over the whole last dimension.
    x [..., S, D], positions 0..S-1."""
    d, s = x.shape[-1], x.shape[-2]
    inv = theta ** (-jnp.arange(0, d, 2, dtype=jnp.float32) / d)
    ang = jnp.arange(s, dtype=jnp.float32)[:, None] * inv[None, :]
    cos = jnp.concatenate([jnp.cos(ang), jnp.cos(ang)], -1)
    sin = jnp.concatenate([jnp.sin(ang), jnp.sin(ang)], -1)
    half = jnp.concatenate([-x[..., d // 2:], x[..., : d // 2]], -1)
    return x * cos + half * sin


def attend(q, k, v, mode: str, window: int | None, q_block: int = Q_BLOCK):
    """One sequence. q [H, S, D], k, v [KV, S, D] -> [H, S, D]: softmax
    attention under the mask `j <= i` (and `i - j < window` where there is
    one), query head n reading key-value head n // (H / KV); `q_block`
    queries of one key-value head's query heads at a time against ALL keys
    (the whole sequence where the block does not divide it)."""
    h, s, d = q.shape
    kv = k.shape[0]
    rep = h // kv
    bq = q_block if s % q_block == 0 else s
    nq = s // bq
    qg = q.reshape(kv, rep, nq, bq, d)

    @jax.checkpoint
    def block(b, g):
        i = b * bq + jnp.arange(bq)[:, None]               # query position
        j = jnp.arange(s)[None, :]                         # key position
        seen = j <= i
        if window is not None:
            seen = seen & (i - j < window)
        scores = _contract("hqd,kd->hqk", qg[g, :, b], k[g], mode) * d ** -0.5
        probs = jax.nn.softmax(jnp.where(seen, scores, NEG_INF), -1)
        return _contract("hqk,kd->hqd", probs, v[g], mode)

    # Query blocks outside, heads inside: a block's mask is then the inner
    # loop's constant, [bq, S], and never all blocks' at once.
    out = lax.map(lambda b: lax.map(lambda g: block(b, g), jnp.arange(kv)),
                  jnp.arange(nq))                          # [nq, kv, rep, bq, D]
    return out.transpose(1, 2, 0, 3, 4).reshape(h, s, d)


def _attention(p, h, c: RefConfig, block: int, mode: str,
               ignore_window: bool):
    q = _contract("bse,ehd->bhsd", h, p["wq"], mode)
    k = _contract("bse,ehd->bhsd", h, p["wk"], mode)
    v = _contract("bse,ehd->bhsd", h, p["wv"], mode)
    if c.rope_layout[block]:
        q, k = _rope(q, c.rope_theta), _rope(k, c.rope_theta)
    window = (c.sliding_window_size
              if c.sliding_window_layout[block] and not ignore_window else None)
    a = jnp.stack([attend(q[i], k[i], v[i], mode, window)
                   for i in range(q.shape[0])])            # [B, H, S, D]
    return _contract("bhsd,hde->bse", a, p["wo"], mode)


def _experts(p, r, y, c: RefConfig, mode: str, forced):
    """r [B, S, E] what the router reads, y [B, S, E] what the experts
    read -> (held experts' part [B, S, E], own choice [B, S, k]). `forced`
    [B, S, k] replaces the selection; the weights are then the softmax
    over ITS six logits."""
    logits = _contract("bse,en->bsn", r, p["router"], mode)
    _, own = lax.top_k(lax.stop_gradient(logits), c.num_experts_per_tok)
    chosen = own if forced is None else forced
    w_chosen = jax.nn.softmax(
        jnp.take_along_axis(logits, chosen, axis=-1), -1)  # [B, S, k]

    @jax.checkpoint
    def reglu(w1, w3, w2, y):
        hidden = (jax.nn.relu(_contract("bse,ef->bsf", y, w1, mode))
                  * _contract("bse,ef->bsf", y, w3, mode))
        return _contract("bsf,fe->bse", hidden, w2, mode)

    out = jnp.zeros_like(y)
    for held in range(c.num_experts_held):
        w = jnp.sum(jnp.where(chosen == c.expert_offset + held, w_chosen, 0.0),
                    -1, keepdims=True)
        out = out + w * reglu(p["w1"][held], p["w3"][held], p["w2"][held], y)
    return out, own


def _block_forward(p, x, c: RefConfig, block: int, mode: str, forced,
                   ignore_window: bool):
    h = _norm(x, p["ln_op"]["scale"], c.norm_eps)
    x = x + _attention(p["attn"], h, c, block, mode, ignore_window)
    y, own = _experts(p["ff"], h, _norm(x, p["ln_ff"]["scale"], c.norm_eps),
                      c, mode, forced)
    return x + y, own


def _hidden(params, tokens, c: RefConfig, mode: str, forced,
            ignore_window: bool):
    """tokens [B, S] -> (what the head reads [B, S, E], own choices)."""
    x = params["embed"]["wte"][tokens]
    own = []
    for block, p in enumerate(params["blocks"]):
        x, chose = jax.checkpoint(functools.partial(
            _block_forward, c=c, block=block, mode=mode,
            ignore_window=ignore_window)
        )(p, x, forced=None if forced is None else forced[block])
        own.append(chose)
    return _norm(x, params["head"]["ln_f"]["scale"], c.norm_eps), own


def forward(params, tokens, c: RefConfig, mode: str = "highest",
            forced=None, ignore_window: bool = False):
    """tokens [B, S] -> (logits [B, S, vocab rows held] float32, own),
    `own` the experts this file would choose in every layer, a list of
    [B, S, k]; `forced`, a list like it, replaces the selection."""
    x, own = _hidden(params, tokens, c, mode, forced, ignore_window)
    logits = _contract("bse,ev->bsv", x, params["head"]["w"], mode)
    return logits[..., :c.vocab_size], own


def loss(params, tokens, c: RefConfig, mode: str = "highest", forced=None,
         ignore_window: bool = False):
    """(mean next-token cross entropy, own choices): position t's logits
    against token t + 1, the last position left out; the head over blocks
    of `LOSS_BLOCK` positions (the whole sequence where that does not
    divide it)."""
    x, own = _hidden(params, tokens, c, mode, forced, ignore_window)
    b, s, e = x.shape
    block = LOSS_BLOCK if s % LOSS_BLOCK == 0 else s
    targets = jnp.pad(tokens[:, 1:], ((0, 0), (0, 1)))
    counted = (jnp.arange(s) < s - 1).astype(jnp.float32)
    w = params["head"]["w"]

    @jax.checkpoint
    def block_nll(x_b, targets_b, counted_b):
        logits = _contract("bse,ev->bsv", x_b, w, mode)[..., :c.vocab_size]
        gold = jnp.take_along_axis(logits, targets_b[..., None], -1)[..., 0]
        return jnp.sum((jax.nn.logsumexp(logits, -1) - gold) * counted_b)

    by_block = lambda t: jnp.moveaxis(
        t.reshape(t.shape[0], s // block, block, *t.shape[2:]), 1, 0)
    total = jnp.sum(lax.map(
        lambda args: block_nll(*args),
        (by_block(x), by_block(targets),
         by_block(jnp.broadcast_to(counted, (b, s))))))
    return total / (b * (s - 1)), own


def loss_and_grads(params, tokens, c: RefConfig, mode: str = "highest",
                   forced=None, ignore_window: bool = False):
    """((loss, own choices), gradients of every parameter)."""
    return jax.value_and_grad(
        functools.partial(loss, c=c, mode=mode, forced=forced,
                          ignore_window=ignore_window),
        has_aux=True)(params, tokens)
