"""Ouro looped language model (`model_type: ouro`, Ouro-2.6B; Zhu et al.
2025, "Scaling Latent Reasoning via Looped Language Models",
arXiv:2510.25741) as an explicit layer list whose blocks a microbatch goes
through `num_passes` times over ONE set of weights.

With `N(x; w)` the plain RMSNorm, `R = num_passes`, `L = num_layers`:

    u = wte[tokens]
    for t = 1 .. R:                              # the same blocks every t
        for b = 0 .. L-1:
            u = u + N(Attn_b(N(u; n1_b)); n2_b)  # a norm on BOTH sides of
            u = u + N(FF_b(N(u; n3_b)); n4_b)    # a branch: a sandwich
        x_t = N(u; n_f);  u = x_t                # the final norm closes
        g_t = x_t . w_g + b_g                    # EVERY pass; an exit gate
        z_t = x_t W_head                         # and an exit, no 2nd norm

    Attn   `num_heads` query and `num_kv_heads` key-value heads of
           `head_dim`, no bias, rotate-half rotary over the whole head at
           `rope_theta` on q and k, causal softmax(q k^T / sqrt(d)) v, W_o.
    FF     SwiGLU, `W2 (silu(W1 h) * W3 h)`, no bias.

    l_t[n]  = -log softmax(z_t[n])[token[n+1]]
    lam_t   = sigmoid(g_t)
    p_t     = lam_t prod_{j<t} (1 - lam_j)  (t < R);  p_R = prod_{j<R} (1 - lam_j)
    loss    = mean_n [ sum_t p_t[n] l_t[n] - beta H(p[n]) ]

the paper's stage-I objective: the expected task loss under the learned
exit distribution, less `exit_entropy_weight` (beta) times its entropy.
`early_exit_threshold` of the published config is a decoding rule and has
no field here.

THE LAYER LIST is `[embed, block_0 .. block_{L-1}, head]`, as every family
of `models/routed.py`, and `repeated_layers` says the blocks repeat
(`models/base.py`: the contract). THE PASS'S CLOSE (the final norm, the
state kept for the pass's exit, the gate's logit: `n_f`, `w_g`, `b_g`,
`hidden_size + hidden_size + 1` parameters) lives WITH BLOCK L-1, under
`close` in that block's parameters, and runs at the end of that block's
application: the repeated range is then the blocks and nothing else, every
tree of `[embed, *blocks, head]` the tools around the model hold keeps its
shape, and a cut of the list anywhere leaves the close on the stage that
ends a pass. The head holds `W_head` alone.

THE CARRY has one tree and one set of shapes from the embedding to the
head, whichever pass and whichever block: `{"h": [B, S, E], "exits":
[R, B, S, E], "gates": [R, B, S] float32}`. A close pushes its pass's
normed state and gate logit in at the END of `exits` and `gates` and drops
the oldest, so after R closes they hold passes 1 .. R in order and no layer
needs to be told which pass it is in: a stage's R visits are one program,
and where one stage holds all the blocks the R passes are the R trips of
one loop inside that program (`execution/pipeline.py`: a `lax.scan` over
the blocks, this carry the loop's, the weights closed over).
The head takes the exits ONE AFTER ANOTHER, each under a checkpoint of its
own (one exit's float32 logits alive at a time, forward and backward), and
hands `loss_from_logits` the R per-position cross-entropies and the gate
logits, never a logit array; `accuracy_from_logits` reads the LAST exit.
"""

from __future__ import annotations

from dataclasses import dataclass, replace
from typing import Any

import jax
import jax.numpy as jnp

from oobleck_tpu.models.base import layer_walk
from oobleck_tpu.models.gpt import NEG_INF
from oobleck_tpu.models.routed import HeldShare, RoutedShareModel, rotate_half
from oobleck_tpu.ops.attention import causal_attention


@dataclass(frozen=True)
class OuroConfig(HeldShare):
    """Defaults: Ouro-2.6B as published (`total_ut_steps` is
    `num_passes`)."""

    vocab_size: int = 49152
    vocab_rows_held: int | None = None           # None: all of them
    max_position_embeddings: int = 65536
    hidden_size: int = 2048
    num_layers: int = 48
    num_passes: int = 4
    num_heads: int = 16
    num_kv_heads: int = 16
    head_dim: int = 128
    intermediate_size: int = 5632
    rope_theta: float = 1e6
    norm_eps: float = 1e-6
    exit_entropy_weight: float = 0.1
    initializer_range: float = 0.02
    dtype: Any = jnp.bfloat16
    param_dtype: Any = jnp.float32
    attention_impl: str = "auto"
    remat: bool = True
    vocab_pad_multiple: int = 128
    # `HeldShare`'s: this family routes nothing.
    num_experts: int = 0
    num_experts_held: int | None = None
    expert_offset: int = 0

    def override(self, **kwargs) -> "OuroConfig":
        fields = OuroConfig.__dataclass_fields__
        unknown = [k for k in kwargs if k not in fields]
        if unknown:
            raise ValueError(f"unknown model_args {unknown}")
        new = replace(self, **kwargs)
        if new.num_passes < 1 or new.num_layers < 1:
            raise ValueError(
                f"{new.num_passes} passes over {new.num_layers} blocks")
        if new.num_heads % new.num_kv_heads or new.head_dim % 2:
            raise ValueError(
                f"query {new.num_heads} / key-value {new.num_kv_heads} "
                f"heads of {new.head_dim}")
        new.check_share()
        return new


class OuroModel(RoutedShareModel):
    """Layer-list Ouro decoder; generic stage path only."""

    def __init__(self, config: OuroConfig):
        super().__init__(config)
        # The layer-list contract (`models/base.py`): what repeats.
        self.repeated_layers = range(1, config.num_layers + 1)
        self.num_passes = config.num_passes

    def is_routed(self, block: int) -> bool:
        return False

    def load_layers(self, num_tokens: int) -> dict:
        return {}

    def layer_name(self, index: int) -> str:
        """The block that closes a pass has a name of its own: the profiler
        times the first of each prefix and reuses it for the rest."""
        if index == self.config.num_layers:
            return f"close_{index - 1}"
        return super().layer_name(index)

    def applied_params(self) -> int:
        """Matrix parameters a token is multiplied through (`models/base.
        applied_param_count`): every block's, and the head's, once a pass.
        Norms, the gate and the embedding's lookup are not products."""
        c = self.config
        attn = (2 * c.num_heads + 2 * c.num_kv_heads) * c.head_dim
        block = c.hidden_size * (attn + 3 * c.intermediate_size)
        return c.num_passes * (
            c.num_layers * block + c.hidden_size * c.padded_vocab_size)

    # ---- init ----

    def _init_head(self, rng):
        c = self.config
        return {"w": jax.random.normal(
            rng, (c.hidden_size, c.padded_vocab_size), c.param_dtype
        ) * c.initializer_range}

    def _init_block(self, rng, block: int):
        c = self.config
        ks = jax.random.split(rng, 8)
        pd, std = c.param_dtype, c.initializer_range
        res_std = std / (2 * c.num_layers) ** 0.5
        e, h, kv, d = c.hidden_size, c.num_heads, c.num_kv_heads, c.head_dim
        f = c.intermediate_size
        normal = lambda k, shape, s: jax.random.normal(k, shape, pd) * s
        ln = lambda: {"scale": jnp.ones((e,), pd)}
        p = {"ln_op": ln(), "ln_op_out": ln(),
             "ln_ff": ln(), "ln_ff_out": ln(),
             "attn": {"wq": normal(ks[0], (e, h, d), std),
                      "wk": normal(ks[1], (e, kv, d), std),
                      "wv": normal(ks[2], (e, kv, d), std),
                      "wo": normal(ks[3], (h, d, e), res_std)},
             "ff": {"w1": normal(ks[4], (e, f), std),
                    "w3": normal(ks[5], (e, f), std),
                    "w2": normal(ks[6], (f, e), res_std)}}
        if block == c.num_layers - 1:
            p["close"] = {"ln_f": ln(), "w_g": normal(ks[7], (e,), std),
                          "b_g": jnp.zeros((), pd)}
        return p

    # ---- forward ----

    @jax.named_scope("embed")
    def embed(self, p, tokens):
        """The carry, its exits and gates still empty."""
        c = self.config
        h = p["wte"][tokens].astype(c.dtype)
        return {"h": h,
                "exits": jnp.zeros((c.num_passes, *h.shape), c.dtype),
                "gates": jnp.zeros((c.num_passes, *h.shape[:2]), jnp.float32)}

    def branch_out(self, branch: str, p, out):
        return self.norm(out, p[f"ln_{branch}_out"]["scale"])

    @jax.named_scope("full_attn")
    def operator_out(self, block: int, p, u):
        c = self.config
        dt = c.dtype
        p = p["attn"]
        q = jnp.einsum("bse,ehd->bhsd", u, p["wq"].astype(dt))
        k = jnp.einsum("bse,ehd->bhsd", u, p["wk"].astype(dt))
        v = jnp.einsum("bse,ehd->bhsd", u, p["wv"].astype(dt))
        q = rotate_half(q, c.rope_theta)
        k = rotate_half(k, c.rope_theta)
        rep = c.num_heads // c.num_kv_heads
        if rep > 1:
            k = jnp.repeat(k, rep, axis=1)
            v = jnp.repeat(v, rep, axis=1)
        attn = causal_attention(q, k, v, impl=c.attention_impl)
        return jnp.einsum("bhsd,hde->bse", attn, p["wo"].astype(dt))

    def apply_block(self, block: int, p, carry, **_):
        with jax.named_scope("loop_blocks"):
            x = super().apply_block(block, p, carry["h"])
        if block < self.config.num_layers - 1:
            return {**carry, "h": x}
        return self.close_pass(p["close"], carry, x)

    @jax.named_scope("exit_heads")
    def close_pass(self, p, carry, x):
        """The end of a pass: the final norm, whose output is the pass's
        exit state AND what the next pass reads, and the exit gate's
        logit; both pushed in at the end of the carry's `exits` / `gates`."""
        x = self.norm(x, p["ln_f"]["scale"])
        gate = jnp.einsum("bse,e->bs", x, p["w_g"].astype(x.dtype),
                          preferred_element_type=jnp.float32
                          ) + p["b_g"].astype(jnp.float32)
        push = lambda held, new: jnp.concatenate([held[1:], new[None]])
        return {"h": x, "exits": push(carry["exits"], x),
                "gates": push(carry["gates"], gate)}

    def apply_layer(self, index: int, params, carry, batch, **kw):
        if index == self.num_pipeline_layers - 1:
            return self.head(params, carry, batch["input_ids"])
        return super().apply_layer(index, params, carry, batch, **kw)

    @jax.named_scope("exit_heads")
    def head(self, p, carry, tokens):
        """What the loss needs of the R exits: `ce` [R, B, S-1], each
        exit's cross-entropy of token n+1 at position n; `gates` [R, B,
        S-1], the gate logits there; `hits` [B, S-1], where the LAST
        exit's argmax is the token. One exit after another, each under a
        checkpoint: its float32 logits are made again in the backward and
        no two exits' logits are alive together."""
        c = self.config
        w = p["w"].astype(c.dtype)
        # Position S-1 predicts nothing: it gets token 0 and is cut off.
        targets = jnp.concatenate(
            [tokens[:, 1:], jnp.zeros_like(tokens[:, :1])], axis=1)

        def exit_logits(x, w):
            logits = (x @ w).astype(jnp.float32)
            if logits.shape[-1] > c.data_vocab_size:
                held = jnp.arange(logits.shape[-1]) < c.data_vocab_size
                logits = jnp.where(held, logits, NEG_INF)
            return logits

        @jax.checkpoint
        def exit_ce(x, w):
            logits = exit_logits(x, w)
            # The target's logit as a masked sum, not a gather
            # (`models/gpt.cross_entropy_loss`).
            hit = jnp.arange(logits.shape[-1]) == targets[..., None]
            gold = jnp.sum(jnp.where(hit, logits, 0.0), axis=-1)
            return jax.nn.logsumexp(logits, axis=-1) - gold

        ce = jnp.stack([exit_ce(carry["exits"][t], w)
                        for t in range(c.num_passes)])
        last = jnp.argmax(exit_logits(carry["exits"][-1], w), axis=-1)
        return {"ce": ce[..., :-1], "gates": carry["gates"][..., :-1],
                "hits": (last == targets)[:, :-1].astype(jnp.float32)}

    @jax.named_scope("exit_heads")
    def loss_from_logits(self, out, batch):
        """`out`: the head's. The exits' cross-entropies weighted by the
        exit distribution the gates give, less beta times its entropy."""
        log_p = exit_log_distribution(out["gates"])
        p = jnp.exp(log_p)
        per_position = jnp.sum(p * out["ce"], axis=0) + (
            self.config.exit_entropy_weight * jnp.sum(p * log_p, axis=0))
        return jnp.mean(per_position)

    def accuracy_from_logits(self, out, batch):
        return jnp.sum(out["hits"]), jnp.float32(out["hits"].size)

    # Forward for one device: chain the layers as the pipeline walks them.
    def forward(self, params_list, tokens):
        """The head's output (`head`), not logits."""
        batch = {"input_ids": tokens}
        carry = None
        for li in layer_walk(self):
            carry = self.apply_layer(li, params_list[li], carry, batch)
        return carry


def exit_log_distribution(gates: jax.Array) -> jax.Array:
    """log p_t [R, ...] from the gate logits g_t [R, ...]: p_t = sigmoid(g_t)
    prod_{j<t} (1 - sigmoid(g_j)) for t < R, and the last pass takes what is
    left (its own gate is not read). Sums to 1 over t."""
    log_stay = jax.nn.log_sigmoid(-gates)
    before = jnp.cumsum(log_stay, axis=0) - log_stay     # sum over j < t
    return jnp.concatenate(
        [(jax.nn.log_sigmoid(gates) + before)[:-1], before[-1:]])
