"""LFM2-MoE decoder (LiquidAI `lfm2_moe`, e.g. LFM2-24B-A2B) as an explicit
layer list whose layers DIFFER.

Every block is `h = x + Op(N(x)); y = h + FF(N(h))` with RMSNorm `N`:

  Op  "conv"            gated short convolution: [B | C | u] = W_in x,
                        z = causal depthwise conv (kernel `conv_L_cache`)
                        of B * u, Op = W_out (C * z). No activation.
      "full_attention"  grouped-query attention, RMSNorm over each head of
                        q and k, rotate-half RoPE, causal softmax.
  FF  dense SwiGLU      for layer index < `num_dense_layers`;
      routed experts    otherwise: sigmoid scores over ALL `num_experts`,
                        top-k of score + bias, weights from the scores
                        (`ops/moe.routed_experts`).

`layer_types` says which operator a layer has. The layer list is
[embed, block_0 .. block_{L-1}, head], as every family's, so the planner
profiles and the MPMD pipeline splits it unchanged; the model runs the
generic stage path (no manual-collective contract).

One chip's share of an expert-parallel deployment is a model of its own
here: `num_experts_held` experts from `expert_offset` (the router still
scores all `num_experts`; the layer gives the part its held experts give),
and the first `vocab_rows_held` rows of the vocabulary (embedding and
untied head; token ids, logits and the loss are over those rows, and the
engine draws its data from them: `data_vocab_size`). Nothing stands in for
the absent chips.

The expert bias is a leaf of the parameters that is never trained
(`frozen_param_names`): no gradient reaches it and the optimizer keeps no
state for it.
"""

from __future__ import annotations

from dataclasses import dataclass, replace
from typing import Any

import jax
import jax.numpy as jnp

from oobleck_tpu.models.gpt import cross_entropy_loss
from oobleck_tpu.models.llama import _rms_norm
from oobleck_tpu.ops.attention import causal_attention

CONV, ATTN = "conv", "full_attention"


def published_layer_types(num_layers: int) -> tuple[str, ...]:
    """conv, conv, attention, then (conv, conv, conv, attention) repeating:
    the published list (30 conv + 10 attention at 40 layers)."""
    return tuple(ATTN if i >= 2 and (i - 2) % 4 == 0 else CONV
                 for i in range(num_layers))


@dataclass(frozen=True)
class Lfm2Config:
    vocab_size: int = 65536
    vocab_rows_held: int | None = None           # None: all of them
    max_position_embeddings: int = 128000
    hidden_size: int = 2048
    num_layers: int = 40
    num_heads: int = 32
    num_kv_heads: int = 8
    intermediate_size: int = 11776
    moe_intermediate_size: int = 1536
    num_experts: int = 64
    num_experts_per_tok: int = 4
    num_dense_layers: int = 2
    layer_types: tuple[str, ...] | None = None   # None: the published list
    norm_topk_prob: bool = True
    use_expert_bias: bool = True
    routed_scaling_factor: float = 1.0
    conv_L_cache: int = 3
    norm_eps: float = 1e-5
    rope_theta: float = 1e6
    expert_offset: int = 0
    num_experts_held: int | None = None          # None: all of them
    initializer_range: float = 0.02
    expert_bias_range: float = 0.01
    dtype: Any = jnp.bfloat16
    param_dtype: Any = jnp.float32
    attention_impl: str = "auto"
    remat: bool = True
    vocab_pad_multiple: int = 128

    @property
    def data_vocab_size(self) -> int:
        """Rows of the vocabulary this model holds: what token ids range
        over (execution/engine.py draws its data from it)."""
        return (self.vocab_size if self.vocab_rows_held is None
                else self.vocab_rows_held)

    @property
    def padded_vocab_size(self) -> int:
        m = self.vocab_pad_multiple
        return (self.data_vocab_size + m - 1) // m * m

    @property
    def head_dim(self) -> int:
        assert self.hidden_size % self.num_heads == 0
        return self.hidden_size // self.num_heads

    @property
    def ffn_dim(self) -> int:
        return self.intermediate_size

    @property
    def experts_held(self) -> int:
        return (self.num_experts if self.num_experts_held is None
                else self.num_experts_held)

    @property
    def operators(self) -> tuple[str, ...]:
        return (published_layer_types(self.num_layers)
                if self.layer_types is None else self.layer_types)

    def override(self, **kwargs) -> "Lfm2Config":
        unknown = [k for k in kwargs if k not in Lfm2Config.__dataclass_fields__]
        if unknown:
            raise ValueError(f"unknown model_args {unknown}")
        if kwargs.get("layer_types") is not None:
            kwargs["layer_types"] = tuple(kwargs["layer_types"])
        new = replace(self, **kwargs)
        ops = new.operators
        if len(ops) != new.num_layers or set(ops) - {CONV, ATTN}:
            raise ValueError(
                f"layer_types must name {new.num_layers} operators out of "
                f"{CONV!r} / {ATTN!r}, got {ops}")
        if not 0 < new.data_vocab_size <= new.vocab_size:
            raise ValueError(
                f"vocab_rows_held {new.vocab_rows_held} of {new.vocab_size}")
        if new.expert_offset + new.experts_held > new.num_experts:
            raise ValueError(
                f"experts {new.expert_offset}..+{new.experts_held} of "
                f"{new.num_experts}")
        return new


def _rope_half(x: jax.Array, theta: float) -> jax.Array:
    """Rotate-half rotary embedding at positions 0..S-1. x: [B, H, S, D]."""
    d, s = x.shape[-1], x.shape[-2]
    freqs = 1.0 / (theta ** (jnp.arange(0, d, 2, dtype=jnp.float32) / d))
    angles = jnp.arange(s, dtype=jnp.float32)[:, None] * freqs[None, :]
    cos = jnp.concatenate([jnp.cos(angles)] * 2, axis=-1)       # [S, D]
    sin = jnp.concatenate([jnp.sin(angles)] * 2, axis=-1)
    x32 = x.astype(jnp.float32)
    x1, x2 = x32[..., : d // 2], x32[..., d // 2:]
    rotated = jnp.concatenate([-x2, x1], axis=-1)
    return (x32 * cos + rotated * sin).astype(x.dtype)


def short_conv(bu: jax.Array, taps: jax.Array) -> jax.Array:
    """Depthwise causal convolution over the sequence, as shifted
    multiply-adds: z_t = sum_j taps[j] * bu_{t-j}. bu [B, S, D], taps
    [L, D]."""
    s = bu.shape[1]
    z = bu * taps[0]
    for j in range(1, taps.shape[0]):
        shifted = jnp.pad(bu, ((0, 0), (j, 0), (0, 0)))[:, :s]
        z = z + shifted * taps[j]
    return z


class Lfm2Model:
    """Layer-list LFM2-MoE decoder; generic stage path only."""

    data_kind = "causal_lm"
    fused_supported = False
    # Leaves with these names take no gradient and no optimizer state
    # (parallel/train.py::make_optimizer).
    frozen_param_names = ("expert_bias",)

    def __init__(self, config: Lfm2Config):
        self.config = config

    # ---- layer list ----

    @property
    def num_pipeline_layers(self) -> int:
        return self.config.num_layers + 2

    def layer_name(self, index: int) -> str:
        if index == 0:
            return "embed"
        if index == self.num_pipeline_layers - 1:
            return "head"
        return f"block_{index - 1}"

    def operator(self, block: int) -> str:
        return self.config.operators[block]

    def is_routed(self, block: int) -> bool:
        return block >= self.config.num_dense_layers

    @property
    def routed_blocks(self) -> tuple[int, ...]:
        return tuple(b for b in range(self.config.num_layers)
                     if self.is_routed(b))

    def init_layer(self, rng: jax.Array, index: int):
        ks = jax.random.split(rng, 3)
        if index == 0:
            return self._init_embed(ks[0])
        if index == self.num_pipeline_layers - 1:
            return self._init_head(ks[2])
        return self._init_block(jax.random.fold_in(ks[1], index), index - 1)

    def apply_layer(self, index: int, params, carry, batch, ctx=None):
        if index == 0:
            return self.embed(params, batch["input_ids"])
        if index == self.num_pipeline_layers - 1:
            return self.head(params, carry)
        return self.apply_block(index - 1, params, carry)

    @jax.named_scope("lm_head")
    def loss_from_logits(self, logits, batch):
        return cross_entropy_loss(logits, batch["input_ids"],
                                  self.config.data_vocab_size)

    def sample_batch(self, batch_size: int, seq_len: int):
        tokens = jax.random.randint(
            jax.random.PRNGKey(0), (batch_size, seq_len), 0,
            self.config.data_vocab_size, dtype=jnp.int32)
        return {"input_ids": tokens}

    # ---- init ----

    def _init_embed(self, rng):
        c = self.config
        return {"wte": jax.random.normal(
            rng, (c.padded_vocab_size, c.hidden_size), c.param_dtype
        ) * c.initializer_range}

    def _init_head(self, rng):
        c = self.config
        return {
            "ln_f": {"scale": jnp.ones((c.hidden_size,), c.param_dtype)},
            "w": jax.random.normal(
                rng, (c.hidden_size, c.padded_vocab_size), c.param_dtype
            ) * c.initializer_range,
        }

    def _init_block(self, rng, block: int):
        c = self.config
        ks = jax.random.split(rng, 12)
        pd, std = c.param_dtype, c.initializer_range
        res_std = std / (2 * c.num_layers) ** 0.5
        e, h, kv, d = c.hidden_size, c.num_heads, c.num_kv_heads, c.head_dim
        normal = lambda k, shape, s: jax.random.normal(k, shape, pd) * s
        p = {"ln_op": {"scale": jnp.ones((e,), pd)},
             "ln_ff": {"scale": jnp.ones((e,), pd)}}
        if self.operator(block) == CONV:
            p["conv"] = {
                "w_in": normal(ks[0], (e, 3, e), std),
                "taps": normal(ks[1], (c.conv_L_cache, e),
                               c.conv_L_cache ** -0.5),
                "w_out": normal(ks[2], (e, e), res_std),
            }
        else:
            p["attn"] = {
                "wq": normal(ks[0], (e, h, d), std),
                "wk": normal(ks[1], (e, kv, d), std),
                "wv": normal(ks[2], (e, kv, d), std),
                "q_norm": jnp.ones((d,), pd),
                "k_norm": jnp.ones((d,), pd),
                "wo": normal(ks[3], (h, d, e), res_std),
            }
        if not self.is_routed(block):
            f = c.intermediate_size
            p["ff"] = {"w1": normal(ks[4], (e, f), std),
                       "w3": normal(ks[5], (e, f), std),
                       "w2": normal(ks[6], (f, e), res_std)}
        else:
            f, ne, held = c.moe_intermediate_size, c.num_experts, c.experts_held
            p["ff"] = {"router": normal(ks[7], (e, ne), std),
                       "w1": normal(ks[8], (held, e, f), std),
                       "w3": normal(ks[9], (held, e, f), std),
                       "w2": normal(ks[10], (held, f, e), res_std)}
            if c.use_expert_bias:
                # Seeded and not zero, so that selecting by score + bias
                # and weighting by score really differ; small beside the
                # scores' spread, as a bias that its (unpublished) update
                # rule has balanced the experts' loads with would be.
                p["ff"]["expert_bias"] = normal(
                    ks[11], (ne,), c.expert_bias_range)
        return p

    # ---- forward ----

    @jax.named_scope("embed")
    def embed(self, p, tokens):
        return p["wte"][tokens].astype(self.config.dtype)

    @jax.named_scope("conv")
    def conv_operator(self, p, h):
        dt = self.config.dtype
        bcu = jnp.einsum("bse,ekd->kbsd", h, p["w_in"].astype(dt))
        z = short_conv(bcu[0] * bcu[2], p["taps"].astype(dt))
        return (bcu[1] * z) @ p["w_out"].astype(dt)

    @jax.named_scope("attention")
    def attention_operator(self, p, h):
        c = self.config
        dt = c.dtype
        q = jnp.einsum("bse,ehd->bhsd", h, p["wq"].astype(dt))
        k = jnp.einsum("bse,ehd->bhsd", h, p["wk"].astype(dt))
        v = jnp.einsum("bse,ehd->bhsd", h, p["wv"].astype(dt))
        q = _rope_half(_rms_norm(q, p["q_norm"], c.norm_eps), c.rope_theta)
        k = _rope_half(_rms_norm(k, p["k_norm"], c.norm_eps), c.rope_theta)
        rep = c.num_heads // c.num_kv_heads
        if rep > 1:
            k = jnp.repeat(k, rep, axis=1)
            v = jnp.repeat(v, rep, axis=1)
        attn = causal_attention(q, k, v, impl=c.attention_impl)
        return jnp.einsum("bhsd,hde->bse", attn, p["wo"].astype(dt))

    @jax.named_scope("mlp")
    def feed_forward(self, block: int, p, h, *, forced_experts=None,
                     return_routing: bool = False):
        """Dense SwiGLU or the routed experts, by the block's index. With
        `return_routing` a routed block also returns its chosen experts
        [B, S, k]."""
        c = self.config
        dt = c.dtype
        if not self.is_routed(block):
            g = jax.nn.silu(h @ p["w1"].astype(dt)) * (h @ p["w3"].astype(dt))
            return g @ p["w2"].astype(dt)
        from oobleck_tpu.ops.moe import routed_experts

        b, s, e = h.shape
        out = routed_experts(
            h.reshape(b * s, e), p["router"], p.get("expert_bias"),
            p["w1"], p["w3"], p["w2"],
            num_experts=c.num_experts, top_k=c.num_experts_per_tok,
            expert_offset=c.expert_offset, norm_topk_prob=c.norm_topk_prob,
            routed_scaling_factor=c.routed_scaling_factor,
            forced_experts=(None if forced_experts is None
                            else forced_experts.reshape(b * s, -1)),
            return_routing=return_routing)
        if return_routing:
            y, experts = out
            return y.reshape(b, s, e), experts.reshape(b, s, -1)
        return out.reshape(b, s, e)

    def apply_block(self, block: int, p, x, *, forced_experts=None,
                    return_routing: bool = False):
        c = self.config
        h = _rms_norm(x, p["ln_op"]["scale"], c.norm_eps)
        if self.operator(block) == CONV:
            x = x + self.conv_operator(p["conv"], h)
        else:
            x = x + self.attention_operator(p["attn"], h)
        h = _rms_norm(x, p["ln_ff"]["scale"], c.norm_eps)
        out = self.feed_forward(block, p["ff"], h,
                                forced_experts=forced_experts,
                                return_routing=return_routing)
        if return_routing and self.is_routed(block):
            return x + out[0], out[1]
        return x + out

    @jax.named_scope("lm_head")
    def head(self, p, x):
        c = self.config
        x = _rms_norm(x, p["ln_f"]["scale"], c.norm_eps)
        return (x @ p["w"].astype(c.dtype)).astype(jnp.float32)

    # Forward for one device: chain the layers as the pipeline does.
    def forward(self, params_list, tokens, *, return_routing: bool = False):
        """Logits [B, S, padded vocab]; with `return_routing` also the
        experts every routed block chose, one [B, S, k] per block in
        `routed_blocks` order."""
        x = self.embed(params_list[0], tokens)
        routing = []
        for block in range(self.config.num_layers):
            p = params_list[block + 1]
            if return_routing and self.is_routed(block):
                x, experts = self.apply_block(block, p, x,
                                              return_routing=True)
                routing.append(experts)
            else:
                x = self.apply_block(block, p, x)
        logits = self.head(params_list[-1], x)
        return (logits, routing) if return_routing else logits

    def loss(self, params_list, batch):
        return self.loss_from_logits(
            self.forward(params_list, batch["input_ids"]), batch)


def routing_probe(model: Lfm2Model, params_list, tokens):
    """The experts every routed block chooses for `tokens` [B, S], read out
    on demand: one jitted forward chaining the model's own layers on the
    given per-layer parameters. Returns a list of host arrays [B, S, k] in
    `routed_blocks` order, and counts what it saw: the probed tokens and,
    per block, the (token, slot) pairs whose expert is held here.

    On demand and not every step: the pipeline's stage programs have no
    output beside the carry and the loss, so a training step cannot say
    where it routed."""
    import numpy as np

    from oobleck_tpu.obs import spans
    from oobleck_tpu.utils import metrics

    c = model.config
    reg = metrics.registry()
    pairs = reg.counter(
        "oobleck_moe_routed_pairs_total",
        "(token, slot) pairs a routing probe saw routed to experts held "
        "here, by routed block")
    probed = reg.counter(
        "oobleck_moe_probed_tokens_total",
        "Tokens a routing probe read the routing of")
    with spans.span("moe.routing_probe"):
        probe = _probe_program(model)
        routing = [np.asarray(r)  # oobleck: allow[OBL002] -- on-demand probe
                   for r in probe(tuple(params_list), tokens)]
    probed.inc(int(routing[0].shape[0] * routing[0].shape[1]) if routing
               else 0)
    for block, chosen in zip(model.routed_blocks, routing):
        local = chosen - c.expert_offset
        pairs.inc(int(((local >= 0) & (local < c.experts_held)).sum()),
                  layer=str(block))
    return routing


def _probe_program(model: Lfm2Model):
    fn = getattr(model, "_routing_probe_fn", None)
    if fn is None:
        def routing_probe_forward(params_list, tokens):
            return model.forward(list(params_list), tokens,
                                 return_routing=True)[1]

        fn = model._routing_probe_fn = jax.jit(routing_probe_forward)
    return fn
