"""T5 encoder-decoder as an explicit layer list.

Capability match for the reference's t5 path (AutoModelForSeq2SeqLM + fx
split at encoder and decoder block boundaries, /root/reference/oobleck/
module/model.py:21-33, sharding.py:23-28).

Layer list (pipeline units):
    [embed, enc_0 .. enc_{Le-1}, bridge, dec_0 .. dec_{Ld-1}, head]
The `bridge` finalizes the encoder (final norm) and embeds the decoder
inputs; decoder stages carry (enc_out, y) so cross-attention needs no
side-channel — the pair flows through stage-to-stage transfers like any
activation.

Architecture: T5.1.1 style — RMS-ish T5 layer norm (no mean subtraction, no
bias), gated-GELU FF, no biases, relative position biases. Deviation from HF:
each block owns its relative-bias table instead of sharing layer 0's, keeping
layers self-contained for pipeline splitting (a few extra KB per layer).

Objective: teacher-forced seq2seq cross-entropy (decoder inputs = targets
shifted right with pad start).
"""

from __future__ import annotations

from dataclasses import dataclass, replace
from typing import Any

import jax
import jax.numpy as jnp
import numpy as np

from oobleck_tpu.models.base import stack_layer_params
from oobleck_tpu.ops import attention, checkpoint_layer

NEG_INF = -1e9


@dataclass(frozen=True)
class T5Config:
    vocab_size: int = 32128
    d_model: int = 768
    num_layers: int = 12            # encoder blocks
    num_decoder_layers: int = 12
    num_heads: int = 12
    d_ff: int | None = None
    rel_buckets: int = 32
    rel_max_distance: int = 128
    layer_norm_epsilon: float = 1e-6
    initializer_range: float = 0.02
    pad_token_id: int = 0
    dtype: Any = jnp.bfloat16
    param_dtype: Any = jnp.float32
    remat: bool = True

    @property
    def ffn_dim(self) -> int:
        return self.d_ff or 4 * self.d_model

    @property
    def head_dim(self) -> int:
        return self.d_model // self.num_heads

    def override(self, **kwargs) -> "T5Config":
        unknown = [k for k in kwargs if k not in T5Config.__dataclass_fields__]
        if unknown:
            raise ValueError(f"unknown model_args {unknown}")
        return replace(self, **kwargs)


def _t5_norm(x, scale, eps):
    dtype = x.dtype
    x = x.astype(jnp.float32)
    var = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
    return (x * jax.lax.rsqrt(var + eps) * scale).astype(dtype)


def _rel_bucket(rel_pos, bidirectional: bool, num_buckets: int, max_dist: int):
    """T5 relative-position bucketing (log-spaced beyond half range)."""
    ret = jnp.zeros_like(rel_pos)
    n = -rel_pos
    if bidirectional:
        num_buckets //= 2
        ret = ret + (n < 0).astype(jnp.int32) * num_buckets
        n = jnp.abs(n)
    else:
        n = jnp.maximum(n, 0)
    max_exact = num_buckets // 2
    is_small = n < max_exact
    log_ratio = jnp.log(n.astype(jnp.float32) / max_exact + 1e-6) / np.log(
        max_dist / max_exact
    )
    large = max_exact + (log_ratio * (num_buckets - max_exact)).astype(jnp.int32)
    large = jnp.minimum(large, num_buckets - 1)
    return ret + jnp.where(is_small, n, large)


def _rel_bias(table: jax.Array, q_len: int, k_len: int, bidirectional: bool,
              num_buckets: int, max_dist: int) -> jax.Array:
    """[H, q, k] additive attention bias from a [buckets, H] table."""
    ctx = jnp.arange(q_len)[:, None]
    mem = jnp.arange(k_len)[None, :]
    buckets = _rel_bucket(mem - ctx, bidirectional, num_buckets, max_dist)
    return table[buckets].transpose(2, 0, 1)


class T5Model:
    # Engine contract: seq2seq batches (input_ids / decoder_input_ids /
    # labels) drive the MPMD pipeline generically; the bridge layer consumes
    # the batch mid-pipeline, so batch_layers lists it for stage placement.
    data_kind = "seq2seq"

    def __init__(self, config: T5Config):
        self.config = config

    @property
    def batch_layers(self) -> set[int]:
        """Layers that read `batch` (beyond the default first/last): the
        bridge starts the decoder stream from decoder_input_ids."""
        return {0, self.config.num_layers + 1, self.num_pipeline_layers - 1}

    # ---- layer list ----

    @property
    def num_pipeline_layers(self) -> int:
        c = self.config
        return 1 + c.num_layers + 1 + c.num_decoder_layers + 1

    def layer_name(self, index: int) -> str:
        c = self.config
        if index == 0:
            return "embed"
        if index <= c.num_layers:
            return f"enc_{index - 1}"
        if index == c.num_layers + 1:
            return "bridge"
        if index < self.num_pipeline_layers - 1:
            return f"dec_{index - c.num_layers - 2}"
        return "head"

    def init_layer(self, rng, index):
        # Same key derivation as init_params so the layer-list and fused
        # views of one seed produce identical weights.
        name = self.layer_name(index)
        ks = jax.random.split(rng, 5)
        c = self.config
        if name == "embed":
            return self._init_embed(ks[0])
        if name == "bridge":
            return self._init_bridge(ks[2])
        if name == "head":
            return self._init_head(ks[4])
        if name.startswith("enc_"):
            return self._init_block(jax.random.fold_in(ks[1], index), cross=False)
        dec_i = index - c.num_layers - 2
        return self._init_block(jax.random.fold_in(ks[3], dec_i + 1), cross=True)

    def apply_layer(self, index, params, carry, batch, ctx=None):
        name = self.layer_name(index)
        if name == "embed":
            return self.embed(params, batch["input_ids"])
        if name.startswith("enc_"):
            return self.apply_encoder_block(params, carry)
        if name == "bridge":
            return self.bridge(params, carry, batch["decoder_input_ids"])
        if name.startswith("dec_"):
            return self.apply_decoder_block(params, carry)
        enc_out, y = carry
        return self.head(params, y)

    def sample_batch(self, batch_size: int, seq_len: int):
        c = self.config
        rng = jax.random.PRNGKey(0)
        inputs = jax.random.randint(rng, (batch_size, seq_len), 0,
                                    c.vocab_size, dtype=jnp.int32)
        targets = jax.random.randint(jax.random.fold_in(rng, 1),
                                     (batch_size, seq_len), 0, c.vocab_size,
                                     dtype=jnp.int32)
        return {
            "input_ids": inputs,
            "labels": targets,
            "decoder_input_ids": self.shift_right(targets),
        }

    def shift_right(self, targets: jax.Array) -> jax.Array:
        c = self.config
        start = jnp.full_like(targets[..., :1], c.pad_token_id)
        return jnp.concatenate([start, targets[..., :-1]], axis=-1)

    # ---- init ----

    def _init_embed(self, rng):
        c = self.config
        return {"wte": jax.random.normal(
            rng, (c.vocab_size, c.d_model), c.param_dtype) * c.initializer_range}

    def _init_bridge(self, rng):
        c = self.config
        return {
            "enc_norm": {"scale": jnp.ones((c.d_model,), c.param_dtype)},
            "wte_dec": jax.random.normal(
                rng, (c.vocab_size, c.d_model), c.param_dtype
            ) * c.initializer_range,
        }

    def _attn_params(self, rng):
        c = self.config
        ks = jax.random.split(rng, 3)
        std = c.initializer_range
        e, h, d = c.d_model, c.num_heads, c.head_dim
        return {
            "wqkv": jax.random.normal(ks[0], (e, 3, h, d), c.param_dtype) * std,
            "wo": jax.random.normal(ks[1], (h, d, e), c.param_dtype) * std,
            "rel": jax.random.normal(ks[2], (c.rel_buckets, h), c.param_dtype) * std,
        }

    def _init_block(self, rng, cross: bool):
        c = self.config
        ks = jax.random.split(rng, 5)
        std = c.initializer_range
        e, f = c.d_model, c.ffn_dim
        out = {
            "ln1": {"scale": jnp.ones((e,), c.param_dtype)},
            "attn": self._attn_params(ks[0]),
            "ln_ff": {"scale": jnp.ones((e,), c.param_dtype)},
            "mlp": {
                "wg": jax.random.normal(ks[1], (e, f), c.param_dtype) * std,
                "wu": jax.random.normal(ks[2], (e, f), c.param_dtype) * std,
                "wo": jax.random.normal(ks[3], (f, e), c.param_dtype) * std,
            },
        }
        if cross:
            h, d = c.num_heads, c.head_dim
            xk = jax.random.split(ks[4], 3)
            out["ln_x"] = {"scale": jnp.ones((e,), c.param_dtype)}
            # Split projections: q from the decoder stream, k/v from the
            # encoder stream — a fused wqkv would compute (and discard) the
            # other stream's projections. No relative bias in cross attention.
            out["xattn"] = {
                "wq": jax.random.normal(xk[0], (e, h, d), c.param_dtype) * std,
                "wkv": jax.random.normal(xk[1], (e, 2, h, d), c.param_dtype) * std,
                "wo": jax.random.normal(xk[2], (h, d, e), c.param_dtype) * std,
            }
        return out

    def _init_head(self, rng):
        c = self.config
        return {
            "ln_f": {"scale": jnp.ones((c.d_model,), c.param_dtype)},
            "w": jax.random.normal(rng, (c.d_model, c.vocab_size), c.param_dtype)
            * c.initializer_range,
        }

    def init_params(self, rng):
        ks = jax.random.split(rng, 5)
        c = self.config
        enc = [self._init_block(jax.random.fold_in(ks[1], i + 1), cross=False)
               for i in range(c.num_layers)]
        dec = [self._init_block(jax.random.fold_in(ks[3], i + 1), cross=True)
               for i in range(c.num_decoder_layers)]
        return {
            "embed": self._init_embed(ks[0]),
            "enc_blocks": stack_layer_params(enc),
            "bridge": self._init_bridge(ks[2]),
            "dec_blocks": stack_layer_params(dec),
            "head": self._init_head(ks[4]),
        }

    # ---- forward ----

    def embed(self, p, tokens):
        return p["wte"][tokens].astype(self.config.dtype)

    def _self_attn(self, p, x, causal: bool):
        c = self.config
        dt = c.dtype
        qkv = jnp.einsum("bse,ethd->tbhsd", x, p["wqkv"].astype(dt))
        s = x.shape[1]
        bias = _rel_bias(p["rel"].astype(jnp.float32), s, s,
                         bidirectional=not causal,
                         num_buckets=c.rel_buckets,
                         max_dist=c.rel_max_distance)
        out = attention._xla_causal_attention(
            qkv[0], qkv[1], qkv[2], bias=bias, causal=causal, scale=1.0)
        return jnp.einsum("bhsd,hde->bse", out, p["wo"].astype(dt))

    def _cross_attn(self, p, y, enc_out):
        dt = self.config.dtype
        q = jnp.einsum("bse,ehd->bhsd", y, p["wq"].astype(dt))
        kv = jnp.einsum("bse,ekhd->kbhsd", enc_out, p["wkv"].astype(dt))
        out = attention._xla_causal_attention(q, kv[0], kv[1], causal=False, scale=1.0)
        return jnp.einsum("bhsd,hde->bse", out, p["wo"].astype(dt))

    def _ff(self, p, x):
        dt = self.config.dtype
        g = jax.nn.gelu(x @ p["wg"].astype(dt)) * (x @ p["wu"].astype(dt))
        return g @ p["wo"].astype(dt)

    def apply_encoder_block(self, p, x):
        c = self.config
        h = _t5_norm(x, p["ln1"]["scale"], c.layer_norm_epsilon)
        x = x + self._self_attn(p["attn"], h, causal=False)
        h = _t5_norm(x, p["ln_ff"]["scale"], c.layer_norm_epsilon)
        return x + self._ff(p["mlp"], h)

    def bridge(self, p, enc_x, decoder_input_ids):
        c = self.config
        enc_out = _t5_norm(enc_x, p["enc_norm"]["scale"], c.layer_norm_epsilon)
        y = p["wte_dec"][decoder_input_ids].astype(c.dtype)
        return (enc_out, y)

    def apply_decoder_block(self, p, carry):
        c = self.config
        enc_out, y = carry
        h = _t5_norm(y, p["ln1"]["scale"], c.layer_norm_epsilon)
        y = y + self._self_attn(p["attn"], h, causal=True)
        h = _t5_norm(y, p["ln_x"]["scale"], c.layer_norm_epsilon)
        y = y + self._cross_attn(p["xattn"], h, enc_out)
        h = _t5_norm(y, p["ln_ff"]["scale"], c.layer_norm_epsilon)
        y = y + self._ff(p["mlp"], h)
        return (enc_out, y)

    def head(self, p, y):
        c = self.config
        y = _t5_norm(y, p["ln_f"]["scale"], c.layer_norm_epsilon)
        # T5 scales decoder output before the (tied-shape) projection.
        y = y * (c.d_model ** -0.5)
        return (y @ p["w"].astype(c.dtype)).astype(jnp.float32)

    def forward(self, params, input_ids, decoder_input_ids):
        c = self.config
        enc_block = self.apply_encoder_block
        dec_block = self.apply_decoder_block
        if c.remat:
            enc_block = checkpoint_layer(enc_block)
            dec_block = checkpoint_layer(dec_block)

        x = self.embed(params["embed"], input_ids)
        x, _ = jax.lax.scan(lambda x, bp: (enc_block(bp, x), None), x,
                            params["enc_blocks"])
        carry = self.bridge(params["bridge"], x, decoder_input_ids)
        carry, _ = jax.lax.scan(lambda cy, bp: (dec_block(bp, cy), None),
                                carry, params["dec_blocks"])
        _, y = carry
        return self.head(params["head"], y)

    def loss_from_logits(self, logits, batch):
        labels = batch["labels"]
        logits = logits.astype(jnp.float32)
        logz = jax.nn.logsumexp(logits, axis=-1)
        gold = jnp.take_along_axis(logits, labels[..., None], axis=-1)[..., 0]
        return jnp.mean(logz - gold)


    def accuracy_from_logits(self, logits, batch):
        from oobleck_tpu.models.base import argmax_accuracy

        return argmax_accuracy(logits, batch["labels"])

    def loss(self, params, batch):
        logits = self.forward(params, batch["input_ids"],
                              batch["decoder_input_ids"])
        return self.loss_from_logits(logits, batch)
