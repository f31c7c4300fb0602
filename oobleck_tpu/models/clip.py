"""CLIP dual-encoder as an explicit layer list with contrastive loss.

Capability match for the reference's clip family (listed in its tested image
models, /root/reference/oobleck/module/model.py:21-33; like swin, the
reference's fx splitter has no clip branch — sharding.py:12-47 — so this
EXCEEDS the reference, which would assert on clip).

Layer list runs the two towers in sequence, so pipeline stages are still
contiguous layer ranges:
    [img_embed, img_block_0.., img_pool, txt_embed, txt_block_0.., head]
The image tower's pooled projection rides the carry through the text tower
as a (img_emb, txt_x) pair — the same mid-pipeline batch-consumer pattern
as T5's bridge (models/t5.py): `txt_embed` reads batch["input_ids"], so
batch_layers lists it for stage placement.

Objective: in-batch symmetric contrastive loss (logits = scale * img @ txt.T,
cross-entropy against the diagonal in both directions). With microbatching,
negatives are per-microbatch — the standard data-parallel CLIP behavior
without cross-device gather; documented, not hidden.

Architecture notes: ViT-style image tower (class token, pre-norm blocks),
causal text tower pooled at the final position, learned logit scale
(clamped at exp(4.6) ~ 100 like OpenAI CLIP).
"""

from __future__ import annotations

from dataclasses import dataclass, replace
from typing import Any

import jax
import jax.numpy as jnp

from oobleck_tpu.models.gpt import _layer_norm
from oobleck_tpu.ops import attention


@dataclass(frozen=True)
class CLIPConfig:
    # vision tower
    image_size: int = 224
    patch_size: int = 32
    num_channels: int = 3
    vision_hidden_size: int = 768
    vision_layers: int = 12
    vision_heads: int = 12
    # text tower
    vocab_size: int = 49408
    max_position_embeddings: int = 77
    text_hidden_size: int = 512
    text_layers: int = 12
    text_heads: int = 8
    # shared
    projection_dim: int = 512
    layer_norm_epsilon: float = 1e-5
    initializer_range: float = 0.02
    logit_scale_init: float = 2.6592  # ln(1/0.07), OpenAI CLIP default
    dtype: Any = jnp.bfloat16
    param_dtype: Any = jnp.float32
    remat: bool = True

    @property
    def num_patches(self) -> int:
        return (self.image_size // self.patch_size) ** 2

    def override(self, **kwargs) -> "CLIPConfig":
        unknown = [k for k in kwargs if k not in CLIPConfig.__dataclass_fields__]
        if unknown:
            raise ValueError(f"unknown model_args {unknown}")
        return replace(self, **kwargs)


def _init_tx_block(rng, e: int, h: int, std: float, param_dtype):
    f = 4 * e
    ks = jax.random.split(rng, 4)
    return {
        "ln1": {"scale": jnp.ones((e,), param_dtype),
                "bias": jnp.zeros((e,), param_dtype)},
        "attn": {
            "wqkv": jax.random.normal(ks[0], (e, 3, h, e // h), param_dtype) * std,
            "bqkv": jnp.zeros((3, h, e // h), param_dtype),
            "wo": jax.random.normal(ks[1], (h, e // h, e), param_dtype) * std,
            "bo": jnp.zeros((e,), param_dtype),
        },
        "ln2": {"scale": jnp.ones((e,), param_dtype),
                "bias": jnp.zeros((e,), param_dtype)},
        "mlp": {
            "wi": jax.random.normal(ks[2], (e, f), param_dtype) * std,
            "bi": jnp.zeros((f,), param_dtype),
            "wo": jax.random.normal(ks[3], (f, e), param_dtype) * std,
            "bo": jnp.zeros((e,), param_dtype),
        },
    }


def _apply_tx_block(p, x, *, causal: bool, eps: float, dtype):
    h = _layer_norm(x, p["ln1"]["scale"], p["ln1"]["bias"], eps)
    qkv = jnp.einsum("bse,ethd->tbhsd", h, p["attn"]["wqkv"].astype(dtype))
    qkv = qkv + p["attn"]["bqkv"].astype(dtype)[:, None, :, None, :]
    attn = attention._xla_causal_attention(qkv[0], qkv[1], qkv[2], causal=causal)
    out = jnp.einsum("bhsd,hde->bse", attn, p["attn"]["wo"].astype(dtype))
    x = x + out + p["attn"]["bo"].astype(dtype)
    h = _layer_norm(x, p["ln2"]["scale"], p["ln2"]["bias"], eps)
    h = jax.nn.gelu(h @ p["mlp"]["wi"].astype(dtype) + p["mlp"]["bi"].astype(dtype))
    return x + h @ p["mlp"]["wo"].astype(dtype) + p["mlp"]["bo"].astype(dtype)


class CLIPModel:
    data_kind = "contrastive"

    def __init__(self, config: CLIPConfig):
        self.config = config

    # ---- layer list ----

    @property
    def _txt_embed_index(self) -> int:
        return 1 + self.config.vision_layers + 1

    @property
    def batch_layers(self) -> set[int]:
        """img_embed reads pixel_values; txt_embed reads input_ids
        mid-pipeline; the head needs no batch (diagonal targets)."""
        return {0, self._txt_embed_index, self.num_pipeline_layers - 1}

    @property
    def num_pipeline_layers(self) -> int:
        c = self.config
        return 1 + c.vision_layers + 1 + 1 + c.text_layers + 1

    def layer_name(self, index: int) -> str:
        c = self.config
        if index == 0:
            return "img_embed"
        if index <= c.vision_layers:
            return f"img_block_{index - 1}"
        if index == c.vision_layers + 1:
            return "img_pool"
        if index == self._txt_embed_index:
            return "txt_embed"
        if index < self.num_pipeline_layers - 1:
            return f"txt_block_{index - self._txt_embed_index - 1}"
        return "head"

    def init_layer(self, rng, index):
        c = self.config
        name = self.layer_name(index)
        ks = jax.random.split(rng, 6)
        std = c.initializer_range
        if name == "img_embed":
            return self._init_img_embed(ks[0])
        if name.startswith("img_block"):
            return _init_tx_block(
                jax.random.fold_in(ks[1], index), c.vision_hidden_size,
                c.vision_heads, std, c.param_dtype)
        if name == "img_pool":
            return {
                "ln_post": {"scale": jnp.ones((c.vision_hidden_size,), c.param_dtype),
                            "bias": jnp.zeros((c.vision_hidden_size,), c.param_dtype)},
                "proj": jax.random.normal(
                    ks[2], (c.vision_hidden_size, c.projection_dim),
                    c.param_dtype) * std,
            }
        if name == "txt_embed":
            k1, k2 = jax.random.split(ks[3])
            return {
                "wte": jax.random.normal(
                    k1, (c.vocab_size, c.text_hidden_size), c.param_dtype) * std,
                "wpe": jax.random.normal(
                    k2, (c.max_position_embeddings, c.text_hidden_size),
                    c.param_dtype) * std,
            }
        if name.startswith("txt_block"):
            return _init_tx_block(
                jax.random.fold_in(ks[4], index), c.text_hidden_size,
                c.text_heads, std, c.param_dtype)
        return {
            "ln_final": {"scale": jnp.ones((c.text_hidden_size,), c.param_dtype),
                         "bias": jnp.zeros((c.text_hidden_size,), c.param_dtype)},
            "proj": jax.random.normal(
                ks[5], (c.text_hidden_size, c.projection_dim),
                c.param_dtype) * std,
            "logit_scale": jnp.asarray(c.logit_scale_init, c.param_dtype),
        }

    def apply_layer(self, index, params, carry, batch, ctx=None):
        c = self.config
        name = self.layer_name(index)
        eps = c.layer_norm_epsilon
        if name == "img_embed":
            return self.img_embed(params, batch["pixel_values"])
        if name.startswith("img_block"):
            return _apply_tx_block(params, carry, causal=False, eps=eps,
                                   dtype=c.dtype)
        if name == "img_pool":
            cls = _layer_norm(carry[:, 0], params["ln_post"]["scale"],
                              params["ln_post"]["bias"], eps)
            return cls @ params["proj"].astype(c.dtype)
        if name == "txt_embed":
            tokens = batch["input_ids"]
            x = (params["wte"][tokens]
                 + params["wpe"][: tokens.shape[-1]]).astype(c.dtype)
            return (carry, x)
        if name.startswith("txt_block"):
            img_emb, x = carry
            return (img_emb, _apply_tx_block(params, x, causal=True, eps=eps,
                                             dtype=c.dtype))
        img_emb, x = carry
        return self._similarity(params, img_emb, x)

    def _similarity(self, p, img_emb, txt_x):
        c = self.config
        x = _layer_norm(txt_x[:, -1], p["ln_final"]["scale"],
                        p["ln_final"]["bias"], c.layer_norm_epsilon)
        txt_emb = x @ p["proj"].astype(c.dtype)
        img = img_emb.astype(jnp.float32)
        txt = txt_emb.astype(jnp.float32)
        img = img / (jnp.linalg.norm(img, axis=-1, keepdims=True) + 1e-8)
        txt = txt / (jnp.linalg.norm(txt, axis=-1, keepdims=True) + 1e-8)
        scale = jnp.exp(jnp.minimum(p["logit_scale"].astype(jnp.float32), 4.6))
        return scale * img @ txt.T  # [B_img, B_txt]

    def loss_from_logits(self, logits, batch):
        """Symmetric InfoNCE against the in-batch diagonal."""
        n = logits.shape[0]
        targets = jnp.arange(n)
        logz_i = jax.nn.logsumexp(logits, axis=-1)
        logz_t = jax.nn.logsumexp(logits, axis=0)
        diag = logits[targets, targets]
        return 0.5 * (jnp.mean(logz_i - diag) + jnp.mean(logz_t - diag))

    def accuracy_from_logits(self, logits, batch):
        """In-batch image->text retrieval accuracy: the matching caption is
        the argmax of each image row (reference accuracy metric parity,
        dataset.py:39-54)."""
        n = logits.shape[0]
        correct = (jnp.argmax(logits, axis=-1) == jnp.arange(n))
        return jnp.sum(correct.astype(jnp.float32)), jnp.float32(n)

    def sample_batch(self, batch_size: int, seq_len: int | None = None):
        c = self.config
        seq = min(seq_len or c.max_position_embeddings,
                  c.max_position_embeddings)
        rng = jax.random.PRNGKey(0)
        return {
            "pixel_values": jax.random.normal(
                rng, (batch_size, c.image_size, c.image_size, c.num_channels),
                jnp.float32),
            "input_ids": jax.random.randint(
                jax.random.fold_in(rng, 1), (batch_size, seq), 0,
                c.vocab_size, dtype=jnp.int32),
        }

    # ---- init / fused views ----

    def _init_img_embed(self, rng):
        c = self.config
        k1, k2, k3 = jax.random.split(rng, 3)
        std = c.initializer_range
        patch_dim = c.patch_size * c.patch_size * c.num_channels
        return {
            "proj": jax.random.normal(
                k1, (patch_dim, c.vision_hidden_size), c.param_dtype) * std,
            "cls": jax.random.normal(
                k2, (1, 1, c.vision_hidden_size), c.param_dtype) * std,
            "pos": jax.random.normal(
                k3, (c.num_patches + 1, c.vision_hidden_size),
                c.param_dtype) * std,
            "ln_pre": {"scale": jnp.ones((c.vision_hidden_size,), c.param_dtype),
                       "bias": jnp.zeros((c.vision_hidden_size,), c.param_dtype)},
        }

    def img_embed(self, p, pixels):
        c = self.config
        b, hh, ww, ch = pixels.shape
        ps = c.patch_size
        x = pixels.reshape(b, hh // ps, ps, ww // ps, ps, ch)
        x = x.transpose(0, 1, 3, 2, 4, 5).reshape(b, c.num_patches, ps * ps * ch)
        x = x.astype(c.dtype) @ p["proj"].astype(c.dtype)
        cls = jnp.broadcast_to(p["cls"].astype(c.dtype),
                               (b, 1, c.vision_hidden_size))
        x = jnp.concatenate([cls, x], axis=1) + p["pos"].astype(c.dtype)
        return _layer_norm(x, p["ln_pre"]["scale"], p["ln_pre"]["bias"],
                           c.layer_norm_epsilon)

    def init_params(self, rng):
        return {self.layer_name(i): self.init_layer(rng, i)
                for i in range(self.num_pipeline_layers)}

    def forward(self, params, pixel_values, input_ids):
        carry = None
        batch = {"pixel_values": pixel_values, "input_ids": input_ids}
        for i in range(self.num_pipeline_layers):
            carry = self.apply_layer(i, params[self.layer_name(i)], carry, batch)
        return carry

    def loss(self, params, batch):
        return self.loss_from_logits(
            self.forward(params, batch["pixel_values"], batch["input_ids"]),
            batch,
        )
