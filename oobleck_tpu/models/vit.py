"""ViT image classifier as an explicit layer list.

Capability match for the reference's image families (vit/swin via
AutoModelForImageClassification, /root/reference/oobleck/module/model.py:26-30,
sharding.py:31-34): patch embedding, bidirectional transformer blocks, CLS
classification head with cross-entropy.

Layer list: [patch_embed, block_0.., head] — the same planning/pipeline
granularity contract as the language families.
"""

from __future__ import annotations

from dataclasses import dataclass, replace
from typing import Any

import jax
import jax.numpy as jnp

from oobleck_tpu.models.base import stack_layer_params
from oobleck_tpu.models.gpt import _layer_norm
from oobleck_tpu.models.bert import BertConfig, BertModel
from oobleck_tpu.ops import checkpoint_layer


@dataclass(frozen=True)
class ViTConfig:
    image_size: int = 224
    patch_size: int = 16
    num_channels: int = 3
    num_classes: int = 1000
    hidden_size: int = 768
    num_layers: int = 12
    num_heads: int = 12
    intermediate_size: int | None = None
    layer_norm_epsilon: float = 1e-6
    initializer_range: float = 0.02
    dtype: Any = jnp.bfloat16
    param_dtype: Any = jnp.float32
    remat: bool = True

    @property
    def num_patches(self) -> int:
        return (self.image_size // self.patch_size) ** 2

    @property
    def ffn_dim(self) -> int:
        return self.intermediate_size or 4 * self.hidden_size

    @property
    def head_dim(self) -> int:
        return self.hidden_size // self.num_heads

    def override(self, **kwargs) -> "ViTConfig":
        unknown = [k for k in kwargs if k not in ViTConfig.__dataclass_fields__]
        if unknown:
            raise ValueError(f"unknown model_args {unknown}")
        return replace(self, **kwargs)


class ViTModel:
    """Reuses the BERT encoder block (bidirectional attention) with a patch
    embed front and a CLS classifier head."""

    # Engine contract: image batches (pixel_values / labels) drive the MPMD
    # pipeline through the generic apply_layer / loss_from_logits path.
    data_kind = "image"

    def __init__(self, config: ViTConfig):
        self.config = config
        # Encoder blocks are BERT blocks of the same width.
        self._encoder = BertModel(BertConfig(
            hidden_size=config.hidden_size, num_layers=config.num_layers,
            num_heads=config.num_heads,
            intermediate_size=config.intermediate_size,
            layer_norm_epsilon=config.layer_norm_epsilon,
            dtype=config.dtype, param_dtype=config.param_dtype,
        ))

    @property
    def num_pipeline_layers(self) -> int:
        return self.config.num_layers + 2

    def layer_name(self, index: int) -> str:
        if index == 0:
            return "embed"
        if index == self.num_pipeline_layers - 1:
            return "head"
        return f"block_{index - 1}"

    def init_layer(self, rng, index):
        ks = jax.random.split(rng, 3)
        if index == 0:
            return self._init_embed(ks[0])
        if index == self.num_pipeline_layers - 1:
            return self._init_head(ks[2])
        return self._encoder._init_block(jax.random.fold_in(ks[1], index))

    def apply_layer(self, index, params, carry, batch, ctx=None):
        if index == 0:
            return self.embed(params, batch["pixel_values"])
        if index == self.num_pipeline_layers - 1:
            return self.head(params, carry)
        return self._encoder.apply_block(params, carry)

    def sample_batch(self, batch_size: int, *_ignored):
        c = self.config
        rng = jax.random.PRNGKey(0)
        return {
            "pixel_values": jax.random.normal(
                rng, (batch_size, c.image_size, c.image_size, c.num_channels),
                jnp.float32,
            ),
            "labels": jax.random.randint(
                jax.random.fold_in(rng, 1), (batch_size,), 0, c.num_classes,
                dtype=jnp.int32,
            ),
        }

    # ---- init ----

    def _init_embed(self, rng):
        c = self.config
        k1, k2, k3 = jax.random.split(rng, 3)
        std = c.initializer_range
        patch_dim = c.patch_size * c.patch_size * c.num_channels
        return {
            "proj": jax.random.normal(k1, (patch_dim, c.hidden_size), c.param_dtype) * std,
            "bias": jnp.zeros((c.hidden_size,), c.param_dtype),
            "cls": jax.random.normal(k2, (1, 1, c.hidden_size), c.param_dtype) * std,
            "pos": jax.random.normal(
                k3, (c.num_patches + 1, c.hidden_size), c.param_dtype
            ) * std,
        }

    def _init_head(self, rng):
        c = self.config
        return {
            "ln_f": {"scale": jnp.ones((c.hidden_size,), c.param_dtype),
                     "bias": jnp.zeros((c.hidden_size,), c.param_dtype)},
            "w": jax.random.normal(rng, (c.hidden_size, c.num_classes), c.param_dtype)
            * c.initializer_range,
            "b": jnp.zeros((c.num_classes,), c.param_dtype),
        }

    def init_params(self, rng):
        ks = jax.random.split(rng, 3)
        blocks = [self._encoder._init_block(jax.random.fold_in(ks[1], i + 1))
                  for i in range(self.config.num_layers)]
        return {"embed": self._init_embed(ks[0]),
                "blocks": stack_layer_params(blocks),
                "head": self._init_head(ks[2])}

    # ---- forward ----

    def embed(self, p, pixels: jax.Array) -> jax.Array:
        """[B, H, W, C] -> [B, 1+P, E]: patchify as a reshape + matmul (the
        conv-as-matmul form XLA tiles straight onto the MXU)."""
        c = self.config
        b, hh, ww, ch = pixels.shape
        ps = c.patch_size
        x = pixels.reshape(b, hh // ps, ps, ww // ps, ps, ch)
        x = x.transpose(0, 1, 3, 2, 4, 5).reshape(b, c.num_patches, ps * ps * ch)
        x = x.astype(c.dtype) @ p["proj"].astype(c.dtype) + p["bias"].astype(c.dtype)
        cls = jnp.broadcast_to(p["cls"].astype(c.dtype), (b, 1, c.hidden_size))
        x = jnp.concatenate([cls, x], axis=1)
        return x + p["pos"].astype(c.dtype)

    def head(self, p, x: jax.Array) -> jax.Array:
        c = self.config
        cls = _layer_norm(x[:, 0], p["ln_f"]["scale"], p["ln_f"]["bias"],
                          c.layer_norm_epsilon)
        return (cls @ p["w"].astype(c.dtype) + p["b"].astype(c.dtype)).astype(jnp.float32)

    def forward(self, params, pixels):
        block = self._encoder.apply_block
        if self.config.remat:
            block = checkpoint_layer(block)
        x = self.embed(params["embed"], pixels)

        def body(x, bp):
            return block(bp, x), None

        x, _ = jax.lax.scan(body, x, params["blocks"])
        return self.head(params["head"], x)

    def loss_from_logits(self, logits, batch):
        logits = logits.astype(jnp.float32)
        logz = jax.nn.logsumexp(logits, axis=-1)
        gold = jnp.take_along_axis(
            logits, batch["labels"][..., None], axis=-1
        )[..., 0]
        return jnp.mean(logz - gold)


    def accuracy_from_logits(self, logits, batch):
        from oobleck_tpu.models.base import argmax_accuracy

        return argmax_accuracy(logits, batch["labels"])

    def loss(self, params, batch):
        return self.loss_from_logits(
            self.forward(params, batch["pixel_values"]), batch
        )
