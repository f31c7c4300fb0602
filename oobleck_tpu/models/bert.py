"""BERT-family encoder as an explicit layer list.

Capability match for the reference's bert path (HF AutoModelForMaskedLM +
fx split points per encoder block, /root/reference/oobleck/module/
model.py:21-33, sharding.py:19-22): bidirectional attention, learned
positions, masked-language-modeling objective.

Same layer-list contract as GPT ([embed, block_0.., head]); blocks reuse the
GPT block shape with `causal=False` attention. MLM batches are produced by
`make_mlm_batch` (corrupt 15% of tokens: 80% [MASK], 10% random, 10% kept);
the loss runs only over corrupted positions.

Engine integration: the MPMD pipeline drives BERT through the generic
apply_layer / loss_from_logits contract with MLMView batches (corruption
done dataset-side); the fused SPMD step remains causal-LM-specific.
"""

from __future__ import annotations

from dataclasses import dataclass, replace
from typing import Any

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

from oobleck_tpu.models.base import stack_layer_params
from oobleck_tpu.models.gpt import NEG_INF, ShardCtx, _layer_norm
from oobleck_tpu.ops import attention, checkpoint_layer


@dataclass(frozen=True)
class BertConfig:
    vocab_size: int = 30522
    max_position_embeddings: int = 512
    hidden_size: int = 768
    num_layers: int = 12
    num_heads: int = 12
    intermediate_size: int | None = None
    layer_norm_epsilon: float = 1e-12
    initializer_range: float = 0.02
    mask_token_id: int = 103  # HF bert [MASK]
    dtype: Any = jnp.bfloat16
    param_dtype: Any = jnp.float32
    remat: bool = True

    @property
    def ffn_dim(self) -> int:
        return self.intermediate_size or 4 * self.hidden_size

    @property
    def head_dim(self) -> int:
        return self.hidden_size // self.num_heads

    def override(self, **kwargs) -> "BertConfig":
        alias = {"n_embd": "hidden_size", "n_layer": "num_layers",
                 "n_head": "num_heads", "n_positions": "max_position_embeddings"}
        kwargs = {alias.get(k, k): v for k, v in kwargs.items()}
        unknown = [k for k in kwargs if k not in BertConfig.__dataclass_fields__]
        if unknown:
            raise ValueError(f"unknown model_args {unknown}")
        return replace(self, **kwargs)


class BertModel:
    # Engine contract: batches carry pre-corrupted inputs + labels + mask
    # (execution.dataset.MLMView); the MPMD pipeline drives apply_layer +
    # loss_from_logits. The fused SPMD step is causal-LM-specific.
    data_kind = "mlm"

    def __init__(self, config: BertConfig):
        self.config = config

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
        return self._init_block(jax.random.fold_in(ks[1], index))

    def apply_layer(self, index, params, carry, batch, ctx=None):
        if index == 0:
            return self.embed(params, batch["input_ids"])
        if index == self.num_pipeline_layers - 1:
            return self.head(params, carry)
        return self.apply_block(params, carry)

    def loss_from_logits(self, logits, batch):
        """Masked-LM loss over corrupted positions. `batch` carries
        pre-corrupted input_ids plus the clean labels and the float mask of
        corrupted positions (MLMView's contract)."""
        labels = batch["labels"]
        mask = batch["loss_mask"].astype(jnp.float32)
        logits = logits.astype(jnp.float32)
        logz = jax.nn.logsumexp(logits, axis=-1)
        gold = jnp.take_along_axis(logits, labels[..., None], axis=-1)[..., 0]
        per_pos = (logz - gold) * mask
        return jnp.sum(per_pos) / jnp.maximum(jnp.sum(mask), 1.0)

    def accuracy_from_logits(self, logits, batch):
        """Masked-token accuracy over the corrupted positions (reference
        accuracy metric parity, dataset.py:39-54)."""
        mask = batch["loss_mask"].astype(jnp.float32)
        pred = jnp.argmax(logits, axis=-1)
        correct = (pred == batch["labels"]).astype(jnp.float32) * mask
        return jnp.sum(correct), jnp.sum(mask)

    def sample_batch(self, batch_size: int, seq_len: int):
        tokens = jax.random.randint(
            jax.random.PRNGKey(0), (batch_size, seq_len), 0,
            self.config.vocab_size, dtype=jnp.int32,
        )
        corrupted, labels, mask = self.make_mlm_batch(
            tokens, jax.random.PRNGKey(1)
        )
        return {"input_ids": corrupted, "labels": labels, "loss_mask": mask}

    # ---- init (GPT block shapes + ln_embed) ----

    def _init_embed(self, rng):
        c = self.config
        k1, k2 = jax.random.split(rng)
        std = c.initializer_range
        return {
            "wte": jax.random.normal(k1, (c.vocab_size, c.hidden_size), c.param_dtype) * std,
            "wpe": jax.random.normal(k2, (c.max_position_embeddings, c.hidden_size), c.param_dtype) * std,
            "ln": {"scale": jnp.ones((c.hidden_size,), c.param_dtype),
                   "bias": jnp.zeros((c.hidden_size,), c.param_dtype)},
        }

    def _init_block(self, rng):
        c = self.config
        ks = jax.random.split(rng, 4)
        std = c.initializer_range
        e, f, h, d = c.hidden_size, c.ffn_dim, c.num_heads, c.head_dim
        return {
            "ln1": {"scale": jnp.ones((e,), c.param_dtype), "bias": jnp.zeros((e,), c.param_dtype)},
            "attn": {
                "wqkv": jax.random.normal(ks[0], (e, 3, h, d), c.param_dtype) * std,
                "bqkv": jnp.zeros((3, h, d), c.param_dtype),
                "wo": jax.random.normal(ks[1], (h, d, e), c.param_dtype) * std,
                "bo": jnp.zeros((e,), c.param_dtype),
            },
            "ln2": {"scale": jnp.ones((e,), c.param_dtype), "bias": jnp.zeros((e,), c.param_dtype)},
            "mlp": {
                "wi": jax.random.normal(ks[2], (e, f), c.param_dtype) * std,
                "bi": jnp.zeros((f,), c.param_dtype),
                "wo": jax.random.normal(ks[3], (f, e), c.param_dtype) * std,
                "bo": jnp.zeros((e,), c.param_dtype),
            },
        }

    def _init_head(self, rng):
        c = self.config
        return {
            "ln_f": {"scale": jnp.ones((c.hidden_size,), c.param_dtype),
                     "bias": jnp.zeros((c.hidden_size,), c.param_dtype)},
            "w": jax.random.normal(
                rng, (c.hidden_size, c.vocab_size), c.param_dtype
            ) * c.initializer_range,
        }

    def init_params(self, rng):
        ks = jax.random.split(rng, 3)
        blocks = [self._init_block(jax.random.fold_in(ks[1], i + 1))
                  for i in range(self.config.num_layers)]
        return {"embed": self._init_embed(ks[0]),
                "blocks": stack_layer_params(blocks),
                "head": self._init_head(ks[2])}

    # ---- forward (bidirectional) ----

    def embed(self, p, tokens):
        c = self.config
        x = p["wte"][tokens] + p["wpe"][: tokens.shape[-1]]
        x = _layer_norm(x, p["ln"]["scale"], p["ln"]["bias"], c.layer_norm_epsilon)
        return x.astype(c.dtype)

    def apply_block(self, p, x):
        c = self.config
        dt = c.dtype
        h = _layer_norm(x, p["ln1"]["scale"], p["ln1"]["bias"], c.layer_norm_epsilon)
        qkv = jnp.einsum("bse,ethd->tbhsd", h, p["attn"]["wqkv"].astype(dt))
        qkv = qkv + p["attn"]["bqkv"].astype(dt)[:, None, :, None, :]
        attn = attention._xla_causal_attention(qkv[0], qkv[1], qkv[2], causal=False)
        out = jnp.einsum("bhsd,hde->bse", attn, p["attn"]["wo"].astype(dt))
        x = x + out + p["attn"]["bo"].astype(dt)
        h = _layer_norm(x, p["ln2"]["scale"], p["ln2"]["bias"], c.layer_norm_epsilon)
        h = jax.nn.gelu(h @ p["mlp"]["wi"].astype(dt) + p["mlp"]["bi"].astype(dt))
        return x + h @ p["mlp"]["wo"].astype(dt) + p["mlp"]["bo"].astype(dt)

    def head(self, p, x):
        c = self.config
        x = _layer_norm(x, p["ln_f"]["scale"], p["ln_f"]["bias"], c.layer_norm_epsilon)
        return (x @ p["w"].astype(c.dtype)).astype(jnp.float32)

    def forward(self, params, tokens):
        block = self.apply_block
        if self.config.remat:
            block = checkpoint_layer(block)
        x = self.embed(params["embed"], tokens)

        def body(x, bp):
            return block(bp, x), None

        x, _ = jax.lax.scan(body, x, params["blocks"])
        return self.head(params["head"], x)

    # ---- MLM objective ----

    def make_mlm_batch(self, tokens: jax.Array, rng: jax.Array):
        """Corrupt 15% of positions (80% [MASK] / 10% random / 10% kept);
        returns (corrupted, labels, loss_mask). jit-safe (pure jax ops)."""
        c = self.config
        k1, k2, k3 = jax.random.split(rng, 3)
        select = jax.random.uniform(k1, tokens.shape) < 0.15
        roll = jax.random.uniform(k2, tokens.shape)
        randoms = jax.random.randint(k3, tokens.shape, 0, c.vocab_size,
                                     dtype=tokens.dtype)
        corrupted = jnp.where(select & (roll < 0.8), c.mask_token_id, tokens)
        corrupted = jnp.where(select & (roll >= 0.8) & (roll < 0.9),
                              randoms, corrupted)
        return corrupted, tokens, select.astype(jnp.float32)

    def mlm_loss(self, params, corrupted, labels, mask):
        logits = self.forward(params, corrupted)
        return self.loss_from_logits(
            logits, {"labels": labels, "loss_mask": mask}
        )

    def loss(self, params, batch, rng: jax.Array | None = None):
        """MLM loss. Pass a fresh `rng` per step so the corruption mask
        varies; the deterministic default is for tests only."""
        if rng is None:
            rng = jax.random.PRNGKey(0)
        corrupted, labels, mask = self.make_mlm_batch(batch["input_ids"], rng)
        return self.mlm_loss(params, corrupted, labels, mask)
