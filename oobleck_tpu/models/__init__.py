"""Model registry.

Capability match for the reference's per-family AutoModel table
(/root/reference/oobleck/module/model.py:21-33): `model_name` strings resolve
to a layer-list model + config, with `model_args` overrides applied the way
the reference threads them into AutoConfig. No HF download is needed — the
architectures are defined natively — but HF-style names are accepted.
"""

from __future__ import annotations

from typing import Any, Callable

from oobleck_tpu.models import base
from oobleck_tpu.models.gpt import GPTConfig, GPTModel

_REGISTRY: dict[str, Callable[[dict[str, Any]], Any]] = {}


def register(name: str):
    def deco(fn):
        _REGISTRY[name] = fn
        return fn
    return deco


def _gpt(overrides: dict[str, Any], **preset) -> GPTModel:
    return GPTModel(GPTConfig().override(**preset).override(**overrides))


# GPT-2 family (HF names; sizes per the released checkpoints)
register("gpt2")(lambda o: _gpt(o, hidden_size=768, num_layers=12, num_heads=12))
register("gpt2-medium")(lambda o: _gpt(o, hidden_size=1024, num_layers=24, num_heads=16))
register("gpt2-large")(lambda o: _gpt(o, hidden_size=1280, num_layers=36, num_heads=20))
register("gpt2-xl")(lambda o: _gpt(o, hidden_size=1600, num_layers=48, num_heads=25))
# GPT-3 shapes (paper table 2.1) reachable by name, matching the reference's
# examples/gpt3.yaml trick of shaping gpt2 via model_args.
register("gpt3-1.3b")(lambda o: _gpt(o, hidden_size=2048, num_layers=24, num_heads=16, max_position_embeddings=2048))
register("gpt3-2.7b")(lambda o: _gpt(o, hidden_size=2560, num_layers=32, num_heads=32, max_position_embeddings=2048))
register("gpt3-6.7b")(lambda o: _gpt(o, hidden_size=4096, num_layers=32, num_heads=32, max_position_embeddings=2048))
# Tiny config for tests/CI.
register("gpt2-tiny")(lambda o: _gpt(o, vocab_size=256, hidden_size=64, num_layers=4, num_heads=4, max_position_embeddings=128))


def _lfm2(overrides: dict[str, Any], **preset):
    from oobleck_tpu.models.lfm2 import Lfm2Config, Lfm2Model

    return Lfm2Model(Lfm2Config().override(**preset).override(**overrides))


# LFM2-MoE (LiquidAI lfm2_moe): gated short convolutions beside GQA layers,
# dropless top-k sigmoid-routed experts; the defaults are LFM2-24B-A2B's.
register("lfm2-24b-a2b")(lambda o: _lfm2(o))
register("lfm2-moe-tiny")(lambda o: _lfm2(o, vocab_size=256, hidden_size=64, num_layers=4, num_heads=4, num_kv_heads=2, intermediate_size=128, moe_intermediate_size=32, num_experts=8, num_experts_per_tok=2, num_dense_layers=1, max_position_embeddings=128))


def _deepseek_v3(overrides: dict[str, Any], **preset):
    from oobleck_tpu.models.deepseek_v3 import DeepseekV3Config, DeepseekV3Model

    return DeepseekV3Model(
        DeepseekV3Config().override(**preset).override(**overrides))


# DeepSeek-V3 family (`deepseek_v3`): latent attention (MLA), shared
# experts beside dropless top-k sigmoid-routed experts; the defaults are
# Moonlight-16B-A3B's.
register("moonlight-16b-a3b")(lambda o: _deepseek_v3(o))
register("moonlight-tiny")(lambda o: _deepseek_v3(o, vocab_size=256, hidden_size=64, num_layers=3, num_heads=4, kv_lora_rank=32, qk_nope_head_dim=16, qk_rope_head_dim=8, v_head_dim=16, intermediate_size=128, moe_intermediate_size=32, num_experts=8, num_experts_per_tok=3, max_position_embeddings=128))


def _nemotron_h(overrides: dict[str, Any], **preset):
    from oobleck_tpu.models.nemotron_h import NemotronHConfig, NemotronHModel

    return NemotronHModel(
        NemotronHConfig().override(**preset).override(**overrides))


# Nemotron-H family (`nemotron_h`): every layer ONE mixer by a published
# pattern: Mamba-2, attention, or routed experts without a gate beside a
# shared one; the defaults are NVIDIA-Nemotron-3-Nano-30B-A3B's.
register("nemotron-3-nano-30b-a3b")(lambda o: _nemotron_h(o))
register("nemotron-h-tiny")(lambda o: _nemotron_h(o, vocab_size=256, hidden_size=64, num_layers=5, hybrid_override_pattern="MEM*E", mamba_num_heads=4, mamba_head_dim=16, ssm_state_size=16, n_groups=2, chunk_size=16, num_heads=4, num_kv_heads=2, head_dim=16, intermediate_size=40, moe_intermediate_size=40, moe_shared_expert_intermediate_size=80, num_experts=8, num_experts_per_tok=3, max_position_embeddings=128))


def _qwen3_next(overrides: dict[str, Any], **preset):
    from oobleck_tpu.models.qwen3_next import Qwen3NextConfig, Qwen3NextModel

    return Qwen3NextModel(
        Qwen3NextConfig().override(**preset).override(**overrides))


# Qwen3-Next family (`qwen3_next`): Gated DeltaNet mixers three layers in
# four beside one gated softmax-attention layer, top-k softmax-routed
# experts beside a sigmoid-gated shared one in every layer; the defaults are
# Qwen3-Next-80B-A3B-Instruct's.
register("qwen3-next-80b-a3b")(lambda o: _qwen3_next(o))
register("qwen3-next-tiny")(lambda o: _qwen3_next(o, vocab_size=256, hidden_size=64, num_layers=4, linear_num_key_heads=2, linear_num_value_heads=4, linear_key_head_dim=16, linear_value_head_dim=16, chunk_size=16, num_heads=4, num_kv_heads=2, head_dim=16, intermediate_size=128, moe_intermediate_size=32, shared_expert_intermediate_size=32, num_experts=16, num_experts_per_tok=4, max_position_embeddings=128))


def _kimi_linear(overrides: dict[str, Any], **preset):
    from oobleck_tpu.models.kimi_linear import (
        KimiLinearConfig,
        KimiLinearModel,
    )

    return KimiLinearModel(
        KimiLinearConfig().override(**preset).override(**overrides))


# Kimi-Linear family (`kimi_linear`): Kimi Delta Attention (a delta rule
# whose decay is a vector a head) three layers in four beside latent
# attention without positions, a dense first layer, then top-k
# sigmoid-routed experts beside a shared one; the defaults are
# Kimi-Linear-48B-A3B-Instruct's.
register("kimi-linear-48b-a3b")(lambda o: _kimi_linear(o))
register("kimi-linear-tiny")(lambda o: _kimi_linear(o, vocab_size=256, hidden_size=64, num_layers=5, kda_layers=(1, 2, 3, 5), full_attn_layers=(4,), linear_num_heads=4, linear_head_dim=16, gate_rank=8, chunk_size=16, num_heads=4, head_dim=16, kv_lora_rank=32, qk_nope_head_dim=16, qk_rope_head_dim=8, v_head_dim=16, intermediate_size=128, moe_intermediate_size=32, num_experts=16, num_experts_per_tok=4, max_position_embeddings=128))


def _smallthinker(overrides: dict[str, Any], **preset):
    from oobleck_tpu.models.smallthinker import (
        SmallThinkerConfig,
        SmallThinkerModel,
    )

    return SmallThinkerModel(
        SmallThinkerConfig().override(**preset).override(**overrides))


# SmallThinker family: sliding-window attention with rotary three layers in
# four beside one full-attention layer without a positional term, a router
# that reads the block's input before the attention, top-k softmax-routed
# ReGLU experts and no shared one; the defaults are
# SmallThinker-21BA3B-Instruct's.
register("smallthinker-21b-a3b")(lambda o: _smallthinker(o))
# The published `model_name`, which a configuration file copied from the
# model's own config.json carries under that key.
register("smallthinker_21b_instruct")(lambda o: _smallthinker(o))
register("smallthinker-tiny")(lambda o: _smallthinker(o, vocab_size=256, hidden_size=64, num_layers=4, num_heads=4, num_kv_heads=2, head_dim=16, sliding_window_size=24, moe_intermediate_size=32, num_experts=16, num_experts_per_tok=4, max_position_embeddings=128))


def _phi4flash(overrides: dict[str, Any], **preset):
    from oobleck_tpu.models.phi4flash import Phi4FlashConfig, Phi4FlashModel

    return Phi4FlashModel(
        Phi4FlashConfig().override(**preset).override(**overrides))


# Phi-4-mini-flash family (`phi4flash`, the SambaY decoder-hybrid-decoder):
# Mamba-1 beside differential attention under a window in the first half, a
# cross-decoder of Gated Memory Units and cross-attention that read ONE
# layer's scan output and ONE layer's keys and values through the carry in
# the second; no routed block. The defaults are
# Phi-4-mini-flash-reasoning's.
register("phi-4-mini-flash")(lambda o: _phi4flash(o))
# The published name.
register("Phi-4-mini-flash-reasoning")(lambda o: _phi4flash(o))
register("phi4flash-tiny")(lambda o: _phi4flash(o, vocab_size=256, hidden_size=64, num_layers=8, num_heads=4, num_kv_heads=2, head_dim=16, sliding_window=8, intermediate_size=128, max_position_embeddings=128))


def _ouro(overrides: dict[str, Any], **preset):
    from oobleck_tpu.models.ouro import OuroConfig, OuroModel

    return OuroModel(OuroConfig().override(**preset).override(**overrides))


# Ouro family (`ouro`, looped language models): a stack of sandwich-norm
# blocks a microbatch goes through `num_passes` times over one set of
# weights, the final norm, an exit gate and an exit after every pass, the
# loss over all exits under the gates' exit distribution. The defaults are
# Ouro-2.6B's.
register("ouro-2.6b")(lambda o: _ouro(o))
# The published name.
register("Ouro-2.6B")(lambda o: _ouro(o))
register("ouro-tiny")(lambda o: _ouro(o, vocab_size=256, hidden_size=64, num_layers=2, num_passes=3, num_heads=4, num_kv_heads=4, head_dim=16, intermediate_size=128, max_position_embeddings=128))


# Bloom family: GPT architecture with ALiBi position biases (no wpe)
register("bloom-560m")(lambda o: _gpt(o, vocab_size=250880, hidden_size=1024, num_layers=24, num_heads=16, position_embedding="alibi"))
register("bloom-7b1")(lambda o: _gpt(o, vocab_size=250880, hidden_size=4096, num_layers=30, num_heads=32, position_embedding="alibi"))
register("bloom-tiny")(lambda o: _gpt(o, vocab_size=256, hidden_size=64, num_layers=4, num_heads=4, max_position_embeddings=128, position_embedding="alibi"))


def _llama(overrides, **preset):
    from oobleck_tpu.models.llama import LlamaConfig, LlamaModel

    return LlamaModel(LlamaConfig().override(**preset).override(**overrides))


# Llama family (HF names; sizes per the released checkpoints)
register("llama-2-7b")(lambda o: _llama(o, hidden_size=4096, num_layers=32, num_heads=32, intermediate_size=11008))
register("llama-2-13b")(lambda o: _llama(o, hidden_size=5120, num_layers=40, num_heads=40, intermediate_size=13824))
register("llama-3-8b")(lambda o: _llama(o, vocab_size=128256, hidden_size=4096, num_layers=32, num_heads=32, num_kv_heads=8, intermediate_size=14336, max_position_embeddings=8192, rope_theta=500000.0))
register("llama-tiny")(lambda o: _llama(o, vocab_size=256, hidden_size=64, num_layers=4, num_heads=4, num_kv_heads=2, max_position_embeddings=128))


def _bert(overrides, **preset):
    from oobleck_tpu.models.bert import BertConfig, BertModel

    return BertModel(BertConfig().override(**preset).override(**overrides))


def _vit(overrides, **preset):
    from oobleck_tpu.models.vit import ViTConfig, ViTModel

    return ViTModel(ViTConfig().override(**preset).override(**overrides))


# BERT family (bidirectional encoder, MLM objective)
register("bert-base-uncased")(lambda o: _bert(o, hidden_size=768, num_layers=12, num_heads=12))
register("bert-large-uncased")(lambda o: _bert(o, hidden_size=1024, num_layers=24, num_heads=16))
register("bert-tiny")(lambda o: _bert(o, vocab_size=256, hidden_size=64, num_layers=4, num_heads=4, max_position_embeddings=128, mask_token_id=1))

def _t5(overrides, **preset):
    from oobleck_tpu.models.t5 import T5Config, T5Model

    return T5Model(T5Config().override(**preset).override(**overrides))


# T5 family (encoder-decoder, seq2seq objective)
register("t5-base")(lambda o: _t5(o, d_model=768, num_layers=12, num_decoder_layers=12, num_heads=12, d_ff=2048))
register("t5-large")(lambda o: _t5(o, d_model=1024, num_layers=24, num_decoder_layers=24, num_heads=16, d_ff=2816))
register("t5-tiny")(lambda o: _t5(o, vocab_size=256, d_model=64, num_layers=2, num_decoder_layers=2, num_heads=4, d_ff=128))

# ViT family (image classification)
register("vit-base-patch16-224")(lambda o: _vit(o, hidden_size=768, num_layers=12, num_heads=12))
register("vit-large-patch16-224")(lambda o: _vit(o, hidden_size=1024, num_layers=24, num_heads=16))
register("vit-tiny")(lambda o: _vit(o, image_size=32, patch_size=8, num_classes=10, hidden_size=64, num_layers=4, num_heads=4))


def _resnet(overrides, **preset):
    from oobleck_tpu.models.resnet import ResNetConfig, ResNetModel

    return ResNetModel(ResNetConfig().override(**preset).override(**overrides))


# ResNet family (conv pipeline; reference sharding.py:37-41 splits per block)
register("resnet-50")(lambda o: _resnet(o, depths=(3, 4, 6, 3)))
register("resnet-152")(lambda o: _resnet(o, depths=(3, 8, 36, 3)))
register("resnet-tiny")(lambda o: _resnet(o, image_size=32, num_classes=10, embedding_size=16, hidden_sizes=(32, 64), depths=(1, 1)))


def _swin(overrides, **preset):
    from oobleck_tpu.models.swin import SwinConfig, SwinModel

    return SwinModel(SwinConfig().override(**preset).override(**overrides))


# Swin family (HF names per released checkpoints; "-micro" is the test config)
register("swin-tiny-patch4-window7-224")(lambda o: _swin(o, embed_dim=96, depths=(2, 2, 6, 2), num_heads=(3, 6, 12, 24)))
register("swin-base-patch4-window7-224")(lambda o: _swin(o, embed_dim=128, depths=(2, 2, 18, 2), num_heads=(4, 8, 16, 32)))
register("swin-micro")(lambda o: _swin(o, image_size=32, patch_size=4, num_classes=10, embed_dim=32, depths=(2, 1), num_heads=(2, 4), window_size=4))


def _clip(overrides, **preset):
    from oobleck_tpu.models.clip import CLIPConfig, CLIPModel

    return CLIPModel(CLIPConfig().override(**preset).override(**overrides))


# CLIP family (dual-encoder contrastive)
register("clip-vit-base-patch32")(lambda o: _clip(o))
register("clip-vit-base-patch16")(lambda o: _clip(o, patch_size=16))
register("clip-tiny")(lambda o: _clip(o, image_size=32, patch_size=8, vision_hidden_size=64, vision_layers=3, vision_heads=4, vocab_size=256, max_position_embeddings=32, text_hidden_size=64, text_layers=3, text_heads=4, projection_dim=32))


def build_model(model_name: str, model_args: dict[str, Any] | None = None,
                execution=None):
    """Resolve a model name (+ overrides) to a layer-list model instance.

    `execution` (an ExecutionArguments, duck-typed) threads the engine's
    precision / remat / attention_impl knobs into the model config — applied
    only where the family's config has the field, and never overriding an
    explicit `model_args` entry.
    """
    try:
        factory = _REGISTRY[model_name]
    except KeyError:
        raise ValueError(
            f"unknown model {model_name!r}; known: {sorted(_REGISTRY)}"
        ) from None
    model_args = dict(model_args or {})
    model = factory(model_args)
    if execution is not None:
        import jax.numpy as jnp

        dtypes = {
            "bfloat16": jnp.bfloat16,
            "float32": jnp.float32,
            "float16": jnp.float16,
        }
        precision = getattr(execution, "precision", None)
        if precision is not None and precision not in dtypes:
            raise ValueError(
                f"unknown precision {precision!r}; known: {sorted(dtypes)}"
            )
        fields = type(model.config).__dataclass_fields__
        extra = {
            k: v for k, v in {
                "dtype": dtypes[precision] if precision else None,
                "remat": getattr(execution, "remat", None),
                "attention_impl": getattr(execution, "attention_impl", None),
            }.items()
            if v is not None and k in fields and k not in model_args
        }
        if extra:
            model = factory({**model_args, **extra})
    return model


def available_models() -> list[str]:
    return sorted(_REGISTRY)


__all__ = ["build_model", "available_models", "register", "base", "GPTConfig", "GPTModel"]
