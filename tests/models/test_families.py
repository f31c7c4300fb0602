"""Model-family breadth tests (bloom / bert / vit), mirroring the reference's
per-family coverage (/root/reference/tests/module/test_model.py): forward
shapes, loss sanity, overfit-ability."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from oobleck_tpu.models import available_models, build_model


def _overfit(model, batch, steps=5, lr=0.05):
    params = model.init_params(jax.random.PRNGKey(0))

    @jax.jit
    def step(params):
        loss, grads = jax.value_and_grad(model.loss)(params, batch)
        return jax.tree.map(lambda p, g: p - lr * g, params, grads), loss

    losses = []
    for _ in range(steps):
        params, loss = step(params)
        losses.append(float(loss))
    return losses


def test_bloom_alibi_decoder():
    model = build_model("bloom-tiny")
    assert "wpe" not in model.init_layer(jax.random.PRNGKey(0), 0)
    batch = model.sample_batch(2, 32)
    losses = _overfit(model, batch)
    assert losses[-1] < losses[0]


def test_bloom_alibi_bias_shape():
    from oobleck_tpu.ops.attention import alibi_bias, alibi_slopes

    assert alibi_slopes(8).shape == (8,)
    assert alibi_slopes(12).shape == (12,)  # non-power-of-2 heads
    b = alibi_bias(4, 8, 8)
    assert b.shape == (4, 8, 8)
    # bias is 0 on the diagonal and decreases with distance
    assert float(b[0, 5, 5]) == 0.0
    assert float(b[0, 5, 2]) < float(b[0, 5, 4]) < 0.0


def test_bert_mlm():
    model = build_model("bert-tiny")
    tokens = model.sample_batch(2, 32)["input_ids"]
    corrupted, labels, mask = model.make_mlm_batch(tokens, jax.random.PRNGKey(1))
    corrupted, labels, mask = map(np.asarray, (corrupted, labels, mask))
    assert corrupted.shape == labels.shape == mask.shape
    assert mask.sum() > 0
    assert (corrupted[mask == 0] == labels[mask == 0]).all()
    # fresh rng -> different corruption pattern
    c2, _, m2 = model.make_mlm_batch(tokens, jax.random.PRNGKey(2))
    assert not np.array_equal(mask, np.asarray(m2))
    losses = _overfit(model, {"input_ids": tokens})
    assert losses[-1] < losses[0]
    # initial MLM loss near uniform log V
    assert abs(losses[0] - np.log(model.config.vocab_size)) < 1.2


def test_fused_path_rejects_non_lm_families():
    """The fused SPMD step is causal-LM only; non-LM families must be told
    to use the MPMD path instead of failing deep in tracing."""
    from oobleck_tpu.config import (ExecutionArguments, ModelArguments,
                                    OobleckArguments)
    from oobleck_tpu.execution.engine import OobleckEngine

    args = OobleckArguments(
        model=ModelArguments(model_name="t5-tiny"),
        execution=ExecutionArguments(engine_path="fused"),
    )
    with pytest.raises(ValueError, match="engine_path: mpmd"):
        OobleckEngine(args)


def test_a_name_the_registry_does_not_hold_is_refused_with_the_known_ones():
    """`gpt2-moe` is no family of this repo (its one expert layer is
    `ops/moe.routed_experts`): the registry's own error names what is."""
    with pytest.raises(ValueError, match="unknown model 'gpt2-moe'") as e:
        build_model("gpt2-moe")
    assert "'lfm2-24b-a2b'" in str(e.value)


def test_bert_attention_is_bidirectional():
    model = build_model("bert-tiny")
    params = model.init_params(jax.random.PRNGKey(0))
    t = np.asarray(model.sample_batch(1, 16)["input_ids"])
    base = np.asarray(model.forward(params, jnp.asarray(t)))
    t2 = t.copy()
    t2[0, -1] = (t2[0, -1] + 1) % model.config.vocab_size
    out2 = np.asarray(model.forward(params, jnp.asarray(t2)))
    # changing the LAST token changes the FIRST position's logits
    assert not np.allclose(base[0, 0], out2[0, 0])


def test_vit_classification():
    model = build_model("vit-tiny")
    batch = model.sample_batch(4)
    logits = model.forward(model.init_params(jax.random.PRNGKey(0)),
                           batch["pixel_values"])
    assert logits.shape == (4, 10)
    losses = _overfit(model, batch, steps=6, lr=0.1)
    assert losses[-1] < losses[0]
    assert abs(losses[0] - np.log(10)) < 1.0


def test_registry_inventory():
    """Every family the reference supports (module/model.py:21-33: gpt2, t5,
    bert, bloom, vit, resnet, clip, swin) resolves by an HF-style name."""
    names = available_models()
    for family in ("gpt2", "gpt3-2.7b", "bloom-560m", "llama-2-7b",
                   "bert-base-uncased", "vit-base-patch16-224", "t5-base",
                   "resnet-50", "clip-vit-base-patch32",
                   "swin-tiny-patch4-window7-224"):
        assert family in names, names


def test_resnet_classification():
    model = build_model("resnet-tiny")
    batch = model.sample_batch(4)
    params = model.init_params(jax.random.PRNGKey(0))
    logits = model.forward(params, batch["pixel_values"])
    assert logits.shape == (4, 10)
    losses = _overfit(model, batch, steps=6, lr=0.1)
    assert losses[-1] < losses[0]
    assert abs(losses[0] - np.log(10)) < 1.5


def test_resnet_layerwise_matches_forward():
    """The per-layer pipeline walk computes the same function as forward()
    (block granularity mirrors reference sharding.py:37-41)."""
    model = build_model("resnet-tiny")
    params = model.init_params(jax.random.PRNGKey(0))
    batch = model.sample_batch(2)
    fused = model.forward(params, batch["pixel_values"])
    carry = None
    for i in range(model.num_pipeline_layers):
        carry = model.apply_layer(i, params[model.layer_name(i)], carry, batch)
    np.testing.assert_allclose(np.asarray(carry), np.asarray(fused),
                               rtol=1e-2, atol=1e-2)


def test_swin_classification():
    model = build_model("swin-micro")
    # stage 0 depth 2 => block 1 exercises the SHIFTED window branch.
    names = [model.layer_name(i) for i in range(model.num_pipeline_layers)]
    assert names == ["embed", "stage0_block0", "stage0_block1", "merge1",
                     "stage1_block0", "head"]
    batch = model.sample_batch(4)
    params = model.init_params(jax.random.PRNGKey(0))
    logits = model.forward(params, batch["pixel_values"])
    assert logits.shape == (4, 10)
    losses = _overfit(model, batch, steps=6, lr=0.1)
    assert losses[-1] < losses[0]
    assert abs(losses[0] - np.log(10)) < 1.5


def test_swin_shift_mask_blocks_wrapped_windows():
    from oobleck_tpu.models.swin import _shift_mask

    mask = _shift_mask(8, 4, 2)  # 8x8 grid, window 4, shift 2
    assert mask.shape == (4, 16, 16)
    # interior window: fully visible; boundary windows: some pairs masked
    assert (mask[0] == 0).all()
    assert (mask[-1] < 0).any()


def test_swin_layerwise_matches_forward():
    model = build_model("swin-micro")
    params = model.init_params(jax.random.PRNGKey(0))
    batch = model.sample_batch(2)
    fused = model.forward(params, batch["pixel_values"])
    carry = None
    for i in range(model.num_pipeline_layers):
        carry = model.apply_layer(i, params[model.layer_name(i)], carry, batch)
    np.testing.assert_allclose(np.asarray(carry), np.asarray(fused),
                               rtol=1e-2, atol=1e-2)


def test_clip_contrastive():
    model = build_model("clip-tiny")
    batch = model.sample_batch(4, 16)
    params = model.init_params(jax.random.PRNGKey(0))
    logits = model.forward(params, batch["pixel_values"], batch["input_ids"])
    assert logits.shape == (4, 4)  # in-batch similarity matrix
    # symmetric InfoNCE starts near log(B) for random embeddings
    losses = _overfit(model, batch, steps=8, lr=0.05)
    assert losses[-1] < losses[0]
    assert abs(losses[0] - np.log(4)) < 1.0
    # txt_embed is a mid-pipeline batch consumer, like T5's bridge
    assert model._txt_embed_index in model.batch_layers


def test_t5_seq2seq():
    model = build_model("t5-tiny")
    assert model.num_pipeline_layers == 2 + 2 + 3  # embed+2enc+bridge+2dec+head
    names = [model.layer_name(i) for i in range(model.num_pipeline_layers)]
    assert names == ["embed", "enc_0", "enc_1", "bridge", "dec_0", "dec_1", "head"]
    batch = model.sample_batch(2, 16)
    losses = _overfit(model, batch, steps=6, lr=0.1)
    assert losses[-1] < losses[0]
    assert abs(losses[0] - np.log(model.config.vocab_size)) < 1.2


def test_t5_layerwise_matches_fused():
    model = build_model("t5-tiny")
    params = model.init_params(jax.random.PRNGKey(0))
    batch = model.sample_batch(1, 8)
    fused = model.forward(params, batch["input_ids"], batch["decoder_input_ids"])
    # layer-list walk over the same weights
    from oobleck_tpu.models.base import unstack_layer_params

    layer_params = (
        [params["embed"]]
        + unstack_layer_params(params["enc_blocks"])
        + [params["bridge"]]
        + unstack_layer_params(params["dec_blocks"])
        + [params["head"]]
    )
    carry = None
    for i, p in enumerate(layer_params):
        carry = model.apply_layer(i, p, carry, batch)
    np.testing.assert_allclose(np.asarray(carry), np.asarray(fused),
                               rtol=1e-2, atol=1e-2)


def test_accuracy_metrics_all_families():
    """Every non-causal-LM family reports a task metric next to the loss
    (reference builds an accuracy metric it never reports, dataset.py:39-54):
    accuracy_from_logits returns (correct, count) with 0 <= correct <= count,
    and a perfectly-predicting logit tensor scores 1.0."""
    cases = [
        ("vit-tiny", "labels"),
        ("resnet-tiny", "labels"),
        ("bert-tiny", "labels"),
        ("t5-tiny", "labels"),
        ("clip-tiny", None),
    ]
    for name, label_key in cases:
        model = build_model(name)
        batch = model.sample_batch(4, 16)
        params = model.init_params(jax.random.PRNGKey(0))
        if name == "t5-tiny":
            logits = model.forward(params, batch["input_ids"],
                                   batch["decoder_input_ids"])
        elif name == "clip-tiny":
            logits = model.forward(params, batch["pixel_values"],
                                   batch["input_ids"])
        elif model.data_kind == "image":
            logits = model.forward(params, batch["pixel_values"])
        else:
            logits = model.forward(params, batch["input_ids"])
        c, n = model.accuracy_from_logits(logits, batch)
        c, n = float(c), float(n)
        assert 0.0 <= c <= n and n > 0, (name, c, n)

        # Oracle logits -> accuracy exactly 1.
        if name == "clip-tiny":
            oracle = jnp.eye(logits.shape[0]) * 10.0
        else:
            num_classes = logits.shape[-1]
            oracle = jax.nn.one_hot(batch["labels"], num_classes) * 10.0
        oc, on = model.accuracy_from_logits(oracle, batch)
        assert float(oc) == float(on), (name, float(oc), float(on))
