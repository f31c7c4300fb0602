"""Per-layer readers on hand-made data."""

import pytest

from benchmarks.readers import hist_mean_ms, kernel_roofline_pct, mfu_pct


def test_hist_mean():
    data = {"hist": {"h": {"sum": 2.4, "count": 2}, "empty": {"sum": 0.0,
                                                              "count": 0}}}
    assert hist_mean_ms.read(data, histogram="h") == pytest.approx(1200.0)
    assert hist_mean_ms.read(data, histogram="empty") is None
    assert hist_mean_ms.read(data, histogram="absent") is None


TRAIN = {"tokens_per_s": 26_700.0, "seq_len": 1024, "microbatch_size": 4,
         "n_params": 577_592_320, "num_layers": 4, "hidden_size": 2560,
         "num_heads": 32}
DEVICE = {"kind": "TPU v5 lite"}


def test_mfu_by_hand():
    per_token = 6 * 577_592_320 + 6 * 4 * 2560 * 1024
    want = 100 * 26_700.0 * per_token / 197e12
    assert mfu_pct.read({"train": TRAIN, "device": DEVICE}) \
        == pytest.approx(want)
    assert 45 < want < 52


def test_kernel_roofline_by_hand():
    args = {"match": "tpu_custom_call",
            "needed": ["causal_attention_fwd", "causal_attention_bwd"]}
    # 10 microbatches x 4 layers; fwd least 1.09e-4 s a call, bwd twice.
    trace = {"time_by_name": {
        "%jvp__.3 bf16[128,1024,128] custom-call:tpu_custom_call": [0.1, 40],
        "%checkpoint.9 f32[128,1024,128] custom-call:tpu_custom_call":
            [0.2, 80],
        "%fusion.3 bf16[4,1024,50304] fusion": [9.0, 40]}}
    data = {"train": dict(TRAIN, microbatches_run=10), "device": DEVICE,
            "trace": trace}
    least = 10 * 4 * (1.0901e-4 + 2.1802e-4)
    assert kernel_roofline_pct.read(data, **args) == pytest.approx(
        100 * least / 0.3, rel=1e-3)
    # No event that matches: nothing to read, no guess.
    del data["trace"]["time_by_name"][
        "%jvp__.3 bf16[128,1024,128] custom-call:tpu_custom_call"]
    del data["trace"]["time_by_name"][
        "%checkpoint.9 f32[128,1024,128] custom-call:tpu_custom_call"]
    assert kernel_roofline_pct.read(data, **args) is None

