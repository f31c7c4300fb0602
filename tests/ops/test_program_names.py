"""Every jitted program of the two hot paths is a named function, so its
XLA module has a name of ours in a device trace (`jit_fwd`, `jit_bwd`,
`jit_grad_zero`, `jit_optimizer_update`, `jit_decode_step`, `jit_prefill`,
...) and none is `jit__lambda`, which says nothing and is the same for
every lambda in the process."""

import ast
from pathlib import Path

import pytest

PKG = Path(__file__).resolve().parents[2] / "oobleck_tpu"
# Training: engine -> pipeline (+ the precompiler that warms its programs)
# and the fused step; serving: batcher -> engine; the model families both run.
HOT_PATH = ["execution/engine.py", "execution/pipeline.py",
            "execution/precompile.py", "execution/fused.py",
            "parallel/train.py", "serve/engine.py", "serve/batcher.py",
            "models/gpt.py", "models/llama.py", "models/lfm2.py",
            "models/routed.py", "models/deepseek_v3.py",
            "models/nemotron_h.py", "models/qwen3_next.py",
            "models/smallthinker.py", "models/phi4flash.py", "ops/flash.py",
            "ops/moe.py", "ops/ssd.py", "ops/gdn.py", "ops/sscan.py"]


def _is_jit(call: ast.Call) -> bool:
    f = call.func
    return (isinstance(f, ast.Attribute) and f.attr == "jit") or (
        isinstance(f, ast.Name) and f.id == "jit")


@pytest.mark.parametrize("module", HOT_PATH)
def test_no_jitted_program_is_a_lambda(module):
    tree = ast.parse((PKG / module).read_text())
    lambdas = [node.lineno for node in ast.walk(tree)
               if isinstance(node, ast.Call) and _is_jit(node) and node.args
               and isinstance(node.args[0], ast.Lambda)]
    assert not lambdas, f"{module}: jax.jit(lambda ...) at lines {lambdas}"


def test_training_programs_carry_their_names():
    from oobleck_tpu.execution import pipeline
    from oobleck_tpu.parallel.train import make_optimizer

    assert pipeline.grad_zero.__name__ == "grad_zero"
    update = pipeline.optimizer_update_program(make_optimizer())
    assert update.__name__ == "optimizer_update"


def test_serving_programs_carry_their_names():
    from oobleck_tpu.models import build_model
    from oobleck_tpu.serve.engine import DecodeEngine, PagedDecodeEngine

    model = build_model("gpt2-tiny", {})
    dense = DecodeEngine(model, slots=2, max_seq=32)
    paged = PagedDecodeEngine(model, lanes=2, max_seq=32, page_size=16,
                              num_pages=8)
    names = {
        "dense decode": dense._decode_fn.__name__,
        "dense prefill": dense._prefill_fn.__name__,
        "paged decode": paged._decode_fn.__name__,
        "paged prefill": paged._prefill_fn.__name__,
        "paged prefill tail": paged._prefill_head_fn.__name__,
        "verify": paged._get_verify_fn().__name__,
    }
    assert names == {
        "dense decode": "decode_step", "dense prefill": "prefill",
        "paged decode": "decode_step", "paged prefill": "prefill",
        "paged prefill tail": "prefill_tail", "verify": "verify_step"}


def test_routing_probe_program_carries_its_name():
    from oobleck_tpu.models import build_model, lfm2

    model = build_model("lfm2-moe-tiny", {})
    assert lfm2._probe_program(model).__name__ == "routing_probe_forward"



def test_the_delta_rule_s_kernels_carry_their_names(as_on_tpu):
    """`name=` on each `pallas_call` is what the chip's compiler names the
    custom call after (`%gdn_fwd.N`, `%gdn_bwd.N`) and what the benchmark's
    kernel metrics match (`benchmarks/layer_metrics/gdn_fwd_ms.json`,
    `gdn_bwd_ms.json`); both are built under the scope `gdn`, which
    `gdn_rule_ms` reads. Traced only: nothing runs."""
    import jax
    import jax.numpy as jnp

    from oobleck_tpu.ops.gdn import gated_delta_rule
    from tests.ops.programs import all_eqns

    shape = lambda *s: jax.ShapeDtypeStruct(s, jnp.float32)
    jaxpr = jax.make_jaxpr(jax.grad(
        lambda *a: jnp.sum(gated_delta_rule(*a, chunk=64)), argnums=(0, 1)))(
        shape(1, 128, 1, 128), shape(1, 128, 1, 128), shape(1, 128, 2, 128),
        shape(1, 128, 2), shape(1, 128, 2))
    calls = [e for e in all_eqns(jaxpr.jaxpr)
             if e.primitive.name == "pallas_call"]
    assert [e.params["name"] for e in calls] == ["gdn_fwd", "gdn_bwd"]
    for e in calls:
        scopes = [getattr(part, "name", None)
                  for part in e.source_info.name_stack.stack]
        assert scopes[-2:] == ["gdn", e.params["name"]], scopes


def test_the_selective_scan_s_and_differential_attention_s_kernels_carry_their_names(
        as_on_tpu):
    """`%sscan_fwd.N`, `%sscan_bwd.N` under the scope `sscan`, and
    `%flash_diff_fwd.N`, `%flash_diff_bwd_dqkv.N` (with a window:
    `flash_diff_swa_*`), never `%flash_fwd.`: what the benchmark's kernel
    metrics match (`benchmarks/layer_metrics/sscan_*`, `flash_diff_*`).
    Traced only: nothing runs."""
    import jax
    import jax.numpy as jnp

    from oobleck_tpu.ops import attention
    from oobleck_tpu.ops.sscan import selective_scan
    from tests.ops.programs import kernel_calls as calls

    shape = lambda *s: jax.ShapeDtypeStruct(s, jnp.float32)

    scan = calls(jax.grad(lambda *a: jnp.sum(selective_scan(*a)),
                          argnums=(0, 1)),
                 shape(1, 128, 128), shape(1, 128, 128), shape(128, 16),
                 shape(1, 128, 16), shape(1, 128, 16), shape(128))
    assert [e.params["name"] for e in scan] == ["sscan_fwd", "sscan_bwd"]
    for e in scan:
        scopes = [getattr(part, "name", None)
                  for part in e.source_info.name_stack.stack]
        assert scopes[-2:] == ["sscan", e.params["name"]], scopes
    for window, names in ((None, ["flash_diff_fwd", "flash_diff_bwd_dqkv"]),
                          (64, ["flash_diff_swa_fwd",
                                "flash_diff_swa_bwd_dqkv"])):
        diff = calls(jax.grad(lambda *a: sum(
            jnp.sum(x) for x in attention.differential_attention(
                *a, window=window)), argnums=(0, 4)),
            *[shape(1, 2, 128, 64)] * 4, shape(1, 2, 128, 128))
        assert sorted(e.params["name"] for e in diff) == sorted(names * 2)
