"""Collective/compute overlap tests (parallel/overlap.py + the unified
overlap-mode train step in parallel/train.py).

Three layers of coverage:

  * unit — OverlapConfig knobs/env, bucketize edge cases, the per-leaf
    grad_sync_axes rule, and the chunked ppermute ring against lax.psum.
  * bucketization invariant — an exhaustive small-mesh sweep
    (data x fsdp x stage over {1,2}, plus two larger combos) proving the
    bucketed ring sync is numerically identical to a single psum per leaf
    (<= 1e-6 in f32) on real model grad shapes.
  * step parity — the unified check_rep=False shard_map step (explicit
    Megatron f/g backward) against the default three-phase path, each
    overlap arm (prefetch, double-buffered sends) against its plain
    counterpart, and the flash (pallas-interpret) attention against XLA
    through the full train step.

The engine-level overlap paths (deferred loss, zero-host-sync steady
state) live in tests/execution/test_overlap.py; this module is about the
collectives themselves.
"""

import functools
from dataclasses import replace

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import NamedSharding
from jax.sharding import PartitionSpec as P

from oobleck_tpu.models import build_model
from oobleck_tpu.parallel import (
    MeshShape,
    OverlapConfig,
    build_train_step,
    make_mesh,
    make_optimizer,
)
from oobleck_tpu.parallel import overlap as ovl
from oobleck_tpu.parallel.mesh import ALL_AXES

SEQ = 32
BATCH = 32
NUM_MB = 4


# --------------------------------------------------------------------------
# config


def test_config_validates_grad_sync():
    with pytest.raises(ValueError, match="grad_sync"):
        OverlapConfig(grad_sync="allreduce")


def test_config_validates_bucket_bytes():
    with pytest.raises(ValueError, match="bucket_bytes"):
        OverlapConfig(bucket_bytes=0)


def test_config_from_env(monkeypatch):
    monkeypatch.setenv("OOBLECK_OVERLAP", "1")
    monkeypatch.setenv("OOBLECK_OVERLAP_BUCKET_MB", "0.5")
    monkeypatch.setenv("OOBLECK_OVERLAP_PREFETCH", "0")
    monkeypatch.setenv("OOBLECK_OVERLAP_DB_SENDS", "true")
    monkeypatch.setenv("OOBLECK_OVERLAP_GRAD_SYNC", "psum")
    monkeypatch.setenv("OOBLECK_OVERLAP_XLA_FLAGS", "no")
    cfg = OverlapConfig.from_env()
    assert cfg.enabled
    assert cfg.bucket_bytes == 512 * 1024
    assert not cfg.prefetch_fsdp
    assert cfg.double_buffer_sends
    assert cfg.grad_sync == "psum"
    assert not cfg.xla_flags


def test_execution_args_env_overrides(monkeypatch):
    from oobleck_tpu.config import ExecutionArguments

    monkeypatch.setenv("OOBLECK_OVERLAP", "1")
    monkeypatch.setenv("OOBLECK_OVERLAP_BUCKET_MB", "2")
    monkeypatch.setenv("OOBLECK_OVERLAP_DB_SENDS", "1")
    ex = ExecutionArguments()
    ex.apply_durable_env_overrides()
    cfg = ex.overlap_config()
    assert cfg.enabled
    assert cfg.bucket_bytes == 2 * 1024 * 1024
    assert cfg.double_buffer_sends
    assert cfg.prefetch_fsdp  # untouched default


def test_apply_xla_overlap_flags_idempotent():
    env = {"XLA_FLAGS": "--xla_foo=1"}
    out1 = ovl.apply_xla_overlap_flags(env=env)
    assert "--xla_foo=1" in out1
    for flag in ovl.XLA_OVERLAP_FLAGS:
        assert flag in out1
    out2 = ovl.apply_xla_overlap_flags(env=env)
    assert out2 == out1  # no duplication on re-apply


def test_apply_xla_overlap_flags_respects_disabled():
    env = {"XLA_FLAGS": ""}
    assert ovl.apply_xla_overlap_flags(OverlapConfig(enabled=False),
                                       env=env) == ""
    assert ovl.apply_xla_overlap_flags(
        OverlapConfig(enabled=True, xla_flags=False), env=env) == ""
    assert env["XLA_FLAGS"] == ""


# --------------------------------------------------------------------------
# bucketize


def test_bucketize_giant_leaf_rides_alone():
    assert ovl.bucketize([10, 100, 10], bucket_bytes=32) == [[0], [1], [2]]


def test_bucketize_groups_tiny_leaves_uneven_tail():
    assert ovl.bucketize([4] * 10, bucket_bytes=16) == [
        [0, 1, 2, 3], [4, 5, 6, 7], [8, 9]]


def test_bucketize_never_mixes_dtypes():
    f32, bf16 = jnp.dtype(jnp.float32), jnp.dtype(jnp.bfloat16)
    assert ovl.bucketize([4, 4, 4], bucket_bytes=64,
                         dtypes=[f32, bf16, bf16]) == [[0], [1, 2]]


def test_bucketize_is_an_in_order_partition():
    sizes = [3, 900, 1, 1, 50, 7]
    buckets = ovl.bucketize(sizes, bucket_bytes=55)
    assert [i for b in buckets for i in b] == list(range(len(sizes)))


# --------------------------------------------------------------------------
# grad_sync_axes


def test_grad_sync_axes_unsharded_leaf():
    sizes = {"stage": 2, "data": 2, "fsdp": 1, "seq": 1, "tensor": 2}
    assert ovl.grad_sync_axes(P(None, None), sizes) == ("stage", "data")


def test_grad_sync_axes_excludes_sharded_and_tensor():
    sizes = {"stage": 2, "data": 2, "fsdp": 2, "seq": 2, "tensor": 2}
    # fsdp-sharded leaf: its reduction is the all_gather transpose; tensor
    # never appears (completed by the Megatron f/g pair in the loss).
    assert ovl.grad_sync_axes(P("fsdp", "tensor"), sizes) == (
        "stage", "data", "seq")
    assert ovl.grad_sync_axes(P(("stage", "fsdp"), None), sizes) == (
        "data", "seq")


def test_grad_sync_axes_size_one_axes_dropped():
    sizes = {"stage": 1, "data": 8, "fsdp": 1, "seq": 1, "tensor": 1}
    assert ovl.grad_sync_axes(P(), sizes) == ("data",)


# --------------------------------------------------------------------------
# ring all-reduce vs psum (unit level)


def _shard_map(fn, mesh, in_specs, out_specs):
    return jax.shard_map(fn, mesh=mesh, in_specs=in_specs,
                         out_specs=out_specs, axis_names=set(ALL_AXES),
                         check_vma=False)


def test_ring_all_reduce_matches_psum_with_padding(devices8):
    # size 13 is not divisible by 8 devices: exercises the pad/unpad path.
    mesh = make_mesh(MeshShape(data=8))
    x = jax.random.normal(jax.random.PRNGKey(0), (8, 13), jnp.float32)

    def body(x_loc):
        ring = ovl.ring_all_reduce(x_loc[0], "data", 8)
        ref = jax.lax.psum(x_loc[0], "data")
        return (ring - ref)[None]

    diff = _shard_map(body, mesh, (P("data"),), P("data"))(x)
    assert float(jnp.max(jnp.abs(diff))) <= 1e-6


def test_bucketed_ring_matches_per_leaf_psum(devices8):
    mesh = make_mesh(MeshShape(data=8))
    keys = jax.random.split(jax.random.PRNGKey(1), 5)
    shapes = [(3,), (17, 5), (2, 2, 2), (1,), (40,)]
    leaves = [jax.random.normal(k, s, jnp.float32)
              for k, s in zip(keys, shapes)]

    def body(*ls):
        ring = ovl.bucketed_ring_all_reduce(list(ls), "data", 8,
                                            bucket_bytes=64)
        ref = [jax.lax.psum(l, "data") for l in ls]
        return functools.reduce(
            jnp.maximum,
            [jnp.max(jnp.abs(r - f)) for r, f in zip(ring, ref)])

    diff = _shard_map(body, mesh, tuple(P() for _ in leaves), P())(*leaves)
    assert float(diff) <= 1e-6


# --------------------------------------------------------------------------
# bucketization invariant: sync_grads ring == psum on real grad shapes,
# exhaustive small-mesh sweep


_SWEEP = [
    MeshShape(data=d, fsdp=f, stage=s)
    for d in (1, 2) for f in (1, 2) for s in (1, 2)
] + [MeshShape(data=4, fsdp=2), MeshShape(stage=2, data=2, fsdp=2)]


@pytest.mark.parametrize("shape", _SWEEP,
                         ids=[f"d{s.data}f{s.fsdp}s{s.stage}" for s in _SWEEP])
def test_sync_grads_ring_equals_psum_per_leaf(devices8, shape):
    """Bucketed ring sync == single psum per leaf, <= 1e-6, over every
    data x fsdp x stage factorization of the small mesh, on real model
    param/grad shapes (tensor is never synced here by construction)."""
    model = build_model("gpt2-tiny", {"remat": True, "dtype": jnp.float32})
    mesh = make_mesh(shape)
    specs = model.param_specs(stacked=True)
    axis_sizes = dict(mesh.shape)
    params = model.init_params(jax.random.PRNGKey(0))
    # Random full-rank tree standing in for grads (same treedef/specs).
    fake_grads = jax.tree.map(
        lambda x: jax.random.normal(jax.random.PRNGKey(x.size % 97),
                                    x.shape, jnp.float32), params)
    fake_grads = jax.device_put(
        fake_grads,
        jax.tree.map(lambda s: NamedSharding(mesh, s), specs,
                     is_leaf=lambda x: isinstance(x, P)))

    def body(g):
        ring = ovl.sync_grads(g, specs, axis_sizes, data_impl="ring",
                              bucket_bytes=1 << 12)
        ref = ovl.sync_grads(g, specs, axis_sizes, data_impl="psum")
        diffs = jax.tree.map(lambda a, b: jnp.max(jnp.abs(a - b)), ring, ref)
        return jax.tree.reduce(jnp.maximum, diffs)

    diff = jax.jit(_shard_map(body, mesh, (specs,), P()))(fake_grads)
    assert float(diff) <= 1e-6, shape


# --------------------------------------------------------------------------
# full-step parity


def _grads_for(shape, overlap=None, model_args=None, batch=BATCH,
               num_mb=NUM_MB):
    model = build_model(
        "gpt2-tiny", {"remat": True, "dtype": jnp.float32,
                      **(model_args or {})})
    mesh = make_mesh(shape)
    init_fn, step = build_train_step(
        model, mesh, num_microbatches=num_mb,
        optimizer=make_optimizer(learning_rate=1e-3, warmup_steps=2),
        overlap=overlap)
    state = init_fn(jax.random.PRNGKey(0))
    tokens = jax.random.randint(jax.random.PRNGKey(1), (batch, SEQ), 0,
                                model.config.vocab_size, dtype=jnp.int32)
    loss, grads = step.loss_and_grads(state.params, *step.prepare(tokens))
    return float(loss), jax.device_get(grads)


def _max_diff(ga, gb):
    return max(float(np.max(np.abs(np.asarray(a) - np.asarray(b))))
               for a, b in zip(jax.tree.leaves(ga), jax.tree.leaves(gb)))


@pytest.mark.parametrize("shape", [
    MeshShape(data=8),
    MeshShape(fsdp=2, data=2),
    MeshShape(stage=2, fsdp=2, tensor=2),
], ids=["d8", "f2d2", "s2f2t2"])
def test_overlap_step_matches_default(devices8, shape):
    """The unified explicit-backward step (psum arm) reproduces the default
    path's loss AND per-leaf grads; the ring arm then matches the psum arm
    to 1e-6 (bucketed collective == spec-transpose psum)."""
    loss_d, g_default = _grads_for(shape)
    loss_p, g_psum = _grads_for(
        shape, OverlapConfig(enabled=True, grad_sync="psum"))
    loss_r, g_ring = _grads_for(
        shape, OverlapConfig(enabled=True, grad_sync="ring",
                             bucket_bytes=1 << 14))
    assert abs(loss_p - loss_d) <= 2e-4
    assert _max_diff(g_psum, g_default) <= 2e-4
    assert abs(loss_r - loss_p) <= 1e-6
    assert _max_diff(g_ring, g_psum) <= 1e-6


def test_prefetch_arm_parity(devices8):
    cfg = OverlapConfig(enabled=True, grad_sync="psum", prefetch_fsdp=False)
    base = _grads_for(MeshShape(fsdp=2, data=4), cfg)
    pref = _grads_for(MeshShape(fsdp=2, data=4),
                      replace(cfg, prefetch_fsdp=True))
    assert abs(base[0] - pref[0]) <= 1e-6
    assert _max_diff(base[1], pref[1]) <= 1e-6


def test_double_buffer_sends_parity(devices8):
    cfg = OverlapConfig(enabled=True, grad_sync="psum")
    base = _grads_for(MeshShape(stage=4, data=2), cfg)
    db = _grads_for(MeshShape(stage=4, data=2),
                    replace(cfg, double_buffer_sends=True))
    assert abs(base[0] - db[0]) <= 1e-6
    assert _max_diff(base[1], db[1]) <= 1e-6


# --------------------------------------------------------------------------
# FSDP gather prefetch mechanics


def test_prefetched_block_scan_matches_sequential_loop():
    """The prefetch must not skew layer order: iteration i applies layer i
    (from the carry) while gathering layer i+1."""
    L, d = 3, 4
    stacked = {"w": (jnp.arange(L * d * d, dtype=jnp.float32)
                     .reshape(L, d, d) / 100.0)}
    h0 = jnp.ones((2, d), jnp.float32)

    out = ovl.prefetched_block_scan(
        lambda p, h: jnp.tanh(h @ p["w"]), lambda bp: bp, stacked, h0, L)
    ref = h0
    for i in range(L):
        ref = jnp.tanh(ref @ stacked["w"][i])
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=1e-6)


def test_prefetch_carry_holds_exactly_one_gathered_layer():
    """The double-buffer window invariant: the scan carry is (activation,
    ONE gathered layer) — never two, never the whole stack."""
    L, d = 3, 4
    stacked = {"w": jnp.zeros((L, d, d)), "b": jnp.zeros((L, d))}
    h0 = jnp.ones((2, d), jnp.float32)
    carry = ovl.prefetch_carry_shapes(lambda bp: bp, stacked, h0)
    assert isinstance(carry, tuple) and len(carry) == 2
    assert carry[0].shape == h0.shape
    # One layer: stacked treedef with the leading (layer) dim dropped.
    assert carry[1]["w"].shape == (d, d)
    assert carry[1]["b"].shape == (d,)
    assert set(carry[1]) == {"w", "b"}


def test_fsdp_gather_block_restores_full_leaves(devices8):
    """Inside the mesh, the gather returns every fsdp-sharded leaf at full
    size (== the replicated original) and passes unsharded leaves through."""
    mesh = make_mesh(MeshShape(fsdp=2, data=4))
    specs = {"w": P("fsdp", None), "b": P()}
    w = jax.random.normal(jax.random.PRNGKey(0), (8, 6), jnp.float32)
    b = jax.random.normal(jax.random.PRNGKey(1), (6,), jnp.float32)

    def body(p, w_full):
        g = ovl.fsdp_gather_block(p, specs, "fsdp")
        assert g["w"].shape == (8, 6)  # local (4, 6) shard gathered back
        assert g["b"].shape == (6,)
        return jnp.maximum(jnp.max(jnp.abs(g["w"] - w_full)),
                           jnp.max(jnp.abs(g["b"] - p["b"])))

    diff = _shard_map(body, mesh, ({"w": P("fsdp"), "b": P()}, P()), P())(
        {"w": w, "b": b}, w)
    assert float(diff) == 0.0


# --------------------------------------------------------------------------
# flash attention through the train step


def test_flash_train_step_matches_xla():
    """attention_impl='pallas' (interpret mode on CPU) through the FULL
    fused step: forward loss and every grad leaf match the XLA attention
    path. Runs in the overlap-mode step — pallas_call has no replication
    rule, so only the check_rep=False unified shard_map can host it."""
    shape = MeshShape(data=1)
    cfg = OverlapConfig(enabled=True, grad_sync="psum")
    loss_x, g_x = _grads_for(shape, cfg,
                             model_args={"attention_impl": "xla"},
                             batch=8, num_mb=2)
    loss_p, g_p = _grads_for(shape, cfg,
                             model_args={"attention_impl": "pallas"},
                             batch=8, num_mb=2)
    assert abs(loss_x - loss_p) <= 2e-4
    assert _max_diff(g_x, g_p) <= 2e-4


@pytest.mark.slow
def test_flash_train_step_matches_xla_alibi():
    """Same, with ALiBi slopes — the in-kernel bias generation path."""
    shape = MeshShape(data=1)
    cfg = OverlapConfig(enabled=True, grad_sync="psum")
    args = {"position_embedding": "alibi"}
    loss_x, g_x = _grads_for(
        shape, cfg, model_args={**args, "attention_impl": "xla"},
        batch=8, num_mb=2)
    loss_p, g_p = _grads_for(
        shape, cfg, model_args={**args, "attention_impl": "pallas"},
        batch=8, num_mb=2)
    assert abs(loss_x - loss_p) <= 2e-4
    assert _max_diff(g_x, g_p) <= 2e-4


def test_pallas_ok_drives_auto_selection(monkeypatch):
    """`ops/kernel.on_tpu` is the single policy point: flipping it flips
    BOTH the flash and the paged 'auto' resolutions."""
    from oobleck_tpu.ops import attention as attn
    from oobleck_tpu.ops import kernel
    from oobleck_tpu.ops import paged_attention as paged
    from oobleck_tpu.ops.flash import flash_attention

    attn.select_attention_impl.cache_clear()
    paged._select_paged_impl.cache_clear()
    try:
        monkeypatch.setattr(kernel, "on_tpu", lambda: True)
        assert attn.select_attention_impl("auto") is flash_attention
        assert paged._select_paged_impl("auto") is paged._paged_decode_pallas

        attn.select_attention_impl.cache_clear()
        paged._select_paged_impl.cache_clear()
        monkeypatch.setattr(kernel, "on_tpu", lambda: False)
        assert attn.select_attention_impl("auto") is attn._xla_causal_attention
        assert paged._select_paged_impl("auto") is paged._paged_decode_xla
    finally:
        attn.select_attention_impl.cache_clear()
        paged._select_paged_impl.cache_clear()


# --------------------------------------------------------------------------
# measurement helpers


def test_comm_hidden_fraction_bounds():
    assert ovl.comm_hidden_fraction(1.25, 1.0, 0.5) == 0.5  # half hidden
    assert ovl.comm_hidden_fraction(1.0, 1.0, 0.0) == 0.0  # no comm at all
    assert ovl.comm_hidden_fraction(0.9, 1.0, 0.5) == 1.0  # clamped high
    assert ovl.comm_hidden_fraction(2.0, 1.0, 0.5) == 0.0  # clamped low


def test_effective_comm():
    assert ovl.effective_comm(3.0, 2.0, 0.0) == 3.0  # serialized
    assert ovl.effective_comm(3.0, 2.0, 1.0) == 1.0  # comm - compute
    assert ovl.effective_comm(1.0, 2.0, 1.0) == 0.0  # never negative
