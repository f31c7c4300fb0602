"""Durable-state plane (oobleck_tpu/ckpt): sharded capture, atomic
manifest commit, crash-consistent restore, retention, and the async
writer's stall discipline. The reference has no checkpointing at all, so
the coverage model is adversarial: every torn/corrupt on-disk state a
crash can produce must be invisible to resume."""

import json
import threading
import time

import numpy as np
import pytest

import jax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from oobleck_tpu import ckpt
from oobleck_tpu.ckpt import manifest as mf


def _state():
    import ml_dtypes

    params = {
        0: {"w": np.arange(24.0, dtype=np.float32).reshape(4, 6),
            "scalar": np.float32(3.5),
            "bf16": np.arange(6, dtype=ml_dtypes.bfloat16).reshape(2, 3),
            "nested": {"lst": [np.ones(2), np.zeros(3)]}},
        3: np.arange(4.0),  # a bare-leaf layer (no tree structure)
    }
    opt = {0: ({"mu": np.zeros((4, 6))}, np.int32(7)), 3: ()}
    return params, opt


def test_roundtrip_trees_dtypes_meta(tmp_path):
    import ml_dtypes

    params, opt = _state()
    plane = ckpt.DurableStatePlane(tmp_path, asynchronous=False)
    plane.save(step=7, params=params, opt_state=opt,
               num_iterations_done=5, epoch=1, extra={"model_name": "t"})
    assert plane.last_durable_step == 7
    pay = ckpt.restore_latest(tmp_path)
    assert pay["meta"] == {"step": 7, "num_iterations_done": 5, "epoch": 1,
                           "model_name": "t"}
    np.testing.assert_array_equal(pay["params"][0]["w"], params[0]["w"])
    assert pay["params"][0]["bf16"].dtype == ml_dtypes.bfloat16
    np.testing.assert_array_equal(pay["params"][0]["bf16"],
                                  params[0]["bf16"])
    assert float(pay["params"][0]["scalar"]) == 3.5
    np.testing.assert_array_equal(pay["params"][0]["nested"]["lst"][1],
                                  np.zeros(3))
    np.testing.assert_array_equal(pay["params"][3], np.arange(4.0))
    # opt leaves stored flat; a leafless state restores as an empty list,
    # not a missing layer.
    assert len(pay["opt"][0]) == 2 and int(pay["opt"][0][1]) == 7
    assert pay["opt"][3] == []


def test_sharded_array_writes_pieces_and_reassembles(tmp_path, devices8):
    """A device-sharded array must be written as per-shard pieces with
    global indices (the mechanism that makes cross-host FSDP state
    checkpointable) and reassemble bitwise."""
    mesh = Mesh(np.array(devices8).reshape(4, 2), ("x", "y"))
    arr = np.arange(64, dtype=np.float32).reshape(8, 8)
    sharded = jax.device_put(arr, NamedSharding(mesh, P("x", "y")))
    plane = ckpt.DurableStatePlane(tmp_path, asynchronous=False)
    plane.save(step=1, params={0: {"w": sharded}}, opt_state={0: ()})
    pm = json.loads((tmp_path / "step_1" / "manifest-00000.json").read_text())
    pieces = [e for e in pm["entries"] if e["key"] == "p/0/w"]
    assert len(pieces) == 8  # one per distinct shard, each with an index
    assert all(e["index"] is not None for e in pieces)
    pay = ckpt.restore_latest(tmp_path)
    np.testing.assert_array_equal(pay["params"][0]["w"], arr)


def test_async_save_survives_buffer_donation(tmp_path):
    """The captured state must be staged to host COPIES before submit
    returns: the engine's train step is jitted with donate_argnums, so
    the captured device buffers are reused by XLA on the very next step.
    A reference (or a zero-copy np view of an XLA CPU buffer) aliases
    donated memory — use-after-free corruption or SIGSEGV, observed in
    the multiprocess elastic test's post-recovery world."""
    from functools import partial

    @partial(jax.jit, donate_argnums=(0,))
    def bump(tree):
        return jax.tree.map(lambda x: x + 1.0, tree)

    state = {0: {"w": jax.numpy.arange(1 << 16, dtype=jax.numpy.float32)}}
    expected = np.array(state[0]["w"])
    plane = ckpt.DurableStatePlane(tmp_path, asynchronous=True)
    plane.save(step=1, params=state, opt_state={0: ()})
    for _ in range(3):
        state = bump(state)  # donates (and lets XLA reuse) old buffers
    assert plane.flush(timeout=60)
    pay = ckpt.restore_latest(tmp_path)
    np.testing.assert_array_equal(pay["params"][0]["w"], expected)
    plane.close()


def test_multi_process_commit_merges_manifests(tmp_path):
    """Two writers (world_size=2) each contribute disjoint layers; rank 0
    commits only after BOTH manifests exist, and restore sees the union."""
    w0 = ckpt.DurableStatePlane(tmp_path, process_index=0, world_size=2)
    w1 = ckpt.DurableStatePlane(tmp_path, process_index=1, world_size=2)
    w0.save(step=4, params={0: {"w": np.ones(3)}}, opt_state={0: ()})
    w1.save(step=4, params={1: {"w": np.full(3, 2.0)}}, opt_state={1: ()})
    assert w1.flush(timeout=30) and w0.flush(timeout=30)
    assert w0.last_durable_step == 4
    gm = json.loads((tmp_path / "step_4" / mf.GLOBAL_MANIFEST).read_text())
    assert len(gm["processes"]) == 2
    pay = ckpt.restore_latest(tmp_path)
    assert set(pay["params"]) == {0, 1}
    np.testing.assert_array_equal(pay["params"][1]["w"], np.full(3, 2.0))
    w0.close(), w1.close()


def test_commit_times_out_without_peer(tmp_path):
    """Rank 0 must NOT commit a step whose peers never wrote (a peer died
    mid-checkpoint): the dir stays uncommitted and restore ignores it."""
    w0 = ckpt.DurableStatePlane(tmp_path, process_index=0, world_size=2,
                                commit_timeout=0.2)
    w0.save(step=9, params={0: {"w": np.ones(2)}}, opt_state={0: ()})
    w0.flush(timeout=30)
    assert not (tmp_path / "step_9" / mf.GLOBAL_MANIFEST).exists()
    assert w0.last_durable_step == -1
    assert ckpt.restore_latest(tmp_path, quarantine_bad=False) is None
    w0.close()


def test_restore_skips_uncommitted_and_corrupt_with_quarantine(tmp_path):
    params, opt = _state()
    plane = ckpt.DurableStatePlane(tmp_path, asynchronous=False)
    for s in (2, 4):
        plane.save(step=s, params=params, opt_state=opt)
    # Corrupt the newest step's shard data (bit flip after commit).
    f = tmp_path / "step_4" / "shards-00000.npz"
    blob = bytearray(f.read_bytes())
    blob[140] ^= 0xFF
    f.write_bytes(bytes(blob))
    # And fake a crash mid-write at a later step: dir without MANIFEST.
    (tmp_path / "step_6").mkdir()
    (tmp_path / "step_6" / "shards-00000.npz").write_bytes(b"partial")

    pay = ckpt.restore_latest(tmp_path)
    assert pay["meta"]["step"] == 2  # newest COMPLETE wins
    assert not (tmp_path / "step_6").exists()
    assert not (tmp_path / "step_4").exists()
    quarantined = sorted(p.name for p in (tmp_path / "quarantine").iterdir())
    assert any(n.startswith("step_6.uncommitted") for n in quarantined)
    assert any(n.startswith("step_4.corrupt") for n in quarantined)


def test_keep_last_k_gc(tmp_path):
    params, opt = _state()
    plane = ckpt.DurableStatePlane(tmp_path, asynchronous=False, keep_last=2)
    for s in (1, 2, 3, 4):
        plane.save(step=s, params=params, opt_state=opt)
    names = sorted(p.name for p in tmp_path.iterdir()
                   if p.name.startswith("step_"))
    assert names == ["step_3", "step_4"]
    # GC never touches the quarantine evidence dir.
    assert ckpt.restore_latest(tmp_path)["meta"]["step"] == 4


def test_async_writer_at_most_one_in_flight_and_cheaper_than_sync(tmp_path):
    """The async submit returns after enqueue (stall = drain + capture);
    the sync baseline pays capture + write + commit inline. Here we assert
    the direction and the at-most-one-in-flight discipline."""
    big = {0: {"w": np.zeros((512, 1024), np.float32)}}  # 2 MB
    opt = {0: (np.zeros((512, 1024), np.float32),)}

    sync = ckpt.DurableStatePlane(tmp_path / "sync", asynchronous=False)
    sync_stalls = [sync.save(step=s, params=big, opt_state=opt)
                   for s in range(1, 5)]

    plane = ckpt.DurableStatePlane(tmp_path / "async", asynchronous=True)
    async_stalls = []
    for s in range(1, 5):
        async_stalls.append(plane.save(step=s, params=big, opt_state=opt))
        time.sleep(np.median(sync_stalls))  # mimic steps between saves
    assert plane.flush(timeout=30)
    assert plane.last_durable_step == 4
    assert np.median(async_stalls) < np.median(sync_stalls)
    # Back-to-back submits serialize: the second blocks until the first
    # drains, so the writer never holds two snapshots.
    t0 = time.perf_counter()
    plane.save(step=10, params=big, opt_state=opt)
    plane.save(step=11, params=big, opt_state=opt)
    assert plane.flush(timeout=30)
    assert (tmp_path / "async" / "step_10" / mf.GLOBAL_MANIFEST).exists()
    assert (tmp_path / "async" / "step_11" / mf.GLOBAL_MANIFEST).exists()
    assert time.perf_counter() - t0 < 30
    plane.close()


def test_resave_same_step_overwrites_cleanly(tmp_path):
    """A restart that re-saves an existing step (restore at N, checkpoint
    at N again) must supersede the old dir, not merge with it."""
    plane = ckpt.DurableStatePlane(tmp_path, asynchronous=False)
    plane.save(step=5, params={0: {"w": np.zeros(4)}}, opt_state={0: ()})
    plane.save(step=5, params={0: {"w": np.ones(4)}}, opt_state={0: ()})
    pay = ckpt.restore_latest(tmp_path)
    np.testing.assert_array_equal(pay["params"][0]["w"], np.ones(4))


def test_slash_in_tree_key_rejected():
    from oobleck_tpu.ckpt import snapshot as snp

    with pytest.raises(ValueError, match="unserializable"):
        snp.capture_layers({0: {"a/b": np.ones(2)}}, {0: ()}, step=1,
                           meta={})


def test_preemption_hook_noop_off_main_thread(tmp_path):
    plane = ckpt.DurableStatePlane(tmp_path)
    err = []
    t = threading.Thread(target=lambda: (
        err.append(None) if plane.install_preemption_hook() is None else None))
    t.start()
    t.join()
    assert err == [None]  # no exception escaped
    plane.close()
