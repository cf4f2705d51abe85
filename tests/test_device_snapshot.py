"""The save's snapshot as a device copy (DESIGN.md §9, "Save path").

``CheckpointManager.save`` copies the state's jax.Array leaves on the
device into buffers nothing donates; the writer thread fetches that copy
to the host.  Where the device lacks room for the copy beside the
caller's reserve, the host snapshot is taken on the training thread, as
before the device copy existed.
"""
import threading

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.checkpoint import manager as manager_mod
from repro.checkpoint import serialization as ser
from repro.checkpoint.manager import CheckpointManager, copy_fits
from repro.configs import ARCHS, reduce_for_smoke
from repro.distributed.sharding import make_variant
from repro.launch.mesh import make_local_mesh
from repro.train.loop import train


def _state(n=1 << 16):
    k = jax.random.PRNGKey(4)
    return {"params": {"w": jax.random.normal(k, (n,)),
                       "b": jnp.arange(24, dtype=jnp.bfloat16)},
            "step": jnp.int32(9),
            "data": {"seed": np.int64(3), "cursor": np.int64(40)}}


def _template(st):
    return jax.tree.map(
        lambda x: jax.ShapeDtypeStruct(np.shape(x), np.asarray(x).dtype), st)


def _restored(mgr, st):
    out, _ = mgr.restore(_template(st))
    return out


def _assert_same(got, want):
    for x, y in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
        assert np.array_equal(np.asarray(x), np.asarray(y))
        if isinstance(y, jax.Array):
            assert x.dtype == y.dtype


def _capture_snapshots(monkeypatch):
    """Record every tree handed to ``ser.snapshot_to_host``."""
    seen = []
    real = ser.snapshot_to_host

    def spy(tree):
        seen.append(tree)
        return real(tree)

    monkeypatch.setattr(ser, "snapshot_to_host", spy)
    return seen


@pytest.mark.parametrize("async_write", [True, False])
def test_snapshot_leaves_sit_in_buffers_of_their_own(tmp_path, monkeypatch,
                                                     async_write):
    st = _state()
    seen = _capture_snapshots(monkeypatch)
    mgr = CheckpointManager(tmp_path, async_write=async_write)
    mgr.save(1, st)
    mgr.wait()
    (snap,) = seen
    arrays = [(x, y) for x, y in zip(jax.tree.leaves(st),
                                     jax.tree.leaves(snap))
              if isinstance(x, jax.Array)]
    assert len(arrays) == 3
    for x, y in arrays:
        assert isinstance(y, jax.Array)
        assert y.unsafe_buffer_pointer() != x.unsafe_buffer_pointer()
        assert y.sharding == x.sharding
    assert mgr.stats["snapshot_device_copies"] == 1
    assert mgr.stats["snapshot_host_fallbacks"] == 0


def test_device_copy_is_not_forwarded_by_the_copy_program():
    x = jnp.arange(1000, dtype=jnp.float32)
    y = ser.device_copy({"x": x, "n": np.int64(2)})
    assert y["x"].unsafe_buffer_pointer() != x.unsafe_buffer_pointer()
    assert isinstance(y["n"], np.ndarray) and int(y["n"]) == 2


def test_donating_step_after_save_leaves_the_checkpoint_whole(
        tmp_path, monkeypatch):
    """A step that donates the state, dispatched right after save() and
    run before the writer fetches anything, overwrites the state's
    buffers; the checkpoint reads back the state of the saved step bit
    for bit."""
    st = _state(1 << 20)
    saved = jax.tree.map(lambda x: np.asarray(x).copy(), st)
    stepped = threading.Event()
    real = ser.snapshot_to_host

    def after_the_step(tree):
        assert stepped.wait(30)
        return real(tree)

    monkeypatch.setattr(ser, "snapshot_to_host", after_the_step)
    step = jax.jit(lambda s: jax.tree.map(lambda x: x * 0 - 99, s),
                   donate_argnums=(0,))
    mgr = CheckpointManager(tmp_path)
    train_st = {"params": st["params"], "step": st["step"]}
    mgr.save(1, st)
    new = step(train_st)
    assert train_st["params"]["w"].is_deleted()      # donated
    jax.block_until_ready(new)
    stepped.set()
    mgr.wait()
    _assert_same(_restored(mgr, saved), saved)


def test_no_room_takes_the_host_snapshot_on_the_training_thread(
        tmp_path, monkeypatch):
    """With the device reporting no room, the host snapshot runs inside
    save() as before the device copy: of the state itself, taken before
    save() returns.  The counters tell the paths apart."""
    seen = _capture_snapshots(monkeypatch)
    st = _state()
    mgr = CheckpointManager(tmp_path, keep=0)
    mgr.save(1, st)                                   # room: device copy
    mgr.wait()
    monkeypatch.setattr(manager_mod, "_free_bytes", lambda d: 0)
    mgr.save(2, st)
    # taken before save() returned, from the state's own buffers
    assert len(seen) == 2 and seen[1] is st
    mgr.wait()
    assert mgr.stats["snapshot_device_copies"] == 1
    assert mgr.stats["snapshot_host_fallbacks"] == 1
    assert mgr.stats["saves"] == 2
    assert mgr.stats["snapshot_bytes"] == 2 * sum(
        x.nbytes for x in jax.tree.leaves(st) if isinstance(x, jax.Array))
    _assert_same(_restored(mgr, st), st)
    assert ser.load_manifest(tmp_path / "step_0000000002")["leaves"] \
        == ser.load_manifest(tmp_path / "step_0000000001")["leaves"]


def test_copy_fits_counts_each_device_against_its_room(monkeypatch):
    st = _state()
    need = sum(x.nbytes for x in jax.tree.leaves(st)
               if isinstance(x, jax.Array))
    free = {"n": None}
    monkeypatch.setattr(manager_mod, "_free_bytes", lambda d: free["n"])
    assert copy_fits(st)                              # no stats: copy
    free["n"] = need
    assert copy_fits(st)
    free["n"] = need - 1
    assert not copy_fits(st)
    assert copy_fits({"n": np.zeros(1 << 20)})        # nothing on a device


def test_free_bytes_reads_the_device_report():
    """The limit less the live buffers and less what loaded programs hold
    reserved; not the peak, which counts the previous save's copy."""
    class Dev:
        def __init__(self, stats):
            self.stats = stats

        def memory_stats(self):
            return self.stats

    assert manager_mod._free_bytes(Dev(None)) is None
    assert manager_mod._free_bytes(Dev({"bytes_in_use": 5})) is None
    assert manager_mod._free_bytes(
        Dev({"bytes_limit": 100, "bytes_in_use": 30,
             "peak_bytes_in_use": 90})) == 70
    assert manager_mod._free_bytes(
        Dev({"bytes_limit": 100, "bytes_in_use": 30, "bytes_reserved": 50,
             "peak_bytes_in_use": 90})) == 20


def test_sync_write_gives_the_async_manifest(tmp_path):
    st = _state()
    man = {}
    for mode in (True, False):
        mgr = CheckpointManager(tmp_path / str(mode), async_write=mode)
        mgr.save(5, st, meta={"k": 1})
        mgr.wait()
        m = ser.load_manifest(tmp_path / str(mode) / "step_0000000005")
        m["meta"].pop("time")
        man[mode] = m
    assert man[True] == man[False]


def test_train_saves_take_the_device_copy(tmp_path, monkeypatch):
    """Each save of train() hands the writer a device copy of the state,
    on a device with no memory report (the CPU)."""
    seen = _capture_snapshots(monkeypatch)
    cfg = reduce_for_smoke(ARCHS["smollm-135m"])
    res = train(cfg, make_local_mesh(n=1), make_variant("baseline"),
                n_steps=4, global_batch=2, seq_len=16, log_every=2, seed=1,
                ckpt_root=tmp_path / "ck", ckpt_every=2, keep=0)
    assert res.ckpt_stats["snapshot_device_copies"] == 2
    assert res.ckpt_stats["snapshot_host_fallbacks"] == 0
    assert len(seen) == 2
    final = jax.tree.leaves(res.state)
    last = jax.tree.leaves(seen[-1]["train"])
    for x, y in zip(final, last):
        assert y.unsafe_buffer_pointer() != x.unsafe_buffer_pointer()
        assert np.array_equal(np.asarray(x), np.asarray(y))
