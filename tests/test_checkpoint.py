"""Checkpoint manager + serialization + data pipeline + train-loop C/R."""
import json
import threading
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.checkpoint.chunkstore import ChunkStore
from repro.checkpoint.manager import CheckpointManager
from repro.checkpoint import serialization as ser
from repro.data.pipeline import TokenPipeline


def _state(seed=0):
    k = jax.random.PRNGKey(seed)
    return {
        "params": {"w": jax.random.normal(k, (32, 16)),
                   "b": jnp.zeros((16,), jnp.bfloat16)},
        "step": jnp.int32(7),
        "nested": [jnp.arange(5), {"x": jnp.float32(1.5)}],
    }


def test_save_restore_roundtrip_exact(tmp_path):
    mgr = CheckpointManager(tmp_path, keep=2)
    st = _state()
    mgr.save(10, st)
    mgr.wait()
    out, meta = mgr.restore(jax.eval_shape(lambda: _state()))
    assert meta["step"] == 10
    for a, b in zip(jax.tree.leaves(st), jax.tree.leaves(out)):
        assert np.array_equal(np.asarray(a), np.asarray(b)), "bitwise restore"
        assert np.asarray(a).dtype == np.asarray(b).dtype


@pytest.mark.parametrize("codec", ["zlib", "zstd"])
def test_shard_codec_roundtrip(tmp_path, codec):
    """Both shard codecs round-trip bitwise; the manifest records which one
    wrote the checkpoint so the reader never has to guess."""
    st = _state()
    ser.save_shards(tmp_path, st, codec=codec)
    man = ser.load_manifest(tmp_path)
    assert man["codec"] == codec
    assert ser.validate(tmp_path)
    out = ser.restore_tree(tmp_path, jax.eval_shape(lambda: _state()))
    for a, b in zip(jax.tree.leaves(st), jax.tree.leaves(out)):
        assert np.array_equal(np.asarray(a), np.asarray(b))


def test_async_write_is_donation_safe(tmp_path):
    """The host snapshot is copied BEFORE save() returns; mutating (or
    donating) the arrays afterwards must not corrupt the checkpoint."""
    mgr = CheckpointManager(tmp_path, keep=2, async_write=True)
    x = np.arange(1000, dtype=np.float32)
    st = {"x": jnp.asarray(x)}
    mgr.save(1, st)
    st["x"] = st["x"] * 0 - 99     # simulate donation/reuse immediately
    mgr.wait()
    out, _ = mgr.restore({"x": jax.ShapeDtypeStruct((1000,), jnp.float32)})
    assert np.array_equal(np.asarray(out["x"]), x)


def _chunks_of(ckpt_dir):
    man = ser.load_manifest(ckpt_dir)
    return set(ser.manifest_chunks(man))


def test_corruption_detected_and_skipped(tmp_path):
    mgr = CheckpointManager(tmp_path, keep=5)
    mgr.save(1, _state(1)); mgr.wait()
    mgr.save(2, _state(2)); mgr.wait()
    # truncate a chunk only step 2 references (shared chunks would
    # invalidate both steps — content addressing really does share them)
    newest = tmp_path / "step_0000000002"
    only2 = _chunks_of(newest) - _chunks_of(tmp_path / "step_0000000001")
    assert only2, "differently-seeded states must have some unique chunks"
    victim = tmp_path / "chunks" / sorted(only2)[0]
    victim.write_bytes(victim.read_bytes()[:-3])
    assert not ser.validate(newest)          # manifest-only fast path
    assert mgr.latest_valid().name == "step_0000000001"
    out, meta = mgr.restore(jax.eval_shape(lambda: _state()))
    assert meta["step"] == 1


def test_restore_falls_back_past_size_preserving_bitflip(tmp_path):
    """A same-size bit flip passes manifest-only validation; the digest
    check catches it during the restore READ and the auto-pick falls back
    to the next older valid checkpoint — the pre-chunk-store 'corrupt
    ones skipped' guarantee, preserved."""
    mgr = CheckpointManager(tmp_path, keep=5)
    mgr.save(1, _state(1)); mgr.wait()
    mgr.save(2, _state(2)); mgr.wait()
    only2 = _chunks_of(tmp_path / "step_0000000002") \
        - _chunks_of(tmp_path / "step_0000000001")
    victim = tmp_path / "chunks" / sorted(only2)[0]
    blob = bytearray(victim.read_bytes())
    blob[len(blob) // 2] ^= 0xFF
    victim.write_bytes(bytes(blob))
    assert ser.validate(tmp_path / "step_0000000002")   # fast path fooled
    out, meta = mgr.restore(jax.eval_shape(lambda: _state()))
    assert meta["step"] == 1                            # ...restore wasn't
    for a, b in zip(jax.tree.leaves(_state(1)), jax.tree.leaves(out)):
        assert np.array_equal(np.asarray(a), np.asarray(b))


def test_device_error_during_restore_propagates(tmp_path, monkeypatch):
    """Only reader-side corruption skips a checkpoint.  A device error
    while placing a restored leaf (out of memory, a lost chip) must reach
    the caller: swallowing it would skip every checkpoint and silently
    restart training from step 0.  A digest mismatch is still skipped in
    favour of the next older checkpoint."""
    from repro.checkpoint import resharding
    mgr = CheckpointManager(tmp_path, keep=5)
    mgr.save(1, _state(1)); mgr.wait()
    mgr.save(2, _state(2)); mgr.wait()
    tpl = jax.eval_shape(lambda: _state())

    def oom(*a, **k):
        raise jax.errors.JaxRuntimeError("RESOURCE_EXHAUSTED: out of memory")

    with monkeypatch.context() as mp:
        mp.setattr(resharding.jax, "device_put", oom)
        with pytest.raises(jax.errors.JaxRuntimeError, match="RESOURCE_EXH"):
            mgr.restore(tpl)
    assert mgr.stats["restores"] == 0

    only2 = _chunks_of(tmp_path / "step_0000000002") \
        - _chunks_of(tmp_path / "step_0000000001")
    victim = tmp_path / "chunks" / sorted(only2)[0]
    blob = bytearray(victim.read_bytes())
    blob[len(blob) // 2] ^= 0xFF
    victim.write_bytes(bytes(blob))
    out, meta = mgr.restore(tpl)
    assert meta["step"] == 1
    for a, b in zip(jax.tree.leaves(_state(1)), jax.tree.leaves(out)):
        assert np.array_equal(np.asarray(a), np.asarray(b))


def test_bitflip_detected_by_deep_validate_and_restore(tmp_path):
    """A same-size bit flip slips past the manifest-only fast path (by
    design — it never reads blobs); deep validation and restore both catch
    it via the content digest."""
    mgr = CheckpointManager(tmp_path, keep=5)
    mgr.save(1, _state(1)); mgr.wait()
    d = tmp_path / "step_0000000001"
    victim = tmp_path / "chunks" / sorted(_chunks_of(d))[0]
    blob = bytearray(victim.read_bytes())
    blob[len(blob) // 2] ^= 0xFF
    victim.write_bytes(bytes(blob))
    assert ser.validate(d)                   # fast path: size unchanged
    assert not ser.validate(d, deep=True)    # deep: digest mismatch
    with pytest.raises(Exception):
        ser.restore_tree(d, jax.eval_shape(lambda: _state()))


def test_byte_shuffle_filter_compresses_floats_and_roundtrips(tmp_path):
    """Multi-byte float shards are byte-transposed before the probe when
    that wins: the near-constant sign/exponent bytes group together and
    chunks that used to be stored raw now compress.  The filter is
    recorded per chunk (manifest codec field + extension) and the digest
    still covers the UNSHUFFLED bytes, so dedup identity and
    self-validation are unchanged."""
    rng = np.random.default_rng(7)
    st = {
        # uniform floats: plain deflate ~1.0 (raw before this filter),
        # shuffled well under the 0.9 probe ratio
        "f32": rng.random((128, 128), dtype=np.float32),
        "f64": rng.random((64, 64)),
        "ints": np.arange(4096, dtype=np.int64),       # filter not applied
    }
    ser.save_shards(tmp_path, st, workers=1)
    man = ser.load_manifest(tmp_path)
    ext = ser._codec_ext(man["codec"])
    for key, itemsize in (("f32", 4), ("f64", 8)):
        s = man["leaves"][key]["shards"][0]
        # shuffled encoding, width in the NAME (decoding can never guess)
        assert s["chunk"].endswith(f".{ext}s{itemsize}"), key
        assert s["codec"] == f"{man['codec']}+shuf{itemsize}"
        assert s["clen"] < 0.9 * s["raw"], key         # it really shrank
    s_int = man["leaves"]["ints"]["shards"][0]
    assert "codec" not in s_int
    assert ser.validate(tmp_path, deep=True)
    out = ser.restore_tree(tmp_path, jax.eval_shape(lambda: dict(st)))
    for a, b in zip(jax.tree.leaves(st), jax.tree.leaves(out)):
        assert np.array_equal(np.asarray(a), np.asarray(b))
        assert np.asarray(a).dtype == np.asarray(b).dtype
    # a bit flip inside a SHUFFLED chunk is still caught by the digest
    victim = tmp_path / man["chunk_dir"] \
        / man["leaves"]["f32"]["shards"][0]["chunk"]
    blob = bytearray(victim.read_bytes())
    blob[len(blob) // 2] ^= 0xFF
    victim.write_bytes(bytes(blob))
    assert not ser.validate(tmp_path, deep=True)


def test_identical_bytes_under_different_dtypes_roundtrip(tmp_path):
    """Two leaves whose RAW BYTES are identical but whose dtypes have
    different widths share a content digest; the shuffle width rides in
    the chunk NAME, so each encoding decodes with the width it was
    written with and both leaves restore bitwise (a reader-dtype-derived
    width would unshuffle one of them into garbage)."""
    rng = np.random.default_rng(9)
    f32 = rng.random((64, 64), dtype=np.float32)
    st = {"a": f32, "b": f32.view(np.float64)}      # same bytes, width 8
    ser.save_shards(tmp_path, st, workers=1)
    man = ser.load_manifest(tmp_path)
    a, b = (man["leaves"][k]["shards"][0] for k in ("a", "b"))
    assert a["chunk"].split(".")[0] == b["chunk"].split(".")[0]  # digest
    assert ser.validate(tmp_path, deep=True)
    out = ser.restore_tree(tmp_path, jax.eval_shape(lambda: dict(st)))
    assert np.array_equal(out["a"], st["a"])
    assert np.array_equal(out["b"], st["b"])


def test_shuffled_and_plain_chunks_share_digest_identity(tmp_path):
    """The SAME content saved under the pre-filter encoding is still a
    store hit for the filtered writer (and vice versa): candidates cover
    every encoding of one digest, so old stores keep deduping."""
    rng = np.random.default_rng(8)
    data = rng.random((64, 64), dtype=np.float32)
    buf = ser._as_buffer(data)
    digest = ser.content_digest(buf)
    store = ChunkStore(tmp_path / "chunks")
    # simulate a pre-PR-5 store: the chunk exists RAW under this digest
    store.put(f"{digest}.raw", bytes(buf), raw_bytes=buf.nbytes)
    ser.save_shards(tmp_path / "ck", {"w": data}, store=store, workers=1)
    man = ser.load_manifest(tmp_path / "ck")
    s = man["leaves"]["w"]["shards"][0]
    assert s["chunk"] == f"{digest}.raw"        # referenced, not rewritten
    assert store.stats["chunks_written"] == 1   # only the seeded put
    out = ser.restore_tree(tmp_path / "ck",
                           jax.eval_shape(lambda: {"w": data}))
    assert np.array_equal(out["w"], data)


def test_missing_manifest_is_invalid(tmp_path):
    mgr = CheckpointManager(tmp_path)
    mgr.save(3, _state()); mgr.wait()
    (tmp_path / "step_0000000003" / "MANIFEST.json").unlink()
    assert mgr.latest_valid() is None


def test_keep_k_gc(tmp_path):
    mgr = CheckpointManager(tmp_path, keep=2)
    for s in (1, 2, 3, 4):
        mgr.save(s, _state(s))
        mgr.wait()
    steps = mgr.list_steps()
    assert steps == [3, 4]
    assert mgr.stats["gc_removed"] == 2


def test_gc_removes_corrupt_keeps_valid(tmp_path):
    """Corrupt/partial dirs (a crashed writer's leftovers — the kind that
    used to accumulate forever) are always collected; valid ones obey
    `keep`; the last remaining valid checkpoint is never removed."""
    mgr = CheckpointManager(tmp_path, keep=2)
    mgr.save(1, _state(1))
    mgr.wait()
    # two crashed-writer leftovers: partial (no manifest) and bit-flipped
    (tmp_path / "step_0000000002").mkdir()
    (tmp_path / "step_0000000002" / "leaf00000_full.zz").write_bytes(b"junk")
    d3 = tmp_path / "step_0000000003"
    d3.mkdir()
    (d3 / "leaf00000_full.zz").write_bytes(b"\x00shard")
    (d3 / "MANIFEST.json").write_text(json.dumps(
        {"version": 1, "codec": "zlib", "meta": {}, "leaves": {"w": {
            "shape": [1], "dtype": "float32", "shards": [{
                "file": "leaf00000_full.zz", "index": [[0, 1]],
                "crc32": 1, "device": -1}]}}}))    # wrong crc
    mgr.save(4, _state(4))        # triggers _gc
    mgr.wait()
    assert mgr.list_steps() == [1, 4]      # both corrupt dirs collected...
    assert mgr.latest_valid() == tmp_path / "step_0000000004"
    assert ser.validate(tmp_path / "step_0000000001")  # ...valid kept


def test_gc_never_removes_last_valid(tmp_path):
    """The seed's inverted guard deleted VALID old checkpoints while corrupt
    ones accumulated: with keep=2 and the two newest dirs corrupt, it would
    have removed the only restorable checkpoint.  Now the valid one survives
    no matter how many newer corrupt dirs outrank it."""
    mgr = CheckpointManager(tmp_path, keep=2, async_write=False)
    mgr.save(1, _state(1))
    for s in (2, 3):               # two NEWER corrupt/partial dirs
        d = tmp_path / f"step_{s:010d}"
        d.mkdir()
        (d / "MANIFEST.json").write_text("{not json")
    mgr._gc()
    assert mgr.list_steps() == [1]
    assert mgr.latest_valid() == tmp_path / "step_0000000001"


def test_write_failure_surfaces_on_wait(tmp_path, monkeypatch):
    mgr = CheckpointManager(tmp_path)
    monkeypatch.setattr(ser, "save_shards",
                        lambda *a, **k: (_ for _ in ()).throw(IOError("disk")))
    mgr.save(1, _state())
    with pytest.raises(RuntimeError):
        mgr.wait()


def test_failed_async_write_never_deletes_previous_valid(tmp_path,
                                                         monkeypatch):
    """A save_shards failure mid-write used to leave _gc running against
    the partial dir; with keep=1 that could collect the only valid
    checkpoint.  Now a failed write skips gc entirely: the previous
    checkpoint (manifest AND chunks) must survive, and the next restore
    must serve it."""
    mgr = CheckpointManager(tmp_path, keep=1)
    mgr.save(1, _state(1))
    mgr.wait()
    good = mgr.latest_valid()
    chunks_before = set(p.name for p in (tmp_path / "chunks").iterdir())

    real = ser.save_shards

    def dies_mid_write(ckpt_dir, state, **kw):
        real(ckpt_dir, state, **kw)           # chunks + manifest land...
        (ckpt_dir / "MANIFEST.json").unlink()  # ...but the commit "crashes"
        raise IOError("disk full")

    monkeypatch.setattr(ser, "save_shards", dies_mid_write)
    mgr.save(2, _state(2))
    with pytest.raises(RuntimeError):
        mgr.wait()
    # gc did NOT run: the old checkpoint is intact, chunks included
    assert mgr.latest_valid() == good
    assert chunks_before <= set(p.name
                                for p in (tmp_path / "chunks").iterdir())
    out, meta = mgr.restore(jax.eval_shape(lambda: _state()))
    assert meta["step"] == 1
    # the next SUCCESSFUL save gc-collects the partial leftovers
    monkeypatch.setattr(ser, "save_shards", real)
    mgr.save(3, _state(3))
    mgr.wait()
    assert mgr.list_steps() == [3]


def test_incremental_save_references_unchanged_chunks(tmp_path):
    """Steady-state incremental save: when only a few leaves change, the
    next save writes only their chunks and hard-references the rest; the
    restore from the incremental chain is bit-identical."""
    mgr = CheckpointManager(tmp_path, keep=3)
    st = _state(0)
    mgr.save(1, st)
    mgr.wait()
    full_written = mgr.stats["last_bytes_written"]
    assert full_written > 0 and mgr.delta_write_fraction() == 1.0
    # change ONE leaf (the optimizer-step analog) and save again
    st2 = dict(st, step=jnp.int32(8))
    mgr.save(2, st2)
    mgr.wait()
    assert mgr.stats["last_bytes_referenced"] > 0
    assert mgr.delta_write_fraction() < 0.25
    out, meta = mgr.restore(jax.eval_shape(lambda: _state()))
    assert meta["step"] == 2
    for a, b in zip(jax.tree.leaves(st2), jax.tree.leaves(out)):
        assert np.array_equal(np.asarray(a), np.asarray(b))


def test_refcount_gc_keeps_shared_chunks(tmp_path):
    """Dropping an old step removes only chunks no retained manifest
    references; shared chunks survive and the survivor still restores."""
    mgr = CheckpointManager(tmp_path, keep=1, async_write=False)
    st = _state(0)
    mgr.save(1, st)
    st2 = dict(st, step=jnp.int32(8))      # mostly-shared successor
    mgr.save(2, st2)                        # gc drops step 1
    assert mgr.list_steps() == [2]
    assert mgr.stats["chunks_gc_removed"] >= 1     # step-1's unique chunk
    live = set(ser.manifest_chunks(ser.load_manifest(mgr.latest_valid())))
    on_disk = set(p.name for p in (tmp_path / "chunks").iterdir())
    assert live == on_disk                  # exactly the live set remains
    out, _ = mgr.restore(jax.eval_shape(lambda: _state()))
    for a, b in zip(jax.tree.leaves(st2), jax.tree.leaves(out)):
        assert np.array_equal(np.asarray(a), np.asarray(b))


# ------------------------------------------------------------ data pipeline

def test_pipeline_deterministic_and_resumable():
    p1 = TokenPipeline(1000, 4, 16, seed=3)
    batches = [p1.next_batch() for _ in range(5)]
    snap = p1.snapshot()
    more = [p1.next_batch() for _ in range(3)]
    p2 = TokenPipeline.restore(snap)
    again = [p2.next_batch() for _ in range(3)]
    for a, b in zip(more, again):
        assert np.array_equal(a["tokens"], b["tokens"])
        assert np.array_equal(a["targets"], b["targets"])
    # batch k is identical regardless of production time/order
    p3 = TokenPipeline(1000, 4, 16, seed=3)
    assert np.array_equal(p3._gen(2)["tokens"], batches[2]["tokens"])


def test_pipeline_prefetch_and_inflight_cache():
    p = TokenPipeline(1000, 2, 8, seed=1, prefetch=3)
    p.start()
    first = [p.next_batch() for _ in range(2)]
    time.sleep(0.05)                       # let the producer fill the queue
    snap = p.snapshot(cache_inflight=True)  # paper-faithful drain-to-cache
    p.stop()
    assert len(snap.get("inflight", [])) >= 1
    p2 = TokenPipeline.restore(snap)
    p2.start()
    nxt = p2.next_batch()
    p2.stop()
    ref = TokenPipeline(1000, 2, 8, seed=1)._gen(2)
    assert np.array_equal(nxt["tokens"], ref["tokens"])


def test_pipeline_targets_are_shifted_tokens():
    p = TokenPipeline(50, 2, 8, seed=0)
    b = p.next_batch()
    assert b["tokens"].shape == (2, 8) and b["targets"].shape == (2, 8)
    assert not np.array_equal(b["tokens"], b["targets"])


# --------------------------------------------------------- train-loop C / R

@pytest.mark.slow
def test_train_crash_resume_loss_continuity(tmp_path):
    from repro.configs import ARCHS, reduce_for_smoke
    from repro.distributed.sharding import make_variant
    from repro.launch.mesh import make_local_mesh
    from repro.train.loop import train

    cfg = reduce_for_smoke(ARCHS["smollm-135m"])
    mesh = make_local_mesh()
    rules = make_variant("baseline")
    kw = dict(n_steps=10, global_batch=4, seq_len=32, log_every=1, seed=5)
    ref = train(cfg, mesh, rules, ckpt_root=None, **kw)
    with pytest.raises(RuntimeError):
        train(cfg, mesh, rules, ckpt_root=tmp_path, ckpt_every=4,
              fail_at_step=7, **kw)
    res = train(cfg, mesh, rules, ckpt_root=tmp_path, ckpt_every=4, **kw)
    assert res.resumed_from == 4          # last ckpt before the injected crash
    assert abs(res.losses[-1] - ref.losses[-1]) < 1e-6
