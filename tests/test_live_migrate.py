"""Pre-copy live migration (DESIGN.md §13).

Covers the three promises the design makes:

  * rounds are EXACT — a round manifest lists every leaf, ships exactly
    the leaves whose content changed since the previous round, and
    references the rest (property-tested: a seeded randomized sweep that
    always runs, plus a hypothesis variant);
  * migration is INVISIBLE to the application — a world that live-migrated
    a rank mid-run finishes bit-identical to an unmigrated control, on
    every fabric (shm / tcp / proc);
  * rounds are STAGING, the manifest is the COMMIT — a death mid-round
    (SIGKILL semantics: os.replace is atomic, so a kill leaves either no
    round file or a complete one, never a torn manifest) leaves the
    previous committed checkpoint exactly as restorable as it was.
"""
import json
import pickle
import threading
import time
from pathlib import Path

import numpy as np
import pytest
from hypothesis import given, settings
from hypothesis import strategies as st

from repro.checkpoint.chunkstore import ChunkStore, content_digest
from repro.core import migrate as migration
from repro.core.ckpt_protocol import checkpoint_valid, load_manifest
from repro.core.coordinator import Membership
from repro.core.runtime import MPIJob

from conftest import exact_transports

N = 2
STEPS = 100


# ------------------------------------------------------------ app fixture

def init_fn(mpi):
    r = mpi.rank
    return {
        "acc": np.zeros(32, dtype=np.float64),
        "hot": np.full(2048, float(r), dtype=np.float64),
        "cold": np.arange(8192, dtype=np.float64),   # never dirtied
    }


def step_fn(mpi, state, step):
    total = mpi.Allreduce(state["acc"][:4] + step)
    state = dict(state)
    state["acc"] = state["acc"].copy()
    state["acc"][:4] += total
    state["hot"] = state["hot"] + 0.5
    time.sleep(0.004)
    return state


def _run_async(job, n_steps, timeout=120.0):
    box = {}

    def runner():
        try:
            box["out"] = job.run(n_steps, timeout=timeout)
        except BaseException as e:  # surfaced by _finish
            box["err"] = e

    t = threading.Thread(target=runner, daemon=True)
    t.start()
    box["thread"] = t
    return box


def _finish(job, box, timeout=120.0):
    box["thread"].join(timeout)
    assert not box["thread"].is_alive(), "job did not finish"
    job.stop()
    if "err" in box:
        raise box["err"]
    return box["out"]


# ----------------------------------------------------- split/join + rounds

def test_split_join_roundtrip():
    d = {"a": np.arange(4), "b": "text", "c": {"nested": 1}}
    assert set(migration.split_state(d)) == {"a", "b", "c"}
    back = migration.join_state(migration.split_state(d))
    assert back["b"] == "text" and back["c"] == {"nested": 1}
    assert np.array_equal(back["a"], d["a"])
    # non-dict states (and dicts that could collide with the singleton
    # leaf name) collapse to one leaf
    for s in ([1, 2, 3], "blob", {"_": 1}, {}, {3: "int-key"}):
        leaves = migration.split_state(s)
        assert set(leaves) == {migration.LEAF_SINGLETON}
        assert migration.join_state(leaves) == s


def test_stream_round_ships_exactly_dirty_leaves(tmp_path, rng):
    """The always-running property sweep: across many randomized rounds,
    a round ships exactly the leaves whose content changed and references
    every unchanged one."""
    store = ChunkStore(tmp_path / "chunks")
    state = {f"k{i}": rng.standard_normal(64) for i in range(6)}
    prev = {}
    prev_entry = None
    for round_no in range(25):
        mutated = set()
        for k in list(state):
            if rng.random() < 0.4:
                state[k] = state[k] + rng.standard_normal()
                mutated.add(k)
        entry, digests = migration.stream_round(store, state, prev)
        # every leaf is listed; exactly the mutated ones were shipped
        assert set(entry["leaves"]) == set(state)
        expected_dirty = mutated if prev else set(state)  # round 1: all
        assert set(entry["dirty_leaves"]) == expected_dirty
        assert entry["shipped_bytes"] == sum(
            entry["leaves"][k]["bytes"] for k in expected_dirty)
        assert entry["total_bytes"] == sum(
            p["bytes"] for p in entry["leaves"].values())
        # unchanged leaves kept their digest; every chunk is in the store
        for k, name in digests.items():
            if k not in expected_dirty:
                assert prev[k] == name
            assert store.has(name)
        if prev_entry is not None:
            clean = set(state) - expected_dirty
            for k in clean:
                assert entry["leaves"][k] == prev_entry["leaves"][k]
        prev, prev_entry = digests, entry


@settings(max_examples=25, deadline=None)
@given(st.lists(st.dictionaries(
    st.sampled_from(["a", "b", "c", "d"]), st.binary(max_size=64),
    min_size=1), min_size=1, max_size=6))
def test_round_manifest_property(states):
    """Hypothesis variant: for any sequence of leaf states, each round's
    dirty set is exactly the keys whose bytes differ from the previous
    round (new keys included), and split/join stays a bijection."""
    import tempfile
    store = ChunkStore(Path(tempfile.mkdtemp(prefix="mig-prop-")) / "chunks")
    prev_digests = {}
    prev_state = None
    for state in states:
        entry, digests = migration.stream_round(store, state, prev_digests)
        expect = {k for k, v in state.items()
                  if prev_state is None or prev_state.get(k) != v
                  or k not in prev_digests}
        assert set(entry["dirty_leaves"]) == expect
        assert migration.join_state(migration.split_state(state)) == state
        prev_digests, prev_state = digests, dict(state)


def test_round_manifest_write_load_latest(tmp_path):
    entries = {0: {"leaves": {"w": {"chunk": "x.bin", "bytes": 3}},
                   "shipped_bytes": 3, "total_bytes": 3,
                   "dirty_leaves": ["w"]}}
    migration.write_round_manifest(tmp_path, 1, entries, generation=4)
    migration.write_round_manifest(tmp_path, 2, entries, generation=4,
                                   store_spec="remote://h:1/ns")
    assert migration.latest_round(tmp_path) == 2
    man = migration.load_round_manifest(tmp_path, 2)
    assert man["generation"] == 4 and man["store"] == "remote://h:1/ns"
    assert man["ranks"]["0"]["dirty_leaves"] == ["w"]
    assert migration.entries_chunks(entries) == {"x.bin"}
    assert migration.latest_round(tmp_path / "nope") is None


# ------------------------------------------------- migration bit-identity

@pytest.mark.parametrize("transport", ["shm", "tcp", "proc"])
def test_live_migrate_bit_identical(tmp_path, transport):
    """A world that live-migrated rank 0 mid-run finishes bit-identical
    to an unmigrated control on the same fabric, and the migration's
    stop-the-world window committed a restorable checkpoint."""
    with exact_transports():
        job = MPIJob(N, step_fn, init_fn, transport=transport)
        box = _run_async(job, STEPS)
        time.sleep(0.3)
        rep = job.migrate(tmp_path / "ck", ranks=(0,), max_rounds=4,
                          timeout=60.0)
        migrated = _finish(job, box)

        ctrl_job = MPIJob(N, step_fn, init_fn, transport=transport)
        control = ctrl_job.run(STEPS, timeout=120.0)
        ctrl_job.stop()

    for r in range(N):
        for k in control[r]:
            assert np.array_equal(migrated[r][k], control[r][k]), \
                f"rank {r} leaf {k} diverged after migration"
    # the report is coherent: rounds streamed, manifest committed,
    # final delta is a subset of the checkpoint
    assert rep["converged"] and rep["rounds"]
    assert 0 <= rep["final_bytes"] <= rep["total_bytes"]
    assert (tmp_path / "ck" / "MANIFEST.json").exists()
    assert checkpoint_valid(tmp_path / "ck")
    assert migration.latest_round(tmp_path / "ck") == len(rep["rounds"])
    st_ = job.stats()["coordinator"]
    assert st_["migrations"] == 1
    assert st_["migrate_rounds"] == len(rep["rounds"])
    assert st_["migrate_pause_s"] > 0.0


def test_migrate_pause_pays_only_final_delta(tmp_path):
    """With a mostly-cold state the converged final round ships a small
    fraction of the checkpoint: pre-copy staged the rest while the world
    ran (the perf contract bench_live_migrate gates in CI)."""
    job = MPIJob(N, step_fn, init_fn, transport="shm")
    box = _run_async(job, STEPS)
    time.sleep(0.3)
    rep = job.migrate(tmp_path / "ck", ranks=(0,), max_rounds=5,
                      timeout=60.0)
    _finish(job, box)
    assert rep["converged"]
    # cold is 8192 float64s per rank; it must never re-ship after round 1
    assert rep["final_fraction"] < 0.9
    dirty = [r["dirty_bytes"] for r in rep["rounds"]]
    assert dirty[-1] < dirty[0], "dirty set never shrank"


# ------------------------------------------- rounds stage, manifest commits

def test_mid_round_death_leaves_previous_checkpoint_restorable(tmp_path):
    """Round files are staging: a migration killed mid-round (emulated by
    torn round tmp files plus committed round manifests — exactly the
    on-disk states a SIGKILL can leave, since os.replace is atomic) does
    not perturb the previously committed checkpoint, which restarts
    cleanly."""
    ck = tmp_path / "ck"
    job = MPIJob(N, step_fn, init_fn, transport="shm")
    job.checkpoint_at(20, ck, resume=True)
    box = _run_async(job, STEPS)
    job.wait_checkpoint()
    _finish(job, box)
    man_before = (ck / "MANIFEST.json").read_bytes()
    assert checkpoint_valid(ck)

    # a migration died mid-round: one committed round file, one torn tmp
    store = ChunkStore(ck / "chunks")
    blob = pickle.dumps(np.arange(16))
    entry, _ = migration.stream_round(store, {"w": 1}, {})
    migration.write_round_manifest(ck, 1, {0: entry}, generation=0)
    (ck / "ROUND_0002.json.tmp99-99").write_text('{"torn')
    (ck / "chunks" / f"{content_digest(blob)}.bin.tmp-dead").write_bytes(
        blob[: len(blob) // 2])

    # the committed checkpoint is untouched and restores
    assert (ck / "MANIFEST.json").read_bytes() == man_before
    assert checkpoint_valid(ck, deep=True)
    job2 = MPIJob.restart(ck, step_fn, init_fn, transport="shm")
    out = job2.run(STEPS, timeout=120.0)
    job2.stop()
    ctrl = MPIJob(N, step_fn, init_fn, transport="shm")
    control = ctrl.run(STEPS, timeout=120.0)
    ctrl.stop()
    for r in range(N):
        for k in control[r]:
            assert np.array_equal(out[r][k], control[r][k])


def test_migrated_checkpoint_restarts_like_any_other(tmp_path):
    """The manifest a migration final commits is an ordinary checkpoint:
    MPIJob.restart consumes it (leaf-split images reassemble) and the
    restarted world finishes identically to an uninterrupted control."""
    job = MPIJob(N, step_fn, init_fn, transport="shm")
    box = _run_async(job, STEPS)
    time.sleep(0.3)
    job.migrate(tmp_path / "ck", ranks=(0,), max_rounds=3, timeout=60.0)
    _finish(job, box)
    man = load_manifest(tmp_path / "ck")
    ent = man["ranks"]["0"]
    leaf_parts = [k for k in ent["parts"] if k.startswith("app/")]
    assert sorted(leaf_parts) == ["app/acc", "app/cold", "app/hot"]
    job2 = MPIJob.restart(tmp_path / "ck", step_fn, init_fn,
                          transport="shm")
    out = job2.run(STEPS, timeout=120.0)
    job2.stop()
    ctrl = MPIJob(N, step_fn, init_fn, transport="shm")
    control = ctrl.run(STEPS, timeout=120.0)
    ctrl.stop()
    for r in range(N):
        for k in control[r]:
            assert np.array_equal(out[r][k], control[r][k])


# -------------------------------------------------- atomic reshape (§8/§13)

def test_atomic_reshape_single_bump_both_layers(tmp_path):
    """One atomic_reshape = ONE generation bump shared by the jax-mesh
    manager and the reshaped rank world — their epochs cannot diverge."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import Mesh

    from repro.checkpoint.manager import CheckpointManager
    from repro.distributed.elastic import atomic_reshape
    from repro.distributed.sharding import DEFAULT_RULES

    ck = tmp_path / "ck"
    membership = Membership(N)
    job = MPIJob(N, step_fn, init_fn, transport="shm",
                 membership=membership)
    job.checkpoint_at(10, ck, resume=True)
    box = _run_async(job, 30)
    job.wait_checkpoint()
    _finish(job, box)
    assert membership.generation == 0

    mgr = CheckpointManager(tmp_path / "mesh", generation=0)
    mgr.save(7, {"w": jnp.arange(8.0)})
    mgr.wait()
    mesh = Mesh(np.array(jax.devices()), ("data",))
    tpl = {"w": jax.ShapeDtypeStruct((8,), jnp.float32)}

    rep = atomic_reshape(membership, dead=(1,),
                         mgr=mgr, template=tpl, mesh=mesh,
                         rules=DEFAULT_RULES,
                         ckpt_dir=ck, step_fn=step_fn, init_fn=init_fn,
                         transport="shm")
    # exactly one bump, visible identically from every layer
    assert rep.generation == 1 == membership.generation
    assert rep.layers == ("mesh", "world")
    assert mgr.generation == 1
    assert rep.job.coord.generation == 1
    assert rep.job.n == rep.world_size == 1
    assert np.array_equal(np.asarray(rep.state["w"]), np.arange(8.0))
    out = rep.job.run(30, timeout=120.0)
    rep.job.stop()
    assert out[0]["acc"].shape == (32,)


def test_atomic_reshape_world_only(tmp_path):
    """Rank-world-only reshape: no manager, still exactly one bump."""
    from repro.distributed.elastic import atomic_reshape

    ck = tmp_path / "ck"
    membership = Membership(N)
    job = MPIJob(N, step_fn, init_fn, transport="shm",
                 membership=membership)
    job.checkpoint_at(10, ck, resume=False)
    box = _run_async(job, 30)
    _finish(job, box)
    rep = atomic_reshape(membership, dead=(), world_size=N,
                         ckpt_dir=ck, step_fn=step_fn, init_fn=init_fn,
                         transport="shm")
    assert rep.generation == 1 and rep.layers == ("world",)
    out = rep.job.run(30, timeout=120.0)
    rep.job.stop()
    assert len(out) == N
