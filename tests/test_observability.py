"""Flight recorder + cross-process tracing (DESIGN.md §16).

Covers the observability acceptance scenario: a proc-world SIGKILL mid
allreduce produces per-process flight-recorder dumps that merge into ONE
causally-ordered Chrome-trace timeline — the kill instant, the recovery
sub-FSM phases (collect → quiesce → patch → resume) nested under the
epoch span, a rank's checkpoint parented ACROSS the socket boundary
under the coordinator's round span, and the chunk service's server-side
spans on the same axis.  Also: the typed-event schema round trip, the
pinned driver-event vocabulary, the metrics registry primitives, the
atomic MPIJob.stats()/CheckpointManager.stats snapshot contract, and the
REPRO_TRACE=0 no-op guarantee.
"""
import json
import os
import signal
import threading

import numpy as np
import pytest

from conftest import exact_transports

from repro.core import MPIJob
from repro.core import metrics
from repro.core import trace
from repro.distributed.faults import (DriverEvent, DriverEventKind,
                                      DriverEventPayload,
                                      FaultTolerantDriver)

N = 3
STEPS = 6
VICTIM = 1
KILL_STEP = STEPS - 1


def _acc_app(n_elems: int = 32):
    def init(mpi):
        return {"seed": mpi.rank, "acc": np.zeros(n_elems), "steps_run": 0}

    def step(mpi, st, k):
        rng = np.random.default_rng(1000 * k + st["seed"])
        x = rng.standard_normal(n_elems)
        tot = mpi.Allreduce(x, op="sum", algo="ring")
        return {"seed": st["seed"], "acc": st["acc"] + tot,
                "steps_run": st["steps_run"] + 1}
    return init, step


@pytest.fixture
def enabled():
    """Tracing on for the test, restored after (another test/bench may
    have toggled it off via set_enabled)."""
    prev = trace.ENABLED
    trace.set_enabled(True)
    yield
    trace.set_enabled(prev)


# ------------------------------------------------------- event schema

def test_every_event_type_survives_wire_roundtrip():
    """Schema round trip: every registered event type is lossless through
    to_wire -> JSON -> from_wire (what the dump files and the merger rely
    on)."""
    samples = {
        "span": trace.SpanEvent(
            name="rank.ckpt", trace_id=7, span_id=11, parent_id=5,
            t0=1.25, dur=0.5, pid=4242, cat="rank", rank=2, generation=3,
            args={"step": 9, "outcome": "resumed"}),
        "instant": trace.InstantEvent(
            name="fault.rank_died", trace_id=8, span_id=None,
            parent_id=None, t=2.5, pid=4243, cat="coord", rank=1,
            generation=None, args={"error": "RankProcessDied"}),
    }
    assert set(samples) == set(trace.EVENT_TYPES), \
        "new event type added without a round-trip sample"
    for kind, ev in samples.items():
        wire = json.loads(json.dumps(ev.to_wire()))
        assert wire["kind"] == kind
        back = trace.from_wire(wire)
        assert back == ev


def test_ring_is_bounded():
    rec = trace.FlightRecorder(cap=16)
    for i in range(100):
        rec.add(i)
    assert len(rec) == 16
    assert rec.snapshot() == list(range(84, 100))


def test_disabled_tracing_is_noop(enabled):
    trace.set_enabled(False)
    before = len(trace.recorder())
    assert trace.span("x") is trace.span("y")          # shared null object
    with trace.span("x") as s:
        s.end(extra=1)
    trace.instant("x")
    win = trace.BatchWindow("w")
    win.add(0.001, 3)
    win.flush()
    assert len(trace.recorder()) == before


def test_span_nesting_and_explicit_parent(enabled):
    trace.clear()
    with trace.span("outer", cat="t") as outer:
        with trace.span("inner", cat="t"):             # thread-local parent
            pass
        trace.instant("mark", cat="t")                 # ditto
    detached = trace.begin("detached", parent=outer.ctx, cat="t")
    detached.end()
    evs = {e.name: e for e in trace.recorder().snapshot()}
    assert evs["inner"].parent_id == outer.span_id
    assert evs["inner"].trace_id == outer.trace_id
    assert evs["mark"].parent_id == outer.span_id
    assert evs["detached"].parent_id == outer.span_id
    assert evs["outer"].parent_id is None


def test_dump_merge_roundtrip(tmp_path, enabled):
    trace.clear()
    with trace.span("parent", cat="t", rank=0):
        with trace.span("child", cat="t", rank=0):
            pass
    path = trace.dump(role="unit", trace_dir=str(tmp_path))
    assert path is not None and path.exists()
    meta, events = trace.load_dump(path)
    assert meta["pid"] == os.getpid() and meta["role"] == "unit"
    assert {e.name for e in events} >= {"parent", "child"}
    merged = trace.merge_dir(tmp_path)
    spans = {e["name"]: e for e in merged["traceEvents"] if e["ph"] == "X"}
    assert spans["child"]["args"]["parent_id"] == \
        spans["parent"]["args"]["span_id"]
    assert spans["child"]["ts"] >= spans["parent"]["ts"]


def test_dump_is_noop_without_trace_dir(monkeypatch):
    monkeypatch.delenv("REPRO_TRACE_DIR", raising=False)
    assert trace.dump(role="nowhere") is None


# ------------------------------------------------- driver event vocabulary

def test_driver_event_vocabulary_pinned():
    """The driver's event kinds are a pinned vocabulary: adding/renaming
    one is an API change and must update this test (and any log
    consumer)."""
    assert {k.value for k in DriverEventKind} == {
        "start", "restart", "dead", "straggler", "recover", "fallback",
        "migrate", "migrate-failed", "ckpt", "wait", "done", "failure"}


def test_driver_event_is_its_legacy_string():
    ev = DriverEvent(DriverEventKind.DEAD, "dead:[1]:gen=2",
                     ranks=(1,), generation=2)
    assert isinstance(ev, str)
    assert ev == "dead:[1]:gen=2"
    assert ev.startswith("dead:")
    assert str(ev) == "dead:[1]:gen=2"
    assert json.loads(json.dumps([ev])) == ["dead:[1]:gen=2"]
    assert ev.kind is DriverEventKind.DEAD
    assert ev.payload == DriverEventPayload(
        kind=DriverEventKind.DEAD, ranks=(1,), generation=2, detail={})
    # kind accepted as a plain string too (the _declare_dead call site)
    assert DriverEvent("straggler", "straggler:[2]:gen=1").kind \
        is DriverEventKind.STRAGGLER


def test_driver_emits_typed_events(tmp_path):
    init, step = _acc_app()
    with exact_transports():
        driver = FaultTolerantDriver(
            job_factory=lambda: MPIJob(2, step, init, transport="shm"),
            restart_factory=lambda d, tr: MPIJob.restart(
                d, step, init, transport=tr),
            ckpt_root=tmp_path, ckpt_every=100)
        driver.run(3, timeout=60)
    assert driver.events == ["start:fresh", "done"]
    assert all(isinstance(e, DriverEvent) for e in driver.events)
    assert [e.kind for e in driver.events] == [DriverEventKind.START,
                                               DriverEventKind.DONE]


# --------------------------------------------------- metrics primitives

def test_metric_group_mapping_contract():
    g = metrics.MetricGroup("t", {"a": 0, "b": 1.5})
    g["a"] += 2                                  # the old stats idiom
    g["c"] = g.get("c", 0.0) + 0.25              # serialization.py idiom
    assert g.add("a", 3) == 5
    assert dict(g) == {"a": 5, "b": 1.5, "c": 0.25}
    assert g["b"] == 1.5 and "c" in g and len(g) == 3
    assert g.snapshot() == dict(g)
    assert g == {"a": 5, "b": 1.5, "c": 0.25}    # Mapping equality


def test_labeled_counter_bounds_its_series():
    c = metrics.LabeledCounter("t", max_series=3)
    for i in range(10):
        c.inc(f"label{i}")
    snap = c.snapshot()
    assert len(snap) == 4                        # 3 series + overflow
    assert snap[metrics.OVERFLOW_LABEL] == 7


def test_histogram_buckets():
    h = metrics.Histogram("t", buckets=(0.001, 0.01, 0.1))
    for v in (0.0005, 0.005, 0.05, 5.0):
        h.observe(v)
    snap = h.snapshot()
    assert snap["count"] == 4
    assert snap["counts"] == [1, 1, 1, 1]        # last = +inf bucket
    assert snap["min"] == 0.0005 and snap["max"] == 5.0


def test_registry_snapshot_sees_live_groups():
    g = metrics.MetricGroup("registry_probe", {"x": 1})
    snap = metrics.REGISTRY.snapshot()
    assert any(s["name"] == "registry_probe" and s["values"] == {"x": 1}
               for s in snap)
    del g


def test_metric_group_snapshot_survives_concurrent_new_keys():
    """Regression for the MPIJob.stats() torn merge: new keys landing
    mid-iteration used to raise 'dictionary changed size during
    iteration'.  Snapshots under the group lock cannot tear."""
    g = metrics.MetricGroup("concurrent", {"base": 0})
    stop = threading.Event()
    errors = []

    def mutate():
        i = 0
        try:
            while not stop.is_set():
                g.add(f"k{i % 512}", 1)          # fresh keys force resizes
                i += 1
        except BaseException as e:   # noqa: BLE001 - surfaced below
            errors.append(e)

    t = threading.Thread(target=mutate)
    t.start()
    try:
        for _ in range(500):
            snap = g.snapshot()
            assert snap["base"] == 0
            list(g.items())
            dict(g)
    finally:
        stop.set()
        t.join(10.0)
    assert not errors


# ------------------------------------------- stats() compatibility pins

JOB_STATS_KEYS = {"transport", "world_size", "live_ranks", "generation",
                  "coordinator", "telemetry", "stragglers", "ledger",
                  "ckpt_store"}

COORD_STATS_KEYS = {
    "drain_rounds", "drain_wall_s", "drained_messages", "checkpoints",
    "counter_reports", "empty_channel_snapshots", "stale_rejected",
    "migrations", "migrate_rounds", "migrate_pause_s", "recoveries",
    "recovery_wall_s", "recovered_ops", "rerun_ops", "recovery_cancelled"}

CKPT_MANAGER_STATS_KEYS = {
    "saves", "drain_device_s", "drain_write_wait_s", "snapshot_s",
    "snapshot_transfer_s", "snapshot_copy_s", "snapshot_bytes", "write_s",
    "snapshot_device_copies", "snapshot_host_fallbacks",
    "gc_removed", "hash_s",
    "compress_s", "io_s", "bytes_written", "bytes_referenced",
    "last_bytes_written", "last_bytes_referenced", "chunks_gc_removed",
    "last_bytes_uploaded", "last_bytes_referenced_remote", "restores",
    "restore_io_s", "restore_decompress_s", "restore_device_s"}


def test_job_stats_keys_pinned_and_snapshot_is_plain_data():
    init, step = _acc_app()
    with exact_transports():
        job = MPIJob(2, step, init, transport="shm")
    try:
        job.run(2, timeout=60)
        s = job.stats()
        assert set(s) == JOB_STATS_KEYS
        assert set(s["coordinator"]) == COORD_STATS_KEYS
        assert isinstance(s["coordinator"], dict)    # a snapshot, not live
        assert s["coordinator"]["counter_reports"] > 0
        json.dumps({k: s[k] for k in ("transport", "world_size",
                                      "live_ranks", "generation",
                                      "coordinator")})
    finally:
        job.stop()


def test_ckpt_manager_stats_keys_pinned(tmp_path):
    from repro.checkpoint.manager import CheckpointManager
    mgr = CheckpointManager(tmp_path, keep=2)
    assert set(mgr.stats.keys()) == CKPT_MANAGER_STATS_KEYS
    assert isinstance(mgr.stats, metrics.MetricGroup)
    # the serialization.py read-modify-write idiom keeps working
    mgr.stats["hash_s"] = mgr.stats.get("hash_s", 0.0) + 0.5
    assert mgr.stats["hash_s"] == 0.5


def test_job_stats_consistent_under_concurrent_mutation():
    """The satellite fix proper: stats() vs rank threads bumping fresh
    coordinator counters (the exact shape that used to blow up dict
    iteration mid-merge)."""
    init, step = _acc_app()
    with exact_transports():
        job = MPIJob(2, step, init, transport="inproc")
    stop = threading.Event()
    errors = []

    def hammer():
        i = 0
        try:
            while not stop.is_set():
                job.coord.stat_add(f"dyn_{i % 256}", 1)
                i += 1
        except BaseException as e:   # noqa: BLE001 - surfaced below
            errors.append(e)

    t = threading.Thread(target=hammer)
    t.start()
    try:
        for _ in range(500):
            s = job.stats()
            assert s["world_size"] == 2
            assert COORD_STATS_KEYS <= set(s["coordinator"])
    finally:
        stop.set()
        t.join(10.0)
        job.stop()
    assert not errors


# -------------------------------------------- thread-world dump + merge

def test_thread_world_checkpoint_timeline(tmp_path, monkeypatch, enabled):
    """A traced thread-world run with one mid-run checkpoint dumps a
    driver ring whose merged timeline carries the whole span taxonomy:
    the coordinator round + phase spans, the per-rank checkpoint dance
    nested under the round, and aggregated proxy batch windows."""
    tdir = tmp_path / "traces"
    monkeypatch.setenv("REPRO_TRACE_DIR", str(tdir))
    trace.clear()
    init, step = _acc_app()
    with exact_transports():
        job = MPIJob(2, step, init, transport="shm")
    job.checkpoint_at(2, tmp_path / "ck")
    out = job.run(4, timeout=60)
    path = job.dump_trace()
    job.stop()                       # re-dumps with the flushed windows
    assert path is not None and path.exists()
    assert all(out[r]["steps_run"] == 4 for r in range(2))

    merged = trace.merge_dir(tdir)
    evs = merged["traceEvents"]
    spans = [e for e in evs if e["ph"] == "X"]
    names = {e["name"] for e in spans}
    assert {"coord.ckpt_round", "coord.pending", "coord.drain",
            "coord.snapshot", "coord.resume", "rank.ckpt", "rank.drain",
            "rank.save_image", "proxy.batch"} <= names, names
    rounds = {e["args"]["span_id"] for e in spans
              if e["name"] == "coord.ckpt_round"}
    rank_ckpts = [e for e in spans if e["name"] == "rank.ckpt"]
    assert rank_ckpts
    assert all(e["args"].get("parent_id") in rounds for e in rank_ckpts)
    saves = [e for e in spans if e["name"] == "rank.save_image"]
    ckpt_ids = {e["args"]["span_id"] for e in rank_ckpts}
    assert all(e["args"].get("parent_id") in ckpt_ids for e in saves)
    # ts axis is sorted (the merger's output contract)
    ts = [e.get("ts", 0.0) for e in evs]
    assert ts == sorted(ts)


# ------------------------- the acceptance scenario: SIGKILL, merged

@pytest.mark.slow
def test_proc_sigkill_merged_timeline_is_causally_ordered(tmp_path,
                                                          monkeypatch,
                                                          enabled):
    """Process world, remote chunk store, REAL SIGKILL mid-allreduce:
    every process dumps its flight recorder, and the merged Chrome-trace
    timeline spans coordinator, surviving ranks and the chunk service
    with the story in causal order — checkpoint round (rank images
    parented across the socket under the coordinator's round, chunk
    uploads under the image save), then the kill instant, then the
    recovery sub-FSM collect -> quiesce -> patch -> resume nested under
    the epoch span, then the survivors finishing."""
    from repro.checkpoint.chunkservice import ChunkServer

    tdir = tmp_path / "traces"
    monkeypatch.setenv("REPRO_TRACE_DIR", str(tdir))
    trace.clear()
    init, base = _acc_app()

    def step(mpi, st, k):
        if mpi.rank == VICTIM and k == KILL_STEP and mpi.generation == 0:
            def hook(phase, hop):
                if (phase, hop) == ("rs", 1):
                    os.kill(os.getpid(), signal.SIGKILL)
            mpi._hop_hook = hook
        return base(mpi, st, k)

    srv = ChunkServer(tmp_path / "chunk_srv").start()
    try:
        spec = srv.spec_for("obs")
        driver = FaultTolerantDriver(
            job_factory=lambda: MPIJob(N, step, init, transport="proc",
                                       heartbeat_timeout=5.0,
                                       ckpt_store=spec),
            restart_factory=lambda d, tr: MPIJob.restart(
                d, step, init, transport=tr, ckpt_store=spec),
            ckpt_root=tmp_path / "ck", ckpt_every=3)
        out = driver.run(STEPS, transport_after_failure="proc", timeout=90)
    finally:
        srv.stop()
    assert driver.events[-1] == "done"
    assert any(e.kind is DriverEventKind.RECOVER for e in driver.events)
    survivors = [r for r in range(N) if r != VICTIM]
    assert all(out[r]["steps_run"] == STEPS for r in survivors)

    # one dump per process that got to say goodbye: the driver (incl. the
    # coordinator + chunk-server threads) and each surviving rank child —
    # the SIGKILLed victim is exactly the process that cannot dump
    dumps = sorted(p.name for p in tdir.glob("trace-*.jsonl"))
    assert any("driver" in d for d in dumps), dumps
    assert sum("rank" in d for d in dumps) >= len(survivors), dumps

    merged = trace.merge_dir(tdir)
    evs = merged["traceEvents"]
    spans = [e for e in evs if e["ph"] == "X"]
    instants = [e for e in evs if e["ph"] == "i"]

    def named(pool, name):
        return [e for e in pool if e["name"] == name]

    # --- the kill is on the timeline
    died = named(instants, "fault.rank_died")
    assert died and died[0]["args"]["rank" if "rank" in died[0]["args"]
                                    else "error"], died
    kill_ts = died[0]["ts"]

    # --- recovery sub-FSM: nested phases, causally ordered after the kill
    epochs = named(spans, "recover.epoch")
    assert len(epochs) == 1, [e["name"] for e in spans]
    epoch_id = epochs[0]["args"]["span_id"]
    phase_ts = []
    for ph in ("collect", "quiesce", "patch", "resume"):
        got = named(spans, f"recover.{ph}")
        assert got, f"recover.{ph} missing"
        assert got[0]["args"]["parent_id"] == epoch_id, ph
        phase_ts.append(got[0]["ts"])
    assert kill_ts <= phase_ts[0]
    assert phase_ts == sorted(phase_ts)
    assert epochs[0]["args"].get("outcome") == "ok"

    # --- the checkpoint round: rank images parented ACROSS the socket
    rounds = named(spans, "coord.ckpt_round")
    assert rounds
    round_ids = {e["args"]["span_id"]: e["pid"] for e in rounds}
    rank_ckpts = named(spans, "rank.ckpt")
    cross = [e for e in rank_ckpts
             if e["args"].get("parent_id") in round_ids
             and e["pid"] != round_ids[e["args"]["parent_id"]]]
    assert cross, "no rank.ckpt parented across the process boundary"

    # --- chunk uploads nested under the image save, and the service's
    # own server-side spans present on the same timeline
    save_ids = {e["args"]["span_id"] for e in named(spans,
                                                    "rank.save_image")}
    rpcs = named(spans, "chunk.rpc")
    assert any(e["args"].get("parent_id") in save_ids for e in rpcs), \
        "no chunk upload parented under a rank image save"
    assert named(spans, "chunkserver.req"), "chunk service side missing"

    # --- survivors run on after the recovery resumed the world
    resume_ts = phase_ts[-1]
    finishes = [e for e in instants if e["name"] == "rank.finish"]
    assert len(finishes) >= len(survivors)
    assert all(e["ts"] >= resume_ts for e in finishes)

    # --- cross-process flow arrows were rendered for the ctx links
    assert any(e["ph"] == "s" for e in evs)
    assert any(e["ph"] == "f" for e in evs)
