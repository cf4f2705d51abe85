"""Spans and counters inside ``train()`` and the save path (DESIGN.md §16).

The profiler mirror of context spans (and its absence for detached
handles, with tracing off, before jax is imported and in a forked
child), the loop's spans and how they nest, the snapshot's split into
transfer and host copy, and the drain's split into device drain and the
wait for the previous write.
"""
import json
import os
import subprocess
import sys
import threading
import time
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.checkpoint import serialization as ser
from repro.checkpoint.manager import CheckpointManager
from repro.configs import ARCHS, reduce_for_smoke
from repro.core import trace
from repro.distributed.sharding import make_variant
from repro.launch.mesh import make_local_mesh
from repro.train.loop import train

SRC = Path(__file__).resolve().parents[1] / "src"


@pytest.fixture
def enabled():
    prev = trace.ENABLED
    trace.set_enabled(True)
    trace.clear()
    yield
    trace.set_enabled(prev)


class _Annotations:
    """A stand-in for jax.profiler.TraceAnnotation that logs (name, event,
    thread) for every enter and exit."""

    def __init__(self):
        self.log = []
        outer = self

        class Annotation:
            def __init__(self, name):
                self.name = name

            def __enter__(self):
                outer.log.append((self.name, "enter",
                                  threading.get_ident()))

            def __exit__(self, *exc):
                outer.log.append((self.name, "exit",
                                  threading.get_ident()))
        self.cls = Annotation


@pytest.fixture
def annotations(monkeypatch):
    fake = _Annotations()
    monkeypatch.setattr(trace, "_ANNOTATION", fake.cls)
    return fake


def _spans(prefix=""):
    return [e for e in trace.recorder().snapshot()
            if e.kind == "span" and e.name.startswith(prefix)]


def _tiny_train(tmp_path, **kw):
    cfg = reduce_for_smoke(ARCHS["smollm-135m"])
    args = dict(n_steps=5, global_batch=2, seq_len=16, log_every=2, seed=3,
                ckpt_root=tmp_path / "ck", ckpt_every=2, keep=0)
    args.update(kw)
    return train(cfg, make_local_mesh(n=1), make_variant("baseline"), **args)


# ---------------------------------------------------------- profiler mirror

def test_context_spans_mirror_into_profiler_annotations(enabled,
                                                        annotations):
    with trace.span("outer"):
        with trace.span("inner"):
            pass
    handle = trace.begin("detached")
    handle.end()
    me = threading.get_ident()
    assert annotations.log == [("outer", "enter", me), ("inner", "enter", me),
                               ("inner", "exit", me), ("outer", "exit", me)]
    assert {e.name for e in _spans()} == {"outer", "inner", "detached"}


def test_disabled_tracing_opens_no_span_and_no_annotation(enabled,
                                                          annotations):
    trace.set_enabled(False)
    with trace.span("x"):
        with trace.span("y"):
            pass
    assert annotations.log == [] and len(trace.recorder()) == 0


def test_mirror_waits_for_jax_and_is_off_in_a_forked_child():
    """core/trace.py imports no jax; once a process has imported
    jax.profiler its context spans annotate, but never in a fork()ed
    child.  A stand-in jax.profiler keeps XLA out of the subprocess."""
    code = """
import json, os, sys, types
from repro.core import trace
assert "jax" not in sys.modules
made = []
class Annotation:
    def __init__(self, name): made.append(name)
    def __enter__(self): pass
    def __exit__(self, *exc): pass
with trace.span("before_jax"):
    pass
prof = types.ModuleType("jax.profiler")
prof.TraceAnnotation = Annotation
sys.modules["jax.profiler"] = prof
with trace.span("after_jax"):
    pass
r, w = os.pipe()
pid = os.fork()
if pid == 0:
    with trace.span("in_child"):
        pass
    os.write(w, json.dumps(made).encode())
    os._exit(0)
os.waitpid(pid, 0)
print(json.dumps({"parent": made, "child": json.loads(os.read(r, 4096)),
                  "jax": "jax" in sys.modules}))
"""
    env = dict(os.environ, PYTHONPATH=str(SRC), REPRO_TRACE="1")
    out = subprocess.run([sys.executable, "-c", code], env=env,
                         capture_output=True, text=True, timeout=60)
    assert out.returncode == 0, out.stderr
    got = json.loads(out.stdout.strip().splitlines()[-1])
    assert got == {"parent": ["after_jax"], "child": ["after_jax"],
                   "jax": False}


# ------------------------------------------------------------- save path

def test_host_array_counts_one_copy_of_replicated_shards():
    """Two shards over one index window (a replicated leaf) are moved
    and counted once."""
    data = np.arange(12, dtype=np.float32).reshape(3, 4)

    class Shard:
        def __init__(self, dev):
            self.index = (slice(None), slice(None))
            self.data = data
            self.device = type("Dev", (), {"id": dev})()

    class Replicated:
        shape, dtype, ndim = data.shape, data.dtype, 2
        addressable_shards = [Shard(0), Shard(1)]

    host = ser.HostArray(Replicated())
    assert len(host.shards) == 1
    assert host.nbytes == data.nbytes
    assert host.transfer_s > 0 and host.copy_s > 0
    parts = ser.snapshot_parts({"a": host, "b": host, "n": np.int64(1)})
    assert parts == {"snapshot_transfer_s": 2 * host.transfer_s,
                     "snapshot_copy_s": 2 * host.copy_s,
                     "snapshot_bytes": 2 * data.nbytes}


def test_save_splits_snapshot_and_drain(tmp_path, enabled, monkeypatch):
    state = {"w": jnp.arange(64 * 64, dtype=jnp.float32).reshape(64, 64),
             "b": jnp.ones(7, jnp.bfloat16), "step": jnp.int32(3),
             "seed": np.int64(5)}
    state_bytes = sum(x.nbytes for x in jax.tree.leaves(state)
                      if isinstance(x, jax.Array))
    real_ready = jax.block_until_ready

    def slow_ready(x):
        time.sleep(0.03)
        return real_ready(x)

    monkeypatch.setattr(jax, "block_until_ready", slow_ready)
    mgr = CheckpointManager(tmp_path, keep=0)
    real_wait = mgr.wait

    def slow_wait():
        time.sleep(0.02)
        real_wait()

    mgr.wait = slow_wait
    for step in (1, 2):
        mgr.save(step, state)
    real_wait()
    st = mgr.stats
    assert st["saves"] == 2
    assert st["snapshot_transfer_s"] > 0 and st["snapshot_copy_s"] > 0
    assert st["snapshot_transfer_s"] + st["snapshot_copy_s"] \
        <= st["snapshot_s"]
    assert st["snapshot_bytes"] == 2 * state_bytes
    assert st["drain_device_s"] >= 2 * 0.03
    assert st["drain_write_wait_s"] >= 2 * 0.02
    spans = {}
    for e in _spans("ckptmgr."):
        spans.setdefault(e.name, []).append(e)
    assert "ckptmgr.drain" not in spans
    # the drain is its two parts: from the device drain's start to the
    # snapshot's start, save by save
    drained = sum(s.t0 - d.t0 for d, s in zip(spans["ckptmgr.drain_device"],
                                              spans["ckptmgr.snapshot"]))
    assert st["drain_device_s"] + st["drain_write_wait_s"] \
        == pytest.approx(drained, abs=5e-3)
    assert st["drain_device_s"] == pytest.approx(
        sum(e.dur for e in spans["ckptmgr.drain_device"]), abs=2e-3)
    assert st["drain_write_wait_s"] == pytest.approx(
        sum(e.dur for e in spans["ckptmgr.write_wait"]), abs=2e-3)
    # each snapshot span carries its own save's parts
    for e in spans["ckptmgr.snapshot"]:
        assert e.args["snapshot_bytes"] == state_bytes
        assert e.args["snapshot_transfer_s"] + e.args["snapshot_copy_s"] \
            <= e.dur
    assert sum(e.args["snapshot_copy_s"] for e in spans["ckptmgr.snapshot"]) \
        == pytest.approx(st["snapshot_copy_s"])


# ------------------------------------------------------------------ loop

def test_train_spans_cover_startup_steps_and_saves(tmp_path, enabled):
    res = _tiny_train(tmp_path)
    assert res.steps_run == 5 and not hasattr(res, "wall_s")
    evs = _spans("train.")
    by = {}
    for e in evs:
        by.setdefault(e.name, []).append(e)
    (startup,) = by["train.startup"]
    (init,) = by["train.init_state"]
    (restore,) = by["train.restore"]
    assert init.parent_id == startup.span_id
    assert restore.parent_id == startup.span_id
    steps = by["train.step"]
    assert [e.args["step"] for e in steps] == [0, 1, 2, 3, 4]
    # start-up ends with the first step's dispatch, which it holds
    assert steps[0].parent_id == startup.span_id
    assert startup.t0 + startup.dur == pytest.approx(
        steps[0].t0 + steps[0].dur, abs=1e-3)
    assert all(e.parent_id is None for e in steps[1:])
    batches = by["train.batch"]
    assert [b.parent_id for b in batches] == [s.span_id for s in steps]
    assert len(by["train.log_sync"]) == len(res.losses) == 3
    assert [e.args["step"] for e in by["train.save"]] == [2, 4]
    (final,) = by["train.final_wait"]
    assert final.t0 > steps[-1].t0
    (teardown,) = by["train.teardown"]
    assert teardown.t0 >= final.t0 + final.dur
    # each save of the manager under the loop's save span
    assert [e.parent_id for e in _spans("ckptmgr.save")] \
        == [e.span_id for e in by["train.save"]]
    assert res.ckpt_stats["snapshot_bytes"] > 0


def test_train_records_nothing_with_tracing_off(tmp_path, enabled,
                                                annotations):
    trace.set_enabled(False)
    res = _tiny_train(tmp_path, n_steps=3)
    assert res.steps_run == 3
    assert len(trace.recorder()) == 0
    assert annotations.log == []
