"""Extended API plumbing (Sendrecv / Alltoall / Reduce_scatter), runtime
failure detection, sharding-variant composition, and launch entrypoints."""
import json
import os
import subprocess
import sys
import time
from pathlib import Path

import numpy as np
import pytest

from repro.core import MPIJob

ROOT = Path(__file__).resolve().parents[1]


def run_app(n, step_fn, init_fn=lambda mpi: {}, steps=1, **kw):
    job = MPIJob(n, step_fn, init_fn, **kw)
    try:
        return job.run(steps, timeout=60), job
    finally:
        job.stop()


# ----------------------------------------------------------- API plumbing

@pytest.mark.parametrize("n", [2, 3, 4])
def test_sendrecv_ring(n):
    def step(mpi, st, k):
        me = mpi.Comm_rank()
        got = mpi.Sendrecv(me * 10, (me + 1) % n, 1, (me - 1) % n, 1)
        assert got == ((me - 1) % n) * 10
        return st
    run_app(n, step)


@pytest.mark.parametrize("n", [2, 4])
def test_alltoall(n):
    def step(mpi, st, k):
        me = mpi.Comm_rank()
        out = mpi.Alltoall([me * 100 + j for j in range(n)])
        assert out == [src * 100 + me for src in range(n)]
        return st
    run_app(n, step)


@pytest.mark.parametrize("n", [2, 3, 4])
def test_reduce_scatter_blocks(n):
    def step(mpi, st, k):
        me = mpi.Comm_rank()
        x = np.arange(n * 3, dtype=np.float64) * (me + 1)
        mine = mpi.Reduce_scatter(x, "sum")
        total = sum(range(1, n + 1))
        expect = np.array_split(np.arange(n * 3, dtype=np.float64) * total,
                                n)[me]
        assert np.allclose(mine, expect), (me, mine, expect)
        return st
    run_app(n, step)


def test_extended_calls_survive_restart(tmp_path):
    def init_fn(mpi):
        return {"rs": None}

    def step_fn(mpi, st, k):
        me = mpi.Comm_rank()
        if k == 2:   # after the checkpoint at step >=1
            st["rs"] = mpi.Reduce_scatter(
                np.ones(8, np.float64) * (me + 1), "sum")
        return st

    job = MPIJob(4, step_fn, init_fn)
    job.checkpoint_at(1, tmp_path / "ck", resume=False)
    job.run(3, timeout=60)
    job.stop()
    job2 = MPIJob.restart(tmp_path / "ck", step_fn, init_fn, transport="tcp")
    out = job2.run(3, timeout=60)
    job2.stop()
    for r in range(4):
        assert np.allclose(out[r]["rs"], np.ones(2) * 10)


# --------------------------------------------------- failure detection

def test_heartbeat_detects_stalled_rank():
    def step(mpi, st, k):
        if mpi.rank == 1 and k == 1:
            time.sleep(0.5)                  # stall beyond timeout
        else:
            time.sleep(0.01)
        return st

    job = MPIJob(3, step, lambda mpi: {}, heartbeat_timeout=0.2)
    import threading
    t = threading.Thread(target=lambda: job.run(3, timeout=60))
    t.start()
    detected = []
    deadline = time.time() + 5
    while time.time() < deadline and 1 not in detected:
        detected = job.heartbeat.dead_ranks()
        time.sleep(0.02)
    t.join(30)
    job.stop()
    assert 1 in detected


def test_straggler_recorded_in_job():
    def step(mpi, st, k):
        time.sleep(0.15 if mpi.rank == 2 else 0.01)
        return st

    _, job = run_app(3, step, steps=3)
    assert 2 in job.stragglers.stragglers()


# --------------------------------------------------- variant composition

def test_variant_composition():
    from repro.distributed.sharding import make_variant
    v = make_variant("seqshard+fsdp")
    assert v.mapping["seq"] == ("model",) and v.fsdp_axes == ("data",)
    v = make_variant("sp_saves+fsdp")
    assert v.mapping["seq_saves"] == ("model",)
    v = make_variant("dponly+fsdp")
    assert v.fsdp_axes == ("data", "model")
    v = make_variant("kvseq")
    assert v.mapping["kv_seq"] == ("model",) and v.mapping["kv_heads"] == ()
    with pytest.raises(KeyError):
        make_variant("fsdp+bogus")


def test_ctx_divisible_outside_ctx_defaults_true():
    from repro.distributed.sharding import ctx_divisible
    assert ctx_divisible("heads", 7)     # no mesh context -> permissive


# --------------------------------------------------- launch entrypoints

@pytest.mark.slow
def test_launch_train_cli(tmp_path):
    r = subprocess.run(
        [sys.executable, "-m", "repro.launch.train", "--arch", "smollm-135m",
         "--reduced", "--steps", "3", "--batch", "2", "--seq", "32",
         "--ckpt-dir", str(tmp_path / "ckpt")],
        capture_output=True, text=True, timeout=600,
        env={**os.environ, "PYTHONPATH": "src",
             "JAX_COMPILATION_CACHE_DIR": str(tmp_path / "jax_cache")},
        cwd=ROOT)
    assert r.returncode == 0, r.stderr[-2000:]
    last = json.loads(r.stdout.strip().splitlines()[-1])
    assert last["steps_run"] == 3 and np.isfinite(last["final_loss"])


@pytest.mark.slow
def test_launch_serve_cli(tmp_path):
    r = subprocess.run(
        [sys.executable, "-m", "repro.launch.serve", "--arch", "smollm-135m",
         "--reduced", "--batch", "2", "--prompt-len", "8",
         "--new-tokens", "8"],
        capture_output=True, text=True, timeout=600,
        env={**os.environ, "PYTHONPATH": "src",
             "JAX_COMPILATION_CACHE_DIR": str(tmp_path / "jax_cache")},
        cwd=ROOT)
    assert r.returncode == 0, r.stderr[-2000:]
    out = json.loads(r.stdout.strip().splitlines()[-1])
    assert out["tok_per_s"] > 0
