#!/usr/bin/env python3
"""Chip smoke test: train, save, kill and resume SmolLM-135M at its
published width on a TPU, through ``repro.train.loop.train`` (the function
``python -m repro.launch.train`` calls) and ``CheckpointManager``.

    python3 chip_smoke.py              # one chip
    python3 chip_smoke.py --chips 4    # (4, 1) data-parallel mesh only

One chip, every phase in this one process (a chip belongs to one process,
so nothing here starts a child that imports JAX):

  (a) refuse to run unless JAX's first device is a TPU; never fall back;
  (b) reference: N steps, no checkpoint;
  (c) the same N steps with an async save every CKPT_EVERY steps and an
      injected failure after step FAIL_AT: train() waits for the write,
      then raises;
  (d) train() again on the same root: it must resume from the last save,
      and the losses of the resumed steps must equal (b)'s bit for bit
      (same layout, same program).

``--chips 4`` runs only the mesh path and what it is compared with: (b),
(c) and (d) on the (4, 1) mesh of ``make_local_mesh()``, with the params
checked to sit on all four devices, then one chip (``devices()[0]``) as
the reference for the first-step loss.

Each earlier line of stdout is one JSON record per phase: wall time, the
compile time inside it (set-up, reported apart), persistent-cache hits and
misses, losses, ``resumed_from``, ``ckpt_stats`` and the device's peak
bytes.  The last line is ``{"ok": true, "device": {...}}``, printed only
when every phase passed; any failure exits non-zero.  Checkpoints go to
``<checkout>/.chip_smoke_ckpt``, removed on exit.
"""
from __future__ import annotations

import argparse
import json
import math
import shutil
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parent
CKPT_ROOT = ROOT / ".chip_smoke_ckpt"

ARCH = "smollm-135m"
BATCH, SEQ = 8, 1024
N_STEPS, CKPT_EVERY, FAIL_AT = 8, 4, 6
#: first-step loss, four chips vs one: same params and batch, only the
#: reduction order differs
REL_TOL_1V4 = 1e-2

_BACKEND_COMPILE = "/jax/core/compile/backend_compile_duration"
_CACHE_HIT = "/jax/compilation_cache/cache_hits"
_CACHE_MISS = "/jax/compilation_cache/cache_misses"


class SmokeFailure(Exception):
    """A phase produced a wrong result."""


def check(cond: bool, msg: str) -> None:
    if not cond:
        raise SmokeFailure(msg)


class CompileCounters:
    """Compile seconds and persistent-cache hits/misses, from JAX's own
    monitoring events (a cache hit still records a backend-compile span:
    the time to load the executable)."""

    def __init__(self):
        import jax
        self.compile_s = 0.0
        self.hits = 0
        self.misses = 0
        #: compile seconds per jitted function name, since the last take
        self.by_fn: dict = {}
        jax.monitoring.register_event_listener(self._event)
        jax.monitoring.register_event_duration_secs_listener(self._duration)

    def _event(self, event, **_):
        if event == _CACHE_HIT:
            self.hits += 1
        elif event == _CACHE_MISS:
            self.misses += 1

    def _duration(self, event, duration_secs, fun_name="?", **_):
        if event == _BACKEND_COMPILE:
            self.compile_s += duration_secs
            self.by_fn[fun_name] = self.by_fn.get(fun_name, 0.0) \
                + duration_secs

    def snapshot(self):
        return self.compile_s, self.hits, self.misses

    def take_by_fn(self) -> dict:
        out, self.by_fn = self.by_fn, {}
        return out


def device_memory(d) -> dict:
    stats = d.memory_stats()
    check(stats is not None, f"{d} reports no memory_stats")
    return stats


def peak_bytes(devices) -> list:
    return [device_memory(d)["peak_bytes_in_use"] for d in devices]


def run_phase(name, counters, devices, fn, **record):
    """Run one phase; print its record; return what `fn` returned."""
    c0, h0, m0 = counters.snapshot()
    counters.take_by_fn()
    t0 = time.perf_counter()
    out, extra = fn()
    wall = time.perf_counter() - t0
    c1, h1, m1 = counters.snapshot()
    rec = {"phase": name, "wall_s": wall, "compile_s": c1 - c0,
           "run_s": wall - (c1 - c0), "cache_hits": h1 - h0,
           "cache_misses": m1 - m0, "compile_s_by_fn": counters.take_by_fn(),
           **record, **extra,
           "peak_bytes_in_use": peak_bytes(devices)}
    print(json.dumps(rec), flush=True)
    return out


def train_phase(cfg, mesh, rules, ckpt_root=None, fail_at=None):
    """One train() call; returns (TrainResult or None, record fields).
    Only the injected failure, matched by its message, is caught."""
    from repro.train.loop import train
    try:
        res = train(cfg, mesh, rules, n_steps=N_STEPS, global_batch=BATCH,
                    seq_len=SEQ, ckpt_root=ckpt_root, ckpt_every=CKPT_EVERY,
                    fail_at_step=fail_at, log_every=1, seed=0)
    except RuntimeError as e:
        if (fail_at is None
                or str(e) != f"injected failure after step {fail_at}"):
            raise
        return None, {"injected_failure": str(e)}
    check(all(math.isfinite(x) for x in res.losses),
          f"non-finite loss: {res.losses}")
    return res, {"losses": res.losses, "resumed_from": res.resumed_from,
                 "steps_run": res.steps_run, "ckpt_stats": res.ckpt_stats}


def kill_and_resume(cfg, mesh, rules, ref, counters, devices, tag=""):
    """Phases (c) and (d) against the uninterrupted reference `ref`."""
    from repro.checkpoint.manager import CheckpointManager
    shutil.rmtree(CKPT_ROOT, ignore_errors=True)
    run_phase(f"save_and_kill{tag}", counters, devices,
              lambda: train_phase(cfg, mesh, rules, CKPT_ROOT, FAIL_AT))
    last_save = FAIL_AT // CKPT_EVERY * CKPT_EVERY
    on_disk = CheckpointManager(CKPT_ROOT).list_steps()
    check(on_disk and on_disk[-1] == last_save,
          f"newest checkpoint on disk {on_disk}, expected step {last_save}")
    hits = counters.hits
    res = run_phase(f"resume{tag}", counters, devices,
                    lambda: train_phase(cfg, mesh, rules, CKPT_ROOT))
    res.state = None
    check(res.resumed_from == last_save,
          f"resumed_from={res.resumed_from}, last save was {last_save}")
    want = ref.losses[last_save:]
    check(res.losses == want,
          f"resumed losses {res.losses} differ from the uninterrupted "
          f"run's {want}")
    check(counters.hits > hits, "the resumed run found nothing in the "
          "compile cache")
    shutil.rmtree(CKPT_ROOT, ignore_errors=True)


def one_chip(cfg, counters):
    from repro.distributed.sharding import make_variant
    from repro.launch.mesh import make_local_mesh
    mesh = make_local_mesh(n=1)
    rules = make_variant("baseline")
    devices = list(mesh.devices.flat)
    ref = run_phase("reference", counters, devices,
                    lambda: train_phase(cfg, mesh, rules))
    ref.state = None
    check(abs(ref.losses[0] - math.log(cfg.vocab_size)) < 0.5,
          f"first loss {ref.losses[0]} is not near ln(vocab) at init")
    kill_and_resume(cfg, mesh, rules, ref, counters, devices)


def four_chips(cfg, counters):
    import jax
    from repro.distributed.sharding import make_variant
    from repro.launch.mesh import make_local_mesh
    devices = jax.devices()
    check(len(devices) == 4, f"--chips 4 needs 4 devices, have {len(devices)}")
    mesh = make_local_mesh()
    check(dict(mesh.shape) == {"data": 4, "model": 1},
          f"unexpected mesh {dict(mesh.shape)}")
    rules = make_variant("baseline")
    ref = run_phase("reference_4chip", counters, devices,
                    lambda: train_phase(cfg, mesh, rules),
                    mesh=dict(mesh.shape))

    # the state train() left on the mesh: every param on all four devices,
    # and each device's memory holds its share
    params = jax.tree.leaves(ref.state["params"])
    share = {d: 0 for d in devices}
    for leaf in params:
        check(leaf.sharding.device_set == set(devices),
              f"param on {leaf.sharding.device_set}, not on all 4 devices")
        for sh in leaf.addressable_shards:
            share[sh.device] += sh.data.nbytes
    in_use = {d: device_memory(d)["bytes_in_use"] for d in devices}
    for d in devices:
        check(0 < share[d] <= in_use[d],
              f"{d}: param share {share[d]} B, bytes_in_use {in_use[d]}")
    print(json.dumps({"phase": "layout_4chip",
                      "param_share_bytes": [share[d] for d in devices],
                      "bytes_in_use": [in_use[d] for d in devices],
                      "param_sharding": str(params[0].sharding)}), flush=True)
    ref.state = params = None

    kill_and_resume(cfg, mesh, rules, ref, counters, devices, tag="_4chip")

    mesh1 = make_local_mesh(n=1)
    one = run_phase("reference_1chip", counters, devices,
                    lambda: train_phase(cfg, mesh1, rules),
                    mesh=dict(mesh1.shape), device=str(devices[0]))
    one.state = None
    gap = abs(ref.losses[0] - one.losses[0]) / abs(one.losses[0])
    print(json.dumps({"phase": "compare_1v4", "first_loss_4chip":
                      ref.losses[0], "first_loss_1chip": one.losses[0],
                      "rel_gap": gap, "rel_tol": REL_TOL_1V4,
                      "rel_gap_per_step": [
                          abs(a - b) / abs(b)
                          for a, b in zip(ref.losses, one.losses)]}),
          flush=True)
    check(gap <= REL_TOL_1V4,
          f"first-step loss 4 chips {ref.losses[0]} vs 1 chip "
          f"{one.losses[0]}: relative gap {gap} > {REL_TOL_1V4}")


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    args = ap.parse_args()

    import jax
    dev0 = jax.devices()[0]
    if dev0.platform != "tpu":
        print(f"chip_smoke: needs a TPU, JAX's first device is "
              f"{dev0.platform!r} ({dev0.device_kind}); refusing to run",
              file=sys.stderr)
        return 2

    sys.path.insert(0, str(ROOT / "src"))
    from repro.configs import get_arch
    from repro.launch.compile_cache import enable_compile_cache

    cache_dir = enable_compile_cache()
    counters = CompileCounters()
    cfg = get_arch(ARCH)
    print(json.dumps({"arch": cfg.name, "params_m": cfg.n_params() / 1e6,
                      "n_layers": cfg.n_layers, "d_model": cfg.d_model,
                      "global_batch": BATCH, "seq_len": SEQ,
                      "n_steps": N_STEPS, "ckpt_every": CKPT_EVERY,
                      "fail_at_step": FAIL_AT, "chips": args.chips,
                      "device_kind": dev0.device_kind,
                      "compile_cache_dir": cache_dir}), flush=True)
    try:
        if args.chips == 4:
            four_chips(cfg, counters)
        else:
            one_chip(cfg, counters)
    finally:
        shutil.rmtree(CKPT_ROOT, ignore_errors=True)
    print(json.dumps({"ok": True, "device": {
        "platform": dev0.platform, "kind": dev0.device_kind,
        "count": len(jax.devices())}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
