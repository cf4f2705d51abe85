#!/usr/bin/env python3
"""Run one cell of the chip benchmark once, on the chips of this machine.

    python3 chipbench/run.py --workload <cell> --seed <n> --seconds <s> \\
        --trace <0|1>

The cell's configuration, traffic, limits and per-layer readers are found
by the names in ``BENCHMARK.json`` (see ``harness.py``).  With
``--trace 0`` the result holds the cell's end-to-end metrics; with
``--trace 1`` the window runs under the JAX profiler and the result holds
its per-layer metrics, the device's busy and window seconds, and a
breakdown.  Earlier stdout lines are JSON records of set-up (compile-cache
hits and misses, steps); the last stdout line is the result; the last
stderr lines give each number compared with `correct` beside its limit.

`memory_peak_bytes` is the larger of the allocator's peak after the
window and the compiled step's own peak, temporaries included, which the
allocator does not count.

It refuses to run (exit 2, no result) unless JAX's first device is a TPU
and there are as many as the cell asks for.  Checkpoints and traces go to
``<checkout>/.chipbench_run`` and are removed before it exits; JAX's
compile cache is ``<checkout>/.jax_cache``.
"""
from __future__ import annotations

import time

T_START = time.monotonic()          # process start, before JAX is imported

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
from contextlib import contextmanager  # noqa: E402
from pathlib import Path  # noqa: E402

HERE = Path(__file__).resolve().parent
ROOT = HERE.parent


def log(**rec) -> None:
    print(json.dumps(rec), flush=True)


def parse(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    return ap.parse_args(argv)


def make_tracer(trace_on: bool, trace_dir: Path):
    """The window's context manager: a host span around it, and the JAX
    profiler when `trace_on`."""
    from chipbench.harness import annotate
    import jax

    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0        # no per-Python-call events
    marks: list = []

    @contextmanager
    def tracer(run):
        if trace_on:
            jax.profiler.start_trace(str(trace_dir), profiler_options=opts)
        try:
            with annotate("chipbench.window", marks):
                yield
        finally:
            if trace_on:
                jax.profiler.stop_trace()
        run.window = marks[-1][1:]
    return tracer


def read_trace(run, counters, trace_dir: Path) -> None:
    """Reduce the window's trace; host spans of the harness, the program
    (``ckptmgr.*``) and JAX's compile phases label the idle gaps."""
    from chipbench import traces
    tr = traces.load(str(trace_dir))
    win = traces.host_events(tr, ("chipbench.window",))
    if not win:
        return
    name, lo, dur = max(win, key=lambda e: e[2])
    # the window's span is on both clocks: CLOCK_MONOTONIC -> trace ns
    shift = lo - int(run.window[0] * 1e9)
    spans = [(n, int(t0 * 1e9) + shift, int(d * 1e9))
             for n, t0, d in run.program_spans + [
                 s for s in counters.spans
                 if run.window[0] <= s[1] <= run.window[1]]]
    spans.append(("train loop (no finer span)", lo, dur))
    run.trace = traces.reduce(tr, lo, lo + dur, spans)


def main(argv=None, require_tpu: bool = True, base=None,
         bench_file=None) -> int:
    """`require_tpu`, `base` and `bench_file` are for tests: run on the
    CPU, find the cell's files elsewhere."""
    args = parse(argv)
    # the benchmark's compile cache, inside the checkout at a fixed path
    # (JAX writes no entry into a directory that does not exist)
    # libtpu would log under /tmp, outside the checkout
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    cache = (base or HERE).parent / ".jax_cache"
    os.environ["JAX_COMPILATION_CACHE_DIR"] = str(cache)
    cache.mkdir(exist_ok=True)
    for p in (str(ROOT / "src"), str(ROOT)):
        if p not in sys.path:
            sys.path.insert(0, p)
    from chipbench import harness
    try:
        cell = harness.find_cell(args.workload, base or HERE, bench_file)
    except harness.BenchError as e:
        print(f"chipbench: {e}", file=sys.stderr)
        return 2

    import jax
    # every program, however quick to compile, goes to the cache, so a
    # warm run compiles nothing
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    devices = jax.devices()
    dev0 = devices[0]
    if require_tpu and dev0.platform != "tpu":
        print(f"chipbench: needs a TPU; JAX's first device is "
              f"{dev0.platform!r} ({dev0.device_kind}); refusing to run",
              file=sys.stderr)
        return 2
    if len(devices) < cell.chips:
        print(f"chipbench: {cell.name} needs {cell.chips} chips, JAX sees "
              f"{len(devices)}", file=sys.stderr)
        return 2
    used = devices[:cell.chips]
    if cell.chips != 1:
        print("chipbench: only one-chip cells are implemented",
              file=sys.stderr)
        return 2

    from repro.core import trace as program_trace
    from repro.distributed.sharding import make_variant
    from repro.launch.compile_cache import enable_compile_cache
    from repro.launch.mesh import make_local_mesh
    from chipbench.flops import train_step_flops

    cache_dir = enable_compile_cache()
    counters = harness.CompileCounters()
    peaks = (harness.peaks_for(dev0.device_kind, cell.base) if require_tpu
             else {"bf16_flops_per_s": float("nan")})
    run = harness.Run(cell=cell, peaks=peaks)
    p = cell.params
    step_flops = train_step_flops(cell.cfg, p["global_batch"], p["seq_len"])
    run.flops_per_step = step_flops["total"] if step_flops else None

    trace_dir = harness.SCRATCH / "trace"
    state = {}

    def setup_done():
        state["setup_s"] = time.monotonic() - T_START
        state["c0"] = counters.snapshot()
        log(phase="setup", setup_s=state["setup_s"],
            compile_cache_dir=cache_dir, **counters.since(
                {"seconds": {}, "hits": 0, "misses": 0}))

    def window_done():
        state["c1"] = counters.since(state["c0"])
        state["peak"] = max(d.memory_stats()["peak_bytes_in_use"]
                            for d in used) if require_tpu else 0

    env = {"cfg": harness.program_config(cell.cfg),
           "mesh": make_local_mesh(n=1), "rules": make_variant("baseline"),
           "setup_done": setup_done, "window_done": window_done}
    shutil.rmtree(harness.SCRATCH, ignore_errors=True)
    try:
        harness.run_train(run, env, args.seed, args.seconds,
                          make_tracer(bool(args.trace), trace_dir))
        lo, hi = run.window
        run.compile = state["c1"]
        run.program_spans = [
            (e.name, e.t0, e.dur) for e in program_trace.recorder().snapshot()
            if getattr(e, "dur", None) is not None and lo <= e.t0 <= hi]
        log(phase="window", seconds=hi - lo, steps=run.steps,
            **run.compile)
        mem = harness.step_footprint(env["cfg"], env["mesh"], env["rules"],
                                     cell.params, run.steps)
        log(phase="memory", allocator_peak=state["peak"], **mem)
        state["peak"] = max(state["peak"], mem["footprint"])
        if args.trace:
            read_trace(run, counters, trace_dir)
            shutil.rmtree(trace_dir, ignore_errors=True)
        t0 = time.monotonic()
        correct, checks, nums = harness.check(run, args.seed)
        log(phase="compared", seconds=time.monotonic() - t0, **nums)
    finally:
        shutil.rmtree(harness.SCRATCH, ignore_errors=True)

    metrics = {}
    if args.trace:
        for m in cell.per_layer:
            value = harness.load_reader(m["name"], cell.base)(run)
            if value is not None:
                metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    else:
        run.end_to_end["setup_s"] = state["setup_s"]
        for m in cell.end_to_end:
            metrics[m["name"]] = {"value": run.end_to_end[m["name"]],
                                  "unit": m["unit"]}
    device = {"platform": dev0.platform, "kind": dev0.device_kind,
              "count": len(used), "memory_peak_bytes": state["peak"]}
    result = {"correct": correct,
              "attempted": run.steps, "failed": 0,
              "metrics": metrics, "device": device}
    if args.trace and run.trace:
        device["busy_s"] = run.trace["busy_s"]
        device["window_s"] = run.trace["window_s"]
        result["breakdown"] = {"device_ops": run.trace["device_ops"],
                               "idle_gaps": run.trace["idle_gaps"]}
    result["checks"] = {k: {"value": c["value"], "limit": c["limit"]}
                        for k, c in checks.items()}
    for k, c in checks.items():
        print(f"check {k} {c['value']!r} limit {c['limit']!r} "
              f"{'ok' if c['ok'] else 'FAIL'} at {c['at']}", file=sys.stderr)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
