"""The benchmark harness: finds a cell's files by name, runs the system's
normal training entry (``repro.train.loop.train``) through set-up and the
measured window, reads the per-layer metrics, and decides `correct`
against the float32 reference.

Files, all found by the names in ``BENCHMARK.json``:

  configs/<config>.json        sizes (the system's own field names), the
                               source, what was reduced, and the name of
                               the plain reference under reference/;
                               nested sections (``moe``, ``mla``,
                               ``encoder``) by the field names of the
                               program's config dataclasses; a list
                               becomes a tuple where the field is one
  traffic/<traffic>.json       the parameters of that traffic mix
  workloads/<cell>.json        the cell's own values on top of the
                               traffic's (batch, nominal rate, limits)
  metrics/<per-layer name>.py  a reader: ``read(run) -> float | None``

One process, one cell, one run:

  set-up  one ``train()`` call with the window's step count that stops
          (train()'s injected failure) after its first save, or after its
          first step in a cell that saves nothing: the window's program
          is then in the persistent compile cache and a save is warm.
  window  one ``train()`` call of the window's step count, from an empty
          checkpoint root, saving every `ckpt_every` steps where the
          traffic says so and keeping every save.
  checked after the window, against the reference's first `check_steps`
          steps from the same seeded weights and batches: the losses the
          window logged before then; in a cell that saves, the window's
          first save (its params and Adam moments, read back through the
          system's own reader), and its last save, at the last step,
          against the state the call returned, bit for bit; and the final
          step counters.
"""
from __future__ import annotations

import dataclasses
import importlib.util
import json
import shutil
import time
import typing
from contextlib import contextmanager
from dataclasses import dataclass, field
from pathlib import Path
from typing import Callable, Dict, List, Optional

HERE = Path(__file__).resolve().parent
ROOT = HERE.parent
#: checkpoints and traces of a run, removed when it ends
SCRATCH = ROOT / ".chipbench_run"

_TRACE_EV = "/jax/core/compile/jaxpr_trace_duration"
_MLIR_EV = "/jax/core/compile/jaxpr_to_mlir_module_duration"
_BACKEND_EV = "/jax/core/compile/backend_compile_duration"
_CACHE_HIT = "/jax/compilation_cache/cache_hits"
_CACHE_MISS = "/jax/compilation_cache/cache_misses"


class BenchError(Exception):
    """The cell cannot be run as asked (no chip, unknown name, ...)."""


# --------------------------------------------------------------------------
# Finding a cell by name
# --------------------------------------------------------------------------

def _load_json(path: Path) -> dict:
    if not path.is_file():
        raise BenchError(f"missing {path.relative_to(path.parents[1])}")
    return json.loads(path.read_text())


@dataclass
class Cell:
    name: str
    chips: int
    cfg: dict                      # configs/<config>.json
    params: dict                   # traffic/<traffic>.json + workload file
    end_to_end: List[dict]         # metrics this cell reports, trace 0
    per_layer: List[dict]          # metrics this cell reports, trace 1
    base: Path = HERE              # the directory its files were found in


def _applies(metric: dict, cell: str) -> bool:
    return "workloads" not in metric or cell in metric["workloads"]


def find_cell(name: str, base: Path = HERE,
              bench_file: Optional[Path] = None) -> Cell:
    bench = _load_json(bench_file or base.parent / "BENCHMARK.json")
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise BenchError(f"unknown workload {name!r}; known: {sorted(cells)}")
    w = cells[name]
    cfg = _load_json(base / "configs" / f"{w['config']}.json")
    params = _load_json(base / "traffic" / f"{w['traffic']}.json")
    own = base / "workloads" / f"{name}.json"
    if own.is_file():
        params.update(json.loads(own.read_text()))
    return Cell(name, int(w["chips"]), cfg, params,
                [m for m in bench["end_to_end"] if _applies(m, name)],
                [m for m in bench["per_layer"] if _applies(m, name)], base)


def load_reader(metric: str, base: Path = HERE) -> Callable:
    path = base / "metrics" / f"{metric}.py"
    if not path.is_file():
        raise BenchError(f"no reader metrics/{metric}.py")
    spec = importlib.util.spec_from_file_location(
        "chipbench_metric_" + metric.replace(".", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def load_reference(cfg: dict, base: Path = HERE):
    path = base / "reference" / f"{cfg['reference']}.py"
    spec = importlib.util.spec_from_file_location(
        "chipbench_reference_" + cfg["reference"], path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def peaks_for(kind: str, base: Path = HERE) -> dict:
    table = _load_json(base / "peaks.json")["devices"]
    if kind not in table:
        raise BenchError(f"no peaks for device kind {kind!r} in peaks.json")
    return table[kind]


# --------------------------------------------------------------------------
# JAX's compile events, and host spans on two clocks
# --------------------------------------------------------------------------

class CompileCounters:
    """Seconds per compile phase and persistent-cache hits/misses, from
    JAX's own monitoring events (a cache hit still records a
    backend-compile duration: the time to load the executable)."""

    def __init__(self):
        import jax
        self.seconds: Dict[str, float] = {}
        self.hits = 0
        self.misses = 0
        #: (short event name, start, duration) on CLOCK_MONOTONIC seconds
        self.spans: List[tuple] = []
        jax.monitoring.register_event_listener(self._event)
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_time_span_listener(self._span)

    def _event(self, event, **_):
        if event == _CACHE_HIT:
            self.hits += 1
        elif event == _CACHE_MISS:
            self.misses += 1

    def _duration(self, event, duration_secs, **_):
        self.seconds[event] = self.seconds.get(event, 0.0) + duration_secs

    def _span(self, event, start_time, end_time, **_):
        # JAX stamps these with time.time(); move them to CLOCK_MONOTONIC
        shift = time.monotonic() - time.time()
        self.spans.append(("jax." + event.rsplit("/", 1)[-1],
                           start_time + shift, end_time - start_time))

    def snapshot(self) -> dict:
        return {"seconds": dict(self.seconds), "hits": self.hits,
                "misses": self.misses}

    def since(self, snap: dict) -> dict:
        sec = {k: v - snap["seconds"].get(k, 0.0)
               for k, v in self.seconds.items()}
        return {"lower_s": sec.get(_TRACE_EV, 0.0) + sec.get(_MLIR_EV, 0.0),
                "backend_s": sec.get(_BACKEND_EV, 0.0),
                "hits": self.hits - snap["hits"],
                "misses": self.misses - snap["misses"]}


@contextmanager
def annotate(name: str, spans: list):
    """A host span in the profiler's trace (when one is recording) and in
    `spans` as (name, start, end) on CLOCK_MONOTONIC seconds."""
    import jax
    t0 = time.monotonic()
    with jax.profiler.TraceAnnotation(name):
        yield
    spans.append((name, t0, time.monotonic()))


# --------------------------------------------------------------------------
# What one run collects
# --------------------------------------------------------------------------

@dataclass
class Run:
    """Everything a per-layer reader may read; see metrics/*.py."""
    cell: Cell
    peaks: dict
    steps: int = 0                      # train steps in the window
    flops_per_step: Optional[float] = None   # None: flops.py cannot count it
    ckpt_stats: List[dict] = field(default_factory=list)
    program_spans: List[tuple] = field(default_factory=list)  # (name, t0, dur)
    compile: dict = field(default_factory=dict)
    trace: Optional[dict] = None        # traces.reduce() of the window
    end_to_end: Dict[str, float] = field(default_factory=dict)
    window: tuple = (0.0, 0.0)          # CLOCK_MONOTONIC seconds
    prog: dict = field(default_factory=dict)   # program outputs checked


def _field_value(hint, value, where: str):
    """A file's value for a field typed `hint`: a nested object built as
    the config dataclass the field holds, a list made a tuple where the
    field is a tuple, anything else as it is."""
    if value is None:
        return None
    kinds = (typing.get_args(hint) if typing.get_origin(hint) is typing.Union
             else (hint,))
    for kind in kinds:
        if dataclasses.is_dataclass(kind):
            if not isinstance(value, dict):
                raise BenchError(f"{where} must be an object of "
                                 f"{kind.__name__}'s fields")
            return _build(kind, value, where, strict=True)
        if typing.get_origin(kind) is tuple and isinstance(value, list):
            return tuple(value)
    return value


def _build(cls, values: dict, where: str, strict: bool):
    """`cls` from a file's object, each field by its declared type.  A key
    that is no field of `cls` is skipped at the top level (`source`,
    `reduced`, ...) and refused inside a nested section (`strict`), where
    it can only be a misspelt field."""
    hints = typing.get_type_hints(cls)
    names = [f.name for f in dataclasses.fields(cls) if f.init]
    if strict:
        unknown = sorted(set(values) - set(names))
        if unknown:
            raise BenchError(f"{where}: {cls.__name__} has no field "
                             f"{unknown[0]!r}")
    kw = {n: _field_value(hints[n], values[n], f"{where}.{n}")
          for n in names if n in values}
    try:
        return cls(**kw)
    except TypeError as e:          # a required field left out
        raise BenchError(f"{where}: {e}") from None


def program_config(cfg: dict):
    """The system's ArchConfig from a configuration file: its fields by
    their declared types, nested sections (``moe``, ``mla``, ...) as the
    program's own config dataclasses."""
    from repro.configs.base import ArchConfig
    return _build(ArchConfig, cfg, cfg.get("name", "config"), strict=False)


def _injected(fn, at: int):
    """Run `fn`, which must stop with train()'s injected failure."""
    try:
        fn()
    except RuntimeError as e:
        if str(e) != f"injected failure after step {at}":
            raise
        return
    raise BenchError(f"train() did not stop after step {at}")


def _flat_host(tree) -> dict:
    import numpy as np
    from chipbench.compare import flat_names
    return {k: np.asarray(v) for k, v in flat_names(tree).items()}


def read_save(root: Path, step: int, cfg, seq: int) -> dict:
    """The window's save of `step`, read back with the system's own
    reader (chunks fetched and checked against their digests) into host
    arrays, flat by name ("train/params/...", "data/seed", ...)."""
    import jax
    import numpy as np
    from repro.checkpoint import serialization as ser
    from repro.train.state import make_train_state
    d = root / f"step_{step:010d}"
    if not d.is_dir():
        raise BenchError(f"the window left no save of step {step}")
    template = {"train": jax.eval_shape(
        lambda: make_train_state(cfg, jax.random.PRNGKey(0), seq)),
        "data": {"seed": np.int64(0), "cursor": np.int64(0)}}
    return _flat_host(ser.restore_tree(d, template))


def logged_steps(n: int, every: int) -> List[int]:
    """The steps whose loss train() reads back to the host."""
    return [t for t in range(n) if t % every == 0 or t == n - 1]


def window_steps(p: dict, seconds: float) -> int:
    """Steps of the window: nominal rate x seconds, at least the steps
    checked, and in a cell that saves a whole number of save periods, so
    that its last save is of its last step."""
    n = max(p["check_steps"], round(p["nominal_steps_per_s"] * seconds))
    k = p.get("ckpt_every")
    if k:
        n = max(1, round(n / k)) * k
    return n


def _roundtrip(last: dict, final: dict, seed: int, n: int) -> tuple:
    """(leaves that differ, names): the last save against the state the
    call returned, and the pipeline's seed and cursor as saved."""
    import numpy as np
    bad = [k for k, v in final.items()
           if not np.array_equal(last.get("train/" + k), v)]
    if int(last["data/seed"]) != seed:
        bad.append("data/seed")
    if int(last["data/cursor"]) != n:
        bad.append("data/cursor")
    return len(bad), bad[:8]


def run_train(run: Run, env: dict, seed: int, seconds: float,
              tracer) -> None:
    from repro.train.loop import train
    p = run.cell.params
    cfg, mesh, rules = env["cfg"], env["mesh"], env["rules"]
    n = window_steps(p, seconds)
    k = p.get("ckpt_every")
    chk = p["check_steps"]
    if k and chk != k:
        raise BenchError("a cell that saves checks its first save: "
                         "check_steps must equal ckpt_every")
    opt = p["optimizer"]
    kw = dict(n_steps=n, global_batch=p["global_batch"],
              seq_len=p["seq_len"], base_lr=opt["base_lr"],
              warmup=opt["warmup"], seed=seed, log_every=p["log_every"],
              ckpt_every=k or n + 1, keep=0)
    warm = SCRATCH / "warm" if k else None
    _injected(lambda: train(cfg, mesh, rules, ckpt_root=warm,
                            fail_at_step=k or 1, **kw), k or 1)
    if warm is not None:
        shutil.rmtree(warm)
    timed = SCRATCH / "timed" if k else None
    env["setup_done"]()
    with tracer(run):
        t0 = time.perf_counter()
        res = train(cfg, mesh, rules, ckpt_root=timed, **kw)
        wall = time.perf_counter() - t0
    env["window_done"]()
    if res.steps_run != n:
        raise BenchError(f"window ran {res.steps_run} steps, not {n}")
    run.steps = n
    run.ckpt_stats = [dict(res.ckpt_stats)] if res.ckpt_stats else []
    run.end_to_end["tokens_per_s"] = \
        p["global_batch"] * p["seq_len"] * n / wall
    losses = dict(zip(logged_steps(n, p["log_every"]), res.losses))
    final = _flat_host(res.state)
    del res
    run.prog = {"losses": {t: v for t, v in losses.items() if t < chk},
                "counters": (int(final["step"]), int(final["opt/count"])),
                "n_steps": n, "check_steps": chk}
    if timed is not None:
        first = read_save(timed, k, cfg, p["seq_len"])
        run.prog["params"] = {name[len("train/params/"):]: v
                              for name, v in first.items()
                              if name.startswith("train/params/")}
        run.prog["m"] = {name[len("train/opt/m/"):]: v
                         for name, v in first.items()
                         if name.startswith("train/opt/m/")}
        del first
        last = read_save(timed, n, cfg, p["seq_len"])
        run.prog["roundtrip"] = _roundtrip(last, final, seed, n)
        del last
        shutil.rmtree(timed)
    del final


def step_footprint(cfg, mesh, rules, p: dict, n: int) -> dict:
    """Device bytes of the step program as ``train()`` jits it, from its
    compiled memory analysis (the same program: a hit in the persistent
    cache).  "footprint" is the compiler's peak for the program while it
    runs: its arguments, outputs and temporaries.  The allocator's own
    peak (``memory_stats``) leaves the temporaries out."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import NamedSharding
    from repro.distributed.sharding import resolve_spec
    from repro.models.layers import Policy
    from repro.train.state import make_train_state
    from repro.train.step import make_train_step
    opt = p["optimizer"]
    b, s = p["global_batch"], p["seq_len"]
    step_fn, st_shard = make_train_step(
        cfg, mesh, rules, accum_steps=1, base_lr=opt["base_lr"],
        warmup=opt["warmup"], policy=Policy(), max_seq=s, total_steps=n,
        remat=True)
    tok = NamedSharding(mesh, resolve_spec(("batch", "seq"), (b, s), mesh,
                                           rules))
    jitted = jax.jit(step_fn, in_shardings=(st_shard, {"tokens": tok,
                                                       "targets": tok}),
                     out_shardings=(st_shard, None), donate_argnums=(0,))
    state = jax.eval_shape(
        lambda: make_train_state(cfg, jax.random.PRNGKey(0), s))
    batch = jax.ShapeDtypeStruct((b, s), jnp.int32)
    ma = jitted.lower(state, {"tokens": batch, "targets": batch}) \
        .compile().memory_analysis()
    out = {k: int(getattr(ma, k + "_size_in_bytes"))
           for k in ("argument", "output", "alias", "temp")}
    out["footprint"] = int(ma.peak_memory_in_bytes)
    return out


# --------------------------------------------------------------------------
# Correctness
# --------------------------------------------------------------------------

def reference_outputs(cell: Cell, seed: int, n_steps: int, total_steps: int,
                      precision: str = "fp32") -> dict:
    """The reference's first `n_steps` steps from the seeded weights on
    the seeded batches (``chipbench.traffic_gen``)."""
    from chipbench import traffic_gen
    ref = load_reference(cell.cfg, cell.base)
    p = cell.params
    batches = traffic_gen.batches(cell.cfg["vocab_size"],
                                  p["global_batch"], p["seq_len"], seed,
                                  n_steps)
    return ref.run(cell.cfg, p["optimizer"], seed, batches,
                   n_steps, total_steps, precision)


def check(run: Run, seed: int) -> tuple:
    """(correct, checks, every number) of the run's program outputs."""
    from chipbench import compare
    ref = reference_outputs(run.cell, seed, run.prog["check_steps"],
                            run.prog["n_steps"])
    nums = compare.numbers(run.prog, ref)
    ok, checks = compare.judge(nums, run.cell.params["limits"])
    return ok, checks, {k: v for k, (v, _) in nums.items()}
