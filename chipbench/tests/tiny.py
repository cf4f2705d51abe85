"""A benchmark directory at a tiny Llama-style size, built in a temp dir
from the real one's readers, reference, traffic and peaks, for driving
``chipbench/run.py`` on the CPU."""
from __future__ import annotations

import json
import shutil
from pathlib import Path

import jax
import pytest

BENCH = Path(__file__).resolve().parents[1]
ROOT = BENCH.parent

TINY = {"name": "tiny", "source": "test", "reference": "llama",
        "family": "dense", "n_layers": 2, "d_model": 64, "n_heads": 4,
        "n_kv_heads": 2, "head_dim": 16, "d_ff": 128, "vocab_size": 256,
        "norm_eps": 1e-05, "rope_theta": 10000.0, "mlp": "swiglu",
        "tie_embeddings": False, "reduced": []}
#: limits for tiny runs on the CPU (2 layers of width 64, 4 x 32 tokens,
#: every loss logged).  Sound runs (seeds 1-6, --seconds 1) read loss_gap
#: 2.3e-4-1.0e-3 and update_gap 0.007-0.018; the fp8 control 0.0045-0.016
#: and 0.021-0.026; half the batch 0.0028-0.014 and 0.23; the other faults
#: read update_gap 351-712, roundtrip 1 or counters 8.  Like the deep
#: model, this one's init leaves moment_gap with no separation (sound
#: 0.07-0.25, fp8 0.17-0.22): not compared here.
LIMITS = {"loss_gap": 2e-3, "update_gap": 0.1, "roundtrip": 0,
          "counters": 0}
NOSAVE_LIMITS = {"loss_gap": 2e-3, "counters": 0}
SEED = 2 ** 31 + 11


def make_base(tmp: Path, cells=("tiny.train_save",)) -> Path:
    """tmp/chipbench: BENCHMARK.json naming `cells`, the tiny config, the
    real traffic, readers, reference and peaks."""
    base = tmp / "chipbench"
    base.mkdir()
    for d in ("metrics", "reference", "traffic"):
        shutil.copytree(BENCH / d, base / d,
                        ignore=shutil.ignore_patterns("__pycache__"))
    shutil.copy(BENCH / "peaks.json", base / "peaks.json")
    (base / "configs").mkdir()
    (base / "configs" / "tiny.json").write_text(json.dumps(TINY))
    (base / "workloads").mkdir()
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    bench["configs"] = [{"name": "tiny", "source": "test", "reduced": [],
                         "file": "chipbench/configs/tiny.json", "why": "test"}]
    bench["workloads"] = []
    for cell in cells:
        traffic = cell.split(".", 1)[1]
        bench["workloads"].append({"name": cell, "config": "tiny",
                                   "traffic": traffic, "chips": 1,
                                   "why": "test"})
        own = {"global_batch": 4, "seq_len": 32, "log_every": 1,
               "nominal_steps_per_s": 4.0}
        if traffic == "train_save":
            own.update(ckpt_every=2, check_steps=2, limits=LIMITS)
        else:
            own.update(check_steps=8, limits=NOSAVE_LIMITS)
        (base / "workloads" / f"{cell}.json").write_text(json.dumps(own))
    for m in bench["end_to_end"] + bench["per_layer"]:
        if "workloads" in m:
            m["workloads"] = list(cells)
    (tmp / "BENCHMARK.json").write_text(json.dumps(bench))
    return base


@pytest.fixture
def restore_jax_config(monkeypatch):
    """run.main sets the compile-cache and TPU-log variables and JAX's
    persistence threshold for its process; put them back after a test."""
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    monkeypatch.delenv("TPU_LOG_DIR", raising=False)
    key = "jax_persistent_cache_min_compile_time_secs"
    saved = getattr(jax.config, key)
    yield
    jax.config.update(key, saved)


def run_cell(base: Path, cell: str, capsys, trace: int = 0,
             seed: int = SEED, seconds: float = 1.0):
    """Drive one run on the CPU; returns (exit code, result or None,
    stderr)."""
    from chipbench import run
    rc = run.main(["--workload", cell, "--seed", str(seed), "--seconds",
                   str(seconds), "--trace", str(trace)],
                  require_tpu=False, base=base,
                  bench_file=base.parent / "BENCHMARK.json")
    out, err = capsys.readouterr()
    lines = [ln for ln in out.splitlines() if ln.startswith("{")]
    last = json.loads(lines[-1]) if lines else None
    result = last if last and "correct" in last else None
    return rc, result, err
