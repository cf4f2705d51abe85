"""chipbench/run.py driven on the CPU at a tiny size: cells, traffic,
configurations and per-layer readers found by name in a temp directory;
the refusal to run without a TPU; and `correct` coming out false for the
float8 control and for each fault planted in the timed path."""
import dataclasses
import json
import os
import subprocess
import sys

import pytest

from chipbench import compare, control, faults, flops, harness, run
from chipbench.tests.tiny import (LIMITS, NOSAVE_LIMITS, ROOT, SEED, TINY,
                                  make_base, restore_jax_config, run_cell)
from repro.configs import ARCHS, reduce_for_smoke
from repro.configs.base import ArchConfig

__all__ = ["restore_jax_config"]
CELLS = ("tiny.train_save", "tiny.train_nosave")


@pytest.fixture
def base(tmp_path, restore_jax_config):
    return make_base(tmp_path, cells=CELLS)


@pytest.mark.parametrize("cell,limits", [("tiny.train_save", LIMITS),
                                         ("tiny.train_nosave",
                                          NOSAVE_LIMITS)])
def test_train_cell_runs_and_is_correct(base, capsys, cell, limits):
    rc, res, err = run_cell(base, cell, capsys)
    assert rc == 0 and res["correct"] is True
    assert set(res["metrics"]) == {"tokens_per_s", "setup_s"}
    assert res["metrics"]["tokens_per_s"]["unit"] == "tokens/s"
    assert res["device"]["platform"] == "cpu" and res["device"]["count"] == 1
    assert list(res)[-1] == "checks"
    assert set(res["checks"]) == set(limits)
    # the numbers compared are the last lines of stderr
    tail = err.strip().splitlines()[-len(limits):]
    assert [ln.split()[1] for ln in tail] == list(limits)
    assert not harness.SCRATCH.exists()


def test_traced_run_reports_per_layer_metrics(base, capsys):
    rc, res, _ = run_cell(base, "tiny.train_save", capsys, trace=1)
    assert rc == 0 and res["correct"] is True
    # the CPU has no device trace: only the program's counters read
    assert set(res["metrics"]) == {"save.snapshot_s", "save.write_s"}
    assert all(m["value"] > 0 for m in res["metrics"].values())


def test_new_cell_found_by_name_with_no_code_edited(tmp_path, capsys,
                                                    restore_jax_config):
    """A configuration, a workload and a per-layer reader added as files."""
    base = make_base(tmp_path, cells=())
    (base / "configs" / "tiny-tied.json").write_text(
        json.dumps(dict(TINY, name="tiny-tied", tie_embeddings=True)))
    (base / "workloads" / "tiny-tied.train_nosave.json").write_text(
        json.dumps({"global_batch": 2, "seq_len": 16, "log_every": 1,
                    "check_steps": 2, "limits": NOSAVE_LIMITS,
                    "nominal_steps_per_s": 3.0}))
    (base / "metrics" / "window.steps.py").write_text(
        "def read(run):\n    return float(run.steps)\n")
    bench_file = tmp_path / "BENCHMARK.json"
    bench = json.loads(bench_file.read_text())
    bench["workloads"].append({"name": "tiny-tied.train_nosave",
                               "config": "tiny-tied",
                               "traffic": "train_nosave", "chips": 1,
                               "why": "test"})
    bench["per_layer"].append({"name": "window.steps", "unit": "steps",
                               "better": "higher", "source": "host_clock",
                               "layer": "test", "moves": "tokens_per_s",
                               "workloads": ["tiny-tied.train_nosave"]})
    bench_file.write_text(json.dumps(bench))
    rc, res, _ = run_cell(base, "tiny-tied.train_nosave", capsys, trace=1)
    assert rc == 0 and res["correct"] is True
    assert res["metrics"] == {"window.steps": {"value": 3.0,
                                               "unit": "steps"}}


class _AtComparison(Exception):
    """Raised where the run would be compared with its reference."""


def test_new_mla_moe_cell_found_by_name_with_no_code_edited(
        tmp_path, capsys, restore_jax_config, monkeypatch):
    """A configuration with latent attention and experts (DeepSeek-V2-Lite
    through ``reduce_for_smoke``, written as JSON) and a cell that saves,
    added as files: the harness finds the cell by name, builds the
    program's config with its nested sections, counts its FLOPs, trains
    with saves, reads the first save back through the system's reader and
    compiles the step for its footprint.  It stops at the comparison,
    which needs that model's plain reference: none is here yet."""
    base = make_base(tmp_path, cells=())
    cfg = dataclasses.asdict(reduce_for_smoke(ARCHS["deepseek-v2-lite-16b"]))
    cfg.update(name="tiny-mla-moe", reduced=[])
    (base / "configs" / "tiny-mla-moe.json").write_text(json.dumps(cfg))
    cell = "tiny-mla-moe.train_save"
    (base / "workloads" / f"{cell}.json").write_text(
        json.dumps({"global_batch": 4, "seq_len": 32, "log_every": 1,
                    "ckpt_every": 2, "check_steps": 2, "limits": LIMITS,
                    "nominal_steps_per_s": 4.0}))
    bench_file = tmp_path / "BENCHMARK.json"
    bench = json.loads(bench_file.read_text())
    bench["workloads"].append({"name": cell, "config": "tiny-mla-moe",
                               "traffic": "train_save", "chips": 1,
                               "why": "test"})
    bench_file.write_text(json.dumps(bench))

    def stop(r, seed):
        raise _AtComparison(r)
    monkeypatch.setattr(harness, "check", stop)
    with pytest.raises(_AtComparison) as stopped:
        run.main(["--workload", cell, "--seed", str(SEED), "--seconds", "1",
                  "--trace", "0"], require_tpu=False, base=base,
                 bench_file=bench_file)
    r = stopped.value.args[0]
    assert r.cell.name == cell and r.steps == 4
    built = harness.program_config(r.cell.cfg)
    assert built.moe.n_routed == 8 and built.mla.kv_lora_rank == 32
    assert r.flops_per_step \
        == flops.train_step_flops(cfg, 4, 32)["total"] > 0
    assert r.prog["counters"] == (4, 4)
    assert r.prog["roundtrip"] == (0, [])
    assert set(r.prog["losses"]) == {0, 1}
    # the first save holds the latent attention's and the experts' leaves
    leaves = " ".join(r.prog["params"])
    assert "w_uk" in leaves and "router" in leaves
    memory = [json.loads(ln) for ln in capsys.readouterr().out.splitlines()
              if '"phase": "memory"' in ln]
    assert memory and memory[0]["footprint"] > 0
    assert not harness.SCRATCH.exists()


@pytest.mark.parametrize("name", sorted(ARCHS))
def test_program_config_round_trips_every_arch(name):
    """Every configuration the program knows, written as a file holds it,
    comes back equal, nested sections and tuples included, and hashable
    (the program keys compiled steps by it)."""
    cfg = harness.program_config(
        json.loads(json.dumps(dataclasses.asdict(ARCHS[name]))))
    assert cfg == ARCHS[name]
    assert hash(cfg) == hash(ARCHS[name])


def test_program_config_of_the_smollm_file_is_as_before():
    """The configuration file as run: its fields passed as they are."""
    cfg = json.loads((harness.HERE / "configs" / "smollm-135m-1l.json")
                     .read_text())
    names = {f.name for f in dataclasses.fields(ArchConfig)}
    assert harness.program_config(cfg) \
        == ArchConfig(**{k: v for k, v in cfg.items() if k in names})


@pytest.mark.parametrize("section,key", [("moe", "topk"), ("mla", "q_lora")])
def test_unknown_key_in_a_nested_section_is_refused(section, key):
    """A misspelt field of a nested section is an error, not a default."""
    cfg = json.loads(json.dumps(dataclasses.asdict(
        ARCHS["deepseek-v2-lite-16b"])))
    cfg[section][key] = 6
    with pytest.raises(harness.BenchError, match=repr(key)):
        harness.program_config(cfg)


def test_unknown_workload_is_refused(base, capsys):
    rc, res, err = run_cell(base, "tiny.nothing", capsys)
    assert rc != 0 and res is None and "unknown workload" in err


def test_run_without_tpu_exits_nonzero_and_prints_no_result():
    cell = json.loads((ROOT / "BENCHMARK.json").read_text())[
        "workloads"][0]["name"]
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run(
        [sys.executable, str(ROOT / "chipbench" / "run.py"), "--workload",
         cell, "--seed", "1", "--seconds", "1", "--trace", "0"],
        capture_output=True, text=True, env=env, timeout=300)
    assert p.returncode != 0
    assert "correct" not in p.stdout
    assert "needs a TPU" in p.stderr


@pytest.mark.parametrize("cell,fault", [
    (c, f) for c in CELLS for f in sorted(faults.PLANTS)
    + (list(faults.SAVE_PLANTS) if c.endswith("train_save") else [])])
def test_planted_fault_makes_correct_false(base, capsys, cell, fault):
    with faults.planted(fault):
        rc, res, _ = run_cell(base, cell, capsys)
    assert rc == 0 and res["correct"] is False


def test_window_steps_and_logged_steps():
    p = {"nominal_steps_per_s": 7.7, "check_steps": 20, "ckpt_every": 20}
    assert harness.window_steps(p, 10) == 80          # 77 -> 4 saves
    assert harness.window_steps(p, 1) == 20           # at least the check
    p = {"nominal_steps_per_s": 7.7, "check_steps": 11}
    assert harness.window_steps(p, 10) == 77
    assert harness.logged_steps(25, 10) == [0, 10, 20, 24]


def test_float8_control_fails_the_comparison(base):
    """The float8 reference in the system's place reads as not correct."""
    cell = harness.find_cell("tiny.train_save", base,
                             base.parent / "BENCHMARK.json")
    ref = harness.reference_outputs(cell, 1, 2, 4, "fp32")
    low = harness.reference_outputs(cell, 1, 2, 4, "fp8")
    prog = {"losses": {0: low["losses"][0], 1: low["losses"][1]},
            "params": low["params"], "m": low["m"]}
    nums = compare.numbers(prog, ref)
    ok, _ = compare.judge(nums, {k: v for k, v in LIMITS.items()
                                 if k in nums})
    assert not ok
    same = compare.numbers({"losses": {0: ref["losses"][0]},
                            "params": ref["params"], "m": ref["m"]}, ref)
    assert all(v == 0 for v, _ in same.values())


def test_control_script_reports_readings(base, capsys):
    control.main(["--workload", "tiny.train_save", "--seconds", "1",
                  "--sound", "3", "--control", "3"], require_tpu=False,
                 base=base, bench_file=base.parent / "BENCHMARK.json")
    recs = [json.loads(ln) for ln in capsys.readouterr().out.splitlines()
            if ln.startswith('{"kind"')]
    assert [r["kind"] for r in recs] == ["sound", "control:fp8"]
    assert recs[0]["correct"] is True and recs[1]["correct"] is False
    assert set(recs[1]["numbers"]) < set(recs[0]["numbers"])
