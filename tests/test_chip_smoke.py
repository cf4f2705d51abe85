"""The chip path on the CPU: the compile-cache rule, chip_smoke.py's
refusal to run without a TPU, and its phases at a tiny size (the chip run
itself is ``python3 chip_smoke.py`` on a TPU host)."""
import importlib.util
import json
import sys
from pathlib import Path

import jax
import pytest
from jax.experimental.compilation_cache import compilation_cache

from repro.configs import get_arch, reduce_for_smoke
from repro.launch import compile_cache

ROOT = Path(__file__).resolve().parents[1]


def _chip_smoke():
    spec = importlib.util.spec_from_file_location("chip_smoke",
                                                  ROOT / "chip_smoke.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@pytest.fixture
def jax_cache_config():
    """Put JAX's cache settings back however a test left them."""
    keys = ("jax_compilation_cache_dir",
            "jax_persistent_cache_min_compile_time_secs")
    saved = {k: getattr(jax.config, k) for k in keys}
    yield
    for k, v in saved.items():
        jax.config.update(k, v)
    compilation_cache.reset_cache()


def test_compile_cache_leaves_env_dir_to_jax(monkeypatch, tmp_path,
                                             jax_cache_config):
    before = jax.config.jax_compilation_cache_dir
    monkeypatch.setenv(compile_cache.ENV, str(tmp_path))
    assert compile_cache.enable_compile_cache() == str(tmp_path)
    assert jax.config.jax_compilation_cache_dir == before


def test_compile_cache_default_is_fixed_checkout_path(monkeypatch,
                                                      jax_cache_config):
    monkeypatch.delenv(compile_cache.ENV, raising=False)
    first = compile_cache.enable_compile_cache()
    second = compile_cache.enable_compile_cache()
    assert first == second == str(ROOT / ".jax_cache")
    assert jax.config.jax_compilation_cache_dir == first


def test_chip_smoke_refuses_without_tpu(monkeypatch, capsys):
    assert jax.devices()[0].platform != "tpu"
    cs = _chip_smoke()
    monkeypatch.setattr(sys, "argv", ["chip_smoke.py"])
    assert cs.main() != 0
    out = capsys.readouterr().out
    assert '"ok"' not in out


def test_chip_smoke_phases_tiny_on_cpu(monkeypatch, tmp_path, capsys,
                                       jax_cache_config):
    """Reference, save + injected kill, resume: bit-identical losses, the
    resume served from the last save and from the compile cache."""
    cs = _chip_smoke()
    monkeypatch.setattr(cs, "BATCH", 4)
    monkeypatch.setattr(cs, "SEQ", 32)
    monkeypatch.setattr(cs, "CKPT_ROOT", tmp_path / "ckpt")
    monkeypatch.setattr(cs, "device_memory",
                        lambda d: {"peak_bytes_in_use": 0})
    jax.config.update("jax_compilation_cache_dir", str(tmp_path / "cache"))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    compilation_cache.reset_cache()
    cs.one_chip(reduce_for_smoke(get_arch("smollm-135m")),
                cs.CompileCounters())
    recs = {r["phase"]: r for r in map(
        json.loads, capsys.readouterr().out.strip().splitlines())}
    assert recs["save_and_kill"]["injected_failure"] == \
        f"injected failure after step {cs.FAIL_AT}"
    assert recs["resume"]["resumed_from"] == 4
    assert recs["resume"]["losses"] == recs["reference"]["losses"][4:]
    assert recs["resume"]["cache_hits"] > 0
    assert not (tmp_path / "ckpt").exists()
