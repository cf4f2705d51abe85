"""Pallas kernels compiled for a described TPU v5e at real widths.

No chip is needed: the TPU compiler is installed, and it compiles for a
chip that is described and not attached.  Interpret-mode tests
(test_kernels.py) cannot see what Mosaic refuses — block shapes off the
(8, 128) tiling, dynamic row offsets it cannot prove aligned — so these
compiles guard every change to a kernel.  Nothing runs, so nothing here
says anything about results or time.

The topology is described inside a module fixture, never at import: only
one process at a time may load the TPU library, and every test worker
imports this file.  Keep every such compile in this one file.
"""
import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels.flash_attention import flash_attention_fwd
from repro.kernels.quantize import dequantize_int8, quantize_int8
from repro.kernels.rglru import rglru_scan


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    with pytest.MonkeyPatch.context() as mp:
        mp.setenv("TPU_LOG_DIR", "disabled")
        try:
            topo = topologies.get_topology_desc(platform="tpu",
                                                topology_name="v5e:2x2")
        except Exception as e:
            pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
        # a compile for a described chip is written to the persistent
        # cache but cannot be read back without the chip: keep it out
        was = jax.config.jax_enable_compilation_cache
        jax.config.update("jax_enable_compilation_cache", False)
        compilation_cache.reset_cache()
        try:
            yield SingleDeviceSharding(topo.devices[0])
        finally:
            jax.config.update("jax_enable_compilation_cache", was)
            compilation_cache.reset_cache()


def _compile(fn, *shapes):
    compiled = jax.jit(fn).lower(*shapes).compile()
    assert "tpu_custom_call" in compiled.as_text()     # the kernel is there
    return compiled


@pytest.mark.parametrize("dtype", [jnp.bfloat16, jnp.float32])
def test_flash_attention_smollm_heads(one_chip, dtype):
    """SmolLM-135M: 9 heads over 3 KV heads, head_dim 64, seq 1024, batch 8."""
    b, s = 8, 1024
    q = jax.ShapeDtypeStruct((b * 9, s, 64), dtype, sharding=one_chip)
    kv = jax.ShapeDtypeStruct((b * 3, s, 64), dtype, sharding=one_chip)
    _compile(lambda q, k, v: flash_attention_fwd(q, k, v, causal=True),
             q, kv, kv)


def test_flash_attention_recurrentgemma_local(one_chip):
    """RecurrentGemma-9B local attention: MQA, head_dim 256, window 2048."""
    s = 4096
    q = jax.ShapeDtypeStruct((16, s, 256), jnp.bfloat16, sharding=one_chip)
    kv = jax.ShapeDtypeStruct((1, s, 256), jnp.bfloat16, sharding=one_chip)
    _compile(lambda q, k, v: flash_attention_fwd(q, k, v, causal=True,
                                                 window=2048), q, kv, kv)


@pytest.mark.parametrize("dtype", [jnp.bfloat16, jnp.float32])
def test_rglru_recurrentgemma_width(one_chip, dtype):
    """RecurrentGemma-9B: d_rnn 4096."""
    a = jax.ShapeDtypeStruct((2, 1024, 4096), dtype, sharding=one_chip)
    h0 = jax.ShapeDtypeStruct((2, 4096), jnp.float32, sharding=one_chip)
    _compile(lambda a, x, h: rglru_scan(a, x, h), a, a, h0)


def test_quantize_int8_1m_gradient(one_chip):
    x = jax.ShapeDtypeStruct((1 << 20,), jnp.float32, sharding=one_chip)
    _compile(lambda x: quantize_int8(x), x)


def test_dequantize_int8_1m_gradient(one_chip):
    nb = (1 << 20) // 256
    q = jax.ShapeDtypeStruct((nb, 256), jnp.int8, sharding=one_chip)
    s = jax.ShapeDtypeStruct((nb,), jnp.float32, sharding=one_chip)
    _compile(lambda q, s: dequantize_int8(q, s), q, s)
