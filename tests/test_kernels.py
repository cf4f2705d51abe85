"""Pallas kernels vs pure-jnp oracles: shape/dtype sweeps (interpret=True
on CPU) + hypothesis property tests on kernel invariants."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings
from hypothesis import strategies as st

from repro.kernels import ops
from repro.kernels.flash_attention import flash_attention_fwd
from repro.kernels.quantize import dequantize_int8, quantize_int8
from repro.kernels.ref import (ref_dequantize_int8, ref_flash_attention,
                               ref_quantize_int8, ref_rglru)
from repro.kernels.rglru import rglru_scan


# ----------------------------------------------------------- flash attention

@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("bh,bkv,sq,sk,hd,causal,window", [
    (4, 2, 256, 256, 64, True, 0),      # GQA g=2
    (2, 2, 128, 128, 128, True, 0),     # MHA hd=128
    (8, 2, 128, 128, 64, True, 0),      # GQA g=4
    (6, 2, 256, 256, 64, True, 64),     # local window (rgemma-style)
    (2, 2, 128, 384, 64, False, 0),     # cross-attention
    (2, 1, 512, 512, 256, True, 0),     # MQA, big head_dim
])
def test_flash_attention_sweep(dtype, bh, bkv, sq, sk, hd, causal, window):
    ks = jax.random.split(jax.random.PRNGKey(0), 3)
    q = jax.random.normal(ks[0], (bh, sq, hd)).astype(dtype)
    k = jax.random.normal(ks[1], (bkv, sk, hd)).astype(dtype)
    v = jax.random.normal(ks[2], (bkv, sk, hd)).astype(dtype)
    out = flash_attention_fwd(q, k, v, causal=causal, window=window,
                              block_q=64, block_k=64, interpret=True)
    ref = ref_flash_attention(q, k, v, causal=causal, window=window)
    tol = 2e-5 if dtype == jnp.float32 else 2e-2
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(ref, np.float32), atol=tol)


@pytest.mark.parametrize("block_q,block_k", [(64, 128), (128, 64), (128, 128)])
def test_flash_attention_block_shapes(block_q, block_k):
    ks = jax.random.split(jax.random.PRNGKey(3), 3)
    q = jax.random.normal(ks[0], (2, 384, 64))
    k = jax.random.normal(ks[1], (2, 384, 64))
    v = jax.random.normal(ks[2], (2, 384, 64))
    out = flash_attention_fwd(q, k, v, block_q=block_q, block_k=block_k,
                              interpret=True)
    ref = ref_flash_attention(q, k, v)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-5)


def test_flash_attention_constant_v_property():
    """softmax rows sum to 1 => constant V must pass through exactly."""
    ks = jax.random.split(jax.random.PRNGKey(1), 2)
    q = jax.random.normal(ks[0], (2, 128, 64))
    k = jax.random.normal(ks[1], (2, 128, 64))
    v = jnp.full((2, 128, 64), 2.5)
    out = flash_attention_fwd(q, k, v, interpret=True)
    np.testing.assert_allclose(np.asarray(out), 2.5, atol=1e-5)


def test_flash_attention_grad_matches_ref():
    ks = jax.random.split(jax.random.PRNGKey(2), 3)
    q = jax.random.normal(ks[0], (2, 128, 64))
    k = jax.random.normal(ks[1], (2, 128, 64))
    v = jax.random.normal(ks[2], (2, 128, 64))

    def loss_kernel(q, k, v):
        return jnp.sum(ops.flash_attention(q, k, v) ** 2)

    def loss_ref(q, k, v):
        return jnp.sum(ref_flash_attention(q, k, v) ** 2)

    g1 = jax.grad(loss_kernel, argnums=(0, 1, 2))(q, k, v)
    g2 = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(g1, g2):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=2e-4)


# -------------------------------------------------------------------- rg-lru

@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("b,s,d,chunk,block_d", [
    (2, 256, 512, 128, 512),
    (1, 128, 1024, 64, 256),
    (3, 512, 256, 256, 256),
    (2, 128, 128, 128, 128),
])
def test_rglru_sweep(dtype, b, s, d, chunk, block_d):
    ks = jax.random.split(jax.random.PRNGKey(0), 3)
    a = (jax.nn.sigmoid(jax.random.normal(ks[0], (b, s, d))) * 0.98).astype(dtype)
    x = (jax.random.normal(ks[1], (b, s, d)) * 0.1).astype(dtype)
    h0 = jax.random.normal(ks[2], (b, d)).astype(jnp.float32)
    hs, hl = rglru_scan(a, x, h0, chunk=chunk, block_d=block_d,
                        interpret=True)
    rhs, rhl = ref_rglru(a, x, h0)
    tol = 1e-4 if dtype == jnp.float32 else 2e-2
    np.testing.assert_allclose(np.asarray(hs), np.asarray(rhs), atol=tol)
    np.testing.assert_allclose(np.asarray(hl), np.asarray(rhl), atol=tol)


@settings(max_examples=20, deadline=None)
@given(st.integers(1, 3), st.integers(1, 4))
def test_rglru_linearity_property(b, chunks):
    """The recurrence is linear in x: h(x1) + h(x2) == h(x1+x2) (h0=0)."""
    s, d = chunks * 64, 128
    key = jax.random.PRNGKey(b * 13 + chunks)
    ks = jax.random.split(key, 3)
    a = jax.nn.sigmoid(jax.random.normal(ks[0], (b, s, d))) * 0.95
    x1 = jax.random.normal(ks[1], (b, s, d)) * 0.1
    x2 = jax.random.normal(ks[2], (b, s, d)) * 0.1
    h0 = jnp.zeros((b, d))
    h_a, _ = rglru_scan(a, x1, h0, chunk=64, block_d=128, interpret=True)
    h_b, _ = rglru_scan(a, x2, h0, chunk=64, block_d=128, interpret=True)
    h_ab, _ = rglru_scan(a, x1 + x2, h0, chunk=64, block_d=128, interpret=True)
    np.testing.assert_allclose(np.asarray(h_a + h_b), np.asarray(h_ab),
                               atol=1e-4)


# ------------------------------------------------------------------ quantize

@pytest.mark.parametrize("n,block", [(4096, 256), (512, 128), (65536, 256)])
def test_quantize_matches_ref(n, block):
    x = jax.random.normal(jax.random.PRNGKey(0), (n,)) * 3
    q, s = quantize_int8(x, block=block, interpret=True)
    rq, rs = ref_quantize_int8(x, block=block)
    assert jnp.array_equal(q, rq)
    np.testing.assert_allclose(np.asarray(s), np.asarray(rs), rtol=1e-6)
    xr = dequantize_int8(q, s, interpret=True)
    rr = ref_dequantize_int8(rq, rs)
    np.testing.assert_allclose(np.asarray(xr), np.asarray(rr), rtol=1e-6)


@settings(max_examples=25, deadline=None)
@given(st.integers(1, 16), st.floats(0.01, 100.0))
def test_quantize_error_bound_property(nblocks, scale_mag):
    """|x - dequant(quant(x))| <= half a quantization step per block."""
    n = nblocks * 256
    x = (jax.random.normal(jax.random.PRNGKey(nblocks), (n,))
         * scale_mag).astype(jnp.float32)
    q, s = quantize_int8(x, interpret=True)
    xr = dequantize_int8(q, s, interpret=True)
    err = np.abs(np.asarray(xr - x)).reshape(nblocks, 256)
    bound = np.asarray(s)[:, None] * 0.5 + 1e-6
    assert (err <= bound).all()


@settings(max_examples=10, deadline=None)
@given(st.integers(0, 5))
def test_quantize_idempotent_property(seed):
    """quant(dequant(quant(x))) == quant(x) (fixed point after one round)."""
    x = jax.random.normal(jax.random.PRNGKey(seed), (1024,)) * 2
    q1, s1 = quantize_int8(x, interpret=True)
    x1 = dequantize_int8(q1, s1, interpret=True)
    q2, s2 = quantize_int8(x1, interpret=True)
    x2 = dequantize_int8(q2, s2, interpret=True)
    np.testing.assert_allclose(np.asarray(x1), np.asarray(x2), atol=1e-5)
