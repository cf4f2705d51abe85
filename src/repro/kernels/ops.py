"""Jit'd public wrappers for the Pallas kernels.

On the CPU platform kernels execute with interpret=True (the kernel body
runs in Python for correctness validation); on TPU they compile to
Mosaic; any other backend is refused rather than silently interpreted.
``flash_attention`` carries a custom_vjp whose backward is the pure-jnp
reference gradient (recompute-based) — the forward kernel is the
serving/prefill fast path; a fused backward kernel is listed as future
work in DESIGN.md §6."""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.kernels import flash_attention as _fa
from repro.kernels import quantize as _q
from repro.kernels import rglru as _rg
from repro.kernels import ref as _ref


def _interpret() -> bool:
    backend = jax.default_backend()
    if backend not in ("tpu", "cpu"):
        raise NotImplementedError(
            f"Pallas kernels compile for TPU and interpret on CPU; "
            f"backend {backend!r} is neither")
    return backend == "cpu"


# ---------------------------------------------------------------- attention

@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4))
def flash_attention(q, k, v, causal: bool = True, window: int = 0):
    """q (BH, Sq, hd); k, v (BKV, Sk, hd).  GQA folded by the caller."""
    return _fa.flash_attention_fwd(q, k, v, causal=causal, window=window,
                                   interpret=_interpret())


def _fa_fwd(q, k, v, causal, window):
    out = flash_attention(q, k, v, causal, window)
    return out, (q, k, v)


def _fa_bwd(causal, window, res, g):
    q, k, v = res
    _, vjp = jax.vjp(
        lambda q, k, v: _ref.ref_flash_attention(q, k, v, causal=causal,
                                                 window=window), q, k, v)
    return vjp(g)


flash_attention.defvjp(_fa_fwd, _fa_bwd)


# ------------------------------------------------------------------- rg-lru

def rglru(a, x, h0):
    """h_t = a_t h_{t-1} + x_t over axis 1.  Returns (h_seq fp32, h_last)."""
    return _rg.rglru_scan(a, x, h0, interpret=_interpret())


# ----------------------------------------------------------------- quantize

def quantize_int8(x, block: int = 256):
    return _q.quantize_int8(x, block=block, interpret=_interpret())


def dequantize_int8(q, scales):
    return _q.dequantize_int8(q, scales, interpret=_interpret())
