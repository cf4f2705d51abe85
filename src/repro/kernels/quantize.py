"""Pallas TPU kernel: blockwise int8 quantize / dequantize (gradient
compression for the DCN axis + checkpoint compression).

Lane layout: one grid step handles ``rows`` scale-blocks of ``block``
elements each — (rows, block) sits in VMEM as an 8x128-aligned tile; the
per-block max|.| reduction runs on the VPU, and the int8 output quarters
HBM/DCN traffic."""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl


def _quant_kernel(x_ref, q_ref, s_ref):
    x = x_ref[...].astype(jnp.float32)                    # (rows, block)
    scale = jnp.maximum(jnp.max(jnp.abs(x), axis=1, keepdims=True),
                        1e-12) / 127.0                    # (rows, 1)
    q = jnp.clip(jnp.round(x / scale), -127, 127)
    q_ref[...] = q.astype(jnp.int8)
    s_ref[...] = scale.astype(jnp.float32)


def _dequant_kernel(q_ref, s_ref, x_ref):
    x_ref[...] = (q_ref[...].astype(jnp.float32)
                  * s_ref[...]).astype(x_ref.dtype)


def _rows_per_step(nb: int, rows: int) -> int:
    """Largest multiple of 8 <= `rows` dividing `nb` (the sublane tiling
    Mosaic requires of a partial block), else the whole array."""
    for r in range(min(rows, nb) // 8 * 8, 0, -8):
        if nb % r == 0:
            return r
    return nb


def quantize_int8(x, *, block: int = 256, rows: int = 64,
                  interpret: bool = False):
    """x (N,) with N % block == 0 -> (q (N//block, block) int8, scales)."""
    n = x.shape[0]
    assert n % block == 0, (n, block)
    nb = n // block
    rows = _rows_per_step(nb, rows)
    xb = x.reshape(nb, block)
    # scales travel as (nb, 1): a 1-D (rows,) block is refused unless rows
    # is a multiple of 128
    q, scales = pl.pallas_call(
        _quant_kernel,
        grid=(nb // rows,),
        in_specs=[pl.BlockSpec((rows, block), lambda i: (i, 0))],
        out_specs=[pl.BlockSpec((rows, block), lambda i: (i, 0)),
                   pl.BlockSpec((rows, 1), lambda i: (i, 0))],
        out_shape=[jax.ShapeDtypeStruct((nb, block), jnp.int8),
                   jax.ShapeDtypeStruct((nb, 1), jnp.float32)],
        interpret=interpret,
    )(xb)
    return q, scales.reshape(nb)


def dequantize_int8(q, scales, *, rows: int = 64, interpret: bool = False):
    """(q (nb, block) int8, scales (nb,)) -> x (nb*block,) fp32."""
    nb, block = q.shape
    rows = _rows_per_step(nb, rows)
    out = pl.pallas_call(
        _dequant_kernel,
        grid=(nb // rows,),
        in_specs=[pl.BlockSpec((rows, block), lambda i: (i, 0)),
                  pl.BlockSpec((rows, 1), lambda i: (i, 0))],
        out_specs=pl.BlockSpec((rows, block), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((nb, block), jnp.float32),
        interpret=interpret,
    )(q, scales.reshape(nb, 1))
    return out.reshape(-1)
