"""Pallas TPU kernel for the RG-LRU linear recurrence
h_t = a_t * h_{t-1} + x_t  (gates precomputed by the caller).

TPU adaptation (DESIGN.md §6): a GPU implementation uses a warp-level
parallel scan; the TPU VPU instead prefers lane-parallel (over D) with a
short sequential walk over time INSIDE a VMEM-resident chunk, carrying h
across chunks in scratch — the sequential grid dimension is the time-chunk
axis, so the carry never leaves VMEM.  Grid: (B, n_d, n_chunks) with
chunks minor/sequential.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _rglru_kernel(a_ref, x_ref, h0_ref, out_ref, hlast_ref, h_ref, *,
                  chunk: int, n_chunks: int, rows: int):
    ci = pl.program_id(2)

    @pl.when(ci == 0)
    def _init():
        h_ref[...] = h0_ref[0].astype(jnp.float32)        # (1, bd)

    # walk the chunk `rows` timesteps at a time: each load is one whole
    # sublane tile (Mosaic needs a dynamic row offset it can prove
    # tile-aligned), and the timesteps inside it are unrolled
    def group(g, h):
        base = pl.multiple_of(g * rows, rows)
        a = a_ref[0, pl.ds(base, rows), :].astype(jnp.float32)  # (rows, bd)
        x = x_ref[0, pl.ds(base, rows), :].astype(jnp.float32)
        for i in range(rows):
            h = a[i:i + 1] * h + x[i:i + 1]
            out_ref[0, pl.ds(base + i, 1), :] = h.astype(out_ref.dtype)
        return h

    h = jax.lax.fori_loop(0, chunk // rows, group, h_ref[...])
    h_ref[...] = h

    @pl.when(ci == n_chunks - 1)
    def _final():
        hlast_ref[0] = h.astype(hlast_ref.dtype)


def rglru_scan(a, x, h0, *, chunk: int = 128, block_d: int = 512,
               interpret: bool = False):
    """a, x (B, S, D); h0 (B, D).  Returns (h_seq (B,S,D) fp32, h_last)."""
    b, s, d = a.shape
    chunk = min(chunk, s)
    block_d = min(block_d, d)
    assert s % chunk == 0 and d % block_d == 0, (s, chunk, d, block_d)
    n_chunks = s // chunk
    n_d = d // block_d
    rows = 8 * 4 // jnp.dtype(a.dtype).itemsize     # one sublane tile
    assert chunk % rows == 0, (chunk, rows)

    kernel = functools.partial(_rglru_kernel, chunk=chunk, n_chunks=n_chunks,
                               rows=rows)
    # h0 / h_last travel as (B, 1, D) so every block's last two dims are
    # (1 == full extent, lane-aligned block_d): Mosaic refuses a (1, bd)
    # block over a (B, D) array for B > 1
    h_seq, h_last = pl.pallas_call(
        kernel,
        grid=(b, n_d, n_chunks),
        in_specs=[
            pl.BlockSpec((1, chunk, block_d), lambda bi, di, ci: (bi, ci, di)),
            pl.BlockSpec((1, chunk, block_d), lambda bi, di, ci: (bi, ci, di)),
            pl.BlockSpec((1, 1, block_d), lambda bi, di, ci: (bi, 0, di)),
        ],
        out_specs=[
            pl.BlockSpec((1, chunk, block_d), lambda bi, di, ci: (bi, ci, di)),
            pl.BlockSpec((1, 1, block_d), lambda bi, di, ci: (bi, 0, di)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((b, s, d), jnp.float32),
            jax.ShapeDtypeStruct((b, 1, d), jnp.float32),
        ],
        scratch_shapes=[pltpu.VMEM((1, block_d), jnp.float32)],
        interpret=interpret,
    )(a, x, h0.reshape(b, 1, d))
    return h_seq, h_last.reshape(b, d)
