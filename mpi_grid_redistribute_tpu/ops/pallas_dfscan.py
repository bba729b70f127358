"""Pallas TPU within-tile double-float prefix sum for the scan deposit.

The scan deposit's accuracy rides on double-float (TwoSum) prefix sums
(`ops/deposit.py`): every prefix is carried as an unevaluated (hi, lo)
f32 pair. The XLA formulation (`deposit._df_cumsum`) is a Hillis-Steele
doubling loop — log2(tile)=8 shifted `_df_add` steps, each a ~6-array
elementwise pass over the FULL [channels, T, tile] weight tensor. At the
64M north-star that is ~100 GB of HBM traffic for level 1 alone
(measured in the config-5 fused step; the three 2 GB temps in the HBM
dump come from this loop).

This kernel runs the whole doubling loop in VMEM: each grid block loads
[R, tile] rows (one row = one tile), performs the identical 8 shifted
`_df_add` steps on-chip, and writes the (hi, lo) pair — HBM traffic
drops to one read + two writes of the tensor, a ~15x reduction. The
in-kernel arithmetic is the same `_two_sum`/`_df_add` float sequence in
the same order, so results are bit-identical to the XLA path on the
same hardware (tested in interpret mode and on-chip).

Contract: ``x [rows, tile]`` f32, ``tile`` a power of two; returns
``(hi, lo)`` of the same shape — the inclusive within-row double-float
prefix. Rows are independent (one tile each).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


R_BLOCK = 256  # tile-rows per grid block ([256, 256] f32 = 256 KB/buf)


def _two_sum(a, b):
    s = a + b
    bb = s - a
    e = (a - (s - bb)) + (b - bb)
    return s, e


def _df_add(a_hi, a_lo, b_hi, b_lo):
    s, e = _two_sum(a_hi, b_hi)
    e = e + (a_lo + b_lo)
    hi = s + e
    lo = e - (hi - s)
    return hi, lo


def _kernel(x_ref, hi_ref, lo_ref, *, tile: int):
    x = x_ref[:]
    hi = x
    lo = jnp.zeros_like(x)
    shift = 1
    while shift < tile:
        zh = jnp.zeros(x.shape[:-1] + (shift,), x.dtype)
        hi_s = jnp.concatenate([zh, hi[:, : tile - shift]], axis=1)
        lo_s = jnp.concatenate([zh, lo[:, : tile - shift]], axis=1)
        hi, lo = _df_add(hi, lo, hi_s, lo_s)
        shift *= 2
    hi_ref[:] = hi
    lo_ref[:] = lo


@functools.partial(jax.jit, static_argnames=("interpret",))
def tile_df_cumsum_rows(x, interpret=False):
    """Inclusive double-float prefix along axis 1 of ``x [rows, tile]``.

    Bit-identical to ``deposit._df_cumsum(x, axis=1)`` (same TwoSum
    sequence, same order); rows padded to the block size internally.
    """
    rows, tile = x.shape
    r_pad = -(-rows // R_BLOCK) * R_BLOCK
    xp = jnp.pad(x, ((0, r_pad - rows), (0, 0)))
    kernel = functools.partial(_kernel, tile=tile)
    hi, lo = pl.pallas_call(
        kernel,
        grid=(r_pad // R_BLOCK,),
        in_specs=[
            pl.BlockSpec((R_BLOCK, tile), lambda b: (b, 0),
                         memory_space=pltpu.VMEM),
        ],
        out_specs=[
            pl.BlockSpec((R_BLOCK, tile), lambda b: (b, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((R_BLOCK, tile), lambda b: (b, 0),
                         memory_space=pltpu.VMEM),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((r_pad, tile), x.dtype,
                                      vma=jax.typeof(x).vma),
            jax.ShapeDtypeStruct((r_pad, tile), x.dtype,
                                      vma=jax.typeof(x).vma),
        ],
        interpret=interpret,
    )(xp)
    return hi[:rows], lo[:rows]
