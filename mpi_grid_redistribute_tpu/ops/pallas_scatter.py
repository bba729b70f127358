"""Pallas TPU row-scatter: ``out[targets[j]] = rows[j]`` (SURVEY.md §7.5
item 7 — the licensed fused-kernel moment).

MEASURED OUTCOME (v5e-class chip, 196k rows into [8.4M, 7]): this kernel
runs at 24.3-24.7 ms vs XLA's flat scatter at 14.6-16.8 ms isolated
(~27 ms in the full migrate step). The per-arrival dynamic-sublane VMEM
store costs ~122 ns/row — the same order as XLA's scatter — so the
formulation change does not beat the hardware's per-row bound, and the
kernel is therefore OFF by default (MPI_GRID_PALLAS_SCATTER=1 opts in,
parallel/migrate._land_scatter). It is kept, tested (interpret mode),
and documented because the exploration pinned down real platform
constraints: Mosaic rejects dynamic 1-D/lane-indexed VMEM loads and
non-128-aligned manual DMA slices (hence the transposed [8, P] arrival
layout + in-kernel tile transposes), and (BLOCK, 7) f32 blocks lane-pad
to (BLOCK, 128) in VMEM (hence vmem_limit_bytes).

XLA's row scatter costs ~120-150 ns per scattered row on TPU regardless
of row width (measured, scripts/profile_stages.py and the round-4
phase knockout) and dominates the migrate step (~27 ms of 53
at 196k rows). This kernel reformulates the scatter as a streamed
overlay:

  1. (XLA side) sort arrivals by target slot and gather their rows into
     sorted order — sorts and gathers are ~20x cheaper per row than
     scatters on TPU — then lay rows and targets out TRANSPOSED
     (``[8, P]``) so per-chunk DMA slices are lane-aligned (Mosaic
     requires 128-aligned dynamic slice extents/offsets; a ``[RMAX, 7]``
     slice is not but an ``[8, RMAX]`` one is);
  2. stream the destination array through VMEM in ``(BLOCK, K)`` row
     blocks (one grid step per block, double-buffered by the pipeline);
  3. each block's arrivals are a *contiguous* range of the sorted arrays
     (precomputed per-block ``starts``); DMA them in RMAX-aligned chunks
     from HBM, transpose the small ``(8, RMAX)`` tiles back to row form
     in VMEM, and overlay with per-row dynamic-sublane VMEM stores — no
     HBM scatter ever happens.

Out-of-range targets (>= n_rows, the drop sentinel) sort to the tail
past ``starts[-1]`` and are never touched, matching ``mode='drop'``.

Requires targets sorted ascending and UNIQUE among in-range rows (the
migrate landing plan guarantees both); rows gathered in the same order.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


from mpi_grid_redistribute_tpu.ops import binning


# VMEM budget: (BLOCK, K) f32 blocks lane-pad K -> 128, so an 8192-row
# block occupies 4.2 MB; x2 double-buffer x (in + out) ~ 17 MB, over the
# default 16 MB scoped-VMEM budget — which is why _scatter_sorted raises
# vmem_limit_bytes. Block size barely moves the measured time (24.5 ms at
# 4096 vs 24.3 at 16384): the per-arrival store loop dominates.
BLOCK = 8192
RMAX = 512  # arrival chunk (lane-aligned: multiple of 128)


def _kernel(starts_ref, rows_t_hbm, tgt_t_hbm, in_ref, out_ref,
            rows_scr, tgt_scr, rows_rt, tgt_rt, sems):
    k = out_ref.shape[1]
    b = pl.program_id(0)
    out_ref[:] = in_ref[:]
    start = starts_ref[b]
    end = starts_ref[b + 1]
    base = b * BLOCK

    def chunk_body(c, _):
        j0 = c * RMAX
        rows_dma = pltpu.make_async_copy(
            rows_t_hbm.at[:, pl.ds(j0, RMAX)], rows_scr, sems.at[0]
        )
        tgt_dma = pltpu.make_async_copy(
            tgt_t_hbm.at[:, pl.ds(j0, RMAX)], tgt_scr, sems.at[1]
        )
        rows_dma.start()
        tgt_dma.start()
        rows_dma.wait()
        tgt_dma.wait()
        # back to row form in VMEM: sublane-indexable per arrival
        rows_rt[:] = rows_scr[:].T  # (RMAX, 8)
        tgt_rt[:] = tgt_scr[:].T  # (RMAX, 8), column 0 = target rows

        def row_body(i, _):
            t = tgt_rt[i, 0] - base
            out_ref[pl.ds(t, 1), :] = rows_rt[pl.ds(i, 1), 0:k]
            return _

        # tight bounds: only this block's arrivals within the chunk (a
        # full-RMAX masked loop costs ~6x the genuine iterations)
        i_lo = jnp.maximum(start - j0, 0)
        i_hi = jnp.minimum(end - j0, RMAX)
        jax.lax.fori_loop(i_lo, i_hi, row_body, None)
        return _

    # lax.div, not `//` — see ops/pallas_overlay.py: jnp floor_divide's
    # sign(const) trace forces an unlowerable `pvary` under shard_map
    c0 = jax.lax.div(start, jnp.int32(RMAX))
    c1 = jax.lax.div(end + jnp.int32(RMAX - 1), jnp.int32(RMAX))
    jax.lax.fori_loop(c0, c1, chunk_body, None)


@functools.partial(jax.jit, static_argnames=("interpret",))
def _scatter_sorted(flat, starts, rows_t, tgt_t, interpret=False):
    n_rows, k = flat.shape
    return pl.pallas_call(
        _kernel,
        grid=(n_rows // BLOCK,),
        in_specs=[
            pl.BlockSpec(memory_space=pltpu.SMEM),  # starts
            pl.BlockSpec(memory_space=pl.ANY),  # rows_t [8, P] (HBM)
            pl.BlockSpec(memory_space=pl.ANY),  # tgt_t [8, P] (HBM)
            pl.BlockSpec((BLOCK, k), lambda b: (b, 0),
                         memory_space=pltpu.VMEM),
        ],
        out_specs=pl.BlockSpec((BLOCK, k), lambda b: (b, 0),
                               memory_space=pltpu.VMEM),
        out_shape=jax.ShapeDtypeStruct((n_rows, k), flat.dtype),
        scratch_shapes=[
            pltpu.VMEM((8, RMAX), flat.dtype),
            pltpu.VMEM((8, RMAX), jnp.int32),
            pltpu.VMEM((RMAX, 8), flat.dtype),
            pltpu.VMEM((RMAX, 8), jnp.int32),
            pltpu.SemaphoreType.DMA((2,)),
        ],
        compiler_params=None if interpret else pltpu.CompilerParams(
            # (BLOCK, 7) f32 blocks lane-pad to (BLOCK, 128): 2 buffers
            # x (in + out) exceed the default 16 MB scoped-VMEM budget at
            # useful block sizes; raise the cap (v5e VMEM is far larger)
            vmem_limit_bytes=100 * 1024 * 1024,
        ),
        interpret=interpret,
    )(starts, rows_t, tgt_t, flat)


def scatter_rows(flat, targets, rows, interpret=False):
    """Drop-in for ``flat.at[targets].set(rows, mode='drop')`` on TPU.

    Sorts (targets, rows) by target, builds the per-block starts, pads the
    arrival count to a multiple of RMAX with drop sentinels, and runs the
    kernel. Falls back to the XLA scatter when shapes don't fit the
    kernel's contract (n_rows not BLOCK-aligned, K > 8, non-f32).
    """
    n_rows, k = flat.shape
    p = targets.shape[0]
    if n_rows % BLOCK or k > 8 or flat.dtype != jnp.float32:
        return flat.at[targets].set(rows, mode="drop")
    sentinel = jnp.int32(n_rows)
    # negatives are drops too; folding them into the sentinel keeps every
    # sort key in [0, n_rows] (bounds_dense's ×2 encoding needs that)
    targets = jnp.where(
        (targets >= n_rows) | (targets < 0), sentinel, targets
    ).astype(jnp.int32)
    ts, order = jax.lax.sort(
        (targets, jnp.arange(p, dtype=jnp.int32)), num_keys=1,
        is_stable=False,
    )
    rows_sorted = jnp.take(rows, order, axis=0)
    p_pad = -(-p // RMAX) * RMAX
    ts = jnp.concatenate(
        [ts, jnp.full((p_pad - p,), sentinel, jnp.int32)]
    )
    rows_sorted = jnp.concatenate(
        [rows_sorted, jnp.zeros((p_pad - p, k), rows.dtype)]
    )
    # transposed, 8-row-padded layouts for lane-aligned chunk DMAs
    rows_t = jnp.zeros((8, p_pad), rows.dtype).at[:k].set(rows_sorted.T)
    tgt_t = jnp.zeros((8, p_pad), jnp.int32).at[0].set(ts)
    starts = binning.match_vma(
        binning.bounds_dense(
            ts, n_rows // BLOCK + 1, stride=BLOCK, key_bound=n_rows
        ),
        flat,
    )
    rows_t = binning.match_vma(rows_t, flat)
    tgt_t = binning.match_vma(tgt_t, flat)
    return _scatter_sorted(flat, starts, rows_t, tgt_t, interpret=interpret)
