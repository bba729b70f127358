"""Pallas TPU segmented CIC deposit: per-cell corner-weight sums straight
from the cell-sorted particle stream (SURVEY.md §3.4, config 5).

THE IDEA. After the payload sort, the scan deposit (ops/deposit.py)
reaches per-cell sums through four more XLA stages — double-float tiled
prefix sums, a dense searchsorted for the 2M+1 run bounds, boundary
gathers, differencing — measured at ~700 ms of the 64M north-star
deposit even after `binning.bounds_dense` (scripts/knockout_deposit.py).
All of it exists to avoid a scatter. This kernel removes the stages
instead of accelerating them: because the stream is SORTED by cell, the
cells a key-block touches form one contiguous canvas span, so

  1. stream ``[T]``-key blocks (with their ``rel``/``mass`` payload
     rows) through VMEM; build the 2^D corner-weight channels in-kernel
     (elementwise — never materialized in HBM);
  2. accumulate each ``CH``-cell (128, measured) canvas chunk in a VMEM
     accumulator via a ONE-HOT MATMUL on the MXU: ``acc += w @ onehot``
     — duplicates (many particles per cell) ADD, which is exactly the
     deposit;
  3. keys only ever advance, so each canvas chunk is open exactly once:
     when the stream moves past it, flush it to HBM with a pure write
     (no read-modify-write, no scatter) and zero the accumulator.

ACCURACY. Per-cell sums accumulate in f32 on the MXU (HIGHEST) within a
block and in f32 VMEM adds across blocks — the same class as a
``segment_sum`` deposit, deterministic (sequential grid, fixed order),
and tested against the float64 oracle at the scan deposit's tolerance.
The double-float scan engine remains the high-accuracy option
(``deposit_method="scan"``); this kernel is the throughput engine.

Contract: ``keys [N]`` int32 CHUNK-MONOTONE with sentinel ``n_cells``
for invalid rows — globally ascending streams qualify, and so do
CONCATENATED PER-SLAB sorts (vrank-major keys, each slab sorted
independently, sentinels at slab tails): the kernel only requires that
consecutive ``T``-blocks' valid-key chunk intervals never step
backwards (``min_chunk(block b+1) >= max_chunk(block b)``; sharing a
chunk is fine), because a chunk, once passed, is flushed and never
reopened. ``rel [D, N]`` block-local coordinates and ``mass [N]``
(or None for unit mass) ride the same order. Returns
``per_cell [2^D, n_cells]``. Off TPU, :func:`segsum_sorted` falls back
to an XLA ``segment_sum`` of the same channel values (same accuracy
class; bit-equal only per-channel-value, not per-sum-order).
"""

from __future__ import annotations

import functools
import itertools
import math

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


from mpi_grid_redistribute_tpu.ops import binning

T = 4096  # keys per grid block
CH = 128  # canvas chunk width (lane-aligned flush unit). On-chip sweep
#           at the 64M north-star (uniform ~32 rows/cell): CH=128 69 ms
#           vs CH=512 117 ms with HIGHEST — narrower chunks waste fewer
#           one-hot columns per (block, chunk) visit. A manual 3-way
#           bf16 split of the weights with DEFAULT-precision matmuls
#           measured 55-57 ms but is only ~1-ulp accurate (the third
#           split term still rounds to bf16); HIGHEST keeps the
#           selection products exact — worth the 14 ms.


def _corner_weights(rel_rows, mass, vblock):
    """Shared 2^D corner-weight channel build (clip-floor fracs, corner
    product, optional mass multiply) — ONE definition so the kernel and
    the XLA fallback stay numerically identical by construction.

    ``rel_rows``: list of D same-shape arrays; ``mass`` broadcastable or
    None (unit). Returns the channels stacked on a new axis 0.
    """
    d = len(rel_rows)
    fracs = []
    for dd in range(d):
        r = rel_rows[dd]
        i0 = jnp.clip(jnp.floor(r), 0.0, jnp.float32(vblock[dd] - 1))
        fracs.append(jnp.clip(r - i0, 0.0, 1.0))
    rows = []
    for corner in itertools.product((0, 1), repeat=d):
        w = None
        for dd in range(d):
            tt = fracs[dd] if corner[dd] == 1 else 1.0 - fracs[dd]
            w = tt if w is None else w * tt
        if mass is not None:
            w = mass * w
        rows.append(w)
    if rows[0].ndim == 2:  # kernel path: [1, T] rows -> [2^D, T]
        return jnp.concatenate(rows, axis=0)
    return jnp.stack(rows, axis=0)  # fallback path: [N] rows -> [2^D, N]


def _kernel(keys_ref, rel_ref, mass_ref, out_hbm, acc,
            cur_ref, sem, *,
            n_cells: int, nblocks: int, d: int, vblock, unit_mass: bool):
    t = pl.program_id(0)

    @pl.when(t == 0)
    def _():
        cur_ref[0] = 0
        acc[:] = jnp.zeros_like(acc)

    k2 = keys_ref[0:1, :]  # [1, T] i32, sorted; sentinel n_cells
    # in-kernel corner-weight channels [2^D, T]: frac from the payload
    # rows, mass multiplied last — never materialized in HBM. No
    # validity masking needed: invalid rows carry the sentinel key,
    # which matches no one-hot column.
    wch = _corner_weights(
        [rel_ref[dd : dd + 1, :] for dd in range(d)],
        None if unit_mass else mass_ref[0:1, :],
        vblock,
    )  # [2^D, T]

    # block extent from the VALID-key min/max (scalar bool reads don't
    # lower — compare int32 scalars instead). The min-based `first`
    # (not k2[0, 0]) is what admits CHUNK-MONOTONE streams: sentinel
    # runs may interleave mid-stream (per-slab sorts concatenated), as
    # long as valid keys never revisit a flushed chunk. Sentinels are
    # n_cells, so min(k2) < n_cells iff the block has any valid key.
    kmin = jnp.min(k2)
    any_valid = kmin < n_cells
    kmax = jnp.max(jnp.where(k2 < n_cells, k2, -1))
    first = lax.div(kmin, jnp.int32(CH))
    last = lax.div(jnp.maximum(kmax, 0), jnp.int32(CH))
    n_chunks = (n_cells + CH - 1) // CH
    io = jax.lax.broadcasted_iota(jnp.int32, (T, CH), 1)

    def flush_upto(c_target):
        # flush open chunks until cur == c_target (pure writes: sorted
        # keys mean a chunk is never revisited once passed)
        def body(i, _):
            cur = cur_ref[0]
            cp = pltpu.make_async_copy(
                acc, out_hbm.at[:, pl.ds(cur * CH, CH)], sem
            )
            cp.start()
            cp.wait()
            acc[:] = jnp.zeros_like(acc)
            cur_ref[0] = cur + 1
            return _

        lax.fori_loop(0, c_target - cur_ref[0], body, None)

    @pl.when(any_valid)
    def _():
        # ONE sublane-major transpose of the keys per block: the
        # lane-major alternative needs an NT dot_general whose per-chunk
        # internal transpose measured 186 vs 118 ms at 64M
        k_t = k2.T  # [T, 1]

        def chunk_body(c, _):
            flush_upto(c)
            # NN one-hot: oh[j, s] = (k[j] - c*CH == s); keys are
            # sublane-major so the matmul is a native [2^D,T]@[T,CH]
            oh = (io == k_t - c * jnp.int32(CH)).astype(jnp.float32)
            acc[:, :] += jax.lax.dot(
                wch, oh,
                preferred_element_type=jnp.float32,
                precision=jax.lax.Precision.HIGHEST,
            )
            return _

        lax.fori_loop(first, last + 1, chunk_body, None)

    @pl.when(t == nblocks - 1)
    def _():
        flush_upto(jnp.int32(n_chunks))


@functools.partial(
    jax.jit,
    static_argnames=("n_cells", "vblock", "d", "interpret"),
)
def _segsum_tpu(keys, rel, mass, n_cells, vblock, d, interpret=False):
    n = keys.shape[0]
    nch = 1 << d
    n_pad = -(-n // T) * T
    s_pad = -(-n_cells // CH) * CH
    keys_p = jnp.pad(keys, (0, n_pad - n),
                     constant_values=n_cells).reshape(1, n_pad)
    rel_p = jnp.pad(rel, ((0, 0), (0, n_pad - n)))
    unit_mass = mass is None
    nblocks = n_pad // T
    impl = functools.partial(
        _kernel, n_cells=n_cells, nblocks=nblocks, d=d,
        vblock=vblock, unit_mass=unit_mass,
    )
    if unit_mass:
        def kernel(keys_ref, rel_ref, out_hbm, acc, cur_ref, sem):
            impl(keys_ref, rel_ref, None, out_hbm, acc, cur_ref, sem)
    else:
        kernel = impl
    keys_p = binning.match_vma(keys_p, rel_p)
    block = lambda rows: pl.BlockSpec(  # noqa: E731
        (rows, T), lambda b: (0, b), memory_space=pltpu.VMEM
    )
    # unit mass drops the mass INPUT entirely (not just the sort
    # operand): a zeros stream the kernel statically ignores would
    # still be DMA'd into VMEM every grid step (~256 MB at 64M)
    operands = [keys_p, rel_p]
    in_specs = [block(1), block(d)]
    if not unit_mass:
        mass_p = binning.match_vma(
            jnp.pad(mass, (0, n_pad - n)).reshape(1, n_pad), rel_p
        )
        operands.append(mass_p)
        in_specs.append(block(1))
    out = pl.pallas_call(
        kernel,
        grid=(nblocks,),
        in_specs=in_specs,
        out_specs=pl.BlockSpec(memory_space=pl.ANY),
        out_shape=jax.ShapeDtypeStruct(
            (nch, s_pad), jnp.float32, vma=jax.typeof(rel_p).vma
        ),
        scratch_shapes=[
            pltpu.VMEM((nch, CH), jnp.float32),
            pltpu.SMEM((1,), jnp.int32),
            pltpu.SemaphoreType.DMA,
        ],
        interpret=interpret,
    )(*operands)
    return out[:, :n_cells]


def _segsum_xla(keys, rel, mass, n_cells, vblock, d):
    """Platform fallback: identical channel VALUES (shared
    :func:`_corner_weights`), summed per cell by ``segment_sum``
    (scatter-add — fine on CPU, the TPU-slow path)."""
    wch = _corner_weights(
        [rel[dd] for dd in range(d)], mass, vblock
    )  # [2^D, N]
    valid = keys < n_cells
    wch = jnp.where(valid[None, :], wch, 0.0)
    seg = jnp.clip(keys, 0, n_cells)
    return jax.vmap(
        lambda w: jax.ops.segment_sum(w, seg, num_segments=n_cells + 1)
    )(wch)[:, :n_cells]


def segsum_sorted(keys, rel, mass, n_cells: int, vblock,
                  interpret: bool = False):
    """Per-cell corner-weight sums of a cell-sorted particle stream.

    ``keys [N]`` int32 CHUNK-MONOTONE (module docstring: globally
    ascending, or concatenated per-slab sorts with sentinel runs at
    slab tails; sentinel ``n_cells`` = invalid), ``rel [D, N]``
    block-local coordinates riding the same order, ``mass [N]`` likewise
    or ``None`` (unit mass — also drops the operand upstream from the
    payload sort). Returns ``[2^D, n_cells]``. The kernel engages on TPU
    (or ``interpret=True``); elsewhere the XLA ``segment_sum`` fallback
    computes the same channel values.
    """
    d = rel.shape[0]
    vblock = tuple(int(b) for b in vblock)
    if n_cells > 2**27:
        raise ValueError(
            f"segsum_sorted: n_cells={n_cells} exceeds the int32/memory "
            "bound (2**27)"
        )
    if interpret or jax.default_backend() == "tpu":
        return _segsum_tpu(
            keys, rel, mass, n_cells, vblock, d, interpret=interpret
        )
    return _segsum_xla(keys, rel, mass, n_cells, vblock, d)
