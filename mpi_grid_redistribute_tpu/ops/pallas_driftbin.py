"""Fused Pallas drift + periodic wrap + destination binning.

THE WALL. The migrate loop's phase 0-1 (drift the planar state, wrap,
bin to destination keys) is pure elementwise arithmetic, yet measures
~9x its bandwidth roofline under XLA (6.3 ms at 8.4M rows, 68 ms at the
64M north-star — the round-4 phase knockout): the chain materializes
several narrow ``[D, m]`` intermediates (2.67x sublane-padded in the
T(8,128) layout) and the scan-carry concatenate rewrites the whole
``[K, m]`` state once more. Both measured XLA reformulations (DUS drift,
flat binning) were negative — the round-4 knockout probes; the
structural fix is ONE streaming pass.

THE KERNEL. Grid ``(V, n // w)`` over the planar ``[K, V * n]`` int32
state; each ``[K, w]`` block is read once, drifted (position rows viewed
as f32), wrapped with the SAME reciprocal-multiply chain as
``binning.remainder_fast`` / ``wrap_periodic_planar`` (bit-identical:
identical op sequence on identical f32 constants), binned with the SAME
floor-mul + clip + stride accumulation as the migrate engines, and
written back once together with the ``[V, n]`` destination-key array the
phase-2 sort consumes. The block's vrank id is ``program_id(0)`` —
scalar, free — so no per-column vrank-id materializes at all.

Bytes per column: read K words, write K + 1 (state + key) — ~0.65 ms
roofline at 8.4M rows vs the 6.3 ms XLA chain it replaces.

Contract (else the caller falls back to the XLA twin, which IS the
engine chain): int32 planar state, one device (global rank == vrank),
no cell->rank assignment table, every periodic axis a power-of-two
extent, ``n % w == 0``. ``drift_wrap_bin_xla`` is the reference twin
used by the fallback and the bit-equality tests.
"""

from __future__ import annotations

import functools

import numpy as np

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


from mpi_grid_redistribute_tpu.domain import Domain, ProcessGrid
from mpi_grid_redistribute_tpu.ops import binning

# candidate lane-block widths, largest first; the largest divisor of n
# wins (they measure within noise of each other at bench shapes — the
# kernel is bandwidth-bound — but bigger blocks mean fewer grid steps)
_WIDTHS = (32768, 16384, 8192, 4096, 2048, 1024)


def _axis_consts(domain: Domain, grid_shape, d: int):
    """Per-axis f32 constants, computed with numpy f32 arithmetic so the
    bits match XLA's constant folding of the engine's jnp expressions."""
    lo = np.float32(domain.lo[d])
    ext = np.float32(domain.extent[d])
    hi = np.float32(lo + ext)  # f32 add, same bits as lo + ext on device
    inv_ext = np.float32(np.float32(1.0) / ext) if binning._is_pow2(
        float(domain.extent[d])
    ) else np.float32(0)
    inv_w = np.float32(np.float32(grid_shape[d]) / ext)
    return lo, ext, hi, inv_ext, inv_w


def _wrap_pow2(p, lo, ext, hi, inv_ext):
    """binning.remainder_fast (pow2 path) + the wrap fold, verbatim:
    ``w = lo + remainder_fast(p - lo, ext); w = where(w >= hi, lo, w)``."""
    q = p - lo
    r = q - jnp.floor(q * inv_ext) * ext
    r = jnp.where((r < jnp.float32(0)) | (r >= ext), jnp.float32(0), r)
    w = lo + r
    return jnp.where(w >= hi, lo, w)


def _kernel(in_ref, out_ref, key_ref, *, K, D, dt, consts, periodic,
            shape, strides, R_total):
    # FMA note: on the real chip BOTH XLA and Mosaic lower `a + b * dt`
    # as a separate mul + add (measured bit-identical, round 4); on CPU
    # both the jitted XLA twin and the jitted interpret-mode kernel are
    # CONTRACTED into an fma by LLVM — so kernel and twin agree at the
    # bit level on every backend AS LONG AS the twin runs under jit
    # (it always does in production; tests jit it explicitly).
    v = pl.program_id(1)
    pv = lax.bitcast_convert_type(in_ref[0 : 2 * D, :], jnp.float32)
    p = pv[0:D, :] + pv[D : 2 * D, :] * jnp.float32(dt)
    new_pos = []
    dv = None
    for d in range(D):
        lo, ext, hi, inv_ext, inv_w = consts[d]
        pd = p[d : d + 1, :]
        if periodic[d]:
            # drift wrap (nbody loop) THEN the engine's binning wrap —
            # the second is an identity only for lo == 0; replicate both
            pd = _wrap_pow2(pd, lo, ext, hi, inv_ext)
            pb = _wrap_pow2(pd, lo, ext, hi, inv_ext)
        else:
            pb = pd
        new_pos.append(pd)
        cell = jnp.clip(
            jnp.floor((pb - lo) * inv_w).astype(jnp.int32),
            0,
            shape[d] - 1,
        )
        t = cell * jnp.int32(strides[d])
        dv = t if dv is None else dv + t
    out_ref[0:D, :] = lax.bitcast_convert_type(
        jnp.concatenate(new_pos, axis=0), jnp.int32
    )
    out_ref[D:, :] = in_ref[D:, :]
    alive = in_ref[K - 1 : K, :] > 0
    # the key block spans ALL V sublanes and is revisited across the
    # inner v-sweep of the (nblk, V) grid (Mosaic rejects 1-sublane
    # blocks at non-8-aligned offsets); each step writes its own
    # sublane, and the block flushes complete after the sweep
    key_ref[pl.ds(v, 1), :] = jnp.where(
        alive & (dv != v), dv, jnp.int32(R_total)
    )


@functools.partial(
    jax.jit,
    static_argnames=(
        "V", "n", "w", "K", "D", "dt", "consts", "periodic", "shape",
        "strides", "R_total", "interpret",
    ),
)
def _driftbin_call(flat, *, V, n, w, K, D, dt, consts, periodic, shape,
                   strides, R_total, interpret=False):
    kernel = functools.partial(
        _kernel, K=K, D=D, dt=dt, consts=consts, periodic=periodic,
        shape=shape, strides=strides, R_total=R_total,
    )
    nblk = n // w
    vma = jax.typeof(flat).vma
    return pl.pallas_call(
        kernel,
        grid=(nblk, V),
        in_specs=[
            pl.BlockSpec(
                (K, w), lambda j, v, nblk=nblk: (0, v * nblk + j),
                memory_space=pltpu.VMEM,
            ),
        ],
        out_specs=[
            pl.BlockSpec(
                (K, w), lambda j, v, nblk=nblk: (0, v * nblk + j),
                memory_space=pltpu.VMEM,
            ),
            pl.BlockSpec((V, w), lambda j, v: (0, j),
                         memory_space=pltpu.VMEM),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((K, V * n), flat.dtype, vma=vma),
            jax.ShapeDtypeStruct((V, n), jnp.int32, vma=vma),
        ],
        # the pre-drift state is dead once streamed: update in place
        input_output_aliases={0: 0},
        interpret=interpret,
    )(flat)


def drift_wrap_bin_xla(flat, dt, domain: Domain, full_grid: ProcessGrid,
                       V: int, R_total: int):
    """Reference twin: the EXACT drift + wrap + bin chain the nbody loop
    and the Dev==1 vrank migrate engine execute (models/nbody.py scan
    body; parallel/migrate.shard_migrate_vranks_fn binning). Used as the
    fallback when the kernel contract doesn't hold and as the
    bit-equality oracle for the kernel."""
    K = flat.shape[0]
    D = domain.ndim
    n = flat.shape[1] // V
    pf = lax.bitcast_convert_type(flat[:D, :], jnp.float32)
    vf = lax.bitcast_convert_type(flat[D : 2 * D, :], jnp.float32)
    p = pf + vf * jnp.asarray(dt, pf.dtype)
    p = binning.wrap_periodic_planar(p, domain)
    flat = jnp.concatenate(
        [lax.bitcast_convert_type(p, jnp.int32), flat[D:, :]], axis=0
    )
    alive = flat[-1, :].reshape(V, n) > 0
    dv = jnp.zeros((V * n,), jnp.int32)
    for d in range(D):
        pd = lax.bitcast_convert_type(flat[d, :], jnp.float32)
        lo = jnp.asarray(domain.lo[d], pd.dtype)
        ext = jnp.asarray(domain.extent[d], pd.dtype)
        if domain.periodic[d]:
            pd = lo + binning.remainder_fast(pd - lo, domain.extent[d])
            pd = jnp.where(pd >= lo + ext, lo, pd)
        inv_w = jnp.asarray(full_grid.shape[d], pd.dtype) / ext
        cell_d = jnp.clip(
            jnp.floor((pd - lo) * inv_w).astype(jnp.int32),
            0,
            full_grid.shape[d] - 1,
        )
        dv = dv + cell_d * jnp.int32(full_grid.strides[d])
    dv = dv.reshape(V, n)
    my_v = jnp.arange(V, dtype=jnp.int32)
    staying = dv == my_v[:, None]
    dest_key = jnp.where(alive & ~staying, dv, R_total).astype(jnp.int32)
    return flat, dest_key


def kernel_width(n: int, V: int = 8, K: int = 7) -> int | None:
    """Largest candidate block width dividing ``n`` whose double-buffered
    VMEM footprint ((2K + V) words x 2 buffers) stays within budget."""
    budget = 8 << 20
    for w in _WIDTHS:
        if n % w == 0 and (2 * K + V) * w * 4 * 2 <= budget:
            return w
    return None


def supports(domain: Domain, V: int, n: int, K: int,
             dtype=jnp.int32) -> bool:
    """True when the fused kernel's contract holds (see module docstring).
    Platform is the CALLER's decision (resolved once at build time, like
    migrate._resolve_scatter_impl) — this checks shapes and domain only."""
    if dtype != jnp.int32 or K < 2 * domain.ndim + 1:
        return False
    if kernel_width(n, V, K) is None:
        return False
    return all(
        binning._is_pow2(float(e))
        for e, p in zip(domain.extent, domain.periodic)
        if p
    )


def drift_wrap_bin(flat, dt: float, domain: Domain,
                   full_grid: ProcessGrid,
                   V: int, R_total: int, interpret=False, w=None):
    """Fused drift + wrap + bin: ``[K, V*n]`` int32 planar state ->
    ``(drifted state, dest_key [V, n])``, one streaming pass.

    Drop-in for the nbody scan-body drift followed by the Dev==1 vrank
    engine's binning (bit-identical — tests/test_pallas_driftbin.py).
    Falls back to :func:`drift_wrap_bin_xla` when the contract doesn't
    hold. ``dt`` must be static (it is baked into the kernel)."""
    K = flat.shape[0]
    D = domain.ndim
    n = flat.shape[1] // V
    if w is None:
        w = kernel_width(n, V, K)
    if (
        w is None
        or n % w
        or not supports(domain, V, n, K, flat.dtype)
    ):
        return drift_wrap_bin_xla(flat, dt, domain, full_grid, V, R_total)
    consts = tuple(
        _axis_consts(domain, full_grid.shape, d) for d in range(D)
    )
    out, key = _driftbin_call(
        flat, V=V, n=n, w=w, K=K, D=D, dt=float(dt), consts=consts,
        periodic=tuple(bool(p) for p in domain.periodic),
        shape=tuple(int(s) for s in full_grid.shape),
        strides=tuple(int(s) for s in full_grid.strides),
        R_total=int(R_total), interpret=interpret,
    )
    return out, key
