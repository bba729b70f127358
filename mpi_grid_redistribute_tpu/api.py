"""Public API: ``GridRedistribute`` + ``redistribute()`` (SURVEY.md §3.1-3.2).

Mirrors the reference's entry point — construct with domain bounds and a
process-grid shape, then call ``redistribute(positions, *payload_arrays)``
([DRIVER] spec in BASELINE.json north_star; reference mount empty, SURVEY.md
§0) — with the mandated ``backend={'jax', 'numpy'}`` switch: ``'jax'`` runs
the SPMD pipeline on the device mesh; ``'numpy'`` runs the bit-level
rank-simulation oracle with identical padded layout and capacity semantics
(the stand-in for the reference's mpi4py oracle path, which needs mpi4py —
absent here, SURVEY.md §4).

Global data layout (both backends):
  * ``pos``:   ``[R * n_local, ndim]`` — shard r owns rows
    ``[r*n_local, (r+1)*n_local)``; only the first ``count[r]`` are valid.
  * ``count``: ``[R]`` int32 valid-row counts (``None`` = all rows valid).
  * fields:    any number of ``[R * n_local, ...]`` arrays riding the same
    permutation (SURVEY.md C7). A field wider than 32 bits (``int64``,
    ``uint64``, ``float64``, ...) rides as its 32-bit words and comes back
    on the jax backend as ``int32 [R * out_capacity, ..., w]`` words, low
    word first; :meth:`RedistributeResult.host_field` joins them into the
    caller's dtype. No field is ever narrowed.
"""

from __future__ import annotations

import functools
import math
import warnings
from typing import NamedTuple, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from mpi_grid_redistribute_tpu.domain import Domain, GridEdges, ProcessGrid
from mpi_grid_redistribute_tpu import oracle
from mpi_grid_redistribute_tpu.parallel import exchange, mesh as mesh_lib
from mpi_grid_redistribute_tpu.parallel import halo as halo_lib
from mpi_grid_redistribute_tpu.parallel.halo import HaloResult
from mpi_grid_redistribute_tpu.telemetry import context as context_lib
from mpi_grid_redistribute_tpu.telemetry import flow as flow_lib
from mpi_grid_redistribute_tpu.telemetry import health as health_lib
from mpi_grid_redistribute_tpu.telemetry import metrics as metrics_lib
from mpi_grid_redistribute_tpu.telemetry.phases import span, traced_span
from mpi_grid_redistribute_tpu.telemetry import recorder as telemetry_lib
from mpi_grid_redistribute_tpu.telemetry import report as report_lib
from mpi_grid_redistribute_tpu.telemetry import traceview as traceview_lib


class RedistributeResult(NamedTuple):
    """Outcome of one redistribute: padded arrays + counts + stats.

    ``field_dtypes`` names the dtype each field was passed in. The numpy
    backend returns every field in that dtype. The jax backend returns a
    host field wider than 32 bits (``int64`` ids, a ``float64`` column)
    as its 32-bit words: an ``int32 [R * out_capacity, ..., w]`` device
    array, ``w = itemsize // 4``, low word first; with ``jax_enable_x64``
    a device field of 8-byte dtype comes back in its own dtype.
    :meth:`host_field` gives any field on the host in its caller's dtype.
    Positions are binned at float32 on both backends (a float64 position
    array is rounded to float32 first).
    """

    positions: object
    fields: Tuple
    count: object
    stats: object
    field_dtypes: Tuple[str, ...] = ()

    def host_field(self, i: int) -> np.ndarray:
        """Field ``i`` as a host NumPy array in the dtype it was passed in,
        its 32-bit words joined where it rode as words."""
        out = np.asarray(self.fields[i])
        if i < len(self.field_dtypes):
            dtype = np.dtype(self.field_dtypes[i])
            if out.dtype != dtype:
                out = join_words(out, dtype)
        return out


def host_words(a):
    """A host NumPy array wider than 32 bits as its 32-bit words: ``int32
    [..., itemsize // 4]``, low word first, a view where ``a`` is
    contiguous and little-endian. Other arrays (device arrays, 32-bit and
    narrower dtypes) pass unchanged. The jax backend carries such fields
    this way; :func:`join_words` is the inverse."""
    if (
        isinstance(a, np.ndarray)
        and a.dtype.itemsize > 4
        and a.dtype.itemsize % 4 == 0
    ):
        a = np.ascontiguousarray(a, a.dtype.newbyteorder("<"))
        return a.view("<i4").reshape(a.shape + (a.dtype.itemsize // 4,))
    return a


def join_words(words, dtype) -> np.ndarray:
    """Inverse of :func:`host_words`: ``int32 [..., itemsize // 4]`` words
    of ``dtype`` values, low word first, joined on the host."""
    dtype = np.dtype(dtype)
    w = np.ascontiguousarray(np.asarray(words), "<i4")
    if w.shape[-1:] != (dtype.itemsize // 4,):
        raise ValueError(
            f"{dtype} values are {dtype.itemsize // 4} words each; got "
            f"words of shape {w.shape}"
        )
    out = w.view(dtype.newbyteorder("<")).reshape(w.shape[:-1])
    return out.astype(dtype, copy=False)


def _payload_words(positions, fields) -> dict:
    """32-bit words one row carries, and how many of them come from
    fields wider than 32 bits (journaled with ``engine_resolved`` and in
    :meth:`GridRedistribute.report`)."""
    words = wide = 0
    for a in (positions,) + tuple(fields):
        n = -(-math.prod(a.shape[1:]) * np.dtype(a.dtype).itemsize // 4)
        words += n
        if np.dtype(a.dtype).itemsize > 4:
            wide += n
    return {"payload_words": words, "payload_words_64": wide}


def _next_pow2(n: int) -> int:
    """Smallest power of two >= n (n >= 1)."""
    return 1 << max(int(n) - 1, 0).bit_length()


class MoverCapacity:
    """Measured-need growth policy for the sparse migrate engine's
    ``mover_cap`` — the same machinery the canonical path runs on
    ``needed_capacity`` (ISSUE 4).

    Host-side and loop-agnostic: fold each window's ``MigrateStats``
    with :meth:`update`. The exact per-step mover count is ``sent +
    backlog`` (granted sends plus held-back leavers); when its observed
    peak exceeds the current cap, the cap ratchets to the next
    power-of-two bucket (recompiles then track bucket crossings only,
    like ``Redistributer._capacities``) and ``update`` returns True —
    the caller rebuilds its loop, e.g. ``cfg = dataclasses.replace(cfg,
    mover_cap=mc.value)`` + ``nbody.make_migrate_loop(cfg, ...)``.
    Never shrinks (a slow drift of shrink/grow would thrash
    recompiles). Each growth journals a ``mover_cap_grow`` event to the
    optional :class:`..telemetry.StepRecorder` (telemetry/SCHEMA.md).
    """

    def __init__(self, initial: int, max_cap: int = None, recorder=None):
        if int(initial) < 1:
            raise ValueError(f"initial must be >= 1, got {initial}")
        self.max_cap = None if max_cap is None else int(max_cap)
        self.value = _next_pow2(int(initial))
        if self.max_cap is not None:
            self.value = min(self.value, self.max_cap)
        self.recorder = recorder
        self.grow_count = 0

    def update(self, stats) -> bool:
        """Fold one step's (or a stacked window's) MigrateStats; True
        when ``value`` grew and the loop should be rebuilt."""
        movers = np.asarray(stats.sent) + np.asarray(stats.backlog)
        peak = int(movers.max()) if movers.size else 0
        if peak <= self.value:
            return False
        new = _next_pow2(peak)
        if self.max_cap is not None:
            new = min(new, self.max_cap)
        if new <= self.value:
            return False
        old, self.value = self.value, new
        self.grow_count += 1
        if self.recorder is not None:
            self.recorder.record(
                "mover_cap_grow", old=old, new=new, peak_movers=peak
            )
        return True


def _planar_refusal(positions, fields) -> Optional[str]:
    """Why these arrays cannot ride the planar fused state, or ``None``
    when they can. The state moves rows as int32 words
    (``migrate.fuse_fields`` semantics): positions must be 32-bit (they
    are binned as float32), a field 4 or 8 bytes a value — an 8-byte
    device array (``jax_enable_x64``) is split into its two words inside
    the program. 8- and 16-bit fields fall back to the row-major engine;
    host fields wider than 32 bits arrive here already as words
    (:func:`host_words`)."""
    if positions.dtype.itemsize != 4:
        return f"positions are {positions.dtype} (planar bins 32-bit)"
    for i, a in enumerate(fields):
        size = a.dtype.itemsize
        if size not in (4, 8) or (size == 8 and a.dtype.kind not in "iuf"):
            return f"field {i} is {a.dtype} ({size}-byte)"
    return None


def _planar_specs(positions, fields):
    """Per-array ``(trailing_shape, dtype, words)`` specs for the planar
    engines — ``words`` is the int32 rows the array takes in the fused
    state, two per value for an 8-byte dtype — or ``None`` when
    :func:`_planar_refusal` refuses the arrays."""
    if _planar_refusal(positions, fields) is not None:
        return None
    return tuple(
        (
            tuple(a.shape[1:]),
            np.dtype(a.dtype),
            math.prod(a.shape[1:]) * (a.dtype.itemsize // 4),
        )
        for a in (positions,) + tuple(fields)
    )


def _fuse_planar(positions, fields, R: int, n_local: int, specs,
                 stacked: bool):
    """``[R*n, ...]`` row-major user arrays -> planar fused state.

    ``stacked=True`` -> ``[R, K, n]`` (vrank engine); ``False`` ->
    ``[K, R*n]`` lane-sharded (mesh engine). One gather per call at the
    API boundary (~3.2 ms per transpose pair at 8.4M rows, measured —
    scripts/microbench_layout.py); inside the engine no narrow-minor
    ``[n, 3]`` buffer ever exists. An 8-byte array's values split into
    two int32 rows each, low word first (``lax.bitcast_convert_type``).

    The fused matrix is built INT32 (everything bitcast): TPU float
    vector copies flush denormal f32 bit patterns — any bitcast int32
    below 2^23 — to zero (measured through the planar pack gather;
    ops/pallas_overlay.py documents the same hazard), while integer
    lanes carry every 32-bit pattern exactly. The engines keep the
    transport int32 end to end and only view the position rows as f32
    for binning.
    """
    parts = []
    for a, (_, dtype, k) in zip((positions,) + tuple(fields), specs):
        if dtype.itemsize == 8:
            flat = jnp.asarray(a).reshape(R, n_local, k // 2)
            flat = jax.lax.bitcast_convert_type(flat, jnp.int32)
            flat = flat.reshape(R, n_local, k)
        else:
            flat = jnp.asarray(a).reshape(R, n_local, k)
            if flat.dtype != jnp.int32:
                flat = jax.lax.bitcast_convert_type(flat, jnp.int32)
        parts.append(jnp.transpose(flat, (0, 2, 1)))  # [R, k, n]
    fused = jnp.concatenate(parts, axis=1)  # [R, K, n] int32
    if not stacked:
        K = fused.shape[1]
        fused = fused.transpose(1, 0, 2).reshape(K, R * n_local)
    return fused


def _unfuse_planar(fused, specs, R: int, out_cap: int, stacked: bool):
    """Inverse of :func:`_fuse_planar`: ``(positions, fields)`` row-major."""
    if not stacked:
        K = fused.shape[0]
        fused = fused.reshape(K, R, out_cap).transpose(1, 0, 2)
    outs = []
    row = 0
    for shape, dtype, k in specs:
        block = jnp.transpose(fused[:, row : row + k, :], (0, 2, 1))
        if dtype.itemsize == 8:
            block = block.reshape(R, out_cap, k // 2, 2)
            block = jax.lax.bitcast_convert_type(block, dtype)
        elif dtype != np.dtype(np.int32):
            block = jax.lax.bitcast_convert_type(block, dtype)
        outs.append(block.reshape((R * out_cap,) + tuple(shape)))
        row += k
    return outs[0], tuple(outs[1:])


@jax.jit
def _accum_overflow_counters(cum, dropped_send, dropped_recv, needed,
                             needed_cross, count):
    """Fold one call's overflow stats into the cumulative device-side
    counters (VERDICT round-3 weak item 1: per-call counters sampled every
    K-th call provably miss a one-call spike between samples; cumulative
    sums make the every-K read cover the WHOLE window). Runs async on
    device — no host sync per call. ``needed_cross`` is the hierarchical
    engine's per-destination-pod peak (zero for every other engine), so
    a deferred window can re-arm the DCN cross block just like
    ``needed_capacity`` re-arms the intra mover block."""
    return {
        "dropped_send": cum["dropped_send"] + jnp.sum(dropped_send),
        "dropped_recv": cum["dropped_recv"] + jnp.sum(dropped_recv),
        "needed_capacity": jnp.maximum(
            cum["needed_capacity"], jnp.max(needed)
        ),
        "needed_cross": jnp.maximum(
            cum["needed_cross"], jnp.max(needed_cross)
        ),
        "needed_out": jnp.maximum(
            cum["needed_out"], jnp.max(count + dropped_recv)
        ),
    }


def _zero_overflow_counters():
    z = jnp.zeros((), jnp.int32)
    return {
        "dropped_send": z,
        "dropped_recv": z,
        "needed_capacity": z,
        "needed_cross": z,
        "needed_out": z,
    }


def _fused_call(engine, specs, R: int, out_cap: int, stacked: bool):
    """One jitted program: boundary fuse (``rd:fuse``) -> ``engine`` ->
    boundary unfuse (``rd:unfuse``), a single dispatch per call."""

    def call(positions, count, *fields):
        n_local = positions.shape[0] // R
        with traced_span("rd:fuse"):
            fused = _fuse_planar(positions, fields, R, n_local, specs,
                                 stacked=stacked)
        out, new_count, stats = engine(fused, count)
        with traced_span("rd:unfuse"):
            pos_out, fields_out = _unfuse_planar(out, specs, R, out_cap,
                                                 stacked=stacked)
        return pos_out, new_count, fields_out, stats

    return jax.jit(call)


@functools.lru_cache(maxsize=64)
def _build_planar_vranks_call(
    domain: Domain, grid: ProcessGrid, cap: int, out_cap: int, specs,
    edges=None,
):
    """The planar vrank exchange under the boundary fuse/unfuse."""
    engine = exchange.vrank_redistribute_planar_fn(
        domain, grid, cap, out_cap, domain.ndim, edges=edges
    )
    return _fused_call(engine, specs, grid.nranks, out_cap, stacked=True)


@functools.lru_cache(maxsize=64)
def _build_planar_mesh_call(
    mesh, domain: Domain, grid: ProcessGrid, cap: int, out_cap: int, specs,
    edges=None,
):
    """The shard_map planar exchange under the boundary fuse/unfuse."""
    sharded = exchange.shard_redistribute_planar_sharded(
        mesh, domain, grid, cap, out_cap, domain.ndim, edges=edges
    )
    return _fused_call(sharded, specs, grid.nranks, out_cap, stacked=False)


@functools.lru_cache(maxsize=64)
def _build_count_driven_vranks_call(
    domain: Domain, grid: ProcessGrid, cap: int, out_cap: int,
    mover_cap: int, eng: str, specs, edges=None,
):
    """The count-driven (sparse/neighbor) vrank exchange under the
    boundary fuse/unfuse."""
    builder = (
        exchange.vrank_redistribute_sparse_fn
        if eng == "sparse"
        else exchange.vrank_redistribute_neighbor_fn
    )
    engine = builder(
        domain, grid, cap, out_cap, mover_cap, domain.ndim, edges=edges
    )
    return _fused_call(engine, specs, grid.nranks, out_cap, stacked=True)


@functools.lru_cache(maxsize=64)
def _build_count_driven_mesh_call(
    mesh, domain: Domain, grid: ProcessGrid, cap: int, out_cap: int,
    mover_cap: int, eng: str, specs, edges=None,
):
    """The shard_map count-driven (sparse/neighbor) exchange under the
    boundary fuse/unfuse."""
    sharded = exchange.shard_redistribute_count_driven_sharded(
        mesh, domain, grid, cap, out_cap, mover_cap, domain.ndim,
        edges=edges, engine=eng,
    )
    return _fused_call(sharded, specs, grid.nranks, out_cap, stacked=False)


@functools.lru_cache(maxsize=64)
def _build_hierarchical_vranks_call(
    domain: Domain, grid: ProcessGrid, hier, cap: int, out_cap: int,
    mover_cap: int, cross_cap: int, specs, edges=None,
):
    """The hierarchical two-level vrank exchange under the boundary
    fuse/unfuse."""
    engine = exchange.vrank_redistribute_hierarchical_fn(
        domain, grid, hier, cap, out_cap, mover_cap, cross_cap,
        domain.ndim, edges=edges,
    )
    return _fused_call(engine, specs, grid.nranks, out_cap, stacked=True)


@functools.lru_cache(maxsize=64)
def _build_hierarchical_mesh_call(
    mesh, domain: Domain, grid: ProcessGrid, hier, cap: int, out_cap: int,
    mover_cap: int, cross_cap: int, specs, edges=None,
):
    """The shard_map hierarchical two-level exchange on the EXPANDED mesh
    under the boundary fuse/unfuse.

    ``mesh`` is the instance's FLAT mesh; its device assignment is
    carried into ``hier.build_mesh`` so explicit user meshes keep their
    placement (the interleaved expanded axes preserve row-major flat
    index == grid rank, so the global layout is unchanged)."""
    emesh = hier.build_mesh(
        None if mesh is None else list(np.asarray(mesh.devices).flat)
    )
    sharded = exchange.shard_redistribute_hierarchical_sharded(
        emesh, domain, grid, hier, cap, out_cap, mover_cap, cross_cap,
        domain.ndim, edges=edges,
    )
    return _fused_call(sharded, specs, grid.nranks, out_cap, stacked=False)


@functools.lru_cache(maxsize=64)
def _neighbor_active_offsets(grid: ProcessGrid, periodic) -> int:
    """Number of active stencil links of ``grid`` — the neighbor engine's
    per-shard wire is ``n_active * mover_cap`` columns (vs ``R * cap``
    dense)."""
    return sum(
        1 for p in mesh_lib.neighbor_perms(grid, tuple(periodic)) if p
    )


@functools.lru_cache(maxsize=64)
def _build_halo_planar_vranks_call(
    domain: Domain, grid: ProcessGrid, widths, pc: int, gc: int, specs
):
    """One jitted program: boundary fuse -> planar vrank halo ->
    boundary unfuse (single dispatch per call)."""
    V = grid.nranks
    engine = halo_lib.vrank_halo_planar_fn(domain, grid, widths, pc, gc)

    def call(positions, count, *fields):
        n_local = positions.shape[0] // V
        fused = _fuse_planar(positions, fields, V, n_local, specs,
                             stacked=True)
        ghost, gcount, overflow = engine(fused, count)
        gpos, gfields = _unfuse_planar(ghost, specs, V, gc, stacked=True)
        return gpos, gcount, gfields, overflow

    return jax.jit(call)


@functools.lru_cache(maxsize=64)
def _build_halo_planar_mesh_call(
    mesh, domain: Domain, grid: ProcessGrid, widths, pc: int, gc: int,
    specs,
):
    """One jitted program: boundary fuse -> shard_map planar halo ->
    boundary unfuse (single dispatch per call)."""
    R = grid.nranks
    engine = halo_lib.build_halo_planar(mesh, domain, grid, widths, pc, gc)

    def call(positions, count, *fields):
        n_local = positions.shape[0] // R
        fused = _fuse_planar(positions, fields, R, n_local, specs,
                             stacked=False)
        ghost, gcount, overflow = engine(fused, count)
        gpos, gfields = _unfuse_planar(ghost, specs, R, gc, stacked=False)
        return gpos, gcount, gfields, overflow

    return jax.jit(call)


@functools.lru_cache(maxsize=64)
def _build_halo_rowmajor_mesh(
    mesh, domain: Domain, grid: ProcessGrid, widths, pc: int, gc: int,
    n_fields: int,
):
    """Cached :func:`halo.build_halo_exchange` with pinned capacities —
    a fresh builder per call would discard its jit cache."""
    return halo_lib.build_halo_exchange(
        mesh, domain, grid, widths, pass_capacity=pc, ghost_capacity=gc,
        n_fields=n_fields,
    )


def _as_domain(domain, lo=None, hi=None, periodic=False) -> Domain:
    if isinstance(domain, Domain):
        return domain
    if domain is None:
        return Domain(lo, hi, periodic)
    raise TypeError(f"domain must be a Domain, got {type(domain)}")


class GridRedistribute:
    """Spatial particle redistribution over a Cartesian grid of shards.

    Args:
      domain: :class:`Domain` (or pass ``lo``/``hi``/``periodic``).
      grid: :class:`ProcessGrid` or a grid-shape tuple like ``(2, 2, 2)``.
      backend: ``'jax'`` (device mesh) or ``'numpy'`` (oracle simulation).
      mesh: optional prebuilt ``jax.sharding.Mesh``; built from
        ``jax.devices()`` when omitted (jax backend only).
      capacity: slots per *remote* (source, dest) pair in the padded
        all-to-all (self-owned rows bypass the wire and are never clipped);
        default ``ceil(n_local / R * capacity_factor)`` at call time.
      capacity_factor: headroom multiplier for the default capacity
        (SURVEY.md §7.6 load-imbalance tension; raise for clustered data).
      out_capacity: padded rows per shard on output; default ``n_local``
        (same layout as input, so drift loops iterate with static shapes).
      on_overflow: what to do when a capacity overflow drops particles
        (SURVEY.md §7.6 "measured capacity + recompile-on-growth", §5.3):

        * ``'grow'`` (default) — read the measured overflow off the stats,
          rebuild at the next power-of-two capacity bucket, and re-run the
          same step on the unchanged inputs; the grown capacities stick on
          the instance, so later calls recompile only on further bucket
          crossings. The overflow check is SYNCHRONOUS (one host fetch per
          call) only while calibrating: after two consecutive clean
          checks the instance switches to DEFERRED checking — EVERY call
          folds its drop counters into CUMULATIVE device-side totals (a
          tiny async kernel, no host sync), and every
          ``check_every``-th call starts an async device-to-host copy of
          those totals while the previous deferred copy (long since
          materialized) is read without blocking dispatch. Because the
          totals are cumulative, each read covers every call of its
          window — a one-call overflow spike between samples cannot slip
          through (round-3 verdict weak item 1). Steady-state loops
          issue no blocking stats sync. A late-detected drop cannot be
          healed retroactively (its result was already consumed), so it
          GROWS capacity for subsequent calls and raises
          :class:`RuntimeError` naming the lossy window — never silent.
          Call :meth:`flush_overflow_checks` at loop end to resolve the
          final (and any partial) window.
        * ``'raise'`` — raise :class:`RuntimeError` on any drop (a host
          sync every call). The opt-out of growth that still never loses
          silently.
        * ``'ignore'`` — return with drop counters surfaced in
          ``result.stats`` (the round-1 behavior). Fully asynchronous,
          zero bookkeeping; callers own the check, e.g.
          ``utils.stats.check_no_loss``.
      check_every: cadence (in calls) of the deferred overflow check once
        ``'grow'`` has calibrated (default 16).
      engine: ``'auto'`` (default), ``'planar'``, ``'sparse'``,
        ``'neighbor'``, ``'hierarchical'`` or ``'rowmajor'`` — which
        canonical exchange carries the payload on the jax backend.
        ``'planar'`` runs the component-major ``[K, n]`` engines
        (payload-carrying-sort compaction; 2.2x the row-major engine at
        4.2M rows — BENCH_CONFIGS.md config 1): no narrow-minor ``[n, 3]``
        buffer exists anywhere, avoiding TPU's T(8,128) tiled-layout
        padding (42.7x for ``[n, 3]``). It requires 32-bit positions
        and fields of 32- or 64-bit values: the rows ride as int32
        words, an 8-byte value as two (a host field wider than 32 bits
        arrives as its words, :func:`host_words`).
        ``'sparse'`` is the COUNT-DRIVEN planar engine: the exchange
        pool shrinks from ``[K, R*C]`` to ``[K, R*mover_cap]``, so wire
        cost scales with the movers rather than the capacity
        provisioning; ``'neighbor'`` additionally replaces the dense
        ``all_to_all`` with a static 3x3x3-stencil ``lax.ppermute``
        shift schedule (<= 26 neighbor blocks). Both carry planar's
        word requirement, guard every step with a globally-agreed
        residence predicate, and fall back to the dense planar pool
        bit-identically when any shard's movers overflow ``mover_cap``
        (surfaced in ``stats.fallback``, billed at dense width in
        ``report()``'s wire model).
        ``'hierarchical'`` is the two-level route (see ``dcn_shape``):
        available only when ``dcn_shape`` declares more than one pod,
        degrading to ``'sparse'`` (journaled) on flat topologies.
        ``'auto'`` picks the hierarchical engine on multi-device
        multi-pod meshes, the count-driven sparse engine on flat
        multi-device meshes, planar on one device (no wire to shrink),
        and falls back to row-major for 8- and 16-bit fields (the
        journaled reason names the field);
        ``'rowmajor'`` forces the round-2 layout (kept for comparison and
        for 8- and 16-bit fields). All produce bit-identical results —
        same routing, same Alltoallv receive order, oracle-tested. Every
        routing decision is journaled as ``engine_resolved``.
      mover_cap: per-destination column count of the count-driven wire
        block (pow2-bucketed, never shrinks). ``None`` derives
        ``capacity // 8`` on first use; measured ``needed_capacity``
        peaks ratchet it (journaled as ``mover_cap_grow``), and a block
        grown to >= ``capacity`` degrades the instance to the planar
        engine (journaled — the count-driven pool would be no smaller
        than dense).
      dcn_shape: optional per-axis DCN domain factors (ISSUE 19 /
        ROADMAP item 2): each grid axis splits into ``dcn_shape[a]``
        pods of ``grid.shape[a] // dcn_shape[a]`` ICI-connected ranks
        (:class:`~.parallel.mesh.HierarchicalMesh`; factors must divide
        the grid). With any factor > 1 the ``'hierarchical'`` engine
        becomes available — and is what ``'auto'`` resolves to on
        multi-device meshes: rows whose destination stays inside the
        sender's pod ride the 3x3x3 neighbor ``ppermute`` schedule
        unchanged, while boundary-crossing rows are condensed into one
        per-destination-pod block, shipped over a single staged DCN
        ``ppermute`` per (pod, pod) pair, and fanned out by a second
        intra-pod hop — DCN carries mover-count-driven bytes instead of
        dense fan-out. Bit-identical to the planar oracle; on a flat
        topology (all factors 1, or no ``dcn_shape``) the route
        degrades to the sparse engine (journaled), never errors.
      cross_cap: per-destination-pod column count of the hierarchical
        engine's condensed DCN block (pow2-bucketed, never shrinks).
        ``None`` derives ``capacity // 8`` on first use; measured
        ``needed_cross`` peaks ratchet it (journaled as
        ``cross_cap_grow``) and — because cross clipping drops rows
        rather than falling back to a dense DCN pool — an overflowing
        call is re-run at the grown block under
        ``on_overflow='grow'``.
      edges: optional :class:`~.domain.GridEdges` — NON-UNIFORM per-axis
        subdomain boundaries (the reference family's ``np.digitize`` /
        searchsorted-on-edges variant, SURVEY.md C1/C2). Ownership,
        routing, the oracle backend and :func:`oracle.assert_ownership`
        all honor the edges; uniform cells remain the default. Build
        load-balancing edges from sample data with
        :meth:`GridEdges.balanced_for`, or let the adaptive loop install
        assignment-aware edges (fine cell -> rank LPT maps) at runtime
        via :meth:`apply_assignment`.
    """

    def __init__(
        self,
        domain: Domain = None,
        grid=None,
        *,
        lo=None,
        hi=None,
        periodic=False,
        backend: str = "jax",
        mesh=None,
        capacity: Optional[int] = None,
        capacity_factor: float = 2.0,
        out_capacity: Optional[int] = None,
        on_overflow: str = "grow",
        check_every: int = 16,
        engine: str = "auto",
        mover_cap: Optional[int] = None,
        dcn_shape: Optional[Sequence[int]] = None,
        cross_cap: Optional[int] = None,
        edges=None,
    ):
        self.domain = _as_domain(domain, lo, hi, periodic)
        if grid is None:
            raise ValueError("grid (ProcessGrid or shape tuple) is required")
        self.grid = (
            grid if isinstance(grid, ProcessGrid) else ProcessGrid(tuple(grid))
        )
        self.grid.validate_against(self.domain)
        if edges is not None and not isinstance(edges, GridEdges):
            # mirror the grid coercion above: a raw per-axis sequence of
            # boundary tuples wraps into GridEdges
            edges = GridEdges(edges)
        self.edges = edges
        if edges is not None:
            edges.validate_against(self.domain, self.grid)
        if backend not in ("jax", "numpy"):
            raise ValueError(f"backend must be 'jax' or 'numpy', got {backend!r}")
        self.backend = backend
        for name, v in (("capacity", capacity), ("out_capacity", out_capacity)):
            if v is not None and int(v) < 1:
                raise ValueError(f"{name} must be >= 1, got {v}")
        if on_overflow not in ("grow", "raise", "ignore"):
            raise ValueError(
                f"on_overflow must be 'grow', 'raise' or 'ignore', "
                f"got {on_overflow!r}"
            )
        self.on_overflow = on_overflow
        if int(check_every) < 1:
            raise ValueError(f"check_every must be >= 1, got {check_every}")
        self.check_every = int(check_every)
        if engine not in exchange.ENGINES:
            raise ValueError(
                f"engine must be one of {exchange.ENGINES}, got {engine!r}"
            )
        self.engine = engine
        # Count-driven wire block (sparse/neighbor canonical engines):
        # pow2-bucketed like the dense capacity, never shrinks, grows from
        # the measured `needed_capacity` (the smallest block that would
        # have kept the fast branch). None = derive from cap on first use.
        if mover_cap is not None and int(mover_cap) < 1:
            raise ValueError(f"mover_cap must be >= 1, got {mover_cap}")
        self._mover_cap = (
            None if mover_cap is None else _next_pow2(int(mover_cap))
        )
        # Two-level topology (ISSUE 19): dcn_shape splits each grid axis
        # into (DCN pods x ICI pod-local) factors. The instance keeps the
        # FLAT mesh as self._mesh (planar/degrade paths are untouched);
        # the expanded mesh exists only inside the hierarchical call
        # builders. dcn factors of all 1 still build the tables but
        # resolve degrades to sparse (n_pods == 1 — journaled).
        self._hier = (
            None if dcn_shape is None
            else mesh_lib.HierarchicalMesh(self.grid, dcn_shape)
        )
        # Per-destination-pod condensed cross block of the hierarchical
        # engine (pow2-bucketed, never shrinks, grows from measured
        # `needed_cross` peaks). None = derive from cap on first use.
        if cross_cap is not None and int(cross_cap) < 1:
            raise ValueError(f"cross_cap must be >= 1, got {cross_cap}")
        self._cross_cap = (
            None if cross_cap is None else _next_pow2(int(cross_cap))
        )
        # (requested engine, vranks, planar_ok, n_devices) of the last
        # resolve — engine_resolved is journaled only when this changes,
        # not once per call
        self._last_resolution = None
        # scheduled-wire model of the last dispatch: engine name,
        # per-shard wire columns, dense-pool columns, shard count — feeds
        # the `wire_bytes` journal field and report()'s
        # wire_bytes_per_step
        self._last_wire = None
        # deferred-check state for 'grow' (see class docstring): number of
        # consecutive clean synchronous checks, calls since the last
        # deferred check was scheduled, the pending async-copied counters,
        # and an instrumentation counter of blocking stat fetches (tests
        # assert the steady state issues none per call). `_cum_counters`
        # are CUMULATIVE device-side drop/need counters folded in on every
        # deferred-mode call, so the every-`check_every` read covers the
        # whole window — a one-call spike between samples is caught
        # (VERDICT round-3 weak item 1). `_seen_*` are the totals already
        # accounted for at the last resolution.
        self._clean_checks = 0
        self._calls_since_check = 0
        self._pending_check = None  # (counters dict, cap, out_cap, call#)
        self._call_index = 0
        self._blocking_fetches = 0
        self._cum_counters = None
        self._seen_send = 0
        self._seen_recv = 0
        self._resolved_through = 0  # call index covered by the last
        # successfully-read counter snapshot (clean OR lossy)
        self._del_warned = False  # __del__ warns at most once
        self._last_caps = None  # (cap, out_cap, n_local) of the last call
        self._halo_caps = {}  # widths tuple -> grown (pass_cap, ghost_cap)
        # Telemetry journal (telemetry/recorder.py): every capacity
        # growth, deferred-window transition and call lands here as a
        # host-side event — recording never syncs the device, same
        # contract as the deferred checks above. `rd.report()` reads the
        # last call's stats plus these counts into one metrics dict.
        self.telemetry = telemetry_lib.StepRecorder()
        self._last_stats = None
        self._last_row_bytes = None
        self._last_payload = None  # _payload_words of the last call
        # Grid observatory (telemetry/flow.py, health.py): the per-link
        # flow gauge and the always-on rule monitor share this instance's
        # journal. Both are host-side only — folding stats into the
        # accumulator happens inside flow()/health() (a tiny explicit
        # sync at the caller's chosen boundary), never per call.
        self.flow_acc = flow_lib.FlowAccumulator()
        self.monitor = health_lib.HealthMonitor(self.telemetry)
        self.capacity = capacity
        self.capacity_factor = float(capacity_factor)
        self.out_capacity = out_capacity
        self._mesh = mesh
        self._vranks_noted = False
        if backend == "jax" and mesh is not None:
            mesh_lib.validate_mesh_for_grid(mesh, self.grid)

    @property
    def nranks(self) -> int:
        return self.grid.nranks

    @property
    def n_pods(self) -> int:
        """Number of DCN domains (1 when no ``dcn_shape`` was given or
        every factor is 1 — a flat mesh)."""
        return 1 if self._hier is None else self._hier.n_pods

    @property
    def mesh(self):
        if self._mesh is None:
            self._mesh = mesh_lib.make_mesh(self.grid)
        return self._mesh

    @property
    def _vranks(self) -> bool:
        """True when the R-rank grid runs as vmapped virtual ranks on one
        device (fewer devices than ranks, no explicit mesh) — same
        semantics, bit-identical outputs, no cluster needed (SURVEY.md §2
        process-grid topology; the TPU answer to ``mpirun -n R`` on one
        node)."""
        if self.backend != "jax" or self._mesh is not None:
            return False
        devs = jax.devices()
        if len(devs) >= self.nranks:
            return False
        if len(devs) > 1 and not self._vranks_noted:
            self._vranks_noted = True
            warnings.warn(
                f"GridRedistribute: {self.nranks} ranks run as virtual "
                f"ranks on 1 of {len(devs)} visible devices ({devs[0]}); "
                "pass mesh= to place ranks on more of them",
                stacklevel=3,
            )
        return True

    def _capacities(self, n_local: int) -> Tuple[int, int]:
        cap = self.capacity
        if cap is None:
            cap = max(1, math.ceil(n_local / self.nranks * self.capacity_factor))
            # Bucket derived capacities to the next power of two: clustered
            # or growing workloads then re-trigger compilation only on
            # bucket crossings, not on every new (n_local, capacity) pair
            # (SURVEY.md §7.6 "measured capacity + recompile-on-growth").
            cap = _next_pow2(cap)
        cap = min(cap, n_local)  # can never send more than n_local to one dest
        out_cap = n_local if self.out_capacity is None else self.out_capacity
        return cap, out_cap

    def _mover_cap_for(self, cap: int) -> int:
        """Per-destination wire block of the count-driven engines. First
        use derives it from the dense capacity (cap/8, pow2-bucketed —
        the ~10% steady-drift operating point of BENCH_CONFIGS.md
        config 4); after that it only ever grows via
        :meth:`_maybe_grow_mover_cap`, so recompiles track pow2 bucket
        crossings exactly like the dense capacities."""
        if self._mover_cap is None:
            self._mover_cap = _next_pow2(max(1, cap // 8))
        return self._mover_cap

    def _maybe_grow_mover_cap(self, needed: int) -> None:
        """Grow the wire block from measured `needed_capacity` (the
        per-destination peak — exactly the smallest block that would
        have kept the count-driven fast branch). The in-graph fallback
        already delivered bit-identical output for the overflowing
        call, so this only re-arms the fast path for the NEXT call; no
        re-run needed. Journals `mover_cap_grow` like MoverCapacity."""
        if self._mover_cap is None or needed <= self._mover_cap:
            return
        wire = self._last_wire
        if wire is None or wire.get("engine") not in (
            "sparse", "neighbor", "hierarchical"
        ):
            return  # dense engines don't consume the wire block
        old = self._mover_cap
        self._mover_cap = _next_pow2(int(needed))
        self.telemetry.record(
            "mover_cap_grow",
            old=old,
            new=self._mover_cap,
            peak_movers=int(needed),
        )

    def _cross_cap_for(self, cap: int) -> int:
        """Per-destination-pod condensed block of the hierarchical
        engine's staged DCN hop. Derived like :meth:`_mover_cap_for`
        (cap/8, pow2-bucketed) on first use — at the ~2% migration
        operating point cross-pod movers are a sliver of an
        already-sparse flow — then only ever grows via
        :meth:`_maybe_grow_cross_cap`."""
        if self._cross_cap is None:
            self._cross_cap = _next_pow2(max(1, cap // 8))
        return self._cross_cap

    def _maybe_grow_cross_cap(self, needed: int) -> bool:
        """Grow the DCN cross block from measured ``needed_cross`` (the
        per-source peak over destination pods of the UNCLIPPED cross
        totals — exactly the smallest block that would have carried
        every boundary-crossing row). Unlike the intra mover overflow,
        cross clipping DROPS rows (no in-graph dense fallback crosses
        DCN — that would defeat the staged schedule), so the caller
        retries the same step when this returns True. Journals
        ``cross_cap_grow``."""
        if self._cross_cap is None or needed <= self._cross_cap:
            return False
        wire = self._last_wire
        if wire is None or wire.get("engine") != "hierarchical":
            return False
        old = self._cross_cap
        self._cross_cap = _next_pow2(int(needed))
        self.telemetry.record(
            "cross_cap_grow",
            old=old,
            new=self._cross_cap,
            peak_cross=int(needed),
        )
        return True

    def _journal_planar_degrade(self, eng: str, B: int, cap: int,
                                rec) -> None:
        """Journal a count-driven or hierarchical engine falling to the
        dense planar pool because the grown mover block ``B`` reached
        the capacity — once, when it changes the engine of the last
        call and no resolution was journaled for this one."""
        if rec is None and self._last_wire is not None and (
            self._last_wire.get("engine") != "planar"
        ):
            self.telemetry.record(
                "engine_resolved",
                requested=self.engine,
                resolved="planar",
                reason=(
                    f"{eng}: mover_cap {B} >= capacity {cap}, "
                    f"count-driven pool no smaller than dense"
                ),
                canonical=True,
                **(self._last_payload or {}),
            )

    def _hierarchical_fn(self, cap: int, out_cap: int, specs, rec):
        """Build the hierarchical two-level call for these capacities,
        or return ``None`` to degrade to planar when the grown mover
        block already reached the dense pool size (mirroring the
        count-driven degrade — journaled). Sets ``_last_wire`` with the
        per-domain column split: the intra stage ships
        ``n_active * mover_cap`` neighbor columns plus the
        ``(P-1) * pod_size * cross_cap`` fanout pool over ICI, while
        DCN carries only the ``(P-1) * cross_cap`` condensed
        per-destination-pod blocks."""
        if any(dt.itemsize not in (4, 8) for _shape, dt, _k in specs):
            # callers hand us _planar_specs output, which already
            # refused other dtypes; re-check because the fused transport
            # below moves every row as int32 words (two for an 8-byte
            # value)
            raise TypeError(
                "hierarchical engine requires 32-bit positions and 32- "
                "or 64-bit fields (planar fused transport)"
            )
        B = self._mover_cap_for(cap)
        if B >= cap:
            self._journal_planar_degrade("hierarchical", B, cap, rec)
            return None
        B2 = self._cross_cap_for(cap)
        hier = self._hier
        n_pods, pod_size = hier.n_pods, hier.pod_size
        n_act = _neighbor_active_offsets(
            hier.local_grid,
            hier.local_periodic(tuple(self.domain.periodic)),
        )
        cols_ici = n_act * B + (n_pods - 1) * pod_size * B2
        cols_dcn = (n_pods - 1) * B2
        R = self.nranks
        self._last_wire = {
            "engine": "hierarchical",
            "engine_cols": cols_ici + cols_dcn,
            "engine_cols_ici": cols_ici,
            "engine_cols_dcn": cols_dcn,
            "dense_cols": R * cap,
            "shards": R,
        }
        if self._vranks:
            return _build_hierarchical_vranks_call(
                self.domain, self.grid, hier, cap, out_cap, B, B2,
                specs, edges=self.edges,
            )
        return _build_hierarchical_mesh_call(
            self.mesh, self.domain, self.grid, hier, cap, out_cap, B,
            B2, specs, edges=self.edges,
        )

    def _check_inputs(self, pos, fields, count):
        R = self.nranks
        # Both backends bin at the same precision: JAX canonicalizes float64
        # positions to float32 when x64 is off, and a particle within one
        # float32 ulp of a cell edge would otherwise land on different ranks
        # per backend, breaking the advertised bit-level comparability.
        # Fields keep their dtype on both backends: the jax backend carries
        # a field wider than 32 bits as its words (_run_engine).
        if self.backend == "numpy":
            pos = np.asarray(pos)
            pos = pos.astype(
                jax.dtypes.canonicalize_dtype(pos.dtype), copy=False
            )
            fields = tuple(np.asarray(f) for f in fields)
        if pos.ndim != 2 or pos.shape[1] != self.domain.ndim:
            raise ValueError(
                f"positions must be [R*n_local, {self.domain.ndim}], "
                f"got {pos.shape}"
            )
        if pos.shape[0] % R:
            raise ValueError(
                f"global rows {pos.shape[0]} must divide evenly over "
                f"{R} ranks"
            )
        n_local = pos.shape[0] // R
        for i, f in enumerate(fields):
            if f.shape[0] != pos.shape[0]:
                raise ValueError(
                    f"field {i} leading dim {f.shape[0]} != {pos.shape[0]}"
                )
        if count is None:
            count = np.full((R,), n_local, dtype=np.int32)
        if isinstance(count, jax.Array) and self.backend == "jax":
            # Device array (e.g. the previous step's result.count): clip
            # on device — a host-side range check would block async dispatch.
            if count.shape != (R,):
                raise ValueError(f"count must be [{R}], got {count.shape}")
            count = jnp.clip(count.astype(jnp.int32), 0, n_local)
        else:
            count_host = np.asarray(count, dtype=np.int32)
            if count_host.shape != (R,):
                raise ValueError(f"count must be [{R}], got {count_host.shape}")
            if (count_host < 0).any() or (count_host > n_local).any():
                raise ValueError(
                    f"count entries must be in [0, {n_local}], got {count_host}"
                )
            count = (
                jnp.asarray(count_host) if self.backend == "jax" else count_host
            )
        return pos, fields, n_local, count

    def _run_once(
        self, positions, fields, count, cap: int, out_cap: int
    ) -> RedistributeResult:
        dtypes = tuple(np.dtype(f.dtype).name for f in fields)
        if self.backend == "numpy":
            pos_out, counts_out, fields_out, stats = (
                oracle.redistribute_oracle_padded(
                    self.domain,
                    self.grid,
                    positions,
                    count,
                    list(fields),
                    cap,
                    out_cap,
                    edges=self.edges,
                )
            )
            return RedistributeResult(
                pos_out,
                tuple(fields_out),
                counts_out,
                exchange.RedistributeStats(**stats),
                dtypes,
            )
        # dispatching the engine moves host inputs to the device; a host
        # field wider than 32 bits goes as its words, never narrowed
        with span("host:to_device"):
            fields = tuple(host_words(f) for f in fields)
            fn = self._engine_program(positions, fields, cap, out_cap)
            pos_out, new_count, fields_out, stats = fn(
                positions, count, *fields
            )
        return RedistributeResult(
            pos_out, fields_out, new_count, stats, dtypes
        )

    def _engine_program(self, positions, fields, cap: int, out_cap: int):
        """The single-dispatch program for arrays like these (host fields
        wider than 32 bits already as words): resolve the engine, journal
        ``engine_resolved`` when the routing inputs changed, set the
        scheduled-wire model, build (cached) the program."""
        specs = why = None
        if self.engine != "rowmajor":
            why = _planar_refusal(positions, fields)
            if why is not None and self.engine != "auto":
                raise TypeError(
                    f"engine={self.engine!r} requires 32-bit positions and "
                    f"32- or 64-bit fields (they ride as int32 words): "
                    f"{why}; cast or use engine='auto'/'rowmajor'"
                )
            if why is None:
                specs = _planar_specs(positions, fields)
        # ONE dispatch rule, shared with the migrate loop
        # (exchange.resolve_engine): multi-device 'auto' routes to the
        # count-driven sparse engine (wire cost scales with movers); the
        # dense pool is reachable only via explicit engine='planar' or
        # the in-graph overflow fallback. The decision is journaled as
        # engine_resolved whenever the routing inputs change.
        n_dev = 1 if self._vranks else int(self.mesh.devices.size)
        R = self.nranks
        res_key = (self.engine, self._vranks, specs is not None, n_dev)
        rec = None
        if res_key != self._last_resolution:
            self._last_resolution = res_key
            rec = self.telemetry
        resolved = exchange.resolve_engine(
            self.engine, vranks=self._vranks, n_devices=n_dev,
            planar_ok=specs is not None, canonical=True,
            n_pods=self.n_pods, recorder=rec, planar_why=why,
            detail=self._last_payload,
        )
        dense_cols = R * cap
        if resolved == "hierarchical" and specs is not None:
            fn = self._hierarchical_fn(cap, out_cap, specs, rec)
            if fn is not None:
                return fn
            resolved = "planar"
        if resolved in ("sparse", "neighbor") and specs is not None:
            B = self._mover_cap_for(cap)
            if B >= cap:
                # the grown mover block reached the dense pool size: the
                # count-driven engine would be a no-op wrapper, run planar
                self._journal_planar_degrade(resolved, B, cap, rec)
                resolved = "planar"
            else:
                if resolved == "neighbor":
                    engine_cols = B * _neighbor_active_offsets(
                        self.grid, tuple(self.domain.periodic)
                    )
                else:
                    engine_cols = R * B
                self._last_wire = {
                    "engine": resolved,
                    "engine_cols": engine_cols,
                    "dense_cols": dense_cols,
                    "shards": R,
                }
                if self._vranks:
                    return _build_count_driven_vranks_call(
                        self.domain, self.grid, cap, out_cap, B, resolved,
                        specs, edges=self.edges,
                    )
                return _build_count_driven_mesh_call(
                    self.mesh, self.domain, self.grid, cap, out_cap,
                    B, resolved, specs, edges=self.edges,
                )
        self._last_wire = {
            "engine": resolved,
            "engine_cols": dense_cols,
            "dense_cols": dense_cols,
            "shards": R,
        }
        if resolved == "planar" and specs is not None:
            # The planar [K, n] engines: the repo's fastest canonical path
            # (BENCH_CONFIGS.md config 1), bit-identical to the row-major
            # engines and the oracle.
            if self._vranks:
                return _build_planar_vranks_call(
                    self.domain, self.grid, cap, out_cap, specs,
                    edges=self.edges,
                )
            return _build_planar_mesh_call(
                self.mesh, self.domain, self.grid, cap, out_cap, specs,
                edges=self.edges,
            )
        if self._vranks:
            raw = exchange.build_redistribute_vranks(
                self.domain, self.grid, cap, out_cap, self.edges
            )

            def fn(positions, count, *fields, _raw=raw, _R=R, _oc=out_cap):
                n = positions.shape[0] // _R
                out = _raw(
                    positions.reshape(_R, n, -1),
                    count,
                    *(
                        f.reshape((_R, n) + f.shape[1:]) for f in fields
                    ),
                )
                unstack = lambda a: a.reshape(
                    (_R * _oc,) + a.shape[2:]
                )
                return (
                    unstack(out[0]),
                    out[1],
                    tuple(unstack(f) for f in out[2:-1]),
                    out[-1],
                )

            return fn
        raw = exchange.build_redistribute(
            self.mesh, self.domain, self.grid, cap, out_cap, len(fields),
            self.edges,
        )

        def fn(positions, count, *fields, _raw=raw):
            out = _raw(positions, count, *fields)
            return out[0], out[1], tuple(out[2:-1]), out[-1]

        return fn

    def engine_fn(self, positions, *fields):
        """Hand out the resolved single-dispatch engine program.

        Returns ``(fn, cap, out_cap)`` where
        ``fn(positions, count, *fields) -> (positions, count, fields,
        stats)`` is the SAME jitted engine :meth:`redistribute` would
        dispatch for arrays of these shapes/dtypes — with no per-call
        Python re-entry: no retry loop, no journal record, no stats
        read. That makes it safe to invoke once per step inside a
        ``lax.scan`` (the resident chunked service loop,
        ``service/resident.py``). The overflow policy moves to the
        CALLER's chunk boundary: read the scanned stats' drop counters
        there, grow via :meth:`_grow` (a fresh ``engine_fn`` picks up
        the grown capacities), and re-run the chunk on its unchanged
        entry arrays. A host field wider than 32 bits is resolved as
        its words, so ``fn`` takes it as :func:`host_words` gives it.

        Engine resolution, the ``engine_resolved`` journal event and the
        scheduled-wire model (``_last_wire``) behave exactly as one
        :meth:`redistribute` call would, so telemetry stays coherent.
        """
        if self.backend != "jax":
            raise ValueError(
                "engine_fn requires backend='jax' — the numpy oracle "
                "has no jitted engine program to hand out"
            )
        R = self.nranks
        if positions.ndim != 2 or positions.shape[0] % R:
            raise ValueError(
                f"positions must be [R*n_local, ndim] over {R} ranks, "
                f"got {positions.shape}"
            )
        n_local = positions.shape[0] // R
        cap, out_cap = self._capacities(n_local)
        self._last_row_bytes = report_lib.row_bytes_of(positions, *fields)
        self._last_payload = _payload_words(positions, fields)
        fields = tuple(host_words(f) for f in fields)
        fn = self._engine_program(positions, fields, cap, out_cap)
        return fn, cap, out_cap

    def redistribute(self, positions, *fields, count=None) -> RedistributeResult:
        """Bin, pack, exchange: every particle moves to its owner shard.

        Returns a :class:`RedistributeResult` in the same global padded
        layout (leading dim ``R * out_capacity``). Under the default
        ``on_overflow='grow'`` a capacity overflow is healed by measuring
        the need from the stats, rebuilding at the next power-of-two
        bucket, and re-running on the unchanged inputs — no particle is
        ever lost and steady workloads recompile only on bucket crossings.
        """
        with span("host:input_check"):
            positions, fields, n_local, count = self._check_inputs(
                positions, fields, count
            )
        self._call_index += 1
        self._last_row_bytes = report_lib.row_bytes_of(positions, *fields)
        self._last_payload = _payload_words(positions, fields)
        # call-scoped step context: every event this call journals
        # (redistribute, capacity_grow, overflow_window_*, alert) carries
        # ctx_call in its envelope, joining it back to this invocation
        with context_lib.scoped(call=self._call_index):
            return self._redistribute_attempts(
                positions, fields, count, n_local
            )

    def _redistribute_attempts(
        self, positions, fields, count, n_local
    ) -> RedistributeResult:
        # the grow-and-retry loop of redistribute(), context already set
        max_attempts = 5
        for _ in range(max_attempts):
            cap, out_cap = self._capacities(n_local)
            result = self._run_once(positions, fields, count, cap, out_cap)
            self._last_stats = result.stats
            wire = self._last_wire or {}
            # scheduled wire bytes of this call's exchange collective
            # (static pool width x row bytes x shards) — what actually
            # crossed the interconnect, independent of occupancy
            wire_bytes = (
                wire.get("engine_cols", 0)
                * (self._last_row_bytes or 0)
                * wire.get("shards", 0)
            )
            self.telemetry.record(
                "redistribute",
                call=self._call_index,
                n_local=n_local,
                capacity=cap,
                out_capacity=out_cap,
                engine=wire.get("engine", self.engine),
                wire_bytes=wire_bytes,
            )
            if self.on_overflow == "ignore":
                return result  # async preserved: no host sync on stats
            if (
                self.on_overflow == "grow"
                and self._clean_checks >= 2
                and self.backend == "jax"
            ):
                # calibrated: deferred checking keeps dispatch async.
                # EVERY call folds its drop counters into the cumulative
                # device-side totals first (one tiny async kernel), so the
                # every-check_every read below covers the whole window —
                # a one-call spike between samples cannot slip through.
                if self._cum_counters is None:
                    self._cum_counters = _zero_overflow_counters()
                self._cum_counters = _accum_overflow_counters(
                    self._cum_counters,
                    result.stats.dropped_send,
                    result.stats.dropped_recv,
                    result.stats.needed_capacity,
                    (
                        result.stats.needed_cross
                        if result.stats.needed_cross is not None
                        else jnp.zeros((), jnp.int32)
                    ),
                    result.count,
                )
                self._deferred_check(n_local, cap, out_cap)
                return result
            self._blocking_fetches += 1
            dropped_send = int(np.asarray(result.stats.dropped_send).sum())
            dropped_recv = int(np.asarray(result.stats.dropped_recv).sum())
            if not dropped_send and not dropped_recv:
                if self.on_overflow == "grow":
                    self._clean_checks += 1
                    self._maybe_grow_mover_cap(
                        int(np.asarray(result.stats.needed_capacity).max())
                    )
                    if result.stats.needed_cross is not None:
                        # clean step: re-arm the DCN cross block for the
                        # NEXT call (nothing was dropped — no retry)
                        self._maybe_grow_cross_cap(
                            int(np.asarray(result.stats.needed_cross).max())
                        )
                return result
            self._clean_checks = 0
            if self.on_overflow == "raise":
                raise RuntimeError(
                    f"particle loss detected: dropped_send={dropped_send}, "
                    f"dropped_recv={dropped_recv} — raise capacity / "
                    f"out_capacity or use on_overflow='grow'"
                )
            # grow: size the rebuild from the measured need, bucketed to
            # powers of two so recompiles track bucket crossings only
            needed = int(np.asarray(result.stats.needed_capacity).max())
            self._maybe_grow_mover_cap(needed)
            # Hierarchical cross-clip drops are healed by growing the
            # DCN cross block, not the dense capacity: a True here makes
            # this attempt retry the SAME step at the grown cross_cap
            # (the clipped rows were dropped, never mis-delivered).
            grew_cross = False
            if result.stats.needed_cross is not None:
                grew_cross = self._maybe_grow_cross_cap(
                    int(np.asarray(result.stats.needed_cross).max())
                )
            needed_out = int(
                (
                    np.asarray(result.count)
                    + np.asarray(result.stats.dropped_recv)
                ).max()
            )
            grew = self._grow(
                dropped_send, dropped_recv, needed, needed_out, n_local,
                cap, out_cap,
            )
            if not (grew or grew_cross):
                raise RuntimeError(
                    f"overflow not resolvable by growth (capacity {cap}, "
                    f"out_capacity {out_cap} already at their maxima): "
                    f"dropped_send={dropped_send} dropped_recv={dropped_recv}"
                )
        raise RuntimeError(
            f"capacity growth did not converge in {max_attempts} attempts"
        )

    def apply_assignment(
        self, edges, positions, *fields, count=None
    ) -> RedistributeResult:
        """Rebind ownership to ``edges`` (typically assignment-aware —
        the :class:`~.telemetry.rebalance.RebalancePlanner`'s fresh
        fine-cell -> rank map) and re-home the state in ONE canonical
        redistribute — the actuation half of the adaptive-rebalancing
        loop.

        The new edges stick on the instance: every subsequent
        :meth:`redistribute` routes by them, and the exchange builders
        recompile exactly once per distinct edges value (they are an
        ``lru_cache`` key). The big redistribute itself is just a row
        permutation — the returned particle SET is bit-identical to the
        input set (id-audited via ``service.elastic.particle_set`` in the
        closed-loop tests), and overflow heals by growing like any other
        call. Pass ``edges=None`` to revert to uniform cells.
        """
        if edges is not None and not isinstance(edges, GridEdges):
            edges = GridEdges(edges)
        if edges is not None:
            edges.validate_against(self.domain, self.grid)
        self.edges = edges
        return self.redistribute(positions, *fields, count=count)

    def halo(
        self,
        positions,
        *fields,
        width,
        count=None,
        headroom: float = 2.0,
        pass_capacity: Optional[int] = None,
        ghost_capacity: Optional[int] = None,
    ) -> HaloResult:
        """Ghost/overlap exchange (SURVEY.md C8): one call returns, for
        every shard, copies of the neighbor shards' particles within
        ``width`` of its subdomain faces — the reference family's
        "overlap width parameter" as a method on the user-facing tool.

        Args:
          positions: ``[R * n_local, ndim]`` in the same global padded
            layout as :meth:`redistribute` (typically its output).
          *fields: per-particle arrays riding along (ids, masses). A
            host field wider than 32 bits rides as its words and its
            ghosts come back as ``int32 [..., itemsize // 4]`` words
            (:func:`join_words` gives the caller's dtype back).
          width: scalar or per-axis halo width in domain units; must not
            exceed the per-axis subdomain width (one-hop shell).
          count: ``[R]`` valid-row counts (e.g. ``result.count``).
          headroom: multiplier for the derived capacities
            (:func:`~.parallel.halo.default_capacities`). Note the
            derivation sizes budgets from the PADDED per-shard rows
            (``positions.shape[0] // R``), not the valid counts — a
            mostly-padding buffer gets generous budgets, so forcing
            overflow in tests needs ``headroom`` well below 1.
          pass_capacity / ghost_capacity: explicit capacity pins; by
            default sized from the halo-volume fraction, and GROWN on
            measured overflow under ``on_overflow='grow'`` (grown sizes
            stick on the instance per width, like redistribute's
            capacities). ``'raise'`` raises on any overflow; ``'ignore'``
            returns with ``HaloResult.overflow`` surfaced.

        Returns a :class:`HaloResult`: ``ghost_positions``
        ``[R * ghost_capacity, ndim]`` (shifted into each receiver's
        frame across periodic wraps), ``ghost_count [R]``,
        ``ghost_fields``, ``overflow [R]``. Engine selection mirrors
        :meth:`redistribute`: planar ``[K, n]`` twins when every array is
        32-bit (24 ns/ghost at config-6 shapes vs 181.7 row-major —
        BENCH_CONFIGS.md), vrank twins when the grid exceeds the device
        count — bit-identical ghosts either way.
        """
        if self.backend != "jax":
            raise ValueError(
                "halo() runs on the jax backend; for NumPy-side "
                "validation use oracle.brute_force_ghosts (the set-level "
                "ghost oracle)"
            )
        if self.edges is not None:
            raise ValueError(
                "halo() requires uniform cells (edges=None): the halo "
                "engines' face predicates assume uniform subdomain "
                "widths — rebalance with GridEdges only on the "
                "redistribute path, or rebuild without edges for ghosts"
            )
        positions, fields, n_local, count = self._check_inputs(
            positions, fields, count
        )
        fields = tuple(host_words(f) for f in fields)
        widths = halo_lib._as_per_axis(width, self.domain.ndim)
        dpc, dgc = halo_lib.default_capacities(
            self.domain, self.grid, widths, n_local, headroom
        )
        grown_pc, grown_gc = self._halo_caps.get(widths, (0, 0))
        pc = pass_capacity if pass_capacity is not None else max(dpc, grown_pc)
        gc = ghost_capacity if ghost_capacity is not None else max(dgc, grown_gc)
        max_attempts = 5
        for attempt in range(1, max_attempts + 1):
            result = self._halo_once(positions, fields, count, widths, pc, gc)
            self.telemetry.record(
                "halo",
                n_local=n_local,
                pass_capacity=pc,
                ghost_capacity=gc,
            )
            if self.on_overflow == "ignore":
                return result  # async preserved: no host sync on stats
            overflow = np.asarray(result.overflow)
            total_ov = int(overflow.sum())
            if not total_ov:
                return result
            if self.on_overflow == "raise":
                raise RuntimeError(
                    f"halo overflow: {total_ov} ghosts dropped at "
                    f"pass_capacity={pc}, ghost_capacity={gc} — raise "
                    f"capacities/headroom or use on_overflow='grow'"
                )
            if pass_capacity is not None and ghost_capacity is not None:
                raise RuntimeError(
                    f"halo overflow: {total_ov} ghosts dropped at the "
                    f"explicitly pinned capacities ({pc}, {gc})"
                )
            if attempt == max_attempts:
                # every grown capacity was actually run (growth below
                # only happens when another attempt follows), so (pc, gc)
                # here are the capacities of the run that still dropped.
                raise RuntimeError(
                    f"halo capacity growth did not converge in "
                    f"{max_attempts} attempts (last run: "
                    f"pass_capacity={pc}, ghost_capacity={gc}, "
                    f"{total_ov} ghosts still dropped)"
                )
            # grow, then retry: the overflow counter aggregates pass- and
            # ghost-capacity drops (they cascade), so grow both budgets
            # by at least the measured per-shard worst case — doubling
            # alone crawls when the starting budget is tiny relative to
            # the need — bucketed to powers of two like redistribute.
            max_ov = int(overflow.max())
            old_pc, old_gc = pc, gc
            if pass_capacity is None:
                pc = _next_pow2(max(2 * pc, pc + max_ov))
            if ghost_capacity is None:
                gc = _next_pow2(gc + max_ov)
            self._halo_caps[widths] = (
                max(pc, grown_pc), max(gc, grown_gc)
            )
            self.telemetry.record(
                "halo_grow",
                old_pass_capacity=old_pc,
                new_pass_capacity=pc,
                old_ghost_capacity=old_gc,
                new_ghost_capacity=gc,
                overflow=total_ov,
            )

    def _halo_once(
        self, positions, fields, count, widths, pc: int, gc: int
    ) -> HaloResult:
        specs = None
        if self.engine in ("auto", "planar"):
            specs = _planar_specs(positions, fields)
            if specs is None and self.engine == "planar":
                raise TypeError(
                    "engine='planar' requires 32-bit positions and 32- or "
                    "64-bit fields (they ride as int32 words): "
                    f"{_planar_refusal(positions, fields)}; cast or use "
                    "engine='auto'/'rowmajor'"
                )
        R = self.nranks
        n_local = positions.shape[0] // R
        if specs is not None:
            if self._vranks:
                fn = _build_halo_planar_vranks_call(
                    self.domain, self.grid, widths, pc, gc, specs
                )
            else:
                fn = _build_halo_planar_mesh_call(
                    self.mesh, self.domain, self.grid, widths, pc, gc,
                    specs,
                )
            gpos, gcount, gfields, overflow = fn(positions, count, *fields)
            return HaloResult(gpos, gcount, gfields, overflow)
        if self._vranks:
            fn = halo_lib.build_halo_vranks(
                self.domain, self.grid, widths, pc, gc
            )
            out = fn(
                positions.reshape(R, n_local, -1),
                count,
                *(f.reshape((R, n_local) + f.shape[1:]) for f in fields),
            )
            unstack = lambda a: a.reshape((R * gc,) + a.shape[2:])
            return HaloResult(
                unstack(out[0]),
                out[1],
                tuple(unstack(f) for f in out[2:-1]),
                out[-1],
            )
        fn = _build_halo_rowmajor_mesh(
            self.mesh, self.domain, self.grid, widths, pc, gc, len(fields)
        )
        return fn(positions, count, *fields)

    def _grow(
        self, dropped_send, dropped_recv, needed, needed_out, n_local,
        cap, out_cap,
    ) -> bool:
        """Raise the instance capacities from measured need; True if grown.

        Growth compares against the CURRENT instance capacities, not just
        the ``cap``/``out_cap`` in force at the measured call: a late
        flush resolving a stale window must never shrink a capacity grown
        in the interim."""
        grew = False
        # Growth triggers when the measured WINDOW needed more than the
        # caps it ran with, but the assigned value keeps a never-shrink
        # floor: the current explicit capacity, or — in derived mode
        # (self.capacity is None) — the caps of the most recent call, so
        # a late flush of a stale small-workload window cannot pin an
        # explicit capacity below what the current workload derives.
        last_cap, last_out = (
            (self._last_caps[0], self._last_caps[1])
            if self._last_caps is not None
            else (0, 0)
        )
        if dropped_send:
            new_cap = min(_next_pow2(needed), n_local)
            if new_cap > cap:
                floor = last_cap if self.capacity is None else self.capacity
                self.capacity = max(new_cap, floor)
                grew = True
                self.telemetry.record(
                    "capacity_grow",
                    which="send",
                    old=cap,
                    new=self.capacity,
                    needed=needed,
                    dropped=dropped_send,
                    call=self._call_index,
                )
        if dropped_recv:
            new_out = min(_next_pow2(needed_out), self.nranks * n_local)
            if new_out > out_cap:
                floor = (
                    last_out if self.out_capacity is None
                    else self.out_capacity
                )
                self.out_capacity = max(new_out, floor)
                grew = True
                self.telemetry.record(
                    "capacity_grow",
                    which="recv",
                    old=out_cap,
                    new=self.out_capacity,
                    needed=needed_out,
                    dropped=dropped_recv,
                    call=self._call_index,
                )
        return grew

    def _deferred_check(self, n_local, cap, out_cap) -> None:
        """Every ``check_every``-th call: resolve the previous deferred
        counter copy (device compute for it finished many calls ago, so
        the read does not serialize dispatch) and schedule a new async
        copy of the CUMULATIVE counters — which at that point already
        include every call of the window, sampled or not."""
        self._last_caps = (cap, out_cap, n_local)
        self._calls_since_check += 1
        if self._calls_since_check < self.check_every:
            return
        self._calls_since_check = 0
        self._resolve_pending()
        counters = dict(self._cum_counters)
        for v in counters.values():
            if hasattr(v, "copy_to_host_async"):
                v.copy_to_host_async()
        self._pending_check = (
            counters, cap, out_cap, n_local, self._call_index
        )
        self.telemetry.record(
            "overflow_window_scheduled",
            through_call=self._call_index,
            window=self.check_every,
        )

    def _resolve_pending(self) -> None:
        if self._pending_check is None:
            return
        counters, cap, out_cap, n_local, call_idx = self._pending_check
        # Blocking device reads FIRST, window bookkeeping after: if a
        # read raises (backend/device failure), the window must stay
        # pending so a later resolve or flush still surfaces the
        # potential loss — clearing the snapshot before the reads
        # succeeded would mark it resolved without ever looking at it.
        total_send = int(np.asarray(counters["dropped_send"]))
        total_recv = int(np.asarray(counters["dropped_recv"]))
        needed = int(np.asarray(counters["needed_capacity"]))
        needed_out = int(np.asarray(counters["needed_out"]))
        self._pending_check = None
        self._resolved_through = max(self._resolved_through, call_idx)
        # re-arm the count-driven fast branch from the window's peak
        # per-destination need (covers the whole window: the cumulative
        # counters fold every call's needed_capacity), and the DCN
        # cross block from its per-destination-pod twin
        self._maybe_grow_mover_cap(needed)
        self._maybe_grow_cross_cap(
            int(np.asarray(counters.get("needed_cross", 0)))
        )
        dropped_send = total_send - self._seen_send
        dropped_recv = total_recv - self._seen_recv
        if not dropped_send and not dropped_recv:
            self.telemetry.record(
                "overflow_window_clean", through_call=call_idx
            )
            return
        self._seen_send, self._seen_recv = total_send, total_recv
        self.telemetry.record(
            "overflow_window_loss",
            through_call=call_idx,
            dropped_send=dropped_send,
            dropped_recv=dropped_recv,
        )
        # A drop this late cannot be healed (results already consumed):
        # grow for subsequent runs, then fail loudly — never silently.
        self._grow(
            dropped_send, dropped_recv, needed, needed_out, n_local,
            cap, out_cap,
        )
        self._clean_checks = 0
        raise RuntimeError(
            f"deferred overflow check: the {self.check_every}-call window "
            f"ending at call {call_idx} dropped {dropped_send} (send) / "
            f"{dropped_recv} (recv) particles; capacities have been grown "
            f"for subsequent calls, but results in that window are lossy — "
            f"restart from the last checkpoint or rerun. Use a smaller "
            f"check_every (or on_overflow='ignore' + your own per-step "
            f"check) to narrow the window."
        )

    def _has_unresolved_windows(self) -> bool:
        """True when deferred-mode calls exist whose cumulative counters
        have not been read back yet — a scheduled-but-unresolved snapshot,
        a trailing partial window, or the tail left when a scheduled
        resolution raised (its RuntimeError accounts only through its own
        snapshot; later calls' counters were folded in but never read)."""
        return (
            self._cum_counters is not None
            and self._call_index > self._resolved_through
        )

    def __enter__(self) -> "GridRedistribute":
        """Context-manager form: ``with GridRedistribute(...) as rd`` —
        ``__exit__`` runs :meth:`flush_overflow_checks`, so a lossy
        trailing window under ``on_overflow='grow'`` raises at block exit
        instead of being silently forgotten (the one human gap the
        deferred-check design left open)."""
        return self

    def __exit__(self, exc_type, exc, tb) -> bool:
        if exc_type is None:
            self.flush_overflow_checks()
        else:
            # An exception is already propagating: still resolve (so
            # growth happens and the loss is surfaced), but as a warning —
            # raising here would mask the in-flight exception. Catch ANY
            # flush failure (the blocking device read can raise
            # backend-specific errors that are not RuntimeError), and
            # force the warning to PRINT rather than raise even under
            # warnings-as-errors: an escaping RuntimeWarning would itself
            # mask the in-flight exception.
            try:
                self.flush_overflow_checks()
            except Exception as loss:
                with warnings.catch_warnings():
                    warnings.simplefilter("always")
                    warnings.warn(
                        f"flush_overflow_checks at context exit: {loss!r}",
                        RuntimeWarning,
                        stacklevel=2,
                    )
        return False

    def __del__(self):
        # Unflushed deferred windows at garbage collection: the user built
        # a 'grow' instance, ran calls whose overflow counters were never
        # read, and dropped it without flush_overflow_checks() / `with`.
        # We cannot raise from __del__, so warn loudly (SURVEY.md §5.3:
        # surfaced, not silent).
        try:
            unresolved = self._has_unresolved_windows() and not self._del_warned
        except Exception:
            return  # partially-constructed instance
        if unresolved:
            self._del_warned = True  # idempotent: explicit __del__ then GC
            warnings.warn(
                "GridRedistribute dropped with unresolved deferred "
                "overflow windows: call flush_overflow_checks() at loop "
                "end (or use the instance as a context manager: "
                "`with GridRedistribute(...) as rd:`) — a capacity "
                "overflow in the trailing window would otherwise go "
                "unreported",
                RuntimeWarning,
                stacklevel=2,
            )

    def flush_overflow_checks(self) -> None:
        """Resolve the FULL cumulative counter history (blocking),
        covering both the pending scheduled window and any trailing
        partial window in one read — the cumulative totals at flush time
        subsume every earlier snapshot, so growth is sized from the whole
        history even when multiple windows were lossy. Call at loop end
        under ``on_overflow='grow'``; raises like the in-loop check on
        detected loss."""
        if self._cum_counters is not None and self._last_caps is not None:
            cap, out_cap, n_local = self._last_caps
            # replace (not chain) any pending snapshot: its totals are a
            # prefix of the current ones
            self._pending_check = (
                dict(self._cum_counters), cap, out_cap, n_local,
                self._call_index,
            )
            self._calls_since_check = 0
        self._resolve_pending()

    def _exchange_topology(self) -> Tuple[str, int]:
        """(domain, n_chips) of the exchange this instance dispatches:
        ``("hbm", 1)`` when the R-rank grid runs on one chip (vranks, or
        a single-device mesh — its "wire" is HBM-side gathers/scatters;
        the numpy oracle reports the same for schema stability), and
        ``("ici", n_devices)`` when rows ride the inter-chip all_to_all."""
        if self.backend != "jax" or self._vranks:
            return "hbm", 1
        n = int(self.mesh.devices.size)
        return ("ici", n) if n > 1 else ("hbm", 1)

    def report(self, step_seconds: Optional[float] = None) -> dict:
        """The instance's metrics surface: one merged, JSON-serializable
        dict (:func:`~.telemetry.report.exchange_report`) from the LAST
        redistribute call's stats — summary counters, exchange bytes per
        step (total and moved), and — when ``step_seconds`` is given —
        achieved GB/s plus ``bw_util`` against this instance's domain
        roof (HBM for single-chip vrank exchanges, summed ICI links per
        chip for multi-chip meshes), plus the telemetry journal's
        all-time event counts and the instance capacities.

        NOTE this fetches the last stats pytree to the host (tiny, but a
        sync): call it at loop/bench boundaries, not per step. Pass a
        scan-differenced ``step_seconds``
        (:func:`~.utils.profiling.scan_time_per_step`) for honest rates —
        wall-clock would bill dispatch overhead as wire time, so without
        it the rate/utilization fields stay ``None``.
        """
        if self._last_stats is None:
            raise RuntimeError(
                "report() needs at least one redistribute() call"
            )
        domain, n_chips = self._exchange_topology()
        wire = self._last_wire or {}
        out = report_lib.exchange_report(
            self._last_stats,
            self._last_row_bytes,
            step_seconds=step_seconds,
            domain=domain,
            n_chips=n_chips,
            recorder=self.telemetry,
            engine_wire_cols=wire.get("engine_cols"),
            dense_wire_cols=wire.get("dense_cols"),
            wire_shards=wire.get("shards"),
        )
        out["engine"] = wire.get("engine", self.engine)
        out.update(self._last_payload or {})
        if "engine_cols_dcn" in wire:
            # hierarchical two-level dispatch: split the scheduled wire
            # into per-domain bytes — DCN carries only the condensed
            # per-destination-pod blocks, ICI the neighbor stencil and
            # the intra-pod fanout pool (same static model as
            # wire_bytes_per_step, gated LOWER by telemetry/regress.py)
            rb = self._last_row_bytes or 0
            shards = wire.get("shards", 0)
            out["dcn_bytes_per_step"] = (
                wire["engine_cols_dcn"] * rb * shards
            )
            out["ici_bytes_per_step"] = (
                wire["engine_cols_ici"] * rb * shards
            )
        out["calls"] = self._call_index
        out["capacity"] = self.capacity
        out["out_capacity"] = self.out_capacity
        out["blocking_fetches"] = self._blocking_fetches
        out["unresolved_windows"] = bool(self._has_unresolved_windows())
        return out

    def flow(self, k: int = 5, update: bool = True) -> dict:
        """Per-link flow view of the LAST redistribute call
        (:mod:`~.telemetry.flow`): the ``[R, R]`` matrix (entry ``[i, j]``
        = rows rank ``i`` sent rank ``j``; row sums equal the per-rank
        send totals, column sums the receive totals), the cumulative
        matrix and population-imbalance gauge from this instance's
        :class:`~.telemetry.flow.FlowAccumulator`, and the ``k`` hottest
        off-diagonal links.

        ``update=True`` (default) folds the last stats into the gauge
        and journals a compact ``flow_snapshot`` event — call it at the
        same boundaries as :meth:`report` (this reads the stats pytree
        to the host; tiny, but a sync).
        """
        if self._last_stats is None:
            raise RuntimeError("flow() needs at least one redistribute() call")
        matrix = flow_lib.flow_matrix_of(self._last_stats)[-1]
        if update:
            self.flow_acc.update(self._last_stats)
            flow_lib.record_flow_snapshot(self.telemetry, self.flow_acc, k=k)
        return {
            "matrix": matrix,
            "cumulative": self.flow_acc.cumulative,
            "imbalance": self.flow_acc.imbalance,
            "hot_links": self.flow_acc.top_pairs(k=k),
            "snapshot": self.flow_acc.snapshot(k=k),
        }

    def health(self) -> dict:
        """Evaluate the always-on health rules
        (:class:`~.telemetry.health.HealthMonitor`) against this
        instance's journal: returns ``{"status": "OK"|"WARN"|"ALERT",
        "findings": [{rule, severity, reason}, ...]}``. New findings are
        journaled as ``alert`` events and fire any callbacks registered
        via ``rd.monitor.add_callback``. Host-side only — never syncs
        the device."""
        return self.monitor.evaluate()

    def metrics(self, render: bool = False):
        """The scrapable metrics plane over this instance's journal
        (:mod:`~.telemetry.metrics`): replays ``rd.telemetry`` into the
        standard grid metric families. Returns the
        :class:`~.telemetry.metrics.MetricsRegistry`; ``render=True``
        returns the OpenMetrics text instead (what
        ``scripts/metrics_serve.py`` serves on ``/metrics``). Counter
        families use the journal's all-time counts, so totals are exact
        even after ring eviction. Host-side only — never syncs the
        device."""
        reg = metrics_lib.from_journal(self.telemetry)
        return reg.render_openmetrics() if render else reg

    def to_perfetto(self, path: Optional[str] = None, **kwargs):
        """Export this instance's journal as Chrome-trace/Perfetto JSON
        (:mod:`~.telemetry.traceview`). With ``path`` the JSON is
        written there (returns the event count); without it the trace
        dict is returned. Extra kwargs (``step_seconds``) pass through
        to :func:`~.telemetry.traceview.to_chrome_trace`."""
        if path is not None:
            return traceview_lib.write_trace(
                path, self.telemetry, **kwargs
            )
        return traceview_lib.to_chrome_trace(self.telemetry, **kwargs)

    __call__ = redistribute


def redistribute(
    positions,
    *fields,
    domain: Domain,
    grid,
    count=None,
    backend: str = "jax",
    **kwargs,
) -> RedistributeResult:
    """One-shot functional form of :class:`GridRedistribute`."""
    rd = GridRedistribute(domain, grid, backend=backend, **kwargs)
    return rd.redistribute(positions, *fields, count=count)


def reshard(
    positions,
    *fields,
    domain: Domain,
    grid,
    n_local: int,
    backend: str = "numpy",
    telemetry=None,
    **kwargs,
) -> RedistributeResult:
    """Route UNPADDED live rows onto ``grid``'s owners in one canonical
    redistribute — the elastic-restart entry point (ROADMAP item 3).

    A snapshot written at R shards holds ``N`` live rows whose ownership
    is derived from *position*, not from the shard that wrote them, so
    re-decomposing onto an M-vrank grid is exactly one redistribute:
    chunk the ``[N, ndim]`` live rows contiguously over M input shards
    (any chunking works — the engine routes by position), then run the
    canonical exchange into the ``[M * n_local, ...]`` padded global
    layout. ``utils/checkpoint.py`` hints at this path ("load
    everything, then redistribute once"); :mod:`.service.elastic` wraps
    it for snapshot restores.

    ``fields`` ride the same permutation (e.g. velocities and the id
    column the service driver threads through for set-level restart
    audits). Rows are only permuted, never recomputed, so per-particle
    values are bit-identical across mesh shapes. Defaults to the numpy
    backend: restores run host-side on whatever process survived, and
    must not require the dead mesh to route the data off its shards.
    Overflow heals by growing (``on_overflow="grow"``) — a reshard must
    never drop rows, whatever the per-owner skew.
    """
    grid = grid if isinstance(grid, ProcessGrid) else ProcessGrid(grid)
    positions = np.asarray(positions)
    n = positions.shape[0]
    m = grid.nranks
    if int(n_local) < 1:
        raise ValueError(f"n_local must be >= 1, got {n_local}")
    in_rows = max(1, -(-n // m))  # ceil: every live row gets an input slot
    fields = tuple(np.asarray(f) for f in fields)
    pos_in = np.zeros((m * in_rows,) + positions.shape[1:], positions.dtype)
    pos_in[:n] = positions
    fields_in = []
    for f in fields:
        buf = np.zeros((m * in_rows,) + f.shape[1:], f.dtype)
        buf[:n] = f
        fields_in.append(buf)
    # contiguous chunking: input shard c's live rows are exactly rows
    # [c*in_rows, c*in_rows + count_in[c]) of the flat live array
    count_in = np.clip(
        n - in_rows * np.arange(m, dtype=np.int64), 0, in_rows
    ).astype(np.int32)
    rd = GridRedistribute(
        domain,
        grid,
        backend=backend,
        capacity=in_rows,
        out_capacity=int(n_local),
        on_overflow="grow",
        **kwargs,
    )
    if telemetry is not None:
        rd.telemetry = telemetry
    return rd.redistribute(pos_in, *fields_in, count=count_in)
