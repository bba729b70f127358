"""The sharded redistribute hot path (SURVEY.md §3.2, §7.3; C5, C6, C7).

Where the reference crosses the process boundary twice — ``comm.Alltoall``
for counts and ``comm.Alltoallv`` for payloads (SURVEY.md §3.2, [DRIVER]) —
this module runs the whole pipeline as one SPMD program under ``shard_map``
on a Cartesian device mesh:

    digitize -> segment_sum histogram -> stable sort-by-destination pack
    -> ``lax.all_to_all`` (counts) -> ``lax.all_to_all`` (payload pytree)
    -> stable compaction to Alltoallv receive order

Everything is static-shape (capacity-padded, SURVEY.md §7.6 "variable->fixed
size gap") so XLA compiles a single fused program per (N, capacity) bucket
and the collectives ride ICI. Overflow past capacity is counted and
returned in the stats pytree, never silent (SURVEY.md §5.3).
"""

from __future__ import annotations

import functools
from typing import NamedTuple, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.sharding import Mesh, PartitionSpec as P
from jax import shard_map

from mpi_grid_redistribute_tpu.domain import Domain, ProcessGrid
from mpi_grid_redistribute_tpu.ops import binning, pack
# rd:bin / rd:pack / rd:exchange / rd:unpack labels on the engine phases:
# a jax.named_scope lands in XLA op metadata, so Perfetto/XProf traces and
# HLO dumps group the pipeline by phase instead of op soup, and a trace
# reduction reads each phase's device time from the op's scope.
from mpi_grid_redistribute_tpu.telemetry.phases import traced_span


ENGINES = (
    "auto", "planar", "rowmajor", "sparse", "neighbor", "hierarchical"
)


def resolve_engine(
    engine: str,
    *,
    vranks: bool = False,
    n_devices: int = 1,
    planar_ok: bool = True,
    canonical: bool = False,
    n_pods: int = 1,
    recorder=None,
    planar_why: str = None,
    detail: dict = None,
) -> str:
    """Resolve a user-facing engine name to a concrete engine — the ONE
    dispatch rule shared by :class:`..api.Redistributer` (canonical
    exchange) and :func:`..models.nbody.make_migrate_loop` (resident-slot
    migrate loop), so the two surfaces cannot drift.

    Canonical exchange (``canonical=True``): ``"auto"`` picks the
    count-driven ``"sparse"`` engine on multi-device meshes (wire cost
    scales with movers — the paper's Alltoallv rationale) and
    ``"planar"`` on one device (no wire to shrink), degrading to
    ``"rowmajor"`` when the payload does not qualify for planar
    transport (``planar_ok`` — fields of 32- or 64-bit values that ride
    as int32 words; ``planar_why`` names the field that does not). The
    dense pool is reachable only via explicit ``engine="planar"`` or
    the sparse/neighbor engines' in-graph overflow fallback.
    ``"sparse"``/``"neighbor"`` are honored as asked (the neighbor
    engine is the static 3x3x3-stencil ``ppermute`` schedule).
    ``"hierarchical"`` is the two-level ICI/DCN schedule and needs a
    multi-pod mesh (``n_pods > 1``); on a flat mesh it degrades to the
    count-driven sparse engine (journaled) rather than erroring, and
    ``"auto"`` on a multi-pod multi-device mesh picks it over sparse.

    Migrate loop (``canonical=False``) returns ``"sparse"`` or
    ``"planar"``: ``"auto"``/``"sparse"`` pick the mover-sparse fast
    path exactly when the step is a single-device vrank step (``vranks``
    and ``n_devices == 1`` — see
    :func:`..parallel.migrate.shard_migrate_vranks_fn` for why
    cross-device steps stay dense); ``"rowmajor"`` and ``"neighbor"``
    have no migrate-loop meaning and raise.

    ``recorder`` (a :class:`..telemetry.StepRecorder`) journals the
    decision as an ``engine_resolved`` event — chosen engine plus the
    reason, including any degradation — so silent routing is observable;
    ``detail`` adds its keys to the event (the api's ``payload_words``).
    """
    if engine not in ENGINES:
        raise ValueError(
            f"engine must be one of {ENGINES}, got {engine!r}"
        )
    if canonical:
        if engine == "rowmajor":
            resolved, reason = "rowmajor", "explicit rowmajor"
        elif engine == "planar":
            resolved, reason = "planar", "explicit planar (dense pool)"
        elif engine == "neighbor":
            resolved, reason = "neighbor", "explicit neighbor stencil"
        elif engine == "sparse":
            resolved, reason = "sparse", "explicit count-driven sparse"
        elif engine == "hierarchical":
            if n_pods > 1:
                resolved, reason = (
                    "hierarchical", "explicit hierarchical two-level wire"
                )
            else:
                resolved, reason = (
                    "sparse",
                    "hierarchical -> sparse: flat mesh (no dcn domains)",
                )
        elif not planar_ok:
            resolved, reason = (
                "rowmajor", "auto: payload not planar-eligible"
                + (f" ({planar_why})" if planar_why else "")
            )
        elif n_devices > 1 and n_pods > 1:
            resolved, reason = (
                "hierarchical",
                "auto: multi-pod mesh -> hierarchical two-level wire",
            )
        elif n_devices > 1:
            resolved, reason = (
                "sparse", "auto: multi-device -> count-driven wire"
            )
        else:
            resolved, reason = (
                "planar", "auto: single device, no wire to shrink"
            )
    else:
        if engine in ("rowmajor", "neighbor", "hierarchical"):
            raise ValueError(
                f"engine={engine!r} is a canonical-exchange engine; the "
                "migrate loop accepts 'auto', 'sparse' or 'planar'"
            )
        if engine in ("auto", "sparse") and vranks and n_devices == 1:
            resolved, reason = "sparse", "migrate: single-device vranks"
        elif engine == "sparse":
            resolved, reason = (
                "planar",
                "sparse -> planar: cross-device migrate steps stay dense",
            )
        else:
            resolved, reason = "planar", "migrate: dense planar step"
    if recorder is not None:
        recorder.record(
            "engine_resolved",
            requested=engine,
            resolved=resolved,
            reason=reason,
            canonical=bool(canonical),
            **(detail or {}),
        )
    return resolved


class RedistributeStats(NamedTuple):
    """Per-step observability (SURVEY.md §5.5). Global (post-shard_map)
    shapes: ``send_counts`` is [R, R] indexed [source, dest];
    ``recv_counts`` is its transpose, [dest, source] (row r = what rank r
    received from each source); drop counters are [R].

    ``needed_capacity`` is the *measured* per-rank max unclipped remote
    per-destination count — the smallest per-pair ``capacity`` that would
    have sent everything (SURVEY.md §7.6 "measured capacity"); the
    adaptive-growth loop in :mod:`..api` sizes its rebuild from it; it is
    also the smallest ``mover_cap`` that would have kept the count-driven
    engines off their dense fallback.

    ``fallback`` ([R] int32, 1 where the shard's step took the in-graph
    dense fallback — mover overflow past ``mover_cap``, or out-of-stencil
    movers on the neighbor engine) is only emitted by the count-driven
    sparse/neighbor engines; it defaults to ``None`` (an EMPTY pytree
    node — zero leaves) so the dense engines' 5-leaf stats trees, their
    shard_map out_specs, and every consumer that never looks at it are
    untouched.

    ``pipeline`` ([R] int32, 1 where the step ran the software-pipelined
    steady-state branch — ISSUE 12) is only emitted by the pipelined
    resident engine and defaults to ``None`` the same way, so every
    existing 5/6-leaf stats tree is untouched.

    ``needed_cross`` ([R] int32, per-source max over destination PODS of
    the unclipped cross-pod mover total) is only emitted by the
    hierarchical two-level engine — the smallest ``cross_cap`` that
    would have carried every boundary-crossing row over the staged DCN
    hop without clipping; the adaptive-growth loop in :mod:`..api`
    ratchets its per-(pod,pod) block width from it. Defaults to ``None``
    (empty pytree node) like ``fallback``/``pipeline``."""

    send_counts: jax.Array
    recv_counts: jax.Array
    dropped_send: jax.Array
    dropped_recv: jax.Array
    needed_capacity: jax.Array
    fallback: jax.Array = None
    pipeline: jax.Array = None
    needed_cross: jax.Array = None


def shard_redistribute_fn(
    domain: Domain,
    grid: ProcessGrid,
    capacity: int,
    out_capacity: int,
    edges=None,
):
    """Build the per-shard function (runs under ``shard_map``).

    Signature of the returned fn: ``(pos[N,D], count[1] int32, *fields)`` ->
    ``(pos_out[out_capacity,D], count_out[1], fields_out..., stats)``.
    """
    R = grid.nranks
    axes = grid.axis_names

    def fn(pos, count, *fields):
        n = pos.shape[0]
        me = lax.axis_index(axes).astype(jnp.int32)
        iota = jnp.arange(n, dtype=jnp.int32)
        valid = iota < count[0]
        with traced_span("rd:bin"):
            dest = binning.rank_of_position(pos, domain, grid, edges=edges)
            dest = jnp.where(valid, dest, R).astype(jnp.int32)
            # Self-owned rows stay local (never hit the wire); the
            # sentinel R routes both invalid and self rows out of the
            # remote pack.
            is_self = valid & (dest == me)
            dest_remote = jnp.where(is_self, R, dest)
            # One stable sort yields both the pack permutation and the
            # per-destination counts (segment_sum histograms lower to a
            # slow scatter-add on TPU — binning.sorted_dest_counts).
            order, remote_counts, _ = binning.sorted_dest_counts(
                dest_remote, R
            )
        dropped_send = jnp.sum(jnp.maximum(remote_counts - capacity, 0))
        send_counts = jnp.minimum(remote_counts, capacity)

        arrays = (pos,) + tuple(fields)
        with traced_span("rd:pack"):
            packed = pack.pack_by_destination(
                dest_remote, remote_counts, arrays, capacity, order=order
            )
        with traced_span("rd:exchange"):
            recv_counts = lax.all_to_all(
                send_counts, axes, split_axis=0, concat_axis=0, tiled=True
            )
            recv = jax.tree.map(
                lambda a: lax.all_to_all(
                    a, axes, split_axis=0, concat_axis=0, tiled=True
                ),
                packed,
            )
        with traced_span("rd:unpack"):
            out, new_count, dropped_recv = pack.compact_with_self(
                recv, recv_counts, arrays, is_self, me, out_capacity
            )
        self_count = jnp.sum(is_self.astype(jnp.int32))
        self_onehot = (jnp.arange(R, dtype=jnp.int32) == me) * self_count
        stats = RedistributeStats(
            send_counts=(send_counts + self_onehot)[None, :],
            recv_counts=(recv_counts + self_onehot)[None, :],
            dropped_send=dropped_send[None].astype(jnp.int32),
            dropped_recv=dropped_recv[None],
            # remote_counts[me] is 0 (self rows carry the sentinel), so the
            # max is over genuine remote pairs.
            needed_capacity=jnp.max(remote_counts)[None].astype(jnp.int32),
        )
        return (out[0], new_count[None]) + tuple(out[1:]) + (stats,)

    return fn


def vrank_redistribute_fn(
    domain: Domain,
    grid: ProcessGrid,
    capacity: int,
    out_capacity: int,
    edges=None,
):
    """R-rank canonical exchange on ONE device (virtual ranks, vmapped).

    Semantically identical to :func:`shard_redistribute_fn` over an R-way
    mesh — same binning, same stable pack, same Alltoallv receive order,
    same capacity/overflow accounting — but the ranks are vmapped slabs on
    a single device and the ``lax.all_to_all`` becomes the transpose it
    would perform on the wire ([V_src, V_dst, C, ...] ->
    [V_dst, V_src, C, ...]). Bit-compatible with the oracle (tested), so a
    single chip can run — and honestly benchmark — the full canonical
    pipeline at any R (the TPU answer to ``mpirun -n R`` on one node;
    SURVEY.md §2 process-grid topology).

    Signature: ``(pos[V, n, D], count[V], *fields[V, n, ...]) ->
    (pos_out[V, out_capacity, D], count_out[V], fields_out..., stats)``.
    """
    V = grid.nranks

    def fn(pos, count, *fields):
        n = pos.shape[1]
        me_ids = jnp.arange(V, dtype=jnp.int32)

        def pack_one(pos_v, count_v, me, *fields_v):
            iota = jnp.arange(n, dtype=jnp.int32)
            valid = iota < count_v
            with traced_span("rd:bin"):
                dest = binning.rank_of_position(
                    pos_v, domain, grid, edges=edges
                )
                dest = jnp.where(valid, dest, V).astype(jnp.int32)
                is_self = valid & (dest == me)
                dest_remote = jnp.where(is_self, V, dest)
                order, remote_counts, _ = binning.sorted_dest_counts(
                    dest_remote, V
                )
            dropped_send = jnp.sum(jnp.maximum(remote_counts - capacity, 0))
            send_counts = jnp.minimum(remote_counts, capacity)
            with traced_span("rd:pack"):
                packed = pack.pack_by_destination(
                    dest_remote, remote_counts, (pos_v,) + tuple(fields_v),
                    capacity, order=order,
                )
            needed = jnp.max(remote_counts).astype(jnp.int32)
            return packed, send_counts, is_self, dropped_send, needed

        packed, send_counts, is_self, dropped_send, needed = jax.vmap(
            pack_one
        )(pos, count, me_ids, *fields)
        # the wire, as a transpose: [V_src, V_dst, C, ...] -> dst-major
        with traced_span("rd:exchange"):
            recv = jax.tree.map(lambda a: jnp.swapaxes(a, 0, 1), packed)
        recv_counts = send_counts.T  # [V_dst, V_src]

        def compact_one(recv_v, recv_counts_v, me, self_mask_v, pos_v,
                        *fields_v):
            return pack.compact_with_self(
                recv_v, recv_counts_v, (pos_v,) + tuple(fields_v),
                self_mask_v, me, out_capacity,
            )

        with traced_span("rd:unpack"):
            out, new_count, dropped_recv = jax.vmap(compact_one)(
                recv, recv_counts, me_ids, is_self, pos, *fields
            )
        self_count = jnp.sum(is_self.astype(jnp.int32), axis=1)
        self_diag = jnp.diag(self_count)
        stats = RedistributeStats(
            send_counts=send_counts + self_diag,
            recv_counts=recv_counts + self_diag,
            dropped_send=dropped_send.astype(jnp.int32),
            dropped_recv=dropped_recv,
            needed_capacity=needed,
        )
        return (out[0], new_count) + tuple(out[1:]) + (stats,)

    return fn


def vrank_redistribute_planar_fn(
    domain: Domain,
    grid: ProcessGrid,
    capacity: int,
    out_capacity: int,
    ndim: int = None,
    edges=None,
):
    """PLANAR canonical exchange: R virtual ranks on one device, ``[V, K, n]``.

    Same routing, same stable pack, same Alltoallv receive order, same
    capacity/overflow accounting as :func:`vrank_redistribute_fn` — but the
    payload is carried component-major (``K`` rows: ``D`` position
    components first, then any 32-bit fields, one row each), so no
    narrow-minor ``[n, 3]`` buffer exists anywhere. The row-major engine
    stores every such buffer in TPU's tiled T(8,128) layout (42.7x memory
    AND bandwidth for ``[n, 3]``) — measured as the canonical path's 7x
    per-row deficit vs the migrate engine (round-2 verdict item 4;
    BENCH_CONFIGS.md config 1). Routing is computed from the same wrap /
    digitize formulas (``binning.rank_of_position_planar``), so the output
    row SET and ORDER are bit-identical to the row-major engine and the
    oracle; only the storage layout differs.

    Signature: ``(fused[V, K, n], count[V]) ->
    (fused_out[V, K, out_capacity], count_out[V], stats)``; rows beyond
    ``count_out[v]`` are zero padding. Bitcast non-float32 fields on the
    way in/out (:func:`..migrate.fuse_fields` semantics, minus the alive
    row — validity here is the count prefix, as everywhere on the
    canonical path). ``fused`` may be float32 or int32; either way the
    TRANSPORT (pack, wire, landing) runs on an int32 bitcast
    view — TPU float vector copies flush denormal f32 bit patterns to
    zero (any bitcast int < 2^23; measured through the pack gather at
    ~3k rows/shard — the hazard ops/pallas_overlay.py biases around),
    while integer lanes have no FTZ semantics, so every 32-bit pattern
    (denormals, NaN payloads, -0.0) survives bit-exactly by
    construction. Output dtype matches the input.

    The rows ride the destination sort (``binning.sort_by_dest``), so
    each destination's slots are one window of them
    (``pack.pack_windows``). Each vrank's own rows are one window too,
    the head of the sentinel segment, and each source's rows the valid
    prefix of its pool window, so ``pack.land_windows`` copies the V
    windows into Alltoallv receive order, source by source, with no
    sort. This path serves every capacity: a key sort with gathers
    leaves the own rows scattered, so it needs the compaction sort over
    ``n + R * C`` columns, and at ``C = n / 64``, where its pack alone
    is cheaper, it took 108.7 ms a call against this path's 56.7 on a
    v5e (8 vranks x ``K = 8`` x ``2^20`` rows, PERF.md).
    """
    V = grid.nranks
    C = capacity
    D = domain.ndim if ndim is None else ndim

    def fn(fused, count):
        if fused.ndim != 3 or fused.shape[0] != V or fused.shape[1] < D:
            raise ValueError(
                f"fused must be [V={V}, K>={D}, n] (K rows: {D} position "
                f"components first, then 32-bit fields), got "
                f"{fused.shape}"
            )
        if fused.dtype not in (jnp.float32, jnp.int32):
            raise TypeError(
                f"fused must be float32 or int32, got {fused.dtype}"
            )
        as_f32 = fused.dtype == jnp.float32
        fi = (
            lax.bitcast_convert_type(fused, jnp.int32) if as_f32 else fused
        )
        pos_f = (
            fused[:, :D, :]
            if as_f32
            else lax.bitcast_convert_type(fi[:, :D, :], jnp.float32)
        )
        n = fused.shape[2]
        me_ids = jnp.arange(V, dtype=jnp.int32)

        def bin_one(pos_v, count_v, me, fi_v):
            valid = jnp.arange(n, dtype=jnp.int32) < count_v
            dest = binning.rank_of_position_planar(
                pos_v, domain, grid, edges=edges
            )
            dest = jnp.where(valid, dest, V).astype(jnp.int32)
            is_self = valid & (dest == me)
            dest_remote = jnp.where(is_self, V, dest)
            # the rows ride the destination sort: [K, n] in order
            rows, remote_counts, bounds = binning.sort_by_dest(
                dest_remote, V, fi_v
            )
            n_self = jnp.sum(is_self, dtype=jnp.int32)
            return rows, remote_counts, bounds, n_self

        # each phase's scope wraps its vmap, so the ops carry the scope
        # itself and not a vmap(...) of it
        with traced_span("rd:bin"):
            rows, remote_counts, bounds, self_count = jax.vmap(bin_one)(
                pos_f, count, me_ids, fi
            )
            dropped_send = jnp.sum(jnp.maximum(remote_counts - C, 0), axis=1)
            send_counts = jnp.minimum(remote_counts, C)
            needed = jnp.max(remote_counts, axis=1).astype(jnp.int32)
            recv_counts = send_counts.T  # [V_dst, V_src]
            self_diag = jnp.diag(self_count)
            send_stats = send_counts + self_diag
            recv_stats = recv_counts + self_diag
        with traced_span("rd:pack"):
            packed = jax.vmap(
                lambda rows_v, bounds_v, sc_v: pack.pack_windows(
                    rows_v, bounds_v[:V], sc_v, C
                )
            )(rows, bounds, send_counts)  # [V, K, V*C] int32
        K = fused.shape[1]
        # the wire, as a transpose: [V_src, K, V_dst, C] -> dst-major pools
        with traced_span("rd:exchange"):
            recv = (
                packed.reshape(V, K, V, C)
                .transpose(2, 1, 0, 3)
                .reshape(V, K, V * C)
            )
        with traced_span("rd:unpack"):
            out, new_count, dropped_recv = pack.land_windows(
                recv, recv_counts, rows, bounds[:, V], self_count,
                out_capacity,
            )
        if as_f32:
            out = lax.bitcast_convert_type(out, jnp.float32)
        stats = RedistributeStats(
            send_counts=send_stats,
            recv_counts=recv_stats,
            dropped_send=dropped_send.astype(jnp.int32),
            dropped_recv=dropped_recv,
            needed_capacity=needed,
        )
        return out, new_count, stats

    return fn


def _planar_shard_prefix(fused, count, domain, grid, D, edges, axes):
    """Shared per-shard routing prefix of the planar/sparse/neighbor
    multi-device engines: validate, bitcast to the int32 transport view,
    bin destinations, and derive the stable pack permutation + per-dest
    counts. Every multi-device planar-family engine runs EXACTLY this
    code, which is what makes the count-driven engines' routing (and the
    shared-prefix stats) bit-identical to the dense engine's by
    construction.

    Returns ``(as_f32, fi, n, me, is_self, order, remote_counts,
    bounds)``.
    """
    R = grid.nranks
    if fused.ndim != 2 or fused.shape[0] < D:
        raise ValueError(
            f"fused must be [K>={D}, n] per shard (K rows: {D} "
            f"position components first, then 32-bit fields), got "
            f"{fused.shape}"
        )
    if (
        fused.dtype not in (jnp.float32, jnp.int32)
        or np.dtype(fused.dtype).itemsize != 4
    ):
        raise TypeError(
            f"fused must be float32 or int32, got {fused.dtype}"
        )
    as_f32 = fused.dtype == jnp.float32
    fi = (
        lax.bitcast_convert_type(fused, jnp.int32) if as_f32 else fused
    )
    pos_f = (
        fused[:D]
        if as_f32
        else lax.bitcast_convert_type(fi[:D], jnp.float32)
    )
    n = fused.shape[1]
    me = lax.axis_index(axes).astype(jnp.int32)
    iota = jnp.arange(n, dtype=jnp.int32)
    valid = iota < count[0]
    with traced_span("rd:bin"):
        dest = binning.rank_of_position_planar(
            pos_f, domain, grid, edges=edges
        )
        dest = jnp.where(valid, dest, R).astype(jnp.int32)
        # Self-owned columns stay local (never hit the wire); sentinel
        # R routes both invalid and self columns out of the remote
        # pack.
        is_self = valid & (dest == me)
        dest_remote = jnp.where(is_self, R, dest)
        order, remote_counts, bounds = binning.sorted_dest_counts(
            dest_remote, R
        )
    return as_f32, fi, n, me, is_self, order, remote_counts, bounds


def shard_redistribute_planar_fn(
    domain: Domain,
    grid: ProcessGrid,
    capacity: int,
    out_capacity: int,
    ndim: int = None,
    edges=None,
):
    """PLANAR multi-device canonical exchange (runs under ``shard_map``).

    The shard_map twin of :func:`vrank_redistribute_planar_fn`: same
    routing (``binning.rank_of_position_planar``), the same send pool
    (filled here by the ``pack_cols`` gather, which the vrank engine
    keeps only for small capacities), same payload-carrying-sort
    compaction
    (``pack.planar_compact_with_self``), same capacity/overflow accounting
    — but the V-way transpose is a real ``lax.all_to_all`` over the mesh
    axes, riding ICI. The per-shard state is ``[K, n]`` component-major
    throughout: no narrow-minor ``[n, 3]`` buffer exists on either side of
    the wire (the row-major :func:`shard_redistribute_fn` gathers and
    exchanges ``[R, C, 3]`` buffers, every one stored in TPU's tiled
    T(8,128) layout at 42.7x the logical bytes — the measured 7x per-row
    deficit the planar engines remove, BENCH_CONFIGS.md config 1).

    Signature of the returned fn: ``(fused[K, n], count[1] int32) ->
    (fused_out[K, out_capacity], count_out[1], stats)``; columns beyond
    ``count_out`` are zero. 32-bit fields ride bitcast
    (:func:`..migrate.fuse_fields` semantics, minus the alive row).
    ``fused`` may be float32 or int32; the transport runs on an int32
    bitcast view either way (TPU denormal-flush hazard — see
    :func:`vrank_redistribute_planar_fn`); output dtype matches input.
    """
    R = grid.nranks
    C = capacity
    D = domain.ndim if ndim is None else ndim
    axes = grid.axis_names

    def fn(fused, count):
        as_f32, fi, n, me, is_self, order, remote_counts, bounds = (
            _planar_shard_prefix(fused, count, domain, grid, D, edges, axes)
        )
        dropped_send = jnp.sum(jnp.maximum(remote_counts - C, 0))
        send_counts = jnp.minimum(remote_counts, C)
        with traced_span("rd:pack"):
            packed, _ = pack.pack_cols(
                fi, order, bounds[:R], send_counts, R, C
            )  # [K, R*C] int32, dest-major slots
        with traced_span("rd:exchange"):
            recv_counts = lax.all_to_all(
                send_counts, axes, split_axis=0, concat_axis=0, tiled=True
            )
            # The wire: tiled all_to_all splits the lane axis into R
            # chunks of C columns (chunk d -> rank d) and concatenates
            # receives source-major — exactly the [K, R*C] dst-major pool
            # the vrank twin builds with its transpose.
            pool = lax.all_to_all(
                packed, axes, split_axis=1, concat_axis=1, tiled=True
            )
        with traced_span("rd:unpack"):
            out, new_count, dropped_recv = pack.planar_compact_with_self(
                pool, recv_counts, me, is_self, fi, out_capacity
            )
        if as_f32:
            out = lax.bitcast_convert_type(out, jnp.float32)
        self_count = jnp.sum(is_self.astype(jnp.int32))
        self_onehot = (jnp.arange(R, dtype=jnp.int32) == me) * self_count
        stats = RedistributeStats(
            send_counts=(send_counts + self_onehot)[None, :],
            recv_counts=(recv_counts + self_onehot)[None, :],
            dropped_send=dropped_send[None].astype(jnp.int32),
            dropped_recv=dropped_recv[None],
            needed_capacity=jnp.max(remote_counts)[None].astype(jnp.int32),
        )
        return out, new_count[None], stats

    return fn


def shard_redistribute_planar_sharded(
    mesh: Mesh,
    domain: Domain,
    grid: ProcessGrid,
    capacity: int,
    out_capacity: int,
    ndim: int = None,
    edges=None,
):
    """``shard_map``-wrapped (unjitted) planar exchange — composable under
    an outer jit (the public API fuses its field-bitcast boundary into the
    same program; see :mod:`..api`).

    Global layout: ``fused`` is ``[K, R * n_local]`` component-major,
    sharded on the LANE axis over all mesh axes (x-major, matching rank
    order — shard r owns columns ``[r * n_local, (r + 1) * n_local)``);
    ``count`` is ``[R]`` int32 with one entry per shard. Returns
    ``(fused_out [K, R * out_capacity], count_out [R], stats)``.
    """
    axes = grid.axis_names
    spec_f = P(None, axes)
    spec_c = P(axes)
    fn = shard_redistribute_planar_fn(
        domain, grid, capacity, out_capacity, ndim, edges=edges
    )
    # 5 explicit specs: `fallback` stays at its None default (an empty
    # pytree node) — the dense engine emits no fallback leaf.
    out_specs = (
        spec_f,
        spec_c,
        RedistributeStats(spec_c, spec_c, spec_c, spec_c, spec_c),
    )
    return shard_map(
        fn, mesh=mesh, in_specs=(spec_f, spec_c), out_specs=out_specs
    )


# gridlint: fastpath-engine
def _sparse_wire(fi, order, starts, counts, R, B, axes):
    """Count-driven wire schedule: pack ``[K, R*B]`` mover blocks through
    the precomputed pack plan and ``all_to_all`` them. O(movers) work
    only — no sorts, no iota-indexed takes (G006-checked region; the
    compaction sort lives outside, in the unpack phase)."""
    with traced_span("rd:pack"):
        packed, _ = pack.pack_cols(fi, order, starts, counts, R, B)
    with traced_span("rd:exchange"):
        return lax.all_to_all(
            packed, axes, split_axis=1, concat_axis=1, tiled=True
        )


# gridlint: fastpath-engine
def _neighbor_wire(fi, plan, slot_valid, axes, perms, n_act, B):
    """Neighbor stencil wire schedule: ONE plan-indexed gather of every
    outgoing mover column, then one static-perm ``lax.ppermute`` shift
    per active stencil offset — ``n_act`` point-to-point neighbor
    exchanges of ``[K, B]`` blocks instead of a dense ``[K, R*C]``
    ``all_to_all``. O(movers) work only — no sorts, no iota-indexed
    takes (G006-checked region)."""
    K = fi.shape[0]
    with traced_span("rd:pack"):
        send = jnp.where(slot_valid[None, :], pack.gather_plan_cols(fi, plan), 0)
    send = send.reshape(K, n_act, B)
    with traced_span("rd:exchange"):
        blocks = [
            lax.ppermute(send[:, o, :], axes, perm=list(perms[o]))
            for o in range(n_act)
        ]
    return jnp.concatenate(blocks, axis=1)


def _dense_pool_wire(fi, order, starts, counts, R, C, axes):
    """Dense ``[K, R*C]`` pool wire — the count-driven engines' in-graph
    fallback, byte-identical to :func:`shard_redistribute_planar_fn`'s
    exchange. Lives at module level so the cond branch functions stay
    free of lexical collectives (the same G001 discipline as
    migrate.py's dense fallback lambda)."""
    with traced_span("rd:pack"):
        packed, _ = pack.pack_cols(fi, order, starts, counts, R, C)
    with traced_span("rd:exchange"):
        return lax.all_to_all(
            packed, axes, split_axis=1, concat_axis=1, tiled=True
        )


def _check_mover_cap(mover_cap, capacity):
    B = int(mover_cap)
    if not 1 <= B < int(capacity):
        raise ValueError(
            f"mover_cap must be in [1, capacity); got mover_cap={B}, "
            f"capacity={capacity} — at mover_cap >= capacity the "
            f"count-driven pool is no smaller than the dense one, build "
            f"the planar engine instead"
        )
    return B


def _check_cross_cap(cross_cap):
    B2 = int(cross_cap)
    if B2 < 1:
        raise ValueError(
            f"cross_cap must be >= 1, got {B2} — it is the per-(pod,pod) "
            f"condensed DCN block width of the hierarchical engine"
        )
    return B2


def _dense_intra_wire(fi, plan, slot_valid, ici_axes):
    """Dense INTRA-POD pool wire — the hierarchical engine's in-graph
    fallback for the intra stage: a ``[K, L*C]`` per-local-dest pack and
    ONE ``all_to_all`` over the ICI axes only (tiled all_to_all over a
    subset of mesh axes runs independently per value of the remaining
    — dcn — axes, so no DCN byte moves here). Lives at module level so
    the cond branch functions stay free of lexical collectives (same
    G001 discipline as :func:`_dense_pool_wire`)."""
    with traced_span("rd:pack"):
        packed = jnp.where(
            slot_valid[None, :], pack.gather_plan_cols(fi, plan), 0
        )
    with traced_span("rd:exchange"):
        return lax.all_to_all(
            packed, ici_axes, split_axis=1, concat_axis=1, tiled=True
        )


def _hier_cross_stage(fi, order, bounds_r, prefix, eff, recv_counts, pme,
                      pod_of_j, rank_table_j, dcn_axes, ici_axes, n_pods,
                      L, B2, n):
    """The staged cross-pod schedule of the hierarchical engine — runs
    OUTSIDE the intra cond (cross rows always ride it; overflow past
    ``cross_cap`` is clipped and counted, never densified, so no DCN
    collective ever widens to a dense pool).

    For each pod distance ``delta`` in ``1..n_pods-1``:

    1. condense every row bound for pod ``(pme+delta) % n_pods`` into ONE
       ``[K, B2]`` block (dest-rank-ascending segments at the statically
       prefix-summed offsets — within a pod, rank-ascending ==
       pod-local-ascending, which step 3 relies on);
    2. one ``ppermute`` over the DCN axes shifts every pod's block (and
       its per-local-dest segment lengths) ``delta`` pods forward —
       this is the ONLY payload touching DCN;
    3. the mirror rank fans the arrived block out to final destinations
       by segmenting it with an exclusive cumsum of the arrived lengths
       and one tiled ``all_to_all`` over the ICI axes.

    Returns per-delta ``(pools [K, L*B2], source-rank keys [L*B2],
    valid [L*B2])`` lists for the shared compaction."""
    j_idx = jnp.arange(B2, dtype=jnp.int32)
    m_idx = jnp.repeat(jnp.arange(L, dtype=jnp.int32), B2)
    jj = jnp.tile(j_idx, L)
    pools, keys, valids = [], [], []
    with traced_span("rd:exchange"):
        for delta in range(1, n_pods):
            q_dst = (pme + delta) % n_pods
            to_q = pod_of_j == q_dst                   # [R] bool (cross)
            hit = (
                to_q[None, :]
                & (j_idx[:, None] >= prefix[None, :])
                & (j_idx[:, None] < (prefix + eff)[None, :])
            )                                          # [B2, R]
            src_col = jnp.sum(
                jnp.where(
                    hit,
                    bounds_r[None, :] + j_idx[:, None] - prefix[None, :],
                    0,
                ),
                axis=1,
            )
            slot_valid = jnp.any(hit, axis=1)
            plan = order[jnp.minimum(src_col, n - 1)]
            blk = jnp.where(
                slot_valid[None, :], pack.gather_plan_cols(fi, plan), 0
            )                                          # [K, B2]
            # my block's per-local-dest segment lengths in the target pod
            eff_loc = eff[rank_table_j[q_dst]]         # [L]
            perm_d = [(p, (p + delta) % n_pods) for p in range(n_pods)]
            mirror = lax.ppermute(blk, dcn_axes, perm=perm_d)
            cnt_loc = lax.ppermute(eff_loc, dcn_axes, perm=perm_d)
            start_loc = jnp.concatenate(
                [jnp.zeros((1,), cnt_loc.dtype), jnp.cumsum(cnt_loc)[:-1]]
            )
            fan_valid = jj < cnt_loc[m_idx]
            fan_col = jnp.minimum(start_loc[m_idx] + jj, B2 - 1)
            fan = jnp.where(fan_valid[None, :], mirror[:, fan_col], 0)
            pool = lax.all_to_all(
                fan, ici_axes, split_axis=1, concat_axis=1, tiled=True
            )                                          # [K, L*B2]
            # chunk s slot j arrived from (pod pme-delta, local s)
            src_ranks = rank_table_j[(pme - delta) % n_pods][m_idx]
            valid_r = jj < recv_counts[src_ranks]
            pools.append(pool)
            keys.append(src_ranks.astype(jnp.int32))
            valids.append(valid_r)
    return pools, keys, valids


def shard_redistribute_sparse_fn(
    domain: Domain,
    grid: ProcessGrid,
    capacity: int,
    out_capacity: int,
    mover_cap: int,
    ndim: int = None,
    edges=None,
    axes=None,
):
    """COUNT-DRIVEN multi-device canonical exchange (under ``shard_map``).

    Same routing prefix, same Alltoallv receive order, same
    capacity/overflow accounting as :func:`shard_redistribute_planar_fn`
    — but the exchanged pool is ``[K, R*mover_cap]`` instead of
    ``[K, R*capacity]``: per-step WIRE cost scales with movers, not
    residents (the paper's Alltoallv rationale, SURVEY.md §3.2). The
    counts ``all_to_all`` runs first (outside any branch); a globally
    ``pmin``-agreed guard — every per-pair mover count fits the block —
    then picks between the mover-block wire and a bit-identical dense
    fallback in ONE ``lax.cond`` (PR 4's dispatch contract: every device
    takes the same branch, so the branch-local collectives cannot
    deadlock). Both branches feed the same payload-sort compaction with
    identical valid slots in identical (source, slot) order, so the
    output is byte-identical either way; ``stats.fallback`` reports
    which branch ran, and ``stats.needed_capacity`` is exactly the
    smallest ``mover_cap`` that would have kept the fast branch.

    NOTE the compaction itself still touches every resident column (the
    canonical output contract forces a full re-pack); it is the WIRE —
    the pool riding ICI — that shrinks from residents to movers.

    ``axes`` overrides the mesh axes the collectives run over (default:
    the grid's own axis names). A :class:`..mesh.HierarchicalMesh`'s
    expanded interleaved axes keep row-major flat index == grid rank, so
    running this engine over them is bit-identical to the flat mesh —
    used by the shardcheck S004 comparison program to bill the flat
    sparse wire's cross-pod bytes to the DCN domain.
    """
    R = grid.nranks
    C = capacity
    B = _check_mover_cap(mover_cap, capacity)
    D = domain.ndim if ndim is None else ndim
    axes = grid.axis_names if axes is None else tuple(axes)

    def fn(fused, count):
        as_f32, fi, n, me, is_self, order, remote_counts, bounds = (
            _planar_shard_prefix(fused, count, domain, grid, D, edges, axes)
        )
        dropped_send = jnp.sum(jnp.maximum(remote_counts - C, 0))
        send_counts = jnp.minimum(remote_counts, C)
        with traced_span("rd:exchange"):
            recv_counts = lax.all_to_all(
                send_counts, axes, split_axis=0, concat_axis=0, tiled=True
            )
        # Globally-agreed dispatch: pmin of the local fit so every device
        # takes the SAME cond branch (a disagreeing branch would strand
        # the branch-local collectives — see migrate.py's dispatch note).
        ok = (jnp.max(remote_counts) <= B).astype(jnp.int32)
        guard = lax.pmin(ok, axes)

        def _count_driven(_):
            pool = _sparse_wire(
                fi, order, bounds[:R], jnp.minimum(send_counts, B), R, B,
                axes,
            )
            with traced_span("rd:unpack"):
                return pack.planar_compact_with_self(
                    pool, recv_counts, me, is_self, fi, out_capacity
                )

        def _dense(_):
            pool = _dense_pool_wire(
                fi, order, bounds[:R], send_counts, R, C, axes
            )
            with traced_span("rd:unpack"):
                return pack.planar_compact_with_self(
                    pool, recv_counts, me, is_self, fi, out_capacity
                )

        out, new_count, dropped_recv = lax.cond(
            guard == 1, _count_driven, _dense, operand=None
        )
        if as_f32:
            out = lax.bitcast_convert_type(out, jnp.float32)
        self_count = jnp.sum(is_self.astype(jnp.int32))
        self_onehot = (jnp.arange(R, dtype=jnp.int32) == me) * self_count
        stats = RedistributeStats(
            send_counts=(send_counts + self_onehot)[None, :],
            recv_counts=(recv_counts + self_onehot)[None, :],
            dropped_send=dropped_send[None].astype(jnp.int32),
            dropped_recv=dropped_recv[None],
            needed_capacity=jnp.max(remote_counts)[None].astype(jnp.int32),
            fallback=(1 - guard)[None].astype(jnp.int32),
        )
        return out, new_count[None], stats

    return fn


def shard_redistribute_neighbor_fn(
    domain: Domain,
    grid: ProcessGrid,
    capacity: int,
    out_capacity: int,
    mover_cap: int,
    ndim: int = None,
    edges=None,
    axes=None,
):
    """NEIGHBOR-STENCIL multi-device canonical exchange (``shard_map``).

    Stage B of the count-driven wire: at drift-scale migration the flow
    matrix is near-neighbor-banded on a Cartesian grid, so the dense
    ``all_to_all`` is replaced by a static Moore-stencil ``ppermute``
    shift schedule (:func:`..mesh.neighbor_tables` — ≤26 neighbor
    exchanges of ``[K, mover_cap]`` blocks in 3D). The guard extends the
    sparse engine's mover-fit check with stencil membership: any mover
    bound beyond the 3x3x3 stencil flips the whole (globally
    ``pmin``-agreed) step onto the bit-identical dense fallback, journaled
    via ``stats.fallback``. Same routing prefix, same compaction ordering
    (the receive keys feed :func:`..ops.pack.planar_compact_keys` with
    the same source-major order), so output is byte-identical to
    :func:`shard_redistribute_planar_fn` on every step.
    """
    from mpi_grid_redistribute_tpu.parallel import mesh as mesh_lib

    R = grid.nranks
    C = capacity
    B = _check_mover_cap(mover_cap, capacity)
    D = domain.ndim if ndim is None else ndim
    axes = grid.axis_names if axes is None else tuple(axes)
    periodic = tuple(bool(p) for p in domain.periodic)
    _, dst_t, src_t, member = mesh_lib.neighbor_tables(grid, periodic)
    perms_all = mesh_lib.neighbor_perms(grid, periodic)
    active = tuple(o for o in range(dst_t.shape[1]) if perms_all[o])
    if not active:
        raise ValueError(
            f"neighbor engine needs a grid with at least one neighbor "
            f"link, got shape {grid.shape}"
        )
    n_act = len(active)
    perms = tuple(perms_all[o] for o in active)
    dst_j = jnp.asarray(dst_t[:, active])        # [R, n_act]
    src_j = jnp.asarray(src_t[:, active])        # [R, n_act]
    member_j = jnp.asarray(member)               # [R, R] bool

    def fn(fused, count):
        as_f32, fi, n, me, is_self, order, remote_counts, bounds = (
            _planar_shard_prefix(fused, count, domain, grid, D, edges, axes)
        )
        dropped_send = jnp.sum(jnp.maximum(remote_counts - C, 0))
        send_counts = jnp.minimum(remote_counts, C)
        with traced_span("rd:exchange"):
            recv_counts = lax.all_to_all(
                send_counts, axes, split_axis=0, concat_axis=0, tiled=True
            )
        member_row = jnp.take(member_j, me, axis=0)  # [R] bool
        # in-stencil movers must fit the block; out-of-stencil pairs must
        # be EMPTY (the schedule has no route for them)
        ok = jnp.all(
            jnp.where(member_row, remote_counts <= B, remote_counts == 0)
        ).astype(jnp.int32)
        guard = lax.pmin(ok, axes)

        def _stencil(_):
            d_o = jnp.take(dst_j, me, axis=0)          # [n_act]
            d_safe = jnp.where(d_o >= 0, d_o, 0)
            sc_b = jnp.minimum(send_counts, B)
            cnt = jnp.where(d_o >= 0, sc_b[d_safe], 0)  # [n_act]
            c_idx = jnp.arange(B, dtype=jnp.int32)
            flat_c = jnp.tile(c_idx, n_act)
            off_i = jnp.repeat(jnp.arange(n_act, dtype=jnp.int32), B)
            slot_valid = flat_c < cnt[off_i]
            src_cols = jnp.minimum(bounds[d_safe][off_i] + flat_c, n - 1)
            plan = order[src_cols]
            pool = _neighbor_wire(fi, plan, slot_valid, axes, perms,
                                  n_act, B)
            # receive keys: block o arrived from src_j[me, o]; under the
            # guard every source occupies exactly ONE block (the dedup in
            # neighbor_tables), so (source, slot-iota) ordering matches
            # the dense pool's — byte-identical compaction.
            s_o = jnp.take(src_j, me, axis=0)          # [n_act]
            s_safe = jnp.where(s_o >= 0, s_o, 0)
            rc = jnp.where(s_o >= 0, recv_counts[s_safe], 0)
            valid_r = flat_c < rc[off_i]
            invalid = ~jnp.concatenate([valid_r, is_self])
            source_key = jnp.concatenate(
                [s_safe[off_i], jnp.broadcast_to(me, (n,))]
            ).astype(jnp.int32)
            values = jnp.concatenate([pool, fi], axis=1)
            new_full = (
                jnp.sum(recv_counts) + jnp.sum(is_self.astype(jnp.int32))
            )
            with traced_span("rd:unpack"):
                return pack.planar_compact_keys(
                    values, invalid, source_key, R, new_full, out_capacity
                )

        def _dense(_):
            pool = _dense_pool_wire(
                fi, order, bounds[:R], send_counts, R, C, axes
            )
            with traced_span("rd:unpack"):
                return pack.planar_compact_with_self(
                    pool, recv_counts, me, is_self, fi, out_capacity
                )

        out, new_count, dropped_recv = lax.cond(
            guard == 1, _stencil, _dense, operand=None
        )
        if as_f32:
            out = lax.bitcast_convert_type(out, jnp.float32)
        self_count = jnp.sum(is_self.astype(jnp.int32))
        self_onehot = (jnp.arange(R, dtype=jnp.int32) == me) * self_count
        stats = RedistributeStats(
            send_counts=(send_counts + self_onehot)[None, :],
            recv_counts=(recv_counts + self_onehot)[None, :],
            dropped_send=dropped_send[None].astype(jnp.int32),
            dropped_recv=dropped_recv[None],
            needed_capacity=jnp.max(remote_counts)[None].astype(jnp.int32),
            fallback=(1 - guard)[None].astype(jnp.int32),
        )
        return out, new_count[None], stats

    return fn


def _validate_planar_vranks(fused, V, D):
    if fused.ndim != 3 or fused.shape[0] != V or fused.shape[1] < D:
        raise ValueError(
            f"fused must be [V={V}, K>={D}, n] (K rows: {D} position "
            f"components first, then 32-bit fields), got "
            f"{fused.shape}"
        )
    if (
        fused.dtype not in (jnp.float32, jnp.int32)
        or np.dtype(fused.dtype).itemsize != 4
    ):
        raise TypeError(
            f"fused must be float32 or int32, got {fused.dtype}"
        )
    as_f32 = fused.dtype == jnp.float32
    fi = (
        lax.bitcast_convert_type(fused, jnp.int32) if as_f32 else fused
    )
    pos_f = (
        fused[:, :D, :]
        if as_f32
        else lax.bitcast_convert_type(fi[:, :D, :], jnp.float32)
    )
    return as_f32, fi, pos_f


def _vrank_sparse_prefix(fi, pos_f, count, domain, grid, edges, n):
    """Vmapped routing prefix of the vrank count-driven engines — the
    same per-vrank binning and destination order as
    :func:`vrank_redistribute_planar_fn`'s ``bin_one`` (here the key
    sort alone, for ``pack_cols``), split from the pack so both cond
    branches (mover-block and dense widths) can share one plan."""
    V = grid.nranks
    me_ids = jnp.arange(V, dtype=jnp.int32)

    def prefix_one(fi_v, pos_v, count_v, me):
        iota = jnp.arange(n, dtype=jnp.int32)
        valid = iota < count_v
        with traced_span("rd:bin"):
            dest = binning.rank_of_position_planar(
                pos_v, domain, grid, edges=edges
            )
            dest = jnp.where(valid, dest, V).astype(jnp.int32)
            is_self = valid & (dest == me)
            dest_remote = jnp.where(is_self, V, dest)
            order, remote_counts, bounds = binning.sorted_dest_counts(
                dest_remote, V
            )
        return is_self, order, remote_counts, bounds

    is_self, order, remote_counts, bounds = jax.vmap(prefix_one)(
        fi, pos_f, count, me_ids
    )
    return me_ids, is_self, order, remote_counts, bounds


def vrank_redistribute_sparse_fn(
    domain: Domain,
    grid: ProcessGrid,
    capacity: int,
    out_capacity: int,
    mover_cap: int,
    ndim: int = None,
    edges=None,
):
    """COUNT-DRIVEN canonical exchange, vrank twin: the HBM-side "wire"
    (the ``[V_src, K, V_dst, W]`` transpose) shrinks from ``W=capacity``
    to ``W=mover_cap`` under the same globally-agreed one-``lax.cond``
    guard as :func:`shard_redistribute_sparse_fn`; overflow falls back to
    the bit-identical dense transpose. Lets a single chip run — and
    honestly benchmark — the count-driven schedule at any R.
    """
    V = grid.nranks
    C = capacity
    B = _check_mover_cap(mover_cap, capacity)
    D = domain.ndim if ndim is None else ndim

    def fn(fused, count):
        as_f32, fi, pos_f = _validate_planar_vranks(fused, V, D)
        n = fused.shape[2]
        K = fused.shape[1]
        me_ids, is_self, order, remote_counts, bounds = (
            _vrank_sparse_prefix(fi, pos_f, count, domain, grid, edges, n)
        )
        dropped_send = jnp.sum(jnp.maximum(remote_counts - C, 0), axis=1)
        send_counts = jnp.minimum(remote_counts, C)
        recv_counts = send_counts.T
        needed = jnp.max(remote_counts, axis=1).astype(jnp.int32)
        guard = jnp.max(remote_counts) <= B

        def _tail(W):
            def pack_one(fi_v, order_v, bounds_v, sc_v):
                with traced_span("rd:pack"):
                    packed, _ = pack.pack_cols(
                        fi_v, order_v, bounds_v[:V],
                        jnp.minimum(sc_v, W), V, W,
                    )
                return packed

            packed = jax.vmap(pack_one)(fi, order, bounds, send_counts)
            with traced_span("rd:exchange"):
                pool = (
                    packed.reshape(V, K, V, W)
                    .transpose(2, 1, 0, 3)
                    .reshape(V, K, V * W)
                )

            def compact_one(pool_v, rcnt_v, me, self_v, fi_v):
                return pack.planar_compact_with_self(
                    pool_v, rcnt_v, me, self_v, fi_v, out_capacity
                )

            with traced_span("rd:unpack"):
                return jax.vmap(compact_one)(
                    pool, recv_counts, me_ids, is_self, fi
                )

        out, new_count, dropped_recv = lax.cond(
            guard, lambda _: _tail(B), lambda _: _tail(C), operand=None
        )
        if as_f32:
            out = lax.bitcast_convert_type(out, jnp.float32)
        self_count = jnp.sum(is_self.astype(jnp.int32), axis=1)
        self_diag = jnp.diag(self_count)
        stats = RedistributeStats(
            send_counts=send_counts + self_diag,
            recv_counts=recv_counts + self_diag,
            dropped_send=dropped_send.astype(jnp.int32),
            dropped_recv=dropped_recv,
            needed_capacity=needed,
            fallback=jnp.broadcast_to(
                (~guard).astype(jnp.int32), (V,)
            ),
        )
        return out, new_count, stats

    return fn


def vrank_redistribute_neighbor_fn(
    domain: Domain,
    grid: ProcessGrid,
    capacity: int,
    out_capacity: int,
    mover_cap: int,
    ndim: int = None,
    edges=None,
):
    """NEIGHBOR-STENCIL canonical exchange, vrank twin: the per-offset
    ``ppermute`` shifts become static cross-vrank block gathers through
    the same :func:`..mesh.neighbor_tables` the sharded engine ships, so
    one chip exercises the exact stencil schedule (guard, fallback, block
    order) the pod runs — bit-identical to the planar vrank engine.
    """
    from mpi_grid_redistribute_tpu.parallel import mesh as mesh_lib
    import numpy as np

    V = grid.nranks
    C = capacity
    B = _check_mover_cap(mover_cap, capacity)
    D = domain.ndim if ndim is None else ndim
    periodic = tuple(bool(p) for p in domain.periodic)
    _, dst_t, src_t, member = mesh_lib.neighbor_tables(grid, periodic)
    perms_all = mesh_lib.neighbor_perms(grid, periodic)
    active = tuple(o for o in range(dst_t.shape[1]) if perms_all[o])
    if not active:
        raise ValueError(
            f"neighbor engine needs a grid with at least one neighbor "
            f"link, got shape {grid.shape}"
        )
    n_act = len(active)
    dst_act = dst_t[:, active]                    # np [V, n_act]
    src_act = src_t[:, active]                    # np [V, n_act]
    d_valid = jnp.asarray(dst_act >= 0)
    d_safe = jnp.asarray(np.where(dst_act >= 0, dst_act, 0))
    s_valid = jnp.asarray(src_act >= 0)
    s_safe = jnp.asarray(np.where(src_act >= 0, src_act, 0))
    member_j = jnp.asarray(member)                # [V, V] bool

    def fn(fused, count):
        as_f32, fi, pos_f = _validate_planar_vranks(fused, V, D)
        n = fused.shape[2]
        K = fused.shape[1]
        me_ids, is_self, order, remote_counts, bounds = (
            _vrank_sparse_prefix(fi, pos_f, count, domain, grid, edges, n)
        )
        dropped_send = jnp.sum(jnp.maximum(remote_counts - C, 0), axis=1)
        send_counts = jnp.minimum(remote_counts, C)
        recv_counts = send_counts.T
        needed = jnp.max(remote_counts, axis=1).astype(jnp.int32)
        guard = jnp.all(
            jnp.where(member_j, remote_counts <= B, remote_counts == 0)
        )

        def _stencil(_):
            sc_b = jnp.minimum(send_counts, B)
            cnt = jnp.where(
                d_valid, jnp.take_along_axis(sc_b, d_safe, axis=1), 0
            )                                      # [V, n_act]
            base = jnp.take_along_axis(bounds, d_safe, axis=1)
            c_idx = jnp.arange(B, dtype=jnp.int32)
            slot_valid = (
                c_idx[None, None, :] < cnt[:, :, None]
            ).reshape(V, n_act * B)
            src_cols = jnp.minimum(
                base[:, :, None] + c_idx[None, None, :], n - 1
            ).reshape(V, n_act * B)
            plan = jnp.take_along_axis(order, src_cols, axis=1)
            with traced_span("rd:pack"):
                send = jax.vmap(pack.gather_plan_cols)(fi, plan)
                send = jnp.where(slot_valid[:, None, :], send, 0)
            blocks = send.reshape(V, K, n_act, B)
            with traced_span("rd:exchange"):
                # block o at vrank v came from src_act[v, o] — the static
                # cross-vrank gather the sharded twin does with one
                # ppermute per offset
                recv = blocks[
                    s_safe, :, jnp.arange(n_act)[None, :], :
                ]                                  # [V, n_act, K, B]
                pool = recv.transpose(0, 2, 1, 3).reshape(V, K, n_act * B)
            rc = jnp.where(
                s_valid, jnp.take_along_axis(recv_counts, s_safe, axis=1),
                0,
            )                                      # [V, n_act]
            valid_r = (
                c_idx[None, None, :] < rc[:, :, None]
            ).reshape(V, n_act * B)
            invalid = ~jnp.concatenate([valid_r, is_self], axis=1)
            source_key = jnp.concatenate(
                [
                    jnp.broadcast_to(
                        s_safe[:, :, None], (V, n_act, B)
                    ).reshape(V, n_act * B),
                    jnp.broadcast_to(me_ids[:, None], (V, n)),
                ],
                axis=1,
            ).astype(jnp.int32)
            values = jnp.concatenate([pool, fi], axis=2)
            new_full = jnp.sum(recv_counts, axis=1) + jnp.sum(
                is_self.astype(jnp.int32), axis=1
            )

            def compact_one(vals_v, inv_v, sk_v, nf_v):
                return pack.planar_compact_keys(
                    vals_v, inv_v, sk_v, V, nf_v, out_capacity
                )

            with traced_span("rd:unpack"):
                return jax.vmap(compact_one)(
                    values, invalid, source_key, new_full
                )

        def _dense(_):
            def pack_one(fi_v, order_v, bounds_v, sc_v):
                with traced_span("rd:pack"):
                    packed, _ = pack.pack_cols(
                        fi_v, order_v, bounds_v[:V], sc_v, V, C
                    )
                return packed

            packed = jax.vmap(pack_one)(fi, order, bounds, send_counts)
            with traced_span("rd:exchange"):
                pool = (
                    packed.reshape(V, K, V, C)
                    .transpose(2, 1, 0, 3)
                    .reshape(V, K, V * C)
                )

            def compact_one(pool_v, rcnt_v, me, self_v, fi_v):
                return pack.planar_compact_with_self(
                    pool_v, rcnt_v, me, self_v, fi_v, out_capacity
                )

            with traced_span("rd:unpack"):
                return jax.vmap(compact_one)(
                    pool, recv_counts, me_ids, is_self, fi
                )

        out, new_count, dropped_recv = lax.cond(
            guard, _stencil, _dense, operand=None
        )
        if as_f32:
            out = lax.bitcast_convert_type(out, jnp.float32)
        self_count = jnp.sum(is_self.astype(jnp.int32), axis=1)
        self_diag = jnp.diag(self_count)
        stats = RedistributeStats(
            send_counts=send_counts + self_diag,
            recv_counts=recv_counts + self_diag,
            dropped_send=dropped_send.astype(jnp.int32),
            dropped_recv=dropped_recv,
            needed_capacity=needed,
            fallback=jnp.broadcast_to(
                (~guard).astype(jnp.int32), (V,)
            ),
        )
        return out, new_count, stats

    return fn


def shard_redistribute_hierarchical_fn(
    domain: Domain,
    grid: ProcessGrid,
    hier,
    capacity: int,
    out_capacity: int,
    mover_cap: int,
    cross_cap: int,
    ndim: int = None,
    edges=None,
):
    """HIERARCHICAL two-level canonical exchange (``shard_map`` over the
    expanded ICI/DCN mesh of a :class:`..mesh.HierarchicalMesh`).

    Two independent wire stages replace the flat schedule (ROADMAP item
    2 — "ICI inside, DCN across"):

    * **intra-pod**: rows whose destination stays inside the sender's
      ICI domain ride the existing Moore-stencil ``ppermute`` schedule
      unchanged, over the POD-LOCAL :func:`..mesh.neighbor_tables` and
      the ICI axes only (a ``ppermute`` over a subset of mesh axes runs
      independently per pod). Out-of-stencil or over-``mover_cap``
      same-pod movers flip the (globally ``pmin``-agreed) intra stage
      onto a bit-identical dense INTRA-POD pool — still ICI-only, so
      the fallback never widens a DCN collective;
    * **cross-pod** (:func:`_hier_cross_stage`): boundary-crossing rows
      are condensed into ONE ``[K, cross_cap]`` block per destination
      pod, shifted by a single staged DCN ``ppermute`` per (pod, pod)
      distance, then fanned out to final ranks by a second intra-pod
      hop — DCN carries mover-count-driven bytes instead of dense
      fan-out. Overflow past ``cross_cap`` is clipped and counted
      (``dropped_send`` + ``stats.needed_cross``), and the adaptive
      loop in :mod:`..api` regrows ``cross_cap``, exactly like the
      ``capacity`` ratchet — there is deliberately NO dense cross-pod
      fallback in-graph.

    Both stages feed the same payload-sort compaction
    (:func:`..ops.pack.planar_compact_keys`) with per-source keys in
    within-source pack order, so the output is byte-identical to
    :func:`shard_redistribute_planar_fn` on every non-overflowing step.

    The expanded mesh interleaves ``dcn_<name>`` axes so row-major flat
    index == grid rank (see :class:`..mesh.HierarchicalMesh`); the
    counts ``all_to_all`` over ALL expanded axes is therefore
    bit-identical to the flat engines' and stats keep rank order.
    """
    from mpi_grid_redistribute_tpu.parallel import mesh as mesh_lib

    R = grid.nranks
    C = capacity
    B = _check_mover_cap(mover_cap, capacity)
    B2 = _check_cross_cap(cross_cap)
    D = domain.ndim if ndim is None else ndim
    if hier.grid != grid:
        raise ValueError(
            f"hierarchical mesh wraps grid {hier.grid.shape}, engine "
            f"built for {grid.shape}"
        )
    n_pods = hier.n_pods
    if n_pods < 2:
        raise ValueError(
            "hierarchical engine needs a multi-pod mesh (n_pods >= 2); "
            "resolve_engine degrades flat meshes to the sparse engine"
        )
    L = hier.pod_size
    axes_all = hier.axis_names
    ici_axes = hier.ici_axes
    dcn_axes = hier.dcn_axes
    periodic_local = hier.local_periodic(domain.periodic)
    _, dstL_t, srcL_t, memberL = mesh_lib.neighbor_tables(
        hier.local_grid, periodic_local
    )
    permsL_all = mesh_lib.neighbor_perms(hier.local_grid, periodic_local)
    activeL = tuple(o for o in range(dstL_t.shape[1]) if permsL_all[o])
    n_actL = len(activeL)
    permsL = tuple(permsL_all[o] for o in activeL)
    dstL_j = jnp.asarray(dstL_t[:, activeL].reshape(L, n_actL))
    srcL_j = jnp.asarray(srcL_t[:, activeL].reshape(L, n_actL))
    memberL_j = jnp.asarray(memberL)                 # [L, L] bool
    pod_of_j = jnp.asarray(hier.pod_of)              # [R]
    local_of_j = jnp.asarray(hier.local_of)          # [R]
    rank_table_j = jnp.asarray(hier.rank_table)      # [n_pods, L]
    same_np = hier.pod_of[:, None] == hier.pod_of[None, :]
    # prefix matrix: M[d', d] = 1 iff d' < d and same destination pod —
    # the condensed block's segment offsets in one matvec
    M_j = jnp.asarray(
        (
            (np.arange(R)[:, None] < np.arange(R)[None, :]) & same_np
        ).astype(np.int32)
    )
    pod_onehot_j = jnp.asarray(
        (hier.pod_of[None, :] == np.arange(n_pods)[:, None]).astype(
            np.int32
        )
    )                                                # [n_pods, R]

    def fn(fused, count):
        as_f32, fi, n, me, is_self, order, remote_counts, bounds = (
            _planar_shard_prefix(
                fused, count, domain, grid, D, edges, axes_all
            )
        )
        K = fi.shape[0]
        pme = lax.axis_index(dcn_axes).astype(jnp.int32)   # pod id
        lme = lax.axis_index(ici_axes).astype(jnp.int32)   # pod-local
        same_pod = pod_of_j == pme
        cross_mask = ~same_pod
        sc = jnp.minimum(remote_counts, C)
        sc_cross = jnp.where(cross_mask, sc, 0)
        prefix = sc_cross @ M_j                      # [R] block offsets
        eff = jnp.where(
            cross_mask, jnp.clip(B2 - prefix, 0, sc), sc
        ).astype(jnp.int32)
        dropped_send = jnp.sum(remote_counts - eff)
        send_counts = eff
        with traced_span("rd:exchange"):
            recv_counts = lax.all_to_all(
                send_counts, axes_all, split_axis=0, concat_axis=0,
                tiled=True,
            )
        needed_cross = jnp.max(pod_onehot_j @ sc_cross).astype(jnp.int32)

        cross_pools, cross_keys, cross_valid = _hier_cross_stage(
            fi, order, bounds[:R], prefix, eff, recv_counts, pme,
            pod_of_j, rank_table_j, dcn_axes, ici_axes, n_pods, L, B2, n,
        )

        # intra guard: same-pod movers must fit the pod-local stencil
        # blocks; cross rows never enter this cond (clip-and-count).
        member_row = memberL_j[lme][local_of_j]      # [R] bool
        ok = jnp.all(
            jnp.where(
                same_pod,
                jnp.where(
                    member_row, remote_counts <= B, remote_counts == 0
                ),
                True,
            )
        ).astype(jnp.int32)
        guard = lax.pmin(ok, axes_all)

        def _finish(pool, valid_r, srckeys):
            invalid = ~jnp.concatenate([valid_r] + cross_valid + [is_self])
            source_key = jnp.concatenate(
                [srckeys] + cross_keys + [jnp.broadcast_to(me, (n,))]
            ).astype(jnp.int32)
            values = jnp.concatenate([pool] + cross_pools + [fi], axis=1)
            new_full = (
                jnp.sum(recv_counts) + jnp.sum(is_self.astype(jnp.int32))
            )
            with traced_span("rd:unpack"):
                return pack.planar_compact_keys(
                    values, invalid, source_key, R, new_full, out_capacity
                )

        def _stencil(_):
            if n_actL == 0:
                # one-rank pods: no intra links, nothing same-pod to wire
                pool = jnp.zeros((K, 0), jnp.int32)
                valid_r = jnp.zeros((0,), bool)
                srckeys = jnp.zeros((0,), jnp.int32)
                return _finish(pool, valid_r, srckeys)
            d_o = jnp.take(dstL_j, lme, axis=0)      # [n_actL] local ids
            d_safe = jnp.where(d_o >= 0, d_o, 0)
            d_glob = rank_table_j[pme, d_safe]       # [n_actL]
            sc_b = jnp.minimum(sc, B)
            cnt = jnp.where(d_o >= 0, sc_b[d_glob], 0)
            c_idx = jnp.arange(B, dtype=jnp.int32)
            flat_c = jnp.tile(c_idx, n_actL)
            off_i = jnp.repeat(jnp.arange(n_actL, dtype=jnp.int32), B)
            slot_valid = flat_c < cnt[off_i]
            src_cols = jnp.minimum(bounds[d_glob][off_i] + flat_c, n - 1)
            plan = order[src_cols]
            pool = _neighbor_wire(
                fi, plan, slot_valid, ici_axes, permsL, n_actL, B
            )
            s_o = jnp.take(srcL_j, lme, axis=0)      # [n_actL]
            s_safe = jnp.where(s_o >= 0, s_o, 0)
            s_glob = rank_table_j[pme, s_safe]
            rc = jnp.where(s_o >= 0, recv_counts[s_glob], 0)
            valid_r = flat_c < rc[off_i]
            return _finish(pool, valid_r, s_glob[off_i])

        def _dense_intra(_):
            m_all = jnp.repeat(jnp.arange(L, dtype=jnp.int32), C)
            cc = jnp.tile(jnp.arange(C, dtype=jnp.int32), L)
            d_glob_all = rank_table_j[pme, m_all]    # [L*C]
            cnt_all = jnp.where(same_pod, sc, 0)[d_glob_all]
            slot_valid = cc < cnt_all
            src_cols = jnp.minimum(bounds[d_glob_all] + cc, n - 1)
            plan = order[src_cols]
            pool = _dense_intra_wire(fi, plan, slot_valid, ici_axes)
            valid_r = cc < recv_counts[d_glob_all]
            return _finish(pool, valid_r, d_glob_all)

        out, new_count, dropped_recv = lax.cond(
            guard == 1, _stencil, _dense_intra, operand=None
        )
        if as_f32:
            out = lax.bitcast_convert_type(out, jnp.float32)
        self_count = jnp.sum(is_self.astype(jnp.int32))
        self_onehot = (jnp.arange(R, dtype=jnp.int32) == me) * self_count
        stats = RedistributeStats(
            send_counts=(send_counts + self_onehot)[None, :],
            recv_counts=(recv_counts + self_onehot)[None, :],
            dropped_send=dropped_send[None].astype(jnp.int32),
            dropped_recv=dropped_recv[None],
            needed_capacity=jnp.max(remote_counts)[None].astype(jnp.int32),
            fallback=(1 - guard)[None].astype(jnp.int32),
            needed_cross=needed_cross[None],
        )
        return out, new_count[None], stats

    return fn


def vrank_redistribute_hierarchical_fn(
    domain: Domain,
    grid: ProcessGrid,
    hier,
    capacity: int,
    out_capacity: int,
    mover_cap: int,
    cross_cap: int,
    ndim: int = None,
    edges=None,
):
    """HIERARCHICAL two-level canonical exchange, vrank twin: the staged
    DCN ``ppermute`` + intra-pod fanout become static cross-vrank block
    gathers through the SAME :class:`..mesh.HierarchicalMesh` tables the
    sharded engine ships (pod ids, pod-local ranks, per-(pod,pod)
    routes), so one chip exercises the exact two-level schedule — guard,
    clip-and-count cross overflow, block order — the fleet runs.
    Bit-identical to the planar vrank engine on non-overflowing steps.
    """
    from mpi_grid_redistribute_tpu.parallel import mesh as mesh_lib

    V = grid.nranks
    C = capacity
    B = _check_mover_cap(mover_cap, capacity)
    B2 = _check_cross_cap(cross_cap)
    D = domain.ndim if ndim is None else ndim
    if hier.grid != grid:
        raise ValueError(
            f"hierarchical mesh wraps grid {hier.grid.shape}, engine "
            f"built for {grid.shape}"
        )
    n_pods = hier.n_pods
    if n_pods < 2:
        raise ValueError(
            "hierarchical engine needs a multi-pod mesh (n_pods >= 2); "
            "resolve_engine degrades flat meshes to the sparse engine"
        )
    L = hier.pod_size
    periodic_local = hier.local_periodic(domain.periodic)
    _, dstL_t, srcL_t, memberL = mesh_lib.neighbor_tables(
        hier.local_grid, periodic_local
    )
    permsL_all = mesh_lib.neighbor_perms(hier.local_grid, periodic_local)
    activeL = tuple(o for o in range(dstL_t.shape[1]) if permsL_all[o])
    n_actL = len(activeL)
    pod_of = hier.pod_of
    local_of = hier.local_of
    rank_table = hier.rank_table
    # pod-local stencil tables lifted to GLOBAL ranks per vrank
    dstL_act = dstL_t[:, activeL].reshape(L, n_actL)
    srcL_act = srcL_t[:, activeL].reshape(L, n_actL)
    dst_loc = dstL_act[local_of]                     # [V, n_actL]
    src_loc = srcL_act[local_of]
    dst_glob = np.where(
        dst_loc >= 0,
        rank_table[pod_of[:, None], np.where(dst_loc >= 0, dst_loc, 0)],
        -1,
    )
    src_glob = np.where(
        src_loc >= 0,
        rank_table[pod_of[:, None], np.where(src_loc >= 0, src_loc, 0)],
        -1,
    )
    d_valid = jnp.asarray(dst_glob >= 0)
    d_safe = jnp.asarray(np.where(dst_glob >= 0, dst_glob, 0))
    s_valid = jnp.asarray(src_glob >= 0)
    s_safe = jnp.asarray(np.where(src_glob >= 0, src_glob, 0))
    same_np = pod_of[:, None] == pod_of[None, :]
    member_j = jnp.asarray(
        same_np & memberL[local_of[:, None], local_of[None, :]]
    )
    same_j = jnp.asarray(same_np)
    cross_j = jnp.asarray(~same_np)
    M_j = jnp.asarray(
        (
            (np.arange(V)[:, None] < np.arange(V)[None, :]) & same_np
        ).astype(np.int32)
    )
    pod_onehot_t = jnp.asarray(
        (pod_of[:, None] == np.arange(n_pods)[None, :]).astype(np.int32)
    )                                                # [V, n_pods]
    # per-delta static cross tables
    to_q_np = []
    mirror_src_np = []
    dst_loc_idx_np = []
    keys_np = []
    for delta in range(n_pods):
        q_dst = (pod_of + delta) % n_pods
        to_q_np.append(pod_of[None, :] == q_dst[:, None])
        mirror_src_np.append(rank_table[(pod_of - delta) % n_pods, local_of])
        dst_loc_idx_np.append(rank_table[q_dst])     # [V, L]
        keys_np.append(
            np.repeat(rank_table[(pod_of - delta) % n_pods], B2, axis=1)
        )                                            # [V, L*B2]
    # fanout "all_to_all over ici axes" as a static within-pod gather
    row_idx_np = np.repeat(rank_table[pod_of], B2, axis=1)   # [V, L*B2]
    col_idx_np = (
        local_of[:, None] * B2 + np.tile(np.arange(B2), L)[None, :]
    )
    m_rep_np = np.repeat(np.arange(L), B2)
    # dense-intra static tables ([V, L*C])
    dloc_np = np.repeat(rank_table[pod_of], C, axis=1)
    drow_np = dloc_np
    dcol_np = local_of[:, None] * C + np.tile(np.arange(C), L)[None, :]

    def fn(fused, count):
        as_f32, fi, pos_f = _validate_planar_vranks(fused, V, D)
        n = fused.shape[2]
        K = fused.shape[1]
        me_ids, is_self, order, remote_counts, bounds = (
            _vrank_sparse_prefix(fi, pos_f, count, domain, grid, edges, n)
        )
        sc = jnp.minimum(remote_counts, C)           # [V, V]
        sc_cross = jnp.where(cross_j, sc, 0)
        prefix = sc_cross @ M_j
        eff = jnp.where(
            cross_j, jnp.clip(B2 - prefix, 0, sc), sc
        ).astype(jnp.int32)
        dropped_send = jnp.sum(remote_counts - eff, axis=1)
        send_counts = eff
        recv_counts = eff.T
        needed = jnp.max(remote_counts, axis=1).astype(jnp.int32)
        needed_cross = jnp.max(
            sc_cross @ pod_onehot_t, axis=1
        ).astype(jnp.int32)

        j_idx = jnp.arange(B2, dtype=jnp.int32)
        jj = jnp.tile(j_idx, L)
        cross_pools, cross_keys, cross_valid = [], [], []
        with traced_span("rd:exchange"):
            for delta in range(1, n_pods):
                to_q = jnp.asarray(to_q_np[delta])
                hit = (
                    to_q[:, None, :]
                    & (j_idx[None, :, None] >= prefix[:, None, :])
                    & (j_idx[None, :, None] < (prefix + eff)[:, None, :])
                )                                    # [V, B2, V]
                src_col = jnp.sum(
                    jnp.where(
                        hit,
                        bounds[:, None, :V]
                        + j_idx[None, :, None]
                        - prefix[:, None, :],
                        0,
                    ),
                    axis=2,
                )
                slot_valid = jnp.any(hit, axis=2)
                plan = jnp.take_along_axis(
                    order, jnp.minimum(src_col, n - 1), axis=1
                )
                blk = jax.vmap(pack.gather_plan_cols)(fi, plan)
                blk = jnp.where(slot_valid[:, None, :], blk, 0)
                # the DCN hop, as a static gather: vrank v's mirror
                # block came from (pod_of[v]-delta, local_of[v])
                mirror = blk[mirror_src_np[delta]]
                cnt_loc = jnp.take_along_axis(
                    eff, jnp.asarray(dst_loc_idx_np[delta]), axis=1
                )[mirror_src_np[delta]]              # [V, L] arrived lens
                start_loc = jnp.concatenate(
                    [
                        jnp.zeros((V, 1), cnt_loc.dtype),
                        jnp.cumsum(cnt_loc, axis=1)[:, :-1],
                    ],
                    axis=1,
                )
                fan_valid = jj[None, :] < cnt_loc[:, m_rep_np]
                fan_col = jnp.minimum(
                    start_loc[:, m_rep_np] + jj[None, :], B2 - 1
                )
                fan = jax.vmap(pack.gather_plan_cols)(mirror, fan_col)
                fan = jnp.where(fan_valid[:, None, :], fan, 0)
                # the intra-pod fanout hop, as a static gather
                arrived = fan[
                    row_idx_np[:, None, :],
                    jnp.arange(K)[None, :, None],
                    col_idx_np[:, None, :],
                ]                                    # [V, K, L*B2]
                keys = jnp.asarray(keys_np[delta])
                valid_r = jj[None, :] < jnp.take_along_axis(
                    recv_counts, keys, axis=1
                )
                cross_pools.append(arrived)
                cross_keys.append(keys)
                cross_valid.append(valid_r)

        guard = jnp.all(
            jnp.where(
                same_j,
                jnp.where(member_j, remote_counts <= B, remote_counts == 0),
                True,
            )
        )

        def _finish(pool, valid_r, srckeys):
            invalid = ~jnp.concatenate(
                [valid_r] + cross_valid + [is_self], axis=1
            )
            source_key = jnp.concatenate(
                [srckeys]
                + cross_keys
                + [jnp.broadcast_to(me_ids[:, None], (V, n))],
                axis=1,
            ).astype(jnp.int32)
            values = jnp.concatenate([pool] + cross_pools + [fi], axis=2)
            new_full = jnp.sum(recv_counts, axis=1) + jnp.sum(
                is_self.astype(jnp.int32), axis=1
            )

            def compact_one(vals_v, inv_v, sk_v, nf_v):
                return pack.planar_compact_keys(
                    vals_v, inv_v, sk_v, V, nf_v, out_capacity
                )

            with traced_span("rd:unpack"):
                return jax.vmap(compact_one)(
                    values, invalid, source_key, new_full
                )

        def _stencil(_):
            sc_b = jnp.minimum(sc, B)
            cnt = jnp.where(
                d_valid, jnp.take_along_axis(sc_b, d_safe, axis=1), 0
            )                                        # [V, n_actL]
            base = jnp.take_along_axis(bounds, d_safe, axis=1)
            c_idx = jnp.arange(B, dtype=jnp.int32)
            slot_valid = (
                c_idx[None, None, :] < cnt[:, :, None]
            ).reshape(V, n_actL * B)
            src_cols = jnp.minimum(
                base[:, :, None] + c_idx[None, None, :], n - 1
            ).reshape(V, n_actL * B)
            plan = jnp.take_along_axis(order, src_cols, axis=1)
            with traced_span("rd:pack"):
                send = jax.vmap(pack.gather_plan_cols)(fi, plan)
                send = jnp.where(slot_valid[:, None, :], send, 0)
            blocks = send.reshape(V, K, n_actL, B)
            with traced_span("rd:exchange"):
                recv = blocks[
                    s_safe, :, jnp.arange(n_actL)[None, :], :
                ]                                    # [V, n_actL, K, B]
                pool = recv.transpose(0, 2, 1, 3).reshape(
                    V, K, n_actL * B
                )
            rc = jnp.where(
                s_valid,
                jnp.take_along_axis(recv_counts, s_safe, axis=1),
                0,
            )
            valid_r = (
                c_idx[None, None, :] < rc[:, :, None]
            ).reshape(V, n_actL * B)
            srckeys = jnp.broadcast_to(
                s_safe[:, :, None], (V, n_actL, B)
            ).reshape(V, n_actL * B)
            return _finish(pool, valid_r, srckeys)

        def _dense_intra(_):
            cc = jnp.tile(jnp.arange(C, dtype=jnp.int32), L)
            dloc = jnp.asarray(dloc_np)
            cnt_all = jnp.take_along_axis(
                jnp.where(same_j, sc, 0), dloc, axis=1
            )                                        # [V, L*C]
            slot_valid = cc[None, :] < cnt_all
            src_cols = jnp.minimum(
                jnp.take_along_axis(bounds, dloc, axis=1) + cc[None, :],
                n - 1,
            )
            plan = jnp.take_along_axis(order, src_cols, axis=1)
            with traced_span("rd:pack"):
                packed = jax.vmap(pack.gather_plan_cols)(fi, plan)
                packed = jnp.where(slot_valid[:, None, :], packed, 0)
            with traced_span("rd:exchange"):
                pool = packed[
                    drow_np[:, None, :],
                    jnp.arange(K)[None, :, None],
                    dcol_np[:, None, :],
                ]                                    # [V, K, L*C]
            valid_r = cc[None, :] < jnp.take_along_axis(
                recv_counts, dloc, axis=1
            )
            return _finish(pool, valid_r, dloc)

        out, new_count, dropped_recv = lax.cond(
            guard, _stencil, _dense_intra, operand=None
        )
        if as_f32:
            out = lax.bitcast_convert_type(out, jnp.float32)
        self_count = jnp.sum(is_self.astype(jnp.int32), axis=1)
        self_diag = jnp.diag(self_count)
        stats = RedistributeStats(
            send_counts=send_counts + self_diag,
            recv_counts=recv_counts + self_diag,
            dropped_send=dropped_send.astype(jnp.int32),
            dropped_recv=dropped_recv,
            needed_capacity=needed,
            fallback=jnp.broadcast_to((~guard).astype(jnp.int32), (V,)),
            needed_cross=needed_cross,
        )
        return out, new_count, stats

    return fn


_COUNT_DRIVEN_SHARD_FNS = {
    "sparse": shard_redistribute_sparse_fn,
    "neighbor": shard_redistribute_neighbor_fn,
}
_COUNT_DRIVEN_VRANK_FNS = {
    "sparse": vrank_redistribute_sparse_fn,
    "neighbor": vrank_redistribute_neighbor_fn,
}

# Public roster of the count-driven engines, in roster order. progcheck's
# J000 completeness rule iterates this: adding an engine here without
# registering a traceable program in analysis/progcheck.py fails the
# registry-coverage check, so no engine ships unanalyzed.
COUNT_DRIVEN_ENGINES = tuple(_COUNT_DRIVEN_SHARD_FNS)
assert COUNT_DRIVEN_ENGINES == tuple(_COUNT_DRIVEN_VRANK_FNS)


def shard_redistribute_count_driven_sharded(
    mesh: Mesh,
    domain: Domain,
    grid: ProcessGrid,
    capacity: int,
    out_capacity: int,
    mover_cap: int,
    ndim: int = None,
    edges=None,
    engine: str = "sparse",
    axes=None,
):
    """``shard_map``-wrapped count-driven exchange (``engine`` picks the
    sparse all_to_all or neighbor ppermute wire). Same global layout as
    :func:`shard_redistribute_planar_sharded`; the stats tree carries the
    extra ``fallback`` leaf ([R] int32). ``axes`` overrides the mesh
    axes (expanded hierarchical meshes — see
    :func:`shard_redistribute_sparse_fn`)."""
    axes = grid.axis_names if axes is None else tuple(axes)
    spec_f = P(None, axes)
    spec_c = P(axes)
    fn = _COUNT_DRIVEN_SHARD_FNS[engine](
        domain, grid, capacity, out_capacity, mover_cap, ndim, edges=edges,
        axes=axes,
    )
    out_specs = (
        spec_f,
        spec_c,
        RedistributeStats(
            spec_c, spec_c, spec_c, spec_c, spec_c, spec_c
        ),
    )
    return shard_map(
        fn, mesh=mesh, in_specs=(spec_f, spec_c), out_specs=out_specs
    )


@functools.lru_cache(maxsize=64)
def build_redistribute_count_driven(
    mesh: Mesh,
    domain: Domain,
    grid: ProcessGrid,
    capacity: int,
    out_capacity: int,
    mover_cap: int,
    ndim: int = None,
    edges=None,
    engine: str = "sparse",
    axes=None,
):
    """jit of :func:`shard_redistribute_count_driven_sharded`."""
    return jax.jit(
        shard_redistribute_count_driven_sharded(
            mesh, domain, grid, capacity, out_capacity, mover_cap, ndim,
            edges=edges, engine=engine, axes=axes,
        )
    )


def shard_redistribute_hierarchical_sharded(
    mesh: Mesh,
    domain: Domain,
    grid: ProcessGrid,
    hier,
    capacity: int,
    out_capacity: int,
    mover_cap: int,
    cross_cap: int,
    ndim: int = None,
    edges=None,
):
    """``shard_map``-wrapped hierarchical two-level exchange. ``mesh``
    must be the EXPANDED mesh (``hier.build_mesh()``); the global layout
    is identical to :func:`shard_redistribute_planar_sharded` because
    the interleaved expanded axes keep row-major flat index == grid
    rank. The stats tree carries ``fallback`` (intra stage) AND
    ``needed_cross`` ([R] int32)."""
    axes = hier.axis_names
    spec_f = P(None, axes)
    spec_c = P(axes)
    fn = shard_redistribute_hierarchical_fn(
        domain, grid, hier, capacity, out_capacity, mover_cap, cross_cap,
        ndim, edges=edges,
    )
    out_specs = (
        spec_f,
        spec_c,
        RedistributeStats(
            spec_c, spec_c, spec_c, spec_c, spec_c, spec_c, None, spec_c
        ),
    )
    return shard_map(
        fn, mesh=mesh, in_specs=(spec_f, spec_c), out_specs=out_specs
    )


@functools.lru_cache(maxsize=64)
def build_redistribute_hierarchical(
    mesh: Mesh,
    domain: Domain,
    grid: ProcessGrid,
    hier,
    capacity: int,
    out_capacity: int,
    mover_cap: int,
    cross_cap: int,
    ndim: int = None,
    edges=None,
):
    """jit of :func:`shard_redistribute_hierarchical_sharded`."""
    return jax.jit(
        shard_redistribute_hierarchical_sharded(
            mesh, domain, grid, hier, capacity, out_capacity, mover_cap,
            cross_cap, ndim, edges=edges,
        )
    )


@functools.lru_cache(maxsize=64)
def build_redistribute_hierarchical_vranks(
    domain: Domain,
    grid: ProcessGrid,
    hier,
    capacity: int,
    out_capacity: int,
    mover_cap: int,
    cross_cap: int,
    ndim: int = None,
    edges=None,
):
    """jit of :func:`vrank_redistribute_hierarchical_fn`."""
    return jax.jit(
        vrank_redistribute_hierarchical_fn(
            domain, grid, hier, capacity, out_capacity, mover_cap,
            cross_cap, ndim, edges=edges,
        )
    )


@functools.lru_cache(maxsize=64)
def build_redistribute_count_driven_vranks(
    domain: Domain,
    grid: ProcessGrid,
    capacity: int,
    out_capacity: int,
    mover_cap: int,
    ndim: int = None,
    edges=None,
    engine: str = "sparse",
):
    """jit of the count-driven vrank twins ([V, K, n] planar)."""
    return jax.jit(
        _COUNT_DRIVEN_VRANK_FNS[engine](
            domain, grid, capacity, out_capacity, mover_cap, ndim,
            edges=edges,
        )
    )


@functools.lru_cache(maxsize=64)
def build_redistribute_planar(
    mesh: Mesh,
    domain: Domain,
    grid: ProcessGrid,
    capacity: int,
    out_capacity: int,
    ndim: int = None,
    edges=None,
):
    """jit of :func:`shard_redistribute_planar_sharded` (global planar)."""
    return jax.jit(
        shard_redistribute_planar_sharded(
            mesh, domain, grid, capacity, out_capacity, ndim, edges=edges
        )
    )


@functools.lru_cache(maxsize=64)
def build_redistribute_planar_vranks(
    domain: Domain,
    grid: ProcessGrid,
    capacity: int,
    out_capacity: int,
    ndim: int = None,
    edges=None,
):
    """jit of :func:`vrank_redistribute_planar_fn` ([V, K, n] planar)."""
    return jax.jit(
        vrank_redistribute_planar_fn(
            domain, grid, capacity, out_capacity, ndim, edges=edges
        )
    )


@functools.lru_cache(maxsize=64)
def build_redistribute_vranks(
    domain: Domain,
    grid: ProcessGrid,
    capacity: int,
    out_capacity: int,
    edges=None,
):
    """jit of :func:`vrank_redistribute_fn` (single-device, [V, n, ...])."""
    return jax.jit(
        vrank_redistribute_fn(domain, grid, capacity, out_capacity, edges)
    )


@functools.lru_cache(maxsize=64)
def build_redistribute(
    mesh: Mesh,
    domain: Domain,
    grid: ProcessGrid,
    capacity: int,
    out_capacity: int,
    n_fields: int,
    edges=None,
):
    """jit-compiled global redistribute over ``mesh``.

    Global layout: ``pos`` is ``[R * n_local, D]`` sharded on axis 0 over all
    mesh axes (x-major, matching rank order); ``count`` is ``[R]`` int32 with
    one entry per shard. Returns the same layout with leading dim
    ``R * out_capacity`` plus a :class:`RedistributeStats`.
    """
    axes = grid.axis_names
    spec = P(axes)
    fn = shard_redistribute_fn(domain, grid, capacity, out_capacity, edges)
    in_specs = (spec, spec) + (spec,) * n_fields
    out_specs = (
        (spec, spec)
        + (spec,) * n_fields
        # 5 explicit specs: no fallback leaf on the row-major engine
        + (RedistributeStats(spec, spec, spec, spec, spec),)
    )
    sharded = shard_map(fn, mesh=mesh, in_specs=in_specs, out_specs=out_specs)
    return jax.jit(sharded)


# ---------------------------------------------------------------------------
# Two-phase (start/finish) exchange surface — the software-pipelined
# resident engine's dispatch point (ISSUE 12).
# ---------------------------------------------------------------------------


class TwoPhaseExchange(NamedTuple):
    """Resolution record for the two-phase exchange surface (ISSUE 12).

    ``armed`` is the STATIC (build-time) verdict: True means the
    pipelined schedule is feasible and ``bundle`` carries the engine
    implementation (a :class:`..migrate.VrankTwoPhase` for the
    single-device vranks mesh, or any object with ``issue``/``complete``
    attributes such as the split :func:`..migrate.shard_migrate_fused_fn`);
    False means the caller must build the sequential body instead
    (``bundle`` is None) and ``reason`` says why. The decision is
    journaled as an ``engine_resolved`` event, same shape as
    :func:`resolve_engine`'s, so silent degradation is observable."""

    engine: str
    armed: bool
    reason: str
    bundle: object = None


def resolve_two_phase(
    engine: str,
    *,
    chunk: int,
    planar_ok: bool = True,
    ragged: bool = False,
    vranks: bool = False,
    n_devices: int = 1,
    n_pods: int = 1,
    build=None,
    recorder=None,
) -> TwoPhaseExchange:
    """Resolve whether the software-pipelined two-phase schedule may arm
    (ISSUE 12) — the ONE dispatch rule shared by
    :func:`..service.pipeline.make_pipelined_chunk_fn` and any future
    pipelined caller, mirroring :func:`resolve_engine`'s role for the
    one-shot engines.

    The pipelined steady state needs (a) at least two scan iterations so
    an exchange can sit in flight across an iteration boundary
    (``chunk >= 2``), (b) a planar-eligible payload (32-bit fields that
    ride bitcast, ``planar_ok``), (c) a rectangular receive side
    (``not ragged`` — out_capacity == n_local, so landed rows never
    re-compact mid-chunk), and (d) a topology whose exchange completes
    on one device (single-device vranks — cross-device two-phase needs
    an async collective surface this engine does not have yet). Any
    miss degrades to the sequential body at BUILD time; the runtime
    ``lax.cond`` inside the pipelined scan handles only the dynamic
    (backlog) case.

    ``build`` is a zero-arg callable constructing the engine bundle
    (deferred so degraded resolutions never trace it); ``recorder``
    journals the decision as ``engine_resolved`` with
    ``requested=engine``, ``resolved`` in {"pipeline", "sequential"}
    and one of the six "pipeline: ..." reason strings
    (telemetry/SCHEMA.md) — a multi-pod hierarchical topology
    (``n_pods > 1``) degrades like the multi-device case: the two-level
    wire has no two-phase surface yet.
    """
    if engine not in ENGINES:
        raise ValueError(
            f"engine must be one of {ENGINES}, got {engine!r}"
        )
    if chunk < 2:
        armed, reason = False, "pipeline: chunk < 2 — sequential body"
    elif not planar_ok:
        armed, reason = (
            False, "pipeline: payload not planar-eligible — sequential body"
        )
    elif ragged:
        armed, reason = (
            False, "pipeline: ragged receive capacity — sequential body"
        )
    elif not (vranks or n_devices == 1):
        armed, reason = (
            False, "pipeline: multi-device topology — sequential body"
        )
    elif n_pods > 1:
        armed, reason = (
            False,
            "pipeline: hierarchical multi-pod topology — sequential body",
        )
    else:
        armed, reason = True, "pipeline: armed (vranks planar two-phase)"
    if recorder is not None:
        recorder.record(
            "engine_resolved",
            requested=engine,
            resolved="pipeline" if armed else "sequential",
            reason=reason,
            canonical=False,
        )
    bundle = build() if (armed and build is not None) else None
    return TwoPhaseExchange(engine, armed, reason, bundle)


def _two_phase_impl(handle):
    impl = handle.bundle if isinstance(handle, TwoPhaseExchange) else handle
    if impl is None:
        raise TypeError(
            "two-phase exchange is not armed (degraded resolution: "
            f"{getattr(handle, 'reason', 'no bundle')!r}) — build the "
            "sequential body instead"
        )
    return impl


def start_exchange(handle, *args):
    """Phase 1 of the two-phase exchange: issue the routing plan (and,
    for engines with a real wire, put the payload in flight). Dispatches
    through a :class:`TwoPhaseExchange` handle — or directly through any
    engine exposing ``issue`` (the split
    :func:`..migrate.shard_migrate_fused_fn` and
    :class:`..migrate.VrankTwoPhase` both do). Reads nothing the
    landing mutates, so a pipelined caller may issue step k+1 while
    step k is still unconsumed."""
    impl = _two_phase_impl(handle)
    return impl.issue(*args)


def finish_exchange(handle, *args):
    """Phase 2 of the two-phase exchange: consume an in-flight plan and
    land the exchanged rows (free-stack update fused into the landing
    kernel). Dispatches to the engine's ``complete`` (flat migrate
    engine) or ``land`` (vranks planar two-phase) half."""
    impl = _two_phase_impl(handle)
    finish = getattr(impl, "complete", None)
    if finish is None:
        finish = impl.land
    return finish(*args)
