"""Resident-state migration: the fast drift-loop exchange (SURVEY.md §3.3).

The general :mod:`exchange` path re-packs every particle into canonical MPI
``Alltoallv`` receive order each step — full-array gathers plus a pool-wide
stable sort. (Its WIRE cost is now also mover-scaled: the count-driven
``sparse``/``neighbor`` canonical engines in :mod:`exchange` ship
``mover_cap``-wide pools over ``all_to_all``/``ppermute`` with an
in-graph dense fallback — this module keeps the mover-scaled COMPUTE
story for resident-slot state.) Profiling on the real chip shows the
true TPU cost model:

  * random-access scatter costs ~76-85 ns *per row* regardless of row width
    (measured in BOTH layouts; see below) — scatters must be few and sized
    to the data actually moved;
  * ``segment_sum`` histograms lower to scatter-add (~37 ms at 4M) — counts
    must come from ``searchsorted`` on already-sorted keys instead;
  * a full stable sort of 4M int32 keys is ~6 ms; elementwise binning ~3 ms.

**Planar layout** (round 3): the fused state is carried TRANSPOSED —
``[K, n]``, components on the sublane axis, particles on the lane
axis — because TPU stores any narrow-minor ``[n, K]`` buffer that
materializes at a program boundary or scan carry in the tiled ``T(8,128)``
layout: ``[n, 7]`` pads 128/7 = 18x (32 GB at 64M rows — the round-2 cap
on the single-chip north-star run). ``[K, n]`` pads only 8/ceil(K) on the
sublane axis (1.14x at K=7). Measured layout costs on the v5e-class chip
(scripts/microbench_layout.py, n=8.4M, P=262k): column gather 25.2 vs row
gather 17.6 ns/row; column scatter 76.1 vs row scatter 84.8 ns/row —
i.e. the planar layout is performance-neutral for the hot ops while
removing the 18x memory padding entirely.

Design (one compiled step, all static shapes):

  1. bin -> ``leaving`` mask (alive rows whose owner changed);
  2. ONE stable key sort groups leaving rows by destination; per-destination
     counts fall out of ``searchsorted`` on the sorted keys (no scatter-add);
  3. migrants beyond the per-(source,dest) ``capacity`` — or beyond what
     the receiver GRANTS (below) — simply STAY resident and retry next
     step (surfaced as ``backlog``; particles are never dropped);
  4. receiver-side flow control makes the receive lossless: desired
     per-pair counts fly first, each receiver grants pairwise swaps
     (self-financing: a swap arrival's matching departure vacates a slot)
     plus a greedy share of its free slots, grants fly back, and only
     granted rows are packed — arrivals are structurally bounded by what
     can land;
  5. one fused ``[R, K, C]`` ``lax.all_to_all`` moves position + payload +
     alive row as a single INT32 matrix (everything bitcast — round 4:
     integer transport is what keeps bit patterns exact on TPU vector
     units, whose float chains flush denormal patterns; see
     :func:`fuse_fields`);
  6. arrivals land exactly in the slots vacated by departures, then in slots
     popped from a carried free-slot *stack* (contiguous dynamic-slice
     push/pop — never a scatter); one single scatter per step writes
     payload, alive flag, and vacancy markers together; ``dropped_recv``
     remains as a surfaced safety counter and is structurally zero.

**Rotation-cycle liveness** (round-3; was a documented stall in round 2):
the least fixpoint of the self-financing grant recursion is zero on a pure
rotation cycle of length >= 3 between COMPLETELY full shards at zero free
slots — pairwise swaps are zero and there is nothing to grant. Both paths
now detect such cycles (:func:`_cycle_rescue`: functional graph of first
pending destinations over totally-stalled shards, boolean-closure cycle
detection) and force ONE granted row along each cycle edge per step; the
forced arrival lands in the slot the member's own forced departure
vacates, so the rescue is lossless with zero free slots and the cycle
drains at one row per member per step. Round 4 closed the last gap:
cycles that SPAN devices on the vrank path are rescued too — the global
pending matrix is all_gathered (it is O(R_total^2) ints and already
crosses the wire in spirit during the grant phase), the same closure
runs on it, and the forced cross-device arrivals are financed through
the free-slot stack (the forced departure's vacated slot is pushed by
the local landing phase and popped by the remote landing that follows).
Above 128 global ranks the global pass is disabled (R^2 log R closure
cost, same bound as the flat engine) and the per-device rescue remains.

**Virtual ranks** (:func:`shard_migrate_vranks_fn`): each device can host a
whole sub-grid of subdomains ("vranks", slabs side by side on the lane
axis), so a 4x4x4 grid runs on 8 chips — or on one — with identical
semantics: the cross-device hop is one ``lax.all_to_all`` on the
``[Dev, V_src, V_dst, K, C]`` buffer; vrank-to-vrank traffic on the same
device never leaves HBM. This is the TPU answer to running an R-rank MPI
job on fewer nodes (SURVEY.md §2 process-grid topology, §7.6 scale).

Slot order is *not* the MPI canonical order — arrivals fill arbitrary holes.
Correctness is therefore set-equality per shard against the oracle (tested
at the BIT level: the engine only ever moves rows), not order-equality; use
:mod:`exchange` when canonical MPI receive order matters.
"""

from __future__ import annotations

import os
from typing import NamedTuple, Sequence

import jax
import jax.numpy as jnp
from jax import lax

from mpi_grid_redistribute_tpu.domain import Domain, ProcessGrid
from mpi_grid_redistribute_tpu.ops import binning
from mpi_grid_redistribute_tpu.ops.pack import pack_cols as _pack_cols
from mpi_grid_redistribute_tpu.ops.pack import (
    gather_plan_cols as _gather_plan_cols,
)
# mig:bin / mig:pack / mig:exchange / mig:unpack named scopes on the step
# phases — XLA op metadata for Perfetto/XProf grouping (telemetry.phases)
from mpi_grid_redistribute_tpu.telemetry.phases import traced_span


def _resolve_scatter_impl(scatter_impl) -> str:
    """Resolve the landing-scatter implementation choice at BUILD time.

    Returns one of ``"overlay"`` (default on TPU: the planar one-hot
    overlay kernel, ops/pallas_overlay — measured 2.6x the XLA scatter at
    bench shapes), ``"xla"``, or ``"rows"`` (the round-2 row-store kernel,
    ops/pallas_scatter — a documented negative result kept for its
    platform findings).

    ``None`` (the default) consults the env once, when the builder runs —
    not inside the traced function, where jit caching (keyed on shapes
    only) would freeze the first value seen and make later env changes
    silently ineffective (round-2 advisor). MPI_GRID_LAND_SCATTER
    ∈ {overlay, xla, rows} picks explicitly; legacy
    MPI_GRID_PALLAS_SCATTER=1 still selects "rows". Passing an explicit
    value overrides the env entirely, so two settings can coexist in one
    process via two builders."""
    on_tpu = jax.devices()[0].platform == "tpu"
    if scatter_impl is None:
        env = os.environ.get("MPI_GRID_LAND_SCATTER")
        if env is None and os.environ.get("MPI_GRID_PALLAS_SCATTER") == "1":
            env = "rows"
        impl = env or ("overlay" if on_tpu else "xla")
    elif scatter_impl is True:
        impl = "rows"
    elif scatter_impl is False:
        impl = "xla"
    else:
        impl = str(scatter_impl)
    if impl not in ("overlay", "xla", "rows"):
        raise ValueError(f"unknown landing-scatter impl {impl!r}")
    return impl if on_tpu else "xla"


def _land_scatter(flat, targets, cols, impl: str = "xla"):
    """The landing column-scatter on planar ``[K, m]`` state.

    ``impl`` is resolved by the builder via :func:`_resolve_scatter_impl`,
    never read from the env here. ``"overlay"`` is the planar one-hot
    overlay kernel (sort arrivals by target, stream the state through
    VMEM, place via MXU one-hot matmuls — no per-element stores; it
    falls back to the XLA scatter itself when its contract doesn't
    hold). ``"rows"`` is the round-2 per-row-store kernel, kept for its
    measured negative result; it takes row-major buffers, so that branch
    pays two transposes on top of its already-losing per-row stores.

    UNIQUENESS INVARIANT (the overlay kernel's correctness contract — a
    duplicate in-range target would accumulate two one-hot contributions
    into the half-planes and produce garbage words silently, where the
    XLA scatter merely picks one writer): every in-range ``targets``
    entry this module passes is unique by construction. In
    :func:`_land_arrivals` / the vranks ``land_plan``, targets are drawn
    from (a) ``vacated`` — distinct resident columns, because they come
    from disjoint prefixes of a PERMUTATION (``_plan_rows`` over the
    sort order), and (b) popped free-stack entries — distinct stack
    positions of a stack holding distinct column ids; (a) targets hold
    live rows and (b) targets hold holes, so the two sets are disjoint,
    and everything else is the drop sentinel. Callers introducing a new
    path into the overlay must preserve this."""
    if impl == "overlay":
        from mpi_grid_redistribute_tpu.ops import pallas_overlay

        return pallas_overlay.overlay_scatter_planar(flat, targets, cols)
    if impl == "rows":
        if flat.dtype != jnp.float32:
            # The row-store kernel is float32-only, and its per-row VMEM
            # stores are exactly the float copy chains that flush denormal
            # bit patterns — running it on a bitcast view of the int32
            # transport would reintroduce the round-4 corruption. Fail
            # loudly rather than silently measuring the XLA scatter under
            # the "rows" label.
            raise TypeError(
                "scatter_impl='rows' (MPI_GRID_LAND_SCATTER=rows) is "
                "float32-only and incompatible with the int32 bit-exact "
                "transport the migrate engines now carry; use 'overlay' "
                "or 'xla'"
            )
        from mpi_grid_redistribute_tpu.ops import pallas_scatter

        return pallas_scatter.scatter_rows(flat.T, targets, cols.T).T
    return flat.at[:, targets].set(cols, mode="drop")


def _pos_row(flat: jax.Array, d: int) -> jax.Array:
    """float32 VIEW of position row ``d`` of the fused state.

    The fused transport matrix is int32 (bit-pattern-safe on TPU vector
    units — see :func:`fuse_fields`); binning arithmetic needs the float
    values, so position rows are bitcast back here. Legacy float32 state
    passes through untouched."""
    row = flat[d, :]
    if row.dtype == jnp.int32:
        return lax.bitcast_convert_type(row, jnp.float32)
    return row


class MigrateStats(NamedTuple):
    """Per-step migration observability (SURVEY.md §5.5). Global shapes [R]
    (one entry per rank; with vranks, device-major ``dev * V + vrank``
    order). ``backlog`` counts migrants delayed by per-pair send capacity
    or by receiver grants (they stay resident and retry — never lost);
    ``dropped_recv`` remains as a surfaced safety counter for arrivals a
    receiver could not land, structurally zero now that sends are
    receiver-granted.

    ``flow`` is the per-pair FLOW MATRIX (telemetry/flow.py): global
    ``[R, R]`` int32, entry ``[i, j]`` = rows rank ``i`` sent to rank
    ``j`` this step. It is the granted send-count table both engines
    already compute for the pack phase, stacked into the stats pytree —
    zero extra device work, zero host syncs. Row sums equal ``sent``
    and column sums equal ``received`` exactly (sends are
    receiver-granted, so the two sides agree by construction). Defaults
    to ``None`` (an empty pytree leaf) for hand-built fixtures.

    ``fast_path`` (ISSUE 4) reports the mover-sparse engine's per-step
    branch decision: [V] int32 per shard, 1 = the step ran the O(movers)
    fast branch, 0 = the residence/overflow guard routed it to the dense
    engine. ``None`` (the default, and what every non-sparse engine
    emits) means the engine carries no sparse path at all — telemetry
    distinguishes "no fast path built" from "built but fell back". The
    step's mover count is derivable as ``sent + backlog``."""

    sent: jax.Array
    received: jax.Array
    population: jax.Array
    backlog: jax.Array
    dropped_recv: jax.Array  # structurally 0 since receiver-granted sends
    flow: jax.Array = None  # [R, R] granted sends; None in old fixtures
    fast_path: jax.Array = None  # [V] 1/0 sparse-branch taken; None = n/a


class InflightExchange(NamedTuple):
    """Everything the ISSUE half of a split migrate step hands the
    COMPLETE half (ISSUE 12 two-phase surface): the exchanged arrival
    pool plus the granted-count tables and the sender's vacated-slot
    plan. Carrying it across a scan iteration is what lets a
    software-pipelined macro-step overlap the exchange with the next
    step's drift/binning before the landing consumes it.

    ``recv`` is the planar ``[K, n_src * C]`` arrival pool (post-wire);
    ``backlog`` counts granted-short rows that stayed resident."""

    recv: jax.Array
    recv_counts: jax.Array
    send_counts: jax.Array
    gather_idx: jax.Array
    backlog: jax.Array


class MigrateState(NamedTuple):
    """Scan-carry state for the fused migration loop.

    ``fused`` is PLANAR ``[K, n]`` int32 (``[K, V * n]`` with V vranks —
    vrank ``v`` owns lane columns ``[v * n, (v + 1) * n)``): position
    component rows first (float32 values bitcast; view via
    :func:`_pos_row`), payload rows, and the alive row last (1/0).
    Legacy float32 state is still accepted by the engines, but only the
    int32 transport is bit-exact for arbitrary payload patterns on TPU
    (see :func:`fuse_fields`).
    ``free_stack`` / ``n_free`` are the hole-slot stack (indices of dead
    columns; only the first ``n_free`` entries are live), per vrank
    (``[V, n]`` / ``[V]``) on the vrank path."""

    fused: jax.Array
    free_stack: jax.Array
    n_free: jax.Array


def fuse_fields(arrays: Sequence[jax.Array], alive: jax.Array):
    """Pack [n, ...] arrays + alive mask into one PLANAR [K, n] INT32
    matrix (components on the sublane axis — see module docstring).

    32-bit dtypes are bitcast to int32 — the INTEGER transport is what
    makes "bit patterns survive exactly" TRUE ON HARDWARE: TPU float
    vector chains (fused gather/select/concat passes over f32 state)
    flush denormal f32 bit patterns — any bitcast int below 2^23 — to
    zero (measured on-chip in round 4: a bitcast-int32 id row came back
    all zeros through the f32 drift loop), while integer lanes have no
    FTZ semantics. The engines bitcast position rows back to float32
    views only where binning arithmetic needs values. The alive mask
    becomes the last row (1/0).

    Returns ``(fused, specs)``; ``specs`` drives :func:`unfuse_fields`.
    """
    n = arrays[0].shape[0]
    parts, specs = [], []
    for a in arrays:
        if a.dtype.itemsize != 4:
            raise TypeError(
                f"fused migration payload requires 32-bit dtypes, got "
                f"{a.dtype}; cast or split the field"
            )
        flat = a.reshape(n, -1)
        if flat.dtype != jnp.int32:
            flat = lax.bitcast_convert_type(flat, jnp.int32)
        parts.append(flat.T)
        specs.append((a.shape[1:], a.dtype))
    parts.append(alive.astype(jnp.int32)[None, :])
    return jnp.concatenate(parts, axis=0), tuple(specs)


def unfuse_fields(fused: jax.Array, specs):
    """Inverse of :func:`fuse_fields`: ``(arrays..., alive)``. Accepts the
    int32 transport layout (canonical) or the legacy float32 layout."""
    out = []
    row = 0
    n = fused.shape[1]
    for shape, dtype in specs:
        k = 1
        for s in shape:
            k *= s
        flat = fused[row : row + k, :].T
        if dtype != flat.dtype:
            flat = lax.bitcast_convert_type(flat, dtype)
        out.append(flat.reshape((n,) + tuple(shape)))
        row += k
    alive = fused[-1, :] > 0
    return tuple(out), alive


def init_state(
    fused: jax.Array, vranks: int = 1, batched: bool = None
) -> MigrateState:
    """Build the free-slot stack from the fused matrix's alive row.

    One-time cost (a full argsort) at loop entry; the stack is maintained
    incrementally afterwards. ``fused`` is planar ``[K, m]``; with
    ``vranks=V``, ``m = V * n`` and the stack is per-vrank ``[V, n]`` over
    LOCAL column indices. ``batched`` (default ``vranks > 1``) forces the
    per-vrank ``[V, n]`` / ``[V]`` stack shapes even at ``V = 1`` — the
    vranks engine (:func:`shard_migrate_vranks_fn`) always expects the
    batched form, while the flat engine expects scalars.
    """
    if batched is None:
        batched = vranks > 1
    alive = fused[-1, :] > 0  # alive row is exactly 0/1 in either dtype
    if batched:
        alive = alive.reshape(vranks, -1)

    def one(a):
        stack = jnp.argsort(
            jnp.where(a, jnp.int32(1), jnp.int32(0)), stable=True
        ).astype(jnp.int32)
        return stack, jnp.sum((~a).astype(jnp.int32))

    if batched:
        free_stack, n_free = jax.vmap(one)(alive)
    else:
        free_stack, n_free = one(alive)
    return MigrateState(fused, free_stack, n_free)


def _segment_of(k: jax.Array, cum: jax.Array) -> jax.Array:
    """For output position(s) ``k`` (any shape, k >= 0), the segment index
    under exclusive cumulative counts ``cum`` ([n_segs+1], cum[0]=0): the
    d with cum[d] <= k < cum[d+1]. Comparison-count against the cum
    table — ``jnp.searchsorted``'s default TPU lowering is a sequential
    per-query scan (measured 200+ ms at 5M queries; the fix bought the
    headline 52 -> 45 ms/step). Use only for cum tables that stay small
    (O(V)); for tables scaling with total rank count prefer
    :func:`_segment_of_auto`."""
    k = jnp.asarray(k)
    return jnp.sum(
        cum[(None,) * k.ndim + (slice(1, None),)] <= k[..., None],
        axis=-1,
        dtype=jnp.int32,
    )


def _segment_of_auto(k: jax.Array, cum: jax.Array) -> jax.Array:
    """:func:`_segment_of`, but switching to the merge-sort ``searchsorted``
    lowering once the cum table outgrows O(tens) entries — the
    comparison-count does O(n_segs) work per query, which on tables that
    scale with the total rank count (R+1, Dev*V+1) becomes O(R^2 * C) per
    step (round-2 advisor). Identical semantics on duplicate boundaries
    (empty segments resolve past the run of duplicates) and for
    ``k >= cum[-1]`` (returns n_segs)."""
    if cum.shape[0] <= 129:
        # comparison-count: O(n_segs) VECTORIZED work per query — cheap up
        # to O(128) tables. The merge-sort searchsorted below introduces a
        # sort op that XLA can neither slice through nor hoist; the
        # round-4 north-star knockout charged +56 ms to the vmapped
        # method="sort" lowering at V=64 (65-entry tables) where the
        # comparison-count costs ~100M vectorized compares (~2-4 ms).
        return _segment_of(k, cum)
    return (
        jnp.searchsorted(cum, k, side="right", method="sort").astype(
            jnp.int32
        )
        - 1
    )


def _cycle_rescue(pending, sends_zero, ok=None):
    """Force one self-financed swap along each stalled rotation cycle.

    The receiver-granted flow control has one liveness hole (round-2
    verdict item 5): a pure rotation cycle of length >= 3 between
    COMPLETELY full shards at zero free slots — pairwise swaps are zero
    and there are no free slots to grant, so the least fixpoint of the
    self-financing grant recursion is zero and the cycle backlogs forever.
    This helper detects such cycles and forces exactly ONE granted row on
    each cycle edge: every member then has one forced departure AND one
    forced arrival, so the arrival lands in the slot the member's own
    departure vacates — lossless with zero free slots, draining the cycle
    at one row per member per step.

    Args:
      pending: [S, S] int32, >0 where source s still wants to send to d
        after normal grants.
      sends_zero: [S] bool — source granted NOTHING this step (totally
        stalled). Only such sources participate (anything else is making
        progress already).
      ok: optional [S] bool budget guard; a cycle is applied only if ALL
        its members are ok (atomicity keeps the swap self-financed — a
        partially applied cycle would give some member an arrival with no
        departure).

    Returns [S, S] int32 in {0, 1}: the forced extra grants. Cycles are
    found in the functional graph v -> first pending destination of v,
    restricted to stalled sources, via log-squared boolean closure of the
    [S, S] adjacency — O(S^2 log S) elementwise work on tiny matrices.
    """
    S = pending.shape[0]
    has = jnp.any(pending > 0, axis=1) & sends_zero
    succ = jnp.argmax(pending > 0, axis=1)
    A = jnp.where(
        has[:, None], jax.nn.one_hot(succ, S, dtype=jnp.float32), 0.0
    )
    clo = A + jnp.eye(S, dtype=jnp.float32)
    for _ in range(max(1, (max(S, 2) - 1).bit_length())):
        clo = jnp.minimum(clo @ clo, 1.0)
    # v is on a cycle iff a path v -> succ(v) ->* v exists
    on_cycle = jnp.sum(A * clo.T, axis=1) > 0
    if ok is not None:
        # mutual reachability = the member set of v's cycle (functional
        # graphs have only cycle SCCs); drop cycles with any !ok member
        mutual = (clo * clo.T) > 0
        cycle_bad = jnp.any(mutual & ~ok[None, :], axis=1)
        on_cycle = on_cycle & ~cycle_bad
    return (A * on_cycle[:, None]).astype(jnp.int32)


def _stack_push_pop(free_stack, n_free, n_pop, n_push, vacated, n_in):
    """Free-stack update after landing: pops lower the head; net-excess
    vacated slots ``vacated[n_in : n_in + n_push]`` are pushed, via a
    read-modify-write of one contiguous window (never a scatter).

    ``vacated`` has static length P; the window is ``min(P, n)`` entries
    whose start is clamped in bounds, so the update costs O(P) however
    many slots the stack holds. Returns ``(free_stack, n_free)``.

    Shared by every landing of a sequential step: the flat engine's
    :func:`_land_arrivals` and the vmapped vranks landings (dense and
    mover-sparse).
    """
    n = free_stack.shape[0]
    P = vacated.shape[0]
    W = min(P, n)
    new_n_free = n_free - n_pop + n_push
    win_start = jnp.clip(n_free, 0, max(n - W, 0)).astype(jnp.int32)
    window = lax.dynamic_slice(free_stack, (win_start,), (W,))
    rel = n_free - win_start  # stack head position inside the window
    w_idx = jnp.arange(W, dtype=jnp.int32)
    # affine index (w + n_in - rel): one dynamic slice of the padded
    # plan replaces a [W]-element gather (out-of-use entries read the
    # zero pads and are masked below)
    buf = jnp.concatenate(
        [
            jnp.zeros((W,), vacated.dtype),
            vacated,
            jnp.zeros((W,), vacated.dtype),
        ]
    )
    pushes = lax.dynamic_slice(buf, (n_in - rel + W,), (W,))
    window = jnp.where(
        (w_idx >= rel) & (w_idx < rel + n_push), pushes, window
    )
    free_stack = lax.dynamic_update_slice(free_stack, window, (win_start,))
    return free_stack, new_n_free


def _land_arrivals(
    fused,
    free_stack,
    n_free,
    recv,
    recv_counts,
    send_counts,
    gather_idx,
    capacity: int,
    scatter_impl: str = "xla",
):
    """Land compacted arrivals into vacated slots, then popped holes.

    ``recv`` is the planar ``[K, n_src * C]`` arrival pool (per-source
    slots, only the first ``recv_counts[s]`` of each source's ``C``
    valid); ``send_counts`` / ``gather_idx`` describe this shard's own
    sends, whose slots are being vacated. One scatter writes arrivals,
    hole markers and the alive row together. Returns
    ``(fused, free_stack, n_free, n_in, dropped_recv)``.
    """
    with traced_span("mig:unpack"):
        n = fused.shape[1]
        C = capacity
        n_dest = send_counts.shape[0]
        n_src = recv_counts.shape[0]
        P = max(n_src, n_dest) * C  # write-plan length
        n_sent = jnp.sum(send_counts).astype(jnp.int32)
        n_in = jnp.sum(recv_counts).astype(jnp.int32)

        cum_send = jnp.concatenate(
            [jnp.zeros((1,), jnp.int32), jnp.cumsum(send_counts)]
        )
        cum_recv = jnp.concatenate(
            [jnp.zeros((1,), jnp.int32), jnp.cumsum(recv_counts)]
        )
        k_idx = jnp.arange(P, dtype=jnp.int32)
        d_of_k = _segment_of_auto(k_idx, cum_send)
        vacated = gather_idx[
            jnp.clip(
                d_of_k * C + (k_idx - cum_send[d_of_k]), 0, n_dest * C - 1
            )
        ]  # first n_sent entries: vacated slot ids
        s_of_k = _segment_of_auto(k_idx, cum_recv)
        arrivals = jnp.take(
            recv,
            jnp.clip(
                s_of_k * C + (k_idx - cum_recv[s_of_k]), 0, n_src * C - 1
            ),
            axis=1,
        )  # first n_in columns: real arrivals (alive row already 1)

        # Write plan for slot j in [P]:
        #   j < min(n_in, n_sent): arrival j -> vacated[j]
        #   n_sent <= j < n_in:    arrival j -> popped free slot
        #   n_in <= j < n_sent:    hole marker -> vacated[j]
        # Receiver overflow: arrivals beyond n_sent + n_free drop (counted).
        n_pop = jnp.clip(n_in - n_sent, 0, n_free)
        dropped_recv = jnp.maximum(n_in - n_sent - n_free, 0).astype(
            jnp.int32
        )
        pop_idx = jnp.clip(n_free - 1 - (k_idx - n_sent), 0, n - 1)
        target = jnp.where(
            k_idx < jnp.minimum(n_in, n_sent),
            vacated,
            jnp.where(
                (k_idx >= n_sent) & (k_idx < n_sent + n_pop),
                free_stack[pop_idx],
                jnp.where((k_idx >= n_in) & (k_idx < n_sent), vacated, n),
            ),
        )
        cols = jnp.where((k_idx < n_in)[None, :], arrivals, 0)
        # THE scatter: payload + alive flag + hole markers in one pass.
        fused = _land_scatter(fused, target, cols, scatter_impl)

    # Net excess departures (n_sent - n_in when positive) were written as
    # holes at vacated[n_in : n_sent]: push them onto the stack. The pops
    # above read the stack before this update.
    with traced_span("mig:stack"):
        n_push = jnp.maximum(n_sent - n_in, 0)
        free_stack, new_n_free = _stack_push_pop(
            free_stack, n_free, n_pop, n_push, vacated, n_in
        )
    return fused, free_stack, new_n_free, n_in, dropped_recv


def shard_migrate_fused_fn(
    domain: Domain, grid: ProcessGrid, capacity: int, ndim: int = None,
    cycle_rescue: bool = True, scatter_impl=None,
):
    """Per-shard migration on planar fused state (runs under ``shard_map``).

    Signature of the returned fn:
      ``MigrateState -> (MigrateState, MigrateStats)``
    where ``state.fused`` is ``[K, n]`` with rows ``0:ndim`` the position
    (default ``domain.ndim``) and the last row the alive flag. Columns with
    alive 0 are holes whose contents are unspecified.

    ``cycle_rescue`` (default on, auto-disabled above 128 ranks) drains
    full-shard rotation cycles via :func:`_cycle_rescue`: one extra
    all_gather of an [R] pending vector per step, then a forced
    self-financed swap along each detected cycle.
    """
    R = grid.nranks
    axes = grid.axis_names
    C = capacity
    D = domain.ndim if ndim is None else ndim
    rescue = cycle_rescue and R <= 128
    if cycle_rescue and not rescue:
        # The liveness guarantee silently changing with scale is worse
        # than the O(R^2 log R) closure cost it avoids — tell the caller
        # (round-3 verdict weak item 5).
        import warnings

        warnings.warn(
            f"cycle_rescue disabled: {R} ranks > 128 (the all-gathered "
            f"[R, R] boolean-closure cost grows as R^2 log R). Full-shard "
            f"rotation cycles will backlog instead of draining — watch "
            f"utils.stats.detect_stall, or pass cycle_rescue=False to "
            f"silence this warning.",
            stacklevel=2,
        )
    impl = _resolve_scatter_impl(scatter_impl)

    def issue(state: MigrateState) -> InflightExchange:
        """ISSUE half (ISSUE 12): bin -> grant -> pack -> wire. Leaves
        the resident state untouched (sent rows stay in place until the
        landing vacates them), so a pipelined caller can keep computing
        on ``state`` while the returned exchange is in flight."""
        fused, free_stack, n_free = state
        K = fused.shape[0]
        me = lax.axis_index(axes).astype(jnp.int32)
        # the live-row mask is drift-side state, as in the vrank engine
        with traced_span("mig:drift"):
            alive = fused[-1, :] > 0
        with traced_span("mig:bin"):
            # per-axis fused elementwise binning (no stacked [D, n]
            # intermediates; see the vranks path for the measurement)
            dest = jnp.zeros(fused.shape[1:], jnp.int32)
            for d in range(D):
                p = _pos_row(fused, d)
                lo = jnp.asarray(domain.lo[d], p.dtype)
                ext = jnp.asarray(domain.extent[d], p.dtype)
                if domain.periodic[d]:
                    # reciprocal-multiply wrap: bit-equal for pow2
                    # extents, 4x cheaper than the f32 division in
                    # jnp.remainder
                    p = lo + binning.remainder_fast(p - lo, domain.extent[d])
                    p = jnp.where(p >= lo + ext, lo, p)
                inv_w = jnp.asarray(grid.shape[d], p.dtype) / ext
                cell_d = jnp.clip(
                    jnp.floor((p - lo) * inv_w).astype(jnp.int32),
                    0,
                    grid.shape[d] - 1,
                )
                dest = dest + cell_d * jnp.int32(grid.strides[d])
            leaving = alive & (dest != me)
            # Sentinel R: holes and staying residents sort to the tail.
            dest_key = jnp.where(leaving, dest, R).astype(jnp.int32)

            # two-level leaver selection; the [1, n] batch shape reuses
            # the vrank engine's machinery (scalar-guard cond, see
            # binning). order is prefix-only: valid through the leaver
            # count, zero tail (see sorted_dest_counts_batched) — every
            # read below is masked or sliced at granted counts.
            o_b, c_b, b_b = binning.sorted_dest_counts_batched(
                dest_key[None], R
            )
            order, full_counts, bounds = o_b[0], c_b[0], b_b[0]
        with traced_span("mig:grant"):
            desired = jnp.minimum(full_counts, C).astype(jnp.int32)

            # Receiver-side flow control (lossless receive): exchange DESIRED
            # counts, let each receiver grant what it can land, send only the
            # granted rows; the rest stay resident and retry (backlog).
            # Grant = pairwise swaps (self-financing: each swap arrival has a
            # matching departure vacating a slot — both sides compute the same
            # symmetric min) + a greedy share of the free slots. Arrivals are
            # then structurally <= swaps + n_free, so the landing never drops.
            recv_desired = lax.all_to_all(
                desired, axes, split_axis=0, concat_axis=0, tiled=True
            )
            swap = jnp.minimum(recv_desired, desired)
            resid = _greedy_alloc(
                (recv_desired - swap)[:, None],
                jnp.maximum(n_free, 0)[None],
            )[:, 0].astype(jnp.int32)
            grants = swap + resid  # what I allow each source to send me
            grants_back = lax.all_to_all(
                grants, axes, split_axis=0, concat_axis=0, tiled=True
            )
            send_counts = jnp.minimum(desired, grants_back)
            # actual arrivals == my grants: grants <= recv_desired by
            # construction (swap and resid are both bounded by it), and each
            # sender sends exactly what I granted it
            recv_counts = grants

            if rescue:
                # drain full-shard rotation cycles: gather everyone's pending
                # vector, find cycles in the first-pending-destination graph
                # among totally-stalled shards, and force one granted swap
                # per cycle edge. Safe without guards here: a stalled sender
                # has an all-zero send row (so +1 <= C), and my grant to a
                # stalled pred was 0 (so its recv slot +1 <= C); the forced
                # arrival lands in the forced departure's vacated slot.
                pend_all = lax.all_gather(
                    desired - send_counts, axes
                ).reshape(R, R)
                sent_tot = lax.all_gather(
                    jnp.sum(send_counts), axes
                ).reshape(R)
                F = _cycle_rescue(pend_all, sent_tot == 0)
                send_counts = send_counts + F[me]
                recv_counts = recv_counts + F[:, me]
            backlog = jnp.sum(full_counts - send_counts).astype(jnp.int32)

        with traced_span("mig:pack"):
            send, gather_idx = _pack_cols(
                fused, order, bounds, send_counts, R, C
            )
        with traced_span("mig:exchange"):
            recv = lax.all_to_all(
                send.reshape(K, R, C).transpose(1, 0, 2), axes,
                split_axis=0, concat_axis=0, tiled=True,
            )  # [R, K, C]
            recv = recv.transpose(1, 0, 2).reshape(K, R * C)
        return InflightExchange(
            recv, recv_counts, send_counts, gather_idx, backlog
        )

    def complete(state: MigrateState, inflight: InflightExchange):
        """COMPLETE half (ISSUE 12): land the exchanged rows (scatter
        under ``mig:unpack``, free-stack update under ``mig:stack``) and
        assemble stats."""
        fused, free_stack, n_free = state
        fused, free_stack, n_free, n_in, dropped_recv = _land_arrivals(
            fused, free_stack, n_free, inflight.recv,
            inflight.recv_counts, inflight.send_counts,
            inflight.gather_idx, C, impl,
        )
        with traced_span("mig:stack"):
            population = jnp.sum((fused[-1, :] > 0).astype(jnp.int32))
            stats = MigrateStats(
                sent=jnp.sum(inflight.send_counts).astype(jnp.int32)[None],
                received=n_in[None],
                population=population[None],
                backlog=inflight.backlog[None],
                dropped_recv=dropped_recv[None],
                # granted sends, already computed for the pack phase: my row
                # of the global [R, R] flow matrix (shard axis 0 stacks rows)
                flow=inflight.send_counts[None],
            )
        return MigrateState(fused, free_stack, n_free), stats

    def fn(state: MigrateState):
        return complete(state, issue(state))

    # the split halves ARE the engine: fn is their recomposition (pure
    # code motion — identical eqn order, so J004 profiles are untouched),
    # and exchange.resolve_two_phase routes pipelined callers here
    fn.issue = issue
    fn.complete = complete
    return fn


def _greedy_alloc(desired: jax.Array, cap: jax.Array) -> jax.Array:
    """Allocate ``desired[s, w]`` units across sources ``s`` per column
    ``w``, greedily in source order, never exceeding ``cap[w]`` total.
    Deterministic; sources with lower index win under pressure (backlogged
    rows keep stable priority and retry next step)."""
    cum = jnp.cumsum(desired, axis=0)
    prev = cum - desired
    capb = cap[None, :]
    return jnp.clip(jnp.minimum(cum, capb) - jnp.minimum(prev, capb), 0)


class VrankPlan(NamedTuple):
    """One step's routing decision from :class:`VrankTwoPhase.issue`
    (ISSUE 12): the sender-side vacated-slot plan, the receiver-side
    arrival gather plan (GLOBAL column ids into the ``[K, V * n]``
    matrix), the granted/desired count tables and the per-source
    ``backlog`` (rows the flow control declined this step). Plans are
    ``n``-wide — wide enough that the flow-control grant is the ONLY
    clip, so ``backlog == 0`` means every leaver was granted."""

    vacated: jax.Array  # [V, n] local vacated slot ids (first n_sent)
    n_sent: jax.Array  # [V]
    arr_plan: jax.Array  # [V, n] global arrival source columns
    n_in: jax.Array  # [V]
    allowed: jax.Array  # [V, V] granted sends [src, dst]
    desired: jax.Array  # [V, V] pre-grant leaver counts [src, dst]
    backlog: jax.Array  # [V] per-source granted-short rows


class VrankTwoPhase(NamedTuple):
    """The two-phase (start/finish) exchange surface for a SINGLE-DEVICE
    vrank mesh (ISSUE 12), built by :func:`vrank_exchange_two_phase_fn`
    and routed to callers via ``exchange.resolve_two_phase``.

    ``bin_key`` computes the per-column destination key; ``issue`` turns
    a key into a :class:`VrankPlan` (routing sort + receiver-granted
    flow control + cycle rescue + both gather plans); ``land`` lands a
    gathered arrival payload in ONE scatter with the free-stack update
    fused in. The split is what a software-pipelined macro-step needs:
    the plan + payload gather for step k can sit in flight while step
    k+1's drift/binning is issued, and the landing consumes them a full
    iteration later."""

    bin_key: object
    issue: object
    land: object
    vranks: int
    n_local: int


def vrank_exchange_two_phase_fn(
    domain: Domain, vgrid: ProcessGrid, n_local: int, ndim: int = None,
    cycle_rescue: bool = True, scatter_impl=None,
) -> VrankTwoPhase:
    """Build the Dev==1 planar vranks two-phase exchange (ISSUE 12).

    All ``V = vgrid.nranks`` ranks live on one device as lane-axis
    blocks of a planar ``[K, V * n]`` matrix, so the "wire" is a pair of
    in-HBM gathers and the issue/complete halves can be separated by a
    whole scan iteration without any collective in flight. Semantics
    mirror :func:`shard_migrate_fused_fn` (receiver-granted flow
    control, cycle rescue, single landing scatter) with plan width
    ``n = n_local`` per vrank: nothing is ever clipped by the plan, so
    ``backlog`` is exactly the flow-control residue.

    The landing scatter preserves the uniqueness invariant of
    :func:`_land_scatter`: per vrank, targets are vacated slots (disjoint
    prefixes of a sort permutation) plus popped stack entries (distinct
    hole ids), globalized onto disjoint column blocks across vranks.

    Note the per-row ``take_along_axis`` gathers here are [V, n]-scale;
    fine on CPU meshes (where this engine is currently gated), but a
    chip session should linearize them like :func:`_plan_rows_batched`
    before lifting the CPU-only restriction.
    """
    V = vgrid.nranks
    n = int(n_local)
    D = domain.ndim if ndim is None else ndim
    rescue = cycle_rescue and V <= 128
    impl = _resolve_scatter_impl(scatter_impl)

    def bin_key(fused: jax.Array) -> jax.Array:
        """[K, V*n] planar matrix -> [V, n] destination-vrank key, with
        the sentinel ``V`` on stayers and holes (the only values
        :func:`..ops.binning.sorted_dest_counts_batched` counts are
        genuine leavers). Routing is the SAME
        :func:`..ops.binning.rank_of_position_planar` the canonical
        planar engines call, so a pipelined step homes every particle on
        exactly the vrank the sequential engine would."""
        m = fused.shape[1]
        alive = fused[-1, :] > 0
        me = (jnp.arange(m, dtype=jnp.int32) // n).astype(jnp.int32)
        pos_f = lax.bitcast_convert_type(fused[:D, :], jnp.float32)
        dest = binning.rank_of_position_planar(pos_f, domain, vgrid)
        key = jnp.where(alive & (dest != me), dest, V).astype(jnp.int32)
        return key.reshape(V, n)

    def issue(key: jax.Array, n_free: jax.Array) -> VrankPlan:
        """Routing sort + receiver-granted flow control + gather plans.
        Reads only the key and the free-slot counts — never the payload
        — so a pipelined caller can issue step k+1 against a matrix
        whose step-k arrivals are still in flight."""
        order, counts, bounds = binning.sorted_dest_counts_batched(key, V)
        desired = counts.astype(jnp.int32)  # [V, V] [src, dst]
        swap = jnp.minimum(desired, desired.T)
        resid = _greedy_alloc(
            desired - swap, jnp.maximum(n_free, 0)
        ).astype(jnp.int32)
        allowed = swap + resid
        if rescue:
            pending = desired - allowed
            F = _cycle_rescue(pending, jnp.sum(allowed, axis=1) == 0)
            allowed = allowed + F
        backlog = jnp.sum(desired - allowed, axis=1).astype(jnp.int32)
        vacated, n_sent = _plan_rows_batched(
            bounds[:, :-1], allowed, order, n
        )
        arr_plan, n_in = _plan_rows_batched(
            bounds[:, :-1].T, allowed.T, order, n,
            seg_rows=jnp.arange(V, dtype=jnp.int32),
        )
        return VrankPlan(
            vacated, n_sent.astype(jnp.int32), arr_plan,
            n_in.astype(jnp.int32), allowed, desired, backlog,
        )

    def land(fused, free_stack, n_free, arr, vacated, n_sent, n_in):
        """Land a gathered ``[K, V, n]`` arrival payload: ONE scatter
        writes payload + alive + hole markers for every vrank, and the
        free-stack update rides the same plan quantities as a fused
        full-width blend (no second pass over the landing rows).
        Row-count agnostic: callers may land an augmented matrix (extra
        key row) through the same kernel. Returns
        ``(fused, free_stack, n_free, dropped [V])``."""
        Kx = fused.shape[0]
        k_idx = jnp.arange(n, dtype=jnp.int32)[None, :]  # [1, n]
        ns = n_sent[:, None]
        ni = n_in[:, None]
        n_pop = jnp.clip(n_in - n_sent, 0, n_free)  # [V]
        dropped = jnp.maximum(n_in - n_sent - n_free, 0).astype(jnp.int32)
        pop_idx = jnp.clip(
            n_free[:, None] - 1 - (k_idx - ns), 0, n - 1
        )
        popped = jnp.take_along_axis(free_stack, pop_idx, axis=1)
        target = jnp.where(
            k_idx < jnp.minimum(ni, ns),
            vacated,
            jnp.where(
                (k_idx >= ns) & (k_idx < ns + n_pop[:, None]),
                popped,
                jnp.where((k_idx >= ni) & (k_idx < ns), vacated, n),
            ),
        )  # [V, n] local targets, sentinel n
        v_off = jnp.arange(V, dtype=jnp.int32)[:, None]
        gtarget = jnp.where(target >= n, V * n, v_off * n + target)
        cols = jnp.where((k_idx < ni)[None, :, :], arr, 0)
        fused = _land_scatter(
            fused, gtarget.reshape(-1), cols.reshape(Kx, V * n), impl
        )
        # free-stack pushes as a full-width blend over the same plan:
        # n_pop and n_push are never both non-zero, so the push base
        # n_free - n_pop is n_free whenever pushes exist
        n_push = jnp.maximum(n_sent - n_in, 0)
        base = n_free - n_pop
        s_idx = jnp.arange(n, dtype=jnp.int32)[None, :]
        push_vals = jnp.take_along_axis(
            vacated,
            jnp.clip(ni + s_idx - base[:, None], 0, n - 1),
            axis=1,
        )
        free_stack = jnp.where(
            (s_idx >= base[:, None]) & (s_idx < (base + n_push)[:, None]),
            push_vals,
            free_stack,
        )
        return fused, free_stack, base + n_push, dropped

    return VrankTwoPhase(bin_key, issue, land, V, n)


def _plan_rows(seg_starts, seg_counts, order, length: int):
    """Expand per-segment (start-in-sorted-order, count) pairs into a flat
    row plan of static ``length``: entry ``j`` is the resident-slot index of
    the ``j``-th planned row (segments concatenated in segment order, the
    first ``count`` rows of each — prefix semantics). Entries ``j >= total``
    are clipped junk; callers mask by ``j < total``.

    All inputs are per-vrank 1-D: ``seg_starts``/``seg_counts`` [n_segs],
    ``order`` [n] (stable sort permutation). Pure searchsorted + gather on
    [length] vectors — cost scales with ``length``, not with n.
    """
    n = order.shape[0]
    cum = jnp.concatenate(
        [jnp.zeros((1,), jnp.int32), jnp.cumsum(seg_counts).astype(jnp.int32)]
    )
    j = jnp.arange(length, dtype=jnp.int32)
    seg = jnp.clip(
        _segment_of_auto(j, cum),
        0,
        seg_counts.shape[0] - 1,
    )
    pos = seg_starts[seg] + (j - cum[seg])
    return order[jnp.clip(pos, 0, n - 1)], cum[-1]


def _plan_rows_batched(seg_starts, seg_counts, order, length: int,
                       seg_rows=None, row_stride: int = None):
    """Batched :func:`_plan_rows` over a leading vrank axis, with every
    gather LINEARIZED into one wide-minor ``jnp.take(..., axis=1)``.

    ``vmap(_plan_rows)`` lowers its ``order[pos]`` to a batched gather
    that costs ~33 ns/element on this stack (round-4 north-star knockout:
    +52.5 ms for a [64, 24537] plan), while the same elements through a
    flat ``[1, V*n]`` axis-1 take cost ~1 ns/element (the arrival
    gather's pattern, phase 5). Inputs: ``seg_starts``/``seg_counts``
    [V, S], ``order`` [V, n]; returns ``(vacated [V, length],
    totals [V])``.

    ``seg_rows`` ([S] int32, round 4 — arrival plans): maps each segment
    to the row of ``order`` it reads — segments of one plan row may live
    in *different* rows (dst ``w`` reads source ``s``'s sorted space at
    segment ``s -> w``). The row index telescopes through the same mask
    (values < S << 2^24, exact in f32) and combines with the local
    position in int32 — positions themselves never exceed n, so the f32
    exactness bound of the einsum is untouched. Returned entries are
    GLOBALIZED: ``seg_row * n + order[seg_row, pos]`` (the [V, length]
    ``row_g * n`` add is O(V*M); pre-globalizing ``order`` instead
    would materialize an O(V*n) temp per step). Default: plan row v
    reads ``order[v]``, values raw.

    ``row_stride`` (ISSUE 4 — the mover-sparse engine): the column
    stride used to GLOBALIZE returned entries in ``seg_rows`` mode.
    Defaults to ``order.shape[-1]``, which conflates two distinct
    widths: the width of ``order`` (indexing) and the width of the
    destination matrix the plan addresses (globalization). The sparse
    fast path plans over a compacted ``[V, B]`` mover block whose
    values index the full ``[K, V * n]`` resident matrix — there
    ``order`` is B wide but the stride must stay ``n``.
    """
    V, S = seg_counts.shape
    n = order.shape[-1]
    stride = n if row_stride is None else row_stride
    cum = jnp.concatenate(
        [
            jnp.zeros((V, 1), jnp.int32),
            jnp.cumsum(seg_counts, axis=1).astype(jnp.int32),
        ],
        axis=1,
    )  # [V, S+1]
    j = jnp.arange(length, dtype=jnp.int32)
    # TELESCOPED segment lookup: with mask[v, j, s] = (j >= cum[v, s+1]),
    # seg = sum_s mask, and any gather from a per-segment table telescopes
    # through the same mask — f[seg[j]] = f[0] + sum_s mask * (f[s+1] -
    # f[s]). One [V, length, S] masked reduction replaces the 65-entry
    # table takes, which cost ~6 ns/element on this stack (round-4
    # diagnostic: +19 ms for two takes at the 64-vrank north-star).
    # Values stay < n = 2^20 << 2^24, exact in f32.
    mask = (
        cum[:, None, 1:] <= j[None, :, None]
    ).astype(jnp.float32)  # [V, length, S]
    d_start = jnp.diff(
        jnp.concatenate(
            [seg_starts, seg_starts[:, -1:]], axis=1
        ).astype(jnp.float32),
        axis=1,
    )  # [V, S]: seg_starts[s+1] - seg_starts[s] (last diff 0 = clamp)
    d_cum = jnp.diff(cum[:, :-1].astype(jnp.float32), axis=1)
    d_cum = jnp.concatenate(
        [d_cum, jnp.zeros((V, 1), jnp.float32)], axis=1
    )  # [V, S]: cum[s+1] - cum[s], clamped at the last segment
    # HIGHEST precision: the default TPU matmul rounds operands to bf16
    # (8-bit mantissa) — diffs reach 2^20 and must multiply exactly
    starts_g = (
        seg_starts[:, :1].astype(jnp.float32)
        + jnp.einsum(
            "vjs,vs->vj", mask, d_start,
            precision=jax.lax.Precision.HIGHEST,
        )
    ).astype(jnp.int32)
    cum_g = (
        jnp.einsum(
            "vjs,vs->vj", mask, d_cum,
            precision=jax.lax.Precision.HIGHEST,
        )
    ).astype(jnp.int32)  # cum[:, 0] == 0
    pos = starts_g + (j[None, :] - cum_g)
    if seg_rows is not None:
        d_row = jnp.diff(
            jnp.concatenate(
                [seg_rows, seg_rows[-1:]]
            ).astype(jnp.float32)
        )  # [S]: seg_rows[s+1] - seg_rows[s] (last diff 0 = clamp)
        row_g = (
            jnp.asarray(seg_rows[0], jnp.float32)
            + jnp.einsum(
                "vjs,s->vj", mask, d_row,
                precision=jax.lax.Precision.HIGHEST,
            )
        ).astype(jnp.int32)  # [V, length]
        idx = row_g * n + jnp.clip(pos, 0, n - 1)
    else:
        v_off = jnp.arange(V, dtype=jnp.int32)[:, None]
        idx = v_off * n + jnp.clip(pos, 0, n - 1)
    # 1-D index vector: the fast axis-1 take lowering keys off flat
    # indices (2-D index arrays fall back to the ~33 ns/elem gather)
    vac = jnp.take(
        order.reshape(1, -1), idx.reshape(-1), axis=1
    ).reshape(V, length)
    if seg_rows is not None:
        vac = row_g * stride + vac
    return vac, cum[:, -1]


def balanced_assignment(cell_loads, n_ranks: int) -> tuple:
    """Static cell -> rank map equalizing per-rank load (host-side, LPT).

    ``cell_loads`` is the measured per-cell ownership histogram ([n_cells]
    row-major, e.g. ``np.bincount`` of cell ids); the classic
    longest-processing-time greedy assigns cells heaviest-first to the
    least-loaded rank, guaranteeing max-bin <= 4/3 optimal. Returns a
    hashable tuple for :func:`shard_migrate_vranks_fn`'s ``assignment``
    (pair it with the cell grid as ``cells``). Slabs can then be sized
    from ``max(bin loads)`` — near the MEAN cell load times
    ``n_cells / n_ranks`` instead of the hottest cell times the same,
    which is the whole point under imbalance.
    """
    import numpy as np

    loads = np.asarray(cell_loads, dtype=np.int64)
    if loads.ndim != 1 or loads.size < n_ranks:
        raise ValueError(
            f"need >= {n_ranks} cells, got shape {loads.shape}"
        )
    order = np.argsort(-loads, kind="stable")
    bins = np.zeros((n_ranks,), np.int64)
    assign = np.zeros(loads.shape, np.int32)
    for c in order:
        r = int(np.argmin(bins))
        assign[c] = r
        bins[r] += loads[c]
    return tuple(int(x) for x in assign)


def shard_migrate_vranks_fn(
    domain: Domain,
    dev_grid: ProcessGrid,
    vgrid: ProcessGrid,
    capacity: int,
    ndim: int = None,
    local_budget: int = None,
    scatter_impl=None,  # None | "overlay" | "xla" | "rows" | bool
    cycle_rescue: bool = True,
    cells: ProcessGrid = None,
    assignment: tuple = None,
    mover_cap: int = None,
):
    """Migration over a ``dev_grid * vgrid`` process grid, planar layout.

    The full Cartesian grid has shape ``dev_grid.shape * vgrid.shape``
    (elementwise): device cell ``i // v`` and vrank cell ``i % v`` per axis.
    Each device owns ``V = vgrid.nranks`` subdomain slabs, side by side on
    the lane axis of one planar ``[K, V * n]`` matrix (vrank ``v`` owns
    columns ``[v * n, (v + 1) * n)``).

    Two-tier exchange (the TPU answer to MPI ranks on fewer nodes):

    * **On-device vrank->vrank traffic never touches a padded collective
      layout.** Migrants are routed compactly: one stable sort groups them,
      [V, V] count matrices allocate arrivals, and a single gather + single
      scatter sized to ``local_budget`` columns move exactly the migrants
      (the round-1 design paid gather+scatter over the full ``R*C`` padded
      layout — ~80 ns/row over mostly-empty slots dominated the step).
      Local routing is **lossless**: senders see receiver free-slot counts
      directly (same device) and hold rows back (``backlog``) instead of
      ever dropping an arrival.
    * **Cross-device traffic** rides a ``[Dev, V_src, V_dst, K, C]``
      ``lax.all_to_all`` over ICI, ``capacity`` columns per (source vrank,
      destination vrank) pair, and is **receiver-granted**: desired counts
      fly first, each destination vrank greedily grants within its free
      slots, grants fly back, and only granted rows are packed — excess
      movers backlog instead of ever hitting a full receiver (the wire
      never carries what cannot land; ``dropped_recv`` stays a safety
      counter). Mutually-full rotation cycles — including cycles that
      span devices — are drained by the cycle rescue (one forced,
      stack-financed row per cycle edge per step; global pass up to 128
      global ranks). When ``Dev == 1`` the collectives and their
      buffers compile away entirely.

    Signature of the returned per-shard fn:
      ``MigrateState -> (MigrateState, MigrateStats)``
    with ``state.fused [K, V * n]``, ``free_stack [V, n]``, ``n_free [V]``;
    stats entries are ``[V]`` per device (global device-major order).
    ``local_budget`` bounds on-device migrants per (vrank, step) in each
    direction (default ``V * capacity``, matching the round-1 total) — the
    landing scatter's cost scales with this PLAN length, not with actual
    migrants, so size it to a few x the expected per-step migration;
    ``capacity`` bounds cross-device migrants per (source vrank,
    destination vrank) pair. ``scatter_impl`` selects the landing-scatter
    implementation: ``None`` (env / platform default — "overlay" on TPU),
    ``"overlay"`` (planar one-hot overlay kernel), ``"xla"``, or
    ``"rows"`` (round-2 per-row-store kernel, a kept negative result);
    bools are accepted for backward compatibility (True = "rows",
    False = "xla"). See :func:`_resolve_scatter_impl`.

    **Load-balanced assignment** (``cells`` + ``assignment``): by default a
    vrank IS a spatial subdomain of the ``dev_grid * vgrid`` product grid —
    under load imbalance every slab must then be sized for the hottest
    subdomain (9.4x slot waste at 7x imbalance, round-2 verdict). Passing
    ``cells`` (the spatial cell grid, e.g. 4x4x4) with ``assignment`` (a
    static tuple mapping row-major cell id -> global rank ``dev * V + v``,
    typically from :func:`balanced_assignment` over a measured ownership
    histogram) decouples storage from space: each vrank owns an arbitrary
    SET of cells with near-equal total load, so uniform static slabs sized
    ~mean load suffice. Only the binning changes (cell id -> one small
    table gather); all routing, flow control and landing below operate on
    rank ids and are untouched. This is the classic HPC answer to
    imbalance — balance the decomposition, not the buffers — in
    static-shape TPU form.

    **Mover-sparse fast path** (``mover_cap``, ISSUE 4): at ~2%
    migration the dense step still pays full-array sort/pack/landing
    over every resident row. Passing ``mover_cap`` (a static mover
    budget per vrank per step, e.g. ``local_budget``) builds a second
    engine behind ONE scalar ``lax.cond``: the two-level selection
    compacts the leavers into a dense ``[V, mover_cap]`` block
    (:func:`..ops.binning.sorted_mover_block`), the grant tables are
    computed on the [V, V] count matrices exactly as the dense engine
    does, and when the residence/overflow guard holds — selection exact,
    nothing clipped (zero backlog), movers and arrivals within
    ``mover_cap`` — landing gathers and scatters only mover columns
    while stayer rows are never touched. Guard-violating steps run the
    dense engine unchanged; outputs are bit-identical either way (the
    guard conditions make the dense plans collapse to the leaver prefix
    the block reproduces). Only built at ``Dev == 1`` (cross-device
    traffic is already mover-sparse and a cond'd collective would
    deadlock); with ``mover_cap`` set the stats carry a ``fast_path``
    [V] leaf (1 = fast branch taken) — ``None`` otherwise. Size
    ``mover_cap`` like ``local_budget`` and grow it with
    :class:`..api.MoverCapacity` on sustained fallbacks.
    """
    axes = dev_grid.axis_names
    V = vgrid.nranks
    Dev = dev_grid.nranks
    C = capacity
    D = domain.ndim if ndim is None else ndim
    M = V * C if local_budget is None else local_budget
    R_total = Dev * V
    if (cells is None) != (assignment is None):
        raise ValueError("cells and assignment must be passed together")
    if assignment is not None:
        if len(assignment) != cells.nranks:
            raise ValueError(
                f"assignment has {len(assignment)} entries for "
                f"{cells.nranks} cells"
            )
        bad = [g for g in assignment if not 0 <= g < R_total]
        if bad:
            raise ValueError(
                f"assignment targets outside [0, {R_total}): {bad[:4]}"
            )
        full_grid = cells
    else:
        full_shape = tuple(
            d * v for d, v in zip(dev_grid.shape, vgrid.shape)
        )
        full_grid = ProcessGrid(full_shape, axis_names=dev_grid.axis_names)
    # static plan lengths: most rows a vrank can send / receive in a step
    S_max = M + ((Dev - 1) * V * C if Dev > 1 else 0)
    P = max(M, S_max)
    if cycle_rescue and Dev > 1 and R_total > 128:
        # same degradation signal as the flat engine (round-3 weak item
        # 5): above 128 global ranks the GLOBAL cycle rescue is off
        # (R^2 log R closure) and only the per-device rescue remains —
        # cross-device rotation cycles backlog again.
        import warnings

        warnings.warn(
            f"global cycle_rescue disabled: {R_total} global ranks > 128 "
            f"(the all-gathered [R, R] boolean-closure cost grows as "
            f"R^2 log R). Per-device cycles still drain, but rotation "
            f"cycles SPANNING devices will backlog — watch "
            f"utils.stats.detect_stall, or pass cycle_rescue=False to "
            f"silence this warning.",
            stacklevel=2,
        )
    scatter_impl = _resolve_scatter_impl(scatter_impl)

    def fn(state: MigrateState, dest_key=None):
        flat, free_stack, n_free = state  # [K, V*n], [V, n], [V]
        K = flat.shape[0]
        n = flat.shape[1] // V
        me_dev = lax.axis_index(axes).astype(jnp.int32)
        my_v = jnp.arange(V, dtype=jnp.int32)  # vrank ids on this device

        # ---- binning: per-axis fused elementwise chains (no stacked
        # [D, m] intermediates — each axis's wrap+floor+clip+accumulate
        # fuses into one pass over [V*n]; the stacked helper variant
        # measured 22x its bandwidth roofline in the knockout profile).
        # A caller may pass a precomputed ``dest_key`` [V, n] instead
        # (device-major global rank, sentinel R_total for holes/stayers)
        # — the fused Pallas drift+wrap+bin kernel emits it in the same
        # streaming pass as the drift (ops/pallas_driftbin.py,
        # bit-identical to this chain by test).
        with traced_span("mig:drift"):
            if dest_key is None:
                alive = flat[-1, :].reshape(V, n) > 0
                dest_dev = jnp.zeros((V * n,), jnp.int32)
                dest_v = jnp.zeros((V * n,), jnp.int32)
                for d in range(D):
                    p = _pos_row(flat, d)
                    lo = jnp.asarray(domain.lo[d], p.dtype)
                    ext = jnp.asarray(domain.extent[d], p.dtype)
                    if domain.periodic[d]:
                        # reciprocal-multiply wrap (see shard_migrate_fused_fn)
                        p = lo + binning.remainder_fast(
                            p - lo, domain.extent[d]
                        )
                        p = jnp.where(p >= lo + ext, lo, p)
                    inv_w = jnp.asarray(full_grid.shape[d], p.dtype) / ext
                    cell_d = jnp.clip(
                        jnp.floor((p - lo) * inv_w).astype(jnp.int32),
                        0,
                        full_grid.shape[d] - 1,
                    )
                    if assignment is not None:
                        # accumulate the full row-major cell id; ownership
                        # comes from the static assignment table below
                        dest_v = dest_v + cell_d * jnp.int32(
                            full_grid.strides[d]
                        )
                    else:
                        vs = vgrid.shape[d]
                        if dev_grid.shape[d] == 1:
                            # single device slab on this axis: cell_d < vs
                            # statically, so the // and % are identities —
                            # int32 div/mod have no native VPU lowering and
                            # cost real passes over [V*n] (round-4 phase-1
                            # attribution)
                            dest_v = dest_v + cell_d * vgrid.strides[d]
                        else:
                            dest_dev = (
                                dest_dev + (cell_d // vs) * dev_grid.strides[d]
                            )
                            dest_v = dest_v + (cell_d % vs) * vgrid.strides[d]
                if assignment is not None:
                    # one gather from the tiny [n_cells] table: cell ->
                    # global rank
                    g = jnp.take(
                        jnp.asarray(assignment, jnp.int32), dest_v, axis=0
                    )
                    dest_dev = g // V
                    dest_v = g - dest_dev * V
                dest_dev = dest_dev.reshape(V, n)
                dest_v = dest_v.reshape(V, n)
                staying = (dest_dev == me_dev) & (dest_v == my_v[:, None])
                leaving = alive & ~staying
                # device-major global destination: dev * V + vrank
                dest_key = jnp.where(
                    leaving, dest_dev * V + dest_v, R_total
                ).astype(jnp.int32)  # [V, n]

        def _step(flat, free_stack, n_free, dest_key):
            """One full DENSE redistribute step given a precomputed
            destination key — the planar vranks engine, O(residents)
            per step. Extracted as a closure so the mover-sparse fast
            path (dispatch below) can route guard-violating steps here
            through ONE scalar ``lax.cond``; without ``mover_cap`` it
            is simply called directly (status quo)."""
            # NOTE a flat composite-key sort (one [V*n] sort replacing the V
            # vmapped sorts) was measured and REJECTED: the vmapped
            # sorted_dest_counts is 5.7 ms at 8x1M while the flat composite
            # sort alone is 9.8 ms, and the boundary lookup it then needs —
            # searchsorted(method="sort"), 72 queries over 8.4M keys — costs
            # a pathological ~97 ms on this stack (scripts/microbench_sort.py).
            # ALSO REJECTED (late round 4): lax.top_k with k = plan capacity
            # on a packed descending key — the order below is only consumed
            # up to the first `leavers` entries, so a truncated selection
            # would suffice semantically, but top_k lowers 2-5.8x SLOWER
            # than the full packed sort (both packing in-loop: 14.6 vs
            # 2.5 ms at 8x1M, 111.2 vs 56.8 at 64x1M —
            # scripts/microbench_topk.py); a Pallas stream compaction was
            # sketched and dropped: within-chunk placement needs a [T, T]
            # one-hot whose VPU construction (~275G elem ops at 64M) dwarfs
            # the sort it would replace.
            # Two-level leaver selection (binning.sorted_dest_counts_batched):
            # chunk sorts + one small candidate sort reproduce the consumed
            # leaver prefix bit-for-bit at ~2.4x the flat packed sort's speed
            # (56.3 -> 23.6 ms at 64x1M, scripts/microbench_select.py); a
            # scalar guard cond-routes dense steps to the flat sort.
            # order is prefix-only (zero tail past the leavers; see
            # sorted_dest_counts_batched) — reads below slice/mask at counts.
            with traced_span("mig:bin"):
                order, counts, bounds = binning.sorted_dest_counts_batched(
                    dest_key, R_total
                )  # [V, n], [V, R_total], [V, R_total + 1]
            with traced_span("mig:grant"):
                leavers = jnp.sum(counts, axis=1).astype(jnp.int32)  # [V]

                # ---- local allocation: [V_src, V_dst] on this device --------
                loc0 = me_dev * V
                loc_counts = lax.dynamic_slice_in_dim(counts, loc0, V, axis=1)
                loc_starts = lax.dynamic_slice_in_dim(bounds, loc0, V, axis=1)
                # per-source budget M: prefix truncation in destination order
                # (rel = each pair segment's offset within the source's local
                # run)
                rel_start = loc_starts - loc_starts[:, :1]
                rel_end = rel_start + loc_counts
                eff = jnp.clip(
                    jnp.minimum(rel_end, M) - jnp.minimum(rel_start, M),
                    0,
                ).astype(jnp.int32)

                # remote sends first: they vacate slots independently of the
                # local allocation, so they seed the receiver-capacity
                # fixpoint. With Dev > 1 the sends are RECEIVER-GRANTED
                # (lossless receive): the desired per-pair counts fly first,
                # each destination vrank greedily grants within its pre-step
                # free slots, the grants fly back, and only granted rows are
                # packed — ungranted rows stay resident and retry (backlog).
                # Remote arrivals are then structurally <= n_free and the
                # remote landing never drops. (Unlike the flat path there is no
                # cross-device swap financing — the remote landing pops free
                # slots only — so mutually-full vranks on different devices
                # trade through backlog.)
                if Dev > 1:
                    desired_rem = jnp.minimum(counts, C).astype(jnp.int32)
                    g_ids = jnp.arange(R_total, dtype=jnp.int32)
                    is_local_g = (g_ids >= loc0) & (g_ids < loc0 + V)
                    desired_rem = jnp.where(
                        is_local_g[None, :], 0, desired_rem
                    )  # [V_src, R_total]
                    # desired -> receiver (same transpose layout as the
                    # payload)
                    desired_t = desired_rem.reshape(V, Dev, V).transpose(
                        1, 0, 2
                    )
                    recv_desired = lax.all_to_all(
                        desired_t, axes, split_axis=0, concat_axis=0,
                        tiled=True,
                    ).transpose(2, 0, 1).reshape(
                        V, Dev * V
                    )  # [V_dst, S_global]
                    grants = _greedy_alloc(
                        recv_desired.T, jnp.maximum(n_free, 0)
                    ).T.astype(jnp.int32)  # [V_dst, S_global]
                    # grants -> sender (reverse layout)
                    grants_t = grants.reshape(V, Dev, V).transpose(1, 0, 2)
                    grants_back = lax.all_to_all(
                        grants_t, axes, split_axis=0, concat_axis=0, tiled=True
                    ).transpose(2, 0, 1).reshape(V, Dev * V)  # [V_src, G_dst]
                    rem_sent_full = jnp.minimum(desired_rem, grants_back)
                    sent_remote = jnp.sum(rem_sent_full, axis=1).astype(
                        jnp.int32
                    )
                    # actual arrivals == my grants (greedy allocates within
                    # each source's desire, so grants <= recv_desired always)
                    recv_counts_rem = grants
                    n_in_rem = jnp.sum(recv_counts_rem, axis=1).astype(
                        jnp.int32
                    )
                else:
                    sent_remote = jnp.zeros((V,), jnp.int32)
                    n_in_rem = jnp.zeros((V,), jnp.int32)

                # Receiver capacity: arrivals may use current free slots PLUS
                # slots vacated by the receiver's own sends this step —
                # otherwise fully-occupied vranks that need to swap livelock.
                # Sends depend on destination capacities (circular), so solve
                # by monotone-increasing fixpoint, seeded with pairwise swaps
                # (which are self-financing: each vrank's swap arrivals exactly
                # equal its swap departures). Every truncation of the
                # increasing orbit is safe: iteration t's arrivals <= n_free +
                # sends(t-1) + remote <= n_free + actual sends.
                swap = jnp.minimum(eff, eff.T).astype(jnp.int32)
                # trim so swap arrivals fit the [M] arrival plan per dst, then
                # re-symmetrize (min with transpose keeps column sums <= M and
                # restores the self-financing arrivals == departures invariant)
                swap = _greedy_alloc(
                    swap, jnp.full((V,), M, jnp.int32)
                ).astype(jnp.int32)
                swap = jnp.minimum(swap, swap.T)
                res_eff = eff - swap
                res = jnp.zeros_like(eff)
                # free slots already promised to granted remote arrivals are
                # off the table for local arrivals (remote lands after local
                # and only pops the stack)
                n_free_local = n_free - n_in_rem
                for _ in range(V):
                    cap_res = jnp.minimum(
                        M - jnp.sum(swap, axis=0),
                        n_free_local + sent_remote + jnp.sum(res, axis=1),
                    ).astype(jnp.int32)
                    res = _greedy_alloc(
                        res_eff, jnp.maximum(cap_res, 0)
                    ).astype(jnp.int32)
                allowed = swap + res  # [V_src, V_dst]
                if cycle_rescue and (Dev == 1 or R_total > 128):
                    # drain full-vrank rotation cycles on THIS device (all the
                    # tables are local — no collective needed). A cycle is only
                    # forced if every member stays within the [M] arrival/send
                    # plans (+1 row); partial application would break the
                    # self-financing pairing, so the guard is per whole cycle.
                    # (Above 128 global ranks the global pass below is off —
                    # matching the flat engine's R^2 log R closure bound — and
                    # this per-device rescue is the remaining guarantee.)
                    pending_loc = (res_eff - res).astype(jnp.int32)
                    sends_zero = (
                        jnp.sum(allowed, axis=1) + sent_remote
                    ) == 0
                    ok = (jnp.sum(allowed, axis=1) < M) & (
                        jnp.sum(allowed, axis=0) < M
                    )
                    allowed = allowed + _cycle_rescue(
                        pending_loc, sends_zero, ok
                    )
                elif cycle_rescue:
                    # GLOBAL rescue (round-3 verdict item 6): a rotation cycle
                    # that SPANS devices has no swap financing in the grant
                    # phase (remote grants draw on free slots only), so at zero
                    # free slots it backlogs under the normal protocol. Gather
                    # the full pending matrix, run the same functional-graph
                    # closure the flat engine uses, and force one row per cycle
                    # edge. The forced arrivals are financed by the forced
                    # departures through the EXISTING landing machinery: a
                    # member's forced remote departure vacates a slot that the
                    # local landing phase pushes onto the free stack
                    # (n_push = n_sent - n_in_local), and the remote landing —
                    # which runs after — pops exactly that slot; local-edge
                    # forced arrivals land in the vacated-slot plan directly.
                    # Every tier stays lossless at zero holes.
                    pending_loc = (res_eff - res).astype(jnp.int32)
                    # local cols are 0
                    pending_rows = desired_rem - rem_sent_full
                    pending_rows = lax.dynamic_update_slice(
                        pending_rows, pending_loc, (jnp.int32(0), loc0)
                    )  # [V, R_total]
                    sent_loc_v = jnp.sum(allowed, axis=1).astype(jnp.int32)
                    recv_loc_v = jnp.sum(allowed, axis=0).astype(jnp.int32)

                    def gat(x):
                        return lax.all_gather(x, axes).reshape(
                            (R_total,) + x.shape[1:]
                        )

                    pending_g = gat(pending_rows)  # [R_total, R_total]
                    sends_zero_g = gat(sent_loc_v + sent_remote) == 0
                    sent_loc_g = gat(sent_loc_v)
                    recv_loc_g = gat(recv_loc_v)
                    rem_sent_g = gat(rem_sent_full)  # [R_total, R_total]
                    g_all = jnp.arange(R_total, dtype=jnp.int32)
                    succ_g = jnp.argmax(pending_g > 0, axis=1)
                    same_dev = (succ_g // V) == (g_all // V)
                    # per-member guard on ITS forced edge (v -> succ(v)); every
                    # cycle edge is thus checked via its sender. Local edge:
                    # sender's local-send plan AND receiver's [M] arrival plan
                    # have room. Remote edge: the (v, succ) pair buffer has a
                    # free slot (covers both ends; the arrival pops the slot
                    # the departure pushes).
                    ok_g = jnp.where(
                        same_dev,
                        (sent_loc_g < M) & (recv_loc_g[succ_g] < M),
                        rem_sent_g[g_all, succ_g] < C,
                    )
                    F = _cycle_rescue(pending_g, sends_zero_g, ok_g)
                    F_rows = lax.dynamic_slice(
                        F, (loc0, jnp.int32(0)), (V, R_total)
                    )  # my vranks' forced sends
                    F_loc = lax.dynamic_slice(
                        F_rows, (jnp.int32(0), loc0), (V, V)
                    )
                    allowed = allowed + F_loc
                    is_local_g2 = (g_all >= loc0) & (g_all < loc0 + V)
                    F_rem = jnp.where(is_local_g2[None, :], 0, F_rows)
                    rem_sent_full = rem_sent_full + F_rem
                    sent_remote = jnp.sum(rem_sent_full, axis=1).astype(
                        jnp.int32
                    )
                    F_cols = lax.dynamic_slice(
                        F, (jnp.int32(0), loc0), (R_total, V)
                    )  # forced arrivals into my vranks, by global source
                    F_cols_rem = jnp.where(is_local_g2[:, None], 0, F_cols)
                    recv_counts_rem = recv_counts_rem + F_cols_rem.T
                    n_in_rem = jnp.sum(recv_counts_rem, axis=1).astype(
                        jnp.int32
                    )
                sent_local = jnp.sum(allowed, axis=1).astype(jnp.int32)
                n_in_local = jnp.sum(allowed, axis=0).astype(jnp.int32)

            # ---- remote sends: [Dev, V_src, V_dst, K, C] over ICI ---------
            if Dev > 1:
                # build the send buffer by index arithmetic + one flat column
                # gather; global rank ids enumerate dev-major (columns
                # 0..R_total-1 of the count/bound tables)
                c_i = jnp.arange(C, dtype=jnp.int32)
                cnt_sg = rem_sent_full  # [V_src, R_total]
                start_sg = bounds[:, :R_total]
                valid = c_i[None, None, :] < cnt_sg[:, :, None]
                pos = start_sg[:, :, None] + c_i[None, None, :]
                # flat 1-D take (same ~33 ns/elem batched-gather avoidance
                # as the plan paths; take_along_axis with 2-D indices falls
                # back to the slow lowering)
                row = jnp.take(
                    order.reshape(1, -1),
                    (
                        my_v[:, None] * n
                        + jnp.clip(pos, 0, n - 1).reshape(V, -1)
                    ).reshape(-1),
                    axis=1,
                ).reshape(V, Dev * V, C)
                gsrc = my_v[:, None, None] * n + row
                vals = jnp.take(flat, gsrc.reshape(-1), axis=1).reshape(
                    K, V, Dev, V, C
                )
                send = jnp.where(
                    valid.reshape(V, Dev, V, C)[None], vals, 0
                )
                # [K, V_src, Dev, V_dst, C] -> [Dev, V_src, V_dst, K, C]
                send = send.transpose(2, 1, 3, 0, 4)
                with traced_span("mig:exchange"):
                    recv = lax.all_to_all(
                        send, axes, split_axis=0, concat_axis=0, tiled=True
                    )  # [Dev_src, V_src, V_dst, K, C]
                    # per-dst pools: [V_dst, K, Dev_src * V_src * C]; arrival
                    # counts (recv_counts_rem) were derived locally in the
                    # grant phase — no extra counts exchange needed
                    recv = recv.transpose(2, 3, 0, 1, 4).reshape(
                        V, K, Dev * V * C
                    )

            with traced_span("mig:stack"):
                n_sent = sent_local + sent_remote

                # ---- vacated slots: all columns leaving each vrank ----------
                # segments: V local pairs (prefix `allowed`) then, with
                # Dev > 1, R_total global ranks (remote prefix
                # `rem_sent_full`).
                if Dev > 1:
                    seg_starts = jnp.concatenate(
                        [loc_starts, bounds[:, :R_total]], axis=1
                    )
                    seg_counts = jnp.concatenate(
                        [allowed, rem_sent_full], axis=1
                    )
                    vacated, _tot = _plan_rows_batched(
                        seg_starts, seg_counts, order, P
                    )  # [V, P] (linearized — vmapped gathers cost ~33 ns/elem)
                elif P <= n:
                    # UNCLIPPED fast path (single-device): stayers sort to the
                    # END (sentinel key R_total), so leavers are a PREFIX of
                    # sorted space grouped by dest, and `eff`'s budget cap is a
                    # prefix truncation — when the grant phase clips nothing
                    # (allowed == eff, the steady-state common case) the slow
                    # plan's positions reduce to pos[v, j] = j exactly, i.e.
                    # vacated IS order[:, :P]. The telescoped-einsum plan + its
                    # ~19 ns/element order[pos] take (round-4 north-star
                    # knockout: +30 ms, the phase-4 floor) collapse to one
                    # slice. Entries beyond sum(allowed) differ between the
                    # branches but are never read (every consumer masks at
                    # k < n_sent). Clipped steps take the exact slow path.
                    if os.environ.get("MPI_GRID_VACATED_PLAN") == "slow":
                        # diagnostic escape hatch (trace-time): force the
                        # general plan to measure what the fast path saves in
                        # context
                        vacated = _plan_rows_batched(
                            loc_starts, allowed, order, P
                        )[0]
                    else:
                        unclipped = jnp.all(allowed == eff)
                        vacated = lax.cond(
                            unclipped,
                            lambda: lax.slice_in_dim(order, 0, P, axis=1),
                            lambda: _plan_rows_batched(
                                loc_starts, allowed, order, P
                            )[0],
                        )
                else:
                    vacated, _tot = _plan_rows_batched(
                        loc_starts, allowed, order, P
                    )

            # ---- local arrivals: one column gather sized to the budget ----
            # dst w's arrivals: sources in order, first allowed[s, w] rows of
            # each (s -> w) segment; arrival columns are globally indexed so
            # one flat gather serves every vrank.
            # dst w's plan walks SOURCE s's sorted space at segment (s -> w):
            # same telescoped/flat-take machinery as the vacated plan
            # (seg_rows maps segment s to order row s and globalizes the
            # result to s * n + row; the vmapped `order[s, pos]` form this
            # replaces pays the ~33 ns/element batched-gather toll — the
            # round-4 knockout hid it inside the in-context landing phase).
            with traced_span("mig:pack"):
                arr_src, _ = _plan_rows_batched(
                    loc_starts.T, allowed.T, order, M,
                    seg_rows=jnp.arange(V, dtype=jnp.int32),
                )  # [V_dst, M] global source columns
                arr_cols = _gather_plan_cols(flat, arr_src)  # [K, V, M]

            with traced_span("mig:stack"):
                # ---- landing plan: one flat scatter for arrivals + holes ----
                k_idx = jnp.arange(P, dtype=jnp.int32)

                def land_plan(vac, nin, nsent, nf):
                    n_pop = jnp.clip(nin - nsent, 0, nf)
                    pop_idx = jnp.clip(nf - 1 - (k_idx - nsent), 0, n - 1)
                    target = jnp.where(
                        k_idx < jnp.minimum(nin, nsent),
                        vac,
                        jnp.where(
                            (k_idx >= nsent) & (k_idx < nsent + n_pop),
                            jnp.zeros((), jnp.int32),  # replaced below (stack)
                            jnp.where(
                                (k_idx >= nin) & (k_idx < nsent), vac, n
                            ),
                        ),
                    )
                    return target, n_pop, pop_idx

                targets, n_pop, pop_idx = jax.vmap(land_plan)(
                    vacated, n_in_local, n_sent, n_free
                )
                # The pop positions are an AFFINE sequence (stack head
                # downward: nf-1, nf-2, ... for k in [nsent, nsent+n_pop)), so
                # the gather is really a reversed contiguous window: slice it,
                # reverse it, and shift it into k-alignment with one more
                # dynamic slice — [P]-sized copies instead of a V*P-element
                # random gather.
                W2 = min(P, n)  # window length (P can exceed n in tiny tests)

                def pops_window(fs_v, nf, nsent):
                    start = jnp.clip(nf - W2, 0, n - W2)
                    win_rev = lax.dynamic_slice(fs_v, (start,), (W2,))[::-1]
                    # win_rev[i] = fs_v[start + W2 - 1 - i]; want
                    # pops[k] = fs_v[nf - 1 - (k - nsent)] = win_rev[k + s],
                    # s = start + W2 - nf - nsent  (every in-use k lands inside
                    # the window; out-of-use entries read the zero pads and are
                    # masked by use_pop below)
                    s = start + W2 - nf - nsent
                    buf = jnp.concatenate(
                        [
                            jnp.zeros((P,), fs_v.dtype),
                            win_rev,
                            jnp.zeros((P,), fs_v.dtype),
                        ]
                    )
                    return lax.dynamic_slice(buf, (s + P,), (P,))

                pops = jax.vmap(pops_window)(free_stack, n_free, n_sent)
                use_pop = (k_idx[None, :] >= n_sent[:, None]) & (
                    k_idx[None, :] < (n_sent + n_pop)[:, None]
                )
                targets = jnp.where(use_pop, pops, targets)
                # global column ids; sentinel n -> out of range of [V*n]
                # (dropped)
                gtargets = jnp.where(
                    targets >= n, V * n, my_v[:, None] * n + targets
                )
                cols_w = jnp.zeros((K, V, P), flat.dtype).at[:, :, :M].set(
                    arr_cols
                )
                cols_w = jnp.where(
                    (k_idx[None, :] < n_in_local[:, None])[None], cols_w, 0
                )
            with traced_span("mig:unpack"):
                flat = _land_scatter(
                    flat, gtargets.reshape(-1), cols_w.reshape(K, V * P),
                    scatter_impl,
                )

            with traced_span("mig:stack"):
                # ---- free-stack update (contiguous window blend) ------------
                n_push = jnp.maximum(n_sent - n_in_local, 0)
                free_stack, n_free = jax.vmap(_stack_push_pop)(
                    free_stack, n_free, n_pop, n_push, vacated, n_in_local
                )

                # ---- remote landing: pops only, overflow counted ------------
                if Dev > 1:
                    P_rem = Dev * V * C
                    kr = jnp.arange(P_rem, dtype=jnp.int32)

                    def land_remote(f, fs, nf, pool, rcnt):
                        # f [K, n] (one vrank's columns), pool [K, P_rem]
                        cum = jnp.concatenate(
                            [jnp.zeros((1,), jnp.int32), jnp.cumsum(rcnt)]
                        ).astype(jnp.int32)
                        nin = cum[-1]
                        # cum here has Dev*V + 1 entries (scales with the whole
                        # machine): use the auto helper (merge-sort
                        # searchsorted beyond O(tens) segments)
                        s = jnp.clip(
                            _segment_of_auto(kr, cum), 0, Dev * V - 1
                        )
                        src_slot = jnp.clip(
                            s * C + (kr - cum[s]), 0, P_rem - 1
                        )
                        arrivals = jnp.take(pool, src_slot, axis=1)
                        npop = jnp.minimum(nin, nf)
                        dropped = (nin - npop).astype(jnp.int32)
                        pop_i = jnp.clip(nf - 1 - kr, 0, n - 1)
                        tgt = jnp.where(kr < npop, fs[pop_i], n)
                        f = f.at[:, tgt].set(
                            jnp.where((kr < nin)[None, :], arrivals, 0),
                            mode="drop",
                        )
                        return f, nf - npop, nin, dropped

                    flat3, n_free, n_in_rem, dropped_recv = jax.vmap(
                        land_remote,
                        in_axes=(1, 0, 0, 0, 0),
                        out_axes=(1, 0, 0, 0),
                    )(flat.reshape(K, V, n), free_stack, n_free, recv,
                      recv_counts_rem)
                    flat = flat3.reshape(K, V * n)
                    received = n_in_local + n_in_rem
                else:
                    dropped_recv = jnp.zeros((V,), jnp.int32)
                    received = n_in_local

                backlog = (leavers - n_sent).astype(jnp.int32)
                population = jnp.sum(
                    (flat[-1, :].reshape(V, n) > 0).astype(jnp.int32), axis=1
                )
                # my V rows of the global [R_total, R_total] flow matrix:
                # remote granted sends with the local block overlaid (both
                # tables are already live for the pack phase — pure stacking,
                # no collective, no host sync). With Dev == 1 the local table
                # IS the full matrix.
                if Dev > 1:
                    flow_rows = lax.dynamic_update_slice(
                        rem_sent_full, allowed, (jnp.int32(0), loc0)
                    )  # [V, R_total]
                else:
                    flow_rows = allowed
                stats = MigrateStats(
                    sent=n_sent,
                    received=received,
                    population=population,
                    backlog=backlog,
                    dropped_recv=dropped_recv,
                    flow=flow_rows,
                )
            return MigrateState(flat, free_stack, n_free), stats

        # ---- engine dispatch: mover-sparse fast path (ISSUE 4) --------
        # Built only when the caller passes ``mover_cap`` AND the whole
        # grid lives on one device: cross-device traffic already rides a
        # mover-sparse C-padded all_to_all, and a cond'd collective
        # would deadlock unless every device took the same branch.
        # Static infeasibility (selection cannot shrink the problem,
        # packing overflow, MPI_GRID_SELECT=flat) also runs dense — with
        # a [V] zeros ``fast_path`` leaf so the stats pytree is uniform
        # for a given call signature.
        B = None
        if mover_cap is not None and Dev == 1:
            B = max(1, min(int(mover_cap), n))
            sel_chunk, sel_cap = binning.sparse_select_params(n, B)
            if not binning.sparse_select_feasible(
                n, R_total, chunk=sel_chunk, cap=sel_cap
            ):
                B = None
        if B is None:
            out_state, stats = _step(flat, free_stack, n_free, dest_key)
            if mover_cap is not None:
                stats = stats._replace(
                    fast_path=jnp.zeros((V,), jnp.int32)
                )
            return out_state, stats

        # ---- shared sparse prefix: O(movers) selection + grant tables -
        # The two-level selection compacts the leavers into a dense
        # [V, B] mover block (exact iff no chunk overflows sel_cap —
        # the ``ok_sel`` scalar); the [V, V] grant fixpoint below is the
        # verbatim dense-engine allocation (Dev == 1 terms only), so
        # under the guard ``allowed_s`` IS the dense engine's ``allowed``.
        with traced_span("mig:select"):
            block_rows, s_counts, s_bounds, ok_sel = (
                binning.sorted_mover_block(
                    dest_key, R_total, B, chunk=sel_chunk, cap=sel_cap
                )
            )  # [V, B], [V, V], [V, V + 1] (R_total == V at Dev == 1)
        with traced_span("mig:grant"):
            loc_counts = s_counts
            loc_starts = s_bounds[:, :V]
            rel_start = loc_starts - loc_starts[:, :1]
            rel_end = rel_start + loc_counts
            eff = jnp.clip(
                jnp.minimum(rel_end, M) - jnp.minimum(rel_start, M), 0
            ).astype(jnp.int32)
            swap = jnp.minimum(eff, eff.T).astype(jnp.int32)
            swap = _greedy_alloc(
                swap, jnp.full((V,), M, jnp.int32)
            ).astype(jnp.int32)
            swap = jnp.minimum(swap, swap.T)
            res_eff = eff - swap
            res = jnp.zeros_like(eff)
            for _ in range(V):
                cap_res = jnp.minimum(
                    M - jnp.sum(swap, axis=0),
                    n_free + jnp.sum(res, axis=1),
                ).astype(jnp.int32)
                res = _greedy_alloc(res_eff, jnp.maximum(cap_res, 0)).astype(
                    jnp.int32
                )
            allowed_s = (swap + res).astype(jnp.int32)
            n_sent_s = jnp.sum(allowed_s, axis=1).astype(jnp.int32)
            n_in_s = jnp.sum(allowed_s, axis=0).astype(jnp.int32)
            # Residence/overflow guard, ONE scalar (a vmapped cond would
            # lower to a select and run both branches):
            #   * ok_sel — the mover block holds every leaver, exactly;
            #   * allowed_s == loc_counts — nothing was clipped by budget,
            #     free slots, or grants. Since allowed <= eff <= counts
            #     elementwise, equality means eff == counts too, the dense
            #     cycle rescue's pending matrix is zero (it would add
            #     nothing) and backlog is structurally zero;
            #   * arrivals fit the [B] landing plan.
            guard = (
                ok_sel
                & jnp.all(allowed_s == loc_counts)
                & jnp.all(n_in_s <= B)
            )

        # gridlint: fastpath-engine
        def _fast_branch():
            # O(movers) landing: the mover block IS the vacated-slot
            # plan (under the guard the dense engine's unclipped vacated
            # plan is exactly the leaver prefix of sorted order, which
            # the block reproduces bit-for-bit), arrivals gather B
            # columns, one targeted scatter writes B columns per vrank,
            # and the ~98% stayer columns are never touched — no
            # full-array permutation, no overlay landing.
            k_b = jnp.arange(B, dtype=jnp.int32)
            with traced_span("mig:pack"):
                arr_src, _ = _plan_rows_batched(
                    loc_starts.T, allowed_s.T, block_rows, B,
                    seg_rows=jnp.arange(V, dtype=jnp.int32),
                    row_stride=n,
                )  # [V_dst, B] global source columns
                arr_cols = _gather_plan_cols(flat, arr_src)  # [K, V, B]

            with traced_span("mig:stack"):
                def land_plan(vac, nin, nsent, nf):
                    n_pop = jnp.clip(nin - nsent, 0, nf)
                    target = jnp.where(
                        k_b < jnp.minimum(nin, nsent),
                        vac,
                        jnp.where(
                            (k_b >= nsent) & (k_b < nsent + n_pop),
                            jnp.zeros((), jnp.int32),  # replaced below
                            jnp.where(
                                (k_b >= nin) & (k_b < nsent), vac, n
                            ),
                        ),
                    )
                    return target, n_pop

                targets, n_pop = jax.vmap(land_plan)(
                    block_rows, n_in_s, n_sent_s, n_free
                )
                Wb = min(B, n)

                def pops_window(fs_v, nf, nsent):
                    start = jnp.clip(nf - Wb, 0, n - Wb)
                    win_rev = lax.dynamic_slice(fs_v, (start,), (Wb,))[::-1]
                    s = start + Wb - nf - nsent
                    buf = jnp.concatenate(
                        [
                            jnp.zeros((B,), fs_v.dtype),
                            win_rev,
                            jnp.zeros((B,), fs_v.dtype),
                        ]
                    )
                    return lax.dynamic_slice(buf, (s + B,), (B,))

                pops = jax.vmap(pops_window)(free_stack, n_free, n_sent_s)
                use_pop = (k_b[None, :] >= n_sent_s[:, None]) & (
                    k_b[None, :] < (n_sent_s + n_pop)[:, None]
                )
                targets = jnp.where(use_pop, pops, targets)
                gtargets = jnp.where(
                    targets >= n, V * n, my_v[:, None] * n + targets
                )
                cols_w = jnp.where(
                    (k_b[None, :] < n_in_s[:, None])[None], arr_cols, 0
                )
            with traced_span("mig:unpack"):
                # always the targeted XLA scatter: the overlay kernel's
                # one-hot matmul is O(n * plan) — exactly the
                # O(residents) landing cost this branch exists to avoid
                new_flat = _land_scatter(
                    flat, gtargets.reshape(-1),
                    cols_w.reshape(K, V * B), "xla",
                )
            with traced_span("mig:stack"):
                n_push = jnp.maximum(n_sent_s - n_in_s, 0)
                new_stack, new_free = jax.vmap(_stack_push_pop)(
                    free_stack, n_free, n_pop, n_push, block_rows, n_in_s
                )
                stats = MigrateStats(
                    sent=n_sent_s,
                    received=n_in_s,
                    # stack invariant: population == n - n_free (init_state
                    # builds the stack from the alive row; every landing
                    # preserves it) — an O(V) read where the dense engine
                    # pays an O(n) alive-row reduce
                    population=(n - new_free).astype(jnp.int32),
                    backlog=jnp.zeros((V,), jnp.int32),
                    dropped_recv=jnp.zeros((V,), jnp.int32),
                    flow=allowed_s,
                )
            # both branches carry the state's varying mesh axes: jax's
            # cond refuses branches whose outputs differ in them
            return binning.match_vma(
                (MigrateState(new_flat, new_stack, new_free), stats), flat
            )

        # the dense fallback goes through a lambda, not a bare function
        # reference: _step's Dev > 1 collectives are statically absent
        # here (Dev == 1), and the lambda keeps gridlint's G001
        # cond-branch scan (lexical by design) out of the dense body
        out_state, stats = lax.cond(
            guard,
            _fast_branch,
            lambda: binning.match_vma(
                _step(flat, free_stack, n_free, dest_key), flat
            ),
        )
        with traced_span("mig:grant"):
            fast_path = jnp.broadcast_to(guard.astype(jnp.int32), (V,))
        return out_state, stats._replace(fast_path=fast_path)

    return fn


def shard_migrate_fn(domain: Domain, grid: ProcessGrid, capacity: int):
    """Per-field wrapper over the fused path (runs under ``shard_map``).

    Signature of the returned fn:
      ``(pos[n,D], alive[n] bool, *fields) ->
        (pos, alive, *fields, MigrateStats)``
    with identical shapes; rows where ``alive`` is False are holes. Fields
    must have 32-bit dtypes (see :func:`fuse_fields`); loops should carry
    :class:`MigrateState` across steps instead (see
    ``models.nbody.make_migrate_loop``) to skip the per-step fuse/unfuse and
    free-stack rebuild.
    """
    fused_fn = shard_migrate_fused_fn(domain, grid, capacity)

    def fn(pos, alive, *fields):
        fused, specs = fuse_fields((pos,) + tuple(fields), alive)
        state, stats = fused_fn(init_state(fused))
        out, alive_new = unfuse_fields(state.fused, specs)
        return (out[0], alive_new) + tuple(out[1:]) + (stats,)

    return fn
