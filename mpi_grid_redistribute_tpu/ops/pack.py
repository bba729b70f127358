"""Sort-by-destination pack and receive-side compaction (SURVEY.md C4, C6).

The reference packs send buffers with a stable argsort on destination rank
and unpacks Alltoallv receive buffers that are contiguous-by-source
(SURVEY.md §3.2 — mount empty, spec from BASELINE.json north_star: "the
sort-by-destination permutation becomes jax.lax.sort on packed (dest_rank,
local_idx) keys"). MPI's Alltoallv is variable-size; XLA's ``all_to_all`` is
static-shape, so this module realizes the MoE-dispatch-style bridge
(SURVEY.md §7.3): every (source, destination) pair gets a fixed ``capacity``
of slots, rows are gathered into a ``[R, capacity, ...]`` layout, unused
slots are zero-masked, and overflow beyond capacity is *counted and
surfaced*, never silently dropped.

All shapes are static; nothing here depends on data values, so everything
jits and shards cleanly.
"""

from __future__ import annotations

from typing import Tuple

import jax
import jax.numpy as jnp


def _mask_rows(a: jax.Array, mask: jax.Array) -> jax.Array:
    """Zero out rows of ``a`` where ``mask`` (matching leading dims) is False."""
    extra = a.ndim - mask.ndim
    return jnp.where(mask.reshape(mask.shape + (1,) * extra), a, 0)


def _take_rows(order: jax.Array, out_capacity: int) -> jax.Array:
    """First ``out_capacity`` entries of ``order``, zero-padded if the slot
    pool is smaller than the requested output (padding rows are masked by the
    caller's validity mask)."""
    take = order[:out_capacity]
    if take.shape[0] < out_capacity:
        take = jnp.concatenate(
            [take, jnp.zeros((out_capacity - take.shape[0],), take.dtype)]
        )
    return take


def pack_by_destination(
    dest: jax.Array,
    counts: jax.Array,
    arrays,
    capacity: int,
    order: jax.Array = None,
):
    """Gather per-particle arrays into a ``[R, capacity, ...]`` send layout.

    Args:
      dest: [N] int32 destination rank per row; rows with the sentinel value
        ``R`` (invalid padding) sort to the end and are never gathered.
      counts: [R] int32 **full** (unclipped) per-destination counts — these
        locate each destination's segment in the sorted order; slots beyond
        ``min(counts[r], capacity)`` are zero-masked, so overflow keeps the
        stable prefix per destination.
      arrays: pytree of [N, ...] arrays sharing the leading axis.
      capacity: static slots per destination.
      order: optional precomputed stable by-destination permutation (e.g.
        from ``binning.sorted_dest_counts``, which yields the counts for
        free from the same sort); computed here when omitted.

    Returns:
      pytree of [R, capacity, ...] arrays, zero in invalid slots.
    """
    R = counts.shape[0]
    n = dest.shape[0]
    if order is None:
        order = jnp.argsort(dest, stable=True)  # invalid (dest==R) last
    start = jnp.concatenate(
        [jnp.zeros((1,), jnp.int32), jnp.cumsum(counts)[:-1]]
    )
    c_idx = jnp.arange(capacity, dtype=jnp.int32)
    # 1-D flat gather indices: 2-D index arrays lower to a slower gather.
    flat_src = (start[:, None] + c_idx[None, :]).reshape(R * capacity)
    slot_valid = (
        c_idx[None, :] < jnp.minimum(counts, capacity)[:, None]
    ).reshape(R * capacity)
    gather_idx = order[jnp.minimum(flat_src, n - 1)]
    return jax.tree.map(
        lambda a: _mask_rows(
            jnp.take(a, gather_idx, axis=0), slot_valid
        ).reshape((R, capacity) + a.shape[1:]),
        arrays,
    )


def _stable_order(invalid: jax.Array, *subkeys: jax.Array) -> jax.Array:
    """Permutation putting valid rows first, ordered by ``subkeys`` then by
    original position (stable). Multi-operand ``lax.sort`` keeps every key in
    int32 — no fused ``s * K + c`` key that could overflow at scale."""
    m = invalid.shape[0]
    iota = jnp.arange(m, dtype=jnp.int32)
    b = max(1, (m - 1).bit_length())
    if not subkeys and b <= 30:
        # packed single-operand sort (same trick as
        # ``binning.sorted_dest_counts``): the 1-bit invalid flag and the
        # iota tiebreak share one int32 word, so an unstable one-word
        # sort reproduces the stable two-operand sort bit-for-bit while
        # moving half the bytes.
        packed = jax.lax.sort(
            ((invalid != 0).astype(jnp.int32) << b) | iota,
            is_stable=False,
        )
        return packed & jnp.int32((1 << b) - 1)
    operands = (invalid.astype(jnp.int32),) + subkeys + (iota,)
    out = jax.lax.sort(operands, num_keys=len(operands) - 1, is_stable=True)
    return out[-1]


def _finish_compact(values, order, new_count_full, out_capacity: int):
    """Shared compaction tail: gather the first ``out_capacity`` rows of the
    ordered pool, zero the invalid tail, report count + overflow."""
    dropped = jnp.maximum(new_count_full - out_capacity, 0)
    new_count = jnp.minimum(new_count_full, out_capacity)
    take = _take_rows(order, out_capacity)
    row_valid = jnp.arange(out_capacity, dtype=jnp.int32) < new_count
    out = jax.tree.map(
        lambda a: _mask_rows(jnp.take(a, take, axis=0), row_valid), values
    )
    return out, new_count.astype(jnp.int32), dropped.astype(jnp.int32)


def pool_source_keys(recv_counts: jax.Array, self_mask: jax.Array, me,
                     capacity: int):
    """Alltoallv-order keys for a [R, capacity] receive pool + local rows.

    Returns ``(invalid, source_key)`` over the concatenated
    ``[R * capacity + n]`` pool: remote slot (s, c) carries source ``s``
    (valid iff ``c < recv_counts[s]``), local row carries source ``me``
    (valid iff ``self_mask``). Sorting by (invalid, source_key, position)
    is exactly MPI Alltoallv receive order with self rows spliced at
    source position ``me`` — the invariant shared by
    :func:`compact_with_self` (row-major) and the planar engines'
    payload-sort compaction (:func:`planar_compact_with_self`); keep it
    in one place so the two cannot drift. The vrank planar engine lands
    without keys (:func:`land_windows`).
    """
    R = recv_counts.shape[0]
    n = self_mask.shape[0]
    c_idx = jnp.arange(capacity, dtype=jnp.int32)
    valid_r = (c_idx[None, :] < recv_counts[:, None]).reshape(R * capacity)
    src_r = jnp.broadcast_to(
        jnp.arange(R, dtype=jnp.int32)[:, None], (R, capacity)
    ).reshape(R * capacity)
    src_s = jnp.full((n,), me, dtype=jnp.int32)
    invalid = ~jnp.concatenate([valid_r, self_mask])
    source_key = jnp.concatenate([src_r, src_s])
    return invalid, source_key


def compact_with_self(
    recv,
    recv_counts: jax.Array,
    local,
    self_mask: jax.Array,
    me: jax.Array,
    out_capacity: int,
):
    """Merge remote receives with locally-retained rows, Alltoallv-ordered.

    Rows already owned by this shard never ride the wire (SURVEY.md §7.3 —
    in a drift loop most particles stay put each step, so capacity only needs
    to cover *migrants*); they are spliced back here at source position
    ``me`` so the output is still exactly MPI Alltoallv receive order
    (source-major, stable within source) and bit-comparable to the oracle.

    Args:
      recv: pytree of [R, capacity, ...] remote receive buffers
        (row ``me`` is all-zero: nothing is sent to self).
      recv_counts: [R] int32 valid rows per source (``recv_counts[me] == 0``).
      local: pytree of [n, ...] — the *original* per-shard arrays.
      self_mask: [n] bool — rows of ``local`` this shard keeps.
      me: scalar int32 — this shard's rank (``lax.axis_index``).
      out_capacity: static output rows.

    Returns:
      (pytree of [out_capacity, ...], new_count, dropped) like
      :func:`compact_received`.
    """
    first = jax.tree.leaves(recv)[0]
    R, capacity = first.shape[0], first.shape[1]
    # Source rank per pooled row: s for remote slot (s, c), `me` for local
    # rows. No valid collision within a source: recv_counts[me] == 0, so
    # the stable iota tiebreak fully orders rows within each source.
    invalid, source_key = pool_source_keys(
        recv_counts, self_mask, me, capacity
    )
    order = _stable_order(invalid, source_key)
    values = jax.tree.map(
        lambda a, b: jnp.concatenate(
            [a.reshape((R * capacity,) + a.shape[2:]), b], axis=0
        ),
        recv,
        local,
    )
    new_count_full = jnp.sum(recv_counts) + jnp.sum(self_mask.astype(jnp.int32))
    return _finish_compact(values, order, new_count_full, out_capacity)


def compact_received(
    recv,
    recv_counts: jax.Array,
    out_capacity: int,
):
    """Compact a ``[R, capacity, ...]`` receive layout into ``[out_capacity, ...]``.

    Valid rows are kept in **source-major, stable** order — exactly MPI
    Alltoallv's receive ordering (SURVEY.md §7.4's canonical order), so the
    result is bit-comparable to the oracle backend.

    Returns:
      (pytree of [out_capacity, ...], new_count int32 scalar,
       dropped int32 scalar — rows beyond out_capacity).
    """
    first = jax.tree.leaves(recv)[0]
    R, capacity = first.shape[0], first.shape[1]
    total = R * capacity
    c_idx = jnp.arange(capacity, dtype=jnp.int32)
    valid = (c_idx[None, :] < recv_counts[:, None]).reshape(total)
    # Stable compaction: valid rows keep their flat (source-major) order.
    order = _stable_order(~valid)
    values = jax.tree.map(lambda a: a.reshape((total,) + a.shape[2:]), recv)
    return _finish_compact(values, order, jnp.sum(recv_counts), out_capacity)


def planar_compact_with_self(
    pool: jax.Array,
    recv_counts: jax.Array,
    me,
    self_mask: jax.Array,
    local: jax.Array,
    out_capacity: int,
):
    """Planar twin of :func:`compact_with_self`: ``[K, R*C]`` receive pool +
    ``[K, n]`` locally-retained columns -> ``[K, out_capacity]`` in exact MPI
    Alltoallv receive order (source-major, stable within source, self rows
    spliced at source position ``me`` — keys from :func:`pool_source_keys`,
    the single definition both layouts share).

    The reorder is a PAYLOAD-CARRYING sort: the K payload rows ride
    ``lax.sort`` as extra operands so the sort network itself moves the
    bytes. A key-sort + per-column gather pays ~24 ns per gathered output
    column (measured: 126.7 ms of a 148.3 ms step at 4.2M rows —
    scripts/microbench_planar_canonical.py); the payload sort does the same
    reorder in ~43 ms. Sorts are cheap on TPU, per-element placement is
    not. Invalid columns fold into the key as sentinel R (they sort last
    and are zero-masked, so their internal order is irrelevant); iota keeps
    the permutation unique, hence deterministic without ``is_stable``.

    Where the local rows rode the destination sort, no sort is needed:
    the vrank planar engine lands them by window copies
    (:func:`land_windows`). The shard_map, sparse, neighbor and
    hierarchical engines sort the key alone, so their own rows are no
    window, and land through this.

    Returns ``(out [K, out_capacity], new_count, dropped)`` — columns
    beyond ``new_count`` are zero.
    """
    R = recv_counts.shape[0]
    C = pool.shape[1] // R
    invalid, source_key = pool_source_keys(recv_counts, self_mask, me, C)
    values = jnp.concatenate([pool, local], axis=1)  # [K, R*C + n]
    new_full = jnp.sum(recv_counts) + jnp.sum(self_mask.astype(jnp.int32))
    return planar_compact_keys(
        values, invalid, source_key, R, new_full, out_capacity
    )


def planar_compact_keys(
    values: jax.Array,
    invalid: jax.Array,
    source_key: jax.Array,
    n_sources: int,
    new_full: jax.Array,
    out_capacity: int,
):
    """Key-generic tail of :func:`planar_compact_with_self`: compact the
    ``[K, m]`` column pool ``values`` by the caller's Alltoallv-order keys.

    The count-driven and neighbor wire schedules receive the same rows as
    the dense pool but at different column addresses (``[R*B]`` blocks,
    per-offset stencil blocks); the compaction ordering — source-major,
    stable within source via the column iota — only depends on ``(invalid,
    source_key)``, so sharing this tail is what makes those engines
    bit-identical to the dense one: any key construction that marks the
    same rows valid with the same sources yields byte-identical output.

    ``new_full`` is the caller-computed valid total (garbage columns sort
    last and are masked); ``n_sources`` is the sentinel written over
    invalid keys (must exceed every valid source).
    """
    source_key = jnp.where(invalid, n_sources, source_key)
    m = values.shape[1]
    iota = jnp.arange(m, dtype=jnp.int32)
    bM = max(1, (m - 1).bit_length())
    if n_sources + 1 <= (1 << (31 - bM)):
        # PACKED single key: ``(source_key << bM) | iota`` is unique and
        # orders exactly like the (source_key, iota) pair, so one int32
        # operand replaces two — 1/(K+2) fewer bytes through the sort
        # network, the step's dominant cost (BENCH_CONFIGS.md config 1).
        operands = ((source_key << bM) | iota,) + tuple(
            values[k] for k in range(values.shape[0])
        )
        sorted_ops = jax.lax.sort(operands, num_keys=1, is_stable=False)
        payload = jnp.stack(sorted_ops[1:], axis=0)
    else:
        operands = (source_key, iota) + tuple(
            values[k] for k in range(values.shape[0])
        )
        sorted_ops = jax.lax.sort(operands, num_keys=2, is_stable=False)
        payload = jnp.stack(sorted_ops[2:], axis=0)
    if payload.shape[1] < out_capacity:
        # pool smaller than the output: zero-pad (the tail is beyond
        # new_count <= m, so the mask below keeps it zero)
        payload = jnp.pad(
            payload, ((0, 0), (0, out_capacity - payload.shape[1]))
        )
    else:
        payload = payload[:, :out_capacity]
    dropped = jnp.maximum(new_full - out_capacity, 0)
    new_count = jnp.minimum(new_full, out_capacity)
    col_valid = jnp.arange(out_capacity, dtype=jnp.int32) < new_count
    out = jnp.where(col_valid[None, :], payload, 0)
    return out, new_count.astype(jnp.int32), dropped.astype(jnp.int32)


def gather_plan_cols(fused: jax.Array, idx: jax.Array) -> jax.Array:
    """Gather plan-addressed columns out of a planar matrix in ONE flat
    1-D take: ``fused [K, W]`` gathered at ``idx [...]`` (flat column
    indices into ``W``) -> ``[K, *idx.shape]``.

    Shared by the migrate engines' arrival gathers (dense and
    mover-sparse): a single flat gather with the index arithmetic done up
    front lowers to one contiguous XLA gather, where the equivalent
    multi-dim ``take`` emits a slower composite (same reason
    :func:`pack_by_destination` pre-flattens its indices). Callers mask
    invalid slots themselves — indices must already be clipped in-range.
    """
    flat = jnp.take(fused, idx.reshape(-1), axis=1)
    return flat.reshape((fused.shape[0],) + idx.shape)


def pack_windows(sorted_cols, bounds, send_counts, capacity: int):
    """:func:`pack_cols` for columns that are already in destination
    order (``binning.sort_by_dest``): destination ``d``'s slots are the
    contiguous window ``[bounds[d], bounds[d] + C)`` of ``sorted_cols``,
    copied whole, so no per-column index exists. Returns the same
    ``[K, n_dest * C]`` send pool, zero in slots ``c >= send_counts[d]``.

    The columns are padded by ``C`` zeros first: a window start is at
    most ``n``, and ``dynamic_slice`` would clamp a window that runs past
    the end, shifting the segment silently. Int32 columns keep every bit
    pattern (see :func:`pack_cols` on the denormal flush)."""
    K = sorted_cols.shape[0]
    C = capacity
    padded = jnp.pad(sorted_cols, ((0, 0), (0, C)))
    win = jax.vmap(
        lambda start: jax.lax.dynamic_slice_in_dim(padded, start, C, axis=1)
    )(bounds)  # [n_dest, K, C]
    slot_valid = jnp.arange(C, dtype=jnp.int32) < send_counts[:, None]
    win = jnp.where(slot_valid[:, None, :], win, 0)
    return win.transpose(1, 0, 2).reshape(K, bounds.shape[0] * C)


def land_windows(pool, recv_counts, sorted_cols, self_start, self_count,
                 out_capacity: int):
    """:func:`planar_compact_with_self` for vranks whose rows rode the
    destination sort, by window copies instead of a sort.

    ``pool [V, K, V*C]`` holds each destination vrank's receive pool:
    source ``s``'s rows are the first ``recv_counts[v, s]`` columns of
    window ``[s*C, s*C + C)`` (``recv_counts [V_dst, V_src]``, zero on
    the diagonal). ``sorted_cols [V, K, n]`` are each vrank's rows as
    ``binning.sort_by_dest`` returns them with own and invalid rows under
    the sentinel ``V``: the packed ``(dest << b) | iota`` key puts the
    ``self_count[v]`` own rows (valid, so below the count) first in that
    segment, in source order, from ``self_start[v]`` (``bounds[V]``).

    Alltoallv receive order is therefore V windows, one a source, laid
    end to end in source order at the exclusive prefix sums of the valid
    counts; each window overwrites the padding of the one before, and
    the columns from ``new_count`` on are zeroed. Each vrank fills a
    ``[K, ...]`` buffer of its own, so a window covers whole tiles of its
    K rows: a ``lax.map`` over the vranks, and in each a ``fori_loop``
    over the sources before it, the own window, and a ``fori_loop`` over
    the sources after it. Every copy is one in-place
    ``dynamic-update-slice`` on scalar offsets (a ``vmap`` over the
    vranks would lower to a scatter), and the program does not grow with
    V (unrolled, the V x V copies made a 16-vrank service step's first
    call 0.89 s on the CPU, against 0.34 s looped). Offsets are clamped
    to ``out_capacity``, the buffer is ``max(n, C)`` columns wider, and
    the sorted rows are padded by ``n`` inside each vrank's copy, so no
    ``dynamic_slice`` or ``dynamic_update_slice`` start is ever clamped
    (a window that starts at ``out_capacity`` writes only columns that
    are cut away). The pad is not shared with :func:`pack_windows`: for
    a v5e this one fuses into the own window's read, where one pad by
    ``max(n, C)`` for both is written out whole as ``[V, K, 2n]``.
    Int32 columns keep every bit pattern (see :func:`pack_cols` on the
    denormal flush).

    Returns ``(out [V, K, out_capacity], new_count [V], dropped [V])``,
    bit for bit those of :func:`planar_compact_with_self` on each vrank.
    """
    V, K, n = sorted_cols.shape
    C = pool.shape[2] // V
    counts = recv_counts + jnp.diag(self_count)
    total = jnp.sum(counts, axis=1)
    off = jnp.minimum(jnp.cumsum(counts, axis=1) - counts, out_capacity)
    off = off.astype(jnp.int32)
    new_count = jnp.minimum(total, out_capacity)
    dropped = jnp.maximum(total - out_capacity, 0)
    zero = jnp.zeros((), pool.dtype)
    width = out_capacity + max(n, C)
    # every index int32, under x64 too: the starts of one slice share a dtype
    row0 = jnp.zeros((), jnp.int32)
    start = self_start.astype(jnp.int32)

    def land_one(v):
        pool_v, off_v = pool[v], off[v]

        def remote(s, buf):
            win = jax.lax.dynamic_slice(
                pool_v, (row0, s * C), (K, C), allow_negative_indices=False
            )
            return jax.lax.dynamic_update_slice(
                buf, win, (row0, off_v[s]), allow_negative_indices=False
            )

        padded = jax.lax.pad(sorted_cols[v], zero, ((0, 0, 0), (0, n, 0)))
        own = jax.lax.dynamic_slice(
            padded, (row0, start[v]), (K, n), allow_negative_indices=False
        )
        buf = jnp.zeros((K, width), pool.dtype)
        buf = jax.lax.fori_loop(0, v, remote, buf)
        buf = jax.lax.dynamic_update_slice(
            buf, own, (row0, off_v[v]), allow_negative_indices=False
        )
        buf = jax.lax.fori_loop(v + 1, V, remote, buf)
        return buf[:, :out_capacity]

    out = jax.lax.map(land_one, jnp.arange(V, dtype=jnp.int32))
    col_valid = jnp.arange(out_capacity, dtype=jnp.int32) < new_count[:, None]
    out = jnp.where(col_valid[:, None, :], out, 0)
    return out, new_count.astype(jnp.int32), dropped.astype(jnp.int32)


def pack_cols(fused, order, bounds, send_counts, n_dest: int,
               capacity: int):
    """Gather the first ``send_counts[d]`` sorted columns of each
    destination segment into a ``[K, n_dest * C]`` send pool (zero in
    invalid slots). Returns ``(send, gather_idx)``; ``gather_idx[j]`` is
    the resident column feeding send slot ``j`` (unique over valid
    slots). Shared by the migrate engine and the planar canonical
    engines but the vrank one (which copies windows,
    :func:`pack_windows`) — the planar twin of
    :func:`pack_by_destination`."""
    n = fused.shape[1]
    C = capacity
    c_idx = jnp.arange(C, dtype=jnp.int32)
    flat_c = jnp.tile(c_idx, n_dest)
    flat_d = jnp.repeat(jnp.arange(n_dest, dtype=jnp.int32), C)
    slot_valid = flat_c < send_counts[flat_d]
    src = jnp.minimum(bounds[flat_d] + flat_c, n - 1)
    gather_idx = order[src]  # [n_dest*C] unique over valid slots
    # dtype-generic zero fill: the planar canonical engines transport the
    # fused matrix BITCAST TO INT32 through this gather — TPU float vector
    # copies flush denormal f32 bit patterns to zero (measured: bitcast
    # int32 ids < 2^23 corrupted through this exact gather+mask at
    # ~3k rows/shard; the same hazard ops/pallas_overlay.py biases
    # around), while integer lanes have no FTZ semantics.
    send = jnp.where(
        slot_valid[None, :], jnp.take(fused, gather_idx, axis=1), 0
    )
    return send, gather_idx
