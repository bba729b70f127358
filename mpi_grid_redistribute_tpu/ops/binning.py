"""Position -> cell -> destination-rank binning (SURVEY.md C2, C3, C9).

The reference's hot-path front end ("position->cell digitize + per-destination
histogram", SURVEY.md §3.2 — reference mount empty, spec from BASELINE.json
north_star) mapped to TPU-friendly primitives: pure elementwise floor-divide
binning (vectorizes trivially; no data-dependent shapes) and a
``segment_sum`` histogram that XLA lowers to an efficient scatter-add.

Every function takes an ``xp`` module argument (``jax.numpy`` or ``numpy``) so
the JAX device path and the pure-NumPy oracle backend execute *the same
code* — semantic drift between backend and oracle is structurally impossible.
"""

from __future__ import annotations

import contextlib
import math
import os

import jax
import jax.numpy as jnp
import numpy as np


def _np_quiet(xp):
    """Silence NumPy overflow/invalid warnings on the oracle twin (the
    JAX path never warns); a no-op for jnp. ONE context guards the ONE
    copy of each bit-sensitive expression — duplicating the expression
    per backend would let the twins drift."""
    if xp is np:
        return np.errstate(over="ignore", invalid="ignore")
    return contextlib.nullcontext()

from mpi_grid_redistribute_tpu.domain import Domain, ProcessGrid


def _is_pow2(x: float) -> bool:
    """True for positive powers of two (reciprocal exactly representable)."""
    if x <= 0 or not math.isfinite(x):
        return False
    mant, _ = math.frexp(x)
    return mant == 0.5


def remainder_fast(q, ext: float, xp=jnp):
    """``remainder(q, ext)`` with a reciprocal-multiply fast path.

    f32 division is the cost of ``remainder`` on the TPU VPU: the binning
    chain measured 6.9 ms with ``jnp.remainder`` vs 1.75 ms with
    ``q - floor(q * (1/ext)) * ext`` at 8.4M rows
    (scripts/microbench_leaver_compact.py). For power-of-two extents the
    two are BIT-EQUAL on non-overflowing inputs (``|q| < f32max * ext``:
    1/ext, the scale and the final subtraction are all exact — IEEE
    remainder by an exact-reciprocal divisor), so the fast path preserves
    the engines' bit-compatibility with the NumPy oracle, which is why it
    only engages when exactness is guaranteed. Beyond that bound (ext < 1
    with |q| near f32max) the product overflows to inf and the fold below
    TOTALIZES the result to 0 — identically on every backend (both twins
    share this function), but differing from ``jnp.remainder``'s value
    there; the claim is engine/oracle compatibility, not equality with
    ``remainder`` on absurd inputs.

    One non-exact corner is handled explicitly: when ``|q|`` is tiny
    enough that ``q * (1/ext)`` is denormal, a flush-to-zero backend (TPU
    vector units; some CPU fast-math paths) makes the raw fast path
    return a tiny NEGATIVE value, while a denormal-honoring backend
    returns a value that rounds to exactly ``ext``. The two-sided fold
    below lands every backend on the same bits — the fast path's result
    is GUARANTEED in ``[0, ext)`` (unlike ``remainder``, whose
    rounds-to-ext corner callers must fold) — and it also totalizes the
    +/-inf products of absurd inputs identically everywhere.
    """
    if _is_pow2(float(ext)):
        dt = q.dtype.type
        with _np_quiet(xp):
            r = q - xp.floor(q * dt(1.0 / ext)) * dt(ext)
            return xp.where((r < dt(0)) | (r >= dt(ext)), dt(0), r)
    return xp.remainder(q, xp.asarray(ext, dtype=q.dtype))


def wrap_periodic(pos, domain: Domain, xp=jnp):
    """Wrap positions into [lo, hi) along the domain's periodic axes.

    Non-periodic axes pass through unchanged (out-of-box particles on those
    axes are clamped into edge cells by ``cell_of_position``). Power-of-two
    extents take the exact reciprocal-multiply path (:func:`remainder_fast`).
    """
    lo = xp.asarray(domain.lo, dtype=pos.dtype)
    extent = xp.asarray(domain.extent, dtype=pos.dtype)
    q = pos - lo
    # fast path gates on the PERIODIC axes only (non-periodic axes'
    # wrap result is discarded by the final where)
    if all(
        _is_pow2(float(e))
        for e, p in zip(domain.extent, domain.periodic)
        if p
    ):
        inv = xp.asarray(
            [1.0 / e if _is_pow2(float(e)) else 0.0 for e in domain.extent],
            dtype=pos.dtype,
        )
        with _np_quiet(xp):
            r = q - xp.floor(q * inv) * extent
        # denormal-product FTZ fold: see remainder_fast
        wrapped = lo + xp.where(r < 0, xp.zeros_like(r), r)
    else:
        wrapped = lo + xp.remainder(q, extent)
    # remainder can round up to exactly `extent` for tiny negative inputs in
    # float32; fold that back to lo.
    wrapped = xp.where(wrapped >= lo + extent, lo, wrapped)
    per = xp.asarray(domain.periodic, dtype=bool)
    return xp.where(per, wrapped, pos)


def _digitize_edges(p, axis_edges, xp):
    """Compare-sum digitize of one axis: ``#{k in 1..g-1 : p >= edges[k]}``
    — ``np.digitize(p, inner_edges)`` semantics, shared between the
    row-major and planar paths and between the NumPy oracle and the jax
    engines (``xp=``), so a semantics change cannot desynchronize them.

    The NumPy twin takes ``searchsorted(inner, p, 'right')`` instead of
    the g-2 Python-level broadcast compares: both count the inner edges
    ``<= p`` — pure comparisons against the same float values, no
    arithmetic on ``p`` — so the two forms are equal on every input
    including exact-tie positions, and the C loop is what keeps the
    oracle's assignment-aware routing off the hot-path flamegraph
    (the native C++ ``bin_positions`` never sees edges)."""
    if xp is np:
        # ``p`` is a host array on this branch (xp is np) and
        # ``axis_edges`` is a static Python tuple — no traced value
        inner = np.asarray(  # gridlint: disable=G002
            axis_edges[1:-1], dtype=p.dtype
        )
        return np.searchsorted(inner, p, side="right").astype(np.int32)
    c = xp.zeros(p.shape, dtype=xp.int32)
    for k in range(1, len(axis_edges) - 1):
        b = xp.asarray(axis_edges[k], dtype=p.dtype)
        c = c + (p >= b).astype(xp.int32)
    return c


def _cell_uniform_axis(p, axis_edges, xp):
    """Floor-multiply binning of one UNIFORMLY-SPACED edges axis:
    ``clip(floor((p - lo) * g / (hi - lo)), 0, g - 1)`` — the same
    arithmetic as the default uniform-grid path, shared between the
    backends (``xp=``) so they stay bit-identical by construction. Only
    engaged for axes :class:`~..domain.GridEdges` detected as exact
    ``np.linspace`` reproductions (``uniform_axes``): there the edge
    grid IS a uniform grid, and the per-edge digitize was the oracle's
    hot-path cost under assignment-aware fine grids."""
    g = len(axis_edges) - 1
    lo = xp.asarray(axis_edges[0], dtype=p.dtype)
    inv = xp.asarray(
        g / (axis_edges[-1] - axis_edges[0]), dtype=p.dtype
    )
    c = xp.floor((p - lo) * inv).astype(xp.int32)
    return xp.clip(c, 0, g - 1)


def _cell_edges_axis(p, edges, a, xp):
    """One axis of the ``edges`` digitize: floor-multiply fast path for
    uniformly spaced axes, compare-sum digitize otherwise."""
    if getattr(edges, "uniform_axes", (False,) * edges.ndim)[a]:
        return _cell_uniform_axis(p, edges.edges[a], xp)
    return _digitize_edges(p, edges.edges[a], xp)


def cell_of_position(pos, domain: Domain, grid: ProcessGrid, xp=jnp,
                     edges=None):
    """Map positions [N, ndim] to integer grid-cell coordinates [N, ndim].

    Uniform cells (default): ``cell = floor((pos - lo) * grid_shape /
    extent)``, clamped into [0, shape-1] so particles exactly at (or
    numerically beyond) the upper edge land in the last cell rather than
    out of range.

    ``edges`` (a :class:`~..domain.GridEdges`): NON-UNIFORM boundaries —
    ``cell = #{k in 1..g-1 : pos >= edges[k]}`` per axis, the digitize
    semantics of ``np.digitize(pos, inner_edges)`` (cell k owns
    ``[edges[k], edges[k+1])``; below-domain positions clamp to cell 0,
    above-domain to the last cell). Implemented as g-1 broadcast
    compares shared verbatim between the NumPy oracle and the jax
    engine (``xp=``), so backend bit-compatibility holds by
    construction — no searchsorted lowering is involved (TPU
    ``method="sort"`` hides a full-length scatter; see
    :func:`bounds_dense`). Axes whose edges are an exact uniform
    lattice (``GridEdges.uniform_axes`` — e.g. the rebalance planner's
    linspace-built fine grids) take the same floor-multiply arithmetic
    as the default path instead of the per-edge digitize, on both
    backends.
    """
    if edges is not None:
        cols = [
            _cell_edges_axis(pos[..., a], edges, a, xp)
            for a in range(grid.ndim)
        ]
        return xp.stack(cols, axis=-1)
    lo = xp.asarray(domain.lo, dtype=pos.dtype)
    inv_width = xp.asarray(
        [s / e for s, e in zip(grid.shape, domain.extent)], dtype=pos.dtype
    )
    cell = xp.floor((pos - lo) * inv_width).astype(xp.int32)
    hi_cell = xp.asarray([s - 1 for s in grid.shape], dtype=xp.int32)
    return xp.clip(cell, 0, hi_cell)


def rank_of_cell(cell, grid: ProcessGrid, xp=jnp):
    """Flat row-major destination rank [N] from cell coordinates [N, ndim]."""
    strides = xp.asarray(grid.strides, dtype=xp.int32)
    return xp.sum(cell * strides, axis=-1).astype(xp.int32)


def _assigned_rank(flat_cell, edges, xp):
    """Fine-cell -> rank table gather for assignment-aware
    :class:`~..domain.GridEdges` (adaptive rebalancing). The assignment
    is a static tuple, so under jit the table is a compile-time constant
    and the gather is one ``take`` — the same pattern the migrate
    engine's ``cells``+``assignment`` routing uses."""
    table = xp.asarray(edges.assignment, dtype=xp.int32)
    return xp.take(table, flat_cell).astype(xp.int32)


def rank_of_position(pos, domain: Domain, grid: ProcessGrid, xp=jnp,
                     edges=None):
    """Fused wrap -> digitize -> cell->rank map: destination rank per particle.

    With assignment-aware ``edges`` the digitize runs over the FINE cell
    grid the edges define and the rank is read from the assignment table;
    otherwise cells map to ranks by row-major strides (identity)."""
    pos = wrap_periodic(pos, domain, xp=xp)
    cell = cell_of_position(pos, domain, grid, xp=xp, edges=edges)
    if edges is not None and edges.assignment is not None:
        strides = xp.asarray(edges.cell_strides, dtype=xp.int32)
        flat = xp.sum(cell * strides, axis=-1).astype(xp.int32)
        return _assigned_rank(flat, edges, xp)
    return rank_of_cell(cell, grid, xp=xp)


def wrap_periodic_planar(pos, domain: Domain, xp=jnp):
    """Planar twin of :func:`wrap_periodic` for ``[..., D, n]`` layouts.

    The migrate engine carries particle state transposed — components on
    the sublane axis, particles on the lane axis — so no narrow-minor
    ``[n, D]`` buffer ever materializes (T(8,128) tiling pads ``[n, 3]``
    42.7x at program boundaries and scan carries; measured, see
    parallel/migrate.py). Components unroll as D elementwise [..., n] ops.
    """
    out = []
    for d in range(pos.shape[-2]):
        p = pos[..., d, :]
        if domain.periodic[d]:
            lo = xp.asarray(domain.lo[d], dtype=pos.dtype)
            ext = xp.asarray(domain.extent[d], dtype=pos.dtype)
            w = lo + remainder_fast(p - lo, domain.extent[d], xp=xp)
            w = xp.where(w >= lo + ext, lo, w)
            out.append(w)
        else:
            out.append(p)
    return xp.stack(out, axis=-2)


def cell_of_position_planar(pos, domain: Domain, grid: ProcessGrid, xp=jnp,
                            edges=None):
    """Planar twin of :func:`cell_of_position`: ``[..., D, n]`` positions to
    ``[..., D, n]`` int32 cell coordinates (same clamp/digitize
    semantics, including the non-uniform ``edges`` compare-sum)."""
    out = []
    for d in range(pos.shape[-2]):
        p = pos[..., d, :]
        if edges is not None:
            out.append(_cell_edges_axis(p, edges, d, xp))
            continue
        inv_w = xp.asarray(
            grid.shape[d] / domain.extent[d], dtype=pos.dtype
        )
        lo = xp.asarray(domain.lo[d], dtype=pos.dtype)
        c = xp.floor((p - lo) * inv_w).astype(xp.int32)
        out.append(xp.clip(c, 0, grid.shape[d] - 1))
    return xp.stack(out, axis=-2)


def rank_of_position_planar(pos, domain: Domain, grid: ProcessGrid, xp=jnp,
                            edges=None):
    """Planar twin of :func:`rank_of_position` for ``[..., D, n]`` layouts."""
    pos = wrap_periodic_planar(pos, domain, xp=xp)
    cell = cell_of_position_planar(pos, domain, grid, xp=xp, edges=edges)
    assigned = edges is not None and edges.assignment is not None
    strides = edges.cell_strides if assigned else grid.strides
    rank = None
    for d in range(cell.shape[-2]):
        t = cell[..., d, :] * xp.int32(strides[d])
        rank = t if rank is None else rank + t
    if assigned:
        return _assigned_rank(rank.astype(xp.int32), edges, xp)
    return rank.astype(xp.int32)


def dest_sort_key(dest, n_dest: int):
    """The key of the stable destination order, and its row-index bits.

    Where the destinations and the row index fit one int32 word the key
    is the PACKED ``(dest << b) | iota``: unique, so an unstable one-word
    sort orders rows exactly as the stable ``(dest, iota)`` sort does
    while moving half the bytes — the sort network is the phase-2 wall
    of the migrate knockout (BENCH_CONFIGS.md), and at the 64-vrank
    north-star the packed form fits easily (64 dests << 20-bit row
    index). Otherwise the key is ``dest`` itself, to be sorted stably.

    Returns ``(key, b)`` with ``b`` the row-index bits of the packed
    key, or ``None`` for the plain one. Shared by
    :func:`sorted_dest_counts` and :func:`sort_by_dest`, so the two
    orders cannot drift apart.
    """
    n = dest.shape[0]
    b = max(1, (n - 1).bit_length())
    if n_dest + 1 <= (1 << (31 - b)):
        iota = jnp.arange(n, dtype=jnp.int32)
        return (dest << b) | iota, b
    return dest, None


def dest_bounds(sorted_key, n_dest: int, b):
    """``bounds`` [n_dest+1] of the destination segments, read off a
    sorted :func:`dest_sort_key` by binary search (free on sorted keys,
    where a ``segment_sum`` histogram lowers to a scatter-add)."""
    probes = jnp.arange(n_dest + 1, dtype=jnp.int32)
    if b is not None:
        probes = probes << b
    return jnp.searchsorted(sorted_key, probes, side="left").astype(
        jnp.int32
    )


def sorted_dest_counts(dest, n_dest: int):
    """Stable sort rows by destination AND count per destination, in one
    ``lax.sort`` + ``searchsorted``.

    On TPU, ``segment_sum`` histograms lower to a scatter-add (~37 ms at 4M
    rows, measured) while a stable int32 key sort is ~6 ms and binary search
    on the sorted keys is free — so the sort the pack needs anyway also
    yields the histogram (SURVEY.md §7.3 steps 3-4 fused).

    Args:
      dest: [N] int32 destination per row; sentinel ``n_dest`` marks rows to
        exclude (they sort to the tail and are not counted).
      n_dest: number of destinations.

    Returns:
      (order, counts, bounds): ``order`` [N] — stable permutation grouping
      rows by destination; ``counts`` [n_dest]; ``bounds`` [n_dest+1] —
      start offset of each destination's segment in ``order``.
    """
    key, b = dest_sort_key(dest, n_dest)
    if b is not None:
        key = jax.lax.sort(key, is_stable=False)
        order = key & jnp.int32((1 << b) - 1)
    else:
        iota = jnp.arange(dest.shape[0], dtype=jnp.int32)
        key, order = jax.lax.sort((key, iota), num_keys=1, is_stable=True)
    bounds = dest_bounds(key, n_dest, b)
    return order, bounds[1:] - bounds[:-1], bounds


def sort_by_dest(dest, n_dest: int, payload):
    """:func:`sorted_dest_counts` that carries the rows along: ONE
    ``lax.sort`` of the destination key with the ``K`` rows of the
    ``[K, N]`` ``payload`` as extra operands.

    Returns ``(sorted_payload, counts, bounds)``: column ``j`` of
    ``sorted_payload`` is ``payload[:, order[j]]`` for the ``order``
    :func:`sorted_dest_counts` gives, and ``counts``/``bounds`` are its.
    Sorts are cheap on TPU and per-column placement is not (see
    ``pack.planar_compact_with_self``): this moves the payload through
    the sort network instead of gathering it by ``order`` afterwards.
    Any 32-bit payload dtype; int32 keeps every bit pattern.

    Rows under the sentinel ``n_dest`` keep their source order too: where
    the caller marks its own (valid) rows and the rows past its count
    with the sentinel, the own rows are the first of that segment, from
    ``bounds[n_dest]``, since every own row's index is below the count.
    ``pack.land_windows`` copies them from there as one window.
    """
    key, b = dest_sort_key(dest, n_dest)
    out = jax.lax.sort(
        (key,) + tuple(payload[k] for k in range(payload.shape[0])),
        num_keys=1, is_stable=b is None,
    )
    bounds = dest_bounds(out[0], n_dest, b)
    return jnp.stack(out[1:], axis=0), bounds[1:] - bounds[:-1], bounds


def sorted_dest_counts_batched(dest, n_dest: int, *, chunk: int = 4096,
                               cap: int = 512):
    """Batched :func:`sorted_dest_counts` over ``[V, n]`` key rows, with a
    TWO-LEVEL leaver selection fast path.

    The migrate engines consume the destination sort ONLY on the leaver
    prefix: stayers carry the sentinel key ``n_dest`` and sort to the
    tail, and every downstream read sits inside a leaver segment (clipped
    and masked by granted counts). A full ``[V, n]`` packed sort is the
    single largest phase of the 64-vrank north-star knockout (~55 ms at
    64x1M, BENCH_CONFIGS.md) — but ``lax.sort``'s per-element cost falls
    with column width (measured 0.49 ns/elem at 4K columns vs 1.68 at 1M,
    ``scripts/microbench_select.py``), so sorting small CHUNKS, keeping
    each chunk's bounded leaver prefix, and finishing with one small sort
    over the candidates reproduces the consumed prefix bit-for-bit at a
    fraction of the moved bytes: 56.3 -> 23.6 ms at 64x1M, 2% leavers.

    Exactness: within a chunk the packed ``(dest << bT) | iota_t`` sort
    orders entries by (dest, global position) — iota_t order IS global
    order within the chunk — and the sentinel sorts past every real
    destination, so chunk ``c``'s leavers are exactly its first ``lc[c]``
    sorted entries. When every ``lc[c] <= cap`` (the GUARD), the sliced
    candidates contain all leavers; repacking them as
    ``(dest << bits(n)) | global_pos`` and sorting once more yields the
    exact stable (dest, position) order the flat packed sort produces.
    Counts and bounds read off the small sorted array are exact. The
    ``order`` tail beyond the leavers is ZEROS (never read — every
    consumer masks at granted counts <= leavers); a ``lax.cond`` routes
    guard-violating steps (a chunk with > ``cap`` leavers) to the flat
    sort, so correctness never depends on the density assumption. The
    guard is ONE scalar across all rows: a per-row (vmapped) cond would
    lower to a select and execute both branches.

    Args:
      dest: [V, n] int32 destinations; sentinel ``n_dest`` marks rows to
        exclude (not counted, sorted to the tail).
      n_dest: number of destinations.
      chunk: power-of-two chunk width for the first-level sorts.
      cap: per-chunk leaver candidate budget (guard threshold).

    Dense-step cost: the ``lax.cond`` fallback traces the full ``[V, n]``
    flat packed sort alongside the two-level graph, so a guard-violating
    step (dense migration — some chunk has > ``cap`` leavers) pays the
    chunk sorts and ``lc`` reduction *and then* the flat sort, and the
    cond's branch buffers can raise peak memory at 64×1M-class shapes.
    This matches the slab-guard pattern elsewhere in the repo: steady
    sparse steps get the fast path; operators should expect a transient
    regression (not an error) when migration bursts exceed ``cap`` per
    chunk.

    Returns:
      (order_prefix [V, n], counts [V, n_dest], bounds [V, n_dest + 1]) —
      the leaver prefix of each ``order_prefix`` row, the counts, and the
      bounds are bit-identical to ``vmap(sorted_dest_counts)``.

      ``order_prefix`` is NOT a full permutation: only the first
      ``counts[v].sum()`` entries of row ``v`` (the leaver prefix) are
      contractual. On the two-level fast path the tail is zero-filled —
      in-range but junk (each gathered tail entry silently reads element
      0 of its row); on the flat fallback (static conditions above, or a
      guard-violating dense step) the tail happens to be the real
      sentinel-sorted suffix. Consumers MUST NOT rely on either: mask or
      slice at granted/leaver counts (all in-repo callers do). The name
      records the prefix-only contract at call sites.
    """
    V, n = dest.shape

    def flat():
        o, c, b = jax.vmap(lambda k: sorted_dest_counts(k, n_dest))(dest)
        return o, c, b

    bN = max(1, (n - 1).bit_length())
    bT = (chunk - 1).bit_length()
    nc = -(-n // chunk)
    if (
        chunk & (chunk - 1)
        or n_dest + 1 > (1 << (31 - bN))  # second-level packing overflow
        or n_dest + 1 > (1 << (31 - bT))  # first-level packing overflow
        or nc * cap >= n  # selection would not shrink the problem
        # TRACE-TIME A/B hook (like MPI_GRID_VACATED_PLAN): consulted
        # when the caller's jit first traces — toggling it later in the
        # same process is ignored by the cached executable.
        or os.environ.get("MPI_GRID_SELECT") == "flat"
    ):
        return flat()
    npad = nc * chunk - n
    ch = dest
    if npad:
        ch = jnp.concatenate(
            [dest, jnp.full((V, npad), n_dest, jnp.int32)], axis=1
        )
    ch = ch.reshape(V, nc, chunk)
    lc = jnp.sum((ch != n_dest).astype(jnp.int32), axis=-1)  # [V, nc]
    ok = jnp.max(lc) <= cap

    def two_level():
        iota_t = jnp.arange(chunk, dtype=jnp.int32)
        packed1 = jax.lax.sort(
            (ch << bT) | iota_t, dimension=-1, is_stable=False
        )
        cand = jax.lax.slice_in_dim(packed1, 0, cap, axis=2)
        dest_c = cand >> bT
        pos_g = (
            jnp.arange(nc, dtype=jnp.int32)[None, :, None] * chunk
        ) | (cand & (chunk - 1))
        live = (
            jnp.arange(cap, dtype=jnp.int32)[None, None, :]
            < lc[:, :, None]
        )
        packed2 = jnp.where(
            live, (dest_c << bN) | pos_g, jnp.int32(n_dest << bN)
        )
        packed2 = jax.lax.sort(
            packed2.reshape(V, nc * cap), dimension=-1, is_stable=False
        )
        order_c = packed2 & jnp.int32((1 << bN) - 1)
        edges = jnp.arange(n_dest + 1, dtype=jnp.int32) << bN
        bounds = jax.vmap(
            lambda p: jnp.searchsorted(p, edges, side="left")
        )(packed2).astype(jnp.int32)
        order = jax.lax.dynamic_update_slice(
            jnp.zeros((V, n), jnp.int32), order_c, (0, 0)
        )
        return order, bounds[:, 1:] - bounds[:, :-1], bounds

    return jax.lax.cond(ok, two_level, flat)


def sparse_select_params(n: int, block: int, *, chunk: int = 4096):
    """Derive ``(chunk, cap)`` for :func:`sorted_mover_block` from the row
    width and the mover-block capacity.

    Policy: shrink ``chunk`` below ``n`` (tiny CPU test meshes), then size
    ``cap`` so a uniformly spread mover population at the full ``block``
    density sits ~4x under the per-chunk guard; when the whole block fits
    in half a chunk, raise ``cap`` to ``block`` so the guard is subsumed
    by the leaver-count check (``leavers <= block`` implies every chunk's
    leavers fit) and the fast path never falls back on clustering alone.
    ``cap`` is clamped to ``chunk // 2`` so the candidate sort always
    moves fewer bytes than the chunk sorts it follows.
    """
    while chunk >= max(2, n) and chunk > 8:
        chunk //= 2
    exp = max(1, -(-block * chunk // max(1, n)))
    cap = 1 << (4 * exp - 1).bit_length()
    if block <= chunk // 2:
        cap = max(cap, 1 << max(0, block - 1).bit_length())
    cap = max(1, min(cap, chunk // 2))
    return chunk, cap


def sparse_select_feasible(n: int, n_dest: int, *, chunk: int = 4096,
                           cap: int = 512) -> bool:
    """True when :func:`sorted_mover_block` can be built for this shape —
    the same STATIC conditions under which :func:`sorted_dest_counts_batched`
    takes its two-level path (packing headroom, pow2 chunk, selection
    actually shrinking the problem, no ``MPI_GRID_SELECT=flat`` override).
    Callers gate engine construction on this; the dynamic per-step guard
    (a chunk overflowing ``cap``, movers overflowing the block) is the
    ``ok`` scalar the builder returns."""
    bN = max(1, (n - 1).bit_length())
    bT = (chunk - 1).bit_length()
    nc = -(-n // chunk)
    return not (
        chunk <= 0
        or chunk & (chunk - 1)
        or n_dest + 1 > (1 << (31 - bN))
        or n_dest + 1 > (1 << (31 - bT))
        or nc * cap >= n
        or os.environ.get("MPI_GRID_SELECT") == "flat"
    )


def sorted_mover_block(dest, n_dest: int, block: int, *, chunk: int = 4096,
                       cap: int = 512):
    """Two-level leaver selection compacted to a DENSE MOVER BLOCK of
    static width ``block`` — the front end of the mover-sparse migrate
    engine (ISSUE 4).

    Same chunk-sort / candidate-slice / packed-repack machinery as
    :func:`sorted_dest_counts_batched`'s fast path (same exactness
    argument: when no chunk overflows ``cap``, the repacked candidate
    sort reproduces the stable (dest, position) order of the flat packed
    sort bit-for-bit), but with NO internal ``lax.cond`` — the caller
    owns the fallback, because only the caller can route the whole step
    (selection + exchange + landing) to the dense engine in one branch.
    Dead candidates pack as ``n_dest << bN`` with ZERO position bits, so
    the extracted block's tail beyond the leavers is zeros without any
    extra masking.

    Args:
      dest: [V, n] int32 destinations; sentinel ``n_dest`` = stayer.
      n_dest: number of destinations.
      block: static mover-block width (``mover_cap``).
      chunk, cap: selection parameters; must satisfy
        :func:`sparse_select_feasible` (raises ValueError otherwise).

    Returns:
      ``(block_rows [V, block], counts [V, n_dest], bounds [V, n_dest+1],
      ok)`` — row indices of the leavers of each vrank in stable (dest,
      position) order, zero-padded past the leaver count; exact counts
      and segment bounds; and ``ok``, ONE scalar that is True iff no
      chunk overflowed ``cap`` AND every vrank's leavers fit in
      ``block``. When ``ok`` is False the other outputs are NOT
      contractual (candidates may be missing movers) and the caller must
      take its dense branch.
    """
    V, n = dest.shape
    if not sparse_select_feasible(n, n_dest, chunk=chunk, cap=cap):
        raise ValueError(
            f"sorted_mover_block infeasible for n={n}, n_dest={n_dest}, "
            f"chunk={chunk}, cap={cap} (gate on sparse_select_feasible)"
        )
    bN = max(1, (n - 1).bit_length())
    bT = (chunk - 1).bit_length()
    nc = -(-n // chunk)
    npad = nc * chunk - n
    ch = dest
    if npad:
        ch = jnp.concatenate(
            [dest, jnp.full((V, npad), n_dest, jnp.int32)], axis=1
        )
    ch = ch.reshape(V, nc, chunk)
    lc = jnp.sum((ch != n_dest).astype(jnp.int32), axis=-1)  # [V, nc]
    iota_t = jnp.arange(chunk, dtype=jnp.int32)
    packed1 = jax.lax.sort((ch << bT) | iota_t, dimension=-1, is_stable=False)
    cand = jax.lax.slice_in_dim(packed1, 0, cap, axis=2)
    dest_c = cand >> bT
    pos_g = (
        jnp.arange(nc, dtype=jnp.int32)[None, :, None] * chunk
    ) | (cand & (chunk - 1))
    live = (
        jnp.arange(cap, dtype=jnp.int32)[None, None, :] < lc[:, :, None]
    )
    packed2 = jnp.where(live, (dest_c << bN) | pos_g, jnp.int32(n_dest << bN))
    packed2 = jax.lax.sort(
        packed2.reshape(V, nc * cap), dimension=-1, is_stable=False
    )
    order_c = packed2 & jnp.int32((1 << bN) - 1)
    edges = jnp.arange(n_dest + 1, dtype=jnp.int32) << bN
    bounds = jax.vmap(
        lambda p: jnp.searchsorted(p, edges, side="left")
    )(packed2).astype(jnp.int32)
    counts = bounds[:, 1:] - bounds[:, :-1]
    if block <= nc * cap:
        block_rows = jax.lax.slice_in_dim(order_c, 0, block, axis=1)
    else:
        block_rows = jnp.zeros((V, block), jnp.int32).at[:, : nc * cap].set(
            order_c
        )
    leavers = jnp.sum(counts, axis=1)
    ok = (jnp.max(lc) <= cap) & (jnp.max(leavers) <= block)
    return block_rows, counts, bounds, ok


def bounds_dense(keys_sorted, n_edges: int, stride: int = 1,
                 key_bound: int = None):
    """``jnp.searchsorted(keys_sorted, arange(n_edges) * stride, 'left')``
    without the rank scatter — two single-operand sorts.

    JAX's ``method="sort"`` searchsorted ranks the concatenated array via
    ``zeros.at[argsort(x)].set(iota)`` — a full-length SCATTER, ~120 ns
    per element on TPU: measured **1140 ms** for 67M keys × 2M edges at
    the 64M north-star deposit (scripts/knockout_deposit.py), the single
    largest phase of the fused config-5 step. For the dense edge grids
    every bounds computation in this repo uses, the scatter is
    unnecessary:

      1. merge by ONE single-operand sort of interleaved codes
         ``keys*2+1`` / ``edges*2`` (the even query code ties BEFORE the
         odd key code of equal value — exactly ``side='left'``). At the
         merged position ``p`` of edge ``k``: ``bounds[k] = p - k``.
      2. the per-position values ``d[p] = p - k(p)`` at query positions
         (+inf elsewhere) are NON-DECREASING in ``k`` (bounds is
         monotone), so ONE more single-operand sort compacts them into
         edge order; take the first ``n_edges``.

    Requires ``keys_sorted`` ascending int32 with values in
    ``[0, key_bound]`` (sentinel values ≥ ``n_edges * stride`` sort past
    every edge and are counted in no bound — matching searchsorted).
    ``key_bound`` defaults to ``n_edges * stride`` (one stride of
    sentinel headroom past the last edge); callers with larger sentinels
    must pass their true static bound. Falls back to ``jnp.searchsorted`` when the ×2 code would
    overflow int32.
    """
    n = keys_sorted.shape[0]
    if key_bound is None:
        key_bound = n_edges * stride
    max_code = 2 * max(int(key_bound), (n_edges - 1) * stride) + 1
    if max_code >= 2**31 or keys_sorted.dtype != jnp.int32:
        if (n_edges - 1) * stride >= 2**31:
            # the fallback's own int32 edge arange would wrap negative
            # and silently return garbage — and edges past int32max are
            # meaningless against int32 keys anyway
            raise ValueError(
                f"bounds_dense: edge grid (n_edges={n_edges}, "
                f"stride={stride}) exceeds int32"
            )
        return jnp.searchsorted(
            keys_sorted,
            jnp.arange(n_edges, dtype=jnp.int32) * stride,
            side="left",
            method="sort",
        ).astype(jnp.int32)
    codes = jnp.concatenate(
        [
            keys_sorted * 2 + 1,
            jnp.arange(n_edges, dtype=jnp.int32) * (2 * stride),
        ]
    )
    m = jax.lax.sort(codes, is_stable=False)
    p = jnp.arange(n + n_edges, dtype=jnp.int32)
    k = (m >> 1) // stride
    d = jnp.where((m & 1) == 0, p - k, jnp.int32(2**31 - 1))
    ds = jax.lax.sort(d, is_stable=False)
    return ds[:n_edges]


def match_vma(tree, ref):
    """Promote every leaf of ``tree`` to ``ref``'s varying mesh axes
    (no-op outside shard_map or when already aligned).

    Pallas kernels under shard_map want every input carrying the same
    varying-axes set; a mismatched scalar-prep array can make tracing
    insert ``pvary`` inside the kernel jaxpr, which Mosaic rejects. The
    branches of a ``lax.cond`` must agree on it too."""
    axes = jax.typeof(ref).vma

    def one(x):
        want = tuple(a for a in axes if a not in jax.typeof(x).vma)
        return jax.lax.pcast(x, want, to="varying") if want else x

    return jax.tree.map(one, tree)


def dest_histogram(dest, nranks: int, valid=None):
    """Per-destination send counts [nranks] (int32), JAX path.

    ``dest`` may contain the sentinel value ``nranks`` for invalid (padding)
    rows; those fall in an extra trash segment that is sliced off.
    """
    weights = jnp.ones(dest.shape, dtype=jnp.int32)
    if valid is not None:
        weights = weights * valid.astype(jnp.int32)
    seg = jax.ops.segment_sum(weights, dest, num_segments=nranks + 1)
    return seg[:nranks]


def dest_histogram_np(dest, nranks: int, valid=None):
    """NumPy twin of ``dest_histogram`` for the oracle backend."""
    weights = np.ones(dest.shape, dtype=np.int64)
    if valid is not None:
        weights = weights * valid.astype(np.int64)
    return np.bincount(dest, weights=weights, minlength=nranks + 1)[
        :nranks
    ].astype(np.int32)
