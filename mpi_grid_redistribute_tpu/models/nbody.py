"""Periodic N-body drift loop + fused particle-mesh pipeline.

Rebuilds the reference's driver-defined composite flows (SURVEY.md §3.3-3.4,
BASELINE.json configs[3] and [4] — mount empty):

  config 4:  for step in range(S): pos += vel*dt; wrap; redistribute(pos, vel)
  config 5:  redistribute(pos, mass) then CIC-deposit onto the rank mesh

TPU-first shape: the whole step (drift + wrap + bin + pack + all_to_all +
compact [+ deposit]) is ONE jitted SPMD program; multi-step runs use
``lax.scan`` so S steps compile once with static shapes. ``out_capacity``
equals the input padding, making the step state a fixed-shape carry.
"""

from __future__ import annotations

import dataclasses
import os

from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.sharding import Mesh, PartitionSpec as P
from jax import shard_map

from mpi_grid_redistribute_tpu.domain import Domain, ProcessGrid
from mpi_grid_redistribute_tpu.ops import (
    binning,
    deposit as deposit_lib,
    pallas_driftbin,
)
from mpi_grid_redistribute_tpu.parallel import exchange, migrate, mesh as mesh_lib
from mpi_grid_redistribute_tpu.telemetry.phases import traced_span


@dataclasses.dataclass(frozen=True)
class DriftConfig:
    """Static configuration for the drift loop (hashable: jit-safe)."""

    domain: Domain
    grid: ProcessGrid
    dt: float
    capacity: int
    n_local: int  # padded rows per shard; also the out_capacity
    deposit_shape: Optional[Tuple[int, ...]] = None  # global CIC mesh cells
    deposit_method: str = "scan"  # "scan" (double-float exact) |
    # "mxu" (Pallas segmented-sum throughput engine, f32 class) |
    # "segment" (scatter-add)
    # on-device migrant budget per (vrank, step) for the vrank migrate
    # path's compact routing (None -> V * capacity); see
    # parallel.migrate.shard_migrate_vranks_fn
    local_budget: Optional[int] = None
    # load-balanced decomposition for the vrank migrate path: the spatial
    # cell grid plus a static row-major cell -> global-rank tuple
    # (migrate.balanced_assignment). Both or neither; vgrid then only
    # fixes the vrank count. See shard_migrate_vranks_fn.
    cells: Optional[ProcessGrid] = None
    assignment: Optional[Tuple[int, ...]] = None
    # migrate-loop engine selection (parallel.exchange.resolve_engine):
    # "auto" picks the mover-sparse fast path when eligible (vgrid on a
    # single device — see shard_migrate_vranks_fn), "sparse" asks for it
    # explicitly (degrades to the dense planar step on cross-device
    # meshes — journaled as engine_resolved when a recorder is wired),
    # "planar" forces the dense engine. The canonical-only engines
    # ("rowmajor", "neighbor") are rejected here.
    engine: str = "auto"
    # static mover-block width for the sparse fast path (rows a vrank
    # may send per step through the O(movers) branch; None -> the
    # resolved local_budget). Grow on sustained fallbacks via
    # api.MoverCapacity.
    mover_cap: Optional[int] = None


def service_drift(pos, vel, dt):
    """One service-loop drift, in-graph: float32 advance + periodic wrap
    with the SAME arithmetic as ``ServiceDriver._advance``'s host-side
    numpy drift (``(p + v*dt) % 1.0`` then the ``>= 1.0`` clamp), so a
    resident macro-step (``service/resident.py``) is bit-identical to
    the eager loop for any chunk length. ``wrap_periodic`` is NOT used
    here on purpose — its arithmetic differs in the last ulp near cell
    edges, which is enough to re-home a particle."""
    one = jnp.asarray(1.0, pos.dtype)
    pos = (pos + vel * jnp.asarray(dt, pos.dtype)) % one
    # float32 `%` can round a tiny negative up to exactly 1.0, which is
    # outside the periodic domain [0, 1)
    return jnp.where(pos >= one, pos - one, pos)


def make_drift_step(cfg: DriftConfig, mesh: Mesh):
    """Build the jitted single-step function.

    ``step(pos, vel, count) -> (pos, vel, count, stats[, rho])`` on global
    padded arrays ([R*n_local, ...] / [R]); ``rho`` is the global density
    mesh when ``cfg.deposit_shape`` is set.
    """
    mesh_lib.validate_mesh_for_grid(mesh, cfg.grid)
    axes = cfg.grid.axis_names
    spec = P(axes)
    redist = exchange.shard_redistribute_fn(
        cfg.domain, cfg.grid, cfg.capacity, cfg.n_local
    )
    dep_fn = None
    if cfg.deposit_shape is not None:
        dep_fn, _ = deposit_lib.shard_deposit_fn(
            cfg.domain, cfg.grid, cfg.deposit_shape,
            method=cfg.deposit_method,
        )

    def shard_step(pos, vel, count):
        pos = pos + vel * jnp.asarray(cfg.dt, pos.dtype)
        pos = binning.wrap_periodic(pos, cfg.domain)
        pos, count, vel, stats = redist(pos, count, vel)
        if dep_fn is None:
            return pos, vel, count, stats
        rho = dep_fn(pos, jnp.ones(pos.shape[:1], pos.dtype), count)
        return pos, vel, count, stats, rho

    out_specs = (
        spec,
        spec,
        spec,
        # 5 explicit specs: the rowmajor engine carries no `fallback`
        # trace, so that leaf stays at its None default (empty pytree
        # node — a 6th spec here would demand a leaf the engine never
        # produces)
        exchange.RedistributeStats(spec, spec, spec, spec, spec),
    )
    if dep_fn is not None:
        out_specs = out_specs + (deposit_lib.deposit_out_spec(cfg.domain, cfg.grid),)
    return jax.jit(
        shard_map(
            shard_step, mesh=mesh, in_specs=(spec, spec, spec),
            out_specs=out_specs,
        )
    )


def make_drift_loop(
    cfg: DriftConfig,
    mesh: Mesh,
    n_steps: int,
    deposit_each_step: bool = False,
):
    """S steps in one compiled program via ``lax.scan``.

    Returns ``loop(pos, vel, count) -> (pos, vel, count, stats)`` where
    stats leaves are stacked per step ([S, ...]); with a deposit mesh
    configured, the *final* step's density is also returned. By default the
    deposit runs once, on the final state (keeping only the last avoids an
    S-times-larger live buffer); ``deposit_each_step=True`` runs it inside
    every scanned step (the config-5 "fused every step" workload), carrying
    only the latest mesh.
    """
    if deposit_each_step and cfg.deposit_shape is None:
        raise ValueError("cfg.deposit_shape is required for deposit")
    step = make_drift_step(
        dataclasses.replace(
            cfg,
            deposit_shape=cfg.deposit_shape if deposit_each_step else None,
        ),
        mesh,
    )
    dep = None
    if cfg.deposit_shape is not None and not deposit_each_step:
        dep = build_deposit_step(cfg, mesh)

    def loop(pos, vel, count):
        def body(carry, _):
            p, v, c = carry[:3]
            out = step(p, v, c)
            p, v, c, stats = out[:4]
            new_carry = (p, v, c) + ((out[4],) if len(out) > 4 else ())
            return new_carry, stats

        init = (pos, vel, count)
        if deposit_each_step:
            init = init + (
                jnp.zeros(
                    deposit_lib.global_node_shape(
                        cfg.domain, cfg.deposit_shape
                    ),
                    jnp.float32,
                ),
            )
        carry, stats = lax.scan(body, init, None, length=n_steps)
        pos_f, vel_f, count_f = carry[:3]
        if deposit_each_step:
            return pos_f, vel_f, count_f, stats, carry[3]
        if dep is None:
            return pos_f, vel_f, count_f, stats
        rho = dep(pos_f, jnp.ones(pos_f.shape[:1], pos_f.dtype), count_f)
        return pos_f, vel_f, count_f, stats, rho

    return jax.jit(loop)


def make_migrate_step(cfg: DriftConfig, mesh: Mesh):
    """Fast drift step on resident slots (see :mod:`..parallel.migrate`).

    State is ``(pos[R*n_local, D], vel[R*n_local, D], alive[R*n_local])``;
    only boundary-crossing migrants ride the all-to-all, so per-step cost
    scales with migrant count, not total particles (full-array row gathers
    dominate the canonical :func:`make_drift_step` on TPU).
    ``cfg.capacity`` here bounds *migrants* per (source, dest) pair.

    Returns ``step(pos, vel, alive) -> (pos, vel, alive, stats[, rho])``.
    """
    mesh_lib.validate_mesh_for_grid(mesh, cfg.grid)
    axes = cfg.grid.axis_names
    spec = P(axes)
    mig = migrate.shard_migrate_fn(cfg.domain, cfg.grid, cfg.capacity)
    dep_fn = None
    if cfg.deposit_shape is not None:
        dep_fn, _ = deposit_lib.shard_deposit_fn_masked(
            cfg.domain, cfg.grid, cfg.deposit_shape,
            method=cfg.deposit_method,
        )

    def shard_step(pos, vel, alive):
        pos = pos + vel * jnp.asarray(cfg.dt, pos.dtype)
        pos = binning.wrap_periodic(pos, cfg.domain)
        pos, alive, vel, stats = mig(pos, alive, vel)
        if dep_fn is None:
            return pos, vel, alive, stats
        rho = dep_fn(pos, jnp.ones(pos.shape[:1], pos.dtype), alive)
        return pos, vel, alive, stats, rho

    # scalar-per-shard leaves stack on the shard axis -> global [R]; the
    # flow leaf is a [1, R] row per shard -> global [R, R] (rows sharded);
    # the flat engine carries no sparse path, so fast_path stays None
    stats_spec = migrate.MigrateStats(
        *([spec] * (len(migrate.MigrateStats._fields) - 2)),
        flow=P(axes, None),
        fast_path=None,
    )
    out_specs = (spec, spec, spec, stats_spec)
    if dep_fn is not None:
        out_specs = out_specs + (deposit_lib.deposit_out_spec(cfg.domain, cfg.grid),)
    return jax.jit(
        shard_map(
            shard_step, mesh=mesh, in_specs=(spec, spec, spec),
            out_specs=out_specs,
        )
    )


def make_migrate_loop(
    cfg: DriftConfig,
    mesh: Mesh,
    n_steps: int,
    vgrid: Optional[ProcessGrid] = None,
    deposit_each_step: bool = False,
):
    """S fast-migration steps in one compiled program via ``lax.scan``.

    ``loop(pos, vel, alive) -> (pos_planar, vel_planar, alive, stats)``
    with stats leaves stacked per step ([S, R]); with ``cfg.deposit_shape``
    set, the final step's global density mesh is appended.
    ``deposit_each_step=True`` fuses the CIC deposit into EVERY scanned
    step (the config-5 workload: exchange + deposit in one compiled
    program, here on the fast resident-slot engine), carrying only the
    latest mesh.

    LAYOUT CONTRACT (struct-of-arrays): ``pos``/``vel`` are accepted as
    ``[N, D]`` host arrays (transposed for free on the host) or as
    PLANAR component-major flat arrays ``[D * N]`` (all x's, then all
    y's, ...; see :func:`rows_to_planar`), and are RETURNED PLANAR FLAT
    (:func:`planar_to_rows` recovers ``[N, D]`` on the host). Any
    row-major ``[N, D]`` device buffer — even a transient reshape at the
    program boundary — materializes in the tiled T(8,128) layout (42.7x
    padding; 32 GB at 64M particles, measured: the reshape alone OOMs
    the 16 GB chip), so the loop's device interface is planar end to
    end.

    The scan carry is the *fused* PLANAR ``[2D+1, n]`` payload matrix
    (position + velocity component rows + alive row; particles on the lane
    axis), fused once on entry and split once on exit, so each step moves
    migrants with a single gather/all_to_all/scatter
    (:mod:`..parallel.migrate`). The planar orientation is what lets the
    scan carry stay COMPACT — a ``[n, K]`` carry materializes in the tiled
    T(8,128) layout (18x padding at K=7; the round-2 single-chip cap at
    ~16-32M particles), while ``[K, n]`` pads only 8/7 on the sublane
    axis, so the 64M-particle north-star fits one chip.

    With ``vgrid``, each device hosts ``V = vgrid.nranks`` subdomain slabs
    of the full ``cfg.grid.shape * vgrid.shape`` grid (virtual ranks —
    oversubscription). Global row layout is then device-major:
    device d's rows hold its V slabs consecutively, ``n_local`` rows each,
    and ``cfg.capacity`` bounds migrants per (source vrank, destination
    global rank) pair; CIC deposit assembles per-vrank blocks on device
    (deposit_lib.shard_deposit_vranks_fn).
    """
    mesh_lib.validate_mesh_for_grid(mesh, cfg.grid)
    axes = cfg.grid.axis_names
    spec = P(axes)
    D = cfg.domain.ndim
    V = 1 if vgrid is None else vgrid.nranks
    mover_cap = None  # set on the sparse-eligible vrank path below
    if vgrid is None:
        if cfg.assignment is not None or cfg.cells is not None:
            raise ValueError(
                "cells/assignment require the vrank path (pass vgrid)"
            )
        mig = migrate.shard_migrate_fused_fn(
            cfg.domain, cfg.grid, cfg.capacity
        )
    else:
        if (
            cfg.assignment is not None
            and cfg.deposit_shape is not None
            and not (
                cfg.deposit_method in ("scan", "mxu") and mesh.size == 1
            )
        ):
            # the DEVICE-keyed planar deposit doesn't care which vrank a
            # particle rides in — it keys by position — so on one device
            # (which owns the whole contiguous mesh) LPT assignment and
            # deposit compose; multi-device LPT leaves each device a
            # non-contiguous cell set, which no block deposit can serve
            raise ValueError(
                "assignment-decomposed vranks own non-contiguous cell "
                "sets; the block deposit assumes each device owns a "
                "contiguous region — deposit on the canonical layout, "
                "or use deposit_method='scan'/'mxu' on a single device"
            )
        eng = exchange.resolve_engine(
            cfg.engine, vranks=True, n_devices=cfg.grid.nranks
        )
        if eng == "sparse":
            mover_cap = (
                cfg.mover_cap
                if cfg.mover_cap is not None
                else (
                    cfg.local_budget
                    if cfg.local_budget is not None
                    else vgrid.nranks * cfg.capacity
                )
            )
        mig = migrate.shard_migrate_vranks_fn(
            cfg.domain, cfg.grid, vgrid, cfg.capacity,
            local_budget=cfg.local_budget,
            cells=cfg.cells, assignment=cfg.assignment,
            mover_cap=mover_cap,
        )
    # Fused Pallas drift+wrap+bin (round 4): one streaming pass replaces
    # the XLA drift chain AND the engine's binning (the knockout's 9x-
    # over-roofline phase 0-1). Resolved at BUILD time like the landing
    # scatter impl: MPI_GRID_DRIFTBIN=xla opts out; the kernel itself
    # falls back to its bit-identical XLA twin when the shape/domain
    # contract doesn't hold (ops/pallas_driftbin.py).
    use_driftbin = (
        os.environ.get("MPI_GRID_DRIFTBIN") != "xla"
        and jax.devices()[0].platform == "tpu"
        and vgrid is not None
        and cfg.grid.nranks == 1
        and cfg.assignment is None
    )
    full_grid = vgrid  # Dev == 1: the full Cartesian grid IS vgrid

    dep_fn = None
    if cfg.deposit_shape is not None:
        if cfg.deposit_method in ("scan", "mxu"):
            # PLANAR deposit (round 4): consumes the fused component-major
            # rows directly — no in-loop [n, 3] transpose (a [64M, 3]
            # transient is a 32 GB T(8,128) allocation; round-3 verdict
            # item 3), so config 5 runs at the 64M north-star shape.
            # DEVICE-keyed (late round 4): segments are device-local
            # global cells, so the per-vrank ghost-block assembly (64
            # sequential dynamic-slice adds, ~54 ms of the 4.2M deposit —
            # scripts/knockout_deposit.py) vanishes into the segment sums.
            # "mxu" (late round 4): the Pallas segmented-sum kernel
            # replaces prefix scans + bounds + boundary gathers entirely
            # (ops/pallas_segdep.py) — throughput engine, f32-accumulation
            # accuracy class; "scan" remains the double-float engine.
            if cfg.deposit_method == "mxu":
                # slab-keyed engine (late round 4): with canonical block
                # vranks the post-redistribute state is slab-partitioned,
                # so vrank-major keys turn the flat 64M payload sort into
                # a batched per-slab [V, n] sort (1.69x at 64M —
                # scripts/microbench_slab_sort.py). LPT/cells vranks
                # break the slab invariant -> flat position-keyed engine.
                slab_ok = (
                    vgrid is not None
                    and cfg.assignment is None
                    and cfg.cells is None
                    and all(
                        (m // g) % v == 0
                        for m, g, v in zip(
                            cfg.deposit_shape,
                            cfg.grid.shape,
                            vgrid.shape,
                        )
                    )
                )
                dep_fn = deposit_lib.shard_deposit_device_mxu_fn(
                    cfg.domain, cfg.grid, cfg.deposit_shape,
                    vgrid=vgrid if slab_ok else None,
                )
            else:
                dep_fn = deposit_lib.shard_deposit_device_planar_fn(
                    cfg.domain, cfg.grid, cfg.deposit_shape
                )
        elif vgrid is None:
            dep_fn, _ = deposit_lib.shard_deposit_fn_masked(
                cfg.domain, cfg.grid, cfg.deposit_shape,
                method=cfg.deposit_method,
            )
        else:
            dep_fn = deposit_lib.shard_deposit_vranks_fn(
                cfg.domain, cfg.grid, vgrid, cfg.deposit_shape,
                method=cfg.deposit_method,
            )

    if deposit_each_step and dep_fn is None:
        raise ValueError("cfg.deposit_shape is required for deposit")

    def _deposit(fused):
        """CIC density of a planar fused state ([K, V*n] or [K, n])."""
        pos_rows = lax.bitcast_convert_type(fused[:D, :], jnp.float32)
        valid_flat = fused[-1, :] > 0
        if cfg.deposit_method == "mxu":
            # unit mass: None drops the mass operand from the payload
            # sort (the deposit's remaining dominant cost)
            return dep_fn(pos_rows, None, valid_flat)
        if cfg.deposit_method == "scan":
            # planar path: component-major rows straight through
            return dep_fn(
                pos_rows,
                jnp.ones(pos_rows.shape[1:], jnp.float32),
                valid_flat,
            )
        if vgrid is not None:
            pv = pos_rows.reshape(D, V, -1).transpose(1, 2, 0)
            valid = valid_flat.reshape(V, -1)
        else:
            pv = pos_rows.T
            valid = valid_flat
        return dep_fn(pv, jnp.ones(pv.shape[:-1], pv.dtype), valid)

    def shard_loop(pos_flat, vel_flat, alive):
        # scan requires carry leaves already marked device-varying (some
        # init_state outputs are iota-derived and start unvaried)
        def _vary(x):
            missing = tuple(
                a for a in axes if a not in jax.typeof(x).vma
            )
            return lax.pcast(x, missing, to="varying") if missing else x

        # inputs cross the shard_map boundary as PLANAR flat arrays
        # (component-major [D * n]): a 1-D parameter converts compactly
        # and the reshape to [D, n] splits the MAJOR axis — no row-major
        # [n, D] buffer ever exists on device (the T(8,128) input copy of
        # one is 42.7x padded: 32 GB at 64M particles, measured).
        # The fused carry is INT32 (values bitcast): TPU float vector
        # chains flush denormal f32 bit patterns (any bitcast int payload
        # < 2^23 — measured on-chip, round 4), integer lanes don't; the
        # drift below views position/velocity rows as f32 for the
        # arithmetic only (migrate.fuse_fields).
        # mig:enter / mig:exit scope the once-per-call work outside the
        # scan: the planar fuse and free-stack argsort, the final split.
        with traced_span("mig:enter"):
            fused = jnp.concatenate(
                [
                    lax.bitcast_convert_type(
                        pos_flat.reshape(D, -1), jnp.int32
                    ),
                    lax.bitcast_convert_type(
                        vel_flat.reshape(D, -1), jnp.int32
                    ),
                    alive.astype(jnp.int32)[None, :],
                ],
                axis=0,
            )
            state = migrate.init_state(
                fused, vranks=V, batched=vgrid is not None
            )
            state = jax.tree.map(_vary, state)

        def body(carry, _):
            state = carry[0]
            f = state.fused  # planar int32 [K, m]
            if use_driftbin:
                # ONE streaming Pallas pass: drift + wrap + bin + dest
                # key (ops/pallas_driftbin.py; bit-identical to the XLA
                # chain below by test; 6-7x its measured cost — the XLA
                # chain runs ~9x its bandwidth roofline)
                with traced_span("mig:drift"):
                    f, dest_key = pallas_driftbin.drift_wrap_bin(
                        f, float(cfg.dt), cfg.domain, full_grid,
                        V, V,
                    )
                state, stats = mig(state._replace(fused=f), dest_key)
            else:
                with traced_span("mig:drift"):
                    pf = lax.bitcast_convert_type(f[:D, :], jnp.float32)
                    vf = lax.bitcast_convert_type(
                        f[D : 2 * D, :], jnp.float32
                    )
                    p = pf + vf * jnp.asarray(cfg.dt, pf.dtype)
                    p = binning.wrap_periodic_planar(p, cfg.domain)
                    f = jnp.concatenate(
                        [lax.bitcast_convert_type(p, jnp.int32), f[D:, :]],
                        axis=0,
                    )
                state, stats = mig(state._replace(fused=f))
            new_carry = (state,)
            if deposit_each_step:
                new_carry = (state, _deposit(state.fused))
            return new_carry, stats

        init = (state,)
        if deposit_each_step:
            if all(cfg.domain.periodic):
                # sharded local block; ends in fold_ghosts (ppermute) ->
                # device-varying, so the carry must start varying too
                rho0 = _vary(jnp.zeros(
                    tuple(
                        m // g
                        for m, g in zip(cfg.deposit_shape, cfg.grid.shape)
                    ),
                    jnp.float32,
                ))
            else:
                # dense-assembled mesh; ends in assemble_dense's psum ->
                # axis-INVARIANT, and the carry must match (a varying
                # init would fail lax.scan's carry-type check)
                rho0 = jnp.zeros(
                    deposit_lib.global_node_shape(
                        cfg.domain, cfg.deposit_shape
                    ),
                    jnp.float32,
                )
            init = (state, rho0)
        carry, stats = lax.scan(body, init, None, length=n_steps)
        state = carry[0]
        # planar exit: row-slices of the fused matrix, flattened
        # component-major — again no [n, D] buffer materializes
        with traced_span("mig:exit"):
            f = state.fused
            pos_f = lax.bitcast_convert_type(
                f[:D, :], jnp.float32
            ).reshape(-1)
            vel_f = lax.bitcast_convert_type(
                f[D : 2 * D, :], jnp.float32
            ).reshape(-1)
            alive_f = f[-1, :] > 0
        if dep_fn is None:
            return pos_f, vel_f, alive_f, stats
        rho = carry[1] if deposit_each_step else _deposit(state.fused)
        return pos_f, vel_f, alive_f, stats, rho

    # stats leaves are [S, V] per shard (scan-stacked): shard axis 1. The
    # flow leaf is [S, V, R_total] per shard — vrank rows stack on axis 1
    # into the global [S, R_total, R_total] step-stacked flow matrix.
    # fast_path is a [S, V] leaf exactly when the sparse engine was
    # requested (mover_cap resolved above), matching the engine's pytree.
    stats_spec = migrate.MigrateStats(
        *([P(None, axes)] * (len(migrate.MigrateStats._fields) - 2)),
        flow=P(None, axes, None),
        fast_path=None if mover_cap is None else P(None, axes),
    )
    out_specs = (spec, spec, spec, stats_spec)
    if dep_fn is not None:
        out_specs = out_specs + (deposit_lib.deposit_out_spec(cfg.domain, cfg.grid),)
    jitted = jax.jit(
        shard_map(
            shard_loop, mesh=mesh, in_specs=(spec, spec, spec),
            out_specs=out_specs,
        )
    )

    n_blocks = mesh.size

    def loop(pos, vel, alive):
        """Accepts pos/vel as [N, D] HOST arrays (converted to the planar
        device format for free via :func:`rows_to_planar`) or as planar
        flat [D*N] arrays (the canonical device format; shard-major
        component-major — what this loop RETURNS). Recover [N, D] rows on
        the host with ``planar_to_rows(out, D, mesh.size)``. A 2-D
        DEVICE array is rejected: it already materialized the 42.7x
        padded T(8,128) layout — build planar arrays host-side instead.
        """

        def to_planar(a):
            if a.ndim == 1:
                return a
            if isinstance(a, np.ndarray):
                return rows_to_planar(a, n_blocks)
            raise TypeError(
                "make_migrate_loop: pass device arrays in planar flat "
                "format (rows_to_planar); a [N, D] device buffer is "
                "already stored 42.7x padded (T(8,128))"
            )

        return jitted(to_planar(pos), to_planar(vel), alive)

    return loop


def rows_to_planar(a, n_blocks: int):
    """Host-side pack of row-major ``[N, D]`` particle data into the
    migrate loop's planar device format: shard-major blocks (``n_blocks``
    = mesh device count), component-major within each block (all x's of
    the block, then all y's, ...). Free on the host; avoids ever placing
    a narrow-minor ``[N, D]`` buffer on the TPU (42.7x T(8,128) padding,
    measured). ``n_blocks`` is REQUIRED and must equal ``mesh.size`` —
    a wrong block count packs other shards' components into each shard
    with no error to catch it."""
    a = np.asarray(a)
    n, d = a.shape
    if n % n_blocks:
        raise ValueError(f"rows {n} not divisible by n_blocks {n_blocks}")
    return np.ascontiguousarray(
        a.reshape(n_blocks, n // n_blocks, d).transpose(0, 2, 1)
    ).reshape(-1)


def planar_to_rows(a, ndim: int, n_blocks: int):
    """Inverse of :func:`rows_to_planar`: planar flat ``[D * N]`` back to
    row-major ``[N, D]`` on the host."""
    a = np.asarray(a)
    n = a.size // (ndim * n_blocks)
    return np.ascontiguousarray(
        a.reshape(n_blocks, ndim, n).transpose(0, 2, 1)
    ).reshape(-1, ndim)


def build_deposit_masked(cfg: DriftConfig, mesh: Mesh):
    """Mask-input fused deposit for migration-path state."""
    if cfg.deposit_shape is None:
        raise ValueError("cfg.deposit_shape is required for deposit")
    fn, _ = deposit_lib.shard_deposit_fn_masked(
        cfg.domain, cfg.grid, cfg.deposit_shape,
        method=cfg.deposit_method,
    )
    axes = cfg.grid.axis_names
    spec = P(axes)
    sharded = shard_map(
        fn, mesh=mesh, in_specs=(spec, spec, spec),
        out_specs=deposit_lib.deposit_out_spec(cfg.domain, cfg.grid)
    )
    return jax.jit(sharded)


def build_deposit_step(cfg: DriftConfig, mesh: Mesh):
    """Standalone fused deposit on already-redistributed state (config 5)."""
    if cfg.deposit_shape is None:
        raise ValueError("cfg.deposit_shape is required for deposit")
    return deposit_lib.build_deposit(
        mesh, cfg.domain, cfg.grid, cfg.deposit_shape,
        method=cfg.deposit_method,
    )
