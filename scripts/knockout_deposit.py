"""Knockout profiling of the PLANAR scan deposit at the 64M north-star
shape (config 5's non-migrate cost): time the deposit truncated after each
phase, scan-length-differenced.

The fused config-5 step at 64M measures 1931 ms while the migrate step
alone is ~261 ms — the deposit is ~1670 ms and has never had its own
attribution. Phases of ``ops.deposit.cic_deposit_vranks_planar``:

  1. key build: rel / i0 / flat segment key (elementwise)
  2. payload sort: (key, iota, rel0..2, mass) — 6 operands, V*n rows
  3. bounds: searchsorted of n_segments+1 edges (method="sort")
  4. channel prefixes: corner-weight rows + double-float tiled prefix
     (Pallas dfscan) + tile-total scan, per channel group
  5. boundary gathers + differencing -> per_cell [8, V*n_cells]
  6. placement: reshape + corner pads + vrank assembly + ghost fold

MAINTENANCE: phases are a DELIBERATE copy of the deposit core (a
truncating profiler cannot share the un-truncatable original). Phase 6
must match the standalone deposit cost inferred from
bench/config5_deposit.py minus the migrate step.

Usage: python scripts/knockout_deposit.py [n_per_vrank]
       KNOCKOUT_GRID=4,4,4 python scripts/knockout_deposit.py 1048576
"""

from __future__ import annotations

import itertools
import math
import os
import sys

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))

import numpy as np
import jax
import jax.numpy as jnp

from mpi_grid_redistribute_tpu.ops import binning, deposit
from mpi_grid_redistribute_tpu.utils import profiling

GRID = tuple(
    int(x) for x in os.environ.get("KNOCKOUT_GRID", "4,4,4").split(",")
)
FILL = 0.9
MESH_CELLS = 128
HBM_PEAK = 819e9


def truncated_deposit(dev_block, V, n, phase, channel_group=2, tile=256):
    """Planar deposit cut after ``phase`` (copy of
    deposit.cic_deposit_device_planar's core, Dev=1: DEVICE-cell keys,
    corner placement by static pads + periodic self-fold — the late-
    round-4 engine; the per-vrank assembly it replaced measured +54 ms
    at 4.2M rows in this script's earlier form)."""
    D = 3
    n_cells = math.prod(dev_block)
    m = V * n
    strides = deposit._row_major_strides(dev_block)
    corners = list(itertools.product((0, 1), repeat=D))
    nch = len(corners)
    K = max(1, min(tile, m))
    n_pad = -(-m // K) * K
    inv_h = np.float32(MESH_CELLS / 1.0)

    def fn(state):
        pos_rows, mass, valid = state  # [3, m], [m], [m] bool

        def probe(*arrs):
            d = jnp.float32(0)
            for a in arrs:
                d = d + (
                    a.ravel()[0] == jnp.asarray(7, a.dtype)
                ).astype(jnp.float32)
            return (pos_rows.at[0, 0].add(d * 1e-12), mass, valid)

        # ---- 1: key build (elementwise, device-cell keys) -----------
        rel = []
        cell = jnp.zeros((m,), jnp.int32)
        for d in range(D):
            r = pos_rows[d] * inv_h  # dev_lo = 0 on the unit domain
            r = jnp.where(valid, r, 0.0)
            i0_d = jnp.clip(
                jnp.floor(r).astype(jnp.int32), 0, dev_block[d] - 1
            )
            cell = cell + i0_d * jnp.int32(strides[d])
            rel.append(r)
        key = jnp.where(valid, cell, n_cells).astype(jnp.int32)
        mass_z = jnp.where(valid, mass, 0.0)
        rel_rows = jnp.stack(rel, axis=0)
        if phase == 1:
            return probe(key, mass_z, rel_rows)

        # ---- 2: payload sort ----------------------------------------
        iota = jnp.arange(m, dtype=jnp.int32)
        operands = (key, iota) + tuple(
            rel_rows[d] for d in range(D)
        ) + (mass_z,)
        s = jax.lax.sort(operands, num_keys=2, is_stable=False)
        keys_sorted = s[0]
        rel_s = jnp.stack(s[2 : 2 + D], axis=0)
        mass_s = s[2 + D]
        if phase == 2:
            return probe(keys_sorted, rel_s, mass_s)

        i0_s = jnp.clip(
            jnp.floor(rel_s).astype(jnp.int32),
            0,
            jnp.asarray(dev_block, jnp.int32)[:, None] - 1,
        )
        frac = jnp.clip(rel_s - i0_s.astype(rel_s.dtype), 0.0, 1.0)

        # ---- 3: bounds (KNOCKOUT_BOUNDS=xla for the jnp rank-scatter
        # searchsorted the engine used before binning.bounds_dense) ----
        n_segments = n_cells
        if os.environ.get("KNOCKOUT_BOUNDS") == "xla":
            bounds = jnp.searchsorted(
                keys_sorted,
                jnp.arange(n_segments + 1, dtype=jnp.int32),
                side="left",
                method="sort",
            ).astype(jnp.int32)
        else:
            bounds = binning.bounds_dense(
                keys_sorted, n_segments + 1, key_bound=n_segments
            )
        if phase == 3:
            return probe(bounds, frac)

        t_idx = bounds // K
        has_local = (bounds % K > 0)[None, :]
        lb = jnp.clip(bounds - 1, 0, n_pad - 1)
        cg = max(1, min(channel_group, nch))

        def per_group(corner_list, upto):
            rows = []
            for corner in corner_list:
                w = None
                for d in range(D):
                    t = frac[d] if corner[d] == 1 else 1.0 - frac[d]
                    w = t if w is None else w * t
                rows.append(mass_s * w)
            wg = jnp.stack(rows, axis=0)
            gch = wg.shape[0]
            wt = jnp.pad(wg, ((0, 0), (0, n_pad - m))).reshape(
                gch, n_pad // K, K
            )
            lhi, llo = deposit._tile_prefix_planar(wt)
            thi, tlo = deposit._df_cumsum(
                lhi[:, :, -1], axis=1, x_lo=llo[:, :, -1]
            )
            if upto == 4:
                return (lhi, llo, thi, tlo)
            zg = jnp.zeros((gch, 1), wg.dtype)
            s_hi = jnp.concatenate([zg, thi], axis=1)
            s_lo = jnp.concatenate([zg, tlo], axis=1)
            l_pack = jnp.concatenate(
                [lhi.reshape(gch, n_pad), llo.reshape(gch, n_pad)],
                axis=0,
            )
            s_pack = jnp.concatenate([s_hi, s_lo], axis=0)
            l_at = jnp.where(
                has_local, jnp.take(l_pack, lb, axis=1), 0.0
            )
            s_at = jnp.take(s_pack, t_idx, axis=1)
            g_hi, g_lo = deposit._df_add(
                s_at[:gch], s_at[gch:], l_at[:gch], l_at[gch:]
            )
            return (g_hi[:, 1:] - g_hi[:, :-1]) + (
                g_lo[:, 1:] - g_lo[:, :-1]
            )

        # ---- 4: channel weight build + prefixes (no gathers) --------
        if phase == 4:
            outs = []
            for g0 in range(0, nch, cg):
                outs.extend(per_group(corners[g0 : g0 + cg], 4))
            return probe(*outs)

        # ---- 5: + boundary gathers + differencing -------------------
        per_cell = jnp.concatenate(
            [
                per_group(corners[g0 : g0 + cg], 5)
                for g0 in range(0, nch, cg)
            ],
            axis=0,
        )
        if phase == 5:
            return probe(per_cell)

        # ---- 6: placement (corner pads + periodic self-fold) --------
        per_cell = per_cell.reshape((nch,) + dev_block)
        ghost = tuple(b + 1 for b in dev_block)
        total = jnp.zeros(ghost, dtype=mass.dtype)
        for kk, corner in enumerate(corners):
            pad = [
                (c, gg - b - c)
                for c, gg, b in zip(corner, ghost, dev_block)
            ]
            total = total + jnp.pad(per_cell[kk], pad)
        total = _self_fold(total)
        return probe(total)

    return fn


def _self_fold(rho_ghost):
    """Dev=1 periodic self-fold of the +1 ghost faces (fold_ghosts with
    grid extent 1 on every axis — no collectives)."""
    for a in range(3):
        mm = rho_ghost.shape[a] - 1
        ghost = jax.lax.slice_in_dim(rho_ghost, mm, mm + 1, axis=a)
        body = jax.lax.slice_in_dim(rho_ghost, 0, mm, axis=a)
        first = jax.lax.slice_in_dim(body, 0, 1, axis=a) + ghost
        rest = jax.lax.slice_in_dim(body, 1, mm, axis=a)
        rho_ghost = jnp.concatenate([first, rest], axis=a)
    return rho_ghost


def main():
    n = int(sys.argv[1]) if len(sys.argv) > 1 else 1 << 20
    V = math.prod(GRID)
    m = V * n
    dev_block = (MESH_CELLS,) * 3  # Dev = 1: the device owns the mesh
    rng = np.random.default_rng(0)
    pos = rng.random((3, m), np.float32)
    mass = np.ones((m,), np.float32)
    valid = rng.random(m) < FILL
    state = (
        jax.device_put(jnp.asarray(pos)),
        jax.device_put(jnp.asarray(mass)),
        jax.device_put(jnp.asarray(valid)),
    )
    print(
        f"grid {GRID} V={V} n={n} m={m} dev_block={dev_block} "
        f"segments={math.prod(dev_block)} "
        f"bounds={'xla' if os.environ.get('KNOCKOUT_BOUNDS') == 'xla' else 'dense'}"
    )
    prev = 0.0
    for phase in (1, 2, 3, 4, 5, 6):
        fn = truncated_deposit(dev_block, V, n, phase)

        def make_loop(S, fn=fn):
            @jax.jit
            def loop(*st):
                def body(c, _):
                    return fn(c), None

                out, _ = jax.lax.scan(body, st, None, length=S)
                return out

            return loop

        per_step, _, _ = profiling.scan_time_per_step(
            make_loop, state, s1=2, s2=6
        )
        ms = per_step * 1e3
        print(
            f"phase {phase}: {ms:8.2f} ms  (delta {ms - prev:+8.2f})",
            flush=True,
        )
        prev = ms


if __name__ == "__main__":
    main()
