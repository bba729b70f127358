"""Per-stage device-time attribution for the headline migrate step.

Times each pipeline stage of the PLANAR vrank migrate step in isolation at
bench-identical shapes (V vranks of n columns, K fused rows, on-device
budget M), using the same scan-length-differencing as bench.py so
compile and dispatch cancel. Each stage's scan carries a data
dependency through the timed op so XLA cannot hoist or DCE it.

In-context attribution (the sum here can differ from the real step —
isolated microbenches measured 2x off for the vmapped scatter) comes
from the device trace of the real step, whose ops carry layer scopes
(``mig:*``; see ``benchmark/``); this script is the per-op sanity
check.

Usage:  python scripts/profile_stages.py [n_local] [capacity]
"""

from __future__ import annotations

import os
import sys

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))

import numpy as np
import jax
import jax.numpy as jnp
from jax import lax

from mpi_grid_redistribute_tpu.domain import Domain, ProcessGrid
from mpi_grid_redistribute_tpu.ops import binning
from mpi_grid_redistribute_tpu.utils import profiling

GRID = (2, 2, 2)
V = 8
R_TOTAL = 8
K = 7  # pos(3) + vel(3) + alive(1)
FILL = 0.9
MIGRATION = 0.02


def main():
    n = int(sys.argv[1]) if len(sys.argv) > 1 else 2**20
    import math

    distinct = sum(1 if g == 2 else 2 for g in GRID)
    C = (
        int(sys.argv[2])
        if len(sys.argv) > 2
        else max(64, math.ceil(FILL * n * MIGRATION / distinct * 1.3))
    )
    M_budget = max(256, math.ceil(FILL * n * MIGRATION * 1.3))
    domain = Domain(0.0, 1.0, periodic=True)
    vgrid = ProcessGrid(GRID)
    dev_grid = ProcessGrid((1, 1, 1))

    rng = np.random.default_rng(0)
    # planar fused state: [K, V*n], alive = last row
    fused = rng.random((K, V * n), dtype=np.float32)
    fused[-1, :] = (rng.random((V * n,)) < FILL).astype(np.float32)
    fused = jax.device_put(jnp.asarray(fused))
    key_np = np.full((V, n), R_TOTAL, np.int32)
    m = int(n * FILL * MIGRATION)
    for v in range(V):
        idx = rng.choice(n, size=m, replace=False)
        key_np[v, idx] = rng.choice([1, 2, 4], size=m)
    dest_key = jax.device_put(jnp.asarray(key_np))
    gather_idx = jax.device_put(
        jnp.asarray(rng.integers(0, n, size=(V, M_budget), dtype=np.int32))
    )
    cols = jax.device_put(
        jnp.asarray(rng.random((K, V * M_budget), dtype=np.float32))
    )

    stages = {}

    def timed(name, make_loop, *args, s1=4, s2=24):
        per_step, _, _out = profiling.scan_time_per_step(
            make_loop, args, s1=s1, s2=s2
        )
        stages[name] = per_step * 1e3
        print(f"  {name:34s} {per_step*1e3:8.2f} ms", file=sys.stderr)

    full_shape = tuple(d * v for d, v in zip(dev_grid.shape, vgrid.shape))
    full_grid = ProcessGrid(full_shape)

    # --- 1. elementwise: drift + wrap + bin -> dest key -----------------
    def make_bin_loop(S):
        @jax.jit
        def loop(fused):
            def body(f, _):
                p = f[:3, :] + f[3:6, :] * jnp.float32(1e-4)
                p = binning.wrap_periodic_planar(p, domain)
                f = jnp.concatenate([p, f[3:, :]], axis=0)
                alive = f[-1, :].reshape(V, n) > 0.5
                cell = binning.cell_of_position_planar(
                    f[:3, :], domain, full_grid
                )
                dv = jnp.zeros((V * n,), jnp.int32)
                for d in range(3):
                    dv = dv + (
                        cell[d] % vgrid.shape[d]
                    ) * vgrid.strides[d]
                dv = dv.reshape(V, n)
                staying = dv == jnp.arange(V, dtype=jnp.int32)[:, None]
                key = jnp.where(alive & ~staying, dv, R_TOTAL)
                dep = key.sum(axis=1).astype(jnp.float32).sum() * 1e-38
                f = f.at[0, 0].add(dep)
                return f, ()

            f, _ = lax.scan(body, fused, None, length=S)
            return f

        return loop

    timed("drift+wrap+bin (planar)", make_bin_loop, fused)

    # --- 2. stable key sort + counts ------------------------------------
    def make_sort_loop(S):
        @jax.jit
        def loop(key):
            def body(k, _):
                order, counts, bounds = jax.vmap(
                    lambda kk: binning.sorted_dest_counts(kk, R_TOTAL)
                )(k)
                dep = (
                    (order[:, :1] + counts[:, :1]).astype(jnp.float32)
                    * jnp.float32(1e-38)
                ).astype(jnp.int32)  # runtime 0, not foldable
                k = (k + dep).astype(jnp.int32)
                return k, ()

            k, _ = lax.scan(body, key, None, length=S)
            return k

        return loop

    timed("stable sort + searchsorted", make_sort_loop, dest_key)

    # --- 3. arrival gather: [K, V*M] columns from [K, V*n] ---------------
    def make_gather_loop(S):
        @jax.jit
        def loop(fused, idx):
            def body(carry, _):
                f, i = carry
                gi = (
                    jnp.arange(V, dtype=jnp.int32)[:, None] * n + i
                ).reshape(-1)
                send = jnp.take(f, gi, axis=1)
                dep = (send[0, :1] * jnp.float32(1e-38)).astype(jnp.int32)
                i = (i + dep[None, :]) % n
                return (f, i), ()

            (f, i), _ = lax.scan(body, (fused, idx), None, length=S)
            return f, i

        return loop

    timed(f"arrival gather ({V}x{M_budget} cols)", make_gather_loop, fused,
          gather_idx)

    # --- 4. landing scatter: [K, V*M] columns into [K, V*n] --------------
    def make_scatter_loop(S):
        @jax.jit
        def loop(fused, tgt, cols):
            def body(carry, _):
                f, t = carry
                gt = (
                    jnp.arange(V, dtype=jnp.int32)[:, None] * n + t
                ).reshape(-1)
                f = f.at[:, gt].set(cols, mode="drop")
                dep = (f[0, :1] * jnp.float32(1e-38)).astype(jnp.int32)
                t = (t + dep[None, :]) % n
                return (f, t), ()

            (f, t), _ = lax.scan(body, (fused, tgt), None, length=S)
            return f, t

        return loop

    timed(f"landing scatter ({V}x{M_budget} cols)", make_scatter_loop,
          fused, gather_idx, cols)

    # --- 5. full migrate step (reference) --------------------------------
    from mpi_grid_redistribute_tpu.parallel import mesh as mesh_lib
    from mpi_grid_redistribute_tpu.models import nbody

    cfg = nbody.DriftConfig(
        domain=domain, grid=dev_grid, dt=1e-4, capacity=C, n_local=n,
        local_budget=M_budget,
    )
    mesh = mesh_lib.make_mesh(dev_grid, devices=jax.devices()[:1])
    pos_all = rng.random((V * n, 3), dtype=np.float32)
    vel_all = rng.random((V * n, 3), dtype=np.float32) * 1e-4
    alive_all = rng.random((V * n,)) < FILL
    args = (
        jax.device_put(
            jnp.asarray(nbody.rows_to_planar(pos_all, mesh.size))
        ),
        jax.device_put(
            jnp.asarray(nbody.rows_to_planar(vel_all, mesh.size))
        ),
        jax.device_put(jnp.asarray(alive_all)),
    )
    timed(
        "FULL migrate step",
        lambda S: nbody.make_migrate_loop(cfg, mesh, S, vgrid=vgrid),
        *args,
    )

    print("\n| stage | ms/step |\n|---|---|")
    for name, ms in stages.items():
        print(f"| {name} | {ms:.2f} |")
    accounted = sum(v for k, v in stages.items() if "FULL" not in k)
    print(f"| (sum of stages) | {accounted:.2f} |")


if __name__ == "__main__":
    main()
