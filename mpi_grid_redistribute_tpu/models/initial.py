"""Seeded initial states and their sizing: uniform particles placed on
their owning rank, the drift-loop velocity scale and exchange
capacities for a target migration fraction, and the device layout of a
rank grid.

``ServiceDriver.init_state`` builds its state here, so a given seed
gives the same particles in every release that keeps these bodies.
"""

from __future__ import annotations

from typing import Tuple

import numpy as np

from mpi_grid_redistribute_tpu.domain import ProcessGrid
from mpi_grid_redistribute_tpu.parallel import mesh as mesh_lib


def pick_layout(grid_shape: Tuple[int, ...]):
    """Map an R-rank Cartesian grid onto the available devices.

    Returns ``(dev_grid, vgrid, mesh, n_chips)``: one rank per device when
    enough devices exist; otherwise the whole grid runs as virtual-rank
    slabs on one device (same semantics, on-device exchange).
    """
    import jax

    devs = jax.devices()
    grid = ProcessGrid(grid_shape)
    if len(devs) >= grid.nranks:
        mesh = mesh_lib.make_mesh(grid, devices=devs[: grid.nranks])
        return grid, None, mesh, grid.nranks
    dev_grid = ProcessGrid((1,) * len(grid_shape))
    mesh = mesh_lib.make_mesh(dev_grid, devices=devs[:1])
    return dev_grid, grid, mesh, 1


def uniform_state(grid_shape, n_local: int, fill: float, rng, vel_scale=0.0):
    """Uniform particles placed on their owning slab (device-major rows).

    ``vel_scale`` may be a scalar or a per-axis array; velocities are drawn
    uniform in ``[-vel_scale, vel_scale]`` per axis.
    """
    grid = ProcessGrid(grid_shape)
    R = grid.nranks
    n = R * n_local
    pos = rng.random((n, 3), dtype=np.float32)
    lo = np.zeros((n, 3), dtype=np.float32)
    for s in range(R):
        cell = grid.cell_of_rank(s)
        for a in range(3):
            lo[s * n_local : (s + 1) * n_local, a] = (
                cell[a] / grid.shape[a]
            )
    pos = lo + pos / np.asarray(grid.shape, np.float32)
    vel = (
        np.asarray(vel_scale, np.float32)
        * (rng.random((n, 3), dtype=np.float32) * 2.0 - 1.0)
    ).astype(np.float32)
    alive = np.tile(np.arange(n_local) < int(fill * n_local), R)
    return pos, vel, alive


def drift_sizing(
    grid_shape, n_local: int, fill: float, migration: float,
    headroom: float = 1.3,
):
    """Shared drift-loop sizing: per-axis velocity scale for ~``migration``
    fraction of rows crossing a subdomain face per step, per-pair exchange
    ``capacity``, and the compact-routing ``local_budget``.

    Face-neighbor count per axis: extent 1 -> 0 (undecomposed), extent 2
    -> 1 (both periodic wraps reach the SAME neighbor, doubling that
    pair's traffic), else 2. Undecomposed axes get the mean decomposed
    velocity scale (any speed, no migration).
    """
    import math

    g = np.asarray(grid_shape, np.int64)
    dec = g > 1
    n_dec = max(int(dec.sum()), 1)
    distinct = int(np.where(g == 1, 0, np.where(g == 2, 1, 2)).sum())
    distinct = max(distinct, 1)
    v = np.where(dec, migration / n_dec * 2.0 / g, 0.0)
    v = np.where(dec, v, v[dec].mean() if dec.any() else migration)
    cap = max(64, math.ceil(fill * n_local * migration / distinct * headroom))
    budget = max(256, math.ceil(fill * n_local * migration * headroom))
    return v.astype(np.float32), cap, budget
