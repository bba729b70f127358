"""Seeded particle states and drift-loop sizing.

Copies of ``uniform_state``, ``lognormal_state`` and ``drift_sizing``
from the program's ``bench/common.py``, kept here so that the inputs
the benchmark measures on do not move with the program. They draw the
same numbers from the same generator. Grids are row-major:
``rank = (i * gy + j) * gz + k``.
"""

from __future__ import annotations

import math

import numpy as np


def seed_sequence(seed: int) -> np.random.SeedSequence:
    """A seed sequence for any whole number, negative ones included."""
    return np.random.SeedSequence([abs(int(seed)), int(seed < 0)])


def strides(shape) -> tuple:
    """Row-major strides of a grid shape."""
    out, acc = [], 1
    for s in reversed(tuple(shape)):
        out.append(acc)
        acc *= int(s)
    return tuple(reversed(out))


def cell_of_rank(rank: int, shape) -> tuple:
    """Grid cell of a row-major rank."""
    cell, rem = [], int(rank)
    for s in strides(shape):
        cell.append(rem // s)
        rem %= s
    return tuple(cell)


def uniform_state(grid_shape, n_local: int, fill: float, rng, vel_scale=0.0):
    """Uniform particles placed on their owning slab (rank-major rows).

    ``vel_scale`` may be a scalar or a per-axis array; velocities are
    drawn uniform in ``[-vel_scale, vel_scale]`` per axis. The first
    ``int(fill * n_local)`` rows of each slab are alive.
    """
    shape = tuple(int(s) for s in grid_shape)
    R = int(np.prod(shape))
    n = R * n_local
    pos = rng.random((n, 3), dtype=np.float32)
    lo = np.zeros((n, 3), dtype=np.float32)
    for s in range(R):
        cell = cell_of_rank(s, shape)
        for a in range(3):
            lo[s * n_local : (s + 1) * n_local, a] = cell[a] / shape[a]
    pos = lo + pos / np.asarray(shape, np.float32)
    vel = (
        np.asarray(vel_scale, np.float32)
        * (rng.random((n, 3), dtype=np.float32) * 2.0 - 1.0)
    ).astype(np.float32)
    alive = np.tile(np.arange(n_local) < int(fill * n_local), R)
    return pos, vel, alive


def lognormal_state(grid_shape, n_local: int, fill: float, rng, sigma=1.0):
    """Log-normal clustered global positions, not placed on their owners.
    For a clustered traffic mix (no cell uses it yet)."""
    R = int(np.prod(grid_shape))
    n = R * n_local
    raw = rng.lognormal(mean=0.0, sigma=sigma, size=(n, 3))
    pos = (raw % 1.0).astype(np.float32)
    alive = np.tile(np.arange(n_local) < int(fill * n_local), R)
    return pos, alive


def drift_sizing(grid_shape, n_local: int, fill: float, migration: float,
                 headroom: float = 1.3):
    """Per-axis velocity scale for ~``migration`` of the rows crossing a
    face per step, the per-pair exchange ``capacity`` and the on-device
    ``local_budget``.

    Face neighbours per axis: extent 1 -> 0, extent 2 -> 1 (both
    periodic wraps reach the same neighbour), else 2. Undecomposed axes
    get the mean decomposed velocity scale.
    """
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


def rows_to_planar(a: np.ndarray, n_blocks: int) -> np.ndarray:
    """Row-major ``[N, D]`` to the loop's planar device format: block-major
    (one block per device), component-major within a block."""
    n, d = a.shape
    return np.ascontiguousarray(
        a.reshape(n_blocks, n // n_blocks, d).transpose(0, 2, 1)
    ).reshape(-1)


def planar_to_rows(a: np.ndarray, ndim: int, n_blocks: int) -> np.ndarray:
    """Inverse of :func:`rows_to_planar`."""
    a = np.asarray(a)
    n = a.size // (ndim * n_blocks)
    return np.ascontiguousarray(
        a.reshape(n_blocks, ndim, n).transpose(0, 2, 1)
    ).reshape(-1, ndim)
