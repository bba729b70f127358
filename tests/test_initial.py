"""Seeded initial states and sizing (models/initial.py).

``uniform_state`` places each rank's rows in its own block, ``drift_sizing``
turns a migration fraction into a velocity scale and exchange capacities,
and ``pick_layout`` maps a rank grid onto the 8 virtual CPU devices from
conftest.py. The last tests drive the drift loop from these pieces and
check that the sizing keeps every row at ~2% migration a step.
"""

import numpy as np
import pytest

from mpi_grid_redistribute_tpu import oracle
from mpi_grid_redistribute_tpu.domain import Domain, ProcessGrid
from mpi_grid_redistribute_tpu.models import initial, nbody

GRIDS = [(1, 1, 1), (2, 2, 1), (2, 2, 2), (4, 4, 4)]
FILLS = [0.5, 0.9]
N_LOCAL = 101  # odd, so int(fill * n_local) rounds down at both fills


@pytest.mark.parametrize("fill", FILLS)
@pytest.mark.parametrize("grid_shape", GRIDS)
def test_uniform_state_rows_lie_in_their_rank_block(grid_shape, fill):
    grid = ProcessGrid(grid_shape)
    v_scale, _, _ = initial.drift_sizing(grid_shape, N_LOCAL, fill, 0.02)
    pos, vel, _ = initial.uniform_state(
        grid_shape, N_LOCAL, fill, np.random.default_rng(5),
        vel_scale=v_scale,
    )
    assert pos.shape == vel.shape == (grid.nranks * N_LOCAL, 3)
    assert pos.dtype == vel.dtype == np.float32
    g = np.asarray(grid.shape, np.float32)
    for r in range(grid.nranks):
        cell = np.asarray(grid.cell_of_rank(r), np.float32)
        rows = pos[r * N_LOCAL : (r + 1) * N_LOCAL]
        assert np.all(rows >= cell / g), r
        assert np.all(rows < (cell + 1) / g), r
    assert np.all(np.abs(vel) <= v_scale)


@pytest.mark.parametrize("fill", FILLS)
@pytest.mark.parametrize("grid_shape", GRIDS)
def test_uniform_state_leading_slots_live(grid_shape, fill):
    grid = ProcessGrid(grid_shape)
    _, _, alive = initial.uniform_state(
        grid_shape, N_LOCAL, fill, np.random.default_rng(5)
    )
    assert alive.dtype == bool
    per_rank = alive.reshape(grid.nranks, N_LOCAL)
    live = int(fill * N_LOCAL)
    expected = np.arange(N_LOCAL) < live
    assert np.array_equal(per_rank, np.tile(expected, (grid.nranks, 1)))
    assert int(alive.sum()) == grid.nranks * live


@pytest.mark.parametrize("fill", FILLS)
@pytest.mark.parametrize("grid_shape", GRIDS)
def test_uniform_state_same_seed_same_arrays(grid_shape, fill):
    def draw(seed):
        return initial.uniform_state(
            grid_shape, N_LOCAL, fill, np.random.default_rng(seed),
            vel_scale=np.asarray([0.01, 0.02, 0.03], np.float32),
        )

    first, again, other = draw(11), draw(11), draw(12)
    for a, b in zip(first, again):
        assert a.dtype == b.dtype
        assert a.tobytes() == b.tobytes()
    assert first[0].tobytes() != other[0].tobytes()


@pytest.mark.parametrize(
    "grid_shape, n_local, fill, migration, v, cap, budget",
    [
        # three extent-2 axes: one neighbour each, 3 distinct pairs;
        # v = 0.02 / 3 * 2 / 2 per axis;
        # cap = ceil(0.9 * 2**20 * 0.02 / 3 * 1.3) = ceil(8178.89);
        # budget = ceil(0.9 * 2**20 * 0.02 * 1.3) = ceil(24536.68)
        ((2, 2, 2), 1 << 20, 0.9, 0.02, [0.02 / 3] * 3, 8179, 24537),
        # z undecomposed: it takes the mean scale of x and y (0.01 each);
        # 0.5 * 1000 * 0.02 = 10 migrants, far under both floors
        ((2, 2, 1), 1000, 0.5, 0.02, [0.01] * 3, 64, 256),
        # extent-4 axes: two neighbours each, 4 distinct pairs;
        # v = 0.05 / 2 * 2 / 4; cap = ceil(2949.12 / 4 * 1.3) = ceil(958.46);
        # budget = ceil(2949.12 * 1.3) = ceil(3833.86)
        ((4, 4, 1), 1 << 16, 0.9, 0.05, [0.0125] * 3, 959, 3834),
    ],
)
def test_drift_sizing_hand_math(grid_shape, n_local, fill, migration, v,
                                cap, budget):
    got_v, got_cap, got_budget = initial.drift_sizing(
        grid_shape, n_local, fill, migration
    )
    assert got_v.dtype == np.float32
    np.testing.assert_array_equal(got_v, np.asarray(v, np.float32))
    assert (got_cap, got_budget) == (cap, budget)


@pytest.mark.parametrize(
    "grid_shape, dev_shape, vshape, mesh_size, n_chips",
    [
        ((2, 2, 2), (2, 2, 2), None, 8, 8),  # one rank a device
        ((4, 4, 4), (1, 1, 1), (4, 4, 4), 1, 1),  # 64 vranks on one device
    ],
)
def test_pick_layout_on_eight_devices(grid_shape, dev_shape, vshape,
                                      mesh_size, n_chips, _devices):
    dev_grid, vgrid, mesh, chips = initial.pick_layout(grid_shape)
    assert dev_grid.shape == dev_shape
    assert (vgrid.shape if vgrid is not None else None) == vshape
    assert mesh.size == mesh_size
    assert chips == n_chips


@pytest.mark.parametrize(
    "grid_shape, n_local, headroom",
    [
        ((2, 2, 2), 1 << 12, 1.3),  # one rank a device
        ((8, 8, 1), 512, 1.5),  # 64 slabs as vranks, z undecomposed
    ],
)
def test_sized_drift_loop_keeps_every_row(grid_shape, n_local, headroom,
                                          _devices):
    """The drift loop from ``uniform_state`` at ``drift_sizing``'s
    capacities: every row survives 16 steps at ~2% migration a step,
    nothing is dropped, and every live row ends on the rank that owns
    its cell."""
    import jax

    fill, steps = 0.9, 16
    grid = ProcessGrid(grid_shape)
    domain = Domain(0.0, 1.0, periodic=True)
    dev_grid, vgrid, mesh, _ = initial.pick_layout(grid_shape)
    v_scale, cap, budget = initial.drift_sizing(
        grid_shape, n_local, fill, 0.02, headroom=headroom
    )
    pos, vel, alive = initial.uniform_state(
        grid_shape, n_local, fill, np.random.default_rng(3),
        vel_scale=v_scale,
    )
    cfg = nbody.DriftConfig(
        domain=domain, grid=dev_grid, dt=1.0, capacity=cap,
        n_local=n_local, local_budget=budget,
    )
    loop = nbody.make_migrate_loop(cfg, mesh, steps, vgrid=vgrid)
    out = loop(
        nbody.rows_to_planar(pos, mesh.size),
        nbody.rows_to_planar(vel, mesh.size),
        alive,
    )
    stats = jax.tree.map(np.asarray, out[3])
    live = np.asarray(out[2])
    assert int(live.sum()) == int(alive.sum())
    assert int(stats.dropped_recv.sum()) == 0
    assert int(stats.sent.sum()) > 0
    rows = nbody.planar_to_rows(out[0], 3, mesh.size)
    oracle.assert_ownership(
        domain, grid,
        [rows[r * n_local : (r + 1) * n_local][
            live[r * n_local : (r + 1) * n_local]]
         for r in range(grid.nranks)],
    )
