"""Resident-slot migration path (parallel/migrate.py) vs the oracle.

Slot order is unspecified (arrivals land in arbitrary holes), so correctness
is *set* equality per shard against a NumPy reference drift loop, plus
conservation and surfaced-overflow accounting (SURVEY.md §4, §5.3).
"""

import numpy as np
import pytest

import jax

from mpi_grid_redistribute_tpu.domain import Domain, ProcessGrid
from mpi_grid_redistribute_tpu.models import nbody
from mpi_grid_redistribute_tpu.ops import binning
from mpi_grid_redistribute_tpu.parallel import mesh as mesh_lib


def _rows_set(pos, vel, mask):
    """EXACT bitcast-int row sets: the migrate path only ever moves rows
    (gather/all_to_all/scatter on the fused matrix), so payload bits must
    survive verbatim — a sub-1e-5 corruption in the bitcast fuse/scatter
    path is a bug, not noise (round-2 verdict item 9)."""
    rows = np.concatenate([pos[mask], vel[mask]], axis=1)
    return {tuple(r) for r in rows.view(np.uint32).tolist()}


def _np_drift_reference(domain, grid, pos, vel, alive, dt, n_steps):
    """Reference drift loop: returns per-shard row sets after n_steps.

    The drift arithmetic runs through the same XLA-compiled elementwise
    kernel as the device step (one jit, unsharded) so float32 rounding —
    including any multiply-add contraction — is bit-identical; the
    redistribution bookkeeping stays plain NumPy. The migrate path itself
    only moves rows, so the final sets must match the device EXACTLY."""
    import jax.numpy as jnp

    @jax.jit
    def _drift(p, v):
        return binning.wrap_periodic(p + v * jnp.asarray(dt, p.dtype), domain)

    pos, vel, alive = pos.copy(), vel.copy(), alive.copy()
    for _ in range(n_steps):
        pos[alive] = np.asarray(_drift(pos[alive], vel[alive]))
    dest = binning.rank_of_position(pos, domain, grid, xp=np)
    shard_sets = []
    for r in range(grid.nranks):
        m = alive & (dest == r)
        shard_sets.append(_rows_set(pos, vel, m))
    return shard_sets


def _check_flat_loop(rng, shape, n_local, capacity, dt, vel_scale,
                     dead_share, n_steps):
    """Run the flat engine's drift loop from a legal random start and
    hold it to the NumPy reference: conservation, no backlog or drops,
    ownership and the exact per-shard row sets."""
    grid = ProcessGrid(shape)
    R = grid.nranks
    domain = Domain(0.0, 1.0, periodic=True)
    n = R * n_local
    mesh = mesh_lib.make_mesh(grid)

    pos = rng.random((n, 3), dtype=np.float32)
    vel = (vel_scale * (rng.random((n, 3), dtype=np.float32) - 0.5)).astype(
        np.float32
    )
    # start with some holes
    alive = rng.random(n) > dead_share
    # place live rows on their owning shard so the starting state is legal
    dest = binning.rank_of_position(pos, domain, grid, xp=np)
    slot_shard = np.repeat(np.arange(R), n_local)
    alive &= dest == slot_shard

    cfg = nbody.DriftConfig(
        domain=domain, grid=grid, dt=dt, capacity=capacity, n_local=n_local
    )
    loop = nbody.make_migrate_loop(cfg, mesh, n_steps)
    pos_f, vel_f, alive_f, stats = jax.tree.map(
        np.asarray, loop(pos, vel, alive)
    )
    pos_f = nbody.planar_to_rows(pos_f, 3, mesh.size)
    vel_f = nbody.planar_to_rows(vel_f, 3, mesh.size)

    assert stats.sent.sum() > 0
    assert stats.backlog.sum() == 0
    assert stats.dropped_recv.sum() == 0
    assert alive_f.sum() == alive.sum()
    # every step's populations sum to the global total
    assert (stats.population.sum(axis=1) == alive.sum()).all()

    # ownership: every live row sits on the shard that owns its position
    dest_f = binning.rank_of_position(pos_f, domain, grid, xp=np)
    assert (dest_f[alive_f] == slot_shard[alive_f]).all()

    want = _np_drift_reference(
        domain, grid, pos, vel, alive, np.float32(dt), n_steps
    )
    for r in range(R):
        sl = slice(r * n_local, (r + 1) * n_local)
        got = _rows_set(pos_f[sl], vel_f[sl], alive_f[sl])
        assert got == want[r], f"shard {r} row set mismatch"


@pytest.mark.parametrize("shape", [(2, 2, 2), (4, 2, 1)])
def test_migrate_matches_reference_sets(shape, rng, _devices):
    _check_flat_loop(rng, shape, n_local=64, capacity=64, dt=0.07,
                     vel_scale=0.6, dead_share=0.125, n_steps=5)


@pytest.mark.parametrize("capacity", [16, 256])
def test_migrate_flat_4dev_matches_reference_sets(capacity, rng, _devices):
    """The flat engine on four devices, one rank each (the four-chip
    cell's layout): with the write plan (4 * capacity) shorter than the
    256 slots of a shard and longer."""
    _check_flat_loop(rng, (2, 2, 1), n_local=256, capacity=capacity,
                     dt=0.05, vel_scale=0.2, dead_share=0.1, n_steps=6)


def test_migrate_step_stats_and_idempotence(rng, _devices):
    grid = ProcessGrid((2, 2, 2))
    R = grid.nranks
    domain = Domain(0.0, 1.0, periodic=True)
    n_local = 32
    n = R * n_local
    mesh = mesh_lib.make_mesh(grid)
    cfg = nbody.DriftConfig(
        domain=domain, grid=grid, dt=0.0, capacity=8, n_local=n_local
    )
    step = nbody.make_migrate_step(cfg, mesh)

    pos = rng.random((n, 3), dtype=np.float32)
    vel = np.zeros((n, 3), dtype=np.float32)
    # legal start: all rows on owner shard
    dest = binning.rank_of_position(pos, domain, grid, xp=np)
    alive = dest == np.repeat(np.arange(R), n_local)

    out = jax.tree.map(np.asarray, step(pos, vel, alive))
    pos1, vel1, alive1, stats = out
    # dt=0 and legal start: nothing moves
    assert stats.sent.sum() == 0
    assert stats.received.sum() == 0
    assert (alive1 == alive).all()
    assert (pos1[alive] == pos[alive]).all()


def test_migrate_overflow_backlogs_lossless(rng, _devices):
    """All particles head to one full shard: the receiver grants nothing
    (no free slots, nothing to swap), so nothing is sent, nothing drops,
    and every mover stays resident in backlog to retry."""
    grid = ProcessGrid((8, 1, 1))
    R = grid.nranks
    domain = Domain(0.0, 1.0, periodic=True)
    n_local = 16
    n = R * n_local
    mesh = mesh_lib.make_mesh(grid)
    cfg = nbody.DriftConfig(
        domain=domain, grid=grid, dt=1.0, capacity=2, n_local=n_local
    )
    step = nbody.make_migrate_step(cfg, mesh)

    # every particle sits at x-center of its slot shard, vel pushes all into
    # shard 0's column
    pos = rng.random((n, 3), dtype=np.float32)
    shard = np.repeat(np.arange(R), n_local)
    pos[:, 0] = (shard + 0.5) / R
    vel = np.zeros((n, 3), dtype=np.float32)
    vel[:, 0] = (0.5 / R) - pos[:, 0]  # land inside shard 0 after dt=1
    alive = np.ones(n, dtype=bool)

    pos1, vel1, alive1, stats = jax.tree.map(
        np.asarray, step(pos, vel, alive)
    )
    sent = stats.sent.sum()
    received = stats.received.sum()
    bl, dr = stats.backlog.sum(), stats.dropped_recv.sum()
    # 7 shards * 16 particles want to move; shard 0 is completely full and
    # has no departures to swap against, so its grants are zero: nothing
    # flies, nothing drops, every mover is backlogged and stays alive.
    assert sent == 0
    assert received == 0
    assert dr == 0
    assert bl == n_local * (R - 1)
    assert alive1.sum() == n  # lossless by construction


def test_migrate_backlog_drains(rng, _devices):
    """Backlogged migrants retry and land on later steps once capacity and
    free slots allow."""
    grid = ProcessGrid((2, 1, 1))
    R = grid.nranks
    domain = Domain(0.0, 1.0, periodic=True)
    n_local = 32
    n = R * n_local
    mesh = mesh_lib.make_mesh(grid, devices=jax.devices()[:2])
    cfg = nbody.DriftConfig(
        domain=domain, grid=grid, dt=0.0, capacity=4, n_local=n_local
    )
    step = nbody.make_migrate_step(cfg, mesh)

    # shard 0: half its rows positioned in shard 1's half-box (16 movers,
    # capacity 4/step); shard 1: half its slots dead (16 free slots)
    pos = rng.random((n, 3), dtype=np.float32)
    pos[:n_local, 0] = np.where(
        np.arange(n_local) < 16,
        0.75,  # owned by shard 1
        0.25,
    ).astype(np.float32)
    pos[n_local:, 0] = 0.75
    vel = np.zeros((n, 3), dtype=np.float32)
    alive = np.ones(n, dtype=bool)
    alive[n_local + 16 :] = False

    total0 = alive.sum()
    moved = 0
    state = (pos, vel, alive)
    for i in range(4):
        p, v, a, stats = jax.tree.map(np.asarray, step(*state))
        state = (p, v, a)
        assert stats.dropped_recv.sum() == 0
        assert stats.sent.sum() == 4  # capacity-limited every step
        moved += stats.sent.sum()
        assert a.sum() == total0
    assert moved == 16  # the full backlog drained at 4/step


def test_migrate_vranks_full_swap_is_lossless(rng, _devices):
    """Two fully-occupied vranks exchanging every particle must complete
    the swap (arrivals may land in same-step-vacated slots; the fixpoint
    allocation seeds with self-financing pairwise swaps)."""
    dev_grid = ProcessGrid((1, 1, 1))
    vgrid = ProcessGrid((2, 1, 1))
    domain = Domain(0.0, 1.0, periodic=True)
    n_local = 8
    n = 2 * n_local
    mesh = mesh_lib.make_mesh(dev_grid, devices=jax.devices()[:1])

    # vrank 0 owns x in [0, .5), vrank 1 owns [.5, 1); place every row in
    # the OTHER vrank's half-box, zero velocity, zero free slots.
    pos = rng.random((n, 3), dtype=np.float32)
    pos[:n_local, 0] = 0.75
    pos[n_local:, 0] = 0.25
    vel = np.zeros((n, 3), dtype=np.float32)
    alive = np.ones(n, dtype=bool)

    cfg = nbody.DriftConfig(
        domain=domain, grid=dev_grid, dt=0.0, capacity=n_local,
        n_local=n_local,
    )
    loop = nbody.make_migrate_loop(cfg, mesh, 1, vgrid=vgrid)
    pos_f, vel_f, alive_f, stats = jax.tree.map(
        np.asarray, loop(pos, vel, alive)
    )
    pos_f = nbody.planar_to_rows(pos_f, 3, mesh.size)
    vel_f = nbody.planar_to_rows(vel_f, 3, mesh.size)
    assert stats.dropped_recv.sum() == 0
    assert stats.backlog.sum() == 0
    assert stats.sent.sum() == n
    assert alive_f.sum() == n
    # every row now sits on its owning vrank slab
    assert (pos_f[:n_local, 0] < 0.5).all()
    assert (pos_f[n_local:, 0] >= 0.5).all()


def _slab_full_ranks(dev_grid, vgrid):
    """full-grid rank of each (device, vrank) slab, device-major order."""
    full = ProcessGrid(
        tuple(d * v for d, v in zip(dev_grid.shape, vgrid.shape))
    )
    out = []
    for d in range(dev_grid.nranks):
        dc = dev_grid.cell_of_rank(d)
        for v in range(vgrid.nranks):
            vc = vgrid.cell_of_rank(v)
            cell = tuple(
                dc[a] * vgrid.shape[a] + vc[a] for a in range(len(dc))
            )
            out.append(full.rank_of_cell(cell))
    return full, np.asarray(out)


@pytest.mark.parametrize(
    "dev_shape,v_shape",
    [((1, 1, 1), (2, 2, 2)), ((2, 2, 1), (1, 2, 2)), ((2, 1, 1), (2, 2, 1))],
)
def test_migrate_vranks_matches_reference_sets(dev_shape, v_shape, rng, _devices):
    dev_grid = ProcessGrid(dev_shape)
    vgrid = ProcessGrid(v_shape)
    full, slab_rank = _slab_full_ranks(dev_grid, vgrid)
    R = full.nranks
    domain = Domain(0.0, 1.0, periodic=True)
    n_local = 64
    n = R * n_local
    mesh = mesh_lib.make_mesh(dev_grid)

    pos = rng.random((n, 3), dtype=np.float32)
    vel = (0.6 * (rng.random((n, 3), dtype=np.float32) - 0.5)).astype(
        np.float32
    )
    alive = rng.random(n) > 0.125
    # legal start: live rows sit on the slab owning their position
    dest = binning.rank_of_position(pos, domain, full, xp=np)
    slot_slab = np.repeat(slab_rank, n_local)  # device-major slabs
    alive &= dest == slot_slab

    n_steps = 5
    cfg = nbody.DriftConfig(
        domain=domain, grid=dev_grid, dt=0.07, capacity=n_local,
        n_local=n_local,
    )
    loop = nbody.make_migrate_loop(cfg, mesh, n_steps, vgrid=vgrid)
    pos_f, vel_f, alive_f, stats = jax.tree.map(
        np.asarray, loop(pos, vel, alive)
    )
    pos_f = nbody.planar_to_rows(pos_f, 3, mesh.size)
    vel_f = nbody.planar_to_rows(vel_f, 3, mesh.size)

    assert stats.backlog.sum() == 0
    assert stats.dropped_recv.sum() == 0
    assert alive_f.sum() == alive.sum()

    dest_f = binning.rank_of_position(pos_f, domain, full, xp=np)
    assert (dest_f[alive_f] == slot_slab[alive_f]).all()

    want = _np_drift_reference(
        domain, full, pos, vel, alive, np.float32(0.07), n_steps
    )
    for slab in range(R):
        sl = slice(slab * n_local, (slab + 1) * n_local)
        got = _rows_set(pos_f[sl], vel_f[sl], alive_f[sl])
        assert got == want[slab_rank[slab]], f"slab {slab} mismatch"


def test_vranks_cross_device_receive_is_lossless(rng, _devices):
    """Cross-device arrivals are receiver-granted: a nearly-full remote
    slab grants only its free slots, excess movers backlog, and nothing
    ever drops (round-1 verdict weak item 4, closed)."""
    dev_grid = ProcessGrid((2, 1, 1))
    vgrid = ProcessGrid((1, 2, 1))
    domain = Domain(0.0, 1.0, periodic=True)
    n_local = 32
    n = 4 * n_local
    mesh = mesh_lib.make_mesh(dev_grid, devices=jax.devices()[:2])
    cfg = nbody.DriftConfig(
        domain=domain, grid=dev_grid, dt=1.0, capacity=n_local,
        n_local=n_local,
    )

    # slab layout (device-major): 0:(0,0) 1:(0,1) 2:(1,0) 3:(1,1).
    # Fill slab 2 (device 1) completely except `free` slots; aim slab 0's
    # movers (device 0) at slab 2's subdomain -> cross-device pressure.
    free = 4
    pos = np.zeros((n, 3), np.float32)
    vel = np.zeros((n, 3), np.float32)
    alive = np.ones((n,), bool)
    # slab 0 rows sit in (x<0.5, y<0.5); velocity pushes them to x>0.5
    pos[:n_local] = rng.uniform(0.01, 0.45, (n_local, 3)).astype(np.float32)
    vel[:n_local, 0] = 0.5
    # slab 1 (0,1): legal resident rows, y in upper half
    pos[n_local:2*n_local] = rng.uniform(0.01, 0.45, (n_local, 3))
    pos[n_local:2*n_local, 1] += 0.5
    # slab 2 (1,0): x upper half; last `free` slots are holes
    pos[2*n_local:3*n_local] = rng.uniform(0.55, 0.95, (n_local, 3))
    pos[2*n_local:3*n_local, 1] -= 0.5
    pos[2*n_local:3*n_local, 1] %= 0.5
    alive[3*n_local - free:3*n_local] = False
    # slab 3 (1,1): legal
    pos[3*n_local:] = rng.uniform(0.55, 0.95, (n_local, 3))
    loop = nbody.make_migrate_loop(cfg, mesh, 1, vgrid=vgrid)
    p1, v1, a1, stats = jax.tree.map(np.asarray, loop(pos, vel, alive))
    assert stats.dropped_recv.sum() == 0
    assert a1.sum() == alive.sum()  # lossless
    # only `free` movers could land; the rest are backlogged
    assert stats.sent.sum() == free
    assert stats.backlog.sum() == n_local - free


def test_migrate_vranks_full_rotation_cycle_drains(rng, _devices):
    """A pure rotation cycle of length 3 between COMPLETELY full vranks
    at zero free slots — the round-2 documented stall — must now drain
    via the forced cycle swaps (one row per member per step), ending at
    zero backlog with every row on its owner (round-2 verdict item 5)."""
    dev_grid = ProcessGrid((1, 1, 1))
    vgrid = ProcessGrid((3, 1, 1))
    domain = Domain(0.0, 1.0, periodic=True)
    n_local = 8
    n = 3 * n_local
    mesh = mesh_lib.make_mesh(dev_grid, devices=jax.devices()[:1])

    # vrank v owns x in [v/3, (v+1)/3); place EVERY row of vrank v inside
    # vrank (v+1)%3's slab -> 0 -> 1 -> 2 -> 0 rotation, zero holes.
    pos = rng.random((n, 3), dtype=np.float32)
    for v in range(3):
        nxt = (v + 1) % 3
        pos[v * n_local : (v + 1) * n_local, 0] = (
            (nxt + 0.5) / 3.0
        )
    vel = np.zeros((n, 3), dtype=np.float32)
    alive = np.ones(n, dtype=bool)

    cfg = nbody.DriftConfig(
        domain=domain, grid=dev_grid, dt=0.0, capacity=n_local,
        n_local=n_local,
    )
    loop = nbody.make_migrate_loop(cfg, mesh, n_local, vgrid=vgrid)
    pos_f, vel_f, alive_f, stats = jax.tree.map(
        np.asarray, loop(pos, vel, alive)
    )
    pos_f = nbody.planar_to_rows(pos_f, 3, mesh.size)
    assert stats.dropped_recv.sum() == 0
    assert alive_f.sum() == n
    # one forced swap per member per step: backlog shrinks monotonically
    per_step = stats.backlog.sum(axis=1)
    assert per_step[0] == n - 3  # 3 rows moved on the first step
    assert per_step[-1] == 0, f"cycle did not drain: {per_step}"
    # every row ended on its owning vrank slab
    full = ProcessGrid((3, 1, 1))
    dest_f = binning.rank_of_position(pos_f, domain, full, xp=np)
    assert (dest_f == np.repeat(np.arange(3), n_local)).all()


def test_migrate_vranks_cross_device_cycle_drains(rng, _devices):
    """A pure rotation cycle of length 3 whose members live on TWO
    devices, every vrank completely full at zero free slots — the
    round-3 documented limitation (`no cross-device swap financing`).
    The round-4 global cycle rescue must drain it: the forced remote
    arrival pops the slot the member's forced departure pushed, so the
    cycle drains one row per member per step with zero drops."""
    dev_grid = ProcessGrid((2, 1, 1))
    vgrid = ProcessGrid((2, 1, 1))
    domain = Domain(0.0, 1.0, periodic=True)
    n_local = 8
    V, R_total = 2, 4
    n = R_total * n_local
    mesh = mesh_lib.make_mesh(dev_grid, devices=jax.devices()[:2])

    # global rank g owns x in [g/4, (g+1)/4); ranks 0 (dev 0) and
    # 2, 3 (dev 1) form the cycle 0 -> 2 -> 3 -> 0 (crossing devices
    # twice); rank 1 is full and static (every row already home).
    pos = rng.random((n, 3), dtype=np.float32)
    cycle = {0: 2, 2: 3, 3: 0}
    for g in range(R_total):
        tgt = cycle.get(g, g)
        pos[g * n_local : (g + 1) * n_local, 0] = (tgt + 0.5) / 4.0
    vel = np.zeros((n, 3), dtype=np.float32)
    alive = np.ones(n, dtype=bool)

    cfg = nbody.DriftConfig(
        domain=domain, grid=dev_grid, dt=0.0, capacity=n_local,
        n_local=n_local,
    )
    loop = nbody.make_migrate_loop(cfg, mesh, n_local + 2, vgrid=vgrid)
    pos_f, vel_f, alive_f, stats = jax.tree.map(
        np.asarray, loop(pos, vel, alive)
    )
    pos_f = nbody.planar_to_rows(pos_f, 3, mesh.size)
    assert stats.dropped_recv.sum() == 0
    assert alive_f.sum() == n
    per_step = stats.backlog.sum(axis=1)
    assert per_step[-1] == 0, f"cross-device cycle did not drain: {per_step}"
    # every row ended on its owning global rank slab
    full = ProcessGrid((4, 1, 1))
    dest_f = binning.rank_of_position(pos_f, domain, full, xp=np)
    assert (dest_f == np.repeat(np.arange(4), n_local)).all()


def test_migrate_flat_full_rotation_cycle_drains(rng, _devices):
    """Same 3-cycle stall on the flat multi-device path: the all_gather
    cycle rescue must drain it."""
    grid = ProcessGrid((3, 1, 1))
    domain = Domain(0.0, 1.0, periodic=True)
    n_local = 6
    n = 3 * n_local
    mesh = mesh_lib.make_mesh(grid, devices=jax.devices()[:3])

    pos = rng.random((n, 3), dtype=np.float32)
    for v in range(3):
        nxt = (v + 1) % 3
        pos[v * n_local : (v + 1) * n_local, 0] = (nxt + 0.5) / 3.0
    vel = np.zeros((n, 3), dtype=np.float32)
    alive = np.ones(n, dtype=bool)

    cfg = nbody.DriftConfig(
        domain=domain, grid=grid, dt=0.0, capacity=n_local,
        n_local=n_local,
    )
    loop = nbody.make_migrate_loop(cfg, mesh, n_local)
    pos_f, vel_f, alive_f, stats = jax.tree.map(
        np.asarray, loop(pos, vel, alive)
    )
    pos_f = nbody.planar_to_rows(pos_f, 3, mesh.size)
    assert stats.dropped_recv.sum() == 0
    assert alive_f.sum() == n
    per_step = stats.backlog.sum(axis=1)
    assert per_step[-1] == 0, f"cycle did not drain: {per_step}"
    dest_f = binning.rank_of_position(pos_f, domain, grid, xp=np)
    assert (dest_f == np.repeat(np.arange(3), n_local)).all()


@pytest.mark.parametrize("seed", [0, 1, 2])
def test_migrate_random_pressure_conserves(seed, _devices):
    """Fuzz: random fills, velocities and capacities — alive count is
    invariant and nothing ever drops, on both the flat multi-device path
    and the vrank two-tier path (grant-protocol safety net)."""
    rng = np.random.default_rng(seed)
    domain = Domain(0.0, 1.0, periodic=True)
    n_local = int(rng.integers(24, 72))
    cap = int(rng.integers(2, 10))

    # flat path: 8 devices
    grid = ProcessGrid((2, 2, 2))
    n = grid.nranks * n_local
    pos = rng.random((n, 3)).astype(np.float32)
    vel = (rng.random((n, 3)).astype(np.float32) - 0.5) * 0.8
    alive = rng.random(n) < rng.uniform(0.3, 1.0)
    cfg = nbody.DriftConfig(
        domain=domain, grid=grid, dt=0.3, capacity=cap, n_local=n_local
    )
    mesh = mesh_lib.make_mesh(grid)
    loop = nbody.make_migrate_loop(cfg, mesh, 6)
    _, _, a1, st = jax.tree.map(np.asarray, loop(pos, vel, alive))
    assert st.dropped_recv.sum() == 0
    assert a1.sum() == alive.sum()

    # vrank two-tier path: 2 devices x 4 vranks
    dev_grid = ProcessGrid((2, 1, 1))
    vgrid = ProcessGrid((2, 2, 1))
    vmesh = mesh_lib.make_mesh(dev_grid, devices=jax.devices()[:2])
    vcfg = nbody.DriftConfig(
        domain=domain, grid=dev_grid, dt=0.3, capacity=cap,
        n_local=n_local, local_budget=int(rng.integers(8, 64)),
    )
    vloop = nbody.make_migrate_loop(vcfg, vmesh, 6, vgrid=vgrid)
    _, _, a2, st2 = jax.tree.map(np.asarray, vloop(pos, vel, alive))
    assert st2.dropped_recv.sum() == 0
    assert a2.sum() == alive.sum()


def test_migrate_vranks_clustered_placement_drains_lossless(rng, _devices):
    """Cold-start placement of log-normal clustered rows that start on
    arbitrary vranks of a 4x4x4 grid (64 vranks on one device): dt=0
    steps at a modest per-pair capacity backlog the excess instead of
    dropping it, and the backlog drains until every row is owned."""
    from mpi_grid_redistribute_tpu import oracle

    grid = ProcessGrid((4, 4, 4))
    R, n_local = grid.nranks, 256
    domain = Domain(0.0, 1.0, periodic=True)
    pos = (rng.lognormal(0.0, 1.0, size=(R * n_local, 3)) % 1.0).astype(
        np.float32
    )
    vel = np.zeros_like(pos)
    alive = np.tile(np.arange(n_local) < n_local // 2, R)
    cap = 64
    cfg = nbody.DriftConfig(
        domain=domain, grid=ProcessGrid((1, 1, 1)), dt=0.0, capacity=cap,
        n_local=n_local, local_budget=4 * cap,
    )
    mesh = mesh_lib.make_mesh(cfg.grid, devices=jax.devices()[:1])
    loop = nbody.make_migrate_loop(cfg, mesh, 8, vgrid=grid)
    state = (
        nbody.rows_to_planar(pos, 1), nbody.rows_to_planar(vel, 1), alive
    )
    sent = dropped = 0
    for _ in range(8):
        p, v, a, st = jax.tree.map(np.asarray, loop(*state))
        state = (p, v, a)
        sent += int(st.sent.sum())
        dropped += int(st.dropped_recv.sum())
        assert int(a.sum()) == int(alive.sum())
        if st.sent[-1].sum() == 0:
            break
    assert st.sent[-1].sum() == 0, "placement backlog did not drain"
    assert dropped == 0
    assert sent > 0
    p, a = nbody.planar_to_rows(state[0], 3, mesh.size), state[2]
    oracle.assert_ownership(
        domain, grid,
        [p[r * n_local : (r + 1) * n_local][a[r * n_local : (r + 1) * n_local]]
         for r in range(R)],
    )


def test_balanced_assignment_properties():
    from mpi_grid_redistribute_tpu.parallel import migrate

    rng = np.random.default_rng(3)
    loads = (rng.lognormal(0.0, 1.5, size=64) * 100).astype(np.int64)
    assign = migrate.balanced_assignment(loads, 8)
    assert len(assign) == 64 and set(assign) == set(range(8))
    bins = np.bincount(np.asarray(assign), weights=loads, minlength=8)
    # LPT guarantee: max bin <= 4/3 OPT; OPT >= mean
    assert bins.max() <= (4 / 3) * max(loads.sum() / 8, loads.max()) + 1
    with pytest.raises(ValueError):
        migrate.balanced_assignment(loads[:4], 8)


def test_migrate_vranks_assignment_matches_reference(rng, _devices):
    """Load-balanced cell->vrank assignment: clustered rows on a 4x4x4
    cell grid run as 8 vranks with uniform slabs sized ~mean load, and
    the engine routes every row to its ASSIGNED vrank (set-equality at
    the bit level vs the reference drift), lossless."""
    from mpi_grid_redistribute_tpu.parallel import migrate

    domain = Domain(0.0, 1.0, periodic=True)
    dev_grid = ProcessGrid((1, 1, 1))
    vgrid = ProcessGrid((2, 2, 2))
    cells = ProcessGrid((4, 4, 4))
    V = vgrid.nranks
    mesh = mesh_lib.make_mesh(dev_grid, devices=jax.devices()[:1])

    total = 2048
    pos = (rng.lognormal(-1.0, 1.2, size=(total, 3)) % 1.0).astype(
        np.float32
    )
    cell = binning.rank_of_position(pos, domain, cells, xp=np)
    loads = np.bincount(cell, minlength=cells.nranks)
    assign = migrate.balanced_assignment(loads, V)
    owner = np.asarray(assign)[cell]
    bins = np.bincount(owner, minlength=V)
    assert bins.max() < 2 * total / V  # the balance actually balanced

    n_local = int(bins.max() * 1.5)
    pos_p = np.zeros((V * n_local, 3), np.float32)
    vel_p = np.zeros((V * n_local, 3), np.float32)
    alive = np.zeros((V * n_local,), bool)
    vel = (0.1 * (rng.random((total, 3), dtype=np.float32) - 0.5)).astype(
        np.float32
    )
    for v in range(V):
        m = owner == v
        k = int(m.sum())
        pos_p[v * n_local : v * n_local + k] = pos[m]
        vel_p[v * n_local : v * n_local + k] = vel[m]
        alive[v * n_local : v * n_local + k] = True

    n_steps = 5
    dt = 0.07
    cfg = nbody.DriftConfig(
        domain=domain, grid=dev_grid, dt=dt, capacity=n_local,
        n_local=n_local, local_budget=2 * n_local,
        cells=cells, assignment=assign,
    )
    loop = nbody.make_migrate_loop(cfg, mesh, n_steps, vgrid=vgrid)
    pos_f, vel_f, alive_f, stats = jax.tree.map(
        np.asarray, loop(pos_p, vel_p, alive)
    )
    pos_f = nbody.planar_to_rows(pos_f, 3, mesh.size)
    vel_f = nbody.planar_to_rows(vel_f, 3, mesh.size)

    assert stats.dropped_recv.sum() == 0
    assert stats.backlog[-1].sum() == 0
    assert alive_f.sum() == total

    # ownership: every live row sits on the vrank its cell is ASSIGNED to
    cell_f = binning.rank_of_position(pos_f, domain, cells, xp=np)
    owner_f = np.asarray(assign)[cell_f]
    slot_v = np.repeat(np.arange(V), n_local)
    assert (owner_f[alive_f] == slot_v[alive_f]).all()

    # bit-level set equality vs the reference drift, grouped by ASSIGNED
    # rank (reference reuses the same XLA drift kernel; see
    # _np_drift_reference)
    import jax.numpy as jnp

    @jax.jit
    def _drift(p, v):
        return binning.wrap_periodic(
            p + v * jnp.asarray(dt, p.dtype), domain
        )

    rp, rv, ra = pos_p.copy(), vel_p.copy(), alive.copy()
    for _ in range(n_steps):
        rp[ra] = np.asarray(_drift(rp[ra], rv[ra]))
    rcell = binning.rank_of_position(rp, domain, cells, xp=np)
    rowner = np.asarray(assign)[rcell]
    for v in range(V):
        sl = slice(v * n_local, (v + 1) * n_local)
        got = _rows_set(pos_f[sl], vel_f[sl], alive_f[sl])
        want = _rows_set(rp, rv, ra & (rowner == v))
        assert got == want, f"vrank {v} row set mismatch"


def test_migrate_assignment_validation(rng, _devices):
    from mpi_grid_redistribute_tpu.parallel import migrate

    domain = Domain(0.0, 1.0, periodic=True)
    dev_grid = ProcessGrid((1, 1, 1))
    vgrid = ProcessGrid((2, 1, 1))
    cells = ProcessGrid((4, 1, 1))
    with pytest.raises(ValueError, match="together"):
        migrate.shard_migrate_vranks_fn(
            domain, dev_grid, vgrid, 8, assignment=(0, 1, 0, 1)
        )
    with pytest.raises(ValueError, match="entries"):
        migrate.shard_migrate_vranks_fn(
            domain, dev_grid, vgrid, 8, cells=cells, assignment=(0, 1)
        )
    with pytest.raises(ValueError, match="outside"):
        migrate.shard_migrate_vranks_fn(
            domain, dev_grid, vgrid, 8, cells=cells,
            assignment=(0, 1, 2, 1),
        )
    mesh = mesh_lib.make_mesh(dev_grid, devices=jax.devices()[:1])
    cfg = nbody.DriftConfig(
        domain=domain, grid=dev_grid, dt=0.0, capacity=8, n_local=16,
        cells=cells, assignment=(0, 1, 0, 1),
    )
    with pytest.raises(ValueError, match="vrank path"):
        nbody.make_migrate_loop(cfg, mesh, 1)  # no vgrid
    import dataclasses as _dc

    # single-device scan deposit keys by DEVICE cell (position, not vrank
    # membership), so LPT assignment now composes with it (late round 4)
    cfg2 = _dc.replace(cfg, deposit_shape=(4, 4, 4))
    nbody.make_migrate_loop(cfg2, mesh, 1, vgrid=vgrid)  # must not raise
    # ...but the per-vrank-block paths still cannot serve assignment-
    # decomposed vranks: segment-method deposit, and any multi-device mesh
    cfg3 = _dc.replace(cfg2, deposit_method="segment")
    with pytest.raises(ValueError, match="deposit"):
        nbody.make_migrate_loop(cfg3, mesh, 1, vgrid=vgrid)
    mesh2 = mesh_lib.make_mesh(
        ProcessGrid((2, 1, 1)), devices=jax.devices()[:2]
    )
    cfg4 = _dc.replace(
        cfg2, grid=ProcessGrid((2, 1, 1)),
        cells=ProcessGrid((2, 2, 1)), assignment=(0, 1, 0, 1),
    )
    with pytest.raises(ValueError, match="deposit"):
        nbody.make_migrate_loop(cfg4, mesh2, 1, vgrid=ProcessGrid((1, 2, 1)))


def test_plan_rows_batched_matches_vmapped(rng):
    """The telescoped/flat-take batched plan (round 4) must reproduce the
    per-vrank ``_plan_rows`` bit-for-bit — it feeds the vacated-slot plan
    of the vrank engine, whose landing correctness rides on it."""
    import jax.numpy as jnp
    from mpi_grid_redistribute_tpu.parallel import migrate

    for V, S, n, length in [(4, 4, 257, 64), (8, 8, 1024, 300),
                            (3, 7, 50, 128)]:
        seg_counts = rng.integers(0, 30, size=(V, S)).astype(np.int32)
        seg_starts = np.cumsum(
            np.concatenate(
                [rng.integers(0, 5, size=(V, 1)), seg_counts[:, :-1]],
                axis=1,
            ),
            axis=1,
        ).astype(np.int32)
        order = np.stack(
            [rng.permutation(n).astype(np.int32) for _ in range(V)]
        )
        ref_v, ref_t = jax.vmap(
            lambda ss, sc, o: migrate._plan_rows(ss, sc, o, length)
        )(jnp.asarray(seg_starts), jnp.asarray(seg_counts),
          jnp.asarray(order))
        got_v, got_t = migrate._plan_rows_batched(
            jnp.asarray(seg_starts), jnp.asarray(seg_counts),
            jnp.asarray(order), length
        )
        # entries beyond each vrank's total are clipped junk by contract
        # (callers mask by j < total); compare only the meaningful prefix
        ref_v, got_v = np.asarray(ref_v), np.asarray(got_v)
        tot = np.asarray(ref_t)
        assert np.array_equal(tot, np.asarray(got_t))
        for v in range(V):
            k = min(int(tot[v]), length)
            assert np.array_equal(ref_v[v, :k], got_v[v, :k]), (V, S, v)


def test_stack_push_pop_window_matches_gather(rng):
    """Round-4 affine-window pushes: one dynamic slice of the padded plan
    must equal the direct ``vacated[clip(n_in + (w - rel))]`` gather on
    the in-use window entries."""
    import jax.numpy as jnp
    from mpi_grid_redistribute_tpu.parallel import migrate

    n, P = 96, 32
    for trial in range(20):
        free_stack = rng.permutation(n).astype(np.int32)
        vacated = rng.integers(0, n, size=P).astype(np.int32)
        n_free = int(rng.integers(0, n))
        n_in = int(rng.integers(0, P // 2))
        n_sent = int(rng.integers(n_in, P))
        n_push = max(n_sent - n_in, 0)
        n_pop = int(rng.integers(0, min(n_free, P - 1) + 1))
        fs2, nf2 = migrate._stack_push_pop(
            jnp.asarray(free_stack), jnp.int32(n_free), jnp.int32(n_pop),
            jnp.int32(n_push), jnp.asarray(vacated), jnp.int32(n_in)
        )
        # reference semantics
        fs_ref = free_stack.copy()
        W = min(P, n)
        win_start = int(np.clip(n_free, 0, max(n - W, 0)))
        rel = n_free - win_start
        for w in range(W):
            if rel <= w < rel + n_push:
                idx = int(np.clip(n_in + (w - rel), 0, P - 1))
                if 0 <= win_start + w < n:
                    fs_ref[win_start + w] = vacated[idx]
        assert int(nf2) == n_free - n_pop + n_push
        assert np.array_equal(np.asarray(fs2), fs_ref), trial


def _blend_stack_reference(free_stack, n_free, vacated, n_in, n_sent):
    """The flat landing's former free-stack update, a full-width blend
    over all ``n`` stack entries: entry ``s`` in ``[base, base +
    n_push)`` takes ``vacated[n_in + s - base]``."""
    n, P = free_stack.shape[0], vacated.shape[0]
    n_pop = int(np.clip(n_in - n_sent, 0, n_free))
    n_push = max(n_sent - n_in, 0)
    base = n_free - n_pop
    s_idx = np.arange(n)
    push_vals = vacated[np.clip(n_in + s_idx - base, 0, P - 1)]
    fs = np.where(
        (s_idx >= base) & (s_idx < base + n_push), push_vals, free_stack
    )
    return fs, base + n_push


# (slots n, capacity C, ranks) and how the sent / received counts and
# the free count are drawn; P = ranks * C is the write-plan length
_LAND_CASES = {
    "push": (64, 4, 4, "push"),
    "pop": (64, 4, 4, "pop"),
    "balanced": (64, 4, 4, "balanced"),
    "clamp": (64, 4, 4, "clamp"),  # n_free near n: window start clamps
    "p_ge_n": (16, 8, 4, "mixed"),  # P = 32 >= n: window is all n
}


@pytest.mark.parametrize("case", sorted(_LAND_CASES))
def test_land_arrivals_stack_matches_full_width_blend(case, rng):
    """The flat landing's windowed stack update (:func:`_stack_push_pop`
    under ``mig:stack``) leaves the free stack bit-identical to the
    full-width blend it replaced, on random consistent plans."""
    import jax.numpy as jnp
    from mpi_grid_redistribute_tpu.parallel import migrate

    n, C, R, mode = _LAND_CASES[case]
    P, K = R * C, 4
    for trial in range(12):
        # n_sent <= live = n - n_free; n_in <= P; per-pair counts <= C
        if mode == "clamp":
            n_free = int(rng.integers(n - P + 1, n - 1))
        else:
            n_free = int(rng.integers(0, n - 1))
        live = n - n_free
        top = min(live, P)
        if mode in ("push", "clamp"):  # n_in < n_sent
            n_sent = int(rng.integers(1, top + 1))
            n_in = int(rng.integers(0, n_sent))
        elif mode == "pop":  # n_in > n_sent, drops when past n_free
            n_sent = int(rng.integers(0, top))
            n_in = int(rng.integers(n_sent + 1, P + 1))
        elif mode == "balanced":
            n_sent = n_in = int(rng.integers(0, top + 1))
        else:
            n_sent = int(rng.integers(0, top + 1))
            n_in = int(rng.integers(0, P + 1))

        def split(total):
            c = np.zeros(R, np.int32)
            for _ in range(total):
                c[rng.choice(np.flatnonzero(c < C))] += 1
            return c

        send_counts, recv_counts = split(n_sent), split(n_in)
        alive = np.zeros(n, bool)
        alive[rng.choice(n, live, replace=False)] = True
        holes, lives = np.flatnonzero(~alive), np.flatnonzero(alive)
        free_stack = np.concatenate(
            [rng.permutation(holes), rng.permutation(lives)]
        ).astype(np.int32)
        # each destination's granted prefix: distinct live slots
        leavers = rng.permutation(lives)[:n_sent]
        gather_idx = rng.integers(0, n, R * C).astype(np.int32)
        cum = np.concatenate([[0], np.cumsum(send_counts)])
        for d in range(R):
            gather_idx[d * C:d * C + send_counts[d]] = leavers[
                cum[d]:cum[d + 1]
            ]
        vacated = np.zeros(P, np.int32)
        vacated[:n_sent] = leavers
        fused = rng.random((K, n), dtype=np.float32)
        fused[-1] = alive
        recv = rng.random((K, R * C), dtype=np.float32)
        recv[-1] = 1.0

        _, fs, nf, n_in_got, dropped = migrate._land_arrivals(
            jnp.asarray(fused), jnp.asarray(free_stack), jnp.int32(n_free),
            jnp.asarray(recv), jnp.asarray(recv_counts),
            jnp.asarray(send_counts), jnp.asarray(gather_idx), C,
        )
        fs_ref, nf_ref = _blend_stack_reference(
            free_stack, n_free, vacated, n_in, n_sent
        )
        assert int(n_in_got) == n_in
        assert int(dropped) == max(n_in - n_sent - n_free, 0)
        assert int(nf) == nf_ref, (case, trial)
        assert np.array_equal(np.asarray(fs), fs_ref), (case, trial)


def test_sorted_dest_counts_packed_fallback_boundary(rng):
    """The packed one-word sort (round 4) and the 2-operand fallback must
    agree bit-for-bit; force both paths across the bit-budget boundary."""
    import jax.numpy as jnp
    from mpi_grid_redistribute_tpu.ops import binning

    n = 4096  # b = 12 bits -> packed path needs n_dest + 1 <= 2^19
    for n_dest in [7, 64, (1 << 19) - 1, 1 << 19]:
        dest = rng.integers(0, n_dest + 1, size=n).astype(np.int32)
        o, c, b = binning.sorted_dest_counts(jnp.asarray(dest), n_dest)
        iota = np.arange(n)
        ordr = np.lexsort((iota, dest))
        ks = dest[ordr]
        bounds = np.searchsorted(
            ks, np.arange(n_dest + 1), side="left"
        ).astype(np.int32)
        assert np.array_equal(np.asarray(o), ordr), n_dest
        assert np.array_equal(np.asarray(b), bounds), n_dest


def test_vacated_prefix_fast_path_identity(rng):
    """The unclipped vacated-slot fast path (round 4) rests on an exact
    identity: with stayers sorted to the END (sentinel dest key) and
    ``allowed == eff`` (prefix-truncated full counts), the slow plan's
    positions are pos[v, j] = j, so the plan IS ``order[:, :P]``.
    Verify bit-for-bit on sorted-dest instances, and that one clipped
    pair breaks the identity (the engine's cond then takes the slow
    path)."""
    import jax.numpy as jnp
    from mpi_grid_redistribute_tpu.ops import binning
    from mpi_grid_redistribute_tpu.parallel import migrate

    V, n, n_dest, M = 5, 512, 5, 96
    dest = rng.integers(0, n_dest, size=(V, n)).astype(np.int32)
    self_id = np.arange(V, dtype=np.int32)
    # mark ~90% as staying (sentinel key n_dest), like the real engine
    stay = rng.random((V, n)) < 0.9
    key = np.where(stay, n_dest, dest).astype(np.int32)
    order, counts, bounds = jax.vmap(
        lambda k: binning.sorted_dest_counts(k, n_dest)
    )(jnp.asarray(key))
    loc_starts = np.asarray(bounds)[:, :n_dest].astype(np.int32)
    full = np.asarray(counts).astype(np.int32)
    # eff = prefix truncation of full counts at budget M (engine formula)
    rel_start = loc_starts - loc_starts[:, :1]
    rel_end = rel_start + full
    eff = np.clip(np.minimum(rel_end, M) - np.minimum(rel_start, M), 0,
                  None).astype(np.int32)
    P = M
    slow, tot = migrate._plan_rows_batched(
        jnp.asarray(loc_starts), jnp.asarray(eff), jnp.asarray(order), P
    )
    slow, tot = np.asarray(slow), np.asarray(tot)
    fast = np.asarray(order)[:, :P]
    for v in range(V):
        k = min(int(tot[v]), P)
        assert np.array_equal(slow[v, :k], fast[v, :k]), v
    # clip one mid-plan pair -> identity must break for that vrank
    clipped = eff.copy()
    v_bad, w_bad = 2, 1
    if clipped[v_bad, w_bad] > 1:
        clipped[v_bad, w_bad] -= 1
        slow2, tot2 = migrate._plan_rows_batched(
            jnp.asarray(loc_starts), jnp.asarray(clipped),
            jnp.asarray(order), P
        )
        slow2, tot2 = np.asarray(slow2), np.asarray(tot2)
        k = min(int(tot2[v_bad]), P)
        assert not np.array_equal(slow2[v_bad, :k], fast[v_bad, :k])


def test_plan_rows_batched_seg_rows_matches_reference(rng):
    """``seg_rows`` mode (round 4 — the arrival plan): segments of one
    plan row read DIFFERENT rows of ``order`` and values come back
    globalized as ``s * n + order[s, pos]``. Reference = the vmapped
    per-destination formulation it replaced, written plainly in NumPy."""
    import jax.numpy as jnp
    from mpi_grid_redistribute_tpu.parallel import migrate

    for V, n, M in [(4, 257, 64), (8, 1024, 300), (3, 50, 40)]:
        # per-source segment starts/counts as the engine lays them out:
        # loc_starts[s, w] = start of (s -> w) in source s's sorted
        # space; allowed[s, w] = granted rows of that segment
        counts = rng.integers(0, 20, size=(V, V)).astype(np.int32)
        starts = np.cumsum(
            np.concatenate(
                [rng.integers(0, 3, size=(V, 1)), counts[:, :-1]], axis=1
            ),
            axis=1,
        ).astype(np.int32)
        allowed = np.minimum(
            counts, rng.integers(0, 20, size=(V, V))
        ).astype(np.int32)
        order = np.stack(
            [rng.permutation(n).astype(np.int32) for _ in range(V)]
        )
        got, tot = migrate._plan_rows_batched(
            jnp.asarray(starts.T), jnp.asarray(allowed.T),
            jnp.asarray(order), M,
            seg_rows=jnp.arange(V, dtype=jnp.int32),
        )
        got, tot = np.asarray(got), np.asarray(tot)
        for w in range(V):
            # reference: walk sources in order, take the first
            # allowed[s, w] rows of each (s -> w) segment
            ref = []
            for s in range(V):
                for k in range(int(allowed[s, w])):
                    p = min(max(int(starts[s, w]) + k, 0), n - 1)
                    ref.append(s * n + int(order[s, p]))
            k = min(len(ref), M)
            assert tot[w] == len(ref), (V, w)
            assert np.array_equal(got[w, :k], np.asarray(ref[:k])), (V, w)
