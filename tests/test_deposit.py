import itertools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from mpi_grid_redistribute_tpu.domain import Domain, ProcessGrid
from mpi_grid_redistribute_tpu.ops import deposit as deposit_lib
from mpi_grid_redistribute_tpu.parallel import mesh as mesh_lib
from mpi_grid_redistribute_tpu import GridRedistribute

DOMAIN = Domain(0.0, 1.0, periodic=True)
GRID = ProcessGrid((2, 2, 2))
MESH_SHAPE = (8, 8, 8)


def cic_numpy(pos, mass, mesh_shape, domain):
    """Global periodic CIC oracle."""
    M = np.asarray(mesh_shape)
    lo = np.asarray(domain.lo, dtype=np.float64)
    ext = np.asarray(domain.extent, dtype=np.float64)
    rel = (pos.astype(np.float64) - lo) / ext * M
    i0 = np.floor(rel).astype(np.int64)
    frac = rel - i0
    rho = np.zeros(mesh_shape, dtype=np.float64)
    for corner in itertools.product((0, 1), repeat=3):
        off = np.asarray(corner)
        w = np.prod(np.where(off == 1, frac, 1.0 - frac), axis=1)
        idx = (i0 + off) % M
        np.add.at(rho, (idx[:, 0], idx[:, 1], idx[:, 2]), mass * w)
    return rho


def _deposit_inputs(rng, n_local=200):
    R = GRID.nranks
    pos = rng.uniform(0, 1, size=(R * n_local, 3)).astype(np.float32)
    mass = rng.uniform(0.5, 2.0, size=(R * n_local,)).astype(np.float32)
    return pos, mass


def test_deposit_matches_numpy_oracle(rng):
    pos, mass = _deposit_inputs(rng)
    # deposit requires particles on their owner shard first
    rd = GridRedistribute(DOMAIN, GRID, capacity_factor=3.0, out_capacity=800)
    res = rd.redistribute(pos, mass)
    mesh = mesh_lib.make_mesh(GRID)
    dep = deposit_lib.build_deposit(mesh, DOMAIN, GRID, MESH_SHAPE)
    rho = np.asarray(dep(res.positions, res.fields[0], res.count))
    assert rho.shape == MESH_SHAPE
    expected = cic_numpy(pos, mass, MESH_SHAPE, DOMAIN)
    np.testing.assert_allclose(rho, expected, rtol=2e-4, atol=1e-4)
    np.testing.assert_allclose(rho.sum(), mass.sum(), rtol=1e-5)


def test_deposit_single_particle_weights():
    # one particle at a known fractional position on rank 0
    pos = np.zeros((8, 3), dtype=np.float32)
    pos[0] = [0.15625, 0.03125, 0.0625]  # rel = (1.25, 0.25, 0.5) on 8^3
    mass = np.zeros((8,), dtype=np.float32)
    mass[0] = 2.0
    count = np.array([1, 0, 0, 0, 0, 0, 0, 0], dtype=np.int32)
    mesh = mesh_lib.make_mesh(GRID)
    dep = deposit_lib.build_deposit(mesh, DOMAIN, GRID, MESH_SHAPE)
    rho = np.asarray(dep(pos, mass, count))
    expected = cic_numpy(pos[:1], mass[:1], MESH_SHAPE, DOMAIN)
    np.testing.assert_allclose(rho, expected, rtol=1e-5, atol=1e-6)
    assert rho[1, 0, 0] == pytest.approx(2.0 * 0.75 * 0.75 * 0.5)


def test_deposit_ghost_fold_across_faces(rng):
    # particles hugging the upper faces spill into neighbor shards (and wrap)
    R = GRID.nranks
    pos = np.full((R * 50, 3), 0.999, dtype=np.float32)
    mass = np.ones((R * 50,), dtype=np.float32)
    rd = GridRedistribute(DOMAIN, GRID, capacity_factor=8.0, out_capacity=R * 50)
    res = rd.redistribute(pos, mass)
    mesh = mesh_lib.make_mesh(GRID)
    dep = deposit_lib.build_deposit(mesh, DOMAIN, GRID, MESH_SHAPE)
    rho = np.asarray(dep(res.positions, res.fields[0], res.count))
    expected = cic_numpy(pos, mass, MESH_SHAPE, DOMAIN)
    np.testing.assert_allclose(rho, expected, rtol=1e-4, atol=1e-4)
    np.testing.assert_allclose(rho.sum(), mass.sum(), rtol=1e-5)


def cic_numpy_clamped(pos, mass, mesh_shape, domain):
    """Global CIC oracle for non-periodic axes: cells+1 node planes, no
    wrap, boundary particles clamp into the last cell (frac -> 1)."""
    M = np.asarray(mesh_shape)
    per = np.asarray(domain.periodic)
    lo = np.asarray(domain.lo, dtype=np.float64)
    ext = np.asarray(domain.extent, dtype=np.float64)
    rel = (pos.astype(np.float64) - lo) / ext * M
    i0 = np.clip(np.floor(rel).astype(np.int64), 0, M - 1)
    frac = np.clip(rel - i0, 0.0, 1.0)
    nodes = tuple(m if p else m + 1 for m, p in zip(mesh_shape, per))
    rho = np.zeros(nodes, dtype=np.float64)
    for corner in itertools.product((0, 1), repeat=3):
        off = np.asarray(corner)
        w = np.prod(np.where(off == 1, frac, 1.0 - frac), axis=1)
        idx = np.where(per, (i0 + off) % M, i0 + off)
        np.add.at(rho, (idx[:, 0], idx[:, 1], idx[:, 2]), mass * w)
    return rho


@pytest.mark.parametrize(
    "periodic", [False, (True, False, True)], ids=["open", "mixed"]
)
def test_deposit_nonperiodic_matches_oracle(rng, periodic):
    # round-1 verdict item 8: non-periodic CIC — one extra clamp-edge node
    # plane per open axis, assembled dense + replicated; boundary mass at
    # the upper faces lands on the last plane instead of wrapping.
    dom = Domain(0.0, 1.0, periodic=periodic)
    pos, mass = _deposit_inputs(rng, n_local=300)
    pos[:40] = 0.999999  # exercise the upper boundary planes
    pos[40:80, 0] = 0.0
    rd = GridRedistribute(dom, GRID, capacity_factor=4.0, out_capacity=1200)
    res = rd.redistribute(pos, mass)
    mesh = mesh_lib.make_mesh(GRID)
    dep = deposit_lib.build_deposit(mesh, dom, GRID, MESH_SHAPE)
    rho = np.asarray(dep(res.positions, res.fields[0], res.count))
    assert rho.shape == deposit_lib.global_node_shape(dom, MESH_SHAPE)
    expected = cic_numpy_clamped(pos, mass, MESH_SHAPE, dom)
    np.testing.assert_allclose(rho, expected, rtol=2e-4, atol=1e-4)
    np.testing.assert_allclose(rho.sum(), mass.sum(), rtol=1e-5)


def test_deposit_nonperiodic_migrate_step(rng):
    # the masked (migrate-path) deposit also supports open domains
    from mpi_grid_redistribute_tpu.models import nbody
    from mpi_grid_redistribute_tpu.ops import binning

    dom = Domain(0.0, 1.0, periodic=False)
    R = GRID.nranks
    n_local = 64
    cfg = nbody.DriftConfig(
        domain=dom, grid=GRID, dt=0.0, capacity=16, n_local=n_local,
        deposit_shape=(4, 4, 4),
    )
    mesh = mesh_lib.make_mesh(GRID)
    step = nbody.make_migrate_step(cfg, mesh)
    pos = rng.random((R * n_local, 3), dtype=np.float32)
    dest = binning.rank_of_position(pos, dom, GRID, xp=np)
    alive = dest == np.repeat(np.arange(R), n_local)
    vel = np.zeros_like(pos)
    out = jax.tree.map(np.asarray, step(pos, vel, alive))
    rho = out[-1]
    assert rho.shape == (5, 5, 5)
    np.testing.assert_allclose(rho.sum(), alive.sum(), rtol=1e-5)


def test_deposit_rejects_indivisible_mesh():
    with pytest.raises(ValueError):
        deposit_lib.shard_deposit_fn(DOMAIN, GRID, (9, 8, 8))


def test_masked_deposit_ignores_garbage_holes(rng, _devices):
    """Dead slots may hold NaN/Inf bytes (migration holes); the masked
    deposit must still produce a finite, mass-conserving mesh."""
    import jax
    from mpi_grid_redistribute_tpu.models import nbody
    from mpi_grid_redistribute_tpu.parallel import mesh as mesh_lib

    grid = ProcessGrid((2, 2, 2))
    R = grid.nranks
    domain = Domain(0.0, 1.0, periodic=True)
    n_local = 32
    n = R * n_local
    mesh = mesh_lib.make_mesh(grid)
    cfg = nbody.DriftConfig(
        domain=domain, grid=grid, dt=0.0, capacity=4, n_local=n_local,
        deposit_shape=(4, 4, 4),
    )
    step = nbody.make_migrate_step(cfg, mesh)

    pos = rng.random((n, 3), dtype=np.float32)
    from mpi_grid_redistribute_tpu.ops import binning
    dest = binning.rank_of_position(pos, domain, grid, xp=np)
    alive = dest == np.repeat(np.arange(R), n_local)
    pos[~alive] = np.nan  # garbage holes
    vel = np.zeros((n, 3), dtype=np.float32)

    out = jax.tree.map(np.asarray, step(pos, vel, alive))
    rho = out[-1]
    assert np.isfinite(rho).all()
    assert np.isclose(rho.sum(), alive.sum(), rtol=1e-4)


def test_scan_deposit_matches_segment(rng, _devices):
    """The scatter-free 'scan' deposit agrees with segment_sum tightly
    (double-float prefixes), including NaN holes and ghost fold."""
    import jax
    import jax.numpy as jnp
    from mpi_grid_redistribute_tpu.ops import deposit as dep

    N = 50000
    M = (8, 8, 8)
    pos = rng.random((N, 3)).astype(np.float32)
    mass = rng.random(N).astype(np.float32)
    valid = rng.random(N) > 0.1
    pos[~valid] = np.nan
    lo = jnp.zeros(3)
    inv_h = jnp.full(3, 8.0)
    a = np.asarray(
        dep.cic_deposit_local(
            jnp.asarray(pos), jnp.asarray(mass), jnp.asarray(valid), lo,
            inv_h, M,
        )
    )
    b = np.asarray(
        dep.cic_deposit_local_sorted(
            jnp.asarray(pos), jnp.asarray(mass), jnp.asarray(valid), lo,
            inv_h, M,
        )
    )
    assert np.isfinite(b).all()
    np.testing.assert_allclose(b.sum(), a.sum(), rtol=1e-5)
    np.testing.assert_allclose(b, a, atol=a.max() * 1e-6)


def test_scan_deposit_accuracy_vs_float64_oracle(rng, _devices):
    """Round-1 verdict item 5: the fast path's per-cell error vs a float64
    oracle is <=1e-5 relative, at scale, on clustered data.

    The f64 oracle sums the *same f32 per-particle weights* in float64, so
    the comparison isolates summation error (the thing the double-float
    prefix scheme fixes) from the shared f32 frac quantization. Strict
    per-cell relative error is checked for every cell above 1e-6 of the
    peak (below that, the ~eps^2 * channel-total double-float floor
    dominates any fixed-precision prefix scheme)."""
    import jax.numpy as jnp
    from mpi_grid_redistribute_tpu.ops import deposit as dep

    N = 1_000_000
    M = (16, 16, 16)
    pos = (rng.lognormal(-1.5, 0.5, size=(N, 3)) % 1.0).astype(np.float32)
    mass = rng.uniform(0.5, 2.0, N).astype(np.float32)
    valid = rng.random(N) > 0.05
    lo = jnp.zeros(3)
    inv_h = jnp.full(3, float(M[0]))
    got = np.asarray(
        dep.cic_deposit_local_sorted(
            jnp.asarray(pos), jnp.asarray(mass), jnp.asarray(valid), lo,
            inv_h, M,
        )
    )
    # float64 oracle over the f32 weight pipeline
    posv, massv = pos[valid], mass[valid]
    rel32 = posv * np.asarray(M, np.float32)
    i0 = np.clip(np.floor(rel32).astype(np.int64), 0, np.asarray(M) - 1)
    frac = np.clip(rel32 - i0.astype(np.float32), 0, 1).astype(np.float32)
    rho = np.zeros(tuple(m + 1 for m in M))
    for corner in itertools.product((0, 1), repeat=3):
        off = np.asarray(corner)
        w = np.prod(
            np.where(off == 1, frac, np.float32(1) - frac), axis=1
        ).astype(np.float32)
        wf = (massv * w).astype(np.float32)
        idx = i0 + off
        np.add.at(rho, (idx[:, 0], idx[:, 1], idx[:, 2]), wf.astype(np.float64))

    diff = np.abs(got - rho)
    floor = rho.max() * 1e-6
    cells = rho > floor
    max_rel = (diff[cells] / rho[cells]).max()
    assert max_rel <= 1e-5, f"max per-cell relative error {max_rel:.2e}"
    assert diff.max() <= rho.max() * 1e-5  # normalized max error, all cells
    np.testing.assert_allclose(got.sum(), rho.sum(), rtol=1e-6)


def test_planar_deposit_matches_rowmajor(rng, _devices):
    """Round-4 planar deposit: component-major [D, V*n] input, no [n, D]
    buffer anywhere — per-cell values are BIT-IDENTICAL to the row-major
    scan deposit (both cores sort by (key, iota) with two compare keys,
    pinning the within-cell summation order)."""
    import jax.numpy as jnp
    from mpi_grid_redistribute_tpu.domain import ProcessGrid
    from mpi_grid_redistribute_tpu.ops import deposit as dep

    V, n = 8, 40000
    vblock = (8, 8, 8)
    pos = rng.random((V, n, 3)).astype(np.float32)
    mass = rng.random((V, n)).astype(np.float32)
    valid = rng.random((V, n)) > 0.1
    # per-vrank origins on a 2x2x2 subgrid of a [0,1) domain
    vg = ProcessGrid((2, 2, 2))
    lo = np.asarray(
        [np.asarray(vg.cell_of_rank(v)) * 0.5 for v in range(V)],
        np.float32,
    )
    pos_abs = lo[:, None, :] + pos * 0.5
    inv_h = jnp.full(3, 16.0)  # vblock 8 over width 0.5
    a = np.asarray(
        dep.cic_deposit_vranks_sorted(
            jnp.asarray(pos_abs), jnp.asarray(mass), jnp.asarray(valid),
            jnp.asarray(lo), inv_h, vblock,
        )
    )
    pos_rows = jnp.asarray(
        np.ascontiguousarray(pos_abs.transpose(2, 0, 1)).reshape(3, V * n)
    )
    b = np.asarray(
        dep.cic_deposit_vranks_planar(
            pos_rows, jnp.asarray(mass.reshape(-1)),
            jnp.asarray(valid.reshape(-1)), jnp.asarray(lo), inv_h,
            vblock,
        )
    )
    np.testing.assert_array_equal(b.view(np.uint32), a.view(np.uint32))


def test_device_planar_deposit_matches_local_sorted(rng, _devices):
    """Late-round-4 DEVICE-keyed planar deposit: keys by device-local
    global cell (no per-vrank assembly) — bit-identical to the row-major
    single-block scan deposit on the same inputs (same (key, iota) sort
    contract), and mass-conserving."""
    import jax.numpy as jnp
    from mpi_grid_redistribute_tpu.ops import deposit as dep

    n = 120000
    dev_block = (16, 16, 16)
    pos = rng.random((n, 3)).astype(np.float32)
    mass = rng.random(n).astype(np.float32)
    valid = rng.random(n) > 0.1
    lo = jnp.zeros(3)
    inv_h = jnp.full(3, 16.0)
    a = np.asarray(
        dep.cic_deposit_local_sorted(
            jnp.asarray(pos), jnp.asarray(mass), jnp.asarray(valid),
            lo, inv_h, dev_block,
        )
    )
    pos_rows = jnp.asarray(np.ascontiguousarray(pos.T))
    b = np.asarray(
        dep.cic_deposit_device_planar(
            pos_rows, jnp.asarray(mass), jnp.asarray(valid),
            lo, inv_h, dev_block,
        )
    )
    np.testing.assert_array_equal(b.view(np.uint32), a.view(np.uint32))
    np.testing.assert_allclose(b.sum(), mass[valid].sum(), rtol=1e-5)
    # the channel-grouped form (the >16M-row memory bound) is bit-identical
    key = jnp.zeros(n, jnp.int32)
    strides = dep._row_major_strides(dev_block)
    rel = jnp.where(jnp.asarray(valid)[None, :],
                    jnp.asarray(pos_rows) * 16.0, 0.0)
    for d in range(3):
        i0 = jnp.clip(
            jnp.floor(rel[d]).astype(jnp.int32), 0, dev_block[d] - 1
        )
        key = key + i0 * jnp.int32(strides[d])
    key = jnp.where(jnp.asarray(valid), key, jnp.int32(16 ** 3))
    mass_z = jnp.where(jnp.asarray(valid), jnp.asarray(mass), 0.0)
    c = np.asarray(dep._sorted_per_segment_planar(
        key, rel, mass_z, 16 ** 3, dev_block, 256, channel_group=2,
    ))
    d = np.asarray(dep._sorted_per_segment_planar(
        key, rel, mass_z, 16 ** 3, dev_block, 256, channel_group=None,
    ))
    np.testing.assert_array_equal(c.view(np.uint32), d.view(np.uint32))


def test_device_planar_deposit_sharded_oracle(rng, _devices):
    """Device-keyed planar deposit through shard_map on a 2x2x2 mesh:
    matches the global NumPy CIC oracle and conserves mass."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import PartitionSpec as P
    from jax import shard_map
    from mpi_grid_redistribute_tpu.ops import deposit as dep
    from mpi_grid_redistribute_tpu.models import initial

    dom = Domain(0.0, 1.0, periodic=True)
    dev_grid = ProcessGrid((2, 2, 2))
    mesh = mesh_lib.make_mesh(dev_grid)
    n = 4096
    fn = dep.shard_deposit_device_planar_fn(dom, dev_grid, MESH_SHAPE)
    spec = P(dev_grid.axis_names)
    wrapped = jax.jit(
        shard_map(
            fn, mesh=mesh,
            in_specs=(P(None, dev_grid.axis_names), spec, spec),
            out_specs=dep.deposit_out_spec(dom, dev_grid),
        )
    )
    pos, _, _ = initial.uniform_state((2, 2, 2), n, 1.0, rng)
    pos_rows = np.ascontiguousarray(
        pos.reshape(8, n, 3).transpose(2, 0, 1)
    ).reshape(3, 8 * n)
    mass = np.ones(8 * n, np.float32)
    valid = np.ones(8 * n, bool)
    rho = np.asarray(wrapped(pos_rows, mass, valid))
    np.testing.assert_allclose(rho.sum(), 8 * n, rtol=1e-6)
    expected = cic_numpy(
        pos_rows.T.astype(np.float32), mass, MESH_SHAPE, dom
    )
    np.testing.assert_allclose(rho, expected, rtol=2e-4, atol=1e-4)


def test_planar_deposit_conserves_and_places(rng, _devices):
    """Mass conservation + correct block placement for the planar deposit
    through the shard-level wrapper (fold_ghosts path)."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import PartitionSpec as P
    from jax import shard_map
    from mpi_grid_redistribute_tpu.domain import Domain, ProcessGrid
    from mpi_grid_redistribute_tpu.ops import deposit as dep
    from mpi_grid_redistribute_tpu.parallel import mesh as mesh_lib

    dom = Domain(0.0, 1.0, periodic=True)
    dev_grid = ProcessGrid((2, 2, 2))
    vgrid = ProcessGrid((1, 1, 1))
    mesh = mesh_lib.make_mesh(dev_grid)
    n = 4096
    fn = dep.shard_deposit_vranks_planar_fn(dom, dev_grid, vgrid, (16, 16, 16))
    spec = P(dev_grid.axis_names)
    wrapped = jax.jit(
        shard_map(
            fn, mesh=mesh, in_specs=(P(None, dev_grid.axis_names), spec, spec),
            out_specs=dep.deposit_out_spec(dom, dev_grid),
        )
    )
    from mpi_grid_redistribute_tpu.models import initial
    pos, _, _ = initial.uniform_state((2, 2, 2), n, 1.0, rng)
    pos_rows = np.ascontiguousarray(
        pos.reshape(8, n, 3).transpose(2, 0, 1)
    ).reshape(3, 8 * n)
    mass = np.ones(8 * n, np.float32)
    valid = np.ones(8 * n, bool)
    rho = np.asarray(wrapped(pos_rows, mass, valid))
    np.testing.assert_allclose(rho.sum(), 8 * n, rtol=1e-6)


def test_drift_loop_scan_deposit_method(rng, _devices):
    """deposit_method='scan' plumbs through BOTH the fused config-5 step
    and make_drift_loop (incl. deposit_each_step, the benchmark path)."""
    import jax
    from mpi_grid_redistribute_tpu.models import nbody
    from mpi_grid_redistribute_tpu.parallel import mesh as mesh_lib

    grid = ProcessGrid((2, 2, 2))
    R = grid.nranks
    domain = Domain(0.0, 1.0, periodic=True)
    n_local = 64
    mesh = mesh_lib.make_mesh(grid)
    cfg = nbody.DriftConfig(
        domain=domain, grid=grid, dt=0.01, capacity=16, n_local=n_local,
        deposit_shape=(8, 8, 8), deposit_method="scan",
    )
    step = nbody.make_drift_step(cfg, mesh)
    pos = rng.random((R * n_local, 3), dtype=np.float32)
    vel = np.zeros((R * n_local, 3), np.float32)
    count = np.full((R,), n_local, np.int32)
    out = jax.tree.map(np.asarray, step(pos, vel, count))
    loop = nbody.make_drift_loop(cfg, mesh, 3, deposit_each_step=True)
    lout = jax.tree.map(np.asarray, loop(pos, vel, count))
    np.testing.assert_allclose(
        lout[-1].sum(), lout[2].sum(), rtol=1e-4
    )
    rho = out[-1]
    # scattered initial placement overflows out_capacity on some shards;
    # the drops are surfaced, and deposited mass must match survivors
    survivors = out[2].sum()
    dropped = out[3].dropped_recv.sum()
    assert survivors + dropped == R * n_local
    np.testing.assert_allclose(rho.sum(), survivors, rtol=1e-4)


def test_migrate_loop_deposit_each_step(rng, _devices):
    """deposit_each_step on the migrate loop (config-5 fused workload):
    every scanned step deposits; the carried mesh equals a standalone
    deposit of the final state and conserves mass."""
    import jax
    from mpi_grid_redistribute_tpu.models import nbody

    grid = ProcessGrid((2, 2, 2))
    R = grid.nranks
    domain = Domain(0.0, 1.0, periodic=True)
    n_local = 64
    mesh = mesh_lib.make_mesh(grid)
    cfg = nbody.DriftConfig(
        domain=domain, grid=grid, dt=0.01, capacity=16, n_local=n_local,
        deposit_shape=(8, 8, 8),
    )
    pos = rng.random((R * n_local, 3), dtype=np.float32)
    vel = (rng.random((R * n_local, 3), dtype=np.float32) - 0.5).astype(
        np.float32
    ) * 0.01
    alive = rng.random(R * n_local) > 0.2
    loop = nbody.make_migrate_loop(cfg, mesh, 3, deposit_each_step=True)
    p, v, a, st, rho = jax.tree.map(np.asarray, loop(pos, vel, alive))
    p = nbody.planar_to_rows(p, 3, mesh.size)
    survivors = int(a.sum())
    np.testing.assert_allclose(rho.sum(), survivors, rtol=1e-4)
    # equals a standalone deposit of the final state
    dep = nbody.build_deposit_masked(cfg, mesh)
    rho2 = np.asarray(dep(p, np.ones(p.shape[0], np.float32), a))
    np.testing.assert_allclose(rho, rho2, rtol=1e-5, atol=1e-5)

    # vrank variant of the same fused workload
    dev_grid = ProcessGrid((2, 1, 1))
    vgrid = ProcessGrid((1, 2, 2))
    vmesh = mesh_lib.make_mesh(dev_grid, devices=jax.devices()[:2])
    vcfg = nbody.DriftConfig(
        domain=domain, grid=dev_grid, dt=0.01, capacity=16,
        n_local=n_local, deposit_shape=(8, 8, 8),
    )
    vloop = nbody.make_migrate_loop(
        vcfg, vmesh, 3, vgrid=vgrid, deposit_each_step=True
    )
    pv, vv, av, stv, rhov = jax.tree.map(np.asarray, vloop(pos, vel, alive))
    np.testing.assert_allclose(rhov.sum(), av.sum(), rtol=1e-4)

    # non-periodic variant: the dense-assembled rho ends in a psum
    # (axis-invariant), and the scan carry must match (regression:
    # a varying init failed lax.scan's carry-type check)
    for per in (False, (True, True, False)):
        odom = Domain(0.0, 1.0, periodic=per)
        ocfg = nbody.DriftConfig(
            domain=odom, grid=grid, dt=0.0, capacity=16, n_local=n_local,
            deposit_shape=(8, 8, 8),
        )
        oloop = nbody.make_migrate_loop(ocfg, mesh, 2,
                                        deposit_each_step=True)
        oo = jax.tree.map(np.asarray, oloop(pos, vel, alive))
        rho_o = oo[-1]
        assert rho_o.shape == deposit_lib.global_node_shape(odom, (8, 8, 8))
        np.testing.assert_allclose(rho_o.sum(), oo[2].sum(), rtol=1e-4)


def test_vrank_deposit_matches_flat(rng, _devices):
    """Deposit through the vrank migrate loop equals the same particles
    deposited on the equivalent flat grid."""
    import jax
    from mpi_grid_redistribute_tpu.models import nbody
    from mpi_grid_redistribute_tpu.parallel import mesh as mesh_lib
    from mpi_grid_redistribute_tpu.ops import binning

    dev_grid = ProcessGrid((2, 1, 1))
    vgrid = ProcessGrid((2, 2, 1))
    full = ProcessGrid((4, 2, 1))
    domain = Domain(0.0, 1.0, periodic=True)
    n_local = 128
    R = 8
    mesh = mesh_lib.make_mesh(dev_grid, devices=jax.devices()[:2])
    dshape = (8, 8, 8)

    # particles legally placed per slab (device-major slabs of the full grid)
    from tests.test_migrate import _slab_full_ranks

    _, slab_rank = _slab_full_ranks(dev_grid, vgrid)
    pos = rng.random((R * n_local, 3), dtype=np.float32)
    dest = binning.rank_of_position(pos, domain, full, xp=np)
    alive = dest == np.repeat(slab_rank, n_local)
    vel = np.zeros_like(pos)

    cfg = nbody.DriftConfig(
        domain=domain, grid=dev_grid, dt=0.0, capacity=8, n_local=n_local,
        deposit_shape=dshape,
    )
    loop = nbody.make_migrate_loop(cfg, mesh, 1, vgrid=vgrid)
    out = jax.tree.map(np.asarray, loop(pos, vel, alive))
    rho = out[-1]
    assert rho.shape == dshape
    np.testing.assert_allclose(rho.sum(), alive.sum(), rtol=1e-5)

    expected = cic_numpy(pos[alive], np.ones(alive.sum(), np.float32),
                         dshape, domain)
    np.testing.assert_allclose(rho, expected, rtol=1e-4, atol=1e-4)


def test_pallas_dfscan_bit_identical_to_xla():
    """The VMEM double-float prefix kernel must reproduce _df_cumsum
    bit-for-bit — the scan deposit's accuracy contract rides on the
    exact TwoSum sequence."""
    import numpy as np
    import jax.numpy as jnp
    from mpi_grid_redistribute_tpu.ops import deposit, pallas_dfscan

    r = np.random.default_rng(11)
    for rows, tile in [(7, 256), (300, 128), (1025, 64)]:
        x = (r.random((rows, tile), dtype=np.float32) - 0.5) * np.exp(
            r.normal(0, 8, size=(rows, tile))
        ).astype(np.float32)
        hi_ref, lo_ref = deposit._df_cumsum(jnp.asarray(x), axis=1)
        hi_k, lo_k = pallas_dfscan.tile_df_cumsum_rows(
            jnp.asarray(x), interpret=True
        )
        assert np.array_equal(
            np.asarray(hi_ref).view(np.uint32),
            np.asarray(hi_k).view(np.uint32),
        ), (rows, tile)
        assert np.array_equal(
            np.asarray(lo_ref).view(np.uint32),
            np.asarray(lo_k).view(np.uint32),
        ), (rows, tile)


def test_segdep_kernel_matches_xla_fallback(rng):
    """The Pallas segmented-sum deposit kernel (interpret mode) matches
    the XLA segment_sum fallback on the same sorted stream — across
    sentinels, empty cells, multi-chunk spans, and block boundaries."""
    import jax.numpy as jnp
    from mpi_grid_redistribute_tpu.ops import pallas_segdep as sd

    for n, density, vblock in [(10_000, 1.0, (8, 8, 8)),
                               (9_000, 0.05, (16, 16, 16)),
                               (4096, 0.0, (8, 8, 8)),
                               (100, 1.0, (8, 8, 8)),
                               (5_000, 0.01, (16, 16, 16))]:
        n_cells = int(np.prod(vblock))
        if density:
            # density < 1 clusters all keys into a FRACTION of the cell
            # range, so blocks span many empty canvas chunks — the
            # kernel's flush-forward gap handling is actually exercised
            hot = max(1, int(n_cells * density))
            cells = rng.choice(n_cells, size=hot, replace=False)
            key = cells[rng.integers(0, hot, size=n)].astype(np.int32)
            valid = rng.random(n) < 0.9
        else:
            key = np.zeros(n, np.int32)
            valid = np.zeros(n, bool)
        key = np.sort(np.where(valid, key, n_cells)).astype(np.int32)
        rel = (rng.random((3, n)) * vblock[0]).astype(np.float32)
        mass = rng.random(n).astype(np.float32)
        a = np.asarray(
            sd._segsum_tpu(
                jnp.asarray(key), jnp.asarray(rel), jnp.asarray(mass),
                n_cells, vblock, 3, interpret=True,
            )
        )
        b = np.asarray(
            sd._segsum_xla(
                jnp.asarray(key), jnp.asarray(rel), jnp.asarray(mass),
                n_cells, vblock, 3,
            )
        )
        np.testing.assert_allclose(a, b, rtol=1e-6, atol=1e-6)
        # unit-mass (mass=None) drops the operand and multiplies by 1
        au = np.asarray(
            sd._segsum_tpu(
                jnp.asarray(key), jnp.asarray(rel), None,
                n_cells, vblock, 3, interpret=True,
            )
        )
        bu = np.asarray(
            sd._segsum_xla(
                jnp.asarray(key), jnp.asarray(rel), None,
                n_cells, vblock, 3,
            )
        )
        np.testing.assert_allclose(au, bu, rtol=1e-6, atol=1e-6)


def test_mxu_deposit_accuracy_and_conservation(rng, _devices):
    """cic_deposit_device_mxu vs the float64 oracle (same tolerance the
    scan engine is held to) + exact-class conservation; and the fused
    migrate loop runs end-to-end with deposit_method='mxu'."""
    import jax
    import jax.numpy as jnp
    from mpi_grid_redistribute_tpu.ops import deposit as dep
    from mpi_grid_redistribute_tpu.models import nbody

    n = 120_000
    dev_block = (16, 16, 16)
    pos = rng.random((n, 3)).astype(np.float32)
    mass = rng.random(n).astype(np.float32)
    valid = rng.random(n) > 0.1
    pos_rows = jnp.asarray(np.ascontiguousarray(pos.T))
    rho = np.asarray(
        dep.cic_deposit_device_mxu(
            pos_rows, jnp.asarray(mass), jnp.asarray(valid),
            jnp.zeros(3), jnp.full(3, 16.0), dev_block,
        )
    )
    np.testing.assert_allclose(rho.sum(), mass[valid].sum(), rtol=1e-5)
    # f64 oracle per-cell (ghost mesh, no fold)
    rel = pos.astype(np.float64) * 16.0
    i0 = np.clip(np.floor(rel).astype(np.int64), 0, 15)
    frac = rel - i0
    want = np.zeros((17, 17, 17))
    import itertools as it
    for corner in it.product((0, 1), repeat=3):
        off = np.asarray(corner)
        w = np.prod(np.where(off == 1, frac, 1.0 - frac), axis=1)
        idx = i0 + off
        np.add.at(
            want, (idx[:, 0], idx[:, 1], idx[:, 2]),
            np.where(valid, mass.astype(np.float64) * w, 0.0),
        )
    np.testing.assert_allclose(rho, want, rtol=2e-5, atol=2e-5)

    # fused loop end-to-end (CPU: exercises the XLA fallback path)
    grid = ProcessGrid((2, 2, 2))
    mesh = mesh_lib.make_mesh(grid)
    n_local = 64
    cfg = nbody.DriftConfig(
        domain=Domain(0.0, 1.0, periodic=True), grid=grid, dt=0.01,
        capacity=16, n_local=n_local, deposit_shape=(8, 8, 8),
        deposit_method="mxu",
    )
    R = grid.nranks
    pos2 = rng.random((R * n_local, 3), dtype=np.float32)
    vel2 = (rng.random((R * n_local, 3), dtype=np.float32) - 0.5) * 0.01
    alive = rng.random(R * n_local) > 0.2
    loop = nbody.make_migrate_loop(cfg, mesh, 3, deposit_each_step=True)
    out = jax.tree.map(np.asarray, loop(pos2, vel2.astype(np.float32), alive))
    rho2 = out[-1]
    np.testing.assert_allclose(rho2.sum(), out[2].sum(), rtol=1e-4)


def test_segdep_kernel_slab_stream(rng):
    """Concatenated per-slab sorts are a legal kernel stream (the
    CHUNK-MONOTONE contract): vrank-major keys sorted per slab leave
    sentinel runs MID-stream — including T-blocks that START with
    sentinels — and the min-key block starts must still match the XLA
    fallback."""
    import jax.numpy as jnp
    from mpi_grid_redistribute_tpu.ops import pallas_segdep as sd

    V, vblock = 4, (8, 8, 8)
    C = int(np.prod(vblock))
    n_cells = V * C
    # slab 0 is 1.5 T-blocks long and 97% invalid, so block 1 STARTS
    # inside slab 0's sentinel tail (k2[0,0] == sentinel while the block
    # holds valid slab-1 keys: the exact case k2[0,0]-based starts skip)
    slab_sizes = [6144, 3000, 4096, 500]
    valid_frac = [0.03, 0.8, 0.5, 1.0]
    keys = []
    for v, (sn, vf) in enumerate(zip(slab_sizes, valid_frac)):
        valid = rng.random(sn) < vf
        k = np.where(
            valid, v * C + rng.integers(0, C, size=sn), n_cells
        )
        keys.append(np.sort(k.astype(np.int32)))
    key = np.concatenate(keys)
    m = key.shape[0]
    rel = (rng.random((3, m)) * vblock[0]).astype(np.float32)
    mass = rng.random(m).astype(np.float32)
    for mz in (jnp.asarray(mass), None):
        a = np.asarray(
            sd._segsum_tpu(
                jnp.asarray(key), jnp.asarray(rel), mz,
                n_cells, vblock, 3, interpret=True,
            )
        )
        b = np.asarray(
            sd._segsum_xla(
                jnp.asarray(key), jnp.asarray(rel), mz,
                n_cells, vblock, 3,
            )
        )
        np.testing.assert_allclose(a, b, rtol=1e-6, atol=1e-6)


def test_slab_mxu_deposit_matches_flat_engine(rng):
    """cic_deposit_vranks_mxu (slab-keyed, per-slab sorts, vrank-major
    canvas remap) against the flat device-keyed engine AND the float64
    oracle, on slab-consistent data (each slab's rows inside its vrank's
    region — the post-redistribute invariant)."""
    import jax.numpy as jnp
    from mpi_grid_redistribute_tpu.ops import deposit as dep

    vgrid_shape = (2, 2, 1)
    V = int(np.prod(vgrid_shape))
    dev_block = (16, 16, 16)
    vblock = tuple(b // v for b, v in zip(dev_block, vgrid_shape))
    n = 30_000
    pos = np.empty((V * n, 3), np.float32)
    vcells = list(itertools.product(*[range(g) for g in vgrid_shape]))
    for v, vc in enumerate(vcells):
        lo = np.asarray(vc) / np.asarray(vgrid_shape)
        wid = 1.0 / np.asarray(vgrid_shape)
        pos[v * n : (v + 1) * n] = (
            lo + rng.random((n, 3)) * wid
        ).astype(np.float32)
    mass = rng.uniform(0.5, 2.0, size=(V * n,)).astype(np.float32)
    valid = rng.random(V * n) > 0.1
    pos_rows = jnp.asarray(np.ascontiguousarray(pos.T))
    lo_all = jnp.asarray(
        np.asarray(vcells, np.float32) / np.asarray(vgrid_shape, np.float32)
    )
    rho_slab = np.asarray(
        dep.cic_deposit_vranks_mxu(
            pos_rows, jnp.asarray(mass), jnp.asarray(valid),
            lo_all, jnp.full(3, 16.0), vblock, vgrid_shape,
        )
    )
    rho_flat = np.asarray(
        dep.cic_deposit_device_mxu(
            pos_rows, jnp.asarray(mass), jnp.asarray(valid),
            jnp.zeros(3), jnp.full(3, 16.0), dev_block,
        )
    )
    # block-local vs device-relative rel arithmetic differ by ~1 ulp
    np.testing.assert_allclose(rho_slab, rho_flat, rtol=2e-5, atol=2e-5)
    np.testing.assert_allclose(
        rho_slab.sum(), mass[valid].sum(), rtol=1e-5
    )
    # f64 oracle (ghost mesh, no fold)
    rel = pos.astype(np.float64) * 16.0
    i0 = np.clip(np.floor(rel).astype(np.int64), 0, 15)
    frac = rel - i0
    want = np.zeros((17, 17, 17))
    for corner in itertools.product((0, 1), repeat=3):
        off = np.asarray(corner)
        w = np.prod(np.where(off == 1, frac, 1.0 - frac), axis=1)
        idx = i0 + off
        np.add.at(
            want, (idx[:, 0], idx[:, 1], idx[:, 2]),
            np.where(valid, mass.astype(np.float64) * w, 0.0),
        )
    np.testing.assert_allclose(rho_slab, want, rtol=2e-5, atol=2e-5)

    # unit mass (mass=None) drops the sort operand on the slab path too
    rho_unit = np.asarray(
        dep.cic_deposit_vranks_mxu(
            pos_rows, None, jnp.asarray(valid),
            lo_all, jnp.full(3, 16.0), vblock, vgrid_shape,
        )
    )
    np.testing.assert_allclose(rho_unit.sum(), valid.sum(), rtol=1e-5)


def test_fused_loop_slab_mxu_deposit(rng, _devices):
    """The fused vrank loop with deposit_method='mxu' routes the
    slab-keyed engine (canonical block vranks) and conserves mass; its
    density matches the double-float scan engine at f32 tolerance."""
    import jax
    from mpi_grid_redistribute_tpu.models import nbody
    from mpi_grid_redistribute_tpu.parallel import mesh as mesh_lib

    dev_grid = ProcessGrid((1, 1, 1))
    vgrid = ProcessGrid((2, 2, 2))
    domain = Domain(0.0, 1.0, periodic=True)
    n_local = 256
    R = vgrid.nranks
    mesh = mesh_lib.make_mesh(dev_grid, devices=jax.devices()[:1])
    pos = rng.random((R * n_local, 3), dtype=np.float32)
    vel = (rng.random((R * n_local, 3), dtype=np.float32) - 0.5) * 0.02
    alive = rng.random(R * n_local) > 0.2
    rhos = {}
    for method in ("mxu", "scan"):
        cfg = nbody.DriftConfig(
            domain=domain, grid=dev_grid, dt=0.01, capacity=64,
            n_local=n_local, deposit_shape=(8, 8, 8),
            deposit_method=method,
        )
        loop = nbody.make_migrate_loop(
            cfg, mesh, 3, vgrid=vgrid, deposit_each_step=True
        )
        out = jax.tree.map(np.asarray, loop(pos, vel, alive))
        rhos[method] = out[-1]
        np.testing.assert_allclose(
            out[-1].sum(), out[2].sum(), rtol=1e-4
        )
    np.testing.assert_allclose(
        rhos["mxu"], rhos["scan"], rtol=2e-4, atol=2e-4
    )


def test_slab_mxu_residence_guard_falls_back(rng, _devices):
    """Random (mis-slabbed) starts leave backlogged rows on the wrong
    slab for several steps; the slab engine's residence guard must
    lax.cond-route those steps to the position-keyed flat engine instead
    of silently clamping them into wrong cells (caught by the round-4
    verify drive: 35% of cells off before the guard)."""
    import jax
    from mpi_grid_redistribute_tpu.models import nbody
    from mpi_grid_redistribute_tpu.parallel import mesh as mesh_lib

    dev_grid = ProcessGrid((2, 1, 1))
    vgrid = ProcessGrid((2, 2, 1))
    domain = Domain(0.0, 1.0, periodic=True)
    n_local = 256
    R = dev_grid.nranks * vgrid.nranks
    mesh = mesh_lib.make_mesh(dev_grid, devices=jax.devices()[:2])
    # deliberately scattered start + tight capacity: rows stay
    # mis-slabbed (backlogged) across the 3 deposited steps
    pos = rng.random((R * n_local, 3), dtype=np.float32)
    vel = (rng.random((R * n_local, 3), dtype=np.float32) - 0.5) * 0.02
    alive = rng.random(R * n_local) > 0.2
    rhos = {}
    for method in ("mxu", "scan"):
        cfg = nbody.DriftConfig(
            domain=domain, grid=dev_grid, dt=0.01, capacity=48,
            n_local=n_local, deposit_shape=(8, 8, 8),
            deposit_method=method,
        )
        loop = nbody.make_migrate_loop(
            cfg, mesh, 3, vgrid=vgrid, deposit_each_step=True
        )
        out = jax.tree.map(np.asarray, loop(pos, vel, alive))
        rhos[method] = out[-1]
        np.testing.assert_allclose(out[-1].sum(), out[2].sum(), rtol=1e-4)
    np.testing.assert_allclose(
        rhos["mxu"], rhos["scan"], rtol=2e-4, atol=2e-4
    )


def test_slab_mxu_fast_path_engages(rng, _devices, monkeypatch):
    """On slab-resident data the builder must take the SLAB branch (and
    the flat branch on mis-slabbed data) — without this, a regression in
    the lo_all/guard logic would silently route every step to the flat
    engine and erase the slab-sort win with zero CI signal (review
    round 4). Each branch is poisoned in turn to observe which one the
    result follows."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import PartitionSpec as P
    from jax import shard_map
    from mpi_grid_redistribute_tpu.ops import deposit as dep
    from mpi_grid_redistribute_tpu.parallel import mesh as mesh_lib

    dom = Domain(0.0, 1.0, periodic=True)
    dev_grid = ProcessGrid((2, 2, 2))
    vgrid = ProcessGrid((2, 1, 1))
    mesh = mesh_lib.make_mesh(dev_grid)
    V, n = vgrid.nranks, 1500
    full = ProcessGrid(
        tuple(d * v for d, v in zip(dev_grid.shape, vgrid.shape))
    )

    def run():
        fn = dep.shard_deposit_device_mxu_fn(
            dom, dev_grid, (8, 8, 8), vgrid=vgrid
        )
        spec = P(dev_grid.axis_names)
        wrapped = jax.jit(shard_map(
            fn, mesh=mesh,
            in_specs=(P(None, dev_grid.axis_names), spec, spec),
            out_specs=dep.deposit_out_spec(dom, dev_grid),
        ))
        return np.asarray(wrapped(pos_rows, mass, valid))

    def slab_positions(legal):
        pos = np.empty((dev_grid.nranks * V * n, 3), np.float32)
        i = 0
        for d in range(dev_grid.nranks):
            dc = dev_grid.cell_of_rank(d)
            for v in range(V):
                vc = vgrid.cell_of_rank(v)
                cell = np.asarray([
                    dc[a] * vgrid.shape[a] + vc[a] for a in range(3)
                ])
                if not legal:
                    cell = (cell + 1) % np.asarray(full.shape)
                lo = cell / np.asarray(full.shape)
                pos[i : i + n] = (
                    lo + rng.random((n, 3)) / np.asarray(full.shape)
                ).astype(np.float32)
                i += n
        return pos

    orig_flat = dep.cic_deposit_device_mxu
    orig_slab = dep._slab_deposit_from_keys

    for legal in (True, False):
        pos = slab_positions(legal)
        mass = rng.uniform(0.5, 2.0, size=(pos.shape[0],)).astype(np.float32)
        valid = rng.random(pos.shape[0]) > 0.1
        pos_rows = np.ascontiguousarray(
            pos.reshape(dev_grid.nranks, V * n, 3).transpose(2, 0, 1)
        ).reshape(3, -1)

        monkeypatch.setattr(dep, "cic_deposit_device_mxu", orig_flat)
        monkeypatch.setattr(dep, "_slab_deposit_from_keys", orig_slab)
        base = run()
        monkeypatch.setattr(
            dep, "cic_deposit_device_mxu",
            lambda *a, **k: orig_flat(*a, **k) + 1000.0,
        )
        flat_poisoned = run()
        monkeypatch.setattr(dep, "cic_deposit_device_mxu", orig_flat)
        monkeypatch.setattr(
            dep, "_slab_deposit_from_keys",
            lambda *a, **k: orig_slab(*a, **k) + 1000.0,
        )
        slab_poisoned = run()
        if legal:
            # slab branch taken: poisoning flat changes nothing,
            # poisoning slab shows up
            np.testing.assert_array_equal(base, flat_poisoned)
            assert np.abs(slab_poisoned - base).max() > 100.0
        else:
            np.testing.assert_array_equal(base, slab_poisoned)
            assert np.abs(flat_poisoned - base).max() > 100.0
