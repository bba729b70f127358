"""AOT compiles of the main path's Pallas kernels at real widths, for a
described TPU v5e chip (no chip needed: the TPU compiler is installed).

Interpret mode cannot show what only Mosaic refuses (unaligned slices,
too much VMEM); these compiles can, at no chip time. Each test asserts
that the compiled program holds exactly one ``tpu_custom_call``: a
kernel whose shape contract failed would fall back to XLA and hold none.

Shapes:

* driftbin -- the ``drift8v.steady`` benchmark cell: V=8 vranks of
  n=2**20 rows, K=7 planar int32 rows;
* overlay (int8) -- the headline landing width m = 8 * 2**20 columns,
  block width W=4096, K=7, with 4,096 updates. The headline's 196,608
  updates compile in ~100 s, all of it in XLA's 8-operand payload sort
  around the kernel, not in the kernel (0.3 s alone at that m); its
  compile time grows with the update count, not with m;
* segdep and dfscan -- BASELINE.json config 5 (a CIC deposit fused
  with the drift loop): 8.4M rows, 2x2x2 vranks, a 128**3 mesh (64**3
  cells per vrank), tile 256.

One whole program is compiled too, at a small width: the one-call
planar vrank program, whose landing must reach the chip as window
copies and never as a sort or a scatter, and whose fuse must keep
fusions of its own.

The topology is described inside a fixture, never at import, so every
xdist worker collects the same tests and only the one that runs them
loads the TPU library. The persistent compile cache is off around them:
an entry written for a described chip cannot be read back without one.
"""

import os

import pytest

import jax
import jax.numpy as jnp


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies

    try:
        return topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2"
        )
    except Exception as e:  # noqa: BLE001 - any failure means no TPU here
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    from jax.sharding import SingleDeviceSharding

    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module", autouse=True)
def _no_persistent_cache():
    from jax.experimental.compilation_cache import compilation_cache

    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


def _kernels(fn, *args) -> int:
    text = jax.jit(fn).lower(*args).compile().as_text()
    return text.count('custom_call_target="tpu_custom_call"')


def _spec(sharding, shape, dtype):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def test_driftbin_headline(one_chip):
    from mpi_grid_redistribute_tpu.domain import Domain, ProcessGrid
    from mpi_grid_redistribute_tpu.ops import pallas_driftbin

    V, n, K = 8, 2**20, 7
    domain = Domain(0.0, 1.0, periodic=True)
    grid = ProcessGrid((2, 2, 2))

    def step(flat):
        return pallas_driftbin.drift_wrap_bin(flat, 1.0, domain, grid, V, V)

    flat = _spec(one_chip, (K, V * n), jnp.int32)
    assert _kernels(step, flat) == 1


def test_overlay_int8_headline_width(one_chip):
    from mpi_grid_redistribute_tpu.ops import pallas_overlay

    K, m, p = 7, 8 * 2**20, 4096

    def land(flat, targets, cols):
        return pallas_overlay.overlay_scatter_planar(
            flat, targets, cols, encoding="int8"
        )

    args = (
        _spec(one_chip, (K, m), jnp.int32),
        _spec(one_chip, (p,), jnp.int32),
        _spec(one_chip, (K, p), jnp.int32),
    )
    assert _kernels(land, *args) == 1


def test_segdep_config5(one_chip):
    from mpi_grid_redistribute_tpu.ops import pallas_segdep

    N, V, vblock = 8 * 2**20, 8, (64, 64, 64)
    n_cells = V * 64**3

    def deposit(keys, rel):
        return pallas_segdep._segsum_tpu(keys, rel, None, n_cells, vblock, 3)

    args = (
        _spec(one_chip, (N,), jnp.int32),
        _spec(one_chip, (3, N), jnp.float32),
    )
    assert _kernels(deposit, *args) == 1


def test_dfscan_config5(one_chip):
    from mpi_grid_redistribute_tpu.ops import pallas_dfscan

    tile, rows = 256, 8 * (8 * 2**20 // 256)  # 8 channels of 8.4M rows
    x = _spec(one_chip, (rows, tile), jnp.float32)
    assert _kernels(pallas_dfscan.tile_df_cumsum_rows, x) == 1


@pytest.fixture(scope="module")
def one_call_ops(one_chip):
    """``(opcode, op_name)`` of every instruction in the one-call program
    ``GridRedistribute.redistribute`` runs on 8 vranks, compiled for the
    chip at the default capacity, fused computations' included."""
    import re

    from mpi_grid_redistribute_tpu import api
    from mpi_grid_redistribute_tpu.domain import Domain, ProcessGrid

    R, n_local = 8, 2**10
    rows = _spec(one_chip, (R * n_local, 3), jnp.float32)
    words = _spec(one_chip, (R * n_local, 2), jnp.int32)
    count = _spec(one_chip, (R,), jnp.int32)
    specs = api._planar_specs(rows, (rows, words))
    fn = api._build_planar_vranks_call(
        Domain(0.0, 1.0, periodic=True), ProcessGrid((2, 2, 2)),
        2 * n_local // R, n_local * 9 // 8, specs,
    )
    text = fn.lower(rows, count, rows, words).compile().as_text()
    ops = []
    for line in text.splitlines():
        m = re.search(r"= .*? ([a-z\-]+)\(", line)
        name = re.search(r'op_name="([^"]*)"', line)
        if m and name:
            ops.append((m.group(1), name.group(1)))
    return ops


def test_one_call_landing_copies_windows(one_call_ops):
    """The landing (``rd:unpack``) holds the window copies and no sort or
    scatter."""
    ops = {op for op, name in one_call_ops if "rd:unpack" in name}
    assert "dynamic-update-slice" in ops
    assert not {"sort", "scatter"} & ops


def test_one_call_fuse_keeps_fusions_of_its_own(one_call_ops):
    """The chip keeps the fuse of the caller's arrays as fusions rooted
    under ``rd:fuse``, so ``fuse_ms_per_call`` reads it (the CPU compiler
    folds it into the destination sort's operand reads)."""
    assert any(op == "fusion" and "rd:fuse" in name.split("/")
               for op, name in one_call_ops)
