"""Multi-host surface (VERDICT round-1 item 10): construction-level tests
for make_hybrid_mesh and initialize_distributed on the virtual CPU mesh.

Real DCN/multi-slice hardware is not reachable here; these tests pin down
what can be pinned: hybrid meshes build, validate, and run the exchange on
8 virtual devices, and the distributed bring-up passthrough initializes a
single-process "cluster" in a subprocess.
"""

import os
import subprocess
import sys

import numpy as np
import pytest

from mpi_grid_redistribute_tpu.domain import Domain, ProcessGrid
from mpi_grid_redistribute_tpu.parallel import mesh as mesh_lib


def test_hybrid_mesh_all_ones_reduces_to_plain(_devices):
    grid = ProcessGrid((2, 2, 2))
    mesh = mesh_lib.make_hybrid_mesh(grid)
    mesh_lib.validate_mesh_for_grid(mesh, grid)
    assert tuple(mesh.devices.shape) == (2, 2, 2)


def test_hybrid_mesh_dcn_split(_devices):
    # dcn_shape=(2,1,1): axis x spans 2 "slices" of 4 devices each. On the
    # virtual CPU platform every device reports the same process/slice, so
    # mesh_utils may either build the hybrid layout or reject it — both
    # are valid constructions to pin; what must hold is: a returned mesh
    # has the right shape and axis names and passes validation.
    grid = ProcessGrid((2, 2, 2))
    try:
        mesh = mesh_lib.make_hybrid_mesh(grid, dcn_shape=(2, 1, 1))
    except (ValueError, AssertionError) as e:
        pytest.skip(f"hybrid layout rejected on virtual devices: {e}")
    mesh_lib.validate_mesh_for_grid(mesh, grid)
    assert tuple(mesh.devices.shape) == (2, 2, 2)


def test_hybrid_mesh_rejects_indivisible():
    grid = ProcessGrid((2, 2, 2))
    with pytest.raises(ValueError, match="not divisible"):
        mesh_lib.make_hybrid_mesh(grid, dcn_shape=(3, 1, 1))
    with pytest.raises(ValueError, match="axes"):
        mesh_lib.make_hybrid_mesh(grid, dcn_shape=(2, 1))


def test_exchange_runs_on_hybrid_mesh(rng, _devices):
    from mpi_grid_redistribute_tpu import GridRedistribute

    grid = ProcessGrid((2, 2, 2))
    mesh = mesh_lib.make_hybrid_mesh(grid)
    rd = GridRedistribute(
        Domain(0.0, 1.0), (2, 2, 2), mesh=mesh, capacity_factor=3.0
    )
    pos = rng.random((8 * 64, 3)).astype(np.float32)
    res = rd.redistribute(pos)
    assert int(np.asarray(res.count).sum()) == 8 * 64


def test_initialize_distributed_single_process():
    # jax.distributed.initialize mutates global state; exercise it in a
    # subprocess so the test session's backend stays untouched.
    code = (
        "import os;"
        "os.environ['XLA_FLAGS']=os.environ.get('XLA_FLAGS','')"
        "+' --xla_force_host_platform_device_count=8';"
        "import jax;"
        "from mpi_grid_redistribute_tpu.parallel import mesh as m;"
        "m.initialize_distributed(coordinator_address='localhost:12399',"
        "num_processes=1, process_id=0);"
        "assert jax.process_count() == 1;"
        "from mpi_grid_redistribute_tpu.domain import ProcessGrid;"
        "mesh = m.make_mesh(ProcessGrid((2, 2, 2)));"
        "print('distributed-init-ok', len(mesh.devices.ravel()))"
    )
    env = dict(os.environ)
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    env["PYTHONPATH"] = root + os.pathsep + env.get("PYTHONPATH", "")
    out = subprocess.run(
        [sys.executable, "-c", code], capture_output=True, text=True,
        timeout=300, env=env,
    )
    assert out.returncode == 0, out.stderr[-2000:]
    assert "distributed-init-ok 8" in out.stdout


# ----------------------------------------------- elastic shrink (ISSUE 8)


def test_shrink_shape_halves_largest_axis():
    assert mesh_lib.shrink_shape((2, 2, 2)) == (1, 2, 2)  # tie: lowest axis
    assert mesh_lib.shrink_shape((1, 2, 2)) == (1, 1, 2)
    assert mesh_lib.shrink_shape((1, 1, 2)) == (1, 1, 1)
    assert mesh_lib.shrink_shape((2, 4, 2)) == (2, 2, 2)
    assert mesh_lib.shrink_shape((1, 8)) == (1, 4)
    # the floor: an all-ones grid cannot shrink and is returned unchanged
    assert mesh_lib.shrink_shape((1, 1, 1)) == (1, 1, 1)


def test_shrink_to_fit_walks_the_shrink_ladder():
    assert mesh_lib.shrink_to_fit((2, 2, 2), 8) == (2, 2, 2)  # already fits
    assert mesh_lib.shrink_to_fit((2, 2, 2), 4) == (1, 2, 2)
    assert mesh_lib.shrink_to_fit((2, 2, 2), 3) == (1, 1, 2)
    assert mesh_lib.shrink_to_fit((2, 2, 2), 1) == (1, 1, 1)
    assert mesh_lib.shrink_to_fit((4, 4), 5) == (2, 2)
    with pytest.raises(ValueError, match="cannot fit"):
        mesh_lib.shrink_to_fit((2, 2, 2), 0)


# ----------------------------------------------- HierarchicalMesh (ISSUE 19)


def test_make_hybrid_mesh_dcn_shape_defaults_to_none():
    # the published signature: dcn_shape is optional and None means
    # "flat" — callers must not need to spell out the all-ones tuple
    import inspect

    sig = inspect.signature(mesh_lib.make_hybrid_mesh)
    param = sig.parameters["dcn_shape"]
    assert param.default is None


@pytest.mark.parametrize(
    "dcn,msg",
    [
        ((2, 1), "must have 3 axes"),
        ((0, 1, 1), ">= 1"),
        ((3, 1, 1), "not divisible"),
    ],
    ids=["rank-mismatch", "nonpositive", "indivisible"],
)
def test_hierarchical_mesh_validates_dcn_shape(dcn, msg):
    grid = ProcessGrid((2, 2, 2))
    with pytest.raises(ValueError, match=msg):
        mesh_lib.HierarchicalMesh(grid, dcn)


def test_hierarchical_mesh_all_ones_is_flat():
    grid = ProcessGrid((2, 2, 2))
    hm = mesh_lib.HierarchicalMesh(grid, (1, 1, 1))
    assert hm.n_pods == 1
    assert hm.pod_size == grid.nranks
    assert hm.dcn_axes == ()
    assert hm.axis_names == grid.axis_names
    assert hm.local_grid.shape == grid.shape
    assert np.array_equal(hm.pod_of, np.zeros(8, np.int32))
    assert np.array_equal(hm.local_of, np.arange(8, dtype=np.int32))


def test_hierarchical_mesh_tables_2pods():
    grid = ProcessGrid((2, 2, 2))
    hm = mesh_lib.HierarchicalMesh(grid, (2, 1, 1))
    assert hm.n_pods == 2
    assert hm.pod_size == 4
    assert hm.ici_shape == (1, 2, 2)
    # interleaved expansion: the split axis becomes (dcn_x, x)
    assert hm.axis_names == ("dcn_x", "x", "y", "z")
    assert hm.axis_sizes == (2, 1, 2, 2)
    assert hm.dcn_axes == ("dcn_x",)
    assert hm.ici_axes == grid.axis_names
    # row-major flat index over the expanded axes IS the grid rank —
    # the bit-identity invariant the whole engine rests on
    ranks = np.arange(grid.nranks).reshape(grid.shape)
    assert np.array_equal(
        ranks.reshape(hm.axis_sizes).reshape(-1),
        np.arange(grid.nranks),
    )
    # pod/local tables are mutually consistent with the rank table
    for r in range(grid.nranks):
        assert hm.rank_table[hm.pod_of[r], hm.local_of[r]] == r
    # each pod's ranks are strictly ascending (deterministic routing)
    assert (np.diff(hm.rank_table, axis=1) > 0).all()
    # periodicity only survives on axes a pod spans fully
    assert hm.local_periodic((True, True, True)) == (False, True, True)
    assert hm.local_periodic((False, True, False)) == (
        False, True, False
    )


def test_hierarchical_mesh_build_mesh_expanded_axes(_devices):
    import jax

    grid = ProcessGrid((2, 2, 2))
    hm = mesh_lib.HierarchicalMesh(grid, (2, 1, 1))
    emesh = hm.build_mesh(list(jax.devices()[:8]))
    assert emesh.axis_names == ("dcn_x", "x", "y", "z")
    assert tuple(emesh.devices.shape) == (2, 1, 2, 2)
    with pytest.raises(ValueError, match="needs 8 devices"):
        hm.build_mesh(list(jax.devices()[:4]))
