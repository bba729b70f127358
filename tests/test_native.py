"""C++ host runtime (native/) vs the NumPy reference — bit-level equality
and fallback behavior (SURVEY.md §2 native components)."""

import numpy as np
import pytest

from mpi_grid_redistribute_tpu.domain import Domain, ProcessGrid
from mpi_grid_redistribute_tpu.ops import binning
from mpi_grid_redistribute_tpu.utils import native


pytestmark = pytest.mark.skipif(
    not native.build(),  # explicit opt-in build (advisor: no implicit g++)
    reason="native library not built (no g++?)",
)


@pytest.mark.parametrize(
    "dom,gshape",
    [
        (Domain(0.0, 1.0, periodic=True), (4, 4, 4)),
        (
            Domain((-1.0, 0.0, 2.5), (1.0, 0.3, 7.1),
                   periodic=(True, False, True)),
            (3, 5, 2),
        ),
        (Domain(0.0, 1.0, ndim=2, periodic=False), (8, 8)),
    ],
)
def test_bin_bit_identical(dom, gshape, rng):
    grid = ProcessGrid(gshape)
    pos = (rng.standard_normal((100000, dom.ndim)) * 2).astype(np.float32)
    pos[:10] = 0.0
    pos[10:20] = 1.0
    pos[20:30] = -1e-8
    want = binning.rank_of_position(pos, dom, grid, xp=np)
    got = native.bin_positions(pos, dom, grid)
    np.testing.assert_array_equal(want, got)


def test_count_sort_matches_stable_argsort(rng):
    dest = rng.integers(0, 9, size=50000).astype(np.int32)  # 8 + sentinel
    counts, order = native.count_sort(dest, 8)
    np.testing.assert_array_equal(
        counts, np.bincount(dest, minlength=9)[:8]
    )
    np.testing.assert_array_equal(order, np.argsort(dest, kind="stable"))


def test_gather_rows(rng):
    src = rng.random((1000, 5)).astype(np.float32)
    order = rng.permutation(1000).astype(np.int64)[:300]
    np.testing.assert_array_equal(native.gather_rows(src, order), src[order])
    ids = rng.integers(0, 1 << 40, size=1000)  # int64 rows
    np.testing.assert_array_equal(native.gather_rows(ids, order), ids[order])


def test_oracle_uses_native_and_matches_jax(rng, _devices):
    """End-to-end: the native-accelerated oracle still bit-matches JAX."""
    import mpi_grid_redistribute_tpu as gr

    n_local = 256
    pos = rng.random((8 * n_local, 3), dtype=np.float32)
    kw = dict(grid=(2, 2, 2), lo=0.0, hi=1.0, periodic=True,
              capacity_factor=8.0)
    res = gr.GridRedistribute(backend="jax", **kw).redistribute(pos)
    res_np = gr.GridRedistribute(backend="numpy", **kw).redistribute(pos)
    assert np.asarray(res.positions).tobytes() == res_np.positions.tobytes()
    assert np.asarray(res.count).tobytes() == res_np.count.tobytes()


def test_only_a_library_built_from_the_committed_files_loads(monkeypatch):
    # the module-level build() stamped the .so with the sources' hash
    assert native._built_from_sources()
    # an edited source or build flag makes the same .so stale: it is
    # neither loaded nor trusted until build() rebuilds it
    monkeypatch.setattr(native, "_source_hash", lambda: "0" * 64)
    assert not native._built_from_sources()
    monkeypatch.setattr(native, "_tried", False)
    monkeypatch.setattr(native, "_lib", None)
    monkeypatch.delenv("MPI_GRID_NATIVE_BUILD", raising=False)
    assert native._load() is None
