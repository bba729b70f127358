"""Fields wider than 32 bits through ``GridRedistribute``: carried as
their 32-bit words, never narrowed.

A snapshot of an 8192^3 run has ids up to 8192**3 > 2**31, so the ids are
``int64``. The jax backend carries such a field as ``int32 [..., 2]``
words (low word first) through every planar engine; the numpy backend
keeps every field's dtype. Both must give the same rows, bit for bit and
in Alltoallv receive order, with every id exact.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from mpi_grid_redistribute_tpu import Domain, GridRedistribute, api
from mpi_grid_redistribute_tpu.service import pipeline
from mpi_grid_redistribute_tpu.telemetry import report as report_lib

DOMAIN = Domain(0.0, 1.0, periodic=True)
GRID = (2, 2, 2)
ID_RANGE = 8192**3


def _snapshot(seed, n_local=256, R=8):
    """Uniform rows in random rank order (about 7/8 change rank), int64
    ids from [0, 8192**3) and a float64 column."""
    rng = np.random.default_rng(seed)
    n = R * n_local
    pos = rng.random((n, 3), dtype=np.float32)
    vel = rng.standard_normal((n, 3)).astype(np.float32)
    ids = rng.choice(ID_RANGE, size=n, replace=False).astype(np.int64)
    mass = rng.random(n)
    return pos, vel, ids, mass


@pytest.fixture
def one_device(monkeypatch):
    """8 ranks as vranks: JAX shows the instance one device."""
    devices = jax.devices()[:1]
    monkeypatch.setattr(jax, "devices", lambda *a, **k: devices)


def _assert_same(res, ref):
    assert np.asarray(res.count).tobytes() == np.asarray(ref.count).tobytes()
    assert (np.asarray(res.positions).tobytes()
            == np.asarray(ref.positions).tobytes())
    assert res.field_dtypes == ref.field_dtypes
    for i, want in enumerate(ref.fields):
        got = res.host_field(i)
        assert got.dtype == want.dtype
        assert got.tobytes() == want.tobytes()


@pytest.mark.parametrize("engine, layout, with_f64", [
    ("planar", "vranks", True),
    ("sparse", "vranks", False),
    ("neighbor", "vranks", False),
    ("auto", "vranks", False),
    ("planar", "mesh", False),
    ("auto", "mesh", True),
])
def test_int64_ids_bit_exact_against_numpy(request, engine, layout,
                                           with_f64):
    if layout == "vranks":
        request.getfixturevalue("one_device")
    pos, vel, ids, mass = _snapshot(7)
    fields = (vel, ids, mass) if with_f64 else (vel, ids)
    kw = dict(out_capacity=512, capacity=256)
    ref = GridRedistribute(DOMAIN, GRID, backend="numpy",
                           **kw).redistribute(pos, *fields)
    assert ref.fields[1].dtype == np.int64
    rd = GridRedistribute(DOMAIN, GRID, engine=engine, **kw)
    res = rd.redistribute(pos, *fields)
    assert rd._vranks == (layout == "vranks")
    # the ids ride as words and nearly all of them exceed 2**31
    assert res.fields[1].dtype == np.int32 and res.fields[1].shape[-1] == 2
    first = ref.fields[1][: int(np.asarray(ref.count)[0])]
    assert (first >= 2**31).mean() > 0.9
    _assert_same(res, ref)
    assert int(np.asarray(res.stats.dropped_send).sum()) == 0
    assert int(np.asarray(res.stats.dropped_recv).sum()) == 0


@pytest.mark.parametrize("layout, want", [("vranks", "planar"),
                                          ("mesh", "sparse")])
def test_auto_resolves_a_planar_engine_and_counts_words(request, layout,
                                                        want):
    if layout == "vranks":
        request.getfixturevalue("one_device")
    pos, vel, ids, _ = _snapshot(3, n_local=64)
    rd = GridRedistribute(DOMAIN, GRID, out_capacity=256)
    rd.redistribute(pos, vel, ids)
    ev = [e.data for e in rd.telemetry.events()
          if e.kind == "engine_resolved"]
    assert len(ev) == 1 and ev[0]["resolved"] == want
    # position 3 + velocity 3 + id 2 words; the id's two are 64-bit
    assert ev[0]["payload_words"] == 8 and ev[0]["payload_words_64"] == 2
    rep = rd.report()
    assert rep["engine"] == want
    assert rep["payload_words"] == 8 and rep["payload_words_64"] == 2


def test_row_bytes_bill_an_int64_id_at_8_bytes():
    pos, vel, ids, mass = _snapshot(5, n_local=8)
    assert report_lib.row_bytes_of(pos, vel, ids) == 32
    assert report_lib.row_bytes_of(pos, vel, ids, mass) == 40
    assert report_lib.row_bytes_of(pos, vel, api.host_words(ids)) == 32


@pytest.mark.parametrize("dtype", ["int64", "uint64", "float64",
                                   "complex64", "complex128"])
def test_numpy_backend_keeps_every_field_dtype(dtype):
    pos, _, ids, mass = _snapshot(11, n_local=32)
    col = (ids if dtype in ("int64", "uint64") else mass).astype(dtype)
    small = ids.astype(np.int16)
    kw = dict(out_capacity=128, backend="numpy")
    res = GridRedistribute(DOMAIN, GRID, **kw).redistribute(pos, col, small)
    assert [f.dtype for f in res.fields] == [np.dtype(dtype), np.int16]
    assert res.field_dtypes == (dtype, "int16")
    # the rows are the input's, moved: the column's values as a multiset
    n = np.asarray(res.count)
    live = np.concatenate([res.fields[0][r * 128: r * 128 + n[r]]
                           for r in range(8)])
    assert np.array_equal(np.sort(live), np.sort(col))


@pytest.mark.parametrize("dtype", ["int64", "uint64", "float64",
                                   "complex128"])
def test_host_words_round_trip(dtype):
    rng = np.random.default_rng(2)
    a = (rng.integers(-2**62, 2**62, size=(17, 2)).view(np.float64)
         .astype(dtype) if dtype == "complex128"
         else rng.integers(-2**62, 2**62, size=(17, 2)).view(dtype))
    w = api.host_words(a)
    assert w.dtype == np.int32
    assert w.shape == a.shape + (np.dtype(dtype).itemsize // 4,)
    assert api.join_words(w, dtype).tobytes() == a.tobytes()
    # int64: low word first
    if dtype == "int64":
        one = api.host_words(np.array([(7 << 32) | 5], np.int64))
        assert one.tolist() == [[5, 7]]
    with pytest.raises(ValueError, match="words"):
        api.join_words(w[..., :1], dtype)
    # narrower host arrays and device arrays pass unchanged
    small = np.arange(4, dtype=np.int16)
    assert api.host_words(small) is small


def test_device_8_byte_fields_split_in_program_under_x64(one_device):
    pos, vel, ids, mass = _snapshot(13, n_local=128)
    kw = dict(out_capacity=256, capacity=128)
    with jax.enable_x64(True):
        ref = GridRedistribute(DOMAIN, GRID, backend="numpy",
                               **kw).redistribute(pos, vel, ids, mass)
        rd = GridRedistribute(DOMAIN, GRID, engine="planar", **kw)
        res = rd.redistribute(jnp.asarray(pos), jnp.asarray(vel),
                              jnp.asarray(ids), jnp.asarray(mass))
        # device arrays of 8-byte dtype come back in their own dtype
        assert res.fields[1].dtype == jnp.int64
        assert res.fields[2].dtype == jnp.float64
        _assert_same(res, ref)


def test_narrow_fields_keep_the_rowmajor_fallback_and_it_carries_words(
        one_device):
    pos, vel, ids, _ = _snapshot(17, n_local=64)
    tag = (np.arange(len(pos)) % 7).astype(np.int16)
    kw = dict(out_capacity=256)
    ref = GridRedistribute(DOMAIN, GRID, backend="numpy",
                           **kw).redistribute(pos, ids, tag)
    rd = GridRedistribute(DOMAIN, GRID, **kw)
    res = rd.redistribute(pos, ids, tag)
    ev = [e.data for e in rd.telemetry.events()
          if e.kind == "engine_resolved"]
    assert ev[-1]["resolved"] == "rowmajor"
    assert "field 1 is int16" in ev[-1]["reason"]
    _assert_same(res, ref)
    with pytest.raises(TypeError, match="field 1 is int16"):
        GridRedistribute(DOMAIN, GRID, engine="planar",
                         **kw).redistribute(pos, ids, tag)


def test_explicit_rowmajor_carries_int64_ids(one_device):
    pos, vel, ids, _ = _snapshot(19, n_local=64)
    kw = dict(out_capacity=256)
    ref = GridRedistribute(DOMAIN, GRID, backend="numpy",
                           **kw).redistribute(pos, vel, ids)
    res = GridRedistribute(DOMAIN, GRID, engine="rowmajor",
                           **kw).redistribute(pos, vel, ids)
    _assert_same(res, ref)


@pytest.mark.parametrize("layout", ["vranks", "mesh"])
def test_halo_carries_int64_ids(request, layout):
    if layout == "vranks":
        request.getfixturevalue("one_device")
    pos, _, ids, _ = _snapshot(23, n_local=128)
    rd = GridRedistribute(DOMAIN, GRID, out_capacity=256)
    res = rd.redistribute(pos, ids, np.arange(len(pos), dtype=np.int32))
    width = 0.1
    h = rd.halo(res.positions, res.fields[0], res.fields[1], width=width,
                count=res.count)
    h64 = rd.halo(res.positions, res.host_field(0), res.fields[1],
                  width=width, count=res.count)
    n = int(np.asarray(h.ghost_count).sum())
    assert n > 0
    # the same ghosts whether the id went in as words or as int64, and
    # each ghost's id is that of the row its index names
    assert (np.asarray(h64.ghost_positions).tobytes()
            == np.asarray(h.ghost_positions).tobytes())
    words = np.asarray(h64.ghost_fields[0])
    assert words.dtype == np.int32 and words.shape[-1] == 2
    ghost_ids = api.join_words(words, np.int64)
    assert ghost_ids.tobytes() == api.join_words(
        np.asarray(h.ghost_fields[0]), np.int64).tobytes()
    idx = np.asarray(h64.ghost_fields[1])
    valid = np.concatenate([
        np.arange(r * h64.ghost_positions.shape[0] // 8,
                  r * h64.ghost_positions.shape[0] // 8 + c)
        for r, c in enumerate(np.asarray(h64.ghost_count))])
    assert np.array_equal(ghost_ids[valid], ids[idx[valid]])


def test_pipelined_service_path_refuses_a_64_bit_field():
    grid = (2, 2, 4)
    rd = GridRedistribute(DOMAIN, grid, engine="auto")
    n = 16 * 64
    rng = np.random.default_rng(29)
    pos = jnp.asarray(rng.random((n, 3), dtype=np.float32))
    vel = jnp.zeros((n, 3), jnp.float32)
    ids = rng.choice(ID_RANGE, size=n, replace=False).astype(np.int64)
    with pytest.raises(TypeError, match="field 1 is int64"):
        pipeline.make_pipelined_chunk_fn(rd, 0.05, 4, pos, vel, ids)
