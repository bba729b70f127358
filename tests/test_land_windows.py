"""The one-call landing's window copies give the compaction sort's output.

The planar vrank engine lands each vrank's rows by
copying one window a source into place (``pack.land_windows``): remote
sources from their ``C``-wide windows of the receive pool, the vrank's own
rows from the head of the sentinel segment of its destination-sorted rows.
The payload-carrying compaction sort (``pack.planar_compact_with_self``)
puts the same rows in Alltoallv receive order, so the two must agree bit
for bit on the output, the new count and the dropped count.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from mpi_grid_redistribute_tpu import Domain, GridRedistribute
from mpi_grid_redistribute_tpu.ops import binning, pack

# int32 words that a float view would flush or canonicalize: denormals,
# -0.0, quiet and signalling NaNs with payloads, the extremes
BIT_PATTERNS = np.array(
    [1, 0x007FFFFF, -0x80000000, 0x7FC00001, 0x7F800001, -0x00000001,
     0x7FFFFFFF, 0x00000000], dtype=np.int64,
).astype(np.int32)

V, K, N = 8, 5, 64


def _words(rng, shape):
    w = rng.integers(-2**31, 2**31, size=shape, dtype=np.int64)
    w = w.astype(np.int32)
    hit = rng.random(shape) < 0.25
    w[hit] = rng.choice(BIT_PATTERNS, size=int(hit.sum()))
    return w


def _case(case, rng, C):
    """``(count [V], dest [V, N], recv_counts [V, V], out_capacity)``: the
    rows each vrank holds, where each goes, what each receives from each
    source at capacity ``C``, and the output's width."""
    count = rng.integers(N // 2, N + 1, size=V)
    dest = rng.integers(0, V, size=(V, N))
    recv = rng.integers(0, C + 1, size=(V, V))
    out_capacity = V * C + N  # room for the pool and the own rows
    if case == "empty_source":  # source 3 sends nothing to anyone
        recv[:, 3] = 0
    elif case == "full_window":  # source 2 fills every window it sends
        recv[:, 2] = C
    elif case == "all_own":  # every row stays, nothing arrives
        count[:] = N
        dest[:] = np.arange(V)[:, None]
        recv[:] = 0
    elif case == "no_own":
        dest = (np.arange(V)[:, None] + rng.integers(1, V, size=(V, N))) % V
    elif case == "me_first_last":  # only vranks 0 and V-1 keep rows
        dest = (np.arange(V)[:, None] + rng.integers(1, V, size=(V, N))) % V
        dest[0, ::3] = 0
        dest[V - 1, 1::2] = V - 1
    elif case == "dropped":  # the output holds less than arrives
        out_capacity = 5 * C // 2
    elif case == "out_above_pool":  # wider than the pool and own rows
        out_capacity = V * C + N + 37
    np.fill_diagonal(recv, 0)
    return count, dest, recv, out_capacity


CASES = ["empty_source", "full_window", "all_own", "no_own",
         "me_first_last", "dropped", "out_above_pool"]


def _check(case, C):
    rng = np.random.default_rng([CASES.index(case), C])
    count, dest, recv, out_capacity = _case(case, rng, C)
    cols = jnp.asarray(_words(rng, (V, K, N)))
    # padding slots hold garbage: neither landing may read them
    pool = jnp.asarray(_words(rng, (V, K, V * C)))
    valid = np.arange(N)[None, :] < count[:, None]
    own = valid & (dest == np.arange(V)[:, None])
    dest_remote = jnp.asarray(np.where(valid & ~own, dest, V), jnp.int32)
    sorted_cols, _, bounds = jax.vmap(
        lambda d, c: binning.sort_by_dest(d, V, c)
    )(dest_remote, cols)
    self_count = jnp.asarray(own.sum(axis=1), jnp.int32)
    recv_counts = jnp.asarray(recv, jnp.int32)

    got = pack.land_windows(pool, recv_counts, sorted_cols, bounds[:, V],
                            self_count, out_capacity)
    want = jax.vmap(
        lambda p, r, me, m, f: pack.planar_compact_with_self(
            p, r, me, m, f, out_capacity)
    )(pool, recv_counts, jnp.arange(V, dtype=jnp.int32), jnp.asarray(own),
      cols)
    assert got[0].shape == (V, K, out_capacity) and got[0].dtype == jnp.int32
    for g, w in zip(got, want):
        assert np.asarray(g).tobytes() == np.asarray(w).tobytes()

    total = recv.sum(axis=1) + own.sum(axis=1)
    assert np.asarray(got[1]).tolist() == np.minimum(total,
                                                     out_capacity).tolist()
    assert np.asarray(got[2]).tolist() == np.maximum(total - out_capacity,
                                                     0).tolist()
    if case == "dropped":
        assert (np.asarray(got[2]) > 0).any()
        # a window starts at or past the output's end and is cut away
        lands = recv + np.diag(own.sum(axis=1))
        starts = np.cumsum(lands, axis=1) - lands
        assert ((starts >= out_capacity) & (lands > 0)).any()
    else:
        assert (np.asarray(got[2]) == 0).all()
    if case == "all_own":
        assert (np.asarray(got[1]) == N).all()


@pytest.mark.parametrize("case", CASES)
def test_window_landing_equals_compaction_sort(case):
    _check(case, 16)  # R * C = 2n, the default capacity's ratio


@pytest.mark.parametrize("case", CASES)
def test_window_landing_at_small_capacity(case):
    """``R * C < n``: a source's window is narrower than the own rows."""
    _check(case, 4)


@pytest.fixture
def one_device(monkeypatch):
    """8 ranks as vranks: JAX shows the instance one device."""
    devices = jax.devices()[:1]
    monkeypatch.setattr(jax, "devices", lambda *a, **k: devices)


def test_int64_ids_at_default_capacity_equal_numpy(one_device):
    """``GridRedistribute`` on 8 vranks at the default capacity returns
    the numpy backend's rows, int64 ids above 2**31 included."""
    R, n_local = 8, 256
    rng = np.random.default_rng(28)
    pos = rng.random((R * n_local, 3), dtype=np.float32)
    vel = rng.random((R * n_local, 3), dtype=np.float32) - np.float32(0.5)
    ids = rng.choice(8192**3, size=R * n_local, replace=False)
    ids = ids.astype(np.int64)
    dom = Domain(0.0, 1.0, periodic=True)
    kw = dict(out_capacity=320)
    ref = GridRedistribute(dom, (2, 2, 2), backend="numpy",
                           **kw).redistribute(pos, vel, ids)
    rd = GridRedistribute(dom, (2, 2, 2), **kw)
    res = rd.redistribute(pos, vel, ids)
    assert np.asarray(res.count).tobytes() == np.asarray(ref.count).tobytes()
    assert (np.asarray(res.positions).tobytes()
            == np.asarray(ref.positions).tobytes())
    assert res.host_field(0).tobytes() == np.asarray(ref.fields[0]).tobytes()
    assert res.host_field(1).tobytes() == np.asarray(ref.fields[1]).tobytes()
    assert (res.host_field(1) >= 2**31).any()
