"""The one-call plan's window pack gives the gather pack's send pool, bit
for bit.

The planar vrank engine carries each vrank's rows through its destination
sort (``binning.sort_by_dest``) and copies each destination's slots as one
window of the sorted rows (``pack.pack_windows``); the other planar
engines sort the key alone and gather (``binning.sorted_dest_counts`` +
``pack.pack_cols``). Slot ``(d, c)`` takes column ``order[bounds[d] + c]``
either way, so the pools, counts and bounds must match exactly, and the
whole call must match the numpy backend at every capacity.
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


def _payload(rng, K, n):
    cols = rng.integers(-2**31, 2**31, size=(K, n), dtype=np.int64)
    cols = cols.astype(np.int32)
    cols[:, rng.random(n) < 0.25] = rng.choice(BIT_PATTERNS, size=K)[:, None]
    return cols


def _dest(case, rng, n, V, C):
    """Destinations of one source; sentinel ``V`` marks rows not sent."""
    if case == "sentinels":  # rows past the count and self rows
        d = rng.integers(0, V + 1, size=n)
        d[n - n // 5:] = V
    elif case == "empty_segments":  # destinations 1, 2 and 5 get nothing
        d = rng.choice(np.array([0, 3, 4, 6, 7, V]), size=n)
    elif case == "overflow":  # destination 2 holds more than C rows
        d = np.where(rng.random(n) < 0.75, 2, rng.integers(0, V, size=n))
    elif case == "clamp":  # a last segment shorter than C ends at column n
        d = rng.integers(0, V - 1, size=n)
        d[rng.permutation(n)[: C // 2 + 1]] = V - 1
    return jnp.asarray(d.astype(np.int32))


CASES = ["sentinels", "empty_segments", "overflow", "clamp"]


@pytest.mark.parametrize("capacity", [16, 48, 200])
@pytest.mark.parametrize("case", CASES)
def test_window_pack_equals_gather_pack(case, capacity):
    V, K, n = 8, 5, 300
    rng = np.random.default_rng([CASES.index(case), capacity])
    dest = _dest(case, rng, n, V, capacity)
    cols = jnp.asarray(_payload(rng, K, n))
    order, counts, bounds = binning.sorted_dest_counts(dest, V)
    sorted_cols, counts_s, bounds_s = binning.sort_by_dest(dest, V, cols)
    assert np.asarray(counts_s).tobytes() == np.asarray(counts).tobytes()
    assert np.asarray(bounds_s).tobytes() == np.asarray(bounds).tobytes()
    assert (np.asarray(sorted_cols).tobytes()
            == np.asarray(cols)[:, np.asarray(order)].tobytes())
    send_counts = jnp.minimum(counts, capacity)
    want, _ = pack.pack_cols(cols, order, bounds[:V], send_counts, V,
                             capacity)
    got = pack.pack_windows(sorted_cols, bounds[:V], send_counts, capacity)
    assert got.shape == (K, V * capacity) and got.dtype == jnp.int32
    assert np.asarray(got).tobytes() == np.asarray(want).tobytes()
    if case == "clamp":
        # the last window starts within C of the end: unpadded, a
        # dynamic_slice would clamp it and shift the segment
        assert int(bounds[V - 1]) + capacity > n
    if case == "overflow":
        assert int(counts[2]) > capacity


def test_sort_by_dest_plain_key_equals_sorted_dest_counts():
    """Destinations too many to pack beside the row index in one word:
    both take the plain stable key sort and agree."""
    n, V, K = (1 << 16) + 1, 1 << 14, 2
    rng = np.random.default_rng(3)
    dest = jnp.asarray(rng.integers(0, V + 1, size=n).astype(np.int32))
    assert binning.dest_sort_key(dest, V)[1] is None
    cols = jnp.asarray(_payload(rng, K, n))
    order, counts, bounds = binning.sorted_dest_counts(dest, V)
    sorted_cols, counts_s, bounds_s = binning.sort_by_dest(dest, V, cols)
    assert np.array_equal(np.asarray(counts_s), np.asarray(counts))
    assert np.array_equal(np.asarray(bounds_s), np.asarray(bounds))
    assert (np.asarray(sorted_cols).tobytes()
            == np.asarray(cols)[:, np.asarray(order)].tobytes())
    send_counts = jnp.minimum(counts, 2)
    want, _ = pack.pack_cols(cols, order, bounds[:V], send_counts, V, 2)
    got = pack.pack_windows(sorted_cols, bounds[:V], send_counts, 2)
    assert np.asarray(got).tobytes() == np.asarray(want).tobytes()


# ------------------------------------------------------------ the call

DOMAIN = Domain(0.0, 1.0, periodic=True)
GRID = (2, 2, 2)


@pytest.fixture
def one_device(monkeypatch):
    """8 ranks as vranks: JAX shows the instance one device."""
    devices = jax.devices()[:1]
    monkeypatch.setattr(jax, "devices", lambda *a, **k: devices)


def _rows(seed, n_local, stay, R=8):
    """``stay`` of each rank's rows inside its own cell, the rest uniform;
    int64 ids above 2**31 and int32 words with float-hostile bits."""
    rng = np.random.default_rng(seed)
    probe = GridRedistribute(DOMAIN, GRID, backend="numpy")
    pos = rng.random((R * n_local, 3), dtype=np.float32)
    for coords in np.ndindex(*GRID):
        r = probe.grid.rank_of_cell(coords)
        rows = slice(r * n_local, r * n_local + int(stay * n_local))
        pos[rows] = (np.asarray(coords, np.float32) + pos[rows]) / 2.0
    ids = rng.choice(8192**3, size=R * n_local, replace=False)
    words = _payload(rng, 2, R * n_local).T.copy()
    return pos, ids.astype(np.int64), words


def _assert_same(res, ref):
    assert np.asarray(res.count).tobytes() == np.asarray(ref.count).tobytes()
    assert (np.asarray(res.positions).tobytes()
            == np.asarray(ref.positions).tobytes())
    for i, want in enumerate(ref.fields):
        assert res.host_field(i).tobytes() == want.tobytes()
    for name in ("send_counts", "recv_counts", "dropped_send",
                 "dropped_recv", "needed_capacity"):
        assert (np.asarray(getattr(res.stats, name)).tobytes()
                == np.asarray(getattr(ref.stats, name)).tobytes()), name


@pytest.mark.parametrize("label, kw, stay", [
    # the default capacity: R * C = 2n
    ("default", dict(out_capacity=320), 0.0),
    # few movers at a capacity with R * C = n / 2
    ("small_capacity", dict(capacity=16, out_capacity=320), 0.9),
    # every row to rank 0, R * C = 2n: the pack overflows C
    ("overflow_wide", dict(capacity=64, out_capacity=320,
                            on_overflow="ignore"), "rank0"),
    # uniform rows, R * C = n / 4: the pack overflows C
    ("overflow_small_capacity", dict(capacity=8, out_capacity=320,
                                     on_overflow="ignore"), 0.0),
])
def test_auto_vranks_equals_numpy_on_both_pack_paths(one_device, label, kw,
                                                     stay):
    """The window pack and landing at capacities on either side of
    ``R * C = n`` equal the numpy backend."""
    n_local = 256
    pos, ids, words = _rows(11, n_local, 0.0 if stay == "rank0" else stay)
    if stay == "rank0":
        pos = pos / 2.0  # every row in cell (0, 0, 0)
    ref = GridRedistribute(DOMAIN, GRID, backend="numpy",
                           **kw).redistribute(pos, ids, words)
    rd = GridRedistribute(DOMAIN, GRID, engine="auto", **kw)
    res = rd.redistribute(pos, ids, words)
    assert rd._vranks
    _assert_same(res, ref)
    dropped = int(np.asarray(res.stats.dropped_send).sum())
    assert (dropped > 0) == label.startswith("overflow")
