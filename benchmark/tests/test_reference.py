import numpy as np
import pytest

from benchmark import generators, reference

GEOM = reference.Geometry(lo=(0.0,) * 3, hi=(1.0,) * 3, periodic=(True,) * 3,
                          grid=(2, 2, 2), n_local=1024, dt=1.0)


def test_wrap_folds_into_the_box_and_keeps_inside_values():
    p = np.array([0.25, 1.0, 1.25, -0.25, -1e-9, 0.999999], np.float32)
    w = reference.wrap_axis(p, 0.0, 1.0)
    assert w.dtype == np.float32
    np.testing.assert_array_equal(
        w, np.array([0.25, 0.0, 0.25, 0.75, 0.0, 0.999999], np.float32))


def test_owner_is_row_major_over_the_grid():
    pos = np.array([[0.1, 0.1, 0.1], [0.9, 0.1, 0.1], [0.1, 0.1, 0.9],
                    [0.9, 0.9, 0.9], [0.5, 0.0, 0.0]], np.float32)
    np.testing.assert_array_equal(reference.owner(pos, GEOM), [0, 4, 1, 7, 4])


def test_bf16_rounding_is_nearest_even():
    x = np.array([1.0, 1.00390625, 1.01171875, 3.14159265], np.float32)
    r = reference.round_bf16(x)
    np.testing.assert_array_equal(r[:3], [1.0, 1.0, 1.015625])
    assert r[3] == np.float32(3.140625)


def test_multiset_diff_counts_rows_both_ways():
    a = np.array([1, 2, 3, 3], np.uint64)
    assert reference.multiset_diff(a, a[::-1].copy()) == 0
    assert reference.multiset_diff(a, np.array([1, 2, 3], np.uint64)) == 1
    assert reference.multiset_diff(a, np.array([1, 2, 3, 4], np.uint64)) == 2
    assert reference.multiset_diff(a, np.array([], np.uint64)) == 4


def _state(seed=0):
    v, _cap, _b = generators.drift_sizing(GEOM.grid, GEOM.n_local, 0.9, 0.02)
    return generators.uniform_state(GEOM.grid, GEOM.n_local, 0.9,
                                    np.random.default_rng(seed), vel_scale=v)


def _settle(pos, vel, alive, steps):
    """A correct call: the plain reference in float32."""
    return reference.call(GEOM, pos, vel, alive, steps)[:3]


def test_compare_reads_zero_on_a_correct_history():
    init = _state()
    mid = _settle(*init, 8)
    final = _settle(*mid, 8)
    sample = np.flatnonzero(init[2])[::3]
    got = reference.compare(GEOM, init, mid, final, 16, 8, sample)
    assert got == dict.fromkeys(got, 0)


@pytest.mark.parametrize("fault", ["unchanged", "lost_row", "flipped_bit",
                                   "off_owner"])
def test_compare_catches_each_fault(fault):
    init = _state(1)
    mid = _settle(*init, 8)
    final = [x.copy() for x in _settle(*mid, 8)]
    live = np.flatnonzero(final[2])
    if fault == "unchanged":
        final = [x.copy() for x in mid]
    elif fault == "lost_row":
        final[2][live[0]] = False
    elif fault == "flipped_bit":
        final[1].view(np.uint32)[live[3], 0] ^= 1
    else:  # a live row moved into a slot of the wrong rank
        src = live[0]
        dst = np.flatnonzero(~final[2] & (np.arange(len(final[2])) >= GEOM.n_local))[0]
        for x in final:
            x[dst] = x[src]
        final[2][src] = False
    sample = np.flatnonzero(init[2])
    got = reference.compare(GEOM, init, mid, tuple(final), 16, 8, sample)
    assert sum(got.values()) > 0, got


def test_the_control_reads_far_above_zero():
    init = _state(2)
    mid = _settle(*init, 8)
    p, v, a, sent = reference.call(GEOM, *mid, 8, bf16=True)
    assert sent > 0
    got = reference.compare(GEOM, init, mid, (p, v, a), 16, 8,
                            np.flatnonzero(init[2]))
    assert got["rows_lost"] == 0 and got["payload_rows_changed"] == 0
    assert got["last_call_rows_wrong"] > 100
    assert got["trajectory_rows_wrong"] > 100
