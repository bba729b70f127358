"""Plain NumPy reference of the one-call redistribute, and the comparison
that decides ``correct``. It imports nothing of the program.

Semantics (those of the upstream project's Alltoallv redistribute): the
input holds ``R * n_local`` rows, rank ``r`` owning rows ``[r * n_local,
(r + 1) * n_local)``, all valid. Each row moves to the rank that owns its
position (``reference.owner``: periodic wrap, ``floor`` of the scaled
float32 position, ranks row-major). Each rank receives its rows in
Alltoallv order, by source rank and then in source order, which for
rank-major input is the input's own row order. Every payload bit rides
unchanged, and the id is a 64-bit integer. The output holds
``out_capacity`` rows a rank; rows past a rank's count are not compared.
"""

from __future__ import annotations

import numpy as np

from benchmark import reference

# every number compared is exact
LIMITS = {
    "rows_lost": 0,
    "count_wrong": 0,
    "rows_off_owner": 0,
    "rows_wrong": 0,
    "ids_wrong": 0,
}


def geometry(config: dict) -> reference.Geometry:
    """The domain and grid of a configuration (no drift: ``dt`` is 0)."""
    dom = config["domain"]
    nd = len(config["grid"])
    per = dom["periodic"]
    return reference.Geometry(
        lo=(float(dom["lo"]),) * nd,
        hi=(float(dom["hi"]),) * nd,
        periodic=(bool(per),) * nd if isinstance(per, bool) else tuple(per),
        grid=tuple(int(g) for g in config["grid"]),
        n_local=int(config["rank_slots"]),
        dt=0.0,
    )


def route(geom: reference.Geometry, pos: np.ndarray):
    """``(order, counts)``: the input rows in receive order, rank after
    rank, and the rows each rank receives."""
    dest = reference.owner(pos, geom)
    R = int(np.prod(geom.grid))
    return np.argsort(dest, kind="stable"), np.bincount(dest, minlength=R)


def redistribute(geom: reference.Geometry, pos: np.ndarray, fields,
                 out_capacity: int):
    """The call written plainly: ``(pos, fields, counts)`` in the padded
    output layout, zeros past each rank's count."""
    order, counts = route(geom, pos)
    R = len(counts)
    if counts.max() > out_capacity:
        raise ValueError(f"a rank receives {counts.max()} rows, more than "
                         f"out_capacity {out_capacity}")
    starts = np.concatenate([[0], np.cumsum(counts)[:-1]])
    slot = (np.repeat(np.arange(R) * out_capacity, counts)
            + np.arange(len(order)) - np.repeat(starts, counts))
    outs = []
    for a in (pos,) + tuple(fields):
        out = np.zeros((R * out_capacity,) + a.shape[1:], a.dtype)
        out[slot] = a[order]
        outs.append(out)
    return outs[0], tuple(outs[1:]), counts.astype(np.int32)


def ids_of(out_ids: np.ndarray) -> np.ndarray:
    """The program's id column as int64: its two int32 words joined (low
    word first), else the values as they came back."""
    a = np.asarray(out_ids)
    if a.dtype == np.int32 and a.ndim == 2 and a.shape[1] == 2:
        return np.ascontiguousarray(a).view(np.int64)[:, 0]
    return a.reshape(len(a)).astype(np.int64)


def _bits(a: np.ndarray) -> np.ndarray:
    """Rows of float32 columns as uint32 bit rows."""
    a = np.ascontiguousarray(a, np.float32)
    return a.view(np.uint32).reshape(len(a), -1)


def compare(geom: reference.Geometry, inputs, outputs,
            out_capacity: int) -> dict:
    """The numbers compared, each exact (its limit is 0).

    ``inputs`` is ``(pos, vel, ids)`` as the call was given them,
    ``outputs`` ``(pos, vel, ids, count)`` as it returned them (``ids``
    as :func:`ids_of` reads them).

    * ``rows_lost``: rows gained or lost;
    * ``count_wrong``: ranks whose row count differs from the reference's;
    * ``rows_off_owner``: output rows held by a rank that does not own
      their position;
    * ``rows_wrong``: output rows whose position, velocity or id bits
      differ from the reference's row at the same place in the rank's
      receive order, every rank, plus the rows one side has and the
      other lacks;
    * ``ids_wrong``: the same, for the 64-bit id alone.
    """
    pos, vel, ids = inputs
    opos, ovel, oids, count = outputs
    count = np.asarray(count, np.int64)
    R = int(np.prod(geom.grid))
    rpos, (rvel, rids), rcount = redistribute(geom, pos, (vel, ids),
                                              out_capacity)
    oids = ids_of(oids)
    out = {"rows_lost": abs(int(count.sum()) - len(pos)),
           "count_wrong": int((count != rcount).sum())}
    off = wrong = wrong_ids = 0
    for r in range(R):
        base = r * out_capacity
        held = int(min(count[r], out_capacity))
        got = slice(base, base + held)
        off += int((reference.owner(opos[got], geom) != r).sum())
        m = min(held, int(rcount[r]))
        both = slice(base, base + m)
        extra = abs(int(count[r]) - int(rcount[r]))
        id_bad = oids[both] != rids[both]
        row_bad = (np.any(_bits(opos[both]) != _bits(rpos[both]), axis=1)
                   | np.any(_bits(ovel[both]) != _bits(rvel[both]), axis=1)
                   | id_bad)
        wrong += int(row_bad.sum()) + extra
        wrong_ids += int(id_bad.sum()) + extra
    out["rows_off_owner"] = off
    out["rows_wrong"] = wrong
    out["ids_wrong"] = wrong_ids
    return out
