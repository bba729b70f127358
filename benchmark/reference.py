"""Plain NumPy reference of the drift loop, and the comparison that
decides ``correct``. It imports nothing of the program.

Semantics (those of the program's ``oracle.py``, written out again): in
each step every live particle advances ``p = wrap(p + v * dt)`` in
float32, where the periodic wrap of a power-of-two extent is
``r = q - floor(q * (1 / ext)) * ext`` on ``q = p - lo``, folded into
``[0, ext)``. The particle then lives on the rank whose grid cell holds
it, ``cell = clip(floor((p - lo) * (g / ext)), 0, g - 1)``, ranks
row-major. Velocities ride along unchanged, bit for bit. Where a
particle sits among its rank's slots is not part of the semantics, so
states are compared as multisets of rows per rank.
"""

from __future__ import annotations

from dataclasses import dataclass

import numpy as np

from benchmark import generators


@dataclass(frozen=True)
class Geometry:
    """The domain, the grid and the slab of rows each rank holds."""

    lo: tuple
    hi: tuple
    periodic: tuple
    grid: tuple
    n_local: int
    dt: float

    @classmethod
    def from_config(cls, config: dict) -> "Geometry":
        dom = config["domain"]
        nd = len(config["grid"])
        per = dom["periodic"]
        return cls(
            lo=(float(dom["lo"]),) * nd,
            hi=(float(dom["hi"]),) * nd,
            periodic=(bool(per),) * nd if isinstance(per, bool) else tuple(per),
            grid=tuple(int(g) for g in config["grid"]),
            n_local=int(config["rank_slots"]),
            dt=float(config["dt"]),
        )


def _pow2(x: float) -> bool:
    m, _ = np.frexp(x)
    return x > 0 and m == 0.5


def wrap_axis(p: np.ndarray, lo: float, hi: float) -> np.ndarray:
    """Periodic wrap of one float32 axis into ``[lo, hi)``."""
    f = np.float32
    lo32, ext = f(lo), f(f(hi) - f(lo))
    if not _pow2(float(ext)):
        raise ValueError(f"the reference wraps power-of-two extents only: {ext}")
    top = f(lo32 + ext)
    # r = q - floor(q * (1 / ext)) * ext, each op rounded to float32,
    # in place to spare the temporaries
    q = np.subtract(p, lo32, dtype=np.float32)
    t = np.multiply(q, f(1.0 / float(ext)))
    np.floor(t, out=t)
    t *= ext
    q -= t
    q[(q < f(0)) | (q >= ext)] = f(0)
    q += lo32
    q[q >= top] = lo32
    return q


def round_bf16(x: np.ndarray) -> np.ndarray:
    """float32 values rounded to bfloat16 (nearest, ties to even), kept
    as float32: the precision the control computes in."""
    u = np.ascontiguousarray(x, np.float32).view(np.uint32)
    u = (u + np.uint32(0x7FFF) + ((u >> np.uint32(16)) & np.uint32(1))) & np.uint32(
        0xFFFF0000
    )
    return u.view(np.float32)


def drift(pos: np.ndarray, vel: np.ndarray, geom: Geometry, steps: int,
          bf16: bool = False) -> np.ndarray:
    """Positions ``[n, D]`` after ``steps`` drift steps (a new array)."""
    dt = np.float32(geom.dt)
    cols = [np.array(pos[:, d], np.float32) for d in range(pos.shape[1])]
    vcols = [np.ascontiguousarray(vel[:, d], np.float32) * dt
             for d in range(vel.shape[1])]
    for _ in range(steps):
        for d, (p, v) in enumerate(zip(cols, vcols)):
            p = p + v
            if geom.periodic[d]:
                p = wrap_axis(p, geom.lo[d], geom.hi[d])
            cols[d] = round_bf16(p) if bf16 else p
    return np.stack(cols, axis=1)


def owner(pos: np.ndarray, geom: Geometry) -> np.ndarray:
    """Rank that owns each position ``[n, D]`` (int64)."""
    f = np.float32
    rank = np.zeros(pos.shape[0], np.int64)
    for d, (g, s) in enumerate(zip(geom.grid, generators.strides(geom.grid))):
        p = np.ascontiguousarray(pos[:, d], np.float32)
        if geom.periodic[d]:
            p = wrap_axis(p, geom.lo[d], geom.hi[d])
        lo, ext = f(geom.lo[d]), f(f(geom.hi[d]) - f(geom.lo[d]))
        c = np.floor((p - lo) * f(f(g) / ext)).astype(np.int64)
        rank += np.clip(c, 0, g - 1) * s
    return rank


_MUL = np.uint64(0xBF58476D1CE4E5B9)


def row_hash(*cols, seed: np.ndarray | None = None) -> np.ndarray:
    """64-bit hash of rows given as columns (float32 columns by their
    bits, integer columns by value). ``seed`` continues an earlier hash."""
    n = len(cols[0])
    h = np.zeros(n, np.uint64) if seed is None else seed.copy()
    with np.errstate(over="ignore"):
        for c in cols:
            c = np.ascontiguousarray(c)
            if c.dtype == np.float32:
                c = c.view(np.uint32)
            h ^= c.astype(np.uint64)
            h *= _MUL
            h ^= h >> np.uint64(29)
    return h


def multiset_diff(a: np.ndarray, b: np.ndarray) -> int:
    """Rows in one multiset of hashes and not in the other, both ways."""
    a, b = np.sort(a), np.sort(b)
    if a.shape == b.shape and np.array_equal(a, b):
        return 0
    if not len(a) or not len(b):
        return len(a) + len(b)
    v = np.concatenate([a, b])
    s = np.concatenate([np.ones(len(a), np.int64), -np.ones(len(b), np.int64)])
    o = np.argsort(v, kind="stable")
    v, s = v[o], s[o]
    starts = np.flatnonzero(np.r_[True, v[1:] != v[:-1]])
    return int(np.abs(np.add.reduceat(s, starts)).sum())


def compare(geom: Geometry, initial, prev, final, steps_total: int,
            steps_last: int, sample: np.ndarray) -> dict:
    """The numbers compared, each exact (its limit is 0).

    ``initial`` is the state made from the seed, ``prev`` the input of
    the window's last call and ``final`` its output, each
    ``(pos [N, D], vel [N, D], alive [N])`` in row-major slot order with
    ``geom.n_local`` slots per rank. ``sample`` indexes live rows of
    ``initial``; ``steps_total`` steps lie between ``initial`` and
    ``final``, ``steps_last`` between ``prev`` and ``final``.

    * ``rows_lost``: live rows gained or lost since the seed;
    * ``payload_rows_changed``: velocity rows (the payload) of the
      final state that are not those the seed made, and the reverse;
    * ``rows_off_owner``: live rows held by a rank that does not own
      their position;
    * ``last_call_rows_wrong``: rows (rank, position bits, velocity
      bits) of the last call's output that differ from the reference
      applied to that call's input, both ways;
    * ``trajectory_rows_wrong``: sampled particles whose final rank or
      position bits differ from the reference's trajectory from the
      seed through every call, or that cannot be found.
    """
    ipos, ivel, ialive = initial
    ppos, pvel, palive = prev
    fpos, fvel, falive = final
    D = fpos.shape[1]
    rank_of_row = np.arange(len(falive), dtype=np.int64) // geom.n_local
    fp, fv, fr = fpos[falive], fvel[falive], rank_of_row[falive]
    out = {"rows_lost": abs(int(falive.sum()) - int(ialive.sum()))}

    h_fv = row_hash(*(fv[:, d] for d in range(D)))
    h_iv = row_hash(*(ivel[ialive][:, d] for d in range(D)))
    out["payload_rows_changed"] = multiset_diff(h_fv, h_iv)
    del h_iv

    out["rows_off_owner"] = int((owner(fp, geom) != fr).sum())

    pp, pv = ppos[palive], pvel[palive]
    ref = drift(pp, pv, geom, steps_last)
    h_ref = row_hash(
        owner(ref, geom), *(ref[:, d] for d in range(D)),
        seed=row_hash(*(pv[:, d] for d in range(D))),
    )
    h_got = row_hash(fr, *(fp[:, d] for d in range(D)), seed=h_fv)
    out["last_call_rows_wrong"] = multiset_diff(h_got, h_ref)
    del h_ref, h_got, ref

    sp = drift(ipos[sample], ivel[sample], geom, steps_total)
    sr = owner(sp, geom)
    key = row_hash(*(ivel[sample][:, d] for d in range(D)))
    order = np.argsort(h_fv)
    sorted_h = h_fv[order]
    loc = np.clip(np.searchsorted(sorted_h, key), 0, max(len(sorted_h) - 1, 0))
    found = (sorted_h[loc] == key) if len(sorted_h) else np.zeros(len(key), bool)
    rows = order[loc]
    same = found.copy()
    same[found] = (
        (fr[rows[found]] == sr[found])
        & np.all(fp[rows[found]].view(np.uint32) == sp[found].view(np.uint32), axis=1)
    )
    out["trajectory_rows_wrong"] = int((~same).sum())
    return out


def call(geom: Geometry, pos: np.ndarray, vel: np.ndarray,
         alive: np.ndarray, steps: int, bf16: bool = False):
    """One call written plainly: ``steps`` drift steps on row-major
    slots, then every live row in a slot of the rank that owns it; where
    a rank's slots would overflow, the last arrivals stay on the rank
    they came from (flow control). With ``bf16`` it is the control: the
    reference put in the program's place and computed in bfloat16.
    Returns ``(pos, vel, alive, sent)``, ``sent`` the rows that changed
    rank."""
    n_rows = len(alive)
    R = n_rows // geom.n_local
    live = np.flatnonzero(alive)
    new = drift(pos[live], vel[live], geom, steps, bf16=bf16)
    src = live // geom.n_local
    dest = owner(new, geom)
    while True:
        counts = np.bincount(dest, minlength=R)
        over = np.flatnonzero(counts > geom.n_local)
        if not len(over):
            break
        for r in over:
            incoming = np.flatnonzero((dest == r) & (src != r))
            back = incoming[len(incoming) - (counts[r] - geom.n_local):]
            dest[back] = src[back]
    sent = int((dest != src).sum())
    order = np.argsort(dest, kind="stable")
    out_pos = np.zeros_like(pos)
    out_vel = np.zeros_like(vel)
    out_alive = np.zeros_like(alive)
    starts = np.concatenate([[0], np.cumsum(counts)[:-1]])
    slot = (
        dest[order] * geom.n_local
        + np.arange(len(order)) - np.repeat(starts, counts)
    )
    out_pos[slot] = new[order]
    out_vel[slot] = vel[live][order]
    out_alive[slot] = True
    return out_pos, out_vel, out_alive, sent
