"""Elastic restore: re-shard a snapshot onto a different mesh (ISSUE 8).

The checkpoint format has been shard-count-elastic since ISSUE 6 (one
self-contained npz per shard + manifest), but a restart only ever came
back on the *same* mesh — losing a device made a perfectly good snapshot
unrecoverable. This module is the missing half: ownership in this
library is derived from POSITION, never from which shard wrote a row, so
re-decomposing R snapshot shards onto an M-vrank :class:`..domain.ProcessGrid`
is exactly one canonical redistribute over the live rows.

Pipeline (:func:`reshard_state`): strip padding with
:func:`..utils.checkpoint.gather_live`, route the live rows with
:func:`..api.reshard` (numpy backend — restores run host-side and must
not need the dead mesh), and report how many rows landed on a different
vrank index than the shard that snapshotted them — the ``moved`` count
the driver journals in its ``reshard`` event (telemetry/SCHEMA.md).
Values are only permuted, never recomputed, so the global particle SET
is invariant across mesh shapes; :func:`particle_set` canonicalizes a
driver state (sort live rows by id) into bytes for exactly that
bit-identity check, used by the fault matrix and the elastic tests.
"""
# gridlint: service-path

from __future__ import annotations

from typing import Dict, NamedTuple, Optional

import numpy as np

from mpi_grid_redistribute_tpu.domain import Domain, ProcessGrid
from mpi_grid_redistribute_tpu.utils import checkpoint


class ElasticRestoreError(RuntimeError):
    """A snapshot cannot be restored onto the configured mesh — the
    shapes disagree and auto-reshard is disabled (or no mesh fits the
    surviving device budget). Raised INSTEAD of the shape error that
    used to surface deep inside state unflattening, and names both
    shapes so the operator can see exactly what to re-enable."""


class ReshardedState(NamedTuple):
    """Outcome of :func:`reshard_state`: the snapshot re-laid-out onto
    the new grid's global padded layout."""

    arrays: Dict[str, np.ndarray]
    n_local: int
    moved_rows: int
    live_rows: int


def reshard_state(
    arrays: Dict[str, np.ndarray],
    manifest: dict,
    grid_shape,
    domain: Optional[Domain] = None,
    n_local: Optional[int] = None,
    pos_key: str = "pos",
    count_key: str = "count",
) -> ReshardedState:
    """Re-shard a loaded snapshot onto ``grid_shape`` in one redistribute.

    ``arrays``/``manifest`` are straight from
    :func:`..utils.checkpoint.load_latest`; every global array except
    ``pos_key`` rides the permutation as a passenger field (velocities,
    the id column, anything the driver snapshots). ``n_local`` defaults
    to ``ceil(R * rows_per_shard / M)`` — total slot capacity is
    preserved across the reshard, so a shrink to half the vranks doubles
    the per-vrank padding instead of silently tightening headroom; the
    engine still grows (pow2) if per-owner skew needs more. The returned
    ``n_local`` is the ACTUAL rows/vrank of the output layout.

    ``moved_rows`` counts live rows whose owning vrank index under the
    new grid differs from the snapshot shard that held them — the data
    that physically moved, journaled in the ``reshard`` event.
    """
    from mpi_grid_redistribute_tpu import api  # lazy: pulls in jax

    grid = (
        grid_shape
        if isinstance(grid_shape, ProcessGrid)
        else ProcessGrid(tuple(int(x) for x in grid_shape))
    )
    if domain is None:
        domain = Domain(0.0, 1.0, periodic=True)
    nranks = int(manifest["nranks"])
    rows = int(manifest["rows_per_shard"])
    count_vec = np.asarray(arrays[count_key]).astype(np.int64).ravel()
    live = checkpoint.gather_live(
        arrays, nranks, rows, count_key=count_key
    )
    field_names = [
        n for n in sorted(live) if n not in (pos_key, count_key)
    ]
    m = grid.nranks
    if n_local is None:
        n_local = max(1, -(-(nranks * rows) // m))
    res = api.reshard(
        live[pos_key],
        *(live[n] for n in field_names),
        domain=domain,
        grid=grid,
        n_local=int(n_local),
        backend="numpy",
    )
    out = {pos_key: np.asarray(res.positions)}
    for name, f in zip(field_names, res.fields):
        out[name] = np.asarray(f)
    out[count_key] = np.asarray(res.count)
    rows_out = out[pos_key].shape[0] // m
    from mpi_grid_redistribute_tpu.ops import binning  # lazy: pulls in jax

    old_shard = np.repeat(np.arange(nranks, dtype=np.int64), count_vec)
    owner = np.asarray(
        binning.rank_of_position(live[pos_key], domain, grid, xp=np)
    ).astype(np.int64)
    moved = int((owner != old_shard).sum())
    return ReshardedState(
        arrays=out,
        n_local=int(rows_out),
        moved_rows=moved,
        live_rows=int(old_shard.shape[0]),
    )


def particle_set(pos, vel, ids, count) -> bytes:
    """Canonical bytes of the global particle SET of a driver state.

    Live rows gathered across shards, sorted by id (stable), then
    ``ids + pos + vel`` raw bytes — two runs agree iff they hold the
    same particles with bit-identical values, regardless of which vrank
    owns which row or how much padding each mesh shape carries. The
    elastic fault-matrix and soak legs compare exactly this.
    """
    count = np.asarray(count).astype(np.int64).ravel()
    nranks = count.shape[0]
    pos = np.asarray(pos)
    rows = pos.shape[0] // max(nranks, 1)
    live = checkpoint.gather_live(
        {"pos": pos, "vel": np.asarray(vel), "ids": np.asarray(ids),
         "count": count},
        nranks,
        rows,
    )
    order = np.argsort(live["ids"], kind="stable")
    return b"".join(
        np.ascontiguousarray(live[k][order]).tobytes()
        for k in ("ids", "pos", "vel")
    )
