"""Traffic driver ``drift_loop``: the program's resident drift loop.

``nbody.make_migrate_loop(engine=...)`` compiled once for the cell's
layout, ``steps_per_call`` drift steps per call. Each call's planar
outputs feed the next call, and its per-step stats come back to the host,
as a user's loop does. The window drives the very compiled object whose
HLO text the trace reduction reads.

The check (``reference.compare``) holds the final state to the plain
NumPy reference: from the seed through every call of the run for a
sample of particles, over the whole population for the last call, and
for conservation, payload bits and ownership of every row.
"""

from __future__ import annotations

import math
from typing import NamedTuple

import numpy as np

from benchmark import generators, reference, work as work_lib

# every number compared is exact
LIMITS = {
    "rows_lost": 0,
    "payload_rows_changed": 0,
    "rows_off_owner": 0,
    "last_call_rows_wrong": 0,
    "trajectory_rows_wrong": 0,
}


class Stats(NamedTuple):
    """The per-step counts a substitute program returns ([steps, ranks])."""

    sent: np.ndarray
    received: np.ndarray
    backlog: np.ndarray


def layout(config: dict):
    """``(grid, device_grid, vrank_shape)`` of a configuration."""
    grid = tuple(int(g) for g in config["grid"])
    dev = tuple(int(d) for d in config["device_grid"])
    if any(g % d for g, d in zip(grid, dev)):
        raise ValueError(f"device grid {dev} does not divide grid {grid}")
    vshape = tuple(g // d for g, d in zip(grid, dev))
    if math.prod(dev) > 1 and math.prod(vshape) > 1:
        # rows would be device-major over vranks, not rank-major over the
        # grid; no configuration asks for that yet
        raise ValueError("vranks on more than one device are not laid out")
    return grid, dev, vshape


class DriftLoop:
    Stats = Stats

    def __init__(self, config: dict, traffic: dict, seed: int, devices):
        import jax
        from jax.sharding import NamedSharding, PartitionSpec as P

        from mpi_grid_redistribute_tpu import Domain
        from mpi_grid_redistribute_tpu.domain import ProcessGrid
        from mpi_grid_redistribute_tpu.models import nbody
        from mpi_grid_redistribute_tpu.parallel import mesh as mesh_lib

        self._jax = jax
        grid, dev_shape, vshape = layout(config)
        n_dev = math.prod(dev_shape)
        if n_dev != len(devices):
            raise ValueError(f"{config['name']} lays out {n_dev} devices, "
                             f"got {len(devices)}")
        n_local = int(config["rank_slots"])
        fill = float(config["fill"])
        steps = int(traffic["steps_per_call"])
        self.geom = reference.Geometry.from_config(config)
        rng_state, rng_sample = (
            np.random.default_rng(s)
            for s in generators.seed_sequence(seed).spawn(2)
        )
        v_scale, cap, budget = generators.drift_sizing(
            grid, n_local, fill, float(traffic["migration_per_step"]),
            float(traffic["headroom"]),
        )
        pos, vel, alive = generators.uniform_state(
            grid, n_local, fill, rng_state, vel_scale=v_scale
        )
        live = np.flatnonzero(alive)
        self.sample = np.sort(rng_sample.choice(
            live, size=min(int(traffic["check_sample_rows"]), len(live)),
            replace=False,
        ))
        self.initial = (pos, vel, alive)

        dom = config["domain"]
        domain = Domain(float(dom["lo"]), float(dom["hi"]),
                        periodic=dom["periodic"])
        dgrid = ProcessGrid(dev_shape)
        mesh = mesh_lib.make_mesh(dgrid, devices=list(devices))
        cfg = nbody.DriftConfig(
            domain=domain, grid=dgrid, dt=float(config["dt"]), capacity=cap,
            n_local=n_local, local_budget=budget, engine=traffic["engine"],
        )
        vgrid = ProcessGrid(grid) if math.prod(vshape) > 1 else None
        loop = nbody.make_migrate_loop(cfg, mesh, steps, vgrid=vgrid)
        sharding = NamedSharding(mesh, P(dgrid.axis_names))
        self.n_blocks = n_dev
        self.state = tuple(
            jax.device_put(a, sharding)
            for a in (generators.rows_to_planar(pos, n_dev),
                      generators.rows_to_planar(vel, n_dev), alive)
        )
        compiled = jax.jit(loop).lower(*self.state).compile()
        self.program = compiled
        self.hlo_text = compiled.as_text()
        self.prev = self.state
        self.steps_per_call = steps
        self.units_per_call = float(len(live) * steps)
        K = 2 * pos.shape[1] + 1
        self.shapes = {
            "slots_per_chip": len(alive) // n_dev,
            "D": pos.shape[1],
            "K": K,
            "row_bytes": work_lib.row_bytes(K),
        }
        self.counters: list = []
        self._host = None

    def call(self, span) -> None:
        jax = self._jax
        with span("bench:dispatch"):
            out = self.program(*self.state)
        with span("bench:fetch"):
            stats = jax.device_get(out[3])
            jax.block_until_ready(out[:3])
        self.prev, self.state = self.state, tuple(out[:3])
        self.counters.append({
            "sent": np.asarray(stats.sent),
            "received": np.asarray(stats.received),
            "backlog": np.asarray(getattr(stats, "backlog", 0)),
        })

    def finish(self) -> None:
        prev, final = self._jax.device_get((self.prev, self.state))
        self.prev = self.state = self.program = None
        D = self.shapes["D"]
        self._host = tuple(
            (generators.planar_to_rows(s[0], D, self.n_blocks),
             generators.planar_to_rows(s[1], D, self.n_blocks),
             np.asarray(s[2], bool))
            for s in (prev, final)
        )

    def check(self) -> dict:
        prev, final = self._host
        got = reference.compare(
            self.geom, self.initial, prev, final,
            steps_total=len(self.counters) * self.steps_per_call,
            steps_last=self.steps_per_call, sample=self.sample,
        )
        return {k: (got[k], LIMITS[k]) for k in LIMITS}


def build(config: dict, traffic: dict, seed: int, devices) -> DriftLoop:
    return DriftLoop(config, traffic, seed, devices)
