"""Traffic driver ``oneshot``: the library's one-call entry point.

Each call is one ``GridRedistribute(domain, grid).redistribute(pos, vel,
ids)`` over the whole snapshot: host NumPy inputs, made once from the seed
and reused, go to the device, every row moves to the rank that owns its
cell, and the outputs stay on the device while the stats come back to the
host. The HLO text the trace reduction reads is that of the program the
call dispatches (``engine_fn`` hands it out).

Before the timed state is built the driver checks the API's 64-bit
contract on a tiny input, so a program that narrows ids fails here, in
seconds, and does not run its fallback over the whole snapshot.

The check (``reference_oneshot.compare``) holds the last call's output to
the plain NumPy reference: counts, ownership, and every row's position,
velocity and id bits in receive order.
"""

from __future__ import annotations

import math

import numpy as np

from benchmark import generators, reference_oneshot


class ContractError(RuntimeError):
    """The program does not carry 64-bit ids through ``redistribute``."""


def snapshot(grid, n_local: int, id_range: int, rng):
    """``(pos, vel, ids)``: uniform positions over the unit box in random
    row order (so about ``1 - 1/R`` of the rows change rank), uniform
    velocities and distinct int64 ids drawn from ``[0, id_range)``."""
    n = math.prod(grid) * n_local
    pos = rng.random((n, 3), dtype=np.float32)
    vel = rng.random((n, 3), dtype=np.float32) * np.float32(2) - np.float32(1)
    ids = np.unique(rng.integers(0, id_range, size=n + n // 64 + 64,
                                 dtype=np.int64))
    if len(ids) < n:
        raise ValueError(f"{len(ids)} distinct ids drawn, {n} needed")
    ids = rng.permutation(ids)[:n]
    return pos, vel, ids


def preflight(gr, domain, grid: tuple, id_range: int, rng) -> None:
    """Raise :class:`ContractError` unless ids at or above 2**31 come back
    exact from both backends, and the jax backend keeps its planar
    engine for a row with a 64-bit id."""
    n_local = 64
    pos, vel, ids = snapshot(grid, n_local, id_range, rng)
    ids[:8] = np.int64(2**31) + np.arange(8)
    kw = dict(out_capacity=2 * n_local)  # no rank overflows: one compile
    ref = gr.GridRedistribute(domain, grid, backend="numpy",
                              **kw).redistribute(pos, vel, ids)
    if np.asarray(ref.fields[1]).dtype != np.int64:
        raise ContractError(
            f"the numpy backend returned ids as "
            f"{np.asarray(ref.fields[1]).dtype}, not int64")
    rd = gr.GridRedistribute(domain, grid, **kw)
    res = rd.redistribute(pos, vel, ids)
    got = reference_oneshot.ids_of(np.asarray(res.fields[1]))
    if not np.array_equal(got, np.asarray(ref.fields[1])):
        raise ContractError("the jax backend does not carry 64-bit ids "
                            "exactly")
    engine = rd.report()["engine"]
    if engine == "rowmajor":
        raise ContractError("a row with a 64-bit id fell back to rowmajor")


class OneShot:
    def __init__(self, config: dict, traffic: dict, seed: int, devices):
        import jax

        import mpi_grid_redistribute_tpu as gr

        self._jax = jax
        grid = tuple(int(g) for g in config["grid"])
        if math.prod(int(d) for d in config["device_grid"]) != len(devices):
            raise ValueError(f"{config['name']} lays out "
                             f"{config['device_grid']} devices, got "
                             f"{len(devices)}")
        n_local = int(config["rank_slots"])
        R = math.prod(grid)
        dom = config["domain"]
        domain = gr.Domain(float(dom["lo"]), float(dom["hi"]),
                           periodic=dom["periodic"])
        id_range = int(traffic["id_range"])
        rng_data, rng_pre = (
            np.random.default_rng(s)
            for s in generators.seed_sequence(seed).spawn(2)
        )
        preflight(gr, domain, grid, id_range, rng_pre)

        self.geom = reference_oneshot.geometry(config)
        self.inputs = snapshot(grid, n_local, id_range, rng_data)
        self.rd = gr.GridRedistribute(
            domain, grid, out_capacity=int(config["out_capacity"]),
            on_overflow=traffic["on_overflow"], engine=traffic["engine"],
        )
        self.program = self.rd.redistribute
        self.R = R
        pos, vel, ids = self.inputs
        fn, _cap, _out_cap = self.rd.engine_fn(pos, vel, ids)
        count = np.full((R,), n_local, np.int32)
        words = np.ascontiguousarray(ids).view(np.int32).reshape(-1, 2)
        self.hlo_text = fn.lower(pos, count, vel, words).compile().as_text()
        self.steps_per_call = int(traffic["steps_per_call"])
        self.units_per_call = float(len(pos))
        row_bytes = (pos.itemsize * pos.shape[1] + vel.itemsize * vel.shape[1]
                     + ids.itemsize)
        self.shapes = {"rows": len(pos), "row_bytes": row_bytes}
        self.counters: list = []
        self.last = None
        self._host = None

    def call(self, span) -> None:
        jax = self._jax
        with span("bench:dispatch"):
            res = self.program(*self.inputs)
        with span("bench:fetch"):
            stats = jax.device_get(res.stats)
            jax.block_until_ready((res.positions, res.fields, res.count))
        self.last = res
        send = np.asarray(stats.send_counts)
        self.counters.append({
            "moved": int(send.sum() - np.trace(send)),
            "backlog": np.zeros(self.R, np.int64),
        })

    def finish(self) -> None:
        # the deferred overflow windows of on_overflow="grow" are read
        # back here: a loss the calls did not report raises
        self.rd.flush_overflow_checks()
        res = self.last
        self._host = tuple(np.asarray(a) for a in (
            res.positions, res.fields[0], res.fields[1], res.count))
        self.last = self.program = self.rd = None

    def check(self) -> dict:
        out_capacity = len(self._host[0]) // self.R
        got = reference_oneshot.compare(self.geom, self.inputs, self._host,
                                        out_capacity)
        lim = reference_oneshot.LIMITS
        return {k: (got[k], lim[k]) for k in lim}


def build(config: dict, traffic: dict, seed: int, devices) -> OneShot:
    return OneShot(config, traffic, seed, devices)
