#!/usr/bin/env python3
"""On-chip smoke of the main path: the quickest proof that the system
still starts on a TPU. Not a benchmark: the times it prints are smoke
timings of one cold run.

    python chip_smoke.py             # one chip: API, drift loop, service
    python chip_smoke.py --chips 4   # four chips: the cross-chip path only

One chip, at the shape of the benchmark's ``drift8v.steady`` cell (grid
2x2x2 as 8 virtual ranks, 2**20 rows per rank, FILL 0.9, ~2% migration
per step):

* api     -- ``GridRedistribute(...).redistribute(pos, vel, ids)`` on
  8 * 2**20 rows, bit-identical (uint32 view) to ``backend="numpy"``;
* loop    -- ``nbody.make_migrate_loop(engine="auto")`` for 32 steps:
  conservation, zero ``dropped_recv``, ownership of the final state, and
  the compiled program must hold the overlay-landing and fused
  drift+bin Pallas kernels (a fallback to XLA fails the smoke);
* service -- ``ServiceDriver`` (jax backend, resident chunks) with one
  snapshot and one restore, bit-identical to an uninterrupted run.

Four chips (``--chips 4``): grid (2, 2, 1), one rank per chip, 2**21
rows per chip -- the public API's all_to_all exchange bit-identical to
``backend="numpy"``, 32 migrate-loop steps, one auto-sized halo
exchange (ppermute) with zero overflow, and every output sharded over
all four devices.

Everything runs in this one process, which holds the chip. The last line
of stdout is one JSON object; it is printed only when every phase passed.
Without a TPU the script exits non-zero and prints no result.
"""

from __future__ import annotations

import argparse
import json
import sys
import tempfile
import time

import numpy as np

FILL = 0.9
MIGRATION = 0.02
STEPS = 32
KERNELS = ("_overlay_sorted_i8", "_driftbin_call")  # HLO names, see ops/


def log(msg: str) -> None:
    print(msg, flush=True)


class CacheCounter:
    """Persistent-cache requests and hits, from ``jax.monitoring``."""

    def __init__(self):
        self.requests = 0
        self.hits = 0

    def __call__(self, event: str, **_kw) -> None:
        if event == "/jax/compilation_cache/compile_requests_use_cache":
            self.requests += 1
        elif event == "/jax/compilation_cache/cache_hits":
            self.hits += 1


class Phase:
    """Wall time and backend compile time of one phase."""

    def __init__(self, name: str, compile_log: list):
        self.name = name
        self._log = compile_log

    def __enter__(self):
        self._t0 = time.perf_counter()
        self._c0 = len(self._log)
        return self

    def __exit__(self, exc_type, exc, tb):
        wall = time.perf_counter() - self._t0
        comp = sum(self._log[self._c0 :])
        status = "FAILED" if exc_type else "ok"
        log(
            f"smoke timing (not a benchmark) {self.name}: {status}, "
            f"wall {wall:.3f} s, backend compile {comp:.3f} s"
        )
        return False


def _u32(a) -> np.ndarray:
    return np.ascontiguousarray(np.asarray(a)).view(np.uint32)


def _shards(rows, count, out_cap):
    return [
        rows[r * out_cap : r * out_cap + int(c)] for r, c in enumerate(count)
    ]


def phase_api(grid_shape, n_local: int, seed: int = 0, mesh=None):
    """Public API redistribute vs the NumPy backend, bit for bit."""
    from mpi_grid_redistribute_tpu import Domain, GridRedistribute, oracle

    R = int(np.prod(grid_shape))
    n = R * n_local
    rng = np.random.default_rng(seed)
    pos = rng.random((n, 3), dtype=np.float32)
    vel = rng.standard_normal((n, 3), dtype=np.float32)
    ids = np.arange(n, dtype=np.int32)
    domain = Domain(0.0, 1.0, periodic=True)
    with GridRedistribute(domain, grid_shape, mesh=mesh) as rd:
        res = rd.redistribute(pos, vel, ids)
        got = (res.positions, res.fields[0], res.fields[1], res.count)
        got = tuple(np.asarray(a) for a in got)
        drops = int(np.asarray(res.stats.dropped_send).sum()) + int(
            np.asarray(res.stats.dropped_recv).sum()
        )
        sharded = [res.positions, res.fields[0], res.count]
    with GridRedistribute(domain, grid_shape, backend="numpy") as ref_rd:
        ref = ref_rd.redistribute(pos, vel, ids)
        want = (ref.positions, ref.fields[0], ref.fields[1], ref.count)
    for name, a, b in zip(("positions", "vel", "ids", "count"), got, want):
        if a.shape != np.shape(b) or not np.array_equal(_u32(a), _u32(b)):
            raise AssertionError(f"api: {name} differs from backend='numpy'")
    kept = int(got[3].sum())
    if kept != n or drops:
        raise AssertionError(f"api: kept {kept} of {n} rows, {drops} dropped")
    out_cap = got[0].shape[0] // R
    held = np.concatenate(_shards(got[2], got[3], out_cap))
    if not np.array_equal(np.sort(held), ids):
        raise AssertionError("api: the id set changed")
    oracle.assert_ownership(domain, rd.grid, _shards(got[0], got[3], out_cap))
    log(
        f"api: {n} rows over grid {tuple(grid_shape)}, bit-identical to "
        f"backend='numpy' (uint32 view of positions, vel, ids, count), "
        f"0 rows dropped, ownership verified"
    )
    return rd, res, sharded


def phase_loop(grid_shape, n_local: int, mesh, vgrid=None, seed: int = 1,
               require_kernels: bool = True):
    """The drift loop through ``make_migrate_loop`` (engine="auto")."""
    import jax
    import jax.numpy as jnp

    from mpi_grid_redistribute_tpu import Domain, oracle
    from mpi_grid_redistribute_tpu.models import initial
    from mpi_grid_redistribute_tpu.domain import ProcessGrid
    from mpi_grid_redistribute_tpu.models import nbody
    from mpi_grid_redistribute_tpu.utils import stats as stats_lib

    grid = ProcessGrid(grid_shape)
    domain = Domain(0.0, 1.0, periodic=True)
    dev_grid = ProcessGrid((1, 1, 1)) if vgrid is not None else grid
    v_scale, cap, budget = initial.drift_sizing(
        grid_shape, n_local, FILL, MIGRATION
    )
    pos, vel, alive = initial.uniform_state(
        grid_shape, n_local, FILL, np.random.default_rng(seed),
        vel_scale=v_scale,
    )
    cfg = nbody.DriftConfig(
        domain=domain, grid=dev_grid, dt=1.0, capacity=cap,
        n_local=n_local, local_budget=budget, engine="auto",
    )
    loop = nbody.make_migrate_loop(cfg, mesh, STEPS, vgrid=vgrid)
    args = (
        jnp.asarray(nbody.rows_to_planar(pos, mesh.size)),
        jnp.asarray(nbody.rows_to_planar(vel, mesh.size)),
        jnp.asarray(alive),
    )
    compiled = jax.jit(loop).lower(*args).compile()
    hlo = compiled.as_text()
    kernels = {
        k: sum(
            1 for line in hlo.splitlines()
            if 'custom_call_target="tpu_custom_call"' in line and k in line
        )
        for k in KERNELS
    }
    log(f"loop: tpu_custom_call per kernel in the compiled loop: {kernels}")
    if require_kernels and not all(kernels.values()):
        raise AssertionError(
            f"loop: a kernel fell back to XLA on this shape: {kernels}"
        )
    out = jax.block_until_ready(compiled(*args))
    stats = jax.tree.map(np.asarray, out[3])
    stats_lib.check_no_loss(stats)
    live = np.asarray(out[2])
    total = int(alive.sum())
    if int(live.sum()) != total or int(stats.dropped_recv.sum()):
        raise AssertionError(
            f"loop: {int(live.sum())} of {total} alive after {STEPS} "
            f"steps, dropped_recv {int(stats.dropped_recv.sum())}"
        )
    rows = nbody.planar_to_rows(out[0], 3, mesh.size)
    R = grid.nranks
    oracle.assert_ownership(
        domain, grid,
        [rows[r * n_local : (r + 1) * n_local][live[r * n_local : (r + 1) * n_local]]
         for r in range(R)],
    )
    moved = stats.sent.sum(axis=1) / total
    log(
        f"loop: {STEPS} steps over grid {tuple(grid_shape)}"
        + (f" as vranks {vgrid.shape}" if vgrid is not None else "")
        + f", {total} particles conserved, dropped_recv 0, ownership "
        f"verified, mean migration/step {moved.mean():.4%}"
    )
    return out


def phase_service(grid_shape, n_local: int, chunk: int = 8) -> None:
    """ServiceDriver: snapshot at step 2*chunk, restore, finish; the
    final state must equal an uninterrupted run bit for bit."""
    import dataclasses

    from mpi_grid_redistribute_tpu.service import DriverConfig, ServiceDriver

    steps = 3 * chunk
    with tempfile.TemporaryDirectory(prefix="chip_smoke_") as root:
        cfg = DriverConfig(
            grid_shape=tuple(grid_shape), n_local=n_local, steps=steps,
            seed=7, backend="jax", chunk=chunk, snapshot_every=2 * chunk,
            snapshot_dir=f"{root}/snaps", snapshot_async=False,
        )
        first = ServiceDriver(cfg)
        first.init_state()
        first.run(max_steps=2 * chunk)
        first.close()
        resumed = ServiceDriver(cfg)
        if not resumed.restore_latest() or resumed.step != 2 * chunk:
            raise AssertionError("service: no snapshot to restore from")
        resumed.run()
        resumed.close()
        ref = ServiceDriver(
            dataclasses.replace(cfg, snapshot_every=0, snapshot_dir=None)
        )
        ref.init_state()
        ref.run()
        ref.close()
    for name, a, b in zip(("pos", "vel", "ids", "count"), resumed.state,
                          ref.state):
        if a.tobytes() != b.tobytes():
            raise AssertionError(
                f"service: restored run's {name} differs from the "
                "uninterrupted run"
            )
    live = int(resumed.state[3].sum())
    log(
        f"service: {steps} steps in chunks of {chunk}, snapshot at step "
        f"{2 * chunk}, restored and finished; state bit-identical to an "
        f"uninterrupted run ({live} live particles)"
    )


def run_one_chip(compile_log: list, n_local: int = 2**20,
                 require_kernels: bool = True) -> None:
    import jax

    from mpi_grid_redistribute_tpu.domain import ProcessGrid
    from mpi_grid_redistribute_tpu.parallel import mesh as mesh_lib

    grid = (2, 2, 2)
    with Phase("api", compile_log):
        phase_api(grid, n_local)
    with Phase("loop", compile_log):
        mesh = mesh_lib.make_mesh(
            ProcessGrid((1, 1, 1)), devices=jax.devices()[:1]
        )
        phase_loop(grid, n_local, mesh, vgrid=ProcessGrid(grid),
                   require_kernels=require_kernels)
    with Phase("service", compile_log):
        phase_service(grid, n_local)


def run_four_chips(compile_log: list, n_local: int = 2**21) -> None:
    import jax

    from mpi_grid_redistribute_tpu.domain import ProcessGrid
    from mpi_grid_redistribute_tpu.parallel import mesh as mesh_lib

    devs = jax.devices()
    if len(devs) < 4:
        raise SystemExit(f"--chips 4 needs 4 devices, found {len(devs)}")
    grid = (2, 2, 1)
    mesh = mesh_lib.make_mesh(ProcessGrid(grid), devices=devs[:4])
    with Phase("api-4chip", compile_log):
        rd, res, sharded = phase_api(grid, n_local, mesh=mesh)
    with Phase("halo-4chip", compile_log):
        width = 0.25 * min(rd.grid.cell_widths(rd.domain))
        halo = rd.halo(res.positions, width=width, count=res.count)
        over = int(np.asarray(halo.overflow).sum())
        ghosts = int(np.asarray(halo.ghost_count).sum())
        if over or not ghosts:
            raise AssertionError(f"halo: overflow {over}, {ghosts} ghosts")
        log(f"halo: {ghosts} ghosts over ppermute, zero overflow")
    with Phase("loop-4chip", compile_log):
        out = phase_loop(grid, n_local, mesh, require_kernels=False)
    outputs = sharded + [halo.ghost_positions] + list(out[:3])
    for a in outputs:
        held = a.sharding.device_set
        if len(held) != 4:
            raise AssertionError(f"an output is held by {len(held)} devices")
    log(f"sharding: {len(outputs)} outputs each held by all 4 devices")


def main(argv=None) -> int:
    p = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    p.add_argument("--chips", type=int, choices=(1, 4), default=1)
    args = p.parse_args(argv)

    import jax

    devs = jax.devices()
    if devs[0].platform != "tpu":
        print(
            f"chip_smoke: needs a TPU, found {devs[0].platform!r}",
            file=sys.stderr,
        )
        return 2

    from mpi_grid_redistribute_tpu.utils import compile_cache

    cache_dir = compile_cache.enable()
    cache = CacheCounter()
    jax.monitoring.register_event_listener(cache)
    compile_log: list = []
    jax.monitoring.register_event_duration_secs_listener(
        lambda ev, d, **kw: compile_log.append(d)
        if ev == "/jax/core/compile/backend_compile_duration" else None
    )
    log(
        f"devices: {len(devs)} x {devs[0].device_kind} "
        f"({devs[0].platform}); compile cache {cache_dir}"
    )
    if args.chips == 4:
        run_four_chips(compile_log)
    else:
        run_one_chip(compile_log)
    log(
        f"compile cache: {cache.hits} hit(s) of {cache.requests} "
        f"request(s)"
    )
    print(json.dumps({
        "ok": True,
        "device": {
            "platform": devs[0].platform,
            "kind": devs[0].device_kind,
            "count": len(devs),
        },
    }), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
