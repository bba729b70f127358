"""Headline benchmark: particles redistributed per second per chip.

Prints ONE JSON line:
  {"metric": "particles_per_sec_per_chip", "value": N, "unit": "particles/s",
   "vs_baseline": N}

North star (BASELINE.json / BASELINE.md): >=10x particles/sec vs 8-rank CPU
MPI on the redistribute pipeline. mpi4py is not installed here (SURVEY.md
§4), so the baseline denominator is the pure-NumPy 8-rank oracle — the same
digitize -> pack -> Alltoallv-semantics exchange the MPI path runs, minus
the wire (favorable to the baseline: zero comm cost). ``vs_baseline`` is
(our aggregate particles/sec) / (8-rank CPU aggregate particles/sec); >=10
means the north star is met.

Workload: the periodic drift loop (SURVEY.md §3.3, the steady-state
redistribution workload) over a 2x2x2 Cartesian grid of subdomains with
particles genuinely crossing subdomain boundaries every step. On one chip
the 8 subdomains run as virtual ranks (vmapped slabs + on-device exchange);
with >=8 devices they run one per device with the all_to_all on the wire.
Timing uses scan-compiled loops of two lengths and differences them, which
cancels compile, dispatch and transfer overhead. It runs on a TPU only: on
any other platform it exits non-zero before measuring anything.

Env overrides: BENCH_N_LOCAL (particles per subdomain), BENCH_MIGRATION
(target per-step migration fraction, default 0.02 — a
generous rate for drift steps, which move particles well under a cell width), BENCH_S1/BENCH_S2
(loop lengths), BENCH_BASELINE_N (CPU-oracle total particles; defaults to
the device run's total so numerator and denominator price the same
population), BENCH_GRID (comma grid shape, default "2,2,2" — "4,4,4" with
the default n_local is the BASELINE north-star 64M-particle workload, run
as 64 vranks on one chip when fewer devices exist), BENCH_STRESS (0
disables the full-reshuffle stress capture appended under "stress").
"""

from __future__ import annotations

import json
import math
import os
import sys
import time

import numpy as np

GRID = tuple(
    int(x) for x in os.environ.get("BENCH_GRID", "2,2,2").split(",")
)
R = math.prod(GRID)


def _stderr(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


FILL = 0.9  # fraction of slots occupied; holes give arrival headroom


def _initial_state(n_local: int, migration: float, rng):
    """Shared slab placement (bench.common) + velocities sized so
    ~``migration`` of live rows cross a subdomain face per step (dt=1)."""
    from mpi_grid_redistribute_tpu.bench import common

    v_scale, _, _ = common.drift_sizing(GRID, n_local, FILL, migration)
    return common.uniform_state(GRID, n_local, FILL, rng, vel_scale=v_scale)


def time_device_pipeline(n_local: int, migration: float, s1: int, s2: int):
    import jax
    import jax.numpy as jnp

    from mpi_grid_redistribute_tpu.domain import Domain, ProcessGrid
    from mpi_grid_redistribute_tpu.models import nbody
    from mpi_grid_redistribute_tpu.parallel import mesh as mesh_lib

    devs = jax.devices()
    domain = Domain(0.0, 1.0, periodic=True)
    # one rank per device when there are enough devices, else the whole
    # grid as vranks on ONE device; the log line below names how many of
    # the visible devices the run used
    if len(devs) >= R:
        dev_grid, vgrid, n_chips = ProcessGrid(GRID), None, R
        mesh = mesh_lib.make_mesh(dev_grid, devices=devs[:R])
    else:
        dev_grid, vgrid, n_chips = (
            ProcessGrid((1, 1, 1)),
            ProcessGrid(GRID),
            1,
        )
        mesh = mesh_lib.make_mesh(dev_grid, devices=devs[:1])

    # capacity per (source, dest) pair: migrants spread over the distinct
    # face neighbors, modest headroom (spikes backlog harmlessly and retry
    # next step); budget bounds the compact on-device routing
    # (bench.common.drift_sizing is the shared sizing policy)
    from mpi_grid_redistribute_tpu.bench import common as bcommon

    _, cap, budget = bcommon.drift_sizing(GRID, n_local, FILL, migration)
    cfg = nbody.DriftConfig(
        domain=domain, grid=dev_grid, dt=1.0, capacity=cap,
        n_local=n_local, local_budget=budget,
    )

    rng = np.random.default_rng(0)
    pos, vel, alive = _initial_state(n_local, migration, rng)
    # transfer FLAT: any [N, 3] array crossing a program boundary (even an
    # eager reshape) materializes the tiled T(8,128) layout — 42.7x
    # padding, 32 GB at 64M particles; the migrate loop takes flat input
    pos, vel, alive = (
        jax.device_put(jnp.asarray(nbody.rows_to_planar(pos, mesh.size))),
        jax.device_put(jnp.asarray(nbody.rows_to_planar(vel, mesh.size))),
        jax.device_put(jnp.asarray(alive)),
    )

    from mpi_grid_redistribute_tpu.utils import profiling

    t0 = time.perf_counter()
    # min-of-k protocol (telemetry.regress): k independent long-loop runs
    # give per-step samples; min is the estimate, spread the noise floor
    detail, long_out = profiling.scan_time_per_step_samples(
        lambda S: nbody.make_migrate_loop(cfg, mesh, S, vgrid=vgrid),
        (pos, vel, alive),
        s1=s1,
        s2=s2,
        reps=int(os.environ.get("BENCH_REPS", 4)),
    )
    per_step = detail["min"]
    c1 = time.perf_counter() - t0  # includes both compiles
    stats = long_out[3]
    sent = np.asarray(stats.sent).sum(axis=1)
    backlog = np.asarray(stats.backlog).sum()
    dropped = np.asarray(stats.dropped_recv).sum()
    total = int(FILL * n_local) * R
    # Exchange bandwidth (the second half of the BASELINE metric): bytes
    # of migrant payload crossing the exchange per step. K fused f32
    # columns per row (pos 3 + vel 3 + alive 1). On one chip the vrank
    # exchange is HBM-side (routing gathers/scatters, no wire); with >=8
    # devices the same rows ride the ICI all_to_all.
    row_bytes = 4 * (2 * 3 + 1)
    xbytes = profiling.exchange_bytes_per_step(stats, row_bytes)
    xdomain = "ici" if n_chips > 1 else "hbm"
    _stderr(
        f"device: {n_chips} of {len(devs)} visible {devs[0].device_kind} "
        f"device(s), grid {GRID}"
        + (f" as vranks {vgrid.shape}" if vgrid else "")
        + f", n/slab={n_local}, cap/pair={cap}, first compile {c1:.0f}s"
    )
    _stderr(
        f"  per-step {per_step*1e3:.2f} ms (spread "
        f"{detail['spread']*100:.1f}% over k={detail['k']}); "
        f"migration/step "
        f"{sent.mean()/total:.3%} (backlog {backlog}, dropped {dropped}); "
        f"exchange {xbytes/1e6:.2f} MB/step ({xdomain})"
    )
    if dropped:
        _stderr("  WARNING: arrivals dropped — raise slab headroom")
    # BENCH_JOURNAL_DIR=dir: journal the already-fetched stats and write
    # this process's shard for pod-wide aggregation (ISSUE 5) — zero
    # extra device reads, stats/per_step are host values at this point
    if os.environ.get("BENCH_JOURNAL_DIR"):
        from mpi_grid_redistribute_tpu import telemetry

        rec = telemetry.StepRecorder()
        telemetry.record_migrate_steps(rec, stats, rank_totals=True)
        if stats.fast_path is not None:
            telemetry.record_fast_path_steps(rec, stats)
        acc = telemetry.FlowAccumulator()
        acc.update(stats)
        telemetry.record_flow_snapshot(rec, acc)
        telemetry.HealthMonitor(rec).note_step_time(per_step)
        bcommon.write_journal_shard(rec, "bench_headline")
    return total / per_step, n_chips, xbytes, xdomain, per_step, detail


def time_cpu_oracle(n_total: int, migration: float, n_steps: int = 5,
                    native_ok: bool = False):
    """8-rank CPU oracle drift loop — the CPU-MPI stand-in.

    ``native_ok=False`` (the baseline) runs the reference-equivalent
    pipeline: NumPy digitize + stable argsort + buffer copies, i.e. what
    the mpi4py utility does minus the wire. ``native_ok=True`` uses this
    repo's own C++ host runtime — a STRONGER comparator than the
    reference, reported alongside for honesty."""
    from mpi_grid_redistribute_tpu.domain import Domain, ProcessGrid
    from mpi_grid_redistribute_tpu import oracle

    grid = ProcessGrid(GRID)
    domain = Domain(0.0, 1.0, periodic=True)
    n_local = n_total // R
    cap = n_local
    rng = np.random.default_rng(0)
    pos, vel, _ = _initial_state(n_local, migration, rng)
    # same FILL as the device run: keep only the live prefix per slab
    n_live = int(FILL * n_local)
    keep = np.tile(np.arange(n_local) < n_live, R)
    pos, vel = pos[keep], vel[keep]
    n_local = n_live
    count = np.full((R,), n_local, dtype=np.int32)

    def one_step(pos, vel, count):
        pos = (pos + vel * np.float32(1.0)) % np.float32(1.0)
        pos, count, (vel,), _stats = oracle.redistribute_oracle_padded(
            domain, grid, pos, count, [vel], cap, n_local,
            native_ok=native_ok,
        )
        return pos, vel, count

    pos, vel, count = one_step(pos, vel, count)  # warm
    t0 = time.perf_counter()
    for _ in range(n_steps):
        pos, vel, count = one_step(pos, vel, count)
    dt = (time.perf_counter() - t0) / n_steps
    return (R * n_local) / dt


def main() -> None:
    import jax

    devs = jax.devices()
    if devs[0].platform != "tpu":
        _stderr(f"bench.py: needs a TPU, found {devs[0].platform!r}")
        sys.exit(2)

    from mpi_grid_redistribute_tpu.analysis import baseline as baseline_lib
    from mpi_grid_redistribute_tpu.telemetry import regress
    from mpi_grid_redistribute_tpu.utils import compile_cache, profiling

    compile_cache.enable()
    n_local = int(os.environ.get("BENCH_N_LOCAL", 2**20))
    migration = float(os.environ.get("BENCH_MIGRATION", 0.02))
    s1 = int(os.environ.get("BENCH_S1", 8))
    s2 = int(os.environ.get("BENCH_S2", 72))
    # default the CPU comparator to the DEVICE run's population, so
    # vs_baseline divides throughputs over the same workload (the old
    # fixed 2**21 silently compared different populations whenever
    # BENCH_N_LOCAL changed)
    baseline_n = int(os.environ.get("BENCH_BASELINE_N", R * n_local))

    pps, n_chips, xbytes, xdomain, per_step, detail = time_device_pipeline(
        n_local, migration, s1, s2
    )
    pps_per_chip = pps / n_chips
    _stderr(f"device pipeline: {pps:.3e} particles/s aggregate")

    cpu_pps = time_cpu_oracle(baseline_n, migration, native_ok=False)
    _stderr(
        f"8-rank CPU baseline (reference-equivalent numpy): "
        f"{cpu_pps:.3e} particles/s"
    )
    from mpi_grid_redistribute_tpu.utils import native

    native.build()  # explicit opt-in; falls back to NumPy with a log line
    cpu_native_pps = time_cpu_oracle(baseline_n, migration, native_ok=True)
    _stderr(
        f"8-rank CPU with our C++ host runtime"
        f"{'' if native.available() else ' (FALLBACK: numpy)'}: "
        f"{cpu_native_pps:.3e} particles/s"
    )

    # full-reshuffle stress capture (bench/config7_stress.py): what
    # utilization the exchange reaches when ~every row moves every step —
    # the drift loop above is compute-bound at 2% migration, so its
    # bw_util says nothing about the exchange's own roof-side headroom
    stress = None
    if os.environ.get("BENCH_STRESS", "1") != "0":
        from mpi_grid_redistribute_tpu.bench import config7_stress

        stress = config7_stress.run()

    # service soak capture (bench/config8_soak.py): sustained throughput
    # through the full service loop with the checkpoint cadence ON, plus
    # the crash/restore leg — guards soak_pps and keeps the <= 2%
    # snapshot-overhead budget honest across PRs
    soak = None
    if os.environ.get("BENCH_SOAK", "1") != "0":
        from mpi_grid_redistribute_tpu.bench import config8_soak

        soak = config8_soak.run()

    # closed-loop adaptive-rebalance capture (bench/config4_drift
    # .run_rebalance): twin drift-bias runs with the loop on/off —
    # guards rebalance_drift_ms (LOWER) so the one-shot remap keeps
    # paying for itself across PRs; CPU-only (numpy backend), so the
    # capture is deterministic modulo host timing noise
    rebalance = None
    if os.environ.get("BENCH_REBALANCE", "1") != "0":
        from mpi_grid_redistribute_tpu.bench import config4_drift

        rebalance = config4_drift.run_rebalance()

    # resident chunked-stepping capture (bench/config10_service.py):
    # service-mode pps with lax.scan macro-steps vs the eager per-step
    # loop — guards service_pps so the chunk path keeps paying for the
    # host syncs it removed, and pipeline_pps (the software-pipelined
    # scan body at the same chunk) so the overlapped schedule keeps its
    # edge over the sequential body; measured in this process
    service = None
    if os.environ.get("BENCH_SERVICE", "1") != "0":
        from mpi_grid_redistribute_tpu.bench import config10_service

        service = config10_service.run()

    # hierarchical two-level wire capture (bench/config4_drift
    # .hierarchical_wire_capture, ISSUE 19): the same ~2% drift workload
    # through the two-level engine on a virtual 2x(1,2,2)-pod split —
    # the per-domain schedule split lands top-level so regress.py's
    # auto-armed LOWER gates (exchange_dcn_bytes_per_step /
    # exchange_ici_bytes_per_step) read it from this capture too
    hier = None
    if os.environ.get("BENCH_HIER", "1") != "0":
        from mpi_grid_redistribute_tpu.bench import config4_drift

        hier = config4_drift.hierarchical_wire_capture(
            (2, 2, 2), (2, 1, 1), migration
        )

    print(
        json.dumps(
            {
                "metric": "particles_per_sec_per_chip",
                "value": round(pps_per_chip, 2),
                "unit": "particles/s",
                "device": {
                    "platform": devs[0].platform,
                    "kind": devs[0].device_kind,
                    "count": len(devs),
                },
                "vs_baseline": round(pps / cpu_pps, 3),
                "vs_our_native_cpu": round(pps / cpu_native_pps, 3),
                # comparator provenance: the population both CPU rates
                # timed, and the rates themselves, so vs_* is reproducible
                # from the capture alone
                "baseline_n": baseline_n,
                "cpu_pps": round(cpu_pps, 2),
                "cpu_native_pps": round(cpu_native_pps, 2),
                "ms_per_step": round(per_step * 1e3, 3),
                # min-of-k noise floor: (max-min)/min over k long-loop
                # runs (telemetry.regress protocol) — a capture whose
                # spread rivals the 10% regression threshold is suspect
                "timing_spread": round(detail["spread"], 4),
                "timing_k": detail["k"],
                # BASELINE metric's second half: exchange bandwidth. On a
                # single chip the vrank exchange never leaves HBM
                # (exchange_domain = "hbm"); on >=8 chips the same rows
                # ride the ICI all_to_all (= "ici").
                "exchange_bytes_per_step": round(xbytes, 1),
                "exchange_bytes_per_sec": round(xbytes / per_step, 1),
                "exchange_domain": xdomain,
                # Utilization = bytes/s vs the domain's peak (HBM 819 GB/s
                # on one chip; 4x45 GB/s summed ICI links per chip on >=8).
                # Low by design at the default 2% migration rate: the
                # exchange moves only migrant payload, so the step is
                # compute-bound (see knockout roofline, BENCH_CONFIGS.md).
                "exchange_bw_util": round(
                    profiling.exchange_bw_util(
                        xbytes / per_step, xdomain, n_chips,
                        devs[0].device_kind,
                    ),
                    6,
                ),
                "stress": stress,
                "soak": soak,
                "rebalance": rebalance,
                "service": service,
                "hier": hier,
                "exchange_dcn_bytes_per_step": (
                    hier.get("dcn_bytes_per_step") if hier else None
                ),
                "exchange_ici_bytes_per_step": (
                    hier.get("ici_bytes_per_step") if hier else None
                ),
                # environment fingerprint (telemetry.regress): the
                # classifier flags cross-capture deltas whose machine
                # changed out from under them
                "env": regress.env_fingerprint(),
                # progcheck static wire-model hash (analysis.baseline):
                # lets bench_check tell a perf delta that coincides with
                # an intentional wire/footprint change from one that
                # doesn't (see classify_capture's drift note)
                "progprofile_hash": baseline_lib.progprofile_hash(),
            }
        )
    )


if __name__ == "__main__":
    main()
