"""Config 1 (BASELINE.json): 1M uniform particles, 2x2x2 grid — the
correctness-oracle config. Runs the one-shot ``redistribute()`` on the JAX
backend, proves bit-equality against the NumPy rank-simulation oracle
(SURVEY.md §7.4), and reports JAX-path throughput.
"""

from __future__ import annotations

import math
import os
import time

import numpy as np

from mpi_grid_redistribute_tpu import GridRedistribute, Domain
from mpi_grid_redistribute_tpu.bench import common


def run(n_total: int = None, reps: int = 3) -> dict:
    import jax

    n_total = n_total or int(
        float(os.environ.get("BENCH_SCALE", 1.0)) * (1 << 20)
    )
    grid_shape = (2, 2, 2)
    R = 8
    devs = jax.devices()
    if len(devs) < R:
        grid_shape = (1, 1, 1)
        R = 1
        common.log("config1: <8 devices, shrinking grid to 1 rank")
    n_local = n_total // R
    rng = np.random.default_rng(42)
    pos = rng.random((R * n_local, 3), dtype=np.float32)
    vel = rng.standard_normal((R * n_local, 3)).astype(np.float32)
    ids = np.arange(R * n_local, dtype=np.int32)

    kw = dict(
        domain=None, lo=0.0, hi=1.0, periodic=True,
        capacity_factor=4.0,
    )
    rd = GridRedistribute(grid=grid_shape, backend="jax", **kw)
    res = rd.redistribute(pos, vel, ids)
    rd_np = GridRedistribute(grid=grid_shape, backend="numpy", **kw)
    res_np = rd_np.redistribute(pos, vel, ids)
    bit_equal = (
        np.asarray(res.positions).tobytes() == res_np.positions.tobytes()
        and np.asarray(res.count).tobytes() == res_np.count.tobytes()
        and all(
            np.asarray(a).tobytes() == np.asarray(b).tobytes()
            for a, b in zip(res.fields, res_np.fields)
        )
    )
    if not bit_equal:
        raise AssertionError("config1: JAX backend != oracle at bit level")

    t = common.timeit_fetch(
        lambda p: rd.redistribute(p, vel, ids).positions, (pos,), reps=reps
    )
    # resolve the deferred overflow windows NOW (device fetch at a known
    # point) instead of warning from __del__ at interpreter teardown
    rd.flush_overflow_checks()
    rd_np.flush_overflow_checks()

    # Scan-differenced device time of the CANONICAL exchange (VERDICT
    # round-1 item 3): a drift loop whose every step runs the full
    # Alltoallv-ordered pipeline — bin, stable sort, pack, exchange,
    # canonical compaction — on 8 vranks of one device (or 8 devices when
    # available via the migrate-comparable layout). Unlike the per-call
    # timing above, the fixed dispatch overhead cancels.
    import jax.numpy as jnp
    from jax import lax
    from mpi_grid_redistribute_tpu.domain import Domain, ProcessGrid
    from mpi_grid_redistribute_tpu.ops import binning
    from mpi_grid_redistribute_tpu.parallel import exchange
    from mpi_grid_redistribute_tpu.utils import profiling

    vR = 8
    vgrid = ProcessGrid((2, 2, 2))
    domain = Domain(0.0, 1.0, periodic=True)
    n_loc = max(1024, n_total // vR)
    # receive headroom: per-vrank arrivals fluctuate around n_loc, so a
    # zero-headroom out_capacity drops arrivals near-certainly; slots
    # beyond count are padding, not particles
    slots = int(n_loc * 1.25)
    migration = 0.02
    rng2 = np.random.default_rng(1)
    from mpi_grid_redistribute_tpu.bench import common as bcommon

    # steady state: rows start on their owner slab and ~2% cross a face
    # per step; the canonical pipeline still re-sorts and re-packs EVERY
    # row every step (that is its contract), but per-pair capacity — and
    # with it the padded pool the compaction sorts — is drift-sized, not
    # cold-start-sized.
    p0, v0, _ = bcommon.uniform_state(
        (2, 2, 2), n_loc, 1.0, rng2,
        vel_scale=migration / 3.0 * 2.0 / np.asarray((2, 2, 2), np.float32),
    )
    posv = np.zeros((vR, slots, 3), np.float32)
    velv = np.zeros((vR, slots, 3), np.float32)
    posv[:, :n_loc] = p0.reshape(vR, n_loc, 3)
    velv[:, :n_loc] = v0.reshape(vR, n_loc, 3)
    countv = np.full((vR,), n_loc, np.int32)
    cap = max(64, math.ceil(n_loc * migration / 3 * 2.5))
    xfn = exchange.vrank_redistribute_fn(domain, vgrid, cap, slots)

    def make_loop(S):
        @jax.jit
        def loop(pos, vel, count):
            def body(carry, _):
                p, v, c = carry
                p = binning.wrap_periodic(
                    p + v * jnp.float32(1.0), domain
                )
                p, c, v, stats = xfn(p, c, v)
                return (p, v, c), stats.dropped_send + stats.dropped_recv
            (p, v, c), drops = lax.scan(
                body, (pos, vel, count), None, length=S
            )
            return p, v, c, drops
        return loop

    per_step, _, long_out = profiling.scan_time_per_step(
        make_loop,
        (jnp.asarray(posv), jnp.asarray(velv), jnp.asarray(countv)),
        s1=4,
        s2=20,
    )
    assert int(np.asarray(long_out[3]).sum()) == 0, "canonical loop lost rows"
    assert int(np.asarray(long_out[2]).sum()) == vR * n_loc

    # The PLANAR canonical engine (round-3, verdict item 4): identical
    # routing/order/bits, but the payload rides [V, K, n] component-major,
    # so no [n, 3] buffer pays the 42.7x T(8,128) tile padding the
    # row-major engine's gathers and carries are bound by.
    xfn_p = exchange.vrank_redistribute_planar_fn(domain, vgrid, cap, slots)
    fusedv = np.ascontiguousarray(
        np.concatenate(
            [posv.transpose(0, 2, 1), velv.transpose(0, 2, 1)], axis=1
        )
    )  # [V, 6, slots]

    def make_loop_planar(S):
        @jax.jit
        def loop(fused, count):
            def body(carry, _):
                f, c = carry
                p = binning.wrap_periodic_planar(
                    f[:, :3, :] + f[:, 3:6, :] * jnp.float32(1.0), domain
                )
                f = jnp.concatenate([p, f[:, 3:6, :]], axis=1)
                f, c, stats = xfn_p(f, c)
                return (f, c), stats.dropped_send + stats.dropped_recv
            (f, c), drops = lax.scan(body, (fused, count), None, length=S)
            return f, c, drops
        return loop

    per_step_p, _, long_p = profiling.scan_time_per_step(
        make_loop_planar,
        (jnp.asarray(fusedv), jnp.asarray(countv)),
        s1=4,
        s2=20,
    )
    assert int(np.asarray(long_p[2]).sum()) == 0, "planar loop lost rows"
    assert int(np.asarray(long_p[1]).sum()) == vR * n_loc

    # THROUGH the public entry point (VERDICT round-3 item 1 done
    # criterion): the same steady-state drift loop, but every exchange is
    # a real `GridRedistribute.redistribute()` call — engine='auto' routes
    # the planar [K, n] payload-sort engine, and each call's inputs are
    # the previous call's device outputs, so dispatch pipelines and only
    # the final fetch blocks. This prices the full public path: boundary
    # fuse/unfuse transposes + one jitted planar exchange per call.
    rd_api = GridRedistribute(
        lo=0.0, hi=1.0, periodic=True, grid=(2, 2, 2),
        capacity=cap, out_capacity=slots, on_overflow="ignore",
    )
    drift = jax.jit(
        lambda p, v: binning.wrap_periodic(p + v * jnp.float32(1.0), domain)
    )
    api_steps = 24
    warm = 4

    def api_loop(steps, res, vel_a):
        for _ in range(steps):
            p = drift(res.positions, vel_a)
            res = rd_api.redistribute(p, vel_a, count=res.count)
            vel_a = res.fields[0]
        jax.block_until_ready(res.positions)
        return res, vel_a

    res_a = rd_api.redistribute(
        jnp.asarray(posv.reshape(vR * slots, 3)),
        jnp.asarray(velv.reshape(vR * slots, 3)),
        count=jnp.asarray(countv),
    )
    res_a, vel_a = api_loop(warm, res_a, res_a.fields[0])  # warm the jits
    t0 = time.perf_counter()
    res_a, vel_a = api_loop(api_steps, res_a, vel_a)
    api_per_step = (time.perf_counter() - t0) / api_steps
    assert int(np.asarray(res_a.count).sum()) == vR * n_loc, (
        "API loop lost rows"
    )
    assert int(np.asarray(res_a.stats.dropped_send).sum()) == 0
    assert int(np.asarray(res_a.stats.dropped_recv).sum()) == 0
    rd_api.flush_overflow_checks()  # on_overflow='ignore' makes this a
    # no-op today, but the driver contract is: no unresolved windows left
    api_report = rd_api.report(step_seconds=api_per_step)
    common.write_journal_shard(rd_api.telemetry, "config1_oracle")

    out = {
        "metric": "config1_redistribute_pps",
        "value": round(vR * n_loc / per_step_p, 2),
        "unit": "particles/s",
        # which engine the headline number measures (the planar
        # payload-sort engine since round 3 — round-over-round dashboards
        # should not read the 2.2x round-2->3 jump as same-engine gains)
        "engine": "planar",
        "bit_equal_vs_oracle": True,
        "n_total": n_total,  # one-shot bit-equality check population
        "ranks": R,
        # the canonical scan loop sizes itself independently (>=1024
        # rows/vrank); 'value' is rows/sec over THIS population
        "canonical_rows": vR * n_loc,
        "canonical_ms_per_step": round(per_step_p * 1e3, 3),
        "canonical_rowmajor_ms_per_step": round(per_step * 1e3, 3),
        "canonical_vranks": vR,
        # the public GridRedistribute.redistribute() path, per call, in a
        # pipelined steady-state loop (includes boundary fuse/unfuse and
        # per-call dispatch; the scan number above is the engine alone)
        "api_ms_per_step": round(api_per_step * 1e3, 3),
        "api_pps": round(vR * n_loc / api_per_step, 2),
        # merged telemetry surface for the public-API loop (rd.report():
        # stats summary + bytes/step + bw_util + recorder event counts)
        "api_report": api_report,
    }
    common.log(f"config1: {t*1e3:.1f} ms/call (incl. dispatch overhead)")
    common.log(
        f"config1: canonical exchange planar {per_step_p*1e3:.2f} vs "
        f"row-major {per_step*1e3:.2f} ms/step on-device "
        f"({vR} vranks x {n_loc} rows, scan-differenced); public API "
        f"{api_per_step*1e3:.2f} ms/call (pipelined loop)"
    )
    return out


if __name__ == "__main__":
    common.emit(run())
