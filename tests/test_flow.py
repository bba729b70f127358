"""Grid observatory (telemetry/flow.py, health.py, traceview.py).

Three layers, each tested against hand math or the engines themselves:

* flow — the [R, R] matrix's row sums must equal ``sent`` and column
  sums ``received`` EXACTLY on every engine path (sends are
  receiver-granted, so both sides agree by construction), and its
  capture must add zero host callbacks to the scanned step (jaxpr
  assertion).
* health — declarative rules over journal events; synthetic event
  sequences drive each rule and the alert/callback/dedup contract.
* traceview — output must be valid Chrome-trace JSON (every event
  carries ``ph``/``pid``, non-metadata events carry ``ts``).
"""

import json

import numpy as np
import pytest

import jax

from mpi_grid_redistribute_tpu.domain import Domain, ProcessGrid
from mpi_grid_redistribute_tpu.models import nbody
from mpi_grid_redistribute_tpu.parallel import mesh as mesh_lib
from mpi_grid_redistribute_tpu.parallel.migrate import MigrateStats
from mpi_grid_redistribute_tpu.telemetry import (
    FlowAccumulator,
    HealthMonitor,
    StepRecorder,
    default_rules,
    flow_matrix_of,
    record_flow_snapshot,
    record_migrate_steps,
    to_chrome_trace,
    write_trace,
)
from mpi_grid_redistribute_tpu.telemetry import flow as flow_lib
from mpi_grid_redistribute_tpu.telemetry import health as health_lib

DOMAIN = Domain(0.0, 1.0, periodic=True)


# ------------------------------------------------------------ hand math


def _stats2(flow_steps, population):
    """Build a 2-rank step-stacked MigrateStats from hand flow matrices."""
    f = np.asarray(flow_steps, np.int32)  # [S, 2, 2]
    return MigrateStats(
        sent=f.sum(axis=2),
        received=f.sum(axis=1),
        population=np.asarray(population, np.int32),
        backlog=np.zeros_like(f.sum(axis=2)),
        dropped_recv=np.zeros_like(f.sum(axis=2)),
        flow=f,
    )


def test_flow_accumulator_hand_math():
    # step 1: rank0 sends 3 to rank1; step 2: 1 back, 5 forward
    stats = _stats2(
        [[[0, 3], [0, 0]], [[0, 5], [1, 0]]],
        [[7, 3], [4, 6]],
    )
    acc = FlowAccumulator(ema_alpha=0.5)
    acc.update(stats)
    np.testing.assert_array_equal(
        acc.cumulative, np.asarray([[0, 8], [1, 0]])
    )
    # EMA seeded with step 1, then 0.5-blended with step 2
    np.testing.assert_allclose(
        acc.ema, np.asarray([[0.0, 4.0], [0.5, 0.0]])
    )
    assert acc.steps == 2
    # imbalance from the LAST step's population: max/mean of [4, 6]
    assert acc.imbalance == pytest.approx(6.0 / 5.0)
    # hot pairs: cumulative, descending, deterministic
    assert acc.top_pairs(k=5) == [(0, 1, 8), (1, 0, 1)]
    snap = acc.snapshot(k=1)
    assert snap["moved_rows_total"] == 9
    assert snap["n_ranks"] == 2
    assert snap["top_pairs"] == [[0, 1, 8]]
    json.dumps(snap)  # journal-able


def test_imbalance_gauge_empty_and_partial_population():
    """Hand math for the zero/partial-population edges: an ALL-empty
    system is perfectly balanced (1.0, not the old 0.0 never-fed
    sentinel), and a SOME-ranks-empty population still reads max/mean —
    the empty ranks push the ratio UP, they don't reset it."""
    acc = FlowAccumulator()
    assert acc.imbalance == 0.0  # never fed: the 0.0 sentinel stands
    acc.update(np.zeros((2, 2), np.int64), population=[0, 0])
    assert acc.imbalance == 1.0  # all-empty = balanced
    assert acc.snapshot()["population"] == [0, 0]
    # partial: [0, 6] -> mean 3, max 6 -> 2.0 (NOT 1.0, NOT 0.0)
    acc.update(np.zeros((2, 2), np.int64), population=[0, 6])
    assert acc.imbalance == pytest.approx(2.0)
    assert acc.snapshot()["population"] == [0, 6]
    # [S, R] population: only the LAST step's gauge sticks
    acc.update(
        np.zeros((2, 2, 2), np.int64), population=[[9, 1], [4, 4]]
    )
    assert acc.imbalance == pytest.approx(1.0)
    assert acc.snapshot()["population"] == [4, 4]
    with pytest.raises(ValueError, match="non-negative"):
        acc.update(np.zeros((2, 2), np.int64), population=[3, -1])


def test_snapshot_population_none_until_fed():
    acc = FlowAccumulator()
    acc.update(np.asarray([[0, 2], [1, 0]], np.int64))  # raw matrix,
    # no population gauge rides along
    snap = acc.snapshot()
    assert snap["population"] is None
    assert snap["imbalance"] == 0.0
    json.dumps(snap)


def test_top_pairs_ordering_diag_and_zeros():
    m = np.asarray([[9, 4, 0], [4, 9, 2], [0, 0, 9]])
    # diagonal excluded by default; tie (0,1) vs (1,0) breaks toward the
    # lower (src, dst); zero links never reported even when k allows
    assert flow_lib.top_pairs(m, k=10) == [
        (0, 1, 4), (1, 0, 4), (1, 2, 2)
    ]
    assert flow_lib.top_pairs(m, k=1, include_diag=True) == [(0, 0, 9)]
    with pytest.raises(ValueError):
        flow_lib.top_pairs(np.zeros((2, 3)))


def test_flow_matrix_of_validation():
    stats = _stats2([[[0, 1], [2, 0]]], [[3, 3]])
    m = flow_matrix_of(stats)
    assert m.shape == (1, 2, 2) and m.dtype == np.int64
    # hand-built fixture without the flow leaf is a named error
    with pytest.raises(ValueError, match="flow is None"):
        flow_matrix_of(stats._replace(flow=None))
    with pytest.raises(TypeError):
        flow_matrix_of(object())
    acc = FlowAccumulator(n_ranks=4)
    with pytest.raises(ValueError, match="built for 4 ranks"):
        acc.update(stats)


def test_link_report_per_link_bw():
    m = np.asarray([[0.0, 100.0], [25.0, 0.0]])
    rep = flow_lib.link_report(m, row_bytes=28, step_seconds=1e-3)
    assert rep["domain"] == "ici"
    top = rep["links"][0]
    assert (top["src"], top["dst"]) == (0, 1)
    assert top["bytes_per_step"] == pytest.approx(2800.0)
    assert top["bytes_per_sec"] == pytest.approx(2.8e6)
    assert top["bw_util"] == pytest.approx(
        2.8e6 / rep["link_roof_bytes_per_sec"]
    )
    # without step_seconds the rate fields stay None, never guessed
    rep2 = flow_lib.link_report(m, row_bytes=28)
    assert rep2["links"][0]["bw_util"] is None


# ------------------------------------- engine exactness (CPU mesh, 8 dev)


def _run_loop(grid_shape, vgrid, n_steps, rng):
    grid = ProcessGrid(grid_shape)
    R = grid.nranks
    n_local = 64
    n = R * n_local
    mesh = mesh_lib.make_mesh(grid)
    pos = rng.random((n, 3), dtype=np.float32)
    vel = (0.6 * (rng.random((n, 3), dtype=np.float32) - 0.5)).astype(
        np.float32
    )
    alive = rng.random(n) > 0.125
    cfg = nbody.DriftConfig(
        domain=DOMAIN, grid=grid, dt=0.07, capacity=n_local,
        n_local=n_local,
    )
    loop = nbody.make_migrate_loop(cfg, mesh, n_steps, vgrid=vgrid)
    _, _, _, stats = jax.tree.map(np.asarray, loop(pos, vel, alive))
    return stats


@pytest.mark.parametrize("grid_shape", [(2, 2, 2), (4, 2, 1)])
def test_flow_row_col_sums_exact_multidevice(grid_shape, rng, _devices):
    """8-device shard_map path: flow rows == sent, columns == received,
    bit-exact, every step."""
    stats = _run_loop(grid_shape, None, 5, rng)
    m = flow_matrix_of(stats)
    np.testing.assert_array_equal(m.sum(axis=2), np.asarray(stats.sent))
    np.testing.assert_array_equal(
        m.sum(axis=1), np.asarray(stats.received)
    )
    # movers only: the diagonal is structurally zero on the migrate path
    assert np.einsum("sii->s", m).sum() == 0


def test_flow_row_col_sums_exact_vranks(rng, _devices):
    """Vranks twin (2 devices x 8 vranks each): same exactness through
    the remote-overlay flow rows (local ``allowed`` table + remote
    granted-send rows stitched at the device's vrank offset)."""
    vgrid = ProcessGrid((2, 2, 2))
    dev_grid = ProcessGrid((2, 1, 1))
    mesh = mesh_lib.make_mesh(dev_grid)
    n_local = 64
    R_total = mesh.size * vgrid.nranks  # 16 global vranks
    n = R_total * n_local
    pos = rng.random((n, 3), dtype=np.float32)
    vel = (0.6 * (rng.random((n, 3), dtype=np.float32) - 0.5)).astype(
        np.float32
    )
    alive = rng.random(n) > 0.125
    cfg = nbody.DriftConfig(
        domain=DOMAIN, grid=dev_grid, dt=0.07, capacity=n_local,
        n_local=n_local,
    )
    loop = nbody.make_migrate_loop(cfg, mesh, 4, vgrid=vgrid)
    stats = jax.tree.map(np.asarray, loop(pos, vel, alive))[3]
    m = flow_matrix_of(stats)
    assert m.shape == (4, R_total, R_total)
    np.testing.assert_array_equal(m.sum(axis=2), np.asarray(stats.sent))
    np.testing.assert_array_equal(
        m.sum(axis=1), np.asarray(stats.received)
    )


_HOST_SYNC_PRIMS = (
    "callback", "infeed", "outfeed", "host", "debug_print",
)


def _sub_jaxprs(params):
    """Yield every Jaxpr nested in an eqn's params (scan/cond/shard_map
    bodies), whatever container they ride in."""
    stack = list(params.values())
    while stack:
        x = stack.pop()
        if isinstance(x, (list, tuple)):
            stack.extend(x)
        elif hasattr(x, "jaxpr"):  # ClosedJaxpr
            yield x.jaxpr
        elif hasattr(x, "eqns"):  # raw Jaxpr
            yield x


def _assert_no_host_prims(jaxpr, seen):
    for eqn in jaxpr.eqns:
        name = eqn.primitive.name
        seen.add(name)
        assert not any(tok in name for tok in _HOST_SYNC_PRIMS), (
            f"host-syncing primitive {name!r} inside the scanned step — "
            "flow capture must stay pure device work"
        )
        for sub in _sub_jaxprs(eqn.params):
            _assert_no_host_prims(sub, seen)


def test_flow_capture_adds_no_host_sync(rng, _devices):
    """Jit-trace assertion: the whole scanned migrate loop — flow leaf
    included — lowers to pure device ops (no callbacks/infeed/outfeed)."""
    grid = ProcessGrid((2, 2, 2))
    n_local = 32
    n = grid.nranks * n_local
    mesh = mesh_lib.make_mesh(grid)
    cfg = nbody.DriftConfig(
        domain=DOMAIN, grid=grid, dt=0.07, capacity=n_local,
        n_local=n_local,
    )
    loop = nbody.make_migrate_loop(cfg, mesh, 3)
    # pre-convert to the planar flat layout: under make_jaxpr the inputs
    # are tracers, so the loop's numpy-side auto-conversion cannot run
    jaxpr = jax.make_jaxpr(loop)(
        nbody.rows_to_planar(np.zeros((n, 3), np.float32), mesh.size),
        nbody.rows_to_planar(np.zeros((n, 3), np.float32), mesh.size),
        np.ones((n,), bool),
    )
    seen = set()
    _assert_no_host_prims(jaxpr.jaxpr, seen)
    assert "scan" in seen  # we really walked the step loop


# --------------------------------------------------------------- health


def _backlog_events(rec, backlogs):
    for s, b in enumerate(backlogs):
        rec.record(
            "migrate_step", step=s, sent=10, received=10, backlog=b,
            dropped_recv=0, population=100,
        )


def test_backlog_growth_alert_and_callback():
    rec = StepRecorder()
    fired = []
    mon = HealthMonitor(rec, on_alert=fired.append)
    _backlog_events(rec, [0, 5, 9, 14, 20])
    verdict = mon.evaluate()
    assert verdict["status"] == health_lib.ALERT
    assert [f["rule"] for f in verdict["findings"]] == ["backlog_growth"]
    assert "5 -> 20" in verdict["findings"][0]["reason"]
    # callback fired once, and the alert landed in the same ring
    assert len(fired) == 1 and fired[0].rule == "backlog_growth"
    alerts = rec.events("alert")
    assert len(alerts) == 1
    assert alerts[0].data["rule"] == "backlog_growth"
    # dedup: re-evaluating the same evidence must not re-fire
    verdict2 = mon.evaluate()
    assert verdict2["status"] == health_lib.ALERT  # still alerting...
    assert len(fired) == 1 and len(rec.events("alert")) == 1  # ...once
    # new evidence re-arms the rule
    _backlog_events(rec, [22, 25, 29, 31])
    mon.evaluate()
    assert len(fired) == 2


def test_backlog_growth_requires_monotone_and_nonzero():
    rec = StepRecorder()
    mon = HealthMonitor(rec)
    # dips mid-window: healthy retry behavior, no alert
    _backlog_events(rec, [0, 5, 3, 6, 4])
    assert mon.evaluate()["status"] == health_lib.OK
    # drains to zero at the end: no alert either
    rec2 = StepRecorder()
    _backlog_events(rec2, [1, 2, 3, 0])
    assert HealthMonitor(rec2).evaluate()["status"] == health_lib.OK


def test_dropped_rows_and_imbalance_rules():
    rec = StepRecorder()
    rec.record(
        "migrate_step", step=0, sent=5, received=4, backlog=0,
        dropped_recv=1, population=10,
    )
    v = HealthMonitor(rec).evaluate()
    assert v["status"] == health_lib.ALERT
    assert any(f["rule"] == "dropped_rows" for f in v["findings"])

    rec2 = StepRecorder()
    acc = FlowAccumulator()
    # max/mean = 90/30 = 3.0x > the 2.0x threshold
    acc.update(
        np.zeros((4, 4), np.int64),
        population=np.asarray([90, 10, 10, 10]),
    )
    record_flow_snapshot(rec2, acc)
    v2 = HealthMonitor(rec2).evaluate()
    assert v2["status"] == health_lib.WARN
    assert any(f["rule"] == "imbalance_ratio" for f in v2["findings"])


def test_step_time_spike_rule():
    rec = StepRecorder()
    mon = HealthMonitor(rec)
    for _ in range(6):
        mon.note_step_time(0.010)
    assert mon.evaluate()["status"] == health_lib.OK
    mon.note_step_time(0.200)  # 20x the EMA
    v = mon.evaluate()
    assert v["status"] == health_lib.WARN
    assert any(f["rule"] == "step_time_spike" for f in v["findings"])


def test_default_rules_cover_issue_list():
    names = {r.name for r in default_rules()}
    assert names >= {
        "backlog_growth", "dropped_rows", "capacity_grow_frequency",
        "imbalance_ratio", "step_time_spike",
    }


# ------------------------------------------------------------- traceview


def _valid_chrome_trace(trace):
    assert isinstance(trace["traceEvents"], list)
    for e in trace["traceEvents"]:
        assert "ph" in e and "pid" in e, e
        if e["ph"] != "M":  # metadata events carry no timestamp
            assert isinstance(e["ts"], (int, float)), e
        if e["ph"] == "X":
            assert e["dur"] >= 0
    json.loads(json.dumps(trace))  # serializable round trip


def test_chrome_trace_schema(tmp_path):
    rec = StepRecorder()
    rec.record("capacity_grow", old=64, new=128)
    _backlog_events(rec, [0, 3, 7, 9])  # monotone window -> alert event
    mon = HealthMonitor(rec)
    assert mon.evaluate()["status"] == health_lib.ALERT
    acc = FlowAccumulator()
    acc.update(np.asarray([[0, 2], [1, 0]]))
    record_flow_snapshot(rec, acc)
    trace = to_chrome_trace(rec, step_seconds=2e-3)
    _valid_chrome_trace(trace)
    evs = trace["traceEvents"]
    by_ph = {}
    for e in evs:
        by_ph.setdefault(e["ph"], []).append(e)
    # instants cover every journal kind, alerts included
    kinds = {e["name"] for e in by_ph["i"]}
    assert kinds >= {"capacity_grow", "migrate_step", "alert",
                     "flow_snapshot"}
    # two track families: the journal and the migrate counters
    assert {e["pid"] for e in evs} == {0, 2}
    assert "X" not in by_ph
    # counter track uses the measured synthetic step time
    counters = [e for e in by_ph["C"] if e["name"] == "backlog"]
    assert [c["ts"] for c in counters] == [0.0, 2e3, 4e3, 6e3]
    assert [c["args"]["backlog"] for c in counters] == [0, 3, 7, 9]
    # file round trip
    path = tmp_path / "trace.json"
    n = write_trace(str(path), rec)
    reloaded = json.loads(path.read_text())
    assert len(reloaded["traceEvents"]) == n
    _valid_chrome_trace(reloaded)


def test_trace_export_cli(tmp_path):
    import subprocess
    import sys as _sys

    rec = StepRecorder()
    _backlog_events(rec, [0, 1])
    jsonl = tmp_path / "journal.jsonl"
    rec.to_jsonl(str(jsonl))
    out = tmp_path / "trace.json"
    proc = subprocess.run(
        [_sys.executable, "scripts/trace_export.py",
         "--journal", str(jsonl), "--out", str(out)],
        capture_output=True, text=True, timeout=120,
    )
    assert proc.returncode == 0, proc.stderr
    _valid_chrome_trace(json.loads(out.read_text()))


# ------------------------------------------------- public API + drift loop


def test_rd_flow_health_perfetto(tmp_path, rng, _devices):
    from mpi_grid_redistribute_tpu import GridRedistribute

    pos = rng.random((1024, 3), dtype=np.float32)
    with GridRedistribute(lo=0.0, hi=1.0, grid=(2, 2, 2),
                          capacity_factor=4.0) as rd:
        with pytest.raises(RuntimeError):
            rd.flow()
        res = rd.redistribute(pos)
        fl = rd.flow(k=3)
        m = np.asarray(fl["matrix"])
        send = np.asarray(res.stats.send_counts)
        np.testing.assert_array_equal(m, send.astype(np.int64))
        assert fl["imbalance"] >= 1.0
        assert len(fl["hot_links"]) <= 3
        # flow() journaled a snapshot; health sees a balanced exchange
        assert rd.telemetry.counts().get("flow_snapshot") == 1
        assert rd.health()["status"] == "OK"
        path = tmp_path / "api_trace.json"
        n = rd.to_perfetto(str(path))
        assert n > 0
        _valid_chrome_trace(json.loads(path.read_text()))


def test_config4_emits_health_and_flow(_devices):
    """BASELINE.json config 4, the balanced drift loop, on 8 devices and
    journaled step by step: the health verdict stays OK, the flow gauges
    span all 8 ranks, the exchange report carries its per-link section,
    and everything is JSON-serialisable."""
    import time

    from mpi_grid_redistribute_tpu import telemetry
    from mpi_grid_redistribute_tpu.models import initial

    grid_shape, n_local, steps = (2, 2, 2), 4096, 16
    dev_grid, vgrid, mesh, n_chips = initial.pick_layout(grid_shape)
    v_scale, cap, budget = initial.drift_sizing(grid_shape, n_local, 0.9, 0.02)
    pos, vel, alive = initial.uniform_state(
        grid_shape, n_local, 0.9, np.random.default_rng(0), vel_scale=v_scale
    )
    cfg = nbody.DriftConfig(
        domain=DOMAIN, grid=dev_grid, dt=1.0, capacity=cap,
        n_local=n_local, local_budget=budget,
    )
    loop = nbody.make_migrate_loop(cfg, mesh, steps, vgrid=vgrid)
    args = (
        nbody.rows_to_planar(pos, mesh.size),
        nbody.rows_to_planar(vel, mesh.size),
        alive,
    )
    jax.block_until_ready(loop(*args))  # compile
    t0 = time.perf_counter()
    stats = jax.tree.map(np.asarray, loop(*args)[3])
    per_step = (time.perf_counter() - t0) / steps

    rec = StepRecorder()
    record_migrate_steps(rec, stats, rank_totals=True)
    acc = FlowAccumulator()
    acc.update(stats)
    record_flow_snapshot(rec, acc)
    monitor = HealthMonitor(rec)
    monitor.note_step_time(per_step)
    out = {
        "report": telemetry.exchange_report(
            stats, 4 * (2 * 3 + 1), step_seconds=per_step,
            domain="ici", n_chips=n_chips,
        ),
        "health": monitor.evaluate(),
        "flow": acc.snapshot(k=5),
    }
    assert out["health"]["status"] == "OK"
    assert out["flow"]["n_ranks"] == 8
    assert out["report"]["links"]["links"], "per-link section missing"
    json.dumps(out)


def test_record_migrate_steps_validates_and_rank_totals():
    good = _stats2([[[0, 3], [1, 0]]], [[5, 5]])
    rec = StepRecorder()
    record_migrate_steps(rec, good, rank_totals=True)
    ev = rec.last("migrate_step")
    assert ev.data["sent_per_rank"] == [3, 1]
    assert ev.data["received_per_rank"] == [1, 3]
    assert ev.data["population_per_rank"] == [5, 5]
    bad = good._replace(backlog=np.zeros((1, 3), np.int32))
    with pytest.raises(ValueError, match="shape-congruent"):
        record_migrate_steps(StepRecorder(), bad)


# ------------------------------------------- steady-state overhead budget


def test_recorder_monitor_overhead_under_2pct(rng, _devices):
    """Acceptance: journaling + health evaluation add <= 2% to the
    config1-style steady-state step (min-of-k protocol; the observatory
    is host-side bookkeeping outside the compiled loop, so its cost must
    be noise against ms-scale device steps)."""
    import time

    grid = ProcessGrid((2, 2, 2))
    n_local = 2048
    n = grid.nranks * n_local
    mesh = mesh_lib.make_mesh(grid)
    cfg = nbody.DriftConfig(
        domain=DOMAIN, grid=grid, dt=0.02, capacity=n_local // 4,
        n_local=n_local,
    )
    steps = 32  # amortize the one stats read-back per loop boundary
    loop = nbody.make_migrate_loop(cfg, mesh, steps)
    pos = rng.random((n, 3), dtype=np.float32)
    vel = (0.2 * (rng.random((n, 3), dtype=np.float32) - 0.5)).astype(
        np.float32
    )
    alive = np.ones((n,), bool)
    jax.block_until_ready(loop(pos, vel, alive))  # compile

    def sample(observe):
        rec = StepRecorder()
        mon = HealthMonitor(rec)
        t0 = time.perf_counter()
        out = loop(pos, vel, alive)
        jax.block_until_ready(out)
        # every caller that reports already reads the stats pytree to
        # the host — that fetch is the shared baseline, not
        # observatory overhead
        stats_host = jax.tree.map(np.asarray, out[3])
        if observe:
            record_migrate_steps(rec, stats_host, rank_totals=True)
            acc = FlowAccumulator()
            acc.update(stats_host)
            record_flow_snapshot(rec, acc)
            mon.note_step_time((time.perf_counter() - t0) / steps)
            mon.evaluate()
        return time.perf_counter() - t0

    # noise protocol: inside a full-suite run the loop itself wobbles
    # by several ms (allocator/scheduler state left by hundreds of
    # prior tests) — far above the sub-ms observe path under test, so
    # a min-of-k difference is noise-dominated. Each observed sample
    # is paired with an immediately preceding base sample (the pair
    # shares the slow drift) and the MEDIAN pair delta rejects the
    # occasional scheduler spike. GC is held off so a collection over
    # the suite's accumulated heap is not billed to the observe path.
    import gc

    def batch_median():
        deltas = []
        gc.collect()
        gc.disable()
        try:
            for k in range(9):
                # alternate which leg runs first: the two legs of a pair
                # share the slow drift, but the SECOND leg systematically
                # pays any residual warm-up/degradation trend —
                # alternating puts that bias on each leg equally often,
                # so the median of the signed deltas cancels it instead
                # of billing it to the observe path
                if k % 2:
                    o = sample(True)
                    b = sample(False)
                else:
                    b = sample(False)
                    o = sample(True)
                deltas.append((o - b) / b)
        finally:
            gc.enable()
        return float(np.median(deltas)), deltas

    overhead, deltas = batch_median()
    if overhead > 0.02:
        # a real regression reproduces; a scheduler-noise excursion does
        # not — confirm before failing (keeps the gate's false-failure
        # rate at p^2 without loosening the 2% acceptance itself)
        overhead2, deltas2 = batch_median()
        if overhead2 < overhead:
            overhead, deltas = overhead2, deltas2
    assert overhead <= 0.02, (
        f"observatory overhead {overhead:.1%} > 2% (median of "
        f"{len(deltas)} paired samples, {steps}-step loop, best of two "
        f"batches; deltas {[f'{d:.1%}' for d in deltas]})"
    )
