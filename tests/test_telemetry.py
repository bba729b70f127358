"""Telemetry package: recorder, report math, phase attribution, regress
gate. All CPU-runnable (tier 1); device work uses the 8 virtual CPU
devices from conftest.py."""

import json

import numpy as np
import pytest

from mpi_grid_redistribute_tpu.parallel.exchange import RedistributeStats
from mpi_grid_redistribute_tpu.parallel.migrate import MigrateStats
from mpi_grid_redistribute_tpu.telemetry import (
    StepRecorder,
    attribute_phases,
    check_capture,
    exchange_report,
    extract_metrics,
    format_phase_table,
    min_of_k,
    record_migrate_steps,
    row_bytes_of,
)
from mpi_grid_redistribute_tpu.telemetry.report import format_report
from mpi_grid_redistribute_tpu.utils import profiling


# ---------------------------------------------------------------- recorder


def test_recorder_ring_eviction_and_counts():
    rec = StepRecorder(capacity=4)
    for i in range(10):
        rec.record("tick", i=i)
    assert len(rec) == 4
    assert rec.total_recorded == 10
    assert rec.evicted == 6
    # all-time counts survive eviction
    assert rec.counts() == {"tick": 10}
    # retained window is the newest events, oldest first
    assert [e.data["i"] for e in rec.events("tick")] == [6, 7, 8, 9]
    assert rec.last("tick").data["i"] == 9
    rec.clear()
    assert len(rec) == 0 and rec.counts() == {}


def test_recorder_disabled_still_counts():
    rec = StepRecorder(capacity=8, enabled=False)
    rec.record("tick")
    rec.record("tock")
    assert len(rec) == 0
    assert rec.counts() == {"tick": 1, "tock": 1}


def test_recorder_jsonl_roundtrip(tmp_path):
    rec = StepRecorder()
    rec.record("capacity_grow", old=8, new=16)
    rec.record("redistribute", call=0)
    path = tmp_path / "events.jsonl"
    assert rec.to_jsonl(str(path)) == 2
    lines = path.read_text().splitlines()
    assert len(lines) == 2
    first = json.loads(lines[0])
    assert first["kind"] == "capacity_grow"
    assert first["old"] == 8 and first["new"] == 16
    assert json.loads(lines[1])["seq"] > first["seq"]


def test_record_migrate_steps_bridges_stacked_stats():
    S, R = 3, 4
    stats = MigrateStats(
        sent=np.full((S, R), 2, np.int32),
        received=np.full((S, R), 2, np.int32),
        population=np.full((S, R), 100, np.int32),
        backlog=np.zeros((S, R), np.int32),
        dropped_recv=np.zeros((S, R), np.int32),
    )
    rec = StepRecorder()
    assert record_migrate_steps(rec, stats) == S
    evs = rec.events("migrate_step")
    assert [e.data["step"] for e in evs] == [0, 1, 2]
    assert all(e.data["sent"] == 2 * R for e in evs)
    # trailing window
    rec2 = StepRecorder()
    assert record_migrate_steps(rec2, stats, max_steps=1) == 1
    assert rec2.last("migrate_step").data["step"] == S - 1


# -------------------------------------------------- recorder from real API


def test_recorder_events_from_real_grow_path():
    from mpi_grid_redistribute_tpu import GridRedistribute

    rng = np.random.default_rng(3)
    pos = rng.random((512, 3), dtype=np.float32)
    with GridRedistribute(
        lo=0.0, hi=1.0, grid=(2, 2, 2), capacity=2, on_overflow="grow"
    ) as rd:
        res = rd.redistribute(pos)
        assert int(np.asarray(res.count).sum()) == 512
        counts = rd.telemetry.counts()
        # a per-pair capacity of 2 cannot carry ~512/8 rows/pair: the
        # retry loop must have grown and journaled it
        assert counts.get("capacity_grow", 0) >= 1
        assert counts.get("redistribute", 0) >= 1
        grow = rd.telemetry.last("capacity_grow")
        assert grow.data["new"] > grow.data["old"]
        assert grow.data["needed"] > 2

        rep = rd.report()
        assert rep["kind"] == "redistribute"
        assert rep["exchange_bytes_per_step"] > 0
        assert rep["bw_util"] is None  # no step_seconds supplied
        rep2 = rd.report(step_seconds=1e-3)
        assert rep2["bw_util"] == "not measured"  # a CPU rate
        assert rep2["events"]["capacity_grow"] == counts["capacity_grow"]
        assert rep2["unresolved_windows"] is False


def test_report_before_any_call_raises():
    from mpi_grid_redistribute_tpu import GridRedistribute

    rd = GridRedistribute(lo=0.0, hi=1.0, grid=(2, 2, 2))
    with pytest.raises(RuntimeError):
        rd.report()


# ------------------------------------------------------------- report math


def test_row_bytes_of():
    import jax

    pos = np.zeros((10, 3), np.float32)
    ids = np.zeros((10,), np.int32)
    vel = np.zeros((10, 3), np.float32)
    assert row_bytes_of(pos) == 12
    assert row_bytes_of(pos, vel, ids) == 28
    structs = [
        jax.ShapeDtypeStruct((10, 3), np.float32),
        jax.ShapeDtypeStruct((10,), np.int32),
    ]
    assert row_bytes_of(*structs) == 16


def _stats_2rank():
    # rank 0 sends 3 (keeps) + 1 (moves); rank 1 sends 2 (moves) + 4
    send = np.array([[3, 1], [2, 4]], np.int32)
    return RedistributeStats(
        send_counts=send,
        recv_counts=send.T,
        dropped_send=np.zeros((2,), np.int32),
        dropped_recv=np.zeros((2,), np.int32),
        needed_capacity=np.full((2,), 4, np.int32),
    )


def test_exchange_report_hand_math_hbm():
    stats = _stats_2rank()
    row_bytes = 28
    rep = exchange_report(
        stats, row_bytes, step_seconds=0.01, domain="hbm",
        device_kind="TPU v5 lite",
    )
    # total = 10 rows, moved (off-diagonal) = 3 rows
    assert rep["exchange_bytes_per_step"] == 10 * row_bytes
    assert rep["moved_bytes_per_step"] == 3 * row_bytes
    # HBM domain: ALL rows cross HBM (gather + scatter)
    expected_bps = 10 * row_bytes / 0.01
    assert rep["exchange_bytes_per_sec"] == pytest.approx(expected_bps)
    assert rep["bw_util"] == pytest.approx(
        expected_bps / profiling.HBM_PEAK_BYTES_PER_SEC
    )
    assert rep["kind"] == "redistribute"
    assert rep["stats"]["dropped_send"] == 0
    json.dumps(rep)  # the whole surface must be JSON-serializable


def test_exchange_report_hand_math_ici():
    stats = _stats_2rank()
    row_bytes = 28
    rep = exchange_report(
        stats, row_bytes, step_seconds=0.01, domain="ici", n_chips=2,
        device_kind="TPU v5 lite",
    )
    # ICI wire carries only the moved rows, and the roof is per chip
    expected_bps = 3 * row_bytes / 0.01
    assert rep["exchange_bytes_per_sec"] == pytest.approx(expected_bps)
    roof = (
        profiling.ICI_LINK_BYTES_PER_SEC * profiling.ICI_LINKS_PER_CHIP
    )
    assert rep["bw_util"] == pytest.approx(expected_bps / 2 / roof)


def test_exchange_report_cpu_rate_has_no_utilization():
    # a rate timed on the CPU is never divided by a chip's roof
    rep = exchange_report(_stats_2rank(), 28, step_seconds=0.01)
    assert rep["exchange_bytes_per_sec"] == pytest.approx(280 / 0.01)
    assert rep["bw_util"] == "not measured"
    assert "not measured" in format_report(rep)


def test_chip_peaks_keyed_by_device_kind():
    assert profiling.chip_peaks("TPU v5 lite").hbm_bytes_per_sec == 819e9
    with pytest.raises(ValueError, match="no published peaks"):
        profiling.chip_peaks("TPU v9 imaginary")
    with pytest.raises(ValueError, match="no published peaks"):
        profiling.exchange_bw_util(1e9, "hbm", device_kind="cpu")


def test_exchange_report_without_step_seconds():
    rep = exchange_report(_stats_2rank(), 28)
    assert rep["exchange_bytes_per_sec"] is None
    assert rep["bw_util"] is None
    assert rep["exchange_bytes_per_step"] == 280


def test_exchange_report_migrate_stats():
    S, R = 2, 4
    stats = MigrateStats(
        sent=np.full((S, R), 5, np.int32),
        received=np.full((S, R), 5, np.int32),
        population=np.full((S, R), 50, np.int32),
        backlog=np.zeros((S, R), np.int32),
        dropped_recv=np.zeros((S, R), np.int32),
    )
    rep = exchange_report(stats, 28, step_seconds=0.001)
    assert rep["kind"] == "migrate"
    # MigrateStats.sent counts movers exclusively: total == moved
    assert rep["exchange_bytes_per_step"] == 5 * R * 28
    assert rep["moved_bytes_per_step"] == rep["exchange_bytes_per_step"]


# ------------------------------------------------------- phase attribution


def test_attribute_phases_orders_and_rooflines():
    import jax
    import jax.numpy as jnp
    from jax import lax

    # phase tokens = number of extra multiply passes; cumulative time
    # must be returned per phase with deltas and roofline columns filled
    def loop_builder(phase, S):
        @jax.jit
        def loop(x):
            def body(c, _):
                for _i in range(phase):
                    c = c * 1.000001 + 1e-9
                return c, ()

            c, _ = lax.scan(body, x, None, length=S)
            return c

        return loop

    x = jnp.ones((64, 64), jnp.float32)
    pb = {1: 1000, 2: 2000}
    rows = attribute_phases(
        loop_builder, (x,), [1, 2], s1=2, s2=6, reps=1, phase_bytes=pb
    )
    assert [r.phase for r in rows] == [1, 2]
    assert rows[0].delta_s == rows[0].cumulative_s
    assert rows[1].delta_s == pytest.approx(
        rows[1].cumulative_s - rows[0].cumulative_s
    )
    assert rows[0].logical_bytes == 1000
    assert rows[0].roofline_s == pytest.approx(
        1000 / profiling.HBM_PEAK_BYTES_PER_SEC
    )
    table = format_phase_table(rows)
    assert table.splitlines()[0].startswith("| phase (cumulative)")
    assert len(table.splitlines()) == 2 + len(rows)
    assert "(first)" in table.splitlines()[2]


# ----------------------------------------------------------------- regress


def _capture(value=100.0, ms=10.0, xbps=1e8, wrap=False):
    line = {
        "metric": "particles_per_sec_per_chip",
        "value": value,
        "ms_per_step": ms,
        "exchange_bytes_per_sec": xbps,
    }
    if wrap:
        return {"n": 1, "cmd": "python bench.py", "rc": 0, "parsed": line}
    return line


def test_min_of_k_protocol():
    it = iter([3.0, 1.0, 2.0])
    d = min_of_k(lambda: next(it), k=3)
    assert d["min"] == 1.0 and d["max"] == 3.0
    assert d["spread"] == pytest.approx(2.0)
    assert d["k"] == 3 and len(d["values"]) == 3
    with pytest.raises(ValueError):
        min_of_k(lambda: 1.0, k=0)


def test_extract_metrics_handles_wrappers():
    assert extract_metrics(_capture())["value"] == 100.0
    assert extract_metrics(_capture(wrap=True))["ms_per_step"] == 10.0
    assert extract_metrics({"parsed": None}) is None
    assert extract_metrics({"tail": "crashed"}) is None


def test_check_capture_accepts_within_threshold():
    ok, lines = check_capture(
        _capture(value=95.0), [_capture(value=100.0), _capture(value=90.0)]
    )
    assert ok, lines
    assert any(ln.startswith("warn") for ln in lines)


def test_check_capture_rejects_regressions():
    # 20% throughput drop vs best
    ok, lines = check_capture(_capture(value=80.0), [_capture(value=100.0)])
    assert not ok
    assert any(ln.startswith("FAIL") and "value" in ln for ln in lines)
    # times regress UPWARD
    ok, lines = check_capture(_capture(ms=12.5), [_capture(ms=10.0)])
    assert not ok
    assert any("ms_per_step" in ln and ln.startswith("FAIL") for ln in lines)


def test_check_capture_compares_against_best_not_latest():
    # history drifted down; the gate must still hold the line at the best
    history = [_capture(value=100.0), _capture(value=92.0, wrap=True)]
    ok, _ = check_capture(_capture(value=88.0), history)
    assert not ok  # 12% below the 100.0 best, despite being ~4% below latest


def test_check_capture_skips_missing_metrics():
    cur = {"value": 100.0, "metric": "x"}  # no ms_per_step in current
    ok, lines = check_capture(cur, [_capture()])
    assert ok
    assert any(ln.startswith("skip") and "ms_per_step" in ln for ln in lines)


def test_regress_cli_on_fixture_files(tmp_path):
    from mpi_grid_redistribute_tpu.telemetry import regress

    good = tmp_path / "BENCH_r01.json"
    good.write_text(json.dumps(_capture(value=100.0, wrap=True)))
    bad = tmp_path / "current_bad.json"
    bad.write_text(json.dumps(_capture(value=70.0)))
    okc = tmp_path / "current_ok.json"
    okc.write_text(json.dumps(_capture(value=99.0)))

    hist = str(tmp_path / "BENCH_r*.json")
    assert regress.main(["--current", str(okc), "--history", hist]) == 0
    assert regress.main(["--current", str(bad), "--history", hist]) == 1
    assert regress.main(["--history", str(tmp_path / "nope*.json")]) == 2


def test_regress_cli_self_test_on_committed_history():
    # the acceptance gate: the repo's own committed history must pass
    from mpi_grid_redistribute_tpu.telemetry import regress

    assert regress.main([]) == 0
