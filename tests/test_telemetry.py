"""Telemetry package: recorder, report math, profiler sessions and trace
export. All CPU-runnable (tier 1); device work uses the 8
virtual CPU devices from conftest.py."""

import json

import numpy as np
import pytest

from mpi_grid_redistribute_tpu.parallel.exchange import RedistributeStats
from mpi_grid_redistribute_tpu.parallel.migrate import MigrateStats
from mpi_grid_redistribute_tpu.telemetry import (
    StepRecorder,
    exchange_report,
    record_migrate_steps,
    row_bytes_of,
)
from mpi_grid_redistribute_tpu.telemetry import metrics, traceview
from mpi_grid_redistribute_tpu.telemetry.profiler import (
    PROFILE_DIR_ENV,
    ProfilerSession,
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


# -------------------------------------------------- profiler sessions


def test_profiler_session_disabled_is_a_true_noop(monkeypatch):
    monkeypatch.delenv(PROFILE_DIR_ENV, raising=False)
    rec = StepRecorder()
    with ProfilerSession(None, recorder=rec) as s:
        assert not s.enabled
    assert rec.events("profile_session") == []


def test_profiler_session_env_knob_arms_it(tmp_path, monkeypatch):
    calls = []
    import jax

    monkeypatch.setattr(
        jax.profiler, "start_trace", lambda d: calls.append(("start", d))
    )
    monkeypatch.setattr(
        jax.profiler, "stop_trace", lambda: calls.append(("stop",))
    )
    monkeypatch.setenv(PROFILE_DIR_ENV, str(tmp_path))
    rec = StepRecorder()
    with ProfilerSession(recorder=rec, label="knob") as s:
        assert s.enabled and s.armed
    assert calls == [("start", str(tmp_path)), ("stop",)]
    (ev,) = rec.events("profile_session")
    assert ev.data["trace_dir"] == str(tmp_path)
    assert ev.data["label"] == "knob"
    assert ev.data["armed"] is True
    assert ev.data["error"] is None
    assert ev.data["duration_s"] >= 0.0
    # the metrics plane counts the session
    text = metrics.from_journal(rec).render_openmetrics()
    assert "grid_profile_sessions_total 1" in text


def test_profiler_session_broken_profiler_degrades(tmp_path, monkeypatch):
    import jax

    def _boom(d):
        raise RuntimeError("profiler says no")

    monkeypatch.setattr(jax.profiler, "start_trace", _boom)
    rec = StepRecorder()
    with ProfilerSession(str(tmp_path), recorder=rec):
        pass  # must not raise
    (ev,) = rec.events("profile_session")
    assert ev.data["armed"] is False
    assert "RuntimeError" in ev.data["error"]


HOST_SPANS = ("host:snapshot", "host:snapshot_write", "host:journal_drain",
              "host:input_check", "host:to_device")


@pytest.fixture(scope="module")
def host_span_names(tmp_path_factory):
    """Names of the host spans in one profiler trace of a tiny service
    run that snapshots and drains its journal, plus one redistribute
    call through the jax engine."""
    from jax.profiler import ProfileData

    from mpi_grid_redistribute_tpu import api
    from mpi_grid_redistribute_tpu.domain import ProcessGrid
    from mpi_grid_redistribute_tpu.service import DriverConfig, ServiceDriver

    root = tmp_path_factory.mktemp("host_spans")
    cfg = DriverConfig(
        grid_shape=(2, 2, 2), n_local=64, steps=4, seed=7,
        backend="numpy", snapshot_every=2,
        snapshot_dir=str(root / "snaps"), store_dir=str(root / "store"),
    )
    rd = api.GridRedistribute(
        grid=ProcessGrid((2, 2, 2)), lo=(0.0,) * 3, hi=(1.0,) * 3,
        periodic=(True,) * 3,
    )
    rng = np.random.default_rng(3)
    pos = rng.random((8 * 32, 3), dtype=np.float32)
    ids = np.arange(8 * 32, dtype=np.int32)
    rd.redistribute(pos, ids)  # compiles outside the trace
    trace_dir = str(root / "trace")
    with ProfilerSession(trace_dir, recorder=StepRecorder()) as sess:
        ServiceDriver(cfg).run()
        rd.redistribute(pos, ids)
    assert sess.armed, sess.error
    (path,) = list((root / "trace").rglob("*.xplane.pb"))
    data = ProfileData.from_file(str(path))
    return {
        ev.name
        for plane in data.planes if plane.name.startswith("/host:")
        for line in plane.lines
        for ev in line.events
    }


@pytest.mark.parametrize("name", HOST_SPANS)
def test_host_spans_land_in_the_profiler_trace(host_span_names, name):
    assert name in host_span_names


# ------------------------------------------------------------- traceview


def test_traceview_instant_args_are_json_safe():
    """Each journal event's payload rides in its instant's ``args``
    (values JSON cannot hold become strings), and the document holds
    the journal and counter lanes only."""
    rec = StepRecorder()
    rec.record("capacity_grow", old=64, new=128, which=("send", 3))
    rec.record("profile_session", trace_dir="/tmp/x", label="s",
               duration_s=0.1, armed=True, error=None)
    doc = traceview.to_chrome_trace(rec)
    inst = {e["name"]: e["args"] for e in doc["traceEvents"]
            if e["ph"] == "i"}
    assert inst["capacity_grow"]["new"] == 128
    assert inst["capacity_grow"]["which"] == str(("send", 3))
    assert inst["profile_session"]["armed"] is True
    assert inst["profile_session"]["error"] is None
    assert {e["pid"] for e in doc["traceEvents"]} == {0, 2}
    assert not any(e["ph"] == "X" for e in doc["traceEvents"])
    json.dumps(doc)  # stays serializable
