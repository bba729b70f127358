"""Incident observatory (ISSUE 17): step context, flight recorder, burn rate.

Everything here is host-only — the causal step context
(``telemetry/context.py``), the flight recorder
(``telemetry/incident.py``), the burn-rate SLO rules and the Perfetto
flow arrows all live on the journal side of the device boundary, so the
tests run on plain recorders plus the numpy service backend. The no-jax
import contract of context.py/incident.py is asserted separately in
``tests/test_metrics.py`` (scrape-path purity).
"""

import dataclasses
import importlib.util
import json
import os
import threading

import pytest

from mpi_grid_redistribute_tpu.telemetry import StepRecorder
from mpi_grid_redistribute_tpu.telemetry import context as context_lib
from mpi_grid_redistribute_tpu.telemetry import health
from mpi_grid_redistribute_tpu.telemetry import incident as incident_lib
from mpi_grid_redistribute_tpu.telemetry import traceview
from mpi_grid_redistribute_tpu.telemetry.context import StepContext
from mpi_grid_redistribute_tpu.telemetry.health import (
    ALERT,
    Finding,
    HealthMonitor,
    HealthRule,
    WARN,
)
from mpi_grid_redistribute_tpu.telemetry.incident import FlightRecorder

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


# ------------------------------------------------------------- context


def test_context_envelope_and_immutability():
    ctx = StepContext(trace="t1", step=3, call=2, attempt=1, origin="main")
    assert ctx.envelope() == {
        "trace": "t1",
        "ctx_step": 3,
        "ctx_call": 2,
        "ctx_attempt": 1,
        "ctx_origin": "main",
    }
    # None fields are omitted so steady-state envelopes stay small
    sparse = StepContext(trace="t2", origin="x")
    assert sparse.envelope() == {"trace": "t2", "ctx_origin": "x"}
    with pytest.raises(AttributeError, match="immutable"):
        ctx.step = 4
    assert "t1" in repr(ctx)
    # a fresh context invents a trace id; explicit origin=None derives
    # from the current thread name
    auto = StepContext()
    assert isinstance(auto.trace, str) and len(auto.trace) == 12
    assert auto.origin == threading.current_thread().name


def test_context_child_inherits_and_clears():
    root = StepContext(trace="run", step=5, attempt=0, origin="driver")
    kid = root.child(step=6)
    assert kid.trace == "run" and kid.step == 6
    assert kid.attempt == 0 and kid.origin == "driver"
    # explicit None clears; unpassed inherits
    cleared = root.child(step=None, origin="snapshot-writer")
    assert cleared.step is None and cleared.origin == "snapshot-writer"
    assert cleared.trace == "run"


def test_context_scoped_nesting_and_restore():
    assert context_lib.current() is None
    with context_lib.scoped(step=1) as outer:
        assert context_lib.current() is outer
        with context_lib.scoped(step=2) as inner:
            assert inner.trace == outer.trace
            assert context_lib.current_trace() == outer.trace
            assert context_lib.current().step == 2
        assert context_lib.current() is outer
    assert context_lib.current() is None
    # exception-safe restore
    with pytest.raises(RuntimeError):
        with context_lib.use(StepContext(trace="boom")):
            raise RuntimeError("x")
    assert context_lib.current() is None


def test_context_is_thread_local():
    seen = {}

    def probe():
        seen["ctx"] = context_lib.current()

    with context_lib.use(StepContext(trace="main-only")):
        t = threading.Thread(target=probe)
        t.start()
        t.join()
    # thread-locals never cross the spawn: handoff is explicit child()
    assert seen["ctx"] is None


def test_recorder_merges_context_payload_wins():
    rec = StepRecorder()
    rec.record("migrate_step", step=0, sent=1)  # no context active
    with context_lib.use(StepContext(trace="abc", step=5, origin="loop")):
        rec.record("migrate_step", step=9, sent=2)
        # payload keys win: a replayed event's original attribution is
        # never restamped by whatever context the replayer runs under
        rec.record_at("alert", 50.0, rule="r", trace="original")
    bare, tagged, replayed = rec.events()
    assert "trace" not in bare.data
    assert tagged.data["trace"] == "abc"
    assert tagged.data["ctx_step"] == 5 and tagged.data["step"] == 9
    assert tagged.data["ctx_origin"] == "loop"
    assert replayed.data["trace"] == "original"


# ---------------------------------------------------- callback isolation


def test_callback_error_isolated():
    rec = StepRecorder()
    rule = HealthRule("boom", ALERT, lambda r: "it broke")
    delivered = []

    def bad_sink(finding):
        raise ValueError("sink down")

    mon = HealthMonitor(rec, rules=[rule], on_alert=bad_sink)
    mon.add_callback(delivered.append)
    verdict = mon.evaluate()
    # the broken sink neither masks the ALERT nor starves later sinks
    assert verdict["status"] == ALERT
    assert delivered and delivered[0].rule == "boom"
    err = rec.last("callback_error")
    assert err.data["rule"] == "boom"
    assert "bad_sink" in err.data["callback"]
    assert err.data["error"].startswith("ValueError: sink down")


# ------------------------------------------------------ burn-rate rules


def _latency_journal(seconds_list):
    rec = StepRecorder()
    for i, s in enumerate(seconds_list):
        rec.record("step_latency", step=i, seconds=float(s), dropped=0)
    return rec


def test_burn_rate_fast_window_fires():
    rule = health.burn_rate_latency(0.25, fast_window=16, slow_window=64)
    assert rule.severity == ALERT and rule.name == "burn_rate_latency"
    # total breach: every step in the fast window blows the threshold
    reason = rule.fn(_latency_journal([1.0] * 16))
    assert reason is not None and "fast window" in reason
    # healthy window: no budget burned
    assert rule.fn(_latency_journal([0.001] * 64)) is None
    # cold journal: neither window is full yet, not a breach
    assert rule.fn(_latency_journal([1.0] * 10)) is None


def test_burn_rate_slow_window_catches_sustained_burn():
    rule = health.burn_rate_latency(0.25, fast_window=16, slow_window=64)
    # 3 bad steps early in the slow window, clean fast window: the
    # point-in-time p99 over the last 16 forgives this, the slow burn
    # (3/64 / 1% budget = 4.7x >= 2x) does not
    seconds = [1.0] * 3 + [0.001] * 61
    reason = rule.fn(_latency_journal(seconds))
    assert reason is not None and "slow window" in reason


def test_burn_rate_dropped_and_validation():
    rule = health.burn_rate_dropped(fast_window=4, slow_window=8)
    rec = StepRecorder()
    for i in range(4):
        rec.record("step_latency", step=i, seconds=0.001, dropped=10)
    assert "fast window" in rule.fn(rec)
    with pytest.raises(ValueError, match="objective"):
        health.burn_rate_latency(0.25, objective=1.5)
    with pytest.raises(ValueError, match="slow_window"):
        health.burn_rate_latency(0.25, fast_window=8, slow_window=8)
    with pytest.raises(ValueError, match="threshold"):
        health.burn_rate_dropped(threshold=-1)
    with pytest.raises(ValueError, match="burn factors"):
        health.burn_rate_latency(0.25, fast_burn=0.0)


# ------------------------------------------------------ flight recorder


def _seeded_journal(rec):
    """A small deterministic journal recorded under a fixed context."""
    with context_lib.use(
        StepContext(trace="fixed-trace", step=7, attempt=0, origin="test")
    ):
        rec.record_at("migrate_step", 100.0, step=0, sent=4, received=4,
                      backlog=0, dropped_recv=0, population=64)
        rec.record_at("flow_snapshot", 100.5, steps=1, n_ranks=2,
                      moved_rows_total=4, imbalance=1.0)
        rec.record_at("alert", 101.0, rule="backlog_growth",
                      severity="ALERT", reason="backlog grew")


def test_capture_writes_consistent_bundle(tmp_path):
    rec = StepRecorder()
    _seeded_journal(rec)
    fr = FlightRecorder(rec, str(tmp_path), clock=lambda: 111.0)
    out = fr.capture(rule="backlog_growth", reason="backlog grew")
    assert os.path.basename(out) == "incident-0001-backlog_growth"

    index = json.load(open(os.path.join(out, "index.json")))
    assert index["schema"] == 1
    assert index["rule"] == "backlog_growth"
    assert index["trigger"] == "alert"
    assert index["captured_at"] == 111.0
    # the triggering step context rode the alert event's envelope into
    # the manifest — the join key back into the frozen journal
    assert index["context"]["trace"] == "fixed-trace"
    assert index["context"]["ctx_step"] == 7
    assert index["events_retained"] == 3
    assert index["files"] == sorted(
        ["journal.jsonl", "counts.json", "metrics.prom", "health.json",
         "flow.json", "env.json"]
    )
    for name in index["files"]:
        assert os.path.isfile(os.path.join(out, name)), name
    # the frozen window predates the incident event (a bundle never
    # contains its own capture), but the live journal carries it
    lines = open(os.path.join(out, "journal.jsonl")).read().splitlines()
    assert len(lines) == 3
    ev = rec.last("incident")
    assert ev.data["id"] == "incident-0001-backlog_growth"
    assert ev.data["rule"] == "backlog_growth" and ev.data["events"] == 3
    assert ev.time == 111.0
    health_doc = json.load(open(os.path.join(out, "health.json")))
    assert health_doc["trigger"]["rule"] == "backlog_growth"
    assert health_doc["recent_alerts"][0]["rule"] == "backlog_growth"
    flow_doc = json.load(open(os.path.join(out, "flow.json")))
    assert flow_doc["imbalance"] == 1.0


def test_capture_debounce_and_prune(tmp_path):
    rec = StepRecorder()
    _seeded_journal(rec)
    now = [0.0]
    fr = FlightRecorder(
        rec, str(tmp_path), debounce_s=60.0, keep=2, clock=lambda: now[0]
    )
    first = fr.capture(rule="r1", reason="x")
    assert first is not None
    # same rule inside the window: suppressed, no second bundle
    now[0] = 30.0
    assert fr.capture(rule="r1", reason="x") is None
    # a different rule has its own debounce clock
    assert fr.capture(rule="r2", reason="y") is not None
    # past the window the same rule captures again; keep=2 prunes the
    # oldest bundle so the incident dir stays bounded
    now[0] = 120.0
    assert fr.capture(rule="r1", reason="x") is not None
    ids = [e["id"] for e in incident_lib.list_bundles(tmp_path)]
    assert len(ids) == 2
    assert "incident-0003-r1" in ids


def test_on_finding_alert_only(tmp_path):
    rec = StepRecorder()
    _seeded_journal(rec)
    fr = FlightRecorder(rec, str(tmp_path), clock=lambda: 1.0)
    assert fr.on_finding(Finding("r", WARN, "advisory")) is None
    assert incident_lib.list_bundles(tmp_path) == []
    out = fr.on_finding(Finding("r", ALERT, "page"))
    assert out is not None


def test_scan_faults_cursor_and_event_context(tmp_path):
    rec = StepRecorder()
    with context_lib.use(StepContext(trace="ft", step=2, origin="loop")):
        rec.record("fault_injected", fault="latency_spike", step=2)
    fr = FlightRecorder(rec, str(tmp_path), clock=lambda: 5.0)
    made = fr.scan_faults()
    assert len(made) == 1
    index = json.load(open(os.path.join(made[0], "index.json")))
    assert index["rule"] == "fault_latency_spike"
    assert index["trigger"] == "fault"
    # context comes from the fault event itself, not the scanner thread
    assert index["context"] == {
        "trace": "ft", "ctx_step": 2, "ctx_origin": "loop",
    }
    # the cursor advanced: an unchanged journal yields nothing new
    assert fr.scan_faults() == []
    rec.record("fault_injected", fault="crash", step=9)
    assert len(fr.scan_faults()) == 1


def test_install_idempotent_across_monitor_restarts(tmp_path):
    rec = StepRecorder()
    mon1 = HealthMonitor(rec, rules=[])
    fr = incident_lib.install(mon1, rec, tmp_path)
    assert incident_lib.install(mon1, rec, tmp_path) is fr
    assert sum(
        1 for cb in mon1.callbacks
        if getattr(cb, "__self__", None) is fr
    ) == 1
    # a supervisor restart builds a fresh monitor around the SAME
    # journal: the flight recorder (debounce clocks, bundle counter)
    # carries over instead of re-capturing every standing alert
    mon2 = HealthMonitor(rec, rules=[])
    assert incident_lib.install(mon2, rec, tmp_path) is fr
    assert any(getattr(cb, "__self__", None) is fr for cb in mon2.callbacks)
    # a different bundle root is a different recorder instance
    other = incident_lib.install(mon2, rec, tmp_path / "other")
    assert other is not fr


def test_bundles_byte_stable_across_seeded_runs(tmp_path):
    def run(out_dir):
        rec = StepRecorder()
        _seeded_journal(rec)
        fr = FlightRecorder(rec, str(out_dir), clock=lambda: 111.0)
        return fr.capture(rule="backlog_growth", reason="backlog grew")

    a = run(tmp_path / "a")
    b = run(tmp_path / "b")
    assert os.path.basename(a) == os.path.basename(b)
    names = sorted(os.listdir(a))
    assert names == sorted(os.listdir(b))
    for name in names:
        wa = open(os.path.join(a, name), "rb").read()
        wb = open(os.path.join(b, name), "rb").read()
        assert wa == wb, f"{name} differs between seeded runs"


def test_list_and_load_bundles(tmp_path):
    assert incident_lib.list_bundles(tmp_path / "missing") == []
    rec = StepRecorder()
    _seeded_journal(rec)
    now = [1.0]
    fr = FlightRecorder(
        rec, str(tmp_path), debounce_s=0.0, clock=lambda: now[0]
    )
    fr.capture(rule="r1", reason="x")
    now[0] = 2.0
    fr.capture(rule="r2", reason="y")
    # a corrupt bundle during an incident is itself a finding — it shows
    # up as an error entry rather than being hidden
    bad = tmp_path / "incident-9999-bad"
    bad.mkdir()
    (bad / "index.json").write_text("{not json")
    entries = incident_lib.list_bundles(tmp_path)
    assert [e.get("id") for e in entries] == [
        "incident-9999-bad", "incident-0001-r1", "incident-0002-r2",
    ]
    assert "error" in entries[0]
    loaded = incident_lib.load_bundle(tmp_path, "incident-0001-r1")
    assert loaded["dir"] == str(tmp_path / "incident-0001-r1")
    assert "journal.jsonl" in loaded["files_present"]
    with pytest.raises(OSError):
        incident_lib.load_bundle(tmp_path, "incident-0000-nope")


# ------------------------------------------- perfetto causal flow arrows


def test_flow_arrows_pair_same_trace_cause_to_effect():
    rec = StepRecorder()
    with context_lib.use(StepContext(trace="t1", step=1, origin="loop")):
        rec.record_at("migrate_step", 100.0, step=0, sent=1, population=8,
                      backlog=0)
        rec.record_at("alert", 101.0, rule="r", severity="ALERT", reason="x")
        rec.record_at("callback_error", 101.5, rule="r", callback="cb",
                      error="ValueError: down")
        rec.record_at("alert", 102.0, rule="r2", severity="ALERT", reason="y")
    doc = traceview.to_chrome_trace(rec)
    flows = [e for e in doc["traceEvents"] if e.get("cat") == "causal"]
    starts = {e["id"]: e for e in flows if e["ph"] == "s"}
    ends = {e["id"]: e for e in flows if e["ph"] == "f"}
    # every arrow is an id-paired s/f couple, finish at or after start
    assert set(starts) == set(ends) and len(starts) == 2
    for fid, s in starts.items():
        f = ends[fid]
        assert f["ts"] >= s["ts"]
        assert s["name"] == f["name"] and s["name"].startswith("cause:")
        assert f.get("bp") == "e"
    # neither the first alert nor the callback_error may act as a flow
    # source: both arrows point at the workload event (ts=0 relative)
    assert {s["ts"] for s in starts.values()} == {0.0}
    # events without a trace draw no arrows
    rec2 = StepRecorder()
    rec2.record("migrate_step", step=0, sent=1)
    rec2.record("alert", rule="r", severity="ALERT", reason="x")
    doc2 = traceview.to_chrome_trace(rec2)
    assert [e for e in doc2["traceEvents"] if e.get("cat") == "causal"] == []


def test_counter_track_uses_real_wall_times():
    rec = StepRecorder()
    # step_time events anchor the counter axis with honest wall times
    rec.record_at("step_time", 100.0, seconds=0.01)
    rec.record_at("step_time", 101.0, seconds=0.01)
    rec.record_at("step_time", 102.5, seconds=0.01)
    for s in range(3):
        rec.record_at("migrate_step", 103.0, step=s, population=10 + s,
                      backlog=0, sent=1)
    doc = traceview.to_chrome_trace(rec)
    counters = [
        e for e in doc["traceEvents"]
        if e["ph"] == "C" and e["name"] == "population"
    ]
    assert [e["ts"] for e in counters] == [0.0, 1.0e6, 2.5e6]
    # without timings the axis degrades to synthetic step spacing
    rec2 = StepRecorder()
    for s in range(3):
        rec2.record_at("migrate_step", 50.0, step=s, population=1, backlog=0,
                       sent=0)
    doc2 = traceview.to_chrome_trace(rec2, step_seconds=2e-3)
    counters2 = [
        e for e in doc2["traceEvents"]
        if e["ph"] == "C" and e["name"] == "population"
    ]
    assert [e["ts"] for e in counters2] == [0.0, 2000.0, 4000.0]


# --------------------------------------------- supervised integration


def test_supervised_slo_breach_freezes_bundles(tmp_path):
    """The demo contract as a tier-1 test: a fault-injected supervised
    run leaves alert- AND fault-triggered bundles, every index carries
    the triggering step context's trace id, and the per-rule debounce
    holds across restarts (one bundle per ALERT rule)."""
    from mpi_grid_redistribute_tpu.service import (
        DriverConfig,
        FaultPlan,
        LatencySpikeFault,
        RestartPolicy,
        ServiceDriver,
        Supervisor,
    )

    bundles = tmp_path / "incidents"
    cfg = DriverConfig(
        grid_shape=(2, 2, 2),
        n_local=256,
        steps=32,
        seed=3,
        backend="numpy",
        snapshot_every=4,
        snapshot_dir=str(tmp_path / "snaps"),
        slo_latency_p99_s=0.25,
        slo_window=4,
        incident_dir=str(bundles),
    )
    rec = StepRecorder()
    faults = FaultPlan([LatencySpikeFault(2, seconds=1.0, spikes=6)])

    def factory(grid_shape=None):
        c = cfg
        if grid_shape is not None:
            c = dataclasses.replace(c, grid_shape=tuple(grid_shape))
        return ServiceDriver(c, recorder=rec, faults=faults)

    sup = Supervisor(
        factory,
        policy=RestartPolicy(
            max_restarts=5, backoff_base_s=0.01, backoff_cap_s=0.02,
            shrink_after=2,
        ),
        recorder=rec,
        sleep_fn=lambda s: None,
    )
    verdict = sup.run()
    assert verdict.ok is True, verdict

    entries = incident_lib.list_bundles(bundles)
    assert entries, "no incident bundles frozen"
    assert all("error" not in e for e in entries)
    triggers = {e["trigger"] for e in entries}
    assert {"alert", "fault"} <= triggers
    # one supervised run = one trace, threaded through every bundle
    traces = {e["context"].get("trace") for e in entries}
    assert len(traces) == 1 and None not in traces
    # every ALERT rule maps to exactly one debounced bundle — a standing
    # alert re-confirmed at every health boundary (and across restarts,
    # which rebuild the monitor around the same journal) must not spam
    alert_rules = {
        e.data["rule"] for e in rec.events("alert")
        if e.data.get("severity") == ALERT
    }
    bundle_rules = [e["rule"] for e in entries if e["trigger"] == "alert"]
    assert sorted(bundle_rules) == sorted(set(bundle_rules))
    assert set(bundle_rules) <= alert_rules
    # journaled incident events mirror the on-disk bundles one-to-one
    journaled = [e.data["id"] for e in rec.events("incident")]
    assert sorted(journaled) == sorted(e["id"] for e in entries)


# ----------------------------------------------------------------- CLI


def _load_cli():
    path = os.path.join(REPO, "scripts", "incident.py")
    spec = importlib.util.spec_from_file_location("_incident_cli", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_incident_cli_list_show_export(tmp_path, capsys):
    rec = StepRecorder()
    _seeded_journal(rec)
    fr = FlightRecorder(rec, str(tmp_path), clock=lambda: 7.0)
    fr.capture(rule="backlog_growth", reason="backlog grew")
    cli = _load_cli()

    assert cli.main(["list", str(tmp_path)]) == 0
    out = capsys.readouterr().out
    assert "incident-0001-backlog_growth" in out
    assert "trigger=alert" in out and "trace=fixed-trace" in out

    assert cli.main(["list", str(tmp_path), "--json"]) == 0
    entries = json.loads(capsys.readouterr().out)
    assert entries[0]["id"] == "incident-0001-backlog_growth"

    assert cli.main(["show", str(tmp_path), "incident-0001-backlog_growth"]) == 0
    doc = json.loads(capsys.readouterr().out)
    assert doc["rule"] == "backlog_growth"
    assert "journal.jsonl" in doc["files_present"]
    with pytest.raises(SystemExit):
        cli.main(["show", str(tmp_path), "incident-0000-nope"])

    trace_out = tmp_path / "incident_trace.json"
    assert cli.main([
        "export", str(tmp_path), "incident-0001-backlog_growth",
        "--out", str(trace_out),
    ]) == 0
    assert "perfetto" in capsys.readouterr().out
    doc = json.load(open(trace_out))
    phases = {e.get("ph") for e in doc["traceEvents"]}
    # the frozen window carried its context, so the exported trace draws
    # the causal arrow from the workload step to the alert
    assert {"s", "f"} <= phases
    assert cli.main(["list", str(tmp_path / "empty")]) == 0
    assert "no bundles" in capsys.readouterr().out


def test_env_fingerprint_reads_the_loaded_jax():
    """``env.json``: with jax loaded (conftest.py imports it) the
    fingerprint names its version and the 8 virtual CPU devices."""
    import platform

    import jax

    fp = incident_lib.env_fingerprint()
    assert fp["python"] == platform.python_version()
    assert fp["jax"] == jax.__version__
    assert fp["backend"] == "cpu"
    assert fp["device_count"] == len(jax.devices())
    json.dumps(fp)


def test_env_fingerprint_never_imports_jax():
    """A capture in a process that never loaded jax leaves it unloaded:
    the fingerprint then carries no jax fields."""
    import subprocess
    import sys

    tel = os.path.join(REPO, "mpi_grid_redistribute_tpu", "telemetry")
    code = (
        "import importlib.util, os, sys, types\n"
        f"tel = {tel!r}\n"
        "pkg = types.ModuleType('cap_pkg')\n"
        "pkg.__path__ = [tel]\n"
        "sys.modules['cap_pkg'] = pkg\n"
        "for name in ('context', 'recorder', 'metrics', 'incident'):\n"
        "    spec = importlib.util.spec_from_file_location(\n"
        "        'cap_pkg.' + name, os.path.join(tel, name + '.py'))\n"
        "    mod = importlib.util.module_from_spec(spec)\n"
        "    sys.modules[spec.name] = mod\n"
        "    spec.loader.exec_module(mod)\n"
        "fp = sys.modules['cap_pkg.incident'].env_fingerprint()\n"
        "assert 'jax' not in sys.modules, 'fingerprint imported jax'\n"
        "assert 'jax' not in fp and 'python' in fp, fp\n"
        "print('clean')\n"
    )
    out = subprocess.run(
        [sys.executable, "-c", code], capture_output=True, text=True,
        cwd=REPO, timeout=120,
    )
    assert out.returncode == 0, out.stderr
    assert out.stdout.strip() == "clean"
