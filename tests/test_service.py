"""service/: driver snapshot/restore, supervisor, fault matrix (ISSUE 6).

Everything runs the numpy backend at tiny sizes — the recovery logic
under test is backend-independent, and the CPU oracle keeps the whole
fault matrix inside the tier-1 budget. The jax path's crash and
device-loss restores run here on the 8 CPU devices
(``test_jax_backend_supervised_restore``); ``chip_smoke.py`` and
``scripts/pod_smoke.py --kill-restore`` restore a snapshot on a chip.
"""

import dataclasses
import json
import os
import subprocess
import sys
import time

import numpy as np
import pytest

from mpi_grid_redistribute_tpu.service import (
    CrashFault,
    DeviceLossFault,
    DriverConfig,
    ElasticRestoreError,
    FallbackFloodFault,
    FaultPlan,
    InjectedCrash,
    JournalShardLossFault,
    LatencySpikeFault,
    RestartPolicy,
    ServiceDriver,
    StallFault,
    Supervisor,
    TornSnapshotFault,
)
from mpi_grid_redistribute_tpu.service import elastic
from mpi_grid_redistribute_tpu.telemetry import StepRecorder
from mpi_grid_redistribute_tpu.telemetry import health
from mpi_grid_redistribute_tpu.utils import checkpoint


def _cfg(tmp_path, **kw):
    base = dict(
        grid_shape=(2, 2, 2),
        n_local=256,
        steps=24,
        seed=3,
        backend="numpy",
        snapshot_every=4,
        snapshot_dir=str(tmp_path / "snaps"),
    )
    base.update(kw)
    return DriverConfig(**base)


def _reference_state(cfg):
    """The uninterrupted trajectory: same config, snapshots/journal off
    (neither may influence the state for restarts to be bit-exact)."""
    ref = ServiceDriver(
        dataclasses.replace(
            cfg, snapshot_every=0, snapshot_dir=None, journal_dir=None,
            watchdog_s=0.0,
        )
    )
    ref.init_state()
    state = ref.run()
    ref.close()
    return state


def _assert_bit_identical(a, b):
    for name, x, y in zip(("pos", "vel", "ids", "count"), a, b):
        assert x.tobytes() == y.tobytes(), f"{name} diverged"


# ------------------------------------------------------- driver basics


def test_driver_config_validation(tmp_path):
    with pytest.raises(ValueError, match="snapshot_dir"):
        ServiceDriver(_cfg(tmp_path, snapshot_dir=None))
    with pytest.raises(ValueError, match="keep_snapshots"):
        ServiceDriver(_cfg(tmp_path, keep_snapshots=1))


def test_snapshot_restore_bit_identical(tmp_path):
    cfg = _cfg(tmp_path, keep_snapshots=2)
    drv = ServiceDriver(cfg)
    drv.init_state()
    drv.run(max_steps=10)  # past two snapshot points (steps 4 and 8)
    drv.close()

    # pruning: only keep_snapshots newest survive on disk
    snaps = checkpoint.list_snapshots(cfg.snapshot_dir)
    assert len(snaps) == 2

    resumed = ServiceDriver(cfg)
    assert resumed.restore_latest() is True
    assert resumed.step == 8
    ev = resumed.recorder.last("restore")
    assert ev.data["what"] == "state" and ev.data["step"] == 8
    assert ev.data["snapshots_skipped"] == 0
    resumed.run()  # 8 -> 24 entirely from the restored snapshot
    resumed.close()
    _assert_bit_identical(resumed.state, _reference_state(cfg))


def test_restore_latest_without_snapshots(tmp_path):
    drv = ServiceDriver(_cfg(tmp_path, snapshot_every=0, snapshot_dir=None))
    assert drv.restore_latest() is False
    drv2 = ServiceDriver(_cfg(tmp_path))  # dir configured but empty
    assert drv2.restore_latest() is False


# ------------------------------------------------------- fault matrix


def _supervised(tmp_path, cfg, faults, max_restarts=5, **policy_kw):
    rec = StepRecorder()

    def factory(grid_shape=None):
        # the supervisor's shrink policy restarts onto a smaller grid by
        # passing grid_shape; a plain restart keeps the configured one
        c = cfg
        if grid_shape is not None:
            c = dataclasses.replace(c, grid_shape=tuple(grid_shape))
        return ServiceDriver(c, recorder=rec, faults=faults)

    sup = Supervisor(
        factory,
        policy=RestartPolicy(
            max_restarts=max_restarts, backoff_base_s=0.01,
            backoff_cap_s=0.02, **policy_kw,
        ),
        recorder=rec,
        sleep_fn=lambda s: None,
    )
    return sup, rec


@pytest.mark.parametrize("kind", [
    "crash", "stall", "torn_snapshot", "journal_loss", "fallback_flood",
])
def test_fault_matrix(tmp_path, kind):
    extra = {}
    if kind == "crash":
        fault, restarts = CrashFault(9), 1
    elif kind == "stall":
        fault, restarts = StallFault(7, seconds=0.5), 1
        extra["watchdog_s"] = 0.2
    elif kind == "torn_snapshot":
        fault, restarts = TornSnapshotFault(snapshot_index=1), 1
    elif kind == "journal_loss":
        fault, restarts = JournalShardLossFault(6), 0
        extra["journal_dir"] = str(tmp_path / "journal")
    else:
        fault, restarts = FallbackFloodFault(start_step=1, steps=24), 0

    cfg = _cfg(tmp_path, **extra)
    sup, rec = _supervised(tmp_path, cfg, FaultPlan([fault]))
    verdict = sup.run()

    # every fault mode ends in a healthy, completed service
    assert verdict.ok is True, verdict
    assert verdict.gave_up is False
    assert verdict.restarts == restarts
    assert verdict.step == cfg.steps
    counts = rec.counts()
    assert counts.get("fault_injected") == 1
    assert counts.get("restart", 0) == restarts

    if kind in ("crash", "stall", "torn_snapshot"):
        # restarted from a snapshot: a journaled restore, then a resumed
        # trajectory byte-equal to the uninterrupted run
        restores = [
            e for e in rec.events("restore")
            if e.data.get("what") == "state"
        ]
        assert len(restores) == 1
        _assert_bit_identical(sup.driver.state, _reference_state(cfg))
        if kind == "torn_snapshot":
            # the corrupted newest snapshot was skipped, not loaded
            assert restores[0].data["snapshots_skipped"] >= 1
            assert restores[0].data["step"] == 4
    if kind == "stall":
        assert "StallError" in rec.last("restart").data["reason"]
    if kind == "journal_loss":
        # loss detected and healed: shard re-exported with the retained
        # window, restore(what=journal) journaled, file back on disk
        heals = [
            e for e in rec.events("restore")
            if e.data.get("what") == "journal"
        ]
        assert len(heals) == 1
        assert os.path.exists(sup.driver.journal_path)
        _assert_bit_identical(sup.driver.state, _reference_state(cfg))
    if kind == "fallback_flood":
        # graceful degrade: exactly one engine -> planar transition,
        # pinned for the rest of the run (never flaps back)
        degrades = rec.events("degrade")
        assert len(degrades) == 1
        assert degrades[0].data["to"] == "planar"
        assert sup.driver.degraded is True
        assert sup.driver.engine == "planar"
        assert verdict.health == "WARN"  # rule still firing, not ALERT


def test_crash_loop_trips_circuit_breaker(tmp_path):
    cfg = _cfg(tmp_path, steps=12)
    sup, rec = _supervised(
        tmp_path, cfg, FaultPlan([CrashFault(None)]), max_restarts=3
    )
    verdict = sup.run()
    assert verdict.ok is False
    assert verdict.gave_up is True
    assert verdict.restarts == 3
    assert "circuit breaker" in verdict.reason
    actions = [e.data["action"] for e in rec.events("restart")]
    assert actions == ["restart"] * 3 + ["give_up"]
    # backoff grows (bounded exponential; jitter keeps it monotone here)
    backoffs = [
        e.data["backoff_s"] for e in rec.events("restart")
        if e.data["action"] == "restart"
    ]
    assert all(b > 0 for b in backoffs)


def test_healthz_alert_forces_restart(tmp_path):
    # a clean exit with a red /healthz is a failure: the supervisor must
    # restart, and a deterministic alert must end at the breaker
    always_red = health.HealthRule(
        "always_red", health.ALERT, lambda rec: "synthetic alert"
    )
    cfg = _cfg(tmp_path, steps=6, snapshot_every=0, snapshot_dir=None)
    rec = StepRecorder()
    sup = Supervisor(
        lambda: ServiceDriver(
            cfg, recorder=rec,
            monitor=health.HealthMonitor(rec, rules=[always_red]),
        ),
        policy=RestartPolicy(max_restarts=2, backoff_base_s=0.01),
        recorder=rec,
        sleep_fn=lambda s: None,
    )
    verdict = sup.run()
    assert verdict.ok is False and verdict.gave_up is True
    assert verdict.health == "ALERT"
    assert "healthz 503" in verdict.reason
    restart = [
        e for e in rec.events("restart") if e.data["action"] == "restart"
    ]
    assert all("healthz 503" in e.data["reason"] for e in restart)


# ------------------------------------------- elastic restore (ISSUE 8)


def test_device_loss_shrink_restore_preserves_particle_set(tmp_path):
    # crash at step 9, and every restore after the crash sees only 4 of
    # the 8 devices: the driver must shrink-to-fit (2,2,2)->(1,2,2),
    # re-shard the snapshot, and finish with the SAME global particles
    cfg = _cfg(tmp_path)
    plan = FaultPlan([CrashFault(9), DeviceLossFault(4)])
    sup, rec = _supervised(tmp_path, cfg, plan)
    verdict = sup.run()

    assert verdict.ok is True, verdict
    assert verdict.restarts == 1
    assert verdict.step == cfg.steps
    assert tuple(sup.driver.cfg.grid_shape) == (1, 2, 2)
    # capacity preserved: half the vranks, double the padded rows
    assert sup.driver.cfg.n_local == 512
    assert rec.counts().get("fault_injected") == 2

    (ev,) = rec.events("reshard")
    assert ev.data["old_grid"] == [2, 2, 2]
    assert ev.data["old_shards"] == 8
    assert ev.data["old_rows_per_shard"] == 256
    assert ev.data["new_grid"] == [1, 2, 2]
    assert ev.data["new_rows_per_shard"] == 512
    assert ev.data["step"] == 8  # resharded the step-8 snapshot
    assert 0 < ev.data["moved"] <= ev.data["rows"]

    # mesh shapes differ, so compare the id-sorted global particle SET
    # (and total row conservation), not the padded per-vrank layout
    ref = _reference_state(cfg)
    assert int(sup.driver.state[3].sum()) == int(ref[3].sum())
    assert elastic.particle_set(*sup.driver.state) == \
        elastic.particle_set(*ref)


@pytest.mark.parametrize("leg", ["crash", "device_loss"])
def test_jax_backend_supervised_restore(tmp_path, leg):
    """The recovery legs on the jax backend (a 2x2x2 grid, one rank on
    each of the 8 CPU devices): one injected crash restores
    bit-identically to the uninterrupted run; a crash plus the loss of
    half the devices shrink-restores onto a smaller grid with the same
    id-sorted particle set."""
    cfg = _cfg(
        tmp_path, backend="jax", steps=12, snapshot_every=3, seed=11,
    )
    faults = [CrashFault(7)]
    if leg == "device_loss":
        faults.append(DeviceLossFault(4))
    sup, rec = _supervised(tmp_path, cfg, FaultPlan(faults))
    verdict = sup.run()
    ref = _reference_state(cfg)

    assert verdict.ok is True, verdict
    assert verdict.restarts == 1
    assert verdict.step == cfg.steps
    if leg == "crash":
        _assert_bit_identical(sup.driver.state, ref)
    else:
        assert tuple(sup.driver.cfg.grid_shape) != cfg.grid_shape
        assert len(rec.events("reshard")) == 1
        assert elastic.particle_set(*sup.driver.state) == \
            elastic.particle_set(*ref)


def test_restore_latest_onto_explicit_grid(tmp_path):
    cfg = _cfg(tmp_path)
    drv = ServiceDriver(cfg)
    drv.init_state()
    drv.run(max_steps=8)
    drv.close()

    res = ServiceDriver(_cfg(tmp_path))
    assert res.restore_latest(grid_shape=(1, 2, 2)) is True
    assert res.step == 8
    assert tuple(res.cfg.grid_shape) == (1, 2, 2)
    assert res.cfg.n_local == 512
    ev = res.recorder.last("reshard")
    assert ev.data["new_grid"] == [1, 2, 2]
    # live rows conserved through the reshard
    assert int(res.state[3].sum()) == int(drv.state[3].sum())
    res.run()  # 8 -> 24 on the smaller mesh
    res.close()
    assert elastic.particle_set(*res.state) == \
        elastic.particle_set(*_reference_state(cfg))


def test_elastic_restore_disabled_raises_naming_both_shapes(tmp_path):
    cfg = _cfg(tmp_path)
    drv = ServiceDriver(cfg)
    drv.init_state()
    drv.run(max_steps=4)
    drv.close()

    # the same layout restores fine with auto_reshard off
    same = ServiceDriver(_cfg(tmp_path, auto_reshard=False))
    assert same.restore_latest() is True

    # a different layout must fail FAST with both shapes in the message
    strict = ServiceDriver(
        _cfg(tmp_path, grid_shape=(1, 2, 2), n_local=512,
             auto_reshard=False)
    )
    with pytest.raises(ElasticRestoreError) as ei:
        strict.restore_latest()
    msg = str(ei.value)
    assert "(2, 2, 2)" in msg and "(1, 2, 2)" in msg
    assert "auto_reshard is disabled" in msg


def test_slo_breach_restarts_then_shrinks(tmp_path):
    # a latency-spike flood breaches the p99 SLO at the step-4 health
    # check -> restart; the leftover spikes breach again -> second
    # consecutive breach trips the shrink policy -> restart onto
    # shrink_shape((2,2,2)) with an elastic re-shard; the spike budget is
    # then spent, so the third attempt completes clean with no operator
    # input anywhere
    cfg = _cfg(
        tmp_path, steps=32, slo_latency_p99_s=0.25, slo_window=4,
    )
    plan = FaultPlan([LatencySpikeFault(2, seconds=1.0, spikes=6)])
    sup, rec = _supervised(tmp_path, cfg, plan, shrink_after=2)
    verdict = sup.run()

    assert verdict.ok is True, verdict
    assert verdict.restarts == 2
    assert tuple(sup.driver.cfg.grid_shape) == (1, 2, 2)
    actions = [e.data["action"] for e in rec.events("restart")]
    assert actions == ["restart", "shrink", "restart"]
    reasons = [
        e.data["reason"] for e in rec.events("restart")
        if e.data["action"] == "restart"
    ]
    assert all("SLOBreachError" in r for r in reasons)
    assert all("slo_latency_p99" in r for r in reasons)
    (shrink,) = [
        e for e in rec.events("restart") if e.data["action"] == "shrink"
    ]
    assert shrink.data["old_grid"] == [2, 2, 2]
    assert shrink.data["new_grid"] == [1, 2, 2]
    assert len(rec.events("reshard")) == 1
    assert rec.counts().get("fault_injected") == 1


# ------------------------------------------------- breaker boundaries


class _FailFirstN:
    """Scripted injector: crash the first ``n`` runs (at step 1), then
    let every later run succeed — exact failure counts for boundary
    tests, where CrashFault(None) can only fail forever."""

    kind = "fail_first_n"

    def __init__(self, n):
        self.left = int(n)

    def before_step(self, driver):
        if self.left > 0 and driver.step == 1:
            self.left -= 1
            raise InjectedCrash("scripted failure")


def _ticking_clock(spacing):
    """Deterministic clock: each restart loop reads the same instant
    twice (breaker check + window append), instants ``spacing`` apart."""

    def gen():
        t = 0.0
        while True:
            yield t
            yield t
            t += spacing

    it = gen()
    return lambda: next(it)


def _boundary_sup(tmp_path, n_failures, policy, clock):
    cfg = _cfg(tmp_path, steps=4, snapshot_every=0, snapshot_dir=None)
    rec = StepRecorder()
    plan = FaultPlan([_FailFirstN(n_failures)])
    sup = Supervisor(
        lambda: ServiceDriver(cfg, recorder=rec, faults=plan),
        policy=policy,
        recorder=rec,
        sleep_fn=lambda s: None,
        clock=clock,
    )
    return sup, rec


def test_breaker_count_boundary(tmp_path):
    # all failures at one instant (static clock): exactly max_restarts
    # failures must NOT trip the breaker (the max_restarts-th restart is
    # still granted), one more must
    policy = RestartPolicy(
        max_restarts=3, backoff_base_s=0.01, backoff_cap_s=0.02
    )
    sup, rec = _boundary_sup(tmp_path, 3, policy, lambda: 0.0)
    verdict = sup.run()
    assert verdict.ok is True and verdict.gave_up is False
    assert verdict.restarts == 3

    sup, rec = _boundary_sup(tmp_path, 4, policy, lambda: 0.0)
    verdict = sup.run()
    assert verdict.ok is False and verdict.gave_up is True
    assert verdict.restarts == 3
    actions = [e.data["action"] for e in rec.events("restart")]
    assert actions == ["restart"] * 3 + ["give_up"]


def test_breaker_window_boundary_is_inclusive(tmp_path):
    # failures spaced EXACTLY window_s apart: the inclusive window keeps
    # at most one prior restart in view, so max_restarts=2 never trips
    # even through 5 straight failures
    policy = RestartPolicy(
        max_restarts=2, window_s=10.0, backoff_base_s=0.01,
        backoff_cap_s=0.02,
    )
    sup, rec = _boundary_sup(tmp_path, 5, policy, _ticking_clock(10.0))
    verdict = sup.run()
    assert verdict.ok is True and verdict.gave_up is False
    assert verdict.restarts == 5

    # the same failures clustered INSIDE the window (spacing < window_s)
    # trip the breaker at the count boundary
    sup, rec = _boundary_sup(tmp_path, 5, policy, _ticking_clock(5.0))
    verdict = sup.run()
    assert verdict.ok is False and verdict.gave_up is True
    assert verdict.restarts == 2
    actions = [e.data["action"] for e in rec.events("restart")]
    assert actions == ["restart"] * 2 + ["give_up"]


def test_backoff_jitter_deterministic_under_seed(tmp_path):
    def backoffs(seed):
        policy = RestartPolicy(
            max_restarts=5, backoff_base_s=0.01, backoff_cap_s=1.0,
            seed=seed,
        )
        sup, rec = _boundary_sup(tmp_path, 3, policy, lambda: 0.0)
        assert sup.run().ok is True
        return [
            e.data["backoff_s"] for e in rec.events("restart")
            if e.data["action"] == "restart"
        ]

    a = backoffs(7)
    assert len(a) == 3
    # the jitter stream is seeded: same seed -> identical journaled
    # schedule; different seed -> different jitter
    assert backoffs(7) == a
    assert backoffs(8) != a
    # bounded exponential under jitter in [1, 1+jitter): each attempt's
    # floor (base*2^k) clears the previous attempt's ceiling
    assert a == sorted(a) and all(x > 0 for x in a)


# ------------------------------------------------- plan and health rule


def test_seeded_fault_plan_is_deterministic():
    a = FaultPlan.seeded(7, 30)
    b = FaultPlan.seeded(7, 30)
    assert len(a.faults) == 5
    sig = lambda plan: [
        (type(f).__name__, getattr(f, "step", getattr(f, "start_step", None)))
        for f in plan.faults
    ]
    assert sig(a) == sig(b)
    assert sig(FaultPlan.seeded(8, 30)) != sig(a)
    with pytest.raises(ValueError, match="steps"):
        FaultPlan.seeded(0, 1)


def test_snapshot_staleness_rule():
    rec = StepRecorder()
    mon = health.HealthMonitor(rec, rules=[health.snapshot_staleness()])
    # quiet: no snapshot yet, then cadence unknown (cold EMA), then fresh
    assert mon.evaluate(record=False)["status"] == "OK"
    rec.record("snapshot", step=4, cadence_s=0.0)
    assert mon.evaluate(record=False)["status"] == "OK"
    rec.record("snapshot", step=8, cadence_s=60.0)
    assert mon.evaluate(record=False)["status"] == "OK"
    # a snapshot event far older than 2x its own cadence: writer is dead
    rec.record_at("snapshot", time.time() - 10.0, step=12, cadence_s=1.0)
    verdict = mon.evaluate(record=False)
    assert verdict["status"] == "WARN"
    (finding,) = verdict["findings"]
    assert finding["rule"] == "snapshot_staleness"
    assert "stalled or dead" in finding["reason"]


# ------------------------------------------------------------------ CLI


def _service_cmd(*args):
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    cmd = [
        sys.executable, "-m", "mpi_grid_redistribute_tpu.service",
        "--backend", "numpy", "--grid", "2,2,2", "--n-local", "128",
    ] + list(args)
    return subprocess.run(
        cmd, env=env, capture_output=True, text=True, timeout=180
    )


def test_cli_breaker_exit_code():
    r = _service_cmd(
        "--steps", "8", "--supervise", "--inject-crash", "-1",
        "--max-restarts", "2", "--backoff-base", "0.01",
        "--backoff-cap", "0.02",
    )
    assert r.returncode == 3, r.stderr
    verdict = json.loads(r.stdout.strip().splitlines()[-1])
    assert verdict["ok"] is False
    assert verdict["gave_up"] is True
    assert verdict["restarts"] == 2


def test_cli_hard_crash_then_resume_bit_identical(tmp_path):
    snaps = str(tmp_path / "snaps")
    common = ["--steps", "10", "--seed", "5", "--snapshot-every", "3"]
    # run 1: os._exit(13) at step 7, after committed snapshots at 3 and 6
    r = _service_cmd(
        *common, "--snapshot-dir", snaps, "--sync-snapshots",
        "--inject-crash", "7", "--hard-crash",
    )
    assert r.returncode == 13, r.stderr
    # run 2: resumes from the newest committed snapshot, finishes
    out = tmp_path / "resumed.npz"
    r = _service_cmd(
        *common, "--snapshot-dir", snaps, "--final-out", str(out),
    )
    assert r.returncode == 0, r.stderr
    # reference: uninterrupted run in a fresh snapshot dir
    ref_out = tmp_path / "ref.npz"
    r = _service_cmd(
        *common, "--snapshot-dir", str(tmp_path / "ref_snaps"),
        "--final-out", str(ref_out),
    )
    assert r.returncode == 0, r.stderr
    got, ref = np.load(out), np.load(ref_out)
    assert int(got["step"]) == int(ref["step"]) == 10
    for k in ("pos", "vel", "count"):
        assert got[k].tobytes() == ref[k].tobytes(), k
