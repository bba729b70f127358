"""service/resident.py: chunked macro-stepping (ISSUE 10).

The chunk *scheduler* — boundary auto-split at snapshot/health cadences,
singleton chunks at fault-eligible steps, per-step journal folding, the
sleep-excluded SLO wall — is backend-independent, so the fault matrix
runs on the numpy oracle at tiny sizes and asserts the whole run is
invariant in ``cfg.chunk``: same final bytes, same fault step, same
journaled ``(step, dropped)`` stream. The jax resident path itself
(``lax.scan`` macro-step, device-resident carry) is exercised in-process
on the 8-virtual-device mesh — chunk-vs-eager particle-set identity,
misaligned snapshot cadence, and a jaxpr walk proving the traced macro
program carries no host callbacks (the dynamic backstop behind gridlint
rule G009). Service-shape speedups are not timed here: a speed is
measured on the chip, and no benchmark cell times the service path yet.
"""

import dataclasses
import os
import time

import numpy as np
import pytest

from mpi_grid_redistribute_tpu.service import (
    CrashFault,
    DriverConfig,
    FallbackFloodFault,
    FaultPlan,
    JournalShardLossFault,
    RestartPolicy,
    ServiceDriver,
    StallError,
    StallFault,
    Supervisor,
    TornSnapshotFault,
)
from mpi_grid_redistribute_tpu.service import elastic, resident
from mpi_grid_redistribute_tpu.telemetry import StepRecorder
from mpi_grid_redistribute_tpu.utils import checkpoint

CHUNKS = (1, 7, 16)


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


def _jax_cfg(tmp_path, **kw):
    base = dict(
        grid_shape=(2, 2, 2),
        n_local=256,
        steps=12,
        seed=5,
        backend="jax",
        snapshot_every=0,
        snapshot_dir=None,
        watchdog_s=0.0,
    )
    base.update(kw)
    return DriverConfig(**base)


def _supervised(cfg, faults, max_restarts=5):
    rec = StepRecorder()

    def factory(grid_shape=None):
        c = cfg
        if grid_shape is not None:
            c = dataclasses.replace(c, grid_shape=tuple(grid_shape))
        return ServiceDriver(c, recorder=rec, faults=faults)

    sup = Supervisor(
        factory,
        policy=RestartPolicy(
            max_restarts=max_restarts, backoff_base_s=0.01,
            backoff_cap_s=0.02,
        ),
        recorder=rec,
        sleep_fn=lambda s: None,
    )
    return sup, rec


def _assert_bit_identical(a, b):
    for name, x, y in zip(("pos", "vel", "ids", "count"), a, b):
        assert x.tobytes() == y.tobytes(), f"{name} diverged"


def _latency_seq(rec):
    """The journaled per-step stream a chunked run must reproduce:
    step numbers and dropped counts (seconds are apportioned wall time,
    legitimately chunk-dependent)."""
    return [
        (e.data["step"], e.data["dropped"])
        for e in rec.events("step_latency")
    ]


# ------------------------------------- fault matrix, chunk-invariant


def _fault_for(kind, workdir):
    """Fresh injector + the per-kind config extras, mirroring
    tests/test_service.py's eager fault matrix."""
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
        extra["journal_dir"] = str(workdir / "journal")
    else:
        fault, restarts = FallbackFloodFault(start_step=1, steps=24), 0
    return fault, restarts, extra


@pytest.mark.parametrize("kind", [
    "crash", "stall", "torn_snapshot", "journal_loss", "fallback_flood",
])
def test_fault_matrix_is_chunk_invariant(tmp_path, kind):
    """Every injector fires at the same step for chunk in {1, 7, 16}
    (singleton chunks at fault-eligible steps) and the run ends
    bit-identical to the chunk=1 run — final state bytes AND the
    journaled (step, dropped) step_latency sequence."""
    results = {}
    for chunk in CHUNKS:
        workdir = tmp_path / f"chunk{chunk}"
        workdir.mkdir()
        fault, restarts, extra = _fault_for(kind, workdir)
        cfg = _cfg(workdir, chunk=chunk, **extra)
        sup, rec = _supervised(cfg, FaultPlan([fault]))
        verdict = sup.run()

        assert verdict.ok is True, (chunk, verdict)
        assert verdict.gave_up is False
        assert verdict.restarts == restarts, (chunk, verdict)
        assert verdict.step == cfg.steps
        fired = rec.events("fault_injected")
        assert len(fired) == 1
        results[chunk] = (
            sup.driver.state, fired[0].data["step"], _latency_seq(rec),
        )

    state1, fault_step1, seq1 = results[1]
    for chunk in CHUNKS[1:]:
        state, fault_step, seq = results[chunk]
        _assert_bit_identical(state, state1)
        assert fault_step == fault_step1, f"chunk={chunk}"
        assert seq == seq1, f"chunk={chunk}"


# ------------------------------------------- jax resident path, in-process


def test_jax_chunked_matches_eager(tmp_path):
    """chunk=5 on the resident lax.scan path vs chunk=1 on the eager
    per-step path, same seed/steps: identical particle set and an
    identical journaled (step, dropped) stream."""
    states, seqs = {}, {}
    for chunk in (1, 5):
        drv = ServiceDriver(_jax_cfg(tmp_path, chunk=chunk))
        drv.init_state()
        drv.run()
        drv.close()
        states[chunk] = drv.state
        seqs[chunk] = _latency_seq(drv.recorder)
    assert elastic.particle_set(*states[5]) == elastic.particle_set(
        *states[1]
    )
    assert states[5][3].tobytes() == states[1][3].tobytes()  # count
    assert seqs[5] == seqs[1]


def test_snapshot_cadence_survives_misaligned_chunk(tmp_path):
    """snapshot_every=6 with chunk=4 (6 % 4 != 0): chunks auto-split so
    snapshots land exactly at steps 6 and 12, from state bit-identical
    to the chunk=1 run's."""
    states = {}
    for chunk in (1, 4):
        snap_dir = tmp_path / f"snaps{chunk}"
        cfg = _jax_cfg(
            tmp_path, chunk=chunk, snapshot_every=6,
            snapshot_dir=str(snap_dir),
        )
        drv = ServiceDriver(cfg)
        drv.init_state()
        drv.run()
        drv.close()
        snaps = checkpoint.list_snapshots(cfg.snapshot_dir)
        steps = sorted(
            int(os.path.basename(p).split("_")[1]) for p in snaps
        )
        assert steps == [6, 12], f"chunk={chunk}"
        states[chunk] = drv.state
    assert elastic.particle_set(*states[4]) == elastic.particle_set(
        *states[1]
    )


# the jaxpr walk lives in the semantic analyzer now (progcheck's public
# API; rule J002 runs this same check over every resident-marked
# program in the registry)
from mpi_grid_redistribute_tpu.analysis.progcheck import (  # noqa: E402
    primitive_names,
)


def test_macro_step_jaxpr_has_no_host_callbacks(tmp_path):
    """The dynamic backstop behind gridlint G009: the traced chunk
    program must be pure device code — no callback/infeed/outfeed
    primitive anywhere in the scan body or its sub-jaxprs, so nothing
    can sync to the host between chunk boundaries."""
    import jax

    drv = ServiceDriver(_jax_cfg(tmp_path))
    drv.init_state()
    drv._ensure_built()
    pos, vel, ids, count = drv.state
    macro, _, _ = resident.make_chunk_fn(drv._rd, drv.cfg.dt, 4,
                                         pos, vel, ids)
    jaxpr = jax.make_jaxpr(macro)(pos, vel, ids, count)
    names = primitive_names(jaxpr.jaxpr)
    assert "scan" in names, "macro-step lost its lax.scan"
    hostile = [
        n for n in names
        if "callback" in n or "infeed" in n or "outfeed" in n
    ]
    assert not hostile, f"host syncs traced into the macro-step: {hostile}"
    drv.close()


# ----------------------------------------- step_sleep vs SLO wall


def test_step_sleep_excluded_from_step_latency(tmp_path):
    """Hand-math: 4 steps paced at step_sleep=0.1 must take >= 0.4s of
    wall clock, yet every journaled step_latency ``seconds`` (and hence
    the SLO histograms and the AmortizationGuard's step EMA fed from
    it) stays far below the 0.1s sleep — pacing is not latency."""
    cfg = _cfg(
        tmp_path, n_local=64, steps=4, snapshot_every=0,
        snapshot_dir=None, step_sleep=0.1,
    )
    drv = ServiceDriver(cfg)
    drv.init_state()
    t0 = time.perf_counter()
    drv.run()
    elapsed = time.perf_counter() - t0
    drv.close()
    evs = drv.recorder.events("step_latency")
    assert [e.data["step"] for e in evs] == [1, 2, 3, 4]
    assert elapsed >= 4 * 0.1  # the pacing itself still happened
    for e in evs:
        assert e.data["seconds"] < 0.05, (
            "step_sleep leaked into the journaled step wall"
        )


def test_step_sleep_still_counts_against_watchdog(tmp_path):
    """The other half of the contract: a sleep longer than watchdog_s
    IS a stall (a stuck pacing sleep must not hide from the watchdog),
    even though the journaled seconds — recorded before the raise —
    stay under the budget."""
    cfg = _cfg(
        tmp_path, n_local=64, steps=3, snapshot_every=0,
        snapshot_dir=None, step_sleep=0.1, watchdog_s=0.05,
    )
    drv = ServiceDriver(cfg)
    drv.init_state()
    with pytest.raises(StallError, match="watchdog"):
        drv.run()
    evs = drv.recorder.events("step_latency")
    assert len(evs) == 1 and evs[0].data["step"] == 1
    assert evs[0].data["seconds"] < cfg.watchdog_s


# ----------------------------------------- rebalance trigger rules


def _backlog_events(rec, backlogs):
    # monotone nonzero backlog growth across a window of migrate_step
    # events is exactly what trips health.backlog_growth (test_flow.py)
    for s, b in enumerate(backlogs):
        rec.record(
            "migrate_step", step=s, sent=10, received=10, backlog=b,
            dropped_recv=0, population=100,
        )


def test_backlog_growth_triggers_rebalance_and_journals_rule():
    cfg = DriverConfig(
        grid_shape=(2, 2, 2), n_local=256, steps=8, backend="numpy",
        snapshot_every=0, rebalance=True,
    )
    drv = ServiceDriver(cfg)
    drv.init_state()
    _backlog_events(drv.recorder, [0, 5, 9, 14, 20])
    drv._health_check()
    evs = [e.data for e in drv.recorder.events("rebalance")]
    assert len(evs) == 1, "backlog_growth ALERT never reached the planner"
    assert evs[0]["rule"] == "backlog_growth"


def test_rebalance_on_filters_trigger_rules():
    """With backlog_growth removed from rebalance_on, the same ALERT
    must NOT actuate — the trigger-rule set is policy, not advisory."""
    cfg = DriverConfig(
        grid_shape=(2, 2, 2), n_local=256, steps=8, backend="numpy",
        snapshot_every=0, rebalance=True,
        rebalance_on=("imbalance_ratio",),
    )
    drv = ServiceDriver(cfg)
    drv.init_state()
    _backlog_events(drv.recorder, [0, 5, 9, 14, 20])
    verdict = drv._health_check()
    assert any(
        f["rule"] == "backlog_growth" for f in verdict["findings"]
    )
    assert drv.recorder.events("rebalance") == []
