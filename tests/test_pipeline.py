"""service/pipeline.py: software-pipelined macro-step (ISSUE 12).

The pipelined scan body reorders the SAME two kernels the sequential
body runs (land step k's exchange; drift+bin step k+1), so everything
observable must be preserved: the final particle SET and per-rank
counts (row order within a rank legitimately differs — resident-slot
layout compacted once at the chunk boundary), the journaled
``(step, dropped)`` stream, and the fault matrix's behavior at every
chunk length. The degrade contract is build-time and total: chunk < 2,
ragged receive capacity and the multi-device topology must hand back
the sequential builder's macro bit-exactly (including its
``ResidentLayoutError``), each journaled as an ``engine_resolved``
event. The overlap itself is a TRACE property, asserted on the jaxpr:
the steady-state cond's pipelined branch issues step k+1's binning
(``floor``) before step k's landing consumer (``scatter``); the
sequential branch does the opposite. Service-shape speedups are not
timed here: a speed is measured on the chip, and no benchmark cell
times the service path yet.
"""

import dataclasses

import numpy as np
import pytest

from mpi_grid_redistribute_tpu.analysis import progcheck, rules_jaxpr
from mpi_grid_redistribute_tpu.service import (
    CrashFault,
    DriverConfig,
    FallbackFloodFault,
    FaultPlan,
    JournalShardLossFault,
    RestartPolicy,
    ServiceDriver,
    StallFault,
    Supervisor,
    TornSnapshotFault,
)
from mpi_grid_redistribute_tpu.service import elastic, pipeline, resident
from mpi_grid_redistribute_tpu.telemetry import StepRecorder

# chunk=1 rides the matrix as the must-degrade case (build-time
# delegation to the sequential builder); 2 is the smallest armed
# steady state (one in-flight exchange); 7 does not divide the
# horizon; 16 crosses every snapshot/fault split boundary.
CHUNKS = (1, 2, 7, 16)

# 16 ranks > the 8 forced host devices -> the vmapped vranks topology,
# the one the two-phase schedule arms on (conftest.py forces
# xla_force_host_platform_device_count=8; an 8-rank grid would resolve
# sharded and degrade).
_GRID = (2, 2, 4)


def _cfg(tmp_path, **kw):
    base = dict(
        grid_shape=_GRID,
        n_local=64,
        steps=24,
        seed=3,
        backend="jax",
        snapshot_every=4,
        snapshot_dir=str(tmp_path / "snaps"),
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


def _latency_seq(rec):
    return [
        (e.data["step"], e.data["dropped"])
        for e in rec.events("step_latency")
    ]


def _pipeline_reasons(rec):
    return [
        e.data["reason"]
        for e in rec.events("engine_resolved")
        if str(e.data.get("reason", "")).startswith("pipeline:")
    ]


def _fault_for(kind, workdir):
    """Fresh injector + per-kind config extras (test_resident.py's
    matrix, on the jax backend)."""
    extra = {}
    if kind == "crash":
        fault, restarts = CrashFault(9), 1
    elif kind == "stall":
        # jax compile steps journal up to ~0.7s of wall on the forced
        # 8-device CPU mesh, so the watchdog budget sits well above
        # that and the stall well above the budget
        fault, restarts = StallFault(7, seconds=3.0), 1
        extra["watchdog_s"] = 2.0
    elif kind == "torn_snapshot":
        fault, restarts = TornSnapshotFault(snapshot_index=1), 1
    elif kind == "journal_loss":
        fault, restarts = JournalShardLossFault(6), 0
        extra["journal_dir"] = str(workdir / "journal")
    else:
        fault, restarts = FallbackFloodFault(start_step=1, steps=24), 0
    return fault, restarts, extra


def _supervised_run(workdir, kind, chunk, pipelined):
    fault, restarts, extra = _fault_for(kind, workdir)
    cfg = _cfg(workdir, chunk=chunk, pipeline=pipelined, **extra)
    sup, rec = _supervised(cfg, FaultPlan([fault]))
    verdict = sup.run()
    assert verdict.ok is True, (kind, chunk, pipelined, verdict)
    assert verdict.gave_up is False
    assert verdict.restarts == restarts, (kind, chunk, pipelined, verdict)
    assert verdict.step == cfg.steps
    fired = rec.events("fault_injected")
    assert len(fired) == 1
    return (
        elastic.particle_set(*sup.driver.state),
        np.asarray(sup.driver.state[3]).tobytes(),
        fired[0].data["step"],
        _latency_seq(rec),
        _pipeline_reasons(rec),
    )


# ------------------------------ fault matrix, pipelined == sequential


@pytest.mark.parametrize("kind", [
    "crash", "stall", "torn_snapshot", "journal_loss", "fallback_flood",
])
def test_fault_matrix_pipelined_matches_sequential(tmp_path, kind):
    """Every injector fires at the same step with the pipelined body at
    chunk in {1, 2, 7, 16} as with the sequential chunk=1 reference,
    ending with the identical particle set, per-rank counts and
    journaled (step, dropped) stream. chunk=1 doubles as the
    must-degrade leg: its run must journal the chunk<2 degrade reason
    and never arm."""
    ref_dir = tmp_path / "seq"
    ref_dir.mkdir()
    ref_set, ref_counts, ref_fault, ref_seq, _ = _supervised_run(
        ref_dir, kind, 1, False
    )
    for chunk in CHUNKS:
        workdir = tmp_path / f"pipe{chunk}"
        workdir.mkdir()
        pset, counts, fault_step, seq, reasons = _supervised_run(
            workdir, kind, chunk, True
        )
        assert pset == ref_set, (kind, chunk)
        assert counts == ref_counts, (kind, chunk)
        assert fault_step == ref_fault, (kind, chunk)
        assert seq == ref_seq, (kind, chunk)
        if chunk == 1:
            # the driver goes eager at chunk=1; any chunk the scheduler
            # does dispatch resident must have degraded, never armed
            assert not any("armed" in r for r in reasons), reasons
        elif kind != "fallback_flood":
            # fallback_flood marks the WHOLE horizon fault-eligible, so
            # the scheduler splits every chunk to a singleton and runs
            # eager — no resident dispatch, hence no resolution to arm
            assert any(
                r.startswith("pipeline: armed") for r in reasons
            ), (kind, chunk, reasons)


# --------------------------------- direct macro identity (no driver)


def _template_state(rd, n_local, seed=11):
    """Random positions/velocities with 25% free slots per rank: enough
    headroom that every mover is granted — the macro-level identity
    contract covers clean (no-drop, no-backlog) trajectories; dirty
    chunks are the driver's discard + eager-rerun territory (the fault
    matrix above exercises that path end to end)."""
    import jax.numpy as jnp

    R = rd.nranks
    shape = np.asarray(rd.grid.shape, np.float32)
    rng = np.random.default_rng(seed)
    pos = np.empty((R * n_local, 3), np.float32)
    for coords in np.ndindex(*rd.grid.shape):
        r = rd.grid.rank_of_cell(coords)
        pos[r * n_local : (r + 1) * n_local] = (
            np.asarray(coords, np.float32)
            + rng.random((n_local, 3), dtype=np.float32)
        ) / shape
    vel = jnp.asarray(
        (rng.random((R * n_local, 3), dtype=np.float32) - 0.5) * 0.2
    )
    ids = jnp.arange(R * n_local, dtype=jnp.int32)
    count = jnp.full((R,), 3 * n_local // 4, jnp.int32)
    return jnp.asarray(pos), vel, ids, count


def _mk_rd(**kw):
    from mpi_grid_redistribute_tpu import api
    from mpi_grid_redistribute_tpu.domain import ProcessGrid

    base = dict(
        grid=ProcessGrid(_GRID),
        lo=(0.0,) * 3,
        hi=(1.0,) * 3,
        periodic=(True,) * 3,
        engine="auto",
    )
    base.update(kw)
    return api.GridRedistribute(**base)


def test_pipelined_macro_matches_sequential_stats():
    """One chunk=7 macro-step pair on identical inputs: same particle
    set, same counts, same per-step count trajectory, same send_counts
    tables, zero drops on both, and every step's stats.pipeline flag
    set (clean flow: the runtime cond always arms)."""
    rd = _mk_rd()
    pos, vel, ids, count = _template_state(rd, 64)
    seq_macro, _, _ = resident.make_chunk_fn(rd, 0.05, 7, pos, vel, ids)
    pipe_macro, _, _ = pipeline.make_pipelined_chunk_fn(
        rd, 0.05, 7, pos, vel, ids
    )
    assert getattr(pipe_macro.__wrapped__, "_progcheck_pipeline", False)

    (s_pos, s_vel, s_ids, s_count), s_ys = seq_macro(pos, vel, ids, count)
    (p_pos, p_vel, p_ids, p_count), p_ys = pipe_macro(pos, vel, ids, count)

    assert elastic.particle_set(
        np.asarray(p_pos), np.asarray(p_vel),
        np.asarray(p_ids), np.asarray(p_count),
    ) == elastic.particle_set(
        np.asarray(s_pos), np.asarray(s_vel),
        np.asarray(s_ids), np.asarray(s_count),
    )
    assert np.array_equal(np.asarray(p_count), np.asarray(s_count))
    assert np.array_equal(
        np.asarray(p_ys["count"]), np.asarray(s_ys["count"])
    )
    assert np.array_equal(
        np.asarray(p_ys["stats"].send_counts),
        np.asarray(s_ys["stats"].send_counts),
    )
    for leaf in ("dropped_send", "dropped_recv"):
        assert int(np.asarray(getattr(p_ys["stats"], leaf)).sum()) == 0
        assert int(np.asarray(getattr(s_ys["stats"], leaf)).sum()) == 0
    flags = np.asarray(p_ys["stats"].pipeline)
    assert flags.shape[0] == 7 and bool(flags.all())
    assert s_ys["stats"].pipeline is None


# ------------------------------------------- build-time degradation


def test_chunk1_degrades_to_sequential_builder():
    rd = _mk_rd()
    pos, vel, ids, _count = _template_state(rd, 32)
    macro, cap, out_cap = pipeline.make_pipelined_chunk_fn(
        rd, 0.05, 1, pos, vel, ids
    )
    assert getattr(macro.__wrapped__, "_progcheck_resident", False)
    assert not getattr(macro.__wrapped__, "_progcheck_pipeline", False)
    seq_macro, seq_cap, seq_out = resident.make_chunk_fn(
        rd, 0.05, 1, pos, vel, ids
    )
    assert (cap, out_cap) == (seq_cap, seq_out)
    assert "pipeline: chunk < 2 — sequential body" in [
        e.data["reason"] for e in rd.telemetry.events("engine_resolved")
    ]


def test_ragged_capacity_degrades_with_sequential_error():
    """out_capacity != n_local: the degrade resolution journals the
    ragged reason, then the sequential builder it delegated to raises
    its own ResidentLayoutError — bit-exact sequential behavior."""
    rd = _mk_rd(out_capacity=128)
    pos, vel, ids, _count = _template_state(rd, 64)
    with pytest.raises(resident.ResidentLayoutError):
        pipeline.make_pipelined_chunk_fn(rd, 0.05, 4, pos, vel, ids)
    assert "pipeline: ragged receive capacity — sequential body" in [
        e.data["reason"] for e in rd.telemetry.events("engine_resolved")
    ]


def test_multidevice_topology_degrades():
    """An 8-rank grid on the 8 forced host devices resolves the sharded
    mesh path (rd._vranks False) — no single-device completion, so the
    build degrades to the sequential macro."""
    from mpi_grid_redistribute_tpu.domain import ProcessGrid
    from mpi_grid_redistribute_tpu.parallel import mesh as mesh_lib
    import jax

    grid = ProcessGrid((2, 2, 2))
    mesh = mesh_lib.make_mesh(grid, jax.devices()[: grid.nranks])
    rd = _mk_rd(grid=grid, mesh=mesh)
    pos, vel, ids, _count = _template_state(rd, 32)
    macro, _, _ = pipeline.make_pipelined_chunk_fn(
        rd, 0.05, 4, pos, vel, ids
    )
    assert not getattr(macro.__wrapped__, "_progcheck_pipeline", False)
    assert "pipeline: multi-device topology — sequential body" in [
        e.data["reason"] for e in rd.telemetry.events("engine_resolved")
    ]


# --------------------------------------------- the overlap, in jaxpr


def test_steady_state_bins_next_step_before_landing():
    """The tentpole's trace property: the scan body's dispatch cond has
    exactly one branch that bins step k+1 (floor) BEFORE step k's
    landing scatter, and a sequential branch that lands first; both
    land with exactly ONE scatter (the free-stack update is fused into
    the landing kernel — no second pass over landing rows) and no
    dynamic_update_slice."""
    import jax

    rd = _mk_rd()
    pos, vel, ids, count = _template_state(rd, 32)
    macro, _, _ = pipeline.make_pipelined_chunk_fn(
        rd, 0.05, 4, pos, vel, ids
    )
    closed = jax.make_jaxpr(macro)(pos, vel, ids, count)
    conds = progcheck.dispatch_conds(
        closed, rules_jaxpr.floor_before_scatter
    )
    assert len(conds) == 1, (
        "expected exactly one pipelined/sequential dispatch cond"
    )
    _eqn, seq_branch, pipe_branch = conds[0]
    for branch in (seq_branch, pipe_branch):
        names = progcheck.primitive_names(branch)
        assert names.count("scatter") == 1, names.count("scatter")
        assert "dynamic_update_slice" not in names
    pipe_names = progcheck.primitive_names(pipe_branch)
    seq_names = progcheck.primitive_names(seq_branch)
    assert pipe_names.index("floor") < pipe_names.index("scatter")
    assert seq_names.index("scatter") < seq_names.index("floor")
    # and the registered program is the same shape end to end: J003
    # green on this exact trace
    spec = progcheck.default_programs()["pipelined_macro_step"]
    assert rules_jaxpr.check_j003(closed, spec) == []
    assert rules_jaxpr.check_j002(closed, spec) == []
