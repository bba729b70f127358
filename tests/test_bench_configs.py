"""Smoke-run the five BASELINE config drivers at tiny sizes (SURVEY.md §6)."""

import os

import numpy as np

import pytest

os.environ.setdefault("BENCH_SCALE", "0.01")


def test_config1_oracle():
    import gc
    import warnings

    from mpi_grid_redistribute_tpu.bench import config1_oracle

    # RuntimeWarnings as errors: the driver must resolve its deferred
    # overflow windows itself (flush/with), not warn from __del__
    with warnings.catch_warnings():
        warnings.simplefilter("error", RuntimeWarning)
        out = config1_oracle.run(n_total=1 << 12, reps=1)
        gc.collect()  # trigger any leftover GridRedistribute.__del__ now
    assert out["bit_equal_vs_oracle"] is True
    assert out["value"] > 0
    # the merged telemetry surface rides the bench JSON
    rep = out["api_report"]
    assert rep["kind"] == "redistribute"
    assert rep["bw_util"] == "not measured"  # timed on the CPU
    assert rep["unresolved_windows"] is False


def test_config7_stress():
    from mpi_grid_redistribute_tpu.bench import config7_stress

    out = config7_stress.run(n_total=1 << 12, reps=1)
    # full-reshuffle regime: destinations are uniform, so ~(R-1)/R of
    # rows change owner every step — far above any drift config
    assert out["migration_fraction"] > 0.5
    assert out["bw_util"] == "not measured"  # timed on the CPU
    assert out["exchange_bytes_per_step"] > 0
    assert out["timing_spread"] >= 0
    assert out["exchange_domain"] == "hbm"


def test_config2_clustered():
    from mpi_grid_redistribute_tpu.bench import config2_clustered

    out = config2_clustered.run(n_local=256, max_rounds=64)
    assert out["dropped_recv"] == 0
    assert out["placement_dropped_recv"] == 0
    assert out["ownership_imbalance"] >= 1.0
    # tiny CPU smoke: scan differencing can be noise-dominated, so only
    # presence/finiteness of the steady-state fields is asserted here
    for k in ("pps_imbalanced", "pps_uniform_ref", "imbalanced_over_uniform"):
        assert np.isfinite(out[k])


def test_config3_slab():
    from mpi_grid_redistribute_tpu.bench import config3_slab

    out = config3_slab.run(n_local=512)
    assert out["value"] > 0
    assert out["chips"] == 1  # 64 slabs as vranks on 8 CPU devices? no: 64>8


def test_config4_drift():
    from mpi_grid_redistribute_tpu.bench import config4_drift

    out = config4_drift.run(n_local=1 << 12, steps=16)
    assert out["value"] > 0
    assert out["chips"] == 8  # 2x2x2 fits the 8 virtual CPU devices


def test_config4_rebalance_smoke_gate():
    # the `make rebalance-smoke` gate at a CI-sized leg: ALERT ->
    # applied rebalance -> post-imbalance <= 1.1x, zero drops, and the
    # particle set bit-identical to the no-rebalance twin. The
    # steady-state ms/step win is regress-guarded at bench scale, not
    # asserted at this size.
    from mpi_grid_redistribute_tpu.bench import config4_drift

    out = config4_drift.run_rebalance(n_local=512, steps=48)
    assert out["alerts"] >= 1
    assert out["rebalances_applied"] >= 1
    assert out["post_rebalance_imbalance"] <= 1.1
    assert out["dropped"] == 0
    assert out["bit_identical"]


def test_config5_deposit():
    from mpi_grid_redistribute_tpu.bench import config5_deposit

    out = config5_deposit.run(n_local=1 << 10, mesh_cells=16)
    assert out["value"] > 0


def test_config8_soak(monkeypatch):
    from mpi_grid_redistribute_tpu.bench import config8_soak

    monkeypatch.setenv("BENCH_SOAK_EVERY", "4")  # short cadence, short run
    monkeypatch.setenv("BENCH_SOAK_STEPS", "12")  # short crash/elastic legs
    out = config8_soak.run(n_local=512, reps=2)
    assert out["metric"] == "soak_pps"
    assert out["value"] > 0
    assert out["snapshots_written"] >= 1
    assert np.isfinite(out["snapshot_overhead"])
    # the crash leg: exactly one supervised restart, and the resumed
    # trajectory byte-equal to the uninterrupted run (the tier-1 half of
    # the `make soak` gate; the 2% overhead budget is gated at real
    # scale by `make soak` / bench-check, not at this smoke size)
    assert out["restarts"] == 1
    assert out["bit_identical_resume"] is True
    # the elastic leg: crash + half the devices lost -> shrink-restore,
    # journaled reshard, and the id-sorted particle set preserved
    assert out["elastic_restarts"] == 1
    assert out["resharded"] == 1
    assert out["elastic_grid"] != out["grid"]
    assert out["elastic_set_identical"] is True
    # the gate helper agrees with a green capture when overhead passes
    ok = dict(out, snapshot_overhead=0.0)
    assert config8_soak._soak_gate(ok) == []
    bad = dict(out, bit_identical_resume=False)
    assert config8_soak._soak_gate(bad) != []
    bad2 = dict(ok, elastic_set_identical=False)
    assert config8_soak._soak_gate(bad2) != []
