"""The per-layer metrics that read the drift loop's layer scopes:
``entry_ms_per_call`` (``mig:enter``, ``mig:exit``), ``drift_ms_per_step``
(``mig:drift``), ``grant_ms_per_step`` (``mig:grant``) and
``stack_ms_per_step`` (``mig:stack``).

Read from a chip trace of the scoped program (two 8-step calls of
``drift8v.steady`` traced on a v5e, trimmed as the older fixture is),
and from synthetic reductions for the cases with nothing to read.
"""

import gzip
import json
from pathlib import Path

import numpy as np
import pytest

from benchmark import harness, manifest, xplane

FIXTURE = (Path(__file__).parent / "fixtures"
           / "drift8v.steady.scoped.trace.json.gz")
NEW = ("entry_ms_per_call", "drift_ms_per_step", "grant_ms_per_step",
       "stack_ms_per_step")


def _record(cell, r, calls):
    counters = [{"sent": np.full((8, 8), 18750),
                 "received": np.full((8, 8), 18750)}
                for _ in range(calls)]
    return harness.RunRecord(
        cell=cell, seed=0, chips=1, device_kind="TPU v5 lite", setup_s=1.0,
        call_s=[0.2] * calls, window_s=r.window_s, units_per_call=1.0,
        steps_per_call=8, counters=counters, trace=r,
        shapes={"slots_per_chip": 8 * 2**20, "D": 3, "K": 7, "row_bytes": 28})


@pytest.fixture(scope="module")
def scoped():
    with gzip.open(FIXTURE, "rt") as f:
        fx = json.load(f)
    r = xplane.reduce(fx["trace"], xplane.hlo_table(fx["hlo"]),
                      xplane.hlo_module(fx["hlo"]))
    calls = sum(1 for s in fx["trace"]["host_spans"]
                if s[0] == xplane.CALL_SPAN)
    cell = manifest.resolve("drift8v.steady")
    run = _record(cell, r, calls)
    got = {m["name"]: cell.readers[m["name"]].read(run)
           for m in cell.per_layer}
    return r, run, got


def test_both_cells_carry_the_new_metrics():
    for name in ("drift8v.steady", "drift4c.steady"):
        cell = manifest.resolve(name)
        layer = {m["name"]: m for m in cell.per_layer}
        for metric in NEW:
            assert layer[metric]["source"] == "device_trace"
            assert layer[metric]["moves"] == "particles_per_s_per_chip"
            assert metric in cell.readers


def test_the_scoped_chip_trace_reads_every_new_metric(scoped):
    r, run, got = scoped
    assert run.calls == 2 and run.steps == 16
    # the per-call argsort of 8.4M slots (~13.7 ms) is most of the entry
    sort_ms = 1e3 * r.time_s(lambda op: op.in_scope("mig:enter")
                             and op.name.startswith("sort")) / run.calls
    assert 12 < sort_ms < got["entry_ms_per_call"] < 25
    # on one chip the fused kernel is the whole drift layer
    kernel_ms = 1e3 * r.time_s(
        lambda op: op.kernel == "_driftbin_call") / run.steps
    assert got["drift_ms_per_step"] == pytest.approx(kernel_ms)
    assert 0.5 < got["drift_ms_per_step"] < 2
    # the mover-sparse branch's grant tables and stack windows are
    # [V, V] and [V, B] sized: microseconds a step
    assert 0 < got["grant_ms_per_step"] < 0.1
    assert 0 < got["stack_ms_per_step"] < 0.1


def test_the_older_metrics_read_the_scoped_trace_as_before(scoped):
    _, _, got = scoped
    assert 40 < got["driftbin_roofline"] <= 100
    assert 0 < got["landing_roofline"] <= 100
    assert 4 < got["plan_ms_per_step"] < 5
    assert got["exchange_bytes_per_step"] == 150000 * 28


def test_no_new_scope_holds_another_layer(scoped):
    r, _, _ = scoped
    new = ("mig:enter", "mig:exit", "mig:drift", "mig:grant", "mig:stack")
    layers = new + ("mig:select", "mig:pack", "mig:unpack",
                    "mig:exchange", "mig:bin")
    for ops in r.devices:
        for op in ops:
            if any(s in op.scope for s in new):
                assert sum(s in op.scope for s in layers) == 1, op.scope


def _synthetic(scope):
    hlo = ("HloModule jit_loop, is_scheduled=true\n"
           "  %fusion.1 = s32[8] fusion(%p), kind=kLoop, "
           f'metadata={{op_name="jit(loop)/while/body/{scope}/add"}}\n')
    trace = {"devices": [{"name": "/device:TPU:0",
                          "events": [["fusion.1", 100, 50, None]]}],
             "host_spans": [["bench:call", 0, 1000]]}
    return xplane.reduce(trace, xplane.hlo_table(hlo), "jit_loop")


@pytest.mark.parametrize("metric", NEW)
def test_nothing_to_read_gives_none(metric):
    cell = manifest.resolve("drift8v.steady")
    reader = cell.readers[metric]
    # no trace (an untraced run), and a trace without the metric's scope
    # (the program before the scopes): no value, no error
    untraced = _record(cell, _synthetic("mig:pack"), 1)
    untraced.trace = None
    assert reader.read(untraced) is None
    assert reader.read(_record(cell, _synthetic("mig:pack"), 1)) is None


@pytest.mark.parametrize("metric, scope, per_call", [
    ("entry_ms_per_call", "mig:enter", True),
    ("entry_ms_per_call", "mig:exit", True),
    ("drift_ms_per_step", "mig:drift", False),
    ("grant_ms_per_step", "mig:grant", False),
    ("stack_ms_per_step", "mig:stack", False),
])
def test_each_metric_reads_its_scope(metric, scope, per_call):
    cell = manifest.resolve("drift8v.steady")
    run = _record(cell, _synthetic(scope), 1)
    # one 50 ns op in one call of 8 steps
    want = 50e-6 if per_call else 50e-6 / 8
    assert cell.readers[metric].read(run) == pytest.approx(want)
