import gzip
import json
from pathlib import Path

import pytest

from benchmark import xplane

HLO = """HloModule jit_loop, is_scheduled=true
  %while.1 = (s32[]) while(%t), condition=%c, body=%b, metadata={op_name="jit(loop)/while"}
  %sort.2 = s32[8] sort(%p), metadata={op_name="jit(loop)/while/body/mig:select/sort"}
  %fusion.3 = s32[8] fusion(%p), kind=kLoop, metadata={op_name="jit(loop)/while/body/mig:pack/add"}
  %_driftbin_call.4 = s32[7,8] custom-call(%p), custom_call_target="tpu_custom_call", metadata={op_name="jit(loop)/while/body/pallas_call"}
  ROOT %copy.5 = s32[8] copy(%p)
"""


def _synthetic():
    # window 0..1000 ns from two calls; a loop (100..900) holding three
    # ops; one op before the window; device idle 0..100, 400..500, 900..1000
    ops = [["while.1", 100, 800, None], ["_driftbin_call.4", 100, 100, None],
           ["sort.2", 200, 200, None], ["fusion.3", 500, 400, None],
           ["copy.5", -50, 40, None]]
    spans = [["bench:call", 0, 500], ["bench:fetch", 300, 200],
             ["bench:call", 500, 500], ["bench:dispatch", 500, 50]]
    return {"devices": [{"name": "/device:TPU:0", "events": ops}],
            "host_spans": spans}


def test_hlo_table_reads_scopes_and_kernels():
    t = xplane.hlo_table(HLO)
    assert "mig:select" in t["sort.2"].scope
    assert t["_driftbin_call.4"].kernel == "_driftbin_call"
    assert t["copy.5"].scope == () and t["copy.5"].kernel is None
    assert xplane.hlo_module(HLO) == "jit_loop"


def test_reduce_self_time_busy_window_and_gaps():
    r = xplane.reduce(_synthetic(), xplane.hlo_table(HLO), "jit_loop")
    assert r.window == (0, 1000) and r.window_s == pytest.approx(1e-6)
    # the loop holds the three ops: the gap inside it (400..500) is idle,
    # not the loop's work
    loop = [op for op in r.devices[0] if op.name == "while.1"][0]
    assert not loop.leaf and loop.self_ns == 0
    assert r.busy_ns == [700]  # 100..400, 500..900; the op before the window is dropped
    assert r.time_s(lambda op: op.in_scope("mig:select", "mig:pack")) == pytest.approx(600e-9)
    assert r.time_s(lambda op: True) == pytest.approx(700e-9)
    assert r.count(lambda op: op.kernel == "_driftbin_call") == 1
    assert r.gaps == [[(0, 100), (400, 500), (900, 1000)]]
    b = r.breakdown()
    assert b["device_ops"][0] == ["mig:pack fusion.3", pytest.approx(400e-9)]
    assert "while.1" not in " ".join(k for k, _ in b["device_ops"])
    assert sorted(g[0] for g in b["idle_gaps"]) == ["bench:call", "bench:call", "bench:fetch"]


def test_gaps_take_the_innermost_host_span():
    tr = _synthetic()
    tr["devices"][0]["events"] = [["sort.2", 0, 300, None]]
    r = xplane.reduce(tr, xplane.hlo_table(HLO))
    labels = [xplane.Reduction.host_label(r, t) for t in (350, 520, 700)]
    assert labels == ["bench:fetch", "bench:dispatch", "bench:call"]


def test_a_trace_without_device_ops_is_an_error():
    with pytest.raises(ValueError):
        xplane.reduce({"devices": [], "host_spans": []}, {})


FIXTURE = Path(__file__).parent / "fixtures" / "drift8v.steady.trace.json.gz"


def test_the_recorded_chip_trace_reduces_to_every_layer_metric():
    """Two 8-step calls of ``drift8v.steady`` traced on a v5e (the
    trace's first two calls, trimmed, with the HLO lines its events
    name)."""
    import numpy as np

    from benchmark import harness, manifest

    with gzip.open(FIXTURE, "rt") as f:
        fx = json.load(f)
    table = xplane.hlo_table(fx["hlo"])
    r = xplane.reduce(fx["trace"], table, xplane.hlo_module(fx["hlo"]))
    calls = sum(1 for s in fx["trace"]["host_spans"] if s[0] == xplane.CALL_SPAN)
    assert calls == 2
    assert all(op.name in table for op in r.devices[0])
    assert 0 < r.busy_s <= r.window_s
    # one fused drift+bin kernel per step, 8 steps per call
    assert r.count(lambda op: op.kernel == "_driftbin_call") == 8 * calls
    b = r.breakdown()
    assert 0 < len(b["device_ops"]) <= 10 and 0 < len(b["idle_gaps"]) <= 10

    cell = manifest.resolve("drift8v.steady")
    # arrivals per step as the chip run counted them (~150k of 7.5M)
    counters = [{"sent": np.full((8, 8), 18750), "received": np.full((8, 8), 18750)}
                for _ in range(calls)]
    run = harness.RunRecord(
        cell=cell, seed=0, chips=1, device_kind="TPU v5 lite", setup_s=1.0,
        call_s=[0.2] * calls, window_s=r.window_s, units_per_call=1.0,
        steps_per_call=8, counters=counters, trace=r,
        shapes={"slots_per_chip": 8 * 2**20, "D": 3, "K": 7, "row_bytes": 28})
    got = {m["name"]: cell.readers[m["name"]].read(run) for m in cell.per_layer}
    assert 0 < got["device_idle_pct"] < 100
    assert 0 < got["driftbin_roofline"] <= 100
    assert 0 < got["landing_roofline"] <= 100
    assert got["plan_ms_per_step"] > 0
    assert got["exchange_bytes_per_step"] == 150000 * 28
