"""``catalog8v.oneshot`` on the CPU at a small size: a sound run reads
correct; the timed path broken four ways, and the control, read not
correct; a program that narrows ids stops at the preflight; and the four
per-layer metrics read their scopes, from synthetic reductions and from a
chip trace of two calls of the cell (a v5e, the whole HLO kept)."""

import gzip
import json
import time
from pathlib import Path
from types import SimpleNamespace

import numpy as np
import pytest

from benchmark import control_oneshot, harness, manifest, xplane

NAME = "catalog8v.oneshot"
N_LOCAL = 4096  # rows a rank: 32,768 in all, about 28,700 of them moving
NEW = ("fuse_ms_per_call", "rd_plan_ms_per_call", "rd_compact_ms_per_call",
       "oneshot_roofline")
FIXTURE = (Path(__file__).parent / "fixtures"
           / "catalog8v.oneshot.trace.json.gz")


def _cell():
    cell = manifest.resolve(NAME)
    cell.config["rank_slots"] = N_LOCAL
    cell.config["out_capacity"] = N_LOCAL * 9 // 8
    return cell


def _run(patch=None, seconds=0.3):
    return harness.run(_cell(), 2**31 + 25, seconds, False,
                       t0=time.perf_counter(), require_chip=False,
                       patch=patch)


def _host(res):
    """The call's outputs on the host, ids as int64."""
    from benchmark import reference_oneshot

    return (np.array(res.positions), np.array(res.fields[0]),
            reference_oneshot.ids_of(np.asarray(res.fields[1])).copy(),
            np.array(res.count))


def _rebuilt(res, pos, vel, ids, count):
    return SimpleNamespace(positions=pos, fields=(vel, ids), count=count,
                           stats=res.stats)


def narrowed_ids(res, out_cap):
    """Every id cut to its low 32 bits."""
    pos, vel, ids, count = _host(res)
    return _rebuilt(res, pos, vel, ids.astype(np.int32), count)


def one_row_dropped(res, out_cap):
    """The last row of rank 0 left out."""
    pos, vel, ids, count = _host(res)
    count[0] -= 1
    return _rebuilt(res, pos, vel, ids, count)


def one_row_on_the_wrong_rank(res, out_cap):
    """The last row of rank 0 appended to rank 1."""
    pos, vel, ids, count = _host(res)
    src, dst = count[0] - 1, out_cap + count[1]
    for a in (pos, vel, ids):
        a[dst] = a[src]
    count[0] -= 1
    count[1] += 1
    return _rebuilt(res, pos, vel, ids, count)


def receive_order_swapped(res, out_cap):
    """Rank 0's first two rows in each other's place."""
    pos, vel, ids, count = _host(res)
    for a in (pos, vel, ids):
        a[[0, 1]] = a[[1, 0]]
    return _rebuilt(res, pos, vel, ids, count)


def _wrap(work, fault):
    real = work.program
    out_cap = int(work.rd.out_capacity)

    def program(*inputs):
        return fault(real(*inputs), out_cap)

    work.program = program


def test_the_cell_resolves_its_files_by_name():
    cell = manifest.resolve(NAME)
    assert cell.chips == 1
    assert cell.traffic["driver"] == "oneshot"
    assert cell.config["row"]["row_bytes"] == 32
    assert {m["name"] for m in cell.end_to_end} == {
        "particles_per_s_per_chip", "call_p95_ms", "setup_s"}
    assert {m["name"] for m in cell.per_layer} == set(NEW)
    for m in cell.per_layer:
        assert m["workloads"] == [NAME]
        assert m["moves"] == "particles_per_s_per_chip"
    # the drift cells do not report the new metrics
    for other in ("drift8v.steady", "drift4c.steady"):
        assert not set(NEW) & {m["name"]
                               for m in manifest.resolve(other).per_layer}


def test_a_sound_run_is_correct():
    res = _run()
    assert res["correct"], res["checks"]
    assert all(c["value"] == 0 for c in res["checks"].values())
    assert set(res["metrics"]) == {"particles_per_s_per_chip", "call_p95_ms",
                                   "setup_s"}
    assert res["attempted"] >= 1 and res["failed"] == 0


@pytest.mark.parametrize("fault", [narrowed_ids, one_row_dropped,
                                   one_row_on_the_wrong_rank,
                                   receive_order_swapped],
                         ids=lambda f: f.__name__)
def test_a_broken_timed_path_is_not_correct(fault):
    res = _run(patch=lambda w: _wrap(w, fault))
    assert not res["correct"], res["checks"]
    assert res["failed"] == res["attempted"]
    checks = {k: c["value"] for k, c in res["checks"].items()}
    if fault is narrowed_ids:
        assert checks["ids_wrong"] > 0.9 * 8 * N_LOCAL
    if fault is one_row_dropped:
        assert checks["rows_lost"] == 1 and checks["count_wrong"] == 1
    if fault is one_row_on_the_wrong_rank:
        assert checks["rows_off_owner"] == 1 and checks["rows_lost"] == 0
    if fault is receive_order_swapped:
        assert checks["rows_wrong"] == 2 and checks["rows_off_owner"] == 0


def test_the_control_is_not_correct():
    res = _run(patch=control_oneshot.control_patch)
    assert not res["correct"]
    checks = {k: c["value"] for k, c in res["checks"].items()}
    assert checks["ids_wrong"] > 0 and checks["rows_wrong"] > 0
    assert checks["rows_lost"] == checks["rows_off_owner"] == 0


class _Narrowing:
    """A library whose backends return ids cut to 32 bits."""

    def __init__(self, gr):
        self._gr = gr

    def GridRedistribute(self, *a, **kw):  # noqa: N802
        rd = self._gr.GridRedistribute(*a, **kw)
        real = rd.redistribute

        def redistribute(pos, *fields, **k):
            fields = tuple(np.asarray(f).astype(np.int32) for f in fields)
            return real(pos, *fields, **k)

        rd.redistribute = redistribute
        return rd


def test_a_program_that_narrows_ids_stops_at_the_preflight():
    import mpi_grid_redistribute_tpu as gr

    from benchmark.drivers import oneshot

    rng = np.random.default_rng(1)
    dom = gr.Domain(0.0, 1.0, periodic=True)
    oneshot.preflight(gr, dom, (2, 2, 2), 8192**3, rng)
    with pytest.raises(oneshot.ContractError, match="int32"):
        oneshot.preflight(_Narrowing(gr), dom, (2, 2, 2), 8192**3, rng)


def _synthetic(scope, module="jit_call"):
    hlo = (f"HloModule {module}, is_scheduled=true\n"
           "  %fusion.1 = s32[8] fusion(%p), kind=kLoop, "
           f'metadata={{op_name="jit(call)/{scope}/add"}}\n')
    trace = {"devices": [{"name": "/device:TPU:0",
                          "events": [["fusion.1", 100, 50, None]]}],
             "host_spans": [["bench:call", 0, 1000]]}
    return xplane.reduce(trace, xplane.hlo_table(hlo), module)


def _record(r, calls=1):
    cell = manifest.resolve(NAME)
    run = harness.RunRecord(
        cell=cell, seed=0, chips=1, device_kind="TPU v5 lite", setup_s=1.0,
        call_s=[0.1] * calls, window_s=r.window_s,
        units_per_call=8 * 2**20, steps_per_call=1,
        counters=[{"backlog": np.zeros(8)}] * calls, trace=r,
        shapes={"rows": 8 * 2**20, "row_bytes": 32})
    return cell, run


@pytest.mark.parametrize("metric, scope", [
    ("fuse_ms_per_call", "rd:fuse"),
    ("fuse_ms_per_call", "rd:unfuse"),
    ("rd_plan_ms_per_call", "rd:bin"),
    ("rd_plan_ms_per_call", "rd:pack"),
    ("rd_compact_ms_per_call", "rd:unpack"),
])
def test_each_metric_reads_its_scope(metric, scope):
    cell, run = _record(_synthetic(scope))
    # one 50 ns op in one call
    assert cell.readers[metric].read(run) == pytest.approx(50e-6)
    # an op of another scope, a vmap of the scope, or no trace: nothing
    for other in ("rd:exchange", f"vmap({scope})", "mig:pack"):
        cell, run = _record(_synthetic(other))
        assert cell.readers[metric].read(run) is None
    run.trace = None
    assert cell.readers[metric].read(run) is None


def test_the_roofline_reads_every_rd_scope():
    # 2 x 8,388,608 rows x 32 B at 819 GB/s over one 50 ns op
    want = 100.0 * 2 * 8 * 2**20 * 32 / 819e9 / 50e-9
    for scope in ("rd:fuse", "rd:exchange", "rd:unpack"):
        cell, run = _record(_synthetic(scope))
        got = cell.readers["oneshot_roofline"].read(run)
        assert got == pytest.approx(want)
    cell, run = _record(_synthetic("mig:pack"))
    assert cell.readers["oneshot_roofline"].read(run) is None


def test_the_chip_trace_reads_every_new_metric():
    with gzip.open(FIXTURE, "rt") as f:
        fx = json.load(f)
    r = xplane.reduce(fx["trace"], xplane.hlo_table(fx["hlo"]),
                      xplane.hlo_module(fx["hlo"]))
    calls = sum(1 for s in fx["trace"]["host_spans"]
                if s[0] == xplane.CALL_SPAN)
    cell, run = _record(r, calls)
    assert run.calls == 2
    got = {m["name"]: cell.readers[m["name"]].read(run)
           for m in cell.per_layer}
    # the pack's two gathers over the 8 x 8 x 262,144-column pool, and
    # the 9-operand compaction sort, are most of a ~706 ms call
    assert 300 < got["rd_plan_ms_per_call"] < 600
    assert 150 < got["rd_compact_ms_per_call"] < 350
    assert 1 < got["fuse_ms_per_call"] < 10
    assert 0 < got["oneshot_roofline"] < 1
    sort_ms = 1e3 * r.time_s(lambda op: op.in_scope("rd:unpack")
                             and op.name.startswith("sort")) / calls
    assert sort_ms > 0.9 * got["rd_compact_ms_per_call"]
    # every op of the call but the compiler's own relayouts sits under an
    # rd: scope
    rd = r.time_s(lambda op: any(p.startswith("rd:") for p in op.scope))
    assert rd > 0.98 * r.busy_s
