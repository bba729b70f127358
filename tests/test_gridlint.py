"""gridlint (mpi_grid_redistribute_tpu.analysis) — rule fixtures + repo gate.

Each rule gets at least one fixture that must FIRE and one that must
stay QUIET; the final test runs the real package through the linter
against the committed baseline and requires zero non-baselined
findings — the tier-1 gate the CLI (`make lint`) also enforces.

Pure AST work: nothing here imports jax or executes fixture code.
"""

import json
import os
import subprocess
import sys
import textwrap

import pytest

from mpi_grid_redistribute_tpu.analysis.baseline import (
    default_baseline_path,
    load_baseline,
    split_baselined,
    write_baseline,
)
from mpi_grid_redistribute_tpu.analysis.cli import main as cli_main
from mpi_grid_redistribute_tpu.analysis.core import RULE_IDS, run_gridlint

REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
PACKAGE = os.path.join(REPO_ROOT, "mpi_grid_redistribute_tpu")


def lint(tmp_path, files, rules=None):
    """Write ``files`` (name -> source) under tmp_path and lint them."""
    for name, src in files.items():
        path = tmp_path / name
        path.parent.mkdir(parents=True, exist_ok=True)
        path.write_text(textwrap.dedent(src))
    return run_gridlint([str(tmp_path)], root=str(tmp_path), rules=rules)


def rules_of(findings):
    return sorted({f.rule for f in findings})


# ---------------------------------------------------------------- G001


_G001_PREAMBLE = """
    import jax
    import jax.numpy as jnp
    from jax import lax
    from jax.sharding import Mesh
    from jax import shard_map

    mesh = Mesh(jax.devices(), axis_names=("shards",))
"""


def test_g001_fires_on_data_dependent_collective(tmp_path):
    findings = lint(
        tmp_path,
        {
            "mod.py": _G001_PREAMBLE
            + """
    def body(x, count):
        if count > 0:
            x = lax.psum(x, axis_name="shards")
        return x

    fn = shard_map(body, mesh=mesh, in_specs=None, out_specs=None)
    """,
        },
    )
    assert rules_of(findings) == ["G001"], findings
    assert "data-dependent" in findings[0].message


def test_g001_quiet_on_unconditional_collective(tmp_path):
    findings = lint(
        tmp_path,
        {
            "mod.py": _G001_PREAMBLE
            + """
    def body(x, count):
        # trace-time host branch on config is fine
        if x.ndim == 2:
            x = x + 1
        return lax.psum(x, axis_name="shards")

    fn = shard_map(body, mesh=mesh, in_specs=None, out_specs=None)
    """,
        },
    )
    assert findings == [], findings


def test_g001_fires_inside_cond_branch_and_try(tmp_path):
    findings = lint(
        tmp_path,
        {
            "mod.py": _G001_PREAMBLE
            + """
    def body(x, flag):
        def hot(y):
            return lax.psum(y, axis_name="shards")

        def cold(y):
            return y

        try:
            z = lax.ppermute(x, "shards", [(0, 1)])
        except ValueError:
            z = x
        return lax.cond(flag, hot, cold, z)

    fn = shard_map(body, mesh=mesh, in_specs=None, out_specs=None)
    """,
        },
    )
    msgs = "\n".join(f.message for f in findings)
    assert "branch function" in msgs
    assert "try block" in msgs


def test_g001_fires_on_undeclared_axis_name(tmp_path):
    findings = lint(
        tmp_path,
        {
            "mod.py": _G001_PREAMBLE
            + """
    def body(x):
        return lax.psum(x, axis_name="shrads")  # typo'd axis

    fn = shard_map(body, mesh=mesh, in_specs=None, out_specs=None)
    """,
        },
    )
    assert rules_of(findings) == ["G001"], findings
    assert "shrads" in findings[0].message


# ---------------------------------------------------------------- G002


def test_g002_fires_on_host_syncs_in_jitted_code(tmp_path):
    findings = lint(
        tmp_path,
        {
            "mod.py": """
    import jax
    import numpy as np

    @jax.jit
    def step(x):
        n = int(x)            # host sync
        y = np.asarray(x)     # device->host copy
        return x.item() + n + y.sum()
    """,
        },
    )
    assert rules_of(findings) == ["G002"]
    assert len(findings) == 3, findings


def test_g002_quiet_on_static_annotated_params_and_host_fns(tmp_path):
    findings = lint(
        tmp_path,
        {
            "mod.py": """
    import jax
    import numpy as np

    @jax.jit
    def step(x, n_steps: int, scale: float):
        # int()/float() on annotated config params is trace-time math
        return x * float(scale) * int(n_steps)

    def host_only(x):
        # not jit-reachable: host syncs are fine here
        return float(np.asarray(x).sum())
    """,
        },
    )
    assert findings == [], findings


def test_g002_reaches_through_builders_and_helpers(tmp_path):
    findings = lint(
        tmp_path,
        {
            "mod.py": """
    import jax

    def helper(x):
        return x.item()  # reached transitively from the jit root

    def build():
        def call(x):
            return helper(x)

        return jax.jit(call)
    """,
        },
    )
    assert rules_of(findings) == ["G002"]
    assert findings[0].symbol == "helper"


# ---------------------------------------------------------------- G003


def test_g003_fires_on_dynamic_shapes(tmp_path):
    findings = lint(
        tmp_path,
        {
            "mod.py": """
    import jax
    import jax.numpy as jnp

    @jax.jit
    def pick(x):
        idx = jnp.nonzero(x > 0)          # unsized
        hits = jnp.where(x > 1)           # 1-arg nonzero form
        return x[x > 0], idx, hits        # boolean-mask indexing
    """,
        },
    )
    assert rules_of(findings) == ["G003"]
    assert len(findings) == 3, findings


def test_g003_quiet_on_sized_and_select_forms(tmp_path):
    findings = lint(
        tmp_path,
        {
            "mod.py": """
    import jax
    import jax.numpy as jnp

    @jax.jit
    def pick(x, cap: int):
        idx = jnp.nonzero(x > 0, size=cap, fill_value=0)
        sel = jnp.where(x > 1, x, 0)
        return idx, sel
    """,
        },
    )
    assert findings == [], findings


# ---------------------------------------------------------------- G004


def test_g004_fires_on_unguarded_fuse(tmp_path):
    findings = lint(
        tmp_path,
        {
            "mod.py": """
    from pack import fuse_fields

    def ship(positions, fields):
        return fuse_fields(positions, fields)
    """,
            "pack.py": """
    def fuse_fields(positions, fields):
        return positions
    """,
        },
    )
    assert rules_of(findings) == ["G004"], findings
    # the contract it asks for: 4-byte values, or 8-byte ones as two words
    assert "itemsize not in (4, 8)" in findings[0].message


# a 4-byte guard, and the 4-or-8-byte guard of api._planar_refusal
@pytest.mark.parametrize("guard", ["!= 4", "not in (4, 8)"])
def test_g004_quiet_when_guard_in_callee_or_caller(tmp_path, guard):
    findings = lint(
        tmp_path,
        {
            "mod.py": f"""
    def fuse_fields(positions, fields):
        # self-guarding fuse (migrate.fuse_fields shape)
        if positions.dtype.itemsize {guard}:
            raise TypeError("planar path needs 32-bit rows")
        return positions

    def specs_of(a):
        if a.dtype.itemsize {guard}:
            return None
        return a.shape

    def build(specs):
        def call(positions, fields):
            return fuse_fields(positions, fields)

        return call

    def entry(positions, fields):
        # one-frame-up guard: entry consults the itemsize helper
        specs = specs_of(positions)
        if specs is None:
            return positions
        return build(specs)(positions, fields)
    """,
        },
    )
    assert findings == [], findings


# ---------------------------------------------------------------- G005


def test_g005_fires_on_defaulted_pallas_call(tmp_path):
    findings = lint(
        tmp_path,
        {
            "mod.py": """
    from jax.experimental import pallas as pl

    def launch(kernel, x):
        return pl.pallas_call(kernel, out_shape=x)(x)
    """,
        },
    )
    msgs = "\n".join(f.message for f in findings)
    assert rules_of(findings) == ["G005"]
    assert "grid" in msgs and "in_specs" in msgs


def test_g005_fires_on_unbounded_program_id_kernel(tmp_path):
    findings = lint(
        tmp_path,
        {
            "pallas_fix.py": """
    import jax.numpy as jnp
    from jax.experimental import pallas as pl

    def _kernel(in_ref, out_ref):
        b = pl.program_id(0)
        out_ref[b] = in_ref[b] + 1  # no bound: last padded block escapes

    def launch(x, grid, in_specs, out_specs):
        return pl.pallas_call(
            _kernel, grid=grid, in_specs=in_specs, out_specs=out_specs,
            out_shape=x,
        )(x)
    """,
        },
    )
    assert rules_of(findings) == ["G005"], findings
    assert "program_id" in findings[0].message


def test_g005_quiet_on_bounded_partial_wrapped_kernel(tmp_path):
    findings = lint(
        tmp_path,
        {
            "pallas_fix.py": """
    import functools
    import jax.numpy as jnp
    from jax.experimental import pallas as pl

    def _kernel(in_ref, out_ref, *, n):
        b = pl.program_id(0)
        i = jnp.minimum(b, n - 1)
        out_ref[i] = in_ref[i] + 1

    def launch(x, n, grid, in_specs, out_specs):
        kernel = functools.partial(_kernel, n=n)
        return pl.pallas_call(
            kernel, grid=grid, in_specs=in_specs, out_specs=out_specs,
            out_shape=x,
        )(x)
    """,
        },
    )
    assert findings == [], findings


def test_g005_quiet_on_scratch_shapes_kernel(tmp_path):
    """scratch_shapes (VMEM accumulators + DMA semaphores) are extra
    positional refs AFTER the in/out refs — the declared-specs and
    bounded-program_id checks must not trip over them."""
    findings = lint(
        tmp_path,
        {
            "pallas_fix.py": """
    import jax.numpy as jnp
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    def _kernel(in_ref, out_ref, acc_ref, sem):
        b = pl.program_id(0)
        nb = pl.num_programs(0)
        i = jnp.minimum(b, nb - 1)
        acc_ref[:] = in_ref[:] * 2.0
        out_ref[:] = acc_ref[:] + i

    def launch(x, grid, in_specs, out_specs):
        return pl.pallas_call(
            _kernel, grid=grid, in_specs=in_specs, out_specs=out_specs,
            out_shape=x,
            scratch_shapes=[
                pltpu.VMEM((8, 128), jnp.float32),
                pltpu.SemaphoreType.DMA((2,)),
            ],
        )(x)
    """,
        },
    )
    assert findings == [], findings


def test_g005_quiet_on_grid_dim_zero_literal(tmp_path):
    """A zero-extent grid dim is lexically a fully-declared launch —
    G005 has nothing to say. Whether running ZERO instances leaves the
    output uncovered is a semantic question: kernelcheck's K002
    coverage rule owns it (see test_kernelcheck.py's twin)."""
    findings = lint(
        tmp_path,
        {
            "pallas_fix.py": """
    import jax.numpy as jnp
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    def _kernel(in_ref, out_ref):
        out_ref[:] = in_ref[:]

    def launch(x, nblk):
        return pl.pallas_call(
            _kernel,
            grid=(nblk, 0),
            in_specs=[pl.BlockSpec((8, 128), lambda i, j: (i, 0),
                                   memory_space=pltpu.VMEM)],
            out_specs=pl.BlockSpec((8, 128), lambda i, j: (i, 0),
                                   memory_space=pltpu.VMEM),
            out_shape=x,
        )(x)
    """,
        },
    )
    assert findings == [], findings


def test_g005_quiet_on_semantically_out_of_bounds_index_map(tmp_path):
    """The AST/semantic split, spiked from the gridlint side: this
    launch is lexically impeccable (grid, specs, no raw program_id in
    the kernel body) yet its index map addresses one block PAST the
    end. G005 must stay quiet — kernelcheck K001 proves the bounds
    violation on the captured site (the disjoint twin lives in
    test_kernelcheck.py::test_k001_and_g005_are_disjoint)."""
    findings = lint(
        tmp_path,
        {
            "pallas_fix.py": """
    import jax.numpy as jnp
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    def _kernel(in_ref, out_ref):
        out_ref[:] = in_ref[:] + 1.0

    def launch(x):
        return pl.pallas_call(
            _kernel,
            grid=(4,),
            in_specs=[pl.BlockSpec((8, 128), lambda i: (i + 1, 0),
                                   memory_space=pltpu.VMEM)],
            out_specs=pl.BlockSpec((8, 128), lambda i: (i, 0),
                                   memory_space=pltpu.VMEM),
            out_shape=x,
        )(x)
    """,
        },
    )
    assert findings == [], findings


# ---------------------------------------------------------------- G006


def test_g006_fires_on_sort_and_arange_take_in_marked_fn(tmp_path):
    findings = lint(
        tmp_path,
        {
            "mod.py": """
    import jax.numpy as jnp
    from jax import lax

    # gridlint: fastpath-engine
    def fast_branch(flat, block, n):
        order = lax.sort(block, dimension=-1)
        cols = jnp.take(flat, jnp.arange(n), axis=1)
        return order, cols
    """,
        },
        rules=["G006"],
    )
    assert rules_of(findings) == ["G006"], findings
    assert len(findings) == 2
    assert any("sort" in f.message for f in findings)
    assert any("arange/iota" in f.message for f in findings)


def test_g006_quiet_on_plan_indexed_gather_and_unmarked_fn(tmp_path):
    findings = lint(
        tmp_path,
        {
            "mod.py": """
    import jax.numpy as jnp
    from jax import lax

    # gridlint: fastpath-engine
    def fast_branch(flat, plan, window):
        # plan-shaped gather: indices come in as a value, no iota
        cols = jnp.take(flat, plan.reshape(-1), axis=1)
        win = lax.dynamic_slice(window, (0,), (8,))
        return cols, win

    def dense_engine(dest, n):
        # unmarked: the dense engine may sort residents freely
        order = jnp.argsort(dest)
        return jnp.take(dest, jnp.arange(n))
    """,
        },
        rules=["G006"],
    )
    assert findings == [], findings


def test_g006_sees_nested_defs_in_marked_fn(tmp_path):
    findings = lint(
        tmp_path,
        {
            "mod.py": """
    import jax.numpy as jnp

    # gridlint: fastpath-engine
    def fast_branch(block):
        def inner(row):
            return jnp.sort(row)
        return inner(block)
    """,
        },
        rules=["G006"],
    )
    assert rules_of(findings) == ["G006"], findings


def test_g006_fires_on_subscript_iota_in_marked_fn(tmp_path):
    # the exchange wire builders' idiom (ISSUE 7): a dense permutation
    # spelled as advanced indexing — x[:, arange(n)] — must fire; the
    # plan-shaped subscript and the unmarked dense engine stay quiet
    findings = lint(
        tmp_path,
        {
            "mod.py": """
    import jax.numpy as jnp
    from jax import lax

    # gridlint: fastpath-engine
    def wire(pool, plan, n):
        dense = pool[:, jnp.arange(n)]
        narrow = pool[:, plan]
        return dense, narrow

    def dense_wire(pool, n):
        return pool[:, jnp.arange(n)]
    """,
        },
        rules=["G006"],
    )
    assert rules_of(findings) == ["G006"], findings
    assert len(findings) == 1
    assert "subscript" in findings[0].message
    assert findings[0].symbol == "wire"


def test_g006_exchange_wire_builders_are_marked_and_clean():
    # the real count-driven wire builders carry the marker (the contract
    # is opted into, not implied) and lint clean — the static half of
    # the wire-cost contract; the jaxpr walks in
    # tests/test_exchange_sparse.py hold the dynamic half
    from mpi_grid_redistribute_tpu.analysis.rules_fastpath import (
        _MARKER_RE,
    )

    path = os.path.join(PACKAGE, "parallel", "exchange.py")
    with open(path, encoding="utf-8") as fh:
        lines = fh.read().splitlines()
    marked = {
        lines[i + 1].split("(")[0].replace("def ", "").strip()
        for i, ln in enumerate(lines)
        if _MARKER_RE.search(ln) and i + 1 < len(lines)
    }
    assert {"_sparse_wire", "_neighbor_wire"} <= marked, marked
    findings = run_gridlint([path], root=REPO_ROOT, rules=["G006"])
    assert findings == [], findings


# ---------------------------------------------------------------- G007


def test_g007_fires_on_jax_import_and_sync_in_marked_module(tmp_path):
    findings = lint(
        tmp_path,
        {
            "mod.py": """
    # gridlint: scrape-path
    import jax
    from jax import numpy as jnp

    def scrape(x):
        return x.block_until_ready()
    """,
        },
        rules=["G007"],
    )
    assert rules_of(findings) == ["G007"], findings
    assert len(findings) == 3, findings  # two imports + one sync


def test_g007_quiet_without_marker_and_on_clean_marked_module(tmp_path):
    findings = lint(
        tmp_path,
        {
            # jax everywhere, but no scrape-path marker: out of scope
            "unmarked.py": """
    import jax

    def f(x):
        return jax.device_get(x)
    """,
            # marked, but host-only: json/math folds are the contract
            "marked.py": """
    # gridlint: scrape-path
    import json
    import math

    def fold(rows):
        return {"n": len(rows), "log": math.log2(max(1, len(rows)))}
    """,
        },
        rules=["G007"],
    )
    assert findings == [], findings


def test_g007_metrics_plane_is_marked_and_clean():
    # the real modules carry the marker (the contract is opted into, not
    # implied) and lint clean — the static half of the scrape-path
    # purity gate (tests/test_metrics.py holds the source-scan half)
    from mpi_grid_redistribute_tpu.analysis.rules_scrape import _MARKER_RE

    tel = os.path.join(PACKAGE, "telemetry")
    # the ISSUE 18 history plane (store.py, query.py) joins the original
    # metrics plane under the same opt-in purity contract
    for name in ("metrics.py", "aggregate.py", "store.py", "query.py"):
        with open(os.path.join(tel, name), encoding="utf-8") as fh:
            src = fh.read()
        assert _MARKER_RE.search(src), f"{name} lost its scrape-path marker"
    findings = run_gridlint([tel], root=REPO_ROOT, rules=["G007"])
    assert findings == [], findings


# ---------------------------------------------------------------- G008


def test_g008_fires_on_bare_except_and_swallowed_handler(tmp_path):
    findings = lint(
        tmp_path,
        {
            "svc.py": """
    # gridlint: service-path

    def step(run):
        try:
            run()
        except:
            pass

    def probe(run):
        try:
            run()
        except ValueError:
            ...
    """,
        },
        rules=["G008"],
    )
    assert rules_of(findings) == ["G008"], findings
    assert len(findings) == 2, findings  # one bare except + one swallow
    msgs = sorted(f.message for f in findings)
    assert "bare `except:`" in msgs[0], msgs
    assert "swallowed exception" in msgs[1], msgs


def test_g008_quiet_without_marker_and_on_real_handling(tmp_path):
    findings = lint(
        tmp_path,
        {
            # swallows everywhere, but unmarked: out of scope
            "unmarked.py": """
    def best_effort(run):
        try:
            run()
        except Exception:
            pass
    """,
            # marked, but every handler does real work: journals the
            # failure, converts it to a verdict, or narrows + re-raises
            "svc.py": """
    # gridlint: service-path

    def supervised(run, recorder):
        try:
            run()
        except Exception as e:
            recorder.record("restart", reason=str(e))

    def teardown(close):
        try:
            close()
        except OSError as e:
            return f"teardown failed: {e}"
        return None

    def narrow(run):
        try:
            run()
        except RuntimeError:
            if not harmless():
                raise

    def harmless():
        return True
    """,
        },
        rules=["G008"],
    )
    assert findings == [], findings


def test_g008_service_subsystem_is_marked_and_clean():
    # the real service modules carry the marker (the supervisor must see
    # every fault) and lint clean — the static half of the never-mask-a-
    # fault gate (tests/test_service.py's fault matrix is the dynamic
    # half)
    from mpi_grid_redistribute_tpu.analysis.rules_service import _MARKER_RE

    svc = os.path.join(PACKAGE, "service")
    marked = [
        os.path.join(svc, name)
        for name in ("driver.py", "supervisor.py", "faults.py", "elastic.py")
    ]
    # the rebalance actuation runs inside the driver's health boundary —
    # a swallowed fault there silently turns the closed loop off
    marked.append(os.path.join(PACKAGE, "telemetry", "rebalance.py"))
    for path in marked:
        with open(path, encoding="utf-8") as fh:
            src = fh.read()
        assert _MARKER_RE.search(src), (
            f"{os.path.basename(path)} lost its service-path marker"
        )
    findings = run_gridlint(
        [svc, os.path.join(PACKAGE, "telemetry", "rebalance.py")],
        root=REPO_ROOT, rules=["G008"],
    )
    assert findings == [], findings


# ---------------------------------------------------------------- G009


def test_g009_fires_on_host_syncs_in_marked_fn(tmp_path):
    findings = lint(
        tmp_path,
        {
            "mod.py": """
    import numpy as np

    # gridlint: resident-path
    def macro(pos, vel, count):
        host = np.asarray(count)
        pos.block_until_ready()
        total = float(count.sum())
        return host, total
    """,
        },
        rules=["G009"],
    )
    assert rules_of(findings) == ["G009"], findings
    assert len(findings) == 3
    assert any("np.asarray" in f.message for f in findings)
    assert any("block_until_ready" in f.message for f in findings)
    assert any("float()" in f.message for f in findings)


def test_g009_scans_nested_scan_body_and_spares_device_ops(tmp_path):
    # the scan body is a nested def — lexically inside the marked
    # function, so it IS scanned; jnp.asarray and float literals are
    # device-safe and must not fire
    findings = lint(
        tmp_path,
        {
            "mod.py": """
    import numpy as np
    import jax.numpy as jnp
    from jax import lax

    # gridlint: resident-path
    def macro(pos, count):
        def body(carry, _):
            p, c = carry
            p = p + jnp.asarray(1.0, p.dtype) * float(0.5)
            c = int(3) + np.asarray(c)
            return (p, c), c
        return lax.scan(body, (pos, count), None, length=4)
    """,
        },
        rules=["G009"],
    )
    assert rules_of(findings) == ["G009"], findings
    assert len(findings) == 1
    assert "np.asarray" in findings[0].message


def test_g009_unmarked_fn_and_boundary_code_are_free(tmp_path):
    # host syncs OUTSIDE marked functions are the chunk-boundary
    # contract working as designed — no findings
    findings = lint(
        tmp_path,
        {
            "mod.py": """
    import numpy as np

    def retire_chunk(ys):
        dropped = np.asarray(ys["dropped"])
        return float(dropped.sum())

    # gridlint: resident-path
    def macro(pos, count):
        return pos, count
    """,
        },
        rules=["G009"],
    )
    assert findings == [], findings


def test_g009_repo_gate_resident_engine_is_marked_and_clean():
    # the chunk engine must carry the resident-path marker (the static
    # half of the no-per-step-host-sync gate; tests/test_resident.py's
    # jaxpr walk is the dynamic half) and lint clean
    from mpi_grid_redistribute_tpu.analysis.rules_resident import (
        _MARKER_RE,
    )

    path = os.path.join(PACKAGE, "service", "resident.py")
    with open(path, encoding="utf-8") as fh:
        lines = fh.read().splitlines()
    marked = {
        lines[i + 1].split("(")[0].replace("def ", "").strip()
        for i, ln in enumerate(lines)
        if _MARKER_RE.search(ln) and i + 1 < len(lines)
    }
    assert "macro" in marked, marked
    findings = run_gridlint([path], root=REPO_ROOT, rules=["G009"])
    assert findings == [], findings


# ------------------------------------------------------------------ G010


def test_g010_fires_on_marked_fn_without_span(tmp_path):
    findings = lint(
        tmp_path,
        {
            "mod.py": """
    # gridlint: fastpath-engine
    def hot_no_span(x):
        return x + 1

    # gridlint: resident-path
    def macro_no_span(pos, count):
        return pos, count
    """,
        },
        rules=["G010"],
    )
    assert rules_of(findings) == ["G010"], findings
    assert len(findings) == 2
    assert {f.symbol for f in findings} == {"hot_no_span", "macro_no_span"}
    assert all("named_scope" in f.message for f in findings)


def test_g010_quiet_with_span_even_in_nested_body(tmp_path):
    # a span anywhere lexically inside the marked function counts —
    # including inside a scan-body nested def; unmarked functions are
    # never G010's business, and host-side span() does NOT satisfy it
    # (it times host code, the profiler never sees it)
    findings = lint(
        tmp_path,
        {
            "mod.py": """
    import jax
    from jax import lax
    from mpi_grid_redistribute_tpu.telemetry.phases import (
        span, traced_span,
    )

    # gridlint: fastpath-engine
    def hot_direct(x):
        with jax.named_scope("hot"):
            return x + 1

    # gridlint: resident-path
    def macro_nested(pos, count):
        def body(carry, _):
            with traced_span("svc:drift"):
                return carry, None
        return lax.scan(body, (pos, count), None, length=4)

    def unmarked_cold(x):
        return x - 1

    # gridlint: resident-path
    def macro_host_span_only(pos):
        with span("host-timer"):
            return pos
    """,
        },
        rules=["G010"],
    )
    assert rules_of(findings) == ["G010"], findings
    assert findings[0].symbol == "macro_host_span_only"


def test_g010_repo_gate_marked_hot_paths_all_carry_spans():
    # every fastpath-engine/resident-path-marked function in the
    # package names at least one profiler scope — the ProfilerSession
    # trace's layer attribution has no blind spots
    findings = run_gridlint([PACKAGE], root=REPO_ROOT, rules=["G010"])
    assert findings == [], findings


# ------------------------------------------------- suppressions, baseline


def test_inline_and_file_suppressions(tmp_path):
    files = {
        "mod.py": """
    import jax
    import jax.numpy as jnp

    @jax.jit
    def pick(x):
        return jnp.nonzero(x > 0)  # gridlint: disable=G003
    """,
        "legacy.py": """
    # gridlint: disable-file=G003
    import jax
    import jax.numpy as jnp

    @jax.jit
    def old(x):
        return jnp.nonzero(x < 0)
    """,
    }
    assert lint(tmp_path, files) == []
    # same fixtures without the pragmas do fire
    stripped = {
        k: v.replace("# gridlint: disable=G003", "").replace(
            "# gridlint: disable-file=G003", ""
        )
        for k, v in files.items()
    }
    assert rules_of(lint(tmp_path, stripped)) == ["G003"]


def test_baseline_roundtrip_and_staleness(tmp_path):
    findings = lint(
        tmp_path,
        {
            "mod.py": """
    import jax
    import jax.numpy as jnp

    @jax.jit
    def pick(x):
        return jnp.nonzero(x > 0)
    """,
        },
    )
    assert len(findings) == 1
    bl_path = str(tmp_path / "baseline.json")
    write_baseline(bl_path, findings, justification="fixture")
    baseline = load_baseline(bl_path)
    new, old = split_baselined(findings, baseline)
    assert new == [] and len(old) == 1
    # entries carry the justification
    payload = json.loads(open(bl_path).read())
    assert payload["findings"][0]["justification"] == "fixture"
    # a key nothing matches is stale
    stale_keys = baseline - {f.baseline_key() for f in old}
    assert stale_keys == set()


def test_cli_exit_codes_and_json(tmp_path, capsys):
    (tmp_path / "mod.py").write_text(
        textwrap.dedent(
            """
            import jax
            import jax.numpy as jnp

            @jax.jit
            def pick(x):
                return jnp.nonzero(x > 0)
            """
        )
    )
    rc = cli_main(
        [
            str(tmp_path / "mod.py"),
            "--root",
            str(tmp_path),
            "--no-baseline",
            "--format",
            "json",
        ]
    )
    out = json.loads(capsys.readouterr().out)
    assert rc == 1
    assert [f["rule"] for f in out["findings"]] == ["G003"]
    # --write-baseline then a clean --check round-trip
    bl = str(tmp_path / "bl.json")
    assert (
        cli_main(
            [
                str(tmp_path / "mod.py"),
                "--root",
                str(tmp_path),
                "--baseline",
                bl,
                "--write-baseline",
            ]
        )
        == 0
    )
    capsys.readouterr()
    assert (
        cli_main(
            [
                str(tmp_path / "mod.py"),
                "--root",
                str(tmp_path),
                "--baseline",
                bl,
                "--check",
            ]
        )
        == 0
    )
    capsys.readouterr()
    assert cli_main(["--list-rules"]) == 0
    listed = capsys.readouterr().out
    assert all(rid in listed for rid in RULE_IDS)


def _violating_tree(tmp_path):
    (tmp_path / "mod.py").write_text(
        textwrap.dedent(
            """
            import jax
            import jax.numpy as jnp

            @jax.jit
            def pick(x):
                return jnp.nonzero(x > 0)
            """
        )
    )
    return [
        str(tmp_path / "mod.py"), "--root", str(tmp_path), "--no-baseline"
    ]


def test_cli_sarif_format(tmp_path, capsys):
    rc = cli_main(_violating_tree(tmp_path) + ["--format", "sarif"])
    sarif = json.loads(capsys.readouterr().out)
    assert rc == 1
    assert sarif["version"] == "2.1.0"
    run = sarif["runs"][0]
    assert run["tool"]["driver"]["name"] == "gridlint"
    results = run["results"]
    assert [r["ruleId"] for r in results] == ["G003"]
    loc = results[0]["locations"][0]["physicalLocation"]
    assert loc["artifactLocation"]["uri"] == "mod.py"
    assert loc["region"]["startLine"] >= 1
    assert loc["region"]["startColumn"] >= 1  # SARIF columns are 1-based
    # the rule catalog rides along for code-scanning display
    assert any(
        r["id"] == "G003" for r in run["tool"]["driver"]["rules"]
    )


def test_cli_github_format(tmp_path, capsys):
    rc = cli_main(_violating_tree(tmp_path) + ["--format", "github"])
    out = capsys.readouterr().out.strip().splitlines()
    assert rc == 1
    assert len(out) == 1
    line = out[0]
    assert line.startswith("::warning file=mod.py,line=")
    assert "title=G003" in line and "::" in line[2:]
    # a clean tree emits no annotation lines and exits 0
    clean = tmp_path / "clean"
    clean.mkdir()
    (clean / "ok.py").write_text("x = 1\n")
    rc = cli_main(
        [str(clean / "ok.py"), "--root", str(clean), "--no-baseline",
         "--format", "github"]
    )
    assert rc == 0
    assert capsys.readouterr().out.strip() == ""


def test_cli_check_baseline_hygiene(tmp_path, capsys):
    """--check-baseline reports ONLY staleness: exit 1 + a named stale
    entry once the violation is fixed, exit 0 while the baseline still
    matches — and it must NOT gate new findings (that's --check's job)."""
    (tmp_path / "mod.py").write_text(
        textwrap.dedent(
            """
            import jax
            import jax.numpy as jnp

            @jax.jit
            def pick(x):
                return jnp.nonzero(x > 0)
            """
        )
    )
    bl = str(tmp_path / "bl.json")
    args = [str(tmp_path / "mod.py"), "--root", str(tmp_path),
            "--baseline", bl]
    assert cli_main(args + ["--write-baseline"]) == 0
    capsys.readouterr()
    # baseline still matches: hygiene passes
    assert cli_main(args + ["--check-baseline"]) == 0
    assert "0 stale" in capsys.readouterr().out
    # fix the violation; the suppression is now stale -> exit 1, and the
    # report names the entry so it can be deleted
    (tmp_path / "mod.py").write_text("x = 1\n")
    rc = cli_main(args + ["--check-baseline"])
    out = capsys.readouterr().out
    assert rc == 1
    assert "stale baseline entry" in out and "G003" in out
    assert "1 stale" in out
    # a NEW finding alone does not trip hygiene mode: fresh violating
    # file, empty-but-present baseline dir via --no-baseline is gated
    # elsewhere; here use a matching baseline plus an extra violation
    (tmp_path / "mod.py").write_text(
        textwrap.dedent(
            """
            import jax
            import jax.numpy as jnp

            @jax.jit
            def pick(x):
                return jnp.nonzero(x > 0)
            """
        )
    )
    (tmp_path / "mod2.py").write_text(
        textwrap.dedent(
            """
            import jax
            import jax.numpy as jnp

            @jax.jit
            def pick2(x):
                return jnp.unique(x)
            """
        )
    )
    rc = cli_main(
        [str(tmp_path / "mod.py"), str(tmp_path / "mod2.py"),
         "--root", str(tmp_path), "--baseline", bl, "--check-baseline"]
    )
    out = capsys.readouterr().out
    assert rc == 0, out  # mod2's new finding is not this mode's business
    assert "0 stale" in out


# ------------------------------------------------------- the repo gate


def test_package_is_gridlint_clean_against_baseline():
    """The tier-1 gate: zero non-baselined findings over the package."""
    findings = run_gridlint([PACKAGE], root=REPO_ROOT)
    baseline = load_baseline(default_baseline_path())
    new, _ = split_baselined(findings, baseline)
    assert new == [], "\n".join(f.render() for f in new)


def test_baseline_has_no_stale_entries():
    findings = run_gridlint([PACKAGE], root=REPO_ROOT)
    baseline = load_baseline(default_baseline_path())
    _, old = split_baselined(findings, baseline)
    stale = baseline - {f.baseline_key() for f in old}
    assert stale == set(), stale


def test_cli_script_entry_point():
    """scripts/gridlint.py is runnable and exits 0 on the package."""
    proc = subprocess.run(
        [sys.executable, os.path.join(REPO_ROOT, "scripts", "gridlint.py"),
         "mpi_grid_redistribute_tpu/", "--check"],
        cwd=REPO_ROOT,
        capture_output=True,
        text=True,
        timeout=120,
    )
    assert proc.returncode == 0, proc.stdout + proc.stderr
