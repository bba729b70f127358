"""progcheck: the semantic jaxpr analyzer (analysis/progcheck.py).

Per-rule coverage: one minimal VIOLATING fixture program and one CLEAN
twin for each of J001-J004, the registry completeness check (J000), the
public walk API the other jaxpr tests import, and the repo-wide gate —
every registered program traces clean under J001-J004 against the
committed profile baseline, mirroring test_gridlint's package gate.

Fixture programs are spiked single-purpose shard_map bodies on a flat
8-device ('x',) mesh: small enough to read, real enough that the traced
jaxpr carries genuine collective primitives.
"""

import functools
import json
import os
import subprocess
import sys

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import Mesh, PartitionSpec as P

from mpi_grid_redistribute_tpu.analysis import rules_jaxpr
from mpi_grid_redistribute_tpu.analysis.baseline import (
    load_progprofile_baseline,
    progprofile_baseline_path,
    write_progprofile_baseline,
)
from mpi_grid_redistribute_tpu.analysis.progcheck import (
    PROGRAMS,
    ProgFinding,
    ProgramSpec,
    aval_bytes,
    default_programs,
    dispatch_conds,
    has_primitive,
    main as progcheck_main,
    primitive_names,
    primitive_set,
    registry_coverage,
    trace_program,
    walk_eqns,
)

# The spiked fixtures break replication on purpose, which jax's own
# vma check refuses to trace; these tests are about the analyzer's check.
shard_map = functools.partial(jax.shard_map, check_vma=False)

REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

AXES = ("x",)


def _mesh():
    return Mesh(np.array(jax.devices()[:8]), AXES)


def _spec(name, fn, args, **kw):
    return ProgramSpec(name=name, build=lambda: (fn, args), **kw)


def _trace(fn, *args):
    return jax.make_jaxpr(fn)(*args)


# --------------------------------------------------------- walk API


def test_walk_eqns_recurses_into_scan_and_cond(_devices):
    def f(x):
        def body(c, _):
            c = lax.cond(c[0] > 0, lambda v: v * 2, lambda v: v + 1, c)
            return c, c.sum()

        return lax.scan(body, x, None, length=3)

    closed = _trace(f, jnp.ones((4,), jnp.float32))
    names = primitive_names(closed)
    assert isinstance(names, list)
    assert "scan" in names and "cond" in names
    assert primitive_set(closed) == set(names)
    # the walk accepts closed and open jaxprs alike
    assert primitive_set(closed.jaxpr) == set(names)
    assert sum(1 for _ in walk_eqns(closed)) == len(names)


def test_dispatch_conds_finds_disagreeing_branches(_devices):
    def f(x):
        return lax.cond(
            x[0] > 0,
            lambda v: jnp.sort(v),
            lambda v: v + 1.0,
            x,
        )

    conds = dispatch_conds(
        _trace(f, jnp.ones((8,), jnp.float32)),
        lambda b: has_primitive(b, "sort"),
    )
    assert len(conds) == 1
    _eqn, fast, flagged = conds[0]
    assert not has_primitive(fast, "sort")
    assert has_primitive(flagged, "sort")

    def g(x):  # both branches sort: NOT a dispatch site
        return lax.cond(
            x[0] > 0, lambda v: jnp.sort(v), lambda v: -jnp.sort(v), x
        )

    assert dispatch_conds(
        _trace(g, jnp.ones((8,), jnp.float32)),
        lambda b: has_primitive(b, "sort"),
    ) == []


def test_aval_bytes(_devices):
    closed = _trace(lambda x: x + 1, jnp.zeros((4, 8), jnp.float32))
    assert aval_bytes(closed.jaxpr.invars[0].aval) == 4 * 8 * 4


# ------------------------------------------------ J001: cond schedules


def _mismatched_cond_program(replicated_pred):
    """cond whose branches issue DIFFERENT collective schedules: one
    psum, the other nothing. With a shard-local predicate that is the
    J001 deadlock; guarded by a pmin-agreed scalar it is exactly the
    repo's one-scalar-cond fallback discipline."""
    mesh = _mesh()

    def body(v):
        if replicated_pred:
            ok = lax.pmin((v[0, 0] > 0).astype(jnp.int32), AXES)
            pred = ok == 1
        else:
            pred = v[0, 0] > 0  # each device decides alone
        return lax.cond(
            pred,
            lambda u: lax.psum(u, AXES),
            lambda u: u * 2.0,
            v,
        )

    def f(x):
        return shard_map(
            body, mesh=mesh, in_specs=P("x"), out_specs=P("x")
        )(x)

    return f, (jnp.zeros((8, 4), jnp.float32),)


def test_j001_fires_on_mismatched_schedules_local_pred(_devices):
    fn, args = _mismatched_cond_program(replicated_pred=False)
    spec = _spec("spiked_j001", fn, args)
    findings = rules_jaxpr.check_j001(trace_program(spec), spec)
    assert [f.rule for f in findings] == ["J001"]
    assert "mismatched collective schedules" in findings[0].message
    assert "psum" in findings[0].message


def test_j001_clean_with_pmin_agreed_pred(_devices):
    fn, args = _mismatched_cond_program(replicated_pred=True)
    spec = _spec("clean_j001", fn, args)
    assert rules_jaxpr.check_j001(trace_program(spec), spec) == []


def test_j001_clean_when_schedules_match(_devices):
    mesh = _mesh()

    def body(v):
        return lax.cond(  # same collective signature in both branches
            v[0, 0] > 0,
            lambda u: lax.psum(u, AXES),
            lambda u: lax.psum(u * 2.0, AXES),
            v,
        )

    def f(x):
        return shard_map(
            body, mesh=mesh, in_specs=P("x"), out_specs=P("x")
        )(x)

    spec = _spec("matched_j001", f, (jnp.zeros((8, 4), jnp.float32),))
    assert rules_jaxpr.check_j001(trace_program(spec), spec) == []


def test_j001_sees_through_scan_carry(_devices):
    """The replication pass must propagate through a scan carry: a
    pmin-agreed guard computed once and carried into a scanned cond is
    still replicated."""
    mesh = _mesh()

    def body(v):
        ok = lax.pmin((v[0, 0] > 0).astype(jnp.int32), AXES)

        def step(carry, _):
            g, u = carry
            u = lax.cond(
                g == 1,
                lambda w: lax.psum(w, AXES),
                lambda w: w * 2.0,
                u,
            )
            return (g, u), None

        (_, out), _ = lax.scan(step, (ok, v), None, length=2)
        return out

    def f(x):
        return shard_map(
            body, mesh=mesh, in_specs=P("x"), out_specs=P("x")
        )(x)

    spec = _spec("scanned_j001", f, (jnp.zeros((8, 4), jnp.float32),))
    assert rules_jaxpr.check_j001(trace_program(spec), spec) == []


# --------------------------------------------------- J002: residency


def _resident_program(spiked):
    mesh = _mesh()

    def body(v):
        if spiked:
            jax.debug.print("peek {}", v[0, 0])  # host callback
        return lax.psum(v, AXES)

    def f(x):
        return shard_map(
            body, mesh=mesh, in_specs=P("x"), out_specs=P("x")
        )(x)

    return f, (jnp.zeros((8, 4), jnp.float32),)


def test_j002_fires_on_debug_print_in_resident_program(_devices):
    fn, args = _resident_program(spiked=True)
    spec = _spec("spiked_j002", fn, args, resident=True)
    findings = rules_jaxpr.check_j002(trace_program(spec), spec)
    assert [f.rule for f in findings] == ["J002"]
    assert "debug_print" in findings[0].message


def test_j002_clean_without_host_syncs(_devices):
    fn, args = _resident_program(spiked=False)
    spec = _spec("clean_j002", fn, args, resident=True)
    assert rules_jaxpr.check_j002(trace_program(spec), spec) == []


def test_j002_ignores_non_resident_programs(_devices):
    fn, args = _resident_program(spiked=True)
    spec = _spec("nonresident", fn, args, resident=False)
    assert rules_jaxpr.check_j002(trace_program(spec), spec) == []


# ------------------------------------------- J003: fast-path contract


def _pred(v):
    return lax.pmin((v[0, 0] > 0).astype(jnp.int32), AXES) == 1


def _migrate_program(fast_sorts=False, fat_gather=False):
    """Sort-dispatch cond in migrate shape: dense branch sorts, fast
    branch must not. Spiking a sort into the fast branch erases the
    branch disagreement — exactly how a real regression would look."""
    mesh = _mesh()

    def body(v):
        def fast(u):
            if fast_sorts:
                u = jnp.sort(u, axis=0)
            if fat_gather:
                # resident-scale permutation: gathers every row
                u = u[jnp.argsort(u[:, 0]).astype(jnp.int32)[::-1]]
            else:
                u = u.at[:2].set(jnp.take(u, jnp.arange(2), axis=0) + 1)
            return u

        def dense(u):
            return jnp.sort(u, axis=0)

        return lax.cond(_pred(v), fast, dense, v)

    def f(x):
        return shard_map(
            body, mesh=mesh, in_specs=P("x"), out_specs=P("x")
        )(x)

    return f, (jnp.zeros((64, 4), jnp.float32),)


def test_j003_migrate_clean(_devices):
    fn, args = _migrate_program()
    spec = _spec(
        "clean_migrate", fn, args, fastpath="migrate", resident_rows=8
    )
    assert rules_jaxpr.check_j003(trace_program(spec), spec) == []


def test_j003_fires_on_spiked_sort_in_fast_branch(_devices):
    fn, args = _migrate_program(fast_sorts=True)
    spec = _spec(
        "spiked_sort", fn, args, fastpath="migrate", resident_rows=8
    )
    findings = rules_jaxpr.check_j003(trace_program(spec), spec)
    assert [f.rule for f in findings] == ["J003"]
    assert "fast path lost" in findings[0].message


def test_j003_fires_on_resident_scale_gather(_devices):
    fn, args = _migrate_program(fat_gather=True)
    spec = _spec(
        "spiked_gather", fn, args, fastpath="migrate", resident_rows=8
    )
    findings = rules_jaxpr.check_j003(trace_program(spec), spec)
    assert findings and all(f.rule == "J003" for f in findings)
    assert any("resident" in f.message for f in findings)


def _wire_program(narrow_cols, wide_cols):
    """Width-dispatch cond in sparse shape: both branches all_to_all,
    at different pool widths."""
    mesh = _mesh()

    def body(v):
        def use(cols):
            def branch(u):
                # per-shard pool [8 destinations, cols]; all_to_all
                # splits the destination axis across the 8 shards
                t = lax.all_to_all(
                    u[:, : 8 * cols].reshape(8, cols), "x", 0, 0
                )
                return jnp.zeros_like(u).at[:, : 8 * cols].set(
                    t.reshape(1, 8 * cols)
                )

            return branch

        return lax.cond(_pred(v), use(narrow_cols), use(wide_cols), v)

    def f(x):
        return shard_map(
            body, mesh=mesh, in_specs=P("x"), out_specs=P("x")
        )(x)

    return f, (jnp.zeros((8, 256), jnp.float32),)


def test_j003_sparse_wire_clean(_devices):
    # narrow * cap == wide * B with cap=16, B=4 -> wide = 4 * narrow
    fn, args = _wire_program(narrow_cols=4, wide_cols=16)
    spec = _spec(
        "clean_wire", fn, args, fastpath="sparse_wire",
        capacity=16, mover_cap=4,
    )
    assert rules_jaxpr.check_j003(trace_program(spec), spec) == []


def test_j003_fires_on_broken_pool_width_ratio(_devices):
    fn, args = _wire_program(narrow_cols=8, wide_cols=16)
    spec = _spec(
        "spiked_wire", fn, args, fastpath="sparse_wire",
        capacity=16, mover_cap=4,
    )
    findings = rules_jaxpr.check_j003(trace_program(spec), spec)
    assert [f.rule for f in findings] == ["J003"]
    assert "B/cap contract" in findings[0].message


def _neighbor_program(fast_permutes):
    mesh = _mesh()
    perm = [(i, (i + 1) % 8) for i in range(8)]

    def body(v):
        def fast(u):
            if fast_permutes:
                return lax.ppermute(u, "x", perm)
            return u * 2.0

        def dense(u):
            return lax.all_to_all(
                u.reshape(8, -1), "x", 0, 0
            ).reshape(u.shape)

        return lax.cond(_pred(v), fast, dense, v)

    def f(x):
        return shard_map(
            body, mesh=mesh, in_specs=P("x"), out_specs=P("x")
        )(x)

    return f, (jnp.zeros((8, 64), jnp.float32),)


def test_j003_neighbor_clean(_devices):
    fn, args = _neighbor_program(fast_permutes=True)
    spec = _spec("clean_neighbor", fn, args, fastpath="neighbor_wire")
    assert rules_jaxpr.check_j003(trace_program(spec), spec) == []


def test_j003_fires_when_fast_branch_loses_ppermute(_devices):
    fn, args = _neighbor_program(fast_permutes=False)
    spec = _spec("spiked_neighbor", fn, args, fastpath="neighbor_wire")
    findings = rules_jaxpr.check_j003(trace_program(spec), spec)
    assert [f.rule for f in findings] == ["J003"]
    assert "ppermute" in findings[0].message


def test_j003_unknown_fastpath_kind_is_loud(_devices):
    fn, args = _neighbor_program(fast_permutes=True)
    spec = _spec("bad_kind", fn, args, fastpath="nope")
    with pytest.raises(ValueError, match="unknown fastpath"):
        rules_jaxpr.check_j003(trace_program(spec), spec)


# --------------------------------- J004: static wire/footprint drift


def _psum_program(width):
    mesh = _mesh()

    def f(x):
        return shard_map(
            lambda v: lax.psum(v, AXES),
            mesh=mesh, in_specs=P("x"), out_specs=P("x"),
        )(x)

    return f, (jnp.zeros((8, width), jnp.float32),)


def test_profile_counts_collective_bytes_and_scan_trips(_devices):
    fn, args = _psum_program(16)
    prof = rules_jaxpr.program_profile(trace_program(_spec("p", fn, args)))
    # one psum over the full f32[8(/8 shards), 16] operand per shard
    assert prof["collective_bytes"] == {"psum": 1 * 16 * 4}
    assert prof["collective_count"] == 1
    assert prof["collective_bytes_total"] == 64
    assert prof["peak_live_bytes"] >= 8 * 16 * 4

    mesh = _mesh()

    def scanned_f(x):
        def body(v):
            def step(c, _):
                return lax.psum(c, AXES), None

            out, _ = lax.scan(step, v, None, length=5)
            return out

        return shard_map(
            body, mesh=mesh, in_specs=P("x"), out_specs=P("x")
        )(x)

    prof5 = rules_jaxpr.program_profile(
        trace_program(_spec("p5", scanned_f, args))
    )
    # scan trip count multiplies the wire: 5 trips x 64 bytes
    assert prof5["collective_bytes_total"] == 5 * 64
    assert prof5["collective_count"] == 5


def test_profile_bills_cond_at_max_bytes_branch(_devices):
    fn, args = _wire_program(narrow_cols=4, wide_cols=16)
    prof = rules_jaxpr.program_profile(trace_program(_spec("c", fn, args)))
    # the cond bills its max-bytes branch: the wide f32[8, 16] pool
    # (512 B), never the narrow f32[8, 4] one (128 B)
    assert prof["collective_bytes"] == {"all_to_all": 8 * 16 * 4, "pmin": 4}


def test_j004_width_perturbation_fails_drift_gate(_devices):
    fn16, a16 = _psum_program(16)
    fn32, a32 = _psum_program(32)
    base = rules_jaxpr.program_profile(trace_program(_spec("w", fn16, a16)))
    wide = rules_jaxpr.program_profile(trace_program(_spec("w", fn32, a32)))

    assert rules_jaxpr.compare_profiles({"w": base}, {"w": base}) == []
    findings = rules_jaxpr.compare_profiles({"w": wide}, {"w": base})
    assert findings and all(f.rule == "J004" for f in findings)
    assert any("collective_bytes_total drifted" in f.message for f in findings)
    assert any("psum" in f.message for f in findings)
    # --update-baseline is the escape hatch: regate against the new
    # profile and the drift is gone
    assert rules_jaxpr.compare_profiles({"w": wide}, {"w": wide}) == []


def test_j004_missing_and_stale_baseline_entries(_devices):
    fn, args = _psum_program(16)
    prof = rules_jaxpr.program_profile(trace_program(_spec("m", fn, args)))
    missing = rules_jaxpr.compare_profiles({"m": prof}, {})
    assert [f.rule for f in missing] == ["J004"]
    assert "no committed profile baseline" in missing[0].message

    stale = rules_jaxpr.compare_profiles(
        {}, {"gone": prof}, check_stale=True
    )
    assert [f.rule for f in stale] == ["J004"]
    assert "stale baseline entry" in stale[0].message
    # a --programs subset run must not read missing names as stale
    assert rules_jaxpr.compare_profiles(
        {}, {"gone": prof}, check_stale=True, partial=True
    ) == []


def test_progprofile_baseline_roundtrip(tmp_path):
    path = str(tmp_path / "prof.json")
    assert load_progprofile_baseline(path) is None
    profiles = {"a": {"collective_bytes_total": 3}}
    write_progprofile_baseline(path, profiles)
    assert load_progprofile_baseline(path) == profiles
    write_progprofile_baseline(path, {"a": {"collective_bytes_total": 4}})
    assert load_progprofile_baseline(path) == {
        "a": {"collective_bytes_total": 4}
    }
    (tmp_path / "bad.json").write_text('{"not": "profiles"}')
    with pytest.raises(SystemExit, match="malformed"):
        load_progprofile_baseline(str(tmp_path / "bad.json"))


# ------------------------------------------ J000: registry coverage


def test_registry_is_complete(_devices):
    assert registry_coverage(default_programs()) == []


def test_registry_coverage_catches_missing_engine(_devices):
    programs = {
        n: s
        for n, s in default_programs().items()
        if s.engine != "sparse"
    }
    findings = registry_coverage(programs)
    assert findings and all(f.rule == "J000" for f in findings)
    assert any("'sparse'" in f.message for f in findings)


def test_registry_coverage_catches_missing_resident_tag(_devices):
    programs = {
        n: s
        for n, s in default_programs().items()
        if "resident" not in s.tags
    }
    findings = registry_coverage(programs)
    assert any(
        f.rule == "J000" and "'resident'" in f.message for f in findings
    )


def test_register_program_rejects_duplicates(_devices):
    default_programs()
    name = next(iter(PROGRAMS))
    from mpi_grid_redistribute_tpu.analysis.progcheck import (
        register_program,
    )

    with pytest.raises(ValueError, match="already registered"):
        register_program(PROGRAMS[name])


def test_resident_program_carries_marker(_devices):
    spec = default_programs()["resident_macro_step"]
    assert spec.resident
    fn, _args = spec.build()  # asserts the _progcheck_resident marker
    assert getattr(fn.__wrapped__, "_progcheck_resident", False)


# ------------------------------------------------------ the repo gate


def test_repo_programs_trace_clean_and_match_baseline(_devices, capsys):
    """The tier-1 gate, mirroring test_gridlint's package gate: every
    registered program traces clean under J000-J004 against the
    committed profile baseline."""
    rc = progcheck_main(["--check"])
    out = capsys.readouterr().out
    assert rc == 0, out


def test_cli_exit_codes_and_json(_devices, capsys, tmp_path):
    assert progcheck_main(["--rules", "J999"]) == 2
    capsys.readouterr()
    assert progcheck_main(["--programs", "nope"]) == 2
    capsys.readouterr()
    assert progcheck_main(["--list-rules"]) == 0
    listed = capsys.readouterr().out
    assert all(r in listed for r in ("J000", "J001", "J004"))
    assert progcheck_main(["--list-programs"]) == 0
    assert "resident_macro_step" in capsys.readouterr().out

    bl = str(tmp_path / "prof.json")
    rc = progcheck_main(
        [
            "--programs", "canonical_planar_sharded",
            "--baseline", bl,
            "--update-baseline",
        ]
    )
    capsys.readouterr()
    assert rc == 0
    rc = progcheck_main(
        [
            "--programs", "canonical_planar_sharded",
            "--baseline", bl,
            "--format", "json",
        ]
    )
    out = json.loads(capsys.readouterr().out)
    assert rc == 0
    assert out["findings"] == []
    assert "canonical_planar_sharded" in out["profiles"]


def test_cli_sarif_and_github_formats(_devices, capsys, tmp_path):
    # an empty baseline file means every program is a J004 finding —
    # a cheap way to exercise the failure formats on one program
    bl = str(tmp_path / "empty.json")
    with open(bl, "w") as fh:
        json.dump({"profiles": {}}, fh)
    rc = progcheck_main(
        [
            "--programs", "canonical_planar_sharded",
            "--baseline", bl,
            "--format", "sarif",
        ]
    )
    sarif = json.loads(capsys.readouterr().out)
    assert rc == 1
    results = sarif["runs"][0]["results"]
    assert results and results[0]["ruleId"] == "J004"
    assert "canonical_planar_sharded" in results[0]["message"]["text"]
    rule_ids = {r["id"] for r in sarif["runs"][0]["tool"]["driver"]["rules"]}
    assert {"J000", "J004"} <= rule_ids

    rc = progcheck_main(
        [
            "--programs", "canonical_planar_sharded",
            "--baseline", bl,
            "--format", "github",
        ]
    )
    lines = capsys.readouterr().out.strip().splitlines()
    assert rc == 1
    assert lines and all(l.startswith("::warning ") for l in lines)
    assert any("J004" in l for l in lines)


def test_cli_script_entry_point():
    """scripts/progcheck.py runs standalone (it forces the 8-device
    virtual mesh itself) and exits 0 on the committed baseline."""
    env = dict(os.environ)
    env.pop("XLA_FLAGS", None)  # the wrapper must set the mesh itself
    proc = subprocess.run(
        [
            sys.executable,
            os.path.join(REPO_ROOT, "scripts", "progcheck.py"),
            "--check",
        ],
        cwd=REPO_ROOT,
        capture_output=True,
        text=True,
        timeout=300,
        env=env,
    )
    assert proc.returncode == 0, proc.stdout + proc.stderr


def test_finding_render_and_dict():
    f = ProgFinding("J001", "prog", "msg")
    assert f.render() == "<prog>: J001: msg"
    d = f.to_dict()
    assert d["rule"] == "J001" and d["program"] == "prog"


def test_check_baseline_clean_on_committed_file(capsys):
    """--check-baseline hygiene mode: every name in the committed
    profiles AND wire_attribution sections is a registered program.
    Pure name check — nothing is traced, so no _devices needed."""
    rc = progcheck_main(["--check-baseline"])
    out = capsys.readouterr().out
    assert rc == 0
    assert "0 stale baseline entr" in out


def test_check_baseline_flags_unregistered_programs(capsys, tmp_path):
    from mpi_grid_redistribute_tpu.analysis.baseline import (
        write_wire_baseline,
    )

    path = str(tmp_path / "prof.json")
    write_progprofile_baseline(
        path,
        {
            "canonical_planar_sharded": {"collective_bytes_total": 1},
            "ghost_profiled": {"collective_bytes_total": 2},
        },
    )
    write_wire_baseline(
        path,
        {
            "ghost_profiled": {"per_axis": {}, "total_bytes": 0},
            "ghost_wired": {"per_axis": {}, "total_bytes": 0},
        },
    )
    rc = progcheck_main(["--check-baseline", "--baseline", path])
    out = capsys.readouterr().out
    assert rc == 1
    assert "2 stale baseline entr" in out
    # each stale name reports WHICH sections still carry it
    assert "ghost_profiled [profiles, wire_attribution]" in out
    assert "ghost_wired [wire_attribution]" in out
    # the registered program is NOT flagged
    assert "canonical_planar_sharded" not in out
