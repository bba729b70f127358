"""shardcheck: the replication abstract interpreter (analysis/shardcheck.py).

Per-rule coverage mirroring test_progcheck: one minimal VIOLATING
fixture program and one CLEAN twin for each of S001-S004, the lattice
edge cases the interpreter must get right (while_loop carry fixpoint,
nested pjit-inside-cond, ppermute full-rotation vs identity vs partial
perms), the wire-attribution hand-math and its drift gate, the shared
suppression/measurement baseline machinery, and the repo-wide gate —
every registered program runs clean under S001-S004 against the
committed wire_attribution baseline.

Fixture programs are spiked single-purpose shard_map bodies on a flat
8-device ('x',) mesh or a (4, 2) ('x', 'y') mesh — small enough to
read, real enough that the traced jaxpr carries genuine collectives.
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

from mpi_grid_redistribute_tpu.analysis import rules_jaxpr, rules_shard
from mpi_grid_redistribute_tpu.analysis import shardcheck as sc
from mpi_grid_redistribute_tpu.analysis.baseline import (
    load_baseline,
    load_progprofile_baseline,
    load_wire_baseline,
    split_baselined,
    write_baseline,
    write_progprofile_baseline,
    write_wire_baseline,
)
from mpi_grid_redistribute_tpu.analysis.progcheck import ProgramSpec
from mpi_grid_redistribute_tpu.analysis.sarif import merge_sarif, to_sarif
from mpi_grid_redistribute_tpu.analysis.shardcheck import (
    S_RULE_IDS,
    ShardFinding,
    analyze,
    main as shardcheck_main,
    run_shardcheck,
)

# The spiked fixtures break replication on purpose, which jax's own
# vma check refuses to trace; these tests are about the analyzer's check.
shard_map = functools.partial(jax.shard_map, check_vma=False)

REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

AXES = ("x",)
AXES2 = ("x", "y")


def _mesh():
    return Mesh(np.array(jax.devices()[:8]), AXES)


def _mesh2(names=AXES2):
    return Mesh(np.array(jax.devices()[:8]).reshape(4, 2), names)


def _spec(name, fn=None, args=(), **kw):
    return ProgramSpec(name=name, build=lambda: (fn, args), **kw)


def _trace(fn, *args):
    return jax.make_jaxpr(fn)(*args)


def _x84():
    return jnp.zeros((8, 4), jnp.float32)


# ----------------------------------------------------- lattice basics


def test_replicated_in_spec_stays_replicated(_devices):
    """A P() in_spec is a broadcast: the body sees the same value on
    every rank, and emitting it back through P() is clean."""
    mesh = _mesh()

    def f(s):
        return shard_map(
            lambda v: v * 2.0, mesh=mesh, in_specs=P(), out_specs=P()
        )(s)

    report = analyze(_trace(f, jnp.float32(3.0)))
    assert report.escapes == []
    assert report.out_vary == [frozenset()]


def test_partitioned_input_varies_and_psum_clears(_devices):
    mesh = _mesh()

    def f(x):
        return shard_map(
            lambda v: lax.psum(jnp.sum(v), AXES),
            mesh=mesh, in_specs=P("x"), out_specs=P(),
        )(x)

    report = analyze(_trace(f, _x84()))
    assert report.escapes == []  # psum makes the P() out legitimate
    # and the full reduction of a varying operand is NOT redundant
    assert report.reductions == []


def test_axis_index_varies_on_its_axis(_devices):
    mesh = _mesh()

    def f(x):
        return shard_map(
            lambda v: v + lax.axis_index("x").astype(jnp.float32),
            mesh=mesh, in_specs=P(), out_specs=P("x"),
        )(x)

    report = analyze(_trace(f, jnp.zeros((8,), jnp.float32)))
    # varying over exactly 'x', and the P('x') out_spec absorbs it
    assert report.escapes == []


# -------------------------------------- S001: declared-replicated outs


def test_s001_fires_on_varying_replicated_out(_devices):
    mesh = _mesh()

    def f(x):
        return shard_map(
            lambda v: jnp.sum(v),  # shard-local sum, no reduction
            mesh=mesh, in_specs=P("x"), out_specs=P(),
        )(x)

    spec = _spec("spiked_s001", f, (_x84(),))
    report = analyze(sc.trace_program(spec))
    findings = rules_shard.check_s001(report, spec)
    assert [f.rule for f in findings] == ["S001"]
    assert "declared fully replicated" in findings[0].message
    assert "'x'" in findings[0].message


def test_s001_clean_with_reduction_before_boundary(_devices):
    mesh = _mesh()

    def f(x):
        return shard_map(
            lambda v: lax.pmin(jnp.min(v), AXES),
            mesh=mesh, in_specs=P("x"), out_specs=P(),
        )(x)

    spec = _spec("clean_s001", f, (_x84(),))
    assert rules_shard.check_s001(analyze(sc.trace_program(spec)), spec) == []


# ------------------------------------------ S002: redundant collectives


def test_s002_fires_on_redundant_psum(_devices):
    """The spiked fixture the ISSUE demands: a psum of a psum — the
    second reduction pays wire for a value every rank already holds."""
    mesh = _mesh()

    def f(x):
        def body(v):
            t = lax.psum(jnp.sum(v), AXES)
            return lax.psum(t, AXES)  # redundant: t is replicated

        return shard_map(body, mesh=mesh, in_specs=P("x"), out_specs=P())(x)

    spec = _spec("spiked_s002", f, (_x84(),))
    findings = rules_shard.check_s002(analyze(sc.trace_program(spec)), spec)
    assert [f.rule for f in findings] == ["S002"]
    assert "redundant psum" in findings[0].message
    assert "['x']" in findings[0].message


def test_s002_fires_on_pmin_of_replicated_guard(_devices):
    mesh = _mesh()

    def f(x):
        def body(v):
            ok = lax.pmin(jnp.min(v), AXES)
            return lax.pmin(ok, AXES)  # double-agreed guard

        return shard_map(body, mesh=mesh, in_specs=P("x"), out_specs=P())(x)

    spec = _spec("spiked_s002_pmin", f, (_x84(),))
    findings = rules_shard.check_s002(analyze(sc.trace_program(spec)), spec)
    assert [f.rule for f in findings] == ["S002"]
    assert "redundant pmin" in findings[0].message


def test_s002_clean_single_reduction_and_partial_axes(_devices):
    mesh = _mesh2()

    def f(x):
        def body(v):
            t = lax.psum(jnp.sum(v), ("x",))  # clears x, still varies y
            return lax.psum(t, ("y",))  # reduces the VARYING axis: fine

        return shard_map(
            body, mesh=mesh, in_specs=P("x", "y"), out_specs=P()
        )(x)

    spec = _spec("clean_s002", f, (_x84(),))
    assert rules_shard.check_s002(analyze(sc.trace_program(spec)), spec) == []


def test_s002_grouped_reduction_never_clears(_devices):
    mesh = _mesh()

    def f(x):
        def body(v):
            t = jnp.sum(v)  # shard-local: varies on x
            # grouped psum: replicated only WITHIN each group, so 'x'
            # must not clear — if it did, the full pmax that follows
            # would be flagged redundant by S002
            g = lax.psum(
                t, AXES, axis_index_groups=[[0, 1, 2, 3], [4, 5, 6, 7]]
            )
            return lax.pmax(g, AXES)

        return shard_map(body, mesh=mesh, in_specs=P("x"), out_specs=P())(x)

    spec = _spec("grouped_s002", f, (_x84(),))
    report = analyze(sc.trace_program(spec))
    assert rules_shard.check_s002(report, spec) == []
    assert rules_shard.check_s001(report, spec) == []  # pmax re-agrees


# ------------------------------------------- S003: varying-value escape


def test_s003_fires_on_partially_reduced_output(_devices):
    mesh = _mesh2()

    def f(x):
        return shard_map(
            lambda v: v * 1.0,
            mesh=mesh, in_specs=P("x", "y"), out_specs=P("x"),
        )(x)

    spec = _spec("spiked_s003", f, (_x84(),))
    findings = rules_shard.check_s003(analyze(sc.trace_program(spec)), spec)
    assert [f.rule for f in findings] == ["S003"]
    assert "program output" in findings[0].message
    assert "'y'" in findings[0].message  # varies on y, only x declared


def test_s003_fires_on_scan_ys_leaf(_devices):
    mesh = _mesh2()

    def f(x):
        sm = shard_map(
            lambda v: v * 1.0,
            mesh=mesh, in_specs=P("x", "y"), out_specs=P("x"),
        )

        def step(c, _):
            return c, sm(c)

        _c, ys = lax.scan(step, x, None, length=3)
        return ys

    spec = _spec("spiked_s003_ys", f, (_x84(),))
    report = analyze(sc.trace_program(spec))
    kinds = {e.kind for e in report.escapes}
    assert "scan_ys" in kinds  # the stacked ys leaf itself
    findings = rules_shard.check_s003(report, spec)
    assert findings and all(f.rule == "S003" for f in findings)
    assert any("scan ys leaf" in f.message for f in findings)


def test_s003_clean_when_out_specs_cover_all_axes(_devices):
    mesh = _mesh2()

    def f(x):
        return shard_map(
            lambda v: v * 1.0,
            mesh=mesh, in_specs=P("x", "y"), out_specs=P("x", "y"),
        )(x)

    spec = _spec("clean_s003", f, (_x84(),))
    assert rules_shard.check_s003(analyze(sc.trace_program(spec)), spec) == []


# ------------------------------------------------- lattice edge cases


def _while_cond_program(replicated_guard):
    """A pmin-agreed (or shard-local) guard carried through a
    while_loop into a mismatched-schedule cond: the carry fixpoint must
    preserve (or propagate) its vary-set."""
    mesh = _mesh()

    def body(v):
        if replicated_guard:
            g0 = lax.pmin((v[0, 0] > 0).astype(jnp.int32), AXES)
        else:
            g0 = (v[0, 0] > 0).astype(jnp.int32)

        def cond_f(carry):
            _g, _u, i = carry
            return i < 3

        def step(carry):
            g, u, i = carry
            u = lax.cond(
                g == 1,
                lambda w: lax.psum(w, AXES),
                lambda w: w * 2.0,
                u,
            )
            return (g, u, i + 1)

        _g, u, _i = lax.while_loop(cond_f, step, (g0, v, 0))
        return u

    def f(x):
        return shard_map(
            body, mesh=mesh, in_specs=P("x"), out_specs=P("x")
        )(x)

    return f, (_x84(),)


def test_while_loop_fixpoint_preserves_replicated_guard(_devices):
    fn, args = _while_cond_program(replicated_guard=True)
    spec = _spec("while_clean", fn, args)
    assert rules_jaxpr.check_j001(sc.trace_program(spec), spec) == []


def test_while_loop_fixpoint_propagates_varying_guard(_devices):
    fn, args = _while_cond_program(replicated_guard=False)
    spec = _spec("while_spiked", fn, args)
    findings = rules_jaxpr.check_j001(sc.trace_program(spec), spec)
    assert [f.rule for f in findings] == ["J001"]


def _pjit_in_cond_program(replicated_pred):
    """The dispatch collective hidden inside a jitted helper inside a
    cond branch: the signature walk and the lattice must both see
    through the nested pjit."""
    mesh = _mesh()

    def body(v):
        if replicated_pred:
            guard = lax.pmin((v[0, 0] > 0).astype(jnp.int32), AXES)
            pred = jax.jit(lambda t: t + 0)(guard) == 1  # pjit identity
        else:
            pred = v[0, 0] > 0
        return lax.cond(
            pred,
            lambda u: jax.jit(lambda w: lax.psum(w, AXES))(u),
            lambda u: u * 2.0,
            v,
        )

    def f(x):
        return shard_map(
            body, mesh=mesh, in_specs=P("x"), out_specs=P("x")
        )(x)

    return f, (_x84(),)


def test_nested_pjit_inside_cond_clean_with_agreed_pred(_devices):
    fn, args = _pjit_in_cond_program(replicated_pred=True)
    spec = _spec("pjit_clean", fn, args)
    assert rules_jaxpr.check_j001(sc.trace_program(spec), spec) == []


def test_nested_pjit_inside_cond_fires_with_local_pred(_devices):
    fn, args = _pjit_in_cond_program(replicated_pred=False)
    spec = _spec("pjit_spiked", fn, args)
    findings = rules_jaxpr.check_j001(sc.trace_program(spec), spec)
    assert [f.rule for f in findings] == ["J001"]
    assert "psum" in findings[0].message  # the signature saw through pjit


def _ppermute_pred_program(perm):
    """A pmin-agreed guard pushed through a ppermute, then used as a
    mismatched-cond predicate: a FULL permutation keeps it replicated
    (J001 clean), a partial one taints it (J001 fires)."""
    mesh = _mesh()

    def body(v):
        ok = lax.pmin((v[0, 0] > 0).astype(jnp.int32), AXES)
        okp = lax.ppermute(ok, "x", perm)
        return lax.cond(
            okp == 1,
            lambda u: lax.psum(u, AXES),
            lambda u: u * 2.0,
            v,
        )

    def f(x):
        return shard_map(
            body, mesh=mesh, in_specs=P("x"), out_specs=P("x")
        )(x)

    return f, (_x84(),)


def test_ppermute_full_rotation_preserves_replication(_devices):
    fn, args = _ppermute_pred_program([(i, (i + 1) % 8) for i in range(8)])
    spec = _spec("rotation", fn, args)
    assert rules_jaxpr.check_j001(sc.trace_program(spec), spec) == []


def test_ppermute_identity_perm_preserves_replication(_devices):
    fn, args = _ppermute_pred_program([(i, i) for i in range(8)])
    spec = _spec("identity", fn, args)
    assert rules_jaxpr.check_j001(sc.trace_program(spec), spec) == []


def test_ppermute_partial_perm_taints(_devices):
    # rank 7's slot receives nothing (zero-filled): rank-dependent
    fn, args = _ppermute_pred_program([(i, i + 1) for i in range(7)])
    spec = _spec("partial", fn, args)
    findings = rules_jaxpr.check_j001(sc.trace_program(spec), spec)
    assert [f.rule for f in findings] == ["J001"]


# --------------------------------- S004: per-axis wire attribution


def test_wire_profile_bills_the_crossed_axis(_devices):
    mesh = _mesh2()

    def f(x):
        return shard_map(
            lambda v: lax.psum(v, ("x",)),
            mesh=mesh, in_specs=P("x", "y"), out_specs=P(None, "y"),
        )(x)

    w = rules_shard.wire_profile(_trace(f, jnp.zeros((8, 8), jnp.float32)))
    # the f32[2, 4] shard is 32 bytes, billed to 'x' only
    assert w == {
        "per_axis": {"x": 32},
        "per_domain": {"dcn": 0, "ici": 32},
        "total_bytes": 32,
    }


def test_wire_profile_two_axis_collective_bills_both(_devices):
    mesh = _mesh2()

    def f(x):
        return shard_map(
            lambda v: lax.psum(v, AXES2),
            mesh=mesh, in_specs=P("x", "y"), out_specs=P(),
        )(x)

    w = rules_shard.wire_profile(_trace(f, jnp.zeros((8, 8), jnp.float32)))
    # per_axis is the axis-crossing view (full bytes on each axis);
    # per_domain bills the collective ONCE, so it sums to J004's total
    assert w["per_axis"] == {"x": 32, "y": 32}
    assert w["per_domain"] == {"dcn": 0, "ici": 32}
    assert w["total_bytes"] == 32


def test_wire_profile_dcn_axis_rolls_up_to_dcn(_devices):
    mesh = _mesh2(names=("dcn", "x"))

    def f(x):
        def body(v):
            a = lax.psum(v, ("dcn",))  # crosses the pod boundary
            return lax.psum(a, ("x",))  # stays on ICI

        return shard_map(
            body, mesh=mesh, in_specs=P("dcn", "x"), out_specs=P()
        )(x)

    w = rules_shard.wire_profile(_trace(f, jnp.zeros((8, 8), jnp.float32)))
    # mesh (4, 2): the f32[2, 4] shard is 32 bytes per collective
    assert w["per_axis"] == {"dcn": 32, "x": 32}
    assert w["per_domain"] == {"dcn": 32, "ici": 32}
    assert w["total_bytes"] == 64
    assert rules_shard.axis_domain("dcn") == rules_shard.DCN_DOMAIN
    assert rules_shard.axis_domain("z") == rules_shard.ICI_DOMAIN


def test_wire_profile_scan_multiplies_and_cond_bills_max(_devices):
    mesh = _mesh()

    def scanned(x):
        def body(v):
            def step(c, _):
                return lax.psum(c, AXES), None

            out, _ = lax.scan(step, v, None, length=5)
            return out

        return shard_map(
            body, mesh=mesh, in_specs=P("x"), out_specs=P("x")
        )(x)

    w = rules_shard.wire_profile(_trace(scanned, _x84()))
    assert w["per_axis"] == {"x": 5 * 16}  # f32[1, 4] shard x 5 trips

    def conded(x):
        def body(v):
            return lax.cond(
                v[0, 0] > 0,
                lambda u: lax.psum(jnp.concatenate([u, u], 1), AXES)[:, :4],
                lambda u: lax.psum(u, AXES),
                v,
            )

        return shard_map(
            body, mesh=mesh, in_specs=P("x"), out_specs=P("x")
        )(x)

    w = rules_shard.wire_profile(_trace(conded, _x84()))
    assert w["per_axis"] == {"x": 32}  # the wide f32[1, 8] branch only


def test_compare_wire_drift_missing_and_stale():
    base = {
        "p": {
            "per_axis": {"x": 32},
            "per_domain": {"dcn": 0, "ici": 32},
            "total_bytes": 32,
        }
    }
    pert = {
        "p": {
            "per_axis": {"x": 64},
            "per_domain": {"dcn": 0, "ici": 64},
            "total_bytes": 64,
        }
    }
    assert rules_shard.compare_wire(base, base) == []
    findings = rules_shard.compare_wire(pert, base)
    assert findings and all(f.rule == "S004" for f in findings)
    assert any("total wire bytes drifted" in f.message for f in findings)
    assert any("axis 'x' drifted" in f.message for f in findings)
    assert rules_shard.compare_wire(pert, pert) == []

    missing = rules_shard.compare_wire(base, None)
    assert [f.rule for f in missing] == ["S004"]
    assert "no committed wire-attribution baseline" in missing[0].message

    stale = rules_shard.compare_wire({}, base, check_stale=True)
    assert [f.rule for f in stale] == ["S004"]
    assert "stale wire-attribution baseline entry" in stale[0].message
    # a --programs subset run must not read missing names as stale
    assert rules_shard.compare_wire({}, base, check_stale=True, partial=True) == []


def test_s004_perturbed_width_fails_check_until_update(
    _devices, capsys, tmp_path
):
    """The acceptance gate: a perturbed collective width fails --check
    against the committed wire table until --update-baseline refreshes
    it — exercised through the real CLI on a real registry program."""
    bl = str(tmp_path / "prof.json")
    prog = "canonical_planar_sharded"
    assert shardcheck_main(
        ["--programs", prog, "--baseline", bl, "--update-baseline"]
    ) == 0
    capsys.readouterr()
    assert shardcheck_main(
        ["--programs", prog, "--baseline", bl, "--check"]
    ) == 0
    capsys.readouterr()

    with open(bl) as fh:
        doc = json.load(fh)
    entry = doc["wire_attribution"]["programs"][prog]
    entry["per_axis"]["x"] += 4  # a collective got 4 bytes wider
    entry["total_bytes"] += 4
    with open(bl, "w") as fh:
        json.dump(doc, fh)

    rc = shardcheck_main(["--programs", prog, "--baseline", bl, "--check"])
    out = capsys.readouterr().out
    assert rc == 1
    assert "S004" in out and "drifted" in out

    assert shardcheck_main(
        ["--programs", prog, "--baseline", bl, "--update-baseline"]
    ) == 0
    capsys.readouterr()
    assert shardcheck_main(
        ["--programs", prog, "--baseline", bl, "--check"]
    ) == 0


# ---------------------------------------------- baseline file plumbing


def test_profile_and_wire_sections_coexist(tmp_path):
    """progcheck's profiles section and shardcheck's wire_attribution
    section share one file: refreshing either must preserve the other."""
    path = str(tmp_path / "prof.json")
    profiles = {"a": {"collective_bytes_total": 3}}
    wires = {
        "a": {
            "per_axis": {"x": 8},
            "per_domain": {"dcn": 0, "ici": 8},
            "total_bytes": 8,
        }
    }
    assert load_wire_baseline(path) is None
    write_progprofile_baseline(path, profiles)
    assert load_wire_baseline(path) is None  # section not written yet
    write_wire_baseline(path, wires)
    assert load_progprofile_baseline(path) == profiles
    assert load_wire_baseline(path) == wires

    # refresh profiles: the wire section survives
    profiles2 = {"b": {"collective_bytes_total": 5}}
    write_progprofile_baseline(path, profiles2)
    assert load_progprofile_baseline(path) == profiles2
    assert load_wire_baseline(path) == wires

    # refresh wires: the profiles survive
    wires2 = {"b": wires["a"]}
    write_wire_baseline(path, wires2)
    assert load_progprofile_baseline(path) == profiles2
    assert load_wire_baseline(path) == wires2

    bad = tmp_path / "bad.json"
    bad.write_text('{"wire_attribution": "nope"}')
    with pytest.raises(SystemExit, match="malformed"):
        load_wire_baseline(str(bad))


def test_suppression_baseline_roundtrip(tmp_path):
    """ShardFindings ride the gridlint suppression machinery verbatim:
    the program name is the symbol, matching is message-exact."""
    path = str(tmp_path / "supp.json")
    known = ShardFinding("S002", "progA", "redundant but deliberate")
    write_baseline(path, [known], justification="journal entry")
    keys = load_baseline(path)
    assert known.baseline_key() in keys
    fresh = ShardFinding("S002", "progB", "a new one")
    new, old = split_baselined([known, fresh], keys)
    assert [f.program for f in new] == ["progB"]
    assert [f.program for f in old] == ["progA"]


def test_shard_finding_surface():
    f = ShardFinding("S001", "prog", "msg")
    assert f.render() == "<prog>: S001: msg"
    assert f.symbol == "prog"
    assert f.baseline_key() == ("S001", f.path, "prog", "msg")
    d = f.to_dict()
    assert d["rule"] == "S001" and d["program"] == "prog"


def test_merge_sarif_concatenates_runs():
    a = to_sarif([ShardFinding("S001", "p", "m")], "shardcheck", {})
    b = to_sarif([], "gridlint", {"G001": "doc"})
    merged = merge_sarif([a, b])
    assert merged["version"] == a["version"]
    assert [r["tool"]["driver"]["name"] for r in merged["runs"]] == [
        "shardcheck",
        "gridlint",
    ]


# ------------------------------------------------------ the repo gate


def test_rule_docs_cover_all_rules():
    assert set(rules_shard.RULE_DOCS) == set(S_RULE_IDS)


def test_repo_programs_shardcheck_clean(_devices, capsys):
    """The tier-1 gate, mirroring the gridlint/progcheck repo gates:
    every registered program runs clean under S001-S004 against the
    committed wire_attribution baseline and suppression file."""
    rc = shardcheck_main(["--check"])
    out = capsys.readouterr().out
    assert rc == 0, out


def test_repo_programs_have_shard_reports(_devices):
    """The interpreter annotates every program: the sharded canonical
    engines must show real inferred vary-sets (not a silent no-op)."""
    findings, wires = run_shardcheck(
        rules=["S001", "S002", "S003", "S004"],
    )
    assert findings == []
    assert set(wires) == set(sc.default_programs())
    w = wires["canonical_planar_sharded"]
    assert w["total_bytes"] > 0
    assert set(w["per_axis"]) == {"x", "y", "z"}
    assert w["per_domain"]["dcn"] == 0  # single-pod meshes today


def test_cli_exit_codes_lists_and_json(_devices, capsys, tmp_path):
    assert shardcheck_main(["--rules", "S999"]) == 2
    capsys.readouterr()
    assert shardcheck_main(["--programs", "nope"]) == 2
    capsys.readouterr()
    assert shardcheck_main(["--list-rules"]) == 0
    listed = capsys.readouterr().out
    assert all(r in listed for r in S_RULE_IDS)
    assert shardcheck_main(["--list-programs"]) == 0
    assert "resident_macro_step" in capsys.readouterr().out

    bl = str(tmp_path / "prof.json")
    prog = "canonical_planar_vranks"
    assert shardcheck_main(
        ["--programs", prog, "--baseline", bl, "--update-baseline"]
    ) == 0
    capsys.readouterr()
    rc = shardcheck_main(
        ["--programs", prog, "--baseline", bl, "--format", "json"]
    )
    out = json.loads(capsys.readouterr().out)
    assert rc == 0
    assert out["findings"] == []
    assert prog in out["wire_attribution"]


def test_cli_sarif_format_and_stale_suppression(_devices, capsys, tmp_path):
    bl = str(tmp_path / "prof.json")
    supp = str(tmp_path / "supp.json")
    prog = "canonical_planar_vranks"
    assert shardcheck_main(
        ["--programs", prog, "--baseline", bl, "--update-baseline"]
    ) == 0
    capsys.readouterr()

    # an unbaselined program renders through the shared SARIF formatter
    empty = str(tmp_path / "empty.json")
    with open(empty, "w") as fh:
        json.dump({"wire_attribution": {"programs": {}}}, fh)
    rc = shardcheck_main(
        ["--programs", prog, "--baseline", empty, "--format", "sarif"]
    )
    doc = json.loads(capsys.readouterr().out)
    assert rc == 1
    results = doc["runs"][0]["results"]
    assert results and results[0]["ruleId"] == "S004"
    assert doc["runs"][0]["tool"]["driver"]["name"] == "shardcheck"

    # a suppression entry matching nothing is stale under --check
    write_baseline(supp, [ShardFinding("S002", "ghost", "long gone")])
    rc = shardcheck_main(
        [
            "--programs", prog,
            "--baseline", bl,
            "--suppressions", supp,
            "--check",
        ]
    )
    out = capsys.readouterr().out
    assert rc == 1
    assert "stale suppression entry" in out


def test_cli_script_entry_point():
    """scripts/shardcheck.py runs standalone (it forces the 8-device
    virtual mesh itself) and exits 0 on the committed baseline."""
    env = dict(os.environ)
    env.pop("XLA_FLAGS", None)  # the wrapper must set the mesh itself
    proc = subprocess.run(
        [
            sys.executable,
            os.path.join(REPO_ROOT, "scripts", "shardcheck.py"),
            "--check",
        ],
        cwd=REPO_ROOT,
        capture_output=True,
        text=True,
        timeout=300,
        env=env,
    )
    assert proc.returncode == 0, proc.stdout + proc.stderr


def _check_all_registry():
    """Load scripts/check_all.py's ANALYZERS registry — the single
    source of truth for the family list, so this test stops needing an
    N -> N+1 edit every time a family lands."""
    import importlib.util

    spec = importlib.util.spec_from_file_location(
        "_check_all", os.path.join(REPO_ROOT, "scripts", "check_all.py")
    )
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.ANALYZERS


def test_check_all_umbrella_merges_every_registered_tool(tmp_path):
    """scripts/check_all.py: every analyzer in its ANALYZERS registry,
    clean at HEAD, one SARIF run per family merged into the requested
    file — and every registered baseline actually committed."""
    analyzers = _check_all_registry()
    expected = [a.name for a in analyzers]
    assert len(expected) >= 6 and "kernelcheck" in expected
    for a in analyzers:
        assert os.path.exists(os.path.join(REPO_ROOT, a.baseline)), (
            f"{a.name}: registered baseline {a.baseline} is not committed"
        )
    out_path = str(tmp_path / "merged.sarif")
    proc = subprocess.run(
        [
            sys.executable,
            os.path.join(REPO_ROOT, "scripts", "check_all.py"),
            "--sarif-out", out_path,
        ],
        cwd=REPO_ROOT,
        capture_output=True,
        text=True,
        timeout=600,
    )
    assert proc.returncode == 0, proc.stdout + proc.stderr
    with open(out_path) as fh:
        merged = json.load(fh)
    names = [r["tool"]["driver"]["name"] for r in merged["runs"]]
    assert names == expected
    assert all(r["results"] == [] for r in merged["runs"])
    # per-analyzer wall-time must stay visible (lint-growth telemetry)
    for name in expected:
        assert any(
            line.startswith(f"check: {name} clean") and line.endswith("s)")
            for line in proc.stdout.splitlines()
        ), proc.stdout
